"""The TPU tiling rule every 1-D block kernel in this package obeys.

XLA lays a 1-D 32-bit array out on the TPU in tiles of 8 sublanes x 128
lanes (layout ``{0:T(1024)}``), and Mosaic refuses a kernel whose block
does not cover whole tiles ("XLA layout ... does not match Mosaic
layout").  Interpret mode has no such rule, which is why the CPU tests
may use small blocks.
"""

from __future__ import annotations

TPU_TILE = 1024  # elements of one (8, 128) tile of a 1-D 32-bit array


def check_block(block: int) -> None:
    """Raise ``ValueError`` unless ``block`` tiles a 1-D TPU array."""
    if block <= 0 or block % TPU_TILE:
        raise ValueError(
            f"block={block} does not tile a 1-D TPU array: it must be a "
            f"positive multiple of {TPU_TILE} elements (compile the plan "
            f"with such a pad_to)")
