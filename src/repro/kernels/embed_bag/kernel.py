"""Pallas TPU embedding-bag kernel: scalar-prefetched row streaming.

The recsys lookup hot path: out[b] = sum_l table[idx[b, l]]. The bag
indices are scalar-prefetched (available before the grid runs), so each
grid step's BlockSpec index_map points the table block AT the row to
gather -- the row is DMA'd HBM->VMEM by the pipeline itself; no giant
gather materializes and the table never passes through registers wholesale.

Grid: (B, L): step (b, l) streams table row idx[b, l] (a (1, D) block) and
accumulates into out[b]; the output block for row b is revisited across the
L inner steps (accumulate-in-place idiom: zero at l == 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, row_ref, out_ref):
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += row_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag(table, indices, *, interpret=False):
    """table: (V, D); indices: (B, L) int32 -> (B, D) sum-bags (fp32)."""
    v, d = table.shape
    b, l = indices.shape
    flat_idx = indices.reshape(-1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, l),
        in_specs=[
            # one table row per step, selected by the prefetched indices
            pl.BlockSpec((1, d), lambda i, j, idx: (idx[i * l + j], 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda i, j, idx: (i, 0)),
    )

    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        interpret=interpret,
    )(flat_idx, table)
