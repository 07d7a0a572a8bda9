"""Pallas TPU kernel: one-launch run-copy for plan-pair migrations.

A replan's :class:`repro.ps.elastic.MigrationDelta` names the new-plan
blocks whose content changes (moved runs + vacated lanes); everything
else is stationary.  This kernel executes the whole transition for ALL
of the state's 1-D leaves (flat/mu/nu/ef) in ONE launch:

  * the caller stages each leaf's touched blocks as a packed
    ``(n_touched * block,)`` buffer (an O(moved bytes) gather through
    the delta's per-lane source map -- see ops.py);
  * grid step i writes tile i of every staged buffer into block
    ``dst_blocks[i]`` of the corresponding full-length base buffer,
    with the destination blocks scalar-prefetched so the DMA engine
    knows the scatter pattern up front;
  * ``input_output_aliases`` pins each base buffer to its output, so
    stationary blocks are never read, copied, or written -- the launch
    cost is O(touched bytes) regardless of how much co-resident state
    shares the space.

Staging is what makes the in-place scatter hazard-free: sources are
read from a separate packed buffer, never from the aliased outputs, so
a run may move a block onto another run's source without ordering
constraints on the grid.

VMEM budget: 2 x n_leaves tiles of ``block`` fp32 lanes -- at the
service's block_align (16384) this is 128 KiB per leaf, far inside v5e
VMEM.  On the TPU ``block`` must be a multiple of ``tiling.TPU_TILE``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import check_block


def _kernel(dst_ref, *refs):
    # refs = (base_0..base_{L-1}, staged_0..staged_{L-1}, out_0..out_{L-1});
    # the bases are aliased to the outputs and never read here -- they only
    # carry the stationary blocks through the launch.
    del dst_ref
    n = len(refs) // 3
    staged, outs = refs[n : 2 * n], refs[2 * n :]
    for s, o in zip(staged, outs):
        o[...] = s[...]


def relayout_scatter(bases, staged, dst_blocks, *, block, interpret=False):
    """Scatter every leaf's staged touched-block tiles into its base.

    bases: sequence of (N,) full new-layout buffers (stationary content
    already in place; N a multiple of ``block``); staged: matching
    sequence of (n_touched * block,) packed buffers holding the final
    content of the touched blocks, in ``dst_blocks`` order; dst_blocks:
    (n_touched,) int32 new-plan block ids.

    Returns the updated buffers (same shapes/dtypes as ``bases``).  The
    bases are donated into the outputs (in-place update); only the
    touched blocks are written.
    """
    bases = list(bases)
    staged = list(staged)
    n_leaves = len(bases)
    assert n_leaves == len(staged) and n_leaves >= 1
    n_t = int(dst_blocks.shape[0])
    n = bases[0].shape[-1]
    assert n % block == 0, f"N={n} not a multiple of block={block}"
    if not interpret:
        check_block(block)
    for b, s in zip(bases, staged):
        assert b.shape == (n,), (b.shape, n)
        assert s.shape == (n_t * block,), (s.shape, n_t, block)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    packed = pl.BlockSpec((block,), lambda i, d: (i,))
    out = pl.BlockSpec((block,), lambda i, d: (d[i],))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_t,),
        in_specs=[any_spec] * n_leaves + [packed] * n_leaves,
        out_specs=[out] * n_leaves,
    )
    # Input k+1 is base k (index 0 is the prefetched dst table); alias it
    # onto output k so stationary blocks stay in place.
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(b.shape, b.dtype) for b in bases],
        input_output_aliases={1 + k: k for k in range(n_leaves)},
        interpret=interpret,
    )(dst_blocks.astype(jnp.int32), *bases, *staged)
