"""Pallas TPU kernel: fused W-way gradient aggregation + Adam update.

The PS Update op. Naive XLA path reads/writes p, mu, nu and reads W grad
buffers in separate HBM passes; this kernel makes one pass: each grid step
streams a (BLOCK,) tile of every operand into VMEM, sums the W worker
gradients on the VPU, applies the Adam update, and writes p/mu/nu tiles
back -- arithmetic intensity goes from ~1/7 to ~1 fused op per byte, which
is what makes aggregation burst-friendly on a shared Aggregator core.

``aggregate_adam`` is the dense form (every block of the space belongs to
the caller).  ``aggregate_adam_blocks`` is the SHARED-space form: the flat
space hosts many jobs, and the grid iterates only the calling job's owned
blocks -- a scalar-prefetched block-index operand drives the BlockSpec
index maps, so the DMA engine gathers exactly the job's tiles of p/mu/nu
out of the full buffers and the update costs O(job bytes) regardless of
how much co-resident state shares the space.

``aggregate_adam_multijob`` is the SERVICE-TICK form: K co-resident jobs'
pending updates run as ONE launch.  Two scalar-prefetched operands drive
the grid -- a concatenated owned-block index table (all participating
jobs' blocks back to back) and a per-block job-slot map -- so grid step i
DMAs block ``block_idx[i]`` of the shared buffers and reads row
``job_slot[i]`` of a (K, HP_COLS) per-job hyperparameter table held whole
in SMEM (lr, betas and their pre-folded complements, eps, bias-correction
reciprocals, weight decay).
Block exclusivity (every block belongs to at
most one job) is what makes the batched pass semantically identical to K
sequential per-job updates.

``aggregate_adam_multijob_fused`` is the SINGLE-LAUNCH form: same grid,
but the outputs are the full shared buffers -- out-specs index by the
prefetched block table and ``input_output_aliases`` pins each buffer in
place (the kernels/relayout pattern), so the three post-apply row
scatters disappear and a whole service tick is ONE kernel launch.

VMEM budget at BLOCK=16384 fp32: (W + 5) x 64 KiB tiles -- e.g. W=8 -> 832
KiB, comfortably inside the ~16 MiB v5e VMEM with double buffering.

SMEM budget of the multi-job forms: the two int32 prefetch tables take
8 B per grid step, and v5e SMEM holds 1 MiB.  At BLOCK=16384 the largest
launch 16 GB of HBM can hold (~1.3e9 fp32 elements of p/mu/nu, ~82k
blocks) needs ~650 KiB; at 2048 a 348M-element tick already overflows.
On the TPU every block must be a multiple of ``tiling.TPU_TILE``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import check_block

BLOCK = 16384  # elements per tile; a multiple of tiling.TPU_TILE


def _kernel(p_ref, g_ref, mu_ref, nu_ref, bc_ref, out_p, out_mu, out_nu,
            *, lr, b1, b2, eps, wd):
    g = g_ref[...].astype(jnp.float32)
    if g.ndim == 2:  # (W, BLOCK) worker pushes -> sum-aggregate
        g = g.sum(axis=0)
    mu = b1 * mu_ref[...] + (1.0 - b1) * g
    nu = b2 * nu_ref[...] + (1.0 - b2) * g * g
    mu_hat = mu * bc_ref[0]  # 1/(1-b1^t)
    nu_hat = nu * bc_ref[1]  # 1/(1-b2^t)
    p32 = p_ref[...].astype(jnp.float32)
    # (lr*mu_hat)/denom keeps the final subtract free of a direct multiply
    # operand, so XLA cannot FMA-contract it differently from the unfused
    # paths (repro.ps.runtime._adam_math uses the same grouping).
    upd = (lr * mu_hat) / (jnp.sqrt(nu_hat) + eps)
    if wd:
        upd = upd + (lr * wd) * p32
    out_p[...] = (p32 - upd).astype(out_p.dtype)
    out_mu[...] = mu
    out_nu[...] = nu


@functools.partial(
    jax.jit,
    static_argnames=("lr", "b1", "b2", "eps", "wd", "block", "interpret"),
)
def aggregate_adam(p, grads, mu, nu, count, *, lr, b1=0.9, b2=0.999,
                   eps=1e-8, wd=0.0, block=BLOCK, interpret=False):
    """p, mu, nu: (N,); grads: (N,) or (W, N); count: int32 scalar (1-based).

    N must be a multiple of `block` (ops.py pads)."""
    n = p.shape[-1]
    assert n % block == 0, f"N={n} not a multiple of block={block}"
    if not interpret:
        check_block(block)
    grid = (n // block,)
    t = count.astype(jnp.float32)
    bc = jnp.stack([1.0 / (1.0 - b1 ** t), 1.0 / (1.0 - b2 ** t)])

    if grads.ndim == 2:
        g_spec = pl.BlockSpec((grads.shape[0], block), lambda i: (0, i))
    else:
        g_spec = pl.BlockSpec((block,), lambda i: (i,))
    vec = pl.BlockSpec((block,), lambda i: (i,))
    bc_spec = pl.BlockSpec((2,), lambda i: (0,))

    kernel = functools.partial(_kernel, lr=lr, b1=b1, b2=b2, eps=eps, wd=wd)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[vec, g_spec, vec, vec, bc_spec],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(p.shape, p.dtype),
            jax.ShapeDtypeStruct(mu.shape, jnp.float32),
            jax.ShapeDtypeStruct(nu.shape, jnp.float32),
        ],
        interpret=interpret,
    )(p, grads, mu, nu, bc)


def _block_kernel(bidx_ref, *refs, **kw):
    # The scalar-prefetched block indices are consumed by the BlockSpec
    # index maps only; the tile math is identical to the dense kernel.
    del bidx_ref
    _kernel(*refs, **kw)


@functools.partial(
    jax.jit,
    static_argnames=("lr", "b1", "b2", "eps", "wd", "block", "interpret"),
)
def aggregate_adam_blocks(p, grads, mu, nu, count, block_idx, *, lr, b1=0.9,
                          b2=0.999, eps=1e-8, wd=0.0, block=BLOCK,
                          interpret=False):
    """Block-owned shared-space update: touch only the caller's blocks.

    mu, nu: (N,) FULL shared buffers (N a multiple of `block`);
    p: (N,) full, or already PACKED (M,) -- the caller usually has the
    packed parameters in hand from the pull, so re-gathering them here
    would cost an extra O(job bytes) pass; grads: (M,) or (W, M) PACKED
    job-domain gradient with M = len(block_idx) * block; block_idx:
    (n_own,) int32 owned block ids; count: int32 scalar (1-based, this
    job's step counter).

    Grid step i DMAs tile ``block_idx[i]`` of mu/nu (and of p when full --
    scalar prefetch makes the indices available to the index maps before
    the body runs) and tile ``i`` of the packed operands, then writes tile
    ``i`` of the PACKED outputs -- the caller scatters them back onto its
    owned lanes.  Returns (new_p, new_mu, new_nu), each (M,).
    """
    n = mu.shape[-1]
    assert n % block == 0, f"N={n} not a multiple of block={block}"
    if not interpret:
        check_block(block)
    n_own = block_idx.shape[0]
    m = grads.shape[-1]
    assert m == n_own * block, (
        f"packed gradient length {m} != n_own*block = {n_own}*{block}")
    assert p.shape[-1] in (n, m), (
        f"p length {p.shape[-1]} is neither full ({n}) nor packed ({m})")
    t = count.astype(jnp.float32)
    bc = jnp.stack([1.0 / (1.0 - b1 ** t), 1.0 / (1.0 - b2 ** t)])

    owned = pl.BlockSpec((block,), lambda i, bidx: (bidx[i],))
    packed = pl.BlockSpec((block,), lambda i, bidx: (i,))
    if grads.ndim == 2:
        g_spec = pl.BlockSpec((grads.shape[0], block), lambda i, bidx: (0, i))
    else:
        g_spec = packed
    p_spec = packed if p.shape[-1] == m else owned
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_own,),
        in_specs=[p_spec, g_spec, owned, owned,
                  pl.BlockSpec((2,), lambda i, bidx: (0,))],
        out_specs=[packed, packed, packed],
    )
    kernel = functools.partial(_block_kernel, lr=lr, b1=b1, b2=b2, eps=eps,
                               wd=wd)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m,), p.dtype),
            jax.ShapeDtypeStruct((m,), jnp.float32),
            jax.ShapeDtypeStruct((m,), jnp.float32),
        ],
        interpret=interpret,
    )(block_idx.astype(jnp.int32), p, grads, mu, nu, bc)


HP_COLS = 16  # (lr, b1, 1-b1, b2, 1-b2, eps, bc1, bc2, wd, pad...) per job


def _multijob_kernel(bidx_ref, jslot_ref, p_ref, g_ref, mu_ref, nu_ref,
                     hp_ref, out_p, out_mu, out_nu):
    # bidx is consumed by the BlockSpec index maps; the whole (K, HP_COLS)
    # hyperparameter table sits in SMEM and this block's owner row is
    # picked by the prefetched job slot.
    # Same arithmetic form as _kernel, with the compile-time constants
    # replaced by the per-job scalars; 1-b1 / 1-b2 come PRE-FOLDED from
    # the table because the dense kernels fold them from python doubles
    # at trace time -- recomputing them here in f32
    # (1.0 - 0.9f != f32(1.0 - 0.9)) would break bit-parity.
    del bidx_ref
    j = jslot_ref[pl.program_id(0)]
    lr, b1, omb1 = hp_ref[j, 0], hp_ref[j, 1], hp_ref[j, 2]
    b2, omb2, eps = hp_ref[j, 3], hp_ref[j, 4], hp_ref[j, 5]
    bc1, bc2, wd = hp_ref[j, 6], hp_ref[j, 7], hp_ref[j, 8]
    g = g_ref[...].astype(jnp.float32)
    if g.ndim == 2:  # (W, BLOCK) worker pushes -> sum-aggregate
        g = g.sum(axis=0)
    mu = b1 * mu_ref[...] + omb1 * g
    nu = b2 * nu_ref[...] + omb2 * g * g
    mu_hat = mu * bc1
    nu_hat = nu * bc2
    p32 = p_ref[...].astype(jnp.float32)
    upd = (lr * mu_hat) / (jnp.sqrt(nu_hat) + eps)
    upd = upd + (lr * wd) * p32
    out_p[...] = (p32 - upd).astype(out_p.dtype)
    out_mu[...] = mu
    out_nu[...] = nu


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def aggregate_adam_multijob_fused(p, grads, mu, nu, hp, block_idx, job_slot,
                                  *, block=BLOCK, interpret=False):
    """Multi-job Adam with the row scatters FUSED into the launch.

    Same grid and tile math as :func:`aggregate_adam_multijob`, but the
    outputs are the FULL shared buffers instead of packed vectors: the
    out-specs index by the scalar-prefetched block table (grid step i
    writes tile ``block_idx[i]``), and ``input_output_aliases`` pins each
    full input buffer to its output -- the kernels/relayout pattern -- so
    stationary blocks are never read, copied, or written and the caller
    needs NO post-apply scatter pass.  The in-place write is hazard-free:
    step i reads and writes the SAME block (exclusive by construction),
    and distinct grid steps touch distinct blocks.

    p, mu, nu: (N,) FULL shared buffers (p cannot arrive packed here: its
    untouched lanes must ride through the launch).  Returns the updated
    full (new_p, new_mu, new_nu), each (N,).
    """
    n = mu.shape[-1]
    assert n % block == 0, f"N={n} not a multiple of block={block}"
    if not interpret:
        check_block(block)
    n_own = block_idx.shape[0]
    assert job_slot.shape == (n_own,), (job_slot.shape, n_own)
    m = grads.shape[-1]
    assert m == n_own * block, (
        f"packed gradient length {m} != n_own*block = {n_own}*{block}")
    assert p.shape[-1] == n, (
        f"p length {p.shape[-1]} != full length {n} (the fused-scatter "
        f"form writes into the full buffers; pass the packed p to "
        f"aggregate_adam_multijob instead)")
    assert hp.ndim == 2 and hp.shape[1] == HP_COLS, hp.shape

    owned = pl.BlockSpec((block,), lambda i, bidx, jslot: (bidx[i],))
    if grads.ndim == 2:
        g_spec = pl.BlockSpec((grads.shape[0], block),
                              lambda i, bidx, jslot: (0, i))
    else:
        g_spec = pl.BlockSpec((block,), lambda i, bidx, jslot: (i,))
    hp_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_own,),
        in_specs=[owned, g_spec, owned, owned, hp_spec],
        out_specs=[owned, owned, owned],
    )
    # Inputs 2/4/5 are p/mu/nu (0 and 1 are the prefetched tables); alias
    # them onto outputs 0/1/2 so untouched blocks stay in place.
    return pl.pallas_call(
        _multijob_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(p.shape, p.dtype),
            jax.ShapeDtypeStruct(mu.shape, jnp.float32),
            jax.ShapeDtypeStruct(nu.shape, jnp.float32),
        ],
        input_output_aliases={2: 0, 4: 1, 5: 2},
        interpret=interpret,
    )(block_idx.astype(jnp.int32), job_slot.astype(jnp.int32),
      p, grads, mu, nu, hp.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("block", "p_packed",
                                              "interpret"))
def aggregate_adam_multijob(p, grads, mu, nu, hp, block_idx, job_slot, *,
                            block=BLOCK, p_packed=False, interpret=False):
    """K co-resident jobs' Adam updates in one launch (one service tick).

    mu, nu: (N,) FULL shared buffers; p: (N,) full, or -- with
    ``p_packed=True`` -- (M,) already packed in block-table order (the
    flag is EXPLICIT because when the jobs jointly own every block M == N
    and the two layouts are indistinguishable by shape yet differently
    ordered); grads: (M,) or (W, M) concatenation of the participating
    jobs' packed gradients, in ``block_idx`` order with
    M = len(block_idx) * block; hp: (K, HP_COLS) float32 per-job
    hyperparameter table ``[lr, b1, 1-b1, b2, 1-b2, eps, bc1, bc2, wd,
    0...]`` (bc* are the bias-correction *reciprocals* for that job's
    1-based step count); block_idx: (n_own,) int32 concatenated
    owned-block table; job_slot: (n_own,) int32 row of ``hp`` owning each
    block.

    Grid step i DMAs tile ``block_idx[i]`` of the shared buffers and tile i
    of the packed operands, reads row ``job_slot[i]`` of the SMEM-resident
    hp, then writes tile i of the PACKED outputs.  Returns (new_p, new_mu,
    new_nu), each (M,).
    """
    n = mu.shape[-1]
    assert n % block == 0, f"N={n} not a multiple of block={block}"
    if not interpret:
        check_block(block)
    n_own = block_idx.shape[0]
    assert job_slot.shape == (n_own,), (job_slot.shape, n_own)
    m = grads.shape[-1]
    assert m == n_own * block, (
        f"packed gradient length {m} != n_own*block = {n_own}*{block}")
    assert p.shape[-1] == (m if p_packed else n), (
        f"p length {p.shape[-1]} != {'packed' if p_packed else 'full'} "
        f"length {(m if p_packed else n)}")
    assert hp.ndim == 2 and hp.shape[1] == HP_COLS, hp.shape

    owned = pl.BlockSpec((block,), lambda i, bidx, jslot: (bidx[i],))
    packed = pl.BlockSpec((block,), lambda i, bidx, jslot: (i,))
    if grads.ndim == 2:
        g_spec = pl.BlockSpec((grads.shape[0], block),
                              lambda i, bidx, jslot: (0, i))
    else:
        g_spec = packed
    p_spec = packed if p_packed else owned
    hp_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_own,),
        in_specs=[p_spec, g_spec, owned, owned, hp_spec],
        out_specs=[packed, packed, packed],
    )
    return pl.pallas_call(
        _multijob_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m,), p.dtype),
            jax.ShapeDtypeStruct((m,), jnp.float32),
            jax.ShapeDtypeStruct((m,), jnp.float32),
        ],
        interpret=interpret,
    )(block_idx.astype(jnp.int32), job_slot.astype(jnp.int32),
      p, grads, mu, nu, hp.astype(jnp.float32))
