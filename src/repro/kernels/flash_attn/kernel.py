"""Pallas TPU flash attention (forward): blockwise online softmax.

Grid: (B*H, S_q / BQ). Each grid step holds one (BQ, D) query tile in VMEM
and loops over (BK, D) key/value tiles with the online-softmax recurrence --
the (S, S) score matrix never exists in HBM. MXU-aligned tiles: BQ = BK =
128, D in {64, 128, 192, 256}. fp32 accumulators (acc, m, l) live in VMEM
scratch for the duration of a query tile.

Causal masking skips fully-masked KV tiles by bounding the fori_loop at the
query tile's diagonal -- ~2x fewer tiles at long S (the IO-aware scheduling
the TPU build relies on; interp-mode tests validate against ref.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
BQ = 128
BK = 128


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, bk, seq_k):
    bq, d = q_ref.shape
    q = q_ref[...].astype(jnp.float32) * scale
    qi = pl.program_id(1)  # query tile index

    n_kv = seq_k // bk
    if causal:
        # Last KV tile that intersects this query tile's causal frontier.
        hi = jnp.minimum(((qi + 1) * bq + bk - 1) // bk, n_kv)
    else:
        hi = n_kv

    def body(j, carry):
        acc, m, l = carry
        k_tile = k_ref[pl.ds(j * bk, bk), :]
        v_tile = v_ref[pl.ds(j * bk, bk), :]
        s = q @ k_tile.astype(jnp.float32).T  # (BQ, BK)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v_tile.astype(jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "bq", "bk", "interpret")
)
def flash_attention(q, k, v, *, causal=True, scale=None, bq=BQ, bk=BK,
                    interpret=False):
    """q,k,v: (B, H, S, D); S % bq == 0 == S % bk. Forward only."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    assert sq % bq == 0 and sk % bk == 0, (sq, sk, bq, bk)
    scale = d ** -0.5 if scale is None else scale

    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, sk, d)
    vr = v.reshape(b * h, sk, d)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bk=bk, seq_k=sk
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, sq // bq),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d)
