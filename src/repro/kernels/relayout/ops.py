"""Dispatch for the run-copy relayout: Pallas on TPU, jnp elsewhere.

``relayout(leaves, delta)`` executes a compiled
:class:`repro.ps.elastic.MigrationDelta` over every 1-D state leaf in
one pass, costing O(moved bytes):

  * TPU: stage each leaf's touched blocks with one gather through the
    delta's per-lane source map, then ONE scalar-prefetched
    ``kernel.relayout_scatter`` launch writes all leaves' touched
    blocks in place (aliased outputs -- stationary blocks never move).
  * off-TPU / interpret: a compiled jnp program -- an unrolled
    ``dynamic_slice``/``dynamic_update_slice`` chain per run when the
    run list is short, or the same staged block gather + one row
    scatter when it is not (both donate the inputs, so stationary
    lanes stay in place under jit).

Both paths are bit-exact with the full-gather oracle
(``repro.ps.elastic.migrate_flat_state``) on valid states (non-payload
lanes zero); ``ref.relayout_ref`` is the numpy oracle used by the
kernel tests.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from . import kernel as K

# Above this many runs the unrolled dynamic-slice program stops paying
# for itself (compile time grows with every run); the staged block
# gather/scatter handles the rest at the same O(touched bytes).
RUNS_UNROLL_MAX = 128


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resize(x, old_len: int, new_len: int):
    """Old buffer viewed at the new length (pad zeros / truncate)."""
    if new_len == old_len:
        return x
    if new_len > old_len:
        return jnp.concatenate([x, jnp.zeros((new_len - old_len,), x.dtype)])
    return jax.lax.slice(x, (0,), (new_len,))


@functools.lru_cache(maxsize=64)
def _runs_applier(moves, zeros, old_len, new_len, dtypes):
    """Jitted unrolled run program for one (delta, leaf-dtypes) pair.

    Donates the leaves: stationary lanes are carried by the (possibly
    in-place) resize, and only the run bytes are rewritten.
    """

    def apply(leaves):
        outs = []
        for x in leaves:
            base = _resize(x, old_len, new_len)
            for dst, length in zeros:
                base = jax.lax.dynamic_update_slice(
                    base, jnp.zeros((length,), x.dtype), (dst,))
            for src, dst, length in moves:
                # Reads come from the ORIGINAL x, never from base: a run
                # may land on another run's source without ordering
                # hazards (XLA inserts the minimal copy if regions alias
                # under donation).
                base = jax.lax.dynamic_update_slice(
                    base, jax.lax.dynamic_slice(x, (src,), (length,)), (dst,))
            outs.append(base)
        return outs

    # Donation only pays when the space keeps its length (in-place run
    # rewrite); a resize can't reuse the buffers and would just warn.
    donate = (0,) if old_len == new_len else ()
    return jax.jit(apply, donate_argnums=donate)


def _stage(x, delta):
    """Final content of the delta's touched blocks, packed in block order:
    one O(touched-bytes) gather through the per-lane source map."""
    # stage_src is always in-bounds: unset (non-kept) lanes carry index 0,
    # and a non-empty touched set implies the old plan had payload.
    gathered = jnp.take(x, jnp.asarray(delta.stage_src), axis=0)
    return jnp.where(jnp.asarray(delta.stage_keep), gathered,
                     jnp.zeros((), x.dtype))


@functools.lru_cache(maxsize=64)
def _staged_applier(delta_key, old_len, new_len, block, dtypes):
    """Jitted staged block gather + row scatter (the many-runs jnp path)."""
    delta = _STAGE_DELTAS[delta_key]
    rows = jnp.asarray(delta.touched_blocks)

    def apply(leaves):
        outs = []
        for x in leaves:
            base = _resize(x, old_len, new_len)
            staged = _stage(x, delta)
            outs.append(
                base.reshape(-1, block).at[rows].set(
                    staged.reshape(-1, block), unique_indices=True,
                    indices_are_sorted=True).reshape(base.shape))
        return outs

    donate = (0,) if old_len == new_len else ()
    return jax.jit(apply, donate_argnums=donate)


# The staged applier needs the delta's numpy arrays at trace time but
# lru_cache needs hashable keys; park the delta under its content key.
_STAGE_DELTAS = {}


def _delta_key(delta):
    return (delta.old_len, delta.new_len, delta.block, delta.moves,
            delta.zeros, delta.touched_blocks.tobytes())


def relayout(leaves: Sequence, delta, *,
             interpret: Optional[bool] = None) -> List:
    """Execute one compiled MigrationDelta over every given 1-D leaf.

    Returns the migrated leaves (length ``delta.new_len`` each), in
    order.  O(moved bytes) on every path; the leaves may be donated.
    """
    leaves = list(leaves)
    if delta.identity or not leaves:
        return leaves
    for x in leaves:
        assert x.ndim == 1 and x.shape[0] == delta.old_len, (
            f"leaf shape {x.shape} != old_len {delta.old_len}")
    dtypes = tuple(jnp.dtype(x.dtype).name for x in leaves)
    if not delta.touched_blocks.size:
        # Pure resize (e.g. a shard appended for an arriving job): no
        # content moves at all.
        return [_resize(x, delta.old_len, delta.new_len) for x in leaves]

    if (_on_tpu() if interpret is None else not interpret):
        # The kernel is the only TPU path: a layout it cannot take raises
        # (relayout_scatter) instead of falling back to the jnp programs.
        bases = [_resize(x, delta.old_len, delta.new_len) for x in leaves]
        staged = [_stage(x, delta) for x in leaves]
        return list(K.relayout_scatter(
            bases, staged, jnp.asarray(delta.touched_blocks),
            block=delta.block, interpret=False))

    if (delta.n_runs <= RUNS_UNROLL_MAX
            or delta.new_len % delta.block != 0):
        fn = _runs_applier(delta.moves, delta.zeros, delta.old_len,
                           delta.new_len, dtypes)
        return fn(leaves)
    key = _delta_key(delta)
    if len(_STAGE_DELTAS) > 256:  # appliers re-park their key on demand
        _STAGE_DELTAS.clear()
    _STAGE_DELTAS.setdefault(key, delta)
    fn = _staged_applier(key, delta.old_len, delta.new_len, delta.block,
                         dtypes)
    return fn(leaves)
