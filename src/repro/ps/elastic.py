"""Elastic re-mesh + tensor migration in the data plane.

Two migration executors re-lay a PS flat state from one FlatPlan to
another (the data-plane half of the paper's tensor migration: the owner
segments move, everything else stays):

``migrate_flat_state``
    The full-gather ORACLE: one permutation gather over the whole new
    space.  Always correct, O(total bytes) per replan -- kept as the
    parity reference the delta path is tested against.

``migrate_flat_state_delta``
    The shipped O(moved-bytes) path: a :class:`MigrationDelta` compiled
    per plan pair reduces the transition to a run-length list of
    contiguous ``(src, dst, len)`` moves plus zero-runs for vacated
    lanes; only those runs are executed (a scalar-prefetched Pallas
    run-copy launch on TPU, ``dynamic_slice``/scatter jnp programs
    elsewhere -- repro.kernels.relayout).  Lanes that do not move are
    never touched, so a small job's arrival costs O(its own bytes), not
    O(every co-resident job's bytes).

    Contract: delta migration is bit-exact with the full-gather oracle
    on *valid* states -- states whose non-payload lanes are zero in
    every 1-D leaf.  That invariant is maintained by every official
    state constructor and mutator (``init_shared_state``,
    ``seed_job_params``, the train steps, and both migration paths), so
    it holds for any state the runtime ever owns.

Plans may be multi-job (compiled by ``ParameterService.compile_plan``):
segments are matched by their job-qualified key ``(job_id, tensor_key)``;
segments that only exist in the new plan (a job arrival) come out
zero-initialized, segments that only exist in the old plan (a job exit)
are dropped.  `reshard_tree` moves any pytree onto new shardings
(elastic scale up/down, spot drain from §6).

Compiled per-pair structures (permutations and deltas) live in one
size-bounded LRU cache: a long-lived service replanning periodically can
not leak one full-space index array per replan.  ``plan_cache_stats`` /
``set_plan_cache_limit`` expose and bound it.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .plan import FlatPlan, ShardedPlan, plan_migration_bytes, segment_mask


class PlanPerm(NamedTuple):
    """Precompiled (old -> new) lane permutation for one plan pair."""

    idx: np.ndarray  # (new.total_len,) int64 source lanes
    keep: np.ndarray  # (new.total_len,) bool: covered by a common segment
    all_kept: bool
    identity: bool  # the move is a no-op (every lane stays put)


class MigrationDelta(NamedTuple):
    """Compiled plan-pair transition: only what CHANGES, as runs.

    ``moves`` are maximal contiguous runs of kept lanes whose flat
    position changed (constant shift within a run); ``zeros`` are runs of
    lanes that held old payload at a position no common segment covers in
    the new plan (vacated by an exit or a relocation) and must read zero
    afterwards.  Everything else is stationary and is never touched.

    ``touched_blocks`` are the new-plan ``block_align`` block ids any
    move/zero run intersects, with ``stage_src``/``stage_keep`` the
    per-lane source map of exactly those blocks (packed, block order) --
    the operands of the one-launch kernel path.  ``touched_jobs`` is the
    control signal for stall-free replans: jobs whose segment layout
    differs between the plans (arrivals and exits included); a job NOT in
    it has a bit-identical layout in both plans, so its queued pushes and
    compiled programs remain valid across the migration.
    """

    old_len: int
    new_len: int
    block: int  # new plan's block_align
    moves: Tuple[Tuple[int, int, int], ...]  # (src, dst, length) runs
    zeros: Tuple[Tuple[int, int], ...]  # (dst, length) runs
    touched_jobs: Tuple[str, ...]
    touched_blocks: np.ndarray  # new-plan block ids hit by moves/zeros
    stage_src: np.ndarray  # (n_touched*block,) int64 source lane per lane
    stage_keep: np.ndarray  # (n_touched*block,) bool: lane carries payload
    moved_elements: int
    zeroed_elements: int

    @property
    def identity(self) -> bool:
        """Nothing to execute: same length, no moves, nothing vacated."""
        return (self.old_len == self.new_len and not self.moves
                and not self.zeros)

    @property
    def n_runs(self) -> int:
        return len(self.moves) + len(self.zeros)

    def moved_bytes(self, bytes_per_element: int = 12) -> int:
        """Bytes the delta path actually copies (master + both moments at
        4 B each by default -- same convention as :func:`migration_bytes`)."""
        return self.moved_elements * bytes_per_element


# ------------------------------------------------------- bounded pair cache
class _PlanPairCache:
    """Size-bounded LRU for per-plan-pair structures (perms + deltas).

    The old unbounded ``lru_cache`` leaked one full-space index array per
    replan in a long-lived service with periodic rebalance; this one
    evicts least-recently-used entries once the numpy payload exceeds
    ``max_bytes`` and exposes a stats hook.
    """

    def __init__(self, max_bytes: int = 256 << 20):
        self.max_bytes = int(max_bytes)
        self._entries: "OrderedDict[Any, Tuple[Any, int]]" = OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @staticmethod
    def _nbytes(value: Any) -> int:
        # Every entry pays a floor (its key strongly pins two FlatPlans)
        # plus its numpy AND python-tuple payload -- a 0-cost estimate
        # would never evict and quietly reintroduce the leak this cache
        # exists to fix.
        def size(v: Any) -> int:
            n = getattr(v, "nbytes", None)
            if n is not None:
                return int(n)
            if isinstance(v, tuple):
                return 56 + sum(size(x) for x in v)
            return 32

        fields = getattr(value, "_fields", None)
        payload = (sum(size(getattr(value, f)) for f in fields)
                   if fields else size(value))
        return 1024 + payload

    def get(self, key):
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return entry[0]

    def put(self, key, value) -> None:
        nbytes = self._nbytes(value)
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
            self._entries[key] = (value, nbytes)
            self._bytes += nbytes
            while self._bytes > self.max_bytes and len(self._entries) > 1:
                _, (_, freed) = self._entries.popitem(last=False)
                self._bytes -= freed
                self.evictions += 1

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "entries": len(self._entries),
                "bytes": self._bytes,
                "max_bytes": self.max_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
            }

    def resize(self, max_bytes: int) -> None:
        with self._lock:
            self.max_bytes = int(max_bytes)
            while self._bytes > self.max_bytes and self._entries:
                _, (_, freed) = self._entries.popitem(last=False)
                self._bytes -= freed
                self.evictions += 1

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0


_PAIR_CACHE = _PlanPairCache()


def plan_cache_stats() -> Dict[str, int]:
    """Hits/misses/evictions/bytes of the per-plan-pair structure cache."""
    return _PAIR_CACHE.stats()


def set_plan_cache_limit(max_bytes: int) -> None:
    """Bound the per-plan-pair cache; evicts immediately if over."""
    _PAIR_CACHE.resize(max_bytes)


def clear_plan_cache() -> None:
    _PAIR_CACHE.clear()


def _plan_perm(old: FlatPlan, new: FlatPlan) -> PlanPerm:
    """(idx, keep) with new_flat[i] = old_flat[idx[i]] where keep[i], else 0.

    Lanes not covered by a common segment (padding, or segments of a job
    that was not in the old plan) get keep=False.  Cached per
    ``(old, new)`` plan pair (plans are frozen/hashable), so periodic
    rebalances that bounce between the same layouts -- or that move
    nothing at all -- never recompute or re-trace the permutation.
    """
    key = ("perm", old, new)
    cached = _PAIR_CACHE.get(key)
    if cached is not None:
        return cached
    old_by_key = old.by_skey
    idx = np.zeros(new.total_len, dtype=np.int64)
    keep = np.zeros(new.total_len, dtype=bool)
    for seg in new.segments:
        o = old_by_key.get(seg.skey)
        if o is None:
            continue  # new job's segment: zero-initialized
        if o.size != seg.size:
            raise ValueError(
                f"segment {seg.skey} changed size {o.size} -> {seg.size}"
            )
        src = old.start(o)
        dst = new.start(seg)
        idx[dst : dst + seg.size] = np.arange(src, src + seg.size)
        keep[dst : dst + seg.size] = True
    all_kept = bool(keep.all())
    identity = (
        all_kept
        and old.total_len == new.total_len
        and bool((idx == np.arange(new.total_len)).all())
    )
    idx.setflags(write=False)
    keep.setflags(write=False)
    perm = PlanPerm(idx, keep, all_kept, identity)
    _PAIR_CACHE.put(key, perm)
    return perm


def _perm_old_to_new(old: FlatPlan, new: FlatPlan) -> Tuple[np.ndarray, np.ndarray]:
    """Back-compat view of :func:`_plan_perm` (idx, keep)."""
    perm = _plan_perm(old, new)
    return perm.idx, perm.keep


def _runs(mask: np.ndarray, shift: Optional[np.ndarray] = None):
    """Maximal runs of True lanes (splitting where ``shift`` changes).

    Yields (start, length) -- contiguous in the mask's index space and,
    when ``shift`` is given, of constant shift (so src is contiguous too).
    """
    pos = np.nonzero(mask)[0]
    if not pos.size:
        return []
    breaks = np.diff(pos) != 1
    if shift is not None:
        breaks |= np.diff(shift[pos]) != 0
    cut = np.nonzero(breaks)[0]
    starts = pos[np.concatenate([[0], cut + 1])]
    ends = pos[np.concatenate([cut, [pos.size - 1]])]
    return [(int(s), int(e - s + 1)) for s, e in zip(starts, ends)]


def _job_layout_sigs(plan: FlatPlan) -> Dict[str, Tuple]:
    """Per-job layout fingerprint: absolute (start, size, key) of every
    segment, the block granularity, and whether the job owns EVERY block
    of the space -- equal fingerprints mean the job's lanes, blocks,
    packed slots, and gather/scatter fast paths (``covers_all``) are
    identical in both plans, so every compiled program that closes over
    its JobLayout stays valid across the pair.

    O(segments log segments): owned-block counts come from merged block
    intervals, never materialized lane- or block-wise (plans can span
    hundreds of millions of lanes in the simulator).
    """
    block = max(1, plan.block_align)
    n_blocks_total = -(-plan.total_len // block)
    sigs: Dict[str, list] = {}
    spans: Dict[str, list] = {}
    for seg in plan.segments:
        start = plan.start(seg)
        sigs.setdefault(seg.job_id, []).append((start, seg.size, seg.key))
        spans.setdefault(seg.job_id, []).append(
            (start // block, (start + seg.size - 1) // block + 1))
    out = {}
    for j, v in sigs.items():
        n_owned, end = 0, -1
        for lo, hi in sorted(spans[j]):  # merged half-open block intervals
            lo = max(lo, end)
            if hi > lo:
                n_owned += hi - lo
                end = hi
        out[j] = (block, n_owned == n_blocks_total, tuple(sorted(v)))
    return out


def plan_transition_summary(old: FlatPlan, new: FlatPlan):
    """Segment-level view of a plan transition: O(segments), no lane
    arrays -- safe at simulator scale (hundreds of millions of lanes).

    Returns ``(moved_elements, touched_jobs)``.  ``moved_elements``
    equals the delta's exactly: a common segment relocates rigidly (its
    lanes share one shift), so the moved-lane count is the summed size
    of the segments whose absolute start changed.  ``touched_jobs`` is
    the same layout-fingerprint diff :func:`compile_migration_delta`
    reports.
    """
    key = ("summary", old, new)
    cached = _PAIR_CACHE.get(key)
    if cached is not None:
        return cached
    old_by_key = old.by_skey
    moved = 0
    for seg in new.segments:
        o = old_by_key.get(seg.skey)
        if o is None:
            continue
        if o.size != seg.size:
            raise ValueError(
                f"segment {seg.skey} changed size {o.size} -> {seg.size}")
        if old.start(o) != new.start(seg):
            moved += seg.size
    old_sigs = _job_layout_sigs(old)
    new_sigs = _job_layout_sigs(new)
    touched = tuple(sorted(
        j for j in set(old_sigs) | set(new_sigs)
        if old_sigs.get(j) != new_sigs.get(j)))
    summary = (moved, touched)
    _PAIR_CACHE.put(key, summary)
    return summary


def compile_migration_delta(old: FlatPlan, new: FlatPlan) -> MigrationDelta:
    """Compile the O(moved-bytes) transition for one plan pair (cached).

    Compilation itself is O(total lanes) numpy ONCE per pair (same cost
    class as the permutation it replaces); what it buys is that
    *execution* -- every replan, on device -- touches only the moved and
    vacated runs.
    """
    key = ("delta", old, new)
    cached = _PAIR_CACHE.get(key)
    if cached is not None:
        return cached
    perm = _plan_perm(old, new)
    old_len, new_len = old.total_len, new.total_len
    lanes = np.arange(new_len, dtype=np.int64)
    needs_copy = perm.keep & (perm.idx != lanes)

    # Vacated lanes: positions that held old payload but are not covered
    # (stationarily or by a copy) in the new plan.  On valid states every
    # other non-kept lane is already zero, so nothing else is written.
    old_payload = segment_mask(old)
    vacated = ~perm.keep
    vacated[old_len:] = False  # resize padding is born zero
    vacated[: min(old_len, new_len)] &= old_payload[: min(old_len, new_len)]

    shift = perm.idx - lanes
    moves = tuple(
        (int(perm.idx[s]), s, n) for s, n in _runs(needs_copy, shift))
    zeros = tuple(_runs(vacated))

    block = max(1, int(new.block_align))
    touched_lanes = needs_copy | vacated
    n_blocks_total = -(-new_len // block)
    padded = np.zeros(n_blocks_total * block, dtype=bool)
    padded[:new_len] = touched_lanes
    touched_blocks = np.nonzero(padded.reshape(-1, block).any(axis=1))[0]
    touched_blocks = touched_blocks.astype(np.int32)

    # Per-lane source map of the touched blocks only (kernel staging).
    own = (touched_blocks.astype(np.int64)[:, None] * block
           + np.arange(block)).reshape(-1)
    own_in = own[own < new_len]
    stage_src = np.zeros(own.size, dtype=np.int64)
    stage_keep = np.zeros(own.size, dtype=bool)
    stage_src[: own_in.size] = perm.idx[own_in]
    stage_keep[: own_in.size] = perm.keep[own_in]

    _, touched_jobs = plan_transition_summary(old, new)

    for arr in (touched_blocks, stage_src, stage_keep):
        arr.setflags(write=False)
    delta = MigrationDelta(
        old_len=old_len, new_len=new_len, block=block, moves=moves,
        zeros=zeros, touched_jobs=touched_jobs,
        touched_blocks=touched_blocks, stage_src=stage_src,
        stage_keep=stage_keep,
        moved_elements=int(needs_copy.sum()),
        zeroed_elements=int(vacated.sum()),
    )
    _PAIR_CACHE.put(key, delta)
    return delta


def migrate_flat_state(state: Dict[str, Any], old: FlatPlan, new: FlatPlan):
    """Full-gather migration oracle (O(total bytes) per replan).

    Every 1-D leaf of length ``old.total_len`` (flat, mu, nu, ef) is
    gathered onto the new layout; scalars (step counters, incl. the shared
    state's per-job ``counts``) pass through untouched.  Common segments
    are relocated bit-exactly.  Equal plans -- and permutations that turn
    out to be the identity (a rebalance that moved nothing) -- return the
    state untouched without dispatching a single device op.
    """
    if old == new:
        return state
    perm = _plan_perm(old, new)
    if perm.identity:
        return state
    idx = jnp.asarray(perm.idx)
    keep = jnp.asarray(perm.keep)

    def move(x):
        if getattr(x, "ndim", 0) != 1 or x.shape[0] != old.total_len:
            return x
        moved = jnp.take(x, idx, axis=0)
        if perm.all_kept:
            return moved
        return jnp.where(keep, moved, jnp.zeros((), x.dtype))

    return jax.tree_util.tree_map(move, state)


def migrate_flat_state_delta(
    state: Dict[str, Any],
    old: FlatPlan,
    new: FlatPlan,
    *,
    delta: Optional[MigrationDelta] = None,
    interpret: Optional[bool] = None,
):
    """O(moved-bytes) migration: execute only the compiled delta's runs.

    Bit-exact with :func:`migrate_flat_state` on valid states (non-payload
    lanes zero -- the invariant every runtime state satisfies).  All 1-D
    leaves of length ``old.total_len`` move in ONE pass
    (``repro.kernels.relayout``: a single scalar-prefetched run-copy
    launch on TPU, compiled ``dynamic_slice``/scatter programs off-TPU);
    everything else passes through untouched.
    """
    if old == new:
        return state
    if delta is None:
        delta = compile_migration_delta(old, new)
    if delta.identity:
        return state
    from repro.kernels.relayout import ops as relayout_ops

    keys = [k for k, v in state.items()
            if getattr(v, "ndim", 0) == 1 and v.shape[0] == delta.old_len]
    moved = relayout_ops.relayout(
        [state[k] for k in keys], delta, interpret=interpret)
    return dict(state, **dict(zip(keys, moved)))


# ------------------------------------------------------- sharded transitions
def sharded_transition_summary(old: ShardedPlan, new: ShardedPlan):
    """Segment-level view of a SHARDED plan transition: O(segments).

    Returns ``(moved_elements, touched_jobs)``.  Segment identity is the
    job-qualified key; a segment *moved* iff its ``(shard_id, offset)``
    home changed -- a shard joining or leaving the fleet does not "move"
    the segments that stayed put on their own Aggregator.  ``touched_jobs``
    diffs each job's per-shard layout fingerprint (keyed by the stable
    ``agg_id``), exactly the jobs whose compiled programs a migration
    invalidates; this is the oracle :func:`migrate_sharded_state`'s
    executed byte count is asserted against.
    """
    key = ("ssummary", old, new)
    cached = _PAIR_CACHE.get(key)
    if cached is not None:
        return cached
    old_by = old.by_skey
    moved = 0
    for sid, sp in zip(new.shard_ids, new.shards):
        for seg in sp.segments:
            prev = old_by.get(seg.skey)
            if prev is None:
                continue
            psid, pseg = prev
            if pseg.size != seg.size:
                raise ValueError(
                    f"segment {seg.skey} changed size "
                    f"{pseg.size} -> {seg.size}")
            if psid != sid or pseg.offset != seg.offset:
                moved += seg.size

    def sigs(plan: ShardedPlan) -> Dict[str, Dict[str, Tuple]]:
        out: Dict[str, Dict[str, Tuple]] = {}
        for sid, sp in zip(plan.shard_ids, plan.shards):
            for j, sig in _job_layout_sigs(sp).items():
                out.setdefault(j, {})[sid] = sig
        return out

    old_sigs, new_sigs = sigs(old), sigs(new)
    touched = tuple(sorted(
        j for j in set(old_sigs) | set(new_sigs)
        if old_sigs.get(j) != new_sigs.get(j)))
    summary = (moved, touched)
    _PAIR_CACHE.put(key, summary)
    return summary


def migrate_sharded_state(
    states: Dict[str, Dict[str, Any]],
    old: ShardedPlan,
    new: ShardedPlan,
    *,
    needs_ef: bool = False,
    interpret: Optional[bool] = None,
    fault_injector=None,
) -> Tuple[Dict[str, Dict[str, Any]], int, Tuple[str, ...]]:
    """Re-lay per-shard states onto a new ShardedPlan.

    ``states`` maps ``agg_id`` -> per-shard state dict whose 1-D leaves
    (flat/mu/nu[/ef]) have the shard's ``total_len``.  The transition
    decomposes into:

      * one :class:`MigrationDelta` per SURVIVING shard (same ``agg_id``
        in both plans) -- within-shard relocations, vacated-lane zeroing,
        and resizes execute on the ``repro.kernels.relayout`` run-copy
        path, O(that shard's moved bytes);
      * fresh zero spaces for shards that joined the fleet;
      * one contiguous slice copy per segment that changed Aggregator
        (the actual cross-shard traffic a split/merge ships).

    Returns ``(new_states, moved_elements, touched_jobs)``; the element
    count and touched set equal :func:`sharded_transition_summary`'s
    exactly -- the property the elastic-scaling benchmark asserts.

    Abort safety: the input ``states`` are never mutated -- each shard's
    relayout produces a NEW dict and arrivals scatter functionally -- so
    a fault at the boundary or at any mid-migration fail point leaves
    the caller's old states fully intact; nothing commits until the
    caller assigns the returned ``new_states``.
    """
    desc = f"sharded:{old.n_shards}->{new.n_shards}"
    if fault_injector is not None:
        # Chaos hook: a fault here models a migration dying BEFORE any
        # state moved (states untouched, caller's replan aborts).
        fault_injector.on_migration(desc)
    moved = 0
    touched: set = set()
    new_states: Dict[str, Dict[str, Any]] = {}
    old_ids = set(old.shard_ids)
    old_by = old.by_skey
    for sid, sp in zip(new.shard_ids, new.shards):
        prev = states.get(sid) if sid in old_ids else None
        if prev is not None and old.shard_of(sid) == sp:
            # Unchanged shard space: nothing moves, and compiling its
            # delta would still cost O(shard lanes) on the host.
            st = dict(prev)
        elif prev is not None:
            old_sp = old.shard_of(sid)
            delta = compile_migration_delta(old_sp, sp)
            st = migrate_flat_state_delta(
                prev, old_sp, sp, delta=delta, interpret=interpret)
            if st is prev:
                st = dict(prev)
            moved += delta.moved_elements
            touched.update(delta.touched_jobs)
        else:
            flat = jnp.zeros((sp.total_len,), jnp.float32)
            st = {"flat": flat, "mu": jnp.zeros_like(flat),
                  "nu": jnp.zeros_like(flat)}
            if needs_ef or any("ef" in s for s in states.values()):
                st["ef"] = jnp.zeros_like(flat)
        new_states[sid] = st
        # Cross-shard arrivals: segments whose old home was a DIFFERENT
        # Aggregator.  Their destination lanes are zero after the
        # within-shard pass (they are uncovered in the per-shard pair);
        # gather all of them and finish the move with ONE scatter per
        # leaf -- per-segment functional updates would copy the whole
        # destination buffer once per (segment, leaf).
        arrivals = []
        for seg in sp.segments:
            prev_home = old_by.get(seg.skey)
            if prev_home is None:
                continue  # new job's segment: stays zero until seeded
            psid, pseg = prev_home
            if psid == sid:
                continue  # same Aggregator: the per-shard delta covered it
            arrivals.append((seg, psid, pseg))
            moved += seg.size
            touched.add(seg.job_id)
        if arrivals:
            # Segments are in offset order within a shard plan, so the
            # concatenated destination index is sorted and unique.
            idx = jnp.asarray(np.concatenate([
                np.arange(seg.offset, seg.offset + seg.size, dtype=np.int64)
                for seg, _, _ in arrivals]))
            for k, buf in st.items():
                if getattr(buf, "ndim", 0) != 1:
                    continue
                pieces = [
                    jax.lax.slice(states[psid][k], (pseg.offset,),
                                  (pseg.offset + pseg.size,))
                    for _, psid, pseg in arrivals
                    if getattr(states[psid].get(k), "ndim", 0) == 1]
                if len(pieces) != len(arrivals):
                    continue  # leaf absent on some source shard: stay zero
                vals = (jnp.concatenate(pieces) if len(pieces) > 1
                        else pieces[0])
                st[k] = buf.at[idx].set(
                    vals, unique_indices=True, indices_are_sorted=True)
        if fault_injector is not None:
            # Mid-migration fail point: this shard is fully relaid
            # (delta + cross-shard arrivals); a fault here probes that
            # a partially-built new_states is simply discarded.
            fault_injector.on_migration_progress(len(new_states), desc)
    # Jobs that only exist on REMOVED shards (or left the fleet) are
    # touched too: diff the per-shard fingerprints like the summary does.
    _, sum_touched = sharded_transition_summary(old, new)
    touched.update(sum_touched)
    return new_states, moved, tuple(sorted(touched))


def migration_bytes(old: FlatPlan, new: FlatPlan, bytes_per_element: int = 12) -> int:
    """Bytes that actually cross shards (master copy + both Adam moments)."""
    return plan_migration_bytes(old, new, bytes_per_element)


def reshard_tree(tree, shardings):
    """Move a pytree onto new shardings (elastic re-mesh / migration)."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), tree, shardings
    )
