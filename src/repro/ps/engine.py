"""Service-tick execution engine: batched multi-job aggregation with
bounded staleness.

The paper's aggregation is a *shared service*: many jobs' bursty pushes
land on the same Aggregator CPUs and should be executed together, not as
one step-function per job.  PR 1 compiled the packing into one shared
FlatPlan and PR 2 made each job's step O(job bytes); this module adds the
service-side loop that actually batches them:

  submit_push  a job pushes its packed gradient into its bounded per-job
               queue and gets a :class:`PushFuture`; nothing is applied yet
  tick         the engine drains the HEAD push of every pending job and
               applies all of them in ONE batched pass over the shared
               flat space -- a single Pallas launch on TPU
               (``kernels.agg_adam.aggregate_adam_multijob``: concatenated
               owned-block index table + per-block job-slot map), a
               fused-scatter jnp pass in interpret mode
  pull         a job reads its own lanes; with ``max_staleness = s`` a job
               may run ``s`` steps ahead of the service before its pull
               blocks on (forces) the tick -- Dynamic-SSP-style bounded
               staleness; ``s = 0`` is BSP

Block exclusivity (every ``block_align`` block of the flat space belongs
to at most one job, the PR-2 invariant) is what makes the batched pass a
pure execution-order change: its result is bit-exact with applying the
same pushes as K sequential per-job block steps.  Below the measured
batching crossover (``min_batch_jobs``; BENCH_service_tick.json showed
the one-launch concatenation LOSING at 2 pending jobs) a tick dispatches
the same pushes as per-job block passes instead -- identical result,
cheaper program.

Replans are STALL-FREE: the runtime compiles a
:class:`repro.ps.elastic.MigrationDelta` for the plan pair and quiesces
ONLY the touched jobs (those whose segment layout changes) -- their
queued pushes apply against the OLD plan before the state migrates.
Untouched jobs keep their queues, their compiled programs, and their
tick cadence straight through the transition; a per-push EPOCH FENCE
(every queued push is tagged with the plan epoch it was packed under,
and untouched jobs' surviving pushes are re-tagged at each replan)
guarantees no push is ever applied across mismatched layouts, extending
the PR-3 invariant: the engine'd runtime stays bit-exact with the
unbatched one -- eager execution matches it bit-for-bit at any sizes,
and the jitted batched apply matches jitted sequential block updates
bit-for-bit at SIMD-even block sizes (fully-jitted END-TO-END runs
additionally see XLA:CPU's ~1-ulp cross-program fusion rounding, the
same caveat PR 2 documents for jitted block-vs-masked; see
tests/test_engine.py).

Usage::

    rt = ServiceRuntime(svc)
    eng = rt.attach_engine(max_staleness=1)
    rt.add_job("a", params_a, loss_a); rt.add_job("b", params_b, loss_b)
    for batch_a, batch_b in data:
        eng.step("a", batch_a)   # pull -> grad -> submit_push
        eng.step("b", batch_b)
        # pushes apply together at the next tick (forced by staleness,
        # queue pressure, an explicit eng.tick(), or fut.result())
    eng.drain()

PR 5 adds the SHARDED sibling: :class:`ShardedTickEngine` runs one
independent tick loop per Aggregator shard space (``tick_shard``), with a
job's push split into one piece per hosting shard -- see the class
docstring and docs/architecture.md.

PR 6 makes the hot path a SINGLE LAUNCH: the row scatters that used to
follow every batched apply are fused into the kernel itself
(``kernels.agg_adam.aggregate_adam_multijob_fused`` writes the updated
flat/mu/nu blocks in place via ``input_output_aliases``), and the sharded
engine gains :meth:`ShardedTickEngine.tick_fleet` -- every lane with
pending pieces ticks in ONE fused launch over the lanes' concatenated
states (``fleet_tick="fused"``, the default; ``"per_shard"`` keeps the
PR-5 loop as a bit-parity oracle).  ``TickStats.n_launches`` counts what
this buys.

PR 7 makes a failed apply SURVIVABLE.  The jitted appliers donate the
state buffers, so an exec failure may have deleted them mid-update;
earlier engines poisoned the WHOLE engine permanently.  Now every lane
(each shard space; the flat engine is one unnamed lane) keeps a
last-good SNAPSHOT of its state, refreshed every ``snapshot_interval``
applying ticks with the copy taken *before* the donated apply, plus a
replay log of the pushes applied since.  The anchor also refreshes
sooner when the log would come to hold more bytes than the snapshot,
so the log never outgrows it.  On an exec failure the lane
restores the snapshot, re-queues the failed heads AND the logged
pushes (in order, futures kept but never re-resolved), and replays them
on subsequent ticks -- at ``max_staleness=0`` the recovered trajectory
is bit-exact with a fault-free run, because sharded pieces carry their
submit-time step counts and flat counts recompute from the restored
state.  A lane that keeps failing (``max_apply_retries`` consecutive
rollbacks) is QUARANTINED: its state stays at the last-good snapshot,
``tick_shard`` skips it, ``tick_fleet`` drops it from the fused launch,
and blocked work (``drain``/``pull``/``result``) raises
:class:`repro.ps.faults.EngineQuarantinedError` naming the shard, tick,
jobs, and original exception.  A fused fleet launch cannot attribute
which lane failed, so its failure handler rolls back EVERY participating
lane and replays each with its own per-shard launch -- the faulty lane
fails (and retries or quarantines) in isolation while the rest re-apply
(``TickStats.n_fleet_fallbacks``).  ``ShardedServiceRuntime.
recover_shard`` turns a quarantined lane back into a healthy fleet via
the PR-4/5 migration machinery; a seedable
:class:`repro.ps.faults.FaultInjector` drives all of it
deterministically in tests and benchmarks.

PR 8 makes the wire path CHEAP.  Compressed-push jobs
(``push_compression="bf16"|"int8"``), which both engines previously
rejected, now flow through batched and fused fleet ticks: the shared
error-feedback buffer (``state["ef"]``, one per shard space under the
sharded engine -- a compressed job gets one EF round per hosting
shard's piece) lives next to flat/mu/nu in the engine's donated state,
so it rides snapshots, rollback replay, relayout migrations, and
checkpoints like any other state leaf, and appliers whose jobs are all
uncompressed compile the exact pre-PR-8 program (bit-exact default
path).  The transform itself is ONE shared function
(:func:`repro.ps.compression.ef_transform`), so the engine'd compressed
trajectory matches ``runtime.step()``'s compressed path bit-for-bit in
eager mode.  Pulls gain a versioned PARAMETER-DIFF protocol: every
applying tick stamps the applied jobs' owned blocks with a monotone
version (host-side numpy, one entry per ``block_align`` block;
rollbacks re-stamp so rewound blocks read as changed), and
``pull(job_id, since_version=<PullVersion>)`` ships only the changed
blocks as a :class:`PullDiff` -- full-pull fallback on the first call,
a plan-epoch change, or a mismatched vector.  ``TickStats`` carries the
transfer-byte accounting (``push_bytes_raw/wire``,
``pull_bytes_full/wire``, ``n_full_pulls``/``n_diff_pulls``), surfaced
by ``debug_stats()`` and measured in BENCH_wire.json
(benchmarks/wire_path.py).
"""

from __future__ import annotations

import time

from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.ps.compression import ef_transform, wire_bytes
from repro.ps.faults import (
    HEALTHY,
    QUARANTINED,
    EngineQuarantinedError,
    LeaseExpiredError,
)
from repro.ps.plan import FlatPlan
from repro.ps.runtime import (
    _gather_owned,
    _gather_packed,
    _layout_rows,
    _pack_slots,
    _scatter_owned,
    _split_pieces,
    _unpack_slots,
)
from repro.ps.spans import NO_SPAN, span

__all__ = ["PullDiff", "PullVersion", "PushFuture", "ServiceTickEngine",
           "ShardedTickEngine", "TickStats"]


class PushFuture:
    """Handle for one submitted push; resolves when a tick applies it.

    Under the sharded engine one push fans out into one PIECE per hosting
    shard (``parts``); the future resolves when the LAST piece applies.
    A push dropped without applying (a job removed with a queue that
    could not drain, or a piece lost with a dead shard) is CANCELLED:
    ``result()`` raises instead of forcing ticks forever.  A push whose
    applied effect was later DISCARDED by shard-loss recovery (it landed
    inside the lost lane's rollback window) keeps its resolved step but
    reports ``rolled_back`` -- re-push to land the update again.
    """

    __slots__ = ("job_id", "_engine", "_done", "_step", "_remaining",
                 "_cancelled", "_cancel_exc", "_rolled_back")

    def __init__(self, job_id: str, engine, parts: int = 1):
        self.job_id = job_id
        self._engine = engine
        self._done = False
        self._step = None
        self._remaining = int(parts)
        self._cancelled = None  # str reason once cancelled
        self._cancel_exc = None  # contextual exception behind the cancel
        self._rolled_back = False  # applied, then lost with a dead shard

    def done(self) -> bool:
        return self._done

    def cancelled(self) -> bool:
        return self._cancelled is not None

    @property
    def rolled_back(self) -> bool:
        """True if this push HAD applied but its effect was discarded by
        ``recover_shard`` (it was inside the lost shard's rollback
        window: at most ``snapshot_interval`` ticks deep, and at most a
        replay log as large as the shard's snapshot)."""
        return self._rolled_back

    def result(self, timeout: Optional[float] = None) -> int:
        """Block (force service ticks) until applied; returns the job's
        1-based step count as of this push.

        ``timeout`` (seconds, wall clock): raise at the deadline if the
        push has not applied in time.  The error is CONTEXTUAL when the
        engine knows why the push is stuck: a push whose lane was
        quarantined mid-wait raises that lane's
        :class:`~repro.ps.faults.EngineQuarantinedError`, and a push
        whose job was lease-expired raises the stored
        :class:`~repro.ps.faults.LeaseExpiredError`; only an
        unexplained stall (e.g. a piece dropped in transit) raises a
        bare ``TimeoutError``.  With no timeout the call never spins
        forever either: if ticking makes no progress and the push cannot
        resolve, it raises the blocking lane's quarantine error (or a
        ``RuntimeError`` when the piece is simply gone).  A cancelled
        push without a stored exception raises ``RuntimeError``
        immediately.  Note the flat engine has a single lane, so its
        quarantine raises out of ``tick()`` itself regardless of
        ``timeout``."""
        deadline = (None if timeout is None
                    else time.monotonic() + float(timeout))
        while not self._done:
            if self._cancelled is not None:
                if self._cancel_exc is not None:
                    raise self._cancel_exc
                raise RuntimeError(
                    f"push for job {self.job_id!r} will never apply: "
                    f"{self._cancelled}")
            if deadline is not None and time.monotonic() >= deadline:
                stall = self._engine._stall_error(self.job_id)
                if isinstance(stall, EngineQuarantinedError):
                    raise stall
                raise TimeoutError(
                    f"push for job {self.job_id!r} still unapplied after "
                    f"{timeout} s (hosting lane quarantined, or a piece "
                    f"was dropped in transit)")
            if self._engine.tick() == 0 and not self._done:
                # No progress and still pending: either a rollback just
                # re-queued work (pieces remain on healthy lanes -- keep
                # ticking) or the push is stuck for good.
                stall = self._engine._stall_error(self.job_id)
                if stall is None:
                    continue
                if deadline is None:
                    raise stall
                time.sleep(0.001)  # wait out the timeout, don't hot-spin
        return self._step

    def _resolve(self, step: int) -> bool:
        """One piece applied; True if this transition completed the push
        (re-applying a rolled-back piece of an already-done future is a
        no-op, so replay never double-commits)."""
        if self._done:
            return False
        self._remaining -= 1
        if self._remaining <= 0:
            self._done = True
            self._step = int(step)
            return True
        return False

    def _unresolve(self) -> None:
        """A rollback un-applied one piece.  A still-pending future gets
        the part back (it must not complete until the replay re-applies
        it); a DONE future stays done -- its result was already
        observable, and the deterministic replay re-lands the identical
        update."""
        if not self._done:
            self._remaining += 1

    def _cancel(self, reason: str,
                exc: Optional[BaseException] = None) -> None:
        """Cancel with an optional contextual exception for ``result()``
        to re-raise (e.g. :class:`LeaseExpiredError`).  The FIRST
        cancellation wins -- later ones must not overwrite its context."""
        if not self._done and self._cancelled is None:
            self._cancelled = reason
            self._cancel_exc = exc


@dataclass
class TickStats:
    """Engine counters: how batched the service actually ran."""

    n_ticks: int = 0  # batched passes executed
    n_applied: int = 0  # pushes applied across all ticks
    n_launches: int = 0  # kernel/applier launches (the single-launch gauge)
    n_forced_staleness: int = 0  # ticks forced by a pull at the bound
    n_forced_capacity: int = 0  # ticks forced by a full push queue
    n_forced_replan: int = 0  # ticks forced to drain TOUCHED jobs on a replan
    n_per_job_dispatch: int = 0  # ticks dispatched as per-job passes (< K_min)
    n_replans: int = 0  # plan changes the engine rode through
    n_retagged: int = 0  # untouched pushes carried across a replan (fence)
    n_snapshots: int = 0  # last-good state copies taken (rollback anchors)
    # ... of which refreshed because the replay log would have outgrown
    # the state's bytes, before snapshot_interval ticks had passed
    n_snapshots_by_bytes: int = 0
    n_rollbacks: int = 0  # failed applies recovered by snapshot restore
    n_replayed: int = 0  # applied pushes re-queued for replay by rollbacks
    n_quarantines: int = 0  # lanes that exhausted retries and stopped
    n_fleet_fallbacks: int = 0  # fused fleet failures replayed per-shard
    n_lease_expirations: int = 0  # jobs reclaimed by expire_leases (PR 9)
    # Engine program-cache misses: applier signatures compiled, and new
    # jitted pack and pull programs (their first call compiles).
    n_applier_compiles: int = 0
    # Wire accounting (PR 8).  Push bytes are counted at submit time with
    # the job's ``push_compression`` wire-size model (fp32 4 B/elem, bf16
    # 2, int8 1 + one fp32 scale per block); pull bytes count the payload
    # a pull shipped vs. what a full pull of the same slice costs.
    push_bytes_raw: int = 0  # fp32 bytes of every submitted push/piece
    push_bytes_wire: int = 0  # same pushes after each job's compression
    n_full_pulls: int = 0  # whole-slice pulls (incl. diff-pull fallbacks)
    n_diff_pulls: int = 0  # versioned pulls that shipped changed blocks only
    pull_bytes_wire: int = 0  # pull payload bytes actually shipped
    pull_bytes_full: int = 0  # what the same pulls cost as full pulls

    @property
    def mean_batch(self) -> float:
        """Mean jobs applied per tick (running counters, O(1) memory --
        the engine may tick for the service's whole lifetime)."""
        if not self.n_ticks:
            return 0.0
        return self.n_applied / self.n_ticks


@dataclass(frozen=True)
class PullVersion:
    """Opaque version vector one versioned pull returns: the plan epoch
    it was taken under plus one monotone version per owned block of the
    job (packed layout order, shard order for sharded jobs).  Hand it
    back as ``since_version`` to receive only the blocks that changed."""

    epoch: int
    versions: np.ndarray  # int64, one per owned block, layout order


@dataclass(frozen=True)
class PullDiff:
    """Result of ``pull(job_id, since_version=...)`` -- the SNIPPETS.md
    parameter-diff shape: only the owned blocks whose version moved past
    the client's vector, plus the new vector to hand back next time.

    ``full=True`` is the fallback (first pull, plan-epoch mismatch, or a
    stale/mismatched vector): ``data`` is the whole packed job vector.
    Otherwise ``data`` is ``(k, block)`` changed rows and ``block_ids``
    their job-local packed block indices; :meth:`apply` patches them onto
    the client's previous packed vector.  ``bytes_wire`` is what this
    pull shipped under the fp32 wire model, ``bytes_full`` what a full
    pull would have."""

    job_id: str
    version: PullVersion
    full: bool
    block: int
    block_ids: np.ndarray  # job-local packed block rows; empty when full
    data: Any  # (packed_len,) when full, else (k, block) changed rows
    bytes_wire: int
    bytes_full: int

    def apply(self, prev_packed):
        """Patch this diff onto the client's previous packed vector and
        return the up-to-date packed vector."""
        if self.full:
            return self.data
        if self.block_ids.size == 0:
            return prev_packed
        rows = prev_packed.reshape(-1, self.block)
        return rows.at[jnp.asarray(self.block_ids)].set(
            self.data, unique_indices=True,
            indices_are_sorted=True).reshape(-1)


@jax.jit
def state_copy(state):
    """Deep copy of one state dict in ONE program, device buffers COPIED
    (not aliased): a snapshot must survive the donated apply that may
    consume -- or a failed apply that may delete -- the live buffers,
    and a restored copy must leave the pristine snapshot available for
    the NEXT rollback (replay re-donates the restored buffers).  Used for
    both snapshot and restore; the host spans tell the two apart."""
    return jax.tree_util.tree_map(jnp.copy, state)


def _anchor_due(anchor, ticks_since: int, interval: int, log,
                incoming: int, state) -> Optional[str]:
    """Why a lane's rollback anchor must be refreshed before this tick's
    apply, or None.  ``"ticks"``: there is no anchor yet, or ``interval``
    applying ticks have passed since it.  ``"bytes"``: the replay log
    plus the ``incoming`` bytes of the pieces this tick will log would
    hold more bytes than the state, which is what the snapshot holds --
    so a log never outgrows its snapshot.  The log's bytes are summed
    from its entries (the piece is each entry's second field), at most
    ``interval`` ticks of them, so no counter has to follow the log
    through refreshes, rollbacks and job removals."""
    if anchor is None or ticks_since >= interval:
        return "ticks"
    logged = sum(entry[1].nbytes for entry in log)
    held = sum(x.nbytes for x in jax.tree_util.tree_leaves(state))
    return "bytes" if logged + incoming > held else None


def _compile_span(stats: TickStats, new: bool):
    """``ps.compile`` around the first call of a new jitted pack or pull
    program (``new``), which traces and compiles it; counted in
    ``stats.n_applier_compiles``."""
    if not new:
        return NO_SPAN
    stats.n_applier_compiles += 1
    return span("ps.compile")


class _Applier:
    """One applier program, compiled apart from its execution.

    :meth:`compiled` lowers and compiles the jitted (state-donating)
    program for the given arguments' types -- once per signature -- and
    returns what to call; the ticks call it OUTSIDE their failure
    handling, so a lowering or compile error (a kernel the chip cannot
    take) propagates to the caller instead of being rolled back, retried
    and quarantined as if an apply had failed.  Eager (``jit=False``)
    programs have no compile step and run as they are.  Each compile
    counts in ``n_applier_compiles`` of every given :class:`TickStats`."""

    __slots__ = ("_fn", "_jit", "_exes", "_stats")

    def __init__(self, fn: Callable, jit: bool, stats=()):
        self._fn = jax.jit(fn, donate_argnums=(0,)) if jit else fn
        self._jit = jit
        self._exes: Dict[Any, Callable] = {}
        self._stats = stats

    def compiled(self, *args) -> Callable:
        if not self._jit:
            return self._fn
        leaves, tree = jax.tree_util.tree_flatten(args)
        sig = (tree, tuple(jax.typeof(x) for x in leaves))
        exe = self._exes.get(sig)
        if exe is None:
            with span("ps.compile"):
                exe = self._exes[sig] = self._fn.lower(*args).compile()
            for stats in self._stats:
                stats.n_applier_compiles += 1
        return exe


# ------------------------------------------------ shared applier building
def _flat_job_hp(info) -> Tuple[float, float, float, float]:
    """(lr, b1, b2, eps) of one flat-runtime job (Adam knobs ride in
    ``step_opts`` on the unsharded runtime)."""
    so = info["step_opts"]
    return (float(info["lr"]), float(so.get("b1", 0.9)),
            float(so.get("b2", 0.999)), float(so.get("eps", 1e-8)))


def _sharded_job_hp(info) -> Tuple[float, float, float, float]:
    """(lr, b1, b2, eps) of one sharded-runtime job (first-class fields)."""
    return (float(info["lr"]), float(info["b1"]), float(info["b2"]),
            float(info["eps"]))


def _fused_tables(layouts, infos, hp_of, base_blocks=None):
    """Bake the trace-time tables one fused multi-job apply needs: the
    concatenated owned-block index table, per-entry packed block counts,
    and per-entry ``(lr, b1, b2, eps)`` columns.

    ONE builder for every applier in this module -- the flat engine, the
    per-shard lane applier, and the fleet tick all route through it.  The
    fleet passes ``base_blocks`` (each entry's shard base offset, in
    blocks, into the concatenated fleet view) so a shard-local block
    table rebases to global block ids; single-space appliers leave it 0.
    """
    if base_blocks is None:
        base_blocks = (0,) * len(layouts)
    block_idx = np.concatenate(
        [l.blocks.astype(np.int32) + np.int32(b)
         for l, b in zip(layouts, base_blocks)])
    job_sizes = tuple(int(l.blocks.size) for l in layouts)
    lr, b1, b2, eps = zip(*(hp_of(i) for i in infos))
    return block_idx, job_sizes, (lr, b1, b2, eps)


def _fused_state_update(state, gs, counts, *, block, block_idx, job_sizes,
                        hps, interpret):
    """ONE fused launch over one state dict: aggregation + Adam + the
    in-place block writes for flat/mu/nu together (PR 6) -- the three
    post-apply row scatters earlier engines ran are gone.  ``gs`` is the
    per-entry packed gradient sequence (concatenated once inside the op:
    this exact program shape is what the bit-exactness tests pin down);
    ``counts`` must already be usable as traced int32 scalars."""
    from repro.kernels.agg_adam import ops as agg_ops

    lr, b1, b2, eps = hps
    new_p, new_mu, new_nu = agg_ops.multi_job_adam_update_fused(
        state["flat"], gs, state["mu"], state["nu"], counts,
        block_idx=block_idx, job_sizes=job_sizes, block=block,
        lr=lr, b1=b1, b2=b2, eps=eps, wd=0.0, interpret=interpret)
    return dict(state, flat=new_p, mu=new_mu, nu=new_nu)


class ServiceTickEngine:
    """Batched executor for one :class:`ServiceRuntime`'s shared state.

    Created via :meth:`ServiceRuntime.attach_engine`.  The engine owns the
    per-job push queues and the compiled batched appliers; the runtime
    keeps owning plan + state (and migrates them on replans, draining this
    engine first).
    """

    MAX_APPLIERS = 32  # compiled programs per plan (one per job subset)

    def __init__(self, runtime, *, max_staleness: int = 1,
                 queue_capacity: Optional[int] = None, jit: bool = True,
                 interpret: Optional[bool] = None, min_batch_jobs: int = 3,
                 snapshot_interval: int = 8, max_apply_retries: int = 1,
                 fault_injector=None, retry_policy=None,
                 lease_interval: Optional[float] = None, clock=None):
        if max_staleness < 0:
            raise ValueError(f"max_staleness must be >= 0, got {max_staleness}")
        if snapshot_interval < 0:
            raise ValueError(
                f"snapshot_interval must be >= 0 (0 disables rollback "
                f"recovery), got {snapshot_interval}")
        if lease_interval is not None and lease_interval <= 0:
            raise ValueError(
                f"lease_interval must be > 0 (None disables leases), "
                f"got {lease_interval}")
        self.runtime = runtime
        self.max_staleness = int(max_staleness)
        self.queue_capacity = (self.max_staleness + 1 if queue_capacity is None
                               else int(queue_capacity))
        if self.queue_capacity < 1:
            raise ValueError("queue_capacity must be >= 1")
        # Batching crossover: with fewer than this many pending jobs a
        # tick dispatches per-job block passes -- the one-launch
        # concatenation only wins once enough jobs share the pass
        # (BENCH_service_tick.json measured batched LOSING at 2 jobs,
        # 0.71x, and winning from 4 up).  Result is identical either
        # way (disjoint blocks commute); this is a pure cost knob.
        self.min_batch_jobs = int(min_batch_jobs)
        # Fault tolerance: a last-good state copy every this many
        # applying ticks bounds both the copy overhead (amortized) and
        # the rollback window a failure can lose; 0 disables snapshots
        # (a jitted exec failure then quarantines immediately, since the
        # donated buffers are unrecoverable).
        self.snapshot_interval = int(snapshot_interval)
        # Apply-retry schedule: ``retry_policy`` (repro.ps.faults
        # .RetryPolicy) wins over the legacy ``max_apply_retries`` count;
        # the attribute is kept in sync for introspection.
        if retry_policy is None:
            from repro.ps.faults import RetryPolicy

            retry_policy = RetryPolicy(max_retries=int(max_apply_retries))
        self.retry_policy = retry_policy
        self.max_apply_retries = int(retry_policy.max_retries)
        self.fault_injector = fault_injector
        # Job leases: pushes/pulls renew; ``expire_leases()`` reclaims
        # jobs whose trainers went silent.  ``clock`` is injectable so
        # chaos tests drive expiry deterministically.
        self.lease_interval = (None if lease_interval is None
                               else float(lease_interval))
        self._clock = clock if clock is not None else time.monotonic
        self._leases: Dict[str, float] = {}  # job -> expiry deadline
        self.stats = TickStats()
        self.health = HEALTHY
        self.quarantine_error: Optional[EngineQuarantinedError] = None
        self._snapshot = None  # (state copy, counts-mirror copy)
        self._snapshot_log: List[Tuple] = []  # (job, packed, fut) applied
        self._ticks_since_snapshot = 0
        self._failures = 0  # consecutive failed applies (reset on success)
        self._jit = jit
        self._interpret = interpret  # None = auto (jnp path off-TPU)
        self._epoch = 0  # bumped per plan change; fences queued pushes
        self._queues: Dict[str, deque] = {}
        # Diff-pull version tracking (PR 8): one monotone version per
        # ``block_align`` block of the flat space, stamped host-side on
        # every applying tick.  Reset on plan changes -- the version
        # vector carries the epoch, so stale clients fall back to a full
        # pull instead of misreading restarted versions.
        self._block_versions: Optional[np.ndarray] = None
        self._version_clock = 0
        # Python-side mirror of state["counts"]: futures resolve from it
        # without a device round-trip per tick.
        self._counts: Dict[str, int] = {}
        # Compiled caches, invalidated on every replan.
        self._appliers: Dict[Tuple[str, ...], Callable] = {}
        self._pull_fns: Dict[str, Callable] = {}
        self._grad_fns: Dict[str, Callable] = {}
        self._pack_fns: Dict[str, Callable] = {}
        # Read tier (PR 10): a ReplicaSet registers itself here and gets
        # offered a publishable snapshot every applying tick.
        self._replica_hub = None

    # ------------------------------------------------------------- plumbing
    @property
    def plan(self) -> Optional[FlatPlan]:
        return self.runtime.plan

    def _queue(self, job_id: str) -> deque:
        info = self.runtime._jobs.get(job_id)
        if info is None:
            raise ValueError(f"unknown job {job_id!r}: not registered with "
                             f"the runtime (have {sorted(self.runtime._jobs)})")
        if (info["step_opts"].get("push_compression")
                and "ef" not in self.runtime.state):
            # A job turned compressed after the state was built (e.g. a
            # restore from a pre-compression checkpoint): widen the state
            # with a zero error-feedback buffer -- exactly what the
            # runtime's replan path does when a compressed job joins.
            self.runtime.state = dict(
                self.runtime.state,
                ef=jnp.zeros_like(self.runtime.state["flat"]))
        if job_id not in self._counts:
            # One sync at first contact; ticks keep the mirror in step.
            self._counts[job_id] = int(jax.device_get(
                self.runtime.state["counts"][job_id]))
        self._renew_lease(job_id)
        return self._queues.setdefault(job_id, deque())

    def outstanding(self, job_id: str) -> int:
        """Pushes submitted by the job but not yet applied by a tick."""
        q = self._queues.get(job_id)
        return len(q) if q else 0

    # --------------------------------------------------------------- leases
    def _renew_lease(self, job_id: str) -> None:
        if self.lease_interval is not None:
            self._leases[job_id] = self._clock() + self.lease_interval

    def lease_deadline(self, job_id: str) -> Optional[float]:
        """The job's current lease expiry (None: leases off / no contact)."""
        return self._leases.get(job_id)

    def expire_leases(self) -> Tuple[str, ...]:
        """Reclaim every job whose lease has lapsed; returns their ids.

        Every push/pull renews the submitting job's lease, so only a
        trainer that went SILENT for a full ``lease_interval`` expires.
        Reclaim is graceful: queued pieces are cancelled with a
        contextual :class:`~repro.ps.faults.LeaseExpiredError` (held
        futures re-raise it), then the job leaves through
        ``runtime.remove_job`` -- i.e. the transactional replan path --
        so its space frees and the autoscaler sees the load drop.  If
        that replan itself aborts, the lease is re-armed one interval
        out and the reclaim retries at the next ``expire_leases()``."""
        if self.lease_interval is None:
            return ()
        now = self._clock()
        expired = tuple(sorted(
            j for j, deadline in self._leases.items()
            if deadline <= now and j in self.runtime._jobs))
        for job_id in expired:
            err = LeaseExpiredError(job_id, self._leases[job_id], now)
            q = self._queues.get(job_id)
            if q:
                for _, fut, _ in q:
                    if fut is not None:
                        fut._cancel(str(err), exc=err)
                q.clear()
            self._leases.pop(job_id, None)
            self.stats.n_lease_expirations += 1
            try:
                self.runtime.remove_job(job_id)
            except Exception:
                # Reclaim replan failed: re-arm the lease so the next
                # sweep retries instead of leaking the job forever.
                self._leases[job_id] = now + self.lease_interval
                raise
        return expired

    def quiesce_for_replan(self, touched) -> int:
        """Drain ONLY the touched jobs' queues ahead of a migration.

        Their queued pushes apply against the OLD plan (their layout is
        about to change); untouched jobs' queues -- and tick cadence --
        are left alone.  Returns pushes applied."""
        applied = 0
        while True:
            pending = [j for j in touched if self._queues.get(j)]
            if not pending:
                return applied
            self.stats.n_forced_replan += 1
            applied += self.tick(only=pending)

    def _on_plan_change(self, touched=None) -> None:
        """Replan landed: invalidate what the new plan breaks.

        ``touched=None`` (full quiesce: first plan, last exit, or a
        gather-path migration) drops every compiled structure and
        requires every queue empty.  With a delta's touched set, only
        the touched jobs' programs die; untouched jobs keep queues and
        compiled programs -- their layout is bit-identical in the new
        plan -- and their surviving pushes are re-tagged to the new
        epoch (the fence that proves no push crosses layouts)."""
        self._epoch += 1
        self.stats.n_replans += 1
        # A snapshot is a copy of the PRE-migration layout: restoring it
        # after the plan changed would resurrect dead geometry.  Drop it
        # (and its replay log); the rollback window restarts under the
        # new plan at the next applying tick.
        self._snapshot = None
        self._snapshot_log = []
        self._ticks_since_snapshot = 0
        # Block versions index the OLD geometry; the epoch bump already
        # invalidates every held PullVersion, so restart the vector.
        self._block_versions = None
        if self._replica_hub is not None:
            # Read-tier snapshots hold the old geometry too; the epoch
            # fence marks them stale and the next serve resubscribes.
            self._replica_hub.on_replan()
        if touched is None:
            assert not any(self._queues.values()), (
                "replan with queued pushes: runtime must drain the "
                "engine first")
            self._appliers.clear()
            self._pull_fns.clear()
            self._grad_fns.clear()
            self._pack_fns.clear()
            return
        touched = set(touched)
        for j in touched:
            assert not self._queues.get(j), (
                f"replan with queued pushes for TOUCHED job {j!r}: "
                f"quiesce_for_replan must drain it first")
        for j, q in self._queues.items():
            if q:  # untouched by construction: carry across the fence
                self.stats.n_retagged += len(q)
                self._queues[j] = deque(
                    (packed, fut, self._epoch) for packed, fut, _ in q)
        for j in touched:
            self._pull_fns.pop(j, None)
            self._grad_fns.pop(j, None)
            self._pack_fns.pop(j, None)
        self._appliers = {k: v for k, v in self._appliers.items()
                         if not touched.intersection(k)}

    def _forget_job(self, job_id: str) -> None:
        q = self._queues.pop(job_id, None)
        if q:
            # remove_job quiesces first, so a surviving push means the
            # drain was bypassed; cancel so held futures raise cleanly
            # instead of forcing ticks forever on an unknown job.
            for _, fut, _ in q:
                if fut is not None:
                    fut._cancel("job removed from the runtime with this "
                                "push still queued (drain was bypassed)")
        self._snapshot_log = [e for e in self._snapshot_log
                              if e[0] != job_id]
        self._counts.pop(job_id, None)
        self._leases.pop(job_id, None)
        self._pull_fns.pop(job_id, None)
        self._grad_fns.pop(job_id, None)
        self._pack_fns.pop(job_id, None)
        # Appliers embedding the job die with the next plan change, which
        # the runtime triggers right after; drop them eagerly anyway.
        self._appliers = {k: v for k, v in self._appliers.items()
                         if job_id not in k}

    # ------------------------------------------------------------ data path
    def pull(self, job_id: str, since_version=None):
        """The job's current parameters from the shared space.

        Bounded staleness: a job ``max_staleness`` steps ahead of the
        service blocks here -- the pull forces ticks until the job is back
        within the bound (one tick applies one queued push, so one
        suffices unless other jobs' queues run deeper).

        ``since_version`` switches to the VERSIONED DIFF protocol: pass
        the :class:`PullVersion` a previous versioned pull returned (or
        ``0`` to bootstrap) and get a :class:`PullDiff` holding only the
        owned blocks whose version moved, plus the new vector.  A stale
        or cross-epoch vector falls back to a full-payload diff; plain
        (``None``) pulls keep returning the parameter pytree."""
        if self.health == QUARANTINED:
            # No fallback: the state froze at the last-good snapshot and
            # will never advance, so serving it as if live would feed the
            # trainer silently stale parameters.  Read-tier replicas
            # (repro.ps.replica) are the degraded-serving path.
            raise self.quarantine_error
        with span("ps.pull", job=job_id):
            self._queue(job_id)  # validates the job id
            while self.outstanding(job_id) > self.max_staleness:
                self.stats.n_forced_staleness += 1
                self.tick()
            if since_version is not None:
                return self._pull_versioned(job_id, since_version)
            layout = self.plan.job_layout(job_id)
            self.stats.n_full_pulls += 1
            self.stats.pull_bytes_wire += 4 * layout.packed_len
            self.stats.pull_bytes_full += 4 * layout.packed_len
            fn = self._pull_fns.get(job_id)
            new = fn is None and self._jit
            if fn is None:
                abstract = self.runtime._jobs[job_id]["abstract"]
                rows = jnp.asarray(layout.blocks)

                def pull_gather(flat, _layout=layout, _rows=rows,
                                _abstract=abstract):
                    packed = (flat if _layout.covers_all else flat.reshape(
                        -1, _layout.block)[_rows].reshape(-1))
                    return _unpack_slots(_layout, packed, _abstract)

                fn = jax.jit(pull_gather) if self._jit else pull_gather
                self._pull_fns[job_id] = fn
            with _compile_span(self.stats, new):
                return fn(self.runtime.state["flat"])

    # ----------------------------------------------------- versioned pulls
    def _versions_array(self) -> np.ndarray:
        plan = self.plan
        nb = plan.total_len // plan.block_align
        if self._block_versions is None or self._block_versions.size != nb:
            self._block_versions = np.zeros(nb, np.int64)
        return self._block_versions

    def _stamp_blocks(self, jobs) -> None:
        """Advance the version clock and stamp every given job's owned
        blocks -- called once per applying tick (and on rollback, so a
        rewound block can never look unchanged to a diff client)."""
        if self.plan is None or not jobs:
            return
        versions = self._versions_array()
        self._version_clock += 1
        for j in jobs:
            versions[np.asarray(self.plan.job_layout(j).blocks)] = \
                self._version_clock

    def _pull_versioned(self, job_id: str, since) -> PullDiff:
        plan = self.plan
        layout = plan.job_layout(job_id)
        blocks = np.asarray(layout.blocks)
        vers = self._versions_array()[blocks].copy()
        version = PullVersion(epoch=self._epoch, versions=vers)
        bytes_full = 4 * layout.packed_len
        flat = self.runtime.state["flat"]
        full = (not isinstance(since, PullVersion)
                or since.epoch != self._epoch
                or since.versions.size != vers.size)
        if full:
            data = _gather_owned(layout, flat)
            diff = PullDiff(
                job_id=job_id, version=version, full=True,
                block=layout.block, block_ids=np.empty(0, np.int64),
                data=data, bytes_wire=bytes_full, bytes_full=bytes_full)
            self.stats.n_full_pulls += 1
        else:
            sel = np.nonzero(vers > since.versions)[0]
            if sel.size:
                data = flat.reshape(-1, layout.block)[
                    jnp.asarray(blocks[sel])]
            else:
                data = jnp.zeros((0, layout.block), flat.dtype)
            diff = PullDiff(
                job_id=job_id, version=version, full=False,
                block=layout.block, block_ids=sel.astype(np.int64),
                data=data, bytes_wire=4 * int(sel.size) * layout.block,
                bytes_full=bytes_full)
            self.stats.n_diff_pulls += 1
        self.stats.pull_bytes_wire += diff.bytes_wire
        self.stats.pull_bytes_full += bytes_full
        return diff

    def submit_push(self, job_id: str, grads) -> PushFuture:
        """Queue a job's gradient pytree for the next tick; returns a
        future.  A full queue exerts backpressure: the submit first forces
        ticks until a slot frees up."""
        with span("ps.push", job=job_id) as sp:
            q = self._queue(job_id)
            while len(q) >= self.queue_capacity:
                self.stats.n_forced_capacity += 1
                self.tick()
            # The step count this push applies with, barring faults.
            sp.set_metadata(step=self._counts[job_id] + len(q) + 1)
            fn = self._pack_fns.get(job_id)
            new = fn is None and self._jit
            if fn is None:
                layout = self.plan.job_layout(job_id)

                def push_pack(grads, _layout=layout):
                    return _pack_slots(_layout, grads)

                fn = jax.jit(push_pack) if self._jit else push_pack
                self._pack_fns[job_id] = fn
            with _compile_span(self.stats, new):
                packed = fn(grads)
            return self.submit_packed(job_id, packed)

    def submit_packed(self, job_id: str, packed) -> PushFuture:
        """Queue an ALREADY-PACKED job-local gradient vector (the layout's
        packed domain, e.g. from a custom jitted grad program) for the
        next tick; same bounded queue and backpressure as
        :meth:`submit_push`."""
        q = self._queue(job_id)
        while len(q) >= self.queue_capacity:
            self.stats.n_forced_capacity += 1
            self.tick()
        return self._enqueue(q, job_id, packed)

    def _enqueue(self, q: deque, job_id: str, packed) -> PushFuture:
        fut = PushFuture(job_id, self)
        # Wire accounting: what this push costs as fp32 vs. under the
        # job's compression (bytes are spent whether or not the injector
        # later drops the push -- it models loss IN transit).
        n = int(packed.size)
        kind = self.runtime._jobs[job_id]["step_opts"].get("push_compression")
        self.stats.push_bytes_raw += 4 * n
        self.stats.push_bytes_wire += wire_bytes(n, kind)
        action = ("deliver" if self.fault_injector is None
                  else self.fault_injector.on_push(job_id, None))
        if action != "drop":
            q.append((packed, fut, self._epoch))
            if action == "duplicate":
                # An at-least-once delivery bug: the copy applies as an
                # extra, untracked push (fut=None -- nothing to resolve).
                q.append((packed, None, self._epoch))
        return fut

    def step(self, job_id: str, batch) -> Dict[str, Any]:
        """One engine-mode iteration: pull (staleness-bounded), compute
        loss/grads, submit the push.  The update lands at a later tick;
        ``metrics["future"]`` tracks it."""
        q = self._queue(job_id)
        while self.outstanding(job_id) > self.max_staleness:
            self.stats.n_forced_staleness += 1
            self.tick()
        while len(q) >= self.queue_capacity:
            self.stats.n_forced_capacity += 1
            self.tick()
        fn = self._grad_fns.get(job_id)
        if fn is None:
            plan = self.plan
            layout = plan.job_layout(job_id)
            info = self.runtime._jobs[job_id]
            abstract, loss_fn = info["abstract"], info["loss_fn"]
            rows = jnp.asarray(layout.blocks)

            def job_grads(flat, batch, _layout=layout, _rows=rows,
                          _abstract=abstract, _loss=loss_fn):
                packed = (flat if _layout.covers_all else
                          flat.reshape(-1, _layout.block)[_rows].reshape(-1))
                params = _unpack_slots(_layout, packed, _abstract)
                loss, grads = jax.value_and_grad(_loss)(params, batch)
                return loss, _pack_slots(_layout, grads)

            fn = jax.jit(job_grads) if self._jit else job_grads
            self._grad_fns[job_id] = fn
        loss, packed = fn(self.runtime.state["flat"], batch)
        return {"loss": loss, "future": self._enqueue(q, job_id, packed)}

    # ----------------------------------------------------------------- tick
    def tick(self, only=None) -> int:
        """One service tick: pop the head push of every pending job (or
        of the ``only`` subset during a replan quiesce) and apply them --
        in ONE batched pass when at least ``min_batch_jobs`` jobs are
        pending, as per-job block passes below that crossover (identical
        result, cheaper program).  Returns the number of jobs applied
        (0 = nothing pending)."""
        if self.health == QUARANTINED:
            raise self.quarantine_error
        with span("ps.tick", tick=self.stats.n_ticks) as sp:
            return self._tick(only, sp)

    def _tick(self, only, sp) -> int:
        pending = [j for j in self.runtime._jobs
                   if self._queues.get(j) and (only is None or j in only)]
        if not pending:
            return 0
        sp.set_metadata(pieces=len(pending))
        # Epoch fence: a queued push packed under a different plan epoch
        # must never reach the apply -- touched jobs are drained before
        # the plan changes and untouched survivors are re-tagged, so a
        # mismatch here is a protocol violation, not a recoverable state.
        for j in pending:
            if self._queues[j][0][2] != self._epoch:
                raise RuntimeError(
                    f"epoch fence: job {j!r} queued a push under plan "
                    f"epoch {self._queues[j][0][2]} but the engine is at "
                    f"{self._epoch}; a replan migrated this job's layout "
                    f"without draining its queue")
        if 1 < len(pending) < self.min_batch_jobs:
            # Below the batching crossover: the same pushes as per-job
            # passes (disjoint blocks commute, so the result is
            # bit-identical to the one-launch concatenation).
            groups = [(j,) for j in pending]
            self.stats.n_per_job_dispatch += 1
        else:
            groups = [tuple(pending)]
        # Refresh the lane snapshot BEFORE any donated apply can consume
        # the live buffers (queues are still intact, so the snapshot plus
        # the -- now empty -- replay log reconstructs this exact moment).
        snapped = self._maybe_snapshot(pending)
        if self._replica_hub is not None:
            # Publish point for the read tier, co-located with the
            # rollback snapshot: on a refresh tick the hub rides the copy
            # just taken instead of making its own.
            self._replica_hub.on_tick(None, snapped)
        applied = 0
        for key in groups:
            heads = [self._queues[j].popleft() for j in key]
            try:
                applier = self._appliers.get(key)
                if applier is None:
                    applier = self._build_applier(key)
                    if len(self._appliers) >= self.MAX_APPLIERS:
                        # One program per pending-job SUBSET: bound the
                        # cache (FIFO eviction) so heterogeneous tick
                        # patterns can't accumulate 2^K compiled appliers.
                        self._appliers.pop(next(iter(self._appliers)))
                    self._appliers[key] = applier
                gs = tuple(packed for packed, _, _ in heads)
                run = applier.compiled(self.runtime.state, gs)
            except BaseException:
                # Build- or compile-time failure (e.g. a
                # non-block-exclusive layout, a kernel the backend cannot
                # lower): no device op ran, so re-queue the popped heads
                # -- nothing is lost and a later tick can retry.
                for j, head in zip(key, heads):
                    self._queues[j].appendleft(head)
                raise
            try:
                if self.fault_injector is not None:
                    self.fault_injector.on_apply(None)
                with span("ps.launch"):
                    self.runtime.state = run(self.runtime.state, gs)
            except BaseException as exc:
                # Execution failure: the jitted applier DONATES the state
                # buffers, so they may already be deleted.  Re-queue the
                # heads, then roll the lane back to its last-good
                # snapshot and replay (or quarantine when retries are
                # exhausted / no snapshot exists) -- the rollback undoes
                # every group this tick already applied, so nothing from
                # this tick survives.
                for j, head in zip(key, heads):
                    self._queues[j].appendleft(head)
                self._handle_apply_failure(exc, key)
                self.stats.n_ticks += 1
                return 0
            self._failures = 0
            for j, (packed, fut, _) in zip(key, heads):
                self._counts[j] += 1
                if fut is not None:
                    fut._resolve(self._counts[j])
                self._snapshot_log.append((j, packed, fut))
            applied += len(key)
        self._stamp_blocks(pending)  # diff-pull clients see these as dirty
        self.stats.n_ticks += 1
        self.stats.n_applied += applied
        self.stats.n_launches += len(groups)
        self._ticks_since_snapshot += 1
        return applied

    # ------------------------------------------------------- fault recovery
    def _maybe_snapshot(self, jobs) -> bool:
        """Copy (state, counts mirror) as the rollback anchor, BEFORE the
        donated apply: every ``snapshot_interval`` applying ticks, or
        sooner when the replay log plus the head pushes of ``jobs`` this
        tick will log would outgrow the state (:func:`_anchor_due`).
        Returns True when the anchor was refreshed this call (the read
        tier reuses its fresh copy instead of taking another)."""
        if self.snapshot_interval <= 0:
            return False
        by = _anchor_due(self._snapshot, self._ticks_since_snapshot,
                         self.snapshot_interval, self._snapshot_log,
                         sum(self._queues[j][0][0].nbytes for j in jobs),
                         self.runtime.state)
        if by is None:
            return False
        # Let go of the old anchor and its log before copying: the copy
        # then never needs room beside them (a tick that raises here has
        # popped nothing, and the next one copies again).
        self._snapshot, self._snapshot_log = None, []
        with span("ps.snapshot", by=by):
            self._snapshot = (state_copy(self.runtime.state),
                              dict(self._counts))
        self._ticks_since_snapshot = 0
        self.stats.n_snapshots += 1
        self.stats.n_snapshots_by_bytes += int(by == "bytes")
        return True

    def _rollback(self) -> None:
        """Restore the last-good snapshot and re-queue the logged pushes
        IN FRONT of whatever is queued (per-job order preserved), so
        subsequent ticks replay the identical sequence.  Replayed
        futures ride along un-resolved-if-pending / kept-done-if-done;
        the snapshot itself stays pristine for a repeated rollback."""
        with span("ps.rollback"):
            snapshot, counts_copy = self._snapshot
            self.runtime.state = state_copy(snapshot)
            self._counts = dict(counts_copy)
            # The restore REWOUND every block the logged pushes had touched:
            # re-stamp them so a diff-pull client who saw the undone values
            # is told those blocks changed (versions only move forward).
            self._stamp_blocks({j for j, _, _ in self._snapshot_log})
            for j, packed, fut in reversed(self._snapshot_log):
                if fut is not None:
                    fut._unresolve()
                self._queues.setdefault(j, deque()).appendleft(
                    (packed, fut, self._epoch))
                self.stats.n_replayed += 1
            self._snapshot_log = []
            self._ticks_since_snapshot = 0
            self.stats.n_rollbacks += 1

    def _handle_apply_failure(self, exc: BaseException, key) -> None:
        """Roll back and return (the tick swallows the failure; later
        ticks replay), or quarantine/re-raise when recovery is off the
        table."""
        self._failures += 1
        can_roll = self._snapshot is not None
        if can_roll and self.retry_policy.should_retry(self._failures):
            self.retry_policy.backoff(self._failures)
            self._rollback()
            return
        if can_roll:
            self._rollback()  # leave last-good state installed
        elif not self._jit:
            # Eager with snapshots disabled: nothing was donated, the
            # state is intact -- surface the raw error, caller may retry.
            raise exc
        self.health = QUARANTINED
        self.quarantine_error = EngineQuarantinedError(
            shard_id=None, tick=self.stats.n_ticks, job_ids=key,
            original=exc)
        self.stats.n_quarantines += 1
        raise self.quarantine_error from exc

    def _stall_error(self, job_id: str) -> Optional[Exception]:
        """Why a zero-progress tick round cannot resolve this job's push:
        an exception to raise, or None when progress is still possible
        (e.g. a rollback just re-queued the work)."""
        if self.health == QUARANTINED:
            return self.quarantine_error
        if self._queues.get(job_id):
            return None
        return RuntimeError(
            f"push for job {job_id!r} can never resolve: no queued push "
            f"remains for it (piece dropped in transit?)")

    def drain(self, only=None) -> int:
        """Quiesce: tick until every (selected) queue is empty.  Returns
        pushes applied.  A tick round may legitimately apply nothing
        while a rollback replays, so the loop only stops when the
        selected queues are actually empty; a quarantined engine raises
        :class:`~repro.ps.faults.EngineQuarantinedError` out of
        ``tick``."""
        applied = 0
        while True:
            n = self.tick(only=only)
            applied += n
            if n:
                continue
            if not any(q for j, q in self._queues.items()
                       if only is None or j in only):
                return applied

    def _build_applier(self, job_ids: Tuple[str, ...]) -> Callable:
        """Compile the batched apply for one combination of pending jobs.

        All plan-derived structures (concatenated owned-block table,
        per-job packed sizes, hyperparameters) are baked in at build time;
        the returned function is (state, packed_grads) -> state with ONE
        fused launch writing the updated flat/mu/nu blocks in place --
        no separate row-scatter passes (PR 6).
        """
        plan = self.plan
        layouts = [plan.job_layout(j) for j in job_ids]
        infos = [self.runtime._jobs[j] for j in job_ids]
        block_idx, job_sizes, hps = _fused_tables(layouts, infos,
                                                  _flat_job_hp)
        block, interpret = plan.block_align, self._interpret
        # Compressed-push jobs (PR 8): each gets the EF transform against
        # its owned rows of the shared error-feedback buffer before the
        # fused update.  ``compressed`` is empty for the common case, and
        # that branch's program is IDENTICAL to the pre-compression
        # applier -- the parity tests pin this down.
        compressed = [(i, kind, layouts[i])
                      for i, info in enumerate(infos)
                      if (kind := info["step_opts"].get("push_compression"))]

        def flat_apply(state, gs):
            counts = [state["counts"][j] + 1 for j in job_ids]
            if compressed:
                ef = state.get("ef")
                if ef is None:
                    # A rollback can restore a snapshot that predates the
                    # ef widening; the buffer was all-zero back then.
                    ef = jnp.zeros_like(state["flat"])
                gs = list(gs)
                for i, kind, layout in compressed:
                    gs[i], resid = ef_transform(
                        gs[i], _gather_owned(layout, ef), kind)
                    ef = _scatter_owned(layout, ef, resid)
                gs = tuple(gs)
            new_state = _fused_state_update(
                state, gs, counts, block=block, block_idx=block_idx,
                job_sizes=job_sizes, hps=hps, interpret=interpret)
            if compressed:
                new_state["ef"] = ef
            new_state["counts"] = dict(
                state["counts"], **{j: c for j, c in zip(job_ids, counts)})
            return new_state

        # Donate the shared state: flat/mu/nu update in place per tick.
        return _Applier(flat_apply, self._jit, (self.stats,))


# --------------------------------------------------------------- sharded
class _ShardLane:
    """One shard space's service loop state: its own queues, compiled
    appliers, TickStats -- and now its own health, rollback snapshot,
    and replay log (the unit of independent cadence is also the unit of
    failure isolation)."""

    __slots__ = ("shard_id", "queues", "appliers", "stats", "health",
                 "quarantine_error", "snapshot", "log",
                 "ticks_since_snapshot", "failures", "versions")

    def __init__(self, shard_id: str):
        self.shard_id = shard_id
        self.queues: Dict[str, deque] = {}  # job -> (piece, count, fut, ep)
        self.appliers: Dict[Tuple[str, ...], Callable] = {}
        self.stats = TickStats()
        self.health = HEALTHY
        self.quarantine_error: Optional[EngineQuarantinedError] = None
        self.snapshot = None  # last-good copy of this shard's state
        self.log: List[Tuple] = []  # (job, piece, count, fut) since copy
        self.ticks_since_snapshot = 0
        self.failures = 0  # consecutive failed applies (reset on success)
        self.versions: Optional[np.ndarray] = None  # per-block, diff pulls


class ShardedTickEngine:
    """Per-shard batched executor for one :class:`ShardedServiceRuntime`.

    Where :class:`ServiceTickEngine` runs ONE tick loop over one shared
    space, this engine runs one independent loop PER SHARD SPACE
    (``tick_shard``): a hot shard ticking fast never stalls a cold one,
    and the autoscaler reads each lane's :class:`TickStats` as its load
    signal.  A job's push splits into one packed PIECE per hosting shard,
    each tagged with the job's global step count at submit time -- Adam is
    elementwise, and each lane applies a job's pieces FIFO, so every lane
    preserves its lanes' per-element ``(gradient, step)`` sequence and the
    trajectory stays bit-exact with the unsharded engine no matter how
    shard cadences interleave.  ``tick()`` runs one round over every lane
    (the BSP convenience); staleness/capacity bounds are per job, taken
    over its hosting lanes.

    Replans reuse the flat engine's protocol: the runtime quiesces ONLY
    the jobs the sharded transition touches, surviving pushes are
    re-tagged across the per-push epoch fence, and lanes are keyed by the
    stable ``agg_id`` so an untouched job's queues and compiled programs
    ride straight through a neighboring shard's split or merge.

    ``fleet_tick`` selects how :meth:`tick` dispatches a round (PR 6):
    ``"fused"`` (the default) runs ONE fused launch over every lane with
    pending pieces -- the lanes' flat/mu/nu concatenate into one fleet
    view, the multi-job kernel runs once with globally-rebased block ids,
    and per-shard states slice back out -- while ``"per_shard"`` keeps
    the PR-5 one-launch-group-per-lane loop as a bit-parity oracle.  The
    attribute is mutable on purpose (benchmarks flip one engine between
    modes; the two paths keep separate applier caches).  Per-element math
    is identical either way, so the trajectories match bit-for-bit in
    eager mode.
    """

    MAX_APPLIERS = 32  # compiled programs per lane (one per job subset)

    def __init__(self, runtime, *, max_staleness: int = 1,
                 queue_capacity: Optional[int] = None, jit: bool = True,
                 interpret: Optional[bool] = None, min_batch_jobs: int = 3,
                 fleet_tick: str = "fused", snapshot_interval: int = 8,
                 max_apply_retries: int = 1, fault_injector=None,
                 retry_policy=None, lease_interval: Optional[float] = None,
                 clock=None):
        if max_staleness < 0:
            raise ValueError(f"max_staleness must be >= 0, got {max_staleness}")
        if fleet_tick not in ("fused", "per_shard"):
            raise ValueError(f"fleet_tick must be 'fused' or 'per_shard', "
                             f"got {fleet_tick!r}")
        if snapshot_interval < 0:
            raise ValueError(
                f"snapshot_interval must be >= 0 (0 disables rollback "
                f"recovery), got {snapshot_interval}")
        if lease_interval is not None and lease_interval <= 0:
            raise ValueError(
                f"lease_interval must be > 0 (None disables leases), "
                f"got {lease_interval}")
        self.runtime = runtime
        self.max_staleness = int(max_staleness)
        self.queue_capacity = (self.max_staleness + 1 if queue_capacity is None
                               else int(queue_capacity))
        if self.queue_capacity < 1:
            raise ValueError("queue_capacity must be >= 1")
        self.min_batch_jobs = int(min_batch_jobs)
        self.fleet_tick = fleet_tick
        # Per-LANE rollback anchors (see ServiceTickEngine): each shard
        # lane copies its state every this many of its own applying
        # ticks, so one shard's failure rolls back (and quarantines) that
        # lane alone.
        self.snapshot_interval = int(snapshot_interval)
        # Shared retry schedule (see ServiceTickEngine): retry_policy
        # wins over the legacy max_apply_retries count.
        if retry_policy is None:
            from repro.ps.faults import RetryPolicy

            retry_policy = RetryPolicy(max_retries=int(max_apply_retries))
        self.retry_policy = retry_policy
        self.max_apply_retries = int(retry_policy.max_retries)
        self.fault_injector = fault_injector
        # Job leases (see ServiceTickEngine.expire_leases).
        self.lease_interval = (None if lease_interval is None
                               else float(lease_interval))
        self._clock = clock if clock is not None else time.monotonic
        self._leases: Dict[str, float] = {}  # job -> expiry deadline
        self.stats = TickStats()  # fleet-aggregate counters
        self._jit = jit
        self._interpret = interpret
        self._epoch = 0
        self._version_clock = 0  # fleet-wide monotone diff-pull clock
        self._lanes: Dict[str, _ShardLane] = {}
        self._counts: Dict[str, int] = {}  # job step mirror (submit time)
        # Fleet appliers are keyed by the whole pending pattern
        # ((shard_id, jobs), ...) -- separate from the per-lane caches.
        self._fleet_appliers: Dict[Tuple, Callable] = {}
        self._pull_fns: Dict[str, Callable] = {}
        self._grad_fns: Dict[str, Callable] = {}
        self._pack_fns: Dict[str, Callable] = {}
        # Read tier (PR 10): a ReplicaSet registers itself here and gets
        # offered each ticking lane for publication.
        self._replica_hub = None

    # ------------------------------------------------------------- plumbing
    @property
    def plan(self):
        return self.runtime.splan

    def _lane(self, shard_id: str) -> _ShardLane:
        lane = self._lanes.get(shard_id)
        if lane is None:
            lane = self._lanes[shard_id] = _ShardLane(shard_id)
        return lane

    def _layout(self, job_id: str):
        info = self.runtime._jobs.get(job_id)
        if info is None:
            raise ValueError(f"unknown job {job_id!r}: not registered with "
                             f"the runtime (have {sorted(self.runtime._jobs)})")
        layout = self.plan.job_layout(job_id)
        if info.get("step_opts", {}).get("push_compression"):
            # Late-arriving compression (e.g. a restore from a
            # pre-compression checkpoint): widen each hosting shard's
            # state with a zero error-feedback buffer, mirroring the
            # runtime's replan-time widening.
            for sid in layout.shard_ids:
                st = self.runtime.states[sid]
                if "ef" not in st:
                    self.runtime.states[sid] = dict(
                        st, ef=jnp.zeros_like(st["flat"]))
        if job_id not in self._counts:
            self._counts[job_id] = int(jax.device_get(
                self.runtime.counts[job_id]))
        self._renew_lease(job_id)
        return layout

    # --------------------------------------------------------------- leases
    def _renew_lease(self, job_id: str) -> None:
        if self.lease_interval is not None:
            self._leases[job_id] = self._clock() + self.lease_interval

    def lease_deadline(self, job_id: str) -> Optional[float]:
        """The job's current lease expiry (None: leases off / no contact)."""
        return self._leases.get(job_id)

    def expire_leases(self) -> Tuple[str, ...]:
        """Reclaim every job whose lease has lapsed; returns their ids.

        Identical contract to :meth:`ServiceTickEngine.expire_leases`,
        with the job's queued PIECES cancelled on every hosting lane
        before the job leaves through the transactional replan path."""
        if self.lease_interval is None:
            return ()
        now = self._clock()
        expired = tuple(sorted(
            j for j, deadline in self._leases.items()
            if deadline <= now and j in self.runtime._jobs))
        for job_id in expired:
            err = LeaseExpiredError(job_id, self._leases[job_id], now)
            for lane in self._lanes.values():
                q = lane.queues.get(job_id)
                if q:
                    for _, _, fut, _ in q:
                        if fut is not None:
                            fut._cancel(str(err), exc=err)
                    q.clear()
            self._leases.pop(job_id, None)
            self.stats.n_lease_expirations += 1
            try:
                self.runtime.remove_job(job_id)
            except Exception:
                self._leases[job_id] = now + self.lease_interval
                raise
        return expired

    def outstanding(self, job_id: str) -> int:
        """Deepest per-shard queue of the job's not-yet-applied pieces."""
        deepest = 0
        for lane in self._lanes.values():
            q = lane.queues.get(job_id)
            if q:
                deepest = max(deepest, len(q))
        return deepest

    def shard_stats(self) -> Dict[str, TickStats]:
        """Per-shard TickStats (the autoscaler's load signal)."""
        return {sid: lane.stats for sid, lane in self._lanes.items()}

    # ---------------------------------------------------------- lane health
    def shard_health(self) -> Dict[str, str]:
        """Per-lane health: ``'healthy'`` or ``'quarantined'`` (the
        autoscaler refuses to resize a fleet with a quarantined lane)."""
        return {sid: lane.health for sid, lane in self._lanes.items()}

    def quarantined_shards(self) -> Tuple[str, ...]:
        return tuple(sid for sid, lane in self._lanes.items()
                     if lane.health == QUARANTINED)

    def _quarantine_blocking(
            self, only=None) -> Optional[EngineQuarantinedError]:
        """The quarantine error blocking the given jobs (any job when
        None): set when a quarantined lane still holds matching queued
        pieces -- no amount of ticking will ever apply them."""
        for lane in self._lanes.values():
            if lane.health != QUARANTINED:
                continue
            if any(q and (only is None or j in only)
                   for j, q in lane.queues.items()):
                return lane.quarantine_error
        return None

    def _has_pending(self, only=None) -> bool:
        return any(q and (only is None or j in only)
                   for lane in self._lanes.values()
                   for j, q in lane.queues.items())

    def _stall_error(self, job_id: str) -> Optional[Exception]:
        """Why a zero-progress tick round cannot resolve this job's push:
        an exception to raise, or None when progress is still possible
        (e.g. a rollback just re-queued the replay)."""
        exc = self._quarantine_blocking((job_id,))
        if exc is not None:
            return exc
        if any(lane.queues.get(job_id) for lane in self._lanes.values()):
            return None
        return RuntimeError(
            f"push for job {job_id!r} can never resolve: no queued piece "
            f"remains for it on any lane (piece dropped in transit?)")

    # ------------------------------------------------------------ data path
    def pull(self, job_id: str, since_version=None):
        """The job's parameters gathered across its hosting shards, after
        forcing tick rounds down to the staleness bound.

        ``since_version`` switches to the VERSIONED DIFF protocol (see
        :meth:`ServiceTickEngine.pull`): a :class:`PullDiff` of only the
        owned blocks whose version moved since the client's
        :class:`PullVersion` -- versions concatenate over the hosting
        shards in shard order, matching the packed piece order."""
        with span("ps.pull", job=job_id):
            layout = self._layout(job_id)
            for sid in layout.shard_ids:
                lane = self._lanes.get(sid)
                if lane is not None and lane.health == QUARANTINED:
                    # A hosting lane froze at its last-good snapshot and will
                    # never advance: raise its error instead of serving
                    # silently stale parameters.  Read-tier replicas
                    # (repro.ps.replica) are the degraded-serving path.
                    raise lane.quarantine_error
            while self.outstanding(job_id) > self.max_staleness:
                self.stats.n_forced_staleness += 1
                if self.tick() == 0:
                    stall = self._stall_error(job_id)
                    if stall is not None:
                        # The backlog lives on a quarantined lane: forcing
                        # more ticks can never drain it.
                        raise stall
            if since_version is not None:
                return self._pull_versioned(job_id, layout, since_version)
            self.stats.n_full_pulls += 1
            self.stats.pull_bytes_wire += 4 * layout.packed_len
            self.stats.pull_bytes_full += 4 * layout.packed_len
            fn = self._pull_fns.get(job_id)
            new = fn is None and self._jit
            if fn is None:
                abstract = self.runtime._jobs[job_id]["abstract"]
                rows = _layout_rows(layout)

                def pull_gather(flats, _layout=layout, _rows=rows,
                                _abstract=abstract):
                    p = _gather_packed(_layout, _rows, flats)
                    return _unpack_slots(_layout, p, _abstract)

                fn = jax.jit(pull_gather) if self._jit else pull_gather
                self._pull_fns[job_id] = fn
            with _compile_span(self.stats, new):
                return fn(tuple(self.runtime.states[sid]["flat"]
                                for sid in layout.shard_ids))

    # ----------------------------------------------------- versioned pulls
    def _lane_versions(self, lane: _ShardLane) -> np.ndarray:
        sp = self.plan.shard_of(lane.shard_id)
        nb = sp.total_len // sp.block_align
        if lane.versions is None or lane.versions.size != nb:
            lane.versions = np.zeros(nb, np.int64)
        return lane.versions

    def _stamp_lane(self, lane: _ShardLane, jobs) -> None:
        """Advance the fleet-wide version clock and stamp the given jobs'
        owned blocks of THIS shard space (applying ticks and rollbacks --
        a rewound block must never look unchanged to a diff client)."""
        if self.plan is None or not jobs:
            return
        sp = self.plan.shard_of(lane.shard_id)
        versions = self._lane_versions(lane)
        self._version_clock += 1
        for j in jobs:
            if j in self.runtime._jobs:
                versions[np.asarray(sp.job_layout(j).blocks)] = \
                    self._version_clock

    def _pull_versioned(self, job_id: str, layout, since) -> PullDiff:
        # The job-local version vector: each hosting shard's versions of
        # the job's owned blocks, concatenated in shard order -- the same
        # order its packed pieces concatenate in, so job-local block row
        # i of the packed vector is entry i of the vector.
        parts = []
        for sid, l in zip(layout.shard_ids, layout.layouts):
            lane = self._lane(sid)
            parts.append(self._lane_versions(lane)[np.asarray(l.blocks)])
        vers = (np.concatenate(parts) if len(parts) > 1
                else parts[0].copy())
        version = PullVersion(epoch=self._epoch, versions=vers)
        bytes_full = 4 * layout.packed_len
        blocks = {l.block for l in layout.layouts}
        uniform = len(blocks) == 1
        full = (not uniform  # mixed granularity: no single row width
                or not isinstance(since, PullVersion)
                or since.epoch != self._epoch
                or since.versions.size != vers.size)
        if full:
            data = _gather_packed(
                layout, _layout_rows(layout),
                [self.runtime.states[sid]["flat"]
                 for sid in layout.shard_ids])
            diff = PullDiff(
                job_id=job_id, version=version, full=True,
                block=(blocks.pop() if uniform else 0),
                block_ids=np.empty(0, np.int64), data=data,
                bytes_wire=bytes_full, bytes_full=bytes_full)
            self.stats.n_full_pulls += 1
        else:
            block = blocks.pop()
            changed = vers > since.versions
            data_parts, id_parts = [], []
            off = 0  # job-local block row of this shard's first piece row
            for sid, l in zip(layout.shard_ids, layout.layouts):
                nb = int(np.asarray(l.blocks).size)
                sel = np.nonzero(changed[off:off + nb])[0]
                if sel.size:
                    flat = self.runtime.states[sid]["flat"]
                    data_parts.append(flat.reshape(-1, l.block)[
                        jnp.asarray(np.asarray(l.blocks)[sel])])
                    id_parts.append(off + sel)
                off += nb
            if data_parts:
                data = (jnp.concatenate(data_parts) if len(data_parts) > 1
                        else data_parts[0])
                ids = np.concatenate(id_parts).astype(np.int64)
            else:
                data = jnp.zeros((0, block), jnp.float32)
                ids = np.empty(0, np.int64)
            diff = PullDiff(
                job_id=job_id, version=version, full=False, block=block,
                block_ids=ids, data=data,
                bytes_wire=4 * int(ids.size) * block,
                bytes_full=bytes_full)
            self.stats.n_diff_pulls += 1
        self.stats.pull_bytes_wire += diff.bytes_wire
        self.stats.pull_bytes_full += bytes_full
        return diff

    def _enqueue(self, job_id: str, layout, pieces) -> PushFuture:
        count = self._counts[job_id] + 1
        self._counts[job_id] = count
        fut = PushFuture(job_id, self, parts=len(pieces))
        inj = self.fault_injector
        kind = self.runtime._jobs[job_id]["step_opts"].get("push_compression")
        for sid, piece in zip(layout.shard_ids, pieces):
            # Wire accounting per PIECE (each crosses to its own hosting
            # shard), on the fleet and the receiving lane's stats alike;
            # bytes are spent even when the injector drops the piece.
            n = int(piece.size)
            wire = wire_bytes(n, kind)
            self.stats.push_bytes_raw += 4 * n
            self.stats.push_bytes_wire += wire
            lane_stats = self._lane(sid).stats
            lane_stats.push_bytes_raw += 4 * n
            lane_stats.push_bytes_wire += wire
            action = "deliver" if inj is None else inj.on_push(job_id, sid)
            if action == "drop":
                # Lost in transit: the future keeps the part, so it can
                # never resolve -- result(timeout=...) surfaces it.
                continue
            q = self._lane(sid).queues.setdefault(job_id, deque())
            q.append((piece, count, fut, self._epoch))
            if action == "duplicate":
                # At-least-once delivery bug: the copy applies as an
                # extra untracked piece (fut=None).
                q.append((piece, count, None, self._epoch))
        return fut

    def _force_capacity(self, job_id: str, layout) -> None:
        while True:
            full = [sid for sid in layout.shard_ids
                    if len(self._lane(sid).queues.get(job_id, ()))
                    >= self.queue_capacity]
            if not full:
                return
            self.stats.n_forced_capacity += 1
            for sid in full:
                lane = self._lanes.get(sid)
                if lane is not None and lane.health == QUARANTINED:
                    # A full queue on a lane that will never tick again:
                    # fail the submit instead of spinning forever.
                    raise lane.quarantine_error
                self.tick_shard(sid)

    def submit_push(self, job_id: str, grads) -> PushFuture:
        """Queue a job's gradient pytree: one packed piece per hosting
        shard, applied by each shard's own ticks."""
        with span("ps.push", job=job_id) as sp:
            layout = self._layout(job_id)
            self._force_capacity(job_id, layout)
            sp.set_metadata(step=self._counts[job_id] + 1)
            fn = self._pack_fns.get(job_id)
            new = fn is None and self._jit
            if fn is None:
                def push_pack(grads, _layout=layout):
                    g = _pack_slots(_layout, grads)
                    return _split_pieces(_layout, g)

                fn = jax.jit(push_pack) if self._jit else push_pack
                self._pack_fns[job_id] = fn
            with _compile_span(self.stats, new):
                pieces = fn(grads)
            return self._enqueue(job_id, layout, pieces)

    def step(self, job_id: str, batch) -> Dict[str, Any]:
        """One engine-mode iteration: staleness-bounded pull, loss/grads,
        one queued piece per hosting shard."""
        layout = self._layout(job_id)
        while self.outstanding(job_id) > self.max_staleness:
            self.stats.n_forced_staleness += 1
            if self.tick() == 0:
                stall = self._stall_error(job_id)
                if stall is not None:
                    raise stall
        self._force_capacity(job_id, layout)
        fn = self._grad_fns.get(job_id)
        if fn is None:
            info = self.runtime._jobs[job_id]
            abstract, loss_fn = info["abstract"], info["loss_fn"]
            rows = _layout_rows(layout)

            def job_grads(flats, batch, _layout=layout, _rows=rows,
                          _abstract=abstract, _loss=loss_fn):
                params = _unpack_slots(
                    _layout, _gather_packed(_layout, _rows, flats),
                    _abstract)
                loss, grads = jax.value_and_grad(_loss)(params, batch)
                return loss, _split_pieces(_layout, _pack_slots(_layout,
                                                                grads))

            fn = jax.jit(job_grads) if self._jit else job_grads
            self._grad_fns[job_id] = fn
        loss, pieces = fn(
            tuple(self.runtime.states[sid]["flat"]
                  for sid in layout.shard_ids), batch)
        return {"loss": loss,
                "future": self._enqueue(job_id, layout, pieces)}

    # ----------------------------------------------------------------- tick
    def tick_shard(self, shard_id: str, only=None) -> int:
        """One tick of ONE shard space: pop the head piece of every
        pending job on this lane and apply them in one per-shard pass
        (batched at/above ``min_batch_jobs`` pending jobs).  Other shards
        are untouched -- this is the independent cadence primitive, and
        the unit of failure isolation: a QUARANTINED lane is skipped
        (returns 0) so its neighbors' cadence never stalls."""
        with span("ps.lane_tick", shard=shard_id):
            return self._tick_shard(shard_id, only)

    def _tick_shard(self, shard_id: str, only) -> int:
        lane = self._lanes.get(shard_id)
        if lane is None or lane.health == QUARANTINED:
            return 0
        pending = [j for j in self.runtime._jobs
                   if lane.queues.get(j) and (only is None or j in only)]
        if not pending:
            return 0
        for j in pending:
            if lane.queues[j][0][3] != self._epoch:
                raise RuntimeError(
                    f"epoch fence: job {j!r} queued a piece on shard "
                    f"{shard_id!r} under plan epoch {lane.queues[j][0][3]} "
                    f"but the engine is at {self._epoch}; a replan "
                    f"migrated this job's layout without draining it")
        if 1 < len(pending) < self.min_batch_jobs:
            groups = [(j,) for j in pending]
            lane.stats.n_per_job_dispatch += 1
        else:
            groups = [tuple(pending)]
        snapped = self._maybe_snapshot_lane(lane, pending)
        if self._replica_hub is not None:
            # Read-tier publish point, co-located with the rollback
            # snapshot so a refresh tick's copy is shared, not repeated.
            self._replica_hub.on_tick(shard_id, snapped)
        applied = 0
        for key in groups:
            heads = [lane.queues[j].popleft() for j in key]
            try:
                applier = lane.appliers.get(key)
                if applier is None:
                    applier = self._build_applier(shard_id, key)
                    if len(lane.appliers) >= self.MAX_APPLIERS:
                        lane.appliers.pop(next(iter(lane.appliers)))
                    lane.appliers[key] = applier
                gs = tuple(piece for piece, _, _, _ in heads)
                counts = tuple(count for _, count, _, _ in heads)
                run = applier.compiled(self.runtime.states[shard_id], gs,
                                       counts)
            except BaseException:
                # Build- or compile-time failure: no device op ran;
                # re-queue and let a later tick retry.
                for j, head in zip(key, heads):
                    lane.queues[j].appendleft(head)
                raise
            try:
                if self.fault_injector is not None:
                    self.fault_injector.on_apply(shard_id)
                with span("ps.launch"):
                    self.runtime.states[shard_id] = run(
                        self.runtime.states[shard_id], gs, counts)
            except BaseException as exc:
                # Execution failure: the jitted applier DONATED this
                # shard's buffers.  Re-queue the heads, restore the
                # lane's last-good snapshot, and replay on later ticks
                # -- or quarantine THIS LANE ONLY when retries are
                # exhausted (neighbor lanes keep ticking either way).
                # The rollback undoes this tick's earlier groups too, so
                # nothing from this tick survives.
                for j, head in zip(key, heads):
                    lane.queues[j].appendleft(head)
                self._handle_lane_failure(lane, exc, key)
                lane.stats.n_ticks += 1
                self.stats.n_ticks += 1
                return 0
            lane.failures = 0
            for j, (piece, count, fut, _) in zip(key, heads):
                if fut is not None and fut._resolve(count):
                    # The push applied on its LAST hosting shard: commit
                    # the job's global step counter (per-shard states
                    # carry no counts -- the runtime owns them, and a
                    # checkpoint must see every applied push).  Only the
                    # done-TRANSITION commits: a replayed piece of an
                    # already-done future must not rewind the counter.
                    self.runtime.counts[j] = jnp.asarray(count, jnp.int32)
                lane.log.append((j, piece, count, fut))
            applied += len(key)
        self._stamp_lane(lane, pending)  # diff-pull dirty marks
        lane.stats.n_ticks += 1
        lane.stats.n_applied += applied
        lane.stats.n_launches += len(groups)
        lane.ticks_since_snapshot += 1
        self.stats.n_ticks += 1
        self.stats.n_applied += applied
        self.stats.n_launches += len(groups)
        return applied

    # ------------------------------------------------------- fault recovery
    def _maybe_snapshot_lane(self, lane: _ShardLane, jobs) -> bool:
        """Refresh this lane's rollback anchor BEFORE the donated apply
        (queues intact, replay log emptied: snapshot + log reconstructs
        any later moment): every ``snapshot_interval`` of ITS applying
        ticks, or sooner when its replay log plus the head pieces of
        ``jobs`` this tick will apply on it would outgrow its state
        (:func:`_anchor_due`).  Returns True when the anchor was
        refreshed this call (the read tier reuses its fresh copy instead
        of taking another)."""
        if self.snapshot_interval <= 0:
            return False
        state = self.runtime.states[lane.shard_id]
        by = _anchor_due(lane.snapshot, lane.ticks_since_snapshot,
                         self.snapshot_interval, lane.log,
                         sum(lane.queues[j][0][0].nbytes for j in jobs),
                         state)
        if by is None:
            return False
        # Let go of the old anchor and its log before copying (see
        # ServiceTickEngine._maybe_snapshot): beside them, a copy near
        # the device's capacity blocks its dispatch with the device idle.
        lane.snapshot, lane.log = None, []
        with span("ps.snapshot", by=by):
            lane.snapshot = state_copy(state)
        lane.ticks_since_snapshot = 0
        for stats in (lane.stats, self.stats):
            stats.n_snapshots += 1
            stats.n_snapshots_by_bytes += int(by == "bytes")
        return True

    def _rollback_lane(self, lane: _ShardLane) -> None:
        """Restore the lane's last-good state and re-queue its logged
        pieces IN FRONT of the queued backlog (per-job order preserved):
        subsequent ticks replay the identical (piece, count) sequence,
        which is bit-exact because counts were fixed at submit time."""
        with span("ps.rollback"):
            self.runtime.states[lane.shard_id] = state_copy(lane.snapshot)
            # The restore rewound the logged jobs' blocks: re-stamp so diff
            # clients who saw the undone values are told they changed.
            self._stamp_lane(lane, {j for j, _, _, _ in lane.log})
            for j, piece, count, fut in reversed(lane.log):
                if fut is not None:
                    fut._unresolve()
                lane.queues.setdefault(j, deque()).appendleft(
                    (piece, count, fut, self._epoch))
                lane.stats.n_replayed += 1
                self.stats.n_replayed += 1
            lane.log = []
            lane.ticks_since_snapshot = 0
            lane.stats.n_rollbacks += 1
            self.stats.n_rollbacks += 1

    def _handle_lane_failure(self, lane: _ShardLane, exc: BaseException,
                             key) -> None:
        """Roll the lane back for replay, or quarantine it (stored, NOT
        raised: the point is that sibling lanes keep ticking -- blocked
        work surfaces the stored error via drain/pull/result)."""
        lane.failures += 1
        can_roll = lane.snapshot is not None
        if can_roll and self.retry_policy.should_retry(lane.failures):
            self.retry_policy.backoff(lane.failures)
            self._rollback_lane(lane)
            return
        if can_roll:
            self._rollback_lane(lane)  # leave last-good state installed
        elif not self._jit:
            # Eager with snapshots disabled: nothing was donated, the
            # shard state is intact -- surface the raw error.
            raise exc
        lane.health = QUARANTINED
        lane.quarantine_error = EngineQuarantinedError(
            shard_id=lane.shard_id, tick=lane.stats.n_ticks, job_ids=key,
            original=exc)
        lane.stats.n_quarantines += 1
        self.stats.n_quarantines += 1

    def tick(self, only=None) -> int:
        """One ROUND over the fleet.  With ``fleet_tick="fused"`` (the
        default) this is ONE fused launch covering every lane with
        pending pieces (:meth:`tick_fleet`); with ``"per_shard"`` it
        ticks every live shard once, one launch group per lane (the PR-5
        oracle path).  Returns pieces applied (0 = nothing pending
        anywhere)."""
        plan = self.plan
        if plan is None:
            return 0
        if self.fleet_tick == "fused":
            return self.tick_fleet(only=only)
        return sum(self.tick_shard(sid, only=only)
                   for sid in plan.shard_ids)

    def tick_fleet(self, only=None) -> int:
        """One FLEET tick: pop the head piece of every pending job on
        EVERY lane and apply all of them in ONE fused launch over the
        pending lanes' concatenated states.  Lanes with nothing pending
        are skipped mid-table -- they contribute neither state movement
        nor launch cost, and their cadence is untouched.  QUARANTINED
        lanes are excluded the same way (their backlog is frozen until
        recovery), so one dead shard never blocks the fleet launch.
        Returns pieces applied across the fleet (0 = nothing pending
        anywhere)."""
        with span("ps.tick", tick=self.stats.n_ticks) as sp:
            return self._tick_fleet(only, sp)

    def _tick_fleet(self, only, sp) -> int:
        plan = self.plan
        if plan is None:
            return 0
        entries = []
        for sid in plan.shard_ids:
            lane = self._lanes.get(sid)
            if lane is None or lane.health == QUARANTINED:
                continue
            pending = tuple(
                j for j in self.runtime._jobs
                if lane.queues.get(j) and (only is None or j in only))
            if not pending:
                continue
            for j in pending:
                if lane.queues[j][0][3] != self._epoch:
                    raise RuntimeError(
                        f"epoch fence: job {j!r} queued a piece on shard "
                        f"{sid!r} under plan epoch "
                        f"{lane.queues[j][0][3]} but the engine is at "
                        f"{self._epoch}; a replan migrated this job's "
                        f"layout without draining it")
            entries.append((sid, pending))
        if not entries:
            return 0
        key = tuple(entries)
        sp.set_metadata(pieces=sum(len(jobs) for _, jobs in key))
        # Build and compile BEFORE popping: a build failure (e.g. mixed
        # block_align across lanes) or a compile failure leaves every
        # queue untouched and propagates -- neither is an apply failure.
        applier = self._fleet_appliers.get(key)
        if applier is None:
            applier = self._build_fleet_applier(key)
            if len(self._fleet_appliers) >= self.MAX_APPLIERS:
                self._fleet_appliers.pop(next(iter(self._fleet_appliers)))
            self._fleet_appliers[key] = applier
        heads = [self._lanes[sid].queues[j][0]
                 for sid, jobs in key for j in jobs]
        gs = tuple(head[0] for head in heads)
        counts = tuple(head[1] for head in heads)
        states = tuple(self.runtime.states[sid] for sid, _ in key)
        run = applier.compiled(states, gs, counts)
        # Snapshot every participating lane BEFORE popping: queues are
        # intact, so each lane's (snapshot, empty log) anchors a rollback
        # of this very launch.
        for sid, jobs in key:
            snapped = self._maybe_snapshot_lane(self._lanes[sid], jobs)
            if self._replica_hub is not None:
                self._replica_hub.on_tick(sid, snapped)
        popped = []  # (sid, job, head) in key order == table order
        for sid, jobs in key:
            lane = self._lanes[sid]
            for j in jobs:
                popped.append((sid, j, lane.queues[j].popleft()))
        try:
            if self.fault_injector is not None:
                for sid, _ in key:
                    self.fault_injector.on_apply(sid)
            with span("ps.launch"):
                new_states = run(states, gs, counts)
        except BaseException as exc:
            # Execution failure: the jitted applier DONATED every pending
            # shard's buffers, and the fused launch cannot attribute
            # WHICH lane blew up.  Re-queue the heads, roll back every
            # participating lane to its own snapshot, then FALL BACK to
            # per-shard launches: the faulty lane fails (and retries or
            # quarantines) in isolation while the healthy rest re-apply.
            for sid, j, head in popped:
                self._lanes[sid].queues[j].appendleft(head)
            if self.snapshot_interval <= 0:
                # No rollback anchors.  Jitted buffers are gone for every
                # participating lane: quarantine them all (the pre-PR-7
                # poisoned behavior, scoped to the participants); eager
                # states are intact, so surface the raw error.
                if not self._jit:
                    raise
                for sid, jobs in key:
                    lane = self._lanes[sid]
                    lane.health = QUARANTINED
                    lane.quarantine_error = EngineQuarantinedError(
                        shard_id=sid, tick=lane.stats.n_ticks,
                        job_ids=jobs, original=exc)
                    lane.stats.n_quarantines += 1
                    self.stats.n_quarantines += 1
                self.stats.n_ticks += 1
                return 0
            self.stats.n_fleet_fallbacks += 1
            applied = 0
            with span("ps.fallback"):
                for sid, _ in key:
                    self._rollback_lane(self._lanes[sid])
                for sid, _ in key:
                    applied += self.tick_shard(sid)
                    # Re-anchor before the next fused launch: if it fails
                    # too (a fused program that no longer fits the device,
                    # say), its rollback must not undo what these
                    # per-shard ticks applied, or every tick would replay
                    # the same pieces.
                    self._lanes[sid].ticks_since_snapshot = \
                        self.snapshot_interval
            self.stats.n_ticks += 1
            return applied
        for (sid, _), st in zip(key, new_states):
            self.runtime.states[sid] = st
        for sid, j, (piece, count, fut, _) in popped:
            lane = self._lanes[sid]
            lane.failures = 0
            if fut is not None and fut._resolve(count):
                # Applied on its LAST hosting shard: commit the job's
                # global step counter (the runtime owns counts); only
                # the done-transition commits (replay never rewinds).
                self.runtime.counts[j] = jnp.asarray(count, jnp.int32)
            lane.log.append((j, piece, count, fut))
        for sid, jobs in key:
            lane = self._lanes[sid]
            self._stamp_lane(lane, jobs)  # diff-pull dirty marks
            lane.stats.n_ticks += 1
            lane.stats.n_applied += len(jobs)
            lane.ticks_since_snapshot += 1
        self.stats.n_ticks += 1
        self.stats.n_applied += len(popped)
        self.stats.n_launches += 1  # the whole point: ONE launch per fleet
        return len(popped)

    def drain(self, only=None) -> int:
        """Tick rounds until every (selected) queue on every lane is
        empty.  Returns pieces applied.  A round may apply nothing while
        a rollback replays (the loop keeps ticking); pieces stuck on a
        QUARANTINED lane can never drain, so that raises the lane's
        :class:`~repro.ps.faults.EngineQuarantinedError` instead of
        spinning forever."""
        applied = 0
        while True:
            n = self.tick(only=only)
            applied += n
            if n:
                continue
            stuck = self._quarantine_blocking(only)
            if stuck is not None:
                raise stuck
            if not self._has_pending(only):
                return applied

    def quiesce_for_replan(self, touched) -> int:
        """Drain ONLY the touched jobs' pieces (on every lane) ahead of a
        sharded migration; untouched lanes and jobs keep their cadence.
        Raises the blocking lane's quarantine error if a touched piece is
        frozen on a dead lane (recover_shard purges the lost lane first,
        so this only fires on user-driven replans of a broken fleet)."""
        applied = 0
        while True:
            pending = [j for j in touched
                       if any(lane.queues.get(j)
                              for lane in self._lanes.values())]
            if not pending:
                return applied
            self.stats.n_forced_replan += 1
            n = self.tick(only=pending)
            applied += n
            if n == 0:
                stuck = self._quarantine_blocking(pending)
                if stuck is not None:
                    raise stuck

    # --------------------------------------------------------------- replan
    def _on_plan_change(self, touched=None) -> None:
        """Sharded replan landed: invalidate what the new plan breaks.

        Same fence protocol as the flat engine, per lane: ``touched=None``
        requires every queue empty and drops everything; with a touched
        set, only touched jobs' programs die, lanes whose Aggregator left
        the fleet are dropped (their jobs are touched by construction, so
        their queues are already drained), and untouched jobs' surviving
        pieces are re-tagged to the new epoch."""
        self._epoch += 1
        self.stats.n_replans += 1
        # Fleet appliers bake EVERY participating shard's length into the
        # concatenated-view offsets, so any plan change invalidates all
        # of them (per-lane appliers survive for untouched jobs).
        self._fleet_appliers.clear()
        # Lane snapshots copy the PRE-migration shard geometry: restoring
        # one after a replan would resurrect dead layouts.  Drop them all
        # (health survives -- a quarantined lane stays quarantined); the
        # rollback window restarts at each lane's next applying tick.
        for lane in self._lanes.values():
            lane.snapshot = None
            lane.log = []
            lane.ticks_since_snapshot = 0
            # Versions index the OLD shard geometry; the epoch bump
            # already sends every held PullVersion to the full-pull
            # fallback, so restart the vector.
            lane.versions = None
        if self._replica_hub is not None:
            # Read-tier snapshots hold the old geometry too; the epoch
            # fence marks them stale and the next serve resubscribes.
            self._replica_hub.on_replan()
        if touched is None:
            assert not any(q for lane in self._lanes.values()
                           for q in lane.queues.values()), (
                "replan with queued pieces: runtime must drain the "
                "engine first")
            self._lanes.clear()
            self._pull_fns.clear()
            self._grad_fns.clear()
            self._pack_fns.clear()
            return
        touched = set(touched)
        live = set(self.plan.shard_ids) if self.plan is not None else set()
        for sid in list(self._lanes):
            lane = self._lanes[sid]
            for j in touched:
                assert not lane.queues.get(j), (
                    f"replan with queued pieces for TOUCHED job {j!r} on "
                    f"shard {sid!r}: quiesce_for_replan must drain it")
            if sid not in live:
                assert not any(lane.queues.values()), (
                    f"shard {sid!r} left the fleet with queued pieces")
                del self._lanes[sid]
                continue
            for j, q in lane.queues.items():
                if q:  # untouched by construction: carry across the fence
                    self.stats.n_retagged += len(q)
                    lane.queues[j] = deque(
                        (piece, count, fut, self._epoch)
                        for piece, count, fut, _ in q)
            for j in touched:
                lane.queues.pop(j, None)
            lane.appliers = {k: v for k, v in lane.appliers.items()
                             if not touched.intersection(k)}
        for j in touched:
            self._pull_fns.pop(j, None)
            self._grad_fns.pop(j, None)
            self._pack_fns.pop(j, None)

    def _forget_job(self, job_id: str) -> None:
        for lane in self._lanes.values():
            q = lane.queues.pop(job_id, None)
            if q:
                for _, _, fut, _ in q:
                    if fut is not None:
                        fut._cancel(
                            "job removed from the runtime with this piece "
                            "still queued (drain was bypassed)")
            lane.log = [e for e in lane.log if e[0] != job_id]
            lane.appliers = {k: v for k, v in lane.appliers.items()
                             if job_id not in k}
        self._fleet_appliers = {
            k: v for k, v in self._fleet_appliers.items()
            if not any(job_id in jobs for _, jobs in k)}
        self._counts.pop(job_id, None)
        self._leases.pop(job_id, None)
        self._pull_fns.pop(job_id, None)
        self._grad_fns.pop(job_id, None)
        self._pack_fns.pop(job_id, None)

    # -------------------------------------------------------------- applier
    def _build_applier(self, shard_id: str, job_ids: Tuple[str, ...]):
        """Compile the batched apply for one shard space and one pending
        job combination.  Identical math to the flat engine's applier --
        one fused launch over THIS shard's buffers, updated blocks
        written in place (PR 6) -- except the per-job step counts arrive
        with the queued pieces (assigned at submit time), so inter-shard
        apply order cannot skew bias correction."""
        shard_plan = self.plan.shard_of(shard_id)
        layouts = [shard_plan.job_layout(j) for j in job_ids]
        infos = [self.runtime._jobs[j] for j in job_ids]
        block_idx, job_sizes, hps = _fused_tables(layouts, infos,
                                                  _sharded_job_hp)
        block, interpret = shard_plan.block_align, self._interpret
        # Compressed-push jobs (PR 8): the EF transform runs per HOSTING
        # SHARD against this shard's own ef buffer (one compressed piece
        # per shard).  Empty for the common case, whose program is
        # byte-identical to the pre-compression applier.
        compressed = [(i, kind, layouts[i])
                      for i, info in enumerate(infos)
                      if (kind := info["step_opts"].get("push_compression"))]

        def lane_apply(state, gs, counts):
            # Counts arrive as the pieces' submit-time step numbers; lift
            # to arrays so eager mode matches the traced path exactly.
            counts = [jnp.asarray(c, jnp.int32) for c in counts]
            if compressed:
                ef = state.get("ef")
                if ef is None:
                    # A rollback can restore a snapshot predating the ef
                    # widening; the buffer was all-zero back then.
                    ef = jnp.zeros_like(state["flat"])
                gs = list(gs)
                for i, kind, layout in compressed:
                    gs[i], resid = ef_transform(
                        gs[i], _gather_owned(layout, ef), kind)
                    ef = _scatter_owned(layout, ef, resid)
                gs = tuple(gs)
            new_state = _fused_state_update(
                state, gs, counts, block=block, block_idx=block_idx,
                job_sizes=job_sizes, hps=hps, interpret=interpret)
            if compressed:
                new_state["ef"] = ef
            return new_state

        return _Applier(lane_apply, self._jit,
                        (self._lane(shard_id).stats, self.stats))

    def _build_fleet_applier(self, key) -> Callable:
        """Compile the SINGLE-LAUNCH fleet apply for one pending pattern.

        ``key`` is ``((shard_id, (job, ...)), ...)`` over the lanes with
        pending pieces, in plan order.  The applier concatenates those
        lanes' flat/mu/nu into one fleet view, runs ONE fused multi-job
        launch whose block table is globally rebased (shard base offset
        // block + local block id), and slices the per-shard states back
        out -- one XLA program and one kernel launch no matter how many
        lanes ticked.  Block exclusivity holds globally because each
        shard's offset is block-aligned, so the launch is bit-exact with
        the per-shard oracle loop."""
        plan = self.plan
        sids = [sid for sid, _ in key]
        offsets, _, block = plan.concat_view(sids)
        lens = [plan.shard_of(sid).total_len for sid in sids]
        layouts, infos, bases = [], [], []
        # Compressed entries (PR 8): (entry index in gs, shard index in
        # ``states``, kind, shard-local layout).  The EF transform runs
        # per entry against ITS shard's own ef buffer -- ef never joins
        # the concatenated fleet view, so the common all-uncompressed
        # launch is byte-identical to the pre-compression program.
        compressed = []
        for si, ((sid, jobs), off) in enumerate(zip(key, offsets)):
            shard_plan = plan.shard_of(sid)
            for j in jobs:
                layout = shard_plan.job_layout(j)
                info = self.runtime._jobs[j]
                kind = info["step_opts"].get("push_compression")
                if kind:
                    compressed.append((len(layouts), si, kind, layout))
                layouts.append(layout)
                infos.append(info)
                bases.append(off // block)
        block_idx, job_sizes, hps = _fused_tables(
            layouts, infos, _sharded_job_hp, base_blocks=bases)
        interpret = self._interpret

        def cat(bufs):
            return jnp.concatenate(bufs) if len(bufs) > 1 else bufs[0]

        def apply(states, gs, counts):
            counts = [jnp.asarray(c, jnp.int32) for c in counts]
            efs = {}
            if compressed:
                gs = list(gs)
                for gi, si, kind, layout in compressed:
                    ef = efs.get(si)
                    if ef is None:
                        ef = states[si].get("ef")
                    if ef is None:  # snapshot predating the ef widening
                        ef = jnp.zeros_like(states[si]["flat"])
                    gs[gi], resid = ef_transform(
                        gs[gi], _gather_owned(layout, ef), kind)
                    efs[si] = _scatter_owned(layout, ef, resid)
                gs = tuple(gs)
            fleet = {k: cat([s[k] for s in states])
                     for k in ("flat", "mu", "nu")}
            new = _fused_state_update(
                fleet, gs, counts, block=block, block_idx=block_idx,
                job_sizes=job_sizes, hps=hps, interpret=interpret)
            return tuple(
                dict(st, flat=new["flat"][lo:lo + n],
                     mu=new["mu"][lo:lo + n], nu=new["nu"][lo:lo + n],
                     **({"ef": efs[i]} if i in efs else {}))
                for i, (st, lo, n) in enumerate(zip(states, offsets,
                                                    lens)))

        # Keeps the name ``apply``: a trace shows it as ``jit_apply``.
        return _Applier(apply, self._jit, (self.stats,))


