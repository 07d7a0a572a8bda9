"""Fault tolerance (PR 7): deterministic fault injection, snapshot-based
rollback recovery, per-lane quarantine, and shard-loss recovery.

Parity notes.  All recovered-vs-oracle comparisons run EAGER at
``max_staleness=0``: rollback replays the identical (piece, count)
sequence through the identical appliers, so a recovered trajectory must
match a fault-free twin bit for bit -- any divergence is a recovery bug,
not rounding.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ParameterService
from repro.ps import engine as engine_mod
from repro.ps.autoscaler import AutoscalerConfig, ElasticScaler
from repro.ps.faults import (
    HEALTHY,
    QUARANTINED,
    EngineQuarantinedError,
    FaultInjector,
    InjectedFault,
)
from repro.ps.service_runtime import (
    RecoveryReport,
    ServiceRuntime,
    ShardedServiceRuntime,
)


def _tree(key, sizes):
    ks = jax.random.split(key, len(sizes))
    return {f"t{i}": jax.random.normal(k, (n,))
            for i, (k, n) in enumerate(zip(ks, sizes))}


def _loss(params, batch):
    return sum(jnp.sum((params[k] - batch["target"][k]) ** 2)
               for k in params)


TREES = {
    "a": _tree(jax.random.PRNGKey(0), (48, 16, 32)),
    "b": _tree(jax.random.PRNGKey(1), (32, 16)),
    "c": _tree(jax.random.PRNGKey(2), (48, 16)),
}
TARGETS = {j: jax.tree_util.tree_map(lambda p: p * 0 + 1.0, t)
           for j, t in TREES.items()}


def _add_jobs(rt, trees=TREES):
    for jid, t in trees.items():
        nbytes = sum(4 * v.size for v in t.values())
        rt.add_job(jid, t, _loss, lr=0.05, required_servers=1,
                   agg_throughput=nbytes / 0.2)


def _flat(trees=TREES, **engine_opts):
    rt = ServiceRuntime(
        ParameterService(total_budget=16, n_clusters=1, plan_pad_to=16),
        jit=False)
    engine_opts.setdefault("max_staleness", 0)
    eng = rt.attach_engine(jit=False, **engine_opts)
    _add_jobs(rt, trees)
    return rt, eng


def _sharded(n_shards=3, trees=TREES, **engine_opts):
    svc = ParameterService(total_budget=16, n_clusters=1, plan_pad_to=16)
    rt = ShardedServiceRuntime(svc, jit=False)
    engine_opts.setdefault("max_staleness", 0)
    eng = rt.attach_engine(jit=False, **engine_opts)
    _add_jobs(rt, trees)
    if n_shards > 1:
        svc.scale_out(n_shards - 1)
    return rt, eng


def _drive(eng, n, trees=TREES):
    for _ in range(n):
        for j in trees:
            eng.step(j, {"target": TARGETS[j]})
    eng.drain()


def _assert_params_equal(rt_a, rt_b, jobs=TREES):
    for j in jobs:
        pa, pb = rt_a.params_of(j), rt_b.params_of(j)
        for k in pa:
            np.testing.assert_array_equal(np.asarray(pa[k]),
                                          np.asarray(pb[k]))


# --------------------------------------------------------------- injector
def test_injector_schedule_is_deterministic():
    def fire_points(inj):
        hits = []
        for i in range(1, 25):
            try:
                inj.on_apply("s0")
            except InjectedFault:
                hits.append(i)
        return hits

    a = FaultInjector(seed=3).random_apply_faults(4, ["s0"])
    b = FaultInjector(seed=3).random_apply_faults(4, ["s0"])
    assert [(r.kind, r.shard_id, r.at) for r in a.rules] == \
        [(r.kind, r.shard_id, r.at) for r in b.rules]
    assert fire_points(a) == fire_points(b)
    assert a.n_fired == len(a.log) > 0


def test_injector_rules_match_shard_and_occurrence():
    inj = FaultInjector()
    inj.fail_apply("s1", at=2)
    inj.on_apply("s1")  # occurrence 1: armed at 2, no fire
    inj.on_apply("s0")  # different lane: not even counted
    with pytest.raises(InjectedFault) as ei:
        inj.on_apply("s1")
    assert ei.value.kind == "fail_apply"
    assert ei.value.shard_id == "s1"
    assert ei.value.occurrence == 2
    inj.on_apply("s1")  # times=1: spent
    # kill = permanent
    inj.kill_shard("s0", at=1)
    for _ in range(3):
        with pytest.raises(InjectedFault):
            inj.on_apply("s0")
    # push rules return an action instead of raising
    inj.drop_push(job_id="a", at=1)
    assert inj.on_push("b") == "deliver"
    assert inj.on_push("a") == "drop"
    assert inj.on_push("a") == "deliver"
    inj.duplicate_push(job_id="a", at=1)
    assert inj.on_push("a") == "duplicate"


# ------------------------------------------------------ flat engine faults
def test_flat_transient_fault_recovers_bit_exact():
    inj = FaultInjector()
    # The first fault lands on a tick whose anchor the byte bound just
    # refreshed (nothing to replay), the second on the last tick, one
    # logged tick past the next such refresh.
    inj.fail_apply(at=4).fail_apply(at=8)
    rt, eng = _flat(snapshot_interval=4, fault_injector=inj)
    twin, teng = _flat(snapshot_interval=4)
    _drive(eng, 8)
    _drive(teng, 8)
    assert inj.n_fired == 2
    assert eng.stats.n_rollbacks >= 2
    assert eng.stats.n_replayed >= 2
    assert eng.stats.n_quarantines == 0
    assert eng.health == HEALTHY
    _assert_params_equal(rt, twin)


def test_flat_persistent_fault_quarantines_with_context():
    inj = FaultInjector()
    inj.kill_shard(None, at=3)  # the flat engine's single unnamed lane
    rt, eng = _flat(snapshot_interval=4, max_apply_retries=1,
                    fault_injector=inj)
    with pytest.raises(EngineQuarantinedError) as ei:
        _drive(eng, 6)
    err = ei.value
    assert eng.health == QUARANTINED
    assert err.shard_id is None
    assert err.tick >= 0
    assert set(err.job_ids) <= set(TREES)
    assert isinstance(err.original, InjectedFault)
    # Every subsequent tick/drain re-raises the SAME carried context.
    with pytest.raises(EngineQuarantinedError) as again:
        eng.tick()
    assert again.value is err
    with pytest.raises(EngineQuarantinedError):
        eng.drain()


def test_flat_eager_without_snapshots_reraises_original():
    inj = FaultInjector()
    inj.fail_apply(at=1)
    rt, eng = _flat(snapshot_interval=0, fault_injector=inj)
    # No snapshot to roll back to, eager buffers intact: the original
    # fault propagates (pre-PR-7 behavior minus the poisoning).
    with pytest.raises(InjectedFault):
        _drive(eng, 2)


# -------------------------------------------------- sharded engine faults
def test_sharded_transient_fault_fleet_falls_back_bit_exact():
    inj = FaultInjector()
    rt, eng = _sharded(fault_injector=inj, snapshot_interval=4)
    twin, teng = _sharded(snapshot_interval=4)
    victim = rt.shard_ids[-1]
    inj.fail_apply(victim, at=2)
    _drive(eng, 8)
    _drive(teng, 8)
    assert inj.n_fired == 1
    # The fused fleet launch cannot attribute the failure: it rolls every
    # participant back and replays per shard.
    assert eng.stats.n_fleet_fallbacks >= 1
    assert eng.stats.n_rollbacks >= 1
    assert eng.stats.n_quarantines == 0
    assert set(eng.shard_health().values()) == {HEALTHY}
    _assert_params_equal(rt, twin)


def test_fleet_launch_failing_every_tick_still_drains_bit_exact():
    """A fused program that fails on every launch while each lane's own
    program runs (one that no longer fits the device, say) falls back per
    shard on every tick, and no fallback undoes the per-shard progress of
    the one before: the queues drain, one piece per job per tick."""
    rt, eng = _sharded(snapshot_interval=4, max_staleness=8)
    twin, teng = _sharded(snapshot_interval=4, max_staleness=8)

    def out_of_memory(states, gs, counts):
        raise RuntimeError("RESOURCE_EXHAUSTED: the fused program")

    eng._build_fleet_applier = lambda key: engine_mod._Applier(
        out_of_memory, jit=False)
    n_steps = 6
    for e in (eng, teng):
        for _ in range(n_steps):
            for j in TREES:
                e.step(j, {"target": TARGETS[j]})
    for _ in range(3 * n_steps):
        if not eng.tick():
            break
    teng.drain()
    assert all(eng.outstanding(j) == 0 for j in TREES)
    s = eng.stats
    assert s.n_fleet_fallbacks == n_steps
    assert s.n_quarantines == 0
    _assert_params_equal(rt, twin)


def test_quarantine_isolates_one_lane_neighbors_tick_on():
    inj = FaultInjector()
    rt, eng = _sharded(fault_injector=inj)
    victim = rt.shard_ids[-1]
    inj.kill_shard(victim, at=2)
    with pytest.raises(EngineQuarantinedError) as ei:
        _drive(eng, 12)
    assert ei.value.shard_id == victim
    assert eng.shard_health()[victim] == QUARANTINED
    assert eng.quarantined_shards() == (victim,)
    # Jobs with no blocks on the dead shard keep training.
    untouched = [j for j in TREES
                 if victim not in rt.splan.job_layout(j).shard_ids]
    assert untouched, "placement left no job off the victim shard"
    before = eng.stats.n_applied
    for _ in range(4):
        for j in untouched:
            eng.step(j, {"target": TARGETS[j]})
    assert eng.stats.n_applied > before
    for sid, health in eng.shard_health().items():
        if sid != victim:
            assert health == HEALTHY
    # Engine-wide drain is blocked on the dead lane's queued pieces and
    # says WHICH lane, but a drain scoped to untouched jobs succeeds.
    with pytest.raises(EngineQuarantinedError) as de:
        eng.drain()
    assert de.value.shard_id == victim
    eng.drain(only=untouched)


def test_chaos_seeded_schedules_recover_bit_exact():
    # Property-style: seeded random transient schedules over the job mix
    # must always recover to the fault-free trajectory at s=0.
    for seed in range(4):
        inj = FaultInjector(seed=seed)
        rt, eng = _sharded(fault_injector=inj, snapshot_interval=4,
                           max_apply_retries=3)
        twin, teng = _sharded(snapshot_interval=4)
        inj.random_apply_faults(3, rt.shard_ids, max_at=15)
        _drive(eng, 10)
        _drive(teng, 10)
        assert eng.stats.n_quarantines == 0, f"seed {seed} quarantined"
        _assert_params_equal(rt, twin)
        if inj.n_fired:
            assert eng.stats.n_rollbacks >= 1


@pytest.mark.parametrize("mode", ["flat", "fused", "per_shard"])
def test_compile_error_propagates_with_queues_intact(mode):
    """A kernel the backend cannot compile is not an apply failure: the
    error leaves the tick, every push stays queued, and nothing is rolled
    back, retried or quarantined.  The Pallas kernel (``interpret=False``)
    refuses the 16-element block of these plans while it lowers."""
    svc = ParameterService(total_budget=16, n_clusters=1, plan_pad_to=16)
    if mode == "flat":
        rt = ServiceRuntime(svc, jit=False)
        eng = rt.attach_engine(interpret=False)
    else:
        rt = ShardedServiceRuntime(svc, jit=False)
        eng = rt.attach_engine(interpret=False, fleet_tick=mode)
    _add_jobs(rt)
    for j in TREES:
        eng.submit_push(j, TARGETS[j])
    with pytest.raises(ValueError, match="does not tile"):
        eng.tick()
    assert all(eng.outstanding(j) == 1 for j in TREES)
    s = eng.stats
    assert s.n_fleet_fallbacks == s.n_rollbacks == s.n_quarantines == 0
    assert s.n_applied == 0


# ----------------------------------------------------- push-piece faults
def test_dropped_piece_times_out_push_future():
    inj = FaultInjector()
    rt, eng = _sharded(fault_injector=inj, max_staleness=8)
    job = "a"
    inj.drop_push(job_id=job, at=1)
    grads = jax.tree_util.tree_map(jnp.ones_like, TREES[job])
    fut = eng.submit_push(job, grads)
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        fut.result(timeout=0.2)
    assert time.monotonic() - t0 < 5.0
    assert not fut.done()


def test_duplicate_piece_applies_untracked():
    inj = FaultInjector()
    rt, eng = _sharded(fault_injector=inj, max_staleness=8)
    job = "a"
    inj.duplicate_push(job_id=job, at=1)
    grads = jax.tree_util.tree_map(jnp.ones_like, TREES[job])
    fut = eng.submit_push(job, grads)
    step = fut.result()
    assert step == 1
    applied_before = eng.stats.n_applied
    eng.drain()  # the duplicate is an extra untracked piece
    assert eng.stats.n_applied >= applied_before
    assert not any(q for lane in eng._lanes.values()
                   for q in lane.queues.values())


# -------------------------------------------------- shard-loss recovery
def test_recover_shard_rehosts_and_training_continues():
    inj = FaultInjector()
    rt, eng = _sharded(fault_injector=inj, snapshot_interval=4)
    victim = rt.shard_ids[-1]
    inj.kill_shard(victim, at=2)
    with pytest.raises(EngineQuarantinedError):
        _drive(eng, 10)
    n_before = rt.n_shards
    report = rt.recover_shard(victim)
    assert isinstance(report, RecoveryReport)
    assert report.shard_id == victim
    assert report.seeded_from == "snapshot"
    assert report.moved_tasks >= 1
    assert report.rehosted_elements > 0
    assert rt.n_shards == n_before - 1
    assert victim not in rt.shard_ids
    assert victim not in eng._lanes
    # The rollback window is bounded: at most snapshot_interval ticks of
    # pushes, and at most a replay log as large as the lane's snapshot,
    # were discarded or cancelled with the lane.
    assert (report.rolled_back_pushes + report.cancelled_pushes
            <= 4 * len(TREES) + len(TREES))
    # The fleet is whole again: every job trains and drains.
    _drive(eng, 3)
    assert set(eng.shard_health().values()) == {HEALTHY}


def test_recover_healthy_shard_is_a_lossless_decommission():
    rt, eng = _sharded()
    _drive(eng, 4)
    params_before = {j: rt.params_of(j) for j in TREES}
    victim = rt.shard_ids[-1]
    report = rt.recover_shard(victim)
    assert report.seeded_from == "live"
    assert report.rolled_back_pushes == 0
    assert report.cancelled_pushes == 0
    for j in TREES:
        after = rt.params_of(j)
        for k in after:
            np.testing.assert_array_equal(np.asarray(after[k]),
                                          np.asarray(params_before[j][k]))
    _drive(eng, 2)


def test_recover_shard_unknown_id_raises():
    rt, _ = _sharded()
    with pytest.raises(ValueError, match="unknown shard"):
        rt.recover_shard("nope/agg9")


# ------------------------------------------- rollback log bounded by bytes
def _engine(kind, **engine_opts):
    return (_flat if kind == "flat" else _sharded)(**engine_opts)


def _anchors(rt, eng):
    """(stats, replay log, state) of each lane: the flat engine's one
    unnamed lane, or each shard space's."""
    if kind_of(eng) == "flat":
        return {None: (eng.stats, eng._snapshot_log, rt.state)}
    return {sid: (lane.stats, lane.log, rt.states[sid])
            for sid, lane in eng._lanes.items()}


def kind_of(eng):
    return "flat" if isinstance(eng, engine_mod.ServiceTickEngine) \
        else "sharded"


def _nbytes(log, state):
    return (sum(entry[1].nbytes for entry in log),
            sum(4 * x.size for x in jax.tree_util.tree_leaves(state)))


@pytest.mark.parametrize("kind", ["flat", "sharded"])
def test_replay_log_never_outgrows_its_snapshot(kind):
    """Every job pushes its whole space each tick, a third of the
    (flat, mu, nu) state: the log reaches the state's bytes in three
    ticks, and the anchor refreshes there, long before the interval."""
    rt, eng = _engine(kind, snapshot_interval=8)
    n_rounds, largest = 12, 0
    for _ in range(n_rounds):
        _drive(eng, 1)
        for _, log, state in _anchors(rt, eng).values():
            logged, held = _nbytes(log, state)
            assert logged <= held
            largest = max(largest, logged / held)
    s = eng.stats
    assert s.n_ticks == n_rounds
    assert 0 < s.n_snapshots_by_bytes <= s.n_snapshots < n_rounds * len(
        _anchors(rt, eng))
    assert largest > 0.5  # the log does fill up to its bound
    assert rt.debug_stats()["engine"]["n_snapshots_by_bytes"] == \
        s.n_snapshots_by_bytes


@pytest.mark.parametrize("kind", ["flat", "sharded"])
def test_small_pieces_keep_the_tick_interval(kind):
    """One job of three pushes: its pieces stay small against the space,
    so the anchor refreshes every snapshot_interval ticks, as before."""
    rt, eng = _engine(kind, **({} if kind == "flat" else {"n_shards": 1}),
                      snapshot_interval=4)
    for t in range(1, 11):
        eng.step("b", {"target": TARGETS["b"]})
        eng.drain()
        assert eng.stats.n_ticks == t
        assert eng.stats.n_snapshots == (t + 3) // 4  # ticks 1, 5, 9
    assert eng.stats.n_snapshots_by_bytes == 0
    for _, log, state in _anchors(rt, eng).values():
        logged, held = _nbytes(log, state)
        assert 0 < logged <= held // 3


@pytest.mark.parametrize("after", [0, 1])
@pytest.mark.parametrize("kind", ["flat", "sharded"])
def test_fault_after_byte_refresh_recovers_bit_exact(kind, after):
    """A fault on the launch of the tick whose anchor the byte bound
    refreshed (``after`` 0: nothing logged yet) or of the tick after it
    (1: one tick to replay) restores that anchor and lands on the
    fault-free twin bit for bit."""
    twin, teng = _engine(kind, snapshot_interval=8)
    lane = None if kind == "flat" else twin.shard_ids[-1]
    first = None
    for t in range(1, 9):
        _drive(teng, 1)
        if first is None and _anchors(twin, teng)[lane][0].\
                n_snapshots_by_bytes:
            first = t
    assert first is not None and first < 8
    inj = FaultInjector()
    inj.fail_apply(lane, at=first + after)
    rt, eng = _engine(kind, snapshot_interval=8, fault_injector=inj)
    _drive(eng, 8)
    assert inj.n_fired == 1
    assert eng.stats.n_rollbacks >= 1
    assert eng.stats.n_quarantines == 0
    assert _anchors(rt, eng)[lane][0].n_snapshots_by_bytes >= 1
    _assert_params_equal(rt, twin)


@pytest.mark.parametrize("kind", ["flat", "sharded"])
def test_snapshot_span_names_the_rule_that_fired(kind, monkeypatch):
    rules = []
    real = engine_mod.span

    def record(name, **meta):
        if name == "ps.snapshot":
            rules.append(meta["by"])
        return real(name, **meta)

    monkeypatch.setattr(engine_mod, "span", record)
    rt, eng = _engine(kind, snapshot_interval=8)
    _drive(eng, 8)
    assert rules[0] == "ticks"  # the first anchor
    assert len(rules) == eng.stats.n_snapshots
    assert rules.count("bytes") == eng.stats.n_snapshots_by_bytes > 0


# --------------------------------------------------- scaler + migration
def test_autoscaler_holds_on_quarantined_fleet():
    inj = FaultInjector()
    rt, eng = _sharded(fault_injector=inj)
    victim = rt.shard_ids[-1]
    scaler = ElasticScaler(rt, AutoscalerConfig(
        shard_capacity=1.0, max_shards=8, cooldown=1))
    inj.kill_shard(victim, at=1)
    with pytest.raises(EngineQuarantinedError):
        _drive(eng, 8)
    n_before = rt.n_shards
    decision = scaler.observe()  # load >> capacity, would grow
    assert decision.quarantined == (victim,)
    assert decision.action == "hold"
    assert rt.n_shards == n_before
    # Recovered fleet scales again.
    rt.recover_shard(victim)
    _drive(eng, 4)
    decision = scaler.observe()
    assert decision.quarantined == ()
    assert decision.action == "grow"


def test_migration_fault_hook_fires_on_replan():
    """A migration fault during a replan no longer escapes: the replan
    transaction (PR 9) rolls the registry back and retries, so the
    scale-out SUCCEEDS and both planes agree on the new layout."""
    inj = FaultInjector()
    rt, eng = _sharded(n_shards=2, fault_injector=inj)
    inj.fail_migration(at=1)
    assert rt.service.scale_out(1) == 1
    assert inj.n_fired == 1
    assert inj.log[0]["kind"] == "fail_migration"
    assert rt.service.n_replan_aborts == 1
    assert rt.service.n_replan_retries == 1
    assert rt.service.compile_sharded_plan() == rt.splan
    assert rt.n_shards == 3


def test_checkpoint_records_shard_health(tmp_path):
    from repro.checkpoint.checkpoint import load_aux

    rt, eng = _sharded(n_shards=2)
    _drive(eng, 2)
    rt.save_checkpoint(tmp_path, step=1)
    aux = load_aux(tmp_path, 1)
    assert aux["shard_health"] == {sid: HEALTHY for sid in rt.shard_ids}


# --------------------------------------------- compressed-push faults (PR 8)
def _sharded_mixed(n_shards=3, compressed=("a",), **engine_opts):
    """Sharded fleet with a MIX of compressed and plain jobs."""
    svc = ParameterService(total_budget=16, n_clusters=1, plan_pad_to=16)
    rt = ShardedServiceRuntime(svc, jit=False)
    engine_opts.setdefault("max_staleness", 0)
    eng = rt.attach_engine(jit=False, **engine_opts)
    for jid, t in TREES.items():
        nbytes = sum(4 * v.size for v in t.values())
        rt.add_job(jid, t, _loss, lr=0.05, required_servers=1,
                   agg_throughput=nbytes / 0.2,
                   **({"push_compression": "int8"}
                      if jid in compressed else {}))
    if n_shards > 1:
        svc.scale_out(n_shards - 1)
    return rt, eng


def test_rollback_restores_ef_buffer_bit_exact():
    """The error-feedback buffer lives in the lane's donated state, so a
    snapshot rollback restores it with flat/mu/nu: a compressed job
    recovered via replay matches a fault-free compressed twin at s=0 --
    params AND the residual itself, bit for bit."""
    inj = FaultInjector(seed=5)
    rt, eng = _sharded_mixed(fault_injector=inj, snapshot_interval=4)
    twin, teng = _sharded_mixed(snapshot_interval=4)
    victim = rt.splan.job_layout("a").shard_ids[0]  # hosts the EF rows
    inj.fail_apply(victim, at=3).fail_apply(victim, at=8)

    _drive(eng, 12)
    _drive(teng, 12)

    assert inj.n_fired >= 1
    assert eng.stats.n_rollbacks >= 1
    assert eng.stats.n_quarantines == 0
    _assert_params_equal(rt, twin)
    for sid in rt.states:
        st, tw = rt.states[sid], twin.states[sid]
        assert ("ef" in st) == ("ef" in tw)
        if "ef" in st:
            np.testing.assert_array_equal(np.asarray(st["ef"]),
                                          np.asarray(tw["ef"]))


# ------------------------------------------- versioned pulls under faults
def test_versioned_pull_after_rollback_restamp_patches_to_full():
    """A rollback replay re-stamps every replayed block (PR 8), so a
    client vector held from BEFORE the fault sees exactly the replayed
    blocks in its next diff -- never a silently-skipped stale block:
    patching the held payload must land on a fresh full pull bit for
    bit, and a job that never stepped stays an empty diff."""
    inj = FaultInjector()
    rt, eng = _flat(snapshot_interval=2, fault_injector=inj)
    for _ in range(3):  # only a and b move; c's blocks never stamp
        for j in ("a", "b"):
            eng.step(j, {"target": TARGETS[j]})
    eng.drain()
    held = {j: eng.pull(j, since_version=0) for j in TREES}
    inj.fail_apply(at=1)  # rules count from arming: the NEXT apply dies
    for j in ("a", "b"):
        eng.step(j, {"target": TARGETS[j]})
    eng.drain()
    assert inj.n_fired == 1
    assert eng.stats.n_rollbacks >= 1
    da = eng.pull("a", since_version=held["a"].version)
    assert not da.full and da.block_ids.size > 0
    for j in ("a", "b"):
        d = (da if j == "a"
             else eng.pull(j, since_version=held[j].version))
        fresh = eng.pull(j, since_version=0)
        np.testing.assert_array_equal(
            np.asarray(d.apply(held[j].data)), np.asarray(fresh.data))
    dc = eng.pull("c", since_version=held["c"].version)
    assert not dc.full and dc.block_ids.size == 0


def test_versioned_pull_against_quarantined_lane_raises():
    """Direct versioned pulls die with the hosting lane (the read tier's
    replicas are the degraded-serving path); jobs off the dead shard
    keep serving diffs."""
    inj = FaultInjector()
    rt, eng = _sharded(fault_injector=inj)
    victim = rt.shard_ids[-1]
    inj.kill_shard(victim, at=2)
    with pytest.raises(EngineQuarantinedError):
        _drive(eng, 12)
    hosted = [j for j in TREES
              if victim in rt.splan.job_layout(j).shard_ids]
    spared = [j for j in TREES
              if victim not in rt.splan.job_layout(j).shard_ids]
    assert hosted and spared, "placement left nothing to compare"
    with pytest.raises(EngineQuarantinedError) as ei:
        eng.pull(hosted[0], since_version=0)
    assert ei.value.shard_id == victim
    with pytest.raises(EngineQuarantinedError):
        eng.pull(hosted[0])  # the plain tree pull dies the same way
    d = eng.pull(spared[0], since_version=0)
    assert d.full and d.bytes_full > 0


def test_chaos_mixed_compression_stays_quarantine_free():
    """Seeded chaos over a mixed compressed/plain job fleet: transient
    schedules must recover in place (no lane quarantined) and land on
    the fault-free mixed twin bit for bit."""
    for seed in (1, 3):
        inj = FaultInjector(seed=seed)
        rt, eng = _sharded_mixed(fault_injector=inj, snapshot_interval=4,
                                 max_apply_retries=3)
        twin, teng = _sharded_mixed(snapshot_interval=4)
        inj.random_apply_faults(3, rt.shard_ids, max_at=15)
        _drive(eng, 10)
        _drive(teng, 10)
        assert eng.stats.n_quarantines == 0, f"seed {seed} quarantined"
        assert all(lane.health == HEALTHY
                   for lane in eng._lanes.values())
        _assert_params_equal(rt, twin)
        if inj.n_fired:
            assert eng.stats.n_rollbacks >= 1
