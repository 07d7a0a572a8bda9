"""Host spans, stable program names and the compile counter of the tick
engines (``repro.ps.spans``, ``repro.ps.engine``), at tiny sizes on the
CPU."""

import glob
import re

import jax
import numpy as np
import pytest

from repro.core import ParameterService
from repro.ps import engine as E
from repro.ps import spans
from repro.ps.service_runtime import ServiceRuntime, ShardedServiceRuntime


def _tree(key, sizes):
    ks = jax.random.split(key, len(sizes))
    return {f"t{i}": jax.random.normal(k, (n,))
            for i, (k, n) in enumerate(zip(ks, sizes))}


def _loss(params, batch):
    raise NotImplementedError("these tests push gradients")


TREES = {"a": _tree(jax.random.PRNGKey(0), (48, 16, 32)),
         "b": _tree(jax.random.PRNGKey(1), (32, 16)),
         "c": _tree(jax.random.PRNGKey(2), (16,))}
GRADS = {j: jax.tree_util.tree_map(lambda p: 0.01 * p + 0.001, t)
         for j, t in TREES.items()}


def _runtime(kind, **engine):
    svc = ParameterService(total_budget=16, n_clusters=1, plan_pad_to=16)
    rt = (ShardedServiceRuntime if kind == "sharded" else ServiceRuntime)(svc)
    eng = rt.attach_engine(max_staleness=0, **engine)
    for jid, t in TREES.items():
        nbytes = sum(4 * v.size for v in t.values())
        rt.add_job(jid, t, _loss, lr=0.05, required_servers=1,
                   agg_throughput=nbytes / 0.2)
    return rt, eng


def _round(eng, jobs=tuple(TREES)):
    """One push of each job, one tick, one pull of each job."""
    futs = [eng.submit_push(j, GRADS[j]) for j in jobs]
    eng.tick()
    assert all(f.done() for f in futs)
    return {j: eng.pull(j) for j in jobs}


@pytest.fixture
def spans_on():
    spans.enable(True)
    try:
        yield
    finally:
        spans.enable(False)


@pytest.mark.parametrize("kind", ["flat", "sharded"])
def test_spans_off_build_no_annotation(kind, monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("a span was recorded while spans are off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert spans.span("ps.tick", tick=1) is spans.NO_SPAN
    rt, eng = _runtime(kind)
    pulled = _round(eng)
    _round(eng)
    assert set(pulled) == set(TREES)
    assert eng.stats.n_ticks == 2


def _host_spans(trace_dir):
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert len(paths) == 1, paths
    data = jax.profiler.ProfileData.from_file(paths[0])
    out = []
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats))
                        for e in line.events if e.name.startswith("ps.")]
    return sorted(out, key=lambda s: s[1])


@pytest.mark.parametrize("kind", ["flat", "sharded"])
def test_spans_on_land_in_the_profiler_trace(kind, spans_on, tmp_path):
    rt, eng = _runtime(kind)
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(_round(eng))
    found = _host_spans(tmp_path)
    names = {n for n, *_ in found}
    assert {"ps.push", "ps.tick", "ps.launch", "ps.pull", "ps.compile",
            "ps.snapshot"} <= names, names
    pushes = [m for n, _, _, m in found if n == "ps.push"]
    assert sorted(m["job"] for m in pushes) == sorted(TREES)
    assert all(m["step"] == 1 for m in pushes)
    assert sorted(m["job"] for n, _, _, m in found
                  if n == "ps.pull") == sorted(TREES)
    # The first anchor of each lane is taken by the tick rule.
    assert all(m["by"] == "ticks" for n, _, _, m in found
               if n == "ps.snapshot")
    (tick,) = [s for s in found if s[0] == "ps.tick"]
    assert tick[3]["tick"] == 0 and tick[3]["pieces"] >= len(TREES)
    launches = [s for s in found if s[0] == "ps.launch"]
    assert launches and all(tick[1] <= s[1] and s[2] <= tick[2]
                            for s in launches)


def _lowered(monkeypatch, kind, fleet_tick):
    """Lowered text of every program one round of ``kind`` runs."""
    texts = []
    compiled = E._Applier.compiled

    def spy(self, *args):
        texts.append(self._fn.lower(*args).as_text())
        return compiled(self, *args)

    opts = {"fleet_tick": fleet_tick} if kind == "sharded" else {}
    rt, eng = _runtime(kind, **opts)
    monkeypatch.setattr(E._Applier, "compiled", spy)
    _round(eng)
    monkeypatch.undo()
    for j in TREES:
        texts.append(eng._pack_fns[j].lower(GRADS[j]).as_text())
        flats = (rt.state["flat"] if kind == "flat" else
                 tuple(rt.states[s]["flat"]
                       for s in rt.splan.job_layout(j).shard_ids))
        texts.append(eng._pull_fns[j].lower(flats).as_text())
    state = rt.state if kind == "flat" else next(iter(rt.states.values()))
    texts.append(E.state_copy.lower(state).as_text())
    return texts


@pytest.mark.parametrize("kind,fleet_tick,program", [
    ("sharded", "fused", "jit_push_pack"),
    ("sharded", "fused", "jit_pull_gather"),
    ("sharded", "fused", "jit_state_copy"),
    ("sharded", "fused", "jit_apply"),
    ("sharded", "per_shard", "jit_lane_apply"),
    ("flat", "fused", "jit_flat_apply"),
])
def test_programs_carry_stable_names(kind, fleet_tick, program,
                                     monkeypatch):
    names = [re.search(r"module @(\w+)", t).group(1)
             for t in _lowered(monkeypatch, kind, fleet_tick)]
    assert program in names, names
    assert "jit_fn" not in names
    # Only the fused fleet tick is named jit_apply.
    assert ("jit_apply" in names) == (kind == "sharded"
                                      and fleet_tick == "fused")


def test_state_copy_snapshot_survives_the_donated_apply():
    rt, eng = _runtime("sharded")
    before = {sid: jax.device_get(st) for sid, st in rt.states.items()}
    _round(eng)
    for sid, lane in eng._lanes.items():
        assert lane.snapshot is not None
        for k, x in lane.snapshot.items():
            assert not x.is_deleted()
            np.testing.assert_array_equal(np.asarray(x), before[sid][k])
        assert not np.array_equal(np.asarray(rt.states[sid]["flat"]),
                                  before[sid]["flat"])
    # The copy holds buffers of its own: donating the state it was taken
    # from deletes that state and leaves the copy whole.
    state = rt.states[next(iter(eng._lanes))]
    # (read through a copy: a host view of the state itself would pin its
    # buffers against donation)
    want = {k: np.asarray(x + 0) for k, x in state.items()}
    snap = E.state_copy(state)
    step = jax.jit(lambda st: jax.tree_util.tree_map(lambda x: x + 1, st),
                   donate_argnums=0)
    jax.block_until_ready(step(state))
    assert all(x.is_deleted() for x in jax.tree_util.tree_leaves(state))
    for k, x in snap.items():
        assert not x.is_deleted()
        np.testing.assert_array_equal(np.asarray(x), want[k])


@pytest.mark.parametrize("fleet_tick", ["fused", "per_shard"])
def test_applier_compiles_count_new_patterns_only(fleet_tick):
    rt, eng = _runtime("sharded", fleet_tick=fleet_tick)
    lanes = lambda: sum(l.stats.n_applier_compiles  # noqa: E731
                        for l in eng._lanes.values())
    _round(eng)
    n_lanes = len(eng._lanes)
    appliers = 1 if fleet_tick == "fused" else n_lanes
    # one applier per pending pattern, one pack and one pull per job
    assert eng.stats.n_applier_compiles == appliers + 2 * len(TREES)
    assert lanes() == (0 if fleet_tick == "fused" else n_lanes)
    first = eng.stats.n_applier_compiles
    _round(eng)
    assert eng.stats.n_applier_compiles == first  # every cache hit
    _round(eng, jobs=("c",))
    hosting = len(rt.splan.job_layout("c").shard_ids)
    assert eng.stats.n_applier_compiles == first + (
        1 if fleet_tick == "fused" else hosting)
    stats = rt.debug_stats()
    assert stats["engine"]["n_applier_compiles"] == \
        eng.stats.n_applier_compiles
    assert sum(s["n_applier_compiles"] for s in stats["shards"].values()) \
        == lanes()


def test_flat_engine_counts_its_compiles():
    rt, eng = _runtime("flat")
    _round(eng)
    first = eng.stats.n_applier_compiles
    assert first == 1 + 2 * len(TREES)
    _round(eng)
    assert eng.stats.n_applier_compiles == first
    assert rt.debug_stats()["engine"]["n_applier_compiles"] == first
