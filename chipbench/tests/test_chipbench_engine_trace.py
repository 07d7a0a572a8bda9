"""CPU tests of the readings taken from the tick engine's own names: its
programs (``jit_push_pack``, ``jit_pull_gather``, ``jit_state_copy``) and
its ``ps.*`` host spans (``chipbench/engine_trace.py``, and their
reduction in ``chipbench/trace.py``)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import engine_trace as ET  # noqa: E402
from chipbench import run as R  # noqa: E402
from chipbench import trace  # noqa: E402
from chipbench.harness import Run  # noqa: E402
from chipbench.readers import FLEET_PROGRAM  # noqa: E402
from test_chipbench_arith import ev, synthetic  # noqa: E402

OUT = trace.OUTSIDE
READERS = ("pull.device_ms.saturate", "push.device_ms.saturate",
           "engine.state_copy_ms.saturate")


def engine_spans():
    """The engine's spans over the synthetic window of
    test_chipbench_arith: a push, a tick that falls back, and a pull.

    0-9 ps.push (a 1-4 ps.compile inside); 10-50 ps.tick: 11-13
    ps.snapshot, 14-18 a failed ps.launch, 20-48 ps.fallback (21-25
    ps.rollback, 26-46 ps.lane_tick with a 28-44 ps.launch); 55-95
    ps.pull."""
    return [ev("ps.push", 0, 9), ev("ps.compile", 1, 3),
            ev("ps.tick", 10, 40), ev("ps.snapshot", 11, 2),
            ev("ps.launch", 14, 4), ev("ps.fallback", 20, 28),
            ev("ps.rollback", 21, 4), ev("ps.lane_tick", 26, 20),
            ev("ps.launch", 28, 16), ev("ps.pull", 55, 40)]


def _run(device, n_ticks=2):
    run = Run(cell="c", config={}, traffic={}, seconds=0.1, t0=0.0,
              t_close=1.0, device_kind="TPU v5 lite")
    run.trace = trace.summarize([device], synthetic()[1])
    run.spans = ([("engine.tick", 0.1 * i, 0.1 * i + 0.01)
                  for i in range(n_ticks)]
                 + [("engine.tick", 5.0, 5.1)])  # after the window
    return run


def named_device():
    device, _ = synthetic()
    device = dict(device)
    device[trace.MODULES_LINE] = [
        ev(FLEET_PROGRAM + "(7)", 10, 20), ev("jit_push_pack(3)", 2, 2),
        ev("jit_push_pack(3)", 5, 4), ev("jit_pull_gather(5)", 35, 5),
        ev("jit_state_copy(4)", 30, 1), ev("jit_state_copy(4)", 82, 2),
        ev("jit_pull_gather(5)", 150, 5)]  # outside the window
    return device


def test_program_readers():
    run = _run(named_device())
    assert R.reader("push.device_ms.saturate")(run) == pytest.approx(3.0)
    assert R.reader("pull.device_ms.saturate")(run) == pytest.approx(5.0)
    # 3 ms of copies over the window's two ticks
    assert R.reader("engine.state_copy_ms.saturate")(run) == \
        pytest.approx(1.5)


def test_device_time_by_program():
    got = ET.device_by_program(_run(named_device()).trace.modules)
    assert list(got)[0] == FLEET_PROGRAM
    assert got == {FLEET_PROGRAM: (1, pytest.approx(0.020)),
                   "jit_push_pack": (2, pytest.approx(0.006)),
                   "jit_pull_gather": (1, pytest.approx(0.005)),
                   "jit_state_copy": (2, pytest.approx(0.003))}


def test_state_copy_reads_zero_where_ticks_copied_nothing():
    device = named_device()
    device[trace.MODULES_LINE] = [
        e for e in device[trace.MODULES_LINE]
        if not e.name.startswith(ET.COPY_PROGRAM)]
    assert R.reader("engine.state_copy_ms.saturate")(_run(device)) == 0.0


def test_program_readers_read_nothing_without_the_names():
    """A program whose pushes and pulls run as jit_fn (as before the
    engine named them) gives no reading, nor does an untraced run."""
    for name in READERS:
        assert R.reader(name)(_run(synthetic()[0])) is None, name
        assert R.reader(name)(Run(cell="c", config={}, traffic={},
                                  seconds=1.0)) is None, name
    # named, but no tick in the window
    assert R.reader("engine.state_copy_ms.saturate")(
        _run(named_device(), n_ticks=0)) is None


def test_self_time_is_time_less_direct_children():
    got = ET.host_by_span(engine_spans())
    assert got["ps.tick"] == (1, pytest.approx(0.040), pytest.approx(0.006))
    assert got["ps.fallback"][2] == pytest.approx(0.004)
    assert got["ps.lane_tick"][2] == pytest.approx(0.004)
    assert got["ps.launch"] == (2, pytest.approx(0.020), pytest.approx(0.020))
    assert got["ps.push"][2] == pytest.approx(0.006)
    assert ET.tick_self_ms(engine_spans()) == pytest.approx(6.0)
    assert ET.tick_self_ms([]) is None


def test_fallback_host_ms_per_tick():
    spans = engine_spans()
    # the 28 ms fallback and the 4 ms failed launch, over one tick
    assert ET.fallback_ms_per_tick(spans) == pytest.approx(32.0)
    # a second tick that did not fall back halves it
    assert ET.fallback_ms_per_tick(spans + [ev("ps.tick", 60, 5)]) == \
        pytest.approx(16.0)
    plain = [ev("ps.tick", 0, 10), ev("ps.launch", 2, 5)]
    assert ET.fallback_ms_per_tick(plain) == 0.0
    assert ET.fallback_ms_per_tick([]) is None


def test_idle_goes_to_the_innermost_span_or_outside():
    ms = 1e6
    idle = [(0, 12 * ms), (33 * ms, 35 * ms), (40 * ms, 62 * ms),
            (80 * ms, 100 * ms), (120 * ms, 130 * ms)]
    got = trace.idle_by_span(idle, engine_spans())
    assert got == pytest.approx({
        "ps.push": 0.006, "ps.compile": 0.003, OUT: 0.021,
        "ps.tick": 0.003, "ps.snapshot": 0.001,
        "ps.launch": 0.006, "ps.lane_tick": 0.002, "ps.fallback": 0.002,
        "ps.pull": 0.022})
    assert sum(got.values()) == pytest.approx(0.066)
    assert trace.engine_idle_pct(got, 0.1) == pytest.approx(45.0)
    assert trace.idle_by_span(idle, []) == pytest.approx({OUT: 0.066})


def test_idle_by_the_program_executing_over_it():
    ms = 1e6
    idle = [(0, 12 * ms), (33 * ms, 35 * ms), (40 * ms, 62 * ms)]
    got = ET.idle_by_program(idle, named_device())
    # jit_push_pack runs 2-4 and 5-9, jit_apply 10-30, jit_state_copy
    # 30-31, jit_pull_gather 35-40
    assert got == pytest.approx({"jit_push_pack": 0.006, "jit_apply": 0.002,
                                 OUT: 0.028})
    assert ET.idle_by_program(idle, {}) == pytest.approx({OUT: 0.036})


def test_device_idle_matches_the_summary():
    device, host = synthetic()
    s = trace.summarize([device], host)
    idle = trace.device_idle(device, 0, 100e6)
    assert sum(b - a for a, b in idle) / 1e9 == pytest.approx(
        s.window_s - s.busy_s)


def test_engine_spans_leave_the_host_attribution_as_it_was():
    device, host = synthetic()
    before = trace.summarize([device], host)
    after = trace.summarize([device], host + engine_spans())
    assert after.idle_by_host == before.idle_by_host == pytest.approx({
        "engine.tick": 0.012, "host.other": 0.002, "client.wait": 0.022,
        "engine.pull": 0.020})


@pytest.mark.parametrize("chips", [1, 2])
def test_engine_span_readers(chips):
    """The readers of the engine's spans in a traced run: the tick's self
    time, and the share of the window's chip-seconds idle under an open
    ``ps.*`` span (45 ms of the synthetic 100 ms window on each chip)."""
    device, host = synthetic()
    run = _run(device)
    run.trace = trace.summarize([device] * chips, host + engine_spans())
    assert run.trace.idle_by_span == pytest.approx(
        {k: chips * v for k, v in trace.idle_by_span(
            trace.device_idle(device, 0, 100e6), engine_spans()).items()})
    assert R.reader("engine.tick_self_ms.saturate")(run) == \
        pytest.approx(6.0)
    assert R.reader("device.idle_engine_pct.saturate")(run) == \
        pytest.approx(45.0)
    run.trace = trace.summarize([device] * chips, host)
    assert run.trace.spans == [] and run.trace.idle_by_span == {}
    for name in ("engine.tick_self_ms.saturate",
                 "device.idle_engine_pct.saturate"):
        assert R.reader(name)(run) is None, name
        assert R.reader(name)(Run(cell="c", config={}, traffic={},
                                  seconds=1.0)) is None, name


def test_applier_compiles_reads_the_window_counters():
    run = Run(cell="c", config={}, traffic={}, seconds=1.0)
    read = R.reader("engine.applier_compiles.cadence")
    assert read(run) is None
    run.counters = {"n_ticks": 9, "window": {"n_applier_compiles": 3,
                                             "n_ticks": 4}}
    assert read(run) == 3


@pytest.mark.parametrize("spans_on", [True, False])
def test_command_reads_the_engine_spans_on_the_cpu(spans_on):
    """The command's path at a tiny size: off the TPU the trace has no
    device plane, so only the host readings come back."""
    import time

    from tiny_cells import SEED, bench, tiny

    out = ET.run_spans("testbed-2s2w.saturate", SEED, 0.5, spans_on,
                       bench=bench(), cfg=tiny("testbed-2s2w.saturate"),
                       require_tpu=False, t_start=time.perf_counter())
    assert out["updates_per_s"] > 0
    assert out["engine.applier_compiles"] == 0
    assert out["pull.device_ms"] is None and out["idle_by_span"] == {}
    assert out["device_by_program"] == {}
    if spans_on:
        assert {"ps.push", "ps.tick", "ps.launch", "ps.pull",
                "ps.snapshot"} <= set(out["host_by_span"])
        assert 0 < out["engine.tick_self_ms"] < out["engine.tick_host_ms"]
        assert out["engine.fallback_host_ms"] == 0.0
    else:
        assert out["host_by_span"] == {}
        assert out["engine.tick_self_ms"] is None
