"""CPU tests of the benchmark's yardstick: the trace reduction, the
required-bytes and roofline arithmetic, the peak table, BENCHMARK.json's
references to its files, and the command's refusal to run off the chip."""

import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import roofline, trace  # noqa: E402
from chipbench import run as R  # noqa: E402
from chipbench.harness import Run  # noqa: E402
from chipbench.readers import AGG_ADAM_KERNEL, FLEET_PROGRAM  # noqa: E402

MS = 1e6  # ns


def ev(name, start_ms, dur_ms):
    return trace.Event(name, start_ms * MS, dur_ms * MS)


def synthetic():
    """A 100 ms window: two fleet-tick executions of 20 ms, each holding
    a 12 ms kernel and a 6 ms copy, one 5 ms pull gather and one DMA."""
    device = {
        trace.MODULES_LINE: [ev(FLEET_PROGRAM + "(7)", 10, 20),
                             ev("jit_fn(9)", 35, 5),
                             ev(FLEET_PROGRAM + "(7)", 60, 20),
                             ev(FLEET_PROGRAM + "(7)", 150, 20)],
        trace.OPS_LINE: [ev(f"%{AGG_ADAM_KERNEL}.1 = f32[8] custom-call()",
                            12, 12),
                         # names its operand after the kernel's output
                         ev(f"%copy.1 = f32[8] copy(%{AGG_ADAM_KERNEL}.1)",
                            24, 6),
                         ev("gather", 35, 5),
                         ev(AGG_ADAM_KERNEL, 62, 12), ev("copy.1", 74, 6),
                         ev(AGG_ADAM_KERNEL, 152, 12)],
        # a DMA that outlasts the first execution's copy by 3 ms
        trace.ASYNC_OPS_LINE: [ev("%copy-start.2 = copy-start()", 28, 5)],
    }
    host = [ev("window", 0, 100), ev("engine.tick", 0, 9),
            ev("client.wait", 40, 20), ev("engine.pull", 80, 20),
            ev("engine.tick", 200, 5)]
    return device, host


def summary(device, host):
    """The summary of one chip's plane."""
    return trace.summarize([device], host)


def test_summary_busy_idle_and_host_attribution():
    s = summary(*synthetic())
    assert s.window_s == pytest.approx(0.1)
    # Busy: the union 12-33, 35-40 and 62-80 ms.
    assert s.busy_s == pytest.approx(0.044)
    assert s.idle_pct == pytest.approx(56.0)
    # Idle 0-12 (under the tick span), 33-35 (no span), 40-62 (mostly
    # the wait), 80-100 (the pull).
    assert s.idle_by_host == pytest.approx({
        "engine.tick": 0.012, "host.other": 0.002, "client.wait": 0.022,
        "engine.pull": 0.020})
    assert s.top_idle()[0] == ["client.wait", pytest.approx(0.022)]


def test_summary_attributes_ops_to_program_executions():
    s = summary(*synthetic())
    runs = s.executions(FLEET_PROGRAM)
    assert len(runs) == 2  # the third lies outside the window
    inside = s.chips[0].ops_within(runs)
    assert sorted(trace.op_name(e.name) for e in inside) == sorted(
        [AGG_ADAM_KERNEL + ".1", AGG_ADAM_KERNEL, "copy.1", "copy.1"])
    assert dict(s.top_ops())[AGG_ADAM_KERNEL] == pytest.approx(0.012)
    assert dict(s.top_ops())[AGG_ADAM_KERNEL + ".1"] == pytest.approx(0.012)


def test_union_and_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert trace.gaps([(0, 3), (5, 7)], 0, 10) == [(3, 5), (7, 10)]
    assert trace.gaps([], 2, 4) == [(2, 4)]
    assert trace.program_name("jit_apply(12)") == "jit_apply"


def test_summary_without_window_or_ops_is_none():
    device, host = synthetic()
    assert summary(device, host[1:]) is None
    assert summary({}, host) is None
    assert trace.summarize([{}, {}], host) is None


def test_planes_of_several_chips_sum_to_chip_seconds():
    """Two chips that ran alike read twice the chip-seconds and the same
    idle share; a third chip that ran nothing adds a whole idle window."""
    device, host = synthetic()
    one = summary(device, host)
    two = trace.summarize([device, device], host)
    assert two.window_s == pytest.approx(0.2)
    assert two.busy_s == pytest.approx(2 * one.busy_s)
    assert two.idle_pct == pytest.approx(one.idle_pct)
    assert two.idle_by_host == pytest.approx(
        {k: 2 * v for k, v in one.idle_by_host.items()})
    assert [len(c.executions(FLEET_PROGRAM)) for c in two.chips] == [2, 2]
    assert len(two.executions(FLEET_PROGRAM)) == 4
    assert dict(two.top_ops()) == pytest.approx(
        {k: 2 * v for k, v in dict(one.top_ops()).items()})
    three = trace.summarize([device, device, {}], host)
    assert three.window_s == pytest.approx(0.3)
    assert three.busy_s == pytest.approx(two.busy_s)
    # the idle chip's whole window, put down to the span overlapping most
    assert three.idle_by_host["client.wait"] == pytest.approx(
        two.idle_by_host["client.wait"] + 0.1)


def test_memory_peaks_reduce_to_the_fullest_chip():
    class Chip:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    chips = [Chip({"peak_bytes_in_use": 7, "bytes_limit": 16}),
             Chip({"peak_bytes_in_use": 11}), Chip(None), Chip({})]
    assert R.memory_peaks(chips) == (11, [7, 11, None, None])
    assert R.memory_peaks(chips[:1]) == (7, [7])
    assert R.memory_peaks([Chip(None)]) == (None, [None])


def test_required_bytes_and_roofline_share():
    assert roofline.required_bytes(1000) == 28_000
    # 819 GB in one second on a v5e is 100% of its HBM roofline.
    assert roofline.roofline_pct(819e9, 1.0, "TPU v5 lite") == \
        pytest.approx(100.0)
    assert roofline.roofline_pct(819e9 / 4, 1.0, "TPU v5 lite") == \
        pytest.approx(25.0)
    assert roofline.roofline_pct(0, 1.0, "TPU v5 lite") is None
    assert roofline.roofline_pct(1e9, 0.0, "TPU v5 lite") is None


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks known"):
        roofline.peak("TPU v99")
    with pytest.raises(KeyError):
        roofline.roofline_pct(1e9, 1.0, "cpu")


def _saturate_run(n_ticks):
    run = Run(cell="c", config={}, traffic={}, seconds=0.1,
              device_kind="TPU v5 lite")
    run.trace = summary(*synthetic())
    # Each tick applied 100M real parameters: 2.8 GB required.
    run.ticks = [(0, 0, 100_000_000)] * n_ticks
    return run


def test_fleet_and_kernel_roofline_from_required_bytes():
    run = _saturate_run(2)
    program = R.reader("fleet_tick_roofline")(run)
    kernel = R.reader("agg_adam_roofline")(run)
    assert program == pytest.approx(100 * 5.6e9 / 819e9 / 0.040)
    assert kernel == pytest.approx(100 * 5.6e9 / 819e9 / 0.024)
    # Ticks that the trace cannot match one to one give no reading.
    assert R.reader("fleet_tick_roofline")(_saturate_run(3)) is None
    assert R.reader("device.idle_pct.saturate")(run) == pytest.approx(56.0)


def test_rooflines_take_one_fleet_execution_per_chip_per_tick():
    """The bytes the ticks need over the device time summed over the
    chips that ran the fleet program: the same share however the work is
    spread; a chip that ran it once too often gives no reading."""
    device, host = synthetic()
    run = _saturate_run(2)
    run.trace = trace.summarize([device, device, {}], host)
    assert R.reader("fleet_tick_roofline")(run) == pytest.approx(
        100 * 5.6e9 / 819e9 / 0.080)
    assert R.reader("agg_adam_roofline")(run) == pytest.approx(
        100 * 5.6e9 / 819e9 / 0.048)
    extra = dict(device)
    extra[trace.MODULES_LINE] = device[trace.MODULES_LINE] + [
        ev(FLEET_PROGRAM + "(7)", 85, 10)]
    run.trace = trace.summarize([device, extra], host)
    assert R.reader("fleet_tick_roofline")(run) is None
    assert R.reader("agg_adam_roofline")(run) is None


def test_rooflines_gate_on_fallbacks_in_the_window_only():
    """A fallback during warm-up leaves the window's rooflines readable;
    one inside the window gives no reading."""
    run = _saturate_run(2)
    run.counters = {"n_fleet_fallbacks": 1,
                    "window": {"n_fleet_fallbacks": 0}}
    assert R.reader("fleet_tick_roofline")(run) == pytest.approx(
        100 * 5.6e9 / 819e9 / 0.040)
    assert R.reader("agg_adam_roofline")(run) == pytest.approx(
        100 * 5.6e9 / 819e9 / 0.024)
    run.counters = {"n_fleet_fallbacks": 2,
                    "window": {"n_fleet_fallbacks": 1}}
    assert R.reader("fleet_tick_roofline")(run) is None
    assert R.reader("agg_adam_roofline")(run) is None


def test_host_clock_readers():
    run = Run(cell="c", config={}, traffic={}, seconds=1.0, t0=10.0,
              t_end=11.0, t_close=12.0, setup_s=3.5)
    run.iters = [("a", 10.0 + i / 100, 10.0 + i / 100, 10.0 + i / 100
                  + (0.1 if i == 99 else 0.01)) for i in range(100)]
    run.iters.append(("a", 11.5, 11.5, 11.6))  # due after the window
    run.spans = [("engine.tick", 10.5, 10.502), ("engine.tick", 10.6,
                                                  10.604),
                 ("engine.tick", 9.0, 9.5)]
    run.replans = [dict(kind="arrival", start=10.1, host_s=0.2,
                        relayout_bytes=4e8, stall_s=0.5),
                   dict(kind="exit", start=10.4, host_s=0.4,
                        relayout_bytes=8e8, stall_s=1.5)]
    run.memory_peak_bytes = 12_500_000_000
    assert R.reader("updates_per_s")(run) == pytest.approx(101 / 2.0)
    assert R.reader("sync_ms_p95")(run) == pytest.approx(10.0)
    assert R.reader("setup_s")(run) == 3.5
    assert R.reader("engine.tick_host_ms.saturate")(run) == \
        pytest.approx(3.0)
    assert R.reader("replan_stall_ms")(run) == pytest.approx(1000.0)
    assert R.reader("replan.host_ms")(run) == pytest.approx(300.0)
    assert R.reader("replan.relayout_gb")(run) == pytest.approx(0.6)
    assert R.reader("peak_hbm_gb")(run) == pytest.approx(12.5)
    empty = Run(cell="c", config={}, traffic={}, seconds=1.0)
    for name in ("updates_per_s", "sync_ms_p95", "replan_stall_ms",
                 "replan.host_ms", "peak_hbm_gb",
                 "device.idle_pct.saturate", "agg_adam_roofline"):
        assert R.reader(name)(empty) is None, name


def test_benchmark_json_names_files_that_exist():
    bench = R._load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert bench["command"][1] == "chipbench/run.py"
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "metrics",
                                           m["name"] + ".py")), m["name"]
    for w in bench["workloads"]:
        cell, conf, cfg, traffic = R.cell_spec(bench, w["name"])
        assert cfg["name"] == conf["name"]
        assert set(conf["reduced"]) == set(cfg["reduced"])
        names = [t["name"] for t in cfg["tenants"]]
        assert len(names) == len(set(names))
        assert all(t["model"] in cfg["models"] for t in cfg["tenants"])


def _command(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "awdlm4-2s2w.saturate", "--seed", "2147483999", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_refuses_to_run_without_a_chip():
    p = _command(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_command_refuses_without_the_system_under_test(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
