#!/usr/bin/env python3
"""The tick engine's own names in a profiler trace, and what they give.

The engine (``repro.ps.engine``) names its programs: ``jit_push_pack``
(a push's pack and split), ``jit_pull_gather`` (a pull), ``jit_state_copy``
(a lane's snapshot or restore), ``jit_lane_apply`` (a per-shard tick) and
the fused fleet tick's ``jit_apply``.  The per-layer readers
``push.device_ms.saturate``, ``pull.device_ms.saturate`` and
``engine.state_copy_ms.saturate`` time those programs in a ``--trace 1``
run.

With ``repro.ps.spans`` on, the engine also records host spans named
``ps.*`` (``ps.push``, ``ps.pull``, ``ps.tick``, ``ps.lane_tick``,
``ps.compile``, ``ps.snapshot``, ``ps.launch``, ``ps.rollback``,
``ps.fallback``).  ``chipbench/run.py --trace 1`` turns them on, and
``chipbench/trace.py`` keeps them with the device's idle time by the
innermost one; the readers ``engine.tick_self_ms.saturate`` and
``device.idle_engine_pct.saturate`` take them from there.  This module's
own command shows more of one cell's window, with the spans on or off::

    python3 chipbench/engine_trace.py --workload <cell> --seed <n> \
        --seconds <s> [--spans 0|1]

It prints one JSON object: the engine's host time by span (self time is a
span's time less that of the spans inside it), the device's idle seconds
by the innermost ``ps.*`` span open over them (``outside`` where none is)
and by the device program executing over them, the device time by
program, and the readings those give, beside the cell's end-to-end rate.
It makes no correctness check: that is ``chipbench/run.py``'s.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

PUSH_PROGRAM = "jit_push_pack"
PULL_PROGRAM = "jit_pull_gather"
COPY_PROGRAM = "jit_state_copy"


# ------------------------------------------------------- program readers
def _named(run) -> bool:
    """Whether the traced window shows the engine's program names (a
    program that lacks them runs its pushes and pulls as ``jit_fn``)."""
    return run.trace is not None and bool(
        run.trace.executions(PUSH_PROGRAM)
        or run.trace.executions(PULL_PROGRAM))


def mean_device_ms(run, program: str) -> Optional[float]:
    """Mean device milliseconds per execution of ``program`` in the
    window."""
    runs = run.trace.executions(program) if _named(run) else []
    if not runs:
        return None
    return sum(e.dur_ns for e in runs) / len(runs) / 1e6


def device_ms_per_tick(run, program: str) -> Optional[float]:
    """Device milliseconds of ``program`` per engine tick of the window
    (the benchmark's ``engine.tick`` spans, one ``ps.tick`` each); 0.0
    where the window ticked and never ran it."""
    ticks = sum(1 for n, a, _ in run.spans
                if n == "engine.tick" and run.t0 <= a <= run.t_close)
    if not _named(run) or not ticks:
        return None
    return sum(e.dur_ns for e in run.trace.executions(program)) / ticks / 1e6


def device_by_program(modules) -> Dict[str, Tuple[int, float]]:
    """``{program: (executions, device seconds)}`` of the window's program
    executions (``Summary.modules``), the longest first."""
    from chipbench.trace import program_name

    out: Dict[str, Tuple[int, float]] = {}
    for e in modules:
        n, sec = out.get(program_name(e.name), (0, 0.0))
        out[program_name(e.name)] = (n + 1, sec + e.dur_ns / 1e9)
    return dict(sorted(out.items(), key=lambda kv: -kv[1][1]))


# -------------------------------------------------------- span reductions
def _parents(spans) -> Dict[int, object]:
    """``{id(span): the span it lies directly inside, or None}``; the
    engine's spans nest, as spans of one thread do."""
    out, stack = {}, []
    for e in sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1].end_ns <= e.start_ns:
            stack.pop()
        out[id(e)] = (stack[-1] if stack and e.end_ns <= stack[-1].end_ns
                      else None)
        stack.append(e)
    return out


def host_by_span(spans) -> Dict[str, Tuple[int, float, float]]:
    """``{name: (count, seconds, self seconds)}``: self time is a span's
    time less that of the spans directly inside it."""
    parents = _parents(spans)
    own = {id(e): e.dur_ns for e in spans}
    for e in spans:
        up = parents[id(e)]
        if up is not None:
            own[id(up)] -= e.dur_ns
    out: Dict[str, List[float]] = {}
    for e in spans:
        rec = out.setdefault(e.name, [0, 0.0, 0.0])
        rec[0] += 1
        rec[1] += e.dur_ns / 1e9
        rec[2] += own[id(e)] / 1e9
    return {k: (int(n), s, o) for k, (n, s, o) in sorted(out.items())}


def tick_self_ms(spans) -> Optional[float]:
    """Mean self milliseconds of ``ps.tick``: the tick's pure host path."""
    rec = host_by_span(spans).get("ps.tick")
    return 1e3 * rec[2] / rec[0] if rec else None


def fallback_ms_per_tick(spans) -> Optional[float]:
    """Host milliseconds per ``ps.tick`` spent on the fleet fallback: the
    ``ps.fallback`` spans, and each failed fused launch (the ``ps.launch``
    directly inside a ``ps.tick`` that fell back)."""
    n_ticks = sum(1 for e in spans if e.name == "ps.tick")
    if not n_ticks:
        return None
    parents = _parents(spans)
    falls = [e for e in spans if e.name == "ps.fallback"]
    fell = {id(parents[id(e)]) for e in falls}
    failed = [e for e in spans if e.name == "ps.launch"
              and id(parents[id(e)]) in fell]
    return sum(e.dur_ns for e in falls + failed) / n_ticks / 1e6


def idle_by_program(idle, device) -> Dict[str, float]:
    """Seconds of the idle stretches ``idle`` by the device program
    executing over them (the ``XLA Modules`` line), ``outside`` where
    none is: idle inside a program is the gaps between its operations."""
    from chipbench import trace

    return trace.idle_by_span(idle, [
        trace.Event(trace.program_name(e.name), e.start_ns, e.dur_ns)
        for e in device.get(trace.MODULES_LINE, [])])


# ---------------------------------------------------------------- command
def run_spans(workload: str, seed: int, seconds: float, spans_on: bool, *,
              bench: dict, cfg: dict = None, require_tpu: bool = True,
              t_start: float = None) -> dict:
    """One cell's set-up and window under the profiler, with the engine's
    spans on or off; returns the readings (no check)."""
    import jax

    from chipbench import harness
    from chipbench import run as R
    from chipbench import trace as tracing

    cell, _, file_cfg, traffic = R.cell_spec(bench, workload)
    devs = R.device_info(int(cell["chips"]), require_tpu)
    h = harness.Harness(workload, file_cfg if cfg is None else cfg,
                        traffic, seed, trace=True)
    run = h.run
    run.device_kind = devs[0].device_kind
    trace_dir = tempfile.mkdtemp(prefix="chipbench-spans-")
    try:
        h.setup()
        harness.engine_spans(spans_on)
        jax.profiler.start_trace(trace_dir)
        with h.span("window"):
            h.window(seconds, T_START if t_start is None else t_start)
        jax.profiler.stop_trace()
        planes, host = tracing.load(trace_dir, [d.id for d in devs])
        run.trace = tracing.summarize(planes, host)
    finally:
        harness.engine_spans(False)
        shutil.rmtree(trace_dir, ignore_errors=True)
        h.close()
    (win,) = [e for e in host if e.name == tracing.WINDOW_SPAN]
    ps = [e for e in host if e.name.startswith(tracing.SPAN_PREFIX)
          and win.start_ns <= e.start_ns <= win.end_ns]
    idle = run.trace.idle_by_span if run.trace else {}
    by_program = collections.Counter()
    if run.trace is not None:
        for plane in planes:
            by_program.update(idle_by_program(
                tracing.device_idle(plane, win.start_ns, win.end_ns), plane))
    return {
        "workload": workload, "seed": seed, "spans": bool(spans_on),
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)},
        "updates_per_s": R.reader("updates_per_s")(run),
        "sync_ms_p95": R.reader("sync_ms_p95")(run),
        "engine.tick_host_ms": R.reader("engine.tick_host_ms.saturate")(run),
        "engine.tick_self_ms": tick_self_ms(ps),
        "engine.fallback_host_ms": fallback_ms_per_tick(ps),
        "engine.state_copy_ms": device_ms_per_tick(run, COPY_PROGRAM),
        "pull.device_ms": mean_device_ms(run, PULL_PROGRAM),
        "push.device_ms": mean_device_ms(run, PUSH_PROGRAM),
        "device.idle_pct": run.trace.idle_pct if run.trace else None,
        "device.idle_engine_pct": (
            tracing.engine_idle_pct(idle, run.trace.window_s)
            if run.trace else None),
        "engine.applier_compiles": R.reader(
            "engine.applier_compiles.cadence")(run),
        "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "idle_by_host": run.trace.idle_by_host if run.trace else {},
        "idle_by_program": dict(by_program),
        "device_by_program": (device_by_program(run.trace.modules)
                              if run.trace else {}),
        "host_by_span": host_by_span(ps),
        "counters": run.counters,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from chipbench import run as R
    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = R._load_json(os.path.join(ROOT, "BENCHMARK.json"))
    try:
        out = run_spans(args.workload, args.seed, args.seconds,
                        bool(args.spans), bench=bench)
    except R.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # As in run.py: trace.py here would shadow the standard library's.
    sys.path[:] = [ROOT] + [p for p in sys.path if p != HERE]
    sys.exit(main())
