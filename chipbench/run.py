#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout.  ``BENCHMARK.json`` names the cell's
configuration (``chipbench/configs/<name>.json``) and traffic
(``chipbench/traffic/<name>.json``); each metric is read by
``chipbench/metrics/<metric>.py``.  The run makes every tenant's
parameters and gradients on the cell's first chip from ``--seed``, warms up
every program the window uses, measures for ``--seconds``, replays each
tenant on the plain reference tensor by tensor, and prints one JSON
object as the last line of standard output.  With ``--trace 1`` the
window runs under the profiler with the engine's own spans on, and the
per-layer metrics are printed; with ``--trace 0`` the end-to-end ones.
Memory and trace are read on every chip of the cell.  Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# The numbers compared are the widest gap between a pulled parameter and
# the reference's, as a share of the largest distance the reference moved
# a parameter (chipbench.reference.gap): of each tenant's last pull
# (``gap``), whose rounding grows with the updates a window holds, and of
# its pull after the traffic's fixed ``check_step`` (``gap_at_k``), whose
# rounding does not.  PERF.md, "How correct is decided", gives the
# readings each limit was set from.
LIMITS = {"gap": 3e-3, "gap_at_k": 2e-3}


class NoChip(RuntimeError):
    pass


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def cell_spec(bench: dict, workload: str):
    """(cell, configuration entry, configuration file, traffic file)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = _load_json(os.path.join(ROOT, conf["file"]))
    traffic = _load_json(os.path.join(HERE, "traffic",
                                      cell["traffic"] + ".json"))
    return cell, conf, cfg, traffic


def metric_names(bench: dict, workload: str, trace: bool):
    group = bench["per_layer" if trace else "end_to_end"]
    return [(m["name"], m["unit"]) for m in group
            if workload in m.get("workloads", (workload,))]


def reader(name: str):
    """``read(run)`` of ``chipbench/metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform!r} device(s)")
    return devs[:chips]


def memory_peaks(devs):
    """(the fullest chip's ``peak_bytes_in_use``, each chip's); None
    where the backend reports none."""
    by_chip = [(d.memory_stats() or {}).get("peak_bytes_in_use")
               for d in devs]
    known = [b for b in by_chip if b is not None]
    return (max(known) if known else None), by_chip


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench: dict, require_tpu: bool = True, cfg: dict = None,
             control: str = None, t_start: float = None, log=print):
    """Set-up, window and check of one cell; returns the result object.
    Tests hand a reduced ``cfg`` and ``require_tpu=False``.  With
    ``control`` (a dtype name) the reference computed in that precision
    takes the place of the program's pulls in the comparison."""
    import jax

    from chipbench import harness
    from chipbench import trace as tracing

    cell, _, file_cfg, traffic = cell_spec(bench, workload)
    cfg = file_cfg if cfg is None else cfg
    devs = device_info(int(cell["chips"]), require_tpu)
    dev = devs[0]
    h = harness.Harness(workload, cfg, traffic, seed, trace=trace)
    run = h.run
    run.device_kind = dev.device_kind
    t_start = T_START if t_start is None else t_start
    try:
        h.setup()
        _, open_peaks = memory_peaks(devs)
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        try:
            if trace:
                harness.engine_spans(True)
                jax.profiler.start_trace(trace_dir)
            with h.span("window"):
                h.window(seconds, t_start)
            if trace:
                jax.profiler.stop_trace()
                planes, host = tracing.load(trace_dir, [d.id for d in devs])
                log(f"trace: {sum(map(bool, planes))} of {len(planes)} "
                    f"chips' planes found")
                run.trace = tracing.summarize(planes, host)
        finally:
            if trace:
                harness.engine_spans(False)
            shutil.rmtree(trace_dir, ignore_errors=True)
        run.memory_peak_bytes, run.memory_peak_bytes_by_chip = \
            memory_peaks(devs)
        kept = h.release()
        t_ref = time.perf_counter()
        gaps = h.check(kept)
        ref_s = time.perf_counter() - t_ref
        if control is not None:
            for name, (g, steps) in sorted(gaps.items()):
                log(f"program {name} {g:.6e} ({steps} steps)")
            gaps = h.check(kept, control=control)
    finally:
        h.close()

    metrics = {}
    for name, unit in metric_names(bench, workload, trace):
        value = reader(name)(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    checks = {name: {"value": g, "limit": LIMITS[name.split(".")[0]]}
              for name, (g, _) in sorted(gaps.items())}
    correct = (bool(gaps) and run.failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    lat = sorted(sub - due for _, due, sub, _ in run.iters)
    log(f"window {run.t_close - run.t0:.3f} s, {len(run.iters)} updates, "
        f"{len(run.ticks)} ticks, {len(run.replans)} replans, "
        f"{run.compiles} programs lowered ({run.cache_misses} compiled), "
        f"set-up {run.setup_s:.3f} s, reference {ref_s:.3f} s")
    if run.compiled:
        log("lowered in the window (program, s): " + json.dumps(run.compiled))
    log("set-up phases (s): " + json.dumps(
        {k: round(v, 3) for k, v in run.setup_phases.items()}))
    if lat:
        log(f"generator lateness (push submitted - due): p50 "
            f"{1e3 * lat[len(lat) // 2]:.3f} ms, max {1e3 * lat[-1]:.3f} ms")
    log("counters: " + json.dumps(run.counters, sort_keys=True))
    host = {}
    for name, a, b in run.spans:
        host[name] = host.get(name, 0.0) + b - a
    log("host seconds by span in the window: " + json.dumps(
        {k: round(v, 3) for k, v in sorted(host.items())}))
    if run.trace is not None:
        log("device busy seconds by chip: " + json.dumps(
            [round(c.busy_s, 3) for c in run.trace.chips]))
        log("device idle chip-seconds by innermost ps.* span: " + json.dumps(
            {k: round(v, 3) for k, v in sorted(
                run.trace.idle_by_span.items(), key=lambda kv: -kv[1])}))
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    log(f"memory: peak_bytes_in_use by chip {run.memory_peak_bytes_by_chip} "
        f"({open_peaks} when the window opened) of bytes_limit {limit}")
    for name, (g, steps) in sorted(gaps.items()):
        log(f"{name} {g:.6e} limit {checks[name]['limit']:.1e} "
            f"({steps} steps)")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes,
              "memory_peak_bytes_by_chip": run.memory_peak_bytes_by_chip}
    out = {"correct": correct, "attempted": len(run.iters),
           "failed": run.failed, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.top_ops(),
                            "idle_gaps": run.trace.top_idle()}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chipbench: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache(ROOT)
    # Cache every program, however fast it compiles, so only a checkout's
    # first run compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), bench=bench, log=log)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        log(f"{name} {c['value']:.6e} limit {c['limit']:.1e}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # Run as a script, this directory heads sys.path, where trace.py would
    # shadow the standard library's module of that name.
    sys.path[:] = [ROOT] + [p for p in sys.path if p != HERE]
    sys.exit(main())
