"""Run benchmarks; print name,value,derived CSV (one per paper table).

Options:
  --list          print every benchmark label and exit
  --only SUBSTR   run only modules whose label contains SUBSTR (repeatable)
  --smoke         shrink sweeps for CI (sets HOTPATH_SMOKE=1)
  --json [PATH]   also write the collected rows as JSON
                  (default PATH: BENCH_hotpath.json -- the perf trajectory
                  file seeded by the hotpath benchmark)
"""

import argparse
import importlib
import json
import os
import sys
import time

MODULES = [
    ("fig2", "fig2_utilization"),
    ("fig7", "fig7_single_job"),
    ("fig8+table2", "fig8_packing"),
    ("fig9", "fig9_perf_loss"),
    ("fig10", "fig10_case_study"),
    ("fig11", "fig11_trace_sim"),
    ("table3", "table3_migration"),
    ("migration", "migration_scaling"),
    ("plan", "plan_scaling"),
    ("hotpath", "hotpath_step"),
    ("service_tick", "service_tick"),
    ("elastic_scaling", "elastic_scaling"),
    ("appd", "appd_interference"),
    ("roofline", "roofline"),
    ("recovery", "recovery"),
    ("wire", "wire_path"),
    ("chaos", "chaos_soak"),
    ("read", "read_tier"),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--list", action="store_true",
                    help="print every benchmark label and exit")
    ap.add_argument("--only", action="append", default=None,
                    help="run only modules whose label contains this")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink benchmark sweeps (CI)")
    ap.add_argument("--json", nargs="?", const="BENCH_hotpath.json",
                    default=None, metavar="PATH",
                    help="write rows to PATH as JSON")
    args = ap.parse_args(argv)
    if args.list:
        for label, mod_name in MODULES:
            print(f"{label}\tbenchmarks/{mod_name}.py")
        return
    if args.smoke:
        os.environ["HOTPATH_SMOKE"] = "1"

    labels = [label for label, _ in MODULES]
    if args.only:
        unknown = [pat for pat in args.only
                   if not any(pat in label for label in labels)]
        if unknown:
            raise SystemExit(
                f"error: --only {', '.join(unknown)} matches no benchmark "
                f"label.\nAvailable labels: {', '.join(labels)}")
    selected = [
        (label, name) for label, name in MODULES
        if not args.only or any(pat in label for pat in args.only)
    ]

    print("name,value,derived")
    collected = []
    failures = 0
    for label, mod_name in selected:
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            for name, value, derived in mod.rows():
                print(f'{name},{value},"{derived}"')
                collected.append(
                    {"name": name, "value": value, "derived": derived})
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f'{label}/ERROR,{type(e).__name__},"{e}"', file=sys.stdout)
        print(f'{label}/elapsed_s,{time.time() - t0:.1f},""')

    if args.json:
        payload = {
            "smoke": bool(args.smoke),
            "modules": [label for label, _ in selected],
            "rows": collected,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f'json/written,{len(collected)},"{args.json}"')
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    # `python benchmarks/run.py` puts benchmarks/ (not the repo root) on
    # sys.path; add the root so `benchmarks.<mod>` imports resolve.
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache(root)
    main()
