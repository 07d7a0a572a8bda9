#!/usr/bin/env python3
"""The control and the planted faults of the correctness check, on the chip:
runs that the check has to refuse.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s> --what bf16_reference|bf16_push|<fault>

For each seed it runs the cell as the benchmark does and prints the
result's ``correct`` with every number compared beside its limit:

- ``bf16_reference``: the reference computed in bfloat16 takes the place of
  the program's pulls (the program's own readings are printed beside it);
- ``bf16_push``: the program with its own lower-precision path on, every
  push meant to cross as bfloat16 with error feedback.  On the v5e it
  reads as the fp32 program does, so it is no control; it is kept to
  show that;
- a fault of ``chipbench/faults.py`` planted in the service.

The benchmark's own runs never run this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def control_run(workload, seed, seconds, what, *, bench, cfg=None,
                require_tpu=True, log=print):
    """The result object of one run of ``workload`` under ``what``."""
    import builtins

    from chipbench import faults
    from chipbench import run as R

    _, _, file_cfg, _ = R.cell_spec(bench, workload)
    cfg = dict(file_cfg if cfg is None else cfg)
    control = None
    if what == "bf16_reference":
        control = "bfloat16"
    elif what == "bf16_push":
        cfg["push_compression"] = "bf16"
    else:
        faults.FAULTS[what](builtins.setattr)
    return R.run_cell(workload, seed, seconds, False, bench=bench, cfg=cfg,
                      control=control, require_tpu=require_tpu,
                      t_start=time.perf_counter(), log=log)


def main(argv=None) -> int:
    from chipbench import faults

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--what", required=True,
                    choices=["bf16_reference", "bf16_push", *faults.FAULTS])
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from chipbench import run as R
    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = R._load_json(os.path.join(ROOT, "BENCHMARK.json"))
    log = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    for seed in (int(s) for s in args.seeds.split(",")):
        out = control_run(args.workload, seed, args.seconds, args.what,
                          bench=bench, log=log)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "what": args.what, "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:] = [ROOT] + [p for p in sys.path if p != HERE]
    sys.exit(main())
