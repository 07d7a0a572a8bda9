"""Cells of the benchmark at a size the CPU tests can hold."""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as R  # noqa: E402

SEED = 2**31 + 101  # wider than 32 signed bits, as benchmark seeds may be
# Every tensor shrinks by this factor, and the profiled aggregation
# throughput with it, so the control plane packs the tenants as at full
# width.
SHRINK = 4096


# The churn traffic has no cell in BENCHMARK.json yet (PERF.md, Open
# questions); the tests drive it on the AWD-LSTM configuration.
CHURN_CELL = {"name": "awdlm4-2s2w.churn", "config": "awdlm4-2s2w",
              "traffic": "churn", "chips": 1, "why": "tests only"}


def bench():
    b = R._load_json(os.path.join(ROOT, "BENCHMARK.json"))
    b["workloads"].append(CHURN_CELL)
    b["end_to_end"].append({"name": "replan_stall_ms", "unit": "ms",
                            "workloads": [CHURN_CELL["name"]]})
    return b


def tiny(workload, shrink=SHRINK):
    _, _, cfg, _ = R.cell_spec(bench(), workload)
    cfg = json.loads(json.dumps(cfg))
    cfg["models"] = {m: [[n, -(-k // shrink)] for n, k in inv]
                     for m, inv in cfg["models"].items()}
    cfg["agg_throughput"] /= shrink
    return cfg


def run_tiny(workload, seconds, trace=False, **kw):
    return R.run_cell(workload, SEED, seconds, trace, bench=bench(),
                      require_tpu=False, cfg=tiny(workload),
                      t_start=time.perf_counter(), log=lambda *a: None,
                      **kw)
