"""Each traffic loop of the benchmark, driven through the harness on the CPU
with tiny tenants, comes out correct against the reference."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tiny_cells import run_tiny, tiny  # noqa: E402


@pytest.mark.parametrize("workload,seconds,metric", [
    ("testbed-2s2w.saturate", 0.5, "updates_per_s"),
    ("awdlm4-2s2w.saturate", 0.5, "updates_per_s"),
    ("awdlm4-2s2w.cadence", 1.0, "sync_ms_p95"),
    ("awdlm4-2s2w.churn", 3.0, "replan_stall_ms"),
])
def test_traffic_loop_matches_reference(workload, seconds, metric):
    out = run_tiny(workload, seconds)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"][metric]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    cfg = tiny(workload)
    assert set(out["checks"]) == {f"{n}.{t['name']}"
                                  for t in cfg["tenants"]
                                  for n in ("gap", "gap_at_k")}
    for c in out["checks"].values():
        assert 0 <= c["value"] <= c["limit"]


def test_traced_run_reports_per_layer_metrics_it_can_read():
    """Off the TPU the trace has no device plane: the device readers
    report nothing, the host-clock ones still do."""
    out = run_tiny("awdlm4-2s2w.saturate", 0.5, trace=True)
    assert out["correct"]
    assert "engine.tick_host_ms.saturate" in out["metrics"]
    assert "agg_adam_roofline" not in out["metrics"]
    assert "updates_per_s" not in out["metrics"]
