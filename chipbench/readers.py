"""Arithmetic the metric readers share (``chipbench/metrics/*.py``).

Each reader returns a number, or None when the run holds nothing to read;
the harness then leaves the metric out of the result line."""

from __future__ import annotations

from typing import Optional

import numpy as np

from chipbench import roofline
from chipbench.trace import op_name

# The fused fleet-tick program and the multi-job Adam kernel inside it, by
# the names a TPU trace gives them.
FLEET_PROGRAM = "jit_apply"
AGG_ADAM_KERNEL = "aggregate_adam_multijob_fused"


def span_ms(run, name: str) -> Optional[float]:
    """Mean host milliseconds of the benchmark's span ``name`` in the
    window."""
    d = [b - a for n, a, b in run.spans
         if n == name and run.t0 <= a <= run.t_close]
    return 1e3 * float(np.mean(d)) if d else None


def traced_ticks(run):
    """The window's ticks, and each chip that ran the fleet program with its
    executions in the trace, or None where they cannot be matched: a tick
    launches at most one fleet program a chip, so each such chip has to
    show one execution per tick, and no tick of the window may have fallen
    back to the per-lane appliers."""
    if (run.trace is None or not run.ticks
            or run.counters.get("window", {}).get("n_fleet_fallbacks")):
        return None
    chips = [(c, c.executions(FLEET_PROGRAM)) for c in run.trace.chips]
    chips = [(c, runs) for c, runs in chips if runs]
    if not chips or any(len(runs) != len(run.ticks) for _, runs in chips):
        return None
    return run.ticks, chips


def fleet_share(run, part: str) -> Optional[float]:
    """Share (%) of the HBM roofline of the fleet-tick program (``part`` =
    "program") or of the kernel inside it ("kernel"): the bytes the
    applied updates need over the device time of that part, summed over
    the chips it ran on, so the share reads the same work however it is
    spread."""
    matched = traced_ticks(run)
    if matched is None:
        return None
    ticks, chips = matched
    nbytes = sum(roofline.required_bytes(applied) for _, _, applied in ticks)
    if part == "program":
        dur = sum(e.dur_ns for _, runs in chips for e in runs)
    else:
        dur = sum(e.dur_ns for c, runs in chips for e in c.ops_within(runs)
                  if op_name(e.name).startswith(AGG_ADAM_KERNEL))
    return roofline.roofline_pct(nbytes, dur / 1e9, run.device_kind)
