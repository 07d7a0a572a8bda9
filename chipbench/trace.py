"""Reduction of one profiler trace to the numbers the readers take.

The profiler writes an ``.xplane.pb``; :func:`load` turns it into plain
events, and :func:`summarize` reduces those:

* busy time is the union of the intervals in which an operation ran on
  the device (the ``XLA Ops`` and ``Async XLA Ops`` lines of its plane),
  inside the window the benchmark's own ``window`` span marks on the host;
* each idle gap of the device inside the window is put down to the host
  span (``client.push``, ``engine.tick``, ``engine.pull``, ``client.wait``,
  ``client.compute``, ``replan``) that overlaps it most;
* program executions (the ``XLA Modules`` line) are kept by program name,
  so a reader can time one program and the operations inside it.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
ASYNC_OPS_LINE = "Async XLA Ops"  # DMAs between their start and done
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"
HOST_SPANS = ("client.push", "engine.tick", "engine.pull", "client.wait",
              "client.compute", "replan")


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Summary:
    window_s: float
    busy_s: float
    ops: List[Event]  # device operations inside the window
    modules: List[Event]  # program executions inside the window
    idle_by_host: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_pct(self) -> Optional[float]:
        if self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def executions(self, program: str) -> List[Event]:
        return [e for e in self.modules if program_name(e.name) == program]

    def ops_within(self, runs: List[Event]) -> List[Event]:
        """Device operations that ran inside the given executions."""
        spans = sorted((r.start_ns, r.end_ns) for r in runs)
        out, i = [], 0
        for op in sorted(self.ops, key=lambda e: e.start_ns):
            while i < len(spans) and spans[i][1] < op.start_ns:
                i += 1
            if i < len(spans) and spans[i][0] <= op.start_ns <= spans[i][1]:
                out.append(op)
        return out

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        tot = collections.Counter()
        for e in self.ops:
            tot[op_name(e.name)] += e.dur_ns / 1e9
        return [[n, s] for n, s in tot.most_common(k)]

    def top_idle(self, k: int = 10) -> List[Tuple[str, float]]:
        return [[n, s] for n, s in sorted(self.idle_by_host.items(),
                                          key=lambda kv: -kv[1])[:k]]


def op_name(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``: a TPU trace
    names each operation by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def program_name(name: str) -> str:
    """``jit_apply(1799...)`` -> ``jit_apply``: the trace adds the
    program's fingerprint."""
    return re.sub(r"\(\d+\)$", "", name)


def union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def gaps(busy, lo, hi) -> List[Tuple[float, float]]:
    """The idle stretches of [lo, hi] between the merged busy intervals."""
    out, cur = [], lo
    for a, b in busy:
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def _clip(events, lo, hi) -> List[Event]:
    out = []
    for e in events:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a or (e.dur_ns == 0 and lo <= e.start_ns <= hi):
            out.append(Event(e.name, a, b - a))
    return out


def summarize(device: Dict[str, List[Event]], host: List[Event]
              ) -> Optional[Summary]:
    """Reduce one device's lines and the host's spans.  None when the trace
    holds no window span or no device operation."""
    win = [e for e in host if e.name == WINDOW_SPAN]
    ops = device.get(OPS_LINE, [])
    if not win or not ops:
        return None
    lo, hi = win[0].start_ns, win[0].end_ns
    ops = _clip(ops, lo, hi)
    modules = _clip(device.get(MODULES_LINE, []), lo, hi)
    dmas = _clip(device.get(ASYNC_OPS_LINE, []), lo, hi)
    busy = union((e.start_ns, e.end_ns) for e in ops + dmas)
    busy_ns = sum(b - a for a, b in busy)
    spans = sorted(((e.start_ns, e.end_ns, e.name) for e in host
                    if e.name in HOST_SPANS))
    idle = collections.Counter()
    for a, b in gaps(busy, lo, hi):
        best, label = 0.0, "host.other"
        for s, t, name in spans:
            if s >= b:
                break
            ov = min(b, t) - max(a, s)
            if ov > best:
                best, label = ov, name
        idle[label] += (b - a) / 1e9
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy_ns / 1e9, ops=ops,
                   modules=modules, idle_by_host=dict(idle))


def _events(line, names=None) -> List[Event]:
    return [Event(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events if names is None or e.name in names]


def load(trace_dir: str):
    """(lines of the first TPU device plane by name, host events) of the
    one ``.xplane.pb`` under ``trace_dir``."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {paths}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and not device:
            device = {line.name: _events(line) for line in plane.lines}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(_events(line, HOST_SPANS + (WINDOW_SPAN,)))
    return device, host
