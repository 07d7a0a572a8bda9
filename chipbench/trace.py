"""Reduction of one profiler trace to the numbers the readers take.

The profiler writes an ``.xplane.pb``; :func:`load` turns it into plain
events, one plane of device lines per chip of the cell, and
:func:`summarize` reduces those, chip by chip:

* busy time is the union of the intervals in which an operation ran on
  a chip (the ``XLA Ops`` and ``Async XLA Ops`` lines of its plane),
  inside the window the benchmark's own ``window`` span marks on the host;
* each idle gap of a chip inside the window is put down to the host span
  (``client.push``, ``engine.tick``, ``engine.pull``, ``client.wait``,
  ``client.compute``, ``replan``) that overlaps it most, and to the
  innermost of the engine's own ``ps.*`` spans open over it;
* program executions (the ``XLA Modules`` line) are kept by program name
  and chip, so a reader can time one program and the operations inside
  it.

Times summed over chips are chip-seconds: the window of a cell on four
chips is four times its length, so ``1 - busy / window`` is the idle
share of the cell's chips.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
ASYNC_OPS_LINE = "Async XLA Ops"  # DMAs between their start and done
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"
HOST_SPANS = ("client.push", "engine.tick", "engine.pull", "client.wait",
              "client.compute", "replan")
DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
SPAN_PREFIX = "ps."  # the engine's own host spans
OUTSIDE = "outside"  # idle under no such span


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Chip:
    """One chip's device operations and program executions inside the
    window."""

    ops: List[Event]
    modules: List[Event]
    busy_s: float = 0.0

    def executions(self, program: str) -> List[Event]:
        return [e for e in self.modules if program_name(e.name) == program]

    def ops_within(self, runs: List[Event]) -> List[Event]:
        """Device operations that ran inside the given executions of this
        chip."""
        spans = sorted((r.start_ns, r.end_ns) for r in runs)
        out, i = [], 0
        for op in sorted(self.ops, key=lambda e: e.start_ns):
            while i < len(spans) and spans[i][1] < op.start_ns:
                i += 1
            if i < len(spans) and spans[i][0] <= op.start_ns <= spans[i][1]:
                out.append(op)
        return out


@dataclass
class Summary:
    window_s: float  # chip-seconds
    busy_s: float  # chip-seconds
    chips: List[Chip]  # in the order of the cell's devices
    idle_by_host: Dict[str, float] = field(default_factory=dict)
    # idle chip-seconds by the innermost ``ps.*`` span open over them
    # (``outside`` where none is); empty where the trace holds no such span
    idle_by_span: Dict[str, float] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)  # ``ps.*``, window

    @property
    def idle_pct(self) -> Optional[float]:
        if self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    @property
    def ops(self) -> List[Event]:
        return [e for c in self.chips for e in c.ops]

    @property
    def modules(self) -> List[Event]:
        return [e for c in self.chips for e in c.modules]

    def executions(self, program: str) -> List[Event]:
        """Every chip's executions of ``program``."""
        return [e for c in self.chips for e in c.executions(program)]

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


def _by_host_span(idle, spans) -> collections.Counter:
    out = collections.Counter()
    for a, b in idle:
        best, label = 0.0, "host.other"
        for s, t, name in spans:
            if s >= b:
                break
            ov = min(b, t) - max(a, s)
            if ov > best:
                best, label = ov, name
        out[label] += (b - a) / 1e9
    return out


def innermost(spans) -> List[Tuple[float, float, str]]:
    """Time cut into ``(start, end, name)`` pieces by the innermost span
    open over it; time under no span is left out."""
    out, stack, t = [], [], float("-inf")

    def advance(to):
        nonlocal t
        while stack and stack[-1].end_ns <= to:
            top = stack.pop()
            if top.end_ns > t:
                out.append((t, top.end_ns, top.name))
                t = top.end_ns
        if stack and to > t:
            out.append((t, to, stack[-1].name))
        t = max(t, to)

    for e in sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns)):
        advance(e.start_ns)
        stack.append(e)
    advance(float("inf"))
    return [(a, b, n) for a, b, n in out if b > a]


def idle_by_span(idle, spans) -> Dict[str, float]:
    """Seconds of the idle stretches ``idle`` (sorted, disjoint ``(start,
    end)`` in ns) by the innermost span open over them, ``outside``
    where none is."""
    pieces = innermost(spans)
    out = collections.Counter()
    j = 0
    for a, b in idle:
        under = 0.0
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            s, e, name = pieces[k]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                out[name] += ov / 1e9
                under += ov
            k += 1
        if b - a > under:
            out[OUTSIDE] += (b - a - under) / 1e9
    return dict(out)


def device_idle(device, lo: float, hi: float):
    """One chip's idle stretches inside [lo, hi], as :func:`summarize`
    finds them (no operation or DMA running), from its plane's lines."""
    busy = union((e.start_ns, e.end_ns)
                 for line in (OPS_LINE, ASYNC_OPS_LINE)
                 for e in _clip(device.get(line, []), lo, hi))
    return gaps(busy, lo, hi)


def engine_idle_pct(by_span: Dict[str, float], window_s: float):
    """Share (%) of the window in which the device was idle under an open
    ``ps.*`` span."""
    if window_s <= 0:
        return None
    return 100.0 * sum(s for n, s in by_span.items()
                       if n != OUTSIDE) / window_s


def summarize(planes: Sequence[Dict[str, List[Event]]], host: List[Event]
              ) -> Optional[Summary]:
    """Reduce the device lines of each of the cell's chips (one dict of
    lines per chip, empty for a chip the trace shows nothing on) and the
    host's spans.  None when the trace holds no window span or no device
    operation on any chip."""
    win = [e for e in host if e.name == WINDOW_SPAN]
    if not win or not any(p.get(OPS_LINE) for p in planes):
        return None
    lo, hi = win[0].start_ns, win[0].end_ns
    spans = sorted(((e.start_ns, e.end_ns, e.name) for e in host
                    if e.name in HOST_SPANS))
    ps = [e for e in host
          if e.name.startswith(SPAN_PREFIX) and lo <= e.start_ns <= hi]
    chips = []
    idle_host, idle_span = collections.Counter(), collections.Counter()
    for plane in planes:
        idle = device_idle(plane, lo, hi)
        idle_host.update(_by_host_span(idle, spans))
        if ps:
            idle_span.update(idle_by_span(idle, ps))
        busy_ns = hi - lo - sum(b - a for a, b in idle)
        chips.append(Chip(ops=_clip(plane.get(OPS_LINE, []), lo, hi),
                          modules=_clip(plane.get(MODULES_LINE, []), lo, hi),
                          busy_s=busy_ns / 1e9))
    return Summary(window_s=len(planes) * (hi - lo) / 1e9,
                   busy_s=sum(c.busy_s for c in chips), chips=chips,
                   idle_by_host=dict(idle_host),
                   idle_by_span=dict(idle_span), spans=ps)


def _events(line, keep=None) -> List[Event]:
    return [Event(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events if keep is None or keep(e.name)]


def _host_event(name: str) -> bool:
    return (name in HOST_SPANS or name == WINDOW_SPAN
            or name.startswith(SPAN_PREFIX))


def load(trace_dir: str, chips: Sequence[int] = (0,)):
    """(lines by name of the TPU plane of each chip in ``chips``, host
    events) of the one ``.xplane.pb`` under ``trace_dir``.  The host
    events are the benchmark's spans and the engine's ``ps.*`` spans."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {paths}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    by_id, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in chips:
            by_id[int(m.group(1))] = {line.name: _events(line)
                                      for line in plane.lines}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(_events(line, _host_event))
    return [by_id.get(c, {}) for c in chips], host
