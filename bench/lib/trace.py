"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

* busy: the union of the intervals in which an operation ran on a device
  (the device planes' ``XLA Ops`` lines), clipped to the traced window and
  averaged over the devices;
* window: the benchmark's ``bench.window`` span on the host, which the step
  hook opens and closes on step boundaries;
* kernel events: device operations whose HLO name starts with a given
  text (the trace names an op by its HLO text, ``%name = shape opcode(...)``);
* breakdown: the device operations that took most time, and the longest idle
  gaps, each named by the innermost host event of the benchmark's thread that
  covers at least half of it (a gap inside the step hook reads
  ``bench.step_hook``; one in the trainer's own loop reads the program call
  that was running), or else the one that overlaps it most.

The device's clock is aligned to the host's by the profiler to within about a
millisecond, which is negligible against a window of seconds.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Tuple

Interval = Tuple[float, float]
WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Event:
    name: str
    start: float   # ns
    end: float     # ns

    @property
    def op(self) -> str:
        """The HLO instruction's own name: ``fusion.12`` of ``%fusion.12 = ...``."""
        return self.name.split(" = ", 1)[0].lstrip("%")

    @property
    def short(self) -> str:
        """Name, result shape and opcode, without layouts, for reports."""
        return re.sub(r"\{[^{}]*\}", "", self.name.lstrip("%"))[:120]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]     # device plane -> op events
    host: List[Event]                   # events of the thread with WINDOW

    @property
    def window(self) -> Interval:
        spans = [e for e in self.host if e.name == WINDOW]
        if len(spans) != 1:
            raise ValueError(f"expected one {WINDOW} span, found {len(spans)}")
        return spans[0].start, spans[0].end


def load(path) -> Trace:
    """Read one ``.xplane.pb`` (or the only one under a directory)."""
    from jax.profiler import ProfileData
    p = Path(path)
    if p.is_dir():
        found = sorted(p.rglob("*.xplane.pb"))
        if len(found) != 1:
            raise FileNotFoundError(f"{p}: expected one .xplane.pb, "
                                    f"found {len(found)}")
        p = found[0]
    pd = ProfileData.from_file(str(p))
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            evs = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if any(e.name == WINDOW for e in evs):
                    host = evs
    return Trace(devices=devices, host=host)


def union(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Sorted disjoint union of ``intervals`` clipped to [lo, hi]."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace) -> float:
    """Seconds of the window in which some op ran, averaged over devices."""
    lo, hi = trace.window
    if not trace.devices:
        return 0.0
    tot = [sum(b - a for a, b in union([(e.start, e.end) for e in evs], lo, hi))
           for evs in trace.devices.values()]
    return sum(tot) / len(tot) / 1e9


def window_s(trace: Trace) -> float:
    lo, hi = trace.window
    return (hi - lo) / 1e9


def kernel_events(trace: Trace, prefix: str) -> List[Event]:
    """Device events inside the window whose op name starts with ``prefix``."""
    lo, hi = trace.window
    return [e for evs in trace.devices.values() for e in evs
            if e.op.startswith(prefix) and e.start >= lo and e.end <= hi]


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """[[op name, seconds]] of the ``n`` ops with the most device time in the
    window, summed over calls and averaged over devices."""
    lo, hi = trace.window
    tot: Dict[str, float] = {}
    for evs in trace.devices.values():
        for e in evs:
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                tot[e.short] = tot.get(e.short, 0.0) + d
    k = max(len(trace.devices), 1)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in top]


def _name_of(gap: Interval, host: List[Event]) -> str:
    length = gap[1] - gap[0]
    over = [(min(e.end, gap[1]) - max(e.start, gap[0]), e) for e in host]
    over = [(ov, e) for ov, e in over if ov > 0]
    if not over:
        return "no host event"
    covering = [e for ov, e in over if ov >= 0.5 * length]
    if covering:
        return min(covering, key=lambda e: e.end - e.start).name
    return max(over, key=lambda oe: oe[0])[1].name


def idle_gaps(trace: Trace, n: int = 10) -> List[list]:
    """[[host event, seconds]] of the ``n`` longest idle gaps of the first
    device in the window."""
    lo, hi = trace.window
    if not trace.devices:
        return [[_name_of((lo, hi), trace.host), (hi - lo) / 1e9]]
    evs = trace.devices[sorted(trace.devices)[0]]
    busy = union([(e.start, e.end) for e in evs], lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = b
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_name_of(g, trace.host), (g[1] - g[0]) / 1e9] for g in gaps[:n]]
