"""From a profiler trace to device busy time, kernel time and idle gaps.

``load_events`` flattens a JAX profiler ``.xplane.pb`` into plain event
records; everything else works on those records, so the reduction can be
checked on a small recorded trace (``tests/data``).

- Device events are those of planes named ``/device:TPU:<n>`` on the line
  ``XLA Ops`` (one record per executed HLO op) and ``XLA Modules`` (one per
  executed program).
- Busy time is the union of a device's op intervals inside the window,
  averaged over the devices used; idle share is one minus busy over window.
- Kernel time sums the device time of the events that ``kernels.json`` maps
  to a kernel (by line and name pattern).
- Each idle gap (a stretch of the window with no op on the device) is
  labelled by the host span of the harness (``bench.*``) that overlaps it
  most, so idle time reads as "waiting for arrivals", "inside run_batch",
  and so on.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_events(xplane_path: str) -> List[Event]:
    """Device op/module events and the harness's host spans of one trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    out = []
    for plane in data.planes:
        is_device = bool(_DEVICE.match(plane.name))
        for line in plane.lines:
            if is_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if not is_device and not e.name.startswith(HOST_PREFIX):
                    continue
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def read_events(path: str) -> List[Event]:
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


def window(events: List[Event]) -> Optional[Tuple[float, float]]:
    """The measured window as the harness's ``bench.window`` span marks it."""
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if not spans:
        return None
    return min(e.start_ns for e in spans), max(e.end_ns for e in spans)


def _clip(events: Iterable[Event], t0: float, t1: float) -> List[Tuple[float, float]]:
    out = []
    for e in events:
        a, b = max(e.start_ns, t0), min(e.end_ns, t1)
        if b > a:
            out.append((a, b))
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def device_planes(events: List[Event]) -> List[str]:
    return sorted({e.plane for e in events if _DEVICE.match(e.plane)})


def busy_ns(events: List[Event], t0: float, t1: float) -> Dict[str, float]:
    """Per device plane: the union of its op intervals inside [t0, t1]."""
    out = {}
    for plane in device_planes(events):
        ops = [e for e in events if e.plane == plane and e.line == OPS_LINE]
        out[plane] = sum(b - a for a, b in _union(_clip(ops, t0, t1)))
    return out


def kernel_ns(events: List[Event], table: Dict[str, dict], t0: float,
              t1: float) -> Dict[str, float]:
    """Per kernel of the name table: summed device time inside the window.
    A kernel with no matching event is left out."""
    out = {}
    for kernel, rule in table.items():
        pat = re.compile(rule["pattern"])
        hits = [e for e in events if _DEVICE.match(e.plane)
                and e.line == rule["line"] and pat.search(e.name)]
        total = sum(b - a for a, b in _clip(hits, t0, t1))
        if total > 0:
            out[kernel] = total
    return out


def op_name(name: str) -> str:
    """An op event's name is its HLO instruction (``%fusion.3 = f32[...]
    fusion(...)``); keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def top_ops(events: List[Event], t0: float, t1: float, k: int = 10) -> List[list]:
    """The device ops that took most time, as [name, seconds]."""
    per: Dict[str, float] = {}
    for e in events:
        if _DEVICE.match(e.plane) and e.line == OPS_LINE:
            a, b = max(e.start_ns, t0), min(e.end_ns, t1)
            if b > a:
                name = op_name(e.name)
                per[name] = per.get(name, 0.0) + (b - a)
    ranked = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(events: List[Event], t0: float, t1: float, k: int = 10) -> List[list]:
    """Idle time of the first device inside the window, summed by the host
    span (``bench.*`` other than the window) that overlaps each gap most;
    gaps no span overlaps count as ``host:other``.  As [label, seconds]."""
    planes = device_planes(events)
    if not planes:
        return []
    ops = [e for e in events if e.plane == planes[0] and e.line == OPS_LINE]
    busy = _union(_clip(ops, t0, t1))
    gaps, cur = [], t0
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        gaps.append((cur, t1))
    spans = sorted((e for e in events if not _DEVICE.match(e.plane)
                    and e.name.startswith(HOST_PREFIX) and e.name != WINDOW_SPAN),
                   key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans]
    import bisect

    per: Dict[str, float] = {}
    longest = max((e.dur_ns for e in spans), default=0.0)
    for a, b in gaps:
        best, best_overlap = "host:other", 0.0
        i = bisect.bisect_left(starts, a - longest)
        while i < len(spans) and spans[i].start_ns < b:
            ov = min(b, spans[i].end_ns) - max(a, spans[i].start_ns)
            if ov > best_overlap:
                best, best_overlap = spans[i].name[len(HOST_PREFIX):], ov
            i += 1
        per[best] = per.get(best, 0.0) + (b - a)
    ranked = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: Optional[float]  # averaged over device planes; None without any
    kernel_s: Dict[str, float]
    device_ops: List[list]
    idle_gaps: List[list]


def summarize(events: List[Event], table: Dict[str, dict]) -> Optional[Summary]:
    w = window(events)
    if w is None:
        return None
    t0, t1 = w
    busy = busy_ns(events, t0, t1)
    return Summary(
        window_s=(t1 - t0) / 1e9,
        busy_s=(sum(busy.values()) / len(busy) / 1e9) if busy else None,
        kernel_s={k: v / 1e9 for k, v in kernel_ns(events, table, t0, t1).items()},
        device_ops=top_ops(events, t0, t1),
        idle_gaps=idle_gaps(events, t0, t1),
    )


def find_xplane(log_dir: str) -> Optional[str]:
    for root, _, files in os.walk(log_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(root, f)
    return None
