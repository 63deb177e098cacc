"""From a profiler trace to numbers: device busy time, idle share, the
operations that took most time, and the longest idle gaps by what the host
was doing.

The arithmetic works on plain lists of (name, start_ns, duration_ns) so that
`benchmarks/selfcheck` can check it on hand-built events; `read_xplane`
turns the profiler's `.xplane.pb` into those lists.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
# the device line that holds one event per executed HLO operation
OPS_LINE = "XLA Ops"


@dataclass
class Trace:
    device_ops: Dict[str, List[Event]] = field(default_factory=dict)
    host_marks: List[Event] = field(default_factory=list)
    inventory: List[str] = field(default_factory=list)   # device line: events


_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_OPCODE = re.compile(r" [a-z][a-z0-9\-]*\(")


def short_op_name(hlo: str) -> str:
    """`%fusion.7 = u32[4096]{0:T(1024)} fusion(u32[8]{..} %a, s32[4096]{..}
    %b), kind=..` as `fusion.7 u32[4096]<-u32[8],s32[4096]`: the name XLA
    gave the operation, with the shapes that say what it is."""
    name, eq, rest = hlo.partition(" = ")
    name = name.lstrip("%")
    if not eq:
        return name
    call = _OPCODE.search(rest)
    if call is None:
        return name
    shapes = _SHAPE.findall(rest[:call.start()])
    args = _SHAPE.findall(rest[call.end():].split("), ")[0])
    return f"{name} {','.join(shapes)}<-{','.join(args)}"[:160]


def read_xplane(trace_dir: str, mark_names: Sequence[str]) -> Trace:
    """The device-operation events of every TPU plane, and the host events
    named in `mark_names` (the benchmark's own TraceAnnotations)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    out = Trace()
    wanted = set(mark_names)
    for plane in ProfileData.from_file(paths[0]).planes:
        is_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            events = [(e.name, float(e.start_ns), float(e.duration_ns))
                      for e in line.events] \
                if is_device or plane.name == HOST_PLANE else []
            if is_device:
                out.inventory.append(f"{plane.name} / {line.name}: "
                                     f"{len(events)}")
            if is_device and line.name == OPS_LINE:
                out.device_ops.setdefault(plane.name, []).extend(
                    (short_op_name(n), s, d) for n, s, d in events)
            elif plane.name == HOST_PLANE:
                out.host_marks.extend(e for e in events if e[0] in wanted)
    out.host_marks.sort(key=lambda e: e[1])
    return out


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    """The parts of `events` inside [lo, hi]."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def merge(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Union of the events' intervals as sorted, disjoint (start, end)."""
    out: List[Tuple[float, float]] = []
    for start, end in sorted((s, s + d) for _, s, d in events):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def busy_ns(events: Sequence[Event]) -> float:
    return sum(e - s for s, e in merge(events))


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Nanoseconds per operation name, a parent (a while loop, a fusion's
    caller) counted without the children nested inside it."""
    total: Dict[str, float] = {}
    stack: List[List] = []               # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _end, self_ns = stack.pop()
            total[name] = total.get(name, 0.0) + self_ns

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            # a child's time is not its parent's
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return total


def idle_gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float,
              marks: Sequence[Event], unmarked: str) -> Dict[str, float]:
    """Idle nanoseconds inside [lo, hi] by what the host was doing: each gap
    is cut where a host mark starts or ends, and each piece goes to the
    innermost mark that covers it (`unmarked` where none does)."""
    out: Dict[str, float] = {}
    marks = sorted(marks, key=lambda e: e[1])
    starts = [s for _, s, _ in marks]
    cuts = sorted({x for _, s, d in marks for x in (s, s + d)})
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    for gap_lo, gap_hi in zip(edges[0::2], edges[1::2]):
        inner = cuts[bisect.bisect_right(cuts, gap_lo):
                     bisect.bisect_left(cuts, gap_hi)]
        for piece_lo, piece_hi in zip([gap_lo] + inner, inner + [gap_hi]):
            if piece_hi <= piece_lo:
                continue
            mid = (piece_lo + piece_hi) / 2
            # innermost: the latest to start among the marks covering mid
            label = unmarked
            for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
                name, start, dur = marks[i]
                if mid <= start + dur:
                    label = name
                    break
            out[label] = out.get(label, 0.0) + (piece_hi - piece_lo)
    return out


def top(ns_by_name: Dict[str, float], n: int = 10) -> List[List]:
    """[[name, seconds], ...], the n largest."""
    ranked = sorted(ns_by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def reduce(trace: Trace, window_mark: str, unmarked: str) -> dict:
    """The traced window is from the first `window_mark` host event's start
    to the last one's end.  Busy time is the union of device-operation
    intervals inside it, per device."""
    spans = [e for e in trace.host_marks if e[0] == window_mark]
    if not spans or not trace.device_ops:
        return {}
    lo = spans[0][1]
    hi = max(s + d for _, s, d in spans)
    per_device = {name: clip(events, lo, hi)
                  for name, events in trace.device_ops.items()}
    busy = {name: busy_ns(events) for name, events in per_device.items()}
    busiest = max(busy, key=busy.get)
    if busy[busiest] <= 0:
        return {}
    gaps = idle_gaps(merge(per_device[busiest]), lo, hi, trace.host_marks,
                     unmarked)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy.values()) / len(busy) / 1e9,
        "busiest_busy_s": busy[busiest] / 1e9,
        "devices": len(busy),
        "breakdown": {"device_ops": top(self_times(per_device[busiest])),
                      "idle_gaps": top(gaps)},
    }
