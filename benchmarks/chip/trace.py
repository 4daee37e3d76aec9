"""Reduction of a profiler trace to the benchmark's device numbers.

The trace is a flat list of :class:`Event` (plane, line, name, start,
duration, in ns), read from the ``.xplane.pb`` that ``jax.profiler``
writes (:func:`load`).  From it:

* ``busy_s`` — the union of the intervals in which an operation ran on
  a device, inside the window, averaged over the devices;
* ``idle_share`` — ``1 - busy_s / window_s``;
* ``kernel_ns`` / ``kernel_calls`` — summed device time and count of
  the device ops whose op name (the HLO instruction's name, before
  `` = `` in the event's text) contains a pattern (a kernel's name);
* ``busy_intervals`` — the first device's merged busy intervals;
* ``spans`` — the ``bench.*`` host spans inside the window, by name;
* ``breakdown`` — the device ops that took most time, and the longest
  idle gaps, each labelled with the benchmark span (``bench.*``, host
  side) that covers the gap's middle.

The window is the benchmark's own ``bench.window`` span.  Device ops
are the events of the ``XLA Ops`` line of each ``/device:`` plane.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10
NAME_CHARS = 160


def op_name(text: str) -> str:
    """``%mgqe_decode.3 = f32[...] custom-call(...)`` -> ``mgqe_decode.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


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


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> List[Event]:
    """Every event of every line of every plane of one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def clip(iv: Tuple[float, float], lo: float, hi: float):
    return max(iv[0], lo), min(iv[1], hi)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, disjoint intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def window_of(events: Sequence[Event]) -> Tuple[float, float]:
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"{len(spans)} {WINDOW_SPAN!r} spans in the trace, "
                         f"want exactly 1")
    return spans[0].start_ns, spans[0].end_ns


def device_ops(events: Sequence[Event]) -> Dict[str, List[Event]]:
    """Device op events, by device plane."""
    out: Dict[str, List[Event]] = {}
    for e in events:
        if e.plane.startswith(DEVICE_PLANE_PREFIX) and e.line == OPS_LINE:
            out.setdefault(e.plane, []).append(e)
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    kernel_ns: Dict[str, float]
    kernel_calls: Dict[str, float]
    breakdown: Dict[str, list]
    spans: Dict[str, List[Tuple[float, float]]]
    busy_intervals: List[Tuple[float, float]]   # first device, ns

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _label(mid: float, spans: Sequence[Event]) -> str:
    best: Optional[Event] = None
    for s in spans:
        if s.start_ns <= mid < s.end_ns and s.name != WINDOW_SPAN:
            if best is None or s.dur_ns < best.dur_ns:
                best = s
    return best.name if best is not None else "no bench span"


def reduce(events: Sequence[Event],
           kernel_patterns: Sequence[str] = ()) -> Reduced:
    lo, hi = window_of(events)
    by_dev = device_ops(events)
    if not by_dev:
        raise ValueError("no device op events in the trace")
    spans = [e for e in events if e.name.startswith(SPAN_PREFIX)]
    busy, ops_time, gaps, first = 0.0, {}, [], None
    kernel_ns = {p: 0.0 for p in kernel_patterns}
    kernel_calls = {p: 0 for p in kernel_patterns}
    for evs in by_dev.values():
        inside = [e for e in evs if e.end_ns > lo and e.start_ns < hi]
        merged = union(clip((e.start_ns, e.end_ns), lo, hi) for e in inside)
        busy += sum(e - s for s, e in merged)
        first = merged if first is None else first
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, (s + e) / 2))
        for e in inside:
            s, t = clip((e.start_ns, e.end_ns), lo, hi)
            ops_time[e.name] = ops_time.get(e.name, 0.0) + (t - s)
            name = op_name(e.name)
            for p in kernel_patterns:
                if p in name:
                    kernel_ns[p] += t - s
                    kernel_calls[p] += 1
    n = len(by_dev)
    kernel_ns = {p: v / n for p, v in kernel_ns.items()}
    kernel_calls = {p: v / n for p, v in kernel_calls.items()}
    gaps.sort(reverse=True)
    breakdown = {
        "device_ops": [[k[:NAME_CHARS], v / 1e9 / n] for k, v in sorted(
            ops_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_label(mid, spans), g / 1e9] for g, mid in gaps[:TOP]],
    }
    by_name: Dict[str, List[Tuple[float, float]]] = {}
    for s in spans:
        if lo <= s.start_ns < hi:
            by_name.setdefault(s.name, []).append((s.start_ns, s.end_ns))
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy / 1e9 / n,
                   n_devices=n, kernel_ns=kernel_ns,
                   kernel_calls=kernel_calls, breakdown=breakdown,
                   spans=by_name, busy_intervals=first)
