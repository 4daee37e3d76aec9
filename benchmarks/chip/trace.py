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
  side) that covers the gap's middle;
* ``scope_ns`` — device ns per named scope of the program, at depths 1
  and 2 (:func:`scope_ns`), and ``found_share``, the share of the
  window's device time whose instruction the programs' texts name.

Scopes: the program names its layers with ``jax.named_scope``; the
compiled HLO carries the scope in each instruction's ``op_name``
metadata (``jit(step)/transpose(jvp(fields))/...`` for the gradient).
A fusion the compiler builds without ``op_name`` takes the first one
found in the computations it calls.  The profiler's device ops carry
only the instruction's name (their stats on a v5e:
``device_offset_ps``, ``device_duration_ps``, ``Time Scale
Multiplier``), so the two are joined on that name
(:func:`hlo_op_names`).  The texts come from a driver's session
(``programs()``); an instruction name that two texts share takes the
first text's ``op_name``.

The window is the benchmark's own ``bench.window`` span.  Device ops
are the events of the ``XLA Ops`` line of each ``/device:`` plane.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10
NAME_CHARS = 160
TOP_OPS = 5
SCOPE_DEPTHS = (1, 2)
WRAPPERS = ("jvp(", "transpose(", "vmap(", "checkpoint(")
INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=")
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLS = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")


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
    # depth -> scope -> device ns; empty without the programs' texts
    scope_ns: Dict[int, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    found_share: Optional[float] = None

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


def reduce(events: Sequence[Event], kernel_patterns: Sequence[str] = (),
           op_names: Optional[Mapping[str, str]] = None) -> Reduced:
    """The window's device numbers; ``op_names`` (instruction name ->
    ``op_name``, :func:`hlo_op_names`) adds ``scope_ns`` at
    :data:`SCOPE_DEPTHS` and ``found_share``."""
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
    scopes, found = {}, None
    if op_names:
        scopes = {d: scope_ns(events, op_names, d) for d in SCOPE_DEPTHS}
        found = found_share(events, op_names)
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy / 1e9 / n,
                   n_devices=n, kernel_ns=kernel_ns,
                   kernel_calls=kernel_calls, breakdown=breakdown,
                   spans=by_name, busy_intervals=first, scope_ns=scopes,
                   found_share=found)


def scope_of(op_path: str, depth: int = 1) -> str:
    """The op path's scope: its first ``depth`` components that are
    neither a ``jit(...)`` nor the final primitive, after unwrapping
    ``jvp(``, ``transpose(``, ``vmap(`` and ``checkpoint(``, joined by
    ``/``; ``<scope>.bwd`` when a ``transpose(`` wraps one of them or a
    component before it; ``""`` when there is none.  Of a merged path
    (``a;b``) the first is taken."""
    parts = op_path.split(";")[0].split("/")
    bwd, names = False, []
    for p in parts[:-1]:
        while True:
            w = next((w for w in WRAPPERS
                      if p.startswith(w) and p.endswith(")")), None)
            if w is None:
                break
            bwd |= w == "transpose("
            p = p[len(w):-1]
        if p and not p.startswith("jit("):
            names.append(p)
            if len(names) == depth:
                break
    return "/".join(names) + (".bwd" if bwd and names else "")


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` metadata, over a compiled
    module's text.  An instruction whose own names no scope (a fusion
    the compiler built without one) takes the first ``op_name`` that
    does in the computations it calls, each read in order and its own
    calls where they stand."""
    body: Dict[str, list] = {}      # computation -> [(name, op_name, calls)]
    cur: list = []
    for line in hlo_text.splitlines():
        c = COMPUTATION.match(line)
        if c:
            cur = body.setdefault(c.group(1), [])
            continue
        m = INSTR.match(line)
        if m:
            o = OP_NAME.search(line)
            cur.append((m.group(1), o.group(1) if o else "",
                        CALLS.findall(line)))

    def named(op_name: str, calls: Sequence[str], seen: set) -> str:
        if scope_of(op_name):
            return op_name
        for k in calls:
            if k not in seen:
                seen.add(k)
                for _, o, c in body.get(k, ()):
                    found = named(o, c, seen)
                    if found:
                        return found
        return ""

    return {name: named(o, calls, set()) or o
            for instrs in body.values() for name, o, calls in instrs}


def _inside(events: Sequence[Event], op_names: Mapping[str, str],
            depth: int = 1):
    """(scope, text, ns inside ``bench.window``) of each device op, its
    scope read from ``op_names`` (instruction name -> ``op_name``) to
    ``depth`` components, and the number of devices."""
    lo, hi = window_of(events)
    by_dev = device_ops(events)
    out = []
    for dev, evs in by_dev.items():
        for e in evs:
            if e.end_ns > lo and e.start_ns < hi:
                s, t = clip((e.start_ns, e.end_ns), lo, hi)
                k = scope_of(op_names.get(op_name(e.name), ""), depth)
                out.append((k, e.name[:NAME_CHARS], t - s))
    return out, max(len(by_dev), 1)


def scope_ns(events: Sequence[Event], op_names: Mapping[str, str],
             depth: int = 1) -> Dict[str, float]:
    """Device ns per scope (``depth`` components of it) inside
    ``bench.window``, averaged over the devices (as :func:`reduce`
    averages ``kernel_ns``)."""
    ops, n = _inside(events, op_names, depth)
    out: Dict[str, float] = {}
    for k, _, ns in ops:
        out[k] = out.get(k, 0.0) + ns / n
    return out


def found_share(events: Sequence[Event],
                op_names: Mapping[str, str]) -> float:
    """Share of the device time inside ``bench.window`` whose
    instruction is a key of ``op_names``: what falls short of 1 was
    run by a program whose text was not read, or under another name."""
    lo, hi = window_of(events)
    found = total = 0.0
    for evs in device_ops(events).values():
        for e in evs:
            if e.end_ns > lo and e.start_ns < hi:
                s, t = clip((e.start_ns, e.end_ns), lo, hi)
                total += t - s
                found += (t - s) * (op_name(e.name) in op_names)
    return found / total if total else 0.0


def top_ops(events: Sequence[Event], op_names: Mapping[str, str],
            n: int = TOP_OPS) -> Dict[str, list]:
    """Per scope, its ``n`` ops of most device ns inside the window
    (averaged over the devices), by the start of their text."""
    ops, n_dev = _inside(events, op_names)
    by: Dict[str, Dict[str, float]] = {}
    for k, name, ns in ops:
        d = by.setdefault(k, {})
        d[name] = d.get(name, 0.0) + ns / n_dev
    return {k: sorted(([a, b] for a, b in d.items()),
                      key=lambda x: -x[1])[:n] for k, d in by.items()}


def split_ms(ns: Mapping[str, float], scope: str, steps: int
             ) -> Dict[str, float]:
    """Of the keys of ``scope_ns(..., depth=2)``, those of ``scope``
    (``scope`` itself and ``scope/<nested>``), in ms per step of a
    window of ``steps`` + 1 steps."""
    return {k: v / (steps + 1) / 1e6 for k, v in sorted(ns.items())
            if k == scope or k.startswith(scope + "/")}
