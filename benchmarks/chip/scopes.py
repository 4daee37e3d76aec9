"""Device time per named scope of the program, and set-up compiles per
function, for one cell: the per-layer split of a train step.

    python3 benchmarks/chip/scopes.py --workload deepfm.train \
        --seed 7 --seconds 10

The program names its layers with ``jax.named_scope`` (``fields``,
``first_order``, ``tower``, ``optimizer``); the step's optimized HLO
carries the scope in each instruction's ``op_name`` metadata
(``jit(step)/transpose(jvp(fields))/...`` for the gradient).  A fusion
the compiler builds without ``op_name`` takes the first one found in
the computations it calls.  The profiler's device ops carry only the
instruction's name (their stats on a v5e: ``device_offset_ps``,
``device_duration_ps``, ``Time Scale Multiplier``), so the two are
joined on that name.  The HLO is the step's text as the runtime keeps
it; the persistent cache is off here, so that the step compiles in
this process.

This runs the cell's set-up as ``run.py`` does, then three windows of
``--seconds`` on the same state, the second under the profiler, and
prints one JSON line:

* ``train_examples_per_s`` of each window (the tracer's cost);
* per step of the traced window (device ns inside ``bench.window``
  over the steps that ran in it, the traffic driver's ``steps`` + 1,
  since the window's first step is dispatched before its loop), in ms:
  ``fields_fwd_ms``, ``fields_bwd_ms``, ``first_order_ms`` (forward and
  gradient), ``tower_ms`` (both), ``optimizer_ms``, ``unscoped_ms``,
  their sum, the device's busy time (union of op intervals), each
  scope's costliest ops, and the longest idle gaps;
* ``optimizer_split_ms``: ``optimizer_ms`` by its nested scope
  (``optimizer/clip``, ``optimizer/<kind>``), per step;
* ``compile_s`` / ``compiles``: backend compiles during set-up, from
  the program's ``compile_stats()``, and the costliest functions with
  their compiles, seconds, jaxpr traces and trace seconds, cache hits
  and misses;
* ``device``: the platform, kind and count of the devices.

Like ``run.py`` it refuses to run off a TPU, with fewer chips than the
cell asks for, or with ``REPRO_KERNEL_BACKEND`` /
``REPRO_KERNEL_TUNE_CACHE`` set.  It checks nothing against the
reference; ``run.py`` does that.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import re
import shutil
import sys
import tempfile
import time
from typing import Dict, Iterable, Mapping, Optional, Sequence, Tuple

import harness as H

T = H.load_module("trace")      # not the stdlib's trace
R = H.load_module("run")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
WRAPPERS = ("jvp(", "transpose(", "vmap(", "checkpoint(")
INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=")
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLS = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
# metric -> the scopes it sums
METRICS = {
    "fields_fwd_ms": ("fields",),
    "fields_bwd_ms": ("fields.bwd",),
    "first_order_ms": ("first_order", "first_order.bwd"),
    "tower_ms": ("tower", "tower.bwd"),
    "optimizer_ms": ("optimizer", "optimizer.bwd"),
    "unscoped_ms": ("",),
}
TOP_COMPILES = 15
TOP_OPS = 5


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


def _inside(events: Sequence, op_names: Mapping[str, str], depth: int = 1):
    """(scope, text, ns inside ``bench.window``) of each device op, its
    scope read from ``op_names`` (instruction name -> ``op_name``) to
    ``depth`` components, and the number of devices."""
    lo, hi = T.window_of(events)
    by_dev = T.device_ops(events)
    out = []
    for dev, evs in by_dev.items():
        for e in evs:
            if e.end_ns > lo and e.start_ns < hi:
                s, t = T.clip((e.start_ns, e.end_ns), lo, hi)
                k = scope_of(op_names.get(T.op_name(e.name), ""), depth)
                out.append((k, e.name[:T.NAME_CHARS], t - s))
    return out, max(len(by_dev), 1)


def scope_ns(events: Sequence, op_names: Mapping[str, str],
             depth: int = 1) -> Dict[str, float]:
    """Device ns per scope (``depth`` components of it) inside
    ``bench.window``, averaged over the devices (as ``trace.reduce``
    averages ``kernel_ns``)."""
    ops, n = _inside(events, op_names, depth)
    out: Dict[str, float] = {}
    for k, _, ns in ops:
        out[k] = out.get(k, 0.0) + ns / n
    return out


def top_ops(events: Sequence, op_names: Mapping[str, str],
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


def per_step_ms(ns: Mapping[str, float], steps: int) -> Dict[str, float]:
    """The metrics of :data:`METRICS`, per step of a window of
    ``steps`` + 1 steps."""
    n = steps + 1
    return {m: sum(ns.get(s, 0.0) for s in scopes) / n / 1e6
            for m, scopes in METRICS.items()}


def compile_totals(stats: Mapping[str, Mapping[str, float]]
                   ) -> Tuple[float, int]:
    """(seconds, number) of backend compiles over every function."""
    return (sum(r["compile_s"] for r in stats.values()),
            int(sum(r["compiles"] for r in stats.values())))


def split_ms(ns: Mapping[str, float], scope: str, steps: int
             ) -> Dict[str, float]:
    """Of the keys of ``scope_ns(..., depth=2)``, those of ``scope``
    (``scope`` itself and ``scope/<nested>``), in ms per step of a
    window of ``steps`` + 1 steps."""
    return {k: v / (steps + 1) / 1e6 for k, v in sorted(ns.items())
            if k == scope or k.startswith(scope + "/")}


def costliest(stats: Mapping[str, Mapping[str, float]],
              n: int = TOP_COMPILES) -> Iterable:
    """[function, compiles, seconds, traces, trace seconds, hits,
    misses] of the ``n`` functions whose compiles took longest."""
    return sorted(([k, r["compiles"], round(r["compile_s"], 3),
                    r["traces"], round(r["trace_s"], 3),
                    r["cache_hits"], r["cache_misses"]]
                   for k, r in stats.items() if r["compiles"]),
                  key=lambda x: -x[2])[:n]


def _step_hlo(session) -> str:
    """The text of a train session's compiled step, as the runtime
    keeps it."""
    import jax
    x = jax.device_put(session.ring[0])
    return session.step_fn.lower(session.state, x).compile().as_text()


def _device(cell, require_tpu: bool = True) -> Dict:
    """The devices JAX sees, refused (:class:`run.Refused`) off a TPU or
    with fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise R.Refused(f"JAX platform is {platform!r}, not 'tpu': the "
                        f"per-layer split is measured on the chip only")
    if len(devices) < cell["chips"]:
        raise R.Refused(f"{cell['name']} needs {cell['chips']} chip(s), "
                        f"JAX sees {len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def main(argv: Optional[Sequence[str]] = None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        out = _measure(args, t_start)
    except R.Refused as e:
        print(f"scopes.py: refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0


def _measure(args, t_start: float) -> Dict:
    for var in R.REFUSED_ENV:
        if os.environ.get(var):
            raise R.Refused(f"{var} is set; the benchmark measures what "
                            f"the program's dispatch picks on its own")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        raise R.Refused(f"no workload {args.workload!r}; have "
                        f"{sorted(cells)}")
    cell = cells[args.workload]
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import compile_stats, setup_compile_cache
    setup_compile_cache()
    import jax
    device = _device(cell)
    # with the persistent cache on, the step's text came back without
    # its op_name metadata on a v5e: compile the step in this process
    jax.config.update("jax_enable_compilation_cache", False)
    traffic = H.load_json("traffic", cell["traffic"] + ".json")
    ctx = H.Ctx(args.workload, args.seed, args.seconds, True,
                H.load_json("configs", cell["config"] + ".json"), traffic,
                H.load_module("configs", cell["config"]))
    session = H.load_module("drivers", traffic["driver"]).setup(ctx)
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    setup = compile_stats()
    before = session.window()
    tdir = tempfile.mkdtemp(prefix="chipbench-scopes-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        with H.span("window"):
            traced = session.window()
        jax.profiler.stop_trace()
        after = session.window()
        events = T.load(T.find_xplane(tdir))
        op_names = hlo_op_names(_step_hlo(session))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    session.free()
    reduced = T.reduce(events)
    steps = traced["stats"]["steps"]
    ms = per_step_ms(scope_ns(events, op_names), steps)
    opt = split_ms(scope_ns(events, op_names, depth=2), "optimizer", steps)
    tops = {k: [[name, ns / (steps + 1) / 1e6] for name, ns in v]
            for k, v in top_ops(events, op_names).items()}
    compile_s, compiles = compile_totals(setup)
    out = {
        "workload": args.workload, "seed": args.seed,
        "train_examples_per_s": {
            k: w["metrics"]["train_examples_per_s"] for k, w in (
                ("untraced_before", before), ("traced", traced),
                ("untraced_after", after))},
        "steps_in_window": steps + 1,
        "per_step_ms": dict(ms, sum=sum(ms.values()),
                            busy=reduced.busy_s * 1e3 / (steps + 1)),
        "optimizer_split_ms": opt,
        "top_ops_ms": tops,
        "idle_share": reduced.idle_share,
        "setup_s": setup_s, "compile_s": compile_s, "compiles": compiles,
        "costliest_compiles": list(costliest(setup)),
        "idle_gaps": reduced.breakdown["idle_gaps"],
        "named_ops": sum(bool(v) for v in op_names.values()),
        "unnamed_ops": sum(not v for v in op_names.values()),
        "device": device,
    }
    return out


if __name__ == "__main__":
    raise SystemExit(main())
