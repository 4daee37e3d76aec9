"""Chip benchmark: run one cell of ``BENCHMARK.json`` once.

    python3 benchmarks/chip/run.py --workload deepfm.train \
        --seed 7 --seconds 10 --trace 0

Everything about a cell is found by name: the workload's ``config``
names ``configs/<config>.json`` (the configuration as it is run) and
its plain reference ``configs/<config>.py``; its ``traffic`` names
``traffic/<traffic>.json``, whose ``driver`` names
``drivers/<driver>.py``; each per-layer metric is read by
``metrics/<metric>.py``.  Adding a cell, a mix, a driver or a metric
adds files and a ``BENCHMARK.json`` entry; nothing here changes.  The
reference states the configuration's work in
``forward_flops_per_example(model)``; a configuration without it is
refused before set-up.

A run: set-up (weights from the seed, the program's own set-up, warm-up
of this cell's shapes; ``setup_s`` ends when the window starts), the
window of ``--seconds``, then the program's state is freed and what the
window produced is compared with the reference.  With ``--trace 1``
the window (at most ``TRACE_SECONDS`` long, so that the trace stays
small enough to read within the run's time) runs under the profiler
and the per-layer metrics are reported instead of the end-to-end ones;
a driver's session that offers ``programs()`` (the optimized HLO texts
of what its window ran) is asked for them after the window, and the
trace's device time is then also split by the program's named scopes
(``trace.scope_ns``).  The last stdout line is the result; the numbers
compared, each with its limit, are the last stderr lines and the
result's last key.  It exits non-zero, with no result, off a TPU,
with fewer chips than the cell asks for, or with
``REPRO_KERNEL_BACKEND`` / ``REPRO_KERNEL_TUNE_CACHE`` set.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Mapping, Optional, Sequence  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REFUSED_ENV = ("REPRO_KERNEL_BACKEND", "REPRO_KERNEL_TUNE_CACHE")
TRACE_SECONDS = 10.0   # a traced window is at most this long
TOP_COMPILES = 5       # functions named in set-up's log
TOP_SCOPE_OPS = 3      # ops named per scope in a traced run's log


class Refused(Exception):
    """The run cannot measure what the cell asks for."""


class Record:
    """What a per-layer metric reader may read: the window's stats and
    end-to-end numbers, the reduced trace, the configuration and its
    work, the chip's peaks, and set-up's compiles by function
    (``compile_stats()`` records)."""

    def __init__(self, ctx, window, reduced, peaks, setup_compiles):
        import work
        self.stats, self.trace = window["stats"], reduced
        self.e2e = window["metrics"]
        self.model, self.traffic, self.peaks = ctx.model, ctx.traffic, peaks
        self.work = work
        self.flops = ctx.ref.forward_flops_per_example(ctx.model)
        self.setup_compiles = setup_compiles

    def scope_ms(self, scopes: Sequence[str], steps: int) -> Optional[float]:
        """Device ms of ``scopes`` (keys of ``trace.scope_ns[1]``)
        together, per step of a window of ``steps`` steps; ``None``
        where no program's text was read or none of them ran."""
        ns = self.trace.scope_ns.get(1, {})
        if not any(s in ns for s in scopes):
            return None
        return sum(ns.get(s, 0.0) for s in scopes) / steps / 1e6


def since(now: Mapping[str, Mapping[str, float]],
          before: Mapping[str, Mapping[str, float]]) -> Dict[str, Dict]:
    """``compile_stats()`` records of what happened between two
    readings, for the functions that did something."""
    out = {}
    for fn, r in now.items():
        d = {k: v - before.get(fn, {}).get(k, 0) for k, v in r.items()}
        if any(d.values()):
            out[fn] = d
    return out


def total(stats: Mapping[str, Mapping[str, float]], key: str) -> float:
    return sum(r[key] for r in stats.values())


def costliest(stats: Mapping[str, Mapping[str, float]],
              n: int = TOP_COMPILES) -> List[list]:
    """[function, compiles, seconds, traces, trace seconds, hits,
    misses] of the ``n`` functions whose compiles took longest."""
    return sorted(([k, r["compiles"], round(r["compile_s"], 3),
                    r["traces"], round(r["trace_s"], 3),
                    r["cache_hits"], r["cache_misses"]]
                   for k, r in stats.items() if r["compiles"]),
                  key=lambda x: -x[2])[:n]


def _applies(metric, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def run(spec, workload: str, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, config=None, traffic=None, peaks=None):
    """One run of one cell; returns the result line as a dict."""
    import harness as H
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    config = config or H.load_json("configs", cell["config"] + ".json")
    traffic = traffic or H.load_json("traffic", cell["traffic"] + ".json")
    ref = H.load_module("configs", cell["config"])
    if not callable(getattr(ref, "forward_flops_per_example", None)):
        raise Refused(f"configs/{cell['config']}.py defines no "
                      f"forward_flops_per_example(model): each "
                      f"configuration states its own work")
    driver = H.load_module("drivers", traffic["driver"])
    readers = {m["name"]: H.load_module("metrics", m["name"])
               for m in spec["per_layer"] if _applies(m, workload)} \
        if trace else {}

    from repro.launch.compile_cache import compile_stats, setup_compile_cache
    cache = setup_compile_cache()
    at_start = compile_stats()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise Refused(f"JAX platform is {platform!r}, not 'tpu': the chip "
                      f"benchmark measures the chip only")
    if len(devices) < cell["chips"]:
        raise Refused(f"{workload} needs {cell['chips']} chip(s), JAX sees "
                      f"{len(devices)}")
    kind = devices[0].device_kind
    import work
    peaks = peaks or work.peaks(kind)
    H.log(f"{workload} seed {seed}: {kind} x{len(devices)}, compile cache "
          f"{cache}")

    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    ctx = H.Ctx(workload, seed, seconds, trace, config, traffic, ref)
    session = driver.setup(ctx)
    # what set-up made lives through the window: keep it out of the
    # cyclic collector's full passes, as a server does after loading
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    at_setup = compile_stats()
    setup = since(at_setup, at_start)
    H.log(f"set-up {setup_s:.1f}s: {total(setup, 'compiles'):.0f} "
          f"compilations ({total(setup, 'traces'):.0f} jaxpr traces), "
          f"persistent cache hits {total(setup, 'cache_hits'):.0f}, misses "
          f"{total(setup, 'cache_misses'):.0f}; costliest [function, "
          f"compiles, s, traces, s, hits, misses]: {costliest(setup)}")
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # spans and device ops only
            jax.profiler.start_trace(tdir, profiler_options=opts)
        with H.span("window"):
            window = session.window()
        if trace:
            jax.profiler.stop_trace()
        inside = since(compile_stats(), at_setup)
        mem = [H.memory(d) for d in devices[:cell["chips"]]]
        reduced = None
        if trace:
            reduced = _reduce(tdir, session, readers)
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    H.log(f"compilations inside the window: "
          f"{total(inside, 'compiles'):.0f} ({total(inside, 'traces'):.0f} "
          f"jaxpr traces)")
    H.log(f"bytes_in_use after the window: "
          f"{[m['bytes_in_use'] for m in mem]}")
    session.free()
    checks = H.check_line(session.check(), config["limits"])
    correct = H.all_within(checks) and window["failed"] == 0

    units = {m["name"]: m["unit"] for m in spec["end_to_end"]
             + spec["per_layer"]}
    metrics = {}
    if trace:
        rec = Record(ctx, window, reduced, peaks, setup)
        for name, reader in readers.items():
            v = reader.read(rec)
            if v is not None:
                metrics[name] = {"value": float(v), "unit": units[name]}
    else:
        values = dict(window["metrics"], setup_s=setup_s)
        for m in spec["end_to_end"]:
            if _applies(m, workload) and m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    peak = [m["peak_bytes_in_use"] for m in mem]
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": max(peak) if None not in peak else None}
    out = {"correct": bool(correct), "attempted": int(window["attempted"]),
           "failed": int(window["failed"]), "metrics": metrics,
           "device": device}
    if trace:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = reduced.breakdown
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    out["checks"] = checks
    return out


def _reduce(tdir: str, session, readers):
    """The traced window reduced (``trace.reduce``), split by scope
    where the session offers its programs' texts."""
    import harness as H
    T = H.load_module("trace")      # not the stdlib's trace
    events = T.load(T.find_xplane(tdir))
    patterns = sorted({getattr(r, "KERNEL") for r in readers.values()
                       if hasattr(r, "KERNEL")})
    op_names: Dict[str, str] = {}
    if hasattr(session, "programs"):
        t = time.perf_counter()
        for text in session.programs():
            for k, v in T.hlo_op_names(text).items():
                op_names.setdefault(k, v)
        H.log(f"programs' texts {time.perf_counter() - t:.1f}s: "
              f"{len(op_names)} instructions")
    reduced = T.reduce(events, patterns, op_names)
    if not op_names:
        H.log("no programs' texts: the scope readers read nothing")
        return reduced
    H.log(f"instructions found in the programs' texts: "
          f"{100 * reduced.found_share:.4f}% of the window's device time")
    tops = T.top_ops(events, op_names, TOP_SCOPE_OPS)
    for scope, ns in sorted(reduced.scope_ns[1].items(),
                            key=lambda kv: -kv[1]):
        H.log(f"scope {scope!r}: {ns / 1e6:.3f} device ms in the window; "
              f"costliest ops (ms): "
              f"{[[o, v / 1e6] for o, v in tops.get(scope, [])]}")
    nested = {k: v / 1e6 for k, v in sorted(reduced.scope_ns[2].items())
              if "/" in k}
    H.log(f"nested scopes, device ms in the window: {nested}")
    return reduced


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        for var in REFUSED_ENV:
            if os.environ.get(var):
                raise Refused(f"{var} is set; the benchmark measures what "
                              f"the program's dispatch picks on its own")
        if args.seed < 0 or not args.seconds > 0:
            raise Refused("--seed must be >= 0 and --seconds > 0")
        spec_path = os.path.join(ROOT, "BENCHMARK.json")
        if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
            raise Refused(f"no src/repro in {ROOT}: run from a checkout of "
                          f"the repository")
        with open(spec_path) as f:
            spec = json.load(f)
        sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
        out = run(spec, args.workload, args.seed, args.seconds,
                  bool(args.trace))
    except Refused as e:
        print(f"run.py: refused: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
