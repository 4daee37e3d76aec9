"""Chip benchmark: run one cell of ``BENCHMARK.json`` once.

    python3 benchmarks/chip/run.py --workload deepfm.ctr_online \
        --seed 7 --seconds 10 --trace 0

Everything about a cell is found by name: the workload's ``config``
names ``configs/<config>.json`` (the configuration as it is run) and
its plain reference ``configs/<config>.py``; its ``traffic`` names
``traffic/<traffic>.json``, whose ``driver`` names
``drivers/<driver>.py``; each per-layer metric is read by
``metrics/<metric>.py``.  Adding a cell, a mix, a driver or a metric
adds files and a ``BENCHMARK.json`` entry; nothing here changes.

A run: set-up (weights from the seed, the program's own set-up, warm-up
of this cell's shapes; ``setup_s`` ends when the window starts), the
window of ``--seconds``, then the program's state is freed and what the
window produced is compared with the reference.  With ``--trace 1``
the window (at most ``TRACE_SECONDS`` long, so that the trace stays
small enough to read within the run's time) runs under the profiler
and the per-layer metrics are reported instead of the end-to-end ones.  The last stdout line is the
result; the numbers compared, each with its limit, are the last
stderr lines and the result's last key.  It exits non-zero, with no
result, off a TPU, with fewer chips than the cell asks for, or with
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

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REFUSED_ENV = ("REPRO_KERNEL_BACKEND", "REPRO_KERNEL_TUNE_CACHE")
TRACE_SECONDS = 10.0   # a traced window is at most this long
CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",
                "/jax/compilation_cache/cache_misses")
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class Refused(Exception):
    """The run cannot measure what the cell asks for."""


class Record:
    """What a per-layer metric reader may read."""

    def __init__(self, ctx, window, reduced, peaks):
        import work
        self.stats, self.trace = window["stats"], reduced
        self.e2e = window["metrics"]
        self.model, self.traffic, self.peaks = ctx.model, ctx.traffic, peaks
        self.work = work
        self.flops = work.forward_flops_per_example(ctx.model)


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
    driver = H.load_module("drivers", traffic["driver"])
    readers = {m["name"]: H.load_module("metrics", m["name"])
               for m in spec["per_layer"] if _applies(m, workload)} \
        if trace else {}

    from repro.launch.compile_cache import setup_compile_cache
    cache = setup_compile_cache()
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

    compiles, cache = [], []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name in COMPILE_EVENTS else None)
    jax.monitoring.register_event_listener(
        lambda name, **kw: cache.append(name)
        if name in CACHE_EVENTS else None)

    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    ctx = H.Ctx(workload, seed, seconds, trace, config, traffic, ref)
    session = driver.setup(ctx)
    # what set-up made lives through the window: keep it out of the
    # cyclic collector's full passes, as a server does after loading
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    n_before = len(compiles)
    H.log(f"set-up {setup_s:.1f}s: {n_before} compilations, persistent "
          f"cache hits {cache.count(CACHE_EVENTS[0])}, misses "
          f"{cache.count(CACHE_EVENTS[1])}")
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
        n_compiles = len(compiles) - n_before
        mem = [H.memory(d) for d in devices[:cell["chips"]]]
        reduced = None
        if trace:
            T = H.load_module("trace")      # not the stdlib's trace
            patterns = sorted({getattr(r, "KERNEL") for r in readers.values()
                               if hasattr(r, "KERNEL")})
            reduced = T.reduce(T.load(T.find_xplane(tdir)), patterns)
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    H.log(f"compilations inside the window: {n_compiles}")
    H.log(f"bytes_in_use after the window: "
          f"{[m['bytes_in_use'] for m in mem]}")
    session.free()
    checks = H.check_line(session.check(), config["limits"])
    correct = H.all_within(checks) and window["failed"] == 0

    units = {m["name"]: m["unit"] for m in spec["end_to_end"]
             + spec["per_layer"]}
    metrics = {}
    if trace:
        rec = Record(ctx, window, reduced, peaks)
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
