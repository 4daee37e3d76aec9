"""Find an online cell's knee: one set-up, then the cell's open-loop
window at each of several offered rates.

    python3 benchmarks/chip/sweep.py --workload deepfm.ctr_online \
        --rates 200,400,600,800 --seconds 4 --seed 9

Prints one JSON line per rate: offered and completed rate, latency
percentiles, the median service time, and how the latency of the last
tenth of the requests compares with the first tenth (a growing backlog
reads well above 1).  The knee is the highest rate whose backlog does
not grow.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arrival-seeds", default="",
                    help="comma list: repeat each rate with the arrival "
                         "order of each of these seeds (same set-up)")
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import numpy as np
    import gen
    import harness as H
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep.py: no TPU")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = {w["name"]: w for w in spec["workloads"]}[args.workload]
    config = H.load_json("configs", cell["config"] + ".json")
    traffic = H.load_json("traffic", cell["traffic"] + ".json")
    ctx = H.Ctx(args.workload, args.seed, args.seconds, False, config,
                traffic, H.load_module("configs", cell["config"]))
    s = H.load_module("drivers", traffic["driver"]).setup(ctx)
    orders = [int(x) for x in args.arrival_seeds.split(",") if x] or [
        args.seed]
    for rate, order in ((float(r), o) for r in args.rates.split(",")
                        for o in orders):
        s.due = gen.arrivals(rate, args.seconds, gen.rng(order, 2))
        w = s.window()
        st = w["stats"]
        lat = np.asarray(st["latency_s"]) * 1e3
        tenth = max(1, len(lat) // 10)
        print(json.dumps({
            "rate": rate, "order": order, "requests": w["attempted"],
            "completed_per_s": w["attempted"] / st["window_s"],
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": w["metrics"]["request_p95_ms"],
            "p99_ms": float(np.percentile(lat, 99)),
            "service_median_ms": float(np.median(st["service_s"]) * 1e3),
            "backlog_growth": float(lat[-tenth:].mean() / lat[:tenth].mean()),
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
