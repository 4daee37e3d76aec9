"""Readings that the limits of ``configs/<config>.json`` are set from.

    python3 benchmarks/chip/control.py --workload deepfm.ctr_online \
        --seeds 1,2,3 --seconds 2

For each seed, in one process: the program's reading of each number
the cell compares (a serving cell runs a short window at its own load;
a training cell needs only its first steps), then the control's: the
reference computed one precision step below what the configuration
states (``reference.Prec(control=True)``) put in the program's place,
read against the float32 reference the same way.  A training cell also
reads the half-batch fault (the reference's steps on half of each
batch, the mean taken over it).  One JSON line per seed and reading on
stdout.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def serving_readings(ctx, driver):
    import numpy as np
    import harness as H
    import reference as R
    s = driver.setup(ctx)
    s.window()
    s.free()
    yield "program", s.check()
    ids = s.pool if hasattr(s, "pool") else s.ring
    exp = H.Expected(ctx, ids)
    ctl, _ = H.reference_scores(ctx, exp.params, exp.flat,
                                R.Prec(control=True))
    exp.free()
    g = exp.gaps(ctl.reshape(ids.shape[:2]), np.arange(ids.shape[0]))
    yield "control", {"score_err": float(g.max())}


def train_readings(ctx, driver):
    import reference as R
    s = driver.setup(ctx)
    s.free()
    yield "program", s.check()
    batches = s.ring[:driver.FIRST_STEPS]
    ref = driver.reference_steps(ctx, batches, R.Prec())

    class Got:
        pass

    for name, prec, bs in (
            ("control", R.Prec(control=True), batches),
            ("half_batch", R.Prec(),
             [{k: v[:len(v) // 2] for k, v in b.items()} for b in batches])):
        r = driver.reference_steps(ctx, bs, prec)
        got = Got()
        got.losses, got.grad_norms, got.change_norms = (
            r["losses"], r["grad_norms"], r["change_norms"])
        yield name, driver.compare(got, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness as H
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("control.py: no TPU")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = {w["name"]: w for w in spec["workloads"]}[args.workload]
    config = H.load_json("configs", cell["config"] + ".json")
    traffic = H.load_json("traffic", cell["traffic"] + ".json")
    ref = H.load_module("configs", cell["config"])
    driver = H.load_module("drivers", traffic["driver"])
    readings = (train_readings if traffic["driver"] == "train_loop"
                else serving_readings)
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = H.Ctx(args.workload, seed, args.seconds, False, config,
                    traffic, ref)
        for name, values in readings(ctx, driver):
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": name, **values}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
