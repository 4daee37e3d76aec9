"""Small-size, CPU-only fixtures for the chip benchmark's own tests:

    python -m pytest benchmarks/chip/tests

They drive the harness with ``require_tpu=False`` on configurations
cut to a few small fields; no test here measures a time.
"""
import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (os.path.join(ROOT, "src"), CHIP):
    if p not in sys.path:
        sys.path.insert(0, p)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

SMALL_VOCABS = [20_000, 10_000, 1_000, 100]
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def small_config(name: str):
    with open(os.path.join(CHIP, "configs", f"{name}.json")) as f:
        c = json.load(f)
    m = c["model"]
    m.update(n_sparse=len(SMALL_VOCABS), field_vocab_sizes=SMALL_VOCABS)
    if "mlp_dims" in m:
        m["mlp_dims"] = [32, 32]
    if "n_attn_layers" in m:
        m.update(n_attn_layers=2, d_attn=8)
    return c


# cells whose files exist and run here, but which are not (yet) cells
# of BENCHMARK.json: their chip proof is still open (PERF.md)
EXTRA_CELLS = [
    ({"name": "deepfm.ctr_online", "config": "deepfm",
      "traffic": "ctr_online_deepfm", "chips": 1, "why": "-"},
     ("request_p95_ms", "ms", "lower")),
    ({"name": "autoint.ctr_online", "config": "autoint",
      "traffic": "ctr_online_autoint", "chips": 1, "why": "-"},
     ("request_p95_ms", "ms", "lower")),
    ({"name": "autoint.ctr_bulk", "config": "autoint",
      "traffic": "ctr_bulk_65536", "chips": 1, "why": "-"},
     ("score_examples_per_s", "examples/s", "higher")),
]


@pytest.fixture(scope="session")
def spec():
    """BENCHMARK.json with the extra cells added."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        s = json.load(f)
    e2e = {m["name"]: m for m in s["end_to_end"]}
    for cell, (metric, unit, better) in EXTRA_CELLS:
        s["workloads"].append(cell)
        m = e2e.setdefault(metric, {"name": metric, "unit": unit,
                                    "better": better, "bound": 0.25,
                                    "source": "host_clock",
                                    "workloads": []})
        m["workloads"].append(cell["name"])
    s["end_to_end"] = list(e2e.values())
    return s


def small_traffic(name: str):
    with open(os.path.join(CHIP, "traffic", f"{name}.json")) as f:
        t = json.load(f)
    if t["driver"] == "open_loop_fifo":
        t.update(batch=64, rate_rps=40, pool=8, warm_requests=1)
    elif t["driver"] == "closed_loop_scoring":
        t.update(batch=256, ring=3, warm_batches=1)
    else:
        t.update(batch=256, ring=4)
    return t


def run_small(spec, workload, seed=3, seconds=0.5, trace=False, **kw):
    import run
    cell = {w["name"]: w for w in spec["workloads"]}[workload]
    return run.run(copy.deepcopy(spec), workload, seed, seconds, trace,
                   require_tpu=False, config=small_config(cell["config"]),
                   traffic=small_traffic(cell["traffic"]), peaks=CPU_PEAKS,
                   **kw)


def with_device_ops(monkeypatch):
    """A CPU trace has no device plane: add one op, 10 ns, for each
    scope the programs' texts name, inside the window, before the trace
    is reduced."""
    import harness as H
    T = H.load_module("trace")
    real = T.reduce

    def reduce(events, patterns=(), op_names=None):
        lo, _ = T.window_of(events)
        one = {}
        for name, path in sorted((op_names or {}).items()):
            one.setdefault(T.scope_of(path), name)
        ops = [T.Event("/device:TPU:0", "XLA Ops", f"%{name} = f32[1] op()",
                       lo + 10 * i, 10.0)
               for i, name in enumerate(one.values())]
        return real(list(events) + ops, patterns, op_names)
    monkeypatch.setattr(T, "reduce", reduce)
