"""A configuration of another family joins the benchmark by new files
and ``BENCHMARK.json`` entries alone: a toy dense regressor (neither
deepfm nor autoint) brings its configuration, reference, traffic,
driver and per-layer metrics into a copy of ``benchmarks/chip`` and
runs through ``run.run``, traced (``Record``, named scopes, its own
work count) and untraced, with no file of the copy changed."""
import copy
import hashlib
import json
import os
import shutil

import pytest

import conftest as C
import harness as H

NEW_FILES = {
    "configs/toy.json": json.dumps({
        "model": {"name": "toy", "rows": 64, "width": 32},
        "limits": {"out_gap": 1e-4}}),
    "configs/toy.py": '''
"""Plain reference of the toy: y = tanh(x @ w1) @ w2."""
import numpy as np


def make(seed, model):
    r = np.random.default_rng(seed)
    d = model["width"]
    return (r.standard_normal((model["rows"], d), np.float32),
            r.standard_normal((d, d), np.float32) / d ** 0.5,
            r.standard_normal((d, 1), np.float32) / d ** 0.5)


def forward(x, w1, w2):
    return np.tanh(x.astype(np.float64) @ w1) @ w2


def forward_flops_per_example(model):
    return 2 * model["width"] * (model["width"] + 1)
''',
    "traffic/toy_rows.json": json.dumps({"driver": "toy_loop"}),
    "drivers/toy_loop.py": '''
"""Calls a jitted dense pair, scoped ``toy``, in a closed loop."""
import time

import numpy as np

import harness as H


class Session:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        self.ctx = ctx
        self.args = ctx.ref.make(ctx.seed, ctx.model)

        def f(x, w1, w2):
            with jax.named_scope("toy"):
                return jnp.tanh(x @ w1) @ w2
        self.fn = jax.jit(f)
        self.dev = [jax.device_put(a) for a in self.args]
        self.out = np.asarray(self.fn(*self.dev))

    def window(self):
        t0, n = time.perf_counter(), 0
        while time.perf_counter() < t0 + self.ctx.seconds or n == 0:
            self.out = np.asarray(self.fn(*self.dev))
            n += 1
        dt = time.perf_counter() - t0
        rows = n * self.ctx.model["rows"]
        return {"attempted": n, "failed": 0,
                "metrics": {"toy_rows_per_s": rows / dt},
                "stats": {"steps": n}}

    def programs(self):
        return [self.fn.lower(*self.dev).compile().as_text()]

    def free(self):
        H.delete(self.dev)

    def check(self):
        want = self.ctx.ref.forward(*self.args)
        return {"out_gap": float(np.max(np.abs(self.out - want)))}


def setup(ctx):
    return Session(ctx)
''',
    "metrics/toy_ms.toy.py": '''
"""Device ms of the ``toy`` scope per call."""


def read(rec):
    return rec.scope_ms(("toy",), rec.stats["steps"])
''',
    "metrics/mfu.toy.py": '''
"""The toy's forward operations x rows/s over the bf16 peak, in %."""


def read(rec):
    return (100.0 * rec.flops * rec.e2e["toy_rows_per_s"]
            / rec.peaks["bf16_flops_per_s"])
''',
}


def toy_spec():
    with open(os.path.join(C.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "toy", "source": "-", "file": "benchmarks/chip/configs/"
        "toy.json", "reduced": [], "why": "another family"})
    spec["workloads"].append({"name": "toy.rows", "config": "toy",
                              "traffic": "toy_rows", "chips": 1,
                              "why": "-"})
    spec["end_to_end"].append({"name": "toy_rows_per_s", "unit": "rows/s",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["toy.rows"]})
    for name in ("toy_ms.toy", "mfu.toy"):
        spec["per_layer"].append({"name": name, "unit": "%", "better":
                                  "higher", "source": "device_trace",
                                  "layer": "toy", "moves": "toy_rows_per_s",
                                  "workloads": ["toy.rows"]})
    return spec


def digest(root):
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture
def toy_chip(tmp_path, monkeypatch):
    """A copy of benchmarks/chip with the toy's files added, as the
    harness's directory; yields the copy's digest before the run."""
    chip = tmp_path / "chip"
    shutil.copytree(C.CHIP, chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(chip)
    for rel, text in NEW_FILES.items():
        assert not (chip / rel).exists(), rel
        (chip / rel).write_text(text.lstrip("\n"))
    monkeypatch.setattr(H, "HERE", str(chip))
    yield chip, before


@pytest.mark.parametrize("trace", [False, True])
def test_another_family_joins_by_new_files(toy_chip, monkeypatch, trace):
    import run
    chip, before = toy_chip
    real = digest(C.CHIP)
    C.with_device_ops(monkeypatch)
    out = run.run(copy.deepcopy(toy_spec()), "toy.rows", 5, 0.3, trace,
                  require_tpu=False, peaks=C.CPU_PEAKS)
    assert out["correct"] is True, out["checks"]
    if trace:
        assert set(out["metrics"]) == {"toy_ms.toy", "mfu.toy"}
        assert out["metrics"]["toy_ms.toy"]["value"] > 0
    else:
        assert set(out["metrics"]) == {"toy_rows_per_s", "setup_s"}
    after = digest(chip)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == set(NEW_FILES)
    assert digest(C.CHIP) == real
