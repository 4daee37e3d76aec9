"""What the drivers share: the cell's context, the weights made from the
seed, the program's model, spans, and the comparison of scores.

Nothing here reads a particular cell, mix or metric: those are found
by name (``configs/<config>.json`` with its reference
``configs/<config>.py``, ``traffic/<traffic>.json``,
``drivers/<driver>.py``, ``metrics/<metric>.py``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, Mapping, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts: str) -> Dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts: str):
    """Import ``benchmarks/chip/<parts>.py`` by path (names may hold
    dots, as metric names do)."""
    path = os.path.join(HERE, *parts) + ".py"
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {os.path.relpath(path, HERE)}")
    name = "chipbench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Ctx:
    """One run of one cell."""
    workload: str
    seed: int
    seconds: float
    trace: bool
    config: Dict                 # configs/<config>.json
    traffic: Dict                # traffic/<traffic>.json
    ref: Any                     # configs/<config>.py

    @property
    def model(self) -> Dict:
        return self.config["model"]

    def recsys_config(self):
        """The program's config, built from the configuration file."""
        from repro.configs.base import RecsysConfig
        names = {f.name for f in dataclasses.fields(RecsysConfig)}
        kw = {k: (tuple(v) if isinstance(v, list) else v)
              for k, v in self.model.items() if k in names}
        return RecsysConfig(**kw)

    def program_model(self):
        """The program's model object, after checking that each field
        runs as the configuration states."""
        from repro.launch.cells import _recsys_model
        import reference as R
        m = self.model
        model = _recsys_model(self.recsys_config())
        for v, emb in zip(m["field_vocab_sizes"], model.fields.embs):
            c = emb.cfg
            want = "mgqe" if R.is_quantized(v, m) else "full"
            if c.kind != want:
                raise ValueError(f"field of {v} ids is {c.kind}, want {want}")
            if want == "mgqe" and (
                    c.mgqe_variant != m["mgqe_variant"]
                    or tuple(c.tier_boundaries) != (R.head_ids(v, m),)
                    or tuple(c.tier_num_centroids) != (
                        m["num_centroids"], m["tier_tail_centroids"])
                    or c.beta != m["beta"]
                    or c.num_subspaces != m["num_subspaces"]):
                raise ValueError(f"field of {v} ids does not run as the "
                                 f"configuration states: {c}")
        return model

    def weights(self, model=None):
        """The weights of this seed, in the program's layout, made on
        the device in one jitted call."""
        import jax
        import reference as R
        params = jax.jit(lambda k: self.ref.make_params(k, self.model))(
            R.key_of(self.seed))
        if model is not None:
            want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            sig = lambda t: [(x.shape, str(x.dtype))
                             for x in jax.tree.leaves(t)]
            if (jax.tree.structure(want) != jax.tree.structure(params)
                    or sig(want) != sig(params)):
                raise ValueError("the weights made from the seed do not "
                                 "match the program's parameter layout")
        jax.block_until_ready(params)
        return params


def span(name: str):
    """A host span in the profiler's trace (``bench.<name>``)."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def sleep_until(t: float) -> None:
    """Wait for perf_counter() >= t: sleep, then spin the last 0.2 ms."""
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        if left > 3e-4:
            time.sleep(left - 2e-4)


def delete(tree) -> None:
    """Free the device buffers of a pytree now."""
    import jax
    for x in jax.tree.leaves(tree):
        if isinstance(x, jax.Array) and not x.is_deleted():
            x.delete()


def reference_scores(ctx: Ctx, params, ids: np.ndarray, prec,
                     swap: bool = False, block: int = 8192):
    """The reference's logits for ids (N, F), in blocks of rows, and the
    number of tied lookups of each example (``reference.assign``)."""
    import jax
    import jax.numpy as jnp
    tie = ctx.config["tie_eps"]
    fn = jax.jit(lambda p, x: ctx.ref.serve_logits(p, x, ctx.model, prec,
                                                   tie, swap))
    scores, tied = [], []
    n = ids.shape[0]
    for lo in range(0, n, block):
        part = ids[lo:lo + block]
        k = part.shape[0]
        if k < block:
            part = np.concatenate([part, np.zeros((block - k,)
                                                  + part.shape[1:],
                                                  part.dtype)])
        s, t = fn(params, jnp.asarray(part))
        scores.append(np.asarray(s)[:k])
        tied.append(np.asarray(t)[:k])
    return np.concatenate(scores), np.concatenate(tied)


class Expected:
    """What the reference says the answers to ``ids`` (R, B, F) are:
    its scores (R, B), the scores with every tied lookup on its
    runner-up code, and which examples are judged (at most one tied
    lookup: then either code is accepted)."""

    def __init__(self, ctx: Ctx, ids: np.ndarray):
        import reference as R
        self.params = ctx.weights()
        flat = ids.reshape(-1, ids.shape[-1])
        best, tied = reference_scores(ctx, self.params, flat, R.Prec())
        alt, _ = reference_scores(ctx, self.params, flat, R.Prec(),
                                  swap=True)
        shape = ids.shape[:2]
        self.best, self.alt = best.reshape(shape), alt.reshape(shape)
        self.judged = tied.reshape(shape) <= 1
        self.rms = float(np.sqrt(np.mean(best.astype(np.float64) ** 2)))
        self.flat = flat

    def gaps(self, answers: np.ndarray, which: np.ndarray) -> np.ndarray:
        """|answer - reference| / rms of the reference's scores, for the
        judged answers; ``answers[j]`` answers request ``which[j]``.  A
        missing (non-finite) answer reads inf."""
        a = np.asarray(answers, np.float64)
        g = np.minimum(np.abs(a - self.best[which]),
                       np.abs(a - self.alt[which])) / self.rms
        g = np.where(np.isfinite(a), g, np.inf)
        return g[self.judged[which]]

    def free(self) -> None:
        delete(self.params)


def check_scores(ctx: Ctx, ids: np.ndarray, answers: np.ndarray,
                 which: np.ndarray) -> Dict[str, float]:
    """Compare every answer with the reference.  Runs after the program
    is freed."""
    exp = Expected(ctx, ids)
    exp.free()
    g = exp.gaps(answers, which)
    tied = np.sum(~exp.judged[which])
    log(f"score gaps / rms over {g.size} answers ({tied} with two or more "
        f"tied lookups not judged): median {np.median(g):.3g}, 99.99th "
        f"{np.percentile(g, 99.99):.3g}, max {g.max():.3g}")
    return {"score_err": float(g.max())}


def percentile(x, q: float) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q))


def check_line(values: Mapping[str, float],
               lims: Mapping[str, float]) -> Dict[str, Dict[str, float]]:
    return {k: {"value": float(values[k]), "limit": float(lims[k])}
            for k in values}


def all_within(checks: Mapping[str, Mapping[str, float]]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def memory(device) -> Dict[str, Optional[int]]:
    st = device.memory_stats() or {}
    return {"peak_bytes_in_use": st.get("peak_bytes_in_use"),
            "bytes_in_use": st.get("bytes_in_use")}


class Served:
    """The serving path a serving cell drives: weights from the seed,
    exported through ``model.fields.export``, float tables deleted, and
    the jitted ``model.serve`` that ``launch/serve.serve_ctr`` returns,
    compiled and run once at ``batch`` examples."""

    def __init__(self, ctx: Ctx, batch: int):
        import jax
        from repro.launch import serve
        self.model = ctx.program_model()
        params = ctx.weights(self.model)
        t = time.perf_counter()
        artifacts = self.model.fields.export(params["fields"])
        jax.block_until_ready(artifacts)
        log(f"export {time.perf_counter() - t:.2f}s")
        # the float tables go; what the artifact shares (centroids) stays
        kept = {id(x) for x in jax.tree.leaves(artifacts)}
        delete([x for x in jax.tree.leaves(params.pop("fields"))
                if id(x) not in kept])
        res = serve.serve_ctr(ctx.recsys_config(), batch, params=params,
                              artifacts=artifacts)
        self.step, self.params, self.artifacts = (res.step, res.params,
                                                  res.artifacts)

    def __call__(self, ids_dev):
        return self.step(self.params, self.artifacts,
                         {"sparse_ids": ids_dev})

    def free(self) -> None:
        delete((self.params, self.artifacts))
        self.step = self.params = self.artifacts = None


