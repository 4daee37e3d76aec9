"""Training steps on pre-made batches, one step in flight.

The step is the one ``launch/train.recsys_setup`` builds (the model's
loss under ``train/optimizer.make_step_fn`` with adagrad), jitted with
the state donated as ``train/loop.fit`` does.  Set-up makes the
weights from the seed, a ring of host batches (truncated-Zipf ids,
labels from the planted teacher), and drives the same state through
its first three steps with the window's own upload and call; what the
comparison needs from them is read there.  The window then goes on
with the same state: each step uploads its batch and calls the step,
then waits for the previous step's loss.  The rate is the examples of
every step completed over the time from the window's start to the last
completion.  The window's stats count ``steps``; the device ran
``steps`` + 1, the first dispatched before the loop.  ``programs()``
gives a traced run the step's text, for the trace's named scopes.
"""
from __future__ import annotations

import time

import numpy as np

import gen
import harness as H

FIRST_STEPS = 3


class Session:
    def __init__(self, ctx: H.Ctx):
        import jax
        import jax.numpy as jnp
        import reference as R
        from repro.train import optimizer as O
        t, o = ctx.traffic, ctx.config["optimizer"]
        self.ctx = ctx
        self.batch = t["batch"]
        model = ctx.program_model()
        # the optimizer recsys_setup builds; the file states its numbers
        ocfg = O.OptimizerConfig(kind="adagrad", lr=1e-2)
        stated = (o["kind"], o["lr"], o["eps"], o["grad_clip"])
        if stated != (ocfg.kind, ocfg.lr, ocfg.eps, ocfg.grad_clip):
            raise ValueError(f"optimizer {ocfg} is not the stated {o}")
        vocabs = ctx.model["field_vocab_sizes"]
        ids = gen.request_ids(gen.rng(ctx.seed, 1), t["ring"], self.batch,
                              vocabs, t["zipf_s"])
        teacher = gen.Teacher(vocabs, gen.rng(ctx.seed, 3))
        lab_rng = gen.rng(ctx.seed, 4)
        self.ring = [{"sparse_ids": x, "label": teacher.labels(x, lab_rng)}
                     for x in ids]
        self.step_fn = jax.jit(O.make_step_fn(ocfg, model.loss),
                               donate_argnums=(0,))
        self.state = O.TrainState.create(ocfg, ctx.weights(model))
        self.k = 0
        t = time.perf_counter()
        losses = [self._step()]
        jax.block_until_ready(losses)
        H.log(f"first step (compile or cache) {time.perf_counter() - t:.1f}s")
        # adagrad's accumulator after one step is the clipped gradient
        # squared: the gradient as the optimizer got it
        self.grad_norms = np.asarray(jax.jit(lambda a: jnp.stack(
            [jnp.sqrt(jnp.sum(x)) for x in jax.tree.leaves(a)]))(
                self.state.opt_state["acc"]))
        losses += [self._step() for _ in range(FIRST_STEPS - 1)]
        self.losses = np.asarray([float(m["loss"]) for m in losses])
        key = R.key_of(ctx.seed)
        change = jax.jit(lambda p: R.leaf_norms(jax.tree.map(
            lambda a, b: a - b, p, ctx.ref.make_params(key, ctx.model))))
        self.change_norms = np.asarray(change(self.state.params))

    def _step(self):
        import jax
        with H.span("upload"):
            x = jax.device_put(self.ring[self.k % len(self.ring)])
        with H.span("call"):
            self.state, m = self.step_fn(self.state, x)
        self.k += 1
        return m

    def window(self):
        t0 = time.perf_counter()
        end = t0 + self.ctx.seconds
        prev, n, done = self._step(), 0, t0
        while done < end:
            m = self._step()
            with H.span("sync"):
                loss = float(prev["loss"])
            n += 1
            done = time.perf_counter()
            if not np.isfinite(loss):
                raise FloatingPointError(f"loss {loss} at step {self.k - 1}")
            prev = m
        float(prev["loss"])
        return {
            "attempted": n, "failed": 0,
            "metrics": {"train_examples_per_s":
                        n * self.batch / (done - t0)},
            "stats": {"steps": n, "window_s": done - t0},
        }

    def programs(self):
        """The optimized HLO text of the window's step, compiled again
        with the persistent cache off for this one compile: a step read
        back from that cache carries no ``op_name`` metadata on a v5e.
        JAX's in-memory caches would hand back the executable the window
        ran, so they are cleared first; nothing runs the step after."""
        import jax
        from jax.experimental.compilation_cache import compilation_cache
        x = jax.device_put(self.ring[0])
        was = jax.config.jax_enable_compilation_cache
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            return [self.step_fn.lower(self.state, x).compile().as_text()]
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()

    def free(self):
        H.delete(self.state)
        self.state = None

    def check(self):
        """Three reference steps from the same weights on the same
        batches: each step's loss, the first clipped gradient's norm and
        the change after three steps, leaf by leaf."""
        import reference as R
        t = time.perf_counter()
        ref = reference_steps(self.ctx, self.ring[:FIRST_STEPS], R.Prec())
        H.log(f"reference steps {time.perf_counter() - t:.1f}s")
        return compare(self, ref)


def reference_steps(ctx: H.Ctx, batches, prec):
    """Losses, first clipped-gradient leaf norms and leaf norms of the
    change after ``len(batches)`` adagrad steps of the reference."""
    import jax
    import jax.numpy as jnp
    import reference as R
    opt = ctx.config["optimizer"]
    params0 = ctx.weights()
    loss_grad = jax.jit(jax.value_and_grad(
        lambda p, b: ctx.ref.train_loss(p, b, ctx.model, prec)))
    update = jax.jit(lambda p, a, g: R.adagrad(p, a, g, opt))
    params = params0
    acc = jax.tree.map(jnp.zeros_like, params0)
    losses, grad_norms = [], None
    for b in batches:
        loss, g = loss_grad(params, jax.tree.map(jnp.asarray, b))
        losses.append(float(loss))
        params, acc, g = update(params, acc, g)
        if grad_norms is None:
            grad_norms = np.asarray(jax.jit(R.leaf_norms)(g))
        H.delete(g)
    change = np.asarray(jax.jit(lambda p, q: R.leaf_norms(
        jax.tree.map(lambda a, b: a - b, p, q)))(params, params0))
    H.delete((params0, params, acc))
    return {"losses": np.asarray(losses), "grad_norms": grad_norms,
            "change_norms": change}


def gaps(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """|got - want| per leaf over the larger of the leaf's reference
    norm and the median leaf's."""
    return np.abs(got - want) / np.maximum(want, np.median(want))


def compare(got, ref) -> dict:
    g_ref = ref["grad_norms"]
    moved = g_ref >= 1e-3 * np.median(g_ref)
    return {
        "loss_gap": float(np.max(np.abs(got.losses - ref["losses"])
                                 / np.abs(ref["losses"]))),
        "grad_gap": float(np.max(gaps(got.grad_norms, g_ref))),
        "update_gap": float(np.max(gaps(got.change_norms[moved],
                                        ref["change_norms"][moved]))),
    }


def setup(ctx: H.Ctx) -> Session:
    return Session(ctx)
