"""Compile each cell's timed step, and the reference's, for a described
TPU v5e chip, without the chip; print ``memory_analysis()`` of each.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [workload ...]

The kernel dispatch is steered to its Pallas kernels here (off the chip
it would pick the XLA reference).  A compile that passes is not a chip
run: it says the step fits and lowers, nothing about time.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import harness as H
    import reference as R
    from repro.kernels import dispatch
    from repro.train import optimizer as O
    jax.config.update("jax_enable_compilation_cache", False)
    dispatch._on_tpu = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    names = (argv if argv is not None else sys.argv[1:]) or [
        w["name"] for w in spec["workloads"]]

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    def report(label, fn, *args, donate=()):
        t = time.perf_counter()
        c = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        m = c.memory_analysis()
        n = c.as_text().count("tpu_custom_call")
        print(json.dumps({
            "step": label, "compile_s": round(time.perf_counter() - t, 1),
            "tpu_custom_calls": n,
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes}), flush=True)

    for name in names:
        cell = {w["name"]: w for w in spec["workloads"]}[name]
        config = H.load_json("configs", cell["config"] + ".json")
        traffic = H.load_json("traffic", cell["traffic"] + ".json")
        ctx = H.Ctx(name, 0, 1.0, False, config, traffic,
                    H.load_module("configs", cell["config"]))
        model = ctx.program_model()
        b, nf = traffic["batch"], ctx.model["n_sparse"]
        params = jax.eval_shape(lambda k: ctx.ref.make_params(k, ctx.model),
                                jax.random.PRNGKey(0))
        ids = jax.ShapeDtypeStruct((b, nf), jnp.int32, sharding=one)
        if traffic["driver"] == "train_loop":
            ocfg = O.OptimizerConfig(kind="adagrad", lr=1e-2)
            state = jax.eval_shape(lambda p: O.TrainState.create(ocfg, p),
                                   params)
            batch = {"sparse_ids": ids, "label": jax.ShapeDtypeStruct(
                (b,), jnp.float32, sharding=one)}
            report(f"{name} program step", O.make_step_fn(ocfg, model.loss),
                   shaped(state), batch, donate=(0,))
            report(f"{name} reference loss+grad",
                   jax.value_and_grad(lambda p, x: ctx.ref.train_loss(
                       p, x, ctx.model, R.Prec())), shaped(params), batch)
            continue
        serve_params = shaped({k: v for k, v in params.items()
                               if k != "fields"})
        art = shaped(model.fields.artifact_struct())
        report(f"{name} program serve B={b}",
               lambda p, a, x: model.serve(p, a, {"sparse_ids": x}),
               serve_params, art, ids)
        blk = jax.ShapeDtypeStruct((8192, nf), jnp.int32, sharding=one)
        report(f"{name} reference serve B=8192",
               lambda p, x: ctx.ref.serve_logits(p, x, ctx.model, R.Prec()),
               shaped(params), blk)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
