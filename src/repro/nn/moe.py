"""Top-k routed mixture-of-experts FFN (GShard-style capacity dispatch).

Two implementations:

* ``moe_ffn`` — mesh-agnostic single-program formulation: static-shape
  scatter into a GLOBAL (E, C, d) dispatch buffer, batched expert
  GEMMs, gather-combine.  Correct everywhere, but under pjit the global
  buffer forces XLA to all-reduce (E, C, d)-sized partial sums every
  layer — the §Perf baseline shows ~10 TB/device/step of collectives
  for mixtral train_4k.

* ``moe_ffn_sharded`` — shard_map grouped dispatch (the real GShard
  scheme): every data shard dispatches its OWN tokens into a local
  (E, C_local, d) buffer (group-wise capacity), then
    - "expert" strategy (E % model_n == 0): all_to_all over the model
      axis routes expert rows to their owning shard; expert GEMMs are
      fully local; reverse all_to_all returns outputs.  Wire cost per
      layer = 2 x local dispatch buffer.
    - "ffn" strategy (E < model_n, e.g. mixtral's 8 experts on a
      16-way axis): experts replicated, d_ff sharded; the only
      collective is one psum of the (E, C_local, d) output buffer.

Router aux loss: load-balancing loss from Switch Transformer
(mean(fraction_tokens_e * mean_router_prob_e) * E).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from repro.nn import initializers as init


def ambient_mesh():
    """The physical mesh installed by ``with mesh:`` (trace-time)."""
    from repro.sharding.gather import ambient_mesh as _ambient
    mesh = _ambient()
    if mesh is None:
        raise RuntimeError("moe_ffn_sharded needs an ambient mesh "
                           "(wrap the jit call in `with mesh:`)")
    return mesh


def moe_init(key, d_model: int, d_ff: int, num_experts: int,
             dtype=jnp.float32) -> dict:
    k_r, k1, k2, k3 = jax.random.split(key, 4)
    s_in = d_model ** -0.5
    s_ff = d_ff ** -0.5
    return {
        "router": init.normal(k_r, (d_model, num_experts), s_in, dtype),
        "w_gate": init.normal(k1, (num_experts, d_model, d_ff), s_in, dtype),
        "w_up": init.normal(k2, (num_experts, d_model, d_ff), s_in, dtype),
        "w_down": init.normal(k3, (num_experts, d_ff, d_model), s_ff, dtype),
    }


def capacity(num_tokens: int, num_experts: int, top_k: int,
             factor: float) -> int:
    c = int(math.ceil(num_tokens * top_k * factor / num_experts))
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def _dispatch_combine(xt: jax.Array, router: jax.Array, top_k: int,
                      cap: int, expert_fn):
    """Shared routing math: route xt (T, d), scatter into (E, cap, d),
    run ``expert_fn(buf) -> (E, cap, d)``, gather-combine.

    Returns (out (T, d), aux_loss)."""
    t, d = xt.shape
    num_experts = router.shape[-1]

    logits = (xt.astype(jnp.float32) @ router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    gate_w, gate_i = jax.lax.top_k(probs, top_k)                  # (T, k)
    gate_w = gate_w / jnp.maximum(
        jnp.sum(gate_w, axis=-1, keepdims=True), 1e-9)

    # Switch load-balance loss.
    me = jnp.mean(probs, axis=0)                                  # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(gate_i, num_experts, dtype=jnp.float32),
                axis=1), axis=0)                                  # (E,)
    aux = jnp.sum(me * ce) * num_experts

    # Position of each (token, choice) within its expert's capacity.
    flat_e = gate_i.reshape(-1)                                   # (T*k,)
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)  # (T*k, E)
    pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot)              # exclusive
    pos = jnp.sum(pos_in_e * onehot, axis=-1)                     # (T*k,)
    keep = pos < cap                                              # drop overflow
    slot = jnp.where(keep, pos, cap - 1)

    # Dispatch: (E, C, d) buffer.  Dropped tokens scatter with weight 0.
    xt_rep = jnp.repeat(xt, top_k, axis=0)                        # (T*k, d)
    w_scatter = keep.astype(xt.dtype)[:, None]
    buf = jnp.zeros((num_experts, cap, d), xt.dtype)
    buf = buf.at[flat_e, slot].add(xt_rep * w_scatter)

    out_buf = expert_fn(buf)                                      # (E, C, d)

    # Combine: gather each (token, choice)'s output, weight, sum over k.
    gathered = out_buf[flat_e, slot]                              # (T*k, d)
    gathered = gathered * (gate_w.reshape(-1)[:, None].astype(gathered.dtype)
                           * w_scatter)
    out = jnp.sum(gathered.reshape(t, top_k, d), axis=1)
    return out, aux


def _expert_swiglu(buf, w_gate, w_up, w_down):
    gate = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(buf.dtype))
    up = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(buf.dtype))
    hidden = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", hidden, w_down.astype(buf.dtype))


def moe_ffn(params: dict, x: jax.Array, *, top_k: int,
            capacity_factor: float = 1.25) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    num_experts = params["router"].shape[-1]
    cap = capacity(t, num_experts, top_k, capacity_factor)
    out, aux = _dispatch_combine(
        xt, params["router"], top_k, cap,
        lambda buf: _expert_swiglu(buf, params["w_gate"], params["w_up"],
                                   params["w_down"]))
    return out.reshape(b, s, d), aux


def moe_ffn_sharded(params: dict, x: jax.Array, *, top_k: int,
                    capacity_factor: float = 1.25,
                    model_axis: str = "model"
                    ) -> Tuple[jax.Array, jax.Array]:
    """shard_map grouped dispatch (docstring at module top).

    Requires an ambient mesh (``with mesh:``) whose axis names include
    ``model_axis``; tokens are sharded over every other axis.  Inside
    jit, operands are resharded to the declared in_specs as needed.
    """
    mesh = ambient_mesh()
    axis_names = tuple(mesh.axis_names)
    data_axes = tuple(a for a in axis_names if a != model_axis)
    model_n = mesh.shape[model_axis]
    num_experts = params["router"].shape[-1]
    expert_par = num_experts % model_n == 0 and num_experts >= model_n

    b, s, d = x.shape

    if expert_par:
        w_spec = P(model_axis, None, None)          # E over model
    else:
        if params["w_gate"].shape[-1] % model_n:
            raise ValueError(
                f"ffn strategy needs d_ff divisible by the model axis: "
                f"w_gate {params['w_gate'].shape} over {model_n}")
        w_spec = P(None, None, model_axis)          # d_ff over model
    wd_spec = (P(model_axis, None, None) if expert_par
               else P(None, model_axis, None))

    def body(router, wg, wu, wd, x_loc):
        bl, sl, _ = x_loc.shape
        t_loc = bl * sl
        xt = x_loc.reshape(t_loc, d)
        cap = capacity(t_loc, num_experts, top_k, capacity_factor)

        if expert_par:
            def expert_fn(buf):                      # (E, cap, d) local grp
                # route expert rows to their owning model shard
                buf = jax.lax.all_to_all(buf, model_axis, split_axis=0,
                                         concat_axis=1, tiled=True)
                # -> (E/model_n, cap*model_n, d); wg is the local slice
                out = _expert_swiglu(buf, wg, wu, wd)
                return jax.lax.all_to_all(out, model_axis, split_axis=1,
                                          concat_axis=0, tiled=True)
        else:
            def expert_fn(buf):                      # experts replicated
                out = _expert_swiglu(buf, wg, wu, wd)   # partial over f
                return jax.lax.psum(out, model_axis)

        out, aux = _dispatch_combine(xt, router, top_k, cap, expert_fn)
        aux = jax.lax.pmean(aux, data_axes + (model_axis,))
        return out.reshape(bl, sl, d), aux

    if expert_par:
        # tokens split over data axes (batch) AND the model axis
        # (sequence): every (data, model) shard group-dispatches its own
        # token slice; all_to_all routes expert rows
        x_spec = P(data_axes, model_axis, None)
    else:
        # ffn strategy: every model shard must see the SAME tokens (the
        # psum sums f-slice partials of one token set), so tokens are
        # replicated over model; routing work duplicates (cheap), the
        # expert GEMMs split over d_ff
        x_spec = P(data_axes, None, None)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), w_spec, w_spec, wd_spec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False)
    return fn(params["router"], params["w_gate"], params["w_up"],
              params["w_down"], x)
