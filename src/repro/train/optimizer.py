"""Optimizers as pure functions over parameter pytrees.

Supported: adam, adamw, adagrad (the classic for sparse recsys
embeddings), sgd (momentum).  All state lives in a pytree mirroring the
params, so it shards/checkpoints exactly like the params do.

Each optimizer is one :class:`OptimizerRule` registered under its kind
string (same pattern as the embedding-scheme registry, DESIGN.md §7):
``init``/``apply_updates`` resolve the rule from the registry instead
of branching per kind, so adding an optimizer is one class + one
decorator.  Moment-buffer keys (``m``/``v``/``acc``/``mom``) are part
of the rule, so checkpoints and the ZeRO-1 sharding rules
(sharding/rules.py) see the same state layout as before.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    kind: str = "adam"          # adam | adamw | adagrad | sgd
    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0   # adamw
    momentum: float = 0.9       # sgd
    grad_clip: Optional[float] = 1.0   # global-norm clip; None = off
    # schedule: constant | cosine | linear_warmup_cosine
    schedule: str = "constant"
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1


def schedule_lr(cfg: OptimizerConfig, step: jax.Array) -> jax.Array:
    base = jnp.asarray(cfg.lr, jnp.float32)
    if cfg.schedule == "constant":
        return base
    warm = jnp.minimum(1.0, (step + 1) / max(cfg.warmup_steps, 1))
    if cfg.schedule == "linear_warmup_cosine" or cfg.schedule == "cosine":
        t = jnp.clip((step - cfg.warmup_steps)
                     / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
        cos = 0.5 * (1 + jnp.cos(jnp.pi * t))
        frac = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos
        return base * warm * frac
    raise ValueError(cfg.schedule)


# ----------------------------------------------------------------------
# optimizer-rule registry
# ----------------------------------------------------------------------

class OptimizerRule:
    """One optimizer: moment-buffer layout + the update math."""

    state_keys: Tuple[str, ...] = ()

    @classmethod
    def update(cls, cfg: OptimizerConfig, lr, step, params, grads,
               moments: Dict) -> Tuple[Any, Dict]:
        """-> (new_params, new_moments) with the same ``state_keys``."""
        raise NotImplementedError


_OPTIMIZERS: Dict[str, Type[OptimizerRule]] = {}


def register_optimizer(kind: str):
    def deco(cls: Type[OptimizerRule]) -> Type[OptimizerRule]:
        prev = _OPTIMIZERS.get(kind)
        if prev is not None and prev is not cls:
            raise ValueError(
                f"optimizer kind {kind!r} already registered to {prev}")
        _OPTIMIZERS[kind] = cls
        return cls
    return deco


def _rule(kind: str) -> Type[OptimizerRule]:
    try:
        return _OPTIMIZERS[kind]
    except KeyError:
        raise ValueError(
            f"unknown optimizer kind {kind!r}; registered: "
            f"{', '.join(sorted(_OPTIMIZERS))}") from None


@register_optimizer("adam")
class _Adam(OptimizerRule):
    state_keys = ("m", "v")
    decoupled_weight_decay = False

    @classmethod
    def update(cls, cfg, lr, step, params, grads, moments):
        t = (step + 1).astype(jnp.float32)
        bc1 = 1 - cfg.b1 ** t
        bc2 = 1 - cfg.b2 ** t
        # all moment math in fp32 (grads may be bf16)
        m = jax.tree.map(
            lambda mm, g: cfg.b1 * mm + (1 - cfg.b1) * g.astype(jnp.float32),
            moments["m"], grads)
        v = jax.tree.map(
            lambda vv, g: cfg.b2 * vv
            + (1 - cfg.b2) * jnp.square(g.astype(jnp.float32)),
            moments["v"], grads)

        def upd(p, mm, vv):
            u = (mm / bc1) / (jnp.sqrt(vv / bc2) + cfg.eps)
            if cls.decoupled_weight_decay and cfg.weight_decay:
                u = u + cfg.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32)
                    - lr * u).astype(p.dtype)

        return jax.tree.map(upd, params, m, v), {"m": m, "v": v}


@register_optimizer("adamw")
class _AdamW(_Adam):
    decoupled_weight_decay = True


@register_optimizer("adagrad")
class _Adagrad(OptimizerRule):
    state_keys = ("acc",)

    @classmethod
    def update(cls, cfg, lr, step, params, grads, moments):
        acc = jax.tree.map(
            lambda a, g: a + jnp.square(g.astype(jnp.float32)),
            moments["acc"], grads)
        new_params = jax.tree.map(
            lambda p, a, g: (p.astype(jnp.float32) - lr
                             * g.astype(jnp.float32)
                             / (jnp.sqrt(a) + cfg.eps)).astype(p.dtype),
            params, acc, grads)
        return new_params, {"acc": acc}


@register_optimizer("sgd")
class _SGD(OptimizerRule):
    state_keys = ("mom",)

    @classmethod
    def update(cls, cfg, lr, step, params, grads, moments):
        mom = jax.tree.map(lambda mm, g: cfg.momentum * mm + g,
                           moments["mom"], grads)
        new_params = jax.tree.map(
            lambda p, mm: p - lr.astype(p.dtype) * mm.astype(p.dtype),
            params, mom)
        return new_params, {"mom": mom}


def init(cfg: OptimizerConfig, params: Any) -> Dict:
    # Moment buffers are always fp32, independent of param dtype (bf16
    # params + fp32 moments is the standard mixed-precision recipe).
    rule = _rule(cfg.kind)
    zeros = lambda: jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    state: Dict[str, Any] = {"step": jnp.zeros((), jnp.int32)}
    for k in rule.state_keys:
        state[k] = zeros()
    return state


def _global_norm(tree) -> jax.Array:
    sq = jax.tree.map(lambda g: jnp.sum(jnp.square(g.astype(jnp.float32))),
                      tree)
    return jnp.sqrt(jax.tree.reduce(jnp.add, sq, jnp.float32(0.0)))


def clip_by_global_norm(grads, max_norm: float):
    norm = _global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), norm


@jax.named_scope("optimizer")
def apply_updates(cfg: OptimizerConfig, params, grads,
                  state: Dict) -> Tuple[Any, Dict]:
    """One update, under the named scope ``optimizer`` (nested: ``clip``
    and the rule's kind), which its device ops carry in their HLO
    ``op_name``."""
    rule = _rule(cfg.kind)
    step = state["step"]
    lr = schedule_lr(cfg, step)
    if cfg.grad_clip is not None:
        with jax.named_scope("clip"):
            grads, _ = clip_by_global_norm(grads, cfg.grad_clip)
    moments = {k: state[k] for k in rule.state_keys}
    with jax.named_scope(cfg.kind):
        new_params, new_moments = rule.update(cfg, lr, step, params, grads,
                                              moments)
    return new_params, {"step": step + 1, **new_moments}


# convenience container ------------------------------------------------

@jax.tree_util.register_pytree_node_class
class TrainState:
    """(params, opt_state, step) bundle that jits/shards as one pytree."""

    def __init__(self, params, opt_state):
        self.params = params
        self.opt_state = opt_state

    @property
    def step(self):
        return self.opt_state["step"]

    def tree_flatten(self):
        return (self.params, self.opt_state), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @staticmethod
    def create(cfg: OptimizerConfig, params) -> "TrainState":
        return TrainState(params, init(cfg, params))


def make_step_fn(cfg: OptimizerConfig,
                 loss_fn: Callable) -> Callable:
    """Standard step: state, batch -> (state, metrics).  loss_fn must
    return (loss, metrics_dict)."""

    def step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, batch)
        new_params, new_opt = apply_updates(cfg, state.params, grads,
                                            state.opt_state)
        return TrainState(new_params, new_opt), metrics

    return step
