"""The train and serve steps name their layers: ``jax.named_scope`` puts
``fields``, ``first_order``, ``tower`` and ``optimizer`` into the HLO
``op_name`` of the ops they build (``transpose(jvp(<scope>))`` for the
gradient), which the profiler's device ops carry on the chip."""
import dataclasses
import math
import os
import re
import sys
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from repro.configs import deepfm as deepfm_cfg
from repro.configs.registry import get_arch
from repro.models.recsys.autoint import AutoInt
from repro.models.recsys.deepfm import DeepFM
from repro.roofline.hlo import (_CALLS, _METADATA_OPNAME,
                                _split_computations)
from repro.train import optimizer as O

NO_COMPUTE = {"parameter", "constant", "tuple", "get-tuple-element",
              "bitcast", "copy"}


def _chip_scopes():
    """The chip benchmark's op-path parser (``benchmarks/chip/scopes.py``),
    which the per-layer metrics read scopes with."""
    chip = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip")
    sys.path.insert(0, chip)        # for scopes.py's ``import harness``
    try:
        import harness
        return harness.load_module("scopes")
    finally:
        sys.path.remove(chip)


S = _chip_scopes()
scope = S.scope_of


def op_name(op) -> str:
    m = _METADATA_OPNAME.search(op.rest)
    return m.group(1) if m else ""


def op_scope(op) -> str:
    return scope(op_name(op))


def elements(op) -> int:
    """Output elements of an op (summed over a tuple's parts)."""
    return sum(math.prod(int(d) for d in m.group(1).split(",") if d)
               for m in re.finditer(r"[a-z]\w*\[([\d,]*)\]", op.shape))


def parse(hlo: str):
    """-> ({computation: [op]}, name of the entry computation)."""
    comps, entry = _split_computations(hlo)
    return {k: c.ops for k, c in comps.items()}, entry


def compiled_step(model, batch, n_fields):
    ocfg = O.OptimizerConfig(kind="adagrad", lr=1e-2)
    step = jax.jit(O.make_step_fn(ocfg, model.loss), donate_argnums=(0,))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda p: O.TrainState.create(ocfg, p), params)
    b = {"sparse_ids": jax.ShapeDtypeStruct((batch, n_fields), jnp.int32),
         "label": jax.ShapeDtypeStruct((batch,), jnp.float32)}
    return step.lower(state, b).compile().as_text()


def everywhere(comps, opcode):
    return [i for body in comps.values() for i in body if i.opcode == opcode]


@pytest.fixture(scope="module")
def deepfm_hlo():
    """The 39-field deepfm adagrad step, vocabularies cut to <= 200k:
    (config, its compiled text)."""
    cfg = deepfm_cfg.CONFIG
    cfg = dataclasses.replace(cfg, field_vocab_sizes=tuple(
        min(v, 200_000) for v in cfg.field_vocab_sizes))
    return cfg, compiled_step(DeepFM(cfg), 256, cfg.n_sparse)


@pytest.fixture(scope="module")
def deepfm_step(deepfm_hlo):
    cfg, hlo = deepfm_hlo
    return cfg, parse(hlo)


def test_deepfm_gathers_and_scatters_sit_in_their_layer(deepfm_step):
    cfg, (comps, _) = deepfm_step
    gathers = Counter(op_scope(i) for i in everywhere(comps, "gather"))
    scatters = Counter(op_scope(i) for i in everywhere(comps, "scatter"))
    assert set(gathers) == {"fields", "first_order"}, gathers
    assert set(scatters) == {"fields.bwd", "first_order.bwd"}, scatters
    # one first-order table per field, each gathered and scattered into
    assert gathers["first_order"] == scatters["first_order.bwd"] \
        == cfg.n_sparse
    # one table per field, its rows gathered and its gradient scattered;
    # the codebooks are decoded by products, so no gather or scatter
    # touches a codebook (the other gathers under ``fields`` read the
    # MGQE tier's centroid budget, one int per id)
    n_mgqe = sum(v >= cfg.mgqe_min_vocab for v in cfg.field_vocab_sizes)
    rows = [i for i in everywhere(comps, "gather")
            if op_scope(i) == "fields" and i.shape.startswith("f32")]
    assert len(rows) == cfg.n_sparse
    assert {re.sub(r"\{.*", "", i.shape) for i in rows} == {
        f"f32[256,1,{cfg.embed_dim}]"}
    assert gathers["fields"] - len(rows) == n_mgqe
    assert scatters["fields.bwd"] == cfg.n_sparse
    assert all(re.match(rf"f32\[\d+,{cfg.embed_dim}\]", i.shape)
               for i in everywhere(comps, "scatter")
               if op_scope(i) == "fields.bwd")
    with_scatter = {c for c, body in comps.items()
                    if any(i.opcode == "scatter" for i in body)}
    fusions = [i for body in comps.values() for i in body
               if i.opcode == "fusion"
               and _CALLS.search(i.rest).group(1) in with_scatter]
    assert fusions and {op_scope(i) for i in fusions} <= {"fields.bwd",
                                                      "first_order.bwd"}


def test_deepfm_dots_are_the_tower_and_the_assign(deepfm_step):
    """Every dot is the tower's (forward and gradient) except, per
    quantized field, the MGQE assign's distance product and the
    codebook decode's one-hot product under ``fields``, and the
    decode's gradient product under ``fields.bwd`` (the
    straight-through gradient has none)."""
    cfg, (comps, _) = deepfm_step
    dots = Counter(op_scope(i) for i in everywhere(comps, "dot"))
    n_mgqe = sum(v >= cfg.mgqe_min_vocab for v in cfg.field_vocab_sizes)
    assert set(dots) == {"tower", "tower.bwd", "fields", "fields.bwd"}, dots
    assert dots["fields"] == 2 * n_mgqe
    assert dots["fields.bwd"] == n_mgqe
    assert dots["tower"] >= len(cfg.mlp_dims) + 1
    nested = Counter(scope(op_name(i), depth=2)
                     for i in everywhere(comps, "dot")
                     if op_scope(i).startswith("fields"))
    assert nested["fields/codebook"] == nested["fields/codebook.bwd"] \
        == n_mgqe, nested


def test_deepfm_adagrad_update_sits_under_optimizer(deepfm_step):
    _, (comps, _) = deepfm_step
    paths = Counter(op_name(i).split("/optimizer/")[-1]
                    for i in everywhere(comps, "sqrt"))
    # the global norm of the clip, then adagrad's sqrt(acc) per leaf
    assert set(paths) == {"clip/sqrt", "adagrad/sqrt"}, paths
    assert paths["clip/sqrt"] == 1
    assert all(op_scope(i) == "optimizer"
               for i in everywhere(comps, "divide")
               if "adagrad" in op_name(i))


def test_deepfm_entry_outputs_mostly_scoped(deepfm_step):
    """At least 85% of the entry computation's computed output elements
    (parameters, tuples, bitcasts and XLA's layout copies left out)
    carry a scope."""
    _, (comps, entry) = deepfm_step
    by = Counter()
    for i in comps[entry]:
        if i.opcode not in NO_COMPUTE:
            by[op_scope(i)] += elements(i)
    total = sum(by.values())
    assert total > 0
    assert 1 - by[""] / total >= 0.85, by
    assert {"fields", "fields.bwd", "first_order", "first_order.bwd",
            "tower", "tower.bwd", "optimizer"} <= set(by)


def test_deepfm_entry_ops_scoped_through_fusion_bodies(deepfm_hlo):
    """Read as the chip benchmark reads it (an op without a scope takes
    the first one in the computations it calls), every computed op of
    the entry computation names its layer."""
    _, hlo = deepfm_hlo
    comps, entry = parse(hlo)
    names = S.hlo_op_names(hlo)
    by = Counter(scope(names[i.name]) for i in comps[entry]
                 if i.opcode not in NO_COMPUTE)
    assert "" not in by, by
    assert set(by) == {"fields", "fields.bwd", "first_order",
                       "first_order.bwd", "tower", "tower.bwd",
                       "optimizer"}, by


def test_autoint_tower_scope():
    _, cfg = get_arch("autoint", smoke=True)
    comps, _ = parse(compiled_step(AutoInt(cfg), 16, cfg.n_sparse))
    dots = Counter(op_scope(i) for i in everywhere(comps, "dot"))
    assert {"tower", "tower.bwd"} <= set(dots) <= {"tower", "tower.bwd",
                                                  "fields", "fields.bwd"}, dots
    # per quantized field: assign and decode, and the decode's gradient
    assert dots["fields"] == 2 * dots["fields.bwd"], dots


def test_deepfm_serve_scopes():
    """The served step: the decode of every field under ``fields``, the
    first-order tables under ``first_order``, the tower's dots under
    ``tower``."""
    _, cfg = get_arch("deepfm", smoke=True)
    m = DeepFM(cfg)
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    arts = m.fields.artifact_struct()
    ids = {"sparse_ids": jax.ShapeDtypeStruct((32, cfg.n_sparse), jnp.int32)}
    hlo = jax.jit(m.serve).lower(params, arts, ids).compile().as_text()
    comps, _ = parse(hlo)
    gathers = Counter(op_scope(i) for i in everywhere(comps, "gather"))
    dots = Counter(op_scope(i) for i in everywhere(comps, "dot"))
    assert set(gathers) == {"fields", "first_order"}, gathers
    assert "tower" in dots and set(dots) <= {"tower", "fields"}, dots


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jvp(fields)/jit(_take)/gather", "fields"),
    ("jit(step)/transpose(jvp(fields))/jit(_take)/scatter-add", "fields.bwd"),
    ("jit(step)/jit(main)/optimizer/adagrad/sqrt", "optimizer"),
    ("jit(step)/transpose(jvp(fields))/codebook/dot_general", "fields.bwd"),
    ("jit(step)/jvp()/mul", ""),
    ("reduce_sum", ""),
    ("", ""),
])
def test_scope_of_op_name(op_name, want):
    assert scope(op_name) == want
