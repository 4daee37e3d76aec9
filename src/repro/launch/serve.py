"""Serving driver: export quantized artifacts, then serve batched
requests on the paper's Figure-1 path (codes + centroids, full table
discarded).

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --smoke \
        --prompt-len 32 --decode-steps 16 --batch 4
    PYTHONPATH=src python -m repro.launch.serve --arch two-tower-retrieval \
        --smoke --candidates 10000 --retrieval ivf_pq --nprobe 8 --topk 100

``--engine`` drives a request stream through the micro-batching
:class:`repro.launch.engine.ServingEngine` instead (device-resident
artifact, queued lookups padded to the decode kernel's block_b) and
reports lookups/second:

    PYTHONPATH=src python -m repro.launch.serve --arch deepfm --smoke \
        --engine --requests 200 --req-batch 64

``--async`` wraps the engine in the latency-SLO front-end
(:class:`repro.launch.async_engine.AsyncServingEngine`): an open-loop
Zipf stream offered at ``--arrival-rate`` req/s, deadline-batched
flushes (``--max-wait-us``), and a p50/p99/p999 latency report judged
against ``--slo-ms``:

    PYTHONPATH=src python -m repro.launch.serve --arch deepfm --smoke \
        --engine --async --arrival-rate 500 --max-wait-us 1000 --slo-ms 5

``--mesh data=2,model=2`` serves the engine's artifact *sharded*
(DESIGN.md §6): codes row-sharded over the ``model`` axis, codebooks
replicated, one shard_map decode fanned across the mesh per flush.
Off-TPU the requested device count is forced via
``--xla_force_host_platform_device_count`` (set before jax
initializes), so the same command works on a CPU dev box:

    PYTHONPATH=src python -m repro.launch.serve --arch deepfm --smoke \
        --engine --mesh data=2,model=2
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_arch
from repro.core.types import KERNEL_BACKENDS
from repro.launch.compile_cache import setup_compile_cache


def parse_mesh(spec: str):
    """'data=2,model=2' -> (("data", "model"), (2, 2))."""
    axes, shape = [], []
    for part in spec.split(","):
        name, _, n = part.partition("=")
        if not n:
            raise ValueError(f"bad mesh axis {part!r}; want name=N")
        axes.append(name.strip())
        shape.append(int(n))
    return tuple(axes), tuple(shape)


def serve_lm(cfg, batch: int, prompt_len: int, decode_steps: int):
    from repro.core import Embedding
    from repro.models import lm
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    emb = Embedding(cfg.embedding)
    artifact = emb.export(params["embed"])
    full_bits = cfg.embedding.vocab_size * cfg.embedding.dim * 32
    print(f"embedding artifact: {emb.serving_size_bits()/8/1e6:.2f} MB "
          f"({100*emb.serving_size_bits()/full_bits:.1f}% of full)")

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)
    max_seq = prompt_len + decode_steps

    t0 = time.time()
    cache, logits = jax.jit(
        lambda p, t: lm.prefill(p, t, cfg, max_seq=max_seq,
                                embed_artifact=artifact)
    )(params, prompts)
    print(f"prefill: {time.time()-t0:.2f}s; logits {logits.shape}")

    decode = jax.jit(
        lambda p, c, t: lm.decode_step(p, c, t, cfg,
                                       embed_artifact=artifact))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for _ in range(decode_steps):
        cache, logits = decode(params, cache, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    print(f"decoded {decode_steps} steps x B={batch} in {dt:.2f}s "
          f"({batch*decode_steps/dt:.1f} tok/s); sample: "
          f"{np.asarray(jnp.stack(out, 1))[0][:8]}")


def serve_retrieval(cfg, n_candidates: int, index_kind: str = "flat_pq",
                    nprobe: int = 8, topk: int = 100,
                    n_requests: int = 50, req_batch: int = 16,
                    backend=None, host_staged: bool = False):
    """Top-k candidate retrieval through the index registry + the
    micro-batching RetrievalEngine (DESIGN.md §8).

    ``host_staged`` keeps the O(corpus) list tables in host memory and
    stages only probed lists per flush (DESIGN.md §12)."""
    from repro.launch.engine import RetrievalEngine
    from repro.models.recsys.two_tower import TwoTower
    from repro.retrieval import IndexConfig, suggest_nlist
    model = TwoTower(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n = min(n_candidates, cfg.n_items)
    item_ids = jnp.arange(n, dtype=jnp.int32)
    # nlist ≈ √N balances probed work against list length; the old
    # min(64, n // 64) cap left a 10M corpus probing 156k-row lists
    nlist = suggest_nlist(n, nprobe)
    icfg = IndexConfig(kind=index_kind, num_subspaces=8, num_centroids=64,
                       nlist=nlist, nprobe=min(nprobe, nlist),
                       kernel_backend=backend)

    # offline: build the index over the PQ-coded candidate tower outputs
    t0 = time.time()
    index, artifact = model.build_index(jax.random.PRNGKey(1), params,
                                        item_ids, icfg)
    code_mb = sum(np.asarray(artifact[name]).nbytes
                  for name in index.rows_leaves) / 1e6
    print(f"{index_kind} index built in {time.time()-t0:.1f}s: "
          f"{code_mb:.1f} MB corpus rows vs "
          f"{n*cfg.tower_mlp[-1]*4/1e6:.1f} MB dense"
          + (f" (nlist={icfg.nlist}, nprobe={icfg.nprobe})"
             if index_kind == "ivf_pq" else ""))

    # online: stream user batches through the engine; top-k ids + scores
    engine = RetrievalEngine(index, artifact, k=topk, block_q=16,
                             host_staged=host_staged)
    rng = np.random.default_rng(0)
    users = [rng.integers(0, cfg.n_users,
                          int(rng.integers(1, req_batch + 1)))
             for _ in range(n_requests)]
    user_vec = jax.jit(lambda p, u: model.user_vec(p, u)[0])
    reqs = [np.asarray(user_vec(params, jnp.asarray(u, jnp.int32)))
            for u in users]
    engine.serve_stream(reqs)                  # warm pass: jit traces
    engine.stats_ = type(engine.stats_)()
    st = engine.serve_stream(reqs)
    print(f"engine: {st.requests} requests / {st.lookups} queries in "
          f"{st.flushes} flushes, {st.seconds:.3f}s -> "
          f"{st.lookups_per_s:,.0f} queries/s x top-{topk}")
    if host_staged:
        print(f"host-staged: {engine.staged_mbytes:.2f} MB staged over "
              f"{st.flushes * 2} flushes (warm+measured) vs "
              f"{code_mb:.1f} MB device-resident")

    # recall vs the exact dense scan, one probe batch
    scores, ids = model.retrieval_topk(params, index, artifact,
                                       jnp.arange(8, dtype=jnp.int32),
                                       topk)
    cand_vecs = model.encode_items(params, item_ids)
    u8, _ = model.user_vec(params, jnp.arange(8, dtype=jnp.int32))
    ex = np.argsort(-np.asarray(u8 @ cand_vecs.T), axis=1)[:, :topk]
    rec = np.mean([len(set(np.asarray(ids)[b].tolist())
                       & set(ex[b].tolist())) / topk for b in range(8)])
    print(f"recall@{topk} vs exact dense scan: {rec:.3f}")


class CtrServed(NamedTuple):
    """What :func:`serve_ctr` built and served, for callers that check
    it (``chip_smoke.py``): ``step`` is the jitted serve function,
    called as ``step(params, artifacts, request)``."""
    model: Any
    params: Dict
    artifacts: Dict
    request: Dict
    step: Callable
    scores: jax.Array


def serve_ctr(cfg, batch: int, params=None, artifacts=None) -> CtrServed:
    """Serve one ``batch``-example CTR request through the jitted
    ``model.serve``.  ``params`` defaults to a fresh init and
    ``artifacts`` to its export (codes through the dispatched
    ``dpq_assign``); passing both serves an existing artifact, e.g.
    under another kernel backend."""
    from repro.launch.cells import _recsys_model
    model = _recsys_model(cfg)
    if params is None:
        params = model.init(jax.random.PRNGKey(0))
    bst = cfg.model == "bst"
    if artifacts is None:
        t0 = time.time()
        artifacts = (model.item_emb.export(params["item_emb"]) if bst
                     else model.fields.export(params["fields"]))
        jax.block_until_ready(artifacts)
        print(f"exported serving artifacts in {time.time()-t0:.2f}s")
    rng = np.random.default_rng(0)
    if bst:
        request = {"hist_ids": jnp.asarray(
                       rng.integers(0, cfg.n_items, (batch, cfg.seq_len)),
                       jnp.int32),
                   "target_id": jnp.asarray(
                       rng.integers(0, cfg.n_items, batch), jnp.int32)}
    else:
        ids = np.stack([rng.integers(0, v, batch)
                        for v in cfg.field_vocab_sizes], 1)
        request = {"sparse_ids": jnp.asarray(ids, jnp.int32)}
    step = jax.jit(lambda p, a, bb: model.serve(p, a, bb))
    t0 = time.time()
    scores = step(params, artifacts, request)
    jax.block_until_ready(scores)
    print(f"served B={batch} in {time.time()-t0:.2f}s (compile "
          f"included); scores mean {float(jnp.mean(scores)):.4f}")
    return CtrServed(model, params, artifacts, request, step, scores)


def serve_async_engine(engine, vocab_size: int, req_batch: int,
                       max_wait_us: float, arrival_rate: float,
                       slo_ms: float, duration_s: float,
                       zipf_a: float, hot_refresh: int = 0):
    """Open-loop latency demo of the async front-end (DESIGN.md §10):
    wrap the engine, replay a Zipf arrival schedule at ``arrival_rate``
    requests/s, report the latency histogram and the SLO verdict."""
    from repro.data.synthetic import zipf_open_loop_stream
    from repro.launch.async_engine import (AsyncServingEngine,
                                           drive_open_loop)
    arrivals, reqs = zipf_open_loop_stream(
        vocab_size, rate_rps=arrival_rate, duration_s=duration_s,
        req_batch=req_batch, zipf_a=zipf_a)
    with AsyncServingEngine(engine, max_wait_us=max_wait_us,
                            refresh_every=hot_refresh) as aeng:
        # warm pass: pay every padded-shape jit trace before measuring
        # (an open-loop p99 with a compile in it measures the compiler)
        drive_open_loop(aeng, reqs, arrivals)
        aeng.drain()
        aeng.reset_stats()
        st = drive_open_loop(aeng, reqs, arrivals)
    offered = len(reqs) / arrivals[-1]
    print(f"async engine: {st.requests} requests ({st.lookups} lookups) "
          f"open-loop at {offered:,.0f} req/s over "
          f"{st.wall_seconds:.2f}s wall -> "
          f"{st.sustained_lookups_per_s:,.0f} lookups/s sustained")
    print(f"  flush triggers: {st.flushes_full} block-full / "
          f"{st.flushes_deadline} deadline({max_wait_us:.0f}us) / "
          f"{st.flushes_drain} drain; device time "
          f"{st.seconds:.3f}s of {st.wall_seconds:.2f}s wall")
    print(f"  latency p50 {st.p50_ms:.2f} ms | p99 {st.p99_ms:.2f} ms | "
          f"p999 {st.p999_ms:.2f} ms")
    ok = st.p99_ms <= slo_ms
    print(f"  SLO p99 <= {slo_ms:.1f} ms: {'MET' if ok else 'MISSED'}")
    return st


def serve_engine(family, cfg, n_requests: int, req_batch: int,
                 backend=None, max_queue: int = 4096, mesh_spec=None,
                 hot_rows: int = 0, hot_refresh: int = 0,
                 zipf_a: float = 0.0, use_async: bool = False,
                 max_wait_us: float = 1000.0, arrival_rate: float = 500.0,
                 slo_ms: float = 5.0, duration_s: float = 2.0):
    """Request-stream demo of the micro-batching engine: N requests of
    random size <= req_batch against the arch's main embedding table.

    ``hot_rows`` enables the hot-row decode-ahead cache (DESIGN.md §9),
    ``hot_refresh`` re-points it at observed traffic every N flushes,
    and ``zipf_a`` > 1 switches the stream from uniform to power-law
    ids — the traffic mix the cache exists for."""
    from repro.core import Embedding
    from repro.launch.engine import (ServingEngine, drive_random_stream,
                                     drive_zipf_stream,
                                     embedding_config_of_arch)
    ecfg = embedding_config_of_arch(family, cfg)
    emb = Embedding(ecfg)
    params = emb.init(jax.random.PRNGKey(0))
    artifact = emb.export(params)
    full_bits = ecfg.vocab_size * ecfg.dim * 32
    print(f"engine table: kind={ecfg.kind} vocab={ecfg.vocab_size} "
          f"d={ecfg.dim}; artifact "
          f"{emb.serving_size_bits()/8/1e6:.2f} MB "
          f"({100*emb.serving_size_bits()/full_bits:.1f}% of full)")

    mesh = None
    if mesh_spec is not None:
        axes, shape = parse_mesh(mesh_spec)
        need = int(np.prod(shape))
        if jax.device_count() < need:
            raise SystemExit(
                f"--mesh {mesh_spec} needs {need} devices, found "
                f"{jax.device_count()} (XLA_FLAGS was set too late? "
                f"export XLA_FLAGS=--xla_force_host_platform_device_"
                f"count={need})")
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(shape, axes)
        model_n = dict(mesh.shape).get("model", 1)
        # size report only for quantized artifacts; other kinds fall
        # through so ServingEngine raises its designed ValueError.
        # Placement info comes off the scheme's artifact spec — the
        # leaves tagged rows=True are what gets row-sharded.
        if emb.scheme.supports_sharded_codes:
            spec = emb.scheme.artifact_leaves()
            mb = lambda ls: sum(l.storage_bits for l in ls) / 8 / 1e6
            codes_mb = mb([l for l in spec if l.rows])
            cb_mb = mb([l for l in spec if not l.rows])
            print(f"mesh {dict(mesh.shape)}: codes {codes_mb:.2f} MB "
                  f"row-sharded x{model_n} -> {codes_mb/model_n:.2f} "
                  f"MB/shard, + {cb_mb:.3f} MB codebooks replicated "
                  f"per device")

    engine = ServingEngine(emb, artifact, backend=backend,
                           max_queue=max_queue, mesh=mesh,
                           hot_rows=hot_rows or None,
                           hot_refresh_every=hot_refresh)
    if engine.hot_rows:
        # true block width comes off the scheme's spec (param_dtype
        # aware — bf16 tables cache bf16 rows)
        width = jnp.dtype(engine.emb.scheme.hot_dtype).itemsize
        hot_mb = engine.hot_rows * ecfg.dim * width / 1e6
        print(f"hot-row cache: {engine.hot_rows} rows pre-decoded "
              f"({hot_mb:.2f} MB dense, replicated)"
              + (f", refresh every {hot_refresh} flushes"
                 if hot_refresh else ""))
    if use_async:
        return serve_async_engine(engine, ecfg.vocab_size, req_batch,
                                  max_wait_us=max_wait_us,
                                  arrival_rate=arrival_rate,
                                  slo_ms=slo_ms, duration_s=duration_s,
                                  zipf_a=zipf_a or 1.2,
                                  hot_refresh=(hot_refresh
                                               if hot_rows else 0))
    if zipf_a:
        st = drive_zipf_stream(engine, ecfg.vocab_size, n_requests,
                               req_batch, zipf_a=zipf_a)
    else:
        st = drive_random_stream(engine, ecfg.vocab_size, n_requests,
                                 req_batch)
    print(f"engine: {st.requests} requests / {st.lookups} lookups in "
          f"{st.flushes} flushes, {st.seconds:.3f}s -> "
          f"{st.lookups_per_s:,.0f} lookups/s "
          f"(block_b={engine.block_b} x {engine.data_shards} data "
          f"shard(s), pad overhead "
          f"{100*(st.padded_lookups/st.lookups-1) if st.lookups else 0.0:.1f}%)")
    if engine.hot_rows:
        print(f"hot cache: hit rate {st.hit_rate:.1%} "
              f"({st.hot_hits}/{st.lookups} lookups cache-served; "
              f"{st.decoded_lookups} rows through the fused decode vs "
              f"{st.padded_lookups} without the cache; "
              f"{st.hot_refreshes} refresh(es))")
    return st


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-steps", type=int, default=16)
    ap.add_argument("--candidates", type=int, default=10000)
    ap.add_argument("--retrieval", default="flat_pq",
                    help="retrieval index kind for two-tower serving "
                         "(registered kinds: flat_pq, ivf_pq, ...)")
    ap.add_argument("--nprobe", type=int, default=8,
                    help="ivf_pq: coarse lists probed per query")
    ap.add_argument("--topk", type=int, default=100,
                    help="candidates returned per retrieval query")
    ap.add_argument("--host-staged", action="store_true",
                    help="retrieval: keep the list tables in host "
                         "memory; stage only probed lists per flush "
                         "(ivf_pq, DESIGN.md §12)")
    ap.add_argument("--engine", action="store_true",
                    help="drive the micro-batching ServingEngine")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--req-batch", type=int, default=64)
    ap.add_argument("--hot-rows", type=int, default=0,
                    help="pre-decode this many head rows into the "
                         "engine's hot-row cache (0 = off; DESIGN.md §9)")
    ap.add_argument("--hot-refresh", type=int, default=0,
                    help="re-point the hot cache at observed traffic "
                         "every N flushes (0 = static head-id set)")
    ap.add_argument("--zipf-a", type=float, default=0.0,
                    help="drive the engine with Zipf(a) power-law ids "
                         "instead of uniform (needs a > 1.0)")
    ap.add_argument("--async", dest="use_async", action="store_true",
                    help="serve through the AsyncServingEngine front-end "
                         "(DESIGN.md §10): open-loop arrival-rate-driven "
                         "stream, deadline-batched flushes, p50/p99/p999 "
                         "latency report against --slo-ms")
    ap.add_argument("--max-wait-us", type=float, default=1000.0,
                    help="async: flush deadline — a partial batch fires "
                         "once its oldest request has waited this long")
    ap.add_argument("--arrival-rate", type=float, default=500.0,
                    help="async: open-loop offered load, requests/second "
                         "(Poisson interarrivals)")
    ap.add_argument("--slo-ms", type=float, default=5.0,
                    help="async: p99 latency SLO the report is judged "
                         "against")
    ap.add_argument("--duration", type=float, default=2.0,
                    help="async: measured stream length in seconds")
    ap.add_argument("--kernel-backend", default=None,
                    choices=KERNEL_BACKENDS)
    ap.add_argument("--mesh", default=None, metavar="data=2,model=2",
                    help="serve the engine's artifact sharded over this "
                         "mesh (codes over 'model', batch over the rest)")
    args = ap.parse_args()
    setup_compile_cache()

    if args.mesh and not args.engine:
        ap.error("--mesh requires --engine")
    if args.mesh:
        # must happen before the first jax call of the process
        from repro.launch.mesh import force_host_device_count
        _, shape = parse_mesh(args.mesh)
        force_host_device_count(int(np.prod(shape)))

    family, cfg = get_arch(args.arch, smoke=args.smoke)
    if (args.hot_rows or args.hot_refresh or args.zipf_a) \
            and not args.engine:
        ap.error("--hot-rows/--hot-refresh/--zipf-a require --engine")
    if args.hot_refresh and not args.hot_rows:
        ap.error("--hot-refresh needs a cache to refresh; pass "
                 "--hot-rows N")
    if args.zipf_a and args.zipf_a <= 1.0:
        ap.error(f"--zipf-a must be > 1.0 (the truncated power law "
                 f"diverges at a <= 1), got {args.zipf_a}")
    if args.use_async and not args.engine:
        ap.error("--async requires --engine")
    if args.use_async and args.arrival_rate <= 0:
        ap.error(f"--arrival-rate must be > 0 (open-loop load is "
                 f"rate-driven), got {args.arrival_rate}")
    if args.host_staged and args.engine:
        ap.error("--host-staged applies to the retrieval serving path, "
                 "not --engine")
    if args.engine:
        serve_engine(family, cfg, args.requests, args.req_batch,
                     backend=args.kernel_backend, mesh_spec=args.mesh,
                     hot_rows=args.hot_rows, hot_refresh=args.hot_refresh,
                     zipf_a=args.zipf_a, use_async=args.use_async,
                     max_wait_us=args.max_wait_us,
                     arrival_rate=args.arrival_rate, slo_ms=args.slo_ms,
                     duration_s=args.duration)
    elif family == "lm":
        serve_lm(cfg, args.batch, args.prompt_len, args.decode_steps)
    elif cfg.model == "two_tower":
        serve_retrieval(cfg, args.candidates, index_kind=args.retrieval,
                        nprobe=args.nprobe, topk=args.topk,
                        backend=args.kernel_backend,
                        host_staged=args.host_staged)
    elif family == "recsys":
        serve_ctr(cfg, args.batch)
    else:
        raise SystemExit("mace has no serving path (train-only arch)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
