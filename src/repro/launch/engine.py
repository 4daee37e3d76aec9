"""Batched serving engines (paper Fig. 1 serving path + DESIGN.md §8).

Production serving traffic is many small requests, not one big batch.
The engines here own device-resident artifacts — placed once with
``jax.device_put`` and reused across every request, never re-uploaded —
and micro-batch queued requests into a single fused call:

  * ``submit(x)`` enqueues a request (coerced HOST-side — no device
    work on the submit path) and returns a handle;
  * ``flush()`` concatenates the queue in numpy, pads the flat batch
    up to the kernel's block granularity (so every launch hits the
    full-block fast path and JIT retraces are bounded by
    queue-size/block, not by request shape), runs ONE jitted call via
    the shared ``run_flat`` device leg — one upload, one fused call —
    and splits results back per request;
  * the synchronous helpers (``lookup`` / ``search``) are
    submit + flush.

Two engines share that plumbing (``_MicroBatchEngine``):

  ``ServingEngine``    id lookups -> embedding rows over one exported
                       quantized table (fused decode kernel);
  ``RetrievalEngine``  query vectors -> (top-k scores, candidate ids)
                       over a built retrieval index (fused batched ADC
                       top-k, flat or IVF — retrieval/).

Stats accumulate across flushes; ``stats()`` reports requests/second —
the numbers `benchmarks/kernel_bench.py` and `launch/serve.py` print
for fused-vs-unfused comparisons.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import Embedding


@dataclasses.dataclass
class EngineStats:
    requests: int = 0
    lookups: int = 0           # items actually requested (pre-padding)
    padded_lookups: int = 0    # items processed incl. block padding
    flushes: int = 0
    seconds: float = 0.0
    # hot-row cache accounting (ServingEngine, DESIGN.md §9): hits are
    # counted over REAL lookups only (flush padding rows never count),
    # decoded_lookups are the rows that actually reached the fused
    # decode kernel including the cold side's own block padding — a
    # fully cache-served flush adds zero here.
    hot_hits: int = 0
    decoded_lookups: int = 0
    hot_refreshes: int = 0

    @property
    def lookups_per_s(self) -> float:
        # zero guard: empty or instantaneous streams (all-cached
        # flushes, zero requests) report 0.0 instead of dividing by 0
        return self.lookups / self.seconds if self.seconds > 0 else 0.0

    @property
    def hit_rate(self) -> float:
        """Fraction of real lookups served from the hot-row cache."""
        return self.hot_hits / self.lookups if self.lookups else 0.0

    @classmethod
    def derived_metrics(cls) -> List[str]:
        """Every derived (computed) metric this stats class exports:
        the properties defined anywhere on the class — ONE registry, so
        subclasses adding derived fields (e.g. the async engine's
        latency percentiles) are exported by ``as_dict`` without
        re-listing them by hand."""
        return sorted({name for klass in cls.__mro__
                       for name, val in vars(klass).items()
                       if isinstance(val, property)})

    def as_dict(self) -> Dict:
        # counters first (a field with its own as_dict — e.g. the async
        # stats' latency histogram — exports through it), then every
        # registered derived metric, including subclass additions
        out = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            out[f.name] = v.as_dict() if hasattr(v, "as_dict") else v
        for name in self.derived_metrics():
            out[name] = getattr(self, name)
        return out


class _MicroBatchEngine:
    """Queue/pad/flush/split plumbing shared by the serving engines.

    Subclasses define ``_coerce_host`` (request -> numpy array with a
    leading batch dim) and ``_run`` (padded flat batch -> pytree of
    arrays with the same leading dim); everything else — queueing,
    padding to ``pad_multiple``, stats, splitting results back per
    request — is identical between id-lookup and retrieval traffic.
    """

    def __init__(self, pad_multiple: int, max_queue: int,
                 mesh=None):
        self.pad_multiple = pad_multiple
        self.max_queue = max_queue
        self.mesh = mesh
        self._queue: List[np.ndarray] = []
        self._queued = 0
        self._n_valid = 0          # real rows of the flush in flight
        self.stats_ = EngineStats()

    # --------------------------------------------------------- hooks
    def _coerce_host(self, request) -> np.ndarray:
        """Request -> host (numpy) array with a leading batch dim, NO
        device upload.  Both front-ends (the queueing ``submit`` here
        and `launch/async_engine.py`) queue requests host-side and
        ship one concatenated array per flush; per-request device
        arrays would cost a dispatch each on the submit path."""
        raise NotImplementedError

    def _run(self, flat: jax.Array):
        """One fused call over the padded flat batch; returns an array
        or pytree of arrays with flat.shape[0] leading rows."""
        raise NotImplementedError

    # --------------------------------------------------------- queue
    def submit(self, request) -> int:
        """Enqueue one request; returns its handle (index into the
        list the next flush() returns).  Requests are coerced and
        queued HOST-side (``_coerce_host``) so the submit path never
        dispatches device work — the whole batch ships as one upload
        inside the flush."""
        arr = self._coerce_host(request)
        self._queue.append(arr)
        self._queued += arr.shape[0]
        return len(self._queue) - 1

    @property
    def pending(self) -> int:
        return self._queued

    def should_flush(self) -> bool:
        return self._queued >= self.max_queue

    # --------------------------------------------------------- serve
    def flush(self) -> List:
        """Process every queued request in one padded micro-batch.

        Assembly and padding are pure host work routed through the
        shared :meth:`run_flat` device leg — the device-side
        ``jnp.pad`` this method used to do re-dispatched (and on a
        fresh length, recompiled) per distinct unpadded batch size
        (lint rule ``pad-in-flush``, DESIGN.md §15)."""
        if not self._queue:
            return []
        reqs, self._queue = self._queue, []
        n_req, n_rows = len(reqs), self._queued
        self._queued = 0
        flat = np.concatenate(reqs) if n_req > 1 else reqs[0]
        out = self.run_flat(flat, n_rows, n_requests=n_req)
        sizes = [r.shape[0] for r in reqs]
        splits = np.cumsum(sizes)[:-1].tolist()
        leaves, treedef = jax.tree.flatten(out)
        pieces = [jnp.split(leaf[:n_rows], splits) if splits
                  else [leaf[:n_rows]] for leaf in leaves]
        return [treedef.unflatten([p[i] for p in pieces])
                for i in range(n_req)]

    def run_flat(self, flat: np.ndarray, n_valid: Optional[int] = None,
                 n_requests: int = 1):
        """One fused call over a HOST-assembled flat batch — the async
        front-end's flush path (`launch/async_engine.py`); the queueing
        ``submit``/``flush`` pair above is unchanged.

        Padding happens in numpy BEFORE the single upload: the
        device-side padding in ``flush`` re-dispatches (and on a fresh
        length, recompiles) for every distinct unpadded batch size,
        which on a latency-SLO path turns each odd-sized micro-batch
        into tens of milliseconds of XLA work.  Host padding is a
        memcpy, and the padded lengths collapse to a couple of stable,
        warmable shapes.  Returns the RAW result pytree (padded rows
        included) — callers slice ``[:n_valid]`` host-side, where it is
        free.  Stats accumulate as ``n_requests`` requests (the queueing
        ``flush`` and the async front-end pass their batch sizes; the
        default 1 fits direct callers) of ``n_valid`` total lookups.
        """
        n_valid = int(flat.shape[0] if n_valid is None else n_valid)
        pad = (-n_valid) % self.pad_multiple
        if pad:
            widths = [(0, pad)] + [(0, 0)] * (flat.ndim - 1)
            flat = np.pad(flat, widths)    # zero rows are always valid
        dev = jnp.asarray(flat)
        self._n_valid = n_valid        # lets _run tell rows from padding
        t0 = time.perf_counter()
        if self.mesh is not None:
            with self.mesh:
                out = self._run(dev)
        else:
            out = self._run(dev)
        jax.block_until_ready(out)
        self.stats_.seconds += time.perf_counter() - t0
        self.stats_.requests += n_requests
        self.stats_.lookups += n_valid
        self.stats_.padded_lookups += int(dev.shape[0])
        self.stats_.flushes += 1
        return out

    def serve_stream(self, requests: Sequence[np.ndarray],
                     outputs: Optional[list] = None) -> EngineStats:
        """Drive a request stream through the micro-batcher; flush
        whenever the queue reaches max_queue, once more at the end.
        ``outputs``, when given, receives each request's result in
        stream order."""
        for r in requests:
            self.submit(r)
            if self.should_flush():
                res = self.flush()
                if outputs is not None:
                    outputs.extend(res)
        res = self.flush()
        if outputs is not None:
            outputs.extend(res)
        return self.stats_

    def stats(self) -> EngineStats:
        return self.stats_


class ServingEngine(_MicroBatchEngine):
    """Micro-batching lookup engine over one exported embedding table.

    Single-device by default.  Pass ``mesh`` to serve a *sharded*
    quantized artifact (DESIGN.md §6): code tables are placed
    row-sharded over ``model_axis`` and codebooks replicated — each
    shard device-resident once — and every flush fans ONE batched
    decode across the whole mesh through the shard_map quantized
    gather, padded to ``block_b x data_shards`` so each data shard's
    local batch still hits the decode kernel's full-block fast path.

    **Hot-row cache** (DESIGN.md §9): recsys traffic is power-law — the
    head tier absorbs most lookups — so when ``hot_rows`` > 0 (or the
    config/artifact carry a pre-decoded ``hot`` block from export) the
    engine keeps a dense ``(C, d)`` block of the hottest rows and
    splits every flush: cached ids are a plain gather from the block,
    only the cold remainder (padded to ``block_b``) reaches the fused
    decode, and a gather-merge reassembles the flush.  Cached rows are
    bit-identical to the cold path — the block is either the artifact's
    export-time pre-decode or re-decoded through THIS engine's own
    serve function.  ``refresh_hot_rows()`` re-points the cache at the
    observed-hottest ids (EMA frequency counters accumulated per
    flush), so the cached set tracks live traffic rather than static
    tiering; ``hot_refresh_every`` automates that every N flushes.
    """

    def __init__(self, emb: Embedding, artifact: dict,
                 block_b: Optional[int] = None,
                 max_queue: int = 65536,
                 backend: Optional[str] = None,
                 mesh=None, model_axis: str = "model",
                 hot_rows: Optional[int] = None,
                 hot_ema_decay: float = 0.99,
                 hot_refresh_every: int = 0,
                 hot_track_freq: Optional[bool] = None):
        overrides = {}
        if backend is not None:
            overrides["kernel_backend"] = backend
        if block_b is not None:
            # the kernel's block size must match the queue padding —
            # otherwise a custom block_b would pad flushes to sizes the
            # decode kernel re-pads anyway, multiplying retraces
            overrides["decode_block_b"] = block_b
        self.model_axis = model_axis
        data_shards = 1
        if mesh is not None:
            cfg = emb.cfg
            # registry-driven capability check: any scheme whose codes
            # the sharded gather can row-shard qualifies (DESIGN.md §7)
            if not emb.scheme.supports_sharded_codes:
                raise ValueError(
                    f"sharded serving needs a quantized table, got "
                    f"kind={cfg.kind!r}")
            if model_axis not in mesh.axis_names:
                raise ValueError(
                    f"mesh {dict(mesh.shape)} has no {model_axis!r} axis "
                    f"to shard codes over")
            model_n = dict(mesh.shape)[model_axis]
            if model_n > 1 and cfg.vocab_size % model_n:
                raise ValueError(
                    f"vocab={cfg.vocab_size} does not divide over "
                    f"{model_axis}={model_n}")
            data_shards = int(np.prod(
                [n for a, n in mesh.shape.items() if a != model_axis])) or 1
            overrides["sharded_codes"] = True
        if overrides:
            # rebuild the config so the decode path dispatches as asked
            emb = Embedding(dataclasses.replace(emb.cfg, **overrides))
        self.emb = emb
        self.block_b = emb.cfg.decode_block_b
        self.data_shards = data_shards
        # flushes pad to this granularity: block_b per data shard
        super().__init__(pad_multiple=self.block_b * data_shards,
                         max_queue=max_queue, mesh=mesh)
        # device-resident once; requests only ship (B,) int32 ids
        if mesh is not None:
            from repro.sharding.rules import shard_quantized_artifact
            self.artifact = shard_quantized_artifact(
                artifact, emb.cfg, mesh, model_axis=model_axis)
        else:
            self.artifact = jax.device_put(artifact)
        self._serve = jax.jit(lambda art, ids: emb.serve(art, ids))

        # ------------------------------------------------ hot-row cache
        self.hot_rows = (emb.cfg.hot_rows if hot_rows is None
                         else int(hot_rows))
        if not 0 <= self.hot_rows <= emb.cfg.vocab_size:
            raise ValueError(
                f"hot_rows={self.hot_rows} must lie in [0, vocab_size="
                f"{emb.cfg.vocab_size}]")
        self.hot_ema_decay = float(hot_ema_decay)
        self.hot_refresh_every = int(hot_refresh_every)
        # the EMA counters cost O(vocab) host work per flush; track
        # them only when the adaptive cache is actually in play
        self.hot_track_freq = (hot_refresh_every > 0
                               if hot_track_freq is None
                               else bool(hot_track_freq))
        self._hot_block = None     # (C, d) device block, None = disabled
        self._hot_slot = None      # host (vocab,) int32 id->slot, -1 cold
        self._hot_ids = None       # (C,) host int64, the cached id set
        self._freq = None          # (vocab,) float32 EMA traffic counters
        if self.hot_rows:
            # Seed with the head ids (frequency-sorted convention).
            # The artifact's export-time pre-decode is reused verbatim
            # only when this engine decodes through the exact same path
            # (no backend/block rebuild, no mesh); otherwise the block
            # is re-decoded through self._serve so cached rows stay
            # bit-identical to this engine's cold decode.
            block = None
            if ("hot" in artifact and not overrides and mesh is None
                    and artifact["hot"].shape[0] == self.hot_rows):
                block = self.artifact["hot"]
            self._set_hot_rows(np.arange(self.hot_rows), block=block)

        def gather_select(hot_block, cold_out, slots, cold_rank):
            # two O(B)-row gathers + a select, NO scatter (XLA scatters
            # crawl on CPU) and NO concatenate (an O(C) buffer copy per
            # flush — the cache block can be tens of MB): position i
            # takes its cache row when slot >= 0, else its decoded row
            # via the host-computed rank into the cold batch.
            hot = jnp.take(hot_block,
                           jnp.clip(slots, 0, hot_block.shape[0] - 1),
                           axis=0)
            cold = jnp.take(cold_out, cold_rank, axis=0)
            return jnp.where((slots >= 0)[:, None], hot, cold)

        def cold_merge(art, hot_block, slots, cold_ids, cold_rank):
            # single device: decode + merge in ONE dispatch
            return gather_select(hot_block, emb.serve(art, cold_ids),
                                 slots, cold_rank)

        self._cold_merge = jax.jit(cold_merge)
        # mesh path: the shard_map decode must run as its OWN jit — a
        # shard_map output consumed by further ops inside one jit
        # miscounts under GSPMD (P() x P('data') concat doubles the
        # sharded operand) — then the same gather-select merges its
        # materialized output, tolerating mixed shardings
        self._mesh_merge = jax.jit(gather_select)
        self._hot_only = jax.jit(
            lambda blk, slots: jnp.take(
                blk, jnp.clip(slots, 0, blk.shape[0] - 1), axis=0))

    # ----------------------------------------------------- hot-row cache
    def _decode_ids(self, ids_np: np.ndarray) -> jax.Array:
        """Decode arbitrary ids through the engine's own jitted serve
        path (padded to the flush granularity) — by construction
        bit-identical to what the cold path of a flush would produce."""
        n = len(ids_np)
        pad = (-n) % self.pad_multiple
        padded = np.concatenate([ids_np, np.zeros(pad, np.int64)]) \
            if pad else ids_np
        ids = jnp.asarray(padded, jnp.int32)
        if self.mesh is not None:
            with self.mesh:
                out = self._serve(self.artifact, ids)
        else:
            out = self._serve(self.artifact, ids)
        return out[:n]

    def prepare_hot_rows(self, ids_np: np.ndarray, block=None) -> tuple:
        """Build (but do not install) the cache state for an id set:
        decode the block through the engine's own serve path, place it
        device-resident (replicated under a mesh), and compute the
        id->slot map.  Pure with respect to the engine's live cache
        fields, so a background thread can run it concurrently with
        flushes and hand the result to :meth:`install_hot_rows` for an
        atomic swap (the async engine's refresh path, DESIGN.md §10)."""
        ids_np = np.asarray(ids_np, np.int64)
        if block is None:
            block = self._decode_ids(ids_np)
        if self.mesh is not None:
            # the serve output is data-sharded; the cache block is read
            # by every flush on every device — replicate it
            from jax.sharding import NamedSharding, PartitionSpec as P
            block = jax.device_put(np.asarray(block),
                                   NamedSharding(self.mesh, P()))
        else:
            block = jax.device_put(jnp.asarray(block))
        slot = np.full(self.emb.cfg.vocab_size, -1, np.int32)
        slot[ids_np] = np.arange(len(ids_np), dtype=np.int32)
        return block, slot, ids_np

    def install_hot_rows(self, state: tuple) -> None:
        """Swap a prepared cache state in.  Three reference assignments
        — effectively atomic under the GIL, and the flush path reads
        each field once — so a refresh never blocks or tears a flush."""
        self._hot_block, self._hot_slot, self._hot_ids = state

    def _set_hot_rows(self, ids_np: np.ndarray, block=None) -> None:
        self.install_hot_rows(self.prepare_hot_rows(ids_np, block=block))

    def select_hot_ids(self):
        """The top ``hot_rows`` ids by the EMA frequency counters (ties
        broken by id, deterministically), or None before any traffic is
        observed."""
        if self._freq is None:
            return None
        order = np.lexsort((np.arange(len(self._freq)), -self._freq))
        return np.sort(order[:self.hot_rows])

    def refresh_hot_rows(self, hot_ids=None) -> np.ndarray:
        """Re-point the cache at the observed-hottest ids and re-decode
        the block through the engine's own serve path.

        ``hot_ids`` defaults to the top ``hot_rows`` ids by the EMA
        frequency counters (:meth:`select_hot_ids`); an explicit id set
        overrides.  Before any traffic is observed the current set is
        kept.  Returns the active hot id set."""
        if not self.hot_rows:
            raise ValueError("hot-row cache disabled (hot_rows=0)")
        if hot_ids is None:
            hot_ids = self.select_hot_ids()
            if hot_ids is None:
                return self._hot_ids       # no traffic observed yet
        hot_ids = np.asarray(hot_ids, np.int64)
        self.stats_.hot_refreshes += 1
        if np.array_equal(hot_ids, self._hot_ids):
            # steady state: the selected set is unchanged — skip the
            # O(C) re-decode, the block upload, and the slot rebuild
            return self._hot_ids
        self._set_hot_rows(hot_ids)
        return self._hot_ids

    # --------------------------------------------------------- serve
    def _coerce_host(self, ids) -> np.ndarray:
        return np.asarray(ids, np.int32).reshape(-1)

    def _run(self, flat: jax.Array) -> jax.Array:
        if self._hot_block is None:
            self.stats_.decoded_lookups += int(flat.shape[0])
            return self._serve(self.artifact, flat)
        # host-side split; clip mirrors jnp.take's OOB-clamp semantics
        flat_np = np.clip(np.asarray(flat), 0, self.emb.cfg.vocab_size - 1)
        if self.hot_track_freq:
            # EMA traffic counters feed refresh_hot_rows (real rows only)
            if self._freq is None:
                self._freq = np.zeros(self.emb.cfg.vocab_size, np.float32)
            self._freq *= self.hot_ema_decay
            self._freq += np.bincount(flat_np[:self._n_valid],
                                      minlength=len(self._freq)
                                      ).astype(np.float32)
        slots = self._hot_slot[flat_np]            # (B,), -1 = cold
        self.stats_.hot_hits += int((slots[:self._n_valid] >= 0).sum())
        # flush-padding rows are dropped after the flush — point them
        # at cache row 0 so they never force fused-decode work
        slots[self._n_valid:] = 0
        cold_mask = slots < 0
        n_cold = int(cold_mask.sum())
        if n_cold == 0:
            # fully cache-served: zero fused-decode (kernel) work
            return self._hot_only(self._hot_block, jnp.asarray(slots))
        cold_rank = np.maximum(np.cumsum(cold_mask) - 1, 0)
        cold_ids = flat_np[cold_mask]
        pad = (-n_cold) % self.pad_multiple
        if pad:
            cold_ids = np.concatenate(
                [cold_ids, np.zeros(pad, cold_ids.dtype)])
        self.stats_.decoded_lookups += cold_ids.size
        slots_dev = jnp.asarray(slots)
        rank_dev = jnp.asarray(cold_rank.astype(np.int32))
        cold_dev = jnp.asarray(cold_ids, jnp.int32)
        if self.mesh is not None:
            cold_out = self._serve(self.artifact, cold_dev)
            return self._mesh_merge(self._hot_block, cold_out,
                                    slots_dev, rank_dev)
        return self._cold_merge(self.artifact, self._hot_block,
                                slots_dev, cold_dev, rank_dev)

    def run_flat(self, flat: np.ndarray, n_valid: Optional[int] = None,
                 n_requests: int = 1):
        out = super().run_flat(flat, n_valid, n_requests=n_requests)
        # one refresh cadence for BOTH front-ends — the queueing flush()
        # routes through here; the async front-end sets
        # hot_refresh_every=0 and refreshes on its own thread
        if (self._hot_block is not None and self.hot_refresh_every
                and self.stats_.flushes % self.hot_refresh_every == 0):
            self.refresh_hot_rows()
        return out

    def lookup(self, ids) -> jax.Array:
        """Synchronous single-request path (submit + flush).  Flushes
        whatever else is queued too and returns THIS request's rows."""
        handle = self.submit(ids)
        return self.flush()[handle]


class RetrievalEngine(_MicroBatchEngine):
    """Micro-batching top-k retrieval over one built index.

    Requests are query-vector batches (B_i, d); every flush pads the
    concatenated queries to ``block_q x data_shards`` and runs ONE
    fused batched search (``Index.search``) returning per request
    ``(scores (B_i, k), candidate ids (B_i, k))`` — candidate ids +
    scores instead of embedding rows, same plumbing.

    Pass ``mesh`` to search a *distributed* corpus (DESIGN.md §8):
    the O(corpus) artifact rows are placed row-sharded over
    ``model_axis`` (``sharding/rules.shard_retrieval_artifact``) and
    every flush fans one shard_map per-shard-top-k + merge across the
    whole mesh — wire bytes O(B·k), corpus-independent.

    Pass ``host_staged=True`` (or build the index with
    ``IndexConfig(host_staged=True)``) to keep the O(corpus) list
    tables in HOST memory (DESIGN.md §12): every flush stages only the
    probed lists to device (``Index.search_host_staged``) — upload
    ∝ B·nprobe·cap per flush, corpus-independent.  Single-device only
    (a sharded corpus already bounds per-device bytes by 1/shards).
    """

    def __init__(self, index, artifact: dict, k: int,
                 block_q: int = 64, max_queue: int = 4096,
                 backend: Optional[str] = None,
                 mesh=None, model_axis: str = "model",
                 host_staged: Optional[bool] = None):
        from repro.retrieval import get_index, sharded_topk
        if backend is not None:
            index = get_index(dataclasses.replace(
                index.cfg, kernel_backend=backend))
        self.index, self.k = index, k
        self.block_q = block_q
        self.model_axis = model_axis
        if host_staged is None:
            host_staged = index.cfg.host_staged
        if host_staged:
            if mesh is not None:
                raise ValueError(
                    "host_staged serving is single-device; a sharded "
                    "corpus already bounds per-device bytes")
            if not index.supports_host_staged:
                raise ValueError(
                    f"index kind {index.cfg.kind!r} has no host-staged "
                    f"serve path")
        self.host_staged = bool(host_staged)
        data_shards = 1
        if mesh is not None:
            if not index.supports_sharded:
                raise ValueError(
                    f"index kind {index.cfg.kind!r} cannot be "
                    f"distributed")
            if model_axis not in mesh.axis_names:
                raise ValueError(
                    f"mesh {dict(mesh.shape)} has no {model_axis!r} axis "
                    f"to shard corpus rows over")
            model_n = dict(mesh.shape)[model_axis]
            bad = {name: artifact[name].shape[0]
                   for name in index.rows_leaves
                   if artifact[name].shape[0] % model_n}
            if model_n > 1 and bad:
                raise ValueError(
                    f"corpus rows {bad} do not divide over "
                    f"{model_axis}={model_n}")
            data_shards = int(np.prod(
                [n for a, n in mesh.shape.items() if a != model_axis])) or 1
        self.data_shards = data_shards
        super().__init__(pad_multiple=block_q * data_shards,
                         max_queue=max_queue, mesh=mesh)
        if mesh is not None:
            from repro.sharding.rules import shard_retrieval_artifact
            self.artifact = shard_retrieval_artifact(
                artifact, index, mesh, model_axis=model_axis)
            self._search = jax.jit(lambda art, q: sharded_topk(
                index, art, q, k, model_axis=model_axis, mesh=mesh))
        elif self.host_staged:
            # host leaves stay numpy; only the tiny replicated leaves
            # (coarse table, codebooks, chain) go to device up front
            host = set(index.host_leaves())
            self.artifact = {
                name: np.asarray(leaf) if name in host
                else jax.device_put(jnp.asarray(leaf))
                for name, leaf in artifact.items()}
            # search_host_staged jits its device stages internally (the
            # staged-list count varies per flush)
            self._search = lambda art, q: index.search_host_staged(
                art, q, k)
        else:
            self.artifact = jax.device_put(
                {name: jnp.asarray(leaf)
                 for name, leaf in artifact.items()})
            self._search = jax.jit(lambda art, q: index.search(art, q, k))

    @property
    def staged_mbytes(self) -> float:
        """Total MB staged to device so far (host-staged mode)."""
        return float(getattr(self.index, "staged_bytes", 0)) / 1e6

    def _coerce_host(self, queries) -> np.ndarray:
        q = np.asarray(queries, np.float32)
        return q[None] if q.ndim == 1 else q

    def _run(self, flat: jax.Array):
        return self._search(self.artifact, flat)

    def search(self, queries):
        """Synchronous single-request path (submit + flush): queries
        (B, d) or (d,) -> (scores, ids).  Flushes whatever else is
        queued too and returns THIS request's results."""
        handle = self.submit(queries)
        return self.flush()[handle]


def drive_random_stream(engine: ServingEngine, vocab_size: int,
                        n_requests: int, req_batch: int,
                        seed: int = 0) -> EngineStats:
    """Shared bench/demo harness: stream n_requests random-size
    requests (1..req_batch ids each) and return the throughput stats.

    The identical stream is driven twice: flush points are a pure
    function of the request sizes, so the first pass compiles every
    padded shape the measured pass will hit — the returned stats
    contain zero XLA compile time."""
    rng = np.random.default_rng(seed)
    reqs = [rng.integers(0, vocab_size, int(rng.integers(1, req_batch + 1)))
            for _ in range(n_requests)]
    engine.serve_stream(reqs)          # warm pass: pays all jit traces
    engine.stats_ = EngineStats()
    return engine.serve_stream(reqs)


def drive_zipf_stream(engine: ServingEngine, vocab_size: int,
                      n_requests: int, req_batch: int,
                      zipf_a: float = 1.2, seed: int = 0,
                      outputs: Optional[list] = None) -> EngineStats:
    """Power-law twin of :func:`drive_random_stream`: Zipf(``zipf_a``)
    ids over the frequency-sorted vocabulary — the head-heavy traffic
    the hot-row cache exists for (DESIGN.md §9).

    The identical stream is driven twice: with a static hot set the
    hot/cold split is a pure function of the request ids, so the warm
    pass compiles every (flush, cold-batch) shape the measured pass
    hits — zero XLA compile time in the returned stats.  (Auto-refresh
    between passes can shift the cached set and re-trace a handful of
    shapes; the EMA counters and stats are reset so the measured pass
    starts clean either way.)  ``outputs`` receives the measured
    pass's per-request results."""
    from repro.data.synthetic import zipf_request_stream
    reqs = zipf_request_stream(vocab_size, n_requests, req_batch,
                               zipf_a=zipf_a, seed=seed)
    engine.serve_stream(reqs)          # warm pass: pays all jit traces
    engine.stats_ = EngineStats()
    if engine._freq is not None:
        engine._freq[:] = 0.0
    return engine.serve_stream(reqs, outputs)


def drive_random_query_stream(engine: RetrievalEngine, dim: int,
                              n_requests: int, req_batch: int,
                              seed: int = 0) -> EngineStats:
    """Retrieval twin of :func:`drive_random_stream`: random-size
    query-vector requests, warm pass first, zero compile time in the
    returned stats."""
    rng = np.random.default_rng(seed)
    reqs = [rng.normal(size=(int(rng.integers(1, req_batch + 1)), dim)
                       ).astype(np.float32)
            for _ in range(n_requests)]
    engine.serve_stream(reqs)          # warm pass: pays all jit traces
    engine.stats_ = EngineStats()
    return engine.serve_stream(reqs)


def embedding_config_of_arch(family: str, cfg):
    """Pick the arch's main large-vocab EmbeddingConfig (engine demo)."""
    from repro.models.recsys.fields import field_embedding_config
    if family == "lm":
        return cfg.embedding
    if cfg.model == "bst":
        return field_embedding_config(cfg, cfg.n_items)
    if cfg.model == "two_tower":
        return field_embedding_config(cfg, cfg.n_items)
    return field_embedding_config(cfg, max(cfg.field_vocab_sizes))


__all__ = ["EngineStats", "RetrievalEngine", "ServingEngine",
           "drive_random_query_stream", "drive_random_stream",
           "drive_zipf_stream", "embedding_config_of_arch"]
