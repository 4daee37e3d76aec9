"""Kernel backend dispatch — one switch for every hand-written kernel.

Every hot-path op in ``repro.kernels`` ships (at least) two
implementations:

  ``pallas``     the hand-written Pallas TPU kernel (``<name>.py``)
  ``xla``        the pure-jnp oracle (``ref.py``) — XLA fuses it well
                 enough to be the correct CPU/GPU fallback
  ``interpret``  the Pallas kernel run in interpret mode — executes the
                 kernel *body* on CPU, so CI exercises the exact code
                 that runs on TPU (DESIGN.md §5)

Call sites never branch on hardware.  They call
:func:`dispatch`/``op(..., backend=None)`` and the backend is resolved
in precedence order:

  1. explicit ``backend=`` argument (e.g. from a config field such as
     ``EmbeddingConfig.kernel_backend``); ``"auto"`` and ``None`` both
     mean "no preference"
  2. the ``REPRO_KERNEL_BACKEND`` environment variable — the operator
     override for everything left on auto (CI exports
     ``REPRO_KERNEL_BACKEND=interpret`` and every default-configured op
     follows; a call site that pins a concrete backend keeps it)
  3. the process-wide default set via :func:`set_default_backend` /
     :func:`use_backend`
  4. ``auto``: ``pallas`` when a TPU is attached, else ``xla``

Only ``auto`` looks at the hardware.  A concrete request is honoured
or refused, never swapped: asking for ``pallas`` with no TPU attached
raises (a measurement that asked for the kernel must not silently time
the XLA reference instead), while ``interpret`` runs anywhere — that is
the whole point of interpret mode.

Registration is done by each kernel package's ``ops.py`` at import
time; :func:`dispatch` lazily imports ``repro.kernels`` so the registry
is populated no matter which module is imported first.

Block-size autotune (DESIGN.md §11)
-----------------------------------

Every kernel in this repo exposes block-geometry knobs (``block_b``,
``block_d``, ``block_n``) whose defaults were historically hand-picked
per op and never revisited per backend or shape.  ``register_op`` now
accepts a declared *tunable-params spec* — kwarg name ->
:class:`Tunable` (default + candidate values) — and two layers use it
with no per-op glue:

  * :func:`tune` sweeps the candidate grid over representative example
    args, timing each combination on the resolved backend, and caches
    the winner keyed by ``(op, backend, shape-bucket)`` where the
    bucket rounds every array dim up to the next power of two (so
    nearby shapes share a tuned config);
  * :func:`dispatch` consults the cache: any declared tunable kwarg the
    caller leaves unset (or passes as ``None``) resolves to the tuned
    value for the call's shape bucket, falling back to the declared
    default.  An explicitly passed concrete value always pins.

The in-process cache optionally persists to a JSON file named by the
``REPRO_KERNEL_TUNE_CACHE`` environment variable: :func:`tune` saves
after each sweep and the first cache lookup loads it, so a CI-produced
cache file can seed a serving process.  A missing or unreadable file
degrades to the declared defaults with a warning — tuning is a
performance layer, never a correctness dependency (tuned and default
block sizes are bit-identical by the kernels' contract; the property
suite in tests/test_autotune.py holds them to it).
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import os
import time
import warnings
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax

BACKENDS = ("auto", "pallas", "xla", "interpret")

ENV_VAR = "REPRO_KERNEL_BACKEND"
TUNE_CACHE_ENV = "REPRO_KERNEL_TUNE_CACHE"

_REGISTRY: Dict[str, Dict[str, Callable]] = {}
_TUNABLES: Dict[str, Dict[str, "Tunable"]] = {}

# (op, backend, shape-bucket) -> {param: value}
_TUNED: Dict[Tuple[str, str, str], Dict[str, Any]] = {}
_tune_file_loaded: Optional[str] = None

_default_backend: str = "auto"


# ----------------------------------------------------------------------
# backend selection
# ----------------------------------------------------------------------

def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def set_default_backend(backend: str) -> None:
    """Set the process-wide default backend (lowest-precedence knob)."""
    global _default_backend
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    _default_backend = backend


def get_default_backend() -> str:
    return _default_backend


@contextlib.contextmanager
def use_backend(backend: str):
    """Temporarily override the default backend (tests, benchmarks)."""
    prev = _default_backend
    set_default_backend(backend)
    try:
        yield
    finally:
        set_default_backend(prev)


def resolve_backend(backend: Optional[str] = None) -> str:
    """Resolve a backend request to a concrete one of pallas|xla|interpret.

    Precedence: explicit arg > $REPRO_KERNEL_BACKEND > process default
    > auto.  ``auto`` resolves to ``pallas`` on TPU and ``xla``
    elsewhere; ``pallas`` without a TPU raises RuntimeError.
    """
    if backend == "auto":
        backend = None          # "auto" carries no preference
    choice = backend or os.environ.get(ENV_VAR) or _default_backend
    if choice not in BACKENDS:
        raise ValueError(f"unknown kernel backend {choice!r}; "
                         f"expected one of {BACKENDS}")
    if choice == "auto":
        return "pallas" if _on_tpu() else "xla"
    if choice == "pallas" and not _on_tpu():
        raise RuntimeError(
            f"kernel backend 'pallas' needs a TPU, but JAX's backend is "
            f"{jax.default_backend()!r}; ask for 'auto', 'xla' or "
            f"'interpret' instead")
    return choice


# ----------------------------------------------------------------------
# op registry
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Tunable:
    """One autotunable kwarg of a kernel op: its default plus the
    candidate values :func:`tune` sweeps.  Candidates must be
    value-interchangeable — the op's output is bit-identical across
    them (block geometry only changes the schedule)."""

    default: Any
    candidates: Tuple[Any, ...]


def register_op(name: str, *, pallas: Callable, xla: Callable,
                interpret: Optional[Callable] = None,
                tunables: Optional[Dict[str, Tunable]] = None) -> None:
    """Register one op's implementations.

    ``interpret`` defaults to the pallas entry point — kernel wrappers
    in this repo accept ``interpret=...`` themselves, so most register
    an explicit closure instead.  ``tunables`` declares the op's
    autotunable block-geometry kwargs (see the module docstring); an
    empty dict means "tunable-aware, nothing to sweep".
    """
    _REGISTRY[name] = {
        "pallas": pallas,
        "xla": xla,
        "interpret": interpret if interpret is not None else pallas,
    }
    _TUNABLES[name] = dict(tunables or {})


def registered_ops() -> Dict[str, Dict[str, Callable]]:
    _ensure_registered()
    return dict(_REGISTRY)


def _ensure_registered() -> None:
    if not _REGISTRY:
        # ops.py modules register themselves at import time
        import repro.kernels  # noqa: F401


def get_impl(name: str, backend: Optional[str] = None) -> Callable:
    """Concrete callable for ``name`` under the resolved backend."""
    _ensure_registered()
    if name not in _REGISTRY:
        raise KeyError(f"kernel op {name!r} not registered; known: "
                       f"{sorted(_REGISTRY)}")
    return _REGISTRY[name][resolve_backend(backend)]


def dispatch(name: str, *args, backend: Optional[str] = None, **kwargs):
    """Run op ``name`` on the resolved backend.

    Declared tunable kwargs the caller leaves unset (or passes as
    ``None``) resolve through the autotune cache for this call's shape
    bucket, falling back to the declared defaults — so tuned block
    sizes apply transparently while explicit values always pin.
    """
    impl = get_impl(name, backend)
    spec = _TUNABLES.get(name)
    if spec:
        tuned = None
        for param, t in spec.items():
            if kwargs.get(param) is None:
                if tuned is None:
                    tuned = tuned_params(name, args, backend=backend)
                kwargs[param] = tuned.get(param, t.default)
    return impl(*args, **kwargs)


# ----------------------------------------------------------------------
# block-size autotune
# ----------------------------------------------------------------------

def op_tunables(name: str) -> Dict[str, Tunable]:
    """Declared tunable spec for ``name`` (empty when none declared)."""
    _ensure_registered()
    return dict(_TUNABLES.get(name, {}))


def _bucket_dim(n: int) -> int:
    return 1 << (int(n) - 1).bit_length() if n > 0 else 0


def shape_bucket(*args) -> str:
    """Canonical shape-bucket key for a call's positional args.

    Array args contribute ``dtype[dims]`` with every dim rounded up to
    the next power of two (so e.g. B=4000 and B=4096 share one tuned
    config); scalars/None contribute their repr.  The bucket, together
    with op and backend, keys the tune cache.
    """
    parts = []
    for a in args:
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            dims = "x".join(str(_bucket_dim(d)) for d in a.shape)
            parts.append(f"{jax.numpy.dtype(a.dtype).name}[{dims}]")
        else:
            parts.append(repr(a))
    return ",".join(parts)


def _tune_file() -> Optional[str]:
    return os.environ.get(TUNE_CACHE_ENV) or None


def _maybe_load_tune_file() -> None:
    """Merge the JSON cache file named by $REPRO_KERNEL_TUNE_CACHE into
    the in-process cache (once per distinct path; in-process entries
    win).  Any read/parse failure warns and falls back to defaults —
    a stale or corrupt cache must never take the process down."""
    global _tune_file_loaded
    path = _tune_file()
    if path is None or path == _tune_file_loaded:
        return
    _tune_file_loaded = path
    if not os.path.exists(path):
        return
    try:
        with open(path) as f:
            raw = json.load(f)
        entries = []
        for op, per_backend in raw.items():
            for be, per_bucket in per_backend.items():
                if be not in BACKENDS:
                    raise ValueError(f"unknown backend {be!r}")
                for bucket, params in per_bucket.items():
                    if not isinstance(params, dict):
                        raise ValueError(f"params for {op}/{be}/{bucket} "
                                         f"not a dict")
                    entries.append(((op, be, bucket), dict(params)))
    except (OSError, ValueError, AttributeError) as e:
        warnings.warn(f"ignoring invalid kernel tune cache {path!r}: {e}; "
                      f"falling back to default block sizes",
                      RuntimeWarning, stacklevel=2)
        return
    for key, params in entries:
        _TUNED.setdefault(key, params)


def save_tune_cache(path: Optional[str] = None) -> Optional[str]:
    """Write the in-process tune cache as JSON to ``path`` (default:
    $REPRO_KERNEL_TUNE_CACHE).  No-op returning None when neither
    names a file."""
    path = path or _tune_file()
    if path is None:
        return None
    out: Dict[str, Dict[str, Dict[str, Dict[str, Any]]]] = {}
    for (op, be, bucket), params in sorted(_TUNED.items()):
        out.setdefault(op, {}).setdefault(be, {})[bucket] = params
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    return path


def clear_tune_cache() -> None:
    """Drop every in-process tuned entry (tests; does not touch the
    JSON file) and forget which file was loaded."""
    global _tune_file_loaded
    _TUNED.clear()
    _tune_file_loaded = None


def tuned_params(name: str, args: Iterable, *,
                 backend: Optional[str] = None) -> Dict[str, Any]:
    """Cached tuned kwargs for op ``name`` called with ``args`` on the
    resolved backend — ``{}`` when the shape bucket was never tuned."""
    if not _TUNABLES.get(name):
        return {}
    _maybe_load_tune_file()
    key = (name, resolve_backend(backend), shape_bucket(*args))
    return dict(_TUNED.get(key, {}))


def _default_timer(thunk: Callable[[], Any], iters: int) -> float:
    out = thunk()                       # compile + warm outside the clock
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())
        best = min(best, time.perf_counter() - t0)
    return best


def tune(name: str, args_sets: Iterable, *, backend: Optional[str] = None,
         iters: int = 3, timer: Optional[Callable] = None,
         force: bool = False, save: bool = True) -> Dict[str, Dict[str, Any]]:
    """Sweep op ``name``'s declared tunable candidates over example
    calls and cache the fastest config per shape bucket.

    ``args_sets``: iterable of positional-arg tuples (concrete arrays —
    the sweep actually executes the op).  ``timer(thunk, iters)``
    overrides the wall-clock measurement (tests inject a deterministic
    one).  Already-tuned buckets are returned from cache unless
    ``force``.  The declared-default combo is always swept FIRST — even
    when it is absent from the candidate grid — and a challenger must
    strictly beat it, so ties and near-ties keep the default and tuning
    can never regress below the pinned behaviour (the
    ``tuned_vs_pinned_speedup < 1`` failure mode); a winner is
    deterministic for a fixed timer.  Returns ``{shape_bucket: winning
    params}`` and, when ``save`` and $REPRO_KERNEL_TUNE_CACHE is set,
    persists the cache file.
    """
    _ensure_registered()
    if name not in _REGISTRY:
        raise KeyError(f"kernel op {name!r} not registered; known: "
                       f"{sorted(_REGISTRY)}")
    spec = _TUNABLES.get(name, {})
    be = resolve_backend(backend)
    impl = _REGISTRY[name][be]
    timer = timer or _default_timer
    out: Dict[str, Dict[str, Any]] = {}
    params_names = list(spec)
    combos = [dict(zip(params_names, values))
              for values in itertools.product(
                  *(spec[p].candidates for p in params_names))] or [{}]
    # The default combo leads the sweep (deduped from the grid): the
    # strict `<` comparison below then keeps it on any tie-or-loss, so
    # a tuned config is never slower than the declared default.
    defaults = {p: spec[p].default for p in params_names}
    if params_names:
        combos = [defaults] + [c for c in combos if c != defaults]
    for args in args_sets:
        if not isinstance(args, tuple):
            args = (args,)
        bucket = shape_bucket(*args)
        key = (name, be, bucket)
        if not force and key in _TUNED:
            out[bucket] = dict(_TUNED[key])
            continue
        best: Optional[Tuple[float, Dict[str, Any]]] = None
        for combo in combos:
            try:
                t = timer(lambda: impl(*args, **combo), iters)
            except Exception:           # combo invalid for this shape
                continue
            if best is None or t < best[0]:
                best = (t, combo)
        if best is None:
            raise ValueError(f"no tunable candidate of {name!r} ran for "
                             f"bucket {bucket!r}")
        _TUNED[key] = dict(best[1])
        out[bucket] = dict(best[1])
    if save:
        save_tune_cache()
    return out


__all__ = ["BACKENDS", "ENV_VAR", "TUNE_CACHE_ENV", "Tunable",
           "clear_tune_cache", "dispatch", "get_default_backend",
           "get_impl", "op_tunables", "register_op", "registered_ops",
           "resolve_backend", "save_tune_cache", "set_default_backend",
           "shape_bucket", "tune", "tuned_params", "use_backend"]
