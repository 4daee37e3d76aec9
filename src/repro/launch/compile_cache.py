"""Where JAX's persistent compilation cache lives, and what compiled.

Entry points (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/kernel_bench.py``) call
:func:`setup_compile_cache` first thing in ``main``; importing this
module changes nothing.

A run finds what an earlier run compiled only if both use the same
directory: ``$JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it
itself, so nothing else is set), otherwise the cache sits at the fixed
``<repo>/.jax_cache`` — never a name built from a temporary directory,
a pid or the time.

:func:`setup_compile_cache` also installs, once, ``jax.monitoring``
listeners that keep one record per function name (see
:func:`compile_stats`): backend compiles and their seconds, jaxpr
trace seconds, and persistent-cache hits and misses.  JAX reports a
cache hit or miss inside the compile it belongs to, on the compiling
thread, before that compile's duration; each thread's hits and misses
wait there for the next compile the thread finishes.
"""
from __future__ import annotations

import copy
import os
import threading
from typing import Dict

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                "/jax/compilation_cache/cache_misses": "cache_misses"}

_lock = threading.Lock()
_installed = False
_stats: Dict[str, Dict[str, float]] = {}
_pending = threading.local()     # this thread's hits/misses, not yet
                                 # assigned to a compile


def _record(name: str) -> Dict[str, float]:
    return _stats.setdefault(name, {
        "compiles": 0, "compile_s": 0.0, "traces": 0, "trace_s": 0.0,
        "cache_hits": 0, "cache_misses": 0})


def _on_duration(event: str, secs: float, fun_name: str = "?",
                 **_) -> None:
    if event == BACKEND_COMPILE_EVENT:
        waiting = getattr(_pending, "counts", None)
        _pending.counts = None
        with _lock:
            r = _record(fun_name)
            r["compiles"] += 1
            r["compile_s"] += secs
            for k, n in (waiting or {}).items():
                r[k] += n
    elif event == TRACE_EVENT:
        # traced as ``f``, compiled as ``jit(f)``: one record for both
        with _lock:
            r = _record(f"jit({fun_name})")
            r["traces"] += 1
            r["trace_s"] += secs


def _on_event(event: str, **_) -> None:
    key = CACHE_EVENTS.get(event)
    if key is not None:
        counts = getattr(_pending, "counts", None) or {}
        counts[key] = counts.get(key, 0) + 1
        _pending.counts = counts


def _install_listeners() -> None:
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def compile_stats() -> Dict[str, Dict[str, float]]:
    """A copy of the records so far, by function name (``jit(f)``):
    ``compiles`` / ``compile_s`` (backend compiles, a persistent-cache
    hit included, and their seconds), ``traces`` / ``trace_s`` (jaxpr
    traces; a nested function's seconds lie inside its caller's), and
    ``cache_hits`` / ``cache_misses`` of the persistent cache.  Empty
    until :func:`setup_compile_cache` has run."""
    with _lock:
        return copy.deepcopy(_stats)


def setup_compile_cache() -> str:
    """Put the persistent compilation cache in place and start counting
    compiles; returns the cache's directory.  Call before the first
    compilation of the process."""
    _install_listeners()
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


__all__ = ["DEFAULT_DIR", "ENV_VAR", "compile_stats", "setup_compile_cache"]
