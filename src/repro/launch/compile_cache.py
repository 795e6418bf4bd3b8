"""JAX's persistent compilation cache at a fixed path, plus compile
accounting.

``enable_compile_cache()`` is called from the entry points' ``main()``
(``repro.launch.train``, ``examples/serve_decode.py``, ``chip_smoke.py``),
never at import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here; otherwise the cache goes to ``.jax_cache/``
at the checkout root. The path is part of the cache key, so it is fixed:
no temporary, per-process or per-run directory.

``compile_stats()`` reads JAX's own monitoring events: seconds spent
lowering and compiling; backend compiles per function name; and
persistent-cache hits and misses. Compile time is kept apart from run time
this way, and a loop can assert that it compiled nothing new.
"""
from __future__ import annotations

import collections
import os
from pathlib import Path

import jax

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]

_COMPILE = "/jax/core/compile/backend_compile_duration"
# lowering + backend compile of each top-level jit (tracing is left out:
# nested jits trace inside their caller, so its durations overlap)
_STAGES = (_COMPILE, "/jax/core/compile/jaxpr_to_mlir_module_duration")
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"

_stats = {"compile_s": 0.0, "compiles": 0, "cache_hits": 0,
          "cache_misses": 0}
_by_fun: collections.Counter = collections.Counter()
_listening = False


def enable_compile_cache() -> str:
    """Turn on the persistent cache; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _on_duration(event: str, duration: float, **kw) -> None:
    if event in _STAGES:
        _stats["compile_s"] += duration
    if event == _COMPILE:
        _stats["compiles"] += 1
        _by_fun[kw.get("fun_name", "?")] += 1


def _on_event(event: str, **kw) -> None:
    if event == _HIT:
        _stats["cache_hits"] += 1
    elif event == _MISS:
        _stats["cache_misses"] += 1


def compile_stats() -> dict:
    """Totals since the first call (which starts the listening): compile
    seconds, backend compiles, cache hits/misses, and ``by_fun`` — backend
    compiles per jitted function name."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return {**_stats, "by_fun": dict(_by_fun)}
