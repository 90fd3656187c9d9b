"""JAX's persistent compilation cache, and the clock that reads it.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.train``, ``repro.launch.serve``) calls :func:`enable` from its
``main``, before anything compiles, so that a second process with the same
shapes loads its executables from disk instead of compiling them again.

:class:`CompileClock` reports, for a block of code, the seconds spent
tracing, lowering and compiling (or loading from the cache) and how many
executables the persistent cache supplied.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax
import jax.monitoring

#: fixed inside the checkout: a cache directory that moves between runs is
#: never found again, so no temporary name, pid or timestamp goes into it
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

#: JAX's duration events for the three stages of a compile
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set.  Otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout.  Every executable is cached, however quick
    its compile: a replay scan compiles in under JAX's default threshold of
    one second on a TPU, and a cold process would compile it again."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


class CompileClock:
    """Context manager: compile seconds and persistent-cache hits inside it.

    ``seconds`` sums JAX's own trace, lowering and backend-compile
    durations (a cache hit counts its load time under the last);
    ``cache_hits`` counts executables loaded from the persistent cache."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
