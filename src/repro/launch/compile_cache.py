"""JAX compilation-cache placement and compile counting for the entry points.

:func:`enable_compile_cache` is called at the start of each entry point's
``main()`` (``chip_smoke.py``, ``repro.launch.train``, ``repro.launch.infer``
and the benches through ``benchmarks/common.py``), never at import, so tests
never turn the persistent cache on.

- Where ``JAX_COMPILATION_CACHE_DIR`` is set, it sets nothing: JAX reads that
  variable itself.
- Otherwise it points ``jax_compilation_cache_dir`` at ``<checkout>/.jax_cache``.
  The path is part of each entry's key, so it is fixed: never built from a
  temp name, a pid or the time.

:class:`CompileCounter` counts the backend compiles and persistent-cache hits
inside a window, through ``jax.monitoring``: a warmed-up timed window should
show none of either.
"""
from __future__ import annotations

import os
import threading

CHECKOUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..")
)
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # keep every program, not only those over the 1 s default: a CPU run
    # compiles in well under a second and would otherwise cache nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return CACHE_DIR


class CompileCounter:
    """``with CompileCounter() as cc:`` counts, inside the block, the
    programs that were not in memory (``cc.loads``, taking ``cc.compile_s``)
    and how many of them the persistent cache supplied
    (``cc.persistent_hits``); the rest were compiled (``cc.compiles``)."""

    def __init__(self):
        self.loads = 0
        self.compile_s = 0.0
        self.persistent_hits = 0
        self._lock = threading.Lock()

    def _on_duration(self, event: str, secs: float, **_) -> None:
        # recorded around compile-or-load, so a cache hit fires it too
        if event == _BACKEND_COMPILE:
            with self._lock:
                self.loads += 1
                self.compile_s += secs

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            with self._lock:
                self.persistent_hits += 1

    def __enter__(self) -> "CompileCounter":
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)

    @property
    def compiles(self) -> int:
        return self.loads - self.persistent_hits
