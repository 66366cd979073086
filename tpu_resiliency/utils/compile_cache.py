"""One place that says where JAX's persistent compilation cache lives.

In-process restarts clear the in-memory caches (``jax.clear_caches()`` in the
abort ladder and the relayout rung) and every respawned worker starts cold,
so without a cache on disk each recovery pays the full compile of the train
step again.  Entry points call :func:`enable` before their first jit.

The directory is placed from outside with ``JAX_COMPILATION_CACHE_DIR`` —
JAX reads that variable itself, so when it is set nothing is set in code.
Otherwise it is one fixed path inside the checkout: the path is part of the
cache key's surroundings (a directory that moves never hits), so it is never
derived from ``tempfile``, a pid or a time.  The launcher exports
:func:`cache_dir` to every worker, which keeps a job's ranks on one cache.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def cache_dir() -> str:
    """The directory in force (does not import jax)."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def enable() -> str:
    """Turn the persistent cache on for this process; returns its directory.
    Call before the first jit."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


class CacheEvents:
    """Counts the persistent-cache hits and misses JAX reports from the
    moment it is built (``jax.monitoring`` events)."""

    def __init__(self):
        import jax

        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
