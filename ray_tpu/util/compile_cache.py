"""One persistent XLA compile cache for every process of a run.

The cache's path is part of its key, so it must not move between processes
or between runs: a directory named after a session, a pid or a time never
hits. Where ``JAX_COMPILATION_CACHE_DIR`` is set it is used as it stands
and no other path is set in code; otherwise the cache lives at one fixed
place inside the checkout. JAX reads the variable when it is imported, so
``configure`` runs before the process's first ``import jax``; worker
processes inherit the variable from the process that spawned them.
"""

from __future__ import annotations

import os
from typing import Dict

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def configure() -> str:
    """Point this process (and its children) at the cache; returns its path."""
    return os.environ.setdefault(ENV, DEFAULT_DIR)


class CompileCounter:
    """Counts this process's XLA compilations from ``jax.monitoring``: how
    many programs were asked for, how many of them the persistent cache
    answered, and the seconds spent compiling or fetching them."""

    def __init__(self):
        import jax.monitoring as mon

        self.programs = self.hits = 0
        self.seconds = 0.0
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def snapshot(self) -> Dict[str, float]:
        return {"programs": self.programs, "cache_hits": self.hits,
                "compiled": self.programs - self.hits,
                "compile_s": round(self.seconds, 3)}
