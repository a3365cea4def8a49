"""Seam wrappers and JAX's compile meter, kept with the benchmark.

The benchmark observes the program only at its public seams: it swaps a
module attribute for a wrapper for the length of a block, and reads JAX's
own monitoring events. Nothing here changes what the wrapped call does.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def patched(obj, name: str, wrapper):
    """Replace `obj.name` by `wrapper(original)` for the block."""
    orig = getattr(obj, name)
    setattr(obj, name, wrapper(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


class CompileMeter:
    """Seconds JAX spent in backend compiles (cache retrievals included)
    and persistent-cache hits, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.hits
