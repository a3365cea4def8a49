"""Named host spans with per-name totals, recorded only under the profiler.

`span(name)` wraps a block of host code. While a JAX profiler trace runs
(`jax.profiler.trace`, or `start_trace` .. `stop_trace`) the block is a
`jax.profiler.TraceAnnotation`, so it lands in the trace's `.xplane.pb`
beside the device ops, and its time is added to `name`'s totals: the
number of times it closed, its total time and its self time (the total
less the time of the spans that closed inside it). Outside a trace a
span is a shared null context and costs one `is_enabled()` check: the
profiler being on is the only switch.

`count(key, n)` adds `n` to `key` on the innermost open span's totals,
and shows the running sum as a stat of that span's trace event.

`totals()` is a copy of everything recorded since the process started:
`{name: {"count": int, "total_s": float, "self_s": float, **counters}}`.
Read it after the traced block; the difference between two readings is
what ran in between.
"""
from __future__ import annotations

import contextlib
import threading
import time

from jax.profiler import TraceAnnotation


class _Open(threading.local):
    """This thread's open spans, innermost last."""

    def __init__(self):
        self.stack: list[_Span] = []


_NULL = contextlib.nullcontext()
_open = _Open()
_lock = threading.Lock()
_totals: dict[str, dict[str, int]] = {}


class _Span:
    __slots__ = ("name", "annotation", "start_ns", "child_ns", "counters")

    def __init__(self, name: str):
        self.name = name
        self.annotation = TraceAnnotation(name)
        self.child_ns = 0
        self.counters: dict[str, int] = {}

    def __enter__(self):
        self.annotation.__enter__()
        _open.stack.append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        elapsed = time.perf_counter_ns() - self.start_ns
        stack = _open.stack
        stack.pop()
        if stack:
            stack[-1].child_ns += elapsed
        with _lock:
            entry = _totals.setdefault(
                self.name, {"count": 0, "total_ns": 0, "self_ns": 0})
            entry["count"] += 1
            entry["total_ns"] += elapsed
            entry["self_ns"] += elapsed - self.child_ns
            for key, n in self.counters.items():
                entry[key] = entry.get(key, 0) + n
        return self.annotation.__exit__(*exc)


def span(name: str):
    """Context manager: a recorded span while the profiler traces, else
    a shared null context."""
    if not TraceAnnotation.is_enabled():
        return _NULL
    return _Span(name)


def count(key: str, n: int) -> None:
    """Add `n` to `key` on the innermost open span, if one is recording."""
    if not _open.stack:
        return
    top = _open.stack[-1]
    top.counters[key] = total = top.counters.get(key, 0) + int(n)
    top.annotation.set_metadata(**{key: total})


def totals() -> dict[str, dict]:
    """Per-span totals recorded so far; times in seconds."""
    with _lock:
        return {name: {"count": e["count"], "total_s": e["total_ns"] * 1e-9,
                       "self_s": e["self_ns"] * 1e-9,
                       **{k: v for k, v in e.items()
                          if k not in ("count", "total_ns", "self_ns")}}
                for name, e in _totals.items()}
