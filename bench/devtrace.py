"""Profiler trace of a window, and its reduction to numbers.

`capture` runs a function under JAX's profiler, with the Python tracer
off, inside a host span named `bench.window`, and reads the `.xplane.pb`
it wrote with `load_events`. That keeps two kinds of events, on the one
clock the profiler gives host and device:

* device ops: every event on the "XLA Ops" line of each `/device:TPU:<i>`
  plane, and the programs they ran in, from its "XLA Modules" line;
* host spans: every event whose name starts with "bench.", which the
  benchmark writes with `jax.profiler.TraceAnnotation` around its calls
  into the program.

`reduce_events` works on those lists alone, so a small recorded list
tests it (`bench/tests/`).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import tempfile

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"


def capture(fn):
    """(fn's result, events) with the profiler on around `fn`."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # no event per Python call
    opts.host_tracer_level = 1         # annotations, not runtime internals
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                out = fn()
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        return out, load_events(paths[0])


def load_events(path: str) -> dict:
    """{"devices": {plane: [[op, start_ns, end_ns], ...]},
    "modules": {plane: [[program, start_ns, end_ns], ...]},
    "spans": [[name, start_ns, end_ns], ...]} from an xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out: dict = {"devices": {}, "modules": {}, "spans": []}
    for plane in pd.planes:
        on_device = bool(_DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if on_device and line.name in (OPS_LINE, MODULES_LINE):
                key = "devices" if line.name == OPS_LINE else "modules"
                out[key][plane.name] = [
                    [e.name, e.start_ns, e.start_ns + e.duration_ns]
                    for e in line.events]
            elif not on_device:
                out["spans"] += [
                    [e.name, e.start_ns, e.start_ns + e.duration_ns]
                    for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return out


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Sorted, merged intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Cover:
    """Length of [lo, hi] that merged, sorted intervals cover, for many
    queries over one list, by bisection."""

    def __init__(self, merged):
        self.starts = [s for s, _ in merged]
        self.merged = merged
        self.prefix = [0.0]
        for s, e in merged:
            self.prefix.append(self.prefix[-1] + (e - s))

    def __call__(self, lo: float, hi: float) -> float:
        i = max(bisect.bisect_right(self.starts, lo) - 1, 0)
        j = bisect.bisect_left(self.starts, hi)
        if j <= i:
            return 0.0
        total = self.prefix[j] - self.prefix[i]
        s, e = self.merged[i]
        total -= min(e, lo) - s if lo > s else 0.0
        s, e = self.merged[j - 1]
        total -= e - max(s, hi) if hi < e else 0.0
        return total


def program(name: str) -> str:
    """A program's name without its fingerprint: 'jit_f(123)' -> 'jit_f'."""
    return re.sub(r"\(\d+\)$", "", name)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                  # device time busy, mean over devices
    span_device_s: dict[str, float]  # host span name -> device time in it
    top_ops: list[list]            # [[program, seconds], ...], 10 longest
    idle_by_host: list[list]       # [[what the host did, seconds], ...]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce_events(ev: dict) -> Reduced:
    """Window, busy time, device time inside each named host span, the
    programs that took most device time and the idle time by the
    innermost host span around it."""
    windows = [(s, e) for n, s, e in ev["spans"] if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    lo, hi = windows[0]
    if not ev["devices"]:
        raise ValueError("the trace holds no TPU device plane")
    per_dev = {plane: union([(s, e) for _, s, e in ops], lo, hi)
               for plane, ops in sorted(ev["devices"].items())}
    covers = [Cover(m) for m in per_dev.values()]
    busy = sum(c(lo, hi) for c in covers)
    kinds: dict[str, float] = {}
    for plane, progs in ev.get("modules", {}).items():
        for name, s, e in progs:
            k = program(name)
            kinds[k] = kinds.get(k, 0.0) + max(0.0, min(e, hi) - max(s, lo))
    ndev = len(per_dev)
    inner = [(n, s, e) for n, s, e in ev["spans"] if n != WINDOW]
    span_dev: dict[str, float] = {}
    for n, s, e in inner:
        t = sum(c(s, e) for c in covers) / ndev
        span_dev[n] = span_dev.get(n, 0.0) + t
    # idle gaps of the first device, named by the innermost span around them
    idle: dict[str, float] = {}
    first = per_dev[min(per_dev)]
    edges = [lo] + [x for se in first for x in se] + [hi]
    by_start = sorted(inner, key=lambda x: x[1])
    starts = [s for _, s, _ in by_start]
    longest = max((e - s for _, s, e in inner), default=0.0)
    for gs, ge in zip(edges[::2], edges[1::2]):
        if ge <= gs:
            continue
        near = by_start[bisect.bisect_left(starts, gs - longest):
                        bisect.bisect_left(starts, ge)]
        near = [(n, s, e) for n, s, e in near if e > gs]
        cuts = sorted({gs, ge, *(x for _, s, e in near for x in (s, e)
                                 if gs < x < ge)})
        for a, z in zip(cuts, cuts[1:]):
            mid = (a + z) / 2
            around = [(e - s, n) for n, s, e in near if s <= mid < e]
            label = (min(around)[1].split("#")[0] if around
                     else "outside spans")
            idle[label] = idle.get(label, 0.0) + (z - a)
    ns = 1e-9
    return Reduced(
        window_s=(hi - lo) * ns,
        busy_s=busy / ndev * ns,
        span_device_s={n: t * ns for n, t in span_dev.items()},
        top_ops=[[k, t * ns / ndev] for k, t in
                 sorted(kinds.items(), key=lambda kv: -kv[1])[:10]],
        idle_by_host=[[k, t * ns] for k, t in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:10]],
    )
