"""Run one cell of `BENCHMARK.json` and build its result line.

Everything that belongs to one cell is found by name: the configuration's
file from `configs[].file`, the traffic mix at `traffic/<traffic>.json`,
the driver that the mix names at `drivers/<driver>.py`, and each per-layer
metric's reader at `metrics/<metric>.py`. A later cell, mix or metric is
added as files and entries; nothing here changes for it.

A driver module has `workload(deployment, traffic, seed, platform=...)`,
which does the whole set-up, and the object it returns has
`window(seconds, traced=...)` (the end-to-end numbers), `seams(records)`
(a context that records the traced window's calls) and `check()`.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
import types
from pathlib import Path

import devtrace

HERE = Path(__file__).resolve().parent


def load_module(path: Path) -> types.ModuleType:
    """Import a file by its path (names may hold '.' and '-')."""
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def peak_for(kind: str) -> dict:
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def memory_peak_bytes(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run_cell(spec: dict, name: str, *, seed: int, seconds: float,
             trace: bool, root: Path, t0: float, platform: str | None,
             meter=None, overrides: dict | None = None,
             hooks: dict | None = None) -> dict:
    """Set up, measure and check one cell; return its result line.

    `platform` is what the GF steps' results must live on (None skips
    that look, for tests off the chip). `overrides` replaces keys of the
    deployment and the traffic mix, for tests at a size a CPU holds;
    `hooks` go to the driver (the control's stand-in for the program).
    """
    import jax

    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])
    deployment = json.loads((root / config["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    over = overrides or {}
    deployment.update(over.get("deployment", {}))
    traffic.update(over.get("traffic", {}))
    driver = load_module(HERE / "drivers" / f"{traffic['driver']}.py")
    devices = jax.devices()[:cell["chips"]]
    kind = devices[0].device_kind
    peak = peak_for(kind) if platform else None

    wl = driver.workload(deployment, traffic, seed, platform=platform,
                         **(hooks or {}))
    setup_s = time.perf_counter() - t0
    before = meter.snapshot() if meter else None
    calls: list[dict] = []
    reduced = None
    if trace:
        with wl.seams(calls):
            e2e, events = devtrace.capture(
                lambda: wl.window(seconds, traced=True))
        reduced = devtrace.reduce_events(events)
    else:
        e2e = wl.window(seconds)
    window_compiles = (meter.snapshot()[1] - before[1]) if meter else 0
    mem = memory_peak_bytes(devices)
    checks, attempted, failed = wl.check()
    correct = all(value <= limit for _, value, limit in checks)

    metrics: dict[str, dict] = {}
    if trace:
        ctx = types.SimpleNamespace(
            calls=calls, trace=reduced, e2e=e2e,
            lost_bytes=getattr(wl, "lost_bytes", 0),
            peak=peak)
        for m in spec["per_layer"]:
            if not applies(m, name):
                continue
            value = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e["setup_s"] = setup_s
        for m in spec["end_to_end"]:
            if applies(m, name):
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = {"device_ops": reduced.top_ops,
                            "idle_gaps": reduced.idle_by_host}
    info = {"setup_s": setup_s,
            "setup_compiles": before[1] if meter else 0,
            "setup_cache_hits": before[2] if meter else 0,
            "window_compiles": window_compiles,
            **{k: v for k, v in e2e.items() if k not in metrics}}
    print(f"run: {name} seed={seed} " + " ".join(
        f"{k}={v}" for k, v in info.items()), file=sys.stderr)
    out["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in checks}
    for k, v, lim in checks:
        print(f"check {k} {v} limit {lim}", file=sys.stderr)
    return out
