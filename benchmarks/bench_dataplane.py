"""Byte data-plane throughput benchmark -> `BENCH_dataplane.json`.

Measures what the repair system ultimately does: *move and recombine
bytes*. A batch of stripes is placed over the Mininet-sized cluster with
`repro.ec.stripe.place_stripes` (rotated RAID-5-style placement), each
stripe gets a real simulator-produced repair plan (ppr/bmf alternating,
relabeled through its placement), and the same byte workload runs twice:

* **serial** — `repro.core.executor.execute_plan` per stripe, the
  per-transfer dict walk with one kernel/ref call per chunk (the
  pre-batched data plane, kept as the oracle);
* **batched** — `repro.core.engine.dataplane.execute_plans_batch`, the
  whole batch lowered to `(B, slots, nbytes)` buffer tensors and executed
  as gather / GF(256)-premultiply / segment-XOR array steps.

Two paths each: the **ref** (non-interpret) path — numpy oracles batched
vs per-chunk jnp calls, the honest CPU-throughput number CI gates at
>= 3x batched-vs-serial on a >= 64-stripe batch — and the **kernel**
path, which runs the Pallas kernels (`gf256_scale_rows` /
`xor_reduce_groups_words` grids vs one `pallas_call` per chunk). Off TPU
the kernels run in the interpreter on a small slice (`*_kernel_interpret`
rows): a correctness path, not a performance proxy, so that split is
informational. On TPU the batched entry points run the compiled kernels
only, so the ref split is skipped (its row says why) and the kernel split
runs compiled at the full batch and chunk size (`*_kernel` rows).

`--small` (or REPRO_BENCH_DATAPLANE_SMALL=1) shrinks chunk size for CI
but keeps the 64-stripe batch the acceptance gate is defined over.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

SMALL = ("--small" in sys.argv
         or os.environ.get("REPRO_BENCH_DATAPLANE_SMALL") == "1")
BATCH = 64 if SMALL else 128
NBYTES = 4096 if SMALL else 16384
KERNEL_BATCH = 4 if SMALL else 8
KERNEL_NBYTES = 256
REPEATS = int(os.environ.get("REPRO_BENCH_DATAPLANE_REPEATS", "3"))
OUT_PATH = "BENCH_dataplane.json"
CLUSTER = 14
CODE_NK = (6, 3)
SCHEMES = ("ppr", "bmf")


def _build_batch(batch: int, nbytes: int):
    """`batch` placed stripes, each with its own executed repair plan."""
    from benchmarks.common import mininet_scenario
    from repro.core.engine.arrays import compile_plan, relabel_plan_nodes
    from repro.core.simulator import run_scheme
    from repro.ec.rs import RSCode
    from repro.ec.stripe import place_stripes, split_blob

    n, k = CODE_NK
    code = RSCode(n, k)
    rng = np.random.default_rng(2026)
    blob = rng.integers(0, 256, size=batch * k * nbytes, dtype=np.uint8)
    datas = split_blob(blob, k, nbytes)
    stripes = place_stripes(batch, code, CLUSTER)
    pas, plans, cws, bmaps = [], [], [], []
    for b in range(batch):
        scheme = SCHEMES[b % len(SCHEMES)]
        sc = mininet_scenario(n, k, (b % n,), chunk_mb=4.0, seed=b)
        plan = run_scheme(sc, scheme).plan
        pa = relabel_plan_nodes(compile_plan(plan),
                                stripes[b].perm(CLUSTER))
        pas.append(pa)
        cws.append(code.encode(datas[b]))
        bmaps.append(stripes[b].block_map(CLUSTER))
    return code, pas, cws, bmaps


def _time_serial(code, pas, cws, bmaps, *, use_kernel):
    from repro.core.engine.arrays import decompile
    from repro.core.executor import execute_plan

    plans = [decompile(pa) for pa in pas]
    best, moved = float("inf"), 0
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        total = 0
        for plan, cw, bmap in zip(plans, cws, bmaps):
            ex = execute_plan(plan, code, cw, use_kernel=use_kernel,
                              block_of=bmap)
            assert ex.verified, "serial data plane failed verification"
            total += ex.bytes_moved
        best = min(best, time.perf_counter() - t0)
        moved = total
    return best, moved


def _time_batched(code, pas, cws, bmaps, *, use_kernel):
    from repro.core.engine.dataplane import execute_plans_batch

    best, moved = float("inf"), 0
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        res = execute_plans_batch(pas, code, cws, block_of=bmaps,
                                  use_kernel=use_kernel)
        assert res.all_verified, "batched data plane failed verification"
        best = min(best, time.perf_counter() - t0)
        moved = int(res.bytes_moved.sum())
    return best, moved


def _entry(seconds: float, moved: int, stripes: int,
           serial_s: float | None = None) -> dict:
    out = {
        "seconds": round(seconds, 4),
        "mb_per_sec": round(moved / seconds / 1e6, 2),
        "bytes_moved": moved,
        "stripes": stripes,
    }
    if serial_s is not None:
        out["speedup_vs_serial"] = round(serial_s / seconds, 2)
    return out


def run():
    import jax

    from benchmarks.common import Row

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    report: dict = {
        "batch": BATCH, "nbytes": NBYTES, "cluster": CLUSTER,
        "code": CODE_NK, "schemes": list(SCHEMES),
        "platform": dev.platform, "device_kind": dev.device_kind,
        "dataplane": {},
    }
    dp = report["dataplane"]
    rows = []

    if on_tpu:
        rows.append(Row("dataplane/ref", 0.0,
                        "skipped: on TPU the batched entry points run the "
                        "compiled kernels only"))
    else:
        code, pas, cws, bmaps = _build_batch(BATCH, NBYTES)
        ser_s, moved = _time_serial(code, pas, cws, bmaps, use_kernel=False)
        dp["serial_ref"] = _entry(ser_s, moved, BATCH)
        bat_s, moved_b = _time_batched(code, pas, cws, bmaps,
                                       use_kernel=False)
        assert moved_b == moved, \
            "serial/batched bytes_moved accounting diverged"
        dp["batched_ref"] = _entry(bat_s, moved_b, BATCH, ser_s)
        report["batched_ref_ge_3x"] = \
            dp["batched_ref"]["speedup_vs_serial"] >= 3.0

    # compiled kernels at the full size on TPU, an interpreted slice elsewhere
    kbatch, knbytes, tag = ((BATCH, NBYTES, "kernel") if on_tpu else
                            (KERNEL_BATCH, KERNEL_NBYTES, "kernel_interpret"))
    kcode, kpas, kcws, kbmaps = _build_batch(kbatch, knbytes)
    kser_s, kmoved = _time_serial(kcode, kpas, kcws, kbmaps,
                                  use_kernel=True)
    dp[f"serial_{tag}"] = _entry(kser_s, kmoved, kbatch)
    kbat_s, kmoved_b = _time_batched(kcode, kpas, kcws, kbmaps,
                                     use_kernel=True)
    assert kmoved_b == kmoved, "serial/batched bytes_moved accounting diverged"
    dp[f"batched_{tag}"] = _entry(kbat_s, kmoved_b, kbatch, kser_s)

    dp["verified"] = True   # every timed run asserted byte-exactness
    with open(OUT_PATH, "w") as f:
        json.dump(report, f, indent=2)

    where = f"platform={dev.platform} device_kind={dev.device_kind!r}"
    rows += [
        Row(f"dataplane/{name}", entry["seconds"] * 1e6 / entry["stripes"],
            f"mb_per_sec={entry['mb_per_sec']}"
            + (f" speedup_vs_serial={entry['speedup_vs_serial']}x"
               if "speedup_vs_serial" in entry else "") + f" {where}")
        for name, entry in dp.items() if isinstance(entry, dict)
    ]
    rows.append(Row("dataplane/json", 0.0, f"wrote {OUT_PATH}"))
    return rows


if __name__ == "__main__":
    from pathlib import Path

    from repro.compile_cache import use_compile_cache

    use_compile_cache(Path(__file__).resolve().parent.parent)
    for row in run():
        print(row.csv())
