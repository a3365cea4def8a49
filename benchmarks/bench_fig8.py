"""Paper Fig. 8: fraction of repair time spent on coding + algorithm
(everything except network transmission). Paper: ~3% — the pruned DFS is
cheap, so BMFRepair scales to large networks.

The simulation half is a declarative `GridSuite` (code x chunk, one seeded
trial each, matching the legacy seed) run by one sweep invocation; the
coding half times `ops.rs_reconstruct` per cell: the compiled GF(256)
kernels on TPU, the numpy references elsewhere.
"""
import time

import numpy as np

from benchmarks.common import BENCH_EXECUTOR, Row, mininet_scenario
from repro.ec.rs import RSCode
from repro.kernels import ops
from repro.sim.suite import GridSuite
from repro.sim.sweep import run_sweep

CODES = [(4, 2), (6, 3), (7, 4)]
CHUNKS_MB = [8, 32]


def fig8_suite() -> GridSuite:
    return GridSuite(
        "fig8",
        axes={"code": CODES, "chunk_mb": CHUNKS_MB},
        build=lambda p, seed: mininet_scenario(
            *p["code"], (0,), chunk_mb=p["chunk_mb"], seed=seed),
        trials=1,
        schemes=("bmf",),
        base_seed=3,
    )


def run() -> list[Row]:
    rows = []
    rng = np.random.default_rng(0)
    sweep = run_sweep(fig8_suite(), executor=BENCH_EXECUTOR)
    groups = sweep.group_by("code", "chunk_mb")
    for (n, k) in CODES:
        for chunk in CHUNKS_MB:
            r = groups[((n, k), chunk)].cases[0].results["bmf"]
            # coding cost: premultiply k chunks + k-1 XOR merges on
            # MB-sized buffers, by what `ops` runs on this platform (the
            # compiled kernels on TPU, the numpy references elsewhere)
            code = RSCode(n, k)
            data = rng.integers(0, 256, size=(k, chunk << 20),
                                dtype=np.uint8)
            coeff = code.repair_coeffs((0,), tuple(range(1, k + 1)))
            fn = lambda: np.asarray(ops.rs_reconstruct(coeff, data))
            fn()                                      # compile
            t0 = time.perf_counter()
            fn()
            coding_s = time.perf_counter() - t0
            plan_frac = 100 * r.planning_time / (r.total_time + r.planning_time)
            overhead = r.planning_time + coding_s
            frac = 100 * overhead / (r.total_time + overhead)
            rows.append(Row(
                f"fig8/rs{n}{k}/chunk{chunk}MB",
                r.planning_time * 1e6,
                f"plan_frac={plan_frac:.2f}% code={coding_s:.2f}s "
                f"transfer={r.total_time:.2f}s total_overhead={frac:.1f}% "
                f"(paper ~3%; off TPU the coding is the numpy reference — "
                f"ISA-L/TPU-grade GF kernels push this to the paper's "
                f"level)",
            ))
    return rows
