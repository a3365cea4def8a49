"""The control of a repair cell: the plain reference in the program's place,
with one guarantee of the deployment broken.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

The reference (`reference_repair`) rebuilds each lost block from its plan's
helpers with `gfref` on the host. The control drops the GF(256) repair
coefficients and XORs the helpers (`xor_only=True`), the shortcut that a
parity-only (RAID-5 or LRC local group) repair would take: it breaks the
guarantee that every lost block comes back byte for byte. Each seed runs
a whole cell (set-up, a window at the cell's size, the check) with the
control in place of `execute_plans_batch`, and prints the numbers compared
beside their limits; `correct` has to come out false on every seed. The
benchmark's own runs never run this. Needs no chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def reference_repair(wl, b: int, *, xor_only: bool = False):
    """Batch b rebuilt by the reference: each job's lost block is
    sum_i c_i * helper_i over its plan's helper blocks."""
    import gfref

    recon = []
    for s in wl.batches[b]:
        cw = wl.codewords[s]
        out = {}
        for job in wl.plans[s].jobs:
            helpers = list(job.helpers)
            coeffs = (np.ones(len(helpers), np.uint8) if xor_only else
                      gfref.repair_row(wl.n, wl.k, job.failed_node, helpers))
            out[job.job_id] = gfref.combine(coeffs, cw[helpers])
        recon.append(out)
    moved = [wl.expected_moved[s] for s in wl.batches[b]]
    return recon, np.asarray(moved, dtype=np.int64)


def control_repair(wl, b: int):
    return reference_repair(wl, b, xor_only=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    import harness

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(
            spec, args.workload, seed=seed, seconds=args.seconds,
            trace=False, root=ROOT, t0=time.perf_counter(), platform=None,
            hooks={"repair": control_repair})
        rows.append({"seed": seed, "correct": out["correct"],
                     "checks": out["checks"]})
        print(json.dumps(rows[-1]))
    print(json.dumps({"control": args.workload,
                      "all_incorrect": not any(r["correct"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
