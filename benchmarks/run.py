"""Benchmark driver — one module per paper table/figure.

Prints `name,us_per_call,derived` CSV. us_per_call is the mean planning /
algorithm wall-time per repair (the paper's Fig. 8 overhead axis); derived
carries each figure's headline metric with the paper's claimed number for
comparison. Roofline terms for the LM cells come from launch/dryrun.py
(see EXPERIMENTS.md), not from this driver.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from pathlib import Path

    from repro.compile_cache import use_compile_cache

    use_compile_cache(Path(__file__).resolve().parent.parent)
    from benchmarks import (bench_ablation, bench_aliyun, bench_dataplane,
                            bench_fig8, bench_fig9, bench_fig10, bench_fig11,
                            bench_kernels, bench_sweep, bench_table2)
    modules = [
        ("table2", bench_table2),
        ("fig8", bench_fig8),
        ("fig9", bench_fig9),
        ("fig10", bench_fig10),
        ("fig11", bench_fig11),
        ("aliyun", bench_aliyun),
        ("kernels", bench_kernels),
        ("ablation", bench_ablation),
        ("sweep", bench_sweep),
        ("dataplane", bench_dataplane),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    for name, mod in modules:
        if only and only != name:
            continue
        t0 = time.time()
        for row in mod.run():
            print(row.csv())
        print(f"# {name} finished in {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
