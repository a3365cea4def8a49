"""Sweep-executor throughput benchmark -> `BENCH_sweep.json`.

Times `run_sweep` under the serial, process, vectorized and jax
executors on fixed 60-case suites (all executors produce identical
results — only wall-clock differs) and writes
cases/sec plus speedups-vs-serial to `BENCH_sweep.json` in the working
directory, so the sweep-throughput trajectory is tracked per PR. The
`jax` rows time the jit device steppers (`repro.core.engine.jax_stepper`)
including compilation on the first repeat; on CPU they clear serial on
the trace-frozen suites (~3.5x on the execution-bound one, still below
the tuned numpy engine) but can land *under* serial on the tiny live
Table II suite, where jit compilation and per-round dispatch dominate a
sub-100ms sweep. The column exists to track the accelerator seam — the
same compiled programs run unchanged on TPU/GPU — not to claim a CPU
win.

Three suites, separating the two bottlenecks a sweep can have:

* ``table2_60`` — the paper's Table II Monte-Carlo suite (RS(7,4) double
  failures, hot churn). *Planner-bound*: most wall-clock is scheduling
  (m-PPR/random/MSRepair) plus bandwidth-epoch rng. Since the
  array-native planner layer landed (batched MSRepair scheduling, batched
  plan lowering/validation, in-stepper BMF replanning), the vectorized
  executor beats serial here too — the json records the planner/exec
  wall-clock split per executor so the remaining ceiling is visible.
* ``table2_60_trace`` — the same 60 scenarios with their bandwidth sample
  paths frozen to replayable traces (`TraceSuite.freeze`), removing the
  shared epoch-rng cost from the comparison. This is the regression-gated
  planner-bound suite (CI asserts its vectorized speedup).
* ``stress_60_trace`` — an *execution-bound* suite (RS(14,10) star +
  binomial-tree repair, 1 GB chunks, hot churn, frozen traces): tens of
  thousands of contention-resolution events and almost no planning. This
  is where executor throughput is actually the bottleneck, and where the
  batched engine's >= 5x-over-serial target is asserted.

Set REPRO_BENCH_SWEEP_CASES to shrink the suites (CI runs the small
variant) — the json records the case count used.
"""
from __future__ import annotations

import json
import os
import time

from benchmarks.bench_table2 import table2_suite
from benchmarks.common import Row
from repro.sim.suite import MonteCarloSuite, SampleSpace, TraceSuite
from repro.sim.sweep import run_sweep

CASES = int(os.environ.get("REPRO_BENCH_SWEEP_CASES", "60"))
REPEATS = int(os.environ.get("REPRO_BENCH_SWEEP_REPEATS", "3"))
OUT_PATH = "BENCH_sweep.json"


EXECUTORS = ("serial", "process", "vectorized", "jax")


def stress_suite(num_cases: int = CASES) -> TraceSuite:
    """Fixed execution-bound suite: fan-in heavy, event-dense, trace-frozen."""
    space = SampleSpace(
        codes=((14, 10),), cluster_sizes=(14,), chunk_mb=(1024.0,),
        regimes=("hot2s",), failure_patterns=("single",),
    )
    live = MonteCarloSuite("stress", num_cases, space,
                           schemes=("traditional", "ppr"), base_seed=17)
    return TraceSuite.freeze(live, num_epochs=256, name="stress_trace")


def _time_sweep(make_suite, executor: str) -> tuple[float, float]:
    """Best wall-clock of REPEATS runs plus the best run's planner
    wall-clock (summed `SimResult.planning_time` across cases/schemes —
    the batched engine charges each case its share of batch planning, so
    the totals are comparable across executors). Pool startup is timed
    too, so the process row honestly carries its spawn cost (or, below
    the spawn-amortization threshold, its serial fallback); repeats
    smooth cold-cache noise."""
    best, best_plan = float("inf"), 0.0
    for _ in range(REPEATS):
        suite = make_suite()
        t0 = time.perf_counter()
        sweep = run_sweep(suite, executor=executor)
        secs = time.perf_counter() - t0
        if secs < best:
            best = secs
            best_plan = sum(r.planning_time for c in sweep.cases
                            for r in c.results.values())
    return best, best_plan


def run() -> list[Row]:
    suites = {
        "table2_60": lambda: table2_suite(CASES),
        "table2_60_trace": lambda: TraceSuite.freeze(
            table2_suite(CASES), num_epochs=64),
        "stress_60_trace": stress_suite,
    }
    report: dict = {"cases": CASES, "suites": {}}
    rows: list[Row] = []
    for name, make in suites.items():
        entry: dict = {}
        serial_s = None
        for ex in EXECUTORS:
            secs, plan_s = _time_sweep(make, ex)
            entry[ex] = {
                "seconds": round(secs, 4),
                "cases_per_sec": round(CASES / secs, 2),
                # planner-time vs execution-time split: how much of the
                # sweep's wall-clock went to planning (schedulers + BMF
                # replanning) vs everything else (event stepping, glue)
                "planner_seconds": round(plan_s, 4),
                "exec_seconds": round(max(secs - plan_s, 0.0), 4),
                "planner_frac": round(plan_s / secs, 3),
            }
            if ex == "serial":
                serial_s = secs
            else:
                entry[ex]["speedup_vs_serial"] = round(serial_s / secs, 2)
            rows.append(Row(
                f"sweep/{name}/{ex}", secs * 1e6 / CASES,
                f"cases_per_sec={CASES / secs:.1f}"
                f" planner_frac={plan_s / secs:.2f}"
                + ("" if ex == "serial"
                   else f" speedup_vs_serial={serial_s / secs:.2f}x"),
            ))
        report["suites"][name] = entry
    vec = report["suites"]["stress_60_trace"]["vectorized"]
    report["vectorized_ge_5x_on_execution_bound"] = \
        vec["speedup_vs_serial"] >= 5.0
    # the array-native planner layer's headline: both planner-bound Table
    # II suites at >= 3x serial (aspirational bar from ISSUE 3; current
    # measurements land ~1.5-2x — the shared per-case scheduler + rng
    # floor caps the ratio, see docs/architecture.md "planner layer")
    report["vectorized_ge_3x_on_planner_bound"] = all(
        report["suites"][s]["vectorized"].get("speedup_vs_serial", 0) >= 3.0
        for s in ("table2_60", "table2_60_trace")
    )
    with open(OUT_PATH, "w") as f:
        json.dump(report, f, indent=2)
    rows.append(Row("sweep/json", 0.0, f"wrote {OUT_PATH}"))
    return rows
