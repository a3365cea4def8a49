"""JAX executor quickstart: the jit device steppers end to end.

1. runs one Monte-Carlo suite under the serial (object) engine and
   `executor="jax"` (the jit `lax.while_loop`/`scan` steppers of
   `repro.core.engine.jax_stepper`) and checks they agree,
2. times the numpy vectorized executor against the jax executor on an
   execution-bound trace-frozen suite — the jax rows include compile
   time on the first run; the point of the backend is that the same
   compiled programs run unchanged on an accelerator.

    PYTHONPATH=src python examples/jax_sweep.py
"""
import time

import jax

from repro.sim import MonteCarloSuite, SampleSpace, TraceSuite, run_sweep


def jax_parity():
    space = SampleSpace(
        codes=((6, 3), (7, 4)), cluster_sizes=(10,), chunk_mb=(8.0,),
        regimes=("hot2s",), failure_patterns=("single", "double"),
    )
    suite = MonteCarloSuite("jaxdemo", 16, space, base_seed=3)
    serial = run_sweep(suite, executor="serial")
    jaxed = run_sweep(suite, executor="jax")
    worst = max(
        abs(cs.results[s].total_time - cj.results[s].total_time)
        / cs.results[s].total_time
        for cs, cj in zip(serial.cases, jaxed.cases) for s in cs.results
    )
    print(f"16-case sweep, serial vs executor='jax': max relative "
          f"difference = {worst:.2e}")
    print(jaxed.summary_table())
    return worst


def jax_throughput():
    """Execution-bound suite (star fan-in, large chunks, frozen traces):
    where event stepping, not planning, is the bottleneck."""
    space = SampleSpace(
        codes=((14, 10),), cluster_sizes=(14,), chunk_mb=(512.0,),
        regimes=("hot2s",), failure_patterns=("single",),
    )
    live = MonteCarloSuite("stress", 24, space,
                           schemes=("traditional", "ppr"), base_seed=17)
    frozen = TraceSuite.freeze(live, num_epochs=256)
    timings = {}
    for executor in ("vectorized", "jax"):
        run_sweep(frozen, executor=executor)       # warm (compile for jax)
        t0 = time.perf_counter()
        run_sweep(frozen, executor=executor)
        timings[executor] = time.perf_counter() - t0
    print(f"\nexecution-bound 24-case suite (warm): "
          f"numpy vectorized {timings['vectorized'] * 1e3:.0f}ms, "
          f"jax {timings['jax'] * 1e3:.0f}ms on "
          f"{jax.devices()[0].platform}")


def main():
    worst = jax_parity()
    assert worst < 1e-6, "jax executor must match the reference engine"
    jax_throughput()


if __name__ == "__main__":
    main()
