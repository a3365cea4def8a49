"""Run the repair system's main path once on one TPU chip and check it.

    python chip_smoke.py

Two phases, each through the entry points a user calls:

* **sweep** — a trace-frozen Monte-Carlo suite of the paper's Table II
  shape (RS(6,3) and RS(14,10) on 14-node clusters, 2 s markov churn,
  single and double failures), every scheme of `ALL_SCHEMES` on every
  case, through `run_sweep(executor="jax")` (the jit device steppers)
  and again through `executor="serial"` (the object engine, the
  reference). Round counts and relay hops must be identical and
  `total_time` within `SWEEP_RTOL`; no batch may leave the device.
* **data plane** — the HDFS `RS-6-3-1024k` erasure-coding policy: 1 MiB
  cells, stripes placed by `place_stripes` over the 14-node cluster,
  one lost block each. Encoded through `ops.rs_encode`, repaired in one
  `execute_plans_batch` call with plans the sweep's schemes produced,
  byte-verified, and checked against the serial `execute_plan`.

Exits non-zero without a TPU, and when any check fails. The last line
of stdout is one JSON object naming the device; everything else comes
before it. JAX's persistent compilation cache lives at
`$JAX_COMPILATION_CACHE_DIR` when set, else at `.jax_cache/` beside
this file, so a second run reports cache hits and lower compile time.
"""
from __future__ import annotations

import collections
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

SEED = 0                     # seeds the sweep suite and the stripe payloads
SWEEP_CASES = 128
SWEEP_EPOCHS = 64
SWEEP_CHUNK_MB = 32.0
# The parity contract of tests/test_jax_engine.py. The device steppers
# run in float64 under jax.enable_x64; a deviation beyond this bound
# means the chip's float64 differs from the host's, and the run fails.
SWEEP_RTOL = 1e-6
DP_STRIPES = 64
DP_NBYTES = 1 << 20          # HDFS RS-6-3-1024k cell size
DP_CLUSTER = 14
DP_SERIAL_CHECKS = 3


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def patched(obj, name: str, wrapper):
    """Replace `obj.name` by `wrapper(original)` for the block."""
    orig = getattr(obj, name)
    setattr(obj, name, wrapper(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


def counting(counts: collections.Counter, key: str, on_result=None):
    """Wrapper factory for `patched`: count calls (and inspect results)."""
    def wrap(fn):
        def inner(*args, **kwargs):
            counts[key] += 1
            out = fn(*args, **kwargs)
            if on_result is not None:
                on_result(out)
            return out
        return inner
    return wrap


class CompileMeter:
    """Seconds JAX spent in backend compiles (cache retrievals included)
    and persistent-cache hits, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple[float, int]:
        return self.seconds, self.hits


def sweep_suite(num_cases: int, seed: int):
    from repro.core.simulator import ALL_SCHEMES
    from repro.sim.suite import MonteCarloSuite, SampleSpace, TraceSuite

    space = SampleSpace(codes=((6, 3), (14, 10)), cluster_sizes=(14,),
                        chunk_mb=(SWEEP_CHUNK_MB,), regimes=("hot2s",),
                        failure_patterns=("single", "double"))
    live = MonteCarloSuite("chip-smoke", num_cases, space,
                           schemes=ALL_SCHEMES, base_seed=seed)
    return TraceSuite.freeze(live, num_epochs=SWEEP_EPOCHS)


def sweep_phase(meter: CompileMeter, *, num_cases: int, seed: int):
    """Run the suite on the device steppers and on the serial reference;
    return the jax sweep (plans kept) for the data-plane phase."""
    from repro.core.engine import jax_stepper, vectorized
    from repro.core.simulator import ALL_SCHEMES
    from repro.sim.sweep import run_sweep

    suite = sweep_suite(num_cases, seed)
    counts: collections.Counter = collections.Counter()

    def declined(out):
        if out is None:
            counts["factory_declined"] += 1

    c0, h0 = meter.snapshot()
    tic = time.perf_counter()
    with contextlib.ExitStack() as stack:
        for obj, name, key, hook in (
            (jax_stepper, "make_round_engine", "round_engines", declined),
            (jax_stepper, "make_pipeline_engine", "pipeline_engines",
             declined),
            (jax_stepper.JaxRoundEngine, "execute_round", "device_rounds",
             None),
            (jax_stepper.JaxRoundEngine, "execute_rounds", "device_scans",
             None),
            (jax_stepper.JaxPipelineEngine, "execute", "device_pipelines",
             None),
            (vectorized, "execute_round_batch", "numpy_rounds", None),
            (vectorized, "execute_pipeline_batch", "numpy_pipelines", None),
            (vectorized, "run_scheme", "object_engine_rows", None),
        ):
            stack.enter_context(
                patched(obj, name, counting(counts, key, hook)))
        jx = run_sweep(suite, executor="jax", keep_plans=True)
    jax_s = time.perf_counter() - tic
    c1, h1 = meter.snapshot()

    tic = time.perf_counter()
    jx_warm = run_sweep(suite, executor="jax")
    warm_s = time.perf_counter() - tic
    c2, _ = meter.snapshot()

    tic = time.perf_counter()
    ref = run_sweep(suite, executor="serial")
    serial_s = time.perf_counter() - tic

    pairs = 0
    worst = 0.0
    schemes_seen: set[str] = set()
    for cs, cj, cw in zip(ref.cases, jx.cases, jx_warm.cases):
        check(set(cs.results) == set(cj.results) == set(ALL_SCHEMES),
              f"case {cs.index}: schemes {sorted(cj.results)}")
        for scheme, a in cs.results.items():
            for b in (cj.results[scheme], cw.results[scheme]):
                check(b.num_rounds == a.num_rounds,
                      f"case {cs.index} {scheme}: rounds {b.num_rounds} "
                      f"!= serial {a.num_rounds}")
                check(b.relay_hops == a.relay_hops,
                      f"case {cs.index} {scheme}: relay hops "
                      f"{b.relay_hops} != serial {a.relay_hops}")
                check(np.isfinite(b.total_time) and b.total_time > 0,
                      f"case {cs.index} {scheme}: total_time {b.total_time}")
                worst = max(worst,
                            abs(b.total_time - a.total_time) / a.total_time)
            schemes_seen.add(scheme)
            pairs += 1
    check(len(jx.cases) == num_cases, f"{len(jx.cases)} cases returned")
    for key in ("factory_declined", "numpy_rounds", "numpy_pipelines",
                "object_engine_rows"):
        check(counts[key] == 0,
              f"a batch left the device engine: {key}={counts[key]}")
    check(counts["round_engines"] > 0 and counts["pipeline_engines"] > 0,
          f"device engines not built: {dict(counts)}")
    check(counts["device_rounds"] + counts["device_scans"] > 0
          and counts["device_pipelines"] > 0,
          f"device steppers not called: {dict(counts)}")
    print(f"sweep: {num_cases} cases x {len(schemes_seen)} schemes = "
          f"{pairs} (case, scheme) pairs, trace-frozen, Table II shape")
    print(f"sweep: device counters {dict(sorted(counts.items()))}")
    print(f"sweep: jax first run {jax_s:.3f}s (compile {c1 - c0:.3f}s, "
          f"persistent-cache hits {h1 - h0}), warm run {warm_s:.3f}s "
          f"(compile {c2 - c1:.3f}s), serial reference {serial_s:.3f}s")
    print(f"sweep: max relative deviation of total_time vs serial = "
          f"{worst:.3e} (bound {SWEEP_RTOL:g}); round counts and relay "
          "hops identical")
    check(worst <= SWEEP_RTOL,
          f"total_time deviates {worst:.3e} > {SWEEP_RTOL:g} from serial")
    return jx


def stripe_plans(sweep, num_stripes: int):
    """(case index, scheme, plan) for `num_stripes` single-job RS(6,3)
    plans from the sweep, cycling over its cases and schemes."""
    picks = []
    for case in sweep.cases:
        if tuple(case.params["code"]) != (6, 3):
            continue
        for scheme, r in sorted(case.results.items()):
            if r.plan is not None and len(r.plan.jobs) == 1:
                picks.append((case.index, scheme, r.plan))
    check(bool(picks), "the sweep produced no single-job RS(6,3) plan")
    return [picks[i % len(picks)] for i in range(num_stripes)]


def dataplane_phase(sweep, meter: CompileMeter, platform: str, *,
                    num_stripes: int, nbytes: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.engine.arrays import (compile_plan, decompile,
                                          relabel_plan_nodes)
    from repro.core.engine.dataplane import execute_plans_batch
    from repro.core.executor import execute_plan
    from repro.ec.rs import RSCode
    from repro.ec.stripe import place_stripes
    from repro.kernels import ops

    code = RSCode(6, 3)
    stripes = place_stripes(num_stripes, code, DP_CLUSTER)
    picks = stripe_plans(sweep, num_stripes)
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, size=(num_stripes, code.k, nbytes),
                        dtype=np.uint8)

    c0, h0 = meter.snapshot()
    tic = time.perf_counter()
    coeff = code.parity_coeffs()
    cws = [np.concatenate([data[s], np.asarray(
        ops.rs_encode(coeff, jnp.asarray(data[s])))]) for s in range(num_stripes)]
    encode_s = time.perf_counter() - tic
    for s in range(min(2, num_stripes)):
        check(np.array_equal(cws[s], code.encode(data[s])),
              f"stripe {s}: kernel encode differs from RSCode.encode")

    plans, bmaps = [], []
    for (_, _, plan), stripe in zip(picks, stripes):
        plans.append(relabel_plan_nodes(compile_plan(plan),
                                        stripe.perm(DP_CLUSTER)))
        bmaps.append(stripe.block_map(DP_CLUSTER))

    outs: dict[str, list] = {"premultiply": [], "fold": []}

    def on(kind):
        def record(out):
            outs[kind].append(
                isinstance(out, jax.Array)
                and {d.platform for d in out.devices()} == {platform})
        return record

    counts: collections.Counter = collections.Counter()
    tic = time.perf_counter()
    with patched(ops, "gf256_scale_batch",
                 counting(counts, "premultiply", on("premultiply"))), \
            patched(ops, "xor_reduce_segments",
                    counting(counts, "fold", on("fold"))):
        res = execute_plans_batch(plans, code, cws, block_of=bmaps)
    batch_s = time.perf_counter() - tic
    c1, h1 = meter.snapshot()

    check(res.all_verified,
          f"{int((~res.verified).sum())} stripes failed byte verification")
    for kind, flags in outs.items():
        check(bool(flags) and all(flags),
              f"{kind}: {flags.count(False)} of {len(flags)} results were "
              f"not {platform} device arrays (numpy reference taken?)")
    lost = sum(len(r) for r in res.reconstructed) * nbytes
    for b in range(min(DP_SERIAL_CHECKS, num_stripes)):
        one = execute_plan(decompile(plans[b]), code, cws[b],
                           block_of=bmaps[b])
        check(one.verified, f"stripe {b}: serial execute_plan not verified")
        check(one.bytes_moved == int(res.bytes_moved[b]),
              f"stripe {b}: bytes moved {int(res.bytes_moved[b])} != "
              f"serial {one.bytes_moved}")
        for job, got in res.reconstructed[b].items():
            check(np.array_equal(got, one.reconstructed[job]),
                  f"stripe {b} job {job}: batched bytes != serial bytes")
    schemes = collections.Counter(s for _, s, _ in picks)
    print(f"dataplane: RS(6,3) {nbytes}-byte cells, {num_stripes} stripes "
          f"over {DP_CLUSTER} nodes, plans by scheme {dict(sorted(schemes.items()))}")
    print(f"dataplane: encode {encode_s:.3f}s, one execute_plans_batch "
          f"call {batch_s:.3f}s (compile in both {c1 - c0:.3f}s, "
          f"persistent-cache hits {h1 - h0})")
    print(f"dataplane: all_verified={res.all_verified}, lost-block bytes "
          f"reconstructed {lost}, bytes moved {int(res.bytes_moved.sum())}, "
          f"premultiply calls {counts['premultiply']} and fold calls "
          f"{counts['fold']} all returned {platform} device arrays")
    print(f"dataplane: {min(DP_SERIAL_CHECKS, num_stripes)} stripes "
          "byte-identical to the serial execute_plan")


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found; JAX's first device is "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}, compile cache {use_compile_cache(ROOT)}")
    meter = CompileMeter()
    sweep = sweep_phase(meter, num_cases=SWEEP_CASES, seed=SEED)
    dataplane_phase(sweep, meter, dev.platform, num_stripes=DP_STRIPES,
                    nbytes=DP_NBYTES, seed=SEED)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
