"""Sweep engine laws: determinism, single-scenario parity, executor
equivalence, and suite-generator shape/validity."""
import numpy as np
import pytest

from repro.core import topology
from repro.core.bandwidth import BandwidthProcess, BandwidthTrace, IngressModel
from repro.core.simulator import (ALL_SCHEMES, RepairSimulator, Scenario,
                                  run_scheme)
from repro.ec.rs import RSCode
from repro.sim.suite import (FAILURE_PATTERNS, GridSuite, MonteCarloSuite,
                             SampleSpace, TraceSuite, VOLATILITY_REGIMES,
                             sample_failures)
from repro.sim.sweep import run_sweep


def _scenario(n=6, k=3, failed=(0,), seed=0, cluster=8, chunk=8.0):
    m = topology.heterogeneous_matrix(cluster, low=3, high=30, seed=seed)
    bwp = BandwidthProcess(base=m, change_interval=2.0, seed=seed, mode="markov")
    return Scenario(num_nodes=cluster, code=RSCode(n, k), failed=failed,
                    bw=bwp, ingress=IngressModel(seed=seed), chunk_mb=chunk)


def _small_mc_suite(base_seed=3, num=8):
    space = SampleSpace(
        codes=((4, 2), (6, 3)), cluster_sizes=(8,), chunk_mb=(8.0,),
        regimes=("hot2s",), failure_patterns=("single", "double", "rack"))
    return MonteCarloSuite("t", num, space, base_seed=base_seed)


# ------------------------------------------------------------- determinism
def test_sweep_deterministic_same_seed():
    a = run_sweep(_small_mc_suite(), executor="serial")
    b = run_sweep(_small_mc_suite(), executor="serial")
    assert len(a.cases) == len(b.cases)
    for ca, cb in zip(a.cases, b.cases):
        assert ca.params == cb.params and ca.seed == cb.seed
        assert set(ca.results) == set(cb.results)
        for s in ca.results:
            assert ca.results[s].total_time == cb.results[s].total_time
            assert ca.results[s].round_times == cb.results[s].round_times
            assert ca.results[s].relay_hops == cb.results[s].relay_hops


def test_sweep_different_seed_differs():
    a = run_sweep(_small_mc_suite(base_seed=3), executor="serial")
    b = run_sweep(_small_mc_suite(base_seed=4), executor="serial")
    ta = [c.results[s].total_time for c in a.cases for s in sorted(c.results)]
    tb = [c.results[s].total_time for c in b.cases for s in sorted(c.results)]
    assert ta != tb


# ------------------------------------------------- single-scenario parity
@pytest.mark.parametrize("scheme", ALL_SCHEMES)
def test_size_one_sweep_matches_simulate(scheme):
    """A sweep of size 1 is bit-identical to the legacy single-Scenario
    path, for every scheme (wall-clock planning_time excluded)."""
    failed = (0, 1) if scheme in ("mppr", "random", "msrepair") else (0,)
    seed = 5
    sc = _scenario(n=6, k=3, failed=failed, seed=seed)
    suite = GridSuite("one", axes={}, build=lambda p, s: sc,
                      trials=1, schemes=(scheme,), base_seed=seed)
    sweep = run_sweep(suite, executor="serial")
    direct = RepairSimulator(sc, random_seed=seed).run(scheme)
    [case] = sweep.cases
    got = case.results[scheme]
    assert got.total_time == direct.total_time
    assert got.round_times == direct.round_times
    assert got.relay_hops == direct.relay_hops
    assert got.num_rounds == direct.num_rounds


def test_run_scheme_is_simulator_run():
    sc = _scenario(seed=2)
    a = run_scheme(sc, "bmf", random_seed=2)
    b = RepairSimulator(sc, random_seed=2).run("bmf")
    assert a.total_time == b.total_time and a.round_times == b.round_times


# ------------------------------------------------------ executor equivalence
@pytest.mark.parametrize("executor", ["thread", "process"])
def test_executors_match_serial(executor):
    suite = _small_mc_suite(num=6)
    ref = run_sweep(suite, executor="serial")
    got = run_sweep(suite, executor=executor, max_workers=2)
    for cr, cg in zip(ref.cases, got.cases):
        assert set(cr.results) == set(cg.results)
        for s in cr.results:
            assert cr.results[s].total_time == cg.results[s].total_time
            assert cr.results[s].round_times == cg.results[s].round_times


def test_process_pool_path_matches_serial(monkeypatch):
    """Exercise the real ProcessPoolExecutor branch (chunked pool.map,
    Scenario pickling incl. the memoized jobs cache) by dropping the
    spawn-amortization threshold below the suite size."""
    import repro.sim.sweep as sweep_mod

    monkeypatch.setattr(sweep_mod, "_MIN_CASES_PER_WORKER", 1)
    suite = _small_mc_suite(num=6)
    ref = run_sweep(suite, executor="serial")
    got = run_sweep(suite, executor="process", max_workers=2)
    for cr, cg in zip(ref.cases, got.cases):
        assert set(cr.results) == set(cg.results)
        for s in cr.results:
            assert cr.results[s].total_time == cg.results[s].total_time
            assert cr.results[s].round_times == cg.results[s].round_times


def _probe_case(args):
    """Pool task for the backend probe below: run the case as the pool
    would, then record whether this worker initialised a JAX backend."""
    import dataclasses

    from jax._src import xla_bridge

    import repro.sim.sweep as sweep_mod

    res = sweep_mod._run_case(*args)
    return dataclasses.replace(res, params={
        **res.params, "backend_initialised": xla_bridge.backends_are_initialized()})


def test_process_pool_workers_initialise_no_backend(monkeypatch):
    """Spawned sweep workers import JAX (through `repro.core`) but must
    never initialise a backend: on an accelerator host the parent holds
    the device, and a worker reaching for it would fail or hang."""
    import jax

    import repro.sim.sweep as sweep_mod

    jax.devices()                       # the parent holds its backend
    monkeypatch.setattr(sweep_mod, "_MIN_CASES_PER_WORKER", 1)
    monkeypatch.setattr(sweep_mod, "_run_case_star", _probe_case)
    got = run_sweep(_small_mc_suite(num=4), executor="process", max_workers=2)
    flags = [c.params["backend_initialised"] for c in got.cases]
    assert flags == [False] * 4


def test_process_executor_spawn_amortization_fallback():
    """Below the spawn-amortization threshold the process executor must
    warn and fall back to serial (identical results) instead of paying
    ~0.5 s of worker start-up per handful of cases."""
    import repro.sim.sweep as sweep_mod

    suite = _small_mc_suite(num=6)
    ref = run_sweep(suite, executor="serial")
    with pytest.warns(RuntimeWarning, match="spawn"):
        got = run_sweep(suite, executor="process", max_workers=2)
    for cr, cg in zip(ref.cases, got.cases):
        for s in cr.results:
            assert cr.results[s].total_time == cg.results[s].total_time
    # worker sizing: never more workers than the threshold can feed
    assert sweep_mod._process_workers(6, None) == 0
    thresh = sweep_mod._MIN_CASES_PER_WORKER
    assert sweep_mod._process_workers(4 * thresh, None) <= 4


# ------------------------------------------------------- suite generators
def test_grid_suite_covers_product():
    built = []

    def build(params, seed):
        built.append((params["a"], params["b"], params["trial"], seed))
        return _scenario(seed=seed)

    suite = GridSuite("g", axes={"a": [1, 2], "b": ["x", "y", "z"]},
                      build=build, trials=2, schemes=("ppr",))
    cases = list(suite.cases())
    assert len(cases) == len(suite) == 2 * 3 * 2
    assert len({c.index for c in cases}) == len(cases)
    assert {(p[0], p[1]) for p in built} == {(a, b) for a in (1, 2)
                                            for b in ("x", "y", "z")}
    assert all(p[2] == p[3] for p in built)      # seed == base_seed + trial


def test_mc_suite_cases_valid_and_reproducible():
    suite = _small_mc_suite(num=16)
    cases = list(suite.cases())
    assert len(cases) == 16
    again = list(_small_mc_suite(num=16).cases())
    for c, c2 in zip(cases, again):
        assert c.params == c2.params and c.seed == c2.seed
        sc = c.scenario
        n, k = c.params["code"]
        assert sc.code.n == n and sc.code.k == k
        assert sc.num_nodes >= n
        assert sc.bw.base.shape == (sc.num_nodes, sc.num_nodes)
        assert all(0 <= f < n for f in sc.failed)
        assert 1 <= len(sc.failed) <= n - k
        assert c.params["regime"] in VOLATILITY_REGIMES
        assert c.params["pattern"] in FAILURE_PATTERNS
        # scheme sets match failure cardinality
        if len(sc.failed) > 1:
            assert c.schemes == ("mppr", "random", "msrepair")
        else:
            assert c.schemes == ("traditional", "ppr", "ppt", "bmf")
    # all runnable end-to-end
    sweep = run_sweep(suite, executor="serial")
    for c in sweep.cases:
        for s, r in c.results.items():
            assert r.total_time > 0 and np.isfinite(r.total_time), s


def test_mc_suite_prefix_stable():
    """Case i is identical no matter the suite size (counter-based seeds)."""
    big = list(_small_mc_suite(num=10).cases())
    small = list(_small_mc_suite(num=4).cases())
    for c_small, c_big in zip(small, big):
        assert c_small.params == c_big.params and c_small.seed == c_big.seed


def test_sample_failures_patterns():
    rng = np.random.default_rng(0)
    for _ in range(50):
        (f,) = sample_failures(rng, 7, 4, "single")
        assert 0 <= f < 7
        d = sample_failures(rng, 7, 4, "double")
        assert len(set(d)) == 2 and all(0 <= f < 7 for f in d)
        r = sample_failures(rng, 7, 4, "rack", rack_size=4)
        assert 1 <= len(r) <= 2 and all(0 <= f < 7 for f in r)
        racks = {f // 4 for f in r}
        assert len(racks) == 1                      # correlated: one rack
    with pytest.raises(ValueError):
        sample_failures(rng, 4, 3, "double")        # n - k < 2
    with pytest.raises(ValueError):
        sample_failures(rng, 4, 2, "nope")


def test_sample_space_validation():
    with pytest.raises(ValueError):
        SampleSpace(codes=((3, 3),))
    with pytest.raises(ValueError):
        SampleSpace(regimes=("warm9s",))
    with pytest.raises(ValueError):
        SampleSpace(failure_patterns=("cascade",))


def test_trace_suite_freeze_reproduces():
    suite = _small_mc_suite(num=4)
    frozen = TraceSuite.freeze(suite, num_epochs=64)
    assert len(frozen) == len(suite)
    for case in frozen.cases():
        assert isinstance(case.scenario.bw, BandwidthTrace)
    # within the recorded window the frozen sweep matches the live one
    live = run_sweep(suite, executor="serial")
    replay = run_sweep(frozen, executor="serial")
    for cl, cr in zip(live.cases, replay.cases):
        for s in cl.results:
            if max(cl.results[s].round_times, default=0) == 0:
                continue
            # identical as long as the repair finished inside the recording
            if cl.results[s].total_time < 64 * 2.0:
                assert cl.results[s].total_time == cr.results[s].total_time


# ------------------------------------------------------------- aggregation
def test_sweep_result_stats_and_cdf():
    suite = GridSuite(
        "agg", axes={"chunk_mb": [4.0, 8.0]},
        build=lambda p, seed: _scenario(seed=seed, chunk=p["chunk_mb"]),
        trials=3, schemes=("ppr", "bmf"))
    sweep = run_sweep(suite, executor="serial")
    assert len(sweep.cases) == 6
    st = sweep.stats("bmf")
    t = sweep.times("bmf")
    assert st.count == 6
    assert st.mean == pytest.approx(float(t.mean()))
    assert st.min <= st.p50 <= st.p90 <= st.max
    spd, cdf = sweep.speedup_cdf("ppr", "bmf")
    assert len(spd) == 6 and np.all(np.diff(spd) >= 0)
    assert cdf[-1] == 1.0
    assert (spd >= 1.0 - 1e-9).all()   # static-per-round BMF never loses to PPR here
    groups = sweep.group_by("chunk_mb")
    assert set(groups) == {(4.0,), (8.0,)}
    assert all(len(g.cases) == 3 for g in groups.values())
    red = sweep.reduction_pct("ppr", "bmf")
    assert np.isfinite(red)
    assert sweep.summary_table()


# ------------------------------------------------------- auto resolution
def test_auto_resolves_to_vectorized_on_cpu():
    """"auto" = the batched array engine; on a CPU jax backend (or no
    jax) the tuned numpy engine always wins, live or trace, any size."""
    from repro.sim.sweep import _resolve_executor

    live = list(_small_mc_suite().cases())
    assert _resolve_executor("auto", live) == "vectorized"
    frozen = list(TraceSuite.freeze(_small_mc_suite()).cases())
    assert _resolve_executor("auto", frozen) == "vectorized"
    # explicit choices pass through untouched
    assert _resolve_executor("serial", live) == "serial"
    assert _resolve_executor("jax", live) == "jax"


def test_auto_picks_jax_on_accelerator_for_large_trace_suites(monkeypatch):
    """With a device backend, auto routes large trace-frozen suites to
    the jax executor — and only those: live epochs or small suites stay
    on the numpy engine (jit compile + dispatch dominate there, the
    BENCH_sweep table2_60 regression)."""
    jax = pytest.importorskip("jax")
    from repro.sim import sweep as sweep_mod

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    big = list(TraceSuite.freeze(
        _small_mc_suite(num=sweep_mod._JAX_AUTO_MIN_CASES)).cases())
    assert sweep_mod._resolve_executor("auto", big) == "jax"
    small = big[: sweep_mod._JAX_AUTO_MIN_CASES - 1]
    assert sweep_mod._resolve_executor("auto", small) == "vectorized"
    live = list(_small_mc_suite(num=sweep_mod._JAX_AUTO_MIN_CASES).cases())
    assert sweep_mod._resolve_executor("auto", live) == "vectorized"


def test_auto_sweep_matches_serial():
    suite = _small_mc_suite(num=4)
    ref = run_sweep(suite, executor="serial")
    got = run_sweep(_small_mc_suite(num=4), executor="auto")
    for ca, cb in zip(ref.cases, got.cases):
        for s in ca.results:
            assert abs(ca.results[s].total_time
                       - cb.results[s].total_time) <= 1e-9


# ------------------------------------------------------- byte verification
def test_verify_bytes_samples_and_passes():
    """`verify_bytes=k` byte-verifies k sampled cases against placed
    stripes — every scheme of every sampled case, batched."""
    suite = _small_mc_suite(num=6)
    sweep = run_sweep(suite, executor="vectorized", verify_bytes=3)
    bv = sweep.byte_verification
    assert bv is not None and bv.verified and not bv.failures
    checked_cases = {i for i, _ in bv.checked}
    assert len(checked_cases) == 3
    # every scheme of each sampled case was executed over bytes
    by_case = {c.index: set(c.results) for c in sweep.cases}
    for i in checked_cases:
        assert {s for j, s in bv.checked if j == i} == by_case[i]
    assert bv.nbytes > 0


def test_verify_bytes_covers_ppt_and_multi():
    """Single-failure suites include ppt (via the pipeline-tree
    lowering); the sample covers it."""
    space = SampleSpace(codes=((6, 3),), cluster_sizes=(9,),
                        chunk_mb=(8.0,), regimes=("hot2s",),
                        failure_patterns=("single",))
    suite = MonteCarloSuite("bv1", 3, space, base_seed=11)
    sweep = run_sweep(suite, executor="serial", verify_bytes=3)
    bv = sweep.byte_verification
    assert bv.verified
    assert any(s == "ppt" for _, s in bv.checked)


def test_verify_bytes_off_by_default():
    sweep = run_sweep(_small_mc_suite(num=2), executor="serial")
    assert sweep.byte_verification is None
