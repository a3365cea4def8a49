"""JAX stepper parity: `run_sweep(executor="jax")` and the underlying
`repro.core.engine.jax_stepper` programs must reproduce the reference
engines — all 8 schemes, all three volatility regimes, 1e-6 relative
tolerance with identical round counts and relay hops — must raise when
jax is unusable, and must fall back to the numpy steppers per batch only
for batches the device engine cannot take."""
import sys

import numpy as np
import pytest

from repro.core import topology
from repro.core.bandwidth import BandwidthProcess, BandwidthTrace, IngressModel
from repro.core.engine.vectorized import run_scheme_vectorized
from repro.core.simulator import ALL_SCHEMES, Scenario, run_scheme
from repro.ec.rs import RSCode
from repro.sim.suite import MonteCarloSuite, SampleSpace, TraceSuite
from repro.sim.sweep import run_sweep

from repro.core.engine import jax_stepper

RTOL = 1e-6
MULTI = ("mppr", "random", "msrepair")


def _scenario(n=7, k=4, failed=(0,), seed=0, cluster=10, chunk=8.0,
              interval=2.0, mode="markov"):
    m = topology.heterogeneous_matrix(cluster, low=3, high=30, seed=seed)
    bwp = BandwidthProcess(base=m, change_interval=interval, seed=seed,
                           mode=mode)
    return Scenario(num_nodes=cluster, code=RSCode(n, k), failed=failed,
                    bw=bwp, ingress=IngressModel(seed=seed), chunk_mb=chunk)


def _assert_parity(ref, got, label=""):
    assert got.num_rounds == ref.num_rounds, label
    assert got.relay_hops == ref.relay_hops, label
    assert got.total_time == pytest.approx(ref.total_time, rel=RTOL), label
    for a, b in zip(ref.round_times, got.round_times):
        assert b == pytest.approx(a, rel=RTOL, abs=1e-9), label


# ------------------------------------------------------------ parity matrix
@pytest.mark.parametrize("mode", ["jitter", "redraw", "markov"])
@pytest.mark.parametrize("scheme", ALL_SCHEMES)
def test_jax_matches_reference_all_schemes_all_regimes(scheme, mode):
    failed = (0, 1) if scheme in MULTI else (0,)
    seeds = list(range(4))
    scs = [_scenario(failed=failed, seed=s, mode=mode) for s in seeds]
    ref = [run_scheme(sc, scheme, random_seed=s)
           for s, sc in zip(seeds, scs)]
    got = run_scheme_vectorized(scs, scheme, seeds=seeds, backend="jax")
    for s, (a, b) in enumerate(zip(ref, got)):
        _assert_parity(a, b, f"{scheme}/{mode} seed={s}")
        assert b.log == a.log, f"{scheme}/{mode} seed={s}"
        assert b.plan == a.plan, f"{scheme}/{mode} seed={s}"


def test_jax_static_network_and_traces():
    static = [_scenario(seed=s, interval=None) for s in range(3)]
    for scheme in ("ppr", "bmf", "ppt"):
        for a, b in zip([run_scheme(sc, scheme) for sc in static],
                        run_scheme_vectorized(static, scheme,
                                              backend="jax")):
            _assert_parity(a, b, f"static {scheme}")
    for cycle in (True, False):
        traced = [
            Scenario(
                num_nodes=sc.num_nodes, code=sc.code, failed=sc.failed,
                bw=BandwidthTrace.record(sc.bw, 16, cycle=cycle),
                ingress=sc.ingress, chunk_mb=sc.chunk_mb,
            )
            for sc in (_scenario(seed=s) for s in range(3))
        ]
        for scheme in ("traditional", "ppr", "ppt", "bmf"):
            for a, b in zip([run_scheme(sc, scheme) for sc in traced],
                            run_scheme_vectorized(traced, scheme,
                                                  backend="jax")):
                _assert_parity(a, b, f"trace cycle={cycle} {scheme}")


def test_jax_epoch_horizon_grows_and_restores_replans(monkeypatch):
    """A live case outrunning the pre-sampled horizon must re-run with a
    doubled horizon — including rolling back BMF splices the aborted
    attempt wrote — and still match the reference engine exactly."""
    monkeypatch.setattr(jax_stepper, "_INITIAL_LIVE_EPOCHS", 2)
    grown: list[int] = []
    orig = jax_stepper._EngineBase.grow

    def spy(self):
        grown.append(self.live_epochs)
        return orig(self)

    monkeypatch.setattr(jax_stepper._EngineBase, "grow", spy)
    scs = [_scenario(n=4, k=2, seed=s, cluster=6, chunk=64.0)
           for s in range(2)]
    for scheme in ("bmf", "ppt"):       # replanned rounds + pipeline
        ref = [run_scheme(sc, scheme) for sc in scs]
        got = run_scheme_vectorized(scs, scheme, backend="jax")
        for a, b in zip(ref, got):
            _assert_parity(a, b, f"horizon {scheme}")
    assert grown, "the 2-epoch horizon must have overflowed"


def test_jax_sweep_matches_serial():
    space = SampleSpace(
        codes=((4, 2), (6, 3)), cluster_sizes=(8,), chunk_mb=(8.0,),
        regimes=("hot2s", "redraw2s"), failure_patterns=("single", "double"),
    )
    suite = MonteCarloSuite("jaxparity", 12, space, base_seed=11)
    serial = run_sweep(suite, executor="serial")
    jaxs = run_sweep(suite, executor="jax")
    assert len(jaxs.cases) == 12
    for cs, cj in zip(serial.cases, jaxs.cases):
        assert set(cs.results) == set(cj.results)
        for scheme in cs.results:
            a, b = cs.results[scheme], cj.results[scheme]
            assert b.num_rounds == a.num_rounds, (cs.index, scheme)
            assert b.relay_hops == a.relay_hops, (cs.index, scheme)
            assert b.total_time == pytest.approx(a.total_time, rel=RTOL), \
                (cs.index, scheme)


def test_jax_sweep_on_frozen_traces_matches_serial():
    space = SampleSpace(codes=((6, 3),), cluster_sizes=(8,), chunk_mb=(8.0,),
                        regimes=("hot2s",), failure_patterns=("single",))
    frozen = TraceSuite.freeze(
        MonteCarloSuite("p", 6, space, base_seed=5), num_epochs=64)
    serial = run_sweep(frozen, executor="serial")
    jaxs = run_sweep(frozen, executor="jax")
    for cs, cj in zip(serial.cases, jaxs.cases):
        for scheme in cs.results:
            assert (cj.results[scheme].total_time
                    == pytest.approx(cs.results[scheme].total_time,
                                     rel=RTOL))


# ------------------------------------------------------------ fallback paths
def test_jax_missing_raises(monkeypatch):
    """The no-jax path: backend='jax' must raise instead of quietly
    running the numpy engine (a device run that silently leaves the
    device is a wrong run), while the numpy backend still works."""
    scs = [_scenario(seed=s, cluster=8) for s in range(2)]
    ref = run_scheme_vectorized(scs, "ppr")
    monkeypatch.setitem(sys.modules, "jax", None)
    with pytest.raises(ImportError):
        run_scheme_vectorized(scs, "ppr", backend="jax")
    assert [r.total_time for r in run_scheme_vectorized(scs, "ppr")] == \
        [r.total_time for r in ref]


def test_jax_non_persistent_shares_fall_back():
    """Epoch-keyed Dirichlet redraws cannot be pretabulated on device:
    the factory must decline and the batch must still match serial."""
    m = topology.heterogeneous_matrix(8, low=3, high=30, seed=2)
    scs = [
        Scenario(num_nodes=8, code=RSCode(6, 3), failed=(0,),
                 bw=BandwidthProcess(base=m, change_interval=2.0, seed=s,
                                     mode="markov"),
                 ingress=IngressModel(seed=s, persistent_shares=False),
                 chunk_mb=8.0)
        for s in range(2)
    ]
    assert jax_stepper.make_round_engine(scs, 8, []) is None
    ref = [run_scheme(sc, "traditional") for sc in scs]
    got = run_scheme_vectorized(scs, "traditional", backend="jax")
    for a, b in zip(ref, got):
        _assert_parity(a, b, "non-persistent fallback")


def test_jax_unsupported_helper_ids_fall_back_per_case():
    """Helper ids >= 64 cannot be bitmask-compiled; those cases must drop
    to the object engine while the rest of the batch runs on device."""
    m = topology.heterogeneous_matrix(70, low=3, high=30, seed=1)
    bwp = BandwidthProcess(base=m, change_interval=2.0, seed=1, mode="markov")
    big = Scenario(num_nodes=70, code=RSCode(6, 3), failed=(0,), bw=bwp,
                   ingress=IngressModel(seed=1), chunk_mb=4.0,
                   helpers=((65, 66, 67),))
    small = _scenario(n=6, k=3, seed=1, cluster=8, chunk=4.0)
    got = run_scheme_vectorized([big, small], "ppr", backend="jax")
    ref = [run_scheme(big, "ppr"), run_scheme(small, "ppr")]
    for a, b in zip(ref, got):
        _assert_parity(a, b, "fallback")


def test_bucketing_shares_compiled_programs():
    """Batches whose raw shapes differ only within a pow2 bucket must pad
    to the same padded shapes (one compiled program per (N, bucket))."""
    assert jax_stepper._pow2(0) == 1
    assert jax_stepper._pow2(1) == 1
    assert jax_stepper._pow2(3) == 4
    assert jax_stepper._pow2(8) == 8
    scs3 = [_scenario(seed=s, cluster=8) for s in range(3)]
    scs4 = [_scenario(seed=s, cluster=8) for s in range(4)]
    e3 = jax_stepper.make_round_engine(scs3, 8, [])
    e4 = jax_stepper.make_round_engine(scs4, 8, [])
    assert e3.Bp == e4.Bp == 4
    hop_u = np.zeros((3, 2, 1), dtype=np.int64)
    n_hops = np.zeros((3, 2), dtype=np.int64)
    hu, hv, nh, tt = e3._pad_round(hop_u, hop_u, n_hops, np.zeros(3))
    assert hu.shape == (4, 2, 1) and tt.shape == (4,)


# ------------------------------------------------------ program-cache reuse
def test_compiled_programs_reused_across_run_sweep_calls():
    """The jit steppers live in a module-global cache keyed by padded
    pow2 shapes: a second `run_sweep(executor="jax")` over a fresh but
    same-shaped suite must trigger ZERO new XLA compilations (this is
    what makes the jax executor amortizable at all — and what "auto"
    relies on when routing repeated trace sweeps to it)."""
    import logging

    import jax

    space = SampleSpace(codes=((6, 3),), cluster_sizes=(8,), chunk_mb=(8.0,),
                        regimes=("hot2s",), failure_patterns=("single",))

    def make():
        return TraceSuite.freeze(
            MonteCarloSuite("reuse", 5, space, base_seed=21), num_epochs=32)

    run_sweep(make(), executor="jax")          # warm every program shape

    compiles: list[str] = []

    class Spy(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            if "Finished XLA compilation" in msg:
                compiles.append(msg)

    spy = Spy(level=logging.WARNING)
    logger = logging.getLogger("jax")
    old_level = logger.level
    jax.config.update("jax_log_compiles", True)
    logger.addHandler(spy)
    logger.setLevel(logging.WARNING)
    try:
        second = run_sweep(make(), executor="jax")
    finally:
        logger.removeHandler(spy)
        logger.setLevel(old_level)
        jax.config.update("jax_log_compiles", False)
    assert len(second.cases) == 5
    assert not compiles, f"recompiled across run_sweep calls: {compiles}"
