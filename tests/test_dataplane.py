"""Batched byte data plane: parity with the serial oracle, stripe
placements, PPT lowering, and the plan-relabeling transform."""
import contextlib

import numpy as np
import pytest

from conftest import interpreted
from repro.core import executor, topology
from repro.core.bandwidth import BandwidthProcess, IngressModel
from repro.core.engine.arrays import (compile_plan, decompile,
                                      relabel_plan_nodes)
from repro.core.engine.dataplane import (execute_plans_batch,
                                         identity_block_map)
from repro.core.plan import Job, RepairPlan, Round, Transfer, validate_plan
from repro.core.ppt import build_ppt_tree, ppt_round_plan
from repro.core.simulator import Scenario, run_scheme
from repro.ec.rs import RSCode
from repro.ec.stripe import place_stripes
from repro.sim.suite import sample_failures
from repro.sim.sweep import _verify_plan

SINGLE = ("traditional", "ppr", "bmf", "bmf_static", "ppt")
MULTI = ("mppr", "random", "msrepair")


def _scenario(n, k, failed, seed, cluster):
    m = topology.heterogeneous_matrix(cluster, low=3, high=30, seed=seed)
    bwp = BandwidthProcess(base=m, change_interval=2.0, seed=seed,
                           mode="markov")
    return Scenario(num_nodes=cluster, code=RSCode(n, k), failed=failed,
                    bw=bwp, ingress=IngressModel(seed=seed), chunk_mb=4.0)


def _plan_for(sc, scheme, seed):
    return _verify_plan(sc, scheme, seed, bmf_optimize_all=False)


def _exec_both(plan, code, cw, block_of=None):
    ser = executor.execute_plan(plan, code, cw, block_of=block_of)
    bat = execute_plans_batch([plan], [code], [cw],
                              block_of=None if block_of is None
                              else [block_of])
    return ser, bat


# ------------------------------------------------------- scheme-sweep parity
@pytest.mark.parametrize("scheme", SINGLE)
def test_single_failure_schemes_byte_identical(scheme, rng):
    code = RSCode(6, 3)
    cw = code.encode(rng.integers(0, 256, size=(3, 640), dtype=np.uint8))
    sc = _scenario(6, 3, (2,), seed=4, cluster=12)
    plan = _plan_for(sc, scheme, 4)
    ser, bat = _exec_both(plan, code, cw)
    assert ser.verified and bool(bat.verified[0])
    assert int(bat.bytes_moved[0]) == ser.bytes_moved
    for jid, blk in ser.reconstructed.items():
        assert np.array_equal(bat.reconstructed[0][jid], np.asarray(blk))
        assert np.array_equal(bat.reconstructed[0][jid], cw[2])


@pytest.mark.parametrize("scheme", MULTI)
def test_multi_failure_schemes_byte_identical(scheme, rng):
    code = RSCode(7, 4)
    cw = code.encode(rng.integers(0, 256, size=(4, 384), dtype=np.uint8))
    sc = _scenario(7, 4, (1, 5), seed=9, cluster=12)
    plan = _plan_for(sc, scheme, 9)
    ser, bat = _exec_both(plan, code, cw)
    assert ser.verified and bool(bat.verified[0])
    assert int(bat.bytes_moved[0]) == ser.bytes_moved
    for j, f in enumerate((1, 5)):
        assert np.array_equal(bat.reconstructed[0][j], cw[f])


def test_mixed_batch_matches_serial_case_for_case(rng):
    """One heterogeneous batch (codes, clusters, schemes, job counts)
    equals running the serial oracle per case."""
    specs = [
        ((4, 2), (0,), "traditional", 8), ((6, 3), (1,), "ppr", 10),
        ((7, 4), (3,), "bmf", 12), ((6, 3), (0, 2), "msrepair", 11),
        ((7, 4), (0, 1), "mppr", 13), ((6, 3), (1, 4), "random", 9),
        ((6, 3), (5,), "ppt", 12), ((7, 4), (2,), "bmf_static", 14),
    ]
    plans, codes, cws, serials = [], [], [], []
    for i, ((n, k), failed, scheme, cluster) in enumerate(specs):
        code = RSCode(n, k)
        cw = code.encode(rng.integers(0, 256, size=(k, 256), dtype=np.uint8))
        sc = _scenario(n, k, failed, seed=20 + i, cluster=cluster)
        plan = _plan_for(sc, scheme, 20 + i)
        serials.append(executor.execute_plan(plan, code, cw))
        plans.append(compile_plan(plan))
        codes.append(code)
        cws.append(cw)
    bat = execute_plans_batch(plans, codes, cws)
    assert bat.all_verified
    for b, ser in enumerate(serials):
        assert ser.verified
        assert int(bat.bytes_moved[b]) == ser.bytes_moved
        for jid, blk in ser.reconstructed.items():
            assert np.array_equal(bat.reconstructed[b][jid],
                                  np.asarray(blk))


def test_kernel_interpret_path_matches_ref(rng):
    """The Pallas kernel path (interpret off-TPU) is byte-identical to
    the numpy ref path on the same batch."""
    code = RSCode(6, 3)
    cws, plans = [], []
    for i in range(3):
        cws.append(code.encode(
            rng.integers(0, 256, size=(3, 200), dtype=np.uint8)))
        sc = _scenario(6, 3, (i % 6,), seed=i, cluster=10)
        plans.append(compile_plan(_plan_for(sc, "ppr", i)))
    ref = execute_plans_batch(plans, code, cws)
    with interpreted():
        ker = execute_plans_batch(plans, code, cws)
    assert ref.all_verified and ker.all_verified
    for b in range(3):
        for jid in ref.reconstructed[b]:
            assert np.array_equal(ref.reconstructed[b][jid],
                                  ker.reconstructed[b][jid])


@pytest.mark.parametrize("placed", (False, True), ids=("simulator", "placed"))
@pytest.mark.parametrize("scheme", SINGLE + MULTI)
def test_kernel_path_byte_identical(scheme, placed, rng):
    """The kernel path, whose round state lives on the device (the
    Pallas interpreter here), equals the numpy ref path and the serial
    oracle on three stripes of every scheme, simulator-placed or placed
    by `place_stripes`."""
    n, k, failed, seed = ((7, 4, (1, 5), 9) if scheme in MULTI
                          else (6, 3, (2,), 4))
    cluster = 12
    code = RSCode(n, k)
    plan = compile_plan(_plan_for(_scenario(n, k, failed, seed=seed,
                                            cluster=cluster), scheme, seed))
    if placed:
        stripes = place_stripes(3, code, cluster)
        plans = [relabel_plan_nodes(plan, s.perm(cluster)) for s in stripes]
        bmaps = [s.block_map(cluster) for s in stripes]
    else:
        plans, bmaps = [plan] * 3, None
    cws = [code.encode(rng.integers(0, 256, size=(k, 200), dtype=np.uint8))
           for _ in plans]
    ref = execute_plans_batch(plans, code, cws, block_of=bmaps)
    with interpreted():
        ker = execute_plans_batch(plans, code, cws, block_of=bmaps)
    assert ref.all_verified and ker.all_verified
    assert np.array_equal(ref.bytes_moved, ker.bytes_moved)
    for c, pa in enumerate(plans):
        ser = executor.execute_plan(
            decompile(pa), code, cws[c],
            block_of=None if bmaps is None else bmaps[c])
        assert ser.verified and int(ker.bytes_moved[c]) == ser.bytes_moved
        assert (ker.reconstructed[c].keys() == ref.reconstructed[c].keys()
                == ser.reconstructed.keys())
        for jid, blk in ser.reconstructed.items():
            assert np.array_equal(ker.reconstructed[c][jid], np.asarray(blk))
            assert np.array_equal(ref.reconstructed[c][jid], np.asarray(blk))
        for j, f in enumerate(failed):
            assert np.array_equal(ker.reconstructed[c][j], cws[c][f])


# -------------------------------------------------------- hypothesis sweep
try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dependency
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @given(
        code_i=st.integers(0, 2),
        pattern=st.sampled_from(("single", "double", "rack")),
        scheme_i=st.integers(0, 4),
        seed=st.integers(0, 2**16),
    )
    @settings(max_examples=30, deadline=None)
    def test_random_plans_byte_identical_property(code_i, pattern,
                                                  scheme_i, seed):
        """For random (code, failure pattern, scheme, seed) draws the
        batched data plane is byte-identical to the serial oracle and to
        `codeword[failed]` — every job, every scheme family."""
        n, k = ((6, 3), (7, 4), (6, 4))[code_i]
        rng = np.random.default_rng(seed)
        failed = sample_failures(rng, n, k, pattern)
        pool = SINGLE if len(failed) == 1 else MULTI
        scheme = pool[scheme_i % len(pool)]
        sc = _scenario(n, k, failed, seed=seed % 1024, cluster=n + 4)
        plan = _plan_for(sc, scheme, seed % 1024)
        code = RSCode(n, k)
        cw = code.encode(rng.integers(0, 256, size=(k, 160), dtype=np.uint8))
        ser, bat = _exec_both(plan, code, cw)
        assert ser.verified and bat.all_verified
        assert int(bat.bytes_moved[0]) == ser.bytes_moved
        for j, f in enumerate(failed):
            assert np.array_equal(bat.reconstructed[0][j], cw[f])
            assert np.array_equal(np.asarray(ser.reconstructed[j]), cw[f])


# ------------------------------------------------------------ PPT lowering
def test_ppt_round_plan_validates_and_folds(rng):
    sc = _scenario(6, 3, (0,), seed=7, cluster=12)
    tree = build_ppt_tree(sc.make_jobs()[0], sc.bw.matrix_at(0.0))
    plan = ppt_round_plan(tree)
    fanin = max((len(c) for c in tree.children.values()), default=1)
    validate_plan(plan, max_recv_per_round=max(fanin, 1))
    # deepest level sends first; the root ends holding every helper term
    assert plan.num_rounds == max(tree.depths().values())
    code = RSCode(6, 3)
    cw = code.encode(rng.integers(0, 256, size=(3, 512), dtype=np.uint8))
    ser, bat = _exec_both(plan, code, cw)
    assert ser.verified and bat.all_verified


# ------------------------------------------------- stripe placement replay
def test_placed_stripe_execution(rng):
    """Plans relabeled through a rotated `place_stripes` placement still
    reconstruct the placed stripe's lost block, batched and serial."""
    code = RSCode(6, 3)
    cluster = 11
    stripes = place_stripes(5, code, cluster)
    sc = _scenario(6, 3, (2,), seed=5, cluster=cluster)
    plan = compile_plan(_plan_for(sc, "bmf", 5))
    plans, cws, bmaps, serials = [], [], [], []
    for stripe in stripes:
        cw = code.encode(rng.integers(0, 256, size=(3, 333), dtype=np.uint8))
        pa = relabel_plan_nodes(plan, stripe.perm(cluster))
        bmap = stripe.block_map(cluster)
        serials.append(executor.execute_plan(
            decompile(pa), code, cw, block_of=bmap))
        plans.append(pa)
        cws.append(cw)
        bmaps.append(bmap)
    bat = execute_plans_batch(plans, code, cws, block_of=bmaps)
    assert bat.all_verified
    for b, (stripe, ser) in enumerate(zip(stripes, serials)):
        assert ser.verified
        # relabeled requestor holds the *placed* failed block, block 2
        assert np.array_equal(bat.reconstructed[b][0], cws[b][2])


# --------------------------------------------------------------- relabeling
def test_relabel_plan_nodes_roundtrip(rng):
    sc = _scenario(7, 4, (0, 1), seed=3, cluster=12)
    pa = compile_plan(_plan_for(sc, "msrepair", 3))
    perm = np.roll(np.arange(12), 5)          # a nontrivial permutation
    out = relabel_plan_nodes(pa, perm)
    validate_plan(decompile(out))             # renaming preserves validity
    inv = np.argsort(perm)
    back = relabel_plan_nodes(out, inv)
    assert decompile(back) == decompile(pa)
    # original untouched
    assert int(pa.t_src[0]) != int(out.t_src[0]) or perm[pa.t_src[0]] == pa.t_src[0]


def test_relabel_rejects_bad_perms():
    jobs = [Job(job_id=0, failed_node=0, requestor=0, helpers=(1, 2))]
    plan = RepairPlan(jobs=jobs, rounds=[Round(transfers=[
        Transfer(src=1, dst=0, job=0, terms=frozenset({1})),
        Transfer(src=2, dst=0, job=0, terms=frozenset({2})),
    ])])
    pa = compile_plan(plan)
    with pytest.raises(ValueError, match="cover"):
        relabel_plan_nodes(pa, np.array([0, 1]))          # too short
    with pytest.raises(ValueError, match="injective"):
        relabel_plan_nodes(pa, np.array([0, 1, 1]))       # collision


# --------------------------------------------------- executable invariants
def test_batched_consumed_source_raises(rng):
    """A later round sourcing a buffer consumed earlier is unexecutable:
    the batched engine refuses it instead of moving zeros."""
    jobs = [Job(job_id=0, failed_node=0, requestor=0, helpers=(1, 2))]
    bad = RepairPlan(jobs=jobs, rounds=[
        Round(transfers=[Transfer(src=1, dst=0, job=0,
                                  terms=frozenset({1}))]),
        Round(transfers=[Transfer(src=1, dst=0, job=0,
                                  terms=frozenset({1}))]),   # 1 already sent
    ])
    code = RSCode(4, 2)
    cw = code.encode(rng.integers(0, 256, size=(2, 64), dtype=np.uint8))
    with pytest.raises(ValueError, match="holds no buffer"):
        execute_plans_batch([bad], [code], [cw])


def test_batched_consumed_source_raises_on_kernel_path(rng,
                                                      interpreted_kernels):
    """The same refusal where the round state lives on the device: the
    source check runs on the host's occupancy before any gather."""
    jobs = [Job(job_id=0, failed_node=0, requestor=0, helpers=(1, 2))]
    bad = RepairPlan(jobs=jobs, rounds=[
        Round(transfers=[Transfer(src=1, dst=0, job=0,
                                  terms=frozenset({1}))]),
        Round(transfers=[Transfer(src=1, dst=0, job=0,
                                  terms=frozenset({1}))]),   # 1 already sent
    ])
    code = RSCode(4, 2)
    cw = code.encode(rng.integers(0, 256, size=(2, 64), dtype=np.uint8))
    with pytest.raises(ValueError, match=r"round 1: case 0 transfer sources "
                       r"slot \(job 0, node 1\) which holds no buffer"):
        execute_plans_batch([bad], [code], [cw])


@pytest.mark.parametrize("path", (contextlib.nullcontext, interpreted),
                         ids=("ref", "kernel"))
def test_consumed_buffer_refilled_later(path, rng):
    """A node that sends its buffer and receives again in a later round
    starts from an empty buffer: the consume must clear the old bytes on
    both paths, or the requestor ends with the wrong block."""
    jobs = [Job(job_id=0, failed_node=0, requestor=0, helpers=(1, 2))]
    plan = RepairPlan(jobs=jobs, rounds=[
        Round(transfers=[Transfer(src=1, dst=2, job=0,
                                  terms=frozenset({1}))]),
        Round(transfers=[Transfer(src=2, dst=1, job=0,
                                  terms=frozenset({1, 2}))]),
        Round(transfers=[Transfer(src=1, dst=0, job=0,
                                  terms=frozenset({1, 2}))]),
    ])
    validate_plan(plan)
    code = RSCode(4, 2)
    cw = code.encode(rng.integers(0, 256, size=(2, 64), dtype=np.uint8))
    ser = executor.execute_plan(plan, code, cw)
    with path():
        bat = execute_plans_batch([plan], [code], [cw])
    assert ser.verified and bat.all_verified
    assert np.array_equal(bat.reconstructed[0][0], cw[0])


def test_batched_incomplete_plan_not_verified(rng):
    """A structurally fine but incomplete plan (requestor never receives
    everything) is reported unverified, not crashed."""
    jobs = [Job(job_id=0, failed_node=0, requestor=0, helpers=(1, 2))]
    partial = RepairPlan(jobs=jobs, rounds=[
        Round(transfers=[Transfer(src=1, dst=0, job=0,
                                  terms=frozenset({1}))]),
    ])
    code = RSCode(4, 2)
    cw = code.encode(rng.integers(0, 256, size=(2, 64), dtype=np.uint8))
    res = execute_plans_batch([partial], [code], [cw])
    assert not res.all_verified


def test_unplaced_block_raises_both_paths(rng):
    """A placement that leaves a failed/helper node without a block must
    fail loudly on both paths — -1 wrapping into python negative indexing
    would 'repair' the wrong block and self-consistently verify it."""
    code = RSCode(4, 2)
    cw = code.encode(rng.integers(0, 256, size=(2, 64), dtype=np.uint8))
    jobs = [Job(job_id=0, failed_node=0, requestor=0, helpers=(1, 2))]
    plan = RepairPlan(jobs=jobs, rounds=[
        Round(transfers=[Transfer(src=1, dst=2, job=0,
                                  terms=frozenset({1}))]),
        Round(transfers=[Transfer(src=2, dst=0, job=0,
                                  terms=frozenset({1, 2}))]),
    ])
    bad_map = np.array([-1, 1, 2, 3])      # failed node 0 unplaced
    with pytest.raises(ValueError, match="holds no block"):
        executor.execute_plan(plan, code, cw, block_of=bad_map)
    with pytest.raises(ValueError, match="holds no block"):
        execute_plans_batch([plan], [code], [cw], block_of=[bad_map])


def test_stripe_placement_accessors():
    code = RSCode(4, 2)
    [s0, s1] = place_stripes(2, code, 6)
    assert s1.node_ids == (4, 5, 0, 1)     # rotated placement
    bmap = s1.block_map(6)
    assert bmap.tolist() == [2, 3, -1, -1, 0, 1]
    perm = s1.perm(6)
    assert perm.tolist() == [4, 5, 0, 1, 2, 3]
    assert sorted(perm.tolist()) == list(range(6))   # a permutation
    with pytest.raises(ValueError, match="domains"):
        s1.block_map(3)


def test_identity_block_map():
    m = identity_block_map(6, 4)
    assert m.tolist() == [0, 1, 2, 3, -1, -1]
    assert identity_block_map(2, 4).tolist() == [0, 1, 2, 3]
