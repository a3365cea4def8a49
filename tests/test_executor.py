"""Data-plane execution: every scheme's plan reconstructs exact bytes."""
import numpy as np
import pytest

from repro.core import executor, topology
from repro.core.bandwidth import BandwidthProcess, IngressModel
from repro.core.simulator import RepairSimulator, Scenario
from repro.ec.rs import RSCode


def _run(n, k, failed, scheme, seed=0, cluster=None):
    cluster = cluster or n + 2
    m = topology.heterogeneous_matrix(cluster, low=3, high=30, seed=seed)
    bwp = BandwidthProcess(base=m, change_interval=2.0, seed=seed,
                           mode="markov")
    sc = Scenario(num_nodes=cluster, code=RSCode(n, k), failed=failed,
                  bw=bwp, ingress=IngressModel(seed=seed), chunk_mb=4.0)
    return RepairSimulator(sc).run(scheme)


@pytest.mark.parametrize("n,k", [(4, 2), (6, 3), (7, 4)])
@pytest.mark.parametrize("scheme", ["traditional", "ppr", "bmf"])
def test_single_failure_byte_exact(n, k, scheme, rng):
    code = RSCode(n, k)
    data = rng.integers(0, 256, size=(k, 2048), dtype=np.uint8)
    cw = code.encode(data)
    res = _run(n, k, (0,), scheme)
    ex = executor.execute_plan(res.plan, code, cw)
    assert ex.verified
    assert np.array_equal(ex.reconstructed[0], cw[0])


@pytest.mark.parametrize("n,k", [(6, 3), (7, 4)])
@pytest.mark.parametrize("scheme", ["mppr", "random", "msrepair"])
def test_multi_failure_byte_exact(n, k, scheme, rng):
    code = RSCode(n, k)
    data = rng.integers(0, 256, size=(k, 1024), dtype=np.uint8)
    cw = code.encode(data)
    res = _run(n, k, (0, 1), scheme)
    ex = executor.execute_plan(res.plan, code, cw)
    assert ex.verified


def test_parity_failure_repairs(rng):
    code = RSCode(6, 3)
    data = rng.integers(0, 256, size=(3, 512), dtype=np.uint8)
    cw = code.encode(data)
    res = _run(6, 3, (4,), "bmf", seed=3)      # a parity node
    ex = executor.execute_plan(res.plan, code, cw)
    assert ex.verified


def test_consumed_source_raises_clear_error(rng):
    """Store-and-forward consumes a source's buffer when it sends: a plan
    whose later round re-sources it is unexecutable and must fail loudly
    (the store.pop audit), not KeyError or silently move zeros."""
    from repro.core.plan import Job, RepairPlan, Round, Transfer

    code = RSCode(4, 2)
    cw = code.encode(rng.integers(0, 256, size=(2, 64), dtype=np.uint8))
    jobs = [Job(job_id=0, failed_node=0, requestor=0, helpers=(1, 2))]
    bad = RepairPlan(jobs=jobs, rounds=[
        Round(transfers=[Transfer(src=1, dst=0, job=0,
                                  terms=frozenset({1}))]),
        Round(transfers=[Transfer(src=1, dst=0, job=0,
                                  terms=frozenset({1}))]),
    ])
    with pytest.raises(ValueError, match="holds no buffer"):
        executor.execute_plan(bad, code, cw)
    # validate_plan rejects the same plan up front — the executor
    # invariant is exactly "validate_plan-clean"
    from repro.core.plan import validate_plan

    with pytest.raises(ValueError):
        validate_plan(bad)


def test_source_refilled_across_rounds_is_fine(rng):
    """A node may send again in a later round once a new fragment arrived
    — consumption is per buffer, not per node."""
    from repro.core.plan import Job, RepairPlan, Round, Transfer, validate_plan

    code = RSCode(4, 2)
    cw = code.encode(rng.integers(0, 256, size=(2, 64), dtype=np.uint8))
    jobs = [Job(job_id=0, failed_node=0, requestor=0, helpers=(1, 2))]
    plan = RepairPlan(jobs=jobs, rounds=[
        Round(transfers=[Transfer(src=2, dst=1, job=0,
                                  terms=frozenset({2}))]),
        Round(transfers=[Transfer(src=1, dst=0, job=0,
                                  terms=frozenset({1, 2}))]),
    ])
    validate_plan(plan)
    ex = executor.execute_plan(plan, code, cw)
    assert ex.verified
    assert ex.bytes_moved == 2 * 64


def test_bytes_moved_relay_accounting(rng):
    """Relays re-send whole chunks: a path of length L moves (L-1)*nbytes.
    Pinned exactly on a hand-built relayed plan (regression for the
    previously untested accounting)."""
    from repro.core.plan import Job, RepairPlan, Round, Transfer, validate_plan

    code = RSCode(4, 2)
    nbytes = 128
    cw = code.encode(rng.integers(0, 256, size=(2, nbytes), dtype=np.uint8))
    jobs = [Job(job_id=0, failed_node=0, requestor=0, helpers=(1, 2))]
    plan = RepairPlan(jobs=jobs, rounds=[
        Round(transfers=[
            Transfer(src=1, dst=0, job=0, terms=frozenset({1})),
            # 2 -> 0 relayed through idle nodes 4 and 5: 3 hops
            Transfer(src=2, dst=0, job=0, terms=frozenset({2}),
                     path=(2, 4, 5, 0)),
        ]),
    ])
    validate_plan(plan, max_recv_per_round=2)
    ex = executor.execute_plan(plan, code, cw)
    assert ex.verified
    assert ex.bytes_moved == nbytes * (1 + 3)
    from repro.core.engine.dataplane import execute_plans_batch

    bat = execute_plans_batch([plan], [code], [cw])
    assert int(bat.bytes_moved[0]) == ex.bytes_moved


def test_execute_plan_block_of_placement(rng):
    """`block_of` decouples node ids from codeword positions: executing
    under a shifted placement reconstructs the placed block."""
    from repro.core.plan import Job, RepairPlan, Round, Transfer

    code = RSCode(4, 2)
    cw = code.encode(rng.integers(0, 256, size=(2, 96), dtype=np.uint8))
    # node 10 holds block 0 (failed), nodes 11/12 blocks 1/2
    jobs = [Job(job_id=0, failed_node=10, requestor=10, helpers=(11, 12))]
    plan = RepairPlan(jobs=jobs, rounds=[
        Round(transfers=[Transfer(src=11, dst=12, job=0,
                                  terms=frozenset({11}))]),
        Round(transfers=[Transfer(src=12, dst=10, job=0,
                                  terms=frozenset({11, 12}))]),
    ])
    block_of = np.full(13, -1, dtype=np.int64)
    block_of[[10, 11, 12]] = [0, 1, 2]
    ex = executor.execute_plan(plan, code, cw, block_of=block_of)
    assert ex.verified
    assert np.array_equal(ex.reconstructed[0], cw[0])


def test_relays_move_extra_bytes(rng):
    """A relayed plan moves more bytes than rounds*chunk (store&forward)."""
    code = RSCode(6, 3)
    data = rng.integers(0, 256, size=(3, 256), dtype=np.uint8)
    cw = code.encode(data)
    found = False
    for seed in range(25):
        res = _run(6, 3, (0,), "bmf", seed=seed, cluster=12)
        if res.relay_hops > 0:
            ex = executor.execute_plan(res.plan, code, cw)
            assert ex.verified
            direct = sum(len(r.transfers) for r in res.plan.rounds) * 256
            assert ex.bytes_moved > direct
            found = True
            break
    assert found, "no BMF relay found in 25 seeds — suspicious"
