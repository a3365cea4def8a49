"""The Facebook-warehouse RS(14,10) deployment through the batched data
plane, at 256-byte cells on the CPU.

The pool is the benchmark cell's own (`fb-warehouse-rs-14-10.repair`):
the repair driver's set-up builds it from the deployment file and the
`repair` traffic mix, so it holds the cell's plans (64 nodes, `plan_seed`
7: 63 single failures planned by BMF and 1 double planned by MSRepair),
placements and batches, with bytes encoded by the benchmark's reference
`bench/gfref.py`. Every batch runs through `execute_plans_batch` on the
device store (Pallas kernels in interpret mode) and on the host store,
and each rebuilt block is compared with gfref's decode and with the
serial `executor.execute_plan`. The batch counters of `repro.spans` are
pinned per batch.
"""
import contextlib
import importlib.util
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from conftest import interpreted
from repro import spans
from repro.core import executor
from repro.core.engine.arrays import decompile
from repro.core.engine.dataplane import execute_plans_batch

BENCH = Path(__file__).resolve().parents[1] / "bench"
NBYTES = 256
SEED = 2**31 + 1411
BATCHES = range(8)              # 64 stripes, 8 per batch


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(
        "fb_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def fb():
    """(the driver's workload, gfref): the cell's set-up at 256-byte cells,
    its warm pass left out."""
    deployment = json.loads(
        (BENCH / "deployments" / "fb-warehouse-rs-14-10.json").read_text())
    deployment["cell_bytes"] = NBYTES
    traffic = json.loads((BENCH / "traffic" / "repair.json").read_text())
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(str(BENCH))      # the driver imports its helpers
        gfref = _load(BENCH / "gfref.py")
        driver = _load(BENCH / "drivers" / "repair.py")
        wl = driver.workload(deployment, traffic, SEED, platform=None,
                             repair=lambda wl, b: None)
    return wl, gfref


def _run(wl, b: int, path=interpreted):
    idx = wl.batches[b]
    with path():
        return execute_plans_batch(
            [wl.compiled[s] for s in idx], wl.code,
            [wl.codewords[s] for s in idx],
            block_of=[wl.block_maps[s] for s in idx])


def _has_double(wl, b: int) -> bool:
    return any(len(wl.plans[s].jobs) == 2 for s in wl.batches[b])


def test_pool_is_the_deployments_mix(fb):
    wl, _ = fb
    assert (wl.n, wl.k, wl.nodes, wl.per_batch) == (14, 10, 64, 8)
    assert sorted(len(p.jobs) for p in wl.plans) == [1] * 63 + [2]
    assert sum(_has_double(wl, b) for b in BATCHES) == 1
    assert len(wl.batches) == len(BATCHES)


@pytest.mark.parametrize("path", (interpreted, contextlib.nullcontext),
                         ids=("device_store", "host_store"))
@pytest.mark.parametrize("b", BATCHES)
def test_batch_matches_gfref_and_serial(fb, b, path):
    wl, gfref = fb
    res = _run(wl, b, path)
    assert res.all_verified
    for pos, s in enumerate(wl.batches[b]):
        plan, cw = wl.plans[s], wl.codewords[s]
        hops = sum(len(t.path) - 1 for r in plan.rounds for t in r.transfers)
        assert int(res.bytes_moved[pos]) == NBYTES * hops
        ser = executor.execute_plan(decompile(wl.compiled[s]), wl.code, cw,
                                    block_of=wl.block_maps[s])
        assert ser.bytes_moved == int(res.bytes_moved[pos])
        jobs = {j.job_id for j in plan.jobs}
        assert res.reconstructed[pos].keys() == jobs
        assert ser.reconstructed.keys() == jobs
        for job in plan.jobs:
            # planner node ids below n are block positions
            helpers = list(job.helpers)
            want = gfref.combine(
                gfref.repair_row(wl.n, wl.k, job.failed_node, helpers),
                cw[helpers])
            assert np.array_equal(want, cw[job.failed_node])
            assert np.array_equal(res.reconstructed[pos][job.job_id], want)
            assert np.array_equal(
                np.asarray(ser.reconstructed[job.job_id]), want)


@pytest.fixture(scope="module")
def counted(fb, tmp_path_factory):
    """Per batch, what `spans.totals()` gained while it ran traced on the
    device store."""
    wl, _ = fb
    keys = {"jobs": ("repro.dataplane.batch", "jobs"),
            "rounds": ("repro.dataplane.batch", "rounds"),
            "device_rounds": ("repro.dataplane.batch", "device_rounds"),
            "round_spans": ("repro.dataplane.round", "count"),
            "helper_bytes": ("repro.dataplane.stage", "helper_bytes")}
    out = []
    with jax.profiler.trace(str(tmp_path_factory.mktemp("fb"))):
        for b in BATCHES:
            before = spans.totals()
            _run(wl, b)
            after = spans.totals()
            out.append({k: after.get(n, {}).get(c, 0)
                        - before.get(n, {}).get(c, 0)
                        for k, (n, c) in keys.items()})
    return out


@pytest.mark.parametrize("b", BATCHES)
def test_batch_counters(fb, counted, b):
    """8 jobs in 4 rounds from 80 helper chunks for a batch of single
    failures; the MSRepair double adds a job, 10 chunks and 2 rounds,
    which the whole batch then runs."""
    wl, _ = fb
    jobs, rounds, helpers = (9, 6, 90) if _has_double(wl, b) else (8, 4, 80)
    got = counted[b]
    assert got["jobs"] == jobs
    assert got["rounds"] == got["round_spans"] == got["device_rounds"] \
        == rounds
    assert got["helper_bytes"] == helpers * NBYTES
