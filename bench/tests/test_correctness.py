"""`correct` of a repair cell: true for the program, false for the control
and for each fault the cell can have.

The runs skip the harness's look for a chip and drive the rest of a cell
(set-up from the seed, a short window, the check) at a size a CPU holds.
The program's batched data plane takes its numpy path here; the faults
are planted in the program's functions that the timed path calls. The
cells run on one chip, so there is no exchange between chips to leave out.
"""
import json
import time

import numpy as np
import pytest

import control
import harness
from conftest import ROOT
from seams import patched

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
SMALL = {"deployment": {"cell_bytes": 256},
         "traffic": {"pool_stripes": 16}}
SEED = 2**31 + 4242


def run(cell, hooks=None):
    return harness.run_cell(SPEC, cell, seed=SEED, seconds=0.3, trace=False,
                            root=ROOT, t0=time.perf_counter(), platform=None,
                            overrides=SMALL, hooks=hooks)


def state_unchanged(_fn):
    """The premultiply hands its input back unscaled."""
    def inner(coeffs, data, **kw):
        return np.asarray(data)
    return inner


def answer_altered(fn):
    """One byte of one folded row raised by one where the fold produces it
    (an XOR flip would cancel itself over an even number of rounds)."""
    def inner(chunks, groups, **kw):
        out = np.array(fn(chunks, groups, **kw))
        out[0, 0] = (int(out[0, 0]) + 1) % 256
        return out
    return inner


def half_batch(fn):
    """Only the first half of the batch's stripes is repaired."""
    def inner(plans, codes, codewords, *, block_of=None, **kw):
        h = len(plans) // 2
        res = fn(plans[:h], codes, codewords[:h],
                 block_of=None if block_of is None else block_of[:h], **kw)
        pad = len(plans) - h
        res.reconstructed += [{} for _ in range(pad)]
        res.verified = np.concatenate([res.verified, np.ones(pad, bool)])
        res.bytes_moved = np.concatenate(
            [res.bytes_moved, np.zeros(pad, np.int64)])
        return res
    return inner


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_reference_in_the_programs_place_is_correct(cell):
    out = run(cell, hooks={"repair": control.reference_repair})
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    out = run(cell, hooks={"repair": control.control_repair})
    assert not out["correct"]
    assert out["checks"]["wrong_blocks"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered",
                                   "half_batch"])
def test_fault_is_not_correct(cell, fault):
    from repro.core.engine import dataplane
    from repro.kernels import ops

    where = {"state_unchanged": (ops, "gf256_scale_batch", state_unchanged),
             "answer_altered": (ops, "xor_reduce_segments", answer_altered),
             "half_batch": (dataplane, "execute_plans_batch", half_batch)}
    with patched(*where[fault]):
        out = run(cell)
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0
