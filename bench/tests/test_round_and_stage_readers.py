"""The readers of `dataplane_ms_per_round.repair` and
`dataplane_stage_ms_per_helper_MiB.repair` on span totals set by hand:
the exact value where the program records the `round` span and the
`helper_bytes` counter, and None where it does not (a program from before
them, no batch span, no `repro.spans`, no lost bytes)."""
import sys
import types

import pytest

import harness
from conftest import BENCH

ROUND = "dataplane_ms_per_round.repair"
STAGE = "dataplane_stage_ms_per_helper_MiB.repair"
MS = 1_000_000                       # ns


def totals(*, batches=3, rounds=14, helper_bytes=250 * 2**20):
    """Span totals as `repro.spans` keeps them (times in ns): 3 batches
    of 14 rounds in all, 50 ms of rounds, 30 ms of staging 250 MiB."""
    out = {"repro.dataplane.batch": {"count": batches, "total_ns": 900 * MS,
                                     "self_ns": 10 * MS, "jobs": 25,
                                     "rounds": rounds},
           "repro.dataplane.stage": {"count": batches, "total_ns": 30 * MS,
                                     "self_ns": 30 * MS},
           "repro.dataplane.gather": {"count": 17, "total_ns": 5 * MS,
                                      "self_ns": 5 * MS}}
    if rounds:
        out["repro.dataplane.round"] = {"count": rounds,
                                        "total_ns": 50 * MS,
                                        "self_ns": 2 * MS}
    if helper_bytes:
        out["repro.dataplane.stage"]["helper_bytes"] = helper_bytes
    return out


def read(name, lost_bytes=25 * 2**20):
    ctx = types.SimpleNamespace(calls=[], trace=None, e2e={}, peak=None,
                                lost_bytes=lost_bytes)
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(ctx)


@pytest.fixture
def recorded(monkeypatch):
    """Sets the program's span totals."""
    import repro.spans as spans

    return lambda t: monkeypatch.setattr(spans, "_totals", t)


def test_ms_per_round_is_round_time_over_rounds(recorded):
    recorded(totals())
    assert read(ROUND) == pytest.approx(50 / 14)


def test_stage_ms_per_helper_mib_is_stage_self_time_over_mib(recorded):
    recorded(totals())
    assert read(STAGE) == pytest.approx(30 / 250)


@pytest.mark.parametrize("name", (ROUND, STAGE))
@pytest.mark.parametrize("case", ("before_round_spans", "no_batch",
                                  "no_module", "no_lost_bytes"))
def test_reader_returns_none_without_its_spans(name, case, recorded,
                                               monkeypatch):
    recorded(totals(batches=0 if case == "no_batch" else 3,
                    rounds=0 if case == "before_round_spans" else 14,
                    helper_bytes=0 if case == "before_round_spans" else 1))
    if case == "no_module":
        monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert read(name, lost_bytes=0 if case == "no_lost_bytes"
                else 2**20) is None
