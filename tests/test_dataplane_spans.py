"""Program spans of the byte data plane (`repro.spans`): what a profiler
trace shows of `execute_plans_batch`, what `spans.totals()` records, that
nothing is recorded with the profiler off, and the benchmark's readers of
those totals (`bench/metrics/`)."""
import glob
import importlib.util
import os
import sys
import types
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from conftest import interpreted
from repro import spans
from repro.core import executor, topology
from repro.core.bandwidth import BandwidthProcess, IngressModel
from repro.core.engine.arrays import (compile_plan, decompile,
                                      relabel_plan_nodes)
from repro.core.engine.dataplane import (execute_plans_batch,
                                        identity_block_map)
from repro.core.simulator import Scenario
from repro.ec.rs import RSCode
from repro.ec.stripe import place_stripes
from repro.kernels import ops
from repro.sim.sweep import _verify_plan

METRICS = Path(__file__).resolve().parents[1] / "bench" / "metrics"
NBYTES = 256
BATCH = "repro.dataplane.batch"
ROUND = "repro.dataplane.round"
GATHER = "repro.dataplane.gather"
STEPS = ("prepare", "stage", "premultiply", "d2h", "scatter", "round",
         "gather", "fold", "accumulate", "verify")
NAMES = (BATCH,) + tuple(f"repro.dataplane.{s}" for s in STEPS)
# what one round of the round loop holds
ROUND_STEPS = tuple(f"repro.dataplane.{s}"
                    for s in ("gather", "fold", "accumulate"))
READERS = ("dataplane_host_ms_per_lost_MiB.repair",
           "dataplane_copy_back_ms_per_lost_MiB.repair",
           "dataplane_gf_call_ms_per_lost_MiB.repair",
           "device_to_host_bytes_per_lost_byte.repair",
           "dataplane_ms_per_round.repair",
           "dataplane_stage_ms_per_helper_MiB.repair")


def _batch(kind: str) -> dict:
    """Three stripes: placed RS(9,6) with one lost block under BMF;
    RS(14,10) with two lost blocks under MSRepair (simulator placement);
    or placed RS(14,10) mixing BMF singles with an MSRepair double, as a
    batch of the Facebook-warehouse cell holds, so its plans differ in
    jobs and rounds."""
    n, k, cluster, placed, stripes = {
        "rs96_bmf_placed": (9, 6, 12, True, [((2,), "bmf")] * 3),
        "rs1410_msrepair": (14, 10, 16, False, [((1, 5), "msrepair")] * 3),
        "rs1410_mixed_placed": (14, 10, 16, True, [
            ((2,), "bmf"), ((1, 5), "msrepair"), ((7,), "bmf")]),
    }[kind]
    code = RSCode(n, k)
    rng = np.random.default_rng(n)
    m = topology.heterogeneous_matrix(cluster, low=3, high=30, seed=n)
    plans = []
    for failed, scheme in stripes:
        sc = Scenario(num_nodes=cluster, code=code, failed=failed,
                      bw=BandwidthProcess(base=m, change_interval=2.0,
                                          seed=n, mode="markov"),
                      ingress=IngressModel(seed=n), chunk_mb=4.0)
        plans.append(compile_plan(
            _verify_plan(sc, scheme, n, bmf_optimize_all=False)))
    cws = [code.encode(rng.integers(0, 256, size=(k, NBYTES), dtype=np.uint8))
           for _ in stripes]
    bmaps = None
    if placed:
        placed = place_stripes(len(stripes), code, cluster)
        plans = [relabel_plan_nodes(pa, s.perm(cluster))
                 for pa, s in zip(plans, placed)]
        bmaps = [s.block_map(cluster) for s in placed]
    return dict(plans=plans, code=code, cws=cws, block_of=bmaps)


def _run(b: dict):
    with interpreted():
        return execute_plans_batch(b["plans"], b["code"], b["cws"],
                                   block_of=b["block_of"])


def _traced(b: dict, tmp: str) -> types.SimpleNamespace:
    """The batch, its result, `spans.totals()` before and after, and the
    trace's `caller` and program span events."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    before = spans.totals()
    with jax.profiler.trace(tmp, profiler_options=opts):
        with TraceAnnotation("caller"):
            res = _run(b)
    after = spans.totals()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    events = []                      # (line, name, start_ns, end_ns)
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            events += [((plane.name, i), e.name, e.start_ns,
                        e.start_ns + e.duration_ns) for e in line.events
                       if e.name == "caller" or e.name.startswith("repro.")]
    return types.SimpleNamespace(b=b, res=res, before=before, after=after,
                                 events=events)


def _delta(before: dict, after: dict, name: str, key: str):
    return after.get(name, {}).get(key, 0) - before.get(name, {}).get(key, 0)


def _inside(ev, outer) -> bool:
    return (ev[0] == outer[0] and outer[2] <= ev[2] and ev[3] <= outer[3]
            and ev is not outer)


def _d2h_bytes(b: dict) -> int:
    """One requestor row per job x cell bytes: the round state stays on
    the device, and only the rebuilt blocks come back."""
    return sum(pa.num_jobs for pa in b["plans"]) * NBYTES


def _rounds_run(b: dict) -> int:
    """Rounds in which some plan of the batch has a transfer."""
    return len({r for pa in b["plans"] for r in range(pa.num_rounds)
                if len(pa.t_src[pa.round_rows(r)])})


def _lost_bytes(b: dict) -> int:
    return NBYTES * sum(pa.num_jobs for pa in b["plans"])


def _helper_bytes(b: dict) -> int:
    return NBYTES * sum(int(pa.job_helpers_len.sum()) for pa in b["plans"])


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


KINDS = ("rs96_bmf_placed", "rs1410_msrepair", "rs1410_mixed_placed")


@pytest.fixture(scope="module", params=KINDS)
def traced(request, tmp_path_factory):
    return _traced(_batch(request.param),
                   str(tmp_path_factory.mktemp(request.param)))


def test_every_span_is_in_the_trace(traced):
    assert {e[1] for e in traced.events} == set(NAMES) | {"caller"}


def test_steps_nest_in_batch_nest_in_caller(traced):
    events = traced.events
    callers = [e for e in events if e[1] == "caller"]
    batches = [e for e in events if e[1] == BATCH]
    rounds = [e for e in events if e[1] == ROUND]
    assert len(callers) == 1 and len(batches) == 1
    assert _inside(batches[0], callers[0])
    for e in events:
        if e[1] not in ("caller", BATCH):
            assert _inside(e, batches[0]), e[1]
        if e[1] not in ("caller", BATCH, ROUND):
            # steps are leaves: none holds another (d2h is no child of
            # the GF call before it)
            assert not any(_inside(o, e) for o in events), e[1]
            # a round holds its gather, fold and accumulate; the other
            # steps are siblings of the rounds
            held = sum(_inside(e, r) for r in rounds)
            if e[1] in ROUND_STEPS[1:]:
                assert held == 1, e[1]
            elif e[1] != GATHER:
                assert held == 0, e[1]
    for r in rounds:
        inside = sorted(o[1] for o in events if _inside(o, r))
        # an empty round stops in its gather
        assert inside in ([GATHER], sorted(ROUND_STEPS)), inside
    # after the last round, one gather takes the requestor rows
    (last,) = [e for e in events if e[1] == GATHER
               and not any(_inside(e, r) for r in rounds)]
    assert all(r[3] <= last[2] for r in rounds)


@pytest.mark.parametrize("name", NAMES)
def test_trace_counts_match_totals(traced, name):
    in_file = sum(e[1] == name for e in traced.events)
    assert in_file == _delta(traced.before, traced.after, name, "count") > 0


@pytest.mark.parametrize("name", NAMES)
def test_trace_self_time_matches_totals(traced, name):
    events = traced.events
    self_ns = 0
    for e in (e for e in events if e[1] == name):
        inner = [o for o in events
                 if o[1].startswith("repro.") and _inside(o, e)]
        children = [o for o in inner if not any(_inside(o, p) for p in inner)]
        self_ns += (e[3] - e[2]) - sum(o[3] - o[2] for o in children)
    recorded = _delta(traced.before, traced.after, name, "self_s")
    assert abs(self_ns * 1e-9 - recorded) <= max(1e-3, 0.05 * recorded)


def test_d2h_bytes_count_every_result(traced):
    assert _delta(traced.before, traced.after, "repro.dataplane.d2h",
                  "bytes") == _d2h_bytes(traced.b)


@pytest.mark.parametrize("kind", KINDS)
def test_d2h_bytes_zero_on_numpy_path(kind, tmp_path):
    """The numpy ref path hands back host arrays: nothing is copied, and
    no round's state lives on the device."""
    b = _batch(kind)
    before = spans.totals()
    with jax.profiler.trace(str(tmp_path)):
        execute_plans_batch(b["plans"], b["code"], b["cws"],
                            block_of=b["block_of"])
    after = spans.totals()
    assert _delta(before, after, "repro.dataplane.d2h", "count") > 0
    assert _delta(before, after, "repro.dataplane.d2h", "bytes") == 0
    assert _delta(before, after, BATCH, "device_rounds") == 0


def test_device_rounds_count_every_round_on_kernel_path(traced):
    rounds = _rounds_run(traced.b)
    assert rounds > 0
    assert _delta(traced.before, traced.after, BATCH,
                  "device_rounds") == rounds


def test_batch_counts_jobs_rounds_and_helper_bytes(traced):
    """`jobs` is the lost blocks rebuilt, `rounds` the batch's longest
    plan, one `round` span each (an empty round too), and `helper_bytes`
    the chunks staged for the premultiply."""
    b = traced.b

    def got(name, key):
        return _delta(traced.before, traced.after, name, key)

    assert got(BATCH, "jobs") == sum(pa.num_jobs for pa in b["plans"])
    assert got(BATCH, "rounds") == got(ROUND, "count") == max(
        pa.num_rounds for pa in b["plans"])
    assert got("repro.dataplane.stage", "helper_bytes") == _helper_bytes(b)


def _helper_rows(b: dict) -> list[tuple[int, int]]:
    """(case, block) of every helper chunk, in staging order: case by
    case, job by job, each job's helpers in plan order."""
    out = []
    for c, pa in enumerate(b["plans"]):
        bmap = (identity_block_map(pa.num_nodes, b["code"].n)
                if b["block_of"] is None else b["block_of"][c])
        for j in range(pa.num_jobs):
            hs = pa.job_helpers[j, :int(pa.job_helpers_len[j])]
            out += [(c, int(blk)) for blk in bmap[hs]]
    return out


@pytest.mark.parametrize("kind", KINDS)
def test_gf_steps_get_device_chunks_and_round_payloads(kind, monkeypatch):
    """The premultiply gets the (M, nbytes) helper chunks on the device,
    byte for byte every job's helper rows in staging order; each fold
    gets exactly its round's (T_r, nbytes) payload, on the device, and
    its (G_r, Kmax) groups: the byte counts the benchmark's rooflines
    take from these arguments stay the work done."""
    b = _batch(kind)
    seen = []

    def spy(name):
        orig = getattr(ops, name)

        def inner(x, y, **kw):
            seen.append((name, x, y))
            return orig(x, y, **kw)
        return inner

    for name in ("gf256_scale_batch", "xor_reduce_segments"):
        monkeypatch.setattr(ops, name, spy(name))
    res = _run(b)
    assert res.all_verified
    (pname, coeffs, chunks), *folds = seen
    helpers = sum(int(pa.job_helpers_len.sum()) for pa in b["plans"])
    assert pname == "gf256_scale_batch"
    assert isinstance(chunks, jax.Array)
    assert chunks.shape == (helpers, NBYTES) and coeffs.shape == (helpers,)
    assert np.array_equal(np.asarray(chunks), np.stack(
        [b["cws"][c][blk] for c, blk in _helper_rows(b)]))
    assert len(folds) == _rounds_run(b)
    for r, (fname, payload, groups) in enumerate(folds):
        sl = [pa.round_rows(r) if r < pa.num_rounds else slice(0, 0)
              for pa in b["plans"]]
        t_r = sum(len(pa.t_src[s]) for pa, s in zip(b["plans"], sl))
        g_r = sum(len(set(zip(pa.t_job_idx[s].tolist(),
                              pa.t_dst[s].tolist())))
                  for pa, s in zip(b["plans"], sl))
        assert fname == "xor_reduce_segments"
        assert isinstance(payload, jax.Array)
        assert payload.shape == (t_r, NBYTES)
        assert groups.shape[0] == g_r
        assert sorted(groups[groups >= 0].tolist()) == list(range(t_r))


def _serial_match(b: dict, res) -> None:
    """`res` is byte for byte what serial `execute_plan` gives, case by
    case, with the same bytes moved."""
    assert res.all_verified
    for c, (pa, cw) in enumerate(zip(b["plans"], b["cws"])):
        ser = executor.execute_plan(
            decompile(pa), b["code"], cw,
            block_of=None if b["block_of"] is None else b["block_of"][c])
        assert int(res.bytes_moved[c]) == ser.bytes_moved
        assert res.reconstructed[c].keys() == ser.reconstructed.keys()
        for jid, blk in ser.reconstructed.items():
            assert np.array_equal(res.reconstructed[c][jid], np.asarray(blk))


@pytest.mark.parametrize("kernels", (True, False), ids=("kernel", "ref"))
@pytest.mark.parametrize("kind", KINDS)
def test_stage_hands_over_codeword_rows_uncopied(kind, kernels, monkeypatch):
    """Each helper chunk reaches `ops.stage_rows` as the row of the
    caller's codeword where it lies: no host copy comes before it, on
    either path, and the results stay byte-identical to serial
    `execute_plan`."""
    b = _batch(kind)
    handed = []
    orig = ops.stage_rows

    def spy(rows):
        handed.append(list(rows))
        return orig(rows)

    monkeypatch.setattr(ops, "stage_rows", spy)
    res = (_run(b) if kernels else execute_plans_batch(
        b["plans"], b["code"], b["cws"], block_of=b["block_of"]))
    (rows,) = handed
    want = _helper_rows(b)
    assert len(rows) == len(want)
    for row, (c, blk) in zip(rows, want):
        assert np.shares_memory(row, b["cws"][c][blk])
        assert row.shape == (NBYTES,)
    _serial_match(b, res)


@pytest.mark.parametrize("kernels", (True, False), ids=("kernel", "ref"))
@pytest.mark.parametrize("kind", KINDS)
def test_device_rows_count_chunks_staged_uncopied(kind, kernels, tmp_path):
    """`device_rows` on the stage span is every helper chunk where the
    kernels run, which hand the rows up as they lie, and 0 on the numpy
    path, which stacks them on the host."""
    b = _batch(kind)
    before = spans.totals()
    with jax.profiler.trace(str(tmp_path)):
        if kernels:
            _run(b)
        else:
            execute_plans_batch(b["plans"], b["code"], b["cws"],
                                block_of=b["block_of"])
    after = spans.totals()
    got = _delta(before, after, "repro.dataplane.stage", "device_rows")
    assert got == (len(_helper_rows(b)) if kernels else 0)
    assert _delta(before, after, "repro.dataplane.stage",
                  "helper_bytes") == _helper_bytes(b)


def test_profiler_off_records_nothing_and_changes_nothing(traced):
    b, traced_res = traced.b, traced.res
    before = spans.totals()
    res = _run(b)
    assert spans.totals() == before
    for got in (res, traced_res):
        _serial_match(b, got)
    assert np.array_equal(res.bytes_moved, traced_res.bytes_moved)


def test_profiler_off_costs_one_check_per_span(traced, monkeypatch):
    """Off, every span is one `is_enabled()` call and the shared null
    context: no annotation is made and no count is kept."""
    checks = []

    class Off:
        def __init__(self, name):
            raise AssertionError("no annotation may be made")

        @staticmethod
        def is_enabled():
            checks.append(1)
            return False

    monkeypatch.setattr(spans, "TraceAnnotation", Off)
    assert spans.span("a") is spans.span("b")
    checks.clear()
    _run(traced.b)
    assert len(checks) == sum(_delta(traced.before, traced.after, n, "count")
                              for n in NAMES)
    kept = spans.totals()
    spans.count("bytes", 1)                 # no open span: nothing kept
    assert spans.totals() == kept


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("reader", READERS)
def test_reader_reads_the_window(kind, reader, monkeypatch, tmp_path):
    """Each reader, on a context built after a traced call, returns its
    value by hand from fresh totals."""
    monkeypatch.setattr(spans, "_totals", {})
    b = _batch(kind)
    _traced(b, str(tmp_path))
    t = spans.totals()
    lost = _lost_bytes(b)
    mib = lost / 2**20

    def self_ms(*steps):
        return 1e3 * sum(t[f"repro.dataplane.{s}"]["self_s"]
                         for s in steps) / mib

    want = {
        READERS[0]: self_ms("prepare", "stage", "scatter", "gather",
                            "accumulate", "verify"),
        READERS[1]: self_ms("d2h"),
        READERS[2]: 1e3 * sum(t[f"repro.dataplane.{s}"]["total_s"]
                              for s in ("premultiply", "fold")) / mib,
        READERS[3]: _d2h_bytes(b) / lost,
        READERS[4]: 1e3 * t[ROUND]["total_s"] / t[ROUND]["count"],
        READERS[5]: 1e3 * t["repro.dataplane.stage"]["self_s"]
        / (_helper_bytes(b) / 2**20),
    }[reader]
    ctx = types.SimpleNamespace(calls=[], trace=None, e2e={},
                                lost_bytes=lost, peak=None)
    assert _reader(reader).read(ctx) == pytest.approx(want, rel=1e-12)
    assert want > 0


@pytest.mark.parametrize("case", ("no_module", "no_batch", "no_lost_bytes"))
@pytest.mark.parametrize("reader", READERS)
def test_reader_returns_none_without_spans(reader, case, monkeypatch):
    monkeypatch.setattr(spans, "_totals", {
        BATCH: {"count": 0 if case == "no_batch" else 1, "total_ns": 10,
                "self_ns": 1},
        **{f"repro.dataplane.{s}": {"count": 1, "total_ns": 1, "self_ns": 1,
                                    "bytes": 8} for s in STEPS}})
    if case == "no_module":
        monkeypatch.setitem(sys.modules, "repro.spans", None)
    ctx = types.SimpleNamespace(
        calls=[], trace=None, e2e={}, peak=None,
        lost_bytes=0 if case == "no_lost_bytes" else 1024)
    assert _reader(reader).read(ctx) is None


@pytest.mark.parametrize("reader", READERS[4:])
def test_reader_returns_none_without_its_own_spans(reader, monkeypatch):
    """A program that has the batch span and the steps before rounds and
    staged bytes were recorded (as before the `round` span and the
    `helper_bytes` counter) reads as nothing, not as zero."""
    monkeypatch.setattr(spans, "_totals", {
        BATCH: {"count": 1, "total_ns": 10, "self_ns": 1},
        **{f"repro.dataplane.{s}": {"count": 1, "total_ns": 1, "self_ns": 1}
           for s in STEPS if s != "round"}})
    ctx = types.SimpleNamespace(calls=[], trace=None, e2e={}, peak=None,
                                lost_bytes=1024)
    assert _reader(reader).read(ctx) is None
