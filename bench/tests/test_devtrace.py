"""The reduction from trace events to metrics, and the algorithmic byte
counts, on a hand-made trace with known answers and on a trace recorded
on a TPU v5e chip (one batch of the HDFS repair cell)."""
import json
import types
from pathlib import Path

import pytest

import devtrace
import roofline

RECORDED = Path(__file__).with_name("trace_hdfs_repair.json")
PEAK = {"hbm_bytes_per_s": 819e9}


def covered(merged, lo, hi):
    """Length of [lo, hi] that merged intervals cover, summed plainly: the
    check on `devtrace.Cover`'s bisection."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def hand_made():
    # window 0..100 ns; device busy 10-30, 25-40 (overlap), 60-70
    return {
        "devices": {"/device:TPU:0": [["%a = f()", 10, 30],
                                      ["%b = g()", 25, 40],
                                      ["%c = h()", 60, 70]]},
        "modules": {"/device:TPU:0": [["jit_f(1)", 10, 40],
                                      ["jit_h(2)", 60, 70]]},
        "spans": [["bench.window", 0, 100], ["bench.batch", 5, 90],
                  ["bench.premultiply#0", 8, 35],
                  ["bench.fold#1", 55, 75]],
    }


def test_hand_made_trace():
    r = devtrace.reduce_events(hand_made())
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(40e-9)          # 10-40 and 60-70
    assert r.idle_share == pytest.approx(0.6)
    assert r.span_device_s["bench.premultiply#0"] == pytest.approx(25e-9)
    assert r.span_device_s["bench.fold#1"] == pytest.approx(10e-9)
    assert r.top_ops == [["jit_f", pytest.approx(30e-9)],
                         ["jit_h", pytest.approx(10e-9)]]
    # idle: 0-5 and 90-100 outside spans, 5-8 / 40-55 / 75-90 in the
    # batch alone, 8-10 in the premultiply's span, 55-60 / 70-75 in the
    # fold's
    idle = dict(r.idle_by_host)
    assert idle["outside spans"] == pytest.approx(15e-9)
    assert idle["bench.batch"] == pytest.approx(33e-9)
    assert idle["bench.premultiply"] == pytest.approx(2e-9)
    assert idle["bench.fold"] == pytest.approx(10e-9)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)


def test_step_share_and_byte_counts():
    assert roofline.premultiply_bytes(48, 1 << 20) == 2 * 48 * (1 << 20)
    assert roofline.fold_bytes(6, 1, 1 << 20) == 7 * (1 << 20)
    ctx = types.SimpleNamespace(
        calls=[{"op": "premultiply", "alg_bytes": 819e9 * 25e-9 * 0.5}],
        trace=devtrace.reduce_events(hand_made()), peak=PEAK)
    assert roofline.step_share(ctx, "premultiply") == pytest.approx(50.0)
    assert roofline.step_share(ctx, "fold") is None      # no call: silent
    assert roofline.share(1, 0.0, 1.0) is None


def test_one_window_span_required():
    ev = hand_made()
    ev["spans"] = ev["spans"][1:]
    with pytest.raises(ValueError):
        devtrace.reduce_events(ev)


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace():
    rec = json.loads(RECORDED.read_text())
    ev, calls = rec["events"], rec["calls"]
    r = devtrace.reduce_events(ev)
    lo, hi = next((s, e) for n, s, e in ev["spans"] if n == "bench.window")
    ops = [(s, e) for _, s, e in ev["devices"]["/device:TPU:0"]]
    merged = devtrace.union(ops, lo, hi)
    assert r.busy_s == pytest.approx(covered(merged, lo, hi) * 1e-9)
    assert 0 < r.busy_s < r.window_s
    for n, s, e in ev["spans"]:
        if "#" in n:
            want = covered(merged, s, e) * 1e-9
            assert r.span_device_s[n] == pytest.approx(want)
    assert sum(t for _, t in r.idle_by_host) == pytest.approx(
        r.window_s - r.busy_s)
    ctx = types.SimpleNamespace(calls=calls, trace=r, peak=PEAK)
    for op in ("premultiply", "fold"):
        share = roofline.step_share(ctx, op)
        assert share is not None and 0 < share <= 100


def test_seams_record_each_gf_call():
    """The traced window's seam wrappers record every GF call with its
    bytes; the byte reader divides them by the lost bytes."""
    import harness
    from conftest import ROOT

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    dep = json.loads((ROOT / spec["configs"][0]["file"]).read_text())
    dep["cell_bytes"] = 256
    traffic = json.loads((ROOT / "bench/traffic/repair.json").read_text())
    traffic["pool_stripes"] = 16
    driver = harness.load_module(ROOT / "bench/drivers/repair.py")
    wl = driver.workload(dep, traffic, 5, platform=None)
    calls = []
    with wl.seams(calls):
        wl.window(0.05, traced=True)
    ops = {c["op"] for c in calls}
    assert ops == {"premultiply", "fold"}
    pre = [c for c in calls if c["op"] == "premultiply"]
    assert len(pre) == len(wl.results)                # one per batch
    k = dep["code"]["data_blocks"]
    assert all(c["alg_bytes"] == 2 * 8 * k * 256 for c in pre)
    reader = harness.load_module(
        ROOT / "bench/metrics/host_to_device_bytes_per_lost_byte.repair.py")
    ctx = types.SimpleNamespace(calls=calls, lost_bytes=wl.lost_bytes)
    # the premultiply's k chunks per lost block, and the folds' at least
    # k (every helper's chunk travels once), all handed from the host
    assert reader.read(ctx) > 2 * k
