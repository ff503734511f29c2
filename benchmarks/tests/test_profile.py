"""The reduction from a device trace to busy/idle, kernel time and idle
seconds by host span, on a small trace recorded on the chip and on one
made by hand."""

import json
import os

import pytest

from benchlib import profile, roofline
from conftest import BENCH


def test_hand_made_trace():
    trace = {"ops": [["fusion.1", 10.0, 0.5, "0"],
                     ["scan_custom-call", 10.25, 0.5, "0"],   # overlaps
                     ["scan_custom-call", 12.0, 1.0, "0"]],
             "anchors": {"bench_anchor": 9.0}}
    # the trace clock runs 5 s ahead of perf_counter
    out = profile.reduce(trace, t_start=4.0, t_stop=9.0, anchor_perf=4.0,
                         spans=[("bind", 5.75, 1.0), ("stage", 8.0, 1.0)])
    assert out["window_s"] == 5.0
    assert out["busy_s"] == pytest.approx(1.75)
    assert out["kernel_s"] == pytest.approx(1.5)
    assert out["kernel_events"] == 2
    assert out["device_ops"][0] == ["scan_custom-call", 1.5]
    gaps = dict(out["idle_gaps"])
    # idle: 4-5, 5.75-7, 8-9; bind covers 5.75-6.75, stage 8-9
    assert gaps["bind"] == pytest.approx(1.0, abs=1e-3)
    assert gaps["stage"] == pytest.approx(1.0, abs=1e-3)
    assert gaps["uncovered"] == pytest.approx(1.25, abs=1e-3)


def test_recorded_trace():
    path = os.path.join(BENCH, "testdata", "trace_small.json")
    with open(path) as f:
        rec = json.load(f)
    out = profile.reduce(rec["raw"], rec["t_start"], rec["t_stop"],
                         rec["anchor"], [tuple(s) for s in rec["spans"]])
    want = rec["expected"]
    assert out["aligned"]
    assert out["kernel_events"] == want["kernel_events"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["kernel_s"] == pytest.approx(want["kernel_s"], rel=1e-9)
    idle_share = 1.0 - out["busy_s"] / out["window_s"]
    assert 0.0 < idle_share < 1.0
    assert sum(s for _, s in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=0.02)


def test_roofline_counts_come_from_shapes_and_the_peaks_table():
    w = roofline.launch_work(pods=2048, n_nodes=5000)
    assert w["ops"] == 2048 * 5120 * roofline.OPS_PER_LANE
    assert roofline.launch_work(1, 5000, terms=1)["ops"] > \
        roofline.launch_work(1, 5000)["ops"]
    peak = roofline.peaks("TPU v5 lite")
    ls = roofline.least_seconds(w, peak)
    assert ls["bound"] in ("operations", "bytes")
    # elementwise work is held against the vector unit, never the MXU
    assert ls["seconds"] >= w["ops"] / peak["vector_ops_per_s"]
    assert peak["vector_ops_per_s"] < peak["flops_per_s"] / 10
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
    with pytest.raises(KeyError):
        roofline.peaks("_source")
