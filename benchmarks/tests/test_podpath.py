"""One pod followed from pods.create to its bind: the join of the
program's spans with the benchmark's records, its tables, the readers of
the per-layer metrics that come from it, and the split of the device's
uncovered idle time, on a CPU rehearsal and on hand-made spans."""

import importlib.util
import json
import os
import types

import pytest

from benchlib import podpath, profile
from conftest import BENCH
from test_rehearsal import run_cell

NEW_METRICS = ["admit_lag_p50_s", "queue_wait_p50_s", "decide_p50_s",
               "commit_p50_s", "create_wait_us_per_pod", "admit_us_per_pod",
               "batch_turnaround_ms"]


@pytest.fixture(scope="module")
def traced():
    p = run_cell("rehearsal-96n.rehearsal-arrivals", "--rehearse", trace=1,
                 seed=2 ** 31 + 11)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_segments_tile_the_wait_of_nearly_every_pod(traced):
    """generator + admit lag + queue wait + decide + commit is the pod's
    bind seen - due, within 1e-6 s, for 99.5 % of the window's pods."""
    assert traced["correct"] is True
    path = traced["detail"]["notes"]["pod_path"]
    assert path["pods"] == traced["attempted"] - traced["failed"]
    assert path["tiled_share"] >= 0.995
    assert path["worst_residual_s"] <= 1e-6
    for seg in podpath.SEGMENTS + ("total",):
        assert set(path[seg]) == {"p50_s", "p95_s", "tail_mean_s"}
    # the whole is what bind_p50_s reads; the parts are of its size
    assert path["total"]["p50_s"] > 0
    assert path["decide"]["p50_s"] <= path["total"]["p95_s"]


def test_every_new_metric_is_reported(traced):
    for name in NEW_METRICS:
        m = traced["metrics"][name]
        assert m["value"] is not None and m["value"] == m["value"], name
    assert traced["metrics"]["batch_turnaround_ms"]["value"] > 0
    assert traced["metrics"]["admit_us_per_pod"]["value"] > 0
    # a create waits for less than the slowest create lasted
    assert 0 <= traced["metrics"]["create_wait_us_per_pod"]["value"] \
        <= 50 * traced["metrics"]["create_us_per_pod"]["value"]


def test_threads_table_covers_the_schedulers_threads(traced):
    threads = traced["detail"]["notes"]["threads"]
    by = threads["by_thread"]
    assert {"scheduler-loop", "batch-completions", "informer-pods",
            "binder"} <= set(by)
    # an idle poll of 0.2 s that was open when the level was raised, at
    # the window's open, is the one stretch that no span records
    for name in ("scheduler-loop", "batch-completions"):
        assert by[name]["uncovered_share"] <= 0.03 + 0.2 / 3, by[name]
        for row in by[name]["stages"].values():
            assert row["own_wall_s"] >= 0 and row["n"] > 0
            assert row["cpu_share"] is None or row["cpu_share"] >= 0
    shares = [row["cpu_share"] for t in by.values()
              for row in t["stages"].values()]
    assert any(s is not None for s in shares)
    assert "queue-empty" in by["scheduler-loop"]["stages"]
    assert "worker-idle" in by["batch-completions"]["stages"]
    create = threads["steps"]["apiserver create pods"]
    assert create["n"] == traced["attempted"]
    parts = [create[k] for k in ("admission_s", "lock_s", "stamp_s",
                                 "encode_s", "store_s", "decode_s",
                                 "hooks_s")]
    assert sum(parts) == pytest.approx(create["wall_s"], rel=0.05)
    assert "handlers_s" in threads["steps"]["informer ADDED pods"]


def _run(spans, created=(), **kw):
    run = types.SimpleNamespace(
        spans=spans, notes={}, created=list(created), t_open=0.0, t_end=10.0,
        due={}, issued={}, bound_t={}, bound_node={}, **kw)
    run.window_spans = lambda stage: [
        (n, t0, d, a) for n, st, t0, d, a in run.spans or []
        if st == stage and run.t_open <= t0 < run.t_end]
    return run


def test_a_program_without_the_spans_reads_as_nothing():
    """The parent commit has none of the new spans: every reader returns
    None and nothing is written into the notes."""
    run = _run([("dispatch", "dispatch", 1.0, 0.1, {"n": 2}),
                ("bind", "bind", 1.2, 0.1, {"n": 2}),
                ("stage", "stage", 0.5, 0.2, None)])
    assert podpath.of(run) is None
    assert run.notes == {}
    for name in NEW_METRICS:
        spec = importlib.util.spec_from_file_location(
            "m_" + name, os.path.join(BENCH, "metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.KIND == "per_layer" and mod.META["name"] == name
        assert mod.read(run) is None, name


def test_join_by_key_and_batch_on_hand_made_spans():
    spans = [
        ("create pods", "apiserver", 1.0, 0.004,
         {"key": "default/p-0000003", "cpu_s": 0.001, "thread": "gen"}),
        ("ADDED pods", "informer", 1.003, 0.002,
         {"key": "default/p-0000003", "cpu_s": 0.002, "thread": "inf"}),
        ("pop", "pop", 1.010, 0.001, {"batch": 9, "n": 1}),
        ("harvest", "harvest", 1.020, 0.005, {"batch": 9}),
        ("bind", "bind", 1.030, 0.010, {"batch": 9}),
        ("pod-path", "path", 1.040, 0.0,
         {"batch": 9, "keys": ["default/p-0000003", "default/other"]}),
    ]
    run = _run(spans, created=[3, 4])
    run.due, run.issued = {3: 0.9, 4: 0.9}, {3: 1.0, 4: 1.0}
    run.bound_t, run.bound_node = {3: 1.05, 4: 2.0}, {3: "n", 4: "n"}
    pp = podpath.of(run)
    assert pp["pods"] == 2 and pp["tiled"] == 1  # pod 4 has no spans
    seg = {s: v[0] for s, v in pp["segments"].items()}
    assert seg == pytest.approx({
        "generator": 0.1, "admit_lag": 0.005, "queue_wait": 0.005,
        "decide": 0.015, "commit": 0.025})
    assert sum(seg.values()) == pytest.approx(1.05 - 0.9, abs=1e-9)
    assert podpath.batch_turnaround_p50(run) == pytest.approx(0.030)
    assert run.notes["pod_path"]["tiled_share"] == 0.5
    # wall less cpu_s of the create, per create that read the CPU clock
    (d, a), = podpath.named_spans(run, "apiserver", "create pods")
    assert d - a["cpu_s"] == pytest.approx(0.003)
    spec = importlib.util.spec_from_file_location("m_cw", os.path.join(
        BENCH, "metrics", "create_wait_us_per_pod.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.read(run) == pytest.approx(3000.0)
    a.pop("cpu_s")  # no create read the clock: nothing to report
    assert mod.read(run) is None


def test_a_nested_spans_time_is_taken_out_of_its_parent():
    spans = [
        ("cycle", "cycle", 1.0, 1.0, {"cpu_s": 0.5, "thread": "s"}),
        ("encode", "encode", 1.2, 0.3, {"thread": "s"}),
        ("queue-empty", "queue-empty", 2.0, 2.0,
         {"cpu_s": 0.0, "thread": "s"}),
        ("bind", "bind", 1.0, 0.5, {"cpu_s": 0.1, "thread": "binder_0",
                                    "posted_s": 0.2}),
        ("bind", "bind", 1.0, 1.5, {"thread": "binder_1", "posted_s": 0.4}),
        ("binder-queue", "binder-queue", 0.5, 0.5, {"batch": 1}),
    ]
    t = podpath.threads_table(spans, 0.0, 4.0)
    s = t["by_thread"]["s"]
    assert s["stages"]["cycle"] == {
        "n": 1, "own_wall_s": 0.7, "cpu_share": 0.5}
    # a stage none of whose spans read the CPU clock has no share
    assert s["stages"]["encode"] == {
        "n": 1, "own_wall_s": 0.3, "cpu_share": None}
    assert s["stages"]["queue-empty"]["cpu_share"] == 0.0
    assert s["uncovered_share"] == pytest.approx(0.25)
    # two binder threads are one row; the share comes from the one span
    # that read the clock; a span with no thread is left out
    b = t["by_thread"]["binder"]
    assert b["threads"] == 2
    assert b["stages"]["bind"] == {
        "n": 2, "own_wall_s": 2.0, "cpu_share": 0.2}
    assert b["uncovered_share"] == pytest.approx(1 - 2.0 / 8.0)
    assert t["steps"]["bind bind"]["posted_s"] == pytest.approx(0.6)
    assert set(t["by_thread"]) == {"s", "binder"}


def test_uncovered_is_split_by_the_named_waits():
    dump = {
        "raw": {"ops": [["scan custom-call", 11.0, 1.0, "0"]],
                "anchors": {"bench_anchor": 10.0}},
        "t_start": 0.0, "t_stop": 6.0, "anchor": 0.0,
        # device busy 1-2; assume covers 2-3; idle and uncovered: 0-1, 3-6
        "spans": [["assume", 2.0, 1.0], ["queue-empty", 3.0, 2.0],
                  ["worker-idle", 0.0, 6.0], ["backpressure", 4.5, 0.5]],
    }
    out = podpath.split_uncovered(dump)
    assert out["uncovered_s"] == pytest.approx(4.0, abs=1e-3)
    assert out["queue-empty"] == pytest.approx(1.5, abs=1e-3)
    assert out["backpressure"] == pytest.approx(0.5, abs=1e-3)
    assert out["worker-idle"] == pytest.approx(2.0, abs=1e-3)
    # and it is the accepted reducer's `uncovered`, on the recorded trace
    with open(os.path.join(BENCH, "testdata", "trace_small.json")) as f:
        rec = json.load(f)
    want = dict(profile.reduce(
        rec["raw"], rec["t_start"], rec["t_stop"], rec["anchor"],
        [tuple(s) for s in rec["spans"]])["idle_gaps"])["uncovered"]
    assert podpath.split_uncovered(rec)["uncovered_s"] == pytest.approx(want)
