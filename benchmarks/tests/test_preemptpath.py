"""A preemptor followed from due to its bind: the tiling of
benchlib/preemptpath.py on hand-made spans (exact sums, pods out of
order dropped, a fast-rung pod folded), its five readers on a program
without the spans and on the traced CPU rehearsal of preemption-5000n,
and the split of the device's uncovered idle time by the preemption
path's stages."""

import importlib.util
import json
import os
import types

import pytest

import run as bench_run
from benchlib import preemptpath
from conftest import BENCH, REPO
from test_rehearsal import run_cell

READERS = ("preemptor_plan_wait_p50_s", "preemptor_wave_hold_p50_s",
           "preemptor_evict_p50_s", "preemptor_rebind_p50_s",
           "preempt_books_ms")
CELL = "preemption-5000n.bursts"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(spans, created=(), **kw):
    run = types.SimpleNamespace(
        spans=spans, notes={}, created=list(created), t_open=0.0, t_end=10.0,
        due={}, issued={}, bound_t={}, bound_node={}, **kw)
    run.window_spans = lambda stage: [
        (n, t0, d, a) for n, st, t0, d, a in run.spans or []
        if st == stage and run.t_open <= t0 < run.t_end]
    return run


def _pod_spans(i, batch, whatif=None, evict_at=3.0, echo_at=3.5):
    """The spans of preemptor p-<i> failed in launch `batch`: admitted at
    1.003, popped at 1.010, harvested at 1.025, its wave 1.03-2.5 (books
    1.03-1.23), planned in `whatif` (t0, dur) or on the fast rung, its
    victims evicted from `evict_at` and echoed by `echo_at`."""
    key = f"default/p-{i:07d}"
    spans = [
        ("ADDED pods", "informer", 1.001, 0.002, {"key": key}),
        ("pop", "pop", 1.010, 0.001, {"batch": batch}),
        ("harvest", "harvest", 1.020, 0.005, {"batch": batch}),
        ("preemption-wave", "preemption-wave", 1.03, 1.47,
         {"batch": batch, "n": 1, "keys": [key], "snapshot_s": 0.01,
          "eligibility_s": 0.02, "plan_s": 1.3}),
        ("preemption-books", "preemption-books", 1.03, 0.2,
         {"base_s": 0.15, "victims_s": 0.05}),
        ("evict", "evict", evict_at, 0.3,
         {"batch": batch, "keys": [key], "victims": 3, "queued_s": 0.1}),
        ("preemption-wait", "preemption-wait", 2.4, echo_at - 2.4,
         {"keys": [key], "victims": 3, "preemptors": 1}),
    ]
    if whatif is not None:
        spans.append(("whatif", "whatif", whatif[0], whatif[1],
                      {"pod": key, "prep_s": 0.001, "wait_s": 0.002,
                       "pick_s": 0.003}))
    return spans


def _pods(run, *idx, bound=4.0):
    for i in idx:
        run.due[i], run.issued[i] = 0.9, 1.0
        run.bound_t[i], run.bound_node[i] = bound, "node-00001"


def test_a_device_planned_preemptor_tiles_exactly():
    run = _run(_pod_spans(3, 9, whatif=(1.8, 0.1)), created=[3])
    _pods(run, 3)
    pp = preemptpath.of(run)
    assert (pp["bound"], pp["pods"], pp["joined"], pp["tiled"],
            pp["fast_rung"]) == (1, 1, 1, 1, 0)
    assert pp["trace_sheds"] == 0  # a run without counters reads none
    (row,) = pp["rows"]
    assert row == pytest.approx({
        "generator": 0.1, "admit_lag": 0.003, "queue_wait": 0.007,
        "decide": 0.015, "plan_wait": 0.775, "plan": 0.1, "wave_hold": 1.1,
        "evict": 0.5, "rebind": 0.5, "total": 3.1})
    assert sum(v for k, v in row.items() if k != "total") == \
        pytest.approx(row["total"], abs=1e-9)
    table = run.notes["preemptor_path"]
    assert table["tiled_share"] == 1.0 and "plan_hold" not in table
    assert set(table["plan_wait"]) == {"p50_s", "p95_s", "tail_mean_s"}
    values = {name: _reader(name).read(run) for name in READERS}
    assert values == pytest.approx({
        "preemptor_plan_wait_p50_s": 0.775,
        "preemptor_wave_hold_p50_s": 1.1,
        "preemptor_evict_p50_s": 0.5,
        "preemptor_rebind_p50_s": 0.5,
        # snapshot + eligibility + the books span inside the wave
        "preempt_books_ms": 230.0})
    waves = run.notes["preemption_waves"]
    assert waves["preemption-books"] == {"n": 1, "wall_s": 0.2,
                                         "base_s": 0.15, "victims_s": 0.05}
    assert waves["whatif"]["pick_s"] == pytest.approx(0.003)
    assert waves["evict"]["queued_s"] == pytest.approx(0.1)


def test_a_pod_whose_cuts_are_out_of_order_is_dropped():
    # pod 4's victims are "evicted" before its own what-if has ended
    spans = _pod_spans(3, 9, whatif=(1.8, 0.1)) + \
        _pod_spans(4, 10, whatif=(1.8, 0.1), evict_at=1.85)
    run = _run(spans, created=[3, 4])
    _pods(run, 3, 4)
    pp = preemptpath.of(run)
    assert (pp["pods"], pp["joined"], pp["tiled"]) == (2, 2, 1)
    assert run.notes["preemptor_path"]["tiled_share"] == 0.5
    assert run.notes["preemptor_path"]["out_of_order"] == {"wave_hold": 1}
    # a bind seen before the echo that would have to precede it
    run = _run(_pod_spans(5, 9, whatif=(1.8, 0.1), evict_at=3.0,
                          echo_at=3.5), created=[5])
    _pods(run, 5, bound=3.2)
    pp = preemptpath.of(run)
    assert (pp["tiled"], pp["out_of_order"]) == (0, {"rebind": 1})


def test_a_pod_admitted_while_its_pop_gathers_waits_in_no_queue():
    """A burst's pop takes pods off the queue while the informer is still
    admitting the rest: such a pod joins its batch at its admission."""
    spans = [s if s[1] != "pop" else ("pop", "pop", 1.002, 0.004, s[4])
             for s in _pod_spans(3, 9, whatif=(1.8, 0.1))]
    run = _run(spans, created=[3])
    _pods(run, 3)
    (row,) = preemptpath.of(run)["rows"]
    assert row["queue_wait"] == 0.0
    assert row["decide"] == pytest.approx(1.025 - 1.003)
    assert run.notes["preemptor_path"]["out_of_order"] == {}


def test_a_fast_rung_preemptor_folds_plan_into_one_segment():
    run = _run(_pod_spans(3, 9) + _pod_spans(4, 10, whatif=(1.8, 0.1)),
               created=[3, 4])
    _pods(run, 3, 4)
    pp = preemptpath.of(run)
    assert (pp["tiled"], pp["fast_rung"]) == (2, 1)
    fast = next(r for r in pp["rows"] if "plan_hold" in r)
    assert fast["plan_hold"] == pytest.approx(3.0 - 1.025)
    assert not {"plan_wait", "plan", "wave_hold"} & set(fast)
    assert sum(v for k, v in fast.items() if k != "total") == \
        pytest.approx(fast["total"], abs=1e-9)
    # the device-rung medians read the device-planned pod alone
    assert _reader("preemptor_plan_wait_p50_s").read(run) == \
        pytest.approx(0.775)
    assert run.notes["preemptor_path"]["plan_hold"]["p50_s"] == \
        pytest.approx(1.975)


def test_stretches_with_tracing_shed_are_counted():
    """The overload monitor had tracing off while pod 4 was created and
    planned (no span of it at all), and while pod 5's victims echoed
    (its eviction recorded, its node's wait not): neither is among the
    pods the share is taken over."""
    reg = "scheduler_overload_sheds_total"
    spans = _pod_spans(3, 9, whatif=(1.8, 0.1)) + [
        s for s in _pod_spans(5, 11, whatif=(1.8, 0.1))
        if s[1] != "preemption-wait"]
    run = _run(spans, created=[3, 4, 5],
               counters0={"registry": {reg: {"explain-harvest": 1}}},
               counters1={"registry": {reg: {"explain-harvest": 2,
                                             "trace": 1}}})
    _pods(run, 3, 4, 5)
    preemptpath.of(run)
    table = run.notes["preemptor_path"]
    assert (table["bound"], table["pods"], table["joined"],
            table["tiled"]) == (3, 2, 1, 1)
    assert table["tiled_share"] == 1.0 and table["trace_sheds"] == 1


def test_a_program_without_the_spans_reads_as_nothing():
    """The parent commit records `preemption-plan`, `whatif` and
    `preemption-wait` but none of the new spans: every reader is None."""
    spans = [
        ("preemption-plan", "planner", 1.0, 0.5, {"n": 2, "device": 2}),
        ("whatif", "whatif", 1.1, 0.1, {"pod": "default/p-0000001"}),
        ("preemption-wait", "preemption-wait", 1.6, 0.5,
         {"victims": 3, "preemptors": 1, "node": "node-00001"}),
        ("pop", "pop", 0.9, 0.01, {"batch": 1}),
    ]
    run = _run(spans, created=[1])
    _pods(run, 1)
    assert preemptpath.of(run) is None and run.notes == {}
    for name in READERS:
        mod = _reader(name)
        assert mod.KIND == "per_layer" and mod.META["name"] == name
        assert mod.read(run) is None, name


def test_the_readers_are_listed_for_the_bursts_cell_only():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    per = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        meta = _reader(name).META
        assert {k: per[name][k] for k in meta} == meta
        assert per[name]["workloads"] == [CELL]
        assert (meta["source"], meta["layer"], meta["moves"]) == (
            "program_span", "preemption", "bind_p50_s")
    # added after every metric that was there
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(READERS)


def test_uncovered_is_split_by_the_preemption_stages():
    dump = {
        "raw": {"ops": [["whatif fusion", 11.0, 1.0, "0"]],
                "anchors": {"bench_anchor": 10.0}},
        "t_start": 0.0, "t_stop": 6.0, "anchor": 0.0,
        # device busy 1-2; idle and uncovered 0-1 and 2-6: the wave 0-4
        # holds the planner 0-3, its books 0-0.5 and a what-if 2.5-3;
        # evict 4-5; nothing 5-6
        "spans": [["preemption-wave", 0.0, 4.0], ["planner", 0.0, 3.0],
                  ["preemption-books", 0.0, 0.5], ["whatif", 2.5, 0.5],
                  ["evict", 4.0, 1.0], ["complete", 0.0, 4.0]],
    }
    out = preemptpath.split_uncovered(dump)
    assert out["uncovered_s"] == pytest.approx(5.0, abs=1e-3)
    assert out["preemption-books"] == pytest.approx(0.5, abs=1e-3)
    assert out["planner"] == pytest.approx(1.0, abs=1e-3)
    assert out["whatif"] == pytest.approx(0.5, abs=1e-3)
    assert out["preemption-wave"] == pytest.approx(1.0, abs=1e-3)
    assert out["evict"] == pytest.approx(1.0, abs=1e-3)
    assert out["nothing"] == pytest.approx(1.0, abs=1e-3)
    assert "complete" not in out  # the wave's stages sit inside it


# -- the traced CPU rehearsal -------------------------------------------------


@pytest.fixture(scope="module")
def traced():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("KTPU_WHATIF", "1")
        p = run_cell("rehearsal-preemption-96n.rehearsal-bursts",
                     "--rehearse", trace=1, seed=2 ** 31 + 11)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_rehearsal_tiles_its_preemptors_and_reads_all_five(traced):
    assert traced["correct"] is True
    path = traced["detail"]["notes"]["preemptor_path"]
    assert path["pods"] == traced["attempted"] - traced["failed"]
    assert path["tiled_share"] >= 0.9
    assert path["worst_residual_s"] <= 1e-6
    for seg in preemptpath.HEAD + preemptpath.DEVICE_RUNG \
            + preemptpath.TAIL + ("total",):
        assert set(path[seg]) == {"p50_s", "p95_s", "tail_mean_s"}, seg
    for name in READERS:
        assert traced["metrics"][name]["value"] > 0, name
    waves = traced["detail"]["notes"]["preemption_waves"]
    assert set(waves) == set(preemptpath.STEPPED)
    assert waves["preemption-books"]["n"] == waves["preemption-wave"]["n"]
    # the accepted preemption readers still read what they read
    for name in ("preempt_plan_ms", "whatif_launch_ms", "whatif_context_ms",
                 "victim_wait_p50_s", "device_plan_share"):
        assert traced["metrics"][name]["value"] > 0, name


def test_bench_run_loads_each_reader_by_name():
    for name in READERS:
        assert bench_run.load_module("metrics", name).META["name"] == name
