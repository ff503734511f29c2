"""The preemption path's spans: a failure wave, its books, each what-if
launch's parts, the eviction on a binder thread, the wait for the echoes
and the nominated bind, as one CPU burst of preemptors records them at
level 1; and at level 0 the same burst builds no span and hands the
recorder no list of keys.

The cluster is the bursts cell in small (benchmarks/configs/preemption-
5000n.json): four 900m priority-0 pods on every 4-CPU node, and bursts of
3000m priority-10 pods that each evict three, planned on the device rung
(KTPU_WHATIF=1, the TPUBackend's jnp session on the CPU)."""

from __future__ import annotations

import importlib.util
import os
import sys

import pytest

from kubernetes_tpu.utils import tracing

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

N_NODES = 16
BURST = 6
CONFIG = {
    "nodes": {"count": N_NODES, "cpu": "4", "memory": "32Gi", "pods": 110,
              "zones": 1},
    "scheduler": {"max_batch": 64},
    "pod_templates": {
        "low": {"cpu": "900m", "memory": "500Mi", "priority": 0,
                "labels": {"app": "batch"}},
        "high": {"cpu": "3000m", "memory": "500Mi", "priority": 10,
                 "labels": {"app": "prod"}},
    },
}
NEW_STAGES = ("preemption-wave", "preemption-books", "evict",
              "preemption-wait", "nominated-place", "whatif-context",
              "template-admit", "whatif-wave")
REPO = os.path.dirname(BENCH)


def _builder():
    spec = importlib.util.spec_from_file_location(
        "t_spans_builder", os.path.join(BENCH, "builders", "preemption.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _has_list(attrs) -> bool:
    return any(isinstance(v, (list, tuple, set, dict))
               for v in (attrs or {}).values())


@pytest.fixture(scope="module")
def burst():
    """(events of a level-1 burst, keys of its pods, what a level-0
    burst built and recorded)."""
    from benchlib.cluster import Cluster

    class CountedSpan(tracing.Span):
        made = 0

        def __init__(self, *a):
            CountedSpan.made += 1
            super().__init__(*a)

    lists = []  # attrs holding a list that reached a trace point at level 0
    real_span, real_record = tracing.span, tracing.RECORDER.record
    real_set = tracing._NoopSpan.set

    def span_spy(name, stage, **attrs):
        lists.extend([stage] if _has_list(attrs) else [])
        return real_span(name, stage, **attrs)

    def record_spy(name, stage, t0, dur, attrs=None):
        lists.extend([stage] if _has_list(attrs) else [])
        return real_record(name, stage, t0, dur, attrs)

    def set_spy(self, **attrs):
        lists.extend(["set"] if _has_list(attrs) else [])
        return real_set(self, **attrs)

    old = tracing.set_level(0)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("KTPU_WHATIF", "1")
        cluster = Cluster(CONFIG, 256, builder=_builder())
        cluster.build()
        mark = 0
        try:
            low = cluster.pod_class("low")
            high = cluster.pod_class("high")
            cluster.stage(cluster.prebuild([low] * (4 * N_NODES)),
                          timeout=120.0)
            # level 0: the first burst (it compiles the what-if launch)
            tracing.RECORDER.clear()
            mp.setattr(tracing, "Span", CountedSpan)
            mp.setattr(tracing, "span", span_spy)
            mp.setattr(tracing.RECORDER, "record", record_spy)
            mp.setattr(tracing._NoopSpan, "set", set_spy)
            cluster.stage(cluster.prebuild([high] * BURST), timeout=120.0)
            off = {"ring": len(tracing.RECORDER.snapshot()),
                   "spans_built": CountedSpan.made, "lists": list(lists)}
            mp.undo()
            mp.setenv("KTPU_WHATIF", "1")
            # level 1: the second burst
            tracing.set_level(tracing.TRACE_STAGES)
            mark = tracing.RECORDER.mark()
            pods = cluster.prebuild([high] * BURST)
            cluster.stage(pods, timeout=120.0)
            assert cluster.sched.recorder.flush(timeout=10)
            keys = {cluster.pods[i].metadata.namespace + "/"
                    + cluster.pods[i].metadata.name for i in pods}
        finally:
            cluster.close()
            events = tracing.RECORDER.snapshot(since=mark)
            tracing.set_level(old)
            tracing.RECORDER.clear()
    return events, keys, off


def _of(events, stage):
    return [(e[1], e[3], e[4], e[6] or {}) for e in events if e[2] == stage]


def test_level_0_burst_builds_no_span_and_no_key_list(burst):
    _, _, off = burst
    assert off == {"ring": 0, "spans_built": 0, "lists": []}


def test_new_stages_are_listed():
    for stage in NEW_STAGES:
        assert stage in tracing.STAGES, stage
    assert "preemption-wave" in tracing.NOT_PIPELINE_WORK


def test_every_stage_recorded_is_listed(burst):
    events, _, _ = burst
    assert {e[2] for e in events} <= set(tracing.STAGES)


def test_wave_span_names_its_preemptors_and_its_steps_fit_inside(burst):
    events, keys, _ = burst
    waves = _of(events, "preemption-wave")
    assert waves
    planned = [k for _, _, _, a in waves for k in a.get("keys", [])]
    assert set(planned) == keys and len(planned) == len(keys)
    for _, _, dur, a in waves:
        assert isinstance(a["batch"], int) and a["n"] >= len(a["keys"])
        steps = [a[s + "_s"] for s in
                 ("snapshot", "eligibility", "plan", "register", "redispatch")]
        assert min(steps) >= 0.0 and sum(steps) <= dur
    # the planner span keeps its name and nests inside its wave
    plans = _of(events, "planner")
    assert [n for n, *_ in plans] == ["preemption-plan"] * len(waves)
    for (_, t0, dur, _), (_, w0, wdur, _) in zip(sorted(plans, key=lambda p: p[1]),
                                                 sorted(waves, key=lambda w: w[1])):
        assert w0 <= t0 and t0 + dur <= w0 + wdur


def test_books_span_has_the_device_planners_steps(burst):
    events, _, _ = burst
    books = _of(events, "preemption-books")
    assert len(books) == len(_of(events, "preemption-wave"))
    for _, _, dur, a in books:
        steps = [a[s + "_s"] for s in
                 ("base", "lanes", "victims", "claimed", "nominated")]
        assert min(steps) >= 0.0 and sum(steps) <= dur
        # the books of the first burst are kept: only the nodes that
        # lost victims and bound preemptors since are walked again
        assert a["kept"] + a["rebuilt"] == N_NODES
        assert a["rebuilt"] <= BURST


def test_whatif_spans_carry_pick_s(burst):
    """One `whatif` span a preemptor, with its `pod`. The burst's
    preemptors are one run of one key: one wave launch plans them, and
    each span is that preemptor's host replay of its step."""
    events, keys, _ = burst
    launches = _of(events, "whatif")
    assert sorted(a["pod"] for *_, a in launches) == sorted(keys)
    for _, _, dur, a in launches:
        assert a["path"] == "wave"
        assert 0.0 < a["pick_s"] <= dur


def test_a_wave_launch_span_names_its_preemptors_and_steps(burst):
    """`whatif-wave`, one a wave launch: `n` preemptors of `steps`, its
    host preparation and its wait inside it, and it ends before the
    replay of its first preemptor begins."""
    from kubernetes_tpu.ops.whatif import WAVE_STEPS

    events, keys, _ = burst
    waves = _of(events, "whatif-wave")
    assert sum(a["n"] for *_, a in waves) == len(keys)
    first = min(t0 for _, t0, _, _ in _of(events, "whatif"))
    for _, t0, dur, a in waves:
        assert a["steps"] == WAVE_STEPS and 1 <= a["n"] <= WAVE_STEPS
        assert a["prep_s"] >= 0.0 and a["wait_s"] > 0.0
        assert a["prep_s"] + a["wait_s"] <= dur
    assert min(t0 + dur for _, t0, dur, _ in waves) <= first


def test_evict_names_the_preemptors_and_their_victims(burst):
    events, keys, _ = burst
    evicts = _of(events, "evict")
    waves = {a["batch"]: a for *_, a in _of(events, "preemption-wave")}
    assert evicts
    evicted = []
    for _, _, dur, a in evicts:
        assert a["keys"] == waves[a["batch"]]["keys"]
        assert a["victims"] == 3 * len(a["keys"])
        assert a["queued_s"] >= 0.0
        assert a["deletes_s"] + a["gang_s"] + a["status_s"] <= dur
        evicted += a["keys"]
    assert set(evicted) == keys


def test_preemption_wait_names_the_preemptors_it_activates(burst):
    events, keys, _ = burst
    waits = _of(events, "preemption-wait")
    activated = [k for *_, a in waits for k in a["keys"]]
    assert sorted(activated) == sorted(keys)
    assert all(a["preemptors"] == len(a["keys"]) for *_, a in waits)


def test_nominated_place_has_batch_and_keys(burst):
    events, keys, _ = burst
    places = _of(events, "nominated-place")
    placed = [k for *_, a in places for k in a["keys"]]
    assert sorted(placed) == sorted(keys)
    assert all(isinstance(a["batch"], int) and a["n"] >= len(a["keys"])
               for *_, a in places)


def test_the_rehearsal_on_wave_launches_tiles_every_preemptor():
    """The CPU rehearsal of the bursts cell, traced, on the device rung:
    every preemptor planned in a wave launch, and benchlib/preemptpath.py
    still tiles each one's wait by its own `whatif` span (on this path
    its host replay), none left untiled by a segment that runs
    backwards."""
    import json
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", KTPU_WHATIF="1",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   REPO, ".xla_cache", "rehearsal"))
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "rehearsal-preemption-96n.rehearsal-bursts", "--seed",
         str(2 ** 31 + 41), "--seconds", "3", "--trace", "1",
         "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["whatif_wave_share"]["value"] == 1.0
    path = line["detail"]["notes"]["preemptor_path"]
    assert path["pods"] == line["attempted"] - line["failed"] > 0
    assert path["out_of_order"] == {}
    assert path["tiled"] == path["joined"] == path["pods"]
