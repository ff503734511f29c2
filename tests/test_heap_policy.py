"""The process's heap policy (utils/selfstats.adopt_heap_policy): when
CPython's cyclic collector runs is set once, by `APIServer` and `Scheduler`
where they start, automatic collection stays on, and the `python_gc_*`
counters say what it cost. The premise the policy stands on is a test here,
not a benchmark: the create -> watch -> informer -> bind path makes no cyclic
garbage, and holds nothing that reference counting would have freed.
"""

import gc
import json
import os
import subprocess
import sys
import weakref

import pytest

from kubernetes_tpu.apiserver import APIServer
from kubernetes_tpu.client import Clientset, SharedInformerFactory
from kubernetes_tpu.scheduler.scheduler import Scheduler
from kubernetes_tpu.utils import selfstats
from kubernetes_tpu.utils.metrics import legacy_registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YOUNG, MIDDLE, OLD = selfstats.GC_THRESHOLDS


def build_apiserver():
    return APIServer()


def build_scheduler():
    cs = Clientset(APIServer())
    return Scheduler(cs, SharedInformerFactory(cs), backend="oracle")


BUILDS = {
    "one-apiserver": [build_apiserver],
    "one-scheduler": [build_scheduler],
    "three-of-each": [build_apiserver, build_scheduler] * 3,
    "scheduler-first": [build_scheduler, build_apiserver],
}


@pytest.fixture
def not_yet_adopted(monkeypatch):
    """A process in which nothing has adopted the policy, with every call
    of gc.set_threshold counted; the policy is adopted again afterwards."""
    while selfstats._gc_hook in gc.callbacks:
        gc.callbacks.remove(selfstats._gc_hook)
    real, calls = gc.set_threshold, []
    real(700, 10, 10)
    monkeypatch.setattr(
        gc, "set_threshold", lambda *a: (calls.append(a), real(*a))[1])
    yield calls
    monkeypatch.undo()
    selfstats.adopt_heap_policy()


@pytest.mark.parametrize("build", sorted(BUILDS))
def test_the_policy_is_adopted_once_however_many_components_start(
        not_yet_adopted, build):
    for make in BUILDS[build]:
        make()
    assert not_yet_adopted == [selfstats.GC_THRESHOLDS]
    assert gc.get_threshold() == selfstats.GC_THRESHOLDS
    assert gc.callbacks.count(selfstats._gc_hook) == 1


@pytest.mark.parametrize("build", sorted(BUILDS))
def test_automatic_collection_stays_on_and_nothing_is_frozen(build):
    frozen = gc.get_freeze_count()  # some import under pytest freezes
    for make in BUILDS[build]:
        make()
    assert gc.isenabled()
    assert gc.get_freeze_count() == frozen
    assert all(gc.get_threshold())  # a 0 would switch a generation off


def counters():
    return {
        name: {key[0]: v for key, v in legacy_registry._metrics[name].items()}
        for name in ("python_gc_collections_total", "python_gc_seconds_total",
                     "python_gc_objects_collected_total")}


class Knot:
    """An object that only the cyclic collector can free."""

    def __init__(self):
        self.me = self


@pytest.mark.parametrize("generation", [0, 1, 2])
def test_the_counters_advance_by_generation(generation):
    build_apiserver()
    gc.collect()
    knots = [Knot() for _ in range(7)]
    del knots
    was = counters()
    gc.collect(generation)
    now = counters()
    g = str(generation)
    moved = {name: now[name][g] - was[name][g] for name in now}
    # at least: a thread another test left behind may allocate too
    assert moved["python_gc_collections_total"] >= 1
    assert moved["python_gc_seconds_total"] > 0
    assert moved["python_gc_objects_collected_total"] >= 7
    for older in range(generation + 1, 3):
        assert all(now[n][str(older)] == was[n][str(older)] for n in now)
    assert "python_gc_seconds_total" in legacy_registry.expose()


@pytest.mark.parametrize("aged", ["young", "middle"])
def test_a_planted_cycle_is_reclaimed_by_automatic_collection(aged):
    """Dropped while young it goes within `young` allocations; once it has
    survived a collection, within `young * middle`. Nothing here calls
    gc.collect after the cycle is dropped."""
    build_apiserver()
    gc.collect()
    knot = Knot()
    gone = weakref.ref(knot)
    if aged == "middle":
        gc.collect(0)  # the knot is in the middle generation now
    bound = YOUNG + 1000 if aged == "young" else YOUNG * (MIDDLE + 2)
    del knot
    # containers that stay: one net allocation each. (The weak reference is
    # not looked at meanwhile: a collection that starts while its referent
    # is on the stack takes it for live.)
    keep = [[] for _ in range(bound)]
    assert gone() is None, f"alive after {len(keep)} allocations"


def test_the_old_generation_is_still_reached_at_the_served_rate():
    """A full collection comes after `young * middle * old` net container
    allocations. A bound pod leaves at least 24 tracked objects behind in
    the API server and one informer alone (ISSUE 35); at 1500 pods/s the
    policy reaches its full collection within half an hour, and a closed
    loop's 51 s window (under 140 000 pods of ~70 objects) within two."""
    to_full = YOUNG * MIDDLE * OLD
    assert to_full <= 1500 * 24 * 1800
    assert 2 * to_full >= 140_000 * 70


POD_PATH = r"""
import gc, json, sys, threading
from kubernetes_tpu.api import types as v1
from kubernetes_tpu.apiserver import APIServer
from kubernetes_tpu.client import Clientset, SharedInformerFactory
from kubernetes_tpu.client.informer import EventHandler
from kubernetes_tpu.store import kv
from kubernetes_tpu.utils import selfstats

mode, n = sys.argv[1], int(sys.argv[2])
frozen = gc.get_freeze_count()
# steady state needs a full history: the store keeps its last events
api = APIServer(store=kv.KVStore(history_limit=3000))
cs = Clientset(api)
factory = SharedInformerFactory(cs)
seen = {"bound": 0, "deleted": 0}
tick = threading.Condition()


def on_update(old, new):
    if new.spec.node_name and not old.spec.node_name:
        with tick:
            seen["bound"] += 1
            tick.notify_all()


def on_delete(obj):
    with tick:
        seen["deleted"] += 1
        tick.notify_all()


factory.pods().add_event_handler(
    EventHandler(on_update=on_update, on_delete=on_delete))
factory.start()
assert factory.wait_for_cache_sync(30.0)


def pod(i):
    labels = {"app": "default"}
    return v1.Pod(
        metadata=v1.ObjectMeta(name=f"p-{i}", namespace="default",
                               labels=labels),
        spec=v1.PodSpec(
            containers=[v1.Container(
                name="c0", image="registry.example/app:v1",
                resources=v1.ResourceRequirements(
                    requests={"cpu": "100m", "memory": "128Mi"}))],
            topology_spread_constraints=[v1.TopologySpreadConstraint(
                max_skew=1, topology_key=v1.LABEL_ZONE,
                when_unsatisfiable="ScheduleAnyway",
                label_selector=v1.LabelSelector(
                    match_labels=dict(labels)))]))


def rounds(lo, hi, delete):
    for i in range(lo, hi):
        cs.pods.create(pod(i))
        cs.pods.bind("default", f"p-{i}", f"node-{i % 50:05d}")
        if delete:
            cs.pods.delete(f"p-{i}", "default")
    with tick:
        assert tick.wait_for(lambda: seen["bound"] >= hi and (
            not delete or seen["deleted"] >= hi), 120.0)


def collected():
    return [v for _, v in sorted(selfstats.gc_collected.items())]


warm = 1500
rounds(0, warm, delete=(mode == "steady"))
gc.collect()
out = {"threshold": gc.get_threshold(), "enabled": gc.isenabled(),
       "frozen": gc.get_freeze_count() - frozen}
if mode == "premise":
    was = collected()
    rounds(warm, warm + n, delete=False)
    gc.collect()  # whatever cyclic garbage the path made is found now
    out["collected"] = [b - a for a, b in zip(was, collected())]
    out["pods_in_informer"] = factory.pods().count()
else:
    began = len(gc.get_objects())
    rounds(warm, warm + n, delete=True)
    out["began"], out["ended"] = began, len(gc.get_objects())
    out["pods_in_informer"] = factory.pods().count()
factory.stop()
print(json.dumps(out))
"""


def pod_path(mode, n):
    """In a process of its own: no thread of another test allocates."""
    p = subprocess.run(
        [sys.executable, "-c", POD_PATH, mode, str(n)], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["threshold"] == list(selfstats.GC_THRESHOLDS)
    assert out["enabled"] is True and out["frozen"] == 0
    return out


def test_the_pod_path_makes_no_cyclic_garbage():
    """The premise: 2000 pods created, informed and bound leave nothing to
    the collector in any generation. A change that starts making cycles on
    the pod path fails here."""
    out = pod_path("premise", 2000)
    assert out["pods_in_informer"] == 1500 + 2000
    assert out["collected"] == [0, 0, 0]


def test_the_policy_holds_nothing_that_reference_counting_frees():
    """Steady state: 20 000 rounds of create, bind and delete end with as
    many tracked objects as they began with (within 5 %)."""
    out = pod_path("steady", 20000)
    assert out["pods_in_informer"] == 0
    assert abs(out["ended"] - out["began"]) <= 0.05 * out["began"], out


# -- the benchmark's reader of the counter ----------------------------------


def reader():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "gc_ms_per_kpod", os.path.join(
            REPO, "benchmarks", "metrics", "gc_ms_per_kpod.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class FakeRun:
    def __init__(self, registry0, registry1, binds):
        self.counters0 = {"registry": registry0}
        self.counters1 = {"registry": registry1}
        self.notes = {}
        self._binds = binds

    def binds_in_window(self):
        return [0.0] * self._binds


def by_generation(young, middle, full):
    return {"0": young, "1": middle, "2": full}


READINGS = {
    # the parent's program keeps no such counter: nothing, and no raise
    "no-counter": (FakeRun({}, {"scheduler_x_total": {"-": 1}}, 4000), None),
    "no-registry": (FakeRun({}, {}, 4000), None),
    "no-pod-bound": (FakeRun(
        {}, {"python_gc_seconds_total": by_generation(1, 1, 1)}, 0), None),
    # 0.5 + 0.25 + 0.25 s of collector over 4000 pods: 250 ms a thousand
    "counted": (FakeRun(
        {"python_gc_seconds_total": by_generation(1.0, 0.5, 0),
         "python_gc_collections_total": by_generation(10, 1, 0),
         "python_gc_objects_collected_total": by_generation(3, 0, 0)},
        {"python_gc_seconds_total": by_generation(1.5, 0.75, 0.25),
         "python_gc_collections_total": by_generation(110, 3, 1),
         "python_gc_objects_collected_total": by_generation(3, 0, 5)},
        4000), 250.0),
}


@pytest.mark.parametrize("case", sorted(READINGS))
def test_the_reader_divides_the_collectors_seconds_by_the_pods_bound(case):
    run, want = READINGS[case]
    got = reader().read(run)
    assert got == want
    if want is None:
        assert run.notes == {}
    else:
        assert run.notes["gc"] == {
            "collections": by_generation(100, 2, 1),
            "seconds": by_generation(0.5, 0.25, 0.25),
            "collected": by_generation(0, 0, 5)}


def test_the_metric_is_listed_where_pods_per_s_is_reported():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "gc_ms_per_kpod")
    pps = next(m for m in bench["end_to_end"] if m["name"] == "pods_per_s")
    assert entry["workloads"] == pps["workloads"]
    mod = reader()
    assert {k: entry[k] for k in mod.META} == mod.META
    assert mod.KIND == "per_layer"
