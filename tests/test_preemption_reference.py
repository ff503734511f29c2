"""The program's preemption against the benchmark's plain reference
(benchmarks/references/preemption.py, DefaultPreemption written out
again): seeded small clusters of low-priority pods of mixed priorities and
sizes, then bursts of preemptors, each burst of one priority and bound
before the next is created. Every pod's node and the set of pods the
program evicted are compared exactly, on each planner rung: the device
what-if rung (KTPU_WHATIF=1, the TPUBackend's jnp session on the CPU),
where every burst is planned by wave launches, and the numpy fast
rung."""

from __future__ import annotations

import importlib.util
import os
import random
import sys

import pytest

from kubernetes_tpu.utils.metrics import legacy_registry

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def _load(directory: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"t_{directory}_{name}", os.path.join(BENCH, directory, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REFERENCE = _load("references", "preemption")
BUILDER = _load("builders", "preemption")
N_NODES = 12


def _template(cpu: int, priority: int) -> dict:
    return {"cpu": f"{cpu}m", "memory": "256Mi", "priority": priority,
            "labels": {"app": f"p{priority}"}}


def _scenario(seed: int):
    """(config, stages): stages of templates, low priorities first, then
    bursts of preemptors, drawn until the reference plans every
    preemptor inside its exact domain with two to five victims."""
    rng = random.Random(seed)
    while True:
        templates, stages = {}, []
        for prio, count, sizes in ((0, 2 * N_NODES, (700, 900, 1100)),
                                   (3, N_NODES, (600, 900)),
                                   (5, N_NODES // 2, (500, 800))):
            stage = []
            for _ in range(count):
                cpu = rng.choice(sizes)
                name = f"c{cpu}p{prio}"
                templates[name] = _template(cpu, prio)
                stage.append(name)
            stages.append(stage)
        for prio in (10, 20):
            cpu = rng.choice((2600, 3000, 3400, 3800))
            name = f"c{cpu}p{prio}"
            templates[name] = _template(cpu, prio)
            stages.append([name] * rng.randint(2, 4))
        config = {"nodes": {"count": N_NODES, "cpu": "4", "memory": "32Gi",
                            "pods": 110, "zones": 1},
                  "scheduler": {"max_batch": 64},
                  "pod_templates": templates}
        classes = [dict(t) for t in templates.values()]
        index = {name: k for k, name in enumerate(templates)}
        log = [("create", i, index[name]) for i, name in
               enumerate(n for stage in stages for n in stage)]
        try:
            binds, evicted = REFERENCE.replay(config, classes, log)
        except REFERENCE.reference.LogError:
            continue
        n_pre = sum(len(s) for s in stages[3:])
        per_node = len(evicted) / n_pre
        if (all(b is not None for b in binds.values())
                and 2 <= per_node <= 5 and len(evicted) >= 2 * n_pre):
            return config, stages, binds, evicted


def _counts(name: str):
    m = legacy_registry._metrics.get(name)
    with m._lock:
        return dict(m._values)


def _planner_paths():
    return {k[0]: v for k, v in
            _counts("scheduler_preemption_planner_total").items()}


def _moved(after, before):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


@pytest.mark.parametrize("rung,seed", [("device", 8), ("fast", 8),
                                       ("device", 11), ("fast", 11)])
def test_preemption_matches_the_reference(monkeypatch, rung, seed):
    from benchlib.cluster import Cluster, node_name

    monkeypatch.setenv("KTPU_WHATIF", "1" if rung == "device" else "0")
    config, stages, want, evicted = _scenario(seed)
    cluster = Cluster(config, 1024, builder=BUILDER)
    cluster.build()
    try:
        before = _planner_paths()
        launched = _counts("scheduler_whatif_planned_total")
        for stage in stages:
            cluster.stage(cluster.prebuild(
                [cluster.pod_class(name) for name in stage]), timeout=120.0)
        stored = cluster.stored()
        gone = set(cluster.deleted_t)
        classes, log = cluster.classes, cluster.log
        after = _planner_paths()
        launched = _moved(_counts("scheduler_whatif_planned_total"),
                          launched)
    finally:
        cluster.close()
    # the benchmark's classes are the configuration's templates in the
    # order first used: the reference replays the run's own log
    binds, ref_evicted = REFERENCE.replay(config, classes, log)
    assert sorted(ref_evicted) == sorted(evicted)
    assert gone == set(ref_evicted)
    for i, node in binds.items():
        if i in gone:
            continue
        assert stored.get(i) == node_name(node), i
    planned = _moved(after, before)
    n_pre = sum(len(s) for s in stages[3:])
    assert planned == {rung: n_pre}
    # the device rung's bursts took wave launches, the pick and the claim
    # on the device
    assert launched == ({("wave", "lane-local"): n_pre}
                        if rung == "device" else {})
