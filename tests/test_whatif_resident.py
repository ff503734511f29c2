"""Wave-resident what-if inputs: each launch after a wave's first sends
only the lanes the claims since changed, into inputs the device keeps.

The device rung's answers must not move with where its inputs live: a
planner on resident inputs must return exactly the candidates (node,
victims in order, PDB violations) and `fits_now` verdicts of a planner
that uploads every launch whole (`resident_inputs=False`), which
tests/test_preemption_fast.py pins to the oracle. Randomised waves
cover what a claim moves: PDB-covered victims, nominated load above and
below the wave's priority, gang units, spread and affinity templates,
victims an earlier wave claimed, and two priorities in one wave.

These are the inputs of preemptors launched one at a time
(`wave_launch=False`): tests/test_whatif_wave.py holds wave launches,
whose inputs stay on the device the same way, to this path.
"""

from __future__ import annotations

import random

import pytest

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.scheduler import metrics
from kubernetes_tpu.scheduler.framework.snapshot import Snapshot
from kubernetes_tpu.scheduler.internal.nominator import PodNominator
from kubernetes_tpu.scheduler.preemption import FastPreemptionPlanner
from kubernetes_tpu.scheduler.preemption_device import DevicePreemptionPlanner
from kubernetes_tpu.testing.synth import make_node, make_pod

from . import test_preemption_fast
from .test_preemption_fast import _mk_backend

CASES = ("pdb", "nominated", "gang", "spread", "affinity", "claimed",
         "two-prio")


def _inputs(path: str, reason: str) -> float:
    return metrics.whatif_inputs.value(path=path, reason=reason)


def _spread(app: str):
    return [v1.TopologySpreadConstraint(
        max_skew=1, topology_key="zone", when_unsatisfiable="DoNotSchedule",
        label_selector=v1.LabelSelector(match_labels={"app": app}),
    )]


def _affinity(anti_key: str):
    """Required affinity to app=y within the zone, and required
    anti-affinity to app=x within `anti_key`'s domain: both count
    drains move with the claimed victims."""
    return v1.Affinity(
        pod_affinity=v1.PodAffinity(
            required_during_scheduling_ignored_during_execution=[
                v1.PodAffinityTerm(
                    label_selector=v1.LabelSelector(match_labels={"app": "y"}),
                    topology_key="zone",
                )
            ]
        ),
        pod_anti_affinity=v1.PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[
                v1.PodAffinityTerm(
                    label_selector=v1.LabelSelector(match_labels={"app": "x"}),
                    topology_key=anti_key,
                )
            ]
        ),
    )


def _pdb(app: str, allowed: int):
    return v1.PodDisruptionBudget(
        metadata=v1.ObjectMeta(name=f"pdb-{app}", namespace="default"),
        spec=v1.PodDisruptionBudgetSpec(
            selector=v1.LabelSelector(match_labels={"app": app})),
        status=v1.PodDisruptionBudgetStatus(disruptions_allowed=allowed),
    )


def _wave_case(case: str, rng: random.Random):
    """(nodes, pods, wave, kwargs of the planner) for one random wave."""
    if case == "gang":
        gangs = test_preemption_fast.TestGangVictimParity()
        nodes, pods, _ = gangs._random_gang_cluster(
            rng, rng.randint(6, 10))
    else:
        nodes, pods = [], []
        apps = {"spread": ["s", "t"], "affinity": ["x", "y", "z"]}.get(
            case, ["a", "b"])
        if case == "pdb":
            apps = ["a", "a", "b"]  # most victims under one budget
        for i in range(rng.randint(6, 12)):
            cap = rng.choice([4000, 8000])
            nodes.append(make_node(
                f"n{i}", cpu=f"{cap}m", memory="16Gi",
                pods=rng.choice([6, 110]),
                labels={"zone": f"z{i % 3}",
                        "kubernetes.io/hostname": f"n{i}"}))
            used = 0
            while True:  # nearly full: a preemptor fits nowhere as is
                req = rng.choice([900, 1500, 1900])
                if used + req > cap - 400:
                    break
                pod = make_pod(
                    f"p{i}-{used}", cpu=f"{req}m", memory="256Mi",
                    node_name=f"n{i}", priority=rng.choice([0, 1, 5, 50]),
                    labels={"app": rng.choice(apps)})
                pod.status.start_time = rng.random() * 100.0
                pods.append(pod)
                used += req
    kwargs = {}
    if case == "pdb":
        kwargs["pdbs"] = [_pdb("a", rng.choice([0, 1]))]
    if case == "claimed":
        kwargs["claimed_victims"] = {
            v1.pod_key(p) for p in rng.sample(pods, len(pods) // 10)}
    nominator = PodNominator()
    if case == "nominated":
        # ghosts above and below the wave's priority (100)
        for k, prio in enumerate((200, 100, 10, 10)):
            ghost = make_pod(f"ghost{k}", cpu=f"{rng.choice([500, 1500])}m",
                             memory="256Mi", priority=prio)
            nominator.add_nominated_pod(
                ghost, rng.choice(nodes).metadata.name)
    kwargs["nominator"] = nominator
    anti_key = rng.choice(["kubernetes.io/hostname", "zone"])
    wave = []
    for k in range(rng.randint(6, 12)):
        prio = rng.choice([100, 60]) if case == "two-prio" else 100
        pod = make_pod(
            f"hi-{k}", cpu=f"{rng.choice([1500, 2500, 3500])}m",
            memory="512Mi", priority=prio,
            labels={"app": "s"} if case == "spread" else None)
        if case == "spread":
            pod.spec.topology_spread_constraints = _spread("s")
        if case == "affinity":
            pod.spec.affinity = _affinity(anti_key)
        wave.append(pod)
    return nodes, pods, wave, kwargs


def _plan(nodes, pods, wave, kwargs, resident: bool, backend=None):
    kw = dict(kwargs)
    nominator = kw.pop("nominator")
    planner = DevicePreemptionPlanner(
        Snapshot.from_objects(pods, nodes), nominator,
        backend or _mk_backend(nodes, pods),
        eligibility={v1.pod_key(p): (True, False) for p in wave},
        resident_inputs=resident, wave_launch=False, **kw)
    cands = planner.plan(wave)
    return planner, cands


def _summary(cands):
    return [
        None if c is None else
        (c.node_name, [p.metadata.name for p in c.victims],
         c.num_pdb_violations)
        for c in cands
    ]


@pytest.mark.parametrize("case", CASES)
def test_resident_matches_full(case):
    """Every launch of a resident planner returns the forced-full
    planner's candidate list and fits_now, and delta launches ran."""
    rng = random.Random(f"resident-{case}")
    delta0 = _inputs("delta", "resident")
    preempted = violations = 0
    for trial in range(6):
        nodes, pods, wave, kwargs = _wave_case(case, rng)
        backend = _mk_backend(nodes, pods)
        full, fcands = _plan(nodes, pods, wave, kwargs, False, backend)
        res, rcands = _plan(nodes, pods, wave, kwargs, True, backend)
        assert full.planner_paths == ["device"] * len(wave), trial
        assert res.planner_paths == full.planner_paths, trial
        assert res.fits_now == full.fits_now, trial
        assert _summary(rcands) == _summary(fcands), trial
        preempted += sum(c is not None for c in rcands)
        violations += sum(c.num_pdb_violations for c in rcands if c)
    assert preempted >= 6, preempted
    assert violations or case != "pdb"
    assert _inputs("delta", "resident") > delta0


def _both(nodes, pods, wave, **kw):
    """Candidates of a forced-full and of a resident planner."""
    out = []
    for resident in (False, True):
        planner, cands = _plan(nodes, pods, wave,
                               {"nominator": PodNominator(), **kw}, resident)
        assert planner.planner_paths == ["device"] * len(wave)
        out.append(_summary(cands))
    assert out[0] == out[1]
    return out[1]


def test_second_claim_on_a_node_rederives_its_pdb_split():
    """Budget 1 over four victims on one node: the most important one
    is within the budget, the rest violate. Once a claim takes it, the
    next most important is within the budget — the delta re-derives the
    claimed row's violating split, not only its validity."""
    nodes = [make_node("n0", cpu="4", pods=110)]
    pods = []
    for j in range(4):
        p = make_pod(f"a{j}", cpu="900m", memory="64Mi", node_name="n0",
                     priority=1, labels={"app": "a"})
        p.status.start_time = float(j)
        pods.append(p)
    wave = [make_pod(f"hi-{k}", cpu="900m", memory="64Mi", priority=100)
            for k in range(3)]
    assert _both(nodes, pods, wave, pdbs=[_pdb("a", 1)]) == [
        ("n0", ["a0"], 0), ("n0", ["a1"], 0), ("n0", ["a2"], 0)]


def test_claimed_affinity_anchor_drains_the_cluster_total():
    """A preemptor that matches its own required affinity may go where
    no pod of the term is left cluster-wide. The first claim takes the
    only such pod; the second preemptor then places in the other zone
    only because the claimed pod drains the term's cluster total."""
    aff = v1.Affinity(pod_affinity=v1.PodAffinity(
        required_during_scheduling_ignored_during_execution=[
            v1.PodAffinityTerm(
                label_selector=v1.LabelSelector(match_labels={"app": "y"}),
                topology_key="zone",
            )
        ]
    ))
    nodes = [make_node(f"n{i}", cpu="4", pods=110, labels={"zone": f"z{i}"})
             for i in range(2)]
    pods = [make_pod("y", cpu="3500m", node_name="n0", priority=1,
                     labels={"app": "y"}),
            make_pod("z", cpu="3500m", node_name="n1", priority=1,
                     labels={"app": "z"})]
    wave = [make_pod(f"hi-{k}", cpu="3000m", priority=100,
                     labels={"app": "y"}, affinity=aff) for k in range(2)]
    assert _both(nodes, pods, wave) == [("n0", ["y"], 0), ("n1", ["z"], 0)]


def test_moved_pdb_budget_takes_the_full_path():
    """A claim that moves a PDB budget of the books moves every node's
    violating split: the next launch uploads whole, and still agrees
    with a planner that always does."""

    class Consuming(DevicePreemptionPlanner):
        def _claim(self, cand, pod, prio, req):
            super()._claim(cand, pod, prio, req)
            self._pdb_allowed[:] = self._pdb_allowed - 1

    nodes = [make_node(f"n{i}", cpu="4", pods=10) for i in range(6)]
    pods = [
        make_pod(f"low-{i}-{j}", cpu="900m", memory="64Mi",
                 node_name=f"n{i}", priority=1, labels={"app": "a"})
        for i in range(6) for j in range(4)
    ]
    pdb = _pdb("a", 6)
    wave = [make_pod(f"hi-{k}", cpu="2500m", memory="64Mi", priority=100)
            for k in range(4)]
    out = []
    pdb0 = _inputs("full", "pdb")
    for resident in (False, True):
        planner = Consuming(
            Snapshot.from_objects(pods, nodes), PodNominator(),
            _mk_backend(nodes, pods), pdbs=[pdb],
            eligibility={v1.pod_key(p): (True, False) for p in wave},
            resident_inputs=resident, wave_launch=False)
        out.append(_summary(planner.plan(wave)))
    assert out[0] == out[1]
    assert all(c is not None for c in out[1])
    assert _inputs("full", "pdb") - pdb0 == 3


def _burst(n_nodes: int, n_pods: int):
    """The bursts cell in small: four 900m priority-0 pods on every
    4-CPU node, a wave of 3000m priority-10 pods that each evict
    three."""
    nodes = [make_node(f"n{i}", cpu="4", memory="32Gi", pods=110)
             for i in range(n_nodes)]
    pods = [
        make_pod(f"low-{i}-{j}", cpu="900m", memory="500Mi",
                 node_name=f"n{i}", priority=0)
        for i in range(n_nodes) for j in range(4)
    ]
    wave = [make_pod(f"high-{k}", cpu="3000m", memory="500Mi", priority=10)
            for k in range(n_pods)]
    return nodes, pods, wave


def test_wave_compiles_nothing_after_its_first_two_launches(monkeypatch):
    """A 250-preemptor wave: the first launch (no nominated load yet)
    and the second (the first claim's nominee) may compile; every later
    launch is a delta into the same program."""
    from kubernetes_tpu.ops.whatif import WhatifContext
    from kubernetes_tpu.utils.device import compile_meter

    meter = compile_meter()
    before = []
    run = WhatifContext.run

    def counting_run(self, *a, **kw):
        before.append(meter.read()["requests"])
        return run(self, *a, **kw)

    monkeypatch.setattr(WhatifContext, "run", counting_run)
    nodes, pods, wave = _burst(260, 250)
    delta0 = _inputs("delta", "resident")
    planner, cands = _plan(nodes, pods, wave, {"nominator": PodNominator()},
                           True)
    assert planner.planner_paths == ["device"] * 250
    assert all(c is not None and len(c.victims) == 3 for c in cands)
    assert len(before) == 250
    assert meter.read()["requests"] == before[2]
    assert _inputs("delta", "resident") - delta0 == 249


def test_fault_mid_wave_falls_back_to_the_full_path():
    """A what-if fault mid-wave: the faulted preemptor falls to the fast
    rung, the launch after it uploads whole (its donated inputs are
    gone), and the wave still matches the fast rung's, which the oracle
    pins."""
    from kubernetes_tpu.testing.faults import FaultInjector

    nodes, pods, wave = _burst(24, 16)
    backend = _mk_backend(nodes, pods)
    inj = FaultInjector()
    backend.faults = inj
    launches = []
    check = backend.check_whatif_fault

    def fault_at_fifth():
        launches.append(1)
        if len(launches) == 5:
            inj.arm("raise-whatif", shots=1)
        check()

    backend.check_whatif_fault = fault_at_fifth
    fault0 = _inputs("full", "fault")
    planner = DevicePreemptionPlanner(
        Snapshot.from_objects(pods, nodes), PodNominator(), backend,
        eligibility={v1.pod_key(p): (True, True) for p in wave},
        wave_launch=False)
    cands = planner.plan(wave)
    assert inj.injected.get("raise-whatif") == 1
    assert planner.planner_paths == ["device"] * 4 + ["fast"] + \
        ["device"] * 11
    assert _inputs("full", "fault") - fault0 == 1
    fast = FastPreemptionPlanner(
        Snapshot.from_objects(pods, nodes), PodNominator()).plan(wave)
    assert _summary(cands) == _summary(fast)
