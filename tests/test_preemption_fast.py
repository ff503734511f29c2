"""Preemption-planner parity vs the oracle DefaultPreemption plugin.

The fast planner (scheduler/preemption.py) replaces the per-node
selectVictimsOnNode dry-run with one vectorized pass whenever the
preemptor's filter envelope reduces to static node gates + resource fit.
Inside that envelope its decisions must be EXACTLY the oracle's —
default_preemption.go:320 dryRunPreemption semantics — which this suite
pins with randomized clusters (the same strategy test_kernel_parity.py
uses for the scheduling kernel).

The DEVICE planner (scheduler/preemption_device.py + ops/whatif.py) is
the rung above: victim search as one fused what-if launch per preemptor.
Its parity surface is pinned three ways here: device vs fast vs oracle
on the fast envelope (randomized, PDBs, nominated load, start times),
and device vs oracle on the affinity / topology-spread extension the
numpy envelope must reject.
"""

from __future__ import annotations

import random

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.scheduler.framework.interface import CycleState
from kubernetes_tpu.scheduler.framework.snapshot import Snapshot
from kubernetes_tpu.scheduler.internal.nominator import PodNominator
from kubernetes_tpu.scheduler.preemption import (
    FastPreemptionPlanner,
    WaveAntiTerms,
    fast_eligible,
)
from kubernetes_tpu.scheduler.preemption_device import (
    DevicePreemptionPlanner,
    device_eligible,
)
from kubernetes_tpu.scheduler.tpu_backend import TPUBackend
from kubernetes_tpu.testing.synth import make_node, make_pod

from .test_preemption import _post_filter


def _mk_backend(nodes, pods) -> TPUBackend:
    """A CPU TPUBackend with the cluster mirrored into its encoding via
    the CacheListener hooks — the device planner's what-if context then
    builds from a scratch snapshot of that encoding (no session needed:
    the same path the pallas/sharded sessions take)."""
    b = TPUBackend()
    b.whatif = True  # CPU default is off (platform-gated); tests opt in
    for n in nodes:
        b.on_add_node(n)
    for p in pods:
        b.on_add_pod(p, p.spec.node_name)
    return b


def _device_plan(snapshot, wave, backend, nominator=None, pdbs=None,
                 fast_ok=False):
    planner = DevicePreemptionPlanner(
        snapshot, nominator, backend, pdbs=pdbs,
        eligibility={v1.pod_key(p): (True, fast_ok) for p in wave},
    )
    cands = planner.plan(wave)
    return planner, cands


def _random_cluster(rng: random.Random, n_nodes: int):
    nodes = []
    pods = []
    for i in range(n_nodes):
        taints = None
        if rng.random() < 0.1:
            taints = [v1.Taint(key="dedicated", value="x", effect="NoSchedule")]
        nodes.append(
            make_node(
                f"n{i}",
                cpu=str(rng.choice([2, 4, 8])),
                memory="16Gi",
                pods=rng.choice([3, 5, 110]),
                unschedulable=rng.random() < 0.05,
                taints=taints,
            )
        )
        # mostly-saturated nodes: preemption paths only exercise when
        # the pending pod cannot fit anywhere as-is
        for j in range(rng.randint(2, 4)):
            pods.append(
                make_pod(
                    f"p{i}-{j}",
                    cpu=f"{rng.choice([900, 1500, 2000, 2500])}m",
                    memory=rng.choice(["64Mi", "512Mi", "2Gi"]),
                    node_name=f"n{i}",
                    priority=rng.choice([0, 1, 5, 50, 200]),
                )
            )
    return nodes, pods


def _plan_single(snapshot, pod, nominator=None):
    planner = FastPreemptionPlanner(snapshot, nominator)
    (cand,) = planner.plan([pod])
    return cand, planner.fits_now[0]


class TestParityFuzz:
    def test_matches_oracle_on_random_clusters(self):
        rng = random.Random(4)
        agree_preempt = 0
        agree_none = 0
        for trial in range(40):
            nodes, pods = _random_cluster(rng, rng.randint(3, 12))
            snapshot = Snapshot.from_objects(pods, nodes)
            pending = make_pod(
                "high",
                # 9000m exceeds every node shape: exercises the
                # no-candidate agreement too
                cpu=f"{rng.choice([1000, 2500, 3500, 9000])}m",
                memory="1Gi",
                priority=100,
            )
            assert fast_eligible(pending, snapshot, [], [])
            cand, fits_now = _plan_single(snapshot, pending)
            if fits_now:
                # the oracle never sees such pods (the scheduler only
                # preempts after a failed cycle); skip
                continue
            result, status = _post_filter(snapshot, pending)
            if cand is None:
                assert result is None, (
                    f"trial {trial}: planner found nothing, oracle chose "
                    f"{result.nominated_node_name} "
                    f"{[p.metadata.name for p in result.victims]}"
                )
                agree_none += 1
            else:
                assert result is not None, (
                    f"trial {trial}: planner chose {cand.node_name}, "
                    "oracle found nothing"
                )
                assert cand.node_name == result.nominated_node_name, trial
                assert sorted(p.metadata.name for p in cand.victims) == sorted(
                    p.metadata.name for p in result.victims
                ), trial
                agree_preempt += 1
        # the fuzz must actually exercise both outcomes
        assert agree_preempt >= 5
        assert agree_none >= 1

    def test_matches_oracle_with_nominated_load(self):
        """A node already nominated by an equal-priority pod has less
        usable capacity (framework.go:610 double-filtering)."""
        rng = random.Random(11)
        checked = 0
        for trial in range(20):
            nodes, pods = _random_cluster(rng, rng.randint(2, 6))
            snapshot = Snapshot.from_objects(pods, nodes)
            nominator = PodNominator()
            ghost = make_pod("ghost", cpu="2", memory="1Gi", priority=100)
            nominator.add_nominated_pod(
                ghost, nodes[rng.randrange(len(nodes))].metadata.name
            )
            pending = make_pod("high", cpu="2500m", memory="1Gi", priority=100)
            cand, fits_now = _plan_single(snapshot, pending, nominator)
            if fits_now:
                continue
            from .test_preemption import _framework

            f = _framework(snapshot)
            f.nominator = nominator
            state = CycleState()
            assert f.run_pre_filter_plugins(state, pending) is None
            statuses = {}
            for ni in snapshot.list():
                s = f.run_filter_plugins(state, pending, ni)
                if s:
                    statuses[ni.node.metadata.name] = next(iter(s.values()))
            plugin = f.plugins["DefaultPreemption"]
            result, status = plugin.post_filter(state, pending, statuses)
            if cand is None:
                assert result is None, trial
            else:
                assert result is not None, trial
                assert cand.node_name == result.nominated_node_name, trial
                assert sorted(p.metadata.name for p in cand.victims) == sorted(
                    p.metadata.name for p in result.victims
                ), trial
                checked += 1
        assert checked >= 3


class TestWaveSemantics:
    def test_wave_claims_distinct_victims_and_capacity(self):
        """A wave of identical preemptors on a saturated cluster: every
        pod gets a candidate, no victim is claimed twice, and no node is
        oversubscribed by the nominations."""
        nodes = [make_node(f"n{i}", cpu="4", pods=10) for i in range(20)]
        pods = [
            make_pod(f"low-{i}-{j}", cpu="900m", memory="64Mi",
                     node_name=f"n{i}", priority=1)
            for i in range(20)
            for j in range(4)
        ]
        snapshot = Snapshot.from_objects(pods, nodes)
        wave = [
            make_pod(f"hi-{k}", cpu="900m", memory="64Mi", priority=100)
            for k in range(20)
        ]
        planner = FastPreemptionPlanner(snapshot, PodNominator())
        cands = planner.plan(wave)
        assert all(c is not None for c in cands)
        victim_keys = [v1.pod_key(v) for c in cands for v in c.victims]
        assert len(victim_keys) == len(set(victim_keys)), "victim claimed twice"
        # nominations must never oversubscribe a node: each node holds
        # 4 victims x 0.9 cpu on 4 cpu, so at most 4 preemptors (0.9
        # each) fit even with every victim evicted
        per_node = {}
        for c in cands:
            per_node[c.node_name] = per_node.get(c.node_name, 0) + 1
            assert len(c.victims) == 1
        for node, count in per_node.items():
            assert count <= 4

    def test_wave_saturates_then_fails(self):
        """Once every lower-priority pod on a node is spoken for, later
        wave pods must not plan preemption there."""
        nodes = [make_node("n0", cpu="4", pods=10)]
        pods = [
            make_pod(f"low{j}", cpu="1900m", memory="64Mi",
                     node_name="n0", priority=1)
            for j in range(2)
        ]
        snapshot = Snapshot.from_objects(pods, nodes)
        wave = [
            make_pod(f"hi-{k}", cpu="1900m", memory="64Mi", priority=100)
            for k in range(4)
        ]
        planner = FastPreemptionPlanner(snapshot, PodNominator())
        cands = planner.plan(wave)
        # 2 victims, each freeing room for one preemptor; the first two
        # plans claim them, the rest find nothing
        assert sum(1 for c in cands if c is not None) == 2
        assert sum(1 for c in cands if c is None) == 2

    def test_fits_now_detected(self):
        nodes = [make_node("n0", cpu="4"), make_node("n1", cpu="4")]
        pods = [make_pod("low", cpu="3500m", node_name="n0", priority=1)]
        snapshot = Snapshot.from_objects(pods, nodes)
        pending = make_pod("hi", cpu="1", priority=100)
        cand, fits_now = _plan_single(snapshot, pending)
        assert fits_now and cand is None


class TestQueueActivate:
    def test_activate_skips_backoff(self):
        from kubernetes_tpu.scheduler.internal.queue import PriorityQueue

        q = PriorityQueue(pod_initial_backoff=100.0, pod_max_backoff=100.0)
        pod = make_pod("p", cpu="1")
        q.add(pod)
        info = q.pop(timeout=0)
        assert info is not None
        q.add_unschedulable_if_not_present(info, q.scheduling_cycle)
        # parked in unschedulableQ: a plain pop times out
        assert q.pop(timeout=0) is None
        assert q.activate(pod)
        got = q.pop(timeout=0)
        assert got is not None and got.pod.metadata.name == "p"
        # not parked anywhere now
        assert not q.activate(pod)

    def test_activate_from_backoff_queue(self):
        from kubernetes_tpu.scheduler.internal.queue import PriorityQueue

        q = PriorityQueue(pod_initial_backoff=100.0, pod_max_backoff=100.0)
        pod = make_pod("p", cpu="1")
        q.add(pod)
        info = q.pop(timeout=0)
        q.move_all_to_active_or_backoff_queue("NodeAdd")  # bump move cycle
        q.add_unschedulable_if_not_present(info, 0)  # -> backoffQ (raced)
        assert q.pop(timeout=0) is None  # 100s backoff
        assert q.activate(pod)
        assert q.pop(timeout=0) is not None


class TestInFlightTracking:
    def test_preemptor_activates_after_last_victim_echo(self):
        """End-to-end through the live loop on the CPU backend of the
        TPU scheduler: a preemptor waits parked until every victim's
        delete echoes, then binds on its nominated node without waiting
        out backoff."""
        import time

        from kubernetes_tpu.apiserver import APIServer
        from kubernetes_tpu.client import Clientset, SharedInformerFactory

        api = APIServer()
        cs = Clientset(api)
        cs.nodes.create(make_node("n0", cpu="4", pods=10))
        for j in range(4):
            cs.pods.create(
                make_pod(f"low{j}", cpu="900m", memory="64Mi",
                         node_name="", priority=1)
            )
        factory = SharedInformerFactory(cs)
        from kubernetes_tpu.scheduler.scheduler import Scheduler

        sched = Scheduler(cs, factory, backend="tpu",
                          pod_initial_backoff=30.0, pod_max_backoff=30.0)
        factory.start()
        assert factory.wait_for_cache_sync()
        sched.start()
        try:
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                pods, _ = cs.pods.list(namespace="default")
                if sum(1 for p in pods if p.spec.node_name) == 4:
                    break
                time.sleep(0.05)
            hi = make_pod("hi", cpu="900m", memory="64Mi", priority=100)
            cs.pods.create(hi)
            # 30s backoff configured: binding within a few seconds proves
            # the activate path, not the backoff clock, re-admitted it
            deadline = time.monotonic() + 20
            bound = False
            while time.monotonic() < deadline:
                got = cs.pods.get("hi", "default")
                if got.spec.node_name:
                    bound = True
                    break
                time.sleep(0.05)
            assert bound, "preemptor did not bind"
            assert got.spec.node_name == "n0"
            pods, _ = cs.pods.list(namespace="default")
            assert sum(1 for p in pods if p.metadata.name.startswith("low")
                       and p.spec.node_name) == 3
            # tracking state drained
            assert not sched._node_waves
            assert not sched._inflight_preemptors
        finally:
            sched.stop()
            factory.stop()


class TestEligibility:
    def test_gates(self):
        nodes = [make_node("n0")]
        snapshot = Snapshot.from_objects([], nodes)
        pod = make_pod("p", cpu="1", priority=10)
        assert fast_eligible(pod, snapshot, [], [])
        # PDBs are inside the envelope now (vectorized PDB partitioning)
        assert fast_eligible(pod, snapshot, [object()], [])
        assert not fast_eligible(pod, snapshot, [], [object()])  # extenders
        never = make_pod("p2", cpu="1", priority=10)
        never.spec.preemption_policy = "Never"
        assert not fast_eligible(never, snapshot, [], [])
        spread = make_pod("p3", cpu="1", priority=10)
        spread.spec.topology_spread_constraints = [
            v1.TopologySpreadConstraint(
                max_skew=1, topology_key="zone",
                when_unsatisfiable="DoNotSchedule",
            )
        ]
        assert not fast_eligible(spread, snapshot, [], [])
        # required anti-affinity gates per POD: only a preemptor the
        # term MATCHES falls back (one anti pod must no longer disable
        # the planner for the whole cluster — VERDICT r4 #6)
        anti = make_pod(
            "anti", cpu="1", node_name="n0",
            affinity=v1.Affinity(
                pod_anti_affinity=v1.PodAntiAffinity(
                    required_during_scheduling_ignored_during_execution=[
                        v1.PodAffinityTerm(
                            label_selector=v1.LabelSelector(
                                match_labels={"app": "x"}
                            ),
                            topology_key="kubernetes.io/hostname",
                        )
                    ]
                )
            ),
        )
        snapshot2 = Snapshot.from_objects([anti], nodes)
        assert fast_eligible(pod, snapshot2, [], [])  # no label match
        matched = make_pod("pm", cpu="1", priority=10,
                           labels={"app": "x"})
        assert not fast_eligible(matched, snapshot2, [], [])


class TestPDBParityFuzz:
    """PDB-covered victims ride the planner: filterPodsWithPDBViolation
    partitioning, violating-first reprieve, and the violations-first
    pick ladder must match the oracle exactly."""

    def _random_pdb_cluster(self, rng: random.Random, n_nodes: int):
        nodes, pods = [], []
        # sometimes every pod shares one app + an exhausted budget, so
        # violations are unavoidable and survive into the chosen
        # candidate (the violations ladder + violating-first reprieve
        # both get exercised)
        apps = ["a", "b", "c"] if rng.random() < 0.5 else ["a"]
        for i in range(n_nodes):
            nodes.append(make_node(
                f"n{i}", cpu=str(rng.choice([2, 4, 8])), memory="16Gi",
                pods=rng.choice([4, 6, 110]),
            ))
            for j in range(rng.randint(2, 6)):
                pod = make_pod(
                    f"p{i}-{j}",
                    cpu=f"{rng.choice([900, 1500, 2000, 2500])}m",
                    memory=rng.choice(["64Mi", "512Mi"]),
                    node_name=f"n{i}",
                    priority=rng.choice([0, 1, 5, 50]),
                    labels={"app": rng.choice(apps)},
                )
                # randomized start times: MoreImportantPod order (prio
                # desc, start asc) must genuinely differ from ni.pods
                # order, or the allowance-consumption-order contract
                # (:612 sort before filterPodsWithPDBViolation) is
                # untested
                pod.status.start_time = rng.random() * 100.0
                pods.append(pod)
        pdbs = []
        for k in range(rng.randint(1, 2)):
            pdbs.append(v1.PodDisruptionBudget(
                metadata=v1.ObjectMeta(name=f"pdb{k}", namespace="default"),
                spec=v1.PodDisruptionBudgetSpec(
                    selector=v1.LabelSelector(
                        match_labels={"app": rng.choice(apps)}),
                ),
                status=v1.PodDisruptionBudgetStatus(
                    # 1/2/3 with up to 6 matching victims per node: the
                    # PARTIALLY consumable range, where which victims
                    # land in the violating group depends entirely on
                    # consumption order
                    disruptions_allowed=rng.choice([0, 1, 2, 3]),
                ),
            ))
        return nodes, pods, pdbs

    def test_pdb_partial_budget_consumed_in_importance_order(self):
        """A budget covering MORE victims than it allows must be
        consumed in MoreImportantPod order (priority desc, earlier start
        first — the :612 sort runs before filterPodsWithPDBViolation),
        so the LEAST important victims land in the violating group.
        Consuming in ni.pods order instead flips which pods violate, and
        the violating-first eviction ORDER makes that observable."""
        nodes = [make_node("n0", cpu="4", memory="16Gi", pods=110)]
        specs = [  # (name, priority, start) in ni.pods order
            ("p0", 0, 5.0), ("p1", 10, 1.0), ("p2", 10, 3.0), ("p3", 5, 2.0),
        ]
        pods = []
        for name, prio, start in specs:
            p = make_pod(name, cpu="900m", node_name="n0", priority=prio,
                         labels={"app": "db"})
            p.status.start_time = start
            pods.append(p)
        pdb = v1.PodDisruptionBudget(
            metadata=v1.ObjectMeta(name="db-pdb", namespace="default"),
            spec=v1.PodDisruptionBudgetSpec(
                selector=v1.LabelSelector(match_labels={"app": "db"})),
            status=v1.PodDisruptionBudgetStatus(disruptions_allowed=2),
        )
        snapshot = Snapshot.from_objects(pods, nodes)
        # needs every victim gone: no reprieve, all four evicted
        pending = make_pod("high", cpu="3900m", priority=100)
        planner = FastPreemptionPlanner(snapshot, None, pdbs=[pdb])
        (cand,) = planner.plan([pending])
        assert cand is not None and not planner.fits_now[0]
        # consumption order p1(10,1) p2(10,3) p3(5) p0(0): the budget's
        # two allowances go to p1+p2, so p3+p0 violate — and evict FIRST
        assert cand.num_pdb_violations == 2
        assert [p.metadata.name for p in cand.victims] == \
            ["p3", "p0", "p1", "p2"]
        result, status = _post_filter(snapshot, pending, pdbs=[pdb])
        assert result is not None
        assert [p.metadata.name for p in result.victims] == \
            [p.metadata.name for p in cand.victims]

    def test_matches_oracle_with_pdbs(self):
        rng = random.Random(21)
        agree_preempt = 0
        saw_violations = 0
        for trial in range(40):
            nodes, pods, pdbs = self._random_pdb_cluster(
                rng, rng.randint(3, 10))
            snapshot = Snapshot.from_objects(pods, nodes)
            pending = make_pod(
                "high",
                cpu=f"{rng.choice([1000, 2500, 3500, 9000])}m",
                memory="1Gi", priority=100,
            )
            assert fast_eligible(pending, snapshot, pdbs, [])
            planner = FastPreemptionPlanner(snapshot, None, pdbs=pdbs)
            (cand,) = planner.plan([pending])
            if planner.fits_now[0]:
                continue
            result, status = _post_filter(snapshot, pending, pdbs=pdbs)
            if cand is None:
                assert result is None, trial
            else:
                assert result is not None, trial
                assert cand.node_name == result.nominated_node_name, trial
                assert [p.metadata.name for p in cand.victims] == [
                    p.metadata.name for p in result.victims
                ], trial
                agree_preempt += 1
                if cand.num_pdb_violations:
                    saw_violations += 1
        assert agree_preempt >= 8
        assert saw_violations >= 1  # the fuzz must exercise violations

    def test_pdb_protected_node_avoided(self):
        """Two equivalent nodes; the victims on one are PDB-protected
        with no disruptions left — the planner must pick the other
        (fewest violations is the FIRST pick-one criterion)."""
        nodes = [make_node("n0", cpu="4"), make_node("n1", cpu="4")]
        pods = [
            make_pod("v0", cpu="3500m", node_name="n0", priority=1,
                     labels={"app": "db"}),
            make_pod("v1", cpu="3500m", node_name="n1", priority=1,
                     labels={"app": "web"}),
        ]
        pdb = v1.PodDisruptionBudget(
            metadata=v1.ObjectMeta(name="db-pdb", namespace="default"),
            spec=v1.PodDisruptionBudgetSpec(
                selector=v1.LabelSelector(match_labels={"app": "db"})),
            status=v1.PodDisruptionBudgetStatus(disruptions_allowed=0),
        )
        snapshot = Snapshot.from_objects(pods, nodes)
        pending = make_pod("hi", cpu="2", priority=100)
        planner = FastPreemptionPlanner(snapshot, None, pdbs=[pdb])
        (cand,) = planner.plan([pending])
        assert cand is not None
        assert cand.node_name == "n1"
        assert cand.num_pdb_violations == 0

    def test_pdb_wave_throughput_envelope(self):
        """A whole wave with PDBs present plans through the planner (no
        oracle fallback) and claims distinct victims."""
        from kubernetes_tpu.scheduler.internal.nominator import PodNominator

        nodes = [make_node(f"n{i}", cpu="4", pods=10) for i in range(10)]
        pods = [
            make_pod(f"low-{i}-{j}", cpu="900m", memory="64Mi",
                     node_name=f"n{i}", priority=1,
                     labels={"app": "w"})
            for i in range(10) for j in range(4)
        ]
        pdb = v1.PodDisruptionBudget(
            metadata=v1.ObjectMeta(name="w-pdb", namespace="default"),
            spec=v1.PodDisruptionBudgetSpec(
                selector=v1.LabelSelector(match_labels={"app": "w"})),
            status=v1.PodDisruptionBudgetStatus(disruptions_allowed=100),
        )
        snapshot = Snapshot.from_objects(pods, nodes)
        wave = [
            make_pod(f"hi-{k}", cpu="900m", memory="64Mi", priority=100)
            for k in range(10)
        ]
        planner = FastPreemptionPlanner(
            snapshot, PodNominator(), pdbs=[pdb])
        cands = planner.plan(wave)
        assert all(c is not None for c in cands)
        victim_keys = [v1.pod_key(v) for c in cands for v in c.victims]
        assert len(victim_keys) == len(set(victim_keys))
        assert all(c.num_pdb_violations == 0 for c in cands)


class TestDeviceParityFuzz:
    """Three-way parity: device what-if planner vs numpy fast planner vs
    the oracle DefaultPreemption plugin, on the fast envelope (where all
    three run). The device rung must be bit-identical on node choice,
    victim sets, victim ORDER, and PDB accounting."""

    def test_three_way_random_clusters(self):
        rng = random.Random(7)
        agree = none = 0
        for trial in range(25):
            nodes, pods = _random_cluster(rng, rng.randint(3, 10))
            snapshot = Snapshot.from_objects(pods, nodes)
            backend = _mk_backend(nodes, pods)
            pending = make_pod(
                "high",
                cpu=f"{rng.choice([1000, 2500, 3500, 9000])}m",
                memory="1Gi", priority=100,
            )
            dp, (dc,) = _device_plan(
                snapshot, [pending], backend, nominator=PodNominator())
            assert dp.planner_paths == ["device"], (trial, dp.planner_paths)
            fp = FastPreemptionPlanner(snapshot, PodNominator())
            (fc,) = fp.plan([pending])
            assert dp.fits_now == fp.fits_now, trial
            if dp.fits_now[0]:
                continue
            result, _ = _post_filter(snapshot, pending)
            if dc is None:
                assert fc is None and result is None, trial
                none += 1
            else:
                assert fc is not None and result is not None, trial
                assert dc.node_name == fc.node_name \
                    == result.nominated_node_name, trial
                assert [p.metadata.name for p in dc.victims] == [
                    p.metadata.name for p in fc.victims
                ], trial
                assert sorted(p.metadata.name for p in dc.victims) == sorted(
                    p.metadata.name for p in result.victims
                ), trial
                agree += 1
        assert agree >= 4 and none >= 1

    def test_three_way_with_pdbs(self):
        """Random partial budgets + random start times: the violating
        split, violating-first reprieve ORDER, and the violations-first
        pick ladder ride the device rung bit-identically."""
        helper = TestPDBParityFuzz()
        rng = random.Random(33)
        agree = saw_violations = 0
        for trial in range(15):
            nodes, pods, pdbs = helper._random_pdb_cluster(
                rng, rng.randint(3, 8))
            snapshot = Snapshot.from_objects(pods, nodes)
            backend = _mk_backend(nodes, pods)
            pending = make_pod(
                "high",
                cpu=f"{rng.choice([1000, 2500, 3500, 9000])}m",
                memory="1Gi", priority=100,
            )
            dp, (dc,) = _device_plan(snapshot, [pending], backend, pdbs=pdbs)
            assert dp.planner_paths == ["device"], trial
            fp = FastPreemptionPlanner(snapshot, None, pdbs=pdbs)
            (fc,) = fp.plan([pending])
            assert dp.fits_now == fp.fits_now, trial
            if dp.fits_now[0]:
                continue
            result, _ = _post_filter(snapshot, pending, pdbs=pdbs)
            if dc is None:
                assert fc is None and result is None, trial
            else:
                assert dc.node_name == fc.node_name \
                    == result.nominated_node_name, trial
                assert [p.metadata.name for p in dc.victims] \
                    == [p.metadata.name for p in fc.victims] \
                    == [p.metadata.name for p in result.victims], trial
                assert dc.num_pdb_violations == fc.num_pdb_violations, trial
                agree += 1
                if dc.num_pdb_violations:
                    saw_violations += 1
        assert agree >= 4
        assert saw_violations >= 1

    def test_device_pdb_partial_budget_order(self):
        """The directed allowance-consumption-ORDER pin, through the
        device rung: violating victims evict FIRST."""
        nodes = [make_node("n0", cpu="4", memory="16Gi", pods=110)]
        specs = [("p0", 0, 5.0), ("p1", 10, 1.0), ("p2", 10, 3.0),
                 ("p3", 5, 2.0)]
        pods = []
        for name, prio, start in specs:
            p = make_pod(name, cpu="900m", node_name="n0", priority=prio,
                         labels={"app": "db"})
            p.status.start_time = start
            pods.append(p)
        pdb = v1.PodDisruptionBudget(
            metadata=v1.ObjectMeta(name="db-pdb", namespace="default"),
            spec=v1.PodDisruptionBudgetSpec(
                selector=v1.LabelSelector(match_labels={"app": "db"})),
            status=v1.PodDisruptionBudgetStatus(disruptions_allowed=2),
        )
        snapshot = Snapshot.from_objects(pods, nodes)
        pending = make_pod("high", cpu="3900m", priority=100)
        dp, (dc,) = _device_plan(
            snapshot, [pending], _mk_backend(nodes, pods), pdbs=[pdb])
        assert dp.planner_paths == ["device"]
        assert dc is not None
        assert [p.metadata.name for p in dc.victims] == \
            ["p3", "p0", "p1", "p2"]
        assert dc.num_pdb_violations == 2

    def test_three_way_with_nominated_load(self):
        """A nominated ghost consumes capacity on its node through the
        framework's two-pass filter; the device rung must see it."""
        rng = random.Random(11)
        checked = 0
        for trial in range(12):
            nodes, pods = _random_cluster(rng, rng.randint(2, 6))
            snapshot = Snapshot.from_objects(pods, nodes)
            backend = _mk_backend(nodes, pods)
            nominator = PodNominator()
            ghost = make_pod("ghost", cpu="2", memory="1Gi", priority=100)
            nominator.add_nominated_pod(
                ghost, nodes[rng.randrange(len(nodes))].metadata.name
            )
            pending = make_pod("high", cpu="2500m", memory="1Gi",
                               priority=100)
            dp, (dc,) = _device_plan(
                snapshot, [pending], backend, nominator=nominator)
            fp = FastPreemptionPlanner(snapshot, nominator)
            (fc,) = fp.plan([pending])
            assert dp.fits_now == fp.fits_now, trial
            if dp.fits_now[0]:
                continue
            if dc is None:
                assert fc is None, trial
            else:
                assert fc is not None, trial
                assert dc.node_name == fc.node_name, trial
                assert [p.metadata.name for p in dc.victims] == [
                    p.metadata.name for p in fc.victims
                ], trial
                checked += 1
        assert checked >= 2


class TestDeviceEnvelope:
    """The capability extension: preemptors with pod (anti-)affinity and
    topology-spread constraints plan on the DEVICE rung — fast_eligible
    rejects them — and must match the oracle exactly."""

    def _anti_hostname(self, sel_labels):
        return v1.Affinity(pod_anti_affinity=v1.PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[
                v1.PodAffinityTerm(
                    label_selector=v1.LabelSelector(match_labels=sel_labels),
                    topology_key="kubernetes.io/hostname",
                )
            ]
        ))

    def _check_oracle(self, nodes, pods, pending, pdbs=None):
        snapshot = Snapshot.from_objects(pods, nodes)
        backend = _mk_backend(nodes, pods)
        assert not fast_eligible(
            pending, snapshot, pdbs or [], []
        ) or pending.spec.topology_spread_constraints is None
        dp, (dc,) = _device_plan(
            snapshot, [pending], backend, nominator=PodNominator(),
            pdbs=pdbs)
        assert dp.planner_paths == ["device"], dp.planner_paths
        result, _ = _post_filter(snapshot, pending, pdbs=pdbs or [])
        if dp.fits_now[0]:
            return "fits", dc, result
        if dc is None:
            assert result is None
            return "none", dc, result
        assert result is not None
        assert dc.node_name == result.nominated_node_name
        assert sorted(p.metadata.name for p in dc.victims) == sorted(
            p.metadata.name for p in result.victims
        )
        return "cand", dc, result

    def test_anti_affinity_preemptor_evicts_repeller(self):
        """The preemptor's own required anti-affinity term matches a
        victim: evicting it clears the node — a candidate the numpy
        envelope can never produce."""
        nodes = [make_node("n0", cpu="4", pods=10, labels={"zone": "z0"})]
        pods = [make_pod("vx", cpu="3500m", node_name="n0", priority=1,
                         labels={"app": "x"})]
        pending = make_pod("hi", cpu="1", priority=100,
                           affinity=self._anti_hostname({"app": "x"}))
        assert not fast_eligible(
            pending, Snapshot.from_objects(pods, nodes), [], [])
        anti = WaveAntiTerms(Snapshot.from_objects(pods, nodes))
        assert device_eligible(pending, [], anti)
        outcome, dc, _ = self._check_oracle(nodes, pods, pending)
        assert outcome == "cand"
        assert [p.metadata.name for p in dc.victims] == ["vx"]

    def test_affinity_preemptor_base_state_semantics(self):
        """A required-affinity preemptor whose term pods are all
        lower-priority: the oracle's base state (every victim removed)
        breaks the affinity, so NO candidate — the anti-monotone case
        the reprieve order makes observable. Parity, not intuition, is
        the contract."""
        aff = v1.Affinity(pod_affinity=v1.PodAffinity(
            required_during_scheduling_ignored_during_execution=[
                v1.PodAffinityTerm(
                    label_selector=v1.LabelSelector(
                        match_labels={"app": "y"}),
                    topology_key="zone",
                )
            ]
        ))
        nodes = [make_node("n0", cpu="4", pods=10, labels={"zone": "z0"})]
        pods = [
            make_pod("vy", cpu="1900m", node_name="n0", priority=1,
                     labels={"app": "y"}),
            make_pod("vz", cpu="1900m", node_name="n0", priority=1,
                     labels={"app": "z"}),
        ]
        pending = make_pod("hi", cpu="1900m", priority=100, affinity=aff)
        outcome, _, _ = self._check_oracle(nodes, pods, pending)
        assert outcome == "none"

    def test_affinity_preemptor_anchor_survives(self):
        """Same shape but the affinity anchor outranks the preemptor
        (never a victim): base feasibility holds, the filler evicts,
        and the reprieve keeps the anchor's zone count intact."""
        aff = v1.Affinity(pod_affinity=v1.PodAffinity(
            required_during_scheduling_ignored_during_execution=[
                v1.PodAffinityTerm(
                    label_selector=v1.LabelSelector(
                        match_labels={"app": "y"}),
                    topology_key="zone",
                )
            ]
        ))
        nodes = [make_node("n0", cpu="4", pods=10, labels={"zone": "z0"})]
        pods = [
            make_pod("anchor", cpu="1900m", node_name="n0", priority=200,
                     labels={"app": "y"}),
            make_pod("vz", cpu="1900m", node_name="n0", priority=1,
                     labels={"app": "z"}),
        ]
        pending = make_pod("hi", cpu="1900m", priority=100, affinity=aff)
        outcome, dc, _ = self._check_oracle(nodes, pods, pending)
        assert outcome == "cand"
        assert [p.metadata.name for p in dc.victims] == ["vz"]

    def test_spread_preemptor(self):
        """DoNotSchedule maxSkew=1 on zone: the what-if must re-derive
        the global min count per candidate (evictions on the candidate
        can lower it) to pick the right node."""
        nodes = [
            make_node("n0", cpu="4", pods=10, labels={"zone": "z0"}),
            make_node("n1", cpu="4", pods=10, labels={"zone": "z1"}),
        ]
        pods = [
            make_pod("s0", cpu="3700m", node_name="n0", priority=1,
                     labels={"app": "s"}),
            make_pod("s1", cpu="500m", node_name="n1", priority=1,
                     labels={"app": "s"}),
            make_pod("f1", cpu="3300m", node_name="n1", priority=1,
                     labels={"app": "f"}),
        ]
        pending = make_pod("hi", cpu="1", priority=100,
                           labels={"app": "s"})
        pending.spec.topology_spread_constraints = [
            v1.TopologySpreadConstraint(
                max_skew=1, topology_key="zone",
                when_unsatisfiable="DoNotSchedule",
                label_selector=v1.LabelSelector(
                    match_labels={"app": "s"}),
            )
        ]
        snapshot = Snapshot.from_objects(pods, nodes)
        assert not fast_eligible(pending, snapshot, [], [])
        outcome, dc, _ = self._check_oracle(nodes, pods, pending)
        assert outcome == "cand"
        assert dc.node_name == "n0"
        assert [p.metadata.name for p in dc.victims] == ["s0"]

    def test_spread_fuzz_vs_oracle(self):
        """Randomized spread-preemptor clusters (zones, mixed labels)
        against the oracle."""
        rng = random.Random(91)
        agree = 0
        for trial in range(12):
            zones = [f"z{i}" for i in range(rng.randint(2, 3))]
            nodes = [
                make_node(f"n{i}", cpu=str(rng.choice([2, 4])), pods=8,
                          labels={"zone": zones[i % len(zones)]})
                for i in range(rng.randint(3, 6))
            ]
            pods = []
            for i, node in enumerate(nodes):
                for j in range(rng.randint(1, 3)):
                    pods.append(make_pod(
                        f"p{i}-{j}",
                        cpu=f"{rng.choice([900, 1500, 1900])}m",
                        node_name=node.metadata.name,
                        priority=rng.choice([0, 1, 5]),
                        labels={"app": rng.choice(["s", "t"])},
                    ))
            pending = make_pod("hi", cpu="1500m", priority=100,
                               labels={"app": "s"})
            pending.spec.topology_spread_constraints = [
                v1.TopologySpreadConstraint(
                    max_skew=1, topology_key="zone",
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=v1.LabelSelector(
                        match_labels={"app": "s"}),
                )
            ]
            snapshot = Snapshot.from_objects(pods, nodes)
            backend = _mk_backend(nodes, pods)
            dp, (dc,) = _device_plan(
                snapshot, [pending], backend, nominator=PodNominator())
            assert dp.planner_paths == ["device"], trial
            if dp.fits_now[0]:
                continue
            result, _ = _post_filter(snapshot, pending)
            if dc is None:
                assert result is None, trial
            else:
                assert result is not None, trial
                assert dc.node_name == result.nominated_node_name, trial
                assert sorted(
                    p.metadata.name for p in dc.victims
                ) == sorted(p.metadata.name for p in result.victims), trial
                agree += 1
        assert agree >= 2

    def test_device_eligibility_gates(self):
        nodes = [make_node("n0")]
        snapshot = Snapshot.from_objects([], nodes)
        anti = WaveAntiTerms(snapshot)
        spread = make_pod("p", cpu="1", priority=10)
        spread.spec.topology_spread_constraints = [
            v1.TopologySpreadConstraint(
                max_skew=1, topology_key="zone",
                when_unsatisfiable="DoNotSchedule",
            )
        ]
        # affinity/spread are INSIDE the device envelope
        assert device_eligible(spread, [], anti)
        aff_pod = make_pod("p2", cpu="1", priority=10,
                           affinity=self._anti_hostname({"a": "b"}))
        assert device_eligible(aff_pod, [], anti)
        # extenders / Never / matched existing-anti stay outside
        assert not device_eligible(spread, [object()], anti)
        never = make_pod("p3", cpu="1", priority=10)
        never.spec.preemption_policy = "Never"
        assert not device_eligible(never, [], anti)
        anti_pod = make_pod(
            "anti", cpu="1", node_name="n0",
            affinity=self._anti_hostname({"app": "x"}),
        )
        snapshot2 = Snapshot.from_objects([anti_pod], nodes)
        anti2 = WaveAntiTerms(snapshot2)
        matched = make_pod("pm", cpu="1", priority=10,
                           labels={"app": "x"})
        assert not device_eligible(matched, [], anti2)


class TestDeviceWave:
    def test_wave_distinct_victims_shared_books(self):
        """A device-planned wave claims distinct victims and matches the
        pure-fast wave bit for bit (shared books across rungs)."""
        nodes = [make_node(f"n{i}", cpu="4", pods=10) for i in range(8)]
        pods = [
            make_pod(f"low-{i}-{j}", cpu="900m", memory="64Mi",
                     node_name=f"n{i}", priority=1)
            for i in range(8) for j in range(4)
        ]
        snapshot = Snapshot.from_objects(pods, nodes)
        wave = [
            make_pod(f"hi-{k}", cpu="900m", memory="64Mi", priority=100)
            for k in range(8)
        ]
        dp, cands = _device_plan(
            snapshot, wave, _mk_backend(nodes, pods),
            nominator=PodNominator())
        assert dp.planner_paths == ["device"] * 8
        assert all(c is not None for c in cands)
        vk = [v1.pod_key(v) for c in cands for v in c.victims]
        assert len(vk) == len(set(vk)), "victim claimed twice"
        fp = FastPreemptionPlanner(snapshot, PodNominator())
        fcands = fp.plan([
            make_pod(f"hi-{k}", cpu="900m", memory="64Mi", priority=100)
            for k in range(8)
        ])
        assert [
            (c.node_name, sorted(p.metadata.name for p in c.victims))
            for c in cands
        ] == [
            (c.node_name, sorted(p.metadata.name for p in c.victims))
            for c in fcands
        ]

    def test_wave_saturates_then_fails(self):
        nodes = [make_node("n0", cpu="4", pods=10)]
        pods = [
            make_pod(f"low{j}", cpu="1900m", memory="64Mi",
                     node_name="n0", priority=1)
            for j in range(2)
        ]
        snapshot = Snapshot.from_objects(pods, nodes)
        wave = [
            make_pod(f"hi-{k}", cpu="1900m", memory="64Mi", priority=100)
            for k in range(4)
        ]
        dp, cands = _device_plan(
            snapshot, wave, _mk_backend(nodes, pods),
            nominator=PodNominator())
        assert sum(1 for c in cands if c is not None) == 2
        assert sum(1 for c in cands if c is None) == 2

    def test_mixed_rung_wave_shares_books(self):
        """Half the wave rides the device rung, half the fast rung (per
        eligibility): no victim is claimed by both."""
        nodes = [make_node(f"n{i}", cpu="4", pods=10) for i in range(4)]
        pods = [
            make_pod(f"low-{i}-{j}", cpu="900m", memory="64Mi",
                     node_name=f"n{i}", priority=1)
            for i in range(4) for j in range(4)
        ]
        snapshot = Snapshot.from_objects(pods, nodes)
        wave = [
            make_pod(f"hi-{k}", cpu="900m", memory="64Mi", priority=100)
            for k in range(6)
        ]
        elig = {
            v1.pod_key(p): ((k % 2 == 0), True)
            for k, p in enumerate(wave)
        }
        planner = DevicePreemptionPlanner(
            snapshot, PodNominator(), _mk_backend(nodes, pods),
            eligibility=elig,
        )
        cands = planner.plan(wave)
        assert planner.planner_paths == [
            "device", "fast", "device", "fast", "device", "fast"
        ]
        assert all(c is not None for c in cands)
        vk = [v1.pod_key(v) for c in cands for v in c.victims]
        assert len(vk) == len(set(vk))


class TestDeviceLadder:
    def test_kill_switch_falls_to_fast(self, monkeypatch):
        nodes = [make_node("n0", cpu="4", pods=10)]
        pods = [make_pod("low", cpu="3500m", node_name="n0", priority=1)]
        snapshot = Snapshot.from_objects(pods, nodes)
        backend = _mk_backend(nodes, pods)
        backend.whatif = False  # KTPU_WHATIF=0
        pending = make_pod("hi", cpu="2", priority=100)
        dp, (dc,) = _device_plan(snapshot, [pending], backend, fast_ok=True)
        assert dp.planner_paths == ["fast"]
        assert dc is not None and dc.node_name == "n0"

    def test_injected_fault_falls_to_fast_no_double_claim(self):
        """raise-whatif mid-wave: the faulted pod falls to the fast
        rung on the SAME books — candidates stay disjoint and the live
        session is not invalidated."""
        from kubernetes_tpu.scheduler.metrics import session_rebuilds
        from kubernetes_tpu.testing.faults import FaultInjector

        nodes = [make_node(f"n{i}", cpu="4", pods=10) for i in range(3)]
        pods = [
            make_pod(f"low-{i}-{j}", cpu="900m", memory="64Mi",
                     node_name=f"n{i}", priority=1)
            for i in range(3) for j in range(4)
        ]
        snapshot = Snapshot.from_objects(pods, nodes)
        backend = _mk_backend(nodes, pods)
        inj = FaultInjector()
        inj.arm("raise-whatif", shots=1)
        backend.faults = inj
        r0 = sum(v for _, v in session_rebuilds.items())
        wave = [
            make_pod(f"hi-{k}", cpu="900m", memory="64Mi", priority=100)
            for k in range(3)
        ]
        dp, cands = _device_plan(
            snapshot, wave, backend, nominator=PodNominator(),
            fast_ok=True)
        # first pod faulted -> fast; the rest ride the device rung
        assert dp.planner_paths == ["fast", "device", "device"]
        assert inj.injected.get("raise-whatif") == 1
        assert all(c is not None for c in cands)
        vk = [v1.pod_key(v) for c in cands for v in c.victims]
        assert len(vk) == len(set(vk)), "double-claimed victim"
        assert sum(v for _, v in session_rebuilds.items()) == r0

    def test_fault_on_device_only_pod_falls_to_oracle_sentinel(self):
        from kubernetes_tpu.scheduler.preemption_device import (
            ORACLE_FALLBACK,
        )
        from kubernetes_tpu.testing.faults import FaultInjector

        nodes = [make_node("n0", cpu="4", pods=10)]
        pods = [make_pod("low", cpu="3500m", node_name="n0", priority=1,
                         labels={"app": "x"})]
        snapshot = Snapshot.from_objects(pods, nodes)
        backend = _mk_backend(nodes, pods)
        inj = FaultInjector()
        inj.arm("raise-whatif", shots=1)
        backend.faults = inj
        pending = make_pod(
            "hi", cpu="2", priority=100,
            affinity=v1.Affinity(pod_anti_affinity=v1.PodAntiAffinity(
                required_during_scheduling_ignored_during_execution=[
                    v1.PodAffinityTerm(
                        label_selector=v1.LabelSelector(
                            match_labels={"app": "x"}),
                        topology_key="kubernetes.io/hostname",
                    )
                ]
            )),
        )
        dp, (dc,) = _device_plan(snapshot, [pending], backend)
        assert dc is ORACLE_FALLBACK
        assert dp.planner_paths == ["oracle"]
        assert dp.fits_now == [False]

    def test_live_session_scratch_snapshot(self):
        """With a live HoistedSession holding the preemptor's template,
        the what-if context snapshots ITS carry (no encoding upload) and
        planning never invalidates the session."""
        from kubernetes_tpu.ops.hoisted import HoistedSession
        from kubernetes_tpu.scheduler.metrics import session_rebuilds

        nodes = [make_node(f"n{i}", cpu="4", pods=10) for i in range(4)]
        pods = [
            make_pod(f"low-{i}-{j}", cpu="900m", memory="64Mi",
                     node_name=f"n{i}", priority=1)
            for i in range(4) for j in range(4)
        ]
        snapshot = Snapshot.from_objects(pods, nodes)
        backend = _mk_backend(nodes, pods)
        probe = make_pod("probe", cpu="900m", memory="64Mi", priority=100)
        (res,) = backend.schedule_many([probe])
        assert res[1] is None  # saturated by design
        sess = backend._session
        assert isinstance(sess, HoistedSession)
        r0 = sum(v for _, v in session_rebuilds.items())
        pending = make_pod("hi", cpu="900m", memory="64Mi", priority=100)
        dp, (dc,) = _device_plan(
            snapshot, [pending], backend, nominator=PodNominator())
        assert dp.planner_paths == ["device"]
        assert dc is not None
        ctx = backend.whatif_context({
            k: v for k, v in backend.pe.encode(pending).items()
            if not k.startswith("_")
        })
        assert ctx._sess is backend._session
        assert backend._session is sess  # never torn down
        assert sum(v for _, v in session_rebuilds.items()) == r0
        # parity against the oracle from the same state
        result, _ = _post_filter(snapshot, pending)
        assert result is not None
        assert dc.node_name == result.nominated_node_name

    def test_pallas_session_routes_through_encoding_snapshot(self):
        """A live PallasSession keeps its carry in a kernel-private
        scaled layout; the what-if context must build from the
        non-donating encoding snapshot instead (construction-level on
        CPU — no pallas kernel run), leave the session untouched, and
        still match the oracle."""
        from kubernetes_tpu.ops.pallas_scan import PallasSession
        from kubernetes_tpu.scheduler.metrics import session_rebuilds

        nodes = [make_node(f"n{i}", cpu="4", pods=10) for i in range(3)]
        pods = [
            make_pod(f"low-{i}-{j}", cpu="900m", memory="64Mi",
                     node_name=f"n{i}", priority=1)
            for i in range(3) for j in range(4)
        ]
        snapshot = Snapshot.from_objects(pods, nodes)
        backend = _mk_backend(nodes, pods)
        pending = make_pod("hi", cpu="900m", memory="64Mi", priority=100)
        pa = {
            k: v for k, v in backend.pe.encode(pending).items()
            if not k.startswith("_")
        }
        sess = PallasSession(backend.enc.scratch_state(), [pa])
        backend._session = sess
        r0 = sum(v for _, v in session_rebuilds.items())
        dp, (dc,) = _device_plan(
            snapshot, [pending], backend, nominator=PodNominator())
        assert dp.planner_paths == ["device"]
        ctx = backend.whatif_context(pa)
        assert ctx._sess is not sess  # encoding-based scratch view
        assert backend._session is sess  # live session untouched
        assert sum(v for _, v in session_rebuilds.items()) == r0
        result, _ = _post_filter(snapshot, pending)
        assert dc is not None and result is not None
        assert dc.node_name == result.nominated_node_name
        assert sorted(p.metadata.name for p in dc.victims) == sorted(
            p.metadata.name for p in result.victims
        )


# -- gang-aware preemption: whole gangs or none ------------------------------


class TestGangVictimParity:
    """Gang-aware victim selection across all three planner rungs:
    co-located gang members are one indivisible eviction unit (whole
    gangs or none), a gang with any member at-or-above the preemptor's
    priority is untouchable (never loses a prefix), and the fast and
    device rungs stay bit-identical to the oracle with gang units in
    the victim pool."""

    @staticmethod
    def _stamp(pod, group, size):
        from kubernetes_tpu.scheduler.plugins.coscheduling import (
            GROUP_LABEL,
            MIN_AVAILABLE_LABEL,
        )

        pod.metadata.annotations = {
            GROUP_LABEL: group,
            MIN_AVAILABLE_LABEL: str(size),
        }

    def _random_gang_cluster(self, rng: random.Random, n_nodes: int):
        """Mostly-saturated nodes where part of the load is co-located
        gangs: evictable gangs (every member below the preemptor),
        MIXED gangs (one member outranks it — untouchable whole), and
        plain singletons, never oversubscribing a node."""
        nodes, pods = [], []
        gangs = {}
        for i in range(n_nodes):
            cap = rng.choice([4000, 8000])
            nodes.append(make_node(
                f"n{i}", cpu=f"{cap}m", memory="16Gi", pods=110))
            used = 0
            if rng.random() < 0.7:
                size = rng.randint(2, 3)
                group = f"gang-n{i}"
                mixed = rng.random() < 0.3
                members = []
                for j in range(size):
                    prio = 200 if (mixed and j == 0) else \
                        rng.choice([0, 1, 5, 50])
                    p = make_pod(
                        f"g{i}-{j}", cpu="900m", memory="256Mi",
                        node_name=f"n{i}", priority=prio,
                    )
                    self._stamp(p, group, size)
                    pods.append(p)
                    members.append(p.metadata.name)
                    used += 900
                gangs[group] = (members, mixed)
            while True:
                req = rng.choice([900, 1500, 2000])
                if used + req > cap - 500:
                    break
                pods.append(make_pod(
                    f"p{i}-{used}", cpu=f"{req}m",
                    memory=rng.choice(["64Mi", "512Mi"]),
                    node_name=f"n{i}",
                    priority=rng.choice([0, 1, 5, 50]),
                ))
                used += req
        return nodes, pods, gangs

    @staticmethod
    def _assert_whole_gangs(victims, gangs, trial):
        names = {p.metadata.name for p in victims}
        whole = 0
        for group, (members, mixed) in gangs.items():
            took = names & set(members)
            if mixed:
                assert not took, (
                    f"trial {trial}: mixed gang {group} lost members "
                    f"{sorted(took)}"
                )
            else:
                assert took in (set(), set(members)), (
                    f"trial {trial}: gang {group} torn — evicted "
                    f"{sorted(took)} of {members}"
                )
                if took:
                    whole += 1
        return whole

    def test_three_way_whole_gang_or_none_fuzz(self):
        rng = random.Random(19)
        agree = none = gang_evictions = 0
        for trial in range(30):
            nodes, pods, gangs = self._random_gang_cluster(
                rng, rng.randint(3, 9))
            snapshot = Snapshot.from_objects(pods, nodes)
            backend = _mk_backend(nodes, pods)
            pending = make_pod(
                "high",
                cpu=f"{rng.choice([2500, 3500, 9000])}m",
                memory="1Gi", priority=100,
            )
            dp, (dc,) = _device_plan(
                snapshot, [pending], backend, nominator=PodNominator())
            assert dp.planner_paths == ["device"], (trial, dp.planner_paths)
            fp = FastPreemptionPlanner(snapshot, PodNominator())
            (fc,) = fp.plan([pending])
            assert dp.fits_now == fp.fits_now, trial
            if dp.fits_now[0]:
                continue
            result, _ = _post_filter(snapshot, pending)
            if dc is None:
                assert fc is None and result is None, trial
                none += 1
                continue
            assert fc is not None and result is not None, trial
            assert dc.node_name == fc.node_name \
                == result.nominated_node_name, trial
            assert [p.metadata.name for p in dc.victims] == [
                p.metadata.name for p in fc.victims
            ], trial
            assert sorted(p.metadata.name for p in dc.victims) == sorted(
                p.metadata.name for p in result.victims
            ), trial
            agree += 1
            for plan_victims in (dc.victims, fc.victims, result.victims):
                whole = self._assert_whole_gangs(plan_victims, gangs, trial)
            gang_evictions += whole
        # the fuzz must exercise agreement, no-candidate clusters, AND
        # actual whole-gang evictions
        assert agree >= 5, agree
        assert none >= 1, none
        assert gang_evictions >= 2, gang_evictions

    def test_mixed_gang_never_loses_a_prefix(self):
        """Directed: the only way to fit the preemptor is through a
        gang with one protected member — every rung must refuse (the
        pre-unit planners evicted the two low members: a torn gang)."""
        nodes = [make_node("n0", cpu="4", memory="16Gi", pods=110)]
        pods = []
        for j, prio in enumerate([200, 1, 1]):
            p = make_pod(f"g0-{j}", cpu="1200m", memory="256Mi",
                         node_name="n0", priority=prio)
            self._stamp(p, "gang-x", 3)
            pods.append(p)
        snapshot = Snapshot.from_objects(pods, nodes)
        pending = make_pod("high", cpu="2", memory="1Gi", priority=100)
        (fc,) = FastPreemptionPlanner(snapshot, PodNominator()).plan(
            [pending])
        assert fc is None
        dp, (dc,) = _device_plan(
            snapshot, [pending], _mk_backend(nodes, pods),
            nominator=PodNominator())
        assert dc is None
        result, _ = _post_filter(snapshot, pending)
        assert result is None

    def test_gang_unit_evicts_whole_even_when_one_member_suffices(self):
        """Directed: capacity-wise one gang member would be enough, but
        the unit is indivisible — all rungs evict the whole gang, and
        agree."""
        nodes = [make_node("n0", cpu="4", memory="16Gi", pods=110)]
        pods = []
        for j in range(2):
            p = make_pod(f"g0-{j}", cpu="1500m", memory="256Mi",
                         node_name="n0", priority=1)
            self._stamp(p, "gang-y", 2)
            pods.append(p)
        snapshot = Snapshot.from_objects(pods, nodes)
        pending = make_pod("high", cpu="2", memory="1Gi", priority=100)
        (fc,) = FastPreemptionPlanner(snapshot, PodNominator()).plan(
            [pending])
        assert fc is not None
        assert sorted(p.metadata.name for p in fc.victims) == \
            ["g0-0", "g0-1"]
        dp, (dc,) = _device_plan(
            snapshot, [pending], _mk_backend(nodes, pods),
            nominator=PodNominator())
        assert dc is not None
        assert [p.metadata.name for p in dc.victims] == [
            p.metadata.name for p in fc.victims
        ]
        result, _ = _post_filter(snapshot, pending)
        assert result is not None
        assert sorted(p.metadata.name for p in result.victims) == \
            ["g0-0", "g0-1"]
