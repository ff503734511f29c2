"""Multi-host mesh scale-out: one scoring backend, 2/4/8-shard parity.

The sharded session (ops/sharded_scan.py) must be a pure performance
property — every subsystem that rides it (session carry deltas, the
what-if preemption planner) stays BIT-IDENTICAL to the single-device reference at every shard
count, including mid-run node churn. And churn itself must stay
delta-class: node add/remove on pre-warmed vocab patches the live
session's node columns instead of tearing it down (the rebuild-storm
regression the 100k-node envelope depends on).

The 8-device mesh is simulated on CPU (tests/conftest.py forces
XLA_FLAGS=--xla_force_host_platform_device_count=8 before jax imports).
"""

from __future__ import annotations

import copy
import random

import numpy as np
import pytest

import jax

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.ops.hoisted import HoistedSession
from kubernetes_tpu.parallel.sharded import make_mesh
from kubernetes_tpu.scheduler import metrics
from kubernetes_tpu.scheduler.internal.cache import SchedulerCache
from kubernetes_tpu.scheduler.tpu_backend import TPUBackend

from .util import make_node, make_pod


def _mesh_or_skip(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices")
    return make_mesh(n_devices=n)


def _node(i, cpu="8", memory="32Gi"):
    return make_node(f"node-{i}", cpu=cpu, memory=memory,
                     labels={v1.LABEL_HOSTNAME: f"node-{i}"})


def _mk_backend(n_nodes, mesh=None, cpu="8"):
    cache = SchedulerCache()
    be = TPUBackend(mesh=mesh)
    cache.add_listener(be)
    for i in range(n_nodes):
        cache.add_node(_node(i, cpu=cpu))
    return cache, be


def _rebuilds(reasons):
    return sum(val for key, val in metrics.session_rebuilds.items()
               if key and key[0] in reasons)


def _pods(prefix, n, cpu="100m", memory="64Mi", seed=None):
    rng = random.Random(seed)
    out = []
    for i in range(n):
        kw = {}
        if seed is not None:
            kw["cpu"] = f"{rng.choice([50, 100, 250, 500])}m"
            kw["memory"] = rng.choice(["64Mi", "256Mi", "1Gi"])
        else:
            kw["cpu"], kw["memory"] = cpu, memory
        out.append(make_pod(f"{prefix}-{i}", namespace="default",
                            labels={"app": prefix}, **kw))
    return out


# ------------------------------------------------- session-delta parity


class TestSessionDeltaParity:
    """Satellite: randomized pod stream scheduled through a mesh backend
    (ShardedPallasSession + KTPU_SESSION_DELTAS carry patches) vs the
    single-device hoisted backend — decisions must match pod for pod,
    with node churn injected mid-stream on the delta path."""

    @pytest.mark.parametrize("nsh", [2, 4, 8])
    def test_randomized_stream_parity(self, nsh, monkeypatch):
        mesh = _mesh_or_skip(nsh)
        monkeypatch.setenv("KTPU_SESSION_DELTAS", "1")
        monkeypatch.setenv("KTPU_NODE_HEADROOM", "0.5")

        def drive(use_mesh):
            cache, be = _mk_backend(10, mesh=mesh if use_mesh else None)
            got = []
            for batch in range(4):
                pods = _pods(f"b{batch}", 5, seed=1000 * nsh + batch)
                got += [n for _, n in be.schedule_many(pods)]
                if batch == 1 and use_mesh:
                    # churn mid-run on the DELTA path only: pre-warmed
                    # names, pod-free lanes -> the session must survive
                    # and keep emitting reference-identical decisions
                    sess = be._session
                    victims = [nm for nm in be.enc.node_names[::-1]
                               if nm and not any(n == nm for n in got)][:2]
                    for nm in victims:
                        cache.remove_node(nm)
                    # re-add LIFO (the tombstone free-stack order) so
                    # every node returns to its original lane: decisions
                    # are lane-ordered, so lane permutation would flip
                    # lowest-index tie-breaks — a different-but-valid
                    # schedule, not the bit-parity this test pins
                    for nm in reversed(victims):
                        num = int(nm.split("-")[1])
                        cache.add_node(_node(num))
                    assert be._session is sess, "churn tore the session"
            return got, type(be._session).__name__

        got, kind = drive(True)
        ref, ref_kind = drive(False)
        assert kind == "ShardedPallasSession"
        assert ref_kind == "HoistedSession"
        assert got == ref, f"nsh={nsh}: {got} != {ref}"

    def test_delta_patch_kinds_survive_churn(self, sim_mesh, monkeypatch):
        """The delta queue actually carries node-join/node-leave entries
        (not silently rebuilding), and flushing them through a schedule
        keeps parity with a fresh rebuild of the same encoding."""
        from kubernetes_tpu.ops.sharded_scan import ShardedPallasSession

        monkeypatch.setenv("KTPU_SESSION_DELTAS", "1")
        cache, be = _mk_backend(12, mesh=sim_mesh)
        warm = _pods("warm", 4)
        got = [n for _, n in be.schedule_many(warm)]
        for nm in ("node-10", "node-11"):
            cache.remove_node(nm)
        cache.add_node(_node(10))
        kinds = [d["kind"] for d in be._deltas]
        assert kinds.count("node-leave") == 2
        assert kinds.count("node-join") == 1
        tail = _pods("tail", 6)
        got += [n for _, n in be.schedule_many(tail)]

        # reference: fresh sharded session over a fresh encoding that
        # saw the same final cluster state and the same committed pods
        ref_cache, ref_be = _mk_backend(12, mesh=sim_mesh)
        for nm in ("node-10", "node-11"):
            ref_cache.remove_node(nm)
        ref_cache.add_node(_node(10))
        ref = [n for _, n in ref_be.schedule_many(copy.deepcopy(warm))]
        ref += [n for _, n in ref_be.schedule_many(copy.deepcopy(tail))]
        assert isinstance(ref_be._session, ShardedPallasSession)
        assert got == ref


# ------------------------------------------------- directed race parity


class TestDirectedParity:
    """Two pods of one batch whose second answer depends on the first's
    commit: the sharded step must land both where the single-device
    HoistedSession does."""

    @pytest.mark.parametrize("nsh", [2, 4, 8])
    def test_directed_last_slot_race(self, nsh):
        from kubernetes_tpu.ops.sharded_scan import ShardedPallasSession

        mesh = _mesh_or_skip(nsh)
        # node-0 fits ONE 2-cpu pod; two racing pods in one batch
        cache, be = _mk_backend(2, cpu="3")
        cache.remove_node("node-1")
        cache.add_node(_node(1, cpu="1"))
        pods = [make_pod(f"race-{i}", namespace="default", cpu="2",
                         memory="128Mi", labels={"app": "race"})
                for i in range(2)]
        arrays = [{k: a for k, a in be.pe.encode(p).items()
                   if not k.startswith("_")} for p in pods]
        cluster = be.enc.device_state()
        ref = HoistedSession(cluster, [arrays[0]], be.weights)
        want = HoistedSession.decisions(ref.schedule(list(arrays)))
        assert want == [0, -1], f"reference surprised us: {want}"

        sess = ShardedPallasSession(
            cluster, [arrays[0]], be.weights, mesh=mesh)
        got = ShardedPallasSession.decisions(sess.schedule(list(arrays)))
        assert got == want


# ------------------------------------------------------- what-if parity


class TestWhatifParity:
    """Satellite: the device preemption planner's what-if context built
    over a sharded cluster (whatif.from_host_snapshot mesh path) plans
    the same victims as the single-device context and the oracle."""

    @pytest.mark.parametrize("nsh", [2, 4, 8])
    def test_preemption_plan_parity(self, nsh):
        from kubernetes_tpu.scheduler.framework.snapshot import Snapshot
        from kubernetes_tpu.scheduler.internal.nominator import PodNominator
        from kubernetes_tpu.scheduler.preemption_device import (
            DevicePreemptionPlanner,
        )

        from .test_preemption import _post_filter

        mesh = _mesh_or_skip(nsh)
        nodes = [_node(i, cpu="4", memory="16Gi") for i in range(5)]
        fills = [
            make_pod(f"low-{i}-{j}", namespace="default", cpu="900m",
                     memory="64Mi", labels={"app": "low"},
                     node_name=f"node-{i}", priority=1)
            for i in range(5) for j in range(4)
        ]
        snapshot = Snapshot.from_objects(fills, nodes)
        pending = make_pod("hi", namespace="default", cpu="900m",
                           memory="64Mi", labels={"app": "hi"},
                           priority=100)

        def plan(use_mesh):
            be = TPUBackend(mesh=mesh if use_mesh else None)
            be.whatif = True  # CPU default is off; tests opt in
            for n in nodes:
                be.on_add_node(n)
            for p in fills:
                be.on_add_pod(p, p.spec.node_name)
            planner = DevicePreemptionPlanner(
                snapshot, PodNominator(), be,
                eligibility={v1.pod_key(pending): (True, False)})
            (cand,) = planner.plan([pending])
            assert planner.planner_paths == ["device"]
            assert cand is not None
            return cand

        got = plan(True)
        ref = plan(False)
        oracle, _ = _post_filter(snapshot, pending)
        assert got.node_name == ref.node_name == oracle.nominated_node_name
        assert (sorted(p.metadata.name for p in got.victims)
                == sorted(p.metadata.name for p in ref.victims)
                == sorted(p.metadata.name for p in oracle.victims))


# ------------------------------------------------- rebuild-storm gates


class TestNodeChurnStorm:
    """Node add/remove churn with pre-warmed vocab must stay
    delta-class: the live sharded session is patched per-lane, never
    torn down, and decisions stay identical to the rebuild-everything
    control. Genuinely structural events (a never-seen node name) are
    the only allowed rebuilds."""

    def test_churn_stays_delta_class(self, sim_mesh, monkeypatch):
        monkeypatch.setenv("KTPU_SESSION_DELTAS", "1")
        monkeypatch.setenv("KTPU_NODE_HEADROOM", "0.5")

        def drive(delta_patching):
            cache, be = _mk_backend(20, mesh=sim_mesh)
            be.delta_patching = delta_patching
            got = [n for _, n in be.schedule_many(_pods("warm", 4))]
            sess = be._session
            r0 = _rebuilds({"node-add", "node-remove"})
            joins = 0
            for _ in range(3):
                for i in range(12, 16):
                    cache.remove_node(f"node-{i}")
                for i in range(12, 16):
                    cache.add_node(_node(i))
                    joins += 1
            alive = be._session is sess
            got += [n for _, n in be.schedule_many(_pods("after", 6))]
            return got, alive, _rebuilds({"node-add", "node-remove"}) - r0

        got, alive, churn = drive(True)
        ref, _, _ = drive(False)
        assert got == ref
        assert alive, "pre-warmed churn tore the session down"
        assert churn == 0, f"churn caused {churn} rebuilds"

    def test_structural_event_still_rebuilds(self, sim_mesh, monkeypatch):
        """A genuinely-new node name (vocab growth) must NOT be forced
        through the delta path — correctness beats session survival."""
        monkeypatch.setenv("KTPU_SESSION_DELTAS", "1")
        cache, be = _mk_backend(8, mesh=sim_mesh)
        got = [n for _, n in be.schedule_many(_pods("warm", 2))]
        cache.add_node(make_node(
            "brand-new-node", cpu="64", memory="256Gi",
            labels={v1.LABEL_HOSTNAME: "brand-new-node"}))
        got += [n for _, n in be.schedule_many(
            _pods("big", 1, cpu="32", memory="128Gi"))]
        assert got[-1] == "brand-new-node"

    @pytest.mark.slow
    def test_storm_20k_nodes_1000_events(self, sim_mesh, monkeypatch):
        """Acceptance gate: 1000-event node add/remove churn at 20k
        nodes stays delta-class except genuine structural events —
        session_rebuilds from churn <= 2."""
        monkeypatch.setenv("KTPU_SESSION_DELTAS", "1")
        monkeypatch.setenv("KTPU_NODE_HEADROOM", "0.25")
        n_nodes = 20_000
        cache, be = _mk_backend(n_nodes, mesh=sim_mesh)
        decisions = [n for _, n in be.schedule_many(_pods("warm", 4))]
        assert all(d is not None for d in decisions)
        sess = be._session
        r0 = _rebuilds({"node-add", "node-remove"})
        rng = random.Random(13)
        removed = []
        for ev in range(1000):
            if removed and (ev % 2 == 1):
                cache.add_node(_node(removed.pop(rng.randrange(len(removed)))))
            else:
                i = rng.randrange(4, n_nodes)
                if f"node-{i}" in be.enc.node_index and i not in removed:
                    cache.remove_node(f"node-{i}")
                    removed.append(i)
        churn = _rebuilds({"node-add", "node-remove"}) - r0
        assert churn <= 2, f"rebuild storm: {churn} rebuilds in 1000 events"
        assert be._session is sess or churn > 0
        tail = [n for _, n in be.schedule_many(_pods("tail", 2))]
        assert all(d is not None for d in tail)


# --------------------------------------------------------- observability


class TestMeshObservability:
    def test_mesh_shards_gauge_and_labels(self, sim_mesh):
        _, be = _mk_backend(6, mesh=sim_mesh)
        assert metrics.mesh_shards.value() == 8.0
        be.schedule_many(_pods("warm", 2))
        keys = [k for k, val in metrics.session_builds.items() if val]
        assert any(k[-1] == "8" for k in keys), keys

    def test_no_mesh_blank_shards_label(self):
        _, be = _mk_backend(4, mesh=None)
        be.schedule_many(_pods("warm", 2))
        keys = [k for k, val in metrics.session_builds.items() if val]
        assert any(k[-1] == "" for k in keys), keys
