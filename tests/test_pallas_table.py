"""The table session (ops/pallas_scan.py): a pod spec is a row that a LIVE
session admits — no rebuild, no compile — decided bind for bind against
the first-max oracle (kubernetes_tpu/testing/oracle.py).

Runs the kernel in interpreter mode on CPU through
TPUBackend(pallas_interpret=True), at tiny clusters: semantics only.
"""

import copy
import random

import numpy as np
import pytest

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.ops import pallas_scan
from kubernetes_tpu.ops.pallas_scan import PallasSession, _balanced_quirks
from kubernetes_tpu.scheduler import metrics as sched_metrics
from kubernetes_tpu.scheduler.tpu_backend import TPUBackend
from kubernetes_tpu.testing.oracle import first_max_decisions
from kubernetes_tpu.utils import tracing

from .util import make_node, make_pod


def _nodes(n, cpu="4", memory="32Gi", zones=3):
    return [
        make_node(f"n-{i:03d}", cpu=cpu, memory=memory, pods=110, labels={
            v1.LABEL_HOSTNAME: f"n-{i:03d}",
            v1.LABEL_ZONE: f"zone-{i % zones}"})
        for i in range(n)
    ]


def _spread(labels):
    return [v1.TopologySpreadConstraint(
        max_skew=1, topology_key=v1.LABEL_ZONE,
        when_unsatisfiable="ScheduleAnyway",
        label_selector=v1.LabelSelector(match_labels=dict(labels)))]


def _anti(labels):
    return v1.Affinity(pod_anti_affinity=v1.PodAntiAffinity(
        required_during_scheduling_ignored_during_execution=[
            v1.PodAffinityTerm(
                label_selector=v1.LabelSelector(match_labels=dict(labels)),
                topology_key=v1.LABEL_HOSTNAME)]))


# the four shapes of benchmarks/configs/deployments-5000n.json: requests at
# the GCDs 50m / 64Mi, zone spread or hostname anti-affinity on the
# Deployment's own label
SHAPES = {
    "web": ("100m", "128Mi", "spread"),
    "small": ("50m", "64Mi", None),
    "ha": ("250m", "320Mi", "anti"),
    "worker": ("350m", "768Mi", "spread"),
}


def _pod(name, shape, group, selector=None):
    cpu, mem, kind = SHAPES[shape]
    labels = {"app": f"{shape}-{group}"}
    sel = selector or labels
    return make_pod(
        name, cpu=cpu, memory=mem, labels=labels,
        constraints=_spread(sel) if kind == "spread" else None,
        affinity=_anti(sel) if kind == "anti" else None)


def _mix(rng, n_specs, n_pods):
    """n_pods pods over n_specs Deployments, a Deployment's replicas in
    runs (as a ReplicaSet controller creates them)."""
    kinds = [rng.choice(["web", "web", "web", "small", "ha", "worker"])
             for _ in range(n_specs)]
    pods, i = [], 0
    while len(pods) < n_pods:
        g = i % n_specs if i < n_specs else rng.randrange(n_specs)
        for _ in range(rng.choice([1, 1, 2, 3])):
            pods.append(_pod(f"p-{len(pods):04d}", kinds[g], g))
        i += 1
    return pods[:n_pods]


def _backend(nodes, bound=(), pods=0, anti=0):
    be = TPUBackend(pallas_interpret=True)
    be.enc.set_cluster(copy.deepcopy(nodes), copy.deepcopy(list(bound)))
    be.enc.reserve(pods=pods, anti_terms=anti)
    return be


def _names(results):
    return [node for _, node in results]


def _oracle(nodes, bound, pending, be):
    return first_max_decisions(
        copy.deepcopy(nodes), copy.deepcopy(list(bound)),
        copy.deepcopy(pending))


def _rebuilds():
    return dict(sched_metrics.session_rebuilds.items())


class TestMixedSpecs:
    @pytest.mark.parametrize("n_specs", [1, 8, 9, 64, 300])
    def test_one_batch_matches_oracle(self, n_specs, monkeypatch):
        """1, 8, 9, 64 and 300 specs in ONE batch: one table session, no
        one-shot, every bind the oracle's."""
        # the room a 65536-pod reserve asks for, without its pod rows
        monkeypatch.setattr(pallas_scan, "table_capacity", lambda _r: 512)
        rng = random.Random(n_specs)
        nodes = _nodes(24)
        n_pods = max(40, n_specs + 12)
        pending = _mix(rng, n_specs, n_pods)
        be = _backend(nodes, pods=1024, anti=1024)
        r0 = _rebuilds()
        got = _names(be.schedule_many(copy.deepcopy(pending)))
        assert got == _oracle(nodes, [], pending, be)
        assert type(be._session) is PallasSession
        assert be._session.specs == len(
            {p.metadata.labels["app"] for p in pending})
        assert _rebuilds() == r0

    @pytest.mark.parametrize("pipelined", [False, True])
    def test_specs_admitted_between_launches(self, pipelined):
        """A live session takes new specs in: same object, same
        executables, no rebuild — on the synchronous path and on the
        pipelined one (dispatch_many + harvest)."""
        rng = random.Random(7)
        nodes = _nodes(18)
        waves = [_mix(rng, 6, 30)]
        for w in range(1, 4):
            more = _mix(rng, 5, 24)
            for p in more:   # new Deployments each wave
                shape, g = p.metadata.labels["app"].rsplit("-", 1)
                q = _pod(p.metadata.name + f"-w{w}", shape, int(g) + 100 * w)
                p.metadata, p.spec = q.metadata, q.spec
            waves.append(more)
        be = _backend(nodes, pods=1024, anti=1024)
        want = _oracle(nodes, [], [p for w in waves for p in w], be)
        r0 = _rebuilds()
        a0 = sched_metrics.session_template_admits.value()
        got, sess, execs = [], None, None
        for i, wave in enumerate(waves):
            pods = copy.deepcopy(wave)
            if pipelined and i:
                got += _names(be.harvest(be.dispatch_many(pods)))
            else:
                got += _names(be.schedule_many(pods))
            if sess is None:
                sess, execs = be._session, dict(be._session._exec)
            assert be._session is sess
            assert {k: id(v) for k, v in sess._exec.items()
                    if k in execs} == {k: id(v) for k, v in execs.items()}
        assert got == want
        assert _rebuilds() == r0
        # (a wave whose labels grow a vocabulary bucket splits into two
        # same-shape runs on the synchronous path: two admissions)
        assert sess.admits >= 3
        assert sched_metrics.session_template_admits.value() - a0 == sum(
            len({p.metadata.labels["app"] for p in w}) for w in waves[1:])

    def test_selector_matches_another_specs_pods(self):
        """Spec B spreads over (and spec C keeps away from) the pods of
        spec A: which rows a pod counts toward is data, not the pod's own
        Deployment. B and C are admitted AFTER pods of A were decided by
        the live session, some of them still in flight."""
        nodes = _nodes(12)
        a = [_pod(f"a-{i}", "small", 0) for i in range(14)]
        b = [_pod(f"b-{i}", "web", 1, selector={"app": "small-0"})
             for i in range(8)]
        c = [_pod(f"c-{i}", "ha", 2, selector={"app": "small-0"})
             for i in range(3)]
        more_a = [_pod(f"a2-{i}", "small", 0) for i in range(6)]
        order = a + b + c + more_a + b[:0]
        be = _backend(nodes, pods=512, anti=512)
        want = _oracle(nodes, [], order, be)
        r0 = _rebuilds()
        got = _names(be.schedule_many(copy.deepcopy(a[:6])))
        sess = be._session
        h = be.dispatch_many(copy.deepcopy(a[6:]))     # in flight
        got += _names(be.harvest(h))
        # pipelined dispatch of B with A's second batch possibly pending
        h1 = be.dispatch_many(copy.deepcopy(b))
        h2 = be.dispatch_many(copy.deepcopy(c + more_a))
        got += _names(be.harvest(h1)) + _names(be.harvest(h2))
        assert got == want
        assert be._session is sess and _rebuilds() == r0

    @pytest.mark.parametrize("seed", range(4))
    def test_irregular_requests_are_exact(self, seed):
        """Requests at the GCDs 50m / 64Mi on 4-CPU / 32Gi nodes, filled
        until nodes stand at states where float64's balanced score reads
        one less than the exact floor (and f32 reads yet another)."""
        rng = random.Random(100 + seed)
        nodes = _nodes(6)
        pending = []
        for i in range(150):
            shape = rng.choice(["web", "small", "worker", "worker", "ha"])
            pending.append(_pod(f"p-{i:03d}", shape, rng.randrange(3)))
        be = _backend(nodes, pods=512, anti=512)
        got = []
        for lo in range(0, len(pending), 50):
            got += _names(be.schedule_many(copy.deepcopy(pending[lo:lo + 50])))
        assert got == _oracle(nodes, [], pending, be)
        assert be._session._cfg.bal_int

    def test_finer_request_unit_rescales_in_place(self):
        """A spec whose requests the live GCD does not divide: the unit is
        refined on the device, the session stays."""
        nodes = _nodes(8)
        first = [_pod(f"w-{i}", "web", 0) for i in range(10)]     # 100m
        odd = [make_pod(f"o-{i}", cpu="30m", memory="48Mi",
                        labels={"app": "odd"}) for i in range(10)]
        be = _backend(nodes, pods=256)
        r0 = _rebuilds()
        got = _names(be.schedule_many(copy.deepcopy(first)))
        sess, g0 = be._session, be._session._gcd.copy()
        got += _names(be.schedule_many(copy.deepcopy(odd)))
        again = [_pod(f"x-{i}", "web", 0) for i in range(4)]
        got += _names(be.schedule_many(copy.deepcopy(again)))
        assert got == _oracle(nodes, [], first + odd + again, be)
        assert be._session is sess and _rebuilds() == r0
        assert (sess._gcd[:2] < g0[:2]).all()


class TestCapacity:
    def test_full_table_rebuilds_under_its_own_reason(self, monkeypatch):
        """A table filled to capacity, and capacity + 1: a rebuild that is
        counted as `table-full`, decisions still the oracle's."""
        monkeypatch.setattr(pallas_scan, "table_capacity", lambda _r: 64)
        nodes = _nodes(12)
        pods = [_pod(f"p-{i:03d}", "small", i) for i in range(66)]
        be = _backend(nodes, pods=256)
        r0 = _rebuilds()
        got = _names(be.schedule_many(copy.deepcopy(pods[:2])))
        sess = be._session
        assert sess.Tcap == 64
        # up to capacity: admitted, one by one and in a batch
        got += _names(be.schedule_many(copy.deepcopy(pods[2:3])))
        got += _names(be.schedule_many(copy.deepcopy(pods[3:64])))
        assert be._session is sess and sess.specs == 64
        assert _rebuilds() == r0
        got += _names(be.schedule_many(copy.deepcopy(pods[64:])))
        assert got == _oracle(nodes, [], pods, be)
        assert be._session is not sess
        assert type(be._session) is PallasSession
        moved = {k: v - r0.get(k, 0) for k, v in _rebuilds().items()
                 if v != r0.get(k, 0)}
        assert list(moved.values()) == [1]
        assert [k[0] for k in moved] == ["table-full"]
        # the rebuild kept the most recently used half, not everything
        assert be._session.specs <= 64 // 2 + 2

    def test_term_spec_in_a_term_free_session(self):
        """No reserve(anti_terms=...): the session is built without the
        term machinery, a term spec is a counted rebuild into one that
        has it."""
        nodes = _nodes(8)
        pods = [_pod(f"s-{i}", "small", 0) for i in range(4)] + [
            _pod(f"h-{i}", "ha", 1) for i in range(4)]
        be = _backend(nodes, pods=128)
        r0 = _rebuilds()
        got = _names(be.schedule_many(copy.deepcopy(pods[:4])))
        assert not be._session.dyn_ipa
        got += _names(be.schedule_many(copy.deepcopy(pods[4:])))
        assert got == _oracle(nodes, [], pods, be)
        assert be._session.dyn_ipa
        moved = {k[0] for k, v in _rebuilds().items() if v != r0.get(k, 0)}
        assert moved == {"terms-enabled"}


class TestSpansAndMetrics:
    def test_dispatch_spans_and_table_gauges(self):
        nodes = _nodes(9)
        first = [_pod(f"a-{i}", "web", i % 3) for i in range(9)]
        second = ([_pod(f"b-{i}", "ha", 10 + i % 2) for i in range(4)]
                  + [_pod(f"c-{i}", "web", 0) for i in range(3)])
        be = _backend(nodes, pods=256, anti=256)
        level = tracing.set_level(1)
        mark = tracing.RECORDER.mark()
        try:
            be.schedule_many(copy.deepcopy(first))
            be.harvest(be.dispatch_many(copy.deepcopy(second)))
        finally:
            tracing.set_level(level)
        ev = [tracing.event_dict(e)
              for e in tracing.RECORDER.snapshot(since=mark)]
        disp = [e for e in ev if e["stage"] == "dispatch"]
        assert [(e["templates"], e["term_pods"])
                for e in disp] == [(3, 0), (3, 4)]
        admits = [e for e in ev if e["name"] == "template-admit"]
        assert [(e["n"], e["rows"] > 0)
                for e in admits] == [(2, True)]
        # the seconds spent landing batches in flight first, 0.0 where
        # no batch could count toward the new specs' rows (a number, as
        # every span attribute named *_s: readers sum them)
        assert all(isinstance(e["flush_s"], float) for e in admits)
        g = sched_metrics.session_templates
        assert g.value(what="specs") == 5
        assert g.value(what="capacity") == be._session.Tcap
        assert 0 < g.value(what="rows") <= g.value(what="row_capacity")
        from kubernetes_tpu.utils.metrics import legacy_registry

        text = legacy_registry.expose()
        assert 'scheduler_session_templates{what="specs"} 5' in text
        assert "scheduler_session_template_admits_total" in text


class TestBalancedQuirks:
    @pytest.mark.parametrize("cap", [(80, 512), (40, 256), (64, 1000),
                                     (80, 500), (7, 13)])
    def test_quirks_are_all_of_the_difference(self, cap):
        """Over the WHOLE grid of node states, float64's balanced score
        equals the exact floor minus the listed quirks."""
        C, M = cap
        c = np.arange(C, dtype=np.int64)[:, None]
        m = np.arange(M, dtype=np.int64)[None, :]
        f64 = ((1.0 - np.abs(c / np.float64(C) - m / np.float64(M)))
               * 100).astype(np.int64)
        exact = (100 * (C * M - np.abs(c * M - m * C))) // (C * M)
        q = np.zeros((C, M), np.int64)
        pts = _balanced_quirks(C, M)
        q[pts[:, 0], pts[:, 1]] = 1
        assert (exact - q == f64).all()
