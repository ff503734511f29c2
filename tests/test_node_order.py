"""Node order is NAME order (api.types.node_order_key), in one definition:
the encoding's live lanes on the incremental paths and after a rebuild,
the host snapshot, the first-max oracle — whatever left or joined the
cluster, and in whatever order its nodes first arrived.

The witnesses: a seeded sequence of leaves and joins with a returning
name, a fresh name past the end and a fresh name that sorts into the
MIDDLE, on equal nodes, so that the tie-break decides most binds. Device
decisions (the interpreted Pallas table session and the hoisted one) are
held to the framework's own first-max oracle and to the benchmark's plain
reference (benchmarks/benchlib/reference.py: numpy, node INDEX order,
imports nothing of the program).
"""

import copy
import os
import random
import sys

import numpy as np
import pytest

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.models.encoding import ClusterEncoding
from kubernetes_tpu.ops.pallas_scan import PallasSession
from kubernetes_tpu.scheduler import metrics as sched_metrics
from kubernetes_tpu.scheduler.framework.snapshot import Snapshot
from kubernetes_tpu.scheduler.internal.cache import SchedulerCache
from kubernetes_tpu.scheduler.tpu_backend import TPUBackend
from kubernetes_tpu.testing.oracle import first_max_decisions
from kubernetes_tpu.utils import tracing

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from benchlib import cluster as bench_cluster  # noqa: E402
from benchlib import reference as plain  # noqa: E402

NODES = {"count": 14, "cpu": "4", "memory": "32Gi", "pods": 110, "zones": 3}
CLS = {"cpu": "100m", "memory": "128Mi", "labels": {"app": "perf"},
       "spread_zone_soft": True}
ABSENT = (4, 9)  # indices the cluster starts without: joins into the middle


def _node(i):
    return bench_cluster.build_node(i, NODES)


def _name(i):
    return bench_cluster.node_name(i)


def _pod(i):
    return bench_cluster.build_pod(f"p-{i:04d}", CLS)


def test_node_order_key_is_index_order_whatever_the_padding():
    key = v1.node_order_key
    names = [f"node-{i}" for i in (0, 1, 2, 9, 10, 11, 99, 100, 1000)]
    assert sorted(reversed(names), key=key) == names
    padded = [_name(i) for i in (0, 7, 42, 4999, 5000, 99999)]
    assert sorted(reversed(padded), key=key) == padded
    assert key("node-99999") < key("node-100000")
    assert key("node-01") != key("node-1")  # equal numbers: the name decides
    assert sorted(["b", "a-2", "a-10", "a"], key=key) == [
        "a", "a-2", "a-10", "b"]


# -- the script: what happens to the cluster, from a seed -----------------------

def _script(seed):
    """[(op, payload)]: schedule n pods / drain and remove nodes / add
    nodes. Three rounds; each round's joins hold a returning name, a fresh
    index past the end and a fresh index in the middle."""
    rng = random.Random(seed)
    live = [i for i in range(NODES["count"]) if i not in ABSENT]
    gone, fresh_mid, top = [], list(ABSENT), NODES["count"]
    ops = [("schedule", 30)]
    for _ in range(3):
        leaving = rng.sample(live, 3)
        live = [i for i in live if i not in leaving]
        ops.append(("remove", leaving))
        ops.append(("schedule", rng.randrange(7, 12)))
        joining = [top]
        top += 1
        if gone:
            joining.append(gone.pop(rng.randrange(len(gone))))
        if fresh_mid:
            joining.append(fresh_mid.pop(0))
        rng.shuffle(joining)
        gone += leaving
        live += joining
        ops.append(("add", joining))
        ops.append(("schedule", rng.randrange(9, 16)))
    return ops


class _Device:
    """The program: a scheduler cache with the TPU backend as its
    listener, nodes arriving in `arrival` order."""

    def __init__(self, kind, arrival):
        self.cache = SchedulerCache()
        self.be = TPUBackend(pallas_interpret=(kind == "pallas"))
        self.be.enc.reserve(pods=512, nodes=20)
        self.cache.add_listener(self.be)
        for i in arrival:
            self.cache.add_node(_node(i))
        self.bound = {}  # pod index -> (pod, node name)
        self.n = 0

    def schedule(self, n):
        pods = [_pod(self.n + k) for k in range(n)]
        self.n += n
        out = []
        for pod, node in self.be.schedule_many(pods):
            assert node is not None
            pod.spec.node_name = node
            self.bound[int(pod.metadata.name[2:])] = (pod, node)
            out.append(node)
        return out

    def remove(self, nodes):
        for i in nodes:
            for idx, (pod, node) in list(self.bound.items()):
                if node == _name(i):  # drained first, as an operator does
                    self.be.on_remove_pod(pod, node)
                    del self.bound[idx]
        for i in nodes:
            self.cache.remove_node(_name(i))

    def add(self, nodes):
        for i in nodes:
            self.cache.add_node(_node(i))

    def play(self, ops):
        out = []
        for op, payload in ops:
            got = getattr(self, op)(payload)
            out += got or []
        return out


def _plain_replay(ops):
    """The same script through the benchmark's plain reference: numpy,
    node INDEX order."""
    ref = plain.ReferenceCluster.from_config({"nodes": NODES})
    for i in ABSENT:
        ref.remove_node(i)
    pc = plain.PodClass(CLS)
    on = {}  # node index -> pods there
    out = []
    for op, payload in ops:
        if op == "schedule":
            for _ in range(payload):
                node = ref.decide(pc)
                on[node] = on.get(node, 0) + 1
                out.append(_name(node))
        elif op == "remove":
            for i in payload:
                for _ in range(on.pop(i, 0)):
                    ref.unplace(pc, i)
                ref.remove_node(i)
        else:
            for i in payload:
                ref.add_node(i)
    return out


def _oracle_replay(ops):
    """The same script through the framework's own plugins, first of the
    maxima in node order (testing/oracle.py)."""
    live = {i: _node(i) for i in range(NODES["count"]) if i not in ABSENT}
    bound, out, n = [], [], 0
    for op, payload in ops:
        if op == "schedule":
            pending = [_pod(n + k) for k in range(payload)]
            n += payload
            nodes = list(live.values())
            random.Random(n).shuffle(nodes)  # the oracle takes any order
            got = first_max_decisions(nodes, copy.deepcopy(bound), pending)
            bound += pending  # node_name set by the oracle
            out += got
        elif op == "remove":
            names = {_name(i) for i in payload}
            bound = [p for p in bound if p.spec.node_name not in names]
            for i in payload:
                del live[i]
        else:
            for i in payload:
                live[i] = _node(i)
    return out


def _in_name_order(enc):
    lanes = [enc.node_index[nm] for nm in sorted(
        enc.node_index, key=v1.node_order_key)]
    return lanes == sorted(lanes) and all(
        enc.node_names[ln] == nm for nm, ln in enc.node_index.items())


def _moved(counter, before):
    return {k[0]: v - before.get(k, 0) for k, v in counter.items()
            if v != before.get(k, 0)}


@pytest.mark.parametrize("kind", ["pallas", "hoisted"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_decisions_after_leaves_and_joins_are_the_oracles(kind, seed):
    """(ii) device == host oracle == plain replay in name order, with
    ties present, across a returning name, a fresh name past the end and a
    fresh name into the middle."""
    ops = _script(seed)
    want = _plain_replay(ops)
    assert want == _oracle_replay(ops)
    assert len(set(want)) > 6
    arrival = [i for i in range(NODES["count"]) if i not in ABSENT]
    joins0 = dict(sched_metrics.node_joins.items())
    leaves0 = dict(sched_metrics.node_leaves.items())
    dev = _Device(kind, arrival)
    try:
        got = dev.play(ops)
        assert got == want
        assert _in_name_order(dev.be.enc)
        if kind == "pallas":
            assert type(dev.be._session) is PallasSession
    finally:
        dev.be.close()
    joins = _moved(sched_metrics.node_joins, joins0)
    # the first twelve arrive before there are arrays: one rebuild lays
    # them out. No join after that needed the encoding rebuilt.
    assert joins.pop("structural") == 12
    assert joins.get("tail-lane", 0) >= 1
    assert joins.get("own-lane", 0) + joins.get("free-lane", 0) >= 1
    assert joins.get("shifted", 0) >= 1  # a middle join with no lane free
    assert sum(joins.values()) == sum(
        len(p) for op, p in ops if op == "add")
    assert _moved(sched_metrics.node_leaves, leaves0) == {"incremental": 9}


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_arrival_order_does_not_move_a_decision(seed):
    """(iii) the same nodes met in shuffled order: the same lanes, the same
    snapshot, the same binds."""
    ops = _script(seed)
    arrival = [i for i in range(NODES["count"]) if i not in ABSENT]
    random.Random(seed).shuffle(arrival)
    assert arrival != sorted(arrival)
    dev = _Device("pallas", arrival)
    try:
        assert dev.play(ops) == _plain_replay(ops)
        assert _in_name_order(dev.be.enc)
        snap = dev.cache.update_snapshot(Snapshot())
        names = [ni.node.metadata.name for ni in snap.node_info_list]
        assert names == sorted(names, key=v1.node_order_key)
        assert set(names) == set(dev.be.enc.node_index)
    finally:
        dev.be.close()


def test_snapshot_lists_nodes_in_name_order_after_a_return():
    cache = SchedulerCache()
    for i in (3, 0, 2, 1):
        cache.add_node(_node(i))
    snap = cache.update_snapshot(Snapshot())
    assert [ni.node.metadata.name for ni in snap.node_info_list] == [
        _name(i) for i in range(4)]
    cache.remove_node(_name(1))
    cache.add_node(_node(7))
    cache.add_node(_node(1))  # back: where its name puts it, not last
    # a pod on a node the cache never met is no node of the snapshot
    ghost = _pod(0)
    ghost.spec.node_name = _name(5)
    cache.add_pod(ghost)
    snap = cache.update_snapshot(snap)
    assert [ni.node.metadata.name for ni in snap.node_info_list] == [
        _name(i) for i in (0, 1, 2, 3, 7)]
    cache.add_node(_node(5))
    cache.remove_node(_name(0))
    snap = cache.update_snapshot(snap)
    assert [ni.node.metadata.name for ni in snap.node_info_list] == [
        _name(i) for i in (1, 2, 3, 5, 7)]


# -- (i) the encoding alone: incremental path against rebuild path -----------

def _canon(enc):
    """The arrays by NAME: what must not depend on lanes or tombstones."""
    A = enc.host_state()
    nodes = {nm: tuple(np.asarray(A[k][ln]).tobytes()
                       for k in enc._NODE_ROW_KEYS)
             for nm, ln in enc.node_index.items()}
    pods = {key: (enc.node_names[A["pnode"][px]],) + tuple(
        np.asarray(A[k][px]).tobytes() for k in ("ppair", "pkey", "pns"))
        for key, px in enc.pod_index.items()}
    dead = [ln for ln, nm in enumerate(enc.node_names) if nm is None]
    assert not A["valid"][dead].any() and not A["pod_count"][dead].any()
    assert int(A["valid"].sum()) == enc.n_nodes == len(nodes)
    return nodes, pods


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_encoding_keeps_lanes_in_name_order_on_both_paths(seed):
    rng = random.Random(seed)
    enc = ClusterEncoding()
    enc.reserve(pods=128, nodes=20)
    start = [i for i in range(NODES["count"]) if i not in ABSENT]
    arrival = list(start)
    rng.shuffle(arrival)
    bound = []
    for k in range(40):
        p = _pod(k)
        p.spec.node_name = _name(rng.choice(start))
        bound.append(p)
    enc.set_cluster([_node(i) for i in arrival], bound)
    enc.host_state()
    assert _in_name_order(enc)
    assert [enc.node_names[ln] for ln in range(12)] == [
        _name(i) for i in start]
    paths = []

    def leave(i):
        for p in [p for p in bound if p.spec.node_name == _name(i)]:
            enc.remove_pod(p)
            bound.remove(p)
        assert enc.remove_node(_name(i)) is not None
        assert enc.last_leave_path == "incremental"

    def join(i, name=None):
        node = _node(i)
        if name:
            node.metadata.name = name
            node.metadata.labels[v1.LABEL_HOSTNAME] = name
        lane = enc.add_node(node)
        paths.append(enc.last_join_path)
        assert (lane is None) == (enc.last_join_path in (
            "shifted", "structural"))
        assert not enc._rebuild_needed
        assert _in_name_order(enc)
        return lane

    # not 4's neighbours, nor the last: the lane one of them left would
    # lie where 4, or 14, sorts
    a, b, c = rng.sample([0, 1, 2, 6, 11, 12], 3)
    lane_a = enc.node_index[_name(a)]
    leave(a), leave(b), leave(c)
    assert join(14) == 12                       # past the end: the tail
    assert join(a) == lane_a                    # back: its own lane
    assert join(4) is None                      # the middle, no lane free:
    assert paths == ["tail-lane", "own-lane", "shifted"]
    # ... the rows up to the nearest free lane moved over, pods and all
    assert enc.n_lanes == 13 and enc.n_nodes == 12
    join(b), join(c)
    assert enc.n_nodes == 14 and not enc._node_free
    join(9)                                     # the middle, the tail opens
    assert paths[-1] == "shifted" and enc.n_lanes == 15
    leave(7)
    # another name where the one that left stood takes that lane
    lane_7 = enc._node_free[0]
    assert join(0, name=_name(7) + "-b") == lane_7
    assert paths[-1] == "free-lane"
    incremental = _canon(enc)
    # a pod bound since lands on its node's new lane
    late = _pod(99)
    late.spec.node_name = _name(4)
    enc.add_pod(late)
    assert not enc._rebuild_needed
    incremental = _canon(enc)
    enc._rebuild_needed = True
    rebuilt = _canon(enc)
    assert _in_name_order(enc) and enc.n_lanes == enc.n_nodes
    assert incremental == rebuilt
    # an existing name through add_node is an update: structural
    enc.add_node(_node(4))
    assert enc.last_join_path == "structural" and enc._rebuild_needed


def test_a_join_past_the_lane_space_is_structural():
    enc = ClusterEncoding()
    enc.set_cluster([_node(i) for i in range(12)], [])
    enc.host_state()
    assert enc._arrays["valid"].shape[0] == 12  # no padding to join into
    assert enc.add_node(_node(12)) is None
    assert enc.last_join_path == "structural" and enc._rebuild_needed
    enc.host_state()
    assert _in_name_order(enc) and enc.n_nodes == 13


# -- the delta path against the rebuild path ------------------------------------

@pytest.mark.parametrize("n_removed", [5, 11])
def test_pod_removals_as_deltas_equal_a_rebuilt_session(n_removed):
    """N `pod-remove` carry deltas (not a power of two: padded entries
    ride along) into the live table session, then a launch — against a
    backend that rebuilds its session from the encoding after the same
    removals, and against the oracle."""
    nodes = [_node(i) for i in range(12)]
    first = [_pod(i) for i in range(40)]
    more = [_pod(100 + i) for i in range(25)]

    def run(delta_patching):
        be = TPUBackend(pallas_interpret=True)
        be.delta_patching = delta_patching
        be.enc.set_cluster(copy.deepcopy(nodes), [])
        be.enc.reserve(pods=256)
        placed = [(p, n) for p, n in be.schedule_many(copy.deepcopy(first))]
        sess = be._session
        victims = random.Random(n_removed).sample(placed, n_removed)
        for pod, node in victims:
            pod.spec.node_name = node
            be.on_remove_pod(pod, node)
        got = [n for _, n in be.schedule_many(copy.deepcopy(more))]
        same = be._session is sess
        be.close()
        return [n for _, n in placed], victims, got, same

    tracing.set_level(1)
    mark = tracing.RECORDER.mark()
    applies0 = sched_metrics.session_delta_applies.value(kind="pod-remove")
    try:
        placed, victims, got, same = run(True)
        spans = [e for e in tracing.RECORDER.snapshot(since=mark)
                 if e[2] == "delta-apply"]
    finally:
        tracing.set_level(0)
    assert same, "the removals tore the session down"
    assert sched_metrics.session_delta_applies.value(
        kind="pod-remove") - applies0 == n_removed
    # the span says what shaped the launch: entries, and the bucket they
    # were padded to
    assert [(e[6]["n"], e[6]["bucket"]) for e in spans] == [
        (n_removed, 8 if n_removed <= 8 else 16)]
    assert spans[0][6]["entries"] >= n_removed
    placed2, _, rebuilt, same2 = run(False)
    assert placed2 == placed and not same2
    assert got == rebuilt
    bound = []
    gone = {p.metadata.name for p, _ in victims}
    for p, n in zip(copy.deepcopy(first), placed):
        if p.metadata.name not in gone:
            p.spec.node_name = n
            bound.append(p)
    assert got == first_max_decisions(
        copy.deepcopy(nodes), bound, copy.deepcopy(more))


def test_session_build_span_says_why_the_last_one_went():
    tracing.set_level(1)
    mark = tracing.RECORDER.mark()
    be = TPUBackend(pallas_interpret=True)
    try:
        be.enc.set_cluster([_node(i) for i in range(8)], [])
        be.enc.reserve(pods=64, nodes=12)
        be.schedule_many([_pod(i) for i in range(6)])
        be.on_add_node(_node(8))
        be.schedule_many([_pod(10 + i) for i in range(6)])
        builds = [e[6] for e in tracing.RECORDER.snapshot(since=mark)
                  if e[1] == "session-build"]
    finally:
        tracing.set_level(0)
        be.close()
    assert [b.get("reason") for b in builds] == ["", "node-add"]
    assert all(b["kind"] == "PallasSession" for b in builds)


# -- the zone-spread product at the counts a 5000-node cluster reaches -------

def test_spread_limbs_are_the_float64_product():
    """count * log(size + 2), truncated, for every count a zone can hold
    and every size: the int32 limb product against float64."""
    import math

    from kubernetes_tpu.ops import pallas_scan as ps

    counts = np.arange(0, ps.PTS_MAX_COUNT, 7, dtype=np.int64)
    special = np.array([4217, 5213, 19856, 20852, 21085, 21848, 22081,
                        ps.PTS_MAX_COUNT - 1], np.int64)
    f32_off = 0
    for size in (0, 1, 2, 3, 5, 8, 30, ps.VZ):
        w = math.log(size + 2)
        for c in (counts, special):
            want = (c.astype(np.float64) * w).astype(np.int64)
            assert (ps.spread_raw_exact(c, size) == want).all()
        f32_off += int(((special.astype(np.float32) * np.float32(w))
                        .astype(np.int64)
                        != (special * w).astype(np.int64)).sum())
    assert f32_off  # what a float32 product reads one off


@pytest.mark.parametrize("counts", [(16494, 16686, 16323),
                                    (24246, 24061, 24305)])
def test_zone_spread_is_exact_at_a_5000_node_clusters_counts(counts):
    """Zones that stand apart by a few hundred matching pods, at the
    counts the churn cells reach, and empty nodes in every zone: after
    the first pods the spread score of the zones that are NOT the emptiest
    decides against the fill of the emptiest one's nodes. Here a float32
    product with a weight one ulp off (the chip's log is not correctly
    rounded; the CPU's is) reads a normalised score one off and moves
    binds 34-36; `churn-5000n.node-rollover` read 40-104 mismatched binds
    a run on the chip before the product went to int32 limbs (PR 34). The
    interpreted table kernel against the plain reference."""
    big = {"count": 15, "cpu": "4000", "memory": "32000Gi", "pods": 110000,
           "zones": 3}
    real = {**NODES, "count": 12}
    nodes = [bench_cluster.build_node(i, real) for i in range(12)]
    ref = plain.ReferenceCluster.from_config({"nodes": real})
    pc = plain.PodClass(CLS)
    bound = []
    for z, n in enumerate(counts):
        # a cordoned node holds the zone's pods: counted, never a candidate
        holder = bench_cluster.build_node(12 + z, big)
        holder.spec.unschedulable = True
        nodes.append(holder)
        ref._per_zone[ref._class_id(pc)][z] += n
        for k in range(n):
            p = bench_cluster.build_pod(f"b-{z}-{k}", CLS)
            p.spec.node_name = holder.metadata.name
            bound.append(p)
    be = TPUBackend(pallas_interpret=True)
    try:
        be.enc.set_cluster(nodes, bound)
        be.enc.reserve(pods=len(bound) + 256)
        pending = [bench_cluster.build_pod(f"p-{i}", CLS) for i in range(120)]
        got = [n for _, n in be.schedule_many(pending)]
        assert type(be._session) is PallasSession
        assert be._session._cfg.pts_int
    finally:
        be.close()
    assert got == [_name(ref.decide(pc)) for _ in pending]
