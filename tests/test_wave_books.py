"""Wave books kept from one preemption wave to the next
(scheduler/wave_books.py).

A planner over kept books must hand its wave exactly what a planner over
an empty WaveBooks builds from the same snapshot: every array and list
of the books, and the candidates planned from them. A randomised
sequence of waves against a live cache and encoding moves what the kept
rows depend on between waves — binds, deletes and terminating updates,
nodes that join, leave and change, claimed victims that come and go
(gang units split by a claim among them), PDBs with partial budgets,
scalar resources, waves of mixed priorities, vocabularies that grow.
"""

from __future__ import annotations

import copy
import random

import numpy as np
import pytest

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.scheduler import metrics, wave_books
from kubernetes_tpu.scheduler.framework.snapshot import Snapshot
from kubernetes_tpu.scheduler.framework.types import next_generation
from kubernetes_tpu.scheduler.internal.cache import SchedulerCache
from kubernetes_tpu.scheduler.internal.nominator import PodNominator
from kubernetes_tpu.scheduler.plugins.coscheduling import (
    GROUP_LABEL,
    MIN_AVAILABLE_LABEL,
)
from kubernetes_tpu.scheduler.preemption_device import DevicePreemptionPlanner
from kubernetes_tpu.scheduler.tpu_backend import TPUBackend
from kubernetes_tpu.scheduler.wave_books import WaveBooks
from kubernetes_tpu.testing.synth import make_node, make_pod
from kubernetes_tpu.utils import tracing

GPU = "example.com/gpu"
ARRAYS = ("_alloc", "_used", "_npods", "_max_pods", "_vvec", "_vprio",
          "_vstart", "_valive", "_vsize", "_vpriosum", "_vlatest_hi",
          "_vsort", "_pdb_match", "_pdb_allowed", "_v_enc_req", "_nom_sum",
          "_nom_cnt", "_enc_idx")


def _plain(a: np.ndarray):
    return str(a.dtype), a.shape, a.tolist()


def _rows(r):
    return (np.flatnonzero(r["self_ppair"]).tolist(),
            np.flatnonzero(r["self_pkey"]).tolist(), int(r["self_ns"]),
            r["self_ppair"].shape[0], r["self_pkey"].shape[0])


def _state(p) -> dict:
    """Everything the books hand the planner, in comparable form."""
    out = {k: _plain(getattr(p, k)) for k in ARRAYS}
    out["_vmax"] = p._vmax
    out["_dims"] = list(p._dims)
    out["_lower_sum"] = {q: _plain(a) for q, a in p._lower_sum.items()}
    out["_lower_cnt"] = {q: _plain(a) for q, a in p._lower_cnt.items()}
    out["_vpods"] = [[[v1.pod_key(m) for m in s] for s in row]
                     for row in p._vpods]
    out["_v_rows"] = [[[_rows(r) for r in s] for s in row]
                      for row in p._v_rows]
    out["_v_term"] = [[list(s) for s in row] for row in p._v_term]
    out["_pre"] = [(lane, _rows(r), vec.tolist(), term, key)
                   for lane, r, vec, term, key in p._pre]
    out["_nom_entries"] = [(i, prio, _rows(r), vec.tolist(), key)
                           for i, prio, r, vec, key in p._nom_entries]
    out["_nominated"] = {i: [(q, vec.tolist(), key) for q, vec, key in e]
                         for i, e in p._nominated.items()}
    return out


class Recording(DevicePreemptionPlanner):
    """Keeps what its books handed the wave, before any claim."""

    def _build(self, wave):
        super()._build(wave)
        self.built = _state(self)


def _summary(cands):
    return [None if c is None else
            (c.node_name, [v1.pod_key(p) for p in c.victims],
             c.num_pdb_violations) for c in cands]


class World:
    """A cache with the backend's encoding listening, as the scheduler
    wires them, and the mutations a wave's books must follow."""

    def __init__(self, rng: random.Random, n_nodes: int):
        self.rng = rng
        self.cache = SchedulerCache()
        self.backend = TPUBackend()
        self.backend.whatif = True
        self.cache.add_listener(self.backend)
        self.nominator = PodNominator()
        self.books = WaveBooks()
        self.snap = Snapshot([])
        self.made = 0
        self.nodes = {}
        self.pods = {}
        for _ in range(n_nodes):
            self.fill(self.add_node())

    def fill(self, name):
        """Nearly full: a preemptor fits nowhere as the node stands."""
        cap = int(self.nodes[name].status.allocatable["cpu"][:-1])
        used = 0
        while True:
            req = self.rng.choice([300, 900, 1500])
            if used + req > cap - 400:
                return
            self.bind(name, cpu=req)
            used += req

    def add_node(self, cpu=None, gpu=None):
        rng = self.rng
        self.made += 1
        name = f"n{self.made}"
        gpu = rng.random() < 0.3 if gpu is None else gpu
        node = make_node(name, cpu=f"{cpu or rng.choice([4000, 8000])}m",
                         memory="16Gi", pods=rng.choice([6, 110]),
                         labels={"zone": f"z{self.made % 3}"},
                         extended={GPU: "4"} if gpu else None)
        self.nodes[name] = node
        self.cache.add_node(node)
        return name

    def bind(self, node_name, label=None, gang=None, prio=None, cpu=None,
             gpu=None):
        self.made += 1
        rng = self.rng
        gpu = rng.random() < 0.15 if gpu is None else gpu
        pod = make_pod(
            f"p{self.made}", cpu=f"{cpu or rng.choice([300, 900, 1500])}m",
            memory="256Mi", node_name=node_name,
            priority=rng.choice([0, 1, 5, 50, 200]) if prio is None else prio,
            labels={"app": label or rng.choice(["a", "b", "c"])},
            extended={GPU: "1"} if gpu else None)
        pod.status.start_time = rng.choice([None, rng.random() * 100.0])
        if gang is not None:
            pod.metadata.annotations = {GROUP_LABEL: gang[0],
                                        MIN_AVAILABLE_LABEL: str(gang[1])}
        self.pods[v1.pod_key(pod)] = pod
        self.cache.add_pod(pod)
        return pod

    def bind_gang(self, node_name):
        self.made += 1
        group, size = f"g{self.made}", self.rng.randint(2, 3)
        return [self.bind(node_name, gang=(group, size)) for _ in range(size)]

    def delete(self, pod):
        self.pods.pop(v1.pod_key(pod))
        self.cache.remove_pod(pod)

    def terminate(self, pod):
        new = copy.deepcopy(pod)
        new.metadata.deletion_timestamp = 1.0
        self.pods[v1.pod_key(pod)] = new
        self.cache.update_pod(pod, new)

    def remove_node(self, name):
        for pod in [p for p in self.pods.values()
                    if p.spec.node_name == name]:
            self.delete(pod)
        del self.nodes[name]
        self.cache.remove_node(name)

    def set_node(self, name):
        node = copy.deepcopy(self.nodes[name])
        node.status.allocatable = dict(node.status.allocatable,
                                       cpu=f"{self.rng.choice([6000, 9000])}m")
        self.nodes[name] = node
        self.cache.update_node(node)

    def mutate(self):
        rng = self.rng
        names = sorted(self.nodes)
        for _ in range(rng.randint(1, 4)):
            op = rng.choice(["bind", "bind", "gang", "delete", "terminate",
                             "add-node", "remove-node", "set-node", "vocab",
                             "nothing"])
            pods = sorted(self.pods)
            if op == "bind":
                self.bind(rng.choice(names))
            elif op == "gang":
                self.bind_gang(rng.choice(names))
            elif op == "delete" and pods:
                self.delete(self.pods[rng.choice(pods)])
            elif op == "terminate" and pods:
                self.terminate(self.pods[rng.choice(pods)])
            elif op == "add-node":
                self.fill(self.add_node())
            elif op == "remove-node" and len(names) > 4:
                self.remove_node(rng.choice(names))
                names = sorted(self.nodes)
            elif op == "set-node":
                self.set_node(rng.choice(names))
            elif op == "vocab":
                # a label pair the encoding has never seen: the pair
                # vocabulary grows past its width every few of these
                self.bind(rng.choice(names), label=f"new{self.made}")

    def wave(self, k):
        out = []
        for _ in range(k):
            self.made += 1
            out.append(make_pod(
                f"hi{self.made}", cpu=f"{self.rng.choice([1500, 3000])}m",
                memory="512Mi", priority=self.rng.choice([10, 60, 100]),
                extended={GPU: "1"} if self.rng.random() < 0.2 else None))
        return out

    def planners(self, wave, claimed, pdbs, books=None, **kw):
        """A planner over the kept books and one over empty books."""
        self.snap = self.cache.update_snapshot(self.snap)
        out = []
        for b in (books or self.books, WaveBooks()):
            p = Recording(self.snap, self.nominator, self.backend,
                          claimed_victims=set(claimed), pdbs=pdbs, books=b,
                          **kw)
            out.append((p, p.plan(wave)))
        return out


def _pdb(app, allowed):
    return v1.PodDisruptionBudget(
        metadata=v1.ObjectMeta(name=f"pdb-{app}", namespace="default"),
        spec=v1.PodDisruptionBudgetSpec(
            selector=v1.LabelSelector(match_labels={"app": app})),
        status=v1.PodDisruptionBudgetStatus(disruptions_allowed=allowed),
    )


def _assert_same(kept, fresh, step):
    (kp, kc), (fp, fc) = kept, fresh
    assert kp.built == fp.built, step
    assert kp.fits_now == fp.fits_now, step
    assert _summary(kc) == _summary(fc), step


@pytest.mark.parametrize("seed", range(4))
def test_kept_books_match_fresh_books_over_a_random_sequence(seed):
    rng = random.Random(f"wave-books-{seed}")
    world = World(rng, rng.randint(8, 14))
    pdbs = [_pdb("a", 1), _pdb("b", 0)] if seed % 2 else []
    claimed = set()
    kept = rebuilt = preempted = split = 0
    for step in range(14):
        world.mutate()
        # claimed victims grow and shrink; now and then one member of a
        # gang alone, which splits the unit for the wave
        pods = sorted(world.pods)
        claimed -= set(rng.sample(sorted(claimed), len(claimed) // 2))
        claimed |= set(rng.sample(pods, min(len(pods), rng.randint(0, 3))))
        claimed &= set(pods)
        gangs = [p for p in world.pods.values()
                 if (p.metadata.annotations or {}).get(GROUP_LABEL)]
        if gangs and rng.random() < 0.5:
            claimed.add(v1.pod_key(rng.choice(gangs)))
        if rng.random() < 0.3:
            world.nominator.add_nominated_pod(
                world.wave(1)[0], rng.choice(sorted(world.nodes)))
        wave = world.wave(rng.randint(3, 6))
        a, b = world.planners(wave, claimed, pdbs)
        _assert_same(a, b, step)
        kept += a[0].n - len(a[0]._rebuilt)
        rebuilt += len(a[0]._rebuilt)
        split += len(a[0]._own_rows)
        preempted += sum(c is not None for c in a[1])
    assert kept > rebuilt, (kept, rebuilt)
    assert preempted > 0


def test_a_claim_of_one_wave_does_not_reach_the_next():
    """Wave k claims victims on its copies; wave k+1, with nothing
    changed in between, reads the books as a fresh build does."""
    rng = random.Random("no-leak")
    world = World(rng, 6)
    for name in sorted(world.nodes):
        for _ in range(4):
            world.bind(name, label="a", prio=0)
    first, _ = world.planners(world.wave(4), set(), [])
    assert any(c is not None for c in first[1])
    before = {k: a.copy() for k, a in world.books.slots.items()}
    a, b = world.planners(world.wave(4), set(), [])
    _assert_same(a, b, "k+1")
    assert len(a[0]._rebuilt) == 0
    for k, arr in world.books.slots.items():
        assert np.array_equal(arr, before[k]), k


def test_a_node_that_moves_while_walked_is_walked_again(monkeypatch):
    """The walk reads live NodeInfos: a node whose generation moves
    during its walk serves this wave and is not kept."""
    rng = random.Random("moved")
    world = World(rng, 6)
    world.planners(world.wave(3), set(), [])
    target = sorted(world.nodes)[2]
    world.bind(target)
    real = wave_books.calculate_resource
    moved = []

    def bump(pod):
        if pod.spec.node_name == target and not moved:
            moved.append(pod)
            world.bind(target)  # the cache moves the node under the walk
        return real(pod)

    monkeypatch.setattr(wave_books, "calculate_resource", bump)
    world.snap = world.cache.update_snapshot(world.snap)
    p = Recording(world.snap, world.nominator, world.backend,
                  books=world.books)
    p.plan(world.wave(3))
    monkeypatch.undo()
    i = world.books.names.index(target)
    assert moved and world.books.gen[i] == -1
    a, b = world.planners(world.wave(3), set(), [])
    _assert_same(a, b, "after the move")
    assert i in a[0]._rebuilt


def test_device_rung_plans_alike_on_kept_books():
    """Bursts of device-planned preemptors, victims evicted and
    preemptors bound between them: the kept books' candidates are the
    fresh books'."""
    rng = random.Random("device")
    world = World(rng, 0)
    for i in range(12):
        name = world.add_node(cpu=4000, gpu=False)
        for _ in range(4):
            world.bind(name, label="batch", prio=0, cpu=900, gpu=False)
    for burst in range(3):
        wave = [make_pod(f"hi-{burst}-{k}", cpu="3000m", memory="512Mi",
                         priority=10) for k in range(4)]
        elig = {v1.pod_key(p): (True, False) for p in wave}
        a, b = world.planners(wave, set(), [], eligibility=elig)
        assert a[0].planner_paths == ["device"] * len(wave)
        _assert_same(a, b, burst)
        for pod, cand in zip(wave, a[1]):
            for victim in cand.victims:
                world.delete(world.pods[v1.pod_key(victim)])
            pod.spec.node_name = cand.node_name
            world.pods[v1.pod_key(pod)] = pod
            world.cache.add_pod(pod)
        if burst:
            assert len(a[0]._rebuilt) <= len(wave)


def test_one_bind_rebuilds_one_node():
    """After a warm wave, one bind: the next wave walks that node only,
    and says so in the counter and on its `preemption-books` span."""
    rng = random.Random("counter")
    world = World(rng, 9)
    world.bind(sorted(world.nodes)[0], label="a", gpu=False)
    world.planners(world.wave(3), set(), [])
    world.bind(sorted(world.nodes)[4], label="a", gpu=False)
    world.snap = world.cache.update_snapshot(world.snap)
    n = len(world.nodes)
    kept0 = metrics.preemption_books_nodes.value(path="kept")
    rebuilt0 = metrics.preemption_books_nodes.value(path="rebuilt")
    old = tracing.set_level(tracing.TRACE_STAGES)
    mark = tracing.RECORDER.mark()
    try:
        Recording(world.snap, world.nominator, world.backend,
                  books=world.books).plan(world.wave(3))
        events = tracing.RECORDER.snapshot(since=mark)
    finally:
        tracing.set_level(old)
    assert metrics.preemption_books_nodes.value(path="kept") - kept0 == n - 1
    assert metrics.preemption_books_nodes.value(path="rebuilt") \
        - rebuilt0 == 1
    books = [e[6] for e in events if e[2] == "preemption-books"]
    assert len(books) == 1
    assert (books[0]["kept"], books[0]["rebuilt"]) == (n - 1, 1)


def test_vocab_growth_rebuilds_every_device_row():
    """Label rows are as wide as the pair vocabulary: once it outgrows
    its width, every node's device rows are built again."""
    rng = random.Random("vocab")
    world = World(rng, 5)
    world.planners(world.wave(2), set(), [])
    enc = world.backend.enc
    width = enc.pod_pair_vocab.capacity
    name = sorted(world.nodes)[0]
    while enc.pod_pair_vocab.capacity == width:
        world.bind(name, label=f"grow{world.made}")
    a, b = world.planners(world.wave(2), set(), [])
    _assert_same(a, b, "grown")
    assert len(a[0]._rebuilt) == a[0].n


def test_generation_is_what_keeps_a_row():
    """A bumped generation with nothing else changed walks the node
    again; an unchanged one does not."""
    rng = random.Random("gen")
    world = World(rng, 4)
    world.planners(world.wave(2), set(), [])
    world.snap.list()[1].generation = next_generation()
    p = Recording(world.snap, world.nominator, world.backend,
                  books=world.books)
    p.plan(world.wave(2))
    assert p._rebuilt == {1}
