"""Reference `preemption`: the plain reference with pod priorities and
kube-scheduler's DefaultPreemption written out again (default_preemption.go:
findCandidates, selectVictimsOnNode, pickOneNodeForPreemption). Imports
nothing of the program.

Pods carry `priority` in their class (0 when absent). Pending pods are
decided highest priority first, then in creation order; each decision is
filter, score and the first of the maxima, as benchlib/reference.py's. The
benchmark creates pods of another priority only after the ones before
them were bound (set-up stages them), so a create whose priority differs
from the pending pods' decides those first.

A pod that fits nowhere preempts:

  candidates  the present nodes in node INDEX order from offset 0 (upstream
              draws a random offset) on which the pod fits with every
              lower-priority pod removed; the first max(10 % of the
              nodes, 100) of them (calculateNumCandidates)
  victims     on each candidate every lower-priority pod is removed, then
              they are added back in MoreImportantPod order (priority
              descending, then earlier start) while the pod still fits;
              those that cannot come back are the victims. No pod has a
              start time (the benchmark runs no kubelet), so the tie falls
              to the node's pod list: pods in the order they were placed,
              where a removal moves the node's last pod into the removed
              one's place (NodeInfo.RemovePod)
  pick        fewest PDB violations (there are no PDBs), lowest highest
              victim priority, lowest sum of (priority + 2^31) over the
              victims, fewest victims, latest start (all tie: no start
              times), then the first candidate

The victims go to `evicted` and the pod is bound on the chosen node at
once: the nomination that upstream makes is honoured by every later
decision. That equals upstream's re-run of the pod only while the pod fits
on no other node: at decision time it fits on none (only the chosen node
changed), but an event that is not a create may open a node before the
program has bound the pod, and the log does not say when it did. So a log
in which an event leaves room anywhere for a pod of a class that has
preempted is refused (LogError), as is a pod with a hostname
anti-affinity term (its dry run is not written out here).
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchlib import reference

PRIO_OFFSET = 2 ** 31  # math.MaxInt32 + 1, added per victim to the sum
MIN_CANDIDATE_PERCENTAGE = 10
MIN_CANDIDATE_ABSOLUTE = 100


class PrioClass(reference.PodClass):
    def __init__(self, spec: Dict):
        super().__init__(spec)
        self.priority = int(spec.get("priority", 0))

    def key(self) -> Tuple:
        return super().key() + (self.priority,)


def num_candidates(n_nodes: int) -> int:
    n = max(n_nodes * MIN_CANDIDATE_PERCENTAGE // 100, MIN_CANDIDATE_ABSOLUTE)
    return min(n, n_nodes)


class PreemptionCluster(reference.ReferenceCluster):
    """The reference cluster with each node's pod list kept in NodeInfo
    order: (pod, class) appended on a placement, the last moved into a
    removed one's place."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.pods_on: List[List[Tuple[int, PrioClass]]] = [
            [] for _ in range(self.n)]
        # node -> {class key: victims}, dropped when the node changes
        self._memo: Dict[int, Dict[Tuple, List]] = {}

    def add_node(self, node: int) -> None:
        super().add_node(node)
        self.pods_on += [[] for _ in range(self.n - len(self.pods_on))]

    def bind(self, i: int, pc: PrioClass, node: int) -> None:
        self.pods_on[node].append((i, pc))

    def unbind(self, i: int, node: int) -> PrioClass:
        lst = self.pods_on[node]
        k = next(k for k, (j, _) in enumerate(lst) if j == i)
        pc = lst[k][1]
        lst[k] = lst[-1]
        lst.pop()
        self.unplace(pc, node)
        return pc

    def place(self, pc, node: int, sign: int = 1) -> None:
        super().place(pc, node, sign)
        self._memo.pop(node, None)

    def select_victims(self, pc: PrioClass, node: int
                       ) -> List[Tuple[int, PrioClass]]:
        """selectVictimsOnNode on a node where the pod fits with every
        lower-priority pod removed; remembered until the node changes."""
        memo = self._memo.setdefault(node, {})
        got = memo.get(pc.key())
        if got is not None:
            return got
        lower = [(k, i, c) for k, (i, c) in enumerate(self.pods_on[node])
                 if c.priority < pc.priority]
        cpu = int(self.req_cpu[node]) - sum(c.cpu for _, _, c in lower)
        mem = int(self.req_mem[node]) - sum(c.mem for _, _, c in lower)
        pods = int(self.n_pods[node]) - len(lower)
        victims = []
        for _, i, c in sorted(lower, key=lambda t: (-t[2].priority, t[0])):
            if (pods + 2 <= self.alloc_pods[node]
                    and cpu + c.cpu + pc.cpu <= self.alloc_cpu[node]
                    and mem + c.mem + pc.mem <= self.alloc_mem[node]):
                cpu, mem, pods = cpu + c.cpu, mem + c.mem, pods + 1
            else:
                victims.append((i, c))
        memo[pc.key()] = victims
        return victims

    def preempt(self, pc: PrioClass) -> Optional[Tuple[int, List[int]]]:
        """(node, victims) of DefaultPreemption for a pod that fits
        nowhere, the victims evicted; None when no node can help."""
        lower = [cid for cid, c in enumerate(self._class_objs)
                 if c.priority < pc.priority]
        n_low = sum((self._per_node[cid] for cid in lower),
                    np.zeros(self.n, np.int64))
        cpu_low = sum((self._per_node[cid] * self._class_objs[cid].cpu
                       for cid in lower), np.zeros(self.n, np.int64))
        mem_low = sum((self._per_node[cid] * self._class_objs[cid].mem
                       for cid in lower), np.zeros(self.n, np.int64))
        base = (self.present & (n_low > 0)
                & (self.n_pods - n_low + 1 <= self.alloc_pods)
                & (self.req_cpu - cpu_low + pc.cpu <= self.alloc_cpu)
                & (self.req_mem - mem_low + pc.mem <= self.alloc_mem))
        limit = num_candidates(int(self.present.sum()))
        found = [(int(node), self.select_victims(pc, int(node)))
                 for node in np.flatnonzero(base)[:limit]]
        if not found:
            return None
        node, victims = min(found, key=lambda f: (
            max(c.priority for _, c in f[1]),
            sum(c.priority + PRIO_OFFSET for _, c in f[1]),
            len(f[1])))  # min() keeps the first of the ties
        for i, _ in victims:
            self.unbind(i, node)
        return node, [i for i, _ in victims]


def replay(config: Dict, classes, log, variant: str = "",
           cluster_cls=PreemptionCluster
           ) -> Tuple[Dict[int, Optional[int]], List[int]]:
    """benchlib.reference.replay with priorities and preemption; returns
    (binds, evicted)."""
    cluster = cluster_cls.from_config(config, variant)
    pcs = [PrioClass(c) for c in classes]
    for pc in pcs:
        cluster._class_id(pc)
    pending: "OrderedDict[int, int]" = OrderedDict()
    binds: Dict[int, Optional[int]] = {}
    evicted: List[int] = []
    placed: Dict[int, int] = {}  # live bound pod -> its node
    preemptors: Dict[Tuple, PrioClass] = {}  # classes that preempted
    n_bound = 0

    def refuse(msg: str):
        e = reference.LogError(msg)
        e.binds, e.evicted = binds, evicted
        return e

    def decide_one(i: int, pc: PrioClass) -> None:
        nonlocal n_bound
        if pc.anti_hostname:
            raise refuse(f"pod {i}: a hostname anti-affinity term is "
                         "outside this reference")
        node = cluster.decide(pc)
        if node is None:
            got = cluster.preempt(pc)
            if got is not None:
                node, victims = got
                for v in victims:
                    del placed[v]
                evicted.extend(victims)
                preemptors[pc.key()] = pc
                cluster.place(pc, node)
        binds[i] = node
        if node is not None:
            cluster.bind(i, pc, node)
            placed[i] = node
            n_bound += 1

    def decide(upto: Optional[int]) -> None:
        # the pending pods are of one priority: creation order
        while pending and (upto is None or n_bound < upto):
            i, c = pending.popitem(last=False)
            decide_one(i, pcs[c])

    for ev in log:
        op, idx = ev[0], ev[1]
        if op == "create":
            if pending and pcs[next(iter(pending.values()))].priority \
                    != pcs[ev[2]].priority:
                decide(None)  # the pods before were bound in set-up
            pending[idx] = ev[2]
            continue
        decide(ev[2])
        try:
            if op == "delete":
                if pending.pop(idx, None) is None and idx in placed:
                    cluster.unbind(idx, placed.pop(idx))
            elif op == "node_remove":
                cluster.remove_node(idx)
            elif op == "node_add":
                cluster.add_node(idx)
            else:
                raise reference.LogError(
                    f"event {op!r} is not one the reference knows")
        except reference.LogError as e:
            e.binds, e.evicted = binds, evicted
            raise
        for pc in preemptors.values():
            room = np.flatnonzero(cluster._feasible(pc))
            if room.size:
                raise refuse(
                    f"after the {op} of {idx} node {int(room[0])} has room "
                    f"for a pod of a class that preempted: upstream's "
                    "re-run of one not yet bound could bind it there")
    decide(None)
    return binds, evicted
