"""Plain reference scheduler: the yardstick `correct` is decided against.

Imports nothing of the program and takes nothing it has made. It is the
default kube-scheduler profile (v1.19 algorithmprovider defaults, as the
configuration files state) written out in numpy for the plugins that can
tell two nodes of these clusters apart:

  filter  NodeResourcesFit (cpu, memory, pod count against allocatable),
          InterPodAffinity (required anti-affinity, both directions)
  score   NodeResourcesLeastAllocated (weight 1),
          NodeResourcesBalancedAllocation (weight 1),
          PodTopologySpread, ScheduleAnyway constraints (weight 2)

Every other default plugin scores all nodes of these clusters alike (no
taints, images, node affinity, preferred terms, volumes or host ports), so
it cannot move the argmax and is left out. One pod is decided at a time
against every node, the decision is assumed before the next pod, and among
the nodes of the highest total the FIRST in node order wins (the reference
scheduler draws one of them at random; a deterministic order is the only
way two schedulers can be compared bind for bind).

A run is a LOG of cluster events (`replay`): creates, deletes, nodes that
leave and nodes that join. Node order, for "the first of the maxima", is
node INDEX order whatever happened to the cluster in between: a node that
left and came back, or one that joined past the first `count`, stands where
its index puts it.

`variant` breaks one guarantee on purpose; those are the controls that
have to come out as not correct (benchmarks/README.md):
  "sampled"   scores only the first half of the feasible nodes
              (percentageOfNodesToScore=50, kube-scheduler's own short cut)
  "last-max"  takes the last node of the highest total, not the first
"""

from __future__ import annotations

import math
from collections import OrderedDict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

MAX_NODE_SCORE = 100
_SUFFIX = {"Ki": 2 ** 10, "Mi": 2 ** 20, "Gi": 2 ** 30, "Ti": 2 ** 40,
           "k": 10 ** 3, "M": 10 ** 6, "G": 10 ** 9}


def milli_cpu(q: str) -> int:
    q = str(q)
    return int(q[:-1]) if q.endswith("m") else int(round(float(q) * 1000))


def quantity_bytes(q: str) -> int:
    q = str(q)
    for suf, mult in _SUFFIX.items():
        if q.endswith(suf):
            return int(q[: -len(suf)]) * mult
    return int(q)


def _matches(selector: Dict[str, str], labels: Dict[str, str]) -> bool:
    return all(labels.get(k) == v for k, v in selector.items())


class LogError(ValueError):
    """The log asks for what cannot be: a node removed with pods on it, a
    node added twice. The benchmark drains before it removes, so this is
    either a fault of the benchmark or a cluster that has already parted
    from the reference; `binds` and `evicted` are what was decided up to
    the event."""

    binds: Dict = {}
    evicted: List = []


class PodClass:
    """One pod shape: requests, labels, constraints. Pods of one class are
    interchangeable to the scheduler."""

    def __init__(self, spec: Dict):
        self.cpu = milli_cpu(spec["cpu"])
        self.mem = quantity_bytes(spec["memory"])
        self.labels = dict(spec.get("labels") or {})
        # soft zone spread / required hostname anti-affinity, both against
        # the pod's own labels (the only forms the configurations use)
        self.spread_zone_soft = bool(spec.get("spread_zone_soft"))
        self.anti_hostname = bool(spec.get("anti_affinity_hostname"))

    def key(self) -> Tuple:
        return (self.cpu, self.mem, tuple(sorted(self.labels.items())),
                self.spread_zone_soft, self.anti_hostname)


class ReferenceCluster:
    def __init__(self, n_nodes: int, cpu: str, memory: str, max_pods: int,
                 n_zones: int, variant: str = ""):
        n = n_nodes
        self.n = n
        self.variant = variant
        self.alloc_cpu = np.full(n, milli_cpu(cpu), np.int64)
        self.alloc_mem = np.full(n, quantity_bytes(memory), np.int64)
        self.alloc_pods = np.full(n, int(max_pods), np.int64)
        self.req_cpu = np.zeros(n, np.int64)
        self.req_mem = np.zeros(n, np.int64)
        self.n_pods = np.zeros(n, np.int64)
        self.zone = np.arange(n, dtype=np.int64) % n_zones
        self.n_zones = n_zones
        self.present = np.ones(n, bool)  # False: the node has left
        # the one node shape, for a node that joins past the first `n`
        self._shape = (milli_cpu(cpu), quantity_bytes(memory), int(max_pods))
        self._classes: Dict[Tuple, int] = {}
        self._class_objs: List[PodClass] = []
        self._per_node: List[np.ndarray] = []  # class -> pods per node
        self._per_zone: List[np.ndarray] = []  # class -> pods per zone

    @classmethod
    def from_config(cls, config: Dict, variant: str = ""
                    ) -> "ReferenceCluster":
        """The cluster a configuration file describes, before any event."""
        nodes = config["nodes"]
        return cls(nodes["count"], nodes["cpu"], nodes["memory"],
                   nodes["pods"], nodes["zones"], variant=variant)

    def shape_of(self, i: int) -> Tuple[int, int, int, int]:
        """(milli-CPU, bytes, pod limit, zone) of node `i` when it joins;
        a reference of several node pools overrides this."""
        return self._shape + (i % self.n_zones,)

    def _class_id(self, pc: PodClass) -> int:
        k = pc.key()
        cid = self._classes.get(k)
        if cid is None:
            cid = len(self._class_objs)
            self._classes[k] = cid
            self._class_objs.append(pc)
            self._per_node.append(np.zeros(self.n, np.int64))
            self._per_zone.append(np.zeros(self.n_zones, np.int64))
        return cid

    # -- one decision ------------------------------------------------------

    def _feasible(self, pc: PodClass) -> np.ndarray:
        ok = (
            (self.n_pods + 1 <= self.alloc_pods)
            & (self.req_cpu + pc.cpu <= self.alloc_cpu)
            & (self.req_mem + pc.mem <= self.alloc_mem)
            & self.present
        )
        for cid, other in enumerate(self._class_objs):
            # the incoming pod's own term against pods already there, and
            # the terms of pods already there against the incoming pod
            mine = pc.anti_hostname and _matches(pc.labels, other.labels)
            theirs = other.anti_hostname and _matches(other.labels, pc.labels)
            if mine or theirs:
                ok &= self._per_node[cid] == 0
        return ok

    def _spread_score(self, pc: PodClass, ok: np.ndarray) -> np.ndarray:
        """PodTopologySpread scoring.go: count of matching pods in the
        node's zone times log(zones + 2), truncated; then normalised
        100 * (max + min - s) / max over the feasible nodes."""
        if not pc.spread_zone_soft:
            return np.zeros(self.n, np.int64)
        cnt = np.zeros(self.n_zones, np.int64)
        for cid, other in enumerate(self._class_objs):
            if _matches(pc.labels, other.labels):
                cnt += self._per_zone[cid]
        zones_present = np.unique(self.zone[ok])
        weight = math.log(len(zones_present) + 2)
        # max_skew is 1 in every configuration: + (max_skew - 1) = 0
        raw_zone = (cnt.astype(np.float64) * weight).astype(np.int64)
        raw = raw_zone[self.zone]
        lo = int(raw[ok].min())
        hi = int(raw[ok].max())
        if hi == 0:
            return np.full(self.n, MAX_NODE_SCORE, np.int64)
        return MAX_NODE_SCORE * (hi + lo - raw) // hi

    def decide(self, pc: PodClass) -> Optional[int]:
        """Node index for one pod of class `pc`, or None when no node
        fits; the pod is assumed on that node."""
        ok = self._feasible(pc)
        if not ok.any():
            return None
        if self.variant == "sampled":
            idx = np.flatnonzero(ok)
            ok = np.zeros(self.n, bool)
            ok[idx[: max(1, len(idx) // 2)]] = True
        cpu = self.req_cpu + pc.cpu
        mem = self.req_mem + pc.mem
        least = (
            (self.alloc_cpu - cpu) * MAX_NODE_SCORE // self.alloc_cpu
            + (self.alloc_mem - mem) * MAX_NODE_SCORE // self.alloc_mem
        ) // 2
        cf = cpu / self.alloc_cpu
        mf = mem / self.alloc_mem
        balanced = ((1.0 - np.abs(cf - mf)) * MAX_NODE_SCORE).astype(np.int64)
        balanced[(cf >= 1.0) | (mf >= 1.0)] = 0
        total = least + balanced + 2 * self._spread_score(pc, ok)
        total = np.where(ok, total, -1)
        if self.variant == "last-max":
            node = self.n - 1 - int(np.argmax(total[::-1]))
        else:
            node = int(np.argmax(total))  # first of the maxima
        self.place(pc, node)
        return node

    def place(self, pc: PodClass, node: int, sign: int = 1) -> None:
        cid = self._class_id(pc)
        self.req_cpu[node] += sign * pc.cpu
        self.req_mem[node] += sign * pc.mem
        self.n_pods[node] += sign
        self._per_node[cid][node] += sign
        self._per_zone[cid][self.zone[node]] += sign

    # -- events that are not creates -----------------------------------------

    def unplace(self, pc: PodClass, node: int) -> None:
        """A bound pod of class `pc` was deleted from `node`."""
        self.place(pc, node, -1)

    def remove_node(self, node: int) -> None:
        """Infeasible from here on. Operators drain, then delete: a log
        that removes a node with pods still on it is the benchmark's own
        fault, not a decision to compare."""
        if not (0 <= node < self.n and self.present[node]):
            raise LogError(f"node_remove {node}: no such node")
        if self.n_pods[node]:
            raise LogError(f"node_remove {node}: {self.n_pods[node]} pods "
                             "still on it; the log has to delete them first")
        self.present[node] = False

    def add_node(self, node: int) -> None:
        """Present and empty: a removed index again, or a new one past the
        nodes there are (the indices between then exist and are absent)."""
        if node >= self.n:
            grow = node + 1 - self.n
            for name in ("alloc_cpu", "alloc_mem", "alloc_pods"):
                # 1, not 0: an absent node is never feasible, and its
                # share of a score is never read; 0 would divide
                setattr(self, name, np.concatenate(
                    [getattr(self, name), np.ones(grow, np.int64)]))
            for name in ("req_cpu", "req_mem", "n_pods", "zone"):
                setattr(self, name, np.concatenate(
                    [getattr(self, name), np.zeros(grow, np.int64)]))
            self.present = np.concatenate([self.present, np.zeros(grow, bool)])
            self._per_node = [np.concatenate([a, np.zeros(grow, np.int64)])
                              for a in self._per_node]
            self.n = node + 1
        elif self.present[node]:
            raise LogError(f"node_add {node}: already there")
        cpu, mem, pods, zone = self.shape_of(node)
        self.alloc_cpu[node], self.alloc_mem[node] = cpu, mem
        self.alloc_pods[node], self.zone[node] = pods, zone
        self.present[node] = True


def replay(config: Dict, classes: Sequence[Dict], log: Iterable[Tuple],
           variant: str = "", cluster_cls=ReferenceCluster
           ) -> Tuple[Dict[int, Optional[int]], List[int]]:
    """What should have happened: the cluster of `config` (the whole
    configuration file) taken through `log`, the ordered events the
    benchmark issued. `classes` are pod shapes (dicts of the
    configuration's template keys, labels filled in).

      ("create", i, c)          pod i of class c joins the pending pods
      ("delete", i, bound, ..)  pod i leaves its node, or the pending pods
      ("node_remove", n, bound, ..) / ("node_add", n, bound, ..)

    A pod waits in a standing backlog, so it is decided cycles after its
    create. An event that is not a create was issued with the scheduler
    paused and drained, and carries `bound`: how many pods had been bound
    in all at that instant. One priority and a FIFO queue make those the
    first `bound` pending pods that find a node, so they are decided
    before the event is applied and the rest after it.

    Returns (binds, evicted): the node index of every pod that was decided
    (None: no node fits; a pod deleted while it was pending is not in it),
    and the pods the scheduler should itself have deleted to make room
    (none: one priority; a reference with priorities says them here)."""
    cluster = cluster_cls.from_config(config, variant)
    pcs = [PodClass(c) for c in classes]
    for pc in pcs:
        cluster._class_id(pc)
    pending: "OrderedDict[int, int]" = OrderedDict()
    binds: Dict[int, Optional[int]] = {}
    placed: Dict[int, int] = {}  # live bound pod -> its class
    n_bound = 0

    def decide(upto: Optional[int]) -> None:
        nonlocal n_bound
        while pending and (upto is None or n_bound < upto):
            i, c = pending.popitem(last=False)
            node = binds[i] = cluster.decide(pcs[c])
            if node is not None:
                placed[i] = c
                n_bound += 1

    for ev in log:
        op, idx = ev[0], ev[1]
        if op == "create":
            pending[idx] = ev[2]
            continue
        decide(ev[2])
        try:
            if op == "delete":
                if pending.pop(idx, None) is None and idx in placed:
                    cluster.unplace(pcs[placed.pop(idx)], binds[idx])
            elif op == "node_remove":
                cluster.remove_node(idx)
            elif op == "node_add":
                cluster.add_node(idx)
            else:
                raise LogError(f"event {op!r} is not one the reference knows")
        except LogError as e:
            e.binds, e.evicted = binds, []
            raise
    decide(None)
    return binds, []
