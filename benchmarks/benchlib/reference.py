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

`variant` breaks one guarantee on purpose; those are the controls that
have to come out as not correct (benchmarks/README.md):
  "sampled"   scores only the first half of the feasible nodes
              (percentageOfNodesToScore=50, kube-scheduler's own short cut)
  "last-max"  takes the last node of the highest total, not the first
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

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
        self._classes: Dict[Tuple, int] = {}
        self._class_objs: List[PodClass] = []
        self._per_node: List[np.ndarray] = []  # class -> pods per node
        self._per_zone: List[np.ndarray] = []  # class -> pods per zone

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

    def place(self, pc: PodClass, node: int) -> None:
        cid = self._class_id(pc)
        self.req_cpu[node] += pc.cpu
        self.req_mem[node] += pc.mem
        self.n_pods[node] += 1
        self._per_node[cid][node] += 1
        self._per_zone[cid][self.zone[node]] += 1


def replay(nodes: Dict, classes: Sequence[Dict], sequence: Sequence[int],
           variant: str = "") -> List[Optional[int]]:
    """Decide one pod after another on an empty cluster of `nodes` (the
    configuration's node block). `classes` are pod shapes (dicts of the
    configuration's template keys, labels filled in) and `sequence` names
    the class of each pod in the order the pods were created; returns the
    node index of each (None = no node fits)."""
    cluster = ReferenceCluster(
        nodes["count"], nodes["cpu"], nodes["memory"], nodes["pods"],
        nodes["zones"], variant=variant)
    pcs = [PodClass(c) for c in classes]
    for pc in pcs:
        cluster._class_id(pc)
    return [cluster.decide(pcs[c]) for c in sequence]
