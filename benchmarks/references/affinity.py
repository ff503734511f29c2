"""Reference `affinity`: the plain reference with the whole of
InterPodAffinity — required affinity and anti-affinity as filters,
preferred terms and hardPodAffinityWeight as a score.

Written out from upstream's plugin (filtering.go, scoring.go), not from
the program, and importing nothing of it:

  filter  required anti-affinity both ways (benchlib.reference's own);
          required affinity: for every term of the incoming pod, the
          node's domain on the term's key holds a pod that matches ALL of
          the pod's required affinity terms; where no pod anywhere does,
          a pod that matches its own terms passes every node
  score   processExistingPod over every existing pod: the incoming pod's
          preferred affinity (+w) and anti-affinity (-w) terms that match
          it, its required affinity terms that match the incoming pod
          (+hardPodAffinityWeight), its preferred affinity (+w) and
          anti-affinity (-w) terms that match the incoming pod, each
          added to the existing pod's pair on the term's key; a node's
          raw score sums the pairs it lies in; normalized over the
          filtered nodes as int64(100 * (float64(s - min) / float64(max -
          min))), 0 where max == min (an empty map leaves every raw 0)

and added with weight 1 to benchlib.reference's scores (LeastAllocated 1,
BalancedAllocation 1, PodTopologySpread 2).

A term of the configuration selects one app label: `<select_app>-<group
mod group_mod>` (builders/affinity.py builds the same pods). Pods of one
class share labels and terms, so the relations are taken once per pair of
classes: a decision is a handful of vector operations over the nodes,
whatever the number of classes.
"""

import math
from typing import Dict, List, Tuple

import numpy as np

from benchlib import reference

HOSTNAME = "kubernetes.io/hostname"
ZONE = "topology.kubernetes.io/zone"
HARD_POD_AFFINITY_WEIGHT = 1   # v1.19 default
IPA_WEIGHT = 1
PTS_WEIGHT = 2


class AffinityClass(reference.PodClass):
    def __init__(self, spec: Dict):
        super().__init__(spec)
        group = int(self.labels["app"].rsplit("-", 1)[1]) \
            if "app" in self.labels else 0
        # (kind, key, weight, selected app)
        self.terms: List[Tuple[str, str, int, str]] = [
            (t["kind"], t["topology_key"], int(t.get("weight", 0)),
             f"{t['select_app']}-{group % t['group_mod']}")
            for t in spec.get("pod_affinity") or []]
        for kind, key, _, _ in self.terms:
            if kind not in ("preferred", "preferred_anti", "required") \
                    or key not in (HOSTNAME, ZONE):
                raise ValueError(f"term {kind} on {key}: not one the "
                                 "reference knows")

    def key(self):
        return super().key() + (tuple(self.terms),)

    def required(self):
        return [(key, app) for kind, key, _, app in self.terms
                if kind == "required"]

    def matched_by(self, app: str) -> bool:
        return self.labels.get("app") == app


class AffinityCluster(reference.ReferenceCluster):
    @classmethod
    def from_config(cls, config: Dict, variant: str = ""):
        self = super().from_config(config, variant)
        self._templates = config["pod_templates"]
        self._as_affinity: Dict[Tuple, AffinityClass] = {}
        self._rel: Dict[int, Tuple] = {}
        return self

    def _class_id(self, pc: reference.PodClass) -> int:
        """A class of benchlib.reference's replay names its template by
        its app label (`<template>-<group>`): its terms are that
        template's."""
        if not isinstance(pc, AffinityClass):
            k = pc.key()
            got = self._as_affinity.get(k)
            if got is None:
                name = pc.labels["app"].rsplit("-", 1)[0]
                got = self._as_affinity[k] = AffinityClass(
                    {**self._templates[name], "labels": pc.labels})
                if got.key()[:5] != k:
                    raise ValueError(f"class {k} is not template {name}'s")
            pc = got
        return super()._class_id(pc)

    def _relations(self, c: int):
        """What class c's decision reads, by class: (spread classes, anti
        classes, the classes matching all its required affinity terms,
        {(class, key): score weight})."""
        rel = self._rel.get(c)
        if rel is not None:
            return rel
        pc = self._class_objs[c]
        spread, anti, aff = [], [], []
        score: Dict[Tuple[int, str], int] = {}
        req = pc.required()
        for d, other in enumerate(self._class_objs):
            if pc.spread_zone_soft and reference._matches(
                    pc.labels, other.labels):
                spread.append(d)
            if ((pc.anti_hostname and reference._matches(
                    pc.labels, other.labels))
                    or (other.anti_hostname and reference._matches(
                        other.labels, pc.labels))):
                anti.append(d)
            if req and all(other.matched_by(app) for _, app in req):
                aff.append(d)
            # processExistingPod: a pod of class d already placed
            for kind, key, w, app in pc.terms:
                if kind != "required" and other.matched_by(app):
                    sign = 1 if kind == "preferred" else -1
                    score[(d, key)] = score.get((d, key), 0) + sign * w
            for kind, key, w, app in other.terms:
                if pc.matched_by(app):
                    w = (HARD_POD_AFFINITY_WEIGHT if kind == "required"
                         else w if kind == "preferred" else -w)
                    score[(d, key)] = score.get((d, key), 0) + w
        rel = self._rel[c] = (spread, anti, aff,
                              {k: w for k, w in score.items() if w})
        return rel

    def _in_domain(self, d: int, key: str) -> np.ndarray:
        """Pods of class d in each node's domain on `key`."""
        if key == HOSTNAME:
            return self._per_node[d]
        return self._per_zone[d][self.zone]

    def _feasible_of(self, c: int) -> np.ndarray:
        pc = self._class_objs[c]
        _, anti, aff, _ = self._relations(c)
        ok = ((self.n_pods + 1 <= self.alloc_pods)
              & (self.req_cpu + pc.cpu <= self.alloc_cpu)
              & (self.req_mem + pc.mem <= self.alloc_mem)
              & self.present)
        for d in anti:
            ok &= self._per_node[d] == 0
        req = pc.required()
        if req:
            exists = sum(int(self._per_node[d].sum()) for d in aff) > 0
            if exists or not all(pc.matched_by(app) for _, app in req):
                for key, _ in req:
                    there = np.zeros(self.n, np.int64)
                    for d in aff:
                        there += self._in_domain(d, key)
                    ok &= there > 0
        return ok

    def _spread_of(self, c: int, ok: np.ndarray) -> np.ndarray:
        """benchlib.reference's PodTopologySpread score, over the classes
        the relations name."""
        pc = self._class_objs[c]
        if not pc.spread_zone_soft:
            return np.zeros(self.n, np.int64)
        cnt = np.zeros(self.n_zones, np.int64)
        for d in self._relations(c)[0]:
            cnt += self._per_zone[d]
        weight = math.log(len(np.unique(self.zone[ok])) + 2)
        raw = (cnt.astype(np.float64) * weight).astype(np.int64)[self.zone]
        lo, hi = int(raw[ok].min()), int(raw[ok].max())
        if hi == 0:
            return np.full(self.n, reference.MAX_NODE_SCORE, np.int64)
        return reference.MAX_NODE_SCORE * (hi + lo - raw) // hi

    def ipa_raw(self, c: int) -> np.ndarray:
        raw = np.zeros(self.n, np.int64)
        for (d, key), w in self._relations(c)[3].items():
            raw += w * self._in_domain(d, key)
        return raw

    def ipa_score(self, c: int, ok: np.ndarray) -> np.ndarray:
        raw = self.ipa_raw(c)
        lo, hi = raw[ok].min(), raw[ok].max()
        if hi == lo:
            return np.zeros(self.n, np.int64)
        return (reference.MAX_NODE_SCORE
                * ((raw - lo) / np.float64(hi - lo))).astype(np.int64)

    def decide(self, pc: reference.PodClass):
        c = self._class_id(pc)
        ok = self._feasible_of(c)
        if not ok.any():
            return None
        if self.variant == "sampled":
            idx = np.flatnonzero(ok)
            ok = np.zeros(self.n, bool)
            ok[idx[: max(1, len(idx) // 2)]] = True
        cpu = self.req_cpu + pc.cpu
        mem = self.req_mem + pc.mem
        least = (
            (self.alloc_cpu - cpu) * reference.MAX_NODE_SCORE
            // self.alloc_cpu
            + (self.alloc_mem - mem) * reference.MAX_NODE_SCORE
            // self.alloc_mem) // 2
        cf = cpu / self.alloc_cpu
        mf = mem / self.alloc_mem
        balanced = ((1.0 - np.abs(cf - mf))
                    * reference.MAX_NODE_SCORE).astype(np.int64)
        balanced[(cf >= 1.0) | (mf >= 1.0)] = 0
        total = (least + balanced + PTS_WEIGHT * self._spread_of(c, ok)
                 + IPA_WEIGHT * self.ipa_score(c, ok))
        total = np.where(ok, total, -1)
        self.last_max = int(total.max())   # the highest total, for tests
        if self.variant == "last-max":
            node = self.n - 1 - int(np.argmax(total[::-1]))
        else:
            node = int(np.argmax(total))  # first of the maxima
        self.place(pc, node)
        return node


def replay(config, classes, log, variant=""):
    return reference.replay(config, classes, log, variant,
                            cluster_cls=AffinityCluster)
