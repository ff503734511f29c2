"""Traffic kind `scaleup-waves`: the closed loop of `waves`, with the pods
of a top-up drawn as scale-ups of many Deployments.

The loop is kinds/waves.py's own `drive`, called from here and not
copied: pause, create pods until `backlog_pods + wave_pods` are unbound,
wait for the queue, resume, wait for the drain, finish the cycle the close
of the window falls in; its clocks, its `stage` spans and its `t_end` are
that module's. What this kind brings is the ORDER pods are created in.

The draw. A scale-up is `size` replicas of ONE Deployment, created one
after another (as a ReplicaSet controller creates them). `max_pods` pods
are cut into scale-ups of the `scaleup_sizes` (250 / 30 / 5) so that each
size carries its `scaleup_pod_shares` of the pods (1/4, 1/4, 1/2:
clusterloader2's BIG / MEDIUM / SMALL groups); within a size the
scale-ups go to the configuration's pod templates by their `share` of the
pods (largest remainders, no chance in it). A template's scale-ups take its
Deployments (`deployments` of them, the label's `{group}`) round-robin.
About 220 scale-ups, so about 220 distinct pod specs, make one 2048-pod
top-up.

What the seed moves: the order of the scale-ups (one shuffle), the
Deployment each template's round-robin starts at, and the order the fresh
Deployments become eligible in. Never the amount of work: every seed gets
the same multiset of (template, size) scale-ups, and every Deployment the
same number of them to within one.

Fresh Deployments. The `fresh_deployments` highest-numbered Deployments
(shared among the templates by their `deployments`: 16 web, 8 small, 4
ha, 4 worker of 32) are named by no pod of set-up: the configuration's
init pods and the traffic's `warm_batches` stay below them, and while the
standing backlog is staged none is eligible. In the window one more
becomes eligible every `fresh_every_s` seconds. A scale-up of a Deployment
that is not eligible yet is HELD, not dropped: the pod objects of every
scale-up are built in set-up (the window pays the API call only), the
draw just passes over it and takes it up, earliest first, once the
Deployment is eligible — its first pod is then its first in the cluster.
So the creation order depends on the clock, the multiset does not; the
benchmark's replay follows the order pods were really created in
(`cluster.order`).

Parameters, all from the traffic file: `backlog_pods`, `wave_pods`,
`max_pods`, `park_s` (as `waves`), `scaleup_sizes`, `scaleup_pod_shares`,
`fresh_deployments`, `fresh_every_s`. From the configuration's pod
templates: `share`, `deployments`.

On a program that keeps only a few pod specs in its device session (the
parent of the PR that added this kind: eight), set-up does not get this
far: run.py's own staging of the init pods (240 Deployments in the first
batch) ends in `RuntimeError: set-up: ... pods bound` after its 300 s wait
and the process exits non-zero (PERF.md section 6, PR 27).
"""

from __future__ import annotations

import importlib.util
import os
import random
import time
from typing import Dict, List, Sequence, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))


def _waves():
    spec = importlib.util.spec_from_file_location(
        "bench_kinds_waves", os.path.join(_HERE, "waves.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _quotas(total: int, shares: Sequence[float]) -> List[int]:
    """`total` split by `shares`, largest remainders first."""
    raw = [total * s / sum(shares) for s in shares]
    out = [int(r) for r in raw]
    for i in sorted(range(len(raw)), key=lambda i: out[i] - raw[i]):
        if sum(out) == total:
            break
        out[i] += 1
    return out


def fresh_groups(templates: Dict, n_fresh: int) -> Dict[str, List[int]]:
    """The highest-numbered Deployments of each template that set-up may
    not name: `n_fresh` shared by the templates' `deployments`."""
    names = list(templates)
    per = _quotas(n_fresh, [templates[t]["deployments"] for t in names])
    return {t: list(range(templates[t]["deployments"] - k,
                          templates[t]["deployments"]))
            for t, k in zip(names, per)}


def scaleups(templates: Dict, traffic: Dict, seed: int
             ) -> List[Tuple[str, int, int]]:
    """[(template, Deployment, replicas)] in the order of the draw."""
    rng = random.Random(seed)
    names = list(templates)
    shares = [templates[t]["share"] for t in names]
    sizes = traffic["scaleup_sizes"]
    pods_of = _quotas(traffic["max_pods"], traffic["scaleup_pod_shares"])
    draw: List[Tuple[str, int]] = []
    for size, pods in zip(sizes, pods_of):
        for t, n in zip(names, _quotas(pods // size, shares)):
            draw += [(t, size)] * n
    rng.shuffle(draw)
    nxt = {t: rng.randrange(templates[t]["deployments"]) for t in names}
    out = []
    for t, size in draw:
        out.append((t, nxt[t], size))
        nxt[t] = (nxt[t] + 1) % templates[t]["deployments"]
    return out


class Draw:
    """The pod indices in creation order, settled as they are asked for:
    `seq[k]` is the k-th pod created, decided when it is first read (the
    loop of kinds/waves.py reads it the instant before the create).
    Slices and len() as a list's."""

    def __init__(self, runs: List[Tuple[Tuple[str, int], List[int]]],
                 eligible_at: Dict[Tuple[str, int], float]):
        self._runs = runs            # [((template, group), pod indices)]
        self._at = eligible_at       # fresh Deployment -> offset, seconds
        self._next_run = 0
        self._held: List[int] = []   # runs passed over, in draw order
        self._out: List[int] = []
        self._n = sum(len(idx) for _, idx in runs)
        self.t_open = float("inf")   # nothing is eligible before the window

    def __len__(self) -> int:
        return self._n

    def _eligible(self, dep) -> bool:
        at = self._at.get(dep)
        return at is None or time.perf_counter() >= self.t_open + at

    def _extend(self) -> None:
        for j, r in enumerate(self._held):
            if self._eligible(self._runs[r][0]):
                self._out += self._runs[self._held.pop(j)][1]
                return
        while self._next_run < len(self._runs):
            r = self._next_run
            self._next_run += 1
            if self._eligible(self._runs[r][0]):
                self._out += self._runs[r][1]
                return
            self._held.append(r)
        # only held runs are left: the window outlived the draw
        self._out += self._runs[self._held.pop(0)][1]

    def __getitem__(self, k):
        if isinstance(k, slice):
            stop = self._n if k.stop is None else min(k.stop, self._n)
            while len(self._out) < stop:
                self._extend()
            return self._out[k]
        while len(self._out) <= k:
            self._extend()
        return self._out[k]


def prepare(cluster, traffic: Dict, seed: int, seconds: float) -> Dict:
    templates = cluster.config["pod_templates"]
    fresh = fresh_groups(templates, traffic.get("fresh_deployments", 0))
    order = [(t, g) for t in templates for g in fresh[t]]
    random.Random(seed ^ 0x5EED).shuffle(order)
    every = float(traffic.get("fresh_every_s", 0.0))
    eligible_at = {dep: every * (j + 1) for j, dep in enumerate(order)}
    runs = []
    for t, g, size in scaleups(templates, traffic, seed):
        runs.append(((t, g), cluster.prebuild(
            [cluster.pod_class(t, g)] * size)))
    seq = Draw(runs, eligible_at)
    backlog = traffic.get("backlog_pods", 0)
    if backlog:
        cluster.sched.pause()
        time.sleep(traffic.get("park_s", 0.0))
        for i in seq[:backlog]:
            cluster.create(i)
        cluster.stage_end(backlog, time.perf_counter() + 60.0, resume=False)
    return {"idxs": seq, "next": backlog}


def drive(cluster, plan: Dict, rec, t_open: float, t_close: float) -> Dict:
    plan["idxs"].t_open = t_open
    return _waves().drive(cluster, plan, rec, t_open, t_close)
