"""The preemption planner's wave books, kept from one wave to the next.

A failure wave's books (preemption.py `FastPreemptionPlanner._build`)
need every node's eviction units: singletons, and co-located gang units
that leave whole. Between two waves only the nodes that bound or lost
pods change, and the cache already says which: every add, remove and
set_node bumps `NodeInfo.generation` (framework/types.py), and a
confirmed assume swaps nothing the books read. WaveBooks keeps each
node's part of the books in padded [N, V] arrays in node order, tagged
with the generation it was walked at, and walks again only the nodes
whose generation moved. A node whose generation moves WHILE it is being
walked keeps its fresh row for the wave but is tagged -1, so the next
wave walks it again.

Only facts that do not depend on the wave are kept: every unit of the
node whatever its priority, and no claimed-victim exclusion. What
depends on the wave — its priorities, the scalar dims it asks for, the
victims earlier waves claimed, the PDB match — is derived per wave on
the planner's own copies; a wave never writes the kept state.

The device rung's per-member rows (`pod_row_delta`, `_pod_self_rows`,
the terminating flag) are kept beside them, tagged with the encoding
and the vocab widths they were built at. A node holding a pod with
volumes has its device rows rebuilt every wave: their attach extras
follow refcounts the node's generation does not see.
"""

from __future__ import annotations

import threading
from typing import Dict, FrozenSet, List, Sequence, Tuple

import numpy as np

from ..api import types as v1
from .framework.types import NodeInfo, calculate_resource

_PRIO_SENTINEL = np.iinfo(np.int64).max  # padding rows never match `< prio`

# per-slot arrays, [N, V] ([N, V, 3] for the request), and their padding
_SLOT_FIELDS = {
    "vec3": (np.int64, 0),
    "prio": (np.int64, _PRIO_SENTINEL),
    "start": (np.float64, 0.0),
    "latest": (np.float64, 0.0),
    "size": (np.int64, 0),
    "priosum": (np.int64, 0),
    "live": (bool, False),
}
# per-node arrays, [N] ([N, 3] for the base dims)
_NODE_FIELDS = ("npods", "max_pods", "alloc3", "used3")


def _prio(pod: v1.Pod) -> int:
    return pod.spec.priority or 0


class Row:
    """One node's eviction units, numbered as the books number their
    slots: singletons in the node's pod order, then co-located gang units
    (members in MoreImportantPod order) in first-seen order — the
    oracle's _victim_units, whole gangs or none. Per unit: the members'
    MAX priority (so `< prio` admits a gang only when every member is
    outranked), the earliest and the latest start among its
    highest-priority members, the member count, the summed priority, the
    summed request in the base dims and (`scal`, where any) in scalar
    resources."""

    __slots__ = ("units", "vec3", "prio", "start", "latest", "size",
                 "priosum", "scal", "npods", "max_pods", "alloc3", "used3",
                 "node_scal")


def walk(ni: NodeInfo, exclude: FrozenSet[str] = frozenset()) -> Row:
    """The node's row; `exclude` leaves pods out (a wave's partly claimed
    gang, whose unclaimed members form the unit)."""
    from .plugins.coscheduling import pod_group

    singles: List[List[v1.Pod]] = []
    gangs: Dict[Tuple[str, str], List[v1.Pod]] = {}
    for pi in ni.pods:
        pod = pi.pod
        if exclude and v1.pod_key(pod) in exclude:
            continue
        group, min_available = pod_group(pod)
        if group and min_available > 1:
            gangs.setdefault((pod.metadata.namespace, group), []).append(pod)
        else:
            singles.append([pod])
    for members in gangs.values():
        members.sort(key=lambda m: (-_prio(m), m.status.start_time or 0.0))
    r = Row()
    r.units = singles + list(gangs.values())
    vec3, prio, start, latest, size, priosum = [], [], [], [], [], []
    r.scal = []
    for j, members in enumerate(r.units):
        prios = [_prio(m) for m in members]
        vp = max(prios)
        hi = [m.status.start_time or 0.0
              for m, p in zip(members, prios) if p == vp]
        cpu = mem = eph = 0
        scal: Dict[str, int] = {}
        for m in members:
            res, _, _ = calculate_resource(m)
            cpu += res.milli_cpu
            mem += res.memory
            eph += res.ephemeral_storage
            for name, val in res.scalar_resources.items():
                scal[name] = scal.get(name, 0) + val
        if scal:
            r.scal.append((j, scal))
        vec3.append((cpu, mem, eph))
        prio.append(vp)
        start.append(min(hi))
        latest.append(max(hi))
        size.append(len(members))
        priosum.append(sum(prios))
    r.vec3 = np.array(vec3, np.int64).reshape(-1, 3)
    r.prio, r.start, r.latest = prio, start, latest
    r.size, r.priosum = size, priosum
    r.npods = len(ni.pods)
    alloc, req = ni.allocatable, ni.requested
    r.max_pods = alloc.allowed_pod_number
    r.alloc3 = (alloc.milli_cpu, alloc.memory, alloc.ephemeral_storage)
    r.used3 = (req.milli_cpu, req.memory, req.ephemeral_storage)
    r.node_scal = (dict(alloc.scalar_resources), dict(req.scalar_resources))
    return r


def fill(slots: Dict[str, np.ndarray], node: Dict[str, np.ndarray],
         rows: Sequence[Tuple[int, Row]]) -> None:
    """Write rows (index, Row) into padded arrays, whole rows at once."""
    if not rows:
        return
    idx = np.array([i for i, _ in rows], np.int64)
    for k, (_, pad) in _SLOT_FIELDS.items():
        slots[k][idx] = pad
    counts = [len(r.units) for _, r in rows]
    ii = np.repeat(idx, counts)
    jj = np.concatenate([np.arange(c) for c in counts])
    slots["vec3"][ii, jj] = np.concatenate([r.vec3 for _, r in rows])
    for k in ("prio", "start", "latest", "size", "priosum"):
        slots[k][ii, jj] = [x for _, r in rows for x in getattr(r, k)]
    slots["live"][ii, jj] = True
    for k in _NODE_FIELDS:
        node[k][idx] = [getattr(r, k) for _, r in rows]


def device_row(backend, units: List[List[v1.Pod]], R: int):
    """A row's device rows: per unit the members' summed requested-row
    delta ([k, R]: a vector of another width counts zero), and per member
    its row delta, label rows and terminating flag; and whether any
    member has volumes."""
    enc = backend.enc
    req = np.zeros((len(units), R), np.int64)
    rows, term, vecs = [], [], []
    volatile = False
    for j, members in enumerate(units):
        r_j, t_j, v_j = [], [], []
        for pod in members:
            vec, _nz = enc.pod_row_delta(pod)
            if vec.shape[0] == R:
                req[j] += vec
            r_j.append(backend._pod_self_rows(pod))
            t_j.append(pod.metadata.deletion_timestamp is not None)
            v_j.append(vec)
            volatile = volatile or bool(pod.spec.volumes)
        rows.append(r_j)
        term.append(t_j)
        vecs.append(v_j)
    return req, rows, term, vecs, volatile


class WaveBooks:
    """Every node's part of the wave books, kept across waves, padded to
    [N, V] so that the planner's reprieve runs over every candidate node
    at once. The planner takes `lock` for its build, calls `sync` (and
    the device rung `sync_device`), and copies what it will write."""

    def __init__(self):
        self.lock = threading.Lock()
        self.names: List[str] = []
        self._index: Dict[str, int] = {}
        self.gen = np.zeros(0, np.int64)  # generation walked at; -1: walk
        self.V = 0  # the most units a node holds
        self.slots = {k: np.full((0, 1) + ((3,) if k == "vec3" else ()),
                                 pad, dt)
                      for k, (dt, pad) in _SLOT_FIELDS.items()}
        self.node = {k: np.zeros((0, 3) if k.endswith("3") else 0, np.int64)
                     for k in _NODE_FIELDS}
        self.units: List[List[List[v1.Pod]]] = []
        self.scal: List[List[Tuple[int, Dict[str, int]]]] = []
        self.node_scal: List[Tuple[Dict[str, int], Dict[str, int]]] = []
        # pod key -> (node name, slot, member) over every kept row
        self.where: Dict[str, Tuple[str, int, int]] = {}
        # device rows: the tag they were built at, per node whether they
        # are current and whether they must be rebuilt every wave
        self.dev_tag = None
        self.dev_ok = np.zeros(0, bool)
        self.dev_volatile = np.zeros(0, bool)
        self.dev_req = np.zeros((0, 1, 0), np.int64)
        self.dev_rows: List[List[List[Dict]]] = []
        self.dev_term: List[List[List[bool]]] = []
        self.dev_vec: List[List[List[np.ndarray]]] = []

    # -- base rows ---------------------------------------------------------

    def sync(self, nodes: Sequence[NodeInfo]) -> np.ndarray:
        """Bring the kept rows to `nodes` (the snapshot's list): walk the
        nodes whose generation moved or that are new. Returns their
        indices."""
        names = [ni.node.metadata.name for ni in nodes]
        if names != self.names:
            self._relayout(names)
        gens = np.fromiter((ni.generation for ni in nodes), np.int64,
                           len(nodes))
        stale = np.flatnonzero(gens != self.gen)
        rows = []
        for i in stale.tolist():
            ni = nodes[i]
            g0 = ni.generation
            row = walk(ni)
            rows.append((i, row, g0 if ni.generation == g0 else -1))
        if rows:
            self._store(rows)
        return stale

    def _store(self, rows) -> None:
        counts = self.slots["live"].sum(axis=1)
        for i, row, _ in rows:
            counts[i] = len(row.units)
        V = int(counts.max(initial=0))
        if V != self.V:
            self._resize(V)
        fill(self.slots, self.node, [(i, r) for i, r, _ in rows])
        for i, row, g in rows:
            name = self.names[i]
            self._unindex(name, self.units[i])
            for j, members in enumerate(row.units):
                for m, pod in enumerate(members):
                    self.where[v1.pod_key(pod)] = (name, j, m)
            self.units[i] = row.units
            self.scal[i] = row.scal
            self.node_scal[i] = row.node_scal
            self.gen[i] = g
            self.dev_ok[i] = False

    def _unindex(self, name: str, units: List[List[v1.Pod]]) -> None:
        for members in units:
            for pod in members:
                key = v1.pod_key(pod)
                if self.where.get(key, ("",))[0] == name:
                    del self.where[key]

    def _resize(self, V: int) -> None:
        """Pad or cut the slot axis to V (at least 1): rows beyond the
        most units any node holds are padding in every row."""
        w = max(V, 1)
        for k, (dt, pad) in _SLOT_FIELDS.items():
            old = self.slots[k]
            new = np.full((old.shape[0], w) + old.shape[2:], pad, dt)
            c = min(w, old.shape[1])
            new[:, :c] = old[:, :c]
            self.slots[k] = new
        old = self.dev_req
        new = np.zeros((old.shape[0], w, old.shape[2]), np.int64)
        c = min(w, old.shape[1])
        new[:, :c] = old[:, :c]
        self.dev_req = new
        self.V = V

    def _relayout(self, names: List[str]) -> None:
        """Node membership or order changed: carry every kept row to its
        new index, drop the rows of nodes that left."""
        for name in set(self.names) - set(names):
            self._unindex(name, self.units[self._index[name]])
        pos = np.array([self._index.get(n, -1) for n in names], np.int64)
        has = pos >= 0
        src = pos[has]

        def carry(old: np.ndarray, pad) -> np.ndarray:
            new = np.full((len(names),) + old.shape[1:], pad, old.dtype)
            new[has] = old[src]
            return new

        for k, (_, pad) in _SLOT_FIELDS.items():
            self.slots[k] = carry(self.slots[k], pad)
        for k in _NODE_FIELDS:
            self.node[k] = carry(self.node[k], 0)
        self.gen = carry(self.gen, -1)
        self.dev_ok = carry(self.dev_ok, False)
        self.dev_volatile = carry(self.dev_volatile, False)
        self.dev_req = carry(self.dev_req, 0)
        p = pos.tolist()
        for attr, empty in (("units", list), ("scal", list),
                            ("node_scal", lambda: ({}, {})),
                            ("dev_rows", list), ("dev_term", list),
                            ("dev_vec", list)):
            old = getattr(self, attr)
            setattr(self, attr, [old[q] if q >= 0 else empty() for q in p])
        self.names = names
        self._index = {n: i for i, n in enumerate(names)}
        V = int(self.slots["live"].sum(axis=1).max(initial=0))
        if V != self.V:
            self._resize(V)

    # -- device rows -------------------------------------------------------

    def sync_device(self, backend, R: int) -> np.ndarray:
        """Bring the device rows to the kept rows at the encoding's
        current widths: rebuild the rows the last `sync` walked, those
        with volumes, and every row when the encoding or a width moved.
        Returns the indices rebuilt."""
        enc = backend.enc
        tag = (enc.pod_pair_vocab.capacity, enc.pod_key_vocab.capacity,
               enc._res_width(), R)
        if (self.dev_tag is None or self.dev_tag[0] is not enc
                or self.dev_tag[1:] != tag):
            self.dev_tag = (enc,) + tag
            self.dev_ok[:] = False
            self.dev_req = np.zeros(
                (len(self.names), max(self.V, 1), R), np.int64)
        todo = np.flatnonzero(~self.dev_ok | self.dev_volatile)
        for i in todo.tolist():
            req, rows, term, vecs, volatile = device_row(
                backend, self.units[i], R)
            self.dev_req[i] = 0
            self.dev_req[i, :len(req)] = req
            self.dev_rows[i], self.dev_term[i], self.dev_vec[i] = \
                rows, term, vecs
            self.dev_volatile[i] = volatile
        self.dev_ok[todo] = True
        return todo
