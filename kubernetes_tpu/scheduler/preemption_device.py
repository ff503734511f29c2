"""Device-side preemption planner: the top rung of the planner ladder.

Three rungs, per failed pod:

  device  — victim search as a batched what-if scan (ops/whatif.py): one
            fused launch per preemptor evaluates base feasibility and
            the exact reprieve walk for EVERY candidate node against a
            scratch copy of the session carry. Covers preemptors with
            pod (anti-)affinity terms and topology-spread constraints —
            the classes the numpy envelope must reject — because the
            session kernels already compute the IPA/PTS count
            interference the dry run needs. Where a run of consecutive
            preemptors of one view, template and priority claims only
            lane-local state (no PDB-covered, pair-matching or gang
            victims, no pair-matching preemptor), one WAVE launch plans
            up to 64 of them, the pick and the claim inside the
            program, and the host replays its picks through the same
            candidate build and claim.
  fast    — the numpy FastPreemptionPlanner (preemption.py): resource
            fit + static gates + vectorized PDB reprieve, host-side.
  oracle  — the DefaultPreemption plugin dry-run via the scheduler's
            redispatch path (per-pod filter chain).

This planner subclasses FastPreemptionPlanner so the WAVE BOOKS are one
set of state across rungs: PDB allowance tensors, the MoreImportantPod
sort, claimed-victim exclusion, and nominated-load accounting are shared
verbatim — two rungs can never double-claim a victim or disagree on the
pick-one ladder, because both read and write the same books. Node
choice, victim sets and PDB handling stay bit-identical to the Go-oracle
semantics pinned in tests/test_preemption_fast.py.

A device fault mid-what-if (launch raise, watchdog timeout) falls the
pod one rung — device -> fast (or oracle when the numpy envelope rejects
it) — through the PR 4 degradation machinery: the fault is counted and
ladder-recorded, but the LIVE session is never invalidated (the what-if
ran on a scratch snapshot; `scheduler_session_rebuilds_total` must not
move from planning).
"""

from __future__ import annotations

import logging
import time as _time
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..api import types as v1
from ..utils import tracing
from . import metrics
from .degradation import DeviceFault
from .plugins.defaultpreemption import PRIORITY_OFFSET, Candidate
from .preemption import (
    FastPreemptionPlanner,
    WaveAntiTerms,
    _prio,
    eviction_invariant_gates,
)
from .wave_books import WaveBooks, device_row

logger = logging.getLogger(__name__)

# sentinel candidate: this pod must fall to the ORACLE rung (the
# scheduler routes it through the batched redispatch + DefaultPreemption)
ORACLE_FALLBACK = object()

_I64_MIN = np.iinfo(np.int64).min


def device_eligible(pod: v1.Pod, extenders: Sequence,
                    anti_terms: WaveAntiTerms) -> bool:
    """The device rung's envelope: fast_eligible WITHOUT the affinity /
    topology-spread gates (the what-if kernel evaluates those filters
    under eviction), keeping the gates eviction cannot express:
    extenders, Never-policy, a pinned spec.nodeName, host ports, PVCs,
    and existing pods whose required anti-affinity terms match the
    preemptor (a victim eviction can only DECREMENT term counts;
    un-ORing another pod's repulsion is outside the count algebra)."""
    if extenders:
        return False
    if anti_terms.matches(pod):
        return False
    return eviction_invariant_gates(pod)


class _Inputs:
    """One (what-if view, template, priority)'s launch inputs across a
    wave: running totals of its nominated and claimed-victim tensors,
    and the device's copy of the whole input set (`x`, None until a
    launch returns one) with what the host keeps in step with it — the
    slot order the epilogue reads, the claims it has taken in, and the
    PDB budgets its violating split was derived from."""

    __slots__ = ("nom", "pre", "x", "lost", "L", "slot_j", "slot_valid",
                 "slot_vio", "claims", "pdb_allowed")

    def __init__(self, n_lanes: int, R: int, C: int, taa: int, vnp: int):
        self.nom = {
            "done": 0, "n": 0,
            "req": np.zeros((n_lanes, R), np.int64),
            "cnt": np.zeros(n_lanes, np.int64),
            "mfs": np.zeros((n_lanes, C), np.int32),
            "manti": np.zeros((n_lanes, taa), np.int32),
            "mall": np.zeros(n_lanes, np.int32),
        }
        self.pre = {
            "done": 0,
            "req": np.zeros((n_lanes, R), np.int64),
            "cnt": np.zeros(n_lanes, np.int64),
            "raw": np.zeros((C, vnp), np.int32),
            "anti": np.zeros((taa, vnp), np.int32),
            "aff": np.zeros(vnp, np.int32),
        }
        self.x: Optional[Dict] = None
        self.lost = False  # x went to a launch that did not return
        self.L = 0
        self.slot_j = self.slot_valid = self.slot_vio = None
        self.claims = 0
        self.pdb_allowed: Optional[np.ndarray] = None


class _Key:
    """A preemptor's launch key: its what-if view, template and
    priority, with the template's host-side slices."""

    __slots__ = ("ctx", "nps", "tj", "prio", "key", "same_key")

    def __init__(self, ctx, nps: Dict, tj: int, prio: int):
        self.ctx, self.nps, self.tj, self.prio = ctx, nps, tj, prio
        self.key = (id(ctx), tj, prio)
        self.same_key = nps["f_same_key"].astype(np.int32)  # [C, C]


class DevicePreemptionPlanner(FastPreemptionPlanner):
    """FastPreemptionPlanner books + a device what-if rung.

    `eligibility` maps pod_key -> (device_ok, fast_ok) as computed by
    the scheduler's wave partition (one WaveAntiTerms pass); pods
    missing from the map ride the fast rung (base-class behavior).

    A launch's inputs stay on the device for the wave: the first launch
    of a (view, template, priority) uploads them whole, each later one
    only the lanes the claims since changed (`resident_inputs=False`
    uploads every launch whole: the parity control).

    A run of preemptors of one launch key whose claims are lane-local
    is planned by wave launches, pick and claim on the device, and
    replayed on the host; `wave_launch=False` launches every preemptor
    alone: the parity control of tests/test_whatif_wave.py."""

    def __init__(self, snapshot, nominator, backend, framework=None,
                 args: Optional[dict] = None,
                 claimed_victims: Optional[Set[str]] = None,
                 pdbs: Optional[Sequence[v1.PodDisruptionBudget]] = None,
                 eligibility: Optional[Dict[str, Tuple[bool, bool]]] = None,
                 resident_inputs: bool = True,
                 books: Optional[WaveBooks] = None,
                 wave_launch: bool = True):
        super().__init__(snapshot, nominator, framework=framework,
                         args=args, claimed_victims=claimed_victims,
                         pdbs=pdbs, books=books)
        self.backend = backend
        self.eligibility = eligibility or {}
        self.resident_inputs = resident_inputs
        self.wave_launch = wave_launch
        self.planner_paths: List[str] = []
        # the last launch's attributes of the `whatif` span, and when its
        # results reached the host
        self._launch_attrs: Dict[str, object] = {}
        self._t_results = 0.0

    # -- wave books: device-side extensions --------------------------------

    def _build(self, wave: List[v1.Pod]) -> None:
        super()._build(wave)  # the books' step `base`
        sp = self._books_span
        self.planner_paths = []
        enc = self.backend.enc
        with self.backend._lock:
            # node_index / row arrays materialize at rebuild time; a
            # fresh backend that never dispatched has neither (host-only
            # rebuild — the cached device dict is untouched)
            if enc._rebuild_needed or not enc._arrays:
                enc.rebuild()
            # pin the lane layout the wave books were built against: a
            # node that joins or leaves (or a rebuild) may reorder the
            # lanes, and the lane map below would attribute verdicts to
            # the wrong nodes — the per-pod launch re-checks this pin and
            # falls a rung instead. Pod binds and deletes move no lane
            self._books_version = enc.lane_version
        # one what-if view per template for the whole wave: taken once,
        # it is a fixed point the books' claims are counted against
        self._ctx: Dict[str, object] = {}
        # memoized per-row-object match tensors: claim lists only grow
        # across a wave, and re-matching EVERY accumulated entry per
        # preemptor is the O(wave^2) trap the base class's running
        # totals exist to avoid (preemption.py _nom_sum comment)
        self._match_memo: Dict[Tuple[int, int, int], Tuple] = {}
        self._slot_memo: Dict[Tuple[int, int], Tuple] = {}
        self._held_memo: Dict[int, np.ndarray] = {}
        # launch inputs by (view, template, priority), and the planner
        # rows whose victims each claim took, in claim order
        self._inputs: Dict[Tuple[int, int, int], _Inputs] = {}
        self._claimed_at: List[int] = []
        self._lane_map = None  # _enc_idx on the device, for wave launches
        # planner (snapshot) node order -> encoding lane
        self._enc_idx = np.array(
            [enc.node_index.get(ni.node.metadata.name, -1)
             for ni in self.nodes],
            dtype=np.int64,
        )
        sp.step("lanes")
        # victim device rows, dense by (planner node, victim slot): a
        # slot is an eviction UNIT (singleton or whole co-located gang)
        # — its request row is the members' SUM, while label rows and
        # terminating flags stay per member (match tensors and the
        # prologue's ~pterm PTS gate are per-pod facts the slot
        # aggregates at tensor-prep time). Kept with the books: only the
        # rows walked again, and rows with volumes, are built
        R = enc._arrays["requested"].shape[1] if enc._arrays else 0
        self._enc_r = R
        books = self.books
        self._rebuilt.update(books.sync_device(self.backend, R).tolist())
        self._v_enc_req = books.dev_req.copy()
        self._v_rows: List[List[List[Dict]]] = list(books.dev_rows)
        self._v_term: List[List[List[bool]]] = list(books.dev_term)
        for i, row in self._own_rows.items():
            req, rows, term, _vecs, _vol = device_row(
                self.backend, row.units, R)
            self._v_enc_req[i] = 0
            self._v_enc_req[i, :len(req)] = req
            self._v_rows[i], self._v_term[i] = rows, term
        sp.step("victims")
        # claimed victims (earlier in-flight waves): resident in the
        # encoding but already spoken for — every what-if state drains
        # them, at topology-pair granularity (their groups span nodes);
        # the last field is the pod key (the view drains only those it
        # still holds)
        self._pre: List[Tuple[int, Dict, np.ndarray, bool, str]] = []
        for i, j, m, key in self._claimed_locs:
            lane = int(self._enc_idx[i])
            if lane < 0:
                continue
            vec = books.dev_vec[i][j][m]
            self._pre.append((
                lane, books.dev_rows[i][j][m],
                vec if vec.shape[0] == R else np.zeros(R, np.int64),
                books.dev_term[i][j][m], key,
            ))
        sp.step("claimed")
        # nominated entries with pod rows (the base class keeps only
        # request vectors in planner dims); claims append here too, with
        # no key: the nominator's entries carry theirs, for a view that
        # already holds the pod (held by the backend, or bound since)
        # counts it itself
        self._nom_entries: List[
            Tuple[int, int, Dict, np.ndarray, Optional[str]]] = []
        if self.nominator is not None:
            wave_keys = {v1.pod_key(p) for p in wave}
            for i, ni in enumerate(self.nodes):
                for np_pod in self.nominator.nominated_pods_for_node(
                    ni.node.metadata.name
                ):
                    if v1.pod_key(np_pod) in wave_keys:
                        continue
                    vec, _nz = enc.pod_row_delta(np_pod)
                    self._nom_entries.append((
                        i, _prio(np_pod),
                        self.backend._pod_self_rows(np_pod),
                        vec if vec.shape[0] == R else np.zeros(R, np.int64),
                        v1.pod_key(np_pod),
                    ))
        sp.step("nominated")

    def _claim(self, cand: Candidate, pod: v1.Pod, prio: int,
               req: np.ndarray) -> None:
        i = self._name_to_idx[cand.node_name]
        lane = int(self._enc_idx[i]) if hasattr(self, "_enc_idx") else -1
        keys = {v1.pod_key(vp) for vp in cand.victims}
        claimed_rows = []
        if lane >= 0:
            enc = self.backend.enc
            for j, slot_pods in enumerate(self._vpods[i]):
                for m, vp in enumerate(slot_pods):
                    if v1.pod_key(vp) not in keys:
                        continue
                    # per-MEMBER request rows (the slot's _v_enc_req is
                    # the unit sum; claimed drains stay per pod)
                    vec, _nz = enc.pod_row_delta(vp)
                    claimed_rows.append((
                        lane, self._v_rows[i][j][m],
                        vec if vec.shape[0] == self._enc_r
                        else np.zeros(self._enc_r, np.int64),
                        bool(self._v_term[i][j][m]),
                        v1.pod_key(vp),
                    ))
        super()._claim(cand, pod, prio, req)
        # the victims just left the books; later what-ifs must drain
        # them from every state, and the preemptor is nominated load
        self._claimed_at.append(i)
        self._pre.extend(claimed_rows)
        if lane >= 0:
            enc = self.backend.enc
            vec, _nz = enc.pod_row_delta(pod)
            self._nom_entries.append((
                i, prio, self.backend._pod_self_rows(pod),
                vec if vec.shape[0] == self._enc_r
                else np.zeros(self._enc_r, np.int64),
                None,
            ))

    # -- per-pod rung routing ----------------------------------------------

    def _plan_pods(self, wave: List[v1.Pod], limit: int):
        """The wave in order. A run of consecutive device-eligible
        preemptors of one launch key (view, template, priority) is
        planned by wave launches of up to WAVE_STEPS preemptors each,
        where its claims are lane-local; every other preemptor on its
        own rung (_plan_one), the reason counted."""
        from ..ops.whatif import WAVE_STEPS

        why = self._wave_reason()
        if why is not None:
            return [self._plan_one(pod, limit, single=why) for pod in wave]
        out: List = []
        k = 0
        while k < len(wave):
            run, head = self._next_run(wave, k, WAVE_STEPS)
            why = "key" if head is None else self._single_reason(head, run)
            if why is None:
                out.extend(self._plan_run(run, head, limit))
            else:
                out.extend(self._plan_one(pod, limit, single=why)
                           for pod in run)
            k += len(run)
        return out

    def _plan_one(self, pod: v1.Pod, limit: int, single: str = "key"):
        """One preemptor on its rung: a launch of its own on the device
        rung (`single`: why it is not in a wave launch), else one rung
        down."""
        dev_ok, fast_ok = self.eligibility.get(v1.pod_key(pod),
                                               (False, True))
        if dev_ok:
            try:
                # own stage (not "planner"): this span nests inside the
                # wave-level planner span, and stage_stats sums per
                # stage — sharing the stage would double-count the
                # wave's wall-clock in the attribution tables. The
                # pod-key attr is gated on enabled(): this is per-POD
                # code, and the disabled path must not pay a string
                # build per preemptor
                sp = tracing.span(
                    "whatif", "whatif", pod=v1.pod_key(pod),
                ) if tracing.enabled() else tracing.NOOP_SPAN
                with sp:
                    fits, cand = self._plan_one_device(pod, limit)
                    if sp is not tracing.NOOP_SPAN:
                        # pick: results on the host -> candidate claimed
                        sp.set(pick_s=_time.perf_counter() - self._t_results,
                               **self._launch_attrs)
                self._planned(fits, "single", single)
                return cand
            except Exception as e:  # noqa: BLE001 — any device/prep
                # failure falls one rung; the wave must keep planning
                self._fell(e)
        return self._lower_rung(pod, limit, fast_ok)

    def _planned(self, fits: bool, path: str, reason: str) -> None:
        self.fits_now.append(fits)
        self.planner_paths.append("device")
        metrics.preemption_planner.inc(path="device")
        metrics.whatif_planned.inc(path=path, reason=reason)

    def _fell(self, e: Exception) -> None:
        """Count why a device launch could not plan its preemptor."""
        from ..ops.whatif import WhatifUnavailable

        if isinstance(e, DeviceFault):
            reason = "fault"
            self.backend.record_whatif_fault(e.kind)
        elif isinstance(e, WhatifUnavailable):
            reason = e.reason
        else:
            reason = "error"
            logger.warning("what-if planning failed; falling back",
                           exc_info=True)
        metrics.whatif_fallbacks.inc(reason=reason)

    def _lower_rung(self, pod: v1.Pod, limit: int, fast_ok: bool):
        if fast_ok:
            self.planner_paths.append("fast")
            return super()._plan_one(pod, limit)
        self.planner_paths.append("oracle")
        self.fits_now.append(False)
        return ORACLE_FALLBACK

    # -- the device rung: launch keys --------------------------------------

    def _launch_key(self, pod: v1.Pod) -> "_Key":
        """The view, template and priority this preemptor launches
        under. Raises WhatifUnavailable to fall a rung."""
        from ..ops.hoisted import template_fingerprint
        from ..ops.whatif import WhatifUnavailable
        from .volume_device import VolumeResolutionChanged

        backend = self.backend
        try:
            enc_pa = backend.pe.encode(pod)
        except VolumeResolutionChanged as e:
            raise WhatifUnavailable(str(e), reason="encode") from e
        pa = {k: v for k, v in enc_pa.items() if not k.startswith("_")}
        fp = template_fingerprint(pa)
        ctx = self._ctx.get(fp)
        if ctx is None:
            ctx = self._ctx[fp] = backend.whatif_context(pa)
        tj = ctx.template_index(pa)
        lanes = self._enc_idx
        if (
            self.n == 0
            or (lanes < 0).any()
            or int(lanes.max()) >= ctx.n_lanes
            # the lane map must describe the SAME encoding epoch the
            # context snapshotted: concurrent churn reorders lanes
            # in-range (capacities are pow2 buckets), so the version
            # pin — not the range check — is the real guard
            or backend.enc.lane_version != self._books_version
        ):
            raise WhatifUnavailable("node table skew vs the encoding",
                                    reason="node-skew")
        return _Key(ctx, ctx.np_slices(tj), tj, _prio(pod))

    def _key_inputs(self, k: "_Key") -> _Inputs:
        inp = self._inputs.get(k.key)
        if inp is None:
            inp = self._inputs[k.key] = _Inputs(
                k.ctx.n_lanes, self._enc_r, k.same_key.shape[0],
                k.nps["ipaaa_valid"].shape[0], k.ctx.vnp)
        return inp

    def _launch_inputs(self, k: "_Key", inp: _Inputs):
        """The next launch's inputs for key `k`: the claims since its
        last launch taken into the running totals, then the device's
        copy (donated to the launch: a raise from here on leaves none,
        and the next launch uploads whole) with a delta, or a whole
        upload. Returns (x, delta, why whole or None, lanes the delta
        touches, bytes uploaded)."""
        ctx, nps, tj, prio, same_key = k.ctx, k.nps, k.tj, k.prio, k.same_key
        nom_new = self._nom_take(ctx, nps, tj, prio, inp, same_key)
        pre_new = self._pre_take(ctx, nps, tj, inp, same_key)
        why = self._full_reason(inp)
        x, inp.x, inp.lost = inp.x, None, True
        n_delta = 0
        if why is None:
            try:
                delta, n_delta = self._delta(ctx, nps, tj, prio, inp,
                                             nom_new, pre_new, same_key)
            except ValueError:  # more changes than the delta holds
                why = "overflow"
        if why is not None:
            x, delta = self._full_inputs(ctx, nps, tj, prio, inp, same_key)
        inp.claims = len(self._claimed_at)
        inp.pdb_allowed = self._pdb_allowed.copy()
        h2d = delta.nbytes + (sum(a.nbytes for a in x.values())
                              if why is not None else 0)
        metrics.whatif_inputs.inc(path="full" if why else "delta",
                                  reason=why or "resident")
        return x, delta, why, n_delta, h2d

    def _launched(self, ys, names: Sequence[str], what: str):
        """Wait for a launch's results under the watchdog and bring
        `names` of them to the host; a wedge or a raise is a fault."""
        backend = self.backend
        try:
            if not backend._wait_ready(ys, backend.watchdog_timeout):
                raise DeviceFault(f"{what} exceeded the watchdog",
                                  kind="timeout")
            return [np.asarray(ys[n]) for n in names]
        except DeviceFault:
            raise
        except Exception as e:  # noqa: BLE001 — launch-path raise = fault
            raise DeviceFault(f"{what} raised: {e}", kind="raise") from e

    # -- the device rung: one preemptor a launch ---------------------------

    def _plan_one_device(self, pod: v1.Pod, limit: int):
        """One fused what-if launch for this preemptor; returns
        (fits_now, Candidate | None). Raises WhatifUnavailable /
        DeviceFault to fall a rung."""
        backend = self.backend
        k = self._launch_key(pod)
        t_prep = _time.perf_counter()
        req = self._req_vec(pod)
        lanes = self._enc_idx
        inp = self._key_inputs(k)
        x, delta, why, n_delta, h2d = self._launch_inputs(k, inp)

        # -- the launch ----------------------------------------------------
        try:
            backend.check_whatif_fault()
            metrics.whatif_launches.inc()
            ys, x = k.ctx.run(k.tj, x, delta, inp.nom["n"] > 0)
        except DeviceFault:
            raise
        except Exception as e:  # noqa: BLE001 — launch-path raise = fault
            raise DeviceFault(f"what-if launch raised: {e}",
                              kind="raise") from e
        t_wait = _time.perf_counter()
        fits_now, base, victims_dev = self._launched(
            ys, ("fits_now", "base", "victims"), "what-if launch")
        if self.resident_inputs:
            inp.x, inp.lost = x, False
        self._t_results = _time.perf_counter()
        self._launch_attrs = {
            "prep_s": t_wait - t_prep,
            "wait_s": self._t_results - t_wait,
            "inputs": "full" if why else "delta",
            "h2d_bytes": h2d,
            "delta_lanes": n_delta,
        }
        L, slot_j, slot_valid, slot_vio = (
            inp.L, inp.slot_j, inp.slot_valid, inp.slot_vio)

        # -- epilogue: candidate cut + pick, host-side like the fast
        # rung (snapshot order is the oracle's candidate order) -------------
        if bool(fits_now[lanes].any()):
            return True, None
        has_victims = slot_valid.any(axis=1)
        feasible = base[lanes] & has_victims
        idxs = np.flatnonzero(feasible)
        if idxs.size == 0:
            return False, None
        Cc = idxs[:limit]
        vmask = victims_dev[lanes[Cc]]                    # [Csz, L]
        vmask = vmask & slot_valid[Cc]
        sj = slot_j[Cc]
        vprio = self._vprio[Cc[:, None], sj]
        vsize = self._vsize[Cc[:, None], sj]
        # pick-ladder tallies are per POD, not per slot: a gang unit
        # contributes its member count / summed priorities / latest
        # highest-priority start
        n_vict = np.where(vmask, vsize, 0).sum(axis=1)
        n_pdbv = np.where(vmask & slot_vio[Cc], vsize, 0).sum(axis=1)
        sum_prio = np.where(
            vmask, self._vpriosum[Cc[:, None], sj], 0
        ).sum(axis=1)
        max_prio = np.where(vmask, vprio, _I64_MIN).max(
            axis=1, initial=_I64_MIN)
        hi_mask = vmask & (vprio == max_prio[:, None])
        latest = np.max(np.where(
            hi_mask, self._vlatest_hi[Cc[:, None], sj], -np.inf
        ), axis=1)
        ci = self._pick_index(n_vict > 0, n_pdbv, max_prio, sum_prio,
                              n_vict, latest)
        if ci is None:
            return False, None
        i = int(Cc[ci])
        cand = self._candidate(i, vmask[ci], slot_j[i], slot_vio[i], L)
        self._claim(cand, pod, k.prio, req)
        return False, cand

    def _candidate(self, i: int, picked: np.ndarray, slot_j: np.ndarray,
                   slot_vio: np.ndarray, L: int) -> Candidate:
        """Planner row i's victims: the members of the picked slots, in
        slot (reprieve) order."""
        sj = slot_j.astype(np.int64)
        victims = [vp for s in range(L) if picked[s]
                   for vp in self._vpods[i][int(sj[s])]]
        n_pdbv = int(np.where(picked & slot_vio, self._vsize[i, sj], 0).sum())
        return Candidate(self.nodes[i].node.metadata.name, victims,
                         num_pdb_violations=n_pdbv)

    # -- the device rung: wave launches ------------------------------------

    def _wave_reason(self) -> Optional[str]:
        """Why no preemptor of this wave may take a wave launch, or
        None."""
        if not self.wave_launch:
            return "off"
        if self._pdb_match.any():
            # a budget moves with every claim and re-splits every node's
            # victims into violating and not: host bookkeeping
            return "pdb"
        return None

    def _next_run(self, wave: List[v1.Pod], k: int, steps: int):
        """(run, its launch key): the preemptors from wave[k] on that
        share one launch key, at most `steps`; ([wave[k]], None) where
        wave[k] has no key (not device-eligible, or its launch would
        fall a rung: _plan_one meets the same and counts it)."""
        head = self._key_of(wave[k])
        if head is None:
            return wave[k:k + 1], None
        end = k + 1
        while end < len(wave) and end - k < steps:
            nxt = self._key_of(wave[end])
            if nxt is None or nxt.key != head.key:
                break
            end += 1
        return wave[k:end], head

    def _key_of(self, pod: v1.Pod) -> Optional["_Key"]:
        if not self.eligibility.get(v1.pod_key(pod), (False, True))[0]:
            return None
        try:
            return self._launch_key(pod)
        except Exception:  # noqa: BLE001 — planned alone, which counts it
            return None

    def _single_reason(self, k: "_Key", run: List[v1.Pod]) -> Optional[str]:
        """Why the run's claims are not lane-local, or None: a claim
        must move only its own lane's victim slots, drains and
        nominated load — no topology-pair count (a victim or a
        preemptor matching the template's spread classes or required
        (anti-)affinity terms) and no gang unit among the victims."""
        valid = self._valive & (self._vprio < k.prio)
        if (self._vsize[valid] > 1).any():
            return "gang"
        if not _reads_pairs(k.nps):
            return None
        mfs, manti, mall = self._slot_matches(k.ctx, k.nps, k.tj,
                                              k.same_key)
        if mfs[valid].any() or manti[valid].any() or mall[valid].any():
            return "pairs"
        mf, manti, mall = self._match_rows(
            k.ctx, k.nps, k.tj,
            [self.backend._pod_self_rows(pod) for pod in run])
        if (mf @ k.same_key.T).any() or manti.any() or mall.any():
            return "pairs"
        return None

    def _plan_run(self, run: List[v1.Pod], k: "_Key", limit: int):
        """A run planned by one wave launch, then replayed on the host
        in plan order. Where the launch fails, its first preemptor falls
        a rung, as its own launch's fault would fall it, and the rest
        launch alone."""
        try:
            inp, res = self._wave_launch(run, k, limit)
        except Exception as e:  # noqa: BLE001 — as _plan_one's
            self._fell(e)
            fast_ok = self.eligibility[v1.pod_key(run[0])][1]
            return ([self._lower_rung(run[0], limit, fast_ok)]
                    + [self._plan_one(pod, limit, single="fault")
                       for pod in run[1:]])
        return self._replay(run, k, inp, res)

    def _wave_launch(self, run: List[v1.Pod], k: "_Key", limit: int):
        """One wave launch over the run (ops/whatif._whatif_wave_run):
        the key's inputs as a single launch takes them, the run's
        request rows and the pick's tallies. Returns (the key's inputs,
        {fits, pick, victims} on the host)."""
        from ..ops.whatif import WAVE_STEPS

        backend = self.backend
        sp = tracing.span(
            "whatif-wave", "whatif-wave", n=len(run), steps=WAVE_STEPS,
        ) if tracing.enabled() else tracing.NOOP_SPAN
        with sp:
            t_prep = _time.perf_counter()
            inp = self._key_inputs(k)
            x, delta, why, n_delta, h2d = self._launch_inputs(k, inp)
            wave = self._wave_tensors(run, inp, limit, WAVE_STEPS)
            try:
                backend.check_whatif_fault()
                metrics.whatif_launches.inc()
                ys, x = k.ctx.run_wave(k.tj, x, delta, wave)
            except DeviceFault:
                raise
            except Exception as e:  # noqa: BLE001 — launch-path raise
                raise DeviceFault(f"what-if wave launch raised: {e}",
                                  kind="raise") from e
            t_wait = _time.perf_counter()
            fits, pick, victims = self._launched(
                ys, ("fits", "pick", "victims"), "what-if wave launch")
            if self.resident_inputs:
                inp.x, inp.lost = x, False
            sp.set(prep_s=t_wait - t_prep,
                   wait_s=_time.perf_counter() - t_wait,
                   inputs="full" if why else "delta", h2d_bytes=h2d,
                   delta_lanes=n_delta)
        return inp, {"fits": fits, "pick": pick, "victims": victims}

    def _wave_tensors(self, run: List[v1.Pod], inp: _Inputs, limit: int,
                      steps: int) -> Dict:
        """The wave program's `wave`: per step whether it is a preemptor
        and its request row in encoding dims (what a claim adds as
        nominated load, as _claim's entry does); the lane map, the
        candidate cut, and the pick's tallies of every planner row's
        slots in the key's slot order, start times as exact integer
        ranks."""
        R = self._enc_r
        enc = self.backend.enc
        active = np.zeros(steps, bool)
        active[:len(run)] = True
        nom = np.zeros((steps, R), np.int64)
        for s, pod in enumerate(run):
            vec, _nz = enc.pod_row_delta(pod)
            if vec.shape[0] == R:
                nom[s] = vec
        at = (np.arange(self.n)[:, None], inp.slot_j)
        _, rank = np.unique(self._vlatest_hi[at].ravel(),
                            return_inverse=True)
        if self._lane_map is None:
            import jax.numpy as jnp

            # once a wave: the planner is built for one
            self._lane_map = jnp.asarray(self._enc_idx.astype(np.int32))
        return {
            "active": active, "nom_req": nom, "lanes": self._lane_map,
            "limit": np.int32(limit), "offset": np.int64(PRIORITY_OFFSET),
            "prio": self._vprio[at], "priosum": self._vpriosum[at],
            "latest": rank.reshape(inp.slot_j.shape).astype(np.int32),
        }

    def _replay(self, run: List[v1.Pod], k: "_Key", inp: _Inputs,
                res: Dict) -> List:
        """The wave launch's steps replayed on the host in plan order:
        each pick becomes its Candidate and is claimed in the books as
        the per-preemptor path claims it. The device took the same
        claims into its copy of the inputs, its slots left in place
        (zeroed): the key's slot validity and running totals follow, so
        no later launch applies them again."""
        out = []
        for s, pod in enumerate(run):
            sp = tracing.span(
                "whatif", "whatif", pod=v1.pod_key(pod), path="wave",
            ) if tracing.enabled() else tracing.NOOP_SPAN
            with sp:
                t0 = _time.perf_counter()
                fits, i, cand = bool(res["fits"][s]), int(res["pick"][s]), None
                if not fits and i >= 0:
                    picked = res["victims"][s]
                    cand = self._candidate(i, picked, inp.slot_j[i],
                                           inp.slot_vio[i], inp.L)
                    self._claim(cand, pod, k.prio, self._req_vec(pod))
                    inp.slot_valid[i, picked] = False
                sp.set(pick_s=_time.perf_counter() - t0)
            self._planned(fits, "wave", "lane-local")
            out.append(cand)
        self._nom_take(k.ctx, k.nps, k.tj, k.prio, inp, k.same_key)
        self._pre_take(k.ctx, k.nps, k.tj, inp, k.same_key)
        inp.claims = len(self._claimed_at)
        return out

    # -- host tensor prep helpers ------------------------------------------

    def _slot_held(self, ctx) -> np.ndarray:
        """[n, Vm]: the slots whose every member the view holds. A victim
        of the books (the cache's snapshot) whose delete landed before
        the view was taken is not in the view's carry: evicting it there
        again would free its room twice. Once per view."""
        got = self._held_memo.get(id(ctx))
        if got is None:
            held = ctx.pod_keys
            got = np.ones((self.n, max(self._vmax, 1)), bool)
            if held is not None:
                # a slot dead now stays dead for the wave
                for i, j in zip(*np.nonzero(self._valive)):
                    if any(v1.pod_key(p) not in held
                           for p in self._vpods[i][j]):
                        got[i, j] = False
            self._held_memo[id(ctx)] = got
        return got

    def _slot_matches(self, ctx, nps, tj, same_key):
        """Per victim slot of the books, its members' match rows against
        the preemptor's template summed: (mfs [n, Vm, C], manti [n, Vm,
        TAA], mall [n, Vm]). A claim only takes a slot out of play, so
        once per view and template for the whole wave."""
        key = (id(ctx), tj)
        got = self._slot_memo.get(key)
        if got is not None:
            return got
        vm = max(self._vmax, 1)
        if not _reads_pairs(nps):
            got = self._slot_memo[key] = (
                np.zeros((self.n, vm, same_key.shape[0]), np.int32),
                np.zeros((self.n, vm, nps["ipaaa_valid"].shape[0]), np.int32),
                np.zeros((self.n, vm), np.int32))
            return got
        rows: List[Dict] = []
        at: List[int] = []
        term: List[bool] = []
        for i, j in zip(*np.nonzero(self._valive)):  # dead stays dead
            for row, t in zip(self._v_rows[i][j], self._v_term[i][j]):
                rows.append(row)
                at.append(i * vm + j)
                term.append(t)
        mf, manti, mall = self._match_rows(ctx, nps, tj, rows)
        # terminating victims never entered the PTS counts (~pterm gate)
        mf[np.asarray(term, bool)] = 0
        idx = np.asarray(at, np.int64)
        out_mfs = np.zeros((self.n * vm, same_key.shape[0]), np.int32)
        out_manti = np.zeros((self.n * vm, manti.shape[1]), np.int32)
        out_mall = np.zeros(self.n * vm, np.int32)
        np.add.at(out_mfs, idx, mf @ same_key.T)
        np.add.at(out_manti, idx, manti)
        np.add.at(out_mall, idx, mall)
        got = self._slot_memo[key] = (
            out_mfs.reshape(self.n, vm, -1),
            out_manti.reshape(self.n, vm, -1),
            out_mall.reshape(self.n, vm))
        return got

    def _match_rows(self, ctx, nps, tj, rows: List[Optional[Dict]]):
        """(mf [B, C], manti [B, TAA], mall [B]) for a list of pod label
        rows against the preemptor's template. Memoized per (template,
        row-object): claim/nominated lists only GROW across a wave, and
        the books hold each row dict for the planner's lifetime, so
        later preemptors re-match only the entries their predecessors'
        claims appended — not the whole accumulated list."""
        from ..ops.hoisted import match_matrices_np
        from ..ops.whatif import ipa_victim_matches_np

        C_n = nps["f_same_key"].shape[0]
        taa = nps["ipaaa_valid"].shape[0]
        B = len(rows)
        mf = np.zeros((B, C_n), np.int32)
        manti = np.zeros((B, taa), np.int32)
        mall = np.zeros(B, np.int32)
        if B == 0 or not _reads_pairs(nps):
            return mf, manti, mall
        view = id(ctx)  # a template index names a row of one view only
        miss = [
            b for b, r in enumerate(rows)
            if (view, tj, id(r)) not in self._match_memo
        ]
        if miss:
            miss_rows = [rows[b] for b in miss]
            mf_t, _ms_t = match_matrices_np(ctx.tp_np, miss_rows)
            mf_new = mf_t[tj].astype(np.int32)
            if ctx.dyn_ipa:
                manti_new, mall_new = ipa_victim_matches_np(nps, miss_rows)
            else:
                manti_new = np.zeros((len(miss), taa), np.int32)
                mall_new = np.zeros(len(miss), np.int32)
            for k, b in enumerate(miss):
                self._match_memo[(view, tj, id(rows[b]))] = (
                    mf_new[k], manti_new[k], mall_new[k])
        for b, r in enumerate(rows):
            mf[b], manti[b], mall[b] = self._match_memo[(view, tj, id(r))]
        return mf, manti, mall

    def _nom_take(self, ctx, nps, tj, prio, inp: _Inputs, same_key):
        """Take the nominated entries appended since `inp`'s last launch
        into its running totals: per-node aggregates of nominated pods
        with priority >= the preemptor's (framework.go:610's add set),
        as POSITIVE deltas, less those the view already holds. The
        entries only grow across a wave (claims append), so a wave of
        thousands of preemptors is not quadratic. Returns the new rows:
        (lanes [k], {field: [k, ...]})."""
        acc = inp.nom
        held = ctx.pod_keys or ()
        entries = [e for e in self._nom_entries[acc["done"]:]
                   if e[1] >= prio and e[4] not in held]
        acc["done"] = len(self._nom_entries)
        if not entries:
            return np.zeros(0, np.int64), {}
        mf, manti, mall = self._match_rows(
            ctx, nps, tj, [e[2] for e in entries])
        lane = self._enc_idx[[e[0] for e in entries]]
        ok = lane >= 0
        lane = lane[ok]
        rows = {
            "req": np.stack([e[3] for e in entries])[ok],
            "cnt": np.ones(lane.size, np.int64),
            "mfs": (mf @ same_key.T)[ok],
            "manti": manti[ok],
            "mall": mall[ok],
        }
        for k, r in rows.items():
            np.add.at(acc[k], lane, r)
        acc["n"] += len(entries)
        return lane, rows

    def _pre_take(self, ctx, nps, tj, inp: _Inputs, same_key):
        """Take the victims claimed since `inp`'s last launch into its
        running totals of drains, applied to every what-if state.
        Utilization is node-local; PTS/IPA counts drain at topology-PAIR
        granularity because a claimed victim on another node still
        empties this node's shared groups. Only the claimed victims the
        view still holds drain it. Returns the new drains — (lanes [k],
        req [k, R]) and the pair entries raw (c, col, val), anti (t,
        col, val), aff (col, val) — or None."""
        acc = inp.pre
        held = ctx.pod_keys
        claimed = [e for e in self._pre[acc["done"]:]
                   if held is None or e[4] in held]
        acc["done"] = len(self._pre)
        if not claimed:
            return None
        mf, manti, mall = self._match_rows(
            ctx, nps, tj, [e[1] for e in claimed])
        lane = np.array([e[0] for e in claimed], np.int64)
        req = np.stack([e[2] for e in claimed])
        np.add.at(acc["req"], lane, req)
        np.add.at(acc["cnt"], lane, 1)
        # terminating victims never entered the PTS counts
        live = ~np.array([e[3] for e in claimed], bool)
        cs = np.broadcast_to(np.arange(same_key.shape[0]), mf.shape)
        raw = (cs[live], nps["f_pair_cn"][lane][live], mf[live])
        np.add.at(acc["raw"], raw[:2], raw[2])
        anti = aff = None
        if ctx.dyn_ipa:
            pok = ctx.pok_np()
            anti_col = pok[lane[:, None], nps["ipaaa_key"][None, :]]
            anti_t = np.broadcast_to(np.arange(anti_col.shape[1]),
                                     anti_col.shape)
            anti = (anti_t, anti_col, manti)
            np.add.at(acc["anti"], anti[:2], manti)
            # a victim matching ALL of the preemptor's affinity terms
            # drains one count at each valid term's pair on its node
            aff_col = pok[lane[:, None], nps["ipaa_key"][None, :]]
            aff_val = ((mall != 0)[:, None]
                       & nps["ipaa_valid"][None, :].astype(bool))
            aff = (aff_col, aff_val.astype(np.int32))
            np.add.at(acc["aff"], aff_col, aff[1])
        return lane, req, raw, anti, aff

    # -- launch inputs: whole, or the delta since the last launch ----------

    def _full_reason(self, inp: _Inputs) -> Optional[str]:
        """Why this launch uploads its inputs whole, or None: it may
        send only what the claims since its last launch changed."""
        if not self.resident_inputs:
            return "off"
        if inp.x is None:
            return "fault" if inp.lost else "first"
        if not np.array_equal(inp.pdb_allowed, self._pdb_allowed):
            # a budget moved: every node's violating split may move
            return "pdb"
        return None

    def _slot_order(self, rows: np.ndarray, prio: int, held: np.ndarray,
                    L: int = 0):
        """Per-node reprieve slot order of planner rows `rows`:
        PDB-violating group first, then the rest, each in
        MoreImportantPod order (the oracle's :633-646 walk; the split is
        host PDB bookkeeping shared with the fast rung). Returns
        (slot_j, slot_valid, slot_vio) [len(rows), L] and L: unless
        given, the pow2 bucket of the most valid slots a row holds."""
        from ..ops.whatif import slot_bucket

        violating = self._pdb_violating(rows, prio)        # [k, Vmax]
        valid_ij = (self._valive[rows] & (self._vprio[rows] < prio)
                    & held[rows])
        js = self._vsort[rows]
        valid_sorted = np.take_along_axis(valid_ij, js, axis=1)
        vio_sorted = np.take_along_axis(violating, js, axis=1)
        if not L:
            L = slot_bucket(int(valid_sorted.sum(axis=1).max(initial=0)))
        order_key = np.where(
            ~valid_sorted, 2, np.where(vio_sorted, 0, 1)
        )
        perm = np.argsort(order_key, axis=1, kind="stable")
        Lp = min(L, js.shape[1])
        out = [np.take_along_axis(a, perm, axis=1)[:, :Lp]
               for a in (js, valid_sorted, vio_sorted)]
        if Lp < L:  # pad slots to the pow2 bucket
            out = [np.concatenate(
                [a, np.zeros((len(rows), L - Lp), a.dtype)], axis=1)
                for a in out]
        return (*out, L)

    def _victim_rows(self, ctx, nps, tj, rows, slot_j, slot_valid,
                     same_key) -> Dict[str, np.ndarray]:
        """Victim tensors of planner rows `rows` ([k, L, ...]): a slot
        aggregates its unit's members (per-member match rows summed;
        request row is the prebuilt unit sum; cnt carries the member
        count the kernel's pod-count filter releases/re-adds per
        slot)."""
        smfs, smanti, small = self._slot_matches(ctx, nps, tj, same_key)
        at = (rows[:, None], slot_j)
        sv = slot_valid
        return {
            "v_valid": sv,
            "v_cnt": np.where(sv, self._vsize[at], 0),
            "v_req": np.where(sv[..., None], self._v_enc_req[at], 0),
            "v_mfs": np.where(sv[..., None], smfs[at], 0),
            "v_manti": np.where(sv[..., None], smanti[at], 0),
            "v_mall": np.where(sv, small[at], 0),
        }

    def _full_inputs(self, ctx, nps, tj, prio, inp: _Inputs, same_key):
        """Every input of the launch in encoding-lane space, as host
        arrays, and an all-padding delta. Records on `inp` the slot
        order the epilogue reads. A key keeps its L: validity only
        falls within a wave."""
        from ..ops.whatif import pack_delta

        rows = np.arange(self.n)
        L = inp.L if self.resident_inputs else 0
        *slots, L = self._slot_order(rows, prio, self._slot_held(ctx), L)
        inp.L, (inp.slot_j, inp.slot_valid, inp.slot_vio) = L, slots
        lanes, Ncap = self._enc_idx, ctx.n_lanes
        x = {}
        for k, r in self._victim_rows(ctx, nps, tj, rows, inp.slot_j,
                                      inp.slot_valid, same_key).items():
            x[k] = np.zeros((Ncap,) + r.shape[1:], r.dtype)
            x[k][lanes] = r
        for k in ("req", "cnt", "mfs", "manti", "mall"):
            x["nom_" + k] = inp.nom[k]
        pre = inp.pre
        shared = (same_key @ pre["raw"]).astype(np.int32)
        anti, aff = pre["anti"].copy(), pre["aff"].copy()
        shared[:, 0] = anti[:, 0] = aff[0] = 0
        x.update(pre_req=pre["req"], pre_cnt=pre["cnt"], pre_shared=shared,
                 pre_anti=anti, pre_aff=aff, pre_atot=np.int32(aff.sum()))
        R, C, taa = self._enc_r, same_key.shape[0], anti.shape[0]
        return x, pack_delta({}, L, R, C, taa)

    def _delta(self, ctx, nps, tj, prio, inp: _Inputs, nom_new, pre_new,
               same_key):
        """What the claims since `inp`'s last launch changed, packed:
        the whole slot rows of the nodes they took victims from, the
        nominated rows they added, and the drains of their victims.
        Returns (delta, lanes it touches); raises ValueError when it
        outgrows the delta's fixed size."""
        from ..ops.whatif import pack_delta

        parts: Dict[str, np.ndarray] = {}
        rows = np.unique(np.asarray(self._claimed_at[inp.claims:], np.int64))
        if rows.size:
            slot_j, slot_valid, slot_vio, _ = self._slot_order(
                rows, prio, self._slot_held(ctx), inp.L)
            inp.slot_j[rows] = slot_j
            inp.slot_valid[rows] = slot_valid
            inp.slot_vio[rows] = slot_vio
            parts["v_lane"] = self._enc_idx[rows]
            parts.update(self._victim_rows(ctx, nps, tj, rows, slot_j,
                                           slot_valid, same_key))
        touched = [parts.get("v_lane", np.zeros(0, np.int64))]
        lane, rows_new = nom_new
        if lane.size:
            parts["nom_lane"], sums = _sum_by(lane, rows_new)
            parts.update({"nom_" + k: a for k, a in sums.items()})
            touched.append(parts["nom_lane"])
        if pre_new is not None:
            lane, req, raw, anti, aff = pre_new
            parts["pre_lane"], sums = _sum_by(
                lane, {"req": req, "cnt": np.ones(lane.size, np.int64)})
            parts["pre_req"], parts["pre_cnt"] = sums["req"], sums["cnt"]
            touched.append(parts["pre_lane"])
            # shared = same_key @ raw is linear: a raw entry (c, col, v)
            # adds v * same_key[:, c] at column col. Pair column 0 is
            # no pair: the launch zeroes it, so no entry lands there
            c, col, val = (a.ravel() for a in raw)
            parts["shared_col"], sums = _sum_by(
                col, {"v": same_key[:, c].T.astype(np.int64)
                      * val[:, None]}, keep=(val != 0) & (col != 0))
            parts["shared_val"] = sums["v"]
            if anti is not None:
                t, col, val = (a.ravel() for a in anti)
                code, sums = _sum_by(t * ctx.vnp + col, {"v": val},
                                     keep=(val != 0) & (col != 0))
                parts["anti_t"], parts["anti_col"] = divmod(code, ctx.vnp)
                parts["anti_val"] = sums["v"]
                col, val = (a.ravel() for a in aff)
                parts["aff_col"], sums = _sum_by(
                    col, {"v": val}, keep=(val != 0) & (col != 0))
                parts["aff_val"] = sums["v"]
                parts["atot"] = np.array([sums["v"].sum()])
        n_lanes = np.unique(np.concatenate(touched)).size
        delta = pack_delta(parts, inp.L, self._enc_r, same_key.shape[0],
                           nps["ipaaa_valid"].shape[0])
        return delta, n_lanes


def _reads_pairs(nps: Dict) -> bool:
    """Does the template read a topology-pair count: a hard spread class
    or a required (anti-)affinity term? Where it reads none, the match
    rows of pods against it are never looked at by the dry run, and are
    zero."""
    return bool(nps["f_valid"].any() or nps["ipaaa_valid"].any()
                or nps["ipaa_valid"].any())


def _sum_by(idx: np.ndarray, vals: Dict[str, np.ndarray],
            keep: Optional[np.ndarray] = None):
    """Rows of `vals` summed by index, over the entries `keep` marks
    (all by default): (unique indices, {field: sums})."""
    if keep is not None:
        idx = idx[keep]
        vals = {k: v[keep] for k, v in vals.items()}
    uniq, inv = np.unique(idx, return_inverse=True)
    out = {}
    for k, v in vals.items():
        out[k] = np.zeros((uniq.size,) + v.shape[1:], v.dtype)
        np.add.at(out[k], inv, v)
    return uniq, out
