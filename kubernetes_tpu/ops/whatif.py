"""What-if scan kernels: preemption victim search as one device launch.

The oracle dry-run (plugins/defaultpreemption.py selectVictimsOnNode,
reference default_preemption.go:592) runs the full filter chain once per
candidate node per victim add-back — O(candidates x victims) host filter
runs per preemptor, the last oracle-bound workload class in BENCH_CONFIGS
after PR 5's session deltas. This module re-expresses that dry run as ONE
fused device program per preemptor:

  * every candidate node's victim set arrives as a batch of INVERSE carry
    deltas (the PR-5 delta algebra run in reverse: a victim leaving node
    i moves exactly the node's utilization row, the PTS pair counts at
    node i's topology pairs, and the preemptor's own IPA term counts in
    node i's groups);
  * base feasibility ("all lower-priority victims removed",
    default_preemption.go:626) is evaluated for ALL nodes at once against
    a SCRATCH copy of the session carry — the live carry chain is never
    donated to, chained on, or invalidated;
  * the reprieve loop (:633 — victims added back highest-priority-first,
    the PDB-violating group first, while the preemptor still fits) runs
    as an in-launch lax.scan over victim slots, vectorized over every
    node: each step re-adds one slot's deltas and re-tests the exact
    filter set (fit, pod count, PodTopologySpread skew with the global
    min re-derived per node via a min/second-min decomposition,
    InterPodAffinity counts) — the sequential greedy the oracle runs,
    node-parallel because nodes' dry runs are independent;
  * nominated pods ride as POSITIVE deltas with the framework's two-pass
    semantics (framework.go:610: pass with them added AND without);
  * the inputs stay on the device for a wave: a launch donates them and
    returns them updated, so the planner uploads them whole once per
    view, template and priority, and then sends each launch one
    fixed-size delta of the lanes the claims since changed;
  * a WAVE launch plans up to WAVE_STEPS consecutive preemptors of one
    view, template and priority in one program: a scan whose step is
    the dry run, the pick (the candidate cut and the pick-one ladder)
    and the claim applied to the carried inputs, so each preemptor sees
    every earlier one's claim without a round trip to the host. The
    planner takes it only where a claim is lane-local (no PDB-covered,
    pair-matching or gang victims, no pair-matching preemptor).

Exactness domain: the preemptor may carry pod (anti-)affinity terms and
topology-spread constraints — the capability the numpy fast planner's
envelope must reject — because the session prologue already computes the
per-template IPA/PTS statics the adjustments are applied to. The planner
(scheduler/preemption_device.py) gates the envelope: no extenders, no
host ports or PVCs on the preemptor, and no existing/nominated pod whose
required anti-affinity term matches the preemptor (those terms are the
one filter input a victim EVICTION cannot express as a count decrement).

Parity is pinned three ways in tests/test_preemption_fast.py: device vs
numpy-fast vs oracle on the fast envelope, device vs oracle on the
affinity/spread extension.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import kernel as K
from .hoisted import (
    HoistedSession,
    _PORT_STEP_KEYS,
    _eval_reqs_batch_np,
    batch_bucket,
    template_fingerprint,
)
from .kernel import _CNT, _I64

# IPA term-table keys of ONE template the host victim-matcher reads
_TERM_SLICE_KEYS = tuple(
    f"{prefix}_{suffix}"
    for prefix in ("ipaaa", "ipaa")
    for suffix in ("op", "rkey", "pairs", "ns", "valid", "key")
)


def ipa_victim_matches_np(tt: Dict, rows_list: List[Dict]):
    """(manti [B, TAA], mall [B]) — does victim b match the preemptor's
    required anti-affinity term t / ALL of its required affinity terms
    (podMatchesAllAffinityTerms, filtering.go:357)? Host numpy twin of
    kernel._ipa_term_matches for a handful of victim rows; namespaces
    and term validity included."""
    B = len(rows_list)
    taa = tt["ipaaa_valid"].shape[0]
    ta = tt["ipaa_valid"].shape[0]
    manti = np.zeros((B, taa), np.int32)
    mall = np.zeros(B, np.int32)
    if B == 0:
        return manti, mall
    pp = np.stack([np.asarray(r["self_ppair"]) for r in rows_list]).astype(bool)
    pk = np.stack([np.asarray(r["self_pkey"]) for r in rows_list]).astype(bool)
    ns = np.asarray([int(np.asarray(r["self_ns"])) for r in rows_list])

    def fam(prefix, width):
        valid = tt[f"{prefix}_valid"].astype(bool)
        if not valid.any():
            return np.zeros((B, width), bool), valid
        m = _eval_reqs_batch_np(
            tt[f"{prefix}_op"], tt[f"{prefix}_rkey"], tt[f"{prefix}_pairs"],
            pp, pk,
        )  # [B, T]
        ns_tbl = tt[f"{prefix}_ns"]  # [T, X]
        ns_ok = (
            (ns_tbl[None, :, :] == ns[:, None, None]) & (ns_tbl[None, :, :] != 0)
        ).any(axis=-1)  # [B, T]
        return m & ns_ok & valid[None, :], valid

    m_anti, _ = fam("ipaaa", taa)
    manti = m_anti.astype(np.int32)
    m_aff, aff_valid = fam("ipaa", ta)
    if aff_valid.any():
        mall = np.all(
            np.where(aff_valid[None, :], m_aff, True), axis=1
        ).astype(np.int32)
    return manti, mall


# ---------------------------------------------------------------------------
# the fused what-if program

# A launch's inputs, by name: victim slots v_* [N, L, ...], nominated
# load nom_* [N, ...], claimed-victim drains pre_* (pair-level [.., Vnp])
INPUT_KEYS = (
    "v_valid", "v_cnt", "v_req", "v_mfs", "v_manti", "v_mall",
    "nom_req", "nom_cnt", "nom_mfs", "nom_manti", "nom_mall",
    "pre_req", "pre_cnt", "pre_shared", "pre_anti", "pre_aff", "pre_atot",
)

# A launch's delta: at most DELTA_LANES lanes per kind (whole victim-slot
# rows set; nominated and drained rows added) and DELTA_PAIRS topology-
# pair entries per kind; padding indexes past every axis and is dropped
DELTA_LANES = 8
DELTA_PAIRS = 32
_DROP = np.iinfo(np.int32).max


@functools.lru_cache(maxsize=64)
def _delta_layout(L: int, R: int, C: int, taa: int):
    """The flat int64 delta vector for inputs of L slots, R resources, C
    spread classes and TAA anti-affinity terms: {field: (offset,
    shape)}, in order, and the all-padding vector."""
    K, P = DELTA_LANES, DELTA_PAIRS
    fields, pad, off = {}, [], 0
    for name, shape in (
        ("v_lane", (K,)), ("v_valid", (K, L)), ("v_cnt", (K, L)),
        ("v_req", (K, L, R)), ("v_mfs", (K, L, C)),
        ("v_manti", (K, L, taa)), ("v_mall", (K, L)),
        ("nom_lane", (K,)), ("nom_req", (K, R)), ("nom_cnt", (K,)),
        ("nom_mfs", (K, C)), ("nom_manti", (K, taa)), ("nom_mall", (K,)),
        ("pre_lane", (K,)), ("pre_req", (K, R)), ("pre_cnt", (K,)),
        ("shared_col", (P,)), ("shared_val", (P, C)),
        ("anti_t", (P,)), ("anti_col", (P,)), ("anti_val", (P,)),
        ("aff_col", (P,)), ("aff_val", (P,)),
        ("atot", (1,)),
    ):
        size = math.prod(shape)
        fields[name] = (off, shape)
        index = name.endswith(("_lane", "_col"))
        pad.append(np.full(size, _DROP if index else 0, np.int64))
        off += size
    return fields, np.concatenate(pad)


def pack_delta(parts: Dict[str, np.ndarray], L: int, R: int, C: int,
               taa: int) -> np.ndarray:
    """The flat int64 delta vector of `parts` (each field's leading rows;
    absent fields and missing rows are padding). Raises ValueError when
    a field has more rows than its fixed size."""
    fields, pad = _delta_layout(L, R, C, taa)
    buf = pad.copy()
    for name, got in parts.items():
        off, shape = fields[name]
        flat = np.asarray(got, np.int64).ravel()
        if flat.size > math.prod(shape):
            raise ValueError(f"delta field {name} overflows")
        buf[off:off + flat.size] = flat
    return buf


def _apply_delta(x: Dict, delta) -> Dict:
    """The resident inputs with one packed delta applied: victim-slot
    rows set whole at their lanes, nominated and drained rows added,
    pair-level drains added at their columns. Padding indexes past the
    end and is dropped by the scatter."""
    L, R = x["v_req"].shape[1:]
    C, taa = x["v_mfs"].shape[2], x["v_manti"].shape[2]
    fields, _ = _delta_layout(L, R, C, taa)
    d = {name: delta[off:off + math.prod(shape)].reshape(shape)
         for name, (off, shape) in fields.items()}
    x = dict(x)

    def scatter(key, idx, val, how):
        ref = x[key].at[idx]
        val = val.astype(x[key].dtype)
        x[key] = (ref.set(val, mode="drop") if how == "set"
                  else ref.add(val, mode="drop"))

    v_lane = d["v_lane"].astype(jnp.int32)
    for k in ("v_valid", "v_cnt", "v_req", "v_mfs", "v_manti", "v_mall"):
        scatter(k, v_lane, d[k], "set")
    nom_lane = d["nom_lane"].astype(jnp.int32)
    for k in ("nom_req", "nom_cnt", "nom_mfs", "nom_manti", "nom_mall"):
        scatter(k, nom_lane, d[k], "add")
    pre_lane = d["pre_lane"].astype(jnp.int32)
    for k in ("pre_req", "pre_cnt"):
        scatter(k, pre_lane, d[k], "add")
    scatter("pre_shared", (slice(None), d["shared_col"].astype(jnp.int32)),
            d["shared_val"].T, "add")
    scatter("pre_anti", (d["anti_t"].astype(jnp.int32),
                         d["anti_col"].astype(jnp.int32)),
            d["anti_val"], "add")
    scatter("pre_aff", d["aff_col"].astype(jnp.int32), d["aff_val"], "add")
    x["pre_atot"] = x["pre_atot"] + d["atot"][0].astype(x["pre_atot"].dtype)
    return x


@functools.partial(
    jax.jit, static_argnames=("tj", "dyn_ipa", "dyn_ports", "has_nom"),
    donate_argnames=("x",),
)
def _whatif_run(S: Dict, c_static: Dict, carry: Dict, x: Dict, delta,
                tj: int = 0, dyn_ipa: bool = False, dyn_ports: bool = False,
                has_nom: bool = False):
    """One launch: the delta scattered into the device-resident inputs
    `x` (donated), then the dry run over them. Returns (results, the
    updated inputs) — the next launch of the same inputs donates those.
    A launch from freshly uploaded inputs passes an all-padding delta:
    one program serves both."""
    x = _apply_delta(x, delta)
    return _whatif_eval(S, c_static, carry, x, tj, dyn_ipa, dyn_ports,
                        has_nom), x


def _whatif_eval(S: Dict, c_static: Dict, carry: Dict, x: Dict, tj: int,
                 dyn_ipa: bool, dyn_ports: bool, has_nom: bool):
    """One preemptor's whole dry run: fits_now[N], base feasibility with
    every victim evicted, and the reprieve walk — one launch.

    Victim tensors are [N, L] slot-ordered PER NODE in the oracle's
    reprieve order (PDB-violating group first, then the rest, each by
    MoreImportantPod); pre_* are the already-claimed-victim aggregates
    (earlier waves / earlier pods of this wave) applied to EVERY state —
    pre_shared/pre_anti/pre_aff at topology-PAIR granularity because a
    claimed victim on another node still drains this node's shared
    groups. All adjustments are exact at the evaluated node, which is
    the only lane each node's verdict reads.

    A slot may hold a whole same-node GANG UNIT (gang-aware preemption:
    a gang's co-located members evict together or not at all): its
    req/mfs/manti/mall are the members' sums and v_cnt [N, L] carries
    the member count the pod-count filter must release/re-add per slot.
    Singleton slots pass v_cnt == v_valid, preserving the original
    per-pod arithmetic bit-for-bit."""
    (v_valid, v_cnt, v_req, v_mfs, v_manti, v_mall,
     nom_req, nom_cnt, nom_mfs, nom_manti, nom_mall,
     pre_req, pre_cnt, pre_shared, pre_anti, pre_aff, pre_atot) = (
        x[k] for k in INPUT_KEYS)

    def sel(key):
        return S[key][tj]

    req = sel("req")
    req_check = sel("req_check")
    req_has_any = sel("req_has_any")
    alloc = c_static["alloc"]
    allowed = c_static["allowed_pods"]
    free0 = alloc - carry["requested"] + pre_req          # [N, R]
    cnt0 = carry["pod_count"].astype(_I64) - pre_cnt      # [N]

    # -- eviction-invariant gate -------------------------------------------
    static_gate = sel("static_mask")
    if dyn_ports:
        static_gate = static_gate & K.ports_mask(
            carry["cp_any"], carry["cp_wild"], carry["cp_trip"],
            {k: sel(k) for k in _PORT_STEP_KEYS},
        )

    # -- IPA effective counts: prologue statics + session-assumed dynamics
    #    (the D1-D3 composition of ops/hoisted._eval_pod) + claimed-victim
    #    pair-level drains ---------------------------------------------------
    if dyn_ipa:
        u_cnt, k_cnt = carry["u_cnt"], carry["k_cnt"]
        pok, nk = c_static["pair_of_key"], c_static["nkey"]
        kaa = S["ipaaa_key"]                          # [U, TAA]
        cnt1 = jax.vmap(lambda uc, pv: uc[pv])(
            u_cnt, pok[:, kaa].transpose(1, 0, 2)
        )  # [U, N, TAA]
        g1 = S["M_anti"][:, :, tj]                    # [U, TAA]
        nk1 = nk[:, kaa].transpose(1, 0, 2)           # [U, N, TAA]
        fail_existing_dyn = jnp.any(
            g1[:, None, :] & nk1 & (cnt1 > 0), axis=(0, 2)
        )  # [N]
        g2 = S["M_anti"][tj].astype(_CNT)             # [TAA, U]
        w2 = g2 @ u_cnt                               # [TAA, Vnp]
        anti_key = sel("ipaaa_key")
        pair_nt = pok[:, anti_key]                    # [N, TAA]
        anti_dyn = jax.vmap(
            lambda wv, pv: wv[pv], in_axes=(0, 1), out_axes=1
        )(w2, pair_nt)                                # [N, TAA]
        g3 = S["match_all"][tj].astype(_CNT)          # [U]
        w3 = g3 @ u_cnt                               # [Vnp]
        aff_key = sel("ipaa_key")
        pair_na = pok[:, aff_key]                     # [N, Ta]
        aff_dyn = w3[pair_na]                         # [N, Ta]
        aff_total_dyn = jnp.sum(
            sel("ipaa_valid")[None, :] * g3[:, None] * k_cnt[:, aff_key]
        )
        anti_pre = jax.vmap(
            lambda vec, pv: vec[pv], in_axes=(0, 1), out_axes=1
        )(pre_anti, pair_nt)                          # [N, TAA]
        aff_pre = pre_aff[pair_na]                    # [N, Ta]
        anti_eff = sel("ipa_anti_cnt_n") + anti_dyn - anti_pre
        aff_eff = sel("ipa_aff_cnt_n") + aff_dyn - aff_pre
        aff_total_eff = sel("ipa_aff_total") + aff_total_dyn - pre_atot
        fail_exist = sel("ipa_fail_existing") | fail_existing_dyn
        anti_valid = sel("ipaaa_valid")
        anti_key_on = sel("ipa_anti_key_on_node")     # [N, TAA]
        aff_valid = sel("ipaa_valid")
        aff_key_on = nk[:, aff_key]                   # [N, Ta]
        aff_all_keys = sel("ipa_aff_all_keys")
        has_aff = sel("ipa_has_aff")
        self_match_all = sel("ipa_self_match_all")
        # one evicted matches-all victim on node n drains aff_total by
        # the number of its node's scattered term entries
        aff_keys_cnt = jnp.sum(
            aff_valid[None, :] & aff_key_on, axis=1
        ).astype(_CNT)                                # [N]
        static_gate = static_gate & ~fail_exist

    # -- PTS base: shared counts (claimed drains applied), min structure ----
    f_valid = sel("f_valid")
    any_f = jnp.any(f_valid)
    shared = jnp.sum(
        jnp.where(
            sel("f_same_key")[:, :, None], carry["f_cnt"][tj][None, :, :], 0
        ),
        axis=1,
    ) - pre_shared                                    # [C, Vnp]
    reg_real = sel("f_reg_real")                      # [C, Vnp]
    pair_cn = sel("f_pair_cn")                        # [N, C]
    self_m = sel("f_self_match")                      # [C]
    key_on_f = sel("f_key_on_node")                   # [N, C]
    fail_missing = jnp.any(f_valid[None, :] & ~key_on_f, axis=1)
    f_skew = sel("f_skew")
    big = jnp.iinfo(_CNT).max
    masked = jnp.where(reg_real, shared, big)
    min1 = jnp.min(masked, axis=1)                    # [C]
    cnt_min1 = jnp.sum(masked == min1[:, None], axis=1)
    min2 = jnp.min(jnp.where(masked == min1[:, None], big, masked), axis=1)
    shared_at = jnp.take_along_axis(shared.T, pair_cn, axis=0)   # [N, C]
    reg_at = jnp.take_along_axis(reg_real.T, pair_cn, axis=0)    # [N, C]
    # global min with this node's own pair EXCLUDED: re-enters adjusted
    min_excl = jnp.where(
        reg_at & (shared_at == min1[None, :]) & (cnt_min1[None, :] == 1),
        min2[None, :], min1[None, :],
    )                                                 # [N, C]

    def feas_one(ev, use_nom: bool):
        ev_req, ev_cnt, ev_mfs, ev_manti, ev_mall = ev
        # NodeResourcesFit + pod count (fit.go:230; victims freed, the
        # node's nominated pods added back — framework.go:610)
        freeN = free0 + ev_req
        cntN = cnt0 - ev_cnt
        if use_nom:
            freeN = freeN - nom_req
            cntN = cntN + nom_cnt
        over = (req[None, :] > freeN) & req_check[None, :]
        fit_ok = ~(
            (req_has_any & jnp.any(over, axis=1))
            | ((cntN + 1) > allowed)
        )
        # PodTopologySpread: counts at this node's pairs drop by the
        # evicted matches; the global min is re-derived with this
        # node's (only-modified) pair re-entered at its adjusted value
        delta = ev_mfs - (nom_mfs if use_nom else 0)  # [N, C]
        pair_adj = shared_at - delta
        cnt_eff = jnp.where(reg_at, pair_adj, 0)
        min_eff = jnp.where(
            reg_at, jnp.minimum(min_excl, pair_adj), min1[None, :]
        )
        min_eff = jnp.where(min_eff == big, 0, min_eff)
        skew = cnt_eff + self_m[None, :] - min_eff
        fail_skew = jnp.any(
            f_valid[None, :] & key_on_f & (skew > f_skew[None, :]), axis=1
        )
        pts_ok = ~(any_f & (fail_missing | fail_skew))
        ok = static_gate & fit_ok & pts_ok
        if dyn_ipa:
            anti_adj = anti_eff - jnp.where(anti_key_on, ev_manti, 0)
            aff_adj = aff_eff - jnp.where(aff_key_on, ev_mall[:, None], 0)
            tot_adj = aff_total_eff - ev_mall * aff_keys_cnt
            if use_nom:
                anti_adj = anti_adj + jnp.where(anti_key_on, nom_manti, 0)
                aff_adj = aff_adj + jnp.where(
                    aff_key_on, nom_mall[:, None], 0
                )
                tot_adj = tot_adj + nom_mall * aff_keys_cnt
            fail_anti = jnp.any(
                anti_valid[None, :] & anti_key_on & (anti_adj > 0), axis=1
            )
            pods_exist = jnp.all(
                jnp.where(aff_valid[None, :], aff_adj > 0, True), axis=1
            )
            aff_ok = ~has_aff | (
                aff_all_keys
                & (pods_exist | ((tot_adj == 0) & self_match_all))
            )
            ok = ok & ~fail_anti & aff_ok
        return ok

    def feas(ev):
        ok = feas_one(ev, False)
        if has_nom:
            ok = ok & feas_one(ev, True)
        return ok

    n = v_valid.shape[0]
    L = v_valid.shape[1]
    zero_ev = (
        jnp.zeros_like(free0), jnp.zeros(n, _I64),
        jnp.zeros_like(shared_at), jnp.zeros_like(v_manti[:, 0]),
        jnp.zeros(n, _CNT),
    )
    fits_now = feas(zero_ev)
    all_ev = (
        jnp.sum(v_req, axis=1),
        jnp.sum(v_cnt, axis=1).astype(_I64),
        jnp.sum(v_mfs, axis=1),
        jnp.sum(v_manti, axis=1),
        jnp.sum(v_mall, axis=1).astype(_CNT),
    )
    base = feas(all_ev)

    def reprieve(state, l):
        ev_req, ev_cnt, ev_mfs, ev_manti, ev_mall = state
        valid_l = v_valid[:, l]
        cand = (
            ev_req - v_req[:, l],
            ev_cnt - v_cnt[:, l].astype(_I64),
            ev_mfs - v_mfs[:, l],
            ev_manti - v_manti[:, l],
            ev_mall - v_mall[:, l].astype(_CNT),
        )
        reprieved = feas(cand) & valid_l
        take = reprieved
        state = tuple(
            jnp.where(
                take.reshape((n,) + (1,) * (old.ndim - 1)), new, old
            )
            for old, new in zip(state, cand)
        )
        return state, valid_l & ~reprieved

    _, victims = jax.lax.scan(reprieve, all_ev, jnp.arange(L))
    return {
        "fits_now": fits_now,
        "base": base,
        "victims": jnp.transpose(victims),  # [N, L]
    }


# preemptors a wave launch plans: one program for every run length, the
# steps past a run's end inert
WAVE_STEPS = 64


@functools.partial(
    jax.jit, static_argnames=("tj", "dyn_ipa", "dyn_ports"),
    donate_argnames=("x",),
)
def _whatif_wave_run(S: Dict, c_static: Dict, carry: Dict, x: Dict, delta,
                     wave: Dict, tj: int = 0, dyn_ipa: bool = False,
                     dyn_ports: bool = False):
    """One wave launch: the delta scattered into the resident inputs `x`
    (donated), then a scan over the steps of `wave`, one preemptor
    each, in plan order: _whatif_eval's dry run (always with the
    nominated pass: with no nominees the two passes agree), the pick
    (_wave_pick) and the claim applied to `x` at the picked lane only
    (_wave_claim). A step whose `active` is False leaves `x` as it is.
    Returns ({fits [T], pick [T] planner row or -1, victims [T, L] the
    picked slots}, the updated inputs).

    `wave` holds, per step, `active` [T] and `nom_req` [T, R] (the
    preemptor's request row, added as nominated load where it claims);
    for the launch, `lanes` [n] (planner row -> lane), `limit` (the
    candidate cut), `offset` (pickOneNodeForPreemption's
    PRIORITY_OFFSET) and the pick's per-slot tallies in planner rows
    [n, L]: `prio`, `priosum` and `latest` (exact integer ranks of the
    latest highest-priority start)."""
    x = _apply_delta(x, delta)
    L = x["v_valid"].shape[1]

    def plan(x, nom_row):
        ys = _whatif_eval(S, c_static, carry, x, tj, dyn_ipa, dyn_ports,
                          True)
        fits, row, take, picked = _wave_pick(ys, x, wave)
        x = _wave_claim(x, wave["lanes"][row], take, picked, nom_row)
        return x, (fits, jnp.where(take, row, -1).astype(jnp.int32), picked)

    def idle(x, nom_row):
        return x, (jnp.zeros((), bool), jnp.full((), -1, jnp.int32),
                   jnp.zeros(L, bool))

    def step(x, s):
        active, nom_row = s
        return jax.lax.cond(active, plan, idle, x, nom_row)

    x, (fits, pick, victims) = jax.lax.scan(
        step, x, (wave["active"], wave["nom_req"]))
    return {"fits": fits, "pick": pick, "victims": victims}, x


def _wave_pick(ys: Dict, x: Dict, wave: Dict):
    """The planner's per-preemptor epilogue on the device, in its order
    (preemption_device._plan_one_device): a preemptor that fits on some
    node claims nothing; otherwise the candidates are the first `limit`
    rows (planner order) that are feasible with every victim gone and
    hold a victim, each tallied over its victim mask, and the pick is
    FastPreemptionPlanner._pick_index's ladder as a lexicographic masked
    argmin in exact integers — fewest PDB violations (none exist where
    a wave launch runs), lowest highest victim priority, lowest
    priority sum + `offset` * victims, fewest victims, latest start of
    the highest-priority victims — the first row on the final tie.
    Returns (fits, row, take, picked slots [L])."""
    lanes = wave["lanes"]
    valid = x["v_valid"][lanes].astype(bool)                 # [n, L]
    fits = jnp.any(ys["fits_now"][lanes])
    feasible = ys["base"][lanes] & jnp.any(valid, axis=1)
    cand = feasible & (jnp.cumsum(feasible.astype(jnp.int32))
                       <= wave["limit"])
    vmask = ys["victims"][lanes] & valid
    n_vict = jnp.sum(jnp.where(vmask, x["v_cnt"][lanes], 0),
                     axis=1).astype(_I64)
    vprio = wave["prio"]
    low = jnp.iinfo(_I64).min
    max_prio = jnp.max(jnp.where(vmask, vprio, low), axis=1)
    sum_prio = jnp.sum(jnp.where(vmask, wave["priosum"], 0), axis=1)
    latest = jnp.max(jnp.where(vmask & (vprio == max_prio[:, None]),
                               wave["latest"], -1), axis=1).astype(_I64)
    best = cand & (n_vict > 0)
    top = jnp.iinfo(_I64).max
    for crit in (max_prio, sum_prio + wave["offset"] * n_vict, n_vict,
                 -latest):
        vals = jnp.where(best, crit, top)
        best = best & (vals == jnp.min(vals))
    row = jnp.argmax(best)
    take = ~fits & best[row]
    return fits, row, take, vmask[row] & take


def _wave_claim(x: Dict, lane, take, picked, nom_row) -> Dict:
    """A pick's claim applied to the inputs at its lane only: the picked
    slots leave (an invalid, zeroed slot is inert in the reprieve walk,
    and the rest of the row keeps its order), their requests and member
    counts become drains, and the preemptor becomes nominated load.
    With `take` False (`picked` then empty) nothing moves."""
    x = dict(x)
    freed_req = jnp.sum(jnp.where(picked[:, None], x["v_req"][lane], 0),
                        axis=0)
    freed_cnt = jnp.sum(jnp.where(picked, x["v_cnt"][lane], 0))
    for k in ("v_valid", "v_cnt", "v_req", "v_mfs", "v_manti", "v_mall"):
        old = x[k][lane]
        keep = ~picked.reshape(picked.shape + (1,) * (old.ndim - 1))
        x[k] = x[k].at[lane].set(jnp.where(keep, old, jnp.zeros_like(old)))
    x["pre_req"] = x["pre_req"].at[lane].add(freed_req)
    x["pre_cnt"] = x["pre_cnt"].at[lane].add(
        freed_cnt.astype(x["pre_cnt"].dtype))
    x["nom_req"] = x["nom_req"].at[lane].add(
        jnp.where(take, nom_row, 0).astype(x["nom_req"].dtype))
    x["nom_cnt"] = x["nom_cnt"].at[lane].add(take.astype(x["nom_cnt"].dtype))
    return x


@functools.partial(jax.jit, static_argnames=("tj", "dyn_ports"))
def _gang_fits_run(S: Dict, c_static: Dict, carry: Dict, k,
                   tj: int = 0, dyn_ports: bool = False):
    """Joint co-placement feasibility for k members of template tj as
    one positive-delta launch: per-node template MULTIPLICITY m_i = how
    many copies the node absorbs at once (min over checked dims of
    floor(free / req), capped by pod-count headroom, zeroed where the
    eviction-invariant static gate fails), feasible iff
    sum(min(m_i, k)) >= k.

    This is the gang-level upgrade of fits_now: k independent per-member
    fit checks all pass on a node with room for ONE member, yet the gang
    as a whole may not place — exactly the blind spot that lets two
    half-reserved gangs deadlock. Optimistic by design: affinity/spread
    couplings between the members themselves (and same-host-port
    members beyond the first) are not modeled, so False is definitive
    ("cannot place even ignoring inter-member constraints") while True
    means "capacity exists". The deadlock breaker wants exactly that
    polarity — it prefers backing off a gang whose demand provably
    exceeds the cluster."""

    def sel(key):
        return S[key][tj]

    req = sel("req")
    req_check = sel("req_check")
    free = c_static["alloc"] - carry["requested"]          # [N, R]
    headroom = (
        c_static["allowed_pods"] - carry["pod_count"].astype(_I64)
    )                                                      # [N]
    gate = sel("static_mask")
    if dyn_ports:
        gate = gate & K.ports_mask(
            carry["cp_any"], carry["cp_wild"], carry["cp_trip"],
            {p: sel(p) for p in _PORT_STEP_KEYS},
        )
    big = jnp.asarray(jnp.iinfo(_I64).max // 2, _I64)
    checked = req_check & (req > 0)
    per_dim = jnp.where(
        checked[None, :],
        jnp.floor_divide(free, jnp.where(checked, req, 1)[None, :])
        .astype(_I64),
        big,
    )                                                      # [N, R]
    m = jnp.minimum(jnp.min(per_dim, axis=1), headroom)    # [N]
    m = jnp.where(gate, jnp.maximum(m, 0), 0)
    return jnp.sum(jnp.minimum(m, k)) >= k


# ---------------------------------------------------------------------------
# context: the scratch snapshot the launches plan against


class WhatifUnavailable(RuntimeError):
    """The what-if path cannot serve this preemptor (template outside
    the session envelope, unencodable pod, node-table skew); the planner
    falls one rung to the numpy fast path or the oracle."""

    def __init__(self, message: str, reason: str = "context"):
        super().__init__(message)
        self.reason = reason


class WhatifContext:
    """One scratch what-if view of the cluster: session statics + a
    SCRATCH copy of the carry, plus the host-side numpy caches the
    per-preemptor tensor prep reads. Built from the live HoistedSession
    (zero uploads — the carry leaves are copied on-device, never
    donated) or from a non-donating encoding snapshot (the pallas /
    sharded sessions keep their carry in kernel-private scaled layouts;
    the host encoding is their exact state mirror after harvest, so the
    scratch hoisted view built from it scores the same cluster)."""

    def __init__(self, sess: HoistedSession, carry: Dict, node_names):
        self._sess = sess
        self.carry = carry
        self.node_names = list(node_names)
        self.n_lanes = int(carry["requested"].shape[0])
        self.fps = sess._fps
        self.dyn_ipa = sess._dyn_ipa
        self.dyn_ports = sess._dyn_ports
        self.tp_np = sess._tp_np  # match_matrices_np tables
        self._np_cache: Dict[int, Dict] = {}  # tj -> host-side slices
        # keys of the pods the view holds (the backend sets it; None:
        # unknown, a caller then takes the view as holding its books')
        self.pod_keys: Optional[frozenset] = None
        self.vnp = int(np.asarray(sess._c_static["npair"]).shape[1])
        self._pok_np: Optional[np.ndarray] = None

    @classmethod
    def from_session(cls, sess: HoistedSession, node_names) -> "WhatifContext":
        carry = {k: jnp.array(v, copy=True) for k, v in sess._carry.items()}
        return cls(sess, carry, node_names)

    @classmethod
    def from_host_snapshot(cls, host: Dict, node_names,
                           pod_arrays: Dict, mesh=None) -> "WhatifContext":
        """Throwaway single-template hoisted view over a host-array
        snapshot (ClusterEncoding.host_snapshot). The snapshot is
        already a consistent copy, so the EXPENSIVE part — the device
        upload and the prologue build — runs outside the encoding
        owner's lock. Never touches the encoder's cached device dict
        (no donation) and never counts as a session build. With `mesh`,
        the snapshot is node-sharded first (parallel/sharded
        shard_cluster) so the scratch view's statics and carry inherit
        the mesh placement through GSPMD — at 100k nodes an unsharded
        what-if copy would replicate the full cluster on every host."""
        if mesh is not None:
            from ..parallel.sharded import shard_cluster

            cluster = shard_cluster(
                {k: np.asarray(a) for k, a in host.items()}, mesh)
        else:
            cluster = {k: jnp.asarray(a) for k, a in host.items()}
        sess = HoistedSession(cluster, [pod_arrays])
        return cls(sess, sess._carry, node_names)

    @classmethod
    def from_encoding(cls, enc, pod_arrays: Dict) -> "WhatifContext":
        """from_host_snapshot over the encoding's current state (single-
        threaded callers: tests, the probe)."""
        return cls.from_host_snapshot(
            enc.host_snapshot(), enc.node_names, pod_arrays)

    # -- host-side per-template slices -------------------------------------

    def pok_np(self) -> np.ndarray:
        if self._pok_np is None:
            self._pok_np = np.asarray(self._sess._c_static["pair_of_key"])
        return self._pok_np

    def template_index(self, pod_arrays: Dict) -> int:
        fp = template_fingerprint(pod_arrays)
        tj = self.fps.get(fp)
        if tj is None:
            raise WhatifUnavailable(
                "preemptor template not in the what-if view",
                reason="template",
            )
        return tj

    def np_slices(self, tj: int) -> Dict:
        got = self._np_cache.get(tj)
        if got is not None:
            return got
        sess = self._sess
        out = {
            "f_same_key": np.asarray(sess._S["f_same_key"])[tj],
            "f_pair_cn": np.asarray(sess._S["f_pair_cn"])[tj],
            "f_valid": np.asarray(sess._S["f_valid"])[tj],
        }
        if self.dyn_ipa:
            for k in _TERM_SLICE_KEYS:
                out[k] = np.asarray(sess._tp[k])[tj]
        else:
            # term-free template: zero-width anti/aff tables
            out.update({
                "ipaaa_valid": np.zeros(1, bool),
                "ipaa_valid": np.zeros(1, bool),
                "ipaaa_key": np.zeros(1, np.int32),
                "ipaa_key": np.zeros(1, np.int32),
            })
        self._np_cache[tj] = out
        return out

    def run(self, tj: int, x: Dict, delta: np.ndarray, has_nom: bool):
        """Launch the fused what-if program; returns (results, inputs),
        device arrays (caller bounds the wait and decodes). `x` holds
        the INPUT_KEYS tensors shaped as _whatif_eval documents: numpy
        arrays (uploaded here) or the inputs a previous launch returned
        (donated: unusable afterwards, whatever the launch's outcome);
        `delta` is pack_delta's vector."""
        sess = self._sess
        # jnp.array, not asarray: the CPU backend would take an aligned
        # host buffer over without a copy, and the launch donates it
        x = {k: a if isinstance(a, jax.Array) else jnp.array(a)
             for k, a in x.items()}
        return _whatif_run(
            sess._S, sess._c_static, self.carry, x, delta,
            tj=tj, dyn_ipa=self.dyn_ipa, dyn_ports=self.dyn_ports,
            has_nom=has_nom,
        )

    def run_wave(self, tj: int, x: Dict, delta: np.ndarray, wave: Dict):
        """Launch the wave program (_whatif_wave_run) over up to
        WAVE_STEPS preemptors; `x` and `delta` as run() takes them,
        `wave` as the program documents. Returns (results, inputs)."""
        sess = self._sess
        x = {k: a if isinstance(a, jax.Array) else jnp.array(a)
             for k, a in x.items()}
        return _whatif_wave_run(
            sess._S, sess._c_static, self.carry, x, delta, wave,
            tj=tj, dyn_ipa=self.dyn_ipa, dyn_ports=self.dyn_ports,
        )

    def gang_fits(self, tj: int, k: int) -> bool:
        """Can k members of template tj co-place right now? One launch
        over the scratch carry (_gang_fits_run); optimistic on
        inter-member couplings — see the kernel docstring."""
        if k <= 1:
            k = 1
        out = _gang_fits_run(
            self._sess._S, self._sess._c_static, self.carry,
            jnp.asarray(k, _I64), tj=tj, dyn_ports=self.dyn_ports,
        )
        return bool(out)


def slot_bucket(n_slots: int) -> int:
    """Pow2 victim-slot bucket (min 4): every distinct L is a fresh XLA
    compile of the reprieve scan, and production victim counts are
    ragged."""
    return batch_bucket(max(n_slots, 1), minimum=4)
