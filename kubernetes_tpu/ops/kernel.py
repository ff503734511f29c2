"""The fused scheduling kernel: one XLA dispatch filters and scores every
node for one pending pod.

This replaces the reference's two hot loops — findNodesThatPassFilters
(reference: pkg/scheduler/core/generic_scheduler.go:235, 16 goroutines,
adaptive node subsampling at :177) and RunScorePlugins
(pkg/scheduler/framework/runtime/framework.go:723) — with dense masked
arithmetic over the ClusterEncoding matrices. No subsampling: every node is
evaluated, removing the 5-50% scoring compromise the Go implementation
makes at 5k-node scale.

Every plugin of the default profile (reference:
pkg/scheduler/algorithmprovider/registry.go:71 getDefaultConfig) is
reproduced bit-exactly; see the per-section docstrings for the formula
provenance. Scores are int64 in [0,100] x weight (interface.go:95).

Outputs (dict):
  feasible[N]    final filter mask
  total[N]       weighted sum of normalized scores (int64)
  mask_*/score_* per-plugin masks and weighted normalized scores for
                 status reconstruction and oracle parity tests
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from ..models.encoding import (
    EFFECT_NO_EXECUTE,
    EFFECT_NO_SCHEDULE,
    EFFECT_PREFER_NO_SCHEDULE,
    ST_PREFERRED_AFFINITY,
    ST_PREFERRED_ANTI,
    ST_REQUIRED_AFFINITY,
)
from .eval import eval_reqs, eval_reqs_single, ns_member

MAX_NODE_SCORE = 100
MB = 1024 * 1024
MIN_IMG_THRESHOLD = 23 * MB  # image_locality.go:33
MAX_CONTAINER_THRESHOLD = 1000 * MB

# Default-profile score plugin weights
# (reference: pkg/scheduler/algorithmprovider/registry.go:110-131)
DEFAULT_WEIGHTS = {
    "balanced": 1,
    "image": 1,
    "ipa": 1,
    "least": 1,
    "node_affinity": 1,
    "prefer_avoid": 10000,
    "pts": 2,
    "taint": 1,
}

_I64 = jnp.int64
_F64 = jnp.float64
# Counting dtype for the pod-table sweeps (PTS/IPA pair counts, match
# sums). int64 is EMULATED on the TPU vector unit — the four
# affinity/topology sections dominated the fused step at ~12.6ms of
# 14.1ms per pod before this. Counts are bounded by the pod-table size
# and weighted sums by 100*weight*terms, so int32 holds them exactly and
# score parity with the int64 oracle is preserved; section outputs are
# cast back to int64 at the [N]-sized boundary.
_CNT = jnp.int32


def _seg_sum(data, segment_ids, num_segments):
    return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)


def _seg_max_bool(flags, segment_ids, num_segments):
    return (
        jax.ops.segment_max(
            flags.astype(jnp.int32), segment_ids, num_segments=num_segments
        )
        > 0
    )


# ---------------------------------------------------------------------------
# Filters


def fit_mask(requested, pod_count, alloc, allowed_pods, req, req_check, req_has_any):
    """NodeResourcesFit (fit.go:230 fitsRequest): insufficient if
    request > allocatable − requested per checked dim, or pod count full.
    Shared by the generic kernel and the hoisted scan step."""
    free = alloc - requested
    over = (req[None, :] > free) & req_check[None, :]
    fail_dims = req_has_any & jnp.any(over, axis=1)
    fail_count = (pod_count.astype(_I64) + 1) > allowed_pods
    return ~(fail_count | fail_dims)


def ports_mask(pair_any, pair_wild, triple, p: Dict):
    """NodePorts conflict mask over the given port tables (reference:
    nodeports/node_ports.go HostPortInfo: a wildcard-ip want conflicts
    with any same (proto,port); a specific-ip want conflicts with a
    wildcard holder or the exact triple). Shared by the one-pod kernel
    (static cluster tables) and the hoisted scan step (carried tables) so
    the semantics cannot diverge."""
    pa = pair_any[:, p["want_pair"]] > 0     # [N, MP]
    pw = pair_wild[:, p["want_pair"]] > 0
    tr = triple[:, p["want_triple"]] > 0
    conflict = jnp.where(p["want_wild"][None, :], pa, pw | tr) & p["want_valid"][None, :]
    return ~jnp.any(conflict, axis=1)


def _filter_basics(c: Dict, p: Dict):
    """NodeName, NodeUnschedulable, TaintToleration, NodePorts,
    NodeResourcesFit masks. References: nodename/node_name.go,
    nodeunschedulable/node_unschedulable.go,
    tainttoleration/taint_toleration.go:55,
    nodeports/node_ports.go, noderesources/fit.go:230."""
    n = c["valid"].shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    mask_name = ~p["has_node_name"] | (idx == p["node_name_idx"])
    mask_unsched = ~(c["unschedulable"] & ~p["tolerates_unsched"])
    eff = c["taint_effect"][None, :]
    hard_taint = (eff == EFFECT_NO_SCHEDULE) | (eff == EFFECT_NO_EXECUTE)
    mask_taint = ~jnp.any(c["taints"] & hard_taint & ~p["tol_ns"][None, :], axis=1)
    mask_ports = ports_mask(
        c["ports_pair_any"], c["ports_pair_wild"], c["ports_triple"], p
    )
    mask_fit = fit_mask(
        c["requested"], c["pod_count"], c["alloc"], c["allowed_pods"],
        p["req"], p["req_check"], p["req_has_any"],
    )
    return mask_name, mask_unsched, mask_taint, mask_ports, mask_fit


def _node_match(c: Dict, p: Dict):
    """pod_matches_node_selector_and_affinity over all nodes (reference:
    pkg/scheduler/framework/plugins/helper/node_affinity.go:27). Shared by
    the NodeAffinity filter and both PodTopologySpread passes."""
    sel_ok = eval_reqs(
        p["nodesel_op"], p["nodesel_key"], p["nodesel_pairs"],
        c["npair"], c["nkey"],
        threshold=p["nodesel_thr"], num=c["nnum"], num_valid=c["nnum_valid"],
    )  # [N]
    term_ok = eval_reqs(
        p["aff_op"], p["aff_key"], p["aff_pairs"],
        c["npair"], c["nkey"],
        threshold=p["aff_thr"], num=c["nnum"], num_valid=c["nnum_valid"],
    )  # [N, T]
    aff_ok = jnp.any(term_ok & p["aff_valid"][None, :], axis=1)
    return sel_ok & jnp.where(p["has_node_affinity"], aff_ok, True)


def _pts_filter(c: Dict, p: Dict, node_match):
    """PodTopologySpread PreFilter+Filter (reference:
    pkg/scheduler/framework/plugins/podtopologyspread/filtering.go:224
    preFilter pair registration, :313 Filter skew check)."""
    n = c["valid"].shape[0]
    vnp = c["npair"].shape[1]
    valid_c = p["ptsf_valid"]  # [C]
    any_c = jnp.any(valid_c)
    key_c = p["ptsf_key"]
    pair_cn = c["pair_of_key"][:, key_c]  # [N, C] pair id of (key_c, value on node)
    has_all_keys = jnp.all(jnp.where(valid_c[None, :], c["nkey"][:, key_c], True), axis=1)
    eligible = node_match & has_all_keys & c["valid"]
    # registered topology pairs (filtering.go:224): eligible nodes only
    reg = jax.vmap(
        lambda pids: _seg_max_bool(eligible, jnp.where(eligible, pids, 0), vnp),
        in_axes=1,
    )(pair_cn)  # [C, Vnp]
    # pods matching each constraint's selector in the incoming pod's namespace
    match_pc = eval_reqs(p["ptsf_op"], p["ptsf_rkey"], p["ptsf_pairs"], c["ppair"], c["pkey"])
    match_pc = (
        match_pc
        & c["pvalid"][:, None]
        & ~c["pterm"][:, None]
        & (c["pns"] == p["self_ns"])[:, None]
    )  # [P, C]
    node_counts = jax.vmap(
        lambda m: _seg_sum(m.astype(_CNT), c["pnode"], n), in_axes=1
    )(match_pc)  # [C, N]
    count_pair = jax.vmap(
        lambda cnts, pids: _seg_sum(cnts, pids, vnp), in_axes=(0, 1)
    )(node_counts, pair_cn)  # [C, Vnp]
    # TpPairToMatchNum is ONE map keyed by (key, value): constraints sharing
    # a topology key accumulate into the same entries (filtering.go:246)
    same_key = (
        (key_c[:, None] == key_c[None, :]) & valid_c[:, None] & valid_c[None, :]
    )  # [C, C]
    shared_cnt = jnp.sum(
        jnp.where(same_key[:, :, None], count_pair[None, :, :], 0), axis=1
    )  # [C, Vnp]
    col = jnp.arange(vnp)[None, :]
    reg_real = reg & (col > 0)
    big = jnp.iinfo(_CNT).max
    min_c = jnp.min(jnp.where(reg_real, shared_cnt, big), axis=1)
    min_c = jnp.where(min_c == big, 0, min_c)  # no registered pairs -> 0
    self_match = eval_reqs_single(
        p["ptsf_op"], p["ptsf_rkey"], p["ptsf_pairs"], p["self_ppair"], p["self_pkey"]
    ).astype(_CNT)  # [C]
    cnt_n = jnp.take_along_axis(shared_cnt.T, pair_cn, axis=0)  # [N, C] counts at node pair
    reg_n = jnp.take_along_axis(reg_real.T, pair_cn, axis=0)
    cnt_n = jnp.where(reg_n, cnt_n, 0)
    key_on_node = c["nkey"][:, key_c]  # [N, C]
    fail_missing = jnp.any(valid_c[None, :] & ~key_on_node, axis=1)
    skew = cnt_n + self_match[None, :] - min_c[None, :]
    fail_skew = jnp.any(
        valid_c[None, :] & key_on_node & (skew > p["ptsf_skew"][None, :].astype(_CNT)),
        axis=1,
    )
    mask = ~(any_c & (fail_missing | fail_skew))
    # missing-key failures are UnschedulableAndUnresolvable (filtering.go:316)
    unresolvable = any_c & fail_missing
    return mask, unresolvable


def _ipa_term_matches(c: Dict, p: Dict, prefix: str):
    """Per-term match of every existing pod: selector + namespaces."""
    match_pt = eval_reqs(
        p[f"{prefix}_op"], p[f"{prefix}_rkey"], p[f"{prefix}_pairs"],
        c["ppair"], c["pkey"],
    )  # [P, T]
    return match_pt & ns_member(
        p[f"{prefix}_ns"][None, :, :], c["pns"][:, None, None]
    )


def _ipa_scatter_terms(c: Dict, match_pt, keys, valid):
    """Accumulate matches into the ONE (key,value)-keyed global map
    (topologyToMatchedTermCount is shared across terms, filtering.go:60)."""
    vnp = c["npair"].shape[1]
    pair_pt = c["pair_of_key"][c["pnode"][:, None], keys[None, :]]  # [P, T]
    m = match_pt & c["pvalid"][:, None] & valid[None, :]
    cnt = jax.vmap(
        lambda mm, pids: _seg_sum(mm.astype(_CNT), pids, vnp), in_axes=(1, 1)
    )(m, pair_pt)  # [T, Vnp]
    return jnp.sum(cnt, axis=0).at[0].set(0)  # [Vnp]


def _ipa_filter_parts(c: Dict, p: Dict) -> Dict:
    """Static pieces of the InterPodAffinity Filter for one incoming pod
    against the REAL pod/term tables. _ipa_filter composes them directly;
    the hoisted session (ops/hoisted.py) adds in-scan dynamic counts from
    session-assumed pods before composing, so the decomposition is the
    single source of truth for the filtering.go math."""
    # existing pods' required anti-affinity terms vs the incoming pod
    # (filtering.go:162 existing anti-affinity map)
    vnp = c["npair"].shape[1]
    match_at = (
        eval_reqs_single(c["at_op"], c["at_rkey"], c["at_pairs"], p["self_ppair"], p["self_pkey"])
        & ns_member(c["at_ns"], p["self_ns"])
        & c["at_valid"]
        & c["pvalid"][c["at_src"]]
    )  # [A]
    at_pair = c["pair_of_key"][c["pnode"][c["at_src"]], c["at_key"]]  # [A]
    existing_cnt = _seg_sum(match_at.astype(_CNT), at_pair, vnp)
    existing_cnt = existing_cnt.at[0].set(0)
    # gather per node LABEL (pair_of_key, ~K columns) instead of sweeping the
    # whole [N, Vnp] pair matrix: nodes carry few labels, Vnp is huge
    hit_per_key = (existing_cnt > 0)[c["pair_of_key"]] & c["nkey"]  # [N, K]
    fail_existing = jnp.any(hit_per_key, axis=1)

    # incoming required anti-affinity (filtering.go:341 satisfyPodAntiAffinity):
    # a pod matching ANY term contributes at that term's topology pair
    anti_valid = p["ipaaa_valid"]
    anti_vec = _ipa_scatter_terms(
        c, _ipa_term_matches(c, p, "ipaaa"), p["ipaaa_key"], anti_valid
    )
    pair_nt = c["pair_of_key"][:, p["ipaaa_key"]]  # [N, Taa]
    anti_key_on_node = c["nkey"][:, p["ipaaa_key"]]
    anti_cnt_n = anti_vec[pair_nt]  # [N, Taa]

    # incoming required affinity (filtering.go:357 satisfyPodAffinity): a pod
    # must match ALL terms to contribute (podMatchesAllAffinityTerms)
    aff_valid = p["ipaa_valid"]
    has_aff = jnp.any(aff_valid)
    match_all = jnp.all(
        jnp.where(aff_valid[None, :], _ipa_term_matches(c, p, "ipaa"), True), axis=1
    ) & has_aff  # [P]
    aff_vec = _ipa_scatter_terms(c, match_all[:, None], p["ipaa_key"], aff_valid)
    pair_na = c["pair_of_key"][:, p["ipaa_key"]]
    aff_cnt_n = aff_vec[pair_na]  # [N, Ta]
    key_aff = c["nkey"][:, p["ipaa_key"]]
    aff_all_keys = jnp.all(jnp.where(aff_valid[None, :], key_aff, True), axis=1)
    # first-pod-in-series escape hatch (filtering.go:357): the global map is
    # empty AND the incoming pod matches its own terms
    aff_total = jnp.sum(aff_vec)
    self_match_all = has_aff & jnp.all(
        jnp.where(
            aff_valid,
            eval_reqs_single(
                p["ipaa_op"], p["ipaa_rkey"], p["ipaa_pairs"],
                p["self_ppair"], p["self_pkey"],
            )
            & ns_member(p["ipaa_ns"], p["self_ns"]),
            True,
        )
    )
    return dict(
        fail_existing=fail_existing,
        anti_cnt_n=anti_cnt_n,
        anti_key_on_node=anti_key_on_node,
        aff_cnt_n=aff_cnt_n,
        aff_all_keys=aff_all_keys,
        aff_total=aff_total,
        self_match_all=self_match_all,
        has_aff=has_aff,
    )


def ipa_compose(p: Dict, parts: Dict, anti_dyn=0, aff_dyn=0, aff_total_dyn=0,
                fail_existing_dyn=False):
    """Compose the InterPodAffinity mask from static parts + dynamic
    in-scan count deltas (all deltas default to the pure-static case).
    anti_dyn/aff_dyn broadcast against [N, Taa]/[N, Ta]."""
    anti_valid = p["ipaaa_valid"]
    fail_anti = jnp.any(
        anti_valid[None, :]
        & parts["anti_key_on_node"]
        & ((parts["anti_cnt_n"] + anti_dyn) > 0),
        axis=1,
    )
    aff_valid = p["ipaa_valid"]
    pods_exist = jnp.all(
        jnp.where(aff_valid[None, :], (parts["aff_cnt_n"] + aff_dyn) > 0, True),
        axis=1,
    )
    counts_empty = (parts["aff_total"] + aff_total_dyn) == 0
    aff_ok = ~parts["has_aff"] | (
        parts["aff_all_keys"]
        & (pods_exist | (counts_empty & parts["self_match_all"]))
    )
    mask = ~(parts["fail_existing"] | fail_existing_dyn) & ~fail_anti & aff_ok
    unresolvable = ~aff_ok  # affinity miss is UnschedulableAndUnresolvable (:374)
    return mask, unresolvable


def _ipa_filter(c: Dict, p: Dict):
    """InterPodAffinity PreFilter+Filter (reference:
    pkg/scheduler/framework/plugins/interpodaffinity/filtering.go:162
    existing anti-affinity map, :194 incoming maps, :374 Filter)."""
    return ipa_compose(p, _ipa_filter_parts(c, p))


# ---------------------------------------------------------------------------
# Scores (each returns raw-normalized int64 in [0,100] BEFORE weighting)


def balanced_score(nz_requested, nz_req, alloc):
    """(1 - |cpuFraction - memFraction|) * 100, fractions over NonZero
    requested+pod (reference: noderesources/balanced_allocation.go:82,
    resource_allocation.go:91). Shared by kernel + hoisted step."""
    cpu_req = (nz_requested[:, 0] + nz_req[0]).astype(_F64)
    mem_req = (nz_requested[:, 1] + nz_req[1]).astype(_F64)
    cpu_cap = alloc[:, 0].astype(_F64)
    mem_cap = alloc[:, 1].astype(_F64)
    cpu_frac = jnp.where(cpu_cap == 0, 1.0, cpu_req / cpu_cap)
    mem_frac = jnp.where(mem_cap == 0, 1.0, mem_req / mem_cap)
    diff = jnp.abs(cpu_frac - mem_frac)
    score = ((1.0 - diff) * MAX_NODE_SCORE).astype(_I64)
    return jnp.where((cpu_frac >= 1) | (mem_frac >= 1), 0, score)


def least_allocated_score(nz_requested, nz_req, alloc):
    """leastResourceScorer with default cpu/mem weights 1/1 (reference:
    noderesources/least_allocated.go:93,:108). Shared by kernel +
    hoisted step."""

    def one(dim):
        cap = alloc[:, dim]
        req = nz_requested[:, dim] + nz_req[dim]
        s = (cap - req) * MAX_NODE_SCORE // jnp.where(cap == 0, 1, cap)
        return jnp.where((cap == 0) | (req > cap), 0, s)

    return (one(0) + one(1)) // 2


def _score_balanced(c: Dict, p: Dict):
    return balanced_score(c["nz_requested"], p["nz_req"], c["alloc"])


def _score_least(c: Dict, p: Dict):
    return least_allocated_score(c["nz_requested"], p["nz_req"], c["alloc"])


def _score_image(c: Dict, p: Dict):
    """ImageLocality (reference: imagelocality/image_locality.go:48 Score,
    :91 sumImageScores, :118 normalizedImageName)."""
    total = jnp.maximum(c["n_nodes"].astype(_F64), 1.0)
    sizes = c["img_size"][:, p["images"]]  # [N, MC]
    spread = c["img_nodes"][p["images"]].astype(_F64) / total  # [MC]
    contrib = (sizes.astype(_F64) * spread[None, :]).astype(_I64)
    sum_scores = jnp.sum(contrib, axis=1)
    max_threshold = MAX_CONTAINER_THRESHOLD * p["n_containers"].astype(_I64)
    sum_scores = jnp.clip(sum_scores, MIN_IMG_THRESHOLD, max_threshold)
    score = (
        MAX_NODE_SCORE * (sum_scores - MIN_IMG_THRESHOLD)
        // jnp.maximum(max_threshold - MIN_IMG_THRESHOLD, 1)
    )
    return jnp.where(p["n_containers"] == 0, 0, score)


def _score_prefer_avoid(c: Dict, p: Dict):
    """NodePreferAvoidPods (reference:
    nodepreferavoidpods/node_prefer_avoid_pods.go:58): 0 when the node's
    preferAvoidPods annotation names the pod's RC/RS controller."""
    avoided = c["avoid"][:, p["avoid_ctrl"]]
    return jnp.where(avoided, 0, MAX_NODE_SCORE).astype(_I64)


def _taint_count(c: Dict, p: Dict):
    """Untolerated PreferNoSchedule taints per node (pre-normalization)."""
    prefer = c["taint_effect"][None, :] == EFFECT_PREFER_NO_SCHEDULE
    return jnp.sum(c["taints"] & prefer & ~p["tol_prefer"][None, :], axis=1).astype(_I64)


def _score_taint(c: Dict, p: Dict, feasible):
    """TaintToleration: count untolerated PreferNoSchedule taints, then
    DefaultNormalizeScore reverse (reference:
    tainttoleration/taint_toleration.go:107, helper/normalize_score.go:26)."""
    return _normalize_default(_taint_count(c, p), feasible, reverse=True)


def _nodeaff_count(c: Dict, p: Dict):
    """Matched preferred-term weight sum per node (pre-normalization)."""
    match = eval_reqs(
        p["npref_op"], p["npref_key"], p["npref_pairs"],
        c["npair"], c["nkey"],
        threshold=p["npref_thr"], num=c["nnum"], num_valid=c["nnum_valid"],
    )  # [N, T]
    return jnp.sum(match.astype(_I64) * p["npref_weight"][None, :], axis=1)


def _score_node_affinity(c: Dict, p: Dict, feasible):
    """NodeAffinity Score: sum preferred-term weights whose preference
    matches, then DefaultNormalizeScore (reference:
    nodeaffinity/node_affinity.go:139)."""
    return _normalize_default(_nodeaff_count(c, p), feasible, reverse=False)


def _normalize_default(scores, feasible, reverse: bool):
    """DefaultNormalizeScore (reference: helper/normalize_score.go:26):
    scale by the max over the feasible set; reverse subtracts from 100."""
    max_count = jnp.max(jnp.where(feasible, scores, 0))
    scaled = MAX_NODE_SCORE * scores // jnp.where(max_count == 0, 1, max_count)
    if reverse:
        out = jnp.where(max_count == 0, MAX_NODE_SCORE, MAX_NODE_SCORE - scaled)
    else:
        out = jnp.where(max_count == 0, scores, scaled)
    return out


def _score_pts(c: Dict, p: Dict, node_match, feasible):
    """PodTopologySpread PreScore+Score+NormalizeScore (reference:
    podtopologyspread/scoring.go:221 preScore pair registration, :279
    topologyNormalizingWeight, :287 Score, :247 NormalizeScore)."""
    n = c["valid"].shape[0]
    vnp = c["npair"].shape[1]
    valid_c = p["ptss_valid"]
    any_c = jnp.any(valid_c)
    key_c = p["ptss_key"]
    hostname = p["ptss_hostname"]
    key_on_node = c["nkey"][:, key_c]  # [N, C]
    has_all = jnp.all(jnp.where(valid_c[None, :], key_on_node, True), axis=1)
    ignored = feasible & ~has_all  # scoring.go:233 ignored filtered nodes
    scored = feasible & has_all
    pair_cn = c["pair_of_key"][:, key_c]  # [N, C]
    # pair registration over filtered nodes (non-hostname constraints)
    reg = jax.vmap(
        lambda pids: _seg_max_bool(scored, jnp.where(scored, pids, 0), vnp),
        in_axes=1,
    )(pair_cn)  # [C, Vnp]
    col = jnp.arange(vnp)[None, :]
    reg_real = reg & (col > 0) & ~hostname[:, None] & valid_c[:, None]
    # duplicate-key constraints register no pairs of their own -> size 0
    # (pair_counts is one (key,value)-keyed map, scoring.go:221-240)
    topo_size = jnp.where(p["ptss_first"], jnp.sum(reg_real, axis=1), 0).astype(_F64)
    n_scored = jnp.sum(scored).astype(_F64)
    weight = jnp.log(jnp.where(hostname, n_scored, topo_size) + 2.0)  # [C]
    # pod counts per pair over ALL nodes passing nodeSelector/affinity+keys
    match_pc = eval_reqs(p["ptss_op"], p["ptss_rkey"], p["ptss_pairs"], c["ppair"], c["pkey"])
    match_pc = (
        match_pc
        & c["pvalid"][:, None]
        & ~c["pterm"][:, None]
        & (c["pns"] == p["self_ns"])[:, None]
    )  # [P, C]
    node_counts = jax.vmap(
        lambda m: _seg_sum(m.astype(_CNT), c["pnode"], n), in_axes=1
    )(match_pc)  # [C, N]
    src = node_match & has_all & c["valid"]  # scoring.go:252 count eligibility
    count_pair = jax.vmap(
        lambda cnts, pids: _seg_sum(cnts * src.astype(_CNT), pids, vnp),
        in_axes=(0, 1),
    )(node_counts, pair_cn)  # [C, Vnp]
    # one shared (key,value)-keyed map across same-key constraints
    same_key = (
        (key_c[:, None] == key_c[None, :]) & valid_c[:, None] & valid_c[None, :]
    )
    shared_cnt = jnp.sum(
        jnp.where(same_key[:, :, None], count_pair[None, :, :], 0), axis=1
    )  # [C, Vnp]
    cnt_n = jnp.take_along_axis(shared_cnt.T, pair_cn, axis=0)  # [N, C]
    reg_n = jnp.take_along_axis(reg_real.T, pair_cn, axis=0)
    cnt_n = jnp.where(reg_n, cnt_n, 0)
    cnt_n = jnp.where(hostname[None, :], node_counts.T, cnt_n)
    terms = jnp.where(
        valid_c[None, :] & key_on_node,
        cnt_n.astype(_F64) * weight[None, :]
        + (p["ptss_skew"][None, :].astype(_F64) - 1.0),
        0.0,
    )
    raw = jnp.sum(terms, axis=1).astype(_I64)  # int(score) truncation
    # NormalizeScore (scoring.go:247)
    big = jnp.iinfo(jnp.int64).max
    min_s = jnp.min(jnp.where(scored, raw, big))
    max_s = jnp.max(jnp.where(scored, raw, 0))
    min_s = jnp.where(min_s == big, 0, min_s)
    norm = MAX_NODE_SCORE * (max_s + min_s - raw) // jnp.where(max_s == 0, 1, max_s)
    norm = jnp.where(max_s == 0, MAX_NODE_SCORE, norm)
    norm = jnp.where(ignored, 0, norm)
    return jnp.where(any_c, norm, 0)


def _score_ipa(c: Dict, p: Dict, feasible):
    """InterPodAffinity PreScore+Score+NormalizeScore (reference:
    interpodaffinity/scoring.go:88 processExistingPod, :225 Score, :247
    NormalizeScore)."""
    raw, any_present = _score_ipa_raw(c, p)
    return _score_ipa_normalize(raw, any_present, feasible)


def _score_ipa_raw(c: Dict, p: Dict):
    """Per-node raw IPA score + whether any term matched (pre-normalize);
    independent of the feasible set."""
    vnp = c["npair"].shape[1]
    hard_w = c["hard_pod_affinity_weight"].astype(_CNT)
    # (a) incoming preferred terms vs existing pods
    match_pt = eval_reqs(p["ipap_op"], p["ipap_rkey"], p["ipap_pairs"], c["ppair"], c["pkey"])
    match_pt = (
        match_pt
        & c["pvalid"][:, None]
        & ns_member(p["ipap_ns"][None, :, :], c["pns"][:, None, None])
        & p["ipap_valid"][None, :]
    )  # [P, T]
    pair_pt = c["pair_of_key"][c["pnode"][:, None], p["ipap_key"][None, :]]
    cnt_t = jax.vmap(
        lambda m, pids: _seg_sum(m.astype(_CNT), pids, vnp), in_axes=(1, 1)
    )(match_pt, pair_pt)  # [T, Vnp]
    cnt_t = cnt_t.at[:, 0].set(0)
    score_vec = jnp.sum(cnt_t * p["ipap_weight"].astype(_CNT)[:, None], axis=0)  # [Vnp]
    present = jnp.any(cnt_t > 0, axis=0)
    # (b) existing pods' terms vs the incoming pod
    w_st = jnp.where(
        c["st_kind"] == ST_REQUIRED_AFFINITY,
        hard_w,
        jnp.where(
            c["st_kind"] == ST_PREFERRED_AFFINITY,
            c["st_weight"].astype(_CNT),
            -c["st_weight"].astype(_CNT),
        ),
    )
    match_st = (
        eval_reqs_single(c["st_op"], c["st_rkey"], c["st_pairs"], p["self_ppair"], p["self_pkey"])
        & ns_member(c["st_ns"], p["self_ns"])
        & c["st_valid"]
        & c["pvalid"][c["st_src"]]
        & ~((c["st_kind"] == ST_REQUIRED_AFFINITY) & (hard_w <= 0))
    )  # [S]
    st_pair = c["pair_of_key"][c["pnode"][c["st_src"]], c["st_key"]]
    score_vec = score_vec + _seg_sum(jnp.where(match_st, w_st, 0), st_pair, vnp)
    present = present | (_seg_sum(match_st.astype(_CNT), st_pair, vnp) > 0)
    present = present.at[0].set(False)
    score_vec = score_vec.at[0].set(0)
    # Score(): sum score_vec over the node's label pairs — gather per label
    # via pair_of_key ([N, K], K ~ label-key vocab) instead of the dense
    # [N, Vnp] sweep; pair id 0 (no label) contributes score_vec[0] == 0
    raw = jnp.sum(
        jnp.where(c["nkey"], score_vec[c["pair_of_key"]], 0), axis=1
    )
    return raw, jnp.any(present)


def _score_ipa_normalize(raw, any_present, feasible):
    big = jnp.iinfo(_CNT).max
    min_s = jnp.min(jnp.where(feasible, raw, big))
    max_s = jnp.max(jnp.where(feasible, raw, -big))
    diff = (max_s - min_s).astype(_F64)
    norm = jnp.where(
        diff > 0,
        (MAX_NODE_SCORE * ((raw - min_s).astype(_F64) / jnp.where(diff > 0, diff, 1.0))).astype(_I64),
        0,
    )
    return jnp.where(any_present, norm, 0)


# ---------------------------------------------------------------------------


def schedule_pod(c: Dict, p: Dict, weights: Dict[str, int] = None) -> Dict:
    """Filter + score every node for one pending pod. Pure; jit-friendly."""
    w = weights or DEFAULT_WEIGHTS
    mask_name, mask_unsched, mask_taint, mask_ports, mask_fit = _filter_basics(c, p)
    node_match = _node_match(c, p)
    mask_pts, pts_unresolvable = _pts_filter(c, p, node_match)
    mask_ipa, ipa_unresolvable = _ipa_filter(c, p)
    feasible = (
        c["valid"]
        & mask_name
        & mask_unsched
        & mask_taint
        & mask_ports
        & mask_fit
        & node_match
        & mask_pts
        & mask_ipa
    )
    out = {
        "feasible": feasible,
        "mask_name": mask_name,
        "mask_unsched": mask_unsched,
        "mask_taint": mask_taint,
        "mask_ports": mask_ports,
        "mask_fit": mask_fit,
        "mask_node_affinity": node_match,
        "mask_pts": mask_pts,
        "pts_unresolvable": pts_unresolvable,
        "mask_ipa": mask_ipa,
        "ipa_unresolvable": ipa_unresolvable,
    }
    scores = {
        "balanced": _score_balanced(c, p),
        "least": _score_least(c, p),
        "image": _score_image(c, p),
        "prefer_avoid": _score_prefer_avoid(c, p),
        "taint": _score_taint(c, p, feasible),
        "node_affinity": _score_node_affinity(c, p, feasible),
        "pts": _score_pts(c, p, node_match, feasible),
        "ipa": _score_ipa(c, p, feasible),
    }
    total = jnp.zeros_like(scores["balanced"])
    for name, s in scores.items():
        weighted = s * w[name]
        out[f"score_{name}"] = weighted
        total = total + weighted
    out["total"] = jnp.where(feasible, total, -1)
    return out


@functools.partial(jax.jit, static_argnames=("weights_key",))
def _jitted(c, p, weights_key):
    return schedule_pod(c, p, dict(weights_key))


def schedule_pod_jit(c: Dict, p: Dict, weights: Dict[str, int] = None) -> Dict:
    key = tuple(sorted((weights or DEFAULT_WEIGHTS).items()))
    return _jitted(c, p, key)


@functools.partial(jax.jit, static_argnames=("weights_key",))
def _jitted_vmapped(c, P, weights_key):
    return jax.vmap(lambda p: schedule_pod(c, p, dict(weights_key)))(P)


def schedule_pods_jit(c: Dict, P: Dict, weights: Dict[str, int] = None) -> Dict:
    """Batched independent evaluation: every pod in the stacked arrays P
    ([B, ...] rows) against the SAME cluster state — per-pod masks,
    scores and totals in one dispatch. This is the status-recovery path
    for preemption dry-runs (default_preemption.go:320 dryRunPreemption
    consumes per-node failure statuses): re-dispatching failed pods one
    at a time was a session teardown + a full kernel launch each; one
    vmapped launch amortizes all of it."""
    key = tuple(sorted((weights or DEFAULT_WEIGHTS).items()))
    return _jitted_vmapped(c, P, key)
