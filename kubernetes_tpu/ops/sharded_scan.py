"""Sharded two-phase scheduling session: the pallas session's math over a
jax.sharding.Mesh, exact.

The single-launch pallas kernel (ops/pallas_scan.py) cannot span chips: a
Mosaic program owns one core's VMEM, and the per-pod loop needs GLOBAL
reductions each step (score normalization min/max over ALL nodes —
reference helper/normalize_score.go:24, framework/runtime/framework.go:757
— the PTS min-match, the cross-node argmax). Sharding those away silently
changes decisions. So the mesh path restructures each per-pod step into
the two-phase form (VERDICT r4 #2 / PERF_NOTES "Sharded pallas"):

  raw partials   — every shard computes masks/counts/scores over ITS node
                   slice only, from node-sharded carries (the pallas
                   session's node-space carry layout: requested/nzpc/
                   cnt_fn/cnt_sn, all [rows, N] — nothing pair-global);
  collectives    — the handful of cross-shard scalars ride named-axis
                   collectives over ICI (psum/pmax/pmin): the PTS filter's
                   per-constraint min-match, zone-presence (<=128-lane
                   vocab rows), n_scored/n_feasible, the four normalize
                   min/max pairs, the argmax (max score, then min global
                   lane among maxima = the first-max convention), and the
                   winner's pair-ids for the count updates;
  finish + apply — normalization and totals are shard-local elementwise;
                   the winning shard alone takes the carry updates (the
                   same off-shard no-op trick as the kernel's apply mode:
                   `hot` is all-zero off the winner).

The step body runs under shard_map inside ONE jit-compiled lax.scan per
batch — one device dispatch per batch, carries device-resident across
batches, exactly the session discipline of HoistedSession/PallasSession.
Decisions are BIT-IDENTICAL to the single-device PallasSession (same
int32 rescaled resources, f32 score math, first-max tie-break); parity is
pinned by tests/test_sharded_scan.py over fuzzed clusters on a virtual
8-device CPU mesh.

Statics and envelope come from the dense host remap (ops/dense_remap.py: the GCD
int32 rescale, per-template static rows, compact topology vocab): a shape
the pallas kernel rejects is rejected here with the same PallasUnsupported
reasons. Templates with affinity TERMS ride the sharded session too: the
D1-D5 ucnt carry is per-node (shards like everything else), kcnt holds
per-shard partial key totals psum'd at read, and the presence flags
(rowany) are a pmax.

Reference frame: pkg/scheduler/internal/parallelize/parallelism.go:27,56
(the 16-goroutine node chunking this replaces) and
framework/plugins/helper/normalize_score.go:24 (the global normalize that
must not be sharded away).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.partition import (
    SESSION_PARTITION_RULES,
    session_specs,
    shard_tree,
)
from ..parallel.sharded import NODE_AXIS
from .hoisted import template_fingerprint
from .kernel import MAX_NODE_SCORE
from .dense_remap import DenseRemap, _carry_delta_scan, batch_prologue
from .pallas_scan import (
    LANE,
    POS_BIG,
    SUB as SUB_IPA,
    PallasUnsupported,
    _ceil,
    ipa_normalize,
)

_CARRY_KEYS = ("requested", "nzpc", "cnt_fn", "cnt_sn")


def _doth(a, b, dims):
    """Exact-f32 dot (counts/pair-ids above 2^8 need HIGHEST) — the same
    convention as the pallas kernel's doth."""
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def _fit_row(cfg, statics, tables, carry, t):
    """NodeResourcesFit row for template t against `carry` (local, no
    collectives)."""
    (T, C, CP, R, SR, K, Npl, TCp, UR) = cfg[0]
    requested, nzpc = carry["requested"], carry["nzpc"]
    alloc = statics["alloc"]
    req_t = jax.lax.dynamic_index_in_dim(tables["req"], t, 0,
                                         keepdims=False)          # (R,)
    req_check = jax.lax.dynamic_index_in_dim(tables["req_check"], t, 0,
                                             keepdims=False)
    over = jnp.zeros((1, Npl), jnp.bool_)
    for r in range(R):
        free = alloc[r:r + 1, :] - requested[r:r + 1, :]
        over = over | ((req_t[r] > free) & (req_check[r] != 0))
    fail_dims = (tables["req_has_any"][t] != 0) & over
    fail_count = (nzpc[2:3, :] + jnp.int32(1)) > nzpc[3:4, :]
    return jnp.logical_not(fail_count | fail_dims)


def _resource_scores(cfg, statics, tables, carry, t):
    """(balanced, least) rows for template t against `carry` (local, no
    collectives)."""
    (T, C, CP, R, SR, K, Npl, TCp, UR) = cfg[0]
    f32 = jnp.float32
    nzpc = carry["nzpc"]
    alloc = statics["alloc"]
    nz_req = jax.lax.dynamic_index_in_dim(tables["nz_req"], t, 0,
                                          keepdims=False)         # (2,)
    nz_cpu = (nzpc[0:1, :] + nz_req[0]).astype(f32)
    nz_mem = (nzpc[1:2, :] + nz_req[1]).astype(f32)
    cap_cpu = alloc[0:1, :].astype(f32)
    cap_mem = alloc[1:2, :].astype(f32)
    frac_c = jnp.where(cap_cpu == 0, f32(1.0), nz_cpu / cap_cpu)
    frac_m = jnp.where(cap_mem == 0, f32(1.0), nz_mem / cap_mem)
    balanced = ((f32(1.0) - jnp.abs(frac_c - frac_m))
                * MAX_NODE_SCORE).astype(jnp.int32)
    balanced = jnp.where((frac_c >= 1) | (frac_m >= 1),
                         jnp.int32(0), balanced)

    def least_dim(cap, reqq):
        d = ((cap - reqq) * MAX_NODE_SCORE
             // jnp.where(cap == 0, jnp.int32(1), cap))
        return jnp.where((cap == 0) | (reqq > cap), jnp.int32(0), d)

    least = (least_dim(alloc[0:1, :], nzpc[0:1, :] + nz_req[0])
             + least_dim(alloc[1:2, :], nzpc[1:2, :] + nz_req[1])
             ) // jnp.int32(2)
    return balanced, least


def _eval_fn(cfg, statics, tables, carry, x):
    """Filter + score one pod against `carry` WITHOUT carry updates
    (local partials -> collectives -> finish -> cross-shard argmax).
    Mirrors ops/pallas_scan.py _build_kernel one_pod (mode="full")
    line for line; divergences are bugs."""
    (T, C, CP, R, SR, K, Npl, TCp, UR) = cfg[0]
    W = dict(cfg[1])
    f32 = jnp.float32
    t = x["tmpl"]
    shard = jax.lax.axis_index(NODE_AXIS)
    glane = shard * Npl + jnp.arange(Npl, dtype=jnp.int32)[None, :]  # (1,Npl)

    def psum(v):
        return jax.lax.psum(v, NODE_AXIS)

    def pmax(v):
        return jax.lax.pmax(v, NODE_AXIS)

    def pmin(v):
        return jax.lax.pmin(v, NODE_AXIS)

    nzpc = carry["nzpc"]
    cnt_fn, cnt_sn = carry["cnt_fn"], carry["cnt_sn"]
    alloc = statics["alloc"]
    valid_n = statics["valid_n"][0:1, :]
    stat3 = statics["stat"]                      # (T, SR, Npl)

    def trow(i):
        return jax.lax.dynamic_index_in_dim(stat3, t, 0,
                                            keepdims=False)[i:i + 1, :]

    static_mask = trow(0)
    raw_ipa = trow(1)
    cnt_taint = trow(2)
    cnt_nodeaff = trow(3)
    sc_image = trow(4)
    sc_avoid = trow(5)

    def tc8(a):
        """[T, C] table -> (CP, 1) column for template t."""
        row = jax.lax.dynamic_index_in_dim(a, t, 0, keepdims=False)  # (C,)
        return jnp.pad(row, (0, CP - C))[:, None]

    def block(a):
        """[TCp, Npl] -> this template's (CP, Npl) rows."""
        return jax.lax.dynamic_slice_in_dim(a, t * CP, CP, axis=0)

    # ---- NodeResourcesFit (exact int32 after the session's GCD rescale)
    mask_fit = _fit_row(cfg, statics, tables, carry, t)

    # ---- PTS filter: local shifted counts, GLOBAL per-constraint min
    cntf = block(cnt_fn).astype(f32)                              # (CP,Npl)
    sameM = jax.lax.dynamic_index_in_dim(
        tables["f_same"], t, 0, keepdims=False)                   # (CP,CP)
    sh = jax.lax.dot_general(
        sameM, cntf, (((1,), (0,)), ((), ())),
        preferred_element_type=f32,
        precision=jax.lax.Precision.HIGHEST)
    reg = block(statics["regrow_f"])
    big = f32(POS_BIG)
    min_c_l = jnp.min(jnp.where(reg != 0, sh, big), axis=1, keepdims=True)
    min_c = pmin(min_c_l)                      # -- collective 1 (CP,1)
    min_c = jnp.where(min_c == big, f32(0.0), min_c)
    cnt_n = jnp.where(reg != 0, sh, f32(0.0))
    konn = block(statics["konn_f"])
    vld = tc8(tables["f_valid"])
    selfm = tc8(tables["f_self_match"]).astype(f32)
    maxskew = tc8(tables["f_skew"]).astype(f32)
    fail_missing = (vld != 0) & (konn == 0)
    skew = cnt_n + selfm - min_c
    fail_skew = (vld != 0) & (konn != 0) & (skew > maxskew)
    fail_pts = jnp.any(fail_missing | fail_skew, axis=0, keepdims=True)

    # ---- InterPodAffinity: static parts + assumed-pod term carries
    # (the pallas kernel's D1-D5 machinery; ucnt is node-sharded, kcnt
    # holds PER-SHARD partial totals psum'd at read) ----
    if UR > 0:
        ucnt, kcnt = carry["ucnt"], carry["kcnt"]
        ucf = ucnt.astype(f32)                            # (UR, Npl)
        pos = (ucnt > 0).astype(f32)

        def t_row(a):                                     # [T?, UR] row t
            return jax.lax.dynamic_index_in_dim(a, t, 0, keepdims=True)

        def t_block(a):                                   # [T, SUB, *]
            return jax.lax.dynamic_index_in_dim(a, t, 0, keepdims=False)

        # D1: assumed pods' required anti terms repel this pod
        fail1 = _doth(t_row(tables["g1"]), pos,
                      (((1,), (0,)), ((), ()))) > 0       # (1, Npl)
        ipa2 = jax.lax.dynamic_index_in_dim(
            statics["ipa_stat"], t, 0, keepdims=False)    # (2, Npl)
        fe_static = ipa2[0:1, :]
        aff_allk = ipa2[1:2, :]
        # D2: assumed pods vs this pod's own anti terms
        anti_dyn = _doth(t_block(tables["wanti"]), ucf,
                         (((1,), (0,)), ((), ())))        # (SUB, Npl)
        a_stat = t_block(statics["anti_static"]).astype(f32)
        akonn = t_block(statics["anti_konn"])
        avld = jax.lax.dynamic_index_in_dim(
            tables["anti_valid"], t, 0, keepdims=False)[:, None]
        fail_anti = jnp.any(
            (avld != 0) & (akonn != 0) & ((a_stat + anti_dyn) > 0),
            axis=0, keepdims=True)                        # (1, Npl)
        # D3: assumed pods matching ALL of this pod's affinity terms
        aff_dyn = _doth(t_block(tables["waff"]), ucf,
                        (((1,), (0,)), ((), ())))
        f_stat = t_block(statics["aff_static"]).astype(f32)
        fvld = jax.lax.dynamic_index_in_dim(
            tables["aff_valid"], t, 0, keepdims=False)[:, None]
        pods_missing = jnp.any(
            (fvld != 0) & ((f_stat + aff_dyn) <= 0),
            axis=0, keepdims=True)
        kc0_g = psum(kcnt).astype(f32)   # -- collective: global totals
        at_dyn = jnp.sum(_doth(t_row(tables["w3tot"]), kc0_g,
                               (((1,), (0,)), ((), ()))))
        counts_empty = (tables["aff_total"][t].astype(f32) + at_dyn) == 0
        has_aff_t = tables["has_aff"][t]
        smatch = tables["self_match_all"][t]
        aff_ok = ((has_aff_t == 0)
                  | ((aff_allk != 0)
                     & (jnp.logical_not(pods_missing)
                        | (counts_empty & (smatch != 0)))))
        mask_ipa = (jnp.logical_not((fe_static != 0) | fail1)
                    & jnp.logical_not(fail_anti) & aff_ok)
    else:
        mask_ipa = jnp.ones((1, Npl), jnp.bool_)

    feasible = ((static_mask != 0) & mask_fit
                & jnp.logical_not(fail_pts) & mask_ipa & (valid_n != 0))
    n_feasible = psum(jnp.sum(feasible.astype(jnp.int32)))

    # ---- resource scores (local) ----
    balanced, least = _resource_scores(cfg, statics, tables, carry, t)

    # ---- PTS score: zone presence is a cross-shard OR ----
    shasall = jax.lax.dynamic_index_in_dim(
        statics["shasall"], t, 0, keepdims=True)                  # (1,Npl)
    scored = feasible & (shasall != 0)
    ignored = feasible & (shasall == 0)
    scored_f32 = scored.astype(f32)
    n_scored = psum(jnp.sum(scored_f32))       # -- collective 2 (scalars)
    zp = []
    zpn = []
    for k in range(K):
        cnt_z = jax.lax.dot_general(
            scored_f32, statics["onehot"][k], (((1,), (0,)), ((), ())),
            preferred_element_type=f32)                           # (1,VZ)
        p = (psum(cnt_z) > 0).astype(f32)      # -- collective 2 (VZ rows)
        zp.append(p)
        zpn.append(jax.lax.dot_general(
            p, statics["onehot"][k], (((1,), (1,)), ((), ())),
            preferred_element_type=f32))                          # (1,Npl)
    cnts = block(cnt_sn).astype(f32)
    sameS = jax.lax.dynamic_index_in_dim(
        tables["s_same"], t, 0, keepdims=False)
    sh_s = jax.lax.dot_general(
        sameS, cnts, (((1,), (0,)), ((), ())),
        preferred_element_type=f32,
        precision=jax.lax.Precision.HIGHEST)                      # (CP,Npl)
    vld_s = tc8(tables["s_valid"])
    perno = tc8(tables["s_perno"])
    key_s = tc8(tables["s_keyid"])
    first = tc8(tables["s_first"])
    sskew = tc8(tables["s_skew"]).astype(f32)
    have_s = (jnp.sum(vld_s) > 0).astype(jnp.int32)
    zval_l = block(statics["zvalid_s_rows"]).astype(f32)          # (CP,VZ)
    zval_n = block(statics["zvalid_node_s"])
    topo = jnp.zeros((CP, 1), f32)
    regn = jnp.zeros((CP, Npl), f32)
    for k in range(K):
        use = (jnp.logical_not(perno != 0) & (key_s == k)).astype(f32)
        topo = topo + use * jnp.sum(zp[k] * zval_l, axis=1, keepdims=True)
        regn = regn + use * zpn[k]
    regn = regn * (zval_n != 0)
    topo_size = jnp.where(first != 0, topo, f32(0.0))
    weight = jnp.log(jnp.where(perno != 0, n_scored, topo_size) + f32(2.0))
    cnt_n_s = jnp.where(perno != 0, sh_s,
                        jnp.where(regn > 0, sh_s, f32(0.0)))
    konn_s = block(statics["konn_s"])
    term = jnp.where((vld_s != 0) & (konn_s != 0),
                     cnt_n_s * weight + (sskew - f32(1.0)), f32(0.0))
    # same HIGHEST ones-dot reduction as the kernel (pallas_scan.py
    # raw): f32 accumulation order must match for bit-parity on TPU
    raw = jax.lax.dot_general(
        jnp.ones((1, CP), f32), term, (((1,), (0,)), ((), ())),
        preferred_element_type=f32,
        precision=jax.lax.Precision.HIGHEST)                      # (1,Npl)
    raw_i = raw.astype(jnp.int32)
    min_r = pmin(jnp.min(jnp.where(scored, raw_i, jnp.int32(POS_BIG))))
    max_r = pmax(jnp.max(jnp.where(scored, raw_i, jnp.int32(0))))
    min_r = jnp.where(min_r == POS_BIG, jnp.int32(0), min_r)
    norm = (MAX_NODE_SCORE * (max_r + min_r - raw_i)
            // jnp.where(max_r == 0, jnp.int32(1), max_r))
    norm = jnp.where(max_r == 0, jnp.int32(MAX_NODE_SCORE), norm)
    norm = jnp.where(ignored, jnp.int32(0), norm)
    sc_pts = jnp.where(have_s != 0, norm, jnp.int32(0))

    # ---- IPA score: static raw + assumed-pod terms (D4+D5) ----
    present = tables["ipa_present"][t] != 0
    if UR > 0:
        dyn45 = _doth(t_row(tables["w45"]), ucf, (((1,), (0,)), ((), ())))
        # w45 is GCD-scaled (pallas_scan._build_ipa); the int32 multiply
        # restores real weight magnitudes exactly — same convention as
        # the single-device kernel
        raw_ipa = raw_ipa + dyn45.astype(jnp.int32) * tables["w45_scale"]
        rowany = pmax(jnp.max(pos, axis=1, keepdims=True))  # (UR,1)
        pres_dyn = jnp.sum(_doth(t_row(tables["gpres"]), rowany,
                                 (((1,), (0,)), ((), ())))) > 0
        present = present | pres_dyn
    min_i = pmin(jnp.min(jnp.where(feasible, raw_ipa, jnp.int32(POS_BIG))))
    max_i = pmax(jnp.max(jnp.where(feasible, raw_ipa,
                                   jnp.int32(-POS_BIG))))
    ipa = ipa_normalize(raw_ipa - min_i, max_i - min_i)
    ipa = jnp.where(present, ipa, jnp.zeros((1, Npl), jnp.int32))

    # ---- default-normalized taint / node-affinity ----
    def norm_default(counts, reverse):
        mx = pmax(jnp.max(jnp.where(feasible, counts, jnp.int32(0))))
        scaled = (MAX_NODE_SCORE * counts
                  // jnp.where(mx == 0, jnp.int32(1), mx))
        if reverse:
            return jnp.where(mx == 0, jnp.int32(MAX_NODE_SCORE),
                             jnp.int32(MAX_NODE_SCORE) - scaled)
        return jnp.where(mx == 0, counts, scaled)

    sc_taint = norm_default(cnt_taint, True)
    sc_nodeaff = norm_default(cnt_nodeaff, False)

    total = (balanced * W["balanced"] + sc_image * W["image"]
             + ipa * W["ipa"] + least * W["least"]
             + sc_nodeaff * W["node_affinity"]
             + sc_avoid * W["prefer_avoid"]
             + sc_pts * W["pts"] + sc_taint * W["taint"])
    total = jnp.where(feasible, total, jnp.int32(-1))

    # ---- cross-shard first-max argmax -- collectives 3+4 ----
    tf = total.astype(f32)
    m = pmax(jnp.max(tf))
    cand = jnp.min(jnp.where(tf >= m, glane, jnp.int32(POS_BIG)))
    best = pmin(cand).astype(jnp.int32)
    ok = (m >= 0) & x["valid"]
    return dict(n_feasible=n_feasible, best=best, score=m, ok=ok)


def _commit_fn(cfg, statics, tables, carry, x, t, best, oki):
    """Winner-shard carry updates for one decided pod (hot == 0 on every
    other shard) — the apply side of the step."""
    (T, C, CP, R, SR, K, Npl, TCp, UR) = cfg[0]
    f32 = jnp.float32

    def psum(v):
        return jax.lax.psum(v, NODE_AXIS)

    shard = jax.lax.axis_index(NODE_AXIS)
    glane = shard * Npl + jnp.arange(Npl, dtype=jnp.int32)[None, :]
    requested, nzpc = carry["requested"], carry["nzpc"]
    cnt_fn, cnt_sn = carry["cnt_fn"], carry["cnt_sn"]
    stat3 = statics["stat"]
    req_t = jax.lax.dynamic_index_in_dim(tables["req"], t, 0,
                                         keepdims=False)
    nz_req = jax.lax.dynamic_index_in_dim(tables["nz_req"], t, 0,
                                          keepdims=False)
    okf = oki.astype(f32)
    hot = (glane == best).astype(jnp.int32) * oki                 # (1,Npl)
    hotf = hot.astype(f32)
    new_requested = requested
    for r in range(R):
        new_requested = new_requested.at[r:r + 1, :].add(hot * req_t[r])
    new_nzpc = nzpc.at[0:1, :].add(hot * nz_req[0])
    new_nzpc = new_nzpc.at[1:2, :].add(hot * nz_req[1])
    new_nzpc = new_nzpc.at[2:3, :].add(hot)

    mf_col = x["mf"][:, None].astype(f32)                         # (TCp,1)
    ms_col = x["ms"][:, None].astype(f32)
    pf = statics["prow_f"].astype(f32)                            # (TCp,Npl)
    # pair id at the winning node, shared across shards -- collective 5
    zb_f = psum(jax.lax.dot_general(
        pf, hotf, (((1,), (1,)), ((), ())),
        preferred_element_type=f32,
        precision=jax.lax.Precision.HIGHEST))                     # (TCp,1)
    m_f = ((pf == zb_f) & (statics["prow_f"] >= 0)).astype(f32) * okf
    ps_ = statics["prow_s"].astype(f32)
    zb_s = psum(jax.lax.dot_general(
        ps_, hotf, (((1,), (1,)), ((), ())),
        preferred_element_type=f32,
        precision=jax.lax.Precision.HIGHEST))
    m_s = ((ps_ == zb_s) & (statics["prow_s"] >= 0)).astype(f32) * okf

    # s_src factor at the winning node per template (stat row 7)
    src_all = stat3[:, 7, :].astype(f32)                          # (T,Npl)
    v_t = psum(jax.lax.dot_general(
        src_all, hotf, (((1,), (1,)), ((), ())),
        preferred_element_type=f32))                              # (T,1)
    # expand to (TCp,1): row t*CP+c gets v_t[t]
    v_rows = jnp.repeat(v_t, CP, axis=0)                          # (TCp,1)
    pernosel = tables["s_perno_rows"][:, None].astype(f32)        # (TCp,1)
    factor = pernosel + (f32(1.0) - pernosel) * v_rows

    new_cnt_fn = (cnt_fn.astype(f32) + mf_col * m_f).astype(jnp.int32)
    new_cnt_sn = (cnt_sn.astype(f32)
                  + ms_col * factor * m_s).astype(jnp.int32)

    new_carry = {
        "requested": new_requested, "nzpc": new_nzpc,
        "cnt_fn": new_cnt_fn, "cnt_sn": new_cnt_sn,
    }
    if UR > 0:
        # the assumed pod joins its node's topology groups for every IPA
        # key the node carries: same-pair mask from prow_ipa (-1 rows =
        # node lacks key -> no-op), written into template t's 8-row ucnt
        # block; kcnt accumulates the PER-SHARD key-presence totals
        # (nonzero only on the winner's shard — global totals psum at
        # read), mirroring the kernel's _apply_updates
        ucnt, kcnt = carry["ucnt"], carry["kcnt"]
        pi = statics["prow_ipa"].astype(f32)              # (SUB, Npl)
        zb_i = psum(_doth(pi, hotf, (((1,), (1,)), ((), ()))))  # (SUB,1)
        m_i = ((pi == zb_i)
               & (statics["prow_ipa"] >= 0)).astype(f32) * okf
        base_u = t * SUB_IPA
        ublock = jax.lax.dynamic_slice_in_dim(ucnt, base_u, SUB_IPA, 0)
        new_ucnt = jax.lax.dynamic_update_slice_in_dim(
            ucnt, (ublock.astype(f32) + m_i).astype(jnp.int32),
            base_u, 0)
        hask_l = _doth((pi >= 0).astype(f32), hotf,
                       (((1,), (1,)), ((), ())))          # (SUB, 1) local
        kblock = jax.lax.dynamic_slice_in_dim(kcnt, base_u, SUB_IPA, 0)
        new_kcnt = jax.lax.dynamic_update_slice_in_dim(
            kcnt, (kblock.astype(f32) + hask_l * okf).astype(jnp.int32),
            base_u, 0)
        new_carry["ucnt"] = new_ucnt
        new_carry["kcnt"] = new_kcnt
    return new_carry


def _step_fn(cfg, statics, tables, carry, x):
    """One pod through the two-phase step (runs per shard, inside
    shard_map): _eval_fn -> _commit_fn."""
    e = _eval_fn(cfg, statics, tables, carry, x)
    ok, best = e["ok"], e["best"]
    new_carry = _commit_fn(cfg, statics, tables, carry, x, x["tmpl"],
                           best, ok.astype(jnp.int32))
    y = {
        "best": jnp.where(ok, best, jnp.int32(-1)),
        "score": jnp.where(ok, e["score"].astype(jnp.int32),
                           jnp.int32(-1)),
        "n_feasible": e["n_feasible"],
    }
    return new_carry, y


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "mesh"),
    donate_argnames=("carry",),
)
def _sharded_scan(cfg, mesh, statics, tables, carry, xs):
    # placements are DECLARED, not wired: the same rule table that placed
    # the session state at build time (parallel/partition.py
    # SESSION_PARTITION_RULES) yields the shard_map in/out specs, so a
    # new carry or static either matches a rule or fails at trace time
    ys_spec = {"best": P(), "score": P(), "n_feasible": P()}
    statics_spec = session_specs("statics", statics)
    tables_spec = session_specs("tables", tables)
    carry_spec = session_specs("carry", carry)
    xs_spec = session_specs("xs", xs)

    def body(statics, tables, carry, xs):
        step = functools.partial(_step_fn, cfg, statics, tables)
        return jax.lax.scan(step, carry, xs)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(statics_spec, tables_spec, carry_spec, xs_spec),
        out_specs=(carry_spec, ys_spec), check_vma=False,
    )(statics, tables, carry, xs)


@functools.partial(
    jax.jit, donate_argnames=("statics", "delta", "carry"))
def _node_col_apply(statics, delta, carry, lane, cols):
    """Write one node lane's columns into the sharded session state in a
    single fused launch. The node-axis position of every leaf comes from
    the same rule table that placed it, so the scatter follows whatever
    sharding the rules declared."""
    out = {"statics": dict(statics), "delta": dict(delta),
           "carry": dict(carry)}
    for group, g_cols in cols.items():
        specs = session_specs(group, out[group])
        for k2, colv in g_cols.items():
            arr = out[group][k2]
            axis = list(specs[k2]).index(NODE_AXIS)
            out[group][k2] = jax.lax.dynamic_update_slice_in_dim(
                arr, jnp.asarray(colv).astype(arr.dtype), lane, axis=axis)
    return out["statics"], out["delta"], out["carry"]


class ShardedPallasSession:
    """Session API (schedule/decisions) over the two-phase sharded scan.

    Construction derives every static from DenseRemap (the
    envelope gates — GCD int32 rescale bounds, <=8 constraints, <=128
    topology values, f32-exact weights, the IPA term/key budgets — apply
    identically), then splits the node axis over the mesh. Affinity-TERM
    templates are supported: the D1-D5 ucnt carry is node-sharded like
    every other per-node count, and the two scalars that are genuinely
    global (the kcnt key-presence totals and the rowany presence flags)
    ride psum/pmax. Raises PallasUnsupported exactly where the pallas
    kernel would."""

    # KTPU_EXPLAIN demotes the mesh to the GSPMD hoisted session — the
    # two-phase scan's phase-A argmax discards the per-plugin sections
    # explain mode needs (same contract as PallasSession)
    supports_explain = False

    @staticmethod
    def explain_payload(ys):
        return None

    # ktpu: allow-sync(session build: host mirrors of shard planes built once at construction)
    def __init__(self, cluster: Dict, template_arrays_list: List[Dict],
                 weights: Optional[Dict[str, int]] = None,
                 mesh: Optional[Mesh] = None):
        assert mesh is not None, "ShardedPallasSession needs a mesh"
        if len(mesh.devices.ravel()) < 1:
            raise PallasUnsupported("empty mesh", reason="other")
        inner = DenseRemap(cluster, template_arrays_list, weights)
        self.mesh = mesh
        self.weights = inner.weights
        self._fps = inner._fps
        self._tp_np = inner._tp_np
        # session-delta interface (tpu_backend classification + apply):
        # same GCD-divisibility envelope and term-match gate as the
        # single-device pallas carry this mirrors
        self._gcd = inner._gcd
        self.dyn_ipa = inner.dyn_ipa
        self._term_np = inner._term_np
        # host mirror of the scaled alloc columns: apply_deltas re-checks
        # the CUMULATIVE int32 score headroom on node-alloc patches (the
        # same guard as PallasSession._patch_alloc_static)
        self._alloc = inner._alloc
        self.T, self.C, self.CP = inner.T, inner.C, inner.CP
        self.R, self.SR, self.K = inner.R, inner.SR, inner.K
        self.TCp = inner.TCp
        nsh = len(mesh.devices.ravel())
        Npl = _ceil(max(inner.Np // nsh, 1), LANE)
        while Npl * nsh < inner.Np:
            Npl += LANE
        self.Npl, self.Nps = Npl, Npl * nsh
        self.UR = inner._ipa["UR"] if inner._ipa is not None else 0
        self._cfg = (
            (self.T, self.C, self.CP, self.R, self.SR, self.K,
             Npl, self.TCp, self.UR),
            tuple(sorted(self.weights.items())),
        )

        def padn(a, axis, fill=0):
            a = np.asarray(a)
            pad = self.Nps - a.shape[axis]
            if pad == 0:
                return a
            widths = [(0, 0)] * a.ndim
            widths[axis] = (0, pad)
            return np.pad(a, widths, constant_values=fill)

        T, SR, TCp = self.T, self.SR, self.TCp
        statics = {
            "alloc": padn(inner._alloc, 1),
            # (T, SR, Nps): template-indexed static rows
            "stat": padn(inner._stat[:T * SR], 1).reshape(T, SR, self.Nps),
            "regrow_f": padn(inner._regrow_f, 1),
            "zvalid_node_s": padn(inner._zvalid_node_s, 1),
            "konn_f": padn(inner._konn_f, 1),
            "konn_s": padn(inner._konn_s, 1),
            "shasall": padn(inner._shasall[:T], 1),
            "valid_n": padn(inner._valid_n[0:1], 1),
            "prow_f": padn(inner._prow_f, 1, fill=-1),
            "prow_s": padn(inner._prow_s, 1, fill=-1),
            "onehot": padn(inner._onehot, 1),
            # replicated but grouped here for the step's block() reads
            "zvalid_s_rows": inner._zvalid_s,
        }
        tb = inner._sc_tables
        CP = self.CP

        def same_pad(a):  # [T, C, C] -> [T, CP, CP]
            out = np.zeros((T, CP, CP), np.float32)
            out[:, :self.C, :self.C] = a
            return out

        tables = {
            "req": inner._req_s,
            "req_check": inner._req_check_s,
            "req_has_any": inner._req_has_any_s,
            "nz_req": inner._nz_req_s,
            "f_valid": tb["f_valid"].astype(np.int32),
            "s_valid": tb["s_valid"].astype(np.int32),
            "f_skew": tb["f_skew"].astype(np.int32),
            "s_skew": tb["s_skew"].astype(np.int32),
            "f_self_match": tb["f_self_match"].astype(np.int32),
            "s_first": tb["s_first"].astype(np.int32),
            "s_perno": inner._s_perno.astype(np.int32),
            "s_keyid": inner._s_keyid,
            "f_same": same_pad(tb["f_same_key"]),
            "s_same": same_pad(tb["s_same_key"]),
            "ipa_present": tb["ipa_present"].astype(np.int32),
            "s_perno_rows": _perno_rows(inner._s_perno, T, self.C, CP),
        }
        if self.UR:
            # IPA term machinery (pallas _build_ipa products): node-axis
            # blocks reshaped template-major for the step's
            # dynamic_index reads; gate/weight matrices replicated
            ipa = inner._ipa
            S8, UR = SUB_IPA, self.UR
            statics["ipa_stat"] = padn(
                ipa["ipa_stat"][:2 * T], 1).reshape(T, 2, self.Nps)
            statics["anti_static"] = padn(
                ipa["anti_static"], 1).reshape(T, S8, self.Nps)
            statics["anti_konn"] = padn(
                ipa["anti_konn"], 1).reshape(T, S8, self.Nps)
            statics["aff_static"] = padn(
                ipa["aff_static"], 1).reshape(T, S8, self.Nps)
            statics["prow_ipa"] = padn(ipa["prow_ipa"], 1, fill=-1)
            tables["g1"] = ipa["g1"][:T]
            tables["wanti"] = ipa["wanti"].reshape(T, S8, UR)
            tables["waff"] = ipa["waff"].reshape(T, S8, UR)
            tables["w3tot"] = ipa["w3tot"][:T]
            tables["w45"] = ipa["w45"][:T]
            tables["w45_scale"] = np.int32(ipa["w45_scale"])
            tables["gpres"] = ipa["gpres"][:T]
            tables["has_aff"] = ipa["has_aff"].astype(np.int32)
            tables["self_match_all"] = ipa["self_match_all"].astype(np.int32)
            tables["aff_total"] = ipa["aff_total"].astype(np.int32)
            tables["anti_valid"] = ipa["anti_valid"].astype(np.int32)
            tables["aff_valid"] = ipa["aff_valid"].astype(np.int32)
        # session-delta statics (apply_deltas): the same-pair masks read
        # prow_f/prow_s (node-sharded statics); the cnt_sn factor needs
        # the row-expanded s_src (node-sharded) + perno flags
        delta_statics = {
            "src_rows": padn(inner._src_rows, 1),
            "perno_rows": inner._perno_rows,
        }
        carry0 = {
            "requested": padn(inner._requested0, 1),
            "nzpc": padn(inner._nzpc0, 1),
            "cnt_fn": padn(inner._cnt_fn0, 1),
            "cnt_sn": padn(inner._cnt_sn0, 1),
        }
        if self.UR:
            # session starts with zero ASSUMED pods (existing pods live
            # in the static tables); kcnt is PER-SHARD partial totals —
            # one column per shard, psum'd at read
            carry0["ucnt"] = np.zeros((self.UR, self.Nps), np.int32)
            carry0["kcnt"] = np.zeros((self.UR, nsh), np.int32)
        # device placement is DECLARED by the session rule table
        # (parallel/partition.py SESSION_PARTITION_RULES): node-sharded
        # leaves split over the mesh so collectives ride ICI, tables
        # replicate, and a leaf no rule covers fails construction loudly
        placed = shard_tree(
            {"statics": statics, "tables": tables,
             "delta": delta_statics, "carry": carry0},
            SESSION_PARTITION_RULES, mesh)
        self._statics = placed["statics"]
        self._tables = placed["tables"]
        self._delta_statics = placed["delta"]
        self._carry = placed["carry"]

        # ---- node-delta envelope (node_join_delta / node_leave_delta) --
        # Node add/remove stays a per-lane column write when NOTHING
        # cross-node can change: no assumed-term machinery (UR), no
        # existing-pod affinity terms, no image-locality scores (they
        # embed the global node count), and hostname-only score
        # topologies (zone one-hots embed a global value vocab). Within
        # that envelope a 1-node slice session reproduces the full
        # rebuild's column exactly (see node_join_delta).
        self._templates = list(template_arrays_list)
        f_valid_b = np.asarray(tb["f_valid"], bool)
        s_valid_b = np.asarray(tb["s_valid"], bool)
        rows_f = np.zeros(TCp, bool)
        rows_s = np.zeros(TCp, bool)
        for t in range(T):
            rows_f[t * CP:t * CP + self.C] = f_valid_b[t]
            rows_s[t * CP:t * CP + self.C] = s_valid_b[t]
        self._rows_f_valid, self._rows_s_valid = rows_f, rows_s
        # host mirrors of the sharded pair rows: the fresh-pair /
        # pair-distinct envelope checks run against these (kept in sync
        # by the node deltas themselves)
        self._prow_f_np = padn(inner._prow_f, 1, fill=-1)
        self._prow_s_np = padn(inner._prow_s, 1, fill=-1)
        cluster_terms = bool(
            np.asarray(cluster["at_valid"]).any()
            or np.asarray(cluster["st_valid"]).any())
        img_rows = inner._stat[:T * SR].reshape(T, SR, -1)[:, 4, :]
        self._node_delta_ok = (
            self.UR == 0 and not cluster_terms
            and not img_rows.any()
            and bool(np.all(inner._s_perno[s_valid_b])))

    def schedule(self, pod_arrays_list: List[Dict]) -> Dict:
        """Enqueue one batch (async); decisions(ys) blocks. KeyError on
        an unregistered template — the backend rebuilds, same contract as
        the other sessions."""
        B = len(pod_arrays_list)
        Bp, tmpl, mfa, msa = batch_prologue(
            self._fps, self._tp_np, pod_arrays_list, minimum=64)
        T, C, CP, TCp = self.T, self.C, self.CP, self.TCp
        mfx = np.zeros((Bp, TCp), np.float32)
        msx = np.zeros((Bp, TCp), np.float32)
        for t in range(T):
            mfx[:B, t * CP:t * CP + C] = mfa[t].reshape(B, C)
            msx[:B, t * CP:t * CP + C] = msa[t].reshape(B, C)
        xs = {
            "tmpl": jnp.asarray(tmpl),
            "valid": jnp.asarray(np.arange(Bp) < B),
            "mf": jnp.asarray(mfx),
            "ms": jnp.asarray(msx),
        }
        self._carry, ys = _sharded_scan(
            self._cfg, self.mesh, self._statics, self._tables,
            self._carry, xs)
        return {"best": ys["best"], "score": ys["score"],
                "n_feasible": ys["n_feasible"], "_b_real": B}

    @staticmethod
    # ktpu: allow-sync(harvest decode: host consumes batch verdicts after the launch completes)
    def decisions(ys: Dict) -> List[int]:
        best = np.asarray(ys["best"])
        return [int(v) for v in best[: ys["_b_real"]]]

    # -- incremental device-state deltas -----------------------------------

    # same GCD-divisibility / int32-headroom envelope as the pallas carry
    # this mirrors (self._gcd is the inner session's)
    delta_compatible = DenseRemap.delta_compatible

    def apply_deltas(self, deltas: List[Dict]) -> None:
        """Sharded face of the session-delta contract, extended with the
        node-axis deltas (node-join / node-leave): pod/alloc deltas batch
        through the fused _carry_delta_scan in runs, node deltas apply as
        per-lane column writes BETWEEN those runs — ordering matters,
        because a pod delta may reference a lane a node-join in the same
        flush introduced."""
        run: List[Dict] = []
        for d in deltas:
            if d["kind"] in ("node-join", "node-leave"):
                if run:
                    self._apply_pod_deltas(run)
                    run = []
                self._statics, self._delta_statics, self._carry = \
                    _node_col_apply(
                        self._statics, self._delta_statics, self._carry,
                        jnp.int32(d["lane"]), d["cols"])
            else:
                run.append(d)
        if run:
            self._apply_pod_deltas(run)

    def _apply_pod_deltas(self, deltas: List[Dict]) -> None:
        """Per-shard counts patch through the SAME fused
        _carry_delta_scan as HoistedSession.apply_deltas — the
        node-sharded carry and the sharded prow/src statics flow through
        GSPMD, so each shard updates only its node slice and the
        per-shard kcnt partials are untouched (batchable pods never
        enter the assumed-term counts)."""
        rp = int(self._carry["requested"].shape[0])
        rows = []
        for d in deltas:
            dres = np.zeros(rp, np.int32)
            dnzpc = np.zeros(SUB_IPA, np.int32)
            mf_rows = np.zeros(self.TCp, np.int32)
            ms_rows = np.zeros(self.TCp, np.int32)
            if d["kind"] == "node-alloc":
                scaled = (
                    np.asarray(d["dalloc"], np.int64) // self._gcd
                ).astype(np.int32)
                n = d["node"]
                col = self._alloc[: self.R, n].astype(np.int64) + scaled
                if int(np.abs(col).max(initial=0)) \
                        * (MAX_NODE_SCORE + 1) >= 2 ** 31:
                    # cumulative capacity bumps outgrew the int32 score
                    # headroom the build guaranteed: rebuild decides
                    raise ValueError(
                        "cumulative alloc patches exceed the int32 "
                        "score headroom")
                self._alloc[: self.R, n] += scaled
                self._statics["alloc"] = (
                    self._statics["alloc"].at[: self.R, n].add(
                        jnp.asarray(scaled))
                )
                dnzpc[3] = d["dallowed"]
            else:
                dres[: self.R] = (
                    np.asarray(d["dres"], np.int64) // self._gcd
                ).astype(np.int32)
                dnzpc[0] = int(d["dnz"][0]) // int(self._gcd[0])
                dnzpc[1] = int(d["dnz"][1]) // int(self._gcd[1])
                dnzpc[2] = d["dcount"]
                for t in range(self.T):
                    mf_rows[t * self.CP: t * self.CP + self.C] = d["mf"][t]
                    ms_rows[t * self.CP: t * self.CP + self.C] = d["ms"][t]
            rows.append((d["node"], dres, dnzpc, mf_rows, ms_rows))
        from .hoisted import batch_bucket

        ep = batch_bucket(len(rows), minimum=8)
        self.last_delta_shape = (len(rows), ep)
        xs = {
            "node": np.zeros(ep, np.int32),
            "dres": np.zeros((ep, rp), np.int32),
            "dnzpc": np.zeros((ep, SUB_IPA), np.int32),
            "mf": np.zeros((ep, self.TCp), np.int32),
            "ms": np.zeros((ep, self.TCp), np.int32),
        }
        for i, (n, dres, dnzpc, mf_rows, ms_rows) in enumerate(rows):
            xs["node"][i] = n
            xs["dres"][i] = dres
            xs["dnzpc"][i] = dnzpc
            xs["mf"][i] = mf_rows
            xs["ms"][i] = ms_rows
        self._carry = _carry_delta_scan(
            self._carry, self._statics["prow_f"], self._statics["prow_s"],
            self._delta_statics["src_rows"],
            self._delta_statics["perno_rows"],
            {k: jnp.asarray(v) for k, v in xs.items()},
        )

    # -- node-axis deltas --------------------------------------------------

    def _pair_rows_shared(self, pf: np.ndarray, ps: np.ndarray,
                          lane: int) -> bool:
        """True when any pair id in (pf, ps) also appears at ANOTHER lane
        of the same valid constraint row. A shared pair couples lanes
        through the registration rows (f_reg_real in the prologue): the
        node event would change columns other than `lane`, so it must go
        structural. Pair id 0 (node lacks the key) is exempt — konn==0
        gates those lanes dead for the row."""
        for rows_valid, col, mirror in (
                (self._rows_f_valid, pf, self._prow_f_np),
                (self._rows_s_valid, ps, self._prow_s_np)):
            hit = ((mirror == col[:, None]) & (col[:, None] > 0)
                   & rows_valid[:, None])
            hit[:, lane] = False
            if hit.any():
                return True
        return False

    def node_join_delta(self, slice_cluster: Dict,
                        lane: int) -> Optional[Dict]:
        """Column-write delta for a node ADD at `lane`, or None when the
        add falls outside the delta envelope (caller rebuilds).

        The column comes from a 1-node DenseRemap built on the node's
        own slice of the encoding (pod rows and term tables zeroed, see
        ClusterEncoding.node_slice_cluster). Inside the envelope —
        _node_delta_ok, fresh pair ids, a pod-free node — that slice's
        lane 0 IS what a full rebuild would put at `lane`: every
        surviving static is per-node, pair ids are global encoding vocab
        ids, and a fresh pair's registration equals the node's own
        eligibility. The alloc column is rescaled by the LIVE session's
        per-dimension GCD from the raw encoding values (the slice
        derives its own, coarser GCD)."""
        if not self._node_delta_ok or not (0 <= lane < self.Nps):
            return None
        try:
            s1 = DenseRemap(slice_cluster, self._templates, self.weights)
        except (PallasUnsupported, KeyError):
            return None
        T, SR, TCp = self.T, self.SR, self.TCp
        if (s1.T, s1.C, s1.CP, s1.SR, s1.R) != (
                T, self.C, self.CP, SR, self.R):
            return None
        raw = np.asarray(slice_cluster["alloc"], np.int64)[0]     # [R]
        if np.any(raw % self._gcd[: self.R]):
            return None
        scaled = raw // self._gcd[: self.R]
        if int(np.abs(scaled).max(initial=0)) * (MAX_NODE_SCORE + 1) \
                >= 2 ** 31:
            return None
        # a fresh node carries no pods: its utilization columns are zero
        # apart from the allowed-pods budget (nzpc row 3)
        if s1._requested0[:, 0].any() or s1._nzpc0[:3, 0].any():
            return None
        pf = s1._prow_f[: TCp, 0].copy()
        ps = s1._prow_s[: TCp, 0].copy()
        if int(max(pf.max(initial=0), ps.max(initial=0))) >= 2 ** 24:
            return None
        if self._pair_rows_shared(pf, ps, lane):
            return None
        stat_col = s1._stat[: T * SR].reshape(T, SR, -1)[:, :, 0]
        if stat_col[:, 1].any() or stat_col[:, 4].any():
            # the slice disagrees with the live envelope (terms / image
            # scores at the joining node) — structural
            return None
        alloc_col = np.zeros(self._alloc.shape[0], np.int32)
        alloc_col[: self.R] = scaled.astype(np.int32)
        cols = {
            "statics": {
                "alloc": alloc_col[:, None],
                "stat": stat_col[:, :, None],
                "regrow_f": s1._regrow_f[: TCp, 0:1],
                "konn_f": s1._konn_f[: TCp, 0:1],
                "konn_s": s1._konn_s[: TCp, 0:1],
                "shasall": s1._shasall[: T, 0:1],
                "valid_n": np.ones((1, 1), np.int32),
                "prow_f": pf[:, None],
                "prow_s": ps[:, None],
            },
            "delta": {"src_rows": s1._src_rows[: TCp, 0:1]},
            "carry": {
                "requested": np.zeros(
                    (int(self._carry["requested"].shape[0]), 1), np.int32),
                "nzpc": s1._nzpc0[:, 0:1],
                "cnt_fn": s1._cnt_fn0[: TCp, 0:1],
                "cnt_sn": s1._cnt_sn0[: TCp, 0:1],
            },
        }
        # host mirrors move at QUEUE time so later joins/leaves in the
        # same flush check against the post-queue state
        self._prow_f_np[:, lane] = pf
        self._prow_s_np[:, lane] = ps
        self._alloc[:, lane] = alloc_col
        return {"kind": "node-join", "lane": lane, "cols": cols}

    def node_leave_delta(self, lane: int) -> Optional[Dict]:
        """Column-clear delta for a node REMOVE at `lane` (the lane
        reverts to padding form: invalid, zero statics and counts, -1
        pair rows), or None outside the envelope. The caller guarantees
        the node hosts no pods; shared pair ids go structural for the
        same registration reason as joins."""
        if not self._node_delta_ok or not (0 <= lane < self.Nps):
            return None
        if self._pair_rows_shared(self._prow_f_np[:, lane],
                                  self._prow_s_np[:, lane], lane):
            return None
        T, SR, TCp = self.T, self.SR, self.TCp
        z = np.zeros((TCp, 1), np.int32)
        cols = {
            "statics": {
                "alloc": np.zeros((self._alloc.shape[0], 1), np.int32),
                "stat": np.zeros((T, SR, 1), np.int32),
                "regrow_f": z, "konn_f": z, "konn_s": z,
                "shasall": np.zeros((T, 1), np.int32),
                "valid_n": np.zeros((1, 1), np.int32),
                "prow_f": np.full((TCp, 1), -1, np.int32),
                "prow_s": np.full((TCp, 1), -1, np.int32),
            },
            "delta": {"src_rows": z},
            "carry": {
                "requested": np.zeros(
                    (int(self._carry["requested"].shape[0]), 1), np.int32),
                "nzpc": np.zeros(
                    (int(self._carry["nzpc"].shape[0]), 1), np.int32),
                "cnt_fn": z, "cnt_sn": z,
            },
        }
        self._prow_f_np[:, lane] = -1
        self._prow_s_np[:, lane] = -1
        self._alloc[:, lane] = 0
        return {"kind": "node-leave", "lane": lane, "cols": cols}


def _perno_rows(s_perno: np.ndarray, T: int, C: int, CP: int) -> np.ndarray:
    out = np.zeros(T * CP, np.float32)
    for t in range(T):
        out[t * CP:t * CP + C] = s_perno[t].astype(np.float32)
    return out
