"""Section attribution for the hoisted session step: toggle sections off
and measure the scan slope (ms/pod) on the real chip.

Duplicates ops/hoisted.py _step with skip flags — a throwaway probe, not
product code; parity is irrelevant here, only cost structure.
"""
import os, sys, time
os.environ.setdefault("JAX_ENABLE_X64", "1")
import jax
jax.config.update("jax_enable_x64", True)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import copy
import functools
import numpy as np
import jax.numpy as jnp
from kubernetes_tpu.models.encoding import ClusterEncoding
from kubernetes_tpu.models.pod_encoder import PodEncoder
from kubernetes_tpu.ops import kernel as K
from kubernetes_tpu.ops import hoisted as H
from kubernetes_tpu.ops.kernel import _CNT, _F64, _I64, DEFAULT_WEIGHTS
from kubernetes_tpu.testing.synth import synth_cluster, synth_pending_pods

N = int(os.environ.get("BENCH_NODES", "5000"))


def make_step(skip):
    """_step clone; names in `skip` replace that section with a constant."""

    def step(S, c_static, weights, carry, x):
        tj = x["tmpl"]
        j = x["j"]
        n = c_static["valid"].shape[0]
        vnp = c_static["npair"].shape[1]
        col = jnp.arange(vnp)[None, :]
        sel = lambda key: S[key][tj]

        req = sel("req")
        if "fit" in skip:
            mask_fit = jnp.ones(n, bool)
        else:
            mask_fit = K.fit_mask(
                carry["requested"], carry["pod_count"], c_static["alloc"],
                c_static["allowed_pods"], req, sel("req_check"), sel("req_has_any"),
            )

        if "ptsf" in skip:
            mask_pts = jnp.ones(n, bool)
        else:
            f_valid = sel("f_valid")
            any_f = jnp.any(f_valid)
            cnt = carry["f_cnt"][tj]
            shared = jnp.sum(
                jnp.where(sel("f_same_key")[:, :, None], cnt[None, :, :], 0), axis=1
            )
            reg_real = sel("f_reg_real")
            big = jnp.iinfo(_CNT).max
            min_c = jnp.min(jnp.where(reg_real, shared, big), axis=1)
            min_c = jnp.where(min_c == big, 0, min_c)
            pair_cn = sel("f_pair_cn")
            cnt_n = jnp.take_along_axis(shared.T, pair_cn, axis=0)
            reg_n = jnp.take_along_axis(reg_real.T, pair_cn, axis=0)
            cnt_n = jnp.where(reg_n, cnt_n, 0)
            key_on_node = sel("f_key_on_node")
            fail_missing = jnp.any(f_valid[None, :] & ~key_on_node, axis=1)
            skew = cnt_n + sel("f_self_match")[None, :] - min_c[None, :]
            fail_skew = jnp.any(
                f_valid[None, :] & key_on_node & (skew > sel("f_skew")[None, :]),
                axis=1,
            )
            mask_pts = ~(any_f & (fail_missing | fail_skew))

        feasible = sel("static_mask") & mask_fit & mask_pts

        nz_req = sel("nz_req")
        if "res_scores" in skip:
            sc_balanced = jnp.zeros(n, _I64)
            sc_least = jnp.zeros(n, _I64)
        else:
            sc_balanced = K.balanced_score(
                carry["nz_requested"], nz_req, c_static["alloc"])
            sc_least = K.least_allocated_score(
                carry["nz_requested"], nz_req, c_static["alloc"])

        if "ptss" in skip:
            sc_pts = jnp.zeros(n, _I64)
        else:
            s_valid = sel("s_valid")
            any_s = jnp.any(s_valid)
            has_all = sel("s_has_all")
            hostname = sel("s_hostname")
            scored = feasible & has_all
            ignored = feasible & ~has_all
            pair_cn_s = sel("s_pair_cn")
            if "ptss_reg" in skip:
                reg_real_s = sel("f_reg_real") & (col > 0)  # wrong but cheap
            else:
                reg_s = jax.vmap(
                    lambda pids: K._seg_max_bool(
                        scored, jnp.where(scored, pids, 0), vnp),
                    in_axes=1,
                )(pair_cn_s)
                reg_real_s = reg_s & (col > 0) & ~hostname[:, None] & s_valid[:, None]
            topo_size = jnp.where(
                sel("s_first"), jnp.sum(reg_real_s, axis=1), 0).astype(_F64)
            n_scored = jnp.sum(scored).astype(_F64)
            weight = jnp.log(jnp.where(hostname, n_scored, topo_size) + 2.0)
            shared_s = jnp.sum(
                jnp.where(sel("s_same_key")[:, :, None],
                          carry["s_cnt"][tj][None, :, :], 0),
                axis=1,
            )
            cnt_n_s = jnp.take_along_axis(shared_s.T, pair_cn_s, axis=0)
            reg_n_s = jnp.take_along_axis(reg_real_s.T, pair_cn_s, axis=0)
            cnt_n_s = jnp.where(reg_n_s, cnt_n_s, 0)
            cnt_n_s = jnp.where(hostname[None, :], carry["h_cnt"][tj].T, cnt_n_s)
            terms = jnp.where(
                s_valid[None, :] & sel("s_key_on_node"),
                cnt_n_s.astype(_F64) * weight[None, :]
                + (sel("s_skew")[None, :].astype(_F64) - 1.0),
                0.0,
            )
            raw = jnp.sum(terms, axis=1).astype(_I64)
            big64 = jnp.iinfo(jnp.int64).max
            min_r = jnp.min(jnp.where(scored, raw, big64))
            max_r = jnp.max(jnp.where(scored, raw, 0))
            min_r = jnp.where(min_r == big64, 0, min_r)
            norm = K.MAX_NODE_SCORE * (max_r + min_r - raw) // jnp.where(
                max_r == 0, 1, max_r)
            norm = jnp.where(max_r == 0, K.MAX_NODE_SCORE, norm)
            norm = jnp.where(ignored, 0, norm)
            sc_pts = jnp.where(any_s, norm, 0)

        if "norms" in skip:
            sc_ipa = jnp.zeros(n, _I64)
            sc_taint = jnp.zeros(n, _I64)
            sc_nodeaff = jnp.zeros(n, _I64)
        else:
            sc_ipa = K._score_ipa_normalize(
                sel("raw_ipa"), sel("ipa_present"), feasible)
            sc_taint = K._normalize_default(
                sel("cnt_taint"), feasible, reverse=True)
            sc_nodeaff = K._normalize_default(
                sel("cnt_nodeaff"), feasible, reverse=False)

        total = (
            sc_balanced * DEFAULT_WEIGHTS["balanced"]
            + sel("sc_image") * DEFAULT_WEIGHTS["image"]
            + sc_ipa * DEFAULT_WEIGHTS["ipa"]
            + sc_least * DEFAULT_WEIGHTS["least"]
            + sc_nodeaff * DEFAULT_WEIGHTS["node_affinity"]
            + sel("sc_avoid") * DEFAULT_WEIGHTS["prefer_avoid"]
            + sc_pts * DEFAULT_WEIGHTS["pts"]
            + sc_taint * DEFAULT_WEIGHTS["taint"]
        )
        total = jnp.where(feasible, total, -1)
        best = jnp.argmax(total).astype(jnp.int32)
        ok = (total[best] >= 0) & x["valid"]
        add64 = ok.astype(_I64)
        addc = ok.astype(_CNT)
        carry = dict(carry)
        if "carry_util" not in skip:
            carry["requested"] = carry["requested"].at[best].add(req * add64)
            carry["nz_requested"] = carry["nz_requested"].at[best].add(nz_req * add64)
            carry["pod_count"] = carry["pod_count"].at[best].add(ok.astype(jnp.int32))
        if "carry_cnt" not in skip:
            t_n = S["f_pair_cn"].shape[0]
            c_n = S["f_pair_cn"].shape[2]
            t_idx = jnp.arange(t_n)[:, None]
            c_idx = jnp.arange(c_n)[None, :]
            mf = S["Mf"][:, j, :] * addc
            ms = S["Ms"][:, j, :] * addc
            pair_b_f = S["f_pair_cn"][:, best, :]
            pair_b_s = S["s_pair_cn"][:, best, :]
            src_b = S["s_src"][:, best]
            carry["f_cnt"] = carry["f_cnt"].at[t_idx, c_idx, pair_b_f].add(mf)
            carry["s_cnt"] = carry["s_cnt"].at[t_idx, c_idx, pair_b_s].add(
                ms * src_b[:, None].astype(_CNT))
            carry["h_cnt"] = carry["h_cnt"].at[:, :, best].add(ms)
        y = {"best": jnp.where(ok, best, -1),
             "score": jnp.where(ok, total[best], -1),
             "n_feasible": jnp.sum(feasible.astype(jnp.int32))}
        return carry, y

    return step


def main():
    nodes, init_pods = synth_cluster(N, pods_per_node=2)
    pending = synth_pending_pods(600, spread=True)
    phantoms = []
    for i, p in enumerate(pending):
        q = copy.deepcopy(p); q.metadata.name = f"ph-{i}"
        q.spec.node_name = nodes[i % len(nodes)].metadata.name
        phantoms.append(q)
    enc = ClusterEncoding(); enc.set_cluster(nodes, init_pods + phantoms)
    pe = PodEncoder(enc)
    for p in pending: pe.encode(p)
    enc.device_state()
    for q in phantoms: enc.remove_pod(q)
    arrays = [{k: v for k, v in pe.encode(p).items() if not k.startswith("_")}
              for p in pending]
    c = enc.device_state()
    templates, seen = [], set()
    for a in arrays:
        fp = H.template_fingerprint(a)
        if fp not in seen: seen.add(fp); templates.append(a)
    print("device:", jax.devices()[0], " templates:", len(templates))
    variants = [
        ("full", frozenset()),
        ("-ptsf", frozenset({"ptsf"})),
        ("-ptss", frozenset({"ptss"})),
        ("-ptss_reg", frozenset({"ptss_reg"})),
        ("-res_scores", frozenset({"res_scores"})),
        ("-norms", frozenset({"norms"})),
        ("-carry_cnt", frozenset({"carry_cnt"})),
        ("-fit", frozenset({"fit"})),
        ("minimal", frozenset({"ptsf", "ptss", "norms", "res_scores", "carry_cnt"})),
    ]
    orig = H._step
    # slope via two batch sizes so fixed dispatch cost cancels
    B1, B2 = 128, 512
    for name, skip in variants:
        H._step = make_step(skip)
        H._session_scan._clear_cache()
        sess = H.HoistedSession(c, templates)
        def run(b):
            ys = sess.schedule(arrays[:b])
            jax.block_until_ready(ys["best"])
        run(B1); run(B2)  # compile both shapes
        t1 = []
        t2 = []
        for _ in range(3):
            t0 = time.perf_counter(); run(B1); t1.append(time.perf_counter() - t0)
            t0 = time.perf_counter(); run(B2); t2.append(time.perf_counter() - t0)
        slope = (min(t2) - min(t1)) / (B2 - B1) * 1e3
        print(f"{name:12s} slope={slope:6.3f} ms/pod  "
              f"B{B1}={min(t1)*1e3:7.1f}ms B{B2}={min(t2)*1e3:7.1f}ms")
    H._step = orig


main()
