"""Dense per-template host remap of the hoisted prologue, for the mesh.

ops/sharded_scan.py (ShardedPallasSession) runs the scheduling step as jnp
under shard_map and takes every static from here: the exact per-dimension
GCD rescale to int32, per-(template, constraint) count rows at stride 8,
and the D1-D5 gate matrices over per-(template, key) assumed-pod counts.
Everything is [T, ...] or [T*8, ...] in the number of templates, which is
why the single-chip session (ops/pallas_scan.py) left this layout for a
table of rows that it admits specs into; the mesh path has not followed
yet (PERF.md section 7) and rebuilds on a new spec, as the jnp
HoistedSession does. No kernel lives here.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .hoisted import (
    _session_prologue,
    _stack_templates,
    match_matrices_np,
    template_fingerprint,
    templates_have_ports,
    templates_have_terms,
)
from .kernel import DEFAULT_WEIGHTS, MAX_NODE_SCORE
from .pallas_scan import (
    LANE,
    POS_BIG,
    SUB,
    VZ,
    PallasUnsupported,
    _ceil,
    _gcd_all,
    _pad2,
)



def _pad_tc(a: np.ndarray, t_n: int) -> np.ndarray:
    """[T, X<=8] -> [T, 8] zero-padded (per-term scalar tables)."""
    out = np.zeros((t_n, SUB), a.dtype)
    out[:, : a.shape[1]] = a
    return out



@functools.partial(jax.jit, static_argnames=("n",))
def _pack_group(n: int, *arrs):
    return jnp.concatenate([a.ravel() for a in arrs])


def _fetch_packed(tree: Dict) -> Dict:
    """Device->host fetch of a dict of device arrays in ONE transfer per
    dtype group, instead of ~80 prologue outputs one np.asarray (one
    blocking transfer) at a time."""
    by_dtype: Dict = {}
    for k, v in tree.items():
        by_dtype.setdefault(jnp.asarray(v).dtype, []).append(k)
    out: Dict = {}
    for dtype, keys in by_dtype.items():
        arrs = [jnp.asarray(tree[k]) for k in keys]
        packed = np.asarray(_pack_group(len(arrs), *arrs))
        off = 0
        for k, a in zip(keys, arrs):
            size = int(np.prod(a.shape)) if a.shape else 1
            out[k] = packed[off:off + size].reshape(a.shape)
            off += size
    return out


def batch_prologue(fps: Dict, tp_np: Dict, pod_arrays_list: List[Dict],
                   minimum: int, require_unbound: bool = True):
    """Shared host-side batch prep for the session schedule paths
    (PallasSession.schedule, _dispatch_mode, ShardedPallasSession):
    pow2 length bucket (each distinct Bp is a fresh compile; production
    batches are ragged), template ids, and the match matrices — computed
    on HOST (match_matrices_np): an on-device compute + readback here
    would wait out the previous batch's scan and kill the
    dispatch/harvest overlap. Returns (Bp, tmpl[Bp], mfa, msa)."""
    from .hoisted import batch_bucket

    B = len(pod_arrays_list)
    Bp = batch_bucket(B, minimum=minimum)
    tmpl = np.zeros(Bp, np.int32)
    for i, pa in enumerate(pod_arrays_list):
        if require_unbound and bool(np.asarray(pa["has_node_name"])):
            raise ValueError("session pods must be unbound")
        tmpl[i] = fps[template_fingerprint(pa)]
    mfa, msa = match_matrices_np(tp_np, pod_arrays_list)
    return Bp, tmpl, mfa, msa


@functools.partial(jax.jit, donate_argnums=(0,))
def _carry_delta_scan(carry, prow_f, prow_s, src_rows, perno_rows, xs):
    """Apply a batch of cluster-event deltas to a pallas-layout carry in
    ONE fused launch (shared by PallasSession and the sharded mirror —
    the math is layout-identical, only Np differs). Each event is the
    jnp twin of the kernel's _apply_updates with `best := node` and a
    sign folded into the payload: utilization columns plus the same-pair
    count masks (prow == prow[:, node], -1 lanes never update, exactly
    the kernel's gating), with cnt_sn's perno/src factor reproduced
    verbatim. lax.scan keeps the launch count at ONE regardless of the
    event count; padding rows are node 0 with all-zero payloads."""

    def step(c, x):
        c = dict(c)
        n = x["node"]
        c["requested"] = c["requested"].at[:, n].add(x["dres"])
        c["nzpc"] = c["nzpc"].at[:, n].add(x["dnzpc"])
        pf_b = jax.lax.dynamic_index_in_dim(prow_f, n, axis=1)  # [TCp, 1]
        same_f = (prow_f == pf_b) & (prow_f >= 0)
        c["cnt_fn"] = c["cnt_fn"] + x["mf"][:, None] * same_f
        ps_b = jax.lax.dynamic_index_in_dim(prow_s, n, axis=1)
        same_s = (prow_s == ps_b) & (prow_s >= 0)
        src_b = jax.lax.dynamic_index_in_dim(src_rows, n, axis=1)
        factor = perno_rows + (1 - perno_rows) * src_b       # [TCp, 1]
        c["cnt_sn"] = c["cnt_sn"] + x["ms"][:, None] * factor * same_s
        return c, None

    carry, _ = jax.lax.scan(step, carry, xs)
    return carry



class DenseRemap:
    """The hoisted prologue of T templates, remapped on the host to the
    dense int32 layout ShardedPallasSession shards over the mesh. Raises
    PallasUnsupported when the cluster shape needs a fallback (e.g. a
    shared-value topology key with more than 128 distinct values)."""

    def __init__(self, cluster: Dict, template_arrays_list: List[Dict],
                 weights: Optional[Dict[str, int]] = None):
        if templates_have_ports(template_arrays_list):
            # the jnp HoistedSession carries host-port tables; this
            # layout does not — signal a fallback, not an error
            raise PallasUnsupported(
                "templates with host ports ride the jnp hoisted session",
                reason="host-ports",
            )
        # affinity-term templates ARE supported: the D1-D5 deltas
        # (ops/hoisted.py term-machinery block) ride per-(template, key)
        # per-node count carries updated with the same same-pair-mask
        # trick as the PTS counts — see _build_ipa below
        self.dyn_ipa = templates_have_terms(template_arrays_list)
        self.weights = dict(weights or DEFAULT_WEIGHTS)
        self._fps = {
            template_fingerprint(t): i for i, t in enumerate(template_arrays_list)
        }
        # pad the template axis to a pow2 bucket (min 2) with inert
        # copies of template 0 (never referenced by a pod's tmpl index):
        # a workload introducing its 2nd..Nth template then reuses the
        # compiled program instead of paying a mid-window recompile
        from ..models.vocab import bucket_capacity

        Tb = bucket_capacity(len(template_arrays_list), minimum=2)
        template_arrays_list = list(template_arrays_list) + [
            template_arrays_list[0]
        ] * (Tb - len(template_arrays_list))
        # first-max tie-break + score output rely on f32-exact totals:
        # every plugin score is <= MAX_NODE_SCORE after normalization
        if sum(abs(int(v)) for v in self.weights.values()) \
                * (MAX_NODE_SCORE + 1) >= 2 ** 24:
            raise PallasUnsupported("weights too large for exact f32 totals",
                                    reason="weights-exceed-f32")
        tp = _stack_templates(template_arrays_list)
        self._tp = tp
        # numpy copies of the selector tables schedule() evaluates on
        # HOST per batch (match_matrices_np) — the jnp path would block
        # the dispatch behind the previous batch's scan (device stream
        # ordering), serializing the scheduler's 1-deep pipeline
        self._tp_np = {
            k: np.asarray(tp[k])
            for k in ("ptsf_op", "ptsf_rkey", "ptsf_pairs",
                      "ptss_op", "ptss_rkey", "ptss_pairs", "self_ns")
        }
        from .hoisted import TERM_NP_KEYS

        # delta classifier input (tpu_backend): a foreign pod matching a
        # template's own IPA terms perturbs the prologue statics, so its
        # event cannot ride the carry-delta path
        self._term_np = (
            {k: np.asarray(tp[k]) for k in TERM_NP_KEYS}
            if self.dyn_ipa else None
        )
        S = _fetch_packed(
            _session_prologue(cluster, tp, dyn_ipa=self.dyn_ipa)
        )
        c = _fetch_packed(cluster)
        self._build(c, S)
        self._ipa = self._build_ipa(c, S, tp) if self.dyn_ipa else None

    # -- host-side prologue remap ------------------------------------------

    # ktpu: allow-sync(session build: one-time host packing of the prologue's outputs, runs before first dispatch)
    def _build(self, c: Dict, S: Dict) -> None:
        T, N = S["static_mask"].shape
        C = S["f_valid"].shape[1]
        self.T, self.C, self.N = T, C, N
        Np = _ceil(N, LANE)
        self.Np = Np
        CP = SUB  # constraint rows padded to 8 per template: dynamic
        # (CP, Np) block reads at t*CP are provably 8-aligned for Mosaic
        if C > CP:
            raise PallasUnsupported(f"{C} constraints > {CP} per template",
                                    reason="too-many-constraints")
        TC = T * C
        TCp = T * CP
        self.CP = CP
        self.TCp = TCp
        R = c["alloc"].shape[1]
        self.R = R
        tp = self._tp

        # ---- exact per-dimension GCD rescale to int32 ----
        alloc = c["alloc"].astype(np.int64).T.copy()            # [R, N]
        requested = c["requested"].astype(np.int64).T.copy()
        req = np.asarray(tp["req"]).astype(np.int64)            # [T, R]
        nz_requested = c["nz_requested"].astype(np.int64).T.copy()  # [2, N]
        nz_req = np.asarray(tp["nz_req"]).astype(np.int64)      # [T, 2]
        # per-dimension rescale factors survive the build: incoming
        # session deltas (tpu_backend carry patches) must divide by the
        # SAME gcd to stay exact — an indivisible delta is classified
        # structural instead (delta_compatible)
        self._gcd = np.ones(R, np.int64)
        for r in range(R):
            extra = [nz_requested[r], nz_req[:, r]] if r < 2 else []
            g = _gcd_all(alloc[r], requested[r], req[:, r], *extra)
            self._gcd[r] = g
            alloc[r] //= g
            requested[r] //= g
            req[:, r] //= g
            if r < 2:
                nz_requested[r] //= g
                nz_req[:, r] //= g
        hi = max((int(a.max(initial=0)) for a in
                  (alloc, requested, req, nz_requested, nz_req)), default=0)
        if hi * (MAX_NODE_SCORE + 1) >= 2 ** 31:
            raise PallasUnsupported(
                f"rescaled resource magnitude {hi} too large for int32",
                reason="resource-magnitude")

        self._alloc = _pad2(alloc.astype(np.int32))             # [Rp, Np]
        self._requested0 = _pad2(requested.astype(np.int32))
        nzpc = np.zeros((SUB, N), np.int64)
        nzpc[0] = nz_requested[0]
        nzpc[1] = nz_requested[1]
        nzpc[2] = c["pod_count"].astype(np.int64)
        nzpc[3] = c["allowed_pods"].astype(np.int64)
        self._nzpc0 = _pad2(nzpc.astype(np.int32))              # [8, Np]
        self._req_s = req.astype(np.int32)
        self._nz_req_s = nz_req.astype(np.int32)
        self._req_check_s = np.asarray(tp["req_check"]).astype(np.int32)
        self._req_has_any_s = np.asarray(tp["req_has_any"]).astype(np.int32)

        # ---- per-template [T, N] statics: row t*SR+i ----
        stat_rows = [
            S["static_mask"], S["raw_ipa"], S["cnt_taint"],
            S["cnt_nodeaff"], S["sc_image"], S["sc_avoid"],
            np.zeros_like(S["static_mask"]), S["s_src"],
        ]
        if any(np.abs(a.astype(np.int64)).max(initial=0) >= POS_BIG
               for a in stat_rows):
            # POS_BIG (2^30), not 2^31: the kernel's min/max sentinels must
            # stay strictly above any genuine value
            raise PallasUnsupported("static score magnitude exceeds sentinel",
                                    reason="score-magnitude")
        SR = len(stat_rows)  # == 8
        self.SR = SR
        stat = np.stack([a.astype(np.int32) for a in stat_rows], axis=1)
        self._stat = _pad2(stat.reshape(T * SR, N))             # [T*SR, Np]

        # ---- PTS: per-constraint representation ----
        valid_nodes = c["valid"].astype(bool)

        def col(side, t, cc):
            return S[f"{side}_pair_cn"][t, :, cc]

        def node_distinct(column):
            real = column[valid_nodes]
            return len(real) == 0 or len(np.unique(real)) == len(real)

        uid_of: Dict[bytes, int] = {}
        uids: List[np.ndarray] = []

        def classify(side, force_host=None, intern=True):
            """-> (keyid [T,C], perno [T,C] bool): perno = per-node count
            representation; otherwise compact key `keyid`. With
            intern=False only perno is computed (the filter path works
            entirely per-node and must not consume the key/value budgets
            that exist for score-side registration)."""
            keyid = np.full((T, C), -1, np.int32)
            perno = np.zeros((T, C), bool)
            for t in range(T):
                for cc in range(C):
                    if not S[f"{side}_valid"][t, cc]:
                        continue
                    column = col(side, t, cc)
                    is_host = (force_host[t, cc] if force_host is not None
                               else node_distinct(column))
                    if is_host:
                        perno[t, cc] = True
                        continue
                    if not intern:
                        continue
                    key = column.tobytes()
                    u = uid_of.get(key)
                    if u is None:
                        u = len(uids)
                        uid_of[key] = u
                        uids.append(column.copy())
                    keyid[t, cc] = u
            return keyid, perno

        # score side MUST follow the prologue's hostname flag (it selects
        # the log(n_scored) weight semantics, not just a representation)
        s_hostflag = S["s_hostname"].astype(bool)
        fk, fh = classify("f", intern=False)
        sk, sh = classify("s", force_host=s_hostflag)
        # a non-hostname score constraint whose pairs are node-distinct
        # would blow the 128-lane vocab — unsupported
        self._f_keyid, self._f_perno = fk, fh
        self._s_keyid, self._s_perno = sk, sh

        K = max(len(uids), 1)
        if len(uids) > 4:
            raise PallasUnsupported(f"{len(uids)} distinct shared-value keys",
                                    reason="too-many-topology-keys")
        self.K = K
        onehot = np.zeros((K, Np, VZ), np.float32)
        zof: List[Dict[int, int]] = []
        for u, column in enumerate(uids):
            vals = np.unique(column[valid_nodes])
            vals = vals[vals > 0]
            if len(vals) > VZ:
                raise PallasUnsupported(
                    f"topology key {u} has {len(vals)} values > {VZ}",
                    reason="too-many-topology-values")
            m = {int(v): z for z, v in enumerate(vals)}
            zof.append(m)
            zid = np.array([m.get(int(v), -1) for v in column], np.int32)
            ok = (zid >= 0) & valid_nodes
            onehot[u, np.arange(N)[ok], zid[ok]] = 1.0
        self._onehot = onehot

        def gather_rows(side, cnt_tcv, perno, perno_src=None):
            """[T, C, Vnp] pair counts -> per-NODE count rows [TCp, Np]:
            row (t*CP+c), lane n = count of the pair node n belongs to."""
            out = np.zeros((TCp, Np), np.int32)
            for t in range(T):
                for cc in range(C):
                    row = t * CP + cc
                    if perno[t, cc] and perno_src is not None:
                        out[row, :N] = perno_src[t, cc]
                    else:
                        out[row, :N] = cnt_tcv[t, cc][col(side, t, cc)]
            return out

        self._cnt_fn0 = gather_rows("f", S["f_cnt0"], fh)
        self._cnt_sn0 = gather_rows(
            "s", S["s_cnt0"], sh,
            perno_src=S["h_cnt0"].astype(np.int64))

        # static per-node structures
        prow_f = np.full((TCp, Np), -1, np.int32)
        prow_s = np.full((TCp, Np), -1, np.int32)
        regrow_f = np.zeros((TCp, Np), np.int32)
        zvalid_node_s = np.zeros((TCp, Np), np.int32)
        zvalid_s = np.zeros((TCp, VZ), np.int32)
        for t in range(T):
            for cc in range(C):
                row = t * CP + cc
                if S["f_valid"][t, cc]:
                    column = col("f", t, cc)
                    prow_f[row, :N] = np.where(valid_nodes, column, -1)
                    regrow_f[row, :N] = S["f_reg_real"][t, cc][column]
                if S["s_valid"][t, cc]:
                    column = col("s", t, cc)
                    prow_s[row, :N] = np.where(valid_nodes, column, -1)
                    if not sh[t, cc] and sk[t, cc] >= 0:
                        zvalid_node_s[row, :N] = (column > 0) & valid_nodes
                        for pair, zz in zof[sk[t, cc]].items():
                            zvalid_s[row, zz] = 1
        self._prow_f = prow_f
        self._prow_s = prow_s
        self._regrow_f = regrow_f
        self._zvalid_node_s = zvalid_node_s
        self._zvalid_s = zvalid_s
        if max(prow_f.max(), prow_s.max()) >= 2 ** 24:
            raise PallasUnsupported("pair ids exceed exact-f32 range",
                                    reason="pair-ids-exceed-f32")

        def tcn(a):  # [T, N, C] bool -> [TCp, Np] i32 (stride CP)
            out = np.zeros((TCp, Np), np.int32)
            for t in range(T):
                for cc in range(C):
                    out[t * CP + cc, :N] = a[t, :, cc]
            return out

        self._konn_f = tcn(S["f_key_on_node"])
        self._konn_s = tcn(S["s_key_on_node"])
        # session-delta statics: row-expanded s_src (score-count node
        # eligibility per row's template) and the per-row perno flag —
        # the jnp twin of the kernel's _apply_updates factor, used by
        # apply_deltas to patch cnt_sn exactly as an in-scan assume would
        src_rows = np.zeros((TCp, Np), np.int32)
        perno_rows = np.zeros((TCp, 1), np.int32)
        for t in range(T):
            for cc in range(C):
                src_rows[t * CP + cc, :N] = S["s_src"][t].astype(np.int32)
                perno_rows[t * CP + cc, 0] = int(self._s_perno[t, cc])
        self._src_rows = src_rows
        self._perno_rows = perno_rows
        sha = np.zeros((_ceil(T, SUB), Np), np.int32)
        sha[:T, :N] = S["s_has_all"].astype(np.int32)
        self._shasall = sha
        vn = np.zeros((SUB, Np), np.int32)
        vn[:, :N] = c["valid"].astype(np.int32)[None, :]
        self._valid_n = vn

        # the layout's own limit: one match lane per (template,
        # constraint) row, 128 lanes
        if TCp > LANE:
            raise PallasUnsupported(f"T*CP={TCp} exceeds {LANE} match lanes",
                                    reason="too-many-match-lanes")

        # per-(template, constraint) scalars, as structured tables
        self._sc_tables = {
            k: np.asarray(S[k]).copy()
            for k in ("f_valid", "s_valid", "f_skew", "s_skew",
                      "f_self_match", "s_first", "f_same_key", "s_same_key",
                      "ipa_present")
        }

    # ktpu: allow-sync(session build: one-time host packing of affinity planes, runs before first dispatch)
    def _build_ipa(self, c: Dict, S: Dict, tp: Dict) -> Dict:
        """InterPodAffinity term machinery for the single-launch kernel.

        The hoisted scan's D1-D5 deltas (ops/hoisted.py term-machinery
        block) all reduce to per-(assumed-template u, topology key ki)
        counts gathered at each node's (ki, value) group. The pallas port
        keeps those counts PER NODE (the same representation trick as the
        PTS cnt_fn/cnt_sn rows): carry row (u*8 + ki) of `ucnt` holds,
        for every node n, the number of session-assumed u-pods in n's
        ki-group — updated on assume with a same-pair mask from `prow_ipa`
        (pair id per node per key; -1 where the node lacks the key, which
        makes the nkey gating implicit: rows never accumulate on keyless
        nodes). `kcnt` row (u*8+ki) carries the scalar total (lanes all
        equal). Every D1-D5 read then becomes a STATIC gate/weight matrix
        (template x term match booleans from _term_gates, resolved host-
        side) times ucnt — one MXU dot each:
          D1 fail-existing  : g1[t] . (ucnt > 0) > 0
          D2 own-anti counts: wanti[t-block] @ ucnt  (+ static anti rows)
          D3 own-aff counts : waff[t-block] @ ucnt   (+ static aff rows)
          D4+D5 score       : w45[t] @ ucnt  (weights pre-folded)
          presence flags    : gpres[t] . rowany(ucnt > 0)
          aff_total delta   : w3tot[t] . kcnt[:, 0]
        Exactness: counts are integers in f32 (exact < 2^24); the 0/1
        dots are bounded by 8 * count; the score dot is guarded below.
        """
        T, N, Np = self.T, self.N, self.Np
        aa_key = np.asarray(tp["ipaaa_key"])
        aa_valid = np.asarray(tp["ipaaa_valid"]).astype(bool)
        a_key = np.asarray(tp["ipaa_key"])
        a_valid = np.asarray(tp["ipaa_valid"]).astype(bool)
        p_key = np.asarray(tp["ipap_key"])
        p_valid = np.asarray(tp["ipap_valid"]).astype(bool)
        p_w = np.asarray(tp["ipap_weight"]).astype(np.int64)
        if aa_key.shape[1] > SUB or a_key.shape[1] > SUB:
            raise PallasUnsupported(
                f"{max(aa_key.shape[1], a_key.shape[1])} required "
                f"(anti-)affinity terms > {SUB} per template",
                reason="too-many-ipa-terms")
        # distinct topology keys across every template's valid terms
        keys: set = set()
        for k_tbl, v_tbl in ((aa_key, aa_valid), (a_key, a_valid),
                             (p_key, p_valid)):
            keys.update(int(x) for x in k_tbl[v_tbl])
        ki_list = sorted(keys)
        if len(ki_list) > SUB:
            raise PallasUnsupported(
                f"{len(ki_list)} IPA topology keys > {SUB}",
                reason="too-many-ipa-keys")
        ki_of = {k: i for i, k in enumerate(ki_list)}
        UR = T * SUB  # ucnt rows: (u * 8 + ki)

        pok = c["pair_of_key"].astype(np.int64)  # [N, K]
        nkey = c["nkey"].astype(bool)
        valid_nodes = c["valid"].astype(bool)
        prow_ipa = np.full((SUB, Np), -1, np.int32)
        for i, key in enumerate(ki_list):
            ok = nkey[:, key] & valid_nodes
            prow_ipa[i, :N] = np.where(ok, pok[:, key], -1)
        if prow_ipa.max(initial=0) >= 2 ** 24:
            raise PallasUnsupported("IPA pair ids exceed exact-f32 range",
                                    reason="pair-ids-exceed-f32")

        M_anti = np.asarray(S["M_anti"]).astype(bool)   # [T, TAA, T]
        M_aff = np.asarray(S["M_aff"]).astype(bool)     # [T, TA, T]
        M_pref = np.asarray(S["M_pref"]).astype(bool)   # [T, TP, T]
        match_all = np.asarray(S["match_all"]).astype(bool)  # [T, T]
        hard_w = int(np.asarray(c["hard_pod_affinity_weight"]))

        t_pad = _ceil(T, SUB)  # per-template matrices: row t (T can be >8)
        g1 = np.zeros((t_pad, UR), np.float32)
        wanti = np.zeros((T * SUB, UR), np.float32)
        waff = np.zeros((T * SUB, UR), np.float32)
        w3tot = np.zeros((t_pad, UR), np.float32)
        w45_i = np.zeros((t_pad, UR), np.int64)
        gpres = np.zeros((t_pad, UR), np.float32)

        def cx(u, key):
            return u * SUB + ki_of[int(key)]

        for t in range(T):
            # D1: assumed u-pods' anti terms repel t where t matches them
            for u in range(T):
                for tau in range(aa_key.shape[1]):
                    if aa_valid[u, tau] and M_anti[u, tau, t]:
                        g1[t, cx(u, aa_key[u, tau])] = 1.0
            # D2: assumed pods counting toward t's own anti terms
            for tau in range(aa_key.shape[1]):
                if not aa_valid[t, tau]:
                    continue
                for u in range(T):
                    if M_anti[t, tau, u]:
                        wanti[t * SUB + tau, cx(u, aa_key[t, tau])] = 1.0
            # D3: assumed pods matching ALL of t's affinity terms
            for tau in range(a_key.shape[1]):
                if not a_valid[t, tau]:
                    continue
                for u in range(T):
                    if match_all[t, u]:
                        waff[t * SUB + tau, cx(u, a_key[t, tau])] = 1.0
                        w3tot[t, cx(u, a_key[t, tau])] += 1.0
            # D4: assumed pods' score terms vs t (required-aff at
            # hardPodAffinityWeight; preferred at signed weight) and
            # D5: t's own preferred terms vs assumed pods
            for u in range(T):
                for tau in range(a_key.shape[1]):
                    if a_valid[u, tau] and M_aff[u, tau, t] and hard_w > 0:
                        w45_i[t, cx(u, a_key[u, tau])] += hard_w
                        gpres[t, cx(u, a_key[u, tau])] = 1.0
                for tau in range(p_key.shape[1]):
                    if p_valid[u, tau] and M_pref[u, tau, t]:
                        w45_i[t, cx(u, p_key[u, tau])] += int(p_w[u, tau])
                        gpres[t, cx(u, p_key[u, tau])] = 1.0
                for tau in range(p_key.shape[1]):
                    if p_valid[t, tau] and M_pref[t, tau, u]:
                        w45_i[t, cx(u, p_key[t, tau])] += int(p_w[t, tau])
                        gpres[t, cx(u, p_key[t, tau])] = 1.0
        # score-dot exactness: |w|.sum * count must stay < 2^24 in f32.
        # Weights first shed their common GCD (the kernel multiplies the
        # int32 dot result back by w45_scale): the harness's weight-100
        # preferred-affinity templates (sum|w| 300) ride the kernel as
        # sum|w/g| 3 instead of downgrading to the hoisted session —
        # the Preferred-affinity configs' silent ~4x slow path.
        w45_scale = _gcd_all(w45_i)
        w45_i //= w45_scale
        # with the scaled dot cast to int32 BEFORE the multiply, only
        # the dot itself must be exact: cap session assumed counts at
        # 2^16 (far above any bench window) -> sum|w/g| < 2^8
        scaled_sum = int(np.abs(w45_i).sum(axis=1).max(initial=0))
        if scaled_sum >= 256:
            raise PallasUnsupported(
                "IPA score weights too large for exact f32 dot",
                reason="ipa-score-weights")
        # ... and the RESTORED magnitude must keep int32 headroom: the
        # multiply-back delta (scale * scaled-sum * count) has to stay
        # clear of the 2^30 score sentinel at the same 2^16 count cap,
        # or raw_ipa's int32 add could wrap for extreme weight mixes
        # (e.g. {100, 25400}: gcd 100, scaled sum 255) that the
        # pre-scale guard used to reject outright
        if w45_scale * scaled_sum >= 2 ** 14:
            raise PallasUnsupported(
                "IPA score weights too large for int32 score headroom",
                reason="ipa-score-weights")

        # static per-term per-node blocks (rows t*8+term)
        anti_static = np.zeros((T * SUB, Np), np.int32)
        anti_konn = np.zeros((T * SUB, Np), np.int32)
        aff_static = np.zeros((T * SUB, Np), np.int32)
        anti_cnt_n = np.asarray(S["ipa_anti_cnt_n"])    # [T, N, TAA]
        anti_kon = np.asarray(S["ipa_anti_key_on_node"])
        aff_cnt_n = np.asarray(S["ipa_aff_cnt_n"])      # [T, N, TA]
        for t in range(T):
            for tau in range(aa_key.shape[1]):
                anti_static[t * SUB + tau, :N] = anti_cnt_n[t, :, tau]
                anti_konn[t * SUB + tau, :N] = anti_kon[t, :, tau]
            for tau in range(a_key.shape[1]):
                aff_static[t * SUB + tau, :N] = aff_cnt_n[t, :, tau]
        # per-template per-node statics (rows t*2 / t*2+1)
        ipa_stat = np.zeros((_ceil(2 * T, SUB), Np), np.int32)
        fe = np.asarray(S["ipa_fail_existing"])         # [T, N]
        aak = np.asarray(S["ipa_aff_all_keys"])
        for t in range(T):
            ipa_stat[2 * t, :N] = fe[t]
            ipa_stat[2 * t + 1, :N] = aak[t]
        if max(int(anti_static.max(initial=0)),
               int(aff_static.max(initial=0))) >= POS_BIG:
            raise PallasUnsupported("IPA static counts exceed sentinel",
                                    reason="score-magnitude")
        return dict(
            UR=UR,
            prow_ipa=prow_ipa, ipa_stat=ipa_stat,
            anti_static=anti_static, anti_konn=anti_konn,
            aff_static=aff_static,
            g1=g1, wanti=wanti, waff=waff, w3tot=w3tot,
            w45=w45_i.astype(np.float32), w45_scale=w45_scale, gpres=gpres,
            # SMEM scalar extension: per-t has_aff/self_match_all/
            # aff_total + per-term valid flags
            has_aff=np.asarray(S["ipa_has_aff"]).astype(np.int32),
            self_match_all=np.asarray(
                S["ipa_self_match_all"]).astype(np.int32),
            aff_total=np.asarray(S["ipa_aff_total"]).astype(np.int32),
            anti_valid=_pad_tc(aa_valid.astype(np.int32), T),
            aff_valid=_pad_tc(a_valid.astype(np.int32), T),
        )

    # -- incremental device-state deltas -----------------------------------

    def delta_compatible(self, dres, dnz) -> bool:
        """A utilization delta rides this session's int32 carry only when
        the build-time per-dimension GCD rescale stays exact on it and
        the rescaled magnitudes keep the int32 headroom the build
        guaranteed."""
        dres = np.asarray(dres, np.int64)
        if dres.shape[0] != self._gcd.shape[0]:
            return False
        if (dres % self._gcd != 0).any():
            return False
        dnz = np.asarray(dnz, np.int64)
        if (dnz % self._gcd[:2] != 0).any():
            return False
        hi = max(
            int(np.abs(dres // self._gcd).max(initial=0)),
            int(np.abs(dnz // self._gcd[:2]).max(initial=0)),
        )
        return hi * (MAX_NODE_SCORE + 1) < 2 ** 31

