"""Pallas mega-kernel for the hoisted scheduling session: the WHOLE batch
scan runs as ONE kernel launch with the carry held in registers.

Why: the lax.scan step compiles to dozens of fusions, each launched
afresh on every scan iteration. Inside one pallas kernel the per-op cost
is VPU cycles, so a fori_loop over pods turns 1024 steps x ~25 launches
into ONE launch.

Design notes (vs ops/hoisted.py _step, whose semantics this mirrors):

- **int64-free**: Mosaic has no 64-bit types. Resource quantities
  (milli-CPU, memory bytes, ...) are rescaled per dimension by the GCD
  of every value in the session. This is EXACT, not approximate: the
  fit comparisons, least-allocated's `(cap-req)*100 // cap`, and
  balanced's fractions are invariant under a common rescale (floors of
  equal rationals are equal). Falls back (PallasUnsupported) if the
  rescaled magnitudes overflow the int32 headroom.
- **gather-free PTS counts**: pair-count tables [C, Vnp] (Vnp ~ 11k,
  dominated by per-node hostname pairs) become (a) per-node count rows
  for constraints whose pairs are node-distinct (hostname), and (b)
  compact Vz<=128-lane tables for shared-value keys (zone, ...), with a
  static one-hot [N, Vz] so count-to-node expansion and scored-set
  registration are MXU matvecs instead of gathers (unsupported in
  Mosaic).
- float64 score math (PTS topology weights, IPA/balanced normalization)
  runs in float32 in-kernel. Decision parity with the f64 path is pinned
  by tests on every workload shape we ship; divergence is only possible
  where two nodes' scores straddle an f32 rounding boundary, in which
  case either choice is a max-score node.
- jnp.argmax tie semantics (first max) are reproduced manually (min
  index among maxima) — Mosaic's argmax lane order is unspecified.

Reference frame: same as ops/hoisted.py — this replaces
findNodesThatPassFilters + RunScorePlugins (generic_scheduler.go:235,
framework.go:723) for template-stamped batchable pods, restructured as a
single accelerator program.
"""

from __future__ import annotations

import functools
import math
import os as _os
import time as _time
import logging
import threading
from typing import Dict, List, NamedTuple, Optional

from ..utils import knobs

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hoisted import (
    _session_prologue,
    _stack_templates,
    match_matrices_np,
    template_fingerprint,
    templates_have_ports,
    templates_have_terms,
)
from .kernel import DEFAULT_WEIGHTS, MAX_NODE_SCORE

VZ = 128          # compact pair-value lanes per shared-value key
LANE = 128
SUB = 8
POS_BIG = 2 ** 30
NEG_BIG = -(2 ** 30)

CARRY_KEYS = ("requested", "nzpc", "cnt_fn", "cnt_sn")

_MISSING = object()  # exec-cache sentinel (None = AOT failed, use jit)

logger = logging.getLogger(__name__)


class PallasUnsupported(Exception):
    """This cluster/template shape can't ride the pallas path; callers
    fall back to the jnp HoistedSession.

    `reason` is a FIXED slug per raise site (no interpolated shape
    numbers) — it feeds the scheduler_tpu_session_builds_total metric's
    reason label, where unbounded values would mint unbounded series."""

    def __init__(self, message: str, reason: str = "other"):
        super().__init__(message)
        self.reason = reason


def _ceil(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _pad_tc(a: np.ndarray, t_n: int) -> np.ndarray:
    """[T, X<=8] -> [T, 8] zero-padded (per-term scalar tables)."""
    out = np.zeros((t_n, SUB), a.dtype)
    out[:, : a.shape[1]] = a
    return out


def _pad2(a: np.ndarray, rows: int = SUB, lanes: int = LANE) -> np.ndarray:
    """Pad the last two dims up to multiples of (rows, lanes)."""
    r, c = a.shape[-2], a.shape[-1]
    widths = [(0, 0)] * (a.ndim - 2) + [
        (0, _ceil(r, rows) - r), (0, _ceil(c, lanes) - c)]
    return np.pad(a, widths)


def _gcd_all(*arrays) -> int:
    g = 0
    for a in arrays:
        for v in np.unique(np.abs(np.asarray(a, dtype=np.int64))):
            g = math.gcd(g, int(v))
            if g == 1:
                return 1
    return max(g, 1)


@functools.partial(jax.jit, static_argnames=("n",))
def _pack_group(n: int, *arrs):
    return jnp.concatenate([a.ravel() for a in arrs])


def _fetch_packed(tree: Dict) -> Dict:
    """Device->host fetch of a dict of device arrays in ONE transfer per
    dtype group, instead of ~80 prologue outputs one np.asarray (one
    blocking transfer) at a time."""
    items = [(k, v) for k, v in tree.items()]
    by_dtype: Dict = {}
    for k, v in items:
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


class _Cfg(NamedTuple):
    """Value-hashable kernel configuration — the ONLY static jit input.
    Sessions with equal shapes/weights share one compiled program; the
    cluster statics flow in as dynamic args (see _dispatch)."""

    shapes: tuple
    weights: tuple
    ur: int
    carry_keys: tuple
    interpret: bool
    mode: str = "full"  # full | eval | apply (see _build_kernel)
    mk: int = 1  # multi-pod step width (full mode only; pow2, <= 64)


class PallasSession:
    """HoistedSession-compatible API over the single-launch kernel.

    Semantics: identical to ops/hoisted.py HoistedSession (same
    prologue, same carry discipline) — parity pinned by
    tests/test_pallas_scan.py. Raises PallasUnsupported when the cluster
    shape needs a fallback (e.g. a shared-value topology key with more
    than 128 distinct values).
    """

    # KTPU_EXPLAIN: the Mosaic kernel's scan does not surface per-plugin
    # mask/score sections — explain mode rides the jnp hoisted session
    # (TPUBackend demotes with session_builds{reason="explain"})
    supports_explain = False

    @staticmethod
    def explain_payload(ys):
        return None

    def __init__(self, cluster: Dict, template_arrays_list: List[Dict],
                 weights: Optional[Dict[str, int]] = None,
                 interpret: bool = False,
                 multipod_k: Optional[int] = None,
                 launches_kernel: bool = True):
        """launches_kernel=False builds the host-side remap only (the
        sharded session reuses it and runs jnp under shard_map): the
        Mosaic kernel's VMEM budget does not apply to it."""
        from .kernel import multipod_k as _resolve_mk

        # multi-pod scan steps (conflict-SUFFIX contract: the kernel
        # defers commits within a group, detects conflicts with the
        # shared algebra, and leaves the conflicted suffix uncommitted
        # + flagged in out row 3 for the backend's host replay).
        # Opt-in (KTPU_MULTIPOD_K or the argument): a suffix costs a
        # relaunch, see kernel.multipod_k.
        self.multipod_k = _resolve_mk(multipod_k, suffix_replay=True)
        if templates_have_ports(template_arrays_list):
            # the jnp HoistedSession carries host-port tables; the pallas
            # kernel does not (yet) — signal a fallback, not an error
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
        self.interpret = interpret
        self._fps = {
            template_fingerprint(t): i for i, t in enumerate(template_arrays_list)
        }
        # pad the template axis to a pow2 bucket (min 2) with inert
        # copies of template 0 (never referenced by a pod's tmpl index):
        # a workload introducing its 2nd..Nth template then reuses the
        # compiled program instead of paying a mid-window recompile —
        # the unschedulable-churn bench lost 21s of its 23s window to
        # exactly that rebuild
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
        if self._ipa is not None:
            # SMEM scalar extension: [T,3] has_aff/self_match_all/aff_total,
            # then anti_valid/aff_valid [T,8] each, then the w45 GCD
            # scale (offsets in _build_kernel). The scale rides SMEM, not
            # the static config: sessions whose weights differ only by a
            # common factor share one compiled program.
            extra = np.concatenate([
                np.stack([
                    self._ipa["has_aff"], self._ipa["self_match_all"],
                    self._ipa["aff_total"],
                ], axis=1).reshape(-1),
                self._ipa["anti_valid"].reshape(-1),
                self._ipa["aff_valid"].reshape(-1),
                np.array([self._ipa["w45_scale"]]),
            ]).astype(np.int32)
            self._scalars = np.concatenate([self._scalars, extra])
        self._carry = None
        self._bundle = None
        # (Bp, mode) -> AOT-compiled executable (None = AOT unavailable,
        # dispatch through jit). Shared between the serving path and the
        # warm_buckets daemon thread; plain dict ops are GIL-atomic and a
        # rare duplicate compile is absorbed by the persistent cache.
        self._exec: Dict = {}
        # (Bp, mode) -> text of the error that pinned that entry to None
        # (AOT compile rejected, or the executable refused its arguments).
        # The jit path still serves — and fails the same way one frame
        # later if the kernel itself is at fault — but the compiler's
        # message is kept: logged where it happened, and read by
        # chip_smoke.py / the bench rows, which fail on any entry here.
        self.exec_errors: Dict = {}
        self._warm_stop = threading.Event()
        if launches_kernel and jax.default_backend() == "tpu":
            # the grid-less kernel holds every operand whole in VMEM: a
            # cluster too wide for the core is a clean downgrade here,
            # not a compiler error at the first dispatch (off-TPU the
            # session only interprets, or is built for its host half)
            need = _kernel_vmem_bytes(
                *self._get_bundle()[1:], self._carry_struct(), 2048)
            if _vmem_request(need) > _vmem_cap():
                raise PallasUnsupported(
                    f"kernel operands ({need >> 20} MiB) exceed the "
                    f"core's VMEM", reason="vmem-budget")

    # -- host-side prologue remap ------------------------------------------

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
        self._delta_statics = None  # device copies, built on first apply
        sha = np.zeros((_ceil(T, SUB), Np), np.int32)
        sha[:T, :N] = S["s_has_all"].astype(np.int32)
        self._shasall = sha
        vn = np.zeros((SUB, Np), np.int32)
        vn[:, :N] = c["valid"].astype(np.int32)[None, :]
        self._valid_n = vn

        # row -> template one-hot [T, TCp, VZ] and identity [TCp, LANE]
        if TCp > LANE:
            raise PallasUnsupported(f"T*CP={TCp} exceeds {LANE} match lanes",
                                    reason="too-many-match-lanes")
        rowt = np.zeros((T, TCp, VZ), np.int32)
        for t in range(T):
            rowt[t, t * CP:t * CP + C, :] = 1
        self._rowt = rowt
        # identity mapping match-lane (t*CP+cc) -> row (t*CP+cc)
        eye = np.zeros((TCp, LANE), np.float32)
        for i in range(TCp):
            if i < LANE:
                eye[i, i] = 1.0
        self._eye = eye

        # multipod IPA interference superset (filled by _build_ipa when
        # the session carries term templates; zeros otherwise): row u,
        # lane t != 0 means assuming a template-u pod can perturb a
        # template-t evaluation through the D1-D5 term machinery — the
        # multipod conflict test then replays instead of speculating
        self._gmat = np.zeros((_ceil(T, SUB), LANE), np.float32)

        # SMEM scalar table
        self._scalars = self._pack_scalars(S)

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

        # multipod template-interference superset (the host twin of the
        # hoisted prologue's G_ipa; symmetrized — a false positive only
        # costs a replay, never a wrong decision)
        a1 = M_anti.any(axis=1)
        a2 = M_aff.any(axis=1)
        a3 = M_pref.any(axis=1)
        g = (a1 | a1.T | a2 | a2.T | a3 | a3.T | match_all | match_all.T)
        self._gmat[:T, :T] = g.astype(np.float32)

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

    # ktpu: allow-sync(session build: packs static scalar rows on host before upload)
    def _pack_scalars(self, S) -> np.ndarray:
        T, C, R = self.T, self.C, self.R
        # the sharded two-phase session (ops/sharded_scan.py) reads these
        # as structured tables instead of SMEM offsets
        self._sc_tables = {
            k: np.asarray(S[k]).copy()
            for k in ("f_valid", "s_valid", "f_skew", "s_skew",
                      "f_self_match", "s_first", "f_same_key", "s_same_key",
                      "ipa_present")
        }
        per_t = np.concatenate([
            self._req_s, self._req_check_s,
            self._req_has_any_s[:, None], self._nz_req_s,
            S["ipa_present"].astype(np.int32)[:, None]], axis=1)  # [T, 2R+4]
        tc = np.stack([
            S["f_valid"].astype(np.int32), S["s_valid"].astype(np.int32),
            S["f_skew"].astype(np.int32), S["s_skew"].astype(np.int32),
            S["f_self_match"].astype(np.int32), S["s_first"].astype(np.int32),
            self._f_keyid, self._s_keyid,
            self._f_perno.astype(np.int32), self._s_perno.astype(np.int32),
        ], axis=0)  # [10, T, C]
        return np.concatenate([
            per_t.reshape(-1), tc.reshape(-1),
            S["f_same_key"].astype(np.int32).reshape(-1),
            S["s_same_key"].astype(np.int32).reshape(-1),
        ]).astype(np.int32)

    # -- scheduling --------------------------------------------------------

    def _initial_carry(self):
        z = jnp.asarray
        carry = {
            "requested": z(self._requested0), "nzpc": z(self._nzpc0),
            "cnt_fn": z(self._cnt_fn0), "cnt_sn": z(self._cnt_sn0),
        }
        if self._ipa is not None:
            # session starts with zero ASSUMED pods (existing pods live in
            # the static tables) — mirrors _init_dynamic_carries
            carry["ucnt"] = jnp.zeros((self._ipa["UR"], self.Np), jnp.int32)
            carry["kcnt"] = jnp.zeros((self._ipa["UR"], LANE), jnp.int32)
        return carry

    def _get_bundle(self):
        """(cfg, statics, ipa) for _dispatch: cfg is the value-hashed
        static config; statics/ipa are device-resident dynamic args."""
        if self._bundle is None:
            z = jnp.asarray
            ipa = None
            carry_keys = CARRY_KEYS
            if self._ipa is not None:
                ipa = {
                    k: z(self._ipa[k])
                    for k in ("ipa_stat", "anti_static", "anti_konn",
                              "aff_static", "prow_ipa", "g1", "wanti",
                              "waff", "w3tot", "w45", "gpres")
                }
                carry_keys = CARRY_KEYS + ("ucnt", "kcnt")
            statics = {
                "alloc": z(self._alloc), "stat": z(self._stat),
                "onehot": z(self._onehot), "regrow_f": z(self._regrow_f),
                "zvalid_node_s": z(self._zvalid_node_s),
                "zvalid_s": z(self._zvalid_s),
                "konn_f": z(self._konn_f), "konn_s": z(self._konn_s),
                "shasall": z(self._shasall), "valid_n": z(self._valid_n),
                "rowt": z(self._rowt), "eye": z(self._eye),
                "prow_f": z(self._prow_f), "prow_s": z(self._prow_s),
                "gmat": z(self._gmat),
                "scalars": z(self._scalars),
            }
            cfg = _Cfg(
                shapes=(self.T, self.C, self.Np, self.R, self.SR,
                        self.TCp, self.K, self.CP),
                weights=tuple(sorted(self.weights.items())),
                ur=(self._ipa["UR"] if self._ipa else 0),
                carry_keys=carry_keys,
                interpret=self.interpret,
                mk=self.multipod_k,
            )
            self._bundle = (cfg, statics, ipa)
        return self._bundle

    def _pack_batch(self, B, Bp, tmpl, mfa, msa):
        """Per-batch host->device payload as TWO arrays instead of four
        (B_real, tmpl, mfT, msT): each transfer carries a fixed cost.
        meta = [B_real | tmpl]; match lanes
        (t*CP+c) = that constraint row per pod, filter block then score
        block — int8 on the wire (weights are 0/1), widened on-device."""
        T, C, CP = self.T, self.C, self.CP
        meta = np.empty(1 + Bp, np.int32)
        meta[0] = B
        meta[1:] = tmpl
        match = np.zeros((Bp, 2 * LANE), np.int8)
        for t in range(T):
            match[:B, t * CP:t * CP + C] = mfa[t].reshape(B, C)
            match[:B, LANE + t * CP:LANE + t * CP + C] = msa[t].reshape(B, C)
        return meta, match

    def schedule(self, pod_arrays_list: List[Dict]):
        """Enqueue one batch; returns the (8, Bp) device result rows —
        row 0 best / row 1 score / row 2 n_feasible. decisions() blocks."""
        B = len(pod_arrays_list)
        Bp, tmpl, mfa, msa = batch_prologue(
            self._fps, self._tp_np, pod_arrays_list, minimum=LANE)
        meta, match = self._pack_batch(B, Bp, tmpl, mfa, msa)
        out = self._run_dispatch(meta, match)
        # bucket rides the result so a harvest-side device fault can
        # retire exactly the executable that produced the bad payload
        # (tpu_backend.py retry path)
        return {"rows": out, "n": B, "bucket": Bp, "mk": self.multipod_k}

    @staticmethod
    # ktpu: allow-sync(harvest decode: host consumes batch verdicts after the launch completes)
    def decisions(ys) -> List[int]:
        return [int(v) for v in np.asarray(ys["rows"])[0, :ys["n"]]]

    @staticmethod
    # ktpu: allow-sync(harvest decode: host reads conflict planes after the launch completes)
    def conflict_stats(ys):
        """(n_conflicts, replay_suffix_start) from out row 3: the kernel
        leaves the conflicted suffix UNCOMMITTED (flag 1) — the backend
        replays exactly those pods through the session, whose carry
        holds the committed prefix. n_conflicts is 1 — ONE detection
        headed the suffix; the flags after it are collateral (the
        kernel cannot know which of them would conflict against the
        replayed carry), and any genuine later conflict is re-detected
        — and re-counted — when the replayed suffix runs. (0, None)
        when the batch ran one-pod-per-step (row 3 is the -1 init
        then)."""
        if ys.get("mk", 1) <= 1:
            return 0, None
        flags = np.asarray(ys["rows"])[3, :ys["n"]] > 0
        if not flags.any():
            return 0, None
        return 1, int(np.argmax(flags))

    def retire_exec(self, bucket: Optional[int] = None,
                    mode: Optional[str] = None) -> int:
        """Retire AOT executables after a device fault: a dispatch that
        raised, wedged, or harvested garbage leaves its compiled program
        suspect. Entries are pinned to None (= dispatch through jit), the
        same retired state the arg-mismatch path uses — warm_buckets
        never resurrects a retired entry, and _run_dispatch never
        recompiles one. With `bucket` given, absent entries are pinned
        too: the backend quarantines a suspect bucket on every REBUILT
        session (the _exec cache dies with its session, but the fault
        does not), and lifts it only after the bucket harvests cleanly
        through jit. bucket/mode both None retires every existing
        entry. Returns the number of entries pinned."""
        n = 0
        modes = (mode,) if mode is not None else ("full", "eval", "apply")
        if bucket is not None:
            for m in modes:
                if self._exec.get((bucket, m), _MISSING) is not None:
                    self._exec[(bucket, m)] = None
                    n += 1
            return n
        for key in list(self._exec):
            if mode is not None and key[1] != mode:
                continue
            if self._exec.get(key) is not None:
                self._exec[key] = None
                n += 1
        return n

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

    def _delta_rows(self, d) -> tuple:
        """One backend delta dict -> (node, dres[Rp] scaled, dnzpc[8],
        mf[TCp], ms[TCp]) in this session's carry layout."""
        rp = self._requested0.shape[0]
        dres = np.zeros(rp, np.int32)
        dnzpc = np.zeros(SUB, np.int32)
        mf_rows = np.zeros(self.TCp, np.int32)
        ms_rows = np.zeros(self.TCp, np.int32)
        if d["kind"] == "node-alloc":
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
        return d["node"], dres, dnzpc, mf_rows, ms_rows

    def _patch_alloc_static(self, d) -> None:
        """node-alloc prologue patch: the static alloc columns move (the
        prologue never reads alloc, so nothing else needs recompute).
        The CUMULATIVE rescaled magnitude must keep the int32 headroom
        the build guaranteed — delta_compatible bounds one delta, not
        the sum of many capacity bumps — so the patched column is
        re-checked and an overflow raises (the backend's apply wrapper
        downgrades to a rebuild, whose own envelope then decides)."""
        scaled = (np.asarray(d["dalloc"], np.int64) // self._gcd).astype(
            np.int32)
        n = d["node"]
        col = self._alloc[: self.R, n].astype(np.int64) + scaled
        if int(np.abs(col).max(initial=0)) * (MAX_NODE_SCORE + 1) >= 2 ** 31:
            raise ValueError(
                "cumulative alloc patches exceed the int32 score headroom")
        self._alloc[: self.R, n] += scaled
        if self._bundle is not None:
            cfg, statics, ipa = self._bundle
            statics = dict(statics)
            statics["alloc"] = statics["alloc"].at[:self.R, n].add(
                jnp.asarray(scaled))
            self._bundle = (cfg, statics, ipa)

    def apply_deltas(self, deltas: List[Dict]) -> None:
        """Absorb batched cluster-event deltas into the carry (and the
        alloc statics) without a session rebuild — the pallas face of
        the session-delta contract (see HoistedSession.apply_deltas).
        With no dispatch yet (carry unmaterialized) the numpy seed
        arrays are patched host-side; otherwise one fused
        _carry_delta_scan launch chains onto the in-flight carry."""
        for d in deltas:
            if d["kind"] == "node-alloc":
                self._patch_alloc_static(d)
        rows = [self._delta_rows(d) for d in deltas]
        if self._carry is None:
            for n, dres, dnzpc, mf_rows, ms_rows in rows:
                self._requested0[:, n] += dres
                self._nzpc0[:, n] += dnzpc
                same_f = (
                    (self._prow_f == self._prow_f[:, n][:, None])
                    & (self._prow_f >= 0)
                )
                self._cnt_fn0 += mf_rows[:, None] * same_f
                same_s = (
                    (self._prow_s == self._prow_s[:, n][:, None])
                    & (self._prow_s >= 0)
                )
                factor = (
                    self._perno_rows
                    + (1 - self._perno_rows) * self._src_rows[:, n][:, None]
                )
                self._cnt_sn0 += ms_rows[:, None] * factor * same_s
            return
        e = len(rows)
        from .hoisted import batch_bucket

        ep = batch_bucket(e, minimum=8)  # pow2: one compile per bucket
        xs = {
            "node": np.zeros(ep, np.int32),
            "dres": np.zeros((ep, self._requested0.shape[0]), np.int32),
            "dnzpc": np.zeros((ep, SUB), np.int32),
            "mf": np.zeros((ep, self.TCp), np.int32),
            "ms": np.zeros((ep, self.TCp), np.int32),
        }
        for i, (n, dres, dnzpc, mf_rows, ms_rows) in enumerate(rows):
            xs["node"][i] = n
            xs["dres"][i] = dres
            xs["dnzpc"][i] = dnzpc
            xs["mf"][i] = mf_rows
            xs["ms"][i] = ms_rows
        if self._delta_statics is None:
            self._delta_statics = {
                "prow_f": jnp.asarray(self._prow_f),
                "prow_s": jnp.asarray(self._prow_s),
                "src_rows": jnp.asarray(self._src_rows),
                "perno_rows": jnp.asarray(self._perno_rows),
            }
        ds = self._delta_statics
        self._carry = _carry_delta_scan(
            self._carry, ds["prow_f"], ds["prow_s"], ds["src_rows"],
            ds["perno_rows"], {k: jnp.asarray(v) for k, v in xs.items()},
        )

    # -- dispatch plumbing: persistent executables ------------------------

    def _carry_struct(self) -> Dict:
        """ShapeDtypeStructs of the carry, WITHOUT touching self._carry:
        warm_buckets runs on a daemon thread concurrently with
        schedule() — a warm-thread write of self._carry would silently
        zero the assumes of any batch dispatched in between."""
        structs = {
            "requested": jax.ShapeDtypeStruct(
                self._requested0.shape, jnp.int32),
            "nzpc": jax.ShapeDtypeStruct(self._nzpc0.shape, jnp.int32),
            "cnt_fn": jax.ShapeDtypeStruct(self._cnt_fn0.shape, jnp.int32),
            "cnt_sn": jax.ShapeDtypeStruct(self._cnt_sn0.shape, jnp.int32),
        }
        if self._ipa is not None:
            structs["ucnt"] = jax.ShapeDtypeStruct(
                (self._ipa["UR"], self.Np), jnp.int32)
            structs["kcnt"] = jax.ShapeDtypeStruct(
                (self._ipa["UR"], LANE), jnp.int32)
        return structs

    def _compile_exec(self, Bp: int, mode: str = "full"):
        """AOT lower+compile the dispatch for one (batch bucket, mode).
        The compiled executable is invoked DIRECTLY on the serving path
        (persistent executable reuse): every dispatch then runs the same
        loaded program object — no jit-dispatch signature hashing, and no
        per-launch program re-resolution for the runtime to pay."""
        cfg, statics, ipa = self._get_bundle()
        if mode != "full":
            cfg = cfg._replace(mode=mode)

        def st(x):
            return jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype)

        statics_s = {k: st(v) for k, v in statics.items()}
        ipa_s = {k: st(v) for k, v in ipa.items()} if ipa else None
        args = [
            cfg, statics_s, ipa_s,
            jax.ShapeDtypeStruct((1 + Bp,), jnp.int32),
            self._carry_struct(),
            jax.ShapeDtypeStruct((Bp, 2 * LANE), jnp.int8),
        ]
        if mode == "apply":
            args.append(jax.ShapeDtypeStruct((2 * Bp,), jnp.int32))
        return _dispatch.lower(*args).compile()

    def _run_dispatch(self, meta: np.ndarray, match: np.ndarray,
                      mode: str = "full", forced=None):
        """Execute one dispatch through the persistent-executable cache
        (fallback: the plain jit path). Owns the carry swap — the carry
        buffers are donated to the launch and replaced by its outputs."""
        if self._carry is None:
            self._carry = self._initial_carry()
        Bp = int(meta.shape[0]) - 1
        meta = jnp.asarray(meta)
        match = jnp.asarray(match)
        key = (Bp, mode)
        fn = self._exec.get(key, _MISSING)
        if not knobs.get_bool("KTPU_PALLAS_AOT"):
            fn = None  # kill switch wins even over warm-installed execs
        elif fn is _MISSING:
            # Counted miss path: a dispatch-time compile is a stall the
            # device timeline must attribute (warm_buckets prefills are
            # deliberate and uncounted).
            from ..utils import devtime
            t0 = _time.perf_counter()
            try:
                fn = self._compile_exec(Bp, mode)
            except Exception as e:  # noqa: BLE001 — the jit path serves; the error is kept
                fn = None
                self._exec_failed(key, "AOT compile failed", e)
            self._exec[key] = fn
            if devtime.enabled():
                devtime.TIMELINE.compile_event(
                    "pallas-bucket", t0, _time.perf_counter() - t0,
                    bucket=Bp, mode=mode, ok=fn is not None)
        if fn is not None:
            args = [meta, self._carry, match]
            if mode == "apply":
                args.append(jnp.asarray(forced, jnp.int32))
            try:
                out, self._carry = fn(self._get_bundle()[1],
                                      self._get_bundle()[2], *args)
                return out
            except (TypeError, ValueError) as e:
                # arg-structure/layout mismatch is raised BEFORE
                # execution (carry buffers untouched): retire this
                # executable and serve through jit from now on
                self._exec[key] = None
                self._exec_failed(key, "AOT executable refused its args", e)
        cfg, statics, ipa = self._get_bundle()
        if mode != "full":
            cfg = cfg._replace(mode=mode)
        fv = None if forced is None else jnp.asarray(forced, jnp.int32)
        out, self._carry = _dispatch(
            cfg, statics, ipa, meta, self._carry, match, forced=fv)
        return out

    def _exec_failed(self, key, what: str, e: BaseException) -> None:
        self.exec_errors[key] = f"{what}: {type(e).__name__}: {e}"
        logger.error("pallas bucket %s mode %s: %s", key[0], key[1], what,
                     exc_info=e)

    def stop_warm(self) -> None:
        """Ask a running warm_buckets to stop after the bucket it is
        compiling (backend close: no compile may outlive the process's
        orderly exit)."""
        self._warm_stop.set()

    def warm_buckets(self, sizes=(LANE, 256, 512, 1024, 2048)) -> None:
        """AOT-compile the dispatch for the ragged-tail batch buckets
        WITHOUT dispatching: .lower().compile() populates jax's caches
        including the persistent one, so a mid-window first-tail-bucket
        batch pays a cache hit instead of a fresh ~30s Mosaic compile (a
        gang rep that drained into a never-seen bucket measured 160
        pods/s against its siblings' 1300). Compiled executables land in
        self._exec, so the serving path reuses the very same loaded
        program. Runs on a daemon thread: it must NEVER write
        self._carry (a mid-warm schedule() would have its batch's
        assumes silently zeroed by the overwrite) — all shapes come from
        _carry_struct. A failure stops the warming (the lazy path would
        hit the same compiler error) and is recorded in exec_errors —
        without pinning the entry, so the serving path still makes its
        own attempt."""
        aot = knobs.get_bool("KTPU_PALLAS_AOT")
        for Bp in sizes:
            if self._warm_stop.is_set():
                return
            if (Bp, "full") in self._exec:
                # present entries stand: a None means the serving
                # path RETIRED this executable — do not resurrect it
                continue
            try:
                compiled = self._compile_exec(Bp)
            except Exception as e:  # noqa: BLE001 — warming is off the serving path; the error is kept
                self._exec_failed((Bp, "full"), "AOT warm compile failed", e)
                return
            # with the AOT kill switch set, warming still fills the
            # (persistent) compile caches, but the serving path must
            # keep dispatching through jit — don't install
            if aot:
                self._exec.setdefault((Bp, "full"), compiled)

    # -- split eval/apply (the sharded session's building blocks) ----------
    # A multi-chip session cannot let each shard apply its own local
    # best: the winner is a cross-shard argmax. These run the SAME
    # kernel in mode="eval" (masks/scores/local best, carries untouched)
    # and mode="apply" (commit externally-decided placements; off-shard
    # lanes no-op), so eval -> global argmax -> apply replays the full
    # kernel exactly (pinned by tests/test_pallas_scan.py
    # TestEvalApplySplit).

    def _dispatch_mode(self, pod_arrays_list, mode, forced=None):
        B = len(pod_arrays_list)
        Bp, tmpl, mfa, msa = batch_prologue(
            self._fps, self._tp_np, pod_arrays_list, minimum=LANE,
            require_unbound=False)
        meta, match = self._pack_batch(B, Bp, tmpl, mfa, msa)
        fvec = None
        if mode == "apply":
            fvec = np.zeros(2 * Bp, np.int32)
            for i, (lane, ok) in enumerate(forced):
                fvec[2 * i] = lane
                fvec[2 * i + 1] = ok
        out = self._run_dispatch(meta, match, mode=mode, forced=fvec)
        return {"rows": out, "n": B}

    def evaluate(self, pod_arrays_list: List[Dict]):
        """Local (best, score) per pod WITHOUT carry updates — every pod
        evaluated against the same carry state."""
        ys = self._dispatch_mode(pod_arrays_list, "eval")
        rows = np.asarray(ys["rows"])
        return [
            (int(rows[0, i]), int(rows[1, i])) for i in range(ys["n"])
        ]

    def apply_decisions(
        self, pod_arrays_list: List[Dict], decisions: List[int]
    ) -> None:
        """Commit placements (node lane or -1 = unplaced / off-shard)
        to the session carry."""
        forced = [(d if d >= 0 else -1, 1 if d >= 0 else 0)
                  for d in decisions]
        self._dispatch_mode(pod_arrays_list, "apply", forced=forced)


# ---------------------------------------------------------------------------
# kernel


def _build_kernel(shapes, weights, Bp: int, ur: int = 0,
                  mode: str = "full", mk: int = 1):
    """mode: "full" = eval + select + apply own decision (single-device
    session); "eval" = masks/scores/local-best only, carries untouched;
    "apply" = apply an externally-decided (cross-shard) placement to the
    carries. The sharded session alternates eval/apply around an ICI
    argmax (ShardedPallasSession).

    mk > 1 (full mode): multi-pod steps with exact conflict detection —
    mk pods are evaluated against the GROUP-START carry (their evals
    share no data dependency), then committed in order; a pod whose
    evaluation an earlier commit could have perturbed (same node, PTS
    match-gate, IPA template gate, or the fit/balanced/least recheck —
    the same algebra as ops/hoisted.py _step_multi) starts the CONFLICT
    SUFFIX: it and every later pod of the batch stay UNCOMMITTED, out
    row 3 flags them, and the host replays exactly that suffix through
    the session (tpu_backend._harvest_locked) — bit-identical to
    one-pod-per-step either way."""
    from ..utils import knobs as _knobs

    skip = frozenset(
        _knobs.get_str("KTPU_PALLAS_SKIP").split(","))  # profiling only
    T, C, Np, R, SR, TCp, K, CP = shapes
    W = dict(weights)
    dyn_ipa = ur > 0 and "ipa" not in skip
    row_len = 2 * R + 4
    off_tc = T * row_len
    off_fsame = off_tc + 10 * T * C
    off_ssame = off_fsame + T * C * C
    # IPA scalar extension (appended when the session has term templates)
    off_ipa_t = off_ssame + T * C * C
    off_av = off_ipa_t + 3 * T
    off_w45s = off_av + 2 * T * SUB  # w45 GCD scale (one scalar)
    (W_F_VALID, W_S_VALID, W_F_SKEW, W_S_SKEW, W_F_SELF, W_S_FIRST,
     W_F_KEY, W_S_KEY, W_F_PERNO, W_S_PERNO) = range(10)

    def kernel(*refs):
        forced_ref = None
        if mode == "apply":
            forced_ref = refs[0]  # SMEM [2*Bp]: (local lane | -1, ok)
            refs = refs[1:]
        (breal_ref, tmpl_ref, sc_ref, mf_ref, ms_ref,
         alloc_ref, stat_ref, onehot_ref, regrowf_ref, zvnode_ref,
         zvalid_ref, konnf_ref, konns_ref, shasall_ref, validn_ref,
         rowt_ref, eye_ref, prowf_ref, prows_ref, gmat_ref) = refs[:20]
        i = 20
        if ur > 0:
            (ipastat_ref, antic_ref, antik_ref, affc_ref, prowipa_ref,
             g1_ref, wanti_ref, waff_ref, w3tot_ref, w45_ref,
             gpres_ref) = refs[i:i + 11]
            i += 11
        ncarry = 6 if ur > 0 else 4
        carry_in = refs[i:i + ncarry]
        i += ncarry
        out_ref = refs[i]
        carry_refs = refs[i + 1:]
        requested_in, nzpc_in = carry_in[0], carry_in[1]
        requested_ref, nzpc_ref, cntfn_ref, cntsn_ref = carry_refs[:4]
        if ur > 0:
            ucnt_ref, kcnt_ref = carry_refs[4], carry_refs[5]
        # carries live in the OUTPUT refs (initialized from the inputs);
        # refs — unlike loop-carried values — support dynamic row reads
        for cin, cref in zip(carry_in, carry_refs):
            cref[:] = cin[:]
        out_ref[:] = jnp.full((SUB, Bp), -1, jnp.int32)

        sc = sc_ref
        f32 = jnp.float32

        def sm_t(t, i):
            return sc[t * row_len + i]

        def sm_tc(which, t, cc):
            return sc[off_tc + which * T * C + t * C + cc]

        def sm_fsame(t, ci, cj):
            return sc[off_fsame + (t * C + ci) * C + cj]

        def sm_ssame(t, ci, cj):
            return sc[off_ssame + (t * C + ci) * C + cj]

        def dotz(mat_1v, k):
            """(1, VZ) . onehot[k]^T -> (1, Np)."""
            return jax.lax.dot_general(
                mat_1v, onehot_ref[k], (((1,), (1,)), ((), ())),
                preferred_element_type=f32)

        def dotn(mat_1n, k):
            """(1, Np) . onehot[k] -> (1, VZ)."""
            return jax.lax.dot_general(
                mat_1n, onehot_ref[k], (((1,), (0,)), ((), ())),
                preferred_element_type=f32)

        def doth(a, b, dims):
            """Exact-f32 dot (counts/ids above 2^8 need HIGHEST)."""
            return jax.lax.dot_general(
                a, b, dims, preferred_element_type=f32,
                precision=jax.lax.Precision.HIGHEST)

        def sm_ipa_t(t, i):
            return sc[off_ipa_t + t * 3 + i]

        def sm_av(which, t, tau):
            return sc[off_av + which * T * SUB + t * SUB + tau]

        def _col_av(which, t):
            """(SUB, 1) f32 column of per-(t, term) valid flags."""
            i0 = jax.lax.broadcasted_iota(jnp.int32, (SUB, 1), 0)
            out = jnp.zeros((SUB, 1), f32)
            for tau in range(SUB):
                e = (i0 == tau).astype(f32)
                out = out + sm_av(which, t, tau).astype(f32) * e
            return out

        def _apply_updates(b, t, lane_n, best, oki, okf):
            """Carry updates for pod b landing on node lane `best` (all
            no-ops when best is off this kernel's node range — `hot` is
            then all-zero, which is exactly how the sharded session's
            non-owning shards stay consistent)."""
            hot = (lane_n == best).astype(jnp.int32) * oki   # (1, Np)
            hotf = hot.astype(f32)
            for r in range(R):
                requested_ref[r:r + 1, :] = (
                    requested_ref[r:r + 1, :] + hot * sm_t(t, r))
            nzpc_ref[0:1, :] = nzpc_ref[0:1, :] + hot * sm_t(t, 2 * R + 1)
            nzpc_ref[1:2, :] = nzpc_ref[1:2, :] + hot * sm_t(t, 2 * R + 2)
            nzpc_ref[2:3, :] = nzpc_ref[2:3, :] + hot

            # per-row match weights: column b of mf/ms via identity-dot
            mf_vec = mf_ref[pl.ds(b, 1), :].astype(f32)      # (1, LANE)
            ms_vec = ms_ref[pl.ds(b, 1), :].astype(f32)
            mf_col = jax.lax.dot_general(
                eye_ref[:], mf_vec, (((1,), (1,)), ((), ())),
                preferred_element_type=f32)                  # (TCp, 1)
            ms_col = jax.lax.dot_general(
                eye_ref[:], ms_vec, (((1,), (1,)), ((), ())),
                preferred_element_type=f32)

            # pair id at best, per row (one matvec each side); same-pair
            # lanes get the count delta — hostname rows degenerate to
            # same-NODE exactly like the pair-space update they mirror
            pf = prowf_ref[:].astype(f32)
            zb_f = jax.lax.dot_general(
                pf, hotf, (((1,), (1,)), ((), ())),
                preferred_element_type=f32,
                precision=jax.lax.Precision.HIGHEST)         # (TCp, 1)
            m_f = ((pf == zb_f) & (prowf_ref[:] >= 0)).astype(f32) * okf
            ps_ = prows_ref[:].astype(f32)
            zb_s = jax.lax.dot_general(
                ps_, hotf, (((1,), (1,)), ((), ())),
                preferred_element_type=f32,
                precision=jax.lax.Precision.HIGHEST)
            m_s = ((ps_ == zb_s) & (prows_ref[:] >= 0)).astype(f32) * okf

            # s_src factor at best per row's template (zone rows only; the
            # per-node/hostname update has no src gate, mirroring _step)
            srcrow = jnp.zeros((TCp, 1), f32)
            for tt in range(T):
                srow = stat_ref[pl.ds(tt * SR + 7, 1), :]
                v = jnp.sum(
                    jnp.where(lane_n == best, srow, jnp.int32(0)).astype(f32))
                srcrow = srcrow + rowt_ref[tt][:, 0:1].astype(f32) * v
            pernosel = _stack_tc(sm_tc, W_S_PERNO, T, C, TCp)             # (TCp, 1)
            factor = pernosel + (f32(1.0) - pernosel) * srcrow

            cntfn_ref[:] = (cntfn_ref[:].astype(f32)
                            + mf_col * m_f).astype(jnp.int32)
            cntsn_ref[:] = (cntsn_ref[:].astype(f32)
                            + ms_col * factor * m_s).astype(jnp.int32)

            if dyn_ipa:
                # the assumed pod joins its node's topology groups for
                # every IPA key the node carries: same-pair mask from
                # prow_ipa (-1 rows = node lacks key -> no-op), written
                # into template t's own 8-row ucnt block
                pi = prowipa_ref[:].astype(f32)                # (SUB, Np)
                zb_i = doth(pi, hotf, (((1,), (1,)), ((), ())))  # (SUB, 1)
                m_i = ((pi == zb_i)
                       & (prowipa_ref[:] >= 0)).astype(f32) * okf
                base_u = pl.multiple_of(t * SUB, SUB)
                ucnt_ref[pl.ds(base_u, SUB), :] = (
                    ucnt_ref[pl.ds(base_u, SUB), :].astype(f32) + m_i
                ).astype(jnp.int32)
                hask = doth((pi >= 0).astype(f32), hotf,
                            (((1,), (1,)), ((), ())))          # (SUB, 1)
                kcnt_ref[pl.ds(base_u, SUB), :] = (
                    kcnt_ref[pl.ds(base_u, SUB), :].astype(f32)
                    + hask * okf
                ).astype(jnp.int32)

        def fit_row(t):
            """NodeResourcesFit row against the CURRENT carry refs —
            shared by the eval and the multipod conflict recheck (the
            fit leg of kernel.multipod_utilization_conflicts)."""
            over = jnp.zeros((1, Np), jnp.bool_)
            for r in range(R):
                free = alloc_ref[r:r + 1, :] - requested_ref[r:r + 1, :]
                over = over | ((sm_t(t, r) > free) & (sm_t(t, R + r) != 0))
            fail_dims = (sm_t(t, 2 * R) != 0) & over
            fail_count = (nzpc_ref[2:3, :] + jnp.int32(1)) > nzpc_in[3:4, :]
            return jnp.logical_not(fail_count | fail_dims)

        def resource_rows(t):
            """(balanced, least) rows against the CURRENT carry refs —
            shared by the eval and the multipod wbl recheck."""
            nz_cpu = (nzpc_ref[0:1, :] + sm_t(t, 2 * R + 1)).astype(f32)
            nz_mem = (nzpc_ref[1:2, :] + sm_t(t, 2 * R + 2)).astype(f32)
            cap_cpu = alloc_ref[0:1, :].astype(f32)
            cap_mem = alloc_ref[1:2, :].astype(f32)
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

            least = (least_dim(alloc_ref[0:1, :],
                               nzpc_ref[0:1, :] + sm_t(t, 2 * R + 1))
                     + least_dim(alloc_ref[1:2, :],
                                 nzpc_ref[1:2, :] + sm_t(t, 2 * R + 2))
                     ) // jnp.int32(2)
            return balanced, least

        def lane_gate(which, t):
            """(1, LANE) gate over match lanes: 1.0 at lane (t*CP+c) for
            template t's VALID constraint slots — counts written to
            invalid slots are never read, so gating the multipod PTS
            conflict test on them is what makes it exact."""
            lanei1 = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
            out = jnp.zeros((1, LANE), f32)
            for tt in range(T):
                sel = (t == tt).astype(f32)
                for cc in range(C):
                    e = (lanei1 == (tt * CP + cc)).astype(f32)
                    out = out + sel * sm_tc(which, tt, cc).astype(f32) * e
            return out

        def eval_pod(b):
            """Filter + score pod b against the CURRENT carry refs
            WITHOUT committing — the eval half of one_pod, reused by the
            multipod group body (where all mk pods run it against the
            group-start refs before any commit)."""
            t = tmpl_ref[b]
            # NOTHING big is hoisted out of the loop: values live across
            # iterations spill out of vector registers and the
            # spill/restore swamps the step (measured; see PERF_NOTES)
            lane_n = jax.lax.broadcasted_iota(jnp.int32, (1, Np), 1)
            valid_n = validn_ref[0:1, :]

            def trow(i):
                return stat_ref[pl.ds(t * SR + i, 1), :]

            static_mask = trow(0)
            raw_ipa = trow(1)
            cnt_taint = trow(2)
            cnt_nodeaff = trow(3)
            sc_image = trow(4)
            sc_avoid = trow(5)
            ipa_present = sm_t(t, 2 * R + 3)


            # ---- NodeResourcesFit (exact int32 after GCD rescale) ----
            mask_fit = fit_row(t)

            # ---- PTS filter (per-node counts; all C constraints as one
            # (C, Np) block — fewer dynamic reads, wider VPU ops) ----
            if "ptsf" in skip:
                fail_pts = jnp.zeros((1, Np), jnp.bool_)
            else:
                base = pl.multiple_of(t * CP, SUB)
                cntf = cntfn_ref[pl.ds(base, CP), :].astype(f32)   # (CP, Np)
                sameM = _sq_from_smem(sm_fsame, t, C, CP)          # (CP, CP)
                sh = jax.lax.dot_general(
                    sameM, cntf, (((1,), (0,)), ((), ())),
                    preferred_element_type=f32,
                    precision=jax.lax.Precision.HIGHEST)           # (CP, Np)
                reg = regrowf_ref[pl.ds(base, CP), :]
                big = f32(POS_BIG)
                min_c = jnp.min(jnp.where(reg != 0, sh, big),
                                axis=1, keepdims=True)             # (C, 1)
                min_c = jnp.where(min_c == big, f32(0.0), min_c)
                cnt_n = jnp.where(reg != 0, sh, f32(0.0))
                konn = konnf_ref[pl.ds(base, CP), :]
                vld = _col_tc(sm_tc, W_F_VALID, t, C, CP)      # (CP, 1)
                selfm = _col_tc(sm_tc, W_F_SELF, t, C, CP)
                maxskew = _col_tc(sm_tc, W_F_SKEW, t, C, CP)
                fail_missing = (vld != 0) & (konn == 0)
                skew = cnt_n + selfm - min_c
                fail_skew = (vld != 0) & (konn != 0) & (skew > maxskew)
                # axis-0 reduction via ones-dot (Mosaic can't lower
                # multi_reduction over the sublane axis here)
                onesC = jnp.ones((1, CP), f32)
                fail_pts = jax.lax.dot_general(
                    onesC, (fail_missing | fail_skew).astype(f32),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=f32) > 0                # (1, Np)

            # ---- InterPodAffinity: static parts + assumed-pod counts
            # (D1-D3 of the hoisted term machinery as gate-matrix dots
            # over the per-node ucnt carry; see _build_ipa) ----
            if dyn_ipa:
                ucf = ucnt_ref[:].astype(f32)                  # (UR, Np)
                pos = (ucnt_ref[:] > 0).astype(f32)
                # D1: assumed pods' anti terms repel this pod
                g1row = g1_ref[pl.ds(t, 1), :]                 # (1, UR)
                fail1 = doth(g1row, pos, (((1,), (0,)), ((), ()))) > 0
                fe_static = ipastat_ref[pl.ds(2 * t, 1), :]
                aff_allk = ipastat_ref[pl.ds(2 * t + 1, 1), :]
                base8 = pl.multiple_of(t * SUB, SUB)
                # D2: assumed pods vs this pod's own anti terms
                anti_dyn = doth(wanti_ref[pl.ds(base8, SUB), :], ucf,
                                (((1,), (0,)), ((), ())))      # (SUB, Np)
                a_stat = antic_ref[pl.ds(base8, SUB), :].astype(f32)
                akonn = antik_ref[pl.ds(base8, SUB), :]
                avld = _col_av(0, t)                           # (SUB, 1)
                onesS = jnp.ones((1, SUB), f32)
                fail_anti_rows = ((avld != 0) & (akonn != 0)
                                  & ((a_stat + anti_dyn) > 0)).astype(f32)
                fail_anti = doth(onesS, fail_anti_rows,
                                 (((1,), (0,)), ((), ()))) > 0  # (1, Np)
                # D3: assumed pods matching ALL of this pod's aff terms
                aff_dyn = doth(waff_ref[pl.ds(base8, SUB), :], ucf,
                               (((1,), (0,)), ((), ())))
                f_stat = affc_ref[pl.ds(base8, SUB), :].astype(f32)
                fvld = _col_av(1, t)
                miss_rows = ((fvld != 0)
                             & ((f_stat + aff_dyn) <= 0)).astype(f32)
                pods_missing = doth(onesS, miss_rows,
                                    (((1,), (0,)), ((), ()))) > 0
                kc0 = kcnt_ref[:, 0:1].astype(f32)             # (UR, 1)
                w3row = w3tot_ref[pl.ds(t, 1), :]
                at_dyn = jnp.sum(doth(w3row, kc0, (((1,), (0,)), ((), ()))))
                counts_empty = (sm_ipa_t(t, 2).astype(f32) + at_dyn) == 0
                has_aff = sm_ipa_t(t, 0)
                smatch = sm_ipa_t(t, 1)
                aff_ok = ((has_aff == 0)
                          | ((aff_allk != 0)
                             & (jnp.logical_not(pods_missing)
                                | (counts_empty & (smatch != 0)))))
                mask_ipa = (jnp.logical_not((fe_static != 0) | fail1)
                            & jnp.logical_not(fail_anti) & aff_ok)
            else:
                mask_ipa = jnp.ones((1, Np), jnp.bool_)

            feasible = ((static_mask != 0) & mask_fit
                        & jnp.logical_not(fail_pts) & mask_ipa
                        & (valid_n != 0))
            n_feasible = jnp.sum(feasible.astype(f32)).astype(jnp.int32)

            # ---- resource scores ----
            balanced, least = resource_rows(t)

            # ---- PTS score ----
            shasall = shasall_ref[pl.ds(t, 1), :]
            scored = feasible & (shasall != 0)
            ignored = feasible & (shasall == 0)
            scored_f32 = scored.astype(f32)
            n_scored = jnp.sum(scored_f32)
            # zone-presence among scored nodes, per key: (1, VZ) and its
            # per-node expansion — the ONLY matvecs in the step
            zp = []
            zpn = []
            for k in range(K) if "zp" not in skip else ():
                p = (dotn(scored_f32, k) > 0).astype(f32)
                zp.append(p)
                zpn.append(dotz(p, k))
            if "zp" in skip:
                zp = [jnp.zeros((1, VZ), f32)] * K
                zpn = [jnp.zeros((1, Np), f32)] * K
            zval_l = None  # (set in the vectorized score block)
            if "ptss" in skip:
                raw = jnp.zeros((1, Np), f32)
                have_s = jnp.int32(0)
            else:
                base = pl.multiple_of(t * CP, SUB)
                cnts = cntsn_ref[pl.ds(base, CP), :].astype(f32)   # (CP, Np)
                sameS = _sq_from_smem(sm_ssame, t, C, CP)
                sh = jax.lax.dot_general(
                    sameS, cnts, (((1,), (0,)), ((), ())),
                    preferred_element_type=f32,
                    precision=jax.lax.Precision.HIGHEST)           # (CP, Np)
                vld = _col_tc(sm_tc, W_S_VALID, t, C, CP)      # (CP, 1)
                perno = _col_tc(sm_tc, W_S_PERNO, t, C, CP)
                key = _col_tc(sm_tc, W_S_KEY, t, C, CP)
                first = _col_tc(sm_tc, W_S_FIRST, t, C, CP)
                sskew = _col_tc(sm_tc, W_S_SKEW, t, C, CP)
                have_s = (jnp.sum(
                    jax.lax.dot_general(
                        jnp.ones((1, CP), f32), vld,
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=f32)) > 0).astype(jnp.int32)
                zval_l = zvalid_ref[pl.ds(base, CP), :].astype(f32)  # (CP, VZ)
                zval_n = zvnode_ref[pl.ds(base, CP), :]              # (CP, Np)
                topo = jnp.zeros((CP, 1), f32)
                regn = jnp.zeros((CP, Np), f32)
                for k in range(K):
                    use = (jnp.logical_not(perno != 0)
                           & (key == k)).astype(f32)               # (C, 1)
                    topo = topo + use * jnp.sum(zp[k] * zval_l, axis=1,
                                                keepdims=True)
                    regn = regn + use * zpn[k]
                regn = regn * (zval_n != 0)
                topo_size = jnp.where(first != 0, topo, f32(0.0))
                weight = jnp.log(jnp.where(perno != 0, n_scored, topo_size)
                                 + f32(2.0))                       # (C, 1)
                cnt_n = jnp.where(perno != 0, sh,
                                  jnp.where(regn > 0, sh, f32(0.0)))
                konn = konns_ref[pl.ds(base, CP), :]
                term = jnp.where(
                    (vld != 0) & (konn != 0),
                    cnt_n * weight + (sskew - f32(1.0)),
                    f32(0.0))
                raw = jax.lax.dot_general(
                    jnp.ones((1, CP), f32), term,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=f32,
                    precision=jax.lax.Precision.HIGHEST)           # (1, Np)
            raw_i = raw.astype(jnp.int32)
            min_r = jnp.min(jnp.where(scored, raw_i, jnp.int32(POS_BIG)))
            max_r = jnp.max(jnp.where(scored, raw_i, jnp.int32(0)))
            min_r = jnp.where(min_r == POS_BIG, jnp.int32(0), min_r)
            norm = (MAX_NODE_SCORE * (max_r + min_r - raw_i)
                    // jnp.where(max_r == 0, jnp.int32(1), max_r))
            norm = jnp.where(max_r == 0, jnp.int32(MAX_NODE_SCORE), norm)
            norm = jnp.where(ignored, jnp.int32(0), norm)
            sc_pts = jnp.where(have_s != 0, norm, jnp.int32(0))

            # ---- IPA score: static raw + assumed-pod terms (D4+D5) ----
            if dyn_ipa:
                w45row = w45_ref[pl.ds(t, 1), :]
                dyn45 = doth(w45row, ucf, (((1,), (0,)), ((), ())))
                # the f32 dot ran on GCD-scaled weights (exactness needs
                # only sum|w/g| * count < 2^24); the int32 multiply
                # restores real magnitudes exactly
                raw_ipa = raw_ipa + dyn45.astype(jnp.int32) * sc[off_w45s]
                rowany = jnp.max(pos, axis=1, keepdims=True)   # (UR, 1)
                gp = gpres_ref[pl.ds(t, 1), :]
                pres_dyn = jnp.sum(
                    doth(gp, rowany, (((1,), (0,)), ((), ())))) > 0
                present = (ipa_present != 0) | pres_dyn
            else:
                present = ipa_present != 0

            # ---- IPA normalize ----
            min_i = jnp.min(jnp.where(feasible, raw_ipa, jnp.int32(POS_BIG)))
            max_i = jnp.max(jnp.where(feasible, raw_ipa, jnp.int32(NEG_BIG)))
            diff = (max_i - min_i).astype(f32)
            ipa = jnp.where(
                diff > 0,
                (MAX_NODE_SCORE * ((raw_ipa - min_i).astype(f32)
                                   / jnp.where(diff > 0, diff, f32(1.0))))
                .astype(jnp.int32),
                jnp.zeros((1, Np), jnp.int32))
            ipa = jnp.where(present, ipa, jnp.zeros((1, Np), jnp.int32))

            # ---- default-normalized taint / node-affinity ----
            def norm_default(counts, reverse):
                mx = jnp.max(jnp.where(feasible, counts, jnp.int32(0)))
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

            # first-max (jnp.argmax tie semantics; exact — scores < 2^24)
            tf = total.astype(f32)
            m = jnp.max(tf)
            idx = jnp.where(tf >= m, lane_n, jnp.int32(POS_BIG))
            best = jnp.min(idx).astype(jnp.int32)
            ok = (m >= 0) & (b < breal_ref[0])
            wbl = balanced * W["balanced"] + least * W["least"]
            return t, lane_n, best, m, ok, n_feasible, total, wbl

        def one_pod(b):
            if mode == "apply":
                # forced decision (the cross-shard winner, mapped to this
                # shard's local lanes or -1): updates only, no eval
                t = tmpl_ref[b]
                lane_n = jax.lax.broadcasted_iota(jnp.int32, (1, Np), 1)
                best = forced_ref[2 * b]
                oki = forced_ref[2 * b + 1]
                okf = oki.astype(f32)
                _apply_updates(b, t, lane_n, best, oki, okf)
                return jnp.int32(0)
            t, lane_n, best, m, ok, n_feasible, total, wbl = eval_pod(b)
            oki = ok.astype(jnp.int32)
            okf = oki.astype(f32)

            if "updates" in skip or mode == "eval":
                # eval-only: best/score/feasible out, carries untouched
                # (the sharded session applies the GLOBAL decision in a
                # separate "apply" launch after the cross-shard argmax)
                subi0 = jax.lax.broadcasted_iota(jnp.int32, (SUB, Bp), 0)
                lanei0 = jax.lax.broadcasted_iota(jnp.int32, (SUB, Bp), 1)
                at_b0 = lanei0 == b
                o = out_ref[:]
                o = jnp.where(at_b0 & (subi0 == 0),
                              jnp.where(ok, best, jnp.int32(-1)), o)
                o = jnp.where(at_b0 & (subi0 == 1),
                              jnp.where(ok, m.astype(jnp.int32),
                                        jnp.int32(-1)), o)
                o = jnp.where(at_b0 & (subi0 == 2), n_feasible, o)
                out_ref[:] = o
                return jnp.int32(0)
            _apply_updates(b, t, lane_n, best, oki, okf)

            subi = jax.lax.broadcasted_iota(jnp.int32, (SUB, Bp), 0)
            lanei = jax.lax.broadcasted_iota(jnp.int32, (SUB, Bp), 1)
            at_b = lanei == b
            o = out_ref[:]
            o = jnp.where(at_b & (subi == 0),
                          jnp.where(ok, best, jnp.int32(-1)), o)
            o = jnp.where(at_b & (subi == 1),
                          jnp.where(ok, m.astype(jnp.int32), jnp.int32(-1)),
                          o)
            o = jnp.where(at_b & (subi == 2), n_feasible, o)
            out_ref[:] = o

        def write_multi(b, best, score, nfeas, okc, flag):
            """Out rows for one multipod-group pod: 0 best / 1 score /
            2 n_feasible / 3 conflict-suffix flag (1 = NOT committed,
            host must replay)."""
            subi = jax.lax.broadcasted_iota(jnp.int32, (SUB, Bp), 0)
            lanei = jax.lax.broadcasted_iota(jnp.int32, (SUB, Bp), 1)
            at_b = lanei == b
            placed = okc != 0
            o = out_ref[:]
            o = jnp.where(at_b & (subi == 0),
                          jnp.where(placed, best, jnp.int32(-1)), o)
            o = jnp.where(at_b & (subi == 1),
                          jnp.where(placed, score, jnp.int32(-1)), o)
            o = jnp.where(at_b & (subi == 2), nfeas, o)
            o = jnp.where(at_b & (subi == 3), flag, o)
            out_ref[:] = o

        def multi_group(j, seen):
            """mk pods per step: parallel-in-spirit evals against the
            group-start carry refs (commits are DEFERRED, so nothing a
            later eval reads has moved), then in-order commits gated by
            the exact conflict test. `seen` carries the suffix flag
            ACROSS groups: later groups' evals chained on a carry
            missing suffix commits are invalid too."""
            base = j.astype(jnp.int32) * jnp.int32(mk)
            evs = [eval_pod(base + jnp.int32(i)) for i in range(mk)]
            conf_seen = seen
            committed = []  # (best, okc, tmpl) of this group's prefix
            for i in range(mk):
                b = base + jnp.int32(i)
                t, lane_n, best, m, ok, nfeas, total, wbl = evs[i]
                score_i = jnp.max(total)  # int32 twin of the f32 argmax m
                conf = jnp.int32(0)
                if i > 0:
                    gate_f = lane_gate(W_F_VALID, t)
                    gate_s = lane_gate(W_S_VALID, t)
                for e, (be, oke, te) in enumerate(committed):
                    same = oke * ((be == best)
                                  & (m >= 0)).astype(jnp.int32)
                    # PTS: pod e's Mf/Ms lanes of template t, valid-gated
                    mf_e = mf_ref[pl.ds(base + jnp.int32(e), 1),
                                  :].astype(f32)
                    ms_e = ms_ref[pl.ds(base + jnp.int32(e), 1),
                                  :].astype(f32)
                    hit = (jnp.sum(mf_e * gate_f)
                           + jnp.sum(ms_e * gate_s)) > 0
                    conf = jnp.maximum(conf, jnp.maximum(
                        same, oke * hit.astype(jnp.int32)))
                    if ur > 0:
                        # IPA template-interference superset (gmat)
                        grow = gmat_ref[pl.ds(te, 1), :]
                        lanei1 = jax.lax.broadcasted_iota(
                            jnp.int32, (1, LANE), 1)
                        gv = jnp.sum(jnp.where(lanei1 == t, grow,
                                               f32(0.0)))
                        conf = jnp.maximum(
                            conf, oke * (gv > 0).astype(jnp.int32))
                # utilization legs (kernel.multipod_utilization_conflicts
                # mirrored in Mosaic): fit/balanced/least are the only
                # carry-reading plugins left once the count gates are
                # clean — recheck them against the CURRENT refs
                fit_new = fit_row(t)
                bal2, least2 = resource_rows(t)
                new_tot = total - wbl + (bal2 * W["balanced"]
                                         + least2 * W["least"])
                feas_old = total >= 0
                flip = jnp.max(jnp.where(
                    feas_old & jnp.logical_not(fit_new),
                    f32(1.0), f32(0.0))) > 0
                over = jnp.max(jnp.where(
                    feas_old & fit_new
                    & ((new_tot > score_i)
                       | ((new_tot == score_i) & (lane_n < best))),
                    f32(1.0), f32(0.0))) > 0
                util = (flip | (over & (m >= 0))).astype(jnp.int32)
                conf = jnp.maximum(conf, util)
                conf = conf * (b < breal_ref[0]).astype(jnp.int32)
                conf_seen = jnp.maximum(conf_seen, conf)
                okc = ok.astype(jnp.int32) * (jnp.int32(1) - conf_seen)
                _apply_updates(b, t, lane_n, best, okc, okc.astype(f32))
                committed.append((best, okc, t))
                write_multi(b, best, score_i, nfeas, okc, conf_seen)
            return conf_seen

        if mode == "full" and mk > 1 and "updates" not in skip:
            jax.lax.fori_loop(0, Bp // mk, multi_group, jnp.int32(0))
            return

        # manual unroll: U pods per loop iteration amortizes Mosaic's
        # per-iteration bookkeeping (the marginal-cost floor; partial
        # `unroll=` is unsupported by the TPU lowering). b >= B_real
        # iterations are no-ops via the ok gate.
        U = int(_knobs.get_int("KTPU_PALLAS_GROUP"))
        while Bp % U:
            U //= 2

        def body(j, _):
            base = j.astype(jnp.int32) * jnp.int32(U)
            for i in range(U):
                one_pod(base + jnp.int32(i))
            return jnp.int32(0)

        jax.lax.fori_loop(0, Bp // U, body, jnp.int32(0))

    return kernel


def _sq_from_smem(sm_pair, t, C, CP):
    """(CP, CP) f32 same-key matrix from SMEM scalars.

    Built as a sum of scalar x static-one-hot constants — Mosaic cannot
    shape-cast stacked scalars into 2D."""
    i0 = jax.lax.broadcasted_iota(jnp.int32, (CP, CP), 0)
    i1 = jax.lax.broadcasted_iota(jnp.int32, (CP, CP), 1)
    out = jnp.zeros((CP, CP), jnp.float32)
    for ci in range(C):
        for cj in range(C):
            e = ((i0 == ci) & (i1 == cj)).astype(jnp.float32)
            out = out + sm_pair(t, ci, cj).astype(jnp.float32) * e
    return out


def _col_tc(sm_tc, which, t, C, CP):
    """(CP, 1) f32 column of per-(t, c) SMEM scalars (one-hot sums)."""
    i0 = jax.lax.broadcasted_iota(jnp.int32, (CP, 1), 0)
    out = jnp.zeros((CP, 1), jnp.float32)
    for cc in range(C):
        e = (i0 == cc).astype(jnp.float32)
        out = out + sm_tc(which, t, cc).astype(jnp.float32) * e
    return out


def _stack_tc(sm_tc, which, T, C, TCp):
    """(TCp, 1) f32 from per-(t,c) SMEM scalars (one-hot sums)."""
    CP = TCp // T
    i0 = jax.lax.broadcasted_iota(jnp.int32, (TCp, 1), 0)
    out = jnp.zeros((TCp, 1), jnp.float32)
    for t in range(T):
        for cc in range(C):
            e = (i0 == (t * CP + cc)).astype(jnp.float32)
            out = out + (sm_tc(which, t, cc) != 0).astype(jnp.float32) * e
    return out


# the kernel's VMEM statics, in operand order (after the SMEM scalars)
_VMEM_STATICS = (
    "alloc", "stat", "onehot", "regrow_f", "zvalid_node_s", "zvalid_s",
    "konn_f", "konn_s", "shasall", "valid_n", "rowt", "eye", "prow_f",
    "prow_s", "gmat",
)


def _kernel_vmem_bytes(statics: Dict, ipa: Optional[Dict], carry: Dict,
                       Bp: int) -> int:
    """Bytes one launch keeps in VMEM: the statics, the carries twice
    (input refs and the aliased output refs both exist in the kernel),
    the widened match rows and the result rows."""
    def nbytes(x):
        return math.prod(x.shape) * np.dtype(x.dtype).itemsize

    return (sum(nbytes(statics[k]) for k in _VMEM_STATICS)
            + sum(nbytes(v) for v in (ipa or {}).values())
            + 2 * sum(nbytes(v) for v in carry.values())
            + 2 * Bp * LANE * 4 + SUB * Bp * 4)


def _vmem_request(operand_bytes: int) -> int:
    """What the kernel asks the compiler for: its operands plus room for
    the step's (CP, Np) temporaries, which grow with the node axis like
    the operands do. At 5000 nodes ~10 MB of operands compile under the
    default 16 MiB scope, so half again plus 16 MiB is generous."""
    return operand_bytes + operand_bytes // 2 + (16 << 20)


def _vmem_cap() -> int:
    """7/8 of the VMEM the core reports (XLA keeps the rest)."""
    cap = pltpu.get_tpu_info().vmem_capacity_bytes
    return cap - cap // 8


def _vmem_limit(operand_bytes: int) -> int:
    """Scoped-VMEM limit for the grid-less kernel: the compiler's default
    scope (16 MiB on a v5e) is a ceiling on cluster size, not on the
    chip, so the launch names what it needs."""
    return min(_vmem_cap(), max(32 << 20, _vmem_request(operand_bytes)))


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("carry",))
def _dispatch(cfg: "_Cfg", statics: Dict, ipa: Optional[Dict],
              meta, carry: Dict, match, forced=None):
    # meta = [B_real | tmpl] (int32), match = [mfT | msT] (int8): the
    # whole per-batch payload in two transfers — the split happens here
    # on-device. B_real stays a DYNAMIC (SMEM) scalar: variable batch
    # lengths must not recompile the kernel (only the padded width Bp is
    # static). The cluster statics arrive as DYNAMIC pytree args, NOT
    # via the static cfg: baking them in as trace constants made every
    # session rebuild a fresh program (different constants -> jit cache
    # miss AND persistent-cache miss) — the 20-30s "warm" rebuild the
    # churn workload paid mid-window. cfg hashes by VALUE, so two
    # sessions with the same shapes share one compiled program.
    Bp = int(meta.shape[0]) - 1
    B_real = meta[:1]
    tmpl = meta[1:]
    kernel = _build_kernel(cfg.shapes, cfg.weights, Bp, cfg.ur,
                           mode=cfg.mode, mk=cfg.mk)
    # widen the int8 wire format on-device (i8 VMEM rows would need
    # 32-sublane alignment in the kernel; one cheap convert avoids that)
    mfT = match[:, :LANE].astype(jnp.int32)
    msT = match[:, LANE:].astype(jnp.int32)
    carry_keys = cfg.carry_keys
    carry_in = [carry[k] for k in carry_keys]
    ipa_in = []
    if ipa is not None:
        ipa_in = [ipa[k] for k in
                  ("ipa_stat", "anti_static", "anti_konn", "aff_static",
                   "prow_ipa", "g1", "wanti", "waff", "w3tot", "w45",
                   "gpres")]
    out_shape = (
        jax.ShapeDtypeStruct((SUB, Bp), jnp.int32),
        *[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in carry_in],
    )
    vm = pl.BlockSpec(memory_space=pltpu.VMEM)
    sm = pl.BlockSpec(memory_space=pltpu.SMEM)
    pre_args: tuple = ()
    pre_specs: list = []
    if cfg.mode == "apply":
        pre_args = (forced.astype(jnp.int32),)
        pre_specs = [sm]
    n_pre = len(pre_specs) + 20 + len(ipa_in)  # inputs before the carries
    vmem_args = (mfT, msT, *(statics[k] for k in _VMEM_STATICS), *ipa_in,
                 *carry_in)
    compiler_params = None
    if not cfg.interpret:
        compiler_params = pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(
                _kernel_vmem_bytes(statics, ipa, carry, Bp)))
    # trace the kernel with x64 OFF: every input is explicitly 32-bit,
    # and weak python literals must not widen ops to i64/f64 (Mosaic has
    # no 64-bit types)
    with jax.enable_x64(False):
        results = pl.pallas_call(
            kernel,
            out_shape=out_shape,
            in_specs=(pre_specs + [sm, sm, sm, vm, vm] + [vm] * 15
                      + [vm] * len(ipa_in) + [vm] * len(carry_in)),
            out_specs=tuple([vm] * (1 + len(carry_in))),
            input_output_aliases={n_pre + i: 1 + i
                                  for i in range(len(carry_in))},
            interpret=cfg.interpret,
            compiler_params=compiler_params,
        )(*pre_args, B_real, tmpl, statics["scalars"], *vmem_args)
    return results[0], dict(zip(carry_keys, results[1:]))
