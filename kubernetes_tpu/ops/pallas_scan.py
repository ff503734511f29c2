"""Pallas mega-kernel for the scheduling session: the WHOLE batch scan
runs as ONE kernel launch, and a pod spec is a ROW of device-resident
tables that a live session admits without a rebuild and without a compile.

Why one launch: the lax.scan step compiles to dozens of fusions, each
launched afresh on every scan iteration. Inside one pallas kernel the
per-op cost is VPU cycles, so a fori_loop over pods turns 1024 steps x
~25 launches into ONE launch.

Why a table: a cluster holds hundreds to thousands of Deployments, each
its own labels, selector and requests, so every Deployment is a spec of
its own. Nothing in the compiled program depends on how many specs are
live or which a launch carries; the shapes are capacities:

- `spec` (SMEM, [Tcap * W] int32): one record of W scalars per spec —
  rescaled requests, the ids of its static rows, per-constraint flags
  and row ids, and (offset, length) of its lists in `pool`. A pod names
  its record (`tmpl[b]`), the kernel reads scalars at `t * W + field`.
- `pool` (SMEM, int32): the variable-length lists, append-only. A
  spec's TOUCH list says which count rows a pod of that spec counts
  toward — entries (count row, pair-id row, weight, eligibility row):
  "which rows this pod counts toward" is data, not an identity assumed
  from the deployment (a pod's labels may match another spec's selector).
- `srow` (VMEM, [SRcap, Np] int32): every per-node static row (feasibility
  mask, static scores, topology pair ids, key-on-node flags, static term
  counts), interned by content: 512 Deployments on one node shape share a
  handful of rows. `zrow` ([ZRcap, 128]) likewise for per-zone rows.
- `cnt` (VMEM carry, [RCcap, Np] int32): the count rows. Every row is
  owned by its READER — (spec, spread constraint) or (spec, affinity
  term / repel key / score) — starts at what the prologue counted in the
  cluster snapshot of the reader's admission (spread) or at zero (terms:
  the snapshot part lives in the reader's static rows), and is
  incremented by the pods on its writers' touch lists. Reader-owned rows
  are what makes late admission exact: no row is shared between readers
  admitted against different snapshots, so nothing is counted twice.

The kernel reads and writes single rows at dynamic offsets
(`ref[pl.ds(row, 1), :]`), loops over a spec's lists with dynamic trip
counts, and skips whole sections (`lax.cond`) for specs that have no such
constraint. Admission (PallasSession.admit) runs the hoisted prologue on
the new specs only — on the host, against the encoding's host arrays —
interns their rows, appends touch entries to every writer, and writes the
changed rows/records to the device: the session object, its executables
and its carry stay.

Design notes (vs ops/hoisted.py _step, whose semantics this mirrors):

- **int64-free**: Mosaic has no 64-bit types. Resource quantities are
  rescaled per dimension by the GCD of every value in the session —
  EXACT: fit comparisons, least-allocated's `(cap-req)*100 // cap` and
  balanced's fractions are invariant under a common rescale. A spec
  whose requests the live GCD does not divide refines it in place (one
  elementwise launch multiplies the utilization rows).
- **BalancedAllocation is exact**: the reference computes
  `int((1 - |c/C - m/M|) * 100)` in float64. With irregular requests an
  f32 evaluation lands on the other side of an integer a few times in
  ten thousand node states. The kernel takes the exact rational floor in
  int32 and then reproduces float64's own rounding at the only states
  where the two can differ — the states whose exact value IS an integer
  — from a short host-enumerated list (see _balanced_quirks).
- **two widths of that arithmetic**: where every rescaled value times
  101, and 100 * C * M, fit int32 (one node shape, round requests) the
  scores are plain int32 divisions. A cluster of real node pools
  reports allocatable in Ki (GCD 1 Ki: capacities of 10^8 units); there
  the session is built WIDE (`_Cfg.wide`): a float32 estimate of each
  score, corrected by one exact comparison in 15-bit limbs
  (_least_wide, _balanced_wide), for every value below POS_BIG.
- **gather-free spread counts**: per-node count rows; zone expansion and
  scored-set registration are MXU matvecs against a static one-hot.
- **InterPodAffinity's normalization is exact**: upstream truncates
  100 * float64((s - min) / (max - min)); the kernel takes the exact
  floor in int32 and reads one less at the three whole values where
  float64 does (ipa_normalize), for every difference below 2^30; a
  session whose raw scores could pass that is refused (_score_bound).
- float64 score math that remains (a per-node PTS weight) runs in
  float32; parity is pinned by tests.
- first-max tie-break (min index among maxima) is explicit — Mosaic's
  argmax lane order is unspecified.

The mesh path (ops/sharded_scan.py) still runs the dense per-template
layout (ops/dense_remap.py): it admits nothing and rebuilds on a new
spec, as the jnp HoistedSession does.

Reference frame: same as ops/hoisted.py — this replaces
findNodesThatPassFilters + RunScorePlugins (generic_scheduler.go:235,
framework.go:723) for batchable pending pods of any mix of specs,
restructured as a single accelerator program.
"""

from __future__ import annotations

import fractions
import functools
import logging
import math
import threading
import time as _time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hoisted import (
    _eval_reqs_batch_np,
    _session_prologue,
    _stack_templates,
    batch_bucket,
    template_fingerprint,
    templates_have_ports,
    templates_have_terms,
)
from ..utils import tracing
from .kernel import DEFAULT_WEIGHTS, MAX_NODE_SCORE

VZ = 128          # compact pair-value lanes per shared-value key
LANE = 128
SUB = 8
POS_BIG = 2 ** 30
NEG_BIG = -(2 ** 30)

CARRY_KEYS = ("requested", "nzpc", "cnt")
ADMIT_CHUNK = 8     # specs per prologue launch (one compiled shape)
WRITE_CHUNK = 64    # rows per table write (one compiled shape per table)
MAX_QUIRKS = 1024   # balanced float64-quirk states the kernel can list
# the narrow form's bounds: (cap - req) * 100 and 100 * C * M in int32
NARROW_MAX = (2 ** 31 - 1) // (MAX_NODE_SCORE + 1)
# float64's balanced score errs by at most 500 ulps of 1 (five roundings
# of values <= 100); a state whose exact value is not an integer lies at
# least 1 / (C * M) from one, so below this product float64 truncates to
# the exact floor everywhere but at the whole states
BAL_F64_MAX = 1 << 44
# whole states _balanced_quirks may enumerate for one capacity pair
QUIRK_SOLVE_MAX = 1 << 22
LIMB = 15
LIMB_MASK = (1 << LIMB) - 1
# PodTopologySpread's raw score is int(float64(count) * log(size + 2))
# (scoring.go). With a zone key the count is every matching pod of a zone:
# tens of thousands, where a float32 product reads one off at a count in
# ~300 (and the normalised score with it, once zones stand apart by more
# than a pod: a drained node, a scale-down). The kernel multiplies in
# int32 limbs instead: the float64 weight of each size as an exact
# integer W = log(size + 2) * 2**53, in five limbs of 11 bits (the first
# 12: three integer bits), listed after the balanced quirks in the `bad`
# scalars. count * limb < 2**30 holds counts below PTS_MAX_COUNT.
PTS_LIMBS = 5
# a quirk is (group, key) narrow and (group, c, m) wide, where the key
# c * (M + 1) + m would pass int32
PTS_BASE = 1 + 2 * MAX_QUIRKS          # where the limbs start in `bad`
PTS_BASE_WIDE = 1 + 3 * MAX_QUIRKS
PTS_MAX_COUNT = 1 << 18
# InterPodAffinity's NormalizeScore is int64(100 * (float64(s - min) /
# float64(max - min))). Where 100 * (s - min) / (max - min) is no whole
# number it lies at least 1 / (max - min) from one, far above float64's
# error: the truncation is the exact floor. Where it is a whole number k,
# (s - min) / (max - min) == k / 100 exactly, so float64 reads what it
# reads at k / 100 alone: one less at these k (0.29 * 100 = 28.99...).
IPA_F64_LOW = tuple(k for k in range(MAX_NODE_SCORE + 1)
                    if int(MAX_NODE_SCORE * (k / MAX_NODE_SCORE)) < k)
# the exact form holds for differences below this (15-bit limbs)
IPA_EXACT_MAX = POS_BIG


def ipa_normalize(d, diff):
    """InterPodAffinity's normalized score, upstream's float64 expression
    bit for bit, in int32: 0 where diff <= 0, else for 0 <= d <= diff <
    IPA_EXACT_MAX the floor k of 100 * d / diff, one less where float64
    reads one less (IPA_F64_LOW). A float32 quotient gives k to within
    one; the exact sign of 100 * d - q * diff, in 15-bit limbs, settles
    it. Runs in the table kernel and in the mesh's (ops/sharded_scan.py)."""
    i32, f32 = jnp.int32, jnp.float32
    b = jnp.maximum(diff, i32(1))
    est = (MAX_NODE_SCORE * (d.astype(f32) / b.astype(f32))).astype(i32)
    est = jnp.clip(est, i32(0), i32(MAX_NODE_SCORE))
    dh, dl = d >> 15, d & i32(0x7FFF)
    bh, bl = b >> 15, b & i32(0x7FFF)

    def excess(q):
        """100 * d - q * b as (hi, lo), lo in [0, 2^15): it is >= 0 iff
        hi >= 0, and 0 iff both are."""
        lo = i32(MAX_NODE_SCORE) * dl - q * bl
        hi = i32(MAX_NODE_SCORE) * dh - q * bh + (lo >> 15)
        return hi, lo & i32(0x7FFF)

    (dn_hi, dn_lo), (at_hi, at_lo), (up_hi, up_lo) = (
        excess(est + i32(step)) for step in (-1, 0, 1))
    k = est - 1 + (at_hi >= 0).astype(i32) + (up_hi >= 0).astype(i32)
    whole = (((k < est) & (dn_hi == 0) & (dn_lo == 0))
             | ((k == est) & (at_hi == 0) & (at_lo == 0))
             | ((k > est) & (up_hi == 0) & (up_lo == 0)))
    low = jnp.zeros(jnp.shape(k), jnp.bool_)
    for v in IPA_F64_LOW:
        low = low | (k == v)
    return jnp.where(diff <= 0, i32(0), k - (whole & low).astype(i32))


@functools.lru_cache(maxsize=None)
def _spread_limbs() -> np.ndarray:
    """[VZ + 1, 5] int32: limbs of log(size + 2) * 2**53, most
    significant first, for size 0..VZ (read-only: one table a process)."""
    out = np.zeros((VZ + 1, PTS_LIMBS), np.int32)
    for size in range(VZ + 1):
        w = int(fractions.Fraction(math.log(size + 2)) * (1 << 53))
        out[size] = [w >> 44] + [(w >> sh) & 2047 for sh in (33, 22, 11, 0)]
    out.setflags(write=False)
    return out


def spread_raw_exact(count: np.ndarray, size: int) -> np.ndarray:
    """What the kernel computes for a zone constraint, in numpy: the
    limb product below, floor(count * log(size + 2)) without a float."""
    k = _spread_limbs()[size].astype(np.int64)
    c = np.asarray(count, np.int64)
    acc = c * k[4]
    for j in (3, 2, 1, 0):
        acc = c * k[j] + (acc >> 11)
    return acc >> 9


# ---------------------------------------------------------------------------
# the wide form's resource scores: exact in int32 for values below POS_BIG
#
# Each score is first estimated in float32, whose error (a few 1e-5 on a
# value of at most 100) is far below one half: rounding the estimate
# gives an integer r within one of the answer, and ONE exact comparison
# of two products of up to 67 bits says which side of r the exact value
# lies. The products are taken in limbs of 15 bits, so that every
# partial product and every carried sum stays inside int32. Both
# functions run inside the kernel and, for tests, on plain jnp arrays.


def _split(x):
    return x >> LIMB, x & LIMB_MASK


def _least_wide(cap, req):
    """LeastAllocated of one resource, `(cap - req) * 100 // cap` (0 where
    cap is 0 or req passes it), for 0 <= cap, req < POS_BIG."""
    i32, f32 = jnp.int32, jnp.float32
    ok = (cap > 0) & (req <= cap)
    cap = jnp.where(ok, cap, i32(1))
    d = jnp.where(ok, cap - req, i32(0))
    r = (d.astype(f32) / cap.astype(f32) * f32(MAX_NODE_SCORE)
         + f32(0.5)).astype(i32)
    # 100 * d - r * cap < 0: the estimate rounded up
    d1, d0 = _split(d)
    k1, k0 = _split(cap)
    lo = MAX_NODE_SCORE * d0 - r * k0
    hi = MAX_NODE_SCORE * d1 - r * k1 + (lo >> LIMB)
    return jnp.where(ok, r - (hi < 0).astype(i32), i32(0))


def _balanced_wide(c, m, C, M, full):
    """(balanced, whole): BalancedAllocation's exact rational floor of
    (1 - |c/C - m/M|) * 100, and whether that value is an integer, for
    0 <= c < C < POS_BIG and 0 <= m < M < POS_BIG (0 and False where
    `full`)."""
    i32, f32 = jnp.int32, jnp.float32
    C = jnp.where(full, i32(1), C)
    M = jnp.where(full, i32(1), M)
    c = jnp.where(full, i32(0), c)
    m = jnp.where(full, i32(0), m)
    # r = round(z), z = 100 * |cM - mC| / (CM), the balanced score 100 -
    # ceil(z)
    z = jnp.abs(c.astype(f32) / C.astype(f32) - m.astype(f32) / M.astype(f32))
    r = (z * f32(MAX_NODE_SCORE) + f32(0.5)).astype(i32)
    c1, c0 = _split(c)
    m1, m0 = _split(m)
    C1, C0 = _split(C)
    M1, M0 = _split(M)
    # S = cM - mC = s2 * 2^30 + s1 * 2^15 + s0, s1 and s0 in [0, 2^15)
    t0 = c0 * M0 - m0 * C0
    t1 = (c1 * M0 - m1 * C0) + (c0 * M1 - m0 * C1) + (t0 >> LIMB)
    s2 = c1 * M1 - m1 * C1 + (t1 >> LIMB)
    s1, s0 = t1 & LIMB_MASK, t0 & LIMB_MASK
    # |S| in four limbs
    neg = s2 < 0
    a0 = jnp.where(neg, -s0, s0)
    a1 = jnp.where(neg, -s1, s1) + (a0 >> LIMB)
    a2 = jnp.where(neg, -s2, s2) + (a1 >> LIMB)
    a3, a2 = _split(a2)
    a1, a0 = a1 & LIMB_MASK, a0 & LIMB_MASK
    # C * M in four limbs
    p0 = C0 * M0
    p1 = C1 * M0 + C0 * M1 + (p0 >> LIMB)
    p2 = C1 * M1 + (p1 >> LIMB)
    p3, p2 = _split(p2)
    p1, p0 = p1 & LIMB_MASK, p0 & LIMB_MASK
    # X = 100 * |S| - r * C * M: its sign says z > r, z == r or z < r
    x0 = MAX_NODE_SCORE * a0 - r * p0
    x1 = MAX_NODE_SCORE * a1 - r * p1 + (x0 >> LIMB)
    x2 = MAX_NODE_SCORE * a2 - r * p2 + (x1 >> LIMB)
    x3 = MAX_NODE_SCORE * a3 - r * p3 + (x2 >> LIMB)
    low = ((x2 & LIMB_MASK) | (x1 & LIMB_MASK) | (x0 & LIMB_MASK)) != 0
    above = (x3 > 0) | ((x3 == 0) & low)
    whole = jnp.logical_not(full) & (x3 == 0) & jnp.logical_not(low)
    balanced = MAX_NODE_SCORE - r - above.astype(i32)
    return jnp.where(full, i32(0), balanced), whole


TOUCH_W = 4        # words per touch entry: count row, pair row, weight, src row
# pods per kernel loop iteration: a manual unroll that amortizes Mosaic's
# per-iteration bookkeeping (partial `unroll=` is unsupported by the TPU
# lowering)
POD_GROUP = 4

_MISSING = object()  # exec-cache sentinel (None = AOT failed, use jit)

logger = logging.getLogger(__name__)


class PallasUnsupported(Exception):
    """This cluster/spec shape can't ride the pallas path; callers fall
    back to the jnp HoistedSession.

    `reason` is a FIXED slug per raise site (no interpolated shape
    numbers) — it feeds the scheduler_tpu_session_builds_total metric's
    reason label, where unbounded values would mint unbounded series."""

    def __init__(self, message: str, reason: str = "other"):
        super().__init__(message)
        self.reason = reason


class TableFull(PallasUnsupported):
    """A live session cannot admit this spec (a capacity is used up, or
    the spec's arrays have another shape than the table's): the backend
    rebuilds, counted under `reason`."""


def _ceil(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


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


def _host_prologue(cluster: Dict, arrays: List[Dict], dyn_ipa: bool) -> Dict:
    """The hoisted prologue (ops/hoisted.py _prologue) for a chunk of
    specs, run on the HOST's own XLA device against the encoding's host
    arrays, outputs as numpy.

    The prologue is an int64/float64 program over every pod row of the
    encoding. The chip has neither type: there it compiled for minutes
    cold (emulated 64-bit) and wanted the whole encoding uploaded
    (~160 MB at 120 000 pod rows) before each use. The host has both
    types and already holds the arrays; a chunk of 8 specs takes ~0.2 s
    and an admission inside a window moves nothing to the chip but the
    rows it wrote."""
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:   # a process held to one platform: use that one
        cpu = jax.devices()[0]
    with jax.default_device(cpu):
        S = _session_prologue(
            {k: np.asarray(v) for k, v in cluster.items()},
            _stack_templates(arrays), dyn_ipa=dyn_ipa)
        return {k: np.asarray(v) for k, v in S.items()}


# ---------------------------------------------------------------------------
# BalancedAllocation: where float64 and the exact rational floor part ways

_QUIRK_CACHE: Dict[Tuple[int, int], Optional[np.ndarray]] = {}


def _whole_states(C: int, M: int) -> Optional[np.ndarray]:
    """[(c, m)] of every state 0 <= c < C, 0 <= m < M at which the exact
    balanced value 100 * (1 - |cM - mC| / CM) is an integer, 100 - |k|;
    None where there are more than QUIRK_SOLVE_MAX.

    With G = gcd(C, M), C = G C', M = G M' and L = G C' M' (the lcm),
    100 (cM - mC) = k CM says c M' - m C' = k L / 100: k a multiple of
    100 / gcd(100, L), and for each such k a linear congruence
    c M' = k L / 100 (mod C'), solved by c = c_k + j C' (j < G), m
    following from c. At most 199 G states, however large C and M are."""
    G = math.gcd(C, M)
    Cp, Mp = C // G, M // G
    L = G * Cp * Mp
    step = MAX_NODE_SCORE // math.gcd(MAX_NODE_SCORE, L)
    ks = range(-((MAX_NODE_SCORE - 1) // step) * step, MAX_NODE_SCORE, step)
    if len(ks) * G > QUIRK_SOLVE_MAX:
        return None
    inv = pow(Mp, -1, Cp) if Cp > 1 else 0
    j = np.arange(G, dtype=np.int64)
    out = []
    for k in ks:
        u = k * L // MAX_NODE_SCORE
        c = (u * inv) % Cp + Cp * j if Cp > 1 else j
        m = (c * Mp - u) // Cp
        ok = (m >= 0) & (m < M)
        out.append(np.stack([c[ok], m[ok]], axis=1))
    return np.concatenate(out) if out else np.zeros((0, 2), np.int64)


def _balanced_quirks(cap_c: int, cap_m: int) -> Optional[np.ndarray]:
    """[(c, m)] of the node states (non-zero requested cpu c < cap_c and
    memory m < cap_m, pod included, in rescaled units) at which the
    reference's float64 `int((1 - |c/C - m/M|) * 100)` reads ONE LESS than
    the exact rational floor; None where they cannot be listed (more
    whole states than QUIRK_SOLVE_MAX, or C * M past BAL_F64_MAX).

    float64 carries ~1e-16 of relative error and the exact value's
    distance to the next integer is at least 1/(C*M) unless it IS an
    integer, so below BAL_F64_MAX the two can differ only at
    exact-integer states (checked over whole grids in
    tests/test_pallas_table.py). Those states solve a linear congruence
    (_whole_states); float64 is evaluated there, here, the way the
    reference evaluates it."""
    key = (int(cap_c), int(cap_m))
    if key in _QUIRK_CACHE:
        return _QUIRK_CACHE[key]
    C, M = key
    out = np.zeros((0, 2), np.int64)
    if C > 0 and M > 0:
        p = _whole_states(C, M) if C * M < BAL_F64_MAX else None
        if p is None:
            out = None
        elif len(p):
            cf = p[:, 0] / np.float64(C)
            mf = p[:, 1] / np.float64(M)
            f64 = ((1.0 - np.abs(cf - mf)) * MAX_NODE_SCORE).astype(np.int64)
            # 100 * |cM - mC| < 2^51: int64 holds the exact value
            exact = MAX_NODE_SCORE - (
                MAX_NODE_SCORE * np.abs(p[:, 0] * M - p[:, 1] * C) // (C * M))
            if (np.abs(f64 - exact) > 1).any() or (f64 > exact).any():
                raise PallasUnsupported(
                    "float64 balanced score strays from the exact floor "
                    "by more than its last unit", reason="balanced-float64")
            out = p[f64 != exact]
    _QUIRK_CACHE[key] = out
    return out


# ---------------------------------------------------------------------------
# the spec record


class _Layout(NamedTuple):
    """Field offsets inside one spec record (W int32 words)."""

    REQ: int
    CHK: int
    HAS: int
    NZ: int
    IPAP: int    # the spec reads an IPA score (static or a score row)
    STAT: int
    PF: int
    PS: int
    FS: int
    SS: int
    TCH: int
    IPA: int
    W: int


PF_W = 6   # valid, skew, self_match, count row, registered row, key-on-node row
PS_W = 9   # valid, skew, first, key, perno, count row, zone-valid row, key-on-node row, zrow
IPA_W = 12  # has_aff, self_match_all, aff_total, fail row, all-keys row,
#             repel (off, n), anti terms (off, n), aff terms (off, n),
#             score row
(ST_MASK, ST_RAW_IPA, ST_TAINT, ST_NODEAFF, ST_IMAGE, ST_AVOID, ST_HAS_ALL,
 ST_SRC) = range(8)


def _layout(R: int, C: int, ipa: bool) -> _Layout:
    stat = 2 * R + 4
    pf = stat + 8
    ps = pf + PF_W * C
    fs = ps + PS_W * C
    ss = fs + C * C
    tch = ss + C * C
    ipa_o = tch + 2
    return _Layout(REQ=0, CHK=R, HAS=2 * R, NZ=2 * R + 1, IPAP=2 * R + 3,
                   STAT=stat, PF=pf, PS=ps, FS=fs, SS=ss, TCH=tch,
                   IPA=ipa_o, W=ipa_o + (IPA_W if ipa else 0))


class _Cfg(NamedTuple):
    """Value-hashable kernel configuration — the ONLY static jit input.
    Sessions with equal capacities/weights share one compiled program;
    everything a spec brings flows in as dynamic args (see _dispatch)."""

    shapes: tuple   # (Tcap, PC, Np, R, C, RC, SRc, ZRc, K, LB)
    weights: tuple
    ipa: bool
    bal_int: bool   # exact int32 balanced (else f32: caps too large)
    interpret: bool
    pts_int: bool = True   # exact int32 zone-spread raw (else f32: more
    # pod rows than PTS_MAX_COUNT)
    wide: bool = False     # resource scores in limbs (rescaled values up
    # to POS_BIG; else int32 divisions, values below NARROW_MAX)


# ---------------------------------------------------------------------------
# kernel


def _build_kernel(cfg: _Cfg, Bp: int):
    """One launch decides Bp pods one after another: filter + score every
    node lane for pod b against the carry, take the first of the maxima,
    commit it to the carry (utilization rows and the count rows on the
    spec's touch list), next pod."""
    (Tcap, PC, Np, R, C, RC, SRc, ZRc, K, LB) = cfg.shapes
    L = _layout(R, C, cfg.ipa)
    Wt = dict(cfg.weights)
    W = L.W
    dyn_ipa = cfg.ipa
    f32 = jnp.float32
    i32 = jnp.int32

    def kernel(breal_ref, tmpl_ref, spec_ref, pool_ref, bad_ref,
               alloc_ref, validn_ref, balgrp_ref, srow_ref, zrow_ref,
               onehot_ref, requested_in, nzpc_in, cnt_in,
               out_ref, requested_ref, nzpc_ref, cnt_ref):
        # carries live in the OUTPUT refs (initialized from the inputs)
        requested_ref[:] = requested_in[:]
        nzpc_ref[:] = nzpc_in[:]
        cnt_ref[:] = cnt_in[:]
        out_ref[:] = jnp.full((SUB, Bp), -1, i32)

        def srow(i):
            return srow_ref[pl.ds(i, 1), :]

        def crow(i):
            return cnt_ref[pl.ds(i, 1), :]

        def at_lane(row, lane_n, best):
            """row[best] as a scalar (NEG_BIG when best is no lane; a max
            and not a sum: Mosaic widens integer sums to 64 bits)."""
            return jnp.max(jnp.where(lane_n == best, row, i32(NEG_BIG)))

        def any_lane(flags_i32):
            return jnp.max(flags_i32) > 0

        def fit_row(sp):
            """NodeResourcesFit row against the CURRENT carry refs."""
            over = jnp.zeros((1, Np), jnp.bool_)
            for r in range(R):
                free = alloc_ref[r:r + 1, :] - requested_ref[r:r + 1, :]
                over = over | ((sp(L.REQ + r) > free) & (sp(L.CHK + r) != 0))
            fail_dims = (sp(L.HAS) != 0) & over
            fail_count = (nzpc_ref[2:3, :] + i32(1)) > nzpc_in[3:4, :]
            return jnp.logical_not(fail_count | fail_dims)

        def resource_rows(sp):
            """(balanced, exact-integer flags, key, least) against the
            CURRENT carry refs."""
            nzc = nzpc_ref[0:1, :] + sp(L.NZ)
            nzm = nzpc_ref[1:2, :] + sp(L.NZ + 1)
            cap_c = alloc_ref[0:1, :]
            cap_m = alloc_ref[1:2, :]
            full = ((cap_c == 0) | (cap_m == 0)
                    | (nzc >= cap_c) | (nzm >= cap_m))
            if cfg.wide and cfg.bal_int:
                balanced, whole = _balanced_wide(nzc, nzm, cap_c, cap_m, full)
                key = (nzc, nzm)
            elif cfg.bal_int:
                # exact floor of (1 - |c/C - m/M|) * 100; the build
                # guarantees 100 * C * M < 2^31
                den = jnp.where(full, i32(1), cap_c * cap_m)
                num = MAX_NODE_SCORE * (
                    den - jnp.abs(nzc * cap_m - nzm * cap_c))
                num = jnp.where(full, i32(0), num)
                balanced = num // den
                whole = jnp.logical_not(full) & (num - balanced * den == 0)
                key = nzc * (cap_m + i32(1)) + nzm
            else:
                fc = jnp.where(cap_c == 0, f32(1.0),
                               nzc.astype(f32) / cap_c.astype(f32))
                fm = jnp.where(cap_m == 0, f32(1.0),
                               nzm.astype(f32) / cap_m.astype(f32))
                balanced = ((f32(1.0) - jnp.abs(fc - fm))
                            * MAX_NODE_SCORE).astype(i32)
                balanced = jnp.where(full, i32(0), balanced)
                whole = jnp.zeros((1, Np), jnp.bool_)
                key = jnp.zeros((1, Np), i32)

            def least_dim(cap, reqq):
                if cfg.wide:
                    return _least_wide(cap, reqq)
                d = ((cap - reqq) * MAX_NODE_SCORE
                     // jnp.where(cap == 0, i32(1), cap))
                return jnp.where((cap == 0) | (reqq > cap), i32(0), d)

            least = (least_dim(cap_c, nzc) + least_dim(cap_m, nzm)) // i32(2)
            return balanced, whole, key, least

        def quirk_mask(key):
            """Lanes at a state where float64 reads one less."""
            grp = balgrp_ref[0:1, :]

            def body(i, acc):
                if cfg.wide:
                    c, m = key
                    e = 1 + 3 * i
                    hit = ((grp == bad_ref[e]) & (c == bad_ref[e + 1])
                           & (m == bad_ref[e + 2]))
                else:
                    hit = ((grp == bad_ref[1 + 2 * i])
                           & (key == bad_ref[2 + 2 * i]))
                return jnp.maximum(acc, hit.astype(i32))

            return jax.lax.fori_loop(0, bad_ref[0], body,
                                     jnp.zeros((1, Np), i32))

        def eval_pod(b):
            t = tmpl_ref[b]
            base = t * W

            def sp(i):
                return spec_ref[base + i]

            # NOTHING big is hoisted out of the loop: values live across
            # iterations spill out of vector registers and the
            # spill/restore swamps the step (measured; see PERF_NOTES)
            lane_n = jax.lax.broadcasted_iota(i32, (1, Np), 1)
            valid_n = validn_ref[0:1, :]
            static_mask = srow(sp(L.STAT + ST_MASK))
            cnt_taint = srow(sp(L.STAT + ST_TAINT))
            cnt_nodeaff = srow(sp(L.STAT + ST_NODEAFF))
            sc_image = srow(sp(L.STAT + ST_IMAGE))
            sc_avoid = srow(sp(L.STAT + ST_AVOID))
            shasall = srow(sp(L.STAT + ST_HAS_ALL))

            # ---- NodeResourcesFit (exact int32 after GCD rescale) ----
            mask_fit = fit_row(sp)

            # ---- PTS filter: per-node counts of the reader's own rows ----
            fail_pts = jnp.zeros((1, Np), i32)
            for c in range(C):
                o = L.PF + PF_W * c

                def hard(o=o, c=c):
                    sh = jnp.zeros((1, Np), i32)
                    for c2 in range(C):
                        sh = sh + sp(L.FS + c * C + c2) * crow(
                            sp(L.PF + PF_W * c2 + 3))
                    reg = srow(sp(o + 4))
                    konn = srow(sp(o + 5))
                    min_c = jnp.min(jnp.where(reg != 0, sh, i32(POS_BIG)))
                    min_c = jnp.where(min_c == POS_BIG, i32(0), min_c)
                    skew = jnp.where(reg != 0, sh, i32(0)) + sp(o + 2) - min_c
                    return ((konn == 0) | (skew > sp(o + 1))).astype(i32)

                fail_pts = jnp.maximum(fail_pts, jax.lax.cond(
                    sp(o) != 0, hard, lambda: jnp.zeros((1, Np), i32)))

            # ---- InterPodAffinity: static rows + the reader's own count
            # rows (D1-D3 of the hoisted term machinery) ----
            if dyn_ipa:
                io = L.IPA

                def terms():
                    def repel(i, acc):  # D1: assumed pods' anti terms
                        return jnp.maximum(
                            acc, (crow(pool_ref[sp(io + 5) + i]) > 0)
                            .astype(i32))

                    fail = jax.lax.fori_loop(
                        0, sp(io + 6), repel,
                        (srow(sp(io + 3)) != 0).astype(i32))

                    def anti(i, acc):  # D2: own anti terms
                        e = sp(io + 7) + 3 * i
                        hit = ((srow(pool_ref[e + 1]) != 0)
                               & ((srow(pool_ref[e]) + crow(pool_ref[e + 2]))
                                  > 0))
                        return jnp.maximum(acc, hit.astype(i32))

                    fail = jax.lax.fori_loop(0, sp(io + 8), anti, fail)

                    def aff(i, acc):  # D3: own affinity terms
                        e = sp(io + 9) + 2 * i
                        dyn = crow(pool_ref[e + 1])
                        miss = ((srow(pool_ref[e]) + dyn) <= 0).astype(i32)
                        return (jnp.maximum(acc[0], miss),
                                jnp.maximum(acc[1], (dyn > 0).astype(i32)))

                    z = jnp.zeros((1, Np), i32)
                    missing, seen = jax.lax.fori_loop(
                        0, sp(io + 10), aff, (z, z))
                    counts_empty = ((sp(io + 2) == 0)
                                    & jnp.logical_not(any_lane(seen)))
                    aff_ok = ((sp(io) == 0)
                              | ((srow(sp(io + 4)) != 0)
                                 & ((missing == 0)
                                    | (counts_empty & (sp(io + 1) != 0)))))
                    return ((fail == 0) & aff_ok).astype(i32)

                # specs no term reads or names keep the all-ones mask
                touched = ((sp(io) != 0) | (sp(io + 6) > 0) | (sp(io + 8) > 0)
                           | (sp(io + 3) != 0))
                mask_ipa = jax.lax.cond(
                    touched, terms, lambda: jnp.ones((1, Np), i32)) != 0
            else:
                mask_ipa = jnp.ones((1, Np), jnp.bool_)

            feasible = ((static_mask != 0) & mask_fit & (fail_pts == 0)
                        & mask_ipa & (valid_n != 0))
            n_feasible = jnp.sum(feasible.astype(f32)).astype(i32)

            # ---- resource scores ----
            balanced, whole, key, least = resource_rows(sp)

            # ---- PTS score ----
            scored = feasible & (shasall != 0)
            ignored = feasible & (shasall == 0)
            scored_f32 = scored.astype(f32)
            n_scored = jnp.sum(scored_f32)
            # raw = sum over the soft constraints of count * weight +
            # (maxSkew - 1), truncated once: whole lanes in raw_w, the
            # zone products' fractions in raw_q (31 bits), a per-node
            # constraint's float32 product in raw_f
            raw_f = jnp.zeros((1, Np), f32)
            raw_w = jnp.zeros((1, Np), i32)
            raw_q = jnp.zeros((1, Np), i32)
            have_s = i32(0)
            for c in range(C):
                o = L.PS + PS_W * c

                def soft(o=o, c=c):
                    sh = jnp.zeros((1, Np), i32)
                    for c2 in range(C):
                        sh = sh + sp(L.SS + c * C + c2) * crow(
                            sp(L.PS + PS_W * c2 + 5))
                    on = srow(sp(o + 7)) != 0
                    skew = sp(o + 1) - 1
                    zero = jnp.zeros((1, Np), i32)

                    def in_f32(cnt, size):
                        weight = jnp.max(jnp.log(
                            jnp.full((1, LANE), size, f32) + f32(2.0)))
                        return (jnp.where(on, cnt.astype(f32) * weight
                                          + skew.astype(f32), f32(0.0)),
                                zero, zero)

                    def zone():
                        # zone presence among scored nodes and its
                        # per-node expansion — the ONLY matvecs in the step
                        k = sp(o + 3)
                        p = (jax.lax.dot_general(
                            scored_f32, onehot_ref[k],
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=f32) > 0).astype(f32)
                        zpn = jax.lax.dot_general(
                            p, onehot_ref[k], (((1,), (1,)), ((), ())),
                            preferred_element_type=f32)
                        zval_l = zrow_ref[pl.ds(sp(o + 8), 1), :].astype(f32)
                        topo = jnp.sum(p * zval_l)
                        regn = zpn * (srow(sp(o + 6)) != 0)
                        topo = jnp.where(sp(o + 2) != 0, topo, f32(0.0))
                        cnt = jnp.where(regn > 0, sh, zero)
                        if not cfg.pts_int:
                            return in_f32(cnt, topo)
                        # count * log(size + 2) in int32 limbs of the
                        # float64 weight (PTS_BASE): exact
                        b = ((PTS_BASE_WIDE if cfg.wide else PTS_BASE)
                             + PTS_LIMBS * topo.astype(i32))
                        acc = cnt * bad_ref[b + 4]
                        acc = cnt * bad_ref[b + 3] + (acc >> 11)
                        acc = cnt * bad_ref[b + 2] + (acc >> 11)
                        q2 = acc & 2047
                        acc = cnt * bad_ref[b + 1] + (acc >> 11)
                        q1 = acc & 2047
                        acc = cnt * bad_ref[b] + (acc >> 11)
                        # 31 bits of the fraction; what lies below is
                        # dropped
                        frac = (((acc & 511) << 22) | (q1 << 11) | q2)
                        return (jnp.zeros((1, Np), f32),
                                jnp.where(on, (acc >> 9) + skew, zero),
                                jnp.where(on, frac, zero))

                    # a per-node key counts the pods of one node (a pod
                    # limit at most): float32 holds that product
                    return jax.lax.cond(
                        sp(o + 4) != 0, lambda: in_f32(sh, n_scored), zone)

                rf, rw, rq = jax.lax.cond(
                    sp(o) != 0, soft,
                    lambda: (jnp.zeros((1, Np), f32),
                             jnp.zeros((1, Np), i32),
                             jnp.zeros((1, Np), i32)))
                raw_f = raw_f + rf
                raw_q = raw_q + rq                    # wraps past 2**31:
                raw_w = raw_w + rw + (raw_q < 0).astype(i32)   # a carry
                raw_q = raw_q & i32(0x7FFFFFFF)
                have_s = jnp.maximum(have_s, (sp(o) != 0).astype(i32))
            raw_i = raw_w + (raw_f + raw_q.astype(f32)
                             * f32(2.0 ** -31)).astype(i32)
            min_r = jnp.min(jnp.where(scored, raw_i, i32(POS_BIG)))
            max_r = jnp.max(jnp.where(scored, raw_i, i32(0)))
            min_r = jnp.where(min_r == POS_BIG, i32(0), min_r)
            norm = (MAX_NODE_SCORE * (max_r + min_r - raw_i)
                    // jnp.where(max_r == 0, i32(1), max_r))
            norm = jnp.where(max_r == 0, i32(MAX_NODE_SCORE), norm)
            norm = jnp.where(ignored, i32(0), norm)
            sc_pts = jnp.where(have_s != 0, norm, i32(0))

            # ---- IPA score: static raw + the reader's score row (D4+D5),
            # normalized over the feasible nodes. A spec that reads no
            # score has a raw of 0 on every lane, and so a score of 0:
            # upstream's empty topology map, without a presence test ----
            def ipa_score():
                raw = srow(sp(L.STAT + ST_RAW_IPA))
                if dyn_ipa:
                    raw = raw + crow(sp(L.IPA + 11))
                min_i = jnp.min(jnp.where(feasible, raw, i32(POS_BIG)))
                max_i = jnp.max(jnp.where(feasible, raw, i32(NEG_BIG)))
                return ipa_normalize(raw - min_i, max_i - min_i)

            ipa = jax.lax.cond(sp(L.IPAP) != 0, ipa_score,
                               lambda: jnp.zeros((1, Np), i32))

            # ---- default-normalized taint / node-affinity ----
            def norm_default(counts, reverse):
                mx = jnp.max(jnp.where(feasible, counts, i32(0)))
                scaled = (MAX_NODE_SCORE * counts
                          // jnp.where(mx == 0, i32(1), mx))
                if reverse:
                    return jnp.where(mx == 0, i32(MAX_NODE_SCORE),
                                     i32(MAX_NODE_SCORE) - scaled)
                return jnp.where(mx == 0, counts, scaled)

            sc_taint = norm_default(cnt_taint, True)
            sc_nodeaff = norm_default(cnt_nodeaff, False)

            total = (balanced * Wt["balanced"] + sc_image * Wt["image"]
                     + ipa * Wt["ipa"] + least * Wt["least"]
                     + sc_nodeaff * Wt["node_affinity"]
                     + sc_avoid * Wt["prefer_avoid"]
                     + sc_pts * Wt["pts"] + sc_taint * Wt["taint"])
            total = jnp.where(feasible, total, i32(-1))
            if cfg.bal_int and Wt["balanced"] != 0:
                # float64 reads one less than the exact floor at a few
                # exact-integer states; lowering a lane can only matter if
                # that lane stands at the maximum now
                m0 = jnp.max(total)
                top_whole = any_lane(
                    (feasible & whole & (total == m0)).astype(i32))
                total = jax.lax.cond(
                    top_whole & (bad_ref[0] > 0),
                    lambda: jnp.where(
                        feasible & whole,
                        total - quirk_mask(key) * Wt["balanced"], total),
                    lambda: total)

            # first-max (jnp.argmax tie semantics), exact in int32
            m = jnp.max(total)
            best = jnp.min(jnp.where(total >= m, lane_n, i32(POS_BIG)))
            ok = (m >= 0) & (b < breal_ref[0])
            return sp, lane_n, best, m, ok, n_feasible

        def apply_pod(sp, lane_n, best, oki):
            """Carry updates for a pod of this spec landing on `best`."""
            hot = (lane_n == best).astype(i32) * oki   # (1, Np)
            for r in range(R):
                requested_ref[r:r + 1, :] = (
                    requested_ref[r:r + 1, :] + hot * sp(L.REQ + r))
            nzpc_ref[0:1, :] = nzpc_ref[0:1, :] + hot * sp(L.NZ)
            nzpc_ref[1:2, :] = nzpc_ref[1:2, :] + hot * sp(L.NZ + 1)
            nzpc_ref[2:3, :] = nzpc_ref[2:3, :] + hot

            def touch(i, _):
                # the pod joins its node's topology group on the row's
                # key: same-pair lanes get the weight (-1 = the node lacks
                # the key: no lane). Zone spread rows also ask that the
                # landing node is one the reader counts on (src row).
                e = sp(L.TCH) + TOUCH_W * i
                row = pool_ref[e]
                pair = srow(pool_ref[e + 1])
                at = at_lane(pair, lane_n, best)
                src = pool_ref[e + 3]
                gate = jnp.where(
                    src < 0, i32(1),
                    at_lane(srow(jnp.maximum(src, 0)), lane_n, best))
                w = pool_ref[e + 2] * oki * (gate > 0).astype(i32)
                same = ((pair == at) & (pair >= 0)).astype(i32)
                cnt_ref[pl.ds(row, 1), :] = crow(row) + same * w
                return i32(0)

            jax.lax.fori_loop(0, sp(L.TCH + 1), touch, i32(0))

        def one_pod(b):
            sp, lane_n, best, m, ok, n_feasible = eval_pod(b)
            apply_pod(sp, lane_n, best, ok.astype(i32))
            subi = jax.lax.broadcasted_iota(i32, (SUB, Bp), 0)
            lanei = jax.lax.broadcasted_iota(i32, (SUB, Bp), 1)
            at_b = lanei == b
            o = out_ref[:]
            o = jnp.where(at_b & (subi == 0),
                          jnp.where(ok, best, i32(-1)), o)
            o = jnp.where(at_b & (subi == 1), jnp.where(ok, m, i32(-1)), o)
            o = jnp.where(at_b & (subi == 2), n_feasible, o)
            out_ref[:] = o

        # b >= B_real iterations are no-ops via the ok gate
        U = POD_GROUP
        while Bp % U:
            U //= 2

        def body(j, _):
            base = j.astype(i32) * i32(U)
            for i in range(U):
                one_pod(base + i32(i))
            return i32(0)

        jax.lax.fori_loop(0, Bp // U, body, i32(0))

    return kernel


# the kernel's VMEM statics, in operand order (after the SMEM operands)
_VMEM_STATICS = ("alloc", "valid_n", "balgrp", "srow", "zrow", "onehot")
_SMEM_STATICS = ("spec", "pool", "bad")


def _kernel_vmem_bytes(statics: Dict, carry: Dict, Bp: int) -> int:
    """Bytes one launch keeps in VMEM: the statics, the carries twice
    (input refs and the aliased output refs both exist in the kernel) and
    the result rows."""
    def nbytes(x):
        return math.prod(x.shape) * np.dtype(x.dtype).itemsize

    return (sum(nbytes(statics[k]) for k in _VMEM_STATICS)
            + 2 * sum(nbytes(v) for v in carry.values())
            + SUB * Bp * 4)


def _vmem_request(operand_bytes: int) -> int:
    """What the kernel asks the compiler for: its operands plus room for
    the step's (1, Np) temporaries."""
    return operand_bytes + operand_bytes // 8 + (16 << 20)


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
def _dispatch(cfg: "_Cfg", statics: Dict, meta, carry: Dict):
    # meta = [B_real | tmpl] (int32): the whole per-batch payload in one
    # transfer. B_real stays a DYNAMIC (SMEM) scalar: variable batch
    # lengths must not recompile the kernel (only the padded width Bp is
    # static). The tables arrive as DYNAMIC pytree args, NOT via the
    # static cfg: cfg hashes by VALUE, so two sessions with the same
    # capacities share one compiled program, and an admitted spec is new
    # data for the same program.
    Bp = int(meta.shape[0]) - 1
    kernel = _build_kernel(cfg, Bp)
    carry_in = [carry[k] for k in CARRY_KEYS]
    out_shape = (
        jax.ShapeDtypeStruct((SUB, Bp), jnp.int32),
        *[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in carry_in],
    )
    vm = pl.BlockSpec(memory_space=pltpu.VMEM)
    sm = pl.BlockSpec(memory_space=pltpu.SMEM)
    n_pre = 2 + len(_SMEM_STATICS) + len(_VMEM_STATICS)
    compiler_params = None
    if not cfg.interpret:
        compiler_params = pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(
                _kernel_vmem_bytes(statics, carry, Bp)))
    # trace the kernel with x64 OFF: every input is explicitly 32-bit,
    # and weak python literals must not widen ops to i64/f64 (Mosaic has
    # no 64-bit types)
    with jax.enable_x64(False):
        results = pl.pallas_call(
            kernel,
            out_shape=out_shape,
            in_specs=([sm] * (2 + len(_SMEM_STATICS))
                      + [vm] * (len(_VMEM_STATICS) + len(carry_in))),
            out_specs=tuple([vm] * (1 + len(carry_in))),
            input_output_aliases={n_pre + i: 1 + i
                                  for i in range(len(carry_in))},
            interpret=cfg.interpret,
            compiler_params=compiler_params,
        )(meta[:1], meta[1:], *(statics[k] for k in _SMEM_STATICS),
          *(statics[k] for k in _VMEM_STATICS), *carry_in)
    return results[0], dict(zip(CARRY_KEYS, results[1:]))


# ---------------------------------------------------------------------------
# table writes: one compiled shape per table, warmed at the build


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_rows(table, idx, rows):
    """table[idx[i]] = rows[i]; an index past the end is dropped (the
    padding of a short chunk)."""
    return table.at[idx].set(rows, mode="drop")


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _rescale(requested, nzpc, alloc, k_res, k_nz):
    """A finer GCD: every utilization value in units of the new one."""
    return (requested * k_res[:, None], nzpc * k_nz[:, None],
            alloc * k_res[:, None])


# the largest _delta_scan entry bucket warmed before a window (the
# backend's queued-delta backstop, KTPU_MAX_QUEUED_DELTAS)
DELTA_WARM_MAX = 4096
_DELTA_WARMED: set = set()  # carry/table shapes whose buckets are warm


@functools.partial(jax.jit, donate_argnums=(0,))
def _delta_scan(carry, srow, xs):
    """Apply cluster-event deltas to the carry in ONE fused launch: each
    entry is the jnp twin of the kernel's apply_pod with `best := node`
    and a sign folded into the payload — utilization columns, then one
    touch (row, pair row, weight, src row). lax.scan keeps the launch
    count at ONE regardless of the entry count; padding entries are node
    0 with all-zero payloads."""

    def step(c, x):
        c = dict(c)
        n = x["node"]
        c["requested"] = c["requested"].at[:, n].add(x["dres"])
        c["nzpc"] = c["nzpc"].at[:, n].add(x["dnzpc"])
        pair = jax.lax.dynamic_index_in_dim(srow, x["pair"], 0)   # [1, Np]
        at = jax.lax.dynamic_index_in_dim(pair, n, 1)
        same = ((pair == at) & (pair >= 0)).astype(jnp.int32)
        src = jax.lax.dynamic_index_in_dim(
            srow, jnp.maximum(x["src"], 0), 0)
        gate = jnp.where(x["src"] < 0, 1,
                         (jax.lax.dynamic_index_in_dim(src, n, 1) != 0)
                         .astype(jnp.int32)[0, 0])
        c["cnt"] = c["cnt"].at[x["row"]].add((same * x["w"] * gate)[0])
        return c, None

    carry, _ = jax.lax.scan(step, carry, xs)
    return carry


# ---------------------------------------------------------------------------
# the session

# per-spec selector tables the host matches label sets against at an
# admission (spread selectors, then the three term families)
_PTS_KEYS = tuple(f"{p}_{s}" for p in ("ptsf", "ptss")
                  for s in ("op", "rkey", "pairs"))
_TERM_FAMILIES = ("ipaaa", "ipaa", "ipap")
_TERM_KEYS = tuple(f"{p}_{s}" for p in _TERM_FAMILIES
                   for s in ("op", "rkey", "pairs", "ns", "valid", "key"))
_STACK_KEYS = _PTS_KEYS + _TERM_KEYS + ("self_ns", "ipap_weight")
# the small part of the cluster dict the host half needs
_CLUSTER_KEYS = ("alloc", "requested", "nz_requested", "pod_count",
                 "allowed_pods", "valid", "pair_of_key", "nkey",
                 "hard_pod_affinity_weight")


def table_capacity(pod_reserve: int) -> int:
    """Specs a session should have room for, from the one hint a caller
    gives (ClusterEncoding.reserve(pods=...)): one spec per 128 pods of
    the reserve — clusterloader2's load test fills a cluster with groups
    of 5/30/250 pods, ~9 pods a Deployment, but a table that size would
    not fit the core, and what does not fit rebuilds (counted) — between
    64 and 1024, a power of two so capacities rarely move between runs."""
    want = max(64, min(1024, pod_reserve // 128))
    return 1 << (want - 1).bit_length()


class PallasSession:
    """HoistedSession-compatible API over the single-launch table kernel.

    Semantics: identical to ops/hoisted.py HoistedSession (same prologue,
    same carry discipline) — parity pinned by tests/test_pallas_scan.py
    and tests/test_pallas_table.py. `admit` takes specs into the LIVE
    session; it raises TableFull where only a rebuild can (a capacity is
    used up), PallasUnsupported where the spec cannot ride this kernel at
    all (e.g. a shared-value topology key with more than 128 values)."""

    # KTPU_EXPLAIN: the Mosaic kernel's scan does not surface per-plugin
    # mask/score sections — explain mode rides the jnp hoisted session
    # (TPUBackend demotes with session_builds{reason="explain"})
    supports_explain = False

    @staticmethod
    def explain_payload(ys):
        return None

    # ktpu: allow-sync(session build: the node-side arrays come to the host once, before the first dispatch)
    def __init__(self, cluster: Dict, template_arrays_list: List[Dict],
                 weights: Optional[Dict[str, int]] = None,
                 interpret: bool = False, capacity: int = 64,
                 terms: Optional[bool] = None):
        """capacity: specs the table has room for (table_capacity).
        terms: build the term machinery even though no template carries a
        term yet (the caller expects some: enc.reserve(anti_terms=...));
        a term spec met by a session built without it is a rebuild."""
        if templates_have_ports(template_arrays_list):
            # the jnp HoistedSession carries host-port tables; the pallas
            # kernel does not (yet) — signal a fallback, not an error
            raise PallasUnsupported(
                "templates with host ports ride the jnp hoisted session",
                reason="host-ports")
        self.dyn_ipa = bool(terms) or templates_have_terms(
            template_arrays_list)
        self.weights = dict(weights or DEFAULT_WEIGHTS)
        self.interpret = interpret
        # totals stay int32: every plugin score is <= MAX_NODE_SCORE
        if sum(abs(int(v)) for v in self.weights.values()) \
                * (MAX_NODE_SCORE + 1) >= 2 ** 24:
            raise PallasUnsupported("weights too large for exact totals",
                                    reason="weights-exceed-f32")
        c = {k: np.asarray(cluster[k]) for k in _CLUSTER_KEYS}
        self._c = c
        t0 = template_arrays_list[0]
        self.R = int(np.asarray(t0["req"]).shape[0])
        self.C = int(np.asarray(t0["ptsf_op"]).shape[0])
        self.N = int(c["valid"].shape[0])
        self.Np = _ceil(self.N, LANE)
        self.Tcap = 1 << (max(capacity, 2 * len(template_arrays_list), 64)
                          - 1).bit_length()
        self.RC = self.Tcap             # count rows
        self.SRc = max(64, self.Tcap // 2)   # interned static rows
        self.ZRc = SUB                  # interned per-zone rows
        self.K = 2                      # shared-value topology keys
        self.PC = 16 * self.Tcap        # pool words
        self._L = _layout(self.R, self.C, self.dyn_ipa)
        if self.Tcap * self._L.W + self.PC > (1 << 17) + (1 << 16):
            raise PallasUnsupported("spec records exceed the scalar memory",
                                    reason="table-capacity")
        self._sig = None       # array shapes every admitted spec shares
        self._fps: Dict = {}   # fingerprint -> spec index
        self.T = 0
        self._arrays: List[Dict] = []
        self._has_terms = np.zeros(self.Tcap, bool)
        self._stack: Dict[str, np.ndarray] = {}
        self._lab_pair = np.zeros((self.Tcap, 0), bool)
        self._lab_key = np.zeros((self.Tcap, 0), bool)
        # per spec, what the kernel loops over (python lists; the pool
        # holds their flattened copies)
        self._touch: List[List[Tuple[int, int, int, int]]] = []
        self._repel: List[List[int]] = []        # D1 count rows
        self._repel_key: List[Dict[int, int]] = []   # key -> D1 row
        self._anti: List[List[Tuple[int, int, int]]] = []
        self._aff: List[List[Tuple[int, int]]] = []
        self._rows: List[Dict] = []   # per spec: its row ids by role
        self._dirty: List[bool] = []  # per spec: lists changed, not in pool
        self._spec = np.zeros(self.Tcap * self._L.W, np.int32)
        self._pool = np.zeros(self.PC, np.int32)
        self._pool_n = 0
        self._srow_ids: Dict[bytes, int] = {}
        self._zrow_ids: Dict[bytes, int] = {}
        self._uids: Dict[bytes, int] = {}
        self._zof: List[Dict[int, int]] = []
        self._pair_rows: Dict[int, int] = {}   # topology key -> pair row
        # pair row -> the most pods one of its topology domains can hold
        self._pair_cap: Dict[int, int] = {}
        self._score_rows: set = set()   # every score row
        self._score_pairs = 0           # D4/D5 touches wired so far
        self._n_cnt = 1   # row 0: all zero, never written (absent reads)
        self._pending: Dict[str, List] = {"srow": [], "zrow": [], "cnt": [],
                                          "onehot": []}
        self._build_base(c, template_arrays_list)
        self._intern_srow(np.zeros(self.Np, np.int32))   # id 0
        self._intern_zrow(np.zeros(VZ, np.int32))        # id 0
        # the carry goes to the device with the first dispatch; until
        # then admissions fill these host seeds
        self._cnt0 = np.zeros((self.RC, self.Np), np.int32)
        self._carry = None
        self._statics = {
            "alloc": jnp.asarray(self._alloc),
            "valid_n": jnp.asarray(self._valid_n),
            "balgrp": jnp.asarray(self._balgrp),
            "srow": jnp.zeros((self.SRc, self.Np), jnp.int32),
            "zrow": jnp.zeros((self.ZRc, VZ), jnp.int32),
            "onehot": jnp.zeros((self.K, self.Np, VZ), jnp.float32),
            "spec": None, "pool": None,
            "bad": jnp.asarray(self._bad),
        }
        self._cfg = _Cfg(
            shapes=(self.Tcap, self.PC, self.Np, self.R, self.C, self.RC,
                    self.SRc, self.ZRc, self.K, MAX_QUIRKS),
            weights=tuple(sorted(self.weights.items())),
            ipa=self.dyn_ipa, bal_int=self._bal_int, interpret=interpret,
            # a zone's count is at most the pod rows there are
            pts_int=int(np.asarray(cluster["pvalid"]).shape[0])
            < PTS_MAX_COUNT, wide=self._wide)
        self.admits = 0   # admissions after the build
        # (Bp, "full") -> AOT-compiled executable (None = AOT unavailable,
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
        # every table write below runs the shape it will run in a window
        self._warm_writers()
        self._admit(cluster, template_arrays_list)
        if not interpret and jax.default_backend() == "tpu":
            # the grid-less kernel holds every operand whole in VMEM: a
            # table too wide for the core is a clean downgrade here, not
            # a compiler error at the first dispatch
            need = _kernel_vmem_bytes(
                self._statics, self._carry_struct(), 2048)
            if _vmem_request(need) > _vmem_cap():
                raise PallasUnsupported(
                    f"kernel operands ({need >> 20} MiB) exceed the "
                    f"core's VMEM", reason="vmem-budget")

    # -- node-side state ----------------------------------------------------

    # ktpu: allow-sync(session build: one-time host packing of the node rows, runs before first dispatch)
    def _build_base(self, c: Dict, templates: List[Dict]) -> None:
        """Utilization rows and the exact per-dimension GCD rescale to
        int32."""
        N, Np, R = self.N, self.Np, self.R
        alloc = c["alloc"].astype(np.int64).T.copy()            # [R, N]
        requested = c["requested"].astype(np.int64).T.copy()
        nz_requested = c["nz_requested"].astype(np.int64).T.copy()  # [2, N]
        req = np.stack([np.asarray(t["req"]).astype(np.int64)
                        for t in templates])                    # [T, R]
        nz_req = np.stack([np.asarray(t["nz_req"]).astype(np.int64)
                           for t in templates])
        # the rescale factors survive the build: incoming deltas and
        # later specs must divide by the SAME gcd to stay exact — a spec
        # that does not refines it (_refine_gcd), an indivisible delta is
        # structural (delta_compatible)
        self._gcd = np.ones(R, np.int64)
        for r in range(R):
            extra = [nz_requested[r], nz_req[:, r]] if r < 2 else []
            g = _gcd_all(alloc[r], requested[r], req[:, r], *extra)
            self._gcd[r] = g
            alloc[r] //= g
            requested[r] //= g
            if r < 2:
                nz_requested[r] //= g
        hi = max((int(a.max(initial=0)) for a in
                  (alloc, requested, nz_requested)), default=0)
        if hi >= POS_BIG:
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
        vn = np.zeros((SUB, Np), np.int32)
        vn[:, :N] = c["valid"].astype(np.int32)[None, :]
        self._valid_n = vn
        # the narrow form where it holds these values, the wide one where
        # only it does (a pool's Ki, a product C * M past 2^31 / 100)
        pairs = self._cap_pairs()
        self._wide = hi > NARROW_MAX or (len(pairs) <= 64 and any(
            MAX_NODE_SCORE * int(cc) * int(cm) >= 2 ** 31
            for cc, cm in pairs))
        self._bal_int = self._balanced_tables(build=True)

    def _fits(self, hi: int) -> bool:
        """Does a rescaled magnitude fit the form this session was built
        in?"""
        return hi <= (POS_BIG - 1 if self._wide else NARROW_MAX)

    def _cap_pairs(self) -> np.ndarray:
        """The distinct (cpu, memory) capacities of the valid nodes."""
        valid = self._c["valid"].astype(bool)
        cap = self._alloc[:2, : self.N].astype(np.int64)
        return np.unique(cap[:, valid].T, axis=0) if valid.any() \
            else np.zeros((0, 2), np.int64)

    def _balanced_tables(self, build: bool = False) -> bool:
        """Node groups by (cpu, memory) capacity and the float64-quirk
        states of each, as the kernel lists them. False: the exact form
        does not fit these capacities (more than 64 of them, C * M past
        the form's bound, quirks past MAX_QUIRKS): the kernel then
        evaluates balanced in f32, and the backend counts the build in
        scheduler_tpu_inexact_builds_total."""
        N = self.N
        cap = self._alloc[:2, :N].astype(np.int64)
        pairs = self._cap_pairs()
        qw, base = (3, PTS_BASE_WIDE) if self._wide else (2, PTS_BASE)
        grp = np.zeros((SUB, self.Np), np.int32)
        bad = np.zeros(base + PTS_LIMBS * (VZ + 1), np.int32)
        bad[base:] = _spread_limbs().ravel()
        ok = len(pairs) <= 64 and all(
            int(cc) * int(cm) < BAL_F64_MAX if self._wide
            else MAX_NODE_SCORE * int(cc) * int(cm) < 2 ** 31
            for cc, cm in pairs)
        n = 0
        with tracing.span("balanced-quirks", "session",
                          pairs=len(pairs), wide=self._wide) as sp:
            for g, (cc, cm) in enumerate(pairs if ok else ()):
                grp[0, :N][(cap[0] == cc) & (cap[1] == cm)] = g
                q = _balanced_quirks(int(cc), int(cm))
                if q is None or n + len(q) > MAX_QUIRKS:
                    ok = False
                    break
                e = 1 + qw * n
                bad[e:e + qw * len(q):qw] = g
                if self._wide:
                    bad[e + 1:e + 3 * len(q):3] = q[:, 0]
                    bad[e + 2:e + 3 * len(q):3] = q[:, 1]
                else:
                    bad[e + 1:e + 2 * len(q):2] = (
                        q[:, 0] * (int(cm) + 1) + q[:, 1])
                n += len(q)
            if not ok:
                grp[:] = 0
                bad[:base] = 0
                n = 0
            sp.set(states=n, exact=ok)
        bad[0] = n
        if not build and ok != self._bal_int:
            raise ValueError("capacities left the exact balanced form")
        self._balgrp, self._bad = grp, bad
        self.quirk_states = n
        return ok

    # -- interned rows ------------------------------------------------------

    def _intern(self, table: str, ids: Dict[bytes, int], cap: int,
                row: np.ndarray) -> int:
        """Id of `row` in a content-addressed static table (`srow`,
        `zrow`); a row met for the first time is queued for the device.
        Id 0 is the all-zero row every table starts with."""
        row = np.ascontiguousarray(row, np.int32)
        key = row.tobytes()
        i = ids.get(key)
        if i is None:
            i = len(ids)
            if i >= cap:
                raise TableFull(f"{table} table is full",
                                reason="table-static-rows")
            ids[key] = i
            if i:
                self._pending[table].append((i, row))
        return i

    def _intern_srow(self, row: np.ndarray) -> int:
        return self._intern("srow", self._srow_ids, self.SRc, row)

    def _intern_zrow(self, row: np.ndarray) -> int:
        return self._intern("zrow", self._zrow_ids, self.ZRc, row)

    def _node_row(self, values, fill: int = 0) -> np.ndarray:
        out = np.full(self.Np, fill, np.int32)
        v = np.asarray(values)
        if np.abs(v.astype(np.int64)).max(initial=0) >= POS_BIG:
            # POS_BIG (2^30), not 2^31: the kernel's min/max sentinels
            # must stay strictly above any genuine value
            raise PallasUnsupported("static magnitude exceeds sentinel",
                                    reason="score-magnitude")
        out[: self.N] = v
        return out

    def _new_cnt(self, init=None) -> int:
        i = self._n_cnt
        if i >= self.RC:
            raise TableFull("count row table is full", reason="table-rows")
        self._n_cnt += 1
        if init is not None and np.any(init):
            self._pending["cnt"].append((i, self._node_row(init)))
        return i

    def _pair_row(self, key: int) -> int:
        """Interned pair-id row of one topology key (-1: the node lacks
        the key, so no lane ever matches it)."""
        i = self._pair_rows.get(key)
        if i is None:
            c = self._c
            ok = c["nkey"][:, key].astype(bool) & c["valid"].astype(bool)
            pair = np.where(ok, c["pair_of_key"][:, key], -1)
            i = self._intern_srow(self._node_row(pair, fill=-1))
            self._pair_rows[key] = i
            pods = np.bincount(pair[ok], weights=c["allowed_pods"][ok]) \
                if ok.any() else np.zeros(1)
            self._pair_cap[i] = int(pods.max())
        return i

    def _zone_key(self, column: np.ndarray) -> Tuple[int, int]:
        """(key id, zone-valid row) of a shared-value topology column."""
        valid_nodes = self._c["valid"].astype(bool)
        kb = column.tobytes()
        u = self._uids.get(kb)
        if u is None:
            u = len(self._uids)
            if u >= self.K:
                raise TableFull("shared-value topology keys used up",
                                reason="too-many-topology-keys")
            vals = np.unique(column[valid_nodes])
            vals = vals[vals > 0]
            if len(vals) > VZ:
                raise PallasUnsupported(
                    f"topology key has {len(vals)} values > {VZ}",
                    reason="too-many-topology-values")
            m = {int(v): z for z, v in enumerate(vals)}
            zid = np.array([m.get(int(v), -1) for v in column], np.int32)
            ok = (zid >= 0) & valid_nodes
            onehot = np.zeros((self.Np, VZ), np.float32)
            onehot[np.arange(self.N)[ok], zid[ok]] = 1.0
            self._uids[kb] = u
            self._zof.append(m)
            self._pending["onehot"].append((u, onehot))
        zv = np.zeros(VZ, np.int32)
        zv[list(self._zof[u].values())] = 1
        return u, self._intern_zrow(zv)

    # -- device writes ------------------------------------------------------

    def _write(self, table, items, width):
        for lo in range(0, len(items), WRITE_CHUNK):
            part = items[lo:lo + WRITE_CHUNK]
            idx = np.full(WRITE_CHUNK, table.shape[0], np.int32)  # dropped
            rows = np.zeros((WRITE_CHUNK,) + tuple(width), table.dtype)
            for j, (i, row) in enumerate(part):
                idx[j] = i
                rows[j] = row
            table = _write_rows(table, jnp.asarray(idx), jnp.asarray(rows))
        return table

    def _warm_writers(self) -> None:
        """Run every table write once with nothing to write, so that the
        shapes an admission inside a window uses are compiled here."""
        st = self._statics
        st["srow"] = self._write(st["srow"], [(self.SRc, 0)], (self.Np,))
        st["zrow"] = self._write(st["zrow"], [(self.ZRc, 0)], (VZ,))
        self._write(jnp.zeros((self.RC, self.Np), jnp.int32),
                    [(self.RC, 0)], (self.Np,))
        oh = np.zeros((1, self.Np, VZ), np.float32)
        st["onehot"] = _write_rows(
            st["onehot"], jnp.asarray(np.array([self.K], np.int32)),
            jnp.asarray(oh))
        rp = self._requested0.shape[0]
        _, _, st["alloc"] = _rescale(
            jnp.asarray(self._requested0), jnp.asarray(self._nzpc0),
            st["alloc"], jnp.ones(rp, jnp.int32), jnp.ones(SUB, jnp.int32))

    def _flush_writes(self) -> int:
        """Everything an admission changed, to the device. Returns the
        number of rows written."""
        st, p = self._statics, self._pending
        n = len(p["srow"]) + len(p["zrow"]) + len(p["cnt"])
        if p["srow"]:
            st["srow"] = self._write(st["srow"], p["srow"], (self.Np,))
        if p["zrow"]:
            st["zrow"] = self._write(st["zrow"], p["zrow"], (VZ,))
        if p["cnt"] and self._carry is None:
            for i, row in p["cnt"]:
                self._cnt0[i] = row
        elif p["cnt"]:
            self._carry["cnt"] = self._write(
                self._carry["cnt"], p["cnt"], (self.Np,))
        for u, onehot in p["onehot"]:
            st["onehot"] = _write_rows(
                st["onehot"], jnp.asarray(np.array([u], np.int32)),
                jnp.asarray(onehot[None]))
        for k in p:
            p[k] = []
        st["spec"] = jnp.asarray(self._spec)
        st["pool"] = jnp.asarray(self._pool)
        return n

    # -- admission ----------------------------------------------------------

    @property
    def specs(self) -> int:
        return self.T

    @property
    def count_rows(self) -> int:
        return self._n_cnt

    @property
    def static_rows(self) -> int:
        return len(self._srow_ids)

    def has(self, fp) -> bool:
        return fp in self._fps

    def admit(self, cluster_fn: Callable[[], Dict],
              template_arrays_list: List[Dict],
              flush: Optional[Callable[[], None]] = None) -> Dict:
        """Take new specs into the LIVE session: no rebuild, no compile.

        `cluster_fn()` gives the cluster dict the prologue reads (host
        arrays: ClusterEncoding.host_state); it is called after
        `flush()`, which is called only if pods still in
        flight could match a selector of a new spec (their decisions are
        on the device alone: the snapshot would miss them). Raises
        TableFull / PallasUnsupported where the specs do not fit: the
        session is then half-written and DEAD, the caller rebuilds.
        Returns {"n", "rows", "score_pairs", "flush_s"} (flush_s: the
        seconds flush() took, 0.0 where it was not called)."""
        new = self._unknown(template_arrays_list)
        if not new:
            return {"n": 0, "rows": 0, "score_pairs": 0, "flush_s": 0.0}
        flush_s = 0.0
        if flush is not None and self._read_by_new(new):
            t0 = _time.perf_counter()
            flush()
            flush_s = _time.perf_counter() - t0
        pairs0 = self._score_pairs
        out = self._admit(cluster_fn(), new)
        self.admits += 1
        out["score_pairs"] = self._score_pairs - pairs0
        out["flush_s"] = flush_s
        return out

    def _unknown(self, arrays_list: List[Dict]) -> List[Dict]:
        seen, out = set(), []
        for a in arrays_list:
            fp = template_fingerprint(a)
            if fp not in self._fps and fp not in seen:
                seen.add(fp)
                out.append(a)
        return out

    def _signature(self, a: Dict) -> tuple:
        return tuple((k, np.asarray(a[k]).shape) for k in _STACK_KEYS) + (
            ("req", np.asarray(a["req"]).shape),)

    def _label_batch(self, arrays_list: List[Dict]):
        """(pair bits, key bits, namespaces) of label sets, at the widest
        vocabulary seen so far (ids are permanent: widening pads zeros)."""
        wp = max([self._lab_pair.shape[1]]
                 + [len(a["self_ppair"]) for a in arrays_list])
        wk = max([self._lab_key.shape[1]]
                 + [len(a["self_pkey"]) for a in arrays_list])
        if wp > self._lab_pair.shape[1]:
            self._lab_pair = np.pad(
                self._lab_pair, ((0, 0), (0, wp - self._lab_pair.shape[1])))
        if wk > self._lab_key.shape[1]:
            self._lab_key = np.pad(
                self._lab_key, ((0, 0), (0, wk - self._lab_key.shape[1])))
        pp = np.zeros((len(arrays_list), wp), bool)
        pk = np.zeros((len(arrays_list), wk), bool)
        for i, a in enumerate(arrays_list):
            pp[i, :len(a["self_ppair"])] = a["self_ppair"]
            pk[i, :len(a["self_pkey"])] = a["self_pkey"]
        ns = np.array([int(np.asarray(a["self_ns"])) for a in arrays_list],
                      np.int64)
        return pp, pk, ns

    @staticmethod
    def _match(tab: Dict, prefix: str, pp, pk, ns, owner_ns=None):
        """[owners, slots, label sets]: does label set b satisfy selector
        slot x of owner o? `tab[prefix_*]` are [owners, slots, ...]; a
        spread selector holds in the owner's namespace (`owner_ns`), a
        term in its own namespace list and only where it is valid."""
        op = tab[f"{prefix}_op"]
        o_n, x_n = op.shape[:2]
        m = _eval_reqs_batch_np(
            op.reshape((o_n * x_n,) + op.shape[2:]),
            tab[f"{prefix}_rkey"].reshape((o_n * x_n,) + op.shape[2:]),
            tab[f"{prefix}_pairs"].reshape(
                (o_n * x_n,) + tab[f"{prefix}_pairs"].shape[2:]),
            pp, pk).T.reshape(o_n, x_n, len(ns))
        if owner_ns is not None:
            return m & (owner_ns[:, None, None] == ns[None, None, :])
        ns_tbl = tab[f"{prefix}_ns"]                      # [O, X, NS]
        ns_ok = ((ns_tbl[:, :, :, None] == ns[None, None, None, :])
                 & (ns_tbl[:, :, :, None] != 0)).any(axis=2)
        return m & ns_ok & tab[f"{prefix}_valid"].astype(bool)[:, :, None]

    def _tables_of(self, arrays_list: List[Dict]) -> Dict:
        return {k: np.stack([np.asarray(a[k]) for a in arrays_list])
                for k in _STACK_KEYS}

    def _read_by_new(self, new: List[Dict]) -> bool:
        """Would a pod of a LIVE spec count toward a row of a new spec?"""
        if not self.T:
            return False
        try:
            tab = self._tables_of(new)
            self._label_batch(new)   # widens the live label store
            pp, pk = self._lab_pair[: self.T], self._lab_key[: self.T]
            ns = self._stack["self_ns"][: self.T].astype(np.int64)
            own = tab["self_ns"].astype(np.int64)
            hit = (self._match(tab, "ptsf", pp, pk, ns, own).any()
                   or self._match(tab, "ptss", pp, pk, ns, own).any())
            if hit or any(self._match(tab, f, pp, pk, ns).any()
                          for f in _TERM_FAMILIES):
                return True
            # ... or a term of a live spec select a new spec's labels
            # (its pods repel the new spec, or move its score: D1, D4, D5)
            live = {k: v[: self.T] for k, v in self._stack.items()}
            npp, npk, nns = self._label_batch(new)
            return bool(any(self._match(live, f, npp, npk, nns).any()
                            for f in _TERM_FAMILIES))
        except (ValueError, IndexError):
            return True   # shapes moved: _admit will say so; be safe

    def _admit(self, cluster: Dict, new: List[Dict]) -> Dict:
        if self._sig is None:
            self._sig = self._signature(new[0])
            self._stack = {
                k: np.zeros((self.Tcap,) + np.asarray(new[0][k]).shape,
                            np.asarray(new[0][k]).dtype)
                for k in _STACK_KEYS}
        for a in new:
            if self._signature(a) != self._sig:
                raise TableFull("spec arrays of another shape",
                                reason="shape-change")
        if self.T + len(new) > self.Tcap:
            raise TableFull("spec table is full", reason="table-full")
        if not self.dyn_ipa and templates_have_terms(new):
            raise TableFull("session was built without the term machinery",
                            reason="terms-enabled")
        if templates_have_ports(new):
            raise PallasUnsupported(
                "templates with host ports ride the jnp hoisted session",
                reason="host-ports")
        first = self.T
        self._refine_gcd(new)
        for lo in range(0, len(new), ADMIT_CHUNK):
            part = new[lo:lo + ADMIT_CHUNK]
            padded = part + [part[0]] * (ADMIT_CHUNK - len(part))
            S = _host_prologue(cluster, padded, self.dyn_ipa)
            for i, a in enumerate(part):
                self._admit_one(S, i, a)
        self._wire(first)
        for t in range(self.T):
            if self._dirty[t]:
                self._write_lists(t)
        return {"n": len(new), "rows": self._flush_writes()}

    def _refine_gcd(self, new: List[Dict]) -> None:
        """Requests the live GCD does not divide: a finer unit, every
        utilization value multiplied up (exact), capacities re-checked."""
        req = np.stack([np.asarray(a["req"]).astype(np.int64) for a in new])
        nz = np.stack([np.asarray(a["nz_req"]).astype(np.int64)
                       for a in new])
        k = np.ones(self.R, np.int64)
        for r in range(self.R):
            vals = [req[:, r]] + ([nz[:, r]] if r < 2 else [])
            g = math.gcd(int(self._gcd[r]), _gcd_all(*vals)) \
                if any(v.any() for v in vals) else int(self._gcd[r])
            k[r] = int(self._gcd[r]) // g
        if (k == 1).all():
            return
        alloc = self._alloc.astype(np.int64)
        alloc[: self.R] *= k[:, None]
        L, Wd = self._L, self._L.W
        rec = self._spec.reshape(self.Tcap, Wd).astype(np.int64)
        rec[:, L.REQ:L.REQ + self.R] *= k[None, :]
        rec[:, L.NZ:L.NZ + 2] *= k[None, :2]
        # the carried utilization is bounded by the capacities
        hi = max(int(alloc.max(initial=0)),
                 int(rec[:, L.REQ:L.REQ + self.R].max(initial=0)))
        if not self._fits(hi):
            raise TableFull("refined resource unit too fine for the "
                            "session's form", reason="resource-magnitude")
        old = (self._alloc, self._gcd, self._balgrp, self._bad,
               self.quirk_states)
        self._alloc = alloc.astype(np.int32)
        self._gcd = self._gcd // k
        try:
            self._balanced_tables()
        except ValueError:
            (self._alloc, self._gcd, self._balgrp, self._bad,
             self.quirk_states) = old
            raise TableFull("refined unit leaves the exact balanced form",
                            reason="resource-magnitude")
        self._spec[:] = rec.astype(np.int32).reshape(-1)
        rp = self._requested0.shape[0]
        k_res = np.ones(rp, np.int32)
        k_res[: self.R] = k
        k_nz = np.ones(SUB, np.int32)
        k_nz[:2] = k[:2]
        st = self._statics
        if self._carry is None:
            self._requested0 *= k_res[:, None]
            self._nzpc0 *= k_nz[:, None]
            st["alloc"] = jnp.asarray(self._alloc)
        else:
            c = self._carry
            c["requested"], c["nzpc"], st["alloc"] = _rescale(
                c["requested"], c["nzpc"], st["alloc"],
                jnp.asarray(k_res), jnp.asarray(k_nz))
        st["balgrp"] = jnp.asarray(self._balgrp)
        st["bad"] = jnp.asarray(self._bad)

    # ktpu: allow-sync(admission: host packing of one spec's rows and record)
    def _admit_one(self, S: Dict, i: int, a: Dict) -> None:
        """Rows, record and lists of one spec from the prologue's outputs
        (index i of the chunk). Its own count rows are created here; who
        writes them is settled in _wire."""
        L, C, N = self._L, self.C, self.N
        t = self.T
        rec = np.zeros(L.W, np.int32)
        req = np.asarray(a["req"]).astype(np.int64) // self._gcd
        nz = np.asarray(a["nz_req"]).astype(np.int64) // self._gcd[:2]
        if not self._fits(max(int(req.max(initial=0)),
                              int(nz.max(initial=0)))):
            # a narrow session: the rebuild, with this spec, is wide
            raise PallasUnsupported("request too large for the session's "
                                    "form", reason="resource-magnitude")
        rec[L.REQ:L.REQ + self.R] = req
        rec[L.CHK:L.CHK + self.R] = np.asarray(a["req_check"])
        rec[L.HAS] = int(np.asarray(a["req_has_any"]))
        rec[L.NZ:L.NZ + 2] = nz
        rec[L.IPAP] = int(S["ipa_present"][i])
        for j, k in ((ST_MASK, "static_mask"), (ST_RAW_IPA, "raw_ipa"),
                     (ST_TAINT, "cnt_taint"), (ST_NODEAFF, "cnt_nodeaff"),
                     (ST_IMAGE, "sc_image"), (ST_AVOID, "sc_avoid"),
                     (ST_HAS_ALL, "s_has_all"), (ST_SRC, "s_src")):
            rec[L.STAT + j] = self._intern_srow(self._node_row(S[k][i]))
        valid_nodes = self._c["valid"].astype(bool)
        rows = {"f": [0] * C, "s": [0] * C, "f_pair": [0] * C,
                "s_pair": [0] * C, "s_src": [-1] * C, "score": 0,
                # pair row -> writer -> weight a pod of it adds there,
                # and what the snapshot counted (_score_bound)
                "score_w": {}, "static_bound": int(np.abs(np.asarray(
                    S["raw_ipa"][i], np.int64)).max(initial=0))}
        self._score_bound(rows)
        for c in range(C):
            if S["f_valid"][i, c]:
                column = S["f_pair_cn"][i][:, c]
                o = L.PF + PF_W * c
                rows["f_pair"][c] = self._intern_srow(self._node_row(
                    np.where(valid_nodes, column, -1), fill=-1))
                rows["f"][c] = self._new_cnt(S["f_cnt0"][i, c][column])
                rec[o:o + PF_W] = (
                    1, int(S["f_skew"][i, c]), int(S["f_self_match"][i, c]),
                    rows["f"][c],
                    self._intern_srow(self._node_row(
                        S["f_reg_real"][i, c][column])),
                    self._intern_srow(self._node_row(
                        S["f_key_on_node"][i][:, c])))
            if S["s_valid"][i, c]:
                column = S["s_pair_cn"][i][:, c]
                o = L.PS + PS_W * c
                # the prologue's hostname flag selects the log(n_scored)
                # weight semantics, not just a representation
                perno = bool(S["s_hostname"][i, c])
                rows["s_pair"][c] = self._intern_srow(self._node_row(
                    np.where(valid_nodes, column, -1), fill=-1))
                key = zrow = zvn = 0
                if perno:
                    rows["s"][c] = self._new_cnt(S["h_cnt0"][i, c])
                else:
                    key, zrow = self._zone_key(column)
                    zvn = self._intern_srow(self._node_row(
                        (column > 0) & valid_nodes))
                    rows["s"][c] = self._new_cnt(S["s_cnt0"][i, c][column])
                    rows["s_src"][c] = int(rec[L.STAT + ST_SRC])
                rec[o:o + PS_W] = (
                    1, int(S["s_skew"][i, c]), int(S["s_first"][i, c]), key,
                    int(perno), rows["s"][c], zvn,
                    self._intern_srow(self._node_row(
                        S["s_key_on_node"][i][:, c])), zrow)
        rec[L.FS:L.FS + C * C] = S["f_same_key"][i].astype(np.int32).ravel()
        rec[L.SS:L.SS + C * C] = S["s_same_key"][i].astype(np.int32).ravel()
        anti: List[Tuple[int, int, int]] = []
        aff: List[Tuple[int, int]] = []
        rows["anti"], rows["aff"] = {}, {}
        has_terms = False
        if self.dyn_ipa:
            io = L.IPA
            rec[io] = int(S["ipa_has_aff"][i])
            rec[io + 1] = int(S["ipa_self_match_all"][i])
            rec[io + 2] = min(int(S["ipa_aff_total"][i]), POS_BIG - 1)
            rec[io + 3] = self._intern_srow(
                self._node_row(S["ipa_fail_existing"][i]))
            rec[io + 4] = self._intern_srow(
                self._node_row(S["ipa_aff_all_keys"][i]))
            for tau in np.flatnonzero(np.asarray(a["ipaaa_valid"])):
                rows["anti"][int(tau)] = self._new_cnt()
                anti.append((
                    self._intern_srow(self._node_row(
                        S["ipa_anti_cnt_n"][i][:, tau])),
                    self._intern_srow(self._node_row(
                        S["ipa_anti_key_on_node"][i][:, tau])),
                    rows["anti"][int(tau)]))
            for tau in np.flatnonzero(np.asarray(a["ipaa_valid"])):
                rows["aff"][int(tau)] = self._new_cnt()
                aff.append((
                    self._intern_srow(self._node_row(
                        S["ipa_aff_cnt_n"][i][:, tau])),
                    rows["aff"][int(tau)]))
            has_terms = bool(anti or aff
                             or np.asarray(a["ipap_valid"]).any())
        self._spec[t * L.W:(t + 1) * L.W] = rec
        for k in _STACK_KEYS:
            self._stack[k][t] = np.asarray(a[k])
        pp, pk, _ = self._label_batch([a])
        self._lab_pair[t], self._lab_key[t] = pp[0], pk[0]
        self._fps[template_fingerprint(a)] = t
        self._arrays.append(a)
        self._has_terms[t] = has_terms
        self._touch.append([])
        self._repel.append([])
        self._repel_key.append({})
        self._anti.append(anti)
        self._aff.append(aff)
        self._rows.append(rows)
        self._dirty.append(True)
        self.T = t + 1

    def _add_touch(self, writer: int, row: int, pair: int, w: int,
                   src: int = -1) -> None:
        self._touch[writer].append((row, pair, int(w), src))
        self._dirty[writer] = True

    def _add_score(self, writer: int, reader: int, pair: int,
                   w: int) -> None:
        """D4/D5: a pod of `writer` moves `reader`'s raw IPA score by w
        in its topology group. The reader's score row is created with its
        first writer; no presence is kept: a score row no writer has
        reached reads 0 on every lane, and so does its normalization."""
        rows = self._rows[reader]
        L = self._L
        if not rows["score"]:
            rows["score"] = self._new_cnt()
            self._score_rows.add(rows["score"])
            self._spec[reader * L.W + L.IPA + 11] = rows["score"]
            self._spec[reader * L.W + L.IPAP] = 1
        by_writer = rows["score_w"].setdefault(pair, {})
        by_writer[writer] = by_writer.get(writer, 0) + abs(w)
        self._score_bound(rows)
        self._add_touch(writer, rows["score"], pair, w)
        self._score_pairs += 1

    def _score_bound(self, rows: Dict) -> None:
        """The most a reader's raw IPA score can read on a lane: what the
        snapshot counted at its admission, and for each topology key the
        most pods a domain can hold times the most one pod of a writer
        adds. Two lanes differ by at most twice that: from IPA_EXACT_MAX
        on, ipa_normalize's exact form, and the kernel's sentinels, would
        no longer hold (refused: the hoisted session takes the cluster)."""
        bound = rows["static_bound"]
        for pair, by_writer in rows["score_w"].items():
            bound += self._pair_cap[pair] * max(by_writer.values())
        rows["score_bound"] = bound
        if 2 * bound >= IPA_EXACT_MAX:
            raise PallasUnsupported(
                "IPA scores could leave the exact int32 normalization",
                reason="ipa-score-weights")

    def _wire(self, first: int) -> None:
        """Who counts toward whose rows, for every (reader, writer) pair
        with a spec admitted now (index >= first) on either side: the
        match booleans of ops/hoisted.py _match_matrices / _term_gates,
        evaluated on the host, turned into touch entries of the writer."""
        T = self.T
        live = {k: v[:T] for k, v in self._stack.items()}
        pp, pk = self._lab_pair[:T], self._lab_key[:T]
        ns = live["self_ns"].astype(np.int64)

        def blocks(prefix, spread=False):
            """The match booleans that have a new spec on a side, as
            [(m [owners, slots, entities], first owner, first entity)]:
            new owners against every label set, old owners against the
            new label sets — O(new x live), not O(live^2)."""
            out = []
            for o0, o1, e0 in ((first, T, 0), (0, first, first)):
                if o1 > o0:
                    tab = {k: v[o0:o1] for k, v in live.items()}
                    out.append((self._match(
                        tab, prefix, pp[e0:], pk[e0:], ns[e0:],
                        ns[o0:o1] if spread else None), o0, e0))
            return out

        def pairs_of(prefix, spread=False):
            """(owner, slot, entity) triples with a new spec on a side."""
            for m, o0, e0 in blocks(prefix, spread):
                o, x, e = np.nonzero(m)
                yield from zip((o + o0).tolist(), x.tolist(),
                               (e + e0).tolist())

        for side in ("f", "s"):
            for o, c, e in pairs_of(f"pts{side}", spread=True):
                row = self._rows[o][side][c]
                if row:
                    self._add_touch(e, row, self._rows[o][f"{side}_pair"][c],
                                    1, self._rows[o]["s_src"][c]
                                    if side == "s" else -1)
        if not self.dyn_ipa:
            return
        hard_w = int(np.asarray(self._c["hard_pod_affinity_weight"]))
        for o, tau, e in pairs_of("ipaaa"):
            pair = self._pair_row(int(live["ipaaa_key"][o, tau]))
            # D2: e counts toward o's own anti term
            self._add_touch(e, self._rows[o]["anti"][tau], pair, 1)
            # D1: o's pods repel e wherever they sit, by the term's key
            key = int(live["ipaaa_key"][o, tau])
            row = self._repel_key[e].get(key)
            if row is None:
                row = self._repel_key[e][key] = self._new_cnt()
                self._repel[e].append(row)
                self._dirty[e] = True
            self._add_touch(o, row, pair, 1)
        a_valid = live["ipaa_valid"].astype(bool)
        for m_aff, o0, e0 in blocks("ipaa"):
            av = a_valid[o0:o0 + m_aff.shape[0]]
            match_all = (np.where(av[:, :, None], m_aff, True).all(axis=1)
                         & av.any(axis=1)[:, None])           # [O, E]
            for o, e in zip(*np.nonzero(match_all)):
                o, e = int(o) + o0, int(e) + e0
                for tau in np.flatnonzero(a_valid[o]):        # D3
                    self._add_touch(
                        e, self._rows[o]["aff"][int(tau)],
                        self._pair_row(int(live["ipaa_key"][o, tau])), 1)
            if hard_w > 0:                                    # D4, required
                for o, tau, e in zip(*np.nonzero(m_aff)):
                    o, e = int(o) + o0, int(e) + e0
                    self._add_score(o, e, self._pair_row(
                        int(live["ipaa_key"][o, tau])), hard_w)
        for o, tau, e in pairs_of("ipap"):
            w = int(live["ipap_weight"][o, tau])
            pair = self._pair_row(int(live["ipap_key"][o, tau]))
            self._add_score(o, e, pair, w)                    # D4, preferred
            self._add_score(e, o, pair, w)                    # D5

    def _write_lists(self, t: int) -> None:
        """Append spec t's lists to the pool (the pool is append-only: a
        list that grew is written anew, its old copy is dead until the
        next rebuild) and point the record at them."""
        L = self._L
        words: List[int] = []

        def put(items) -> int:
            off = self._pool_n + len(words)
            for it in items:
                words.extend(it if isinstance(it, tuple) else (it,))
            return off

        base = t * L.W
        self._spec[base + L.TCH] = put(self._touch[t])
        self._spec[base + L.TCH + 1] = len(self._touch[t])
        if self.dyn_ipa:
            io = base + L.IPA
            self._spec[io + 5] = put(self._repel[t])
            self._spec[io + 6] = len(self._repel[t])
            self._spec[io + 7] = put(self._anti[t])
            self._spec[io + 8] = len(self._anti[t])
            self._spec[io + 9] = put(self._aff[t])
            self._spec[io + 10] = len(self._aff[t])
        if self._pool_n + len(words) > self.PC:
            raise TableFull("list pool is full", reason="table-pool")
        self._pool[self._pool_n:self._pool_n + len(words)] = words
        self._pool_n += len(words)
        self._dirty[t] = False

    # -- scheduling --------------------------------------------------------

    def schedule(self, pod_arrays_list: List[Dict]):
        """Enqueue one batch; returns the (8, Bp) device result rows —
        row 0 best / row 1 score / row 2 n_feasible. decisions() blocks.
        Host work is one dictionary lookup a pod: what a spec matches was
        settled when it was admitted."""
        B = len(pod_arrays_list)
        # pow2 length bucket: each distinct Bp is a fresh compile, and
        # production batches are ragged
        Bp = batch_bucket(B, minimum=LANE)
        meta = np.zeros(1 + Bp, np.int32)
        meta[0] = B
        fps = self._fps
        for i, pa in enumerate(pod_arrays_list):
            if bool(np.asarray(pa["has_node_name"])):
                raise ValueError("session pods must be unbound")
            meta[1 + i] = fps[template_fingerprint(pa)]
        out = self._run_dispatch(meta)
        tm = meta[1:1 + B]
        # bucket rides the result so a harvest-side device fault can
        # retire exactly the executable that produced the bad payload
        # (tpu_backend.py retry path)
        specs = np.unique(tm).tolist()
        written = {e[0] for t in specs for e in self._touch[t]}
        read = {self._rows[t]["score"] for t in specs}
        score = self._spec[tm * self._L.W + self._L.IPAP] != 0
        return {"rows": out, "n": B, "bucket": Bp,
                # what the launch carried, for its dispatch span
                "templates": len(specs),
                "term_pods": int(self._has_terms[tm].sum()),
                "count_rows": len(written),
                "score_pods": int(score.sum()),
                "score_rows": len((written | read) & self._score_rows)}

    @staticmethod
    # ktpu: allow-sync(harvest decode: host consumes batch verdicts after the launch completes)
    def decisions(ys) -> List[int]:
        return [int(v) for v in np.asarray(ys["rows"])[0, :ys["n"]]]

    def retire_exec(self, bucket: Optional[int] = None) -> int:
        """Retire AOT executables after a device fault: a dispatch that
        raised, wedged, or harvested garbage leaves its compiled program
        suspect. Entries are pinned to None (= dispatch through jit), the
        same retired state the arg-mismatch path uses — warm_buckets
        never resurrects a retired entry, and _run_dispatch never
        recompiles one. With `bucket` given, an absent entry is pinned
        too: the backend quarantines a suspect bucket on every REBUILT
        session (the _exec cache dies with its session, but the fault
        does not), and lifts it only after the bucket harvests cleanly
        through jit. bucket None retires every existing entry. Returns
        the number of entries pinned."""
        if bucket is not None:
            if self._exec.get((bucket, "full"), _MISSING) is not None:
                self._exec[(bucket, "full")] = None
                return 1
            return 0
        n = 0
        for key in list(self._exec):
            if self._exec.get(key) is not None:
                self._exec[key] = None
                n += 1
        return n

    # -- incremental device-state deltas -----------------------------------

    # what the backend's delta classifier matches a foreign pod against:
    # the live specs' selector tables, [T, ...]
    @property
    def _tp_np(self) -> Dict:
        return {k: self._stack[k][: self.T]
                for k in _PTS_KEYS + ("self_ns",)}

    @property
    def _term_np(self) -> Optional[Dict]:
        if not self.dyn_ipa:
            return None
        return {k: self._stack[k][: self.T] for k in _TERM_KEYS
                if not k.endswith("_key")}

    def delta_compatible(self, dres, dnz) -> bool:
        """A utilization delta rides this session's int32 carry only when
        the per-dimension GCD rescale stays exact on it and the rescaled
        magnitudes fit the form the session was built in."""
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
        return self._fits(hi)

    def _delta_entries(self, d) -> List[tuple]:
        """One backend delta dict -> [(node, dres[Rp], dnzpc[8], row,
        pair, w, src)]: the utilization move on the first entry, then one
        entry per count row the pod's labels are counted on (d["mf"] /
        d["ms"] are [specs at the time of the event, C])."""
        rp = self._requested0.shape[0]
        dres = np.zeros(rp, np.int32)
        dnzpc = np.zeros(SUB, np.int32)
        touches: List[tuple] = []
        if d["kind"] == "node-alloc":
            dnzpc[3] = d["dallowed"]
        else:
            dres[: self.R] = (
                np.asarray(d["dres"], np.int64) // self._gcd
            ).astype(np.int32)
            dnzpc[0] = int(d["dnz"][0]) // int(self._gcd[0])
            dnzpc[1] = int(d["dnz"][1]) // int(self._gcd[1])
            dnzpc[2] = d["dcount"]
            for side, m in (("f", d["mf"]), ("s", d["ms"])):
                for t, c in zip(*np.nonzero(np.asarray(m)[: self.T])):
                    rows = self._rows[t]
                    if rows[side][c]:
                        touches.append((
                            rows[side][c], rows[f"{side}_pair"][c],
                            int(m[t][c]),
                            rows["s_src"][c] if side == "s" else -1))
        touches = touches or [(0, 0, 0, -1)]
        zero_r, zero_n = np.zeros(rp, np.int32), np.zeros(SUB, np.int32)
        return [(d["node"], dres if i == 0 else zero_r,
                 dnzpc if i == 0 else zero_n) + tc
                for i, tc in enumerate(touches)]

    def _patch_alloc_static(self, d) -> None:
        """node-alloc patch: the static alloc columns move (the prologue
        never reads alloc, so nothing else needs recompute). The
        CUMULATIVE rescaled magnitude must fit the form the session was
        built in, and the capacities must stay inside the exact
        balanced form — else this raises (the backend's apply wrapper
        downgrades to a rebuild, whose own envelope then decides)."""
        scaled = (np.asarray(d["dalloc"], np.int64) // self._gcd).astype(
            np.int32)
        n = d["node"]
        col = self._alloc[: self.R, n].astype(np.int64) + scaled
        if not self._fits(int(np.abs(col).max(initial=0))):
            raise ValueError(
                "cumulative alloc patches exceed the session's form")
        self._alloc[: self.R, n] += scaled
        self._balanced_tables()
        st = self._statics
        st["alloc"] = st["alloc"].at[:self.R, n].add(jnp.asarray(scaled))
        st["balgrp"] = jnp.asarray(self._balgrp)
        st["bad"] = jnp.asarray(self._bad)

    def apply_deltas(self, deltas: List[Dict]) -> None:
        """Absorb batched cluster-event deltas into the carry (and the
        alloc statics) without a session rebuild — the pallas face of
        the session-delta contract (see HoistedSession.apply_deltas): one
        fused _delta_scan launch chains onto the in-flight carry."""
        for d in deltas:
            if d["kind"] == "node-alloc":
                self._patch_alloc_static(d)
        entries = [e for d in deltas for e in self._delta_entries(d)]
        ep = batch_bucket(len(entries), minimum=8)  # pow2: one compile each
        self.last_delta_shape = (len(entries), ep)
        rp = self._requested0.shape[0]
        xs = {
            "node": np.zeros(ep, np.int32),
            "dres": np.zeros((ep, rp), np.int32),
            "dnzpc": np.zeros((ep, SUB), np.int32),
            "row": np.zeros(ep, np.int32), "pair": np.zeros(ep, np.int32),
            "w": np.zeros(ep, np.int32), "src": np.full(ep, -1, np.int32),
        }
        for i, (n, dres, dnzpc, row, pair, w, src) in enumerate(entries):
            xs["node"][i] = n
            xs["dres"][i] = dres
            xs["dnzpc"][i] = dnzpc
            xs["row"][i], xs["pair"][i] = row, pair
            xs["w"][i], xs["src"][i] = w, src
        self._carry = _delta_scan(
            self._initial_carry(), self._statics["srow"],
            {k: jnp.asarray(v) for k, v in xs.items()})

    def _initial_carry(self) -> Dict:
        """The live carry; from the host seeds if no launch has run."""
        if self._carry is None:
            self._carry = {
                "requested": jnp.asarray(self._requested0),
                "nzpc": jnp.asarray(self._nzpc0),
                "cnt": jnp.asarray(self._cnt0),
            }
        return self._carry

    # ktpu: allow-sync(tests and probes: one count row, to the host)
    def count_row(self, fp, side: str, c: int) -> np.ndarray:
        """The carried count row of spec `fp`'s spread constraint c
        ("f" filter / "s" score), per node lane."""
        row = self._rows[self._fps[fp]][side][c]
        return np.asarray(self._initial_carry()["cnt"][row])[: self.N]

    # -- dispatch plumbing: persistent executables ------------------------

    def _carry_struct(self) -> Dict:
        """ShapeDtypeStructs of the carry, WITHOUT touching self._carry:
        warm_buckets runs on a daemon thread concurrently with
        schedule()."""
        return {
            "requested": jax.ShapeDtypeStruct(
                self._requested0.shape, jnp.int32),
            "nzpc": jax.ShapeDtypeStruct(self._nzpc0.shape, jnp.int32),
            "cnt": jax.ShapeDtypeStruct((self.RC, self.Np), jnp.int32),
        }

    def _compile_exec(self, Bp: int):
        """AOT lower+compile the dispatch for one batch bucket. The
        compiled executable is invoked DIRECTLY on the serving path
        (persistent executable reuse): every dispatch then runs the same
        loaded program object — no jit-dispatch signature hashing, and no
        per-launch program re-resolution for the runtime to pay. Its
        argument shapes are capacities: no admission changes them."""
        def st(x):
            return jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype)

        statics_s = {k: st(v) for k, v in self._statics.items()}
        return _dispatch.lower(
            self._cfg, statics_s,
            jax.ShapeDtypeStruct((1 + Bp,), jnp.int32),
            self._carry_struct()).compile()

    def _run_dispatch(self, meta: np.ndarray):
        """Execute one dispatch through the persistent-executable cache
        (fallback: the plain jit path). Owns the carry swap — the carry
        buffers are donated to the launch and replaced by its outputs."""
        self._initial_carry()
        Bp = int(meta.shape[0]) - 1
        meta = jnp.asarray(meta)
        key = (Bp, "full")
        fn = self._exec.get(key, _MISSING)
        if fn is _MISSING:
            # Counted miss path: a dispatch-time compile is a stall the
            # device timeline must attribute (warm_buckets prefills are
            # deliberate and uncounted).
            from ..utils import devtime
            t0 = _time.perf_counter()
            try:
                fn = self._compile_exec(Bp)
            except Exception as e:  # noqa: BLE001 — the jit path serves; the error is kept
                fn = None
                self._exec_failed(Bp, "AOT compile failed", e)
            self._exec[key] = fn
            if devtime.enabled():
                devtime.TIMELINE.compile_event(
                    "pallas-bucket", t0, _time.perf_counter() - t0,
                    bucket=Bp, mode="full", ok=fn is not None)
        if fn is not None:
            try:
                out, self._carry = fn(self._statics, meta, self._carry)
                return out
            except (TypeError, ValueError) as e:
                # arg-structure/layout mismatch is raised BEFORE
                # execution (carry buffers untouched): retire this
                # executable and serve through jit from now on
                self._exec[key] = None
                self._exec_failed(Bp, "AOT executable refused its args", e)
        out, self._carry = _dispatch(self._cfg, self._statics, meta,
                                     self._carry)
        return out

    def _exec_failed(self, bucket: int, what: str, e: BaseException) -> None:
        self.exec_errors[(bucket, "full")] = f"{what}: {type(e).__name__}: {e}"
        logger.error("pallas bucket %s: %s", bucket, what, exc_info=e)

    def stop_warm(self) -> None:
        """Ask a running warm_buckets to stop after the bucket it is
        compiling (backend close: no compile may outlive the process's
        orderly exit)."""
        self._warm_stop.set()

    def warm_buckets(self, sizes=(LANE, 256, 512, 1024, 2048)) -> None:
        """AOT-compile the dispatch for the ragged-tail batch buckets
        WITHOUT dispatching: .lower().compile() populates jax's caches
        including the persistent one, so a mid-window first-tail-bucket
        batch pays a cache hit instead of a fresh ~30s Mosaic compile.
        Compiled executables land in self._exec, so the serving path
        reuses the very same loaded program. Runs on a daemon thread: it
        never touches self._carry. A failure stops the warming (the lazy
        path would hit the same compiler error) and is recorded in
        exec_errors — without pinning the entry, so the serving path
        still makes its own attempt."""
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
                self._exec_failed(Bp, "AOT warm compile failed", e)
                return
            self._exec.setdefault((Bp, "full"), compiled)
        self._warm_delta_buckets()

    def _warm_delta_buckets(self) -> None:
        """Run _delta_scan once per entry bucket up to DELTA_WARM_MAX on a
        throwaway zero carry, so that a burst of pod deletes and adds (a
        preemption wave's victims, a churn cycle) compiles its bucket
        here and not inside a window. Once per process per shape; the
        live carry is never touched."""
        rp = self._requested0.shape[0]
        # an admission may donate the live table meanwhile: a zero one
        srow = self._statics["srow"]
        srow = jnp.zeros(srow.shape, srow.dtype)
        key = (self._requested0.shape, self._nzpc0.shape,
               (self.RC, self.Np), tuple(srow.shape))
        if key in _DELTA_WARMED:
            return
        carry = {k: jnp.zeros(v.shape, v.dtype)
                 for k, v in self._carry_struct().items()}
        ep = 8
        while ep <= DELTA_WARM_MAX and not self._warm_stop.is_set():
            xs = {"node": np.zeros(ep, np.int32),
                  "dres": np.zeros((ep, rp), np.int32),
                  "dnzpc": np.zeros((ep, SUB), np.int32),
                  "row": np.zeros(ep, np.int32),
                  "pair": np.zeros(ep, np.int32),
                  "w": np.zeros(ep, np.int32),
                  "src": np.full(ep, -1, np.int32)}
            # the carry is donated: one buffer serves every bucket
            carry = _delta_scan(carry, srow, {k: jnp.asarray(v)
                                              for k, v in xs.items()})
            ep *= 2
        if ep > DELTA_WARM_MAX:
            _DELTA_WARMED.add(key)
