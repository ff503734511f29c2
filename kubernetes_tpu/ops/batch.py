"""Batched sequential scheduling: B pods in ONE device dispatch.

The reference schedules strictly one pod per cycle (reference:
pkg/scheduler/scheduler.go:427 scheduleOne), paying the full host loop per
pod. A device dispatch per pod would be all launch overhead; this module
keeps the decision semantics sequential — pod i sees the assumed state of
pods 0..i-1, exactly like the assume-cache (pkg/scheduler/internal/cache/
cache.go:361 AssumePod) — but runs the whole batch inside one `lax.scan`:

    carry = mutable slice of cluster state (requested, nz_requested,
            pod_count + the pod-row table)
    step  = fused filter/score kernel (ops/kernel.py) -> argmax ->
            in-carry assume update

Restrictions (callers fall back to the per-pod path otherwise):
  * batch pods must share encoded array shapes (template-stamped pods do);
  * batch pods must carry no pod-(anti-)affinity terms and no host ports —
    those mutate the term/port tables, which stay static in the carry.
    Labels, resources, spread constraints, node affinity are all fine:
    their effect on later pods flows through the carried pod rows.

Tie-breaking is lowest-node-index (deterministic argmax) rather than the
reference's reservoir sample over ties (core/generic_scheduler.go:152);
the A/B decision tests pin the oracle to the same rule.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import DEFAULT_WEIGHTS, schedule_pod

# cluster arrays mutated by the in-scan assume update
CARRY_KEYS = (
    "requested", "nz_requested", "pod_count",
    "ppair", "pkey", "pnode", "pns", "pterm", "pvalid",
)


def _step(static_c: Dict, weights: Dict, carry: Dict, x: Dict):
    c = dict(static_c)
    c.update(carry)
    out = schedule_pod(c, x["pod"], weights)
    total = out["total"]
    best = jnp.argmax(total).astype(jnp.int32)
    feasible = (total[best] >= 0) & x["valid"]
    p = x["pod"]
    add = feasible.astype(jnp.int64)
    carry = dict(carry)
    carry["requested"] = carry["requested"].at[best].add(p["req"] * add)
    carry["nz_requested"] = carry["nz_requested"].at[best].add(p["nz_req"] * add)
    carry["pod_count"] = carry["pod_count"].at[best].add(add.astype(jnp.int32))
    pidx = x["pidx"]
    carry["pvalid"] = carry["pvalid"].at[pidx].set(feasible)
    carry["ppair"] = carry["ppair"].at[pidx].set(p["self_ppair"])
    carry["pkey"] = carry["pkey"].at[pidx].set(p["self_pkey"])
    carry["pnode"] = carry["pnode"].at[pidx].set(jnp.where(feasible, best, 0))
    carry["pns"] = carry["pns"].at[pidx].set(p["self_ns"])
    carry["pterm"] = carry["pterm"].at[pidx].set(False)
    y = {
        "best": jnp.where(feasible, best, -1),
        "score": jnp.where(feasible, total[best], -1),
        "n_feasible": jnp.sum(out["feasible"].astype(jnp.int32)),
    }
    return carry, y


@functools.partial(jax.jit, static_argnames=("weights_key",))
def _scan_batch(static_c: Dict, carry: Dict, xs: Dict, weights_key) -> Tuple[Dict, Dict]:
    # NOTE: no buffer donation — the carry aliases ClusterEncoding's cached
    # device arrays; donating would invalidate its copies.
    step = functools.partial(_step, static_c, dict(weights_key))
    return jax.lax.scan(step, carry, xs)


# -- pod-array packing ------------------------------------------------------
# Every host->device transfer carries a fixed cost; a batch's ~50
# stacked pod arrays are therefore packed host-side into one buffer per
# dtype group (bool / int32-ish / int64) and sliced back apart on-device
# inside the jit. 3 transfers per batch instead of ~50.

_GROUP_OF_DTYPE = {
    np.dtype(np.bool_): ("b", np.bool_),
    np.dtype(np.int8): ("i4", np.int32),
    np.dtype(np.int16): ("i4", np.int32),
    np.dtype(np.int32): ("i4", np.int32),
    np.dtype(np.int64): ("i8", np.int64),
}


def _pack_stacked(stacked: Dict[str, np.ndarray]):
    """-> ({group: [B, W] array}, layout) with layout hashable/static."""
    b = next(iter(stacked.values())).shape[0]
    offsets = {"b": 0, "i4": 0, "i8": 0}
    chunks = {"b": [], "i4": [], "i8": []}
    layout = []
    for key in sorted(stacked):
        arr = stacked[key]
        group, gdtype = _GROUP_OF_DTYPE[arr.dtype]
        flat = np.ascontiguousarray(arr.reshape(b, -1), dtype=gdtype)
        layout.append(
            (key, group, offsets[group], flat.shape[1], arr.shape[1:], arr.dtype.str)
        )
        offsets[group] += flat.shape[1]
        chunks[group].append(flat)
    packed = {
        g: (
            np.concatenate(chunks[g], axis=1)
            if chunks[g]
            else np.zeros((b, 0), np.dtype(np.bool_ if g == "b" else np.int32))
        )
        for g in chunks
    }
    return packed, tuple(layout)


def _unpack_stacked(packed: Dict, layout) -> Dict:
    """Inverse of _pack_stacked, traceable (runs inside jit)."""
    out = {}
    for key, group, off, width, shape, dtype_str in layout:
        b = packed[group].shape[0]
        sl = jax.lax.slice_in_dim(packed[group], off, off + width, axis=1)
        out[key] = sl.reshape((b,) + tuple(shape)).astype(jnp.dtype(dtype_str))
    return out


@functools.partial(jax.jit, static_argnames=("weights_key", "layout"))
def _scan_batch_packed(
    static_c: Dict, carry: Dict, packed: Dict, pidx, valid, weights_key, layout
) -> Tuple[Dict, Dict]:
    xs = {"pod": _unpack_stacked(packed, layout), "pidx": pidx, "valid": valid}
    step = functools.partial(_step, static_c, dict(weights_key))
    return jax.lax.scan(step, carry, xs)


def pod_batchable(pod_arrays: Dict) -> bool:
    """True if the encoded pod leaves term/port tables untouched when
    assumed: no required/preferred (anti-)affinity terms, no host ports."""
    return not (
        np.asarray(pod_arrays["ipaa_valid"]).any()
        or np.asarray(pod_arrays["ipaaa_valid"]).any()
        or np.asarray(pod_arrays["ipap_valid"]).any()
        or np.asarray(pod_arrays["want_valid"]).any()
    )


def shape_signature(pod_arrays: Dict) -> Tuple:
    return tuple(sorted((k, np.shape(v)) for k, v in pod_arrays.items()))


def schedule_batch(
    cluster: Dict,
    pod_arrays_list: List[Dict],
    free_slots: List[int],
    weights: Optional[Dict[str, int]] = None,
) -> Tuple[List[int], Dict]:
    """Schedule the batch sequentially on-device.

    cluster: full device dict (models/encoding.py device_state()).
    pod_arrays_list: encoded pods, all with identical shapes.
    free_slots: pre-allocated pod-table row ids, len >= len(batch).

    Returns (decisions, new_carry): decisions[i] is the chosen node index
    or -1; new_carry holds the post-batch mutable arrays (callers sync the
    host encoding from the returned decisions instead).
    """
    b = len(pod_arrays_list)
    assert len(free_slots) >= b
    sig0 = shape_signature(pod_arrays_list[0])
    for pa in pod_arrays_list[1:]:
        assert shape_signature(pa) == sig0, "batch pods must share shapes"
    # stack host-side, then pack into 3 dtype-grouped buffers: transfers
    # per batch drop from ~50 (one per key) to 3
    stacked = {
        k: np.stack([np.asarray(pa[k]) for pa in pod_arrays_list])
        for k in pod_arrays_list[0]
        if not k.startswith("_")
    }
    packed, layout = _pack_stacked(stacked)
    static_c = {k: v for k, v in cluster.items() if k not in CARRY_KEYS}
    carry = {k: cluster[k] for k in CARRY_KEYS}
    key = tuple(sorted((weights or DEFAULT_WEIGHTS).items()))
    new_carry, ys = _scan_batch_packed(
        static_c,
        carry,
        {g: jnp.asarray(a) for g, a in packed.items()},
        jnp.asarray(np.asarray(free_slots[:b], np.int32)),
        jnp.ones(b, bool),
        key,
        layout,
    )
    return [int(v) for v in np.asarray(ys["best"])], new_carry
