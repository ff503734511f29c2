"""Least work of one scheduling launch, counted from shapes alone.

The counts follow the algorithm in `reference.py`, not the kernel: one
launch decides `pods` pods one after another, each against every node
lane, with the per-node state carried from pod to pod on the chip.

  operations  per pod and node lane: the fit test (3 compares, 2 ands),
              least-allocated (2 x [sub, mul, div], add, shift),
              balanced-allocation (2 div, sub, abs, sub, mul, convert),
              zone spread (gather, sub, mul, div), the weighted sum (3),
              the running first-max (compare, select) and the assume
              (compare, 3 selects-adds): 36; a required anti-affinity
              term adds a count row to test and to assume: 4 more.
  bytes       the carried rows (requested cpu, memory, pod count, the
              per-zone counts' lane view; with a term, its count row) are
              read once and written once per launch, the static rows
              (allocatable cpu, memory, pods, zone id, valid) read once,
              4 bytes a lane; plus 8 bytes out per pod.

The operations are elementwise int32/f32 work of the vector unit, so they
are held against `vector_ops_per_s` of peaks.json (8 x 128 lanes x 4 ALUs
x the clock), not against the MXU's `flops_per_s`, which no scan could
reach. What this least time leaves out: the pods of one launch are decided
one after another, each needing the one before, so a real kernel also
pays a fixed latency per pod (reductions across lanes, scalar work) that
no published number bounds. A share of a few tenths of a percent therefore
says "bound by per-pod latency", not "a hundred times too slow"; steer a
kernel change by `kernel_us_per_pod` and read this share as its ratio
between two PRs.
"""

from __future__ import annotations

import json
import os
from typing import Dict

LANE = 128
OPS_PER_LANE = 36
OPS_PER_LANE_TERM = 4
CARRIED_ROWS = 4
STATIC_ROWS = 5


def peaks(device_kind: str) -> Dict:
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def padded_nodes(n_nodes: int) -> int:
    return -(-n_nodes // LANE) * LANE


def launch_work(pods: int, n_nodes: int, terms: int = 0) -> Dict[str, float]:
    lanes = padded_nodes(n_nodes)
    ops = pods * lanes * (OPS_PER_LANE + OPS_PER_LANE_TERM * terms)
    rows = 2 * (CARRIED_ROWS + terms) + STATIC_ROWS
    return {"ops": float(ops), "bytes": float(rows * lanes * 4 + 8 * pods)}


def least_seconds(work: Dict[str, float], peak: Dict) -> Dict:
    t_ops = work["ops"] / peak["vector_ops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_bytes),
            "bound": "operations" if t_ops >= t_bytes else "bytes"}
