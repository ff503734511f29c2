"""Least work of one scheduling launch over a MIX of pod specs, counted
from the launch's own mix.

`roofline.py` beside this file takes one `terms` flag for a whole run: all
pods carry a term or none does, and the launch carries one count row. A
launch over hundreds of Deployments is neither, so the count here takes
what the launch really held, as its `dispatch` span says it:

  pods        pods decided in the launch (`n`)
  term_pods   of them, those whose spec carries an affinity term
  rows        distinct count rows — one per (spec, constraint) — that the
              launch's pods count toward
  lanes       node lanes, padded to 128 as roofline.padded_nodes

The derivation is roofline.py's, per pod instead of per run:

  operations  per pod and node lane 36 (fit, least-allocated,
              balanced-allocation, zone spread, weighted sum, running
              first-max, assume: roofline.OPS_PER_LANE), and 4 more for a
              pod that carries a term (roofline.OPS_PER_LANE_TERM: its
              count row is tested and assumed)
  bytes       the 4 carried utilization rows and every count row the
              launch touches are read once and written once, the 5 static
              rows read once, 4 bytes a lane; 8 bytes out a pod

It is the work the ALGORITHM needs (reference.py's), whatever kernel does
it: a kernel that reads its whole table every launch, or re-reads a row
per pod, does more and shows a lower share. Like roofline.py it knows no
per-pod latency; read the share as a ratio between two PRs.
"""

from __future__ import annotations

from typing import Dict

from benchlib.roofline import (
    CARRIED_ROWS,
    OPS_PER_LANE,
    OPS_PER_LANE_TERM,
    STATIC_ROWS,
    padded_nodes,
)


def launch_work(pods: int, term_pods: int, rows: int,
                n_nodes: int) -> Dict[str, float]:
    lanes = padded_nodes(n_nodes)
    ops = lanes * (OPS_PER_LANE * pods + OPS_PER_LANE_TERM * term_pods)
    moved = 2 * (CARRIED_ROWS + rows) + STATIC_ROWS
    return {"ops": float(ops), "bytes": float(moved * lanes * 4 + 8 * pods)}
