"""Least work of one table launch whose pods read InterPodAffinity scores,
counted from the launch's own mix as its `dispatch` span says it:

  pods, term_pods, rows   as tableroofline.py (rows: the distinct count
                          rows the launch's pods count toward, score rows
                          among them)
  score_pods              pods whose spec reads an IPA score
  score_rows              distinct score rows the launch's pods read or
                          write

On top of tableroofline.py's count:

  operations  per score pod and node lane 7: the static and the carried
              raw score added (1), their min and max over the filtered
              lanes (2), the difference to the min (1), its scale by 100
              and floor division by the range (2), the weighted add to
              the total (1): upstream's NormalizeScore as the reference
              takes it
  bytes       every score row read once, 4 bytes a lane (a row that is
              also written is counted again there: read and written)

It is the work the algorithm needs, whatever kernel does it: a kernel that
takes the floor in several steps, or reads a score row once per pod, does
more and shows a lower share. Like tableroofline.py it knows no per-pod
latency; read the share as a ratio between two PRs.
"""

from __future__ import annotations

from typing import Dict

from benchlib.roofline import padded_nodes
from benchlib.tableroofline import launch_work as table_work

OPS_PER_LANE_SCORE = 7


def launch_work(pods: int, term_pods: int, rows: int, score_pods: int,
                score_rows: int, n_nodes: int) -> Dict[str, float]:
    work = table_work(pods, term_pods, rows, n_nodes)
    lanes = padded_nodes(n_nodes)
    return {"ops": work["ops"] + float(OPS_PER_LANE_SCORE * score_pods
                                       * lanes),
            "bytes": work["bytes"] + float(score_rows * lanes * 4)}
