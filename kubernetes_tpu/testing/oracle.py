"""First-max Go-semantics oracle: the plain reference the device path is
held to at any cluster size.

The framework's own filter and score plugins (the path the kernels are
decision-parity-tested against, tests/test_kernel_parity.py) decide each
pod sequentially with assume-by-snapshot-mutation, every node scored.
Only the tie-break differs from the reference scheduler: where
generic_scheduler.go:152 reservoir-samples among maxima, every device
path takes the LOWEST LANE among them (TPUBackend._select_host), and
lanes stand in NODE ORDER (api.types.node_order_key: by name, digit runs
as numbers), so the oracle takes the first of the maxima in that order —
from the names alone, never from the encoding it is compared with:
otherwise two correct schedulers could never be compared decision for
decision, and a scheduler that met the same nodes in another order would
decide differently.
"""

from __future__ import annotations

import random
from typing import List, Optional

from ..api import types as v1
from ..scheduler.core import GenericScheduler
from ..scheduler.framework.interface import CycleState, FitError
from ..scheduler.framework.runtime import Framework
from ..scheduler.framework.snapshot import Snapshot
from ..scheduler.plugins.registry import (
    default_plugins_without,
    new_in_tree_registry,
)


class _FirstMaxScheduler(GenericScheduler):
    def __init__(self):
        super().__init__(percentage_of_nodes_to_score=100,
                         rng=random.Random(0))

    def select_host(self, node_score_list) -> str:
        best = max(ns.score for ns in node_score_list)
        return min((ns.name for ns in node_score_list if ns.score == best),
                   key=v1.node_order_key)


def first_max_decisions(nodes: List[v1.Node], bound_pods: List[v1.Pod],
                        pending: List[v1.Pod]) -> List[Optional[str]]:
    """Node name (None = unschedulable) for each pending pod, decided in
    order against `bound_pods` on `nodes` (in any order: ties go to the
    first in node order), each decision assumed before the next. The
    pending pods are mutated (spec.node_name) — pass copies of anything
    still needed pristine."""
    snap = Snapshot.from_objects(bound_pods, nodes)
    fwk = Framework(
        new_in_tree_registry(),
        plugins=default_plugins_without("DefaultPreemption"),
        snapshot_fn=lambda: snap,
    )
    sched = _FirstMaxScheduler()
    out: List[Optional[str]] = []
    for pod in pending:
        try:
            host = sched.schedule(CycleState(), fwk, pod, snap).suggested_host
        except FitError:
            out.append(None)
            continue
        pod.spec.node_name = host
        snap.get(host).add_pod(pod)
        out.append(host)
    return out
