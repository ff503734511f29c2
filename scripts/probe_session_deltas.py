"""Probe: delta-apply vs full-rebuild cost, plus the per-event
classification a synthetic churn trace gets from the session-delta
classifier (ISSUE 5 tooling satellite).

Builds a TPU-backend cluster directly (no apiserver — this measures the
backend, not the loop), warms a live session, then replays a synthetic
churn trace shaped like the preemption benchmarks' event mix: victim
delete echoes, foreign batchable adds, affinity-pod adds, node
heartbeats, and allocatable-only node updates. For each event it prints
the classification (carry-delta / prologue-patch / structural /
heartbeat-noop), then times

  * one fused delta apply for the whole queued batch, vs
  * one full session rebuild (what every one of those events cost
    before this round),

both on the live device. Chip-runnable as-is; degrades to CPU exactly
like fault_drill.py (the backend rides the hoisted session there):

    JAX_PLATFORMS=cpu JAX_ENABLE_X64=1 python scripts/probe_session_deltas.py
"""

import argparse
import os
import random
import sys
import time

os.environ.setdefault("JAX_ENABLE_X64", "1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402,F401

from kubernetes_tpu.api import types as v1  # noqa: E402
from kubernetes_tpu.scheduler import metrics  # noqa: E402
from kubernetes_tpu.scheduler.internal.cache import SchedulerCache  # noqa: E402
from kubernetes_tpu.scheduler.tpu_backend import TPUBackend  # noqa: E402
from kubernetes_tpu.testing.synth import make_node, make_pod  # noqa: E402


def counter_total(counter) -> float:
    return sum(val for _, val in counter.items())


def build_cluster(n_nodes: int):
    cache = SchedulerCache()
    be = TPUBackend()
    cache.add_listener(be)
    for i in range(n_nodes):
        cache.add_node(make_node(
            f"node-{i}",
            labels={v1.LABEL_HOSTNAME: f"node-{i}", "zone": f"zone-{i % 3}"},
        ))
    return cache, be


def spread_pod(name, cpu="100m", node=""):
    return make_pod(
        name, namespace="default", cpu=cpu, memory="64Mi",
        labels={"app": "perf"},
        constraints=[v1.TopologySpreadConstraint(
            max_skew=1, topology_key=v1.LABEL_ZONE,
            when_unsatisfiable="ScheduleAnyway",
            label_selector=v1.LabelSelector(match_labels={"app": "perf"}),
        )],
        node_name=node,
    )


def anti_pod(name, node=""):
    return make_pod(
        name, namespace="default", cpu="100m", memory="64Mi",
        labels={"app": "anti"},
        affinity=v1.Affinity(pod_anti_affinity=v1.PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[
                v1.PodAffinityTerm(
                    label_selector=v1.LabelSelector(
                        match_labels={"app": "anti"}),
                    topology_key=v1.LABEL_HOSTNAME,
                )
            ]
        )),
        node_name=node,
    )


def classify_and_queue(be, event, payload) -> str:
    """Replay one trace event against the backend and report which class
    the classifier gave it (reading the queue/session state around the
    listener call — the probe's whole point is showing the classification)."""
    sess = be._session
    n_deltas = len(be._deltas)
    event(payload)
    if be._session is not sess or be._session is None:
        return "structural  (session teardown)"
    if len(be._deltas) == n_deltas:
        return "noop        (gated: heartbeat / never-encoded)"
    kind = be._deltas[-1]["kind"]
    if kind == "node-alloc":
        return "prologue-patch (alloc column)"
    return f"carry-delta ({kind})"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--nodes", type=int, default=200)
    ap.add_argument("--warm-pods", type=int, default=256)
    ap.add_argument("--events", type=int, default=64)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()

    rng = random.Random(args.seed)
    cache, be = build_cluster(args.nodes)
    # pre-size the pod table like the perf harness does: walking the
    # capacity ladder mid-trace is a (legitimate) structural rebuild and
    # would pollute the classification histogram this probe is after
    be.enc.reserve(pods=2 * (args.warm_pods + 3 * args.events) + 64)
    print(f"platform={jax.devices()[0].platform} nodes={args.nodes} "
          f"(session kind follows the ladder top: "
          f"{'pallas' if be.use_pallas else 'hoisted'})")

    # warm: build the session + compile the dispatch shapes; confirm the
    # binds into the cache (the informer echo the real loop produces —
    # swallowed by the assume-echo gate, and the precondition for their
    # later delete echoes to reach the listener at all)
    t0 = time.perf_counter()
    res = be.schedule_many(
        [spread_pod(f"warm-{i}") for i in range(args.warm_pods)])
    n_bound = sum(1 for _, n in res if n)
    victims = []
    for p, node in res:
        if not node:
            continue
        confirmed = spread_pod(p.metadata.name, node=node)
        cache.add_pod(confirmed)
        if len(victims) < args.events:
            victims.append(confirmed)
    print(f"warm batch: {n_bound}/{args.warm_pods} bound in "
          f"{time.perf_counter() - t0:.2f}s "
          f"(session={type(be._session).__name__})")
    trace = []
    for i in range(args.events):
        r = rng.random()
        if r < 0.45 and victims:
            v = victims.pop(rng.randrange(len(victims)))
            trace.append(("victim-delete-echo", cache.remove_pod, v))
        elif r < 0.70:
            trace.append((
                "foreign-batchable-add", cache.add_pod,
                spread_pod(f"foreign-{i}",
                           node=f"node-{rng.randrange(args.nodes)}"),
            ))
        elif r < 0.80:
            trace.append((
                "affinity-pod-add", cache.add_pod,
                anti_pod(f"anti-{i}",
                         node=f"node-{rng.randrange(args.nodes)}"),
            ))
        elif r < 0.90:
            j = rng.randrange(args.nodes)
            trace.append((
                "node-heartbeat", cache.update_node,
                make_node(f"node-{j}", labels={
                    v1.LABEL_HOSTNAME: f"node-{j}",
                    "zone": f"zone-{j % 3}"}),
            ))
        else:
            j = rng.randrange(args.nodes)
            trace.append((
                "node-alloc-update", cache.update_node,
                make_node(f"node-{j}", cpu="8", labels={
                    v1.LABEL_HOSTNAME: f"node-{j}",
                    "zone": f"zone-{j % 3}"}),
            ))

    print(f"\n--- per-event classification ({len(trace)} events) ---")
    by_class = {}
    for name, fn, payload in trace:
        cls = classify_and_queue(be, fn, payload)
        by_class[cls] = by_class.get(cls, 0) + 1
        print(f"  {name:24s} -> {cls}")
        if be._session is None:
            # keep the probe measuring the delta path: rebuild and go on
            be.schedule_many([spread_pod(f"rewarm-{name}-{len(by_class)}")])
    print("\nclassification histogram:")
    for cls, n in sorted(by_class.items()):
        print(f"  {n:4d}  {cls}")

    # timing: fused delta apply (whole queue, one launch) vs full
    # rebuild. Round 0 pays the delta-scan compile for this event-count
    # bucket; round 1 is the steady-state number (the compile is cached
    # persistently, like every other dispatch shape).
    be._apply_session_deltas_locked()  # land the trace leftovers first
    burst = max(8, args.events // 2)
    t_apply = 0.0
    for rnd in range(2):
        if be._session is None:
            be.schedule_many([spread_pod(f"rewarm-t{rnd}")])
        for i in range(burst):
            cache.add_pod(spread_pod(
                f"burst{rnd}-{i}", node=f"node-{rng.randrange(args.nodes)}"))
        queued = len(be._deltas)
        t0 = time.perf_counter()
        be._apply_session_deltas_locked()
        if be._session is not None:
            jax.block_until_ready(be._session._carry)
        t_apply = time.perf_counter() - t0
    t0 = time.perf_counter()
    with be._lock:
        be._invalidate_session("probe-timing")
        be._session = be._build_session()
    t_rebuild = time.perf_counter() - t0

    applies = counter_total(metrics.session_delta_applies)
    rebuilds = counter_total(metrics.session_rebuilds)
    print("\n--- cost ---")
    print(f"delta apply ({queued} queued events, one fused launch, "
          f"warm): {t_apply * 1e3:.1f} ms")
    print(f"full session rebuild (what each event used to cost):     "
          f"{t_rebuild * 1e3:.1f} ms")
    if t_apply > 0:
        print(f"ratio: {t_rebuild / max(t_apply, 1e-9):.1f}x per flush "
              f"(and the old path paid it per EVENT)")
    print(f"counters: delta_applies={applies:.0f} "
          f"session_rebuilds={rebuilds:.0f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
