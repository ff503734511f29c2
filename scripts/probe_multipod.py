"""Probe: multi-pod scan-step conflict rate and step cost vs k
(ISSUE 6 tooling satellite — picks the default KTPU_MULTIPOD_K per
workload class).

Builds a TPU-backend cluster directly (no apiserver — this measures the
session scan, not the loop), warms it to realistic utilization, then
runs the SAME measured batches through fresh sessions built at each k
in --ks for three workload profiles shaped like the bench matrix:

  * default   — soft zone-spread pods (Default-5000n shape): conflicts
                only through the fit/balanced/least recheck, so big k
                should hold a near-zero conflict rate until nodes fill;
  * pts       — HARD zone-spread (PTS-heavy shape): every pod of a step
                moves the zone counts every other pod reads, so the
                PTS match-gate fires and the rate approaches (k-1)/k;
  * ipachurn  — required anti-affinity by hostname (IPA-churn shape):
                the template-interference superset (G_ipa) is hot for
                the same reason.

For each (profile, k) the probe reports pods/step, the measured
conflict rate, per-pod step cost, and the implied speedup vs k=1 —
and asserts decisions stay bit-identical to the k=1 reference (the
whole point of EXACT conflict replay). CPU-runnable as-is through the
hoisted session (the in-device lax.cond replay path):

    JAX_PLATFORMS=cpu JAX_ENABLE_X64=1 python scripts/probe_multipod.py

On a TPU it additionally probes the pallas session (conflict-SUFFIX
contract: the probe replays the uncommitted suffix through the live
session exactly like tpu_backend._harvest_locked does).
"""

import argparse
import os
import sys
import time

os.environ.setdefault("JAX_ENABLE_X64", "1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402,F401

from kubernetes_tpu.api import types as v1  # noqa: E402
from kubernetes_tpu.ops.hoisted import HoistedSession  # noqa: E402
from kubernetes_tpu.scheduler.internal.cache import SchedulerCache  # noqa: E402
from kubernetes_tpu.scheduler.tpu_backend import TPUBackend  # noqa: E402
from kubernetes_tpu.testing.synth import make_node, make_pod  # noqa: E402


def spread_pod(name, hard=False):
    return make_pod(
        name, namespace="default", cpu="100m", memory="64Mi",
        labels={"app": "perf"},
        constraints=[v1.TopologySpreadConstraint(
            max_skew=1, topology_key=v1.LABEL_ZONE,
            when_unsatisfiable=(
                "DoNotSchedule" if hard else "ScheduleAnyway"),
            label_selector=v1.LabelSelector(match_labels={"app": "perf"}),
        )],
    )


def anti_pod(name):
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
    )


PROFILES = {
    "default": lambda i: spread_pod(f"d-{i}"),
    "pts": lambda i: spread_pod(f"p-{i}", hard=True),
    "ipachurn": lambda i: anti_pod(f"a-{i}"),
}


def build_backend(n_nodes: int, reserve_pods: int):
    cache = SchedulerCache()
    be = TPUBackend()
    cache.add_listener(be)
    for i in range(n_nodes):
        cache.add_node(make_node(
            f"node-{i}",
            labels={v1.LABEL_HOSTNAME: f"node-{i}",
                    v1.LABEL_ZONE: f"zone-{i % 3}"},
        ))
    # pre-size the pod table like the perf harness: a capacity-ladder
    # walk mid-probe would be a structural rebuild, not what we measure
    be.enc.reserve(pods=reserve_pods)
    return cache, be


def land_batch(session, arrays):
    """Run one batch to completion the way tpu_backend._harvest_locked
    does: schedule, then — for sessions on the conflict-SUFFIX contract
    (pallas/sharded; hoisted replays in-device and always returns
    suffix None) — replay the uncommitted suffix through the session
    until everything landed. Returns (decisions, n_conflicts)."""
    decisions = []
    conflicts = 0
    while arrays:
        ys = session.schedule(arrays)
        got = session.decisions(ys)
        n_conf, suffix = type(session).conflict_stats(ys)
        conflicts += n_conf
        if suffix is None:
            decisions.extend(got)
            break
        decisions.extend(got[:suffix])
        arrays = arrays[suffix:]
    return decisions, conflicts


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--nodes", type=int, default=200)
    ap.add_argument("--warm-pods", type=int, default=128)
    ap.add_argument("--pods", type=int, default=512,
                    help="measured pods per profile")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--ks", type=int, nargs="+", default=[1, 2, 4, 8, 16])
    args = ap.parse_args()

    platform = jax.devices()[0].platform
    print(f"platform={platform} nodes={args.nodes} "
          f"pods={args.pods} batch={args.batch} ks={args.ks}")

    for profile, mk in PROFILES.items():
        cache, be = build_backend(
            args.nodes, 2 * (args.warm_pods + args.pods) + 64)
        # warm through the backend: registers the template, fills the
        # cluster to realistic utilization, and confirms binds into the
        # encoding (so the measured sessions see occupied nodes)
        warm = [mk(f"warm-{i}") for i in range(args.warm_pods)]
        for p, node in be.schedule_many(warm):
            if node:
                p.spec.node_name = node  # landed in enc by schedule_many
        templates = list(be._known_templates.values())
        cluster = be.enc.device_state()
        weights = be.weights
        arrays = []
        for i in range(args.pods):
            enc = be.pe.encode(mk(i))
            arrays.append(
                {k: v for k, v in enc.items() if not k.startswith("_")})
        batches = [arrays[i:i + args.batch]
                   for i in range(0, len(arrays), args.batch)]

        sessions = {"hoisted": lambda k: HoistedSession(
            cluster, templates, weights, multipod_k=k)}
        # (the table PallasSession has no multi-pod step: pods of one
        # spec all pick the same node against the same carry)

        for kind, build in sessions.items():
            print(f"\n--- {profile} / {kind} ---")
            ref = None
            base_cost = None
            for k in args.ks:
                sess = build(k)
                # warm dispatch: absorb the (k-specific) scan compile
                land_batch(build(k), batches[0])
                t0 = time.perf_counter()
                decisions = []
                conflicts = 0
                for b in batches:
                    d, c = land_batch(sess, list(b))
                    decisions.extend(d)
                    conflicts += c
                dt = time.perf_counter() - t0
                if ref is None:
                    ref = decisions
                    base_cost = dt
                ok = decisions == ref
                rate = conflicts / max(1, len(decisions))
                print(f"  k={k:3d}: {1e6 * dt / len(decisions):8.1f} "
                      f"us/pod  conflict_rate={rate:6.3f}  "
                      f"speedup_vs_k1={base_cost / dt:5.2f}x  "
                      f"parity={'OK' if ok else 'MISMATCH'}")
                if not ok:
                    print(f"!! {profile}/{kind} k={k}: decisions diverged "
                          f"from the k=1 reference", file=sys.stderr)
                    return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
