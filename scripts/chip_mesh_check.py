"""Multi-chip check of the mesh product path, at the Mesh-20000n shape.

The same harness path as chip_smoke.py (APIServer + informers + queue +
cache + Scheduler(backend="tpu") + binder) with the node axis sharded
over `--devices` chips of one host: 20000 nodes, 1024 init pods, 4096
measured pods, batch 1024 (scripts/bench_configs.py "mesh20k", whose
8-device rows need more chips than one host has). `--template plain` is
that row's template; `--template anti` stamps every pod with a required
hostname anti-affinity term — the term-template session, the path a
NameError in the sharded commit step used to send to the host oracle.

Establishes, and exits non-zero unless all hold: ShardedPallasSession is
the live session (one pallas@N/mesh-sharded build, nothing else), no
fault, retry, demotion or worker restart, every pod bound, every chip
holds exactly its 1/N slice of each node-sharded carry leaf (and reports
memory in use), and a prefix of further pods lands on the same nodes as
the single-chip compiled PallasSession — built on device 0 of the same
process from the same cluster state — would put them (one further batch:
both sessions then reuse the bucket the run already compiled).

One JSON row on stdout. With JAX_PLATFORMS=cpu the devices are simulated
and the reference session runs in the Pallas interpreter: a dry run of
this script, not a measurement.

    python scripts/chip_mesh_check.py --template plain
    python scripts/chip_mesh_check.py --template anti
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

os.environ.setdefault("JAX_ENABLE_X64", "1")
# CPU dry runs need virtual devices; the flag only multiplies the HOST
# platform, so it is harmless where the devices are real chips
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))



def _placement(sess, mesh) -> dict:
    """Where the sharded carry lives: per leaf, the spec and each
    device's slice shape; plus per-device bytes in use."""
    mesh_ids = sorted(int(d.id) for d in mesh.devices.ravel())
    leaves = {}
    node_sharded = 0
    even = True
    for name, arr in sess._carry.items():
        shards = {int(s.device.id): tuple(s.data.shape)
                  for s in arr.addressable_shards}
        leaves[name] = {"shape": tuple(arr.shape),
                        "spec": str(arr.sharding.spec), "shards": shards}
        if not arr.sharding.is_fully_replicated:
            node_sharded += 1
            # every chip of the mesh holds one slice, all the same
            # shape: the global shape cut N ways along one axis
            want = {tuple(g // len(mesh_ids) if i == ax else g
                          for i, g in enumerate(arr.shape))
                    for ax in range(arr.ndim)}
            even &= (sorted(shards) == mesh_ids
                     and len(set(shards.values())) == 1
                     and next(iter(shards.values())) in want)
    mem = {}
    for d in mesh.devices.ravel():
        stats = d.memory_stats() or {}
        mem[int(d.id)] = stats.get("bytes_in_use")
    return {"carry": leaves, "node_sharded_leaves": node_sharded,
            "each_chip_holds_its_slice": bool(node_sharded and even),
            "node_rows_total": int(sess.Nps),
            "node_rows_per_chip": int(sess.Npl),
            "bytes_in_use": mem}


def _after_window(template, n_parity: int, interpret: bool):
    def hook(cs, sched, stage):
        from kubernetes_tpu.perf.harness import bind_more
        from kubernetes_tpu.scheduler.internal.cache import SchedulerCache
        from kubernetes_tpu.scheduler.tpu_backend import TPUBackend

        tpu = sched.tpu
        sess = tpu._session
        out = {"session_kind": type(sess).__name__}
        if out["session_kind"] != "ShardedPallasSession":
            return out
        out["placement"] = _placement(sess, tpu.mesh)

        # the single-chip reference: same nodes in the same lane order
        # (first-max ties break by lane), same bound pods
        pods, _ = cs.pods.list(namespace="default")
        nodes, _ = cs.nodes.list()
        by_name = {n.metadata.name: n for n in nodes}
        ref = TPUBackend(pallas_interpret=interpret)
        cache = SchedulerCache()
        cache.add_listener(ref)
        for name in tpu.enc.node_names:
            if name is not None:
                cache.add_node(by_name[name])
        ref.enc.reserve(pods=len(pods) + 4 * n_parity)
        for p in pods:
            if p.spec.node_name:
                cache.add_pod(p)
        want = [n for _, n in ref.schedule_many(
            [template.build(f"parity-{i}") for i in range(n_parity)])]
        ref._stop_warm_threads()  # one bucket serves; don't compile five
        out["reference_session"] = type(ref._session).__name__
        out["reference_device"] = str(
            next(iter(ref._session._carry.values())).devices())
        ref.close()

        got = bind_more(cs, sched, stage, template, n_parity, "parity",
                        timeout=300.0)
        out.update(
            parity_pods=n_parity, parity_bound=len(got),
            # identical pods: the multiset of nodes is the decision
            # sequence, whichever pod the queue popped first
            parity_equal=(collections.Counter(got.values())
                          == collections.Counter(want)),
            parity_want_head=sorted(want)[:4],
            parity_got_head=sorted(got.values())[:4],
            session_kind_end=type(tpu._session).__name__,
        )
        return out

    return hook


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--template", choices=("plain", "anti"), default="plain")
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--nodes", type=int, default=20000)
    ap.add_argument("--init-pods", type=int, default=1024)
    ap.add_argument("--pods", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=1024)
    args = ap.parse_args()

    import jax

    jax.config.update("jax_enable_x64", True)

    from kubernetes_tpu.perf.harness import (
        PodTemplate,
        Workload,
        run_workload,
    )
    from kubernetes_tpu.utils.compilation_cache import (
        enable_persistent_cache,
    )
    from kubernetes_tpu.utils.device import require_device

    dev = require_device()
    if dev["count"] < args.devices:
        print(f"chip_mesh_check: {args.devices} devices asked, "
              f"{dev['count']} x {dev['platform']} found", file=sys.stderr)
        return 4
    cache_dir = enable_persistent_cache()
    template = (PodTemplate() if args.template == "plain" else PodTemplate(
        anti_affinity_hostname=True, labels={"app": "churn"}))
    w = Workload(
        f"Mesh-{args.nodes}n-{args.devices}sh-{args.template}",
        num_nodes=args.nodes, num_init_pods=args.init_pods,
        num_pods=args.pods, init_template=template, template=template,
        mesh_devices=args.devices, max_batch=args.batch, timeout=1800.0,
    )
    t0 = time.perf_counter()
    # a full batch of parity pods: both sessions reuse the bucket the
    # run already compiled instead of building a smaller one
    n_parity = args.batch
    r = run_workload(w, after_window=_after_window(
        template, n_parity, interpret=dev["platform"] != "tpu"))
    aw = r.after_window or {}
    bad = list(r.failures)
    want_builds = {f"pallas@{args.devices}/mesh-sharded"}
    if set(r.session_build_reasons or {}) != want_builds:
        bad.append(f"session builds {r.session_build_reasons}, expected "
                   f"only {want_builds}")
    if aw.get("session_kind") != "ShardedPallasSession" \
            or aw.get("session_kind_end") != "ShardedPallasSession":
        bad.append(f"live session {aw.get('session_kind')!r} / "
                   f"{aw.get('session_kind_end')!r}")
    if r.num_bound != r.num_pods:
        bad.append(f"bound {r.num_bound} of {r.num_pods}")
    if args.devices > 1 and not (aw.get("placement") or {}).get(
            "each_chip_holds_its_slice"):
        bad.append("carry is not split evenly over the mesh's chips")
    if aw.get("reference_session") != "PallasSession":
        bad.append(f"reference session {aw.get('reference_session')!r}")
    if aw.get("parity_bound") != n_parity or not aw.get("parity_equal"):
        bad.append("parity prefix differs from the single-chip session")
    row = {
        "ok": not bad,
        "device": dev,
        "workload": w.name,
        "session_kind": r.session_kind,
        "session_build_reasons": r.session_build_reasons,
        "backend_mode": r.backend_mode,
        "pods_bound": r.num_bound, "pods": r.num_pods,
        "check_pods_per_sec": r.throughput_avg,
        "window_s": r.duration_s,
        "compile_setup": r.compile_setup,
        "compile_window": r.compile_window,
        "device_faults": r.device_faults,
        "dispatch_retries": r.dispatch_retries,
        "ladder_demotions": r.ladder_demotions,
        "worker_restarts": r.worker_restarts,
        "after_window": aw,
        "cache_dir": cache_dir,
        "wall_s": round(time.perf_counter() - t0, 1),
        "failures": bad,
    }
    print(json.dumps(row), flush=True)
    for b in bad:
        print(f"chip_mesh_check: FAILED: {b}", file=sys.stderr)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
