"""Weak-scaling probe for the mesh scale-out backend: pods/s and
per-host RSS vs node-axis shard count, parity-asserted.

For each shard count the probe forks a fresh interpreter (RSS is
process-wide — per-shard-count memory is only honest from a clean
process), builds a TPUBackend over a mesh of that many devices, and
drives schedule_many over a synthetic cluster:

  * parity prefix: the first PROBE_PARITY pods are also scheduled
    through a single-device backend over the same cluster —
    decisions must be BIT-IDENTICAL before any number is recorded
    (the scale-out contract: sharding is a performance property);
  * throughput: pods/s over the measured schedule_many batches on the
    mesh backend;
  * memory: ru_maxrss after the run, plus the session's per-host node
    rows (Npl = Nps/nsh) — the bound that makes 100k nodes fit.

The children use whatever device JAX finds, and each row names it: on a
multi-chip TPU host the shards are real chips and the collectives ride
ICI (the single-device reference is then the compiled PallasSession on
device 0); with JAX_PLATFORMS=cpu the devices are simulated
(XLA_FLAGS=--xla_force_host_platform_device_count, set below before jax
imports; the flag only multiplies the HOST platform). This parent never
imports jax, so it never holds a chip a child needs.

Usage: python scripts/probe_mesh_scaling.py
Env: PROBE_NODES (20000), PROBE_PODS (512), PROBE_PARITY (32),
     PROBE_SHARDS (comma list, default 2,4,8).

Output: one JSON row per shard count on stdout.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

NODES = int(os.environ.get("PROBE_NODES", "20000"))
PODS = int(os.environ.get("PROBE_PODS", "512"))
PARITY = int(os.environ.get("PROBE_PARITY", "32"))
SHARDS = [int(s) for s in
          os.environ.get("PROBE_SHARDS", "2,4,8").split(",")]


def _vmrss_mb() -> float:
    """Current VmRSS from /proc (0.0 where /proc is unavailable)."""
    try:
        with open("/proc/self/status") as f:
            for ln in f:
                if ln.startswith("VmRSS:"):
                    return round(int(ln.split()[1]) / 1024, 1)
    except OSError:
        pass
    return 0.0


def _child(nsh: int) -> None:
    """One measurement in THIS process (spawned by main): mesh backend
    at nsh shards, single-device parity prefix, one JSON row."""
    os.environ["JAX_ENABLE_X64"] = "1"
    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={max(nsh, 8)}"
        )
    import resource
    import time

    import jax

    jax.config.update("jax_enable_x64", True)

    from kubernetes_tpu.utils.device import require_device, row_fields

    device = row_fields(require_device())

    from kubernetes_tpu.api import types as v1
    from kubernetes_tpu.parallel.sharded import make_mesh
    from kubernetes_tpu.scheduler.internal.cache import SchedulerCache
    from kubernetes_tpu.scheduler.tpu_backend import TPUBackend
    from kubernetes_tpu.testing.synth import make_node, make_pod

    def build(mesh):
        cache = SchedulerCache()
        be = TPUBackend(mesh=mesh)
        cache.add_listener(be)
        for i in range(NODES):
            cache.add_node(make_node(
                f"node-{i}",
                labels={v1.LABEL_HOSTNAME: f"node-{i}",
                        v1.LABEL_ZONE: f"zone-{i % 3}"}))
        be.enc.reserve(pods=int(PODS * 1.5))
        return be

    def pods(prefix, n):
        return [make_pod(f"{prefix}-{i}", cpu="100m", memory="64Mi")
                for i in range(n)]

    be = build(make_mesh(n_devices=nsh))
    got = [n for _, n in be.schedule_many(pods("parity", PARITY))]
    sess = be._session
    assert type(sess).__name__ == "ShardedPallasSession", type(sess)

    # parity prefix vs the single-device reference — weak-scaling rows
    # are only recorded for a backend that still schedules identically
    ref_be = build(None)
    ref = [n for _, n in ref_be.schedule_many(pods("parity", PARITY))]
    assert got == ref, f"nsh={nsh} parity broke: {got[:8]} vs {ref[:8]}"
    del ref_be

    batch = 128
    t0 = time.perf_counter()
    done = 0
    for start in range(0, PODS, batch):
        n = min(batch, PODS - start)
        res = be.schedule_many(pods(f"m{start}", n))
        done += sum(1 for _, nm in res if nm is not None)
    dt = time.perf_counter() - t0

    row = {
        **device,
        "nsh": nsh,
        "nodes": NODES,
        "pods": PODS,
        "bound": done,
        "pods_per_sec": round(done / dt, 2) if dt else 0.0,
        # peak RSS (NB: includes the single-device parity reference
        # built above) and current RSS after the measured run — the
        # second is the honest per-host steady-state number
        "maxrss_mb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024, 1),
        "rss_mb": _vmrss_mb(),
        # per-host node rows: the session splits Nps rows over nsh
        # shards; this is the array bound that scales the node axis out
        "node_rows_total": int(sess.Nps),
        "node_rows_per_host": int(sess.Npl),
        "parity_prefix": PARITY,
        "parity": "ok",
    }
    assert sess.Npl * nsh == sess.Nps
    print(json.dumps(row), flush=True)


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _child(int(sys.argv[2]))
        return
    for nsh in SHARDS:
        print(f"=== nsh={nsh}: {NODES} nodes, {PODS} pods",
              file=sys.stderr, flush=True)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", str(nsh)],
            stdout=subprocess.PIPE, text=True, check=True)
        sys.stdout.write(proc.stdout)
        sys.stdout.flush()


if __name__ == "__main__":
    main()
