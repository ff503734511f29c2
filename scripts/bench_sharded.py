"""Measure the sharded two-phase session against its single-device twins.

Two measurements:

  1. TPU, mesh=[1 chip]: ShardedPallasSession vs PallasSession vs
     HoistedSession per-pod cost at N nodes — the STRUCTURE tax of the
     per-pod two-phase scan (collectives are no-ops at 1 device, so this
     isolates what the scan-over-pods shape costs vs the single-launch
     kernel and the jnp hoisted scan).
  2. CPU, 8 virtual devices: ShardedPallasSession at 1/2/4/8 shards at
     5k/10k/20k nodes — the SCALING shape (emulated collectives; wall
     clock is only comparable within this table, never to TPU numbers).

Writes one JSON line per row to BENCH_SHARDED.json.

Usage: python scripts/bench_sharded.py tpu|cpu
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_ENABLE_X64", "1")

mode = sys.argv[1] if len(sys.argv) > 1 else "tpu"
if mode == "cpu":
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
if mode == "cpu":
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from kubernetes_tpu.utils.compilation_cache import (  # noqa: E402
    enable_persistent_cache,
)

enable_persistent_cache()

from __graft_entry__ import _synth_session_inputs  # noqa: E402
from kubernetes_tpu.ops.hoisted import HoistedSession  # noqa: E402
from kubernetes_tpu.ops.pallas_scan import PallasSession  # noqa: E402
from kubernetes_tpu.ops.sharded_scan import ShardedPallasSession  # noqa: E402
from kubernetes_tpu.parallel.sharded import make_mesh  # noqa: E402
from kubernetes_tpu.testing.synth import (  # noqa: E402
    synth_cluster,
    synth_pending_pods,
)

OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_SHARDED.json")


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def measure(sess_cls, cluster, arrays, templates, batch, reps, **kw):
    sess = sess_cls(cluster, templates, **kw)
    decide = sess_cls.decisions
    warm = arrays[:batch]
    t0 = time.perf_counter()
    decide(sess.schedule(warm))
    compile_s = time.perf_counter() - t0
    rates = []
    for r in range(reps):
        lo = batch * (1 + r)
        b = arrays[lo:lo + batch]
        if len(b) < batch:
            break
        t0 = time.perf_counter()
        decide(sess.schedule(b))
        dt = time.perf_counter() - t0
        rates.append(len(b) / dt)
    rates.sort()
    med = rates[(len(rates) - 1) // 2] if rates else 0.0
    return med, rates, compile_s


def emit(row):
    print(json.dumps(row), flush=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(row) + "\n")


def main():
    from kubernetes_tpu.utils.device import require_device, row_fields

    # "tpu" mode needs the chip; "cpu" mode asked for the CPU by name
    device = row_fields(require_device(allow_cpu=mode == "cpu"))
    reps = int(os.environ.get("BENCH_REPS", "3"))
    if mode == "tpu":
        n_nodes = int(os.environ.get("BENCH_NODES", "5000"))
        batch = 1024
        nodes, init_pods = synth_cluster(n_nodes, pods_per_node=2)
        pending = synth_pending_pods(batch * (1 + reps), spread=True)
        cluster, arrays, templates = _synth_session_inputs(
            nodes, init_pods, pending)
        mesh = make_mesh(n_devices=1)
        for name, cls, kw in (
            ("pallas", PallasSession, {}),
            ("hoisted", HoistedSession, {}),
            ("sharded2p-1dev", ShardedPallasSession, {"mesh": mesh}),
        ):
            med, rates, comp = measure(
                cls, cluster, arrays, templates, batch, reps, **kw)
            log(f"tpu {name}: median {med:.0f} pods/s "
                f"({['%.0f' % r for r in rates]}, compile {comp:.1f}s)")
            emit({
                "bench": "sharded-structure-tax", **device,
                "session": name, "nodes": n_nodes, "batch": batch,
                "pods_per_sec_median": round(med, 1),
                "pods_per_sec_runs": [round(r, 1) for r in rates],
                "compile_s": round(comp, 1), "reps": len(rates),
                "round": int(os.environ.get("BENCH_ROUND", "0")) or None,
            })
    else:
        batch = 256
        for n_nodes in (5000, 10000, 20000):
            nodes, init_pods = synth_cluster(n_nodes, pods_per_node=1)
            pending = synth_pending_pods(batch * (1 + reps), spread=True)
            cluster, arrays, templates = _synth_session_inputs(
                nodes, init_pods, pending)
            rows = [("hoisted-1dev", HoistedSession, {})]
            for nsh in (1, 2, 4, 8):
                rows.append((f"sharded2p-{nsh}dev", ShardedPallasSession,
                             {"mesh": make_mesh(n_devices=nsh)}))
            for name, cls, kw in rows:
                med, rates, comp = measure(
                    cls, cluster, arrays, templates, batch, reps, **kw)
                log(f"cpu {n_nodes}n {name}: median {med:.0f} pods/s "
                    f"(compile {comp:.1f}s)")
                emit({
                    "bench": "sharded-scaling-shape", **device,
                    "session": name, "nodes": n_nodes, "batch": batch,
                    "pods_per_sec_median": round(med, 1),
                    "pods_per_sec_runs": [round(r, 1) for r in rates],
                    "compile_s": round(comp, 1), "reps": len(rates),
                    "round": int(os.environ.get("BENCH_ROUND", "0")) or None,
                })


if __name__ == "__main__":
    main()
