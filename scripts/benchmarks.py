"""Full scheduler_perf-style benchmark suite (one JSON line per workload).

Mirrors the reference's performance-config.yaml coverage at configurable
scale: SchedulingBasic, PodTopologySpread (preferred zone spread + hard
hostname spread), required PodAntiAffinity on hostname, and the
gang-scheduling stress (8-pod groups with extended GPU resources).
bench.py remains the single-number headline; this is the coverage sweep
(reference: test/integration/scheduler_perf/config/
performance-config.yaml, scheduler_perf_test.go).

  python scripts/benchmarks.py              # small CI shapes
  BENCH_SCALE=full python scripts/benchmarks.py   # 5000-node shapes
"""
import json
import os
import sys

os.environ.setdefault("JAX_ENABLE_X64", "1")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

from kubernetes_tpu.perf import Workload, run_workload  # noqa: E402
from kubernetes_tpu.perf.harness import PodTemplate  # noqa: E402

FULL = os.environ.get("BENCH_SCALE") == "full"
NODES = 5000 if FULL else 200
INIT = 1000 if FULL else 100
PODS = 1000 if FULL else 200
BACKEND = os.environ.get("BENCH_BACKEND", "tpu")

WORKLOADS = [
    Workload(
        name="SchedulingBasic",
        num_nodes=NODES, num_init_pods=INIT, num_pods=PODS,
        backend=BACKEND,
    ),
    Workload(
        name="SchedulingPodTopologySpread",
        num_nodes=NODES, num_init_pods=INIT, num_pods=PODS,
        template=PodTemplate(spread_zone=True),
        backend=BACKEND,
    ),
    Workload(
        name="SchedulingPreferredPodTopologySpread",
        num_nodes=NODES, num_init_pods=INIT, num_pods=PODS,
        init_template=PodTemplate(spread_zone=True),
        template=PodTemplate(spread_zone=True),
        backend=BACKEND,
    ),
    Workload(
        name="SchedulingPodAntiAffinity",
        num_nodes=NODES, num_init_pods=0,
        # hostname anti-affinity: one pod per node max, so NODES//2
        # measured pods stay well inside feasibility
        num_pods=min(PODS, NODES // 2),
        template=PodTemplate(anti_affinity_hostname=True),
        backend=BACKEND,
    ),
    Workload(
        name="SchedulingHardHostnameSpread",
        num_nodes=NODES, num_init_pods=0, num_pods=min(PODS, NODES // 2),
        template=PodTemplate(spread_hostname_hard=True),
        backend=BACKEND,
    ),
    Workload(
        name="SchedulingGangStress",
        num_nodes=NODES, num_init_pods=0, num_pods=min(PODS, 512),
        gang_size=8,
        template=PodTemplate(extended={"example.com/gpu": "1"}),
        node_extended={"example.com/gpu": "8"},
        backend=BACKEND,
    ),
]

for w in WORKLOADS:
    try:
        result = run_workload(w)
        print(json.dumps(result.to_dict()), flush=True)
    except Exception as e:  # noqa: BLE001
        print(json.dumps({"name": w.name, "error": str(e)}), flush=True)
