"""Builder `gke-pools`: nodes of GKE node pools, each reporting the
allocatable a kubelet reports, and pods whose requests a template may
draw per Deployment.

Node i belongs to pool `nodes.cycle[i mod len(cycle)]` and lies in zone
i mod `zones`; its capacity is the machine's nominal size, its
allocatable the pool's `allocatable_cpu` / `allocatable_memory` as the
configuration writes them (m and Ki). A pod template with a `draw` has
no request of its own: each of its Deployments (the `{group}` of its
`app` label) takes the request `batch_requests` draws for it. A builder
may import `kubernetes_tpu.api.types` and the two functions of
`benchlib/cluster.py`, and nothing else of the program.
"""

import functools
import math
import random
from typing import Dict, List, Tuple

from benchlib import cluster
from kubernetes_tpu.api import types as v1


def pool_of(i: int, nodes: Dict) -> Dict:
    return nodes["pools"][nodes["cycle"][i % len(nodes["cycle"])]]


def build_node(i: int, config: Dict) -> v1.Node:
    nodes = config["nodes"]
    pool = pool_of(i, nodes)
    node = cluster.build_node(i, {
        "cpu": pool["allocatable_cpu"], "memory": pool["allocatable_memory"],
        "pods": nodes["pods"], "zones": nodes["zones"]})
    node.status.capacity = {"cpu": str(pool["vcpu"]),
                            "memory": pool["memory"],
                            "pods": str(nodes["pods"])}
    return node


def batch_requests(draw: Dict, n: int) -> List[Tuple[str, str]]:
    """(cpu, memory) of Deployments 0..n-1: CPU log-uniform between
    `cpu_min_m` and `cpu_max_m` in steps of `cpu_step_m`, memory that CPU
    times a ratio log-uniform over `gib_per_cpu_min`..`gib_per_cpu_max`
    GiB per core, in whole Mi."""
    rng = random.Random(draw["seed"])
    lo, hi, step = draw["cpu_min_m"], draw["cpu_max_m"], draw["cpu_step_m"]
    out = []
    for _ in range(n):
        cpu = math.exp(rng.uniform(math.log(lo), math.log(hi)))
        cpu_m = min(hi, max(lo, step * round(cpu / step)))
        ratio = math.exp(rng.uniform(math.log(draw["gib_per_cpu_min"]),
                                     math.log(draw["gib_per_cpu_max"])))
        out.append((f"{cpu_m}m", f"{round(cpu_m / 1000 * ratio * 1024)}Mi"))
    return out


@functools.lru_cache(maxsize=None)
def _drawn(draw: Tuple, n: int) -> List[Tuple[str, str]]:
    return batch_requests(dict(draw), n)


def build_pod(name: str, cls: Dict) -> v1.Pod:
    draw = cls.get("draw")
    if draw is None:
        return cluster.build_pod(name, cls)
    group = int(cls["labels"]["app"].rsplit("-", 1)[1])
    cpu, memory = _drawn(tuple(sorted(draw.items())),
                         cls["deployments"])[group]
    return cluster.build_pod(name, {**cls, "cpu": cpu, "memory": memory})
