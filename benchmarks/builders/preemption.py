"""Builder `preemption`: nodes as ever, pods with the priority of their
class (`priority` in the configuration's template, 0 when absent) set in
`spec.priority` directly, as upstream's pod-low-priority.yaml and
pod-high-priority.yaml do; the API server's Priority admission resolves
only a `priorityClassName`. Imports `kubernetes_tpu.api.types` (through
benchlib/cluster.py's two functions) and nothing else of the program.
"""

from typing import Dict

from benchlib import cluster


def build_node(i: int, config: Dict):
    return cluster.build_node(i, config["nodes"])


def build_pod(name: str, cls: Dict):
    pod = cluster.build_pod(name, cls)
    pod.spec.priority = int(cls.get("priority", 0))
    return pod
