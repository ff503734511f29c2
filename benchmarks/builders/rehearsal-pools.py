"""Builder `rehearsal-pools`: nodes of several pools, pods as ever.

The configuration's `nodes.pools` is a list of node shapes; a pool with
`every: k` takes the nodes whose index is a multiple of k, the last pool
the rest. A builder may import `kubernetes_tpu.api.types` and the two
functions of `benchlib/cluster.py`, and nothing else of the program.
"""

from typing import Dict

from benchlib import cluster
from kubernetes_tpu.api import types as v1

build_pod = cluster.build_pod


def pool_of(i: int, nodes: Dict) -> Dict:
    for pool in nodes["pools"]:
        if "every" not in pool or i % pool["every"] == 0:
            return pool
    raise ValueError(f"node {i} is in no pool")


def build_node(i: int, config: Dict) -> v1.Node:
    nodes = config["nodes"]
    return cluster.build_node(i, {**pool_of(i, nodes),
                                  "zones": nodes["zones"]})
