"""Reference `rehearsal-pools`: the plain reference over several node
pools (the configuration's `nodes.pools`, as builders/rehearsal-pools.py
reads them). Imports nothing of the program: the pool rule is written out
here again, on purpose.
"""

from typing import Dict

from benchlib import reference


class PoolsCluster(reference.ReferenceCluster):
    @classmethod
    def from_config(cls, config: Dict, variant: str = ""):
        nodes = config["nodes"]
        last = nodes["pools"][-1]
        self = cls(nodes["count"], last["cpu"], last["memory"], last["pods"],
                   nodes["zones"], variant=variant)
        self._pools = nodes["pools"]
        for i in range(self.n):
            cpu, mem, pods, _ = self.shape_of(i)
            self.alloc_cpu[i], self.alloc_mem[i] = cpu, mem
            self.alloc_pods[i] = pods
        return self

    def shape_of(self, i: int):
        for pool in self._pools:
            if "every" not in pool or i % pool["every"] == 0:
                return (reference.milli_cpu(pool["cpu"]),
                        reference.quantity_bytes(pool["memory"]),
                        int(pool["pods"]), i % self.n_zones)
        raise ValueError(f"node {i} is in no pool")


def replay(config, classes, log, variant=""):
    return reference.replay(config, classes, log, variant,
                            cluster_cls=PoolsCluster)
