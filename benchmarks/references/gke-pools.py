"""Reference `gke-pools`: the plain reference over GKE node pools, with
requests a pod template may draw per Deployment.

The pool rule and the batch draw are written out here again, on purpose,
beside builders/gke-pools.py: node i takes pool `nodes.cycle[i mod
len(cycle)]` and that pool's allocatable (m, Ki); a template with a
`draw` gives Deployment g the g-th request of a log-uniform draw seeded by
the configuration. Scores are benchlib.reference's: LeastAllocated in
int64, BalancedAllocation in float64 over each node's own capacity.
Imports nothing of the program.
"""

import math
from typing import Dict, List, Tuple

import numpy as np

from benchlib import reference

_N = 624  # MT19937's state words


def _mt19937_seeded(seed: int) -> np.random.RandomState:
    """A Mersenne Twister in the state Python's `random.seed(seed)` leaves
    it: `init_by_array` over the seed's 32-bit words, least significant
    first (Matsumoto and Nishimura's reference code). Written out here:
    a reference imports numpy, math and benchlib, not `random`."""
    key = []
    while True:
        key.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            break
    mt = [19650218]
    for i in range(1, _N):
        mt.append((1812433253 * (mt[-1] ^ (mt[-1] >> 30)) + i) & 0xFFFFFFFF)
    i, j = 1, 0
    for _ in range(max(_N, len(key))):
        mt[i] = ((mt[i] ^ ((mt[i - 1] ^ (mt[i - 1] >> 30)) * 1664525))
                 + key[j] + j) & 0xFFFFFFFF
        i, j = i + 1, (j + 1) % len(key)
        if i >= _N:
            mt[0], i = mt[_N - 1], 1
    for _ in range(_N - 1):
        mt[i] = ((mt[i] ^ ((mt[i - 1] ^ (mt[i - 1] >> 30)) * 1566083941))
                 - i) & 0xFFFFFFFF
        i += 1
        if i >= _N:
            mt[0], i = mt[_N - 1], 1
    mt[0] = 0x80000000
    rs = np.random.RandomState()
    rs.set_state(("MT19937", np.array(mt, np.uint32), _N))
    return rs


def drawn_requests(draw: Dict, n: int) -> List[Tuple[str, str]]:
    """(cpu, memory) of Deployments 0..n-1 of a template with a `draw`:
    log-uniform CPU and GiB per core, each uniform the 53-bit float
    `random_sample` gives (Python's `random()`), scaled as `uniform`
    scales it."""
    rng = _mt19937_seeded(draw["seed"])

    def uniform(a: float, b: float) -> float:
        return a + (b - a) * rng.random_sample()

    lo_m, hi_m = draw["cpu_min_m"], draw["cpu_max_m"]
    out = []
    for _ in range(n):
        milli = math.exp(uniform(math.log(lo_m), math.log(hi_m)))
        milli = min(hi_m, max(lo_m, draw["cpu_step_m"]
                              * round(milli / draw["cpu_step_m"])))
        gib_per_cpu = math.exp(uniform(math.log(draw["gib_per_cpu_min"]),
                                       math.log(draw["gib_per_cpu_max"])))
        mebi = round(milli / 1000 * gib_per_cpu * 1024)
        out.append((f"{milli}m", f"{mebi}Mi"))
    return out


def with_requests(classes) -> List[Dict]:
    """The classes, a drawn template's given its Deployment's request."""
    out = []
    for c in classes:
        if "draw" in c:
            g = int(c["labels"]["app"].rsplit("-", 1)[1])
            cpu, mem = drawn_requests(c["draw"], c["deployments"])[g]
            c = {**c, "cpu": cpu, "memory": mem}
        out.append(c)
    return out


class GkePoolsCluster(reference.ReferenceCluster):
    @classmethod
    def from_config(cls, config: Dict, variant: str = ""):
        nodes = config["nodes"]
        first = nodes["pools"][nodes["cycle"][0]]
        self = cls(nodes["count"], first["allocatable_cpu"],
                   first["allocatable_memory"], nodes["pods"],
                   nodes["zones"], variant=variant)
        self._nodes = nodes
        for i in range(self.n):
            cpu, mem, pods, _ = self.shape_of(i)
            self.alloc_cpu[i], self.alloc_mem[i] = cpu, mem
            self.alloc_pods[i] = pods
        return self

    def shape_of(self, i: int):
        cycle = self._nodes["cycle"]
        pool = self._nodes["pools"][cycle[i % len(cycle)]]
        return (reference.milli_cpu(pool["allocatable_cpu"]),
                reference.quantity_bytes(pool["allocatable_memory"]),
                int(self._nodes["pods"]), i % self.n_zones)


def replay(config, classes, log, variant=""):
    return reference.replay(config, with_requests(classes), log, variant,
                            cluster_cls=GkePoolsCluster)
