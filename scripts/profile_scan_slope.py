"""Per-step vs fixed cost of the batched scan: time B in {1,8,32,128}.

Slope = true per-step device cost; intercept = dispatch overhead.
Inputs are re-uploaded fresh each run (new arrays).
"""
import os, sys, time
os.environ.setdefault("JAX_ENABLE_X64", "1")
import jax
jax.config.update("jax_enable_x64", True)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
from kubernetes_tpu.models.encoding import ClusterEncoding
from kubernetes_tpu.models.pod_encoder import PodEncoder
from kubernetes_tpu.ops import batch as B
from kubernetes_tpu.testing.synth import synth_cluster, synth_pending_pods

N = int(os.environ.get("BENCH_NODES", "5000"))
nodes, init_pods = synth_cluster(N, pods_per_node=2)
pending = synth_pending_pods(300, spread=True)
enc = ClusterEncoding()
phantoms = []
for i, p in enumerate(pending):
    q = synth_pending_pods(1, spread=True)[0]
    q.metadata.name = f"phantom-{i}"
    q.metadata.labels = dict(p.metadata.labels or {})
    q.spec.node_name = nodes[i % len(nodes)].metadata.name
    phantoms.append(q)
enc.set_cluster(nodes, init_pods + phantoms)
pe = PodEncoder(enc)
for p in pending[:8]:
    pe.encode(p)
enc.device_state()
for q in phantoms:
    enc.remove_pod(q)

print("device:", jax.devices()[0])
for bs in (1, 8, 32, 128):
    pods = pending[:bs]
    arrays = [{k: v for k, v in pe.encode(p).items() if not k.startswith("_")} for p in pods]
    c = enc.device_state()
    slots = [enc._pod_free[-1 - i] for i in range(bs)]
    # warm compile
    d, _ = B.schedule_batch(c, arrays, slots)
    times = []
    for rep in range(3):
        t0 = time.perf_counter()
        d, carry = B.schedule_batch(c, arrays, slots)
        jax.block_until_ready(carry)
        times.append(time.perf_counter() - t0)
    print(f"B={bs:4d}  best={min(times)*1e3:8.1f}ms  per-step={min(times)/bs*1e3:7.2f}ms  times={[f'{t*1e3:.0f}' for t in times]}")
