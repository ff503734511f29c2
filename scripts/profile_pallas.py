"""PallasSession on the real chip at bench scale: compile + blocking timing
+ decision parity vs the jnp HoistedSession."""
import os, sys, time
os.environ.setdefault("JAX_ENABLE_X64", "1")
import jax
jax.config.update("jax_enable_x64", True)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
from kubernetes_tpu.models.encoding import ClusterEncoding
from kubernetes_tpu.models.pod_encoder import PodEncoder
from kubernetes_tpu.ops.hoisted import HoistedSession, template_fingerprint
from kubernetes_tpu.ops.pallas_scan import PallasSession
from kubernetes_tpu.testing.synth import synth_cluster, synth_pending_pods

N = int(os.environ.get("BENCH_NODES", "5000"))
B = int(os.environ.get("BENCH_BATCH", "1024"))
M = 3
nodes, init_pods = synth_cluster(N, pods_per_node=2)
pending = synth_pending_pods(M * B, spread=True)
phantoms = []
for i, p in enumerate(pending):
    q = synth_pending_pods(1, spread=True)[0]
    q.metadata.name = f"ph-{i}"
    q.metadata.labels = dict(p.metadata.labels or {})
    q.spec.node_name = nodes[i % len(nodes)].metadata.name
    phantoms.append(q)
enc = ClusterEncoding(); enc.set_cluster(nodes, init_pods + phantoms)
pe = PodEncoder(enc)
for p in pending[:8]: pe.encode(p)
enc.device_state()
for q in phantoms: enc.remove_pod(q)
arrays = [{k: v for k, v in pe.encode(p).items() if not k.startswith("_")} for p in pending]
templates, seen = [], set()
for a in arrays:
    fp = template_fingerprint(a)
    if fp not in seen: seen.add(fp); templates.append(a)
print("templates:", len(templates), "device:", jax.devices()[0])

t0 = time.perf_counter()
ps = PallasSession(enc.device_state(), templates)
print(f"session build (prologue + remap): {time.perf_counter()-t0:.1f}s")
t0 = time.perf_counter()
ys = ps.schedule(arrays[:B])
d0 = PallasSession.decisions(ys)
print(f"first schedule (compile): {time.perf_counter()-t0:.1f}s")
ts = []
outs = [d0]
for i in range(1, M):
    t0 = time.perf_counter()
    ys = ps.schedule(arrays[i*B:(i+1)*B])
    d = PallasSession.decisions(ys)
    ts.append(time.perf_counter() - t0)
    outs.append(d)
print(f"pallas steady: {min(ts)*1e3:.1f}ms/batch ({min(ts)/B*1e6:.1f} us/pod)")

# parity vs jnp session on the same batches
js = HoistedSession(enc.device_state(), templates)
ref = []
for i in range(M):
    ref.append(HoistedSession.decisions(js.schedule(arrays[i*B:(i+1)*B])))
for i in range(M):
    same = outs[i] == ref[i]
    n_diff = sum(1 for a, b in zip(outs[i], ref[i]) if a != b)
    print(f"batch {i}: parity={'OK' if same else f'{n_diff} DIFF'}")
