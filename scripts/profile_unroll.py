import os, sys, time
os.environ.setdefault("JAX_ENABLE_X64", "1")
import jax
jax.config.update("jax_enable_x64", True)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
from kubernetes_tpu.models.encoding import ClusterEncoding
from kubernetes_tpu.models.pod_encoder import PodEncoder
import kubernetes_tpu.ops.hoisted as H
from kubernetes_tpu.ops.hoisted import HoistedSession, template_fingerprint
from kubernetes_tpu.testing.synth import synth_cluster, synth_pending_pods

N, B = 5000, 512
nodes, init_pods = synth_cluster(N, pods_per_node=2)
pending = synth_pending_pods(2 * B, spread=True)
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
for unroll in (1, 8, 32):
    os.environ["KTPU_SCAN_UNROLL"] = str(unroll)
    H._session_scan._clear_cache()
    sess = HoistedSession(enc.device_state(), templates)
    t0 = time.perf_counter()
    jax.block_until_ready(sess.schedule(arrays[:B])["best"])
    t_compile = time.perf_counter() - t0
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(sess.schedule(arrays[:B])["best"])
        ts.append(time.perf_counter() - t0)
    print(f"unroll={unroll:3d}: {min(ts)*1e3:8.1f}ms ({min(ts)/B*1e3:6.3f} ms/pod) "
          f"compile={t_compile:.0f}s")
