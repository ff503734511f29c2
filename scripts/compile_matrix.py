"""AOT-compile every Pallas dispatch variant the product can launch, once,
without running it: batch buckets x {a table built without the term
machinery (zone-spread spec), one built with it (hostname anti-affinity
spec)} at the Default-5000n cluster shape and the table capacity a
100 000-pod reserve asks for. On a TPU the kernels go through Mosaic; on
CPU (asked for by name with JAX_PLATFORMS=cpu) they trace in interpret
mode, which checks the harness of this script and nothing about Mosaic.

One JSON line per variant on stdout and in chiprun_out/compile_matrix.jsonl
(a compiler error keeps its full text there); exits non-zero if any
variant failed to compile.

    python scripts/compile_matrix.py [--nodes 5000] [--buckets 128 2048]
        [--templates spread anti] [--run]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

os.environ.setdefault("JAX_ENABLE_X64", "1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

from kubernetes_tpu.utils.compilation_cache import (  # noqa: E402
    enable_persistent_cache,
)
from kubernetes_tpu.utils.device import require_device  # noqa: E402

TEMPLATES = {
    # the Default-5000n / PTS rows' shape: soft zone spread, no terms
    "spread": dict(spread_zone=True),
    # the IPA-churn rows' shape: required hostname anti-affinity — the
    # kernel with the term sections (repel / anti / affinity lists)
    "anti": dict(anti_affinity_hostname=True, labels={"app": "churn"}),
}


def build_session(n_nodes: int, template: str, interpret: bool):
    """PallasSession over a synthetic cluster of the harness's node shape
    with a quarter of the nodes already holding one template pod each
    (counts and anti-affinity statics are then non-trivial)."""
    from kubernetes_tpu.models.encoding import ClusterEncoding
    from kubernetes_tpu.models.pod_encoder import PodEncoder
    from kubernetes_tpu.ops.pallas_scan import PallasSession
    from kubernetes_tpu.perf.harness import PodTemplate
    from kubernetes_tpu.testing.synth import synth_cluster

    tmpl = PodTemplate(**TEMPLATES[template])
    nodes, _ = synth_cluster(n_nodes)
    init = []
    for i in range(0, n_nodes, 4):
        p = tmpl.build(f"init-{i}")
        p.spec.node_name = nodes[i].metadata.name
        init.append(p)
    enc = ClusterEncoding()
    enc.set_cluster(nodes, init)
    pe = PodEncoder(enc)
    pa = {k: v for k, v in pe.encode(tmpl.build("probe")).items()
          if not k.startswith("_")}
    from kubernetes_tpu.ops.pallas_scan import table_capacity

    sess = PallasSession(enc.device_state(), [pa], interpret=interpret,
                         capacity=table_capacity(100_000))
    return sess, pa


def _run_once(sess, pa, bucket: int) -> dict:
    """One blocking dispatch of `bucket` template pods on the compiled
    executable: wall seconds and the first decisions."""
    t0 = time.perf_counter()
    ys = sess.schedule([pa] * bucket)
    decisions = type(sess).decisions(ys)
    dt = time.perf_counter() - t0
    return dict(run_s=round(dt, 4),
                placed=sum(1 for d in decisions if d >= 0),
                head=decisions[:16])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--nodes", type=int, default=5000)
    ap.add_argument("--buckets", type=int, nargs="+",
                    default=[128, 256, 512, 1024, 2048])
    ap.add_argument("--templates", nargs="+", default=list(TEMPLATES))
    ap.add_argument("--run", action="store_true",
                    help="also dispatch one full batch per compiled "
                         "variant and report its decisions")
    args = ap.parse_args()

    dev = require_device()
    cache_dir = enable_persistent_cache()
    interpret = dev["platform"] != "tpu"
    out_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    failed = 0
    with open(os.path.join(out_dir, "compile_matrix.jsonl"), "w") as f:
        for template in args.templates:
            sess, pa = build_session(args.nodes, template, interpret)
            for bucket in args.buckets:
                row = dict(
                    device=dev, template=template, bucket=bucket,
                    nodes=args.nodes, np=sess.Np, specs=sess.Tcap,
                    terms=sess.dyn_ipa, interpret=interpret,
                    cache_dir=cache_dir)
                t0 = time.perf_counter()
                try:
                    fn = sess._compile_exec(bucket)
                    row["compile_s"] = round(time.perf_counter() - t0, 2)
                    row["ok"] = True
                    if args.run:
                        sess._exec[(bucket, "full")] = fn
                        row.update(_run_once(sess, pa, bucket))
                except Exception as e:  # noqa: BLE001 — the matrix reports every variant
                    failed += 1
                    row["ok"] = False
                    row["error"] = f"{type(e).__name__}: {e}"
                    row["traceback"] = traceback.format_exc()
                f.write(json.dumps(row) + "\n")
                f.flush()
                row.pop("traceback", None)
                if "error" in row:
                    row["error"] = row["error"][:2000]
                print(json.dumps(row), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
