"""Run the five BASELINE.json benchmark configs through the FULL
scheduler loop (perf/harness.py: APIServer + informers + queue + cache +
Scheduler with the TPU backend) and write one JSON line per config to
BENCH_CONFIGS.json.

This is the harness-level counterpart of bench.py (which drives the
session kernel directly): the reference's scheduler_perf runs the real
scheduler against a real apiserver (test/integration/scheduler_perf/
util.go:61 mustSetupScheduler), so the headline numbers must reproduce
through the same full loop here.

Usage: python scripts/bench_configs.py [config-name ...]
(no args = the full matrix; see CONFIGS for the names)
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_ENABLE_X64", "1")
# the mesh rows (mesh20k/50k/100k) shard the node axis over 8 devices;
# on a CPU host the devices are simulated (harmless on real chips: the
# flag only multiplies the HOST platform). Must land before jax imports.
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

from kubernetes_tpu.utils.compilation_cache import (  # noqa: E402
    enable_persistent_cache,
)

enable_persistent_cache()

from kubernetes_tpu.perf.harness import (  # noqa: E402
    DEFAULT_5000N_10K,
    PodTemplate,
    Workload,
    run_workload,
)

# The five north-star configs (BASELINE.md "Benchmark configs to
# reproduce"; shapes from the reference's performance-config.yaml)
CONFIGS = {
    # SchedulingBasic 500/1000 (CPU-baseline shape)
    "basic": Workload(
        "SchedulingBasic-500", num_nodes=500, num_init_pods=1000,
        num_pods=1000, max_batch=1024,
    ),
    # 5000 nodes / 10k pods, default profile (perf/harness.py)
    "default5000": DEFAULT_5000N_10K,
    # PodTopologySpread-heavy: 5000 nodes, 3 zones, maxSkew=1, 20k pods
    "pts20k": Workload(
        "PTS-heavy-5000n-20k", num_nodes=5000, num_init_pods=4096,
        num_pods=20000,
        init_template=PodTemplate(spread_zone=True, spread_zone_hard=True),
        template=PodTemplate(spread_zone=True, spread_zone_hard=True),
        max_batch=2048, timeout=1200.0,
    ),
    # InterPodAffinity churn: 2000 nodes, 5000 required-anti-affinity pods
    # (hostname terms: 2000 bindable, 3000 permanently pending -> the
    # stall_stop ends the run once the scheduler has churned through them)
    "ipachurn": Workload(
        "IPA-churn-2000n-5000", num_nodes=2000, num_init_pods=1024,
        num_pods=5000,
        init_template=PodTemplate(anti_affinity_hostname=True,
                                  labels={"app": "churn"}),
        template=PodTemplate(anti_affinity_hostname=True,
                             labels={"app": "churn"}),
        max_batch=1024, timeout=900.0, stall_stop=15.0,
        saturating=True,  # ~2000 bindable of 5000 by design
    ),
    # gang stress: 1000 x 8-pod groups, 4000 GPU nodes. Batch 1024:
    # same ~1000 pods/s as 2048 but attempt_p50 3.5s -> 1.4s (the r3
    # profile's "smaller overlapped waves" — wave cadence, not CPU,
    # bounds gang latency)
    "gang": Workload(
        "Gang-4000n-1000x8", num_nodes=4000, num_init_pods=2048,
        num_pods=8000, gang_size=8,
        init_template=PodTemplate(extended={"example.com/gpu": "1"}),
        template=PodTemplate(extended={"example.com/gpu": "1"}),
        node_extended={"example.com/gpu": "8"},
        max_batch=1024, timeout=900.0,
    ),
    # rank-scaled gang rows (round 18): the same GPU cluster at 64- and
    # 256-rank gangs — the MPI-style tightly-coupled shapes the ROADMAP
    # names. A 64-rank gang spans 8 nodes, a 256-rank gang 32 nodes, so
    # these rows stress the all-or-nothing permit wave (one straggler
    # parks 63/255 siblings) rather than per-pod throughput; the
    # headline pair is aggregate pods/s + gang_admission_p99, and the
    # gang_{rollbacks,rejected} counters must read 0 on a clean run.
    # Batch >= gang_size keeps each wave inside one dispatch bucket.
    "gang64": Workload(
        "Gang-4000n-64x64", num_nodes=4000, num_init_pods=2048,
        num_pods=4096, gang_size=64,
        init_template=PodTemplate(extended={"example.com/gpu": "1"}),
        template=PodTemplate(extended={"example.com/gpu": "1"}),
        node_extended={"example.com/gpu": "8"},
        max_batch=1024, timeout=900.0,
    ),
    "gang256": Workload(
        "Gang-4000n-8x256", num_nodes=4000, num_init_pods=2048,
        num_pods=2048, gang_size=256,
        init_template=PodTemplate(extended={"example.com/gpu": "1"}),
        template=PodTemplate(extended={"example.com/gpu": "1"}),
        node_extended={"example.com/gpu": "8"},
        max_batch=1024, timeout=900.0,
    ),
    # Preemption (performance-config.yaml Preemption section shape):
    # 500 nodes saturated by 2000 low-priority pods (4 x 900m fills a
    # 4-CPU node); 500 high-priority pods must each evict a victim via
    # the DefaultPreemption dry-run, then bind on the freed node
    "preemption": Workload(
        "Preemption-500n-500hi", num_nodes=500, num_init_pods=2000,
        num_pods=500,
        init_template=PodTemplate(cpu="900m", memory="64Mi", priority=1),
        template=PodTemplate(cpu="900m", memory="64Mi", priority=100),
        max_batch=512, timeout=900.0, stall_stop=30.0,
    ),
    # Unschedulable churn (the reference's Unschedulable workload
    # variants): every 3rd measured pod requests 8 CPU (> any node) and
    # churns permanently; the schedulable majority binds through the
    # noise. stall_stop ends the run once only churners remain.
    # batch 512 (not 1024): the bind stream lands at batch-harvest
    # boundaries; at 1024 a median SECOND of the short measured window
    # saw zero binds (throughput_p50 = 0) while the avg was fine —
    # finer batches trade nothing measurable here for a steady cadence
    "unschedchurn": Workload(
        "Unschedulable-churn-500n", num_nodes=500, num_init_pods=1000,
        num_pods=3000,
        init_template=PodTemplate(spread_zone=True),
        template=PodTemplate(spread_zone=True),
        second_template=PodTemplate(cpu="8", memory="64Gi"),
        second_every=3,
        max_batch=512, timeout=900.0, stall_stop=15.0,
        saturating=True,  # 1000 of 3000 can never fit by design
    ),
    # -- the volume/affinity tail of the reference's matrix
    #    (performance-config.yaml:51-272), round-4 additions ------------
    # SchedulingSecrets: secret-volume pods (no scheduling constraint;
    # pins that volume-bearing non-PVC pods keep the kernel fast path)
    "secrets": Workload(
        "SchedulingSecrets-500n", num_nodes=500, num_init_pods=1000,
        num_pods=1000, template=PodTemplate(secret_volumes=2),
        max_batch=1024,
    ),
    # SchedulingInTreePVs: one pre-bound zonal PV+PVC per pod — VolumeZone
    # constraints ride the kernel's node-affinity mask (volume_device.py)
    "intreepvs": Workload(
        "SchedulingInTreePVs-500n", num_nodes=500, num_init_pods=1000,
        num_pods=1000,
        init_template=PodTemplate(with_pvc="zonal"),  # same shapes as
        template=PodTemplate(with_pvc="zonal"),  # measured (ref config
        max_batch=1024, timeout=900.0,  # gives init pods PVs too)
    ),
    # SchedulingCSIPVs: pre-bound CSI PVs — attach limits ride the
    # resource-fit mask via attachable-volumes-csi-* scalars
    "csipvs": Workload(
        "SchedulingCSIPVs-500n", num_nodes=500, num_init_pods=1000,
        num_pods=1000,
        init_template=PodTemplate(with_pvc="csi"),
        template=PodTemplate(with_pvc="csi"),
        max_batch=1024, timeout=900.0,
    ),
    # SchedulingPodAffinity: required zone affinity toward self-labels
    "podaffinity": Workload(
        "SchedulingPodAffinity-500n", num_nodes=500, num_init_pods=1000,
        num_pods=1000,
        init_template=PodTemplate(labels={"app": "aff"}),
        template=PodTemplate(pod_affinity_zone=True, labels={"app": "aff"}),
        max_batch=1024, timeout=900.0,
    ),
    # SchedulingPreferredPodAffinity / ...AntiAffinity: soft zone terms
    "prefaffinity": Workload(
        "SchedulingPreferredPodAffinity-500n", num_nodes=500,
        num_init_pods=1000, num_pods=1000,
        init_template=PodTemplate(labels={"app": "aff"}),
        template=PodTemplate(preferred_affinity_zone=True,
                             labels={"app": "aff"}),
        max_batch=1024, timeout=900.0,
    ),
    "prefantiaffinity": Workload(
        "SchedulingPreferredPodAntiAffinity-500n", num_nodes=500,
        num_init_pods=1000, num_pods=1000,
        init_template=PodTemplate(labels={"app": "aff"}),
        template=PodTemplate(preferred_anti_affinity_zone=True,
                             labels={"app": "aff"}),
        max_batch=1024, timeout=900.0,
    ),
    # SchedulingNodeAffinity: required node affinity zone In [0, 1]
    "nodeaffinity": Workload(
        "SchedulingNodeAffinity-500n", num_nodes=500, num_init_pods=1000,
        num_pods=1000,
        template=PodTemplate(node_affinity_zones=["zone-0", "zone-1"]),
        max_batch=1024,
    ),
    # SchedulingMigratedInTreePVs (performance-config.yaml:99-135):
    # in-tree AWS EBS PVs ride the csi-translation layer onto the same
    # kernel attach-scalar machinery as native CSI PVs
    "migratedpvs": Workload(
        "SchedulingMigratedInTreePVs-500n", num_nodes=500,
        num_init_pods=1000, num_pods=1000,
        init_template=PodTemplate(with_pvc="migrated"),
        template=PodTemplate(with_pvc="migrated"),
        max_batch=1024, timeout=900.0,
    ),
    # Preemption with PDB-covered victims: same shape as preemption but
    # every victim is under a PodDisruptionBudget — the planner's
    # vectorized filterPodsWithPDBViolation + violating-first reprieve
    # are on the measured path (VERDICT r4 #6)
    "preemptionpdb": Workload(
        "Preemption-PDB-500n-500hi", num_nodes=500, num_init_pods=2000,
        num_pods=500,
        init_template=PodTemplate(cpu="900m", memory="64Mi", priority=1,
                                  labels={"app": "victim"}),
        template=PodTemplate(cpu="900m", memory="64Mi", priority=100),
        max_batch=512, timeout=900.0, stall_stop=30.0,
        pdb_disruptions_allowed=2000,
    ),
    # Preemption with AFFINITY-carrying preemptors: the measured pods
    # carry a required pod-affinity term toward the victims' app label
    # (zone topology), putting every preemptor OUTSIDE the numpy fast
    # planner's envelope — before the device what-if planner this row
    # walked the oracle dry-run per candidate node. The per-rep
    # planner-path + what-if-launch counters adjudicate the
    # oracle-bound -> dispatch-bound claim on the chip rerun.
    "preemptionipa": Workload(
        "Preemption-IPA-500n-500hi", num_nodes=500, num_init_pods=2000,
        num_pods=500,
        init_template=PodTemplate(cpu="900m", memory="64Mi", priority=1,
                                  labels={"app": "victim"}),
        template=PodTemplate(cpu="900m", memory="64Mi", priority=100,
                             pod_affinity_zone=True,
                             labels={"app": "victim"}),
        max_batch=512, timeout=900.0, stall_stop=30.0,
    ),
    # 5000-node PV variant: the volume class at headline scale
    "intreepvs5000": Workload(
        "SchedulingInTreePVs-5000n", num_nodes=5000, num_init_pods=2048,
        num_pods=5000,
        init_template=PodTemplate(with_pvc="zonal"),
        template=PodTemplate(with_pvc="zonal"),
        max_batch=2048, timeout=900.0,
    ),
    # -- 5000-node affinity variants: the reference's matrix runs every
    #    affinity workload at BOTH 500 and 5000 nodes
    #    (performance-config.yaml:137-272); only the 500n halves were
    #    recorded through r5 ---------------------------------------------
    "podaffinity5000": Workload(
        "SchedulingPodAffinity-5000n", num_nodes=5000, num_init_pods=2048,
        num_pods=5000,
        init_template=PodTemplate(labels={"app": "aff"}),
        template=PodTemplate(pod_affinity_zone=True, labels={"app": "aff"}),
        max_batch=2048, timeout=900.0,
    ),
    "prefaffinity5000": Workload(
        "SchedulingPreferredPodAffinity-5000n", num_nodes=5000,
        num_init_pods=2048, num_pods=5000,
        init_template=PodTemplate(labels={"app": "aff"}),
        template=PodTemplate(preferred_affinity_zone=True,
                             labels={"app": "aff"}),
        max_batch=2048, timeout=900.0,
    ),
    "prefantiaffinity5000": Workload(
        "SchedulingPreferredPodAntiAffinity-5000n", num_nodes=5000,
        num_init_pods=2048, num_pods=5000,
        init_template=PodTemplate(labels={"app": "aff"}),
        template=PodTemplate(preferred_anti_affinity_zone=True,
                             labels={"app": "aff"}),
        max_batch=2048, timeout=900.0,
    ),
    "nodeaffinity5000": Workload(
        "SchedulingNodeAffinity-5000n", num_nodes=5000,
        num_init_pods=2048, num_pods=5000,
        template=PodTemplate(node_affinity_zones=["zone-0", "zone-1"]),
        max_batch=2048, timeout=900.0,
    ),
    # -- multi-host mesh scale-out (round 15): the node axis sharded
    #    over an 8-device mesh (simulated on CPU via the XLA_FLAGS set
    #    above; real ICI on a pod slice). Rows prove the 50k-100k-node
    #    regime is survivable host-side — per-host session arrays are
    #    bounded to Nps/8 rows — and that throughput holds while the
    #    encoding/cache layers carry 20x the node count of the
    #    single-device headline rows. Pod counts stay moderate: these
    #    rows measure node-axis scale, not pod backlog (the 5000n rows
    #    own that axis).
    "mesh20k": Workload(
        "Mesh-20000n-8sh", num_nodes=20000, num_init_pods=1024,
        num_pods=4096, mesh_devices=8, max_batch=1024, timeout=1800.0,
    ),
    "mesh50k": Workload(
        "Mesh-50000n-8sh", num_nodes=50000, num_init_pods=512,
        num_pods=2048, mesh_devices=8, max_batch=512, timeout=2400.0,
    ),
    "mesh100k": Workload(
        "Mesh-100000n-8sh", num_nodes=100000, num_init_pods=256,
        num_pods=1024, mesh_devices=8, max_batch=256, timeout=3600.0,
    ),
}


def _median(vals):
    s = sorted(vals)
    return s[len(s) // 2]


def main() -> None:
    """Each config runs BENCH_REPS times (VERDICT r3 weak #3: single
    runs made the recorded number whichever run got committed last);
    the row carries the MEDIAN run's full detail plus per-rep
    throughput min/median/max. Heavy 5000-node configs halve the reps.
    Set BENCH_WIRE=1 to run the matrix over the real HTTP socket."""
    from kubernetes_tpu.utils.device import require_device

    dev = require_device()
    failed = []
    names = sys.argv[1:] or list(CONFIGS)
    reps_default = int(os.environ.get("BENCH_REPS", "3"))
    wire = os.environ.get("BENCH_WIRE", "0") == "1"
    out_path = os.path.join(os.path.dirname(__file__), "..",
                            "BENCH_WIRE_CONFIGS.json" if wire
                            else "BENCH_CONFIGS.json")
    mode = "a" if sys.argv[1:] else "w"  # full runs rewrite; partials append
    for name in names:
        import dataclasses

        # every bench row measures the same-config kernel-direct rate
        # in-process after the loop phase and records loop_kernel_ratio
        # — the adjudicating number for the ROADMAP "close the
        # loop-vs-kernel gap" target (full-loop >= 50% of kernel-direct
        # on Default-5000n)
        w = dataclasses.replace(CONFIGS[name], kernel_direct=True)
        if wire:
            w = dataclasses.replace(w, wire=True)
        # shadow parity sentinel knob (round 12): BENCH_SHADOW_SAMPLE
        # opts every row into the oracle replay at that rate (default 0 —
        # the sentinel is decision-inert and launch-free when off, so
        # baseline rows pay nothing)
        shadow_sample = float(os.environ.get("BENCH_SHADOW_SAMPLE", "0") or 0)
        if shadow_sample:
            w = dataclasses.replace(w, shadow_sample=shadow_sample)
        # heavy (>=5000-node) configs used to halve the reps; VERDICT r4
        # weak #2: never below 3 — a single sample is not a measurement
        reps = max(min(3, reps_default), reps_default // 2) \
            if w.num_nodes >= 5000 else reps_default
        print(f"=== {w.name}: {w.num_nodes} nodes, {w.num_pods} pods "
              f"(batch {w.max_batch}, reps {reps}, wire {wire}) on "
              f"{dev['platform']} ({dev['kind']} x{dev['count']})",
              file=sys.stderr, flush=True)
        runs = []
        for rep in range(reps):
            t0 = time.perf_counter()
            r = run_workload(w)
            wall = time.perf_counter() - t0
            line = r.to_dict()
            line["wall_s"] = round(wall, 1)
            runs.append(line)
            print(f"  rep {rep}: {line['throughput_avg']} pods/s "
                  f"({line['attempts_per_sec']} attempts/s)",
                  file=sys.stderr, flush=True)
            for why in line["failures"]:
                failed.append(f"{w.name} rep {rep}: {why}")
                print(f"  rep {rep} FAILED: {why}", file=sys.stderr,
                      flush=True)
        key = "attempts_per_sec" if w.saturating else "throughput_avg"
        vals = [r[key] for r in runs]
        line = next(r for r in runs if r[key] == _median(vals))
        line["reps"] = reps
        # every rep's failures, not just the median rep's: a fault in
        # one rep must not hide behind a clean median
        line["failures_runs"] = [r["failures"] for r in runs]
        line["throughput_avg_runs"] = [r["throughput_avg"] for r in runs]
        line["attempts_per_sec_runs"] = [r["attempts_per_sec"] for r in runs]
        # per-rep session accounting: the rebuild storm was invisible
        # when only the median rep's dict survived (Preemption-PDB's
        # [62.4, 123.6, 123.1] reps hid 60+ rebuilds in rep 0)
        line["session_builds_runs"] = [
            r.get("session_builds") for r in runs
        ]
        line["session_rebuild_reasons_runs"] = [
            r.get("session_rebuild_reasons") for r in runs
        ]
        line["session_delta_applies_runs"] = [
            r.get("session_delta_applies") for r in runs
        ]
        # per-rep speculation accounting: same reasoning as the session
        # counters above — a speculation-miss cascade in one rep must
        # not hide behind the median rep's dict
        line["speculative_hits_runs"] = [
            r.get("speculative_hits") for r in runs
        ]
        line["speculative_misses_runs"] = [
            r.get("speculative_misses") for r in runs
        ]
        line["loop_kernel_ratio_runs"] = [
            r.get("loop_kernel_ratio") for r in runs
        ]
        # per-rep preemption planner-ladder accounting (round 10): the
        # device/fast/oracle split and what-if launch/fallback counts
        # must survive per rep — a fallback storm in one rep must not
        # hide behind the median rep's dict
        line["preemption_planner_paths_runs"] = [
            r.get("preemption_planner_paths") for r in runs
        ]
        line["whatif_launches_runs"] = [
            r.get("whatif_launches") for r in runs
        ]
        line["whatif_fallbacks_runs"] = [
            r.get("whatif_fallbacks") for r in runs
        ]
        # per-rep gang atomicity accounting (round 18): the Gang-* rows'
        # acceptance reads THESE — admitted * gang_size must equal
        # num_bound in every rep, and a rollback/rejection storm in one
        # rep must not hide behind the median rep's dict. Admission p99
        # is exact per rep (plugin sample buffer, not histogram buckets).
        line["gang_admitted_runs"] = [r.get("gang_admitted") for r in runs]
        line["gang_rejected_runs"] = [r.get("gang_rejected") for r in runs]
        line["gang_rollbacks_runs"] = [
            r.get("gang_rollbacks") for r in runs
        ]
        line["gang_preempted_runs"] = [
            r.get("gang_preempted") for r in runs
        ]
        line["gang_admission_p99_runs"] = [
            r.get("gang_admission_p99") for r in runs
        ]
        # per-rep stage-latency attribution (round 11): with KTPU_TRACE
        # on, each rep's per-stage p50/p99 breakdown survives — the chip
        # rerun reads WHICH stage owns the loop-vs-kernel gap per rep,
        # not a median rep's summary (None per rep with tracing off)
        line["stage_latency_runs"] = [
            r.get("stage_latency") for r in runs
        ]
        # per-rep completion-tax attribution (round 14): the assume
        # (cache writeback) and bind stages pulled out of each rep's
        # stage_latency so the chip rerun adjudicates the columnar
        # batched delta-apply directly, without unpacking the full
        # stage dict per rep (None with tracing off)
        line["assume_stage_runs"] = [
            (r.get("stage_latency") or {}).get("assume") for r in runs
        ]
        line["bind_stage_runs"] = [
            (r.get("stage_latency") or {}).get("bind") for r in runs
        ]
        # per-rep device-timeline attribution (round 16): with
        # KTPU_DEVTIME on, each rep's host<->device overlap ratio, its
        # kernel/transfer/compile device-seconds split, and its
        # dispatch-path recompile count survive — the chip rerun reads
        # where device time went PER REP (a compile storm in rep 0 must
        # not hide behind the median rep's dict). Always present:
        # 0.0/None/0 per rep with devtime off, mirroring
        # stage_latency_runs, so the schema is stable across knob sets.
        line["overlap_ratio_runs"] = [
            r.get("overlap_ratio") for r in runs
        ]
        line["device_time_runs"] = [
            r.get("device_time") for r in runs
        ]
        line["recompiles_runs"] = [
            r.get("recompiles") for r in runs
        ]
        # per-rep shadow parity accounting (round 12): at sample>0 the
        # chip rerun adjudicates drift from THESE counters — a drift
        # burst in one rep must not hide behind the median rep's dict
        line["shadow_sample"] = shadow_sample
        line["shadow_samples_runs"] = [
            r.get("shadow_samples") for r in runs
        ]
        line["shadow_drift_runs"] = [
            r.get("shadow_drift") for r in runs
        ]
        line["throughput_avg_min"] = min(r["throughput_avg"] for r in runs)
        line["throughput_avg_median"] = _median(
            [r["throughput_avg"] for r in runs]
        )
        line["wire"] = wire
        # artifact provenance (VERDICT r4 weak #2: append-mode rows with
        # mixed schemas made "the number" whichever row was last); only
        # stamped when the round is actually known — a wrong assertion
        # is worse than an absent field
        if os.environ.get("BENCH_ROUND"):
            line["round"] = int(os.environ["BENCH_ROUND"])
        print(json.dumps(line), flush=True)
        # append per config: a crash or timeout must not lose finished runs
        with open(out_path, mode) as f:
            f.write(json.dumps(line) + "\n")
        mode = "a"
    if failed:
        sys.exit("bench_configs: rows are not clean measurements:\n  "
                 + "\n  ".join(failed))


if __name__ == "__main__":
    main()
