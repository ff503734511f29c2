"""Scheduler metric set (reference: pkg/scheduler/metrics/metrics.go:45-163).

Same metric names as the reference so dashboards/harnesses carry over:
schedule_attempts_total{result,profile}, e2e/algorithm duration histograms,
framework_extension_point_duration_seconds, pending_pods{queue},
scheduler_cache_size, preemption_victims/attempts.
"""

from __future__ import annotations

from ..utils.metrics import Counter, Gauge, Histogram, legacy_registry

SCHEDULED = "scheduled"
UNSCHEDULABLE = "unschedulable"
ERROR = "error"

schedule_attempts = legacy_registry.register(
    Counter(
        "scheduler_schedule_attempts_total",
        "Number of attempts to schedule pods, by result.",
        ("result", "profile"),
    )
)
e2e_scheduling_duration = legacy_registry.register(
    Histogram(
        "scheduler_e2e_scheduling_duration_seconds",
        "E2e scheduling latency (scheduling algorithm + binding).",
        ("result", "profile"),
    )
)
scheduling_algorithm_duration = legacy_registry.register(
    Histogram(
        "scheduler_scheduling_algorithm_duration_seconds",
        "Scheduling algorithm latency.",
        (),
    )
)
framework_extension_point_duration = legacy_registry.register(
    Histogram(
        "scheduler_framework_extension_point_duration_seconds",
        "Latency per scheduling framework extension point.",
        ("extension_point", "status", "profile"),
    )
)
pending_pods = legacy_registry.register(
    Gauge(
        "scheduler_pending_pods",
        "Pending pods by queue: active, backoff, unschedulable.",
        ("queue",),
    )
)
cache_size = legacy_registry.register(
    Gauge(
        "scheduler_scheduler_cache_size",
        "Scheduler cache contents by type.",
        ("type",),
    )
)
preemption_attempts = legacy_registry.register(
    Counter(
        "scheduler_preemption_attempts_total",
        "Total preemption attempts in the cluster.",
        (),
    )
)
preemption_victims = legacy_registry.register(
    Histogram(
        "scheduler_preemption_victims",
        "Number of selected preemption victims.",
        (),
        buckets=(1, 2, 4, 8, 16, 32, 64),
    )
)
batch_size = legacy_registry.register(
    Histogram(
        "scheduler_tpu_batch_size",
        "Pods per fused TPU scheduling dispatch (TPU-build metric).",
        (),
        buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
    )
)
pod_scheduling_duration = legacy_registry.register(
    Histogram(
        "scheduler_pod_scheduling_duration_seconds",
        "E2e latency for a pod being scheduled, from first attempt "
        "(queue admission) to bind sent — the metric scheduler_perf "
        "extracts Perc50/90/99 from (reference: metrics.go "
        "PodSchedulingDuration; test/integration/scheduler_perf/"
        "util.go:177-218).",
        ("attempts",),
        # metrics.go PodSchedulingDuration: ExponentialBuckets(0.001, 2, 20)
        buckets=tuple(0.001 * 2**i for i in range(20)),
    )
)
scheduling_attempt_duration = legacy_registry.register(
    Histogram(
        "scheduler_pod_scheduling_attempt_duration_seconds",
        "Latency of ONE scheduling attempt: queue pop to bind sent "
        "(excludes queue wait; the per-attempt half of the north-star "
        "latency metric).",
        (),
        buckets=tuple(0.001 * 2**i for i in range(20)),
    )
)
e2e_duration = legacy_registry.register(
    Histogram(
        "scheduler_e2e_duration_seconds",
        "Kube-style e2e scheduling SLO histogram: queue admission "
        "(first attempt) to bind sent, per pod — the distribution "
        "behind the harness's pod_scheduling_p50/90/99 extracts, "
        "exposed on /metricsz so an SLO reader needs no harness. Fed "
        "from the same bind timestamps the latency sample ring uses.",
        (),
        buckets=tuple(0.001 * 2**i for i in range(20)),
    )
)
attempt_duration = legacy_registry.register(
    Histogram(
        "scheduler_attempt_duration_seconds",
        "Per-stage scheduling SLO histogram (kube's "
        "scheduling_attempt_duration sliced by pipeline stage): "
        "stage=attempt is one attempt queue-pop->bind-sent (per pod); "
        "stage=bind is the batched bind POST (per batch); "
        "stage=complete is the completion worker's harvest+assume+bind "
        "pass (per batch); stage=fifo-wait is dispatch-enqueue->"
        "completion-finish age (per batch; the overload monitor's "
        "primary signal, as a distribution instead of a last-value "
        "gauge).",
        ("stage",),
        buckets=tuple(0.001 * 2**i for i in range(20)),
    )
)
queue_wait = legacy_registry.register(
    Histogram(
        "scheduler_queue_wait_seconds",
        "Queue wait per scheduled pod: queue admission (first attempt "
        "timestamp) to the pop that led to its bind — e2e minus the "
        "attempt, as its own SLO distribution (kube's "
        "pod_scheduling_sli_duration decomposition).",
        (),
        buckets=tuple(0.001 * 2**i for i in range(20)),
    )
)
device_time = legacy_registry.register(
    Counter(
        "scheduler_device_time_seconds_total",
        "Accumulated device time by kind and session slug (TPU-build "
        "metric; KTPU_DEVTIME >= 1, zero-cost and absent at 0): "
        "kind=kernel is scheduling-scan submit->ready time, "
        "kind=transfer is session-build cluster upload, kind=compile "
        "is AOT executable-cache misses. slug carries the session kind "
        "and mesh shard count ('pallas@8', 'hoisted') in the "
        "session_builds slug convention, so the mesh bench rows read "
        "collective/transfer cost PER SHARD COUNT. Rate(kernel) vs "
        "wall-clock is the device-utilization half of the overlap "
        "accounting in utils/devtime.py.",
        ("slug", "kind"),
    )
)
backend_mode = legacy_registry.register(
    Gauge(
        "scheduler_backend_mode",
        "Active scoring-backend rung of the degradation ladder "
        "(TPU-build metric): 2=pallas single-launch, 1=hoisted jnp scan, "
        "0=oracle (host Go-semantics path). Anything below the platform's "
        "top rung means the backend demoted itself after consecutive "
        "device faults and a background probe is working on re-promotion "
        "— alert on a sustained drop.",
        (),
    )
)
device_faults = legacy_registry.register(
    Counter(
        "scheduler_device_faults_total",
        "Device dispatch faults seen by the TPU backend, by kind: "
        "kind=raise (launch/dispatch raised), kind=timeout (a pending "
        "scan exceeded the dispatch watchdog — wedged device wait), "
        "kind=invalid (harvested masks/scores failed the finite/in-range "
        "guard before assume). Enough consecutive faults demote the "
        "backend one ladder rung (scheduler_backend_mode).",
        ("kind",),
    )
)
device_waits = legacy_registry.register(
    Counter(
        "scheduler_device_waits_total",
        "Watchdog-bounded waits for a device launch's results, by how "
        "each ended: outcome=ready (done at the first look, no hand-over), "
        "outcome=woken (the caller slept on a device waiter's event and "
        "was woken when the launch ended), outcome=timed_out (the "
        "deadline passed: a device fault follows), outcome=polled (no "
        "waiter thread could be started, or a fault drill held the wait "
        "wedged: the 2 ms poll served). One wait a pipelined launch.",
        ("outcome",),
    )
)
dispatch_retries = legacy_registry.register(
    Counter(
        "scheduler_dispatch_retries_total",
        "Device dispatches re-driven after a fault: session rebuild + "
        "capped exponential backoff with jitter (the Supervisor's restart "
        "policy at dispatch granularity). A retry storm without matching "
        "binds means the retry budget is being burned on a sick device.",
        (),
    )
)
worker_restarts = legacy_registry.register(
    Counter(
        "scheduler_worker_restarts_total",
        "Scheduling-pipeline worker threads (worker=scheduler | "
        "completion) restarted by the in-process supervision wrapper "
        "after a crash; the in-flight dispatch FIFO is drained back to "
        "the scheduling queue before the restart.",
        ("worker",),
    )
)
session_rebuilds = legacy_registry.register(
    Counter(
        "scheduler_session_rebuilds_total",
        "Live device sessions torn down, by WHY (TPU-build metric). "
        "Every teardown costs the next batch a full rebuild (prologue "
        "sweeps + cluster upload, seconds at 5000 nodes), so this "
        "counter is the rebuild-storm detector: cluster-churn reasons "
        "(foreign-pod-add, pod-remove) should be near zero now that "
        "batchable pod events apply as carry deltas "
        "(scheduler_session_delta_applies_total) — a sustained rate "
        "there means events are falling off the delta fast path. "
        "shards = mesh shard count at teardown time ('' off-mesh): at "
        "100k nodes a rebuild storm is a per-HOST cost, so alerts key "
        "on the sharded series.",
        ("reason", "shards"),
    )
)
session_delta_applies = legacy_registry.register(
    Counter(
        "scheduler_session_delta_applies_total",
        "Cluster events absorbed into the LIVE device session as "
        "incremental state deltas instead of session teardowns "
        "(TPU-build metric): kind=pod-add / pod-remove are batchable-pod "
        "carry deltas (utilization row + PTS pair-count patch), "
        "kind=node-alloc is an allocatable-only prologue patch. Each "
        "apply replaces a full rebuild on the old path.",
        ("kind",),
    )
)
node_joins = legacy_registry.register(
    Counter(
        "scheduler_node_joins_total",
        "Nodes added to the cluster encoding, by the path that kept "
        "live lanes in node order (TPU-build metric): own-lane (a "
        "returning name took back the lane it left), free-lane (another "
        "tombstone between its live neighbours' lanes), tail-lane (a "
        "name after every live node, into the padded tail) are "
        "incremental; shifted (no free lane where the name sorts: the "
        "live rows up to the nearest free lane moved over by one, no pod "
        "re-encoded, the session rebuilt); structural (vocabulary or "
        "lane space grew, "
        "or the name was there: the encoding is rebuilt from its "
        "objects, seconds at 100k pods).",
        ("path",),
    )
)
node_leaves = legacy_registry.register(
    Counter(
        "scheduler_node_leaves_total",
        "Nodes removed from the cluster encoding (TPU-build metric): "
        "incremental (a drained node: its lane becomes a tombstone) or "
        "structural (it still carried pods: the encoding is rebuilt).",
        ("path",),
    )
)
session_builds = legacy_registry.register(
    Counter(
        "scheduler_tpu_session_builds_total",
        "Device session (re)builds by kernel kind (TPU-build metric): "
        "kind=pallas is the single-launch fast path; kind=hoisted is the "
        "jnp lax.scan fallback. A pallas->hoisted downgrade on a workload "
        "that previously rode pallas is a ~2.4x throughput cliff — alert "
        "on it; the build also logs the downgrade reason. shards = mesh "
        "shard count the session spans ('' off-mesh), so per-shard build "
        "rates separate mesh rebuild storms from single-chip ones.",
        ("kind", "reason", "shards"),
    )
)
session_templates = legacy_registry.register(
    Gauge(
        "scheduler_session_templates",
        "The live device session's table of pod specs (TPU-build "
        "metric): what=specs the specs admitted, what=capacity the "
        "room for them, what=rows / what=row_capacity the count rows "
        "their constraints own, what=static_rows / "
        "what=static_row_capacity the interned per-node static rows "
        "(masks, raw scores, pair ids). A spec that does not fit is a "
        "rebuild "
        "(scheduler_session_rebuilds_total{reason=table-*}); watch "
        "specs against capacity to see one coming.",
        ("what",),
    )
)
inexact_builds = legacy_registry.register(
    Counter(
        "scheduler_tpu_inexact_builds_total",
        "Device session builds that cannot score every node as the "
        "reference does (TPU-build metric): what=balanced a table "
        "session whose BalancedAllocation runs in float32 (more than 64 "
        "node capacities, a capacity product past the exact forms, more "
        "float64-quirk states than the kernel lists), what=spread one "
        "whose zone-spread product runs in float32 (more pod rows than "
        "its limbs count), what=demoted a table session that could not "
        "be built at all, replaced by the jnp hoisted session. Reads 0 "
        "wherever the table kernel holds the cluster exactly.",
        ("what",),
    )
)
balanced_quirk_states = legacy_registry.register(
    Gauge(
        "scheduler_tpu_balanced_quirk_states",
        "Node states at which the reference's float64 BalancedAllocation "
        "reads one less than the exact floor, as the live table session "
        "lists them for its kernel (TPU-build metric): what=listed the "
        "states listed, what=capacity the room for them. Past capacity "
        "the session scores balanced in float32 "
        "(scheduler_tpu_inexact_builds_total{what=balanced}).",
        ("what",),
    )
)
session_template_admits = legacy_registry.register(
    Counter(
        "scheduler_session_template_admits_total",
        "Pod specs a LIVE device session took in without a rebuild and "
        "without a compile (a row write each); the specs of a session's "
        "own build are not counted.",
        (),
    )
)
mesh_shards = legacy_registry.register(
    Gauge(
        "scheduler_mesh_shards",
        "Devices in the node-axis scoring mesh (TPU-build metric): 0 = "
        "single-device dispatch (no mesh), N = every per-node array is "
        "split N ways and each host holds 1/N of the cluster encoding. "
        "Changes only at backend construction — a drop to 0 in a fleet "
        "that should be meshed means the mesh env (KTPU_MESH_DEVICES / "
        "megascale topology) regressed.",
        (),
    )
)
preemption_planner = legacy_registry.register(
    Counter(
        "scheduler_preemption_planner_total",
        "Preemptors planned, by planner-ladder rung (TPU-build metric): "
        "path=device is the batched what-if scan (one fused launch per "
        "preemptor over every candidate node — covers affinity/spread "
        "preemptors); path=fast is the numpy vectorized planner "
        "(resource-fit envelope); path=oracle is the per-pod "
        "DefaultPreemption dry-run via redispatch. A preemption-heavy "
        "workload sitting on path=oracle is the crawl this ladder "
        "exists to prevent — check "
        "scheduler_whatif_fallbacks_total{reason} for why.",
        ("path",),
    )
)
whatif_launches = legacy_registry.register(
    Counter(
        "scheduler_whatif_launches_total",
        "Fused what-if device launches: one per preemptor planned "
        "alone (base feasibility + the full reprieve walk across all "
        "candidate nodes), one per wave launch of up to 64 preemptors "
        "(scheduler_whatif_planned_total{path=\"wave\"}). Launches "
        "never touch the live session carry — "
        "scheduler_session_rebuilds_total must not move with this "
        "counter.",
        (),
    )
)
whatif_planned = legacy_registry.register(
    Counter(
        "scheduler_whatif_planned_total",
        "Preemptors planned by a what-if launch, by how: path=wave "
        "(reason=lane-local) in a wave launch, whose program picks and "
        "claims on the device for a run of preemptors of one view, "
        "template and priority; path=single in a launch of their own, "
        "the reason saying why they left the wave launch: pdb (a "
        "victim of the wave is covered by a PDB: budgets move with "
        "claims), pairs (a victim or the preemptor matches the "
        "template's spread classes or required (anti-)affinity terms: "
        "a claim writes topology-pair counts), gang (a gang unit among "
        "the victims), fault (the wave launch they were in failed), "
        "key (no run of their key could be formed), off (a planner "
        "built without wave launches).",
        ("path", "reason"),
    )
)
whatif_inputs = legacy_registry.register(
    Counter(
        "scheduler_whatif_inputs_total",
        "How each what-if launch got its inputs onto the device: "
        "path=delta (reason=resident) sent only the lanes the wave's "
        "claims changed since the same view, template and priority "
        "last launched, into inputs kept on the device; path=full "
        "uploaded them whole, reason=first (that view, template and "
        "priority's first launch), overflow (more changes than a delta "
        "holds), fault (the previous launch raised: its donated inputs "
        "are gone), pdb (a PDB budget of the wave's books moved), off "
        "(a planner built without resident inputs).",
        ("path", "reason"),
    )
)
preemption_books_nodes = legacy_registry.register(
    Counter(
        "scheduler_preemption_books_nodes_total",
        "Nodes of each preemption wave's books, by how their part was "
        "had: path=kept reused the part an earlier wave built (the "
        "node's generation had not moved), path=rebuilt walked the "
        "node's pods again (its generation moved, it is new, a claim "
        "split one of its gang units, or its device rows were due). "
        "Waves of a few hundred preemptors into thousands of nodes "
        "should read >= 90 % kept after the first.",
        ("path",),
    )
)
whatif_fallbacks = legacy_registry.register(
    Counter(
        "scheduler_whatif_fallbacks_total",
        "Device-rung preemptors that fell a rung, by reason: "
        "reason=fault (device fault mid-what-if — counted in "
        "scheduler_device_faults_total and ladder-recorded, live "
        "session untouched), reason=disabled (KTPU_WHATIF=0 kill "
        "switch), reason=demoted (degradation ladder at oracle), "
        "reason=template/context/encode/node-skew (preemptor outside "
        "the what-if view), reason=error (host-side prep failure).",
        ("reason",),
    )
)
trace_dumps = legacy_registry.register(
    Counter(
        "scheduler_trace_dumps_total",
        "Flight-recorder dumps emitted at pipeline fault seams, by seam: "
        "seam=device-fault-<kind> (watchdog timeout / harvest validation "
        "/ dispatch raise), seam=pipeline-stalled (_drain_pipeline budget "
        "exceeded), seam=ladder-demoted, seam=whatif-fault, "
        "seam=worker-restart-<worker>, seam=shadow-drift (the parity "
        "sentinel caught a device decision the oracle replay disagrees "
        "with — scheduler_parity_drift_total names the plugin). Each "
        "dump snapshots the last N "
        "span events (utils/tracing.py) to the log/file before recovery "
        "proceeds — nonzero here means a fault seam fired with a "
        "triageable record attached.",
        ("seam",),
    )
)
fencing_rejections = legacy_registry.register(
    Counter(
        "scheduler_fencing_rejections_total",
        "State-changing writes the apiserver rejected because their "
        "lease fencing token was stale (different holder or an older "
        "leaseTransitions epoch than the stored leader lease), by "
        "op=bind|update_status|delete. Nonzero means a deposed leader "
        "tried to write after failover and the fence held — the "
        "split-brain double-bind that write would have been never "
        "reached the store. The healthy-path count is ZERO: the "
        "elector self-fences KTPU_LEASE_FENCE_MARGIN seconds before "
        "its lease expires, so only clock skew, a GC pause outliving "
        "the margin, or a drill's deliberate stale replay lands here.",
        ("op",),
    )
)
restart_reconcile = legacy_registry.register(
    Counter(
        "scheduler_restart_reconcile_total",
        "Pods processed by the cold-restart/promotion reconcile "
        "(authoritative store relist), by outcome: outcome=adopted "
        "(already bound — folded into the SchedulerCache as its node's "
        "tenant), outcome=requeued (unbound in-flight pod re-entered "
        "the active queue, exactly once — dedup against the queue and "
        "the drained-FIFO set), outcome=cleared (stale "
        "nominated_node_name from a preemption that never completed "
        "wiped so the slot isn't double-reserved).",
        ("outcome",),
    )
)
leader_transitions = legacy_registry.register(
    Counter(
        "scheduler_leader_transitions_total",
        "Times THIS scheduler instance was promoted to leader "
        "(lease acquired or adopted). Summed across instances it "
        "counts failovers + initial elections; a climb with no chaos "
        "running means the lease is flapping (fence margin too tight "
        "for the renew cadence, or the store is slow).",
        (),
    )
)
gang_admitted = legacy_registry.register(
    Counter(
        "scheduler_gang_admitted_total",
        "Gangs whose Permit transaction committed: every member was "
        "reserved, the gang gate flipped waiting->completed exactly "
        "once, and all members were released to bind as one batch. "
        "The all-or-nothing success count; pairs with "
        "scheduler_gang_rollbacks_total as the failure count.",
        (),
    )
)
gang_rejected = legacy_registry.register(
    Counter(
        "scheduler_gang_rejected_total",
        "Gang members bounced at Permit before reserving completed, by "
        "reason: reason=invalid (min-available < 1), reason=late (a "
        "member arrived after its gang already failed this wave — it "
        "requeues rather than camp on a dead transaction). Counted per "
        "member, not per gang; these never held a reservation.",
        ("reason",),
    )
)
gang_rollbacks = legacy_registry.register(
    Counter(
        "scheduler_gang_rollbacks_total",
        "Whole-gang rollbacks (every reserved/waiting member released "
        "and requeued as one wave), by reason: reason=timeout "
        "(KTPU_GANG_PERMIT_TIMEOUT elapsed before completion), "
        "reason=member-deleted (a waiting member was deleted "
        "mid-permit), reason=member-rejected (a Permit plugin rejected "
        "a member), reason=deadlock (the deadlock breaker backed off "
        "the youngest of mutually-blocking gangs), reason=reconcile "
        "(promotion reconcile found an orphaned gang reservation from "
        "a dead leader), reason=device-fault (a member's dispatch "
        "abandoned — the whole gang re-drives through recovery), "
        "reason=demotion (leader demoted with the gang mid-permit), "
        "reason=preempted (the gang's bound members were chosen as "
        "preemption victims — its waiting wave unwinds too). "
        "Counted once per gang per rollback.",
        ("reason",),
    )
)
gang_preempted = legacy_registry.register(
    Counter(
        "scheduler_gang_preempted_total",
        "Gangs evicted whole by gang-aware preemption: the victim scan "
        "groups same-node members into one eviction unit, and "
        "_apply_preemptions closes over the gang's off-node siblings "
        "so no partial gang survives a preemption. Counted once per "
        "gang per preemption (however many members it had).",
        (),
    )
)
gang_admission_duration = legacy_registry.register(
    Histogram(
        "scheduler_gang_admission_duration_seconds",
        "Gang admission latency: first member parked at Permit to the "
        "gang gate committing (waiting->completed). The gang-level "
        "SLO the Gang-{8,64,256} bench rows report as "
        "gang_admission_p99; one observation per admitted gang.",
        (),
        buckets=tuple(0.001 * 2**i for i in range(20)),
    )
)


def dump_seam(seam: str, **attrs) -> None:
    """Flight-recorder dump + scheduler_trace_dumps_total bump, PAIRED.
    Every fault seam goes through here so the counter and the dump can
    never drift apart — fault_drill's --dump-trace integrity check
    counts faults against dumps, and a seam that bumps without dumping
    (or vice versa) would silently break that accounting. The device
    timeline dumps HERE too (utils/devtime.py): a device fault leaves
    both the host span trail and the launch timeline, each gated on its
    own level. No-op with both recorders off (the rings are empty there
    and the fault path stays cheap)."""
    from ..utils import devtime, tracing

    if tracing.enabled():
        trace_dumps.inc(seam=seam)
        tracing.dump(seam, **attrs)
    if devtime.enabled():
        devtime.dump(seam, **attrs)


shadow_samples = legacy_registry.register(
    Counter(
        "scheduler_shadow_samples_total",
        "Decided pods replayed through the oracle filter/score chain by "
        "the shadow parity sentinel (KTPU_SHADOW_SAMPLE > 0): each "
        "sample re-derives the decision read-only against the "
        "decision-time cache state the completion worker already holds "
        "for assume ordering. The denominator for "
        "scheduler_parity_drift_total.",
        (),
    )
)
shadow_skips = legacy_registry.register(
    Counter(
        "scheduler_shadow_skips_total",
        "Shadow audits voided by the stale-basis gate: the cache's "
        "foreign-mutation generation advanced between dispatch and "
        "completion (informer add/update/remove, node event, TTL "
        "expiry, forget), so the oracle replay would adjudicate against "
        "state the device never decided on. A skip is lost sentinel "
        "COVERAGE, never a drift signal — sustained high skip:sample "
        "ratios mean completions lag events (see the overload monitor).",
        ("reason",),
    )
)
parity_drift = legacy_registry.register(
    Counter(
        "scheduler_parity_drift_total",
        "Shadow-sentinel mismatches between a device decision and the "
        "oracle replay, by the plugin whose filter verdict or weighted "
        "score diverged (plugin=decision when the totals disagree "
        "without a per-plugin culprit, e.g. explain attribution was "
        "unavailable). Every drift dumps the flight-recorder ring "
        "(seam=shadow-drift) and writes a repro bundle that "
        "scripts/replay_drift.py re-adjudicates offline — on chips this "
        "counter IS the continuously-measured form of the CI parity "
        "gate, so any sustained nonzero rate is a page. Informer events "
        "landing between dispatch and completion can produce isolated "
        "false positives; the bundle replay tells them apart.",
        ("plugin",),
    )
)
explain_harvests = legacy_registry.register(
    Counter(
        "scheduler_explain_harvests_total",
        "Batches harvested WITH per-pod decision attribution attached "
        "(KTPU_EXPLAIN / shadow sampling): the sessions returned "
        "per-plugin filter verdicts and weighted score splits alongside "
        "decisions. Explain mode pins the hoisted session "
        "(scheduler_tpu_session_builds_total reason=explain), so "
        "this counter moving on a pallas-class platform names the "
        "audit-mode throughput cost.",
        (),
    )
)
speculative_dispatches = legacy_registry.register(
    Counter(
        "scheduler_speculative_dispatches_total",
        "Batches dispatched chained on a NOT-YET-HARVESTED carry "
        "(pipelined scans enqueued while earlier batches were still in "
        "flight), by outcome: outcome=hit harvested cleanly; "
        "outcome=miss was re-driven synchronously because the carry it "
        "chained on was invalidated (device fault, harvest validation "
        "failure, or a completion-worker crash abandon). "
        "KTPU_SPECULATION=0 serializes dispatch on "
        "harvest and zeroes this counter.",
        ("outcome",),
    )
)
overload_sheds = legacy_registry.register(
    Counter(
        "scheduler_overload_sheds_total",
        "Optional work SHED by the host overload monitor under sustained "
        "pressure (completion-FIFO age / queue depth / stage latency past "
        "their high-water marks for the dwell window), by lever: "
        "what=explain-harvest (host skips attribution decode), "
        "what=shadow-sample (parity-sentinel rate to 0), what=devtime "
        "(device timeline off), what=trace (flight recorder off), "
        "what=speculation (dispatch serializes on "
        "harvest). Levers shed in that fixed order and restore LIFO after "
        "a sustained-calm window — decision correctness is never shed, so "
        "this counter moving changes observability coverage, not "
        "placements. Sustained nonzero rate = the host is the "
        "bottleneck; see the paired OverloadShed k8s Events for the "
        "triggering signal values.",
        ("what",),
    )
)
overload_restores = legacy_registry.register(
    Counter(
        "scheduler_overload_restores_total",
        "Shed levers restored by the overload monitor after the calm "
        "dwell window (LIFO: last lever shed is first restored). "
        "sheds_total - restores_total = levers currently shed (also on "
        "scheduler_overload_level).",
        ("what",),
    )
)
overload_level = legacy_registry.register(
    Gauge(
        "scheduler_overload_level",
        "Number of overload-shed levers currently engaged (0 = full "
        "observability, 5 = maximally shed: explain+shadow+devtime+"
        "trace+speculation). Alert on this sitting above 0 — the host "
        "cannot "
        "keep up with the configured audit load.",
        (),
    )
)
expired_assumes = legacy_registry.register(
    Counter(
        "scheduler_cache_expired_assumes_total",
        "Assumed pods expired by the cache TTL sweep because no bind "
        "confirmation (informer add) arrived within the assume TTL. "
        "Expiry routes through the cache listeners like any other "
        "remove (live device sessions absorb it as a carry delta), but "
        "each expiry means a bind was sent and never observed — lost "
        "bind, apiserver lag, or informer stall. Production rate should "
        "be ~0; the endurance soak asserts it.",
        (),
    )
)
assumed_pods = legacy_registry.register(
    Gauge(
        "scheduler_cache_assumed_pods",
        "Pods currently in the assumed (optimistically bound, awaiting "
        "informer confirmation) state in the scheduler cache.",
        (),
    )
)
oldest_assume_age = legacy_registry.register(
    Gauge(
        "scheduler_cache_oldest_assume_seconds",
        "Age past bind-finish of the OLDEST still-assumed pod at the "
        "last TTL sweep (0 when none are overdue-tracked). The sweep "
        "runs every ~1 s, so this exceeding assume TTL + a couple of "
        "sweep periods means the expiry sweep itself is stalled — the "
        "soak's no-pod-outlives-its-TTL invariant reads this gauge.",
        (),
    )
)
completion_fifo_depth = legacy_registry.register(
    Gauge(
        "scheduler_completion_fifo_depth",
        "In-flight dispatched batches awaiting completion (the pipeline "
        "FIFO between the scheduler thread and the completion worker). "
        "Bounded by pipeline_depth; pinned at the bound = dispatch is "
        "waiting on host completion.",
        (),
    )
)
completion_fifo_age = legacy_registry.register(
    Gauge(
        "scheduler_completion_fifo_age_seconds",
        "Queue-to-completion age of the batch most recently completed: "
        "time from dispatch enqueue to completion finish. The overload "
        "monitor's primary hot signal — sustained age above the "
        "high-water mark sheds optional work "
        "(scheduler_overload_sheds_total).",
        (),
    )
)
