"""TPU scheduling backend: the kernel-driven replacement for the oracle
filter/score path.

Where the reference runs findNodesThatPassFilters + RunScorePlugins per
node on goroutines (reference: pkg/scheduler/core/generic_scheduler.go:235,
pkg/scheduler/framework/runtime/framework.go:723), this backend keeps the
whole cluster as device-resident dense arrays (models/encoding.py), mirrors
every scheduler-cache mutation into them via CacheListener hooks, and
evaluates ALL nodes in one fused dispatch (ops/kernel.py) — no adaptive
subsampling (generic_scheduler.go:177's 5-50% compromise removed).

Status reconstruction: each kernel mask corresponds to one plugin's Filter;
infeasible nodes get Unschedulable statuses naming the failing plugins so
FitError output matches the oracle's shape (plugin-name level, not
message-string level).
"""

from __future__ import annotations

import logging
import os
import queue
import random
import threading
import time as _time
import weakref
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..api import types as v1
from ..models.encoding import ClusterEncoding
from ..models.pod_encoder import PodEncoder
from ..ops.batch import shape_signature
from ..ops.hoisted import (
    HoistedSession,
    ipa_term_match_np,
    match_matrices_np,
    template_fingerprint,
)
from ..utils import devtime, knobs, tracing
from .degradation import (
    RUNG_HOISTED,
    RUNG_ORACLE,
    RUNG_PALLAS,
    DegradationLadder,
    DeviceFault,
)

logger = logging.getLogger(__name__)

# sentinel "node" for a gate/encode volume-resolution race: the pod is
# not unschedulable — it must RE-GATE promptly (the scheduler re-adds it
# to the active queue instead of parking it for the leftover flusher)
RETRY_NODE = "\x00volume-retry"
from ..ops.kernel import DEFAULT_WEIGHTS, schedule_pod_jit
from .core import ScheduleResult
from .framework.interface import FitError, Status
from .internal.cache import CacheListener
from .volume_device import VolumeResolutionChanged

# kernel mask key -> plugin name (for FitError statuses)
MASK_PLUGINS = (
    ("mask_name", "NodeName"),
    ("mask_unsched", "NodeUnschedulable"),
    ("mask_taint", "TaintToleration"),
    ("mask_ports", "NodePorts"),
    ("mask_fit", "NodeResourcesFit"),
    ("mask_node_affinity", "NodeAffinity"),
    ("mask_pts", "PodTopologySpread"),
    ("mask_ipa", "InterPodAffinity"),
)


def _explain_topk(payload: Dict, node_names: List[str]) -> List[Tuple[str, int]]:
    """Level-2 provenance rendering of one pod's explain payload: the
    top-k candidates as (node, weighted total), best first. The full
    per-plugin masks/scores stay on the batch handle for the sentinel and
    the explain CLI — the flight-recorder record carries the ranking."""
    out: List[Tuple[str, int]] = []
    for idx, total in zip(payload["topk_idx"], payload["topk_total"]):
        idx, total = int(idx), int(total)
        if 0 <= idx < len(node_names) and total >= 0:
            out.append((node_names[idx], total))
    return out


class _DeviceWaiter:
    """A long-lived daemon thread whose one job is to block in the
    runtime until a launch's result leaves are ready, and then to set
    an event. The pipeline's threads wait on that event under the
    watchdog (TPUBackend._wait_ready), so a wedged device pins this
    thread and never one of theirs: a waiter that has not come back by
    the deadline is told to stop and left behind, and the next wait
    starts another. One wait at a time: a waiter is either in the
    backend's idle list or in the hands of the one thread that took it
    out."""

    __slots__ = ("done", "thread", "_work")

    def __init__(self):
        self.done = threading.Event()
        self._work: "queue.SimpleQueue" = queue.SimpleQueue()
        self.thread = threading.Thread(
            target=self._run, name="tpu-device-waiter", daemon=True)
        self.thread.start()

    def _run(self) -> None:
        import jax

        while True:
            leaves = self._work.get()
            if leaves is None:
                return
            try:
                # ktpu: allow-sync(device waiter: the one thread whose job is to block; the pipeline waits on its event under the watchdog)
                jax.block_until_ready(leaves)
            except Exception:  # noqa: BLE001 — reported as ready: let
                pass  # decode surface it
            finally:
                # set even if this thread is dying: no wait sleeps out
                # its deadline for a waiter that is gone
                leaves = None  # the arrays are the harvest's to free
                self.done.set()

    def wait(self, leaves, timeout: float) -> bool:
        """Hand the leaves over and sleep until they are ready (True)
        or `timeout` seconds have passed (False)."""
        self.done.clear()
        self._work.put(leaves)
        return self.done.wait(timeout)

    def stop(self) -> None:
        """Exit once the current blocking call, if any, returns."""
        self._work.put(None)


def _stop_waiters(waiters: List[_DeviceWaiter]) -> List[_DeviceWaiter]:
    """Stop every idle waiter of a backend (close(), and the backend's
    finalizer: a backend dropped without close() leaves no thread)."""
    stopped = []
    while waiters:
        w = waiters.pop()
        w.stop()
        stopped.append(w)
    return stopped


class _BatchHandle:
    """One dispatched batch: device outputs + how to decode them. The
    decode fn is captured at dispatch time because the session may be
    invalidated (by foreign cluster events) before harvest — the computed
    ys stay valid either way."""

    __slots__ = ("group", "ys", "decide", "node_names", "results",
                 "deadline", "bucket", "timed_out", "waited", "speculative",
                 "prov", "explain", "basis_mutations", "dt", "batch")

    def __init__(self, group: List[v1.Pod], batch: Optional[int] = None):
        self.group = group
        # the scheduler loop's batch id (its scheduling cycle): the
        # backend's spans carry the same number as the loop's
        self.batch = batch
        self.ys = None
        self.decide = None
        # speculative dispatch: this scan was enqueued while EARLIER
        # batches were still in flight — it chained on a carry whose
        # decisions had not been harvested/validated yet. A clean FIFO
        # harvest is a speculation hit; a re-drive because that carry
        # was invalidated (fault, validation failure, worker-crash
        # abandon) is a miss.
        self.speculative = False
        # decisions are node INDICES into the cluster as of dispatch; a
        # node remove/rebuild before harvest would shift enc.node_names,
        # so the dispatch-time table rides the handle
        self.node_names: Optional[List[str]] = None
        self.results: Optional[List[Tuple[v1.Pod, Optional[str]]]] = None
        # dispatch watchdog: the wall-clock deadline for this scan's
        # results; a wait past it is a device fault, not a longer wait
        self.deadline: Optional[float] = None
        self.bucket: Optional[int] = None  # pallas AOT-exec bucket (Bp)
        self.timed_out = False
        # harvest() saw the results ready outside the lock: the locked
        # harvest does not ask again
        self.waited = False
        # flight-recorder provenance captured at dispatch time (rung,
        # session kind, build reason, ...). None unless KTPU_TRACE >= 2
        # — the disabled path must not allocate per batch beyond the
        # handle itself (pinned by the overhead test)
        self.prov: Optional[Dict] = None
        # KTPU_EXPLAIN: the decoded per-pod explain payloads (packed
        # filter-mask bits + top-k totals/score stacks), index-aligned
        # with `group`. None with explain off — same allocation contract
        # as prov — and None on sessions without explain support
        self.explain: Optional[List[Dict]] = None
        # (cache foreign-mutation generation, scheduler dropped-decision
        # count) latched just before dispatch: the shadow sentinel's
        # stale-basis gate — if either advanced by completion time, the
        # oracle replay would run against a cluster the device never
        # decided on, so the audit is skipped (counted) instead of
        # reporting false drift
        self.basis_mutations: Optional[Tuple[int, int]] = None
        # device-timeline launch token (utils/devtime.py): submit
        # stamped at dispatch enqueue, ready at harvest — None below
        # KTPU_DEVTIME=1 (the disabled path allocates nothing per
        # batch; pinned with prov/explain by the overhead test)
        self.dt = None


class TPUBackend(CacheListener):
    """Owns the dense encoding + kernel dispatch; registered as a cache
    listener so device state tracks the assume-cache at O(changed rows)."""

    # templates a session that cannot admit one is (re)built with
    DENSE_SESSION_TEMPLATES = 8

    def __init__(
        self,
        weights: Optional[Dict[str, int]] = None,
        rng: Optional[random.Random] = None,
        mesh=None,
        pallas_interpret: bool = False,
    ):
        self.enc = ClusterEncoding()
        self.pe = PodEncoder(self.enc)
        self.weights = weights or DEFAULT_WEIGHTS
        self.rng = rng or random.Random()
        # multi-chip: a jax.sharding.Mesh shards the NODE axis of every
        # dispatch (parallel/sharded.py) — session statics and carry
        # inherit the sharding through GSPMD, reductions ride ICI
        # collectives. Decisions are bit-identical to single-device
        # (tests/test_sharded.py through the Scheduler loop).
        self.mesh = mesh
        if mesh is not None:
            # rebuild-time node capacity lands on a shard multiple, so
            # the mesh path never re-pads (shape-stable across rebuilds)
            # and incremental node adds stay inside the session's lanes
            from ..parallel.sharded import node_capacity_multiple

            self.enc.node_quantum = node_capacity_multiple(mesh)
        self._lock = threading.RLock()
        # cross-cycle hoisted session (ops/hoisted.py HoistedSession): the
        # device-resident carry survives between schedule_many calls as
        # long as the ONLY cluster mutations are the assumes the session
        # itself produced (tracked in _session_assumed — the cache.assume
        # confirmation arrives later through on_add_pod and must not
        # invalidate). Any other mutation tears the session down; the next
        # batch rebuilds it from the synced encoding.
        self._session = None  # HoistedSession or pallas PallasSession
        self._session_assumed: set = set()
        # incremental device-state deltas: cluster events the classifier
        # proved touch ONLY the session's carry (batchable pod add/remove
        # on a known node) or template-invariant statics (allocatable-only
        # node updates) queue here instead of tearing the session down,
        # and the next dispatch applies them in one fused launch
        # (_apply_session_deltas_locked). Teardown stays the path for
        # everything structural: node add/remove, pods with affinity
        # terms or host ports, vocab/capacity growth. The kill switch
        # exists for A/B parity runs (tests + probe_session_deltas.py).
        self._deltas: List[Dict] = []
        self.delta_patching = knobs.get_bool("KTPU_SESSION_DELTAS")
        # backstop for an idle scheduler accumulating events with no
        # dispatch to flush them: past this the rebuild is cheaper than
        # the queue is worth, and the teardown path absorbs everything
        self.max_queued_deltas = knobs.get_int("KTPU_MAX_QUEUED_DELTAS")
        self._node_fps: Dict[str, tuple] = {}  # heartbeat-change gate
        # fingerprint -> pod arrays of every spec met, least recently
        # used first: what a (re)build takes into the session. A live
        # table session (ops/pallas_scan.py) admits a spec it has not
        # met; any other session kind is rebuilt with it.
        self._known_templates: "OrderedDict" = OrderedDict()
        # (distinct specs, pods that carry a term) of the last launch,
        # for the synchronous dispatch span
        self._launch_stats: Optional[Tuple[int, int]] = None
        self._session_sig: Tuple = ()  # array shapes the session stacked
        self._batch_specs = 0  # distinct specs of the batch in hand
        # in-flight batches, oldest first. Depth 2 double-buffers the
        # device: batch k+1's scan is enqueued (chained on k's carry as a
        # pure data dependency) while k still runs, so the device never
        # drains between the host's harvest of k-1 and the dispatch of
        # k+1. Harvests are strictly FIFO — sequential assume semantics
        # ride the carry chain, and the host encoding applies each
        # batch's decisions in dispatch order (_harvest_locked).
        self._pending: deque = deque()  # of _BatchHandle
        self.max_pending = 2
        # back-pressure seam: when _pending is full, dispatch_many
        # either waits on this condition for the completion worker to
        # drain (async_harvest_drain=True — set by the Scheduler at
        # pipeline_depth >= 1, so the scheduler thread NEVER decodes a
        # harvest) or harvests inline (direct backend users: bench,
        # depth-0). Signalled whenever _pending shrinks.
        self._pending_cv = threading.Condition(self._lock)
        self.async_harvest_drain = False
        # speculative dispatch kill switch (KTPU_SPECULATION=0): with
        # speculation off, a new scan never chains on a not-yet-
        # harvested carry — dispatch_many flushes the pipeline first
        # (serializing; the A/B lever for the bench matrix)
        self.speculation = knobs.get_bool("KTPU_SPECULATION")
        self.volume_resolver = None  # scheduler/volume_device.py
        # pallas rides only on real TPUs: on CPU the interpreter is
        # pathologically slow and compile-heavy, so only a caller that
        # asks for it (pallas_interpret: tests and CPU dry runs of the
        # chip path, at tiny sizes) gets the pallas rung there.
        # A mesh also disables it: the Mosaic kernel is a single-device
        # program; multi-chip rides the two-phase sharded session.
        import jax

        self.pallas_interpret = pallas_interpret
        self.use_pallas = (
            (jax.devices()[0].platform == "tpu" or pallas_interpret)
            and mesh is None
        )
        # device-side preemption planning (ops/whatif.py): the what-if
        # context is a SCRATCH view of the cluster (live-session carry
        # copy, or a non-donating encoding snapshot for pallas/sharded
        # sessions) — launches never chain onto or invalidate the live
        # session. Platform default: ON where the launch is a real device
        # dispatch (TPU), OFF on CPU where the jnp what-if pays XLA
        # compiles the numpy fast rung + oracle don't (the parity suites
        # and probe enable it explicitly).
        # KTPU_WHATIF=0 is the kill switch / =1 the CPU opt-in.
        self.whatif = knobs.get_bool(
            "KTPU_WHATIF",
            default=jax.devices()[0].platform == "tpu",
        )
        # -- device fault tolerance ------------------------------------
        # Optional FaultInjector seam (testing/faults.py, duck-typed):
        # chaos drills arm dispatch raises / NaN harvests / wedged waits
        # through it. None in production.
        self.faults = None
        # watchdog: no device wait (harvest, flush, probe) may exceed
        # this — past it the dispatch is a fault, the in-flight chain is
        # abandoned, and the batch re-drives synchronously
        self.watchdog_timeout = knobs.get_float("KTPU_WATCHDOG_TIMEOUT")
        # bounded retry (capped exponential backoff + full jitter — the
        # Supervisor's restart policy at dispatch granularity)
        self.retry_cap = knobs.get_int("KTPU_DISPATCH_RETRIES")
        self.retry_base = knobs.get_float("KTPU_RETRY_BASE")
        self.retry_max = knobs.get_float("KTPU_RETRY_MAX")
        # degradation ladder: consecutive faults demote pallas -> hoisted
        # -> oracle; the probe loop below re-promotes when a canary
        # dispatch answers correctly again
        self.ladder = DegradationLadder(
            top=RUNG_PALLAS if self.use_pallas else RUNG_HOISTED,
            threshold=knobs.get_int("KTPU_DEMOTE_THRESHOLD"),
            probe_interval=knobs.get_float("KTPU_PROBE_INTERVAL"),
            rng=self.rng,
        )
        self._probe_thread: Optional[threading.Thread] = None
        self._probe_lock = threading.Lock()
        self._probe_stop = threading.Event()
        # pallas batch buckets whose AOT executable produced a fault:
        # quarantined (jit-only) on every rebuilt session — the _exec
        # cache dies with its session, the suspicion must not — until
        # the bucket harvests cleanly again (_harvest_locked)
        self._suspect_buckets: set = set()
        # (session, thread) per live pallas bucket-warm thread
        self._warm: List[Tuple] = []
        # device waiters not in use (_wait_ready): one in steady state,
        # two while the completion worker and a locked flush both wait
        self._idle_waiters: List[_DeviceWaiter] = []
        weakref.finalize(self, _stop_waiters, self._idle_waiters)
        self._whatif_cache: Dict = {}
        self._whatif_cache_version = -1
        # nominated preemptors held in the encoding: pod key -> node
        # (reserve_nominated)
        self._reserved: Dict[str, str] = {}
        # backend-health event hook: the Scheduler wires this to its
        # EventRecorder so ladder demote/promote, supervised-worker
        # restarts and speculation-miss re-drives surface as k8s Events
        # (cluster-level observers see device health without scraping
        # metrics). Signature: (event_type, reason, message). Must never
        # raise into the dispatch path — _notify_health guards it.
        self.health_cb = None
        # decision explainability (ISSUE 10): KTPU_EXPLAIN makes every
        # hoisted harvest carry per-plugin filter-mask verdicts and
        # weighted score splits for the top-k candidate nodes
        # (ops/hoisted.py explain mode; decisions stay bit-identical).
        # KTPU_SHADOW_SAMPLE arms the scheduler's shadow parity sentinel
        # — and needs the explain payload to attribute drift per plugin,
        # so any sample rate > 0 turns explain on. Explain rides the
        # hoisted session only: pallas/sharded sessions demote (loudly,
        # session_builds{reason="explain"}) while it is armed.
        self.shadow_sample = min(1.0, max(0.0,
            knobs.get_float("KTPU_SHADOW_SAMPLE")))
        self.explain = (
            knobs.get_bool("KTPU_EXPLAIN")
            or self.shadow_sample > 0
        )
        self.explain_topk = max(1, knobs.get_int("KTPU_EXPLAIN_TOPK"))
        # overload-shed lever (scheduler/degradation.OverloadMonitor):
        # False = the device still computes explain outputs (the session
        # shape is untouched — no teardown) but the host SKIPS the
        # attribution decode at harvest, shedding the decode cost while
        # overloaded. Decision columns are decoded either way.
        self.explain_harvest = True
        # flight-recorder provenance context: the last session build
        # ("kind/reason") and the last teardown reason — what the
        # per-pod provenance records (KTPU_TRACE=2) report as the
        # session half of "where did this pod's time go"
        self._last_build = ""
        self._last_invalidate = ""
        # device-timeline hand-off: _build_session_impl measures the
        # cluster upload (kind=transfer) before the session kind is
        # known; the _build_session wrapper reads this and feeds the
        # per-shard slug counter once the built session names the slug
        self._upload_seconds = 0.0
        # runtime-effective KTPU_* knob surface (utils/configz.py):
        # today the env vars are invisible at runtime; /configz shows
        # the values this backend actually resolved
        from ..models.vocab import node_headroom as _nh
        from ..utils import configz
        from .metrics import mesh_shards

        mesh_shards.set(
            float(self.mesh.devices.size) if self.mesh is not None else 0.0)
        configz.install_knobs(
            "ktpu",
            mesh_devices=(
                int(self.mesh.devices.size) if self.mesh is not None else 0),
            node_headroom=_nh(),
            speculation=self.speculation,
            whatif=self.whatif,
            session_deltas=self.delta_patching,
            max_queued_deltas=self.max_queued_deltas,
            use_pallas=self.use_pallas,
            watchdog_timeout=self.watchdog_timeout,
            dispatch_retries=self.retry_cap,
            demote_threshold=self.ladder.threshold,
            trace_level=tracing.level(),
            trace_capacity=tracing.RECORDER.capacity,
            devtime_level=devtime.level(),
            devtime_capacity=devtime.TIMELINE.capacity,
            explain=self.explain,
            explain_topk=self.explain_topk,
            shadow_sample=self.shadow_sample,
        )

    def _notify_health(self, event_type: str, reason: str,
                       message: str) -> None:
        """Best-effort backend-health event (ladder transitions, worker
        restarts, speculation-miss re-drives). Never raises: health
        reporting must not add a failure mode to the fault path."""
        cb = self.health_cb
        if cb is None:
            return
        try:
            cb(event_type, reason, message)
        except Exception:  # noqa: BLE001 — observability is best-effort
            logger.warning("backend health event failed", exc_info=True)

    def set_shadow_sample(self, rate: float) -> None:
        """Arm (or disarm) the shadow parity sentinel at runtime — the
        bench/harness knob (Workload.shadow_sample rides the row, not the
        process env). Arming forces explain mode on so drift can be
        attributed per plugin; a live non-explain session is torn down
        and the next dispatch rebuilds with explain outputs."""
        from ..utils import configz

        with self._lock:
            self.shadow_sample = min(1.0, max(0.0, float(rate)))
            explain = (
                knobs.get_bool("KTPU_EXPLAIN")
                or self.shadow_sample > 0
            )
            if explain != self.explain:
                self.explain = explain
                self._invalidate_session("explain-toggle")
            configz.install_knobs(
                "ktpu", explain=self.explain,
                shadow_sample=self.shadow_sample,
            )

    def set_shadow_rate_only(self, rate: float) -> None:
        """Overload-shed path for the sentinel: change the sample rate
        WITHOUT re-deriving explain mode. set_shadow_sample tears down a
        live session when the rate transition flips explain ("explain-
        toggle" rebuild) — exactly wrong under overload, where the point
        of shedding is to spend LESS. Leaving `explain` as resolved at
        arm time keeps the session shape (and therefore decisions)
        bit-identical; the completion worker just stops drawing samples
        while the rate is 0."""
        from ..utils import configz

        with self._lock:
            self.shadow_sample = min(1.0, max(0.0, float(rate)))
            configz.install_knobs("ktpu", shadow_sample=self.shadow_sample)

    def set_volume_resolver(self, resolver) -> None:
        """Enable the volume device path: bound-PVC pods encode their PV
        constraints + attach counts into kernel inputs (volume_device.py)
        instead of diverting to the oracle."""
        with self._lock:
            self.volume_resolver = resolver
            self.pe.volume_resolver = resolver
            self.enc.volume_hook = resolver
            resolver.on_new_driver = self._on_new_volume_driver

    def _on_new_volume_driver(self) -> None:
        """A driver just entered use: node rows built before it carry no
        limit column (reads 0 = limit 0) — rebuild before the next
        dispatch treats every node as attach-full."""
        with self._lock:
            self._invalidate_session("volume-driver")
            self.enc._rebuild_needed = True

    def volume_kernel_safe(self, pod: v1.Pod) -> bool:
        """True when this PVC-bearing pod's volume constraints resolve
        into the kernel envelope RIGHT NOW (gates the oracle diversion)."""
        if self.volume_resolver is None:
            return False
        return self.volume_resolver.resolve(pod) is not None

    def on_volume_change(self, kind: str = "", obj=None) -> None:
        """A PVC/PV/CSINode event: resolver.version bumps always (cached
        pod encodings key off it), but the EXPENSIVE part — session
        teardown + full encoding rebuild — only runs when the object can
        actually touch encoded state: a claim some encoded pod
        references, a PV bound to such a claim, or a CSINode for a
        driver in use. A steady provisioning drip for not-yet-scheduled
        pods must not cost a multi-second rebuild per event."""
        resolver = self.volume_resolver
        if resolver is None:
            return
        with self._lock:
            resolver.bump()
            if not self._volume_obj_encoded(kind, obj, resolver):
                return
            self._invalidate_session("volume-change")
            self.enc._rebuild_needed = True

    @staticmethod
    def _volume_obj_encoded(kind: str, obj, resolver) -> bool:
        if obj is None or not kind:
            return True  # unknown shape: stay conservative
        try:
            if kind == "pvc":
                key = (obj.metadata.namespace, obj.metadata.name)
                return resolver.claim_referenced(key)
            if kind == "pv":
                ns = obj.spec.claim_ref_namespace
                name = obj.spec.claim_ref_name
                if not name:
                    return False  # unbound PV: no encoded pod can see it
                return resolver.claim_referenced((ns or "default", name))
            if kind == "csinode":
                drivers = {d.name for d in obj.spec.drivers or []}
                return resolver.drivers_referenced(drivers)
        except Exception:  # noqa: BLE001 — malformed object: conservative
            return True
        return True

    def _shards_label(self) -> str:
        """`shards` metric label: mesh device count, '' off-mesh —
        appended LAST at every inc site (label order is declared)."""
        return str(int(self.mesh.devices.size)) if self.mesh is not None \
            else ""

    def _devtime_slug(self, session=None) -> str:
        """Per-shard device-time slug ('pallas@8', 'hoisted', '-' with
        no live session): the session_builds kind@shards convention, so
        scheduler_device_time_seconds_total reads per shard count."""
        s = session if session is not None else self._session
        if s is None:
            return "-"
        kind = "pallas" if "Pallas" in type(s).__name__ else "hoisted"
        sh = self._shards_label()
        return f"{kind}@{sh}" if sh else kind

    def _feed_device_time(self, kind: str, seconds: float,
                          session=None) -> None:
        """Accumulate one launch's device seconds into the per-shard
        slug counter (KTPU_DEVTIME >= 1 only — callers gate)."""
        from .metrics import device_time

        if seconds > 0:
            device_time.inc(
                seconds, slug=self._devtime_slug(session), kind=kind)

    def _invalidate_session(self, reason: str = "unspecified") -> None:
        # _session_assumed survives invalidation deliberately: an assume
        # echo (cache confirming a pod the torn-down session scheduled)
        # is host-bookkeeping either way and must not tear down the NEXT
        # session too. Queued deltas do NOT survive: they reconcile the
        # LIVE session with the encoding, and the fresh session builds
        # from the already-mutated encoding.
        import os as _os

        self._deltas.clear()
        if self._session is None:
            return
        from .metrics import session_rebuilds

        session_rebuilds.inc(reason=reason, shards=self._shards_label())
        self._last_invalidate = reason
        tracing.event("session-teardown", "session", reason=reason)
        if knobs.get_flag("KTPU_DEBUG_INVALIDATE"):
            import traceback as _tb

            print(f"SESSION INVALIDATED ({reason}) BY:",
                  file=__import__("sys").stderr)
            _tb.print_stack(limit=8)
        self._session = None

    # -- device fault tolerance --------------------------------------------
    # Every device-touching path runs under this discipline: the dispatch
    # is guarded (injector seam + real exceptions), the wait is bounded by
    # the watchdog, and the harvested payload passes a finite/in-range
    # check BEFORE its decisions reach assume(). A fault retires the
    # suspect AOT executable, tears the session down, counts toward the
    # ladder (demotion after `threshold` consecutive), and the batch
    # re-drives synchronously with capped backoff; an exhausted batch
    # resolves to RETRY_NODE so the scheduler returns its pods to the
    # queue exactly once.

    def _check_dispatch_fault(self, rung: Optional[int] = None) -> None:
        inj = self.faults
        if inj is not None:
            inj.on_dispatch(rung=self.ladder.rung() if rung is None else rung)

    def _wait_ready(self, ys, timeout: float,
                    span=tracing.NOOP_SPAN) -> bool:
        """Watchdog-bounded device wait: True when every result leaf is
        ready, False when the deadline passes (wedged device). Leaves
        that are ready at the first look cost nothing more. Otherwise a
        device waiter (_DeviceWaiter) blocks in the runtime for them
        and the caller sleeps on its event until the deadline, so it is
        woken when the launch ends and a hung XLA wait pins the waiter,
        never the calling thread — the one failure PR 3's pipeline
        could not survive. How the wait ended is counted
        (scheduler_device_waits_total) and set on `span` as `outcome`:
        ready, woken, timed_out, or polled — the 2 ms poll that serves
        while a fault drill holds the wait wedged or no waiter thread
        can be started."""
        import jax

        from .metrics import device_waits

        deadline = _time.monotonic() + max(0.0, timeout)
        leaves = [
            x for x in jax.tree_util.tree_leaves(ys) if hasattr(x, "is_ready")
        ]
        outcome = None
        if not self._wedged():
            try:
                leaves = [x for x in leaves if not x.is_ready()]
            except Exception:  # noqa: BLE001 — let decode surface it
                leaves = []
            if not leaves:
                outcome = "ready"
            elif _time.monotonic() >= deadline:
                outcome = "timed_out"
            else:
                waiter = self._take_waiter()  # None: no thread, poll
                if waiter is not None:
                    if not waiter.wait(leaves, deadline - _time.monotonic()):
                        # pinned in the runtime, or about to come back
                        # too late: either way not handed out again
                        waiter.stop()
                        outcome = "timed_out"
                    else:
                        self._idle_waiters.append(waiter)
                        # a wedge armed meanwhile holds this wait too
                        if not self._wedged():
                            outcome = "woken"
        if outcome is None:
            outcome = ("polled" if self._poll_ready(leaves, deadline)
                       else "timed_out")
        device_waits.inc(outcome=outcome)
        span.set(outcome=outcome)
        return outcome != "timed_out"

    def _wedged(self) -> bool:
        """A fault drill holds device waits wedged (faults.wedge-wait)."""
        inj = self.faults
        return inj is not None and inj.wedge_active()

    def _take_waiter(self) -> Optional[_DeviceWaiter]:
        """An idle device waiter, or a new one; None when no thread can
        be started (the wait then polls)."""
        idle = self._idle_waiters
        while idle:
            try:
                w = idle.pop()
            except IndexError:  # another waiting thread took the last
                break
            if w.thread.is_alive():
                return w
        try:
            return _DeviceWaiter()
        except Exception:  # noqa: BLE001 — never a fault of the wait
            logger.warning("device waiter did not start", exc_info=True)
            return None

    def _poll_ready(self, leaves, deadline: float) -> bool:
        """The wait without a waiter: ask every 2 ms until the deadline.
        While a wedge is armed the answer is not-ready whatever the
        device says."""
        while True:
            if not self._wedged():
                try:
                    leaves = [x for x in leaves if not x.is_ready()]
                except Exception:  # noqa: BLE001 — let decode surface it
                    return True
                if not leaves:
                    return True
            if _time.monotonic() >= deadline:
                # an injected wedge shot is NOT consumed here: with
                # concurrent waiters (completion worker + a locked
                # flush) the first watchdog would otherwise absorb the
                # shot and the second thread would harvest "cleanly" —
                # the shot ends when the timeout FAULT is recorded
                # (_device_fault_locked), i.e. when recovery begins
                return False
            _time.sleep(0.002)

    def _validate_decisions(self, decisions: List[int], n_names: int,
                            ys=None) -> None:
        """Cheap guard between harvest and assume: every decision must be
        a node index (or -1) against the dispatch-time node table, and
        any float payload must be finite. Garbage from a sick device is
        a fault to recover from, not state to propagate."""
        for d in decisions:
            if not (-1 <= int(d) < n_names):
                raise DeviceFault(
                    f"decision {d} outside [-1, {n_names})", kind="invalid")
        if isinstance(ys, dict):
            for k, val in ys.items():
                if not hasattr(val, "dtype"):
                    continue
                a = np.asarray(val)
                if a.dtype.kind == "f" and not np.isfinite(a).all():
                    raise DeviceFault(
                        f"non-finite device payload in {k!r}", kind="invalid")

    def _device_fault_locked(self, kind: str, buckets=(),
                             attrs: Optional[Dict] = None) -> None:
        """Record one device fault: count it, quarantine the suspect AOT
        buckets (pallas — the quarantine outlives the session teardown
        one line down, _build_session re-applies it to every rebuild),
        tear the session down, and demote the ladder when this fault
        crossed the consecutive threshold. The flight recorder dumps its
        ring BEFORE recovery proceeds: a watchdog timeout or validation
        fault leaves the faulted dispatch's span trail (bucket, rung,
        speculation state) in the log, not just a counter bump."""
        from .metrics import device_faults, dump_seam

        device_faults.inc(kind=kind)
        if kind == "timeout" and self.faults is not None:
            # injected-wedge shot accounting: the watchdog fired and the
            # fault is now recorded — recovery's retry path must see a
            # responsive device again
            self.faults.consume_wedge()
        self._suspect_buckets.update(b for b in buckets if b is not None)
        fault_attrs = dict(attrs or ())
        fault_attrs.update(
            kind=kind, rung=self.ladder.mode(),
            buckets=sorted(b for b in buckets if b is not None),
        )
        tracing.event("device-fault", "fault", **fault_attrs)
        dump_seam(f"device-fault-{kind}", **fault_attrs)
        self._invalidate_session("device-fault")
        if self.ladder.record_fault(kind):
            logger.warning(
                "TPU backend demoted to %s after %d consecutive device "
                "faults (last: %s); background probe will re-promote",
                self.ladder.mode(), self.ladder.threshold, kind,
            )
            dump_seam("ladder-demoted", **fault_attrs)
            self._notify_health(
                "Warning", "BackendDemoted",
                f"scoring backend demoted to {self.ladder.mode()} after "
                f"consecutive device faults (last: {kind})",
            )
            self._ensure_probe_thread()

    def _dispatch_with_retry(self, attempt):
        """THE bounded-retry policy, shared by every synchronous dispatch
        path: capped exponential backoff + full jitter (the Supervisor's
        restart policy at dispatch granularity), one recorded fault per
        failed attempt (so persistent faults walk the ladder down), and
        an immediate stop once the ladder hits oracle (a sick device
        must not be hammered with retry storms the scheduler is already
        routing around). Returns `attempt()`'s value; raises DeviceFault
        when retries exhaust or the backend is fully demoted."""
        from .metrics import dispatch_retries

        delay = self.retry_base
        for n in range(self.retry_cap + 1):
            if self.ladder.rung() <= RUNG_ORACLE:
                break
            if n:
                dispatch_retries.inc()
                tracing.event("dispatch-retry", "fault", attempt=n,
                              rung=self.ladder.mode())
                _time.sleep(
                    min(delay, self.retry_max) * (1 + self.rng.random()))
                delay *= 2
            try:
                out = attempt()
                self.ladder.record_success()
                return out
            except DeviceFault as e:
                logger.warning("device dispatch fault (%s, attempt %d/%d)",
                               e.kind, n + 1, self.retry_cap + 1)
                self._device_fault_locked(e.kind)
            except Exception:  # noqa: BLE001 — any device-path error
                logger.warning("device dispatch fault (attempt %d/%d)",
                               n + 1, self.retry_cap + 1, exc_info=True)
                self._device_fault_locked("raise")
        raise DeviceFault(
            "dispatch retries exhausted (or backend demoted)", kind="raise")

    def _session_schedule_guarded(self, arrays: List[Dict]) -> Optional[List[int]]:
        """_session_schedule under the retry policy. Returns None when
        retries exhaust or the ladder hit oracle — callers turn the
        group into RETRY_NODE results (back to the scheduling queue
        exactly once; the scheduler routes the re-pop through the
        oracle while demoted)."""

        def attempt():
            self._check_dispatch_fault()
            decisions = self._session_schedule(arrays)
            self._validate_decisions(decisions, self.enc.n_lanes)
            return decisions

        try:
            return self._dispatch_with_retry(attempt)
        except DeviceFault:
            return None

    def _recover_dispatches_locked(self, kind: str, first: "_BatchHandle") -> None:
        """Harvest-side fault: `first`'s payload is bad, and every later
        pending batch chained its scan on the same carry — all of it is
        suspect. Abandon the chain, record the fault, then re-decide
        each batch synchronously IN DISPATCH ORDER (schedule_many runs
        the guarded/retrying session path), so sequential-assume
        semantics — and decision parity when the fault was transient —
        survive the recovery. Nothing from the abandoned scans ever
        touched the host encoding: pre-harvest handles carry no state."""
        from .metrics import dispatch_retries

        dropped = [first] + list(self._pending)
        self._pending.clear()
        self._pending_cv.notify_all()
        # every later batch was a speculative dispatch chained on the
        # carry this fault just invalidated — count the misses (the
        # faulting batch itself is the fault, not a miss)
        self._miss_speculative(dropped[1:])
        buckets = {h.bucket for h in dropped if h.bucket is not None}
        self._device_fault_locked(
            kind, buckets=buckets,
            attrs={
                "n_batches": len(dropped), "n_pods": len(first.group),
                "bucket": first.bucket, "speculative": first.speculative,
            },
        )
        for h in dropped:
            h.ys = None
            dispatch_retries.inc()
            with tracing.span("re-drive", "replay", n=len(h.group),
                              speculative=h.speculative, kind=kind):
                h.results = self.schedule_many(h.group)

    def abandon_pending(self) -> int:
        """Drop every not-yet-harvested in-flight dispatch WITHOUT
        re-deciding it (completion-worker crash recovery: the restarted
        worker requeues the pods instead). Abandoned handles resolve to
        RETRY_NODE results, so a completion that still holds one sends
        its pods back to the queue exactly once; the session is torn
        down because its device carry includes the abandoned assumes."""
        with self._lock:
            n = len(self._pending)
            self._miss_speculative(self._pending)
            for h in self._pending:
                h.ys = None
                h.results = [(p, RETRY_NODE) for p in h.group]
            self._pending.clear()
            self._pending_cv.notify_all()
            if n:
                self._invalidate_session("abandon-pending")
            return n

    # -- device-side preemption: what-if context ---------------------------

    def whatif_enabled(self) -> bool:
        """True when the planner's device rung may run: kill switch on
        and the degradation ladder above oracle."""
        return self.whatif and self.ladder.rung() > RUNG_ORACLE

    def whatif_context(self, pod_arrays: Dict):
        """A WhatifContext for this preemptor template against CURRENT
        cluster state. Preference order: the live HoistedSession when it
        knows the template (queued deltas reconciled first, carry
        snapshotted on-device — zero uploads); otherwise a throwaway
        hoisted view over a non-donating encoding snapshot (the pallas /
        sharded sessions keep their carry in kernel-private scaled
        layouts, and the host encoding is their exact mirror after
        harvest). Neither path invalidates the live session or counts a
        session build. Cached per encoding version."""
        from ..ops.whatif import WhatifContext, WhatifUnavailable

        with self._lock:
            if not self.whatif:
                raise WhatifUnavailable("KTPU_WHATIF=0", reason="disabled")
            if self.ladder.rung() <= RUNG_ORACLE:
                raise WhatifUnavailable("backend demoted to oracle",
                                        reason="demoted")
            if self.enc.n_nodes == 0:
                raise WhatifUnavailable("empty cluster", reason="context")
            # settle the array epoch BEFORE keying the cache: volume
            # events flag _rebuild_needed without an object-level
            # version bump, and rebuild() bumps the version itself
            if self.enc._rebuild_needed or self.enc._caps_grew():
                self.enc.rebuild()
            if self._whatif_cache_version != self.enc.version:
                self._whatif_cache.clear()
                self._whatif_cache_version = self.enc.version
            fp = template_fingerprint(pod_arrays)
            sess = self._session
            if isinstance(sess, HoistedSession) and fp in sess._fps:
                ctx = self._whatif_cache.get(("sess",))
                if ctx is not None and ctx._sess is sess:
                    return ctx
                # reconcile queued cluster-event deltas into the live
                # carry first (the normal pre-dispatch apply — the
                # scratch copy must see them); an apply failure falls
                # through to the encoding path
                t0 = _time.perf_counter()
                self._apply_session_deltas_locked()
                sess = self._session
                if isinstance(sess, HoistedSession) and fp in sess._fps:
                    ctx = WhatifContext.from_session(
                        sess, self.enc.node_names)
                    ctx.pod_keys = frozenset(self.enc._pods)
                    self._whatif_cache[("sess",)] = ctx
                    self._record_whatif_context(t0, "session", ctx)
                    return ctx
            ctx = self._whatif_cache.get(("enc", fp))
            if ctx is not None:
                return ctx
            # the throwaway hoisted view costs a device upload + a
            # prologue build — carry a consistent host copy out and do
            # the expensive part WITHOUT the lock (dispatch/harvest
            # contend on it); double-checked insert below
            t0 = _time.perf_counter()
            host = self.enc.host_snapshot()
            node_names = list(self.enc.node_names)
            version = self.enc.version
            pod_keys = frozenset(self.enc._pods)
        ctx = WhatifContext.from_host_snapshot(host, node_names, pod_arrays,
                                               mesh=self.mesh)
        # the pods the view holds: a planner drains only those of its
        # claimed victims, and adds only those of its nominees, that it
        # does not already hold
        ctx.pod_keys = pod_keys
        self._record_whatif_context(t0, "snapshot", ctx)
        with self._lock:
            if (self._whatif_cache_version == version
                    and self.enc.version == version):
                self._whatif_cache[("enc", fp)] = ctx
        return ctx

    @staticmethod
    def _record_whatif_context(t0: float, how: str, ctx) -> None:
        """The `whatif-context` span: one build of a what-if view."""
        tracing.RECORDER.record(
            "whatif-context", "whatif-context", t0,
            _time.perf_counter() - t0, {"how": how, "lanes": ctx.n_lanes})

    def gang_feasible(self, pod: v1.Pod, k: int) -> Optional[bool]:
        """Joint co-placement probe for the gang deadlock breaker: can
        k pods of this pod's template co-place on the current cluster?
        One positive-delta what-if launch on a scratch carry
        (ops/whatif._gang_fits_run) — False is definitive capacity-wise
        ("cannot place even ignoring inter-member constraints"), True
        is optimistic on inter-member couplings. None when the what-if
        path cannot serve (disabled, demoted, template outside the
        envelope, encode failure): the probe is advisory, and the
        caller treats unknown as 'maybe feasible'."""
        try:
            enc_pa = self.pe.encode(pod)
            pa = {n: a for n, a in enc_pa.items() if not n.startswith("_")}
            ctx = self.whatif_context(pa)
            tj = ctx.template_index(pa)
            return ctx.gang_fits(tj, int(k))
        except Exception:  # noqa: BLE001 — advisory probe, never fatal
            return None

    def check_whatif_fault(self) -> None:
        """Injector seam for the what-if launch path (testing/faults.py
        raise-whatif)."""
        inj = self.faults
        if inj is not None:
            inj.on_whatif()

    def record_whatif_fault(self, kind: str) -> None:
        """A what-if launch faulted: count it and walk the PR 4 ladder
        (consecutive faults demote and wake the probe), but DO NOT
        invalidate the live session — the what-if ran on a scratch
        snapshot, so there is nothing to quarantine or rebuild, and
        tearing the session down would charge planning with a rebuild
        storm (the acceptance contract pins session_rebuilds_total
        unchanged by planning)."""
        from .metrics import device_faults

        device_faults.inc(kind=kind)
        tracing.event("whatif-fault", "fault", kind=kind,
                      rung=self.ladder.mode())
        from .metrics import dump_seam

        dump_seam("whatif-fault", kind=kind)
        with self._lock:
            self._whatif_cache.clear()
            self._whatif_cache_version = -1
        if self.ladder.record_fault(kind):
            logger.warning(
                "TPU backend demoted to %s after %d consecutive device "
                "faults (last: what-if %s); background probe will "
                "re-promote", self.ladder.mode(), self.ladder.threshold,
                kind,
            )
            self._notify_health(
                "Warning", "BackendDemoted",
                f"scoring backend demoted to {self.ladder.mode()} after "
                f"consecutive device faults (last: {kind})",
            )
            self._ensure_probe_thread()

    # -- ladder probe: background re-promotion -----------------------------

    def _ensure_probe_thread(self) -> None:
        with self._probe_lock:
            t = self._probe_thread
            if t is not None and t.is_alive():
                return
            t = threading.Thread(
                target=self._probe_loop, name="tpu-ladder-probe", daemon=True)
            self._probe_thread = t
            t.start()

    def _probe_loop(self) -> None:
        """While demoted, periodically run a canary dispatch vouching for
        the NEXT rung up; a correct answer promotes one rung (cadence
        resets), a wrong/absent one doubles the cadence (capped) so a
        flapping device cannot whipsaw the session cache. Exits once
        fully re-promoted; a later demotion starts a fresh thread."""
        while not self._probe_stop.is_set():
            if self.ladder.rung() >= self.ladder.top:
                return
            if self._probe_stop.wait(self.ladder.probe_delay()):
                return
            ok = self._probe_device()
            if self.ladder.on_probe(ok):
                logger.warning(
                    "TPU backend re-promoted to %s after a clean probe",
                    self.ladder.mode(),
                )
                self._notify_health(
                    "Normal", "BackendPromoted",
                    f"scoring backend re-promoted to {self.ladder.mode()} "
                    f"after a clean probe",
                )
                with self._lock:
                    # the next batch must rebuild at the restored rung
                    self._invalidate_session("probe-promoted")

    def _probe_device(self) -> bool:
        """One canary with a known answer through the same fault seam as
        real dispatches (rung = the rung being vouched for)."""
        try:
            target = min(self.ladder.rung() + 1, self.ladder.top)
            inj = self.faults
            if inj is not None:
                inj.on_dispatch(rung=target, probe=True)
            import jax.numpy as jnp

            y = (jnp.arange(64, dtype=jnp.int32) * 2).sum()
            if not self._wait_ready(y, self.watchdog_timeout):
                # the probe's wait IS a device wait that hit the
                # watchdog: consume an armed wedge shot here too — at
                # the oracle rung no dispatch traffic exists to consume
                # it, and an unconsumed shot would wedge every future
                # probe (permanently demoted backend)
                if inj is not None:
                    inj.consume_wedge()
                return False
            # ktpu: allow-sync(ladder probe: the 1-element sentinel readback IS the probe)
            return int(np.asarray(y)) == 64 * 63
        except Exception:  # noqa: BLE001 — a raising probe is a failed probe
            return False

    def close(self) -> None:
        """Stop the background probe and the bucket-warm thread
        (Scheduler.shutdown). The warm thread stops after the bucket it
        is compiling — a Mosaic compile cannot be interrupted, and a
        daemon thread must not still be inside one when the interpreter
        tears down — so its join is bounded by one compile, not by a
        timeout."""
        self._probe_stop.set()
        t = self._probe_thread
        if t is not None:
            t.join(timeout=2)
        for wt in self._stop_warm_threads():
            wt.join()
        for w in _stop_waiters(self._idle_waiters):
            w.thread.join(timeout=2)

    def wait_warm(self) -> None:
        """Block until the live session's bucket-warm thread has built
        (or loaded) every executable — the harness calls this before a
        measured window opens."""
        for _, wt in list(self._warm):
            wt.join()

    def _stop_warm_threads(self) -> List[threading.Thread]:
        """Tell every live bucket-warm thread to stop after its current
        compile (a rebuilt session supersedes the one they warm);
        returns the threads still running."""
        self._warm = [(s, t) for s, t in self._warm if t.is_alive()]
        for sess, _ in self._warm:
            sess.stop_warm()
        return [t for _, t in self._warm]

    # -- CacheListener (called under the cache lock) -----------------------
    # Classification contract (the session-delta design): every event is
    # one of
    #   carry-delta     — a batchable pod (no affinity terms, no host
    #                     ports) added to / removed from a KNOWN node,
    #                     whose row fits the encoding incrementally and
    #                     whose labels match no session template's IPA
    #                     term: exactly (a) a utilization row and (b) PTS
    #                     pair counts move — both ARE the session carry
    #                     (the PERF_NOTES exactness invariant), so the
    #                     event queues as a device-side patch;
    #   prologue-patch  — a node update whose fingerprint moved ONLY in
    #                     allocatable/capacity: alloc is read in-step,
    #                     never by the prologue, so the static column
    #                     patches in place;
    #   structural      — everything else (node add/remove, term/port
    #                     pods, vocab or capacity growth, volume-world
    #                     changes): the old path — session teardown, full
    #                     rebuild at the next dispatch.

    def reserve_nominated(self, pod: v1.Pod, node_name: str) -> None:
        """A preemptor nominated on `node_name`: held in the encoding as
        if placed there until it binds, so that no launch hands the room
        its victims leave to another pod (the filter with nominated pods,
        framework.go:610). The cache and the host planners keep it as a
        nomination; its own bind or release_nominated ends the hold."""
        with self._lock:
            key = v1.pod_key(pod)
            held = self._reserved.get(key)
            if held == node_name:
                return
            if held is not None:
                self._unreserve_locked(pod, self._reserved.pop(key))
            if key in self.enc._pods:
                return  # placed already
            self._reserved[key] = node_name
            if not self._queue_pod_delta(
                pod, node_name, +1,
                lambda: self.enc.add_pod(pod, node_name),
            ):
                self._invalidate_session("foreign-pod-add")

    def release_nominated(self, pod: v1.Pod) -> None:
        """The nomination ended without a bind (cleared, the pod deleted,
        outranked): the held room goes back."""
        with self._lock:
            node = self._reserved.pop(v1.pod_key(pod), None)
            if node is not None:
                self._unreserve_locked(pod, node)

    def _unreserve_locked(self, pod: v1.Pod, node_name: str) -> None:
        if not self._queue_pod_delta(
            pod, node_name, -1, lambda: self.enc.remove_pod(pod),
        ):
            self._invalidate_session("pod-remove")

    def on_add_pod(self, pod: v1.Pod, node_name: str) -> None:
        with self._lock:
            key = (pod.metadata.namespace, pod.metadata.name, node_name)
            held = self._reserved.pop(v1.pod_key(pod), None)
            if held == node_name and key not in self._session_assumed:
                # a nominated preemptor bound where it was held: the
                # encoding and the session already count it
                self.enc.swap_pod_object(v1.pod_key(pod), pod, node_name)
                return
            if held is not None:
                self._unreserve_locked(pod, held)
            if key in self._session_assumed:
                # the cache confirming an assume the session already
                # applied on-device: host bookkeeping only
                self._session_assumed.discard(key)
                self.enc.add_pod(pod, node_name)
                return
            if v1.pod_key(pod) in self.enc._pods:
                # duplicate add (re-add of a key the encoding already
                # holds nets a remove+add inside enc.add_pod — the old
                # row's counts are not reconstructible here)
                self._invalidate_session("foreign-pod-add")
                self.enc.add_pod(pod, node_name)
                return
            if not self._queue_pod_delta(
                pod, node_name, +1,
                lambda: self.enc.add_pod(pod, node_name),
            ):
                self._invalidate_session("foreign-pod-add")

    def on_assume_pods(self, items) -> None:
        """Batched assume-echo from the cache's columnar assume_pods: one
        listener call per harvest instead of N on_add_pod events. For
        placements this backend itself applied on-device
        (_apply_decisions_locked recorded them in _session_assumed), the
        echo's remove+re-add through enc.add_pod would be array-identical
        — the only object difference vs the decision-time pod is
        spec.node_name, which is not encoded — so the echo collapses to a
        pure stored-object swap (enc.swap_pod_object): no row encode, no
        volume refcount round-trip, no Quantity re-parse. Anything else
        (nominated placements, swap misses) falls through to the per-pod
        on_add_pod path, preserving object-path semantics exactly."""
        leftovers = None
        with self._lock:
            assumed = self._session_assumed
            enc = self.enc
            swap = enc.swap_pod_object
            for pod, node_name in items:
                key = (pod.metadata.namespace, pod.metadata.name, node_name)
                if key in assumed and swap(v1.pod_key(pod), pod, node_name):
                    assumed.discard(key)
                    continue
                if leftovers is None:
                    leftovers = []
                leftovers.append((pod, node_name))
            if leftovers:
                for pod, node_name in leftovers:
                    self.on_add_pod(pod, node_name)  # RLock: nested is fine

    def on_forget_pods(self, items) -> None:
        """Batched forget-echo (gang rollback): every member's removal
        lands under ONE backend lock acquisition, so the whole gang's
        release queues as one contiguous carry-delta batch the session
        absorbs together — the retraction dual of on_assume_pods."""
        with self._lock:
            for pod, node_name in items:
                self.on_remove_pod(pod, node_name)  # RLock: nested is fine

    def on_remove_pod(self, pod: v1.Pod, node_name: str) -> None:
        with self._lock:
            # mirror of the add path's assume-echo gate: removing a pod
            # the encoding never contained (never encoded, or bound to
            # no node) is a no-op, not a session teardown
            if not node_name or v1.pod_key(pod) not in self.enc._pods:
                return
            self._session_assumed.discard(
                (pod.metadata.namespace, pod.metadata.name, node_name)
            )
            if not self._queue_pod_delta(
                pod, node_name, -1, lambda: self.enc.remove_pod(pod),
            ):
                self._invalidate_session("pod-remove")

    def on_add_node(self, node: v1.Node) -> None:
        with self._lock:
            self._node_fps[node.metadata.name] = ClusterEncoding.node_fingerprint(node)
            lane = self.enc.add_node(node)
            from .metrics import node_joins

            node_joins.inc(path=self.enc.last_join_path)
            if not self._queue_node_delta(lane, "node-join"):
                self._invalidate_session("node-add")

    def on_update_node(self, node: v1.Node) -> None:
        with self._lock:
            # heartbeat gate: kubelets PATCH node status every ~10s
            # (conditions + heartbeat timestamps), none of which the
            # encoding consumes — tearing down the session (and forcing
            # a full encoding rebuild) per heartbeat would make the
            # cross-batch session useless in a live cluster. Only
            # scheduling-relevant changes (labels, annotations, taints,
            # unschedulable, allocatable/capacity, images) invalidate.
            name = node.metadata.name
            fp = ClusterEncoding.node_fingerprint(node)
            old = self._node_fps.get(name)
            if old == fp:
                return
            self._node_fps[name] = fp
            if self._queue_alloc_patch(node, old, fp):
                return
            self._invalidate_session("node-update")
            self.enc.update_node(node)

    def _queue_alloc_patch(self, node: v1.Node, old, fp) -> bool:
        """Prologue-patch classification for a node update: when ONLY the
        allocatable/capacity slot of the fingerprint moved, the encoding
        updates the row in place and the live session patches its static
        alloc column — no other prologue product reads alloc (fit and
        the utilization scores consume it in-step), so nothing else
        needs recomputing. False -> caller takes the structural path."""
        sess = self._session
        if (
            not self.delta_patching
            or sess is None
            or old is None
            or len(self._deltas) >= self.max_queued_deltas
            or self.enc._rebuild_needed
            # fingerprint slots: labels, avoid-annotation, taints,
            # unschedulable, alloc, images — everything but alloc equal
            or old[:4] != fp[:4]
            or old[5] != fp[5]
        ):
            return False
        got = self.enc.update_node_alloc(node)
        if got is None:
            return False
        dalloc, dallowed = got
        if not sess.delta_compatible(dalloc, np.zeros(2, np.int64)):
            # the row is already patched in the host encoding (dirty-row
            # sync covers the next build); only the session must go
            self._invalidate_session("node-update")
            return True
        nidx = self.enc.node_index[node.metadata.name]
        self._deltas.append({
            "kind": "node-alloc", "node": nidx,
            "dalloc": dalloc, "dallowed": dallowed,
        })
        return True

    def on_remove_node(self, node_name: str) -> None:
        with self._lock:
            self._node_fps.pop(node_name, None)
            lane = self.enc.remove_node(node_name)
            from .metrics import node_leaves

            node_leaves.inc(path=self.enc.last_leave_path)
            if not self._queue_node_delta(lane, "node-leave"):
                self._invalidate_session("node-remove")

    def _queue_node_delta(self, lane: Optional[int], kind: str) -> bool:
        """Absorb a node add/remove into the LIVE session as a lane-column
        delta. The encoding has already decided the host half: `lane` is
        None when the event was structural there (vocab bucket growth,
        lane space exhausted, node still carrying pods). The session half
        gates itself (node_join_delta / node_leave_delta return None
        outside their exactness envelope — shared topology pairs, term
        templates, image-locality mass). True -> the event is fully
        reconciled; False -> the caller tears the session down (rebuild
        from the already-mutated encoding is always correct)."""
        if lane is None or not self.delta_patching:
            return False
        sess = self._session
        if sess is None:
            return True  # nothing device-resident; next build sees it
        if (
            not hasattr(sess, "node_join_delta")
            or len(self._deltas) >= self.max_queued_deltas
        ):
            return False
        try:
            if kind == "node-join":
                d = sess.node_join_delta(
                    self.enc.node_slice_cluster(lane), lane)
            else:
                d = sess.node_leave_delta(lane)
        except Exception:  # noqa: BLE001 — rebuild is always correct
            logger.warning("node delta classification failed; rebuilding",
                           exc_info=True)
            return False
        if d is None:
            return False
        self._deltas.append(d)
        return True

    # -- session-delta classification + apply ------------------------------

    def _pod_self_rows(self, pod: v1.Pod) -> Dict:
        """The pod's label/namespace bit rows at current vocab widths —
        what match_matrices_np and the term-match classifier evaluate.
        Built with get() (never intern): a label pair the vocab has
        never seen cannot appear in any compiled selector, so the zero
        sentinel is exact."""
        enc = self.enc
        pp = np.zeros(enc.pod_pair_vocab.capacity, bool)
        pk = np.zeros(enc.pod_key_vocab.capacity, bool)
        for k, val in (pod.metadata.labels or {}).items():
            kid = enc.pod_key_vocab.get(k)
            pid = enc.pod_pair_vocab.get((k, val))
            if kid:
                pk[kid] = True
            if pid:
                pp[pid] = True
        return {
            "self_ppair": pp, "self_pkey": pk,
            "self_ns": np.int32(enc.ns_vocab.get(pod.metadata.namespace)),
        }

    @staticmethod
    def _pod_structural(pod: v1.Pod) -> bool:
        """Pods whose assume/remove touches term/port tables (the exact
        complement of ops/batch.py pod_batchable, from the spec)."""
        from .framework.types import PodInfo

        pi = PodInfo(pod)
        if (
            pi.required_affinity_terms
            or pi.required_anti_affinity_terms
            or pi.preferred_affinity_terms
            or pi.preferred_anti_affinity_terms
        ):
            return True
        return any(
            port.host_port > 0
            for c in pod.spec.containers
            for port in c.ports or []
        )

    def _queue_pod_delta(self, pod: v1.Pod, node_name: str, sign: int,
                         mutate) -> bool:
        """Run `mutate` (the host-encoding update) and try to absorb the
        event into the live session as a carry delta. True -> the event
        is fully reconciled (delta queued, or no live session to
        reconcile); False -> structural, the caller tears the session
        down. The utilization delta is captured as the host ROW diff
        around the mutation, so volume attach-scalar extras and every
        other row-math subtlety transfer exactly."""
        sess = self._session
        enc = self.enc
        nidx = None
        snap = None
        if (
            self.delta_patching
            and sess is not None
            and len(self._deltas) < self.max_queued_deltas
            and not enc._rebuild_needed
            # a remove must hit the row the encoding actually holds: a
            # relocated pod (informer-wins path) removes from its STORED
            # node, which is the node_name the cache passes — verify
            and (sign > 0
                 or enc._pods.get(v1.pod_key(pod), (None, node_name))[1]
                 == node_name)
        ):
            nidx = enc.node_index.get(node_name)
            if nidx is not None:
                A = enc._arrays
                snap = (
                    A["requested"][nidx].copy(),
                    A["nz_requested"][nidx].copy(),
                    int(A["pod_count"][nidx]),
                )
        mutate()
        if sess is None:
            # nothing device-resident to reconcile; the next session
            # builds from the mutated encoding
            return True
        if snap is None or enc._rebuild_needed:
            return False  # structural: unknown node or capacity growth
        if self._pod_structural(pod):
            return False
        rows = self._pod_self_rows(pod)
        if getattr(sess, "dyn_ipa", False) and ipa_term_match_np(
                sess._term_np, rows):
            # the pod counts toward a template's own-term statics
            # (anti/aff counts, D5 score rows) — not carry-only
            return False
        A = enc._arrays
        dres = A["requested"][nidx] - snap[0]
        dnz = A["nz_requested"][nidx] - snap[1]
        dcount = int(A["pod_count"][nidx]) - snap[2]
        if not sess.delta_compatible(dres, dnz):
            return False  # pallas int32/GCD envelope
        t_n = sess._tp_np["self_ns"].shape[0]
        c_n = sess._tp_np["ptsf_op"].shape[1]
        if pod.metadata.deletion_timestamp is not None:
            # terminating pods never enter the prologue's PTS counts
            # (the ~pterm gate); only utilization moves
            mf = np.zeros((t_n, c_n), np.int32)
            ms = np.zeros((t_n, c_n), np.int32)
        else:
            mfa, msa = match_matrices_np(sess._tp_np, [rows])
            mf = mfa[:, 0, :].astype(np.int32) * sign
            ms = msa[:, 0, :].astype(np.int32) * sign
        self._deltas.append({
            "kind": "pod-add" if sign > 0 else "pod-remove",
            "node": nidx, "dres": dres, "dnz": dnz, "dcount": dcount,
            "mf": mf, "ms": ms,
        })
        return True

    def _apply_session_deltas_locked(self,
                                     batch: Optional[int] = None) -> None:
        """Flush the queued deltas into the live session in one fused
        launch — called right before a dispatch rides the session, so
        patches chain onto any in-flight scans as pure data
        dependencies. An apply failure downgrades to the structural
        path (teardown + rebuild from the already-mutated encoding) —
        never to wrong state."""
        if not self._deltas:
            return
        if self._session is None:
            self._deltas.clear()
            return
        deltas, self._deltas = self._deltas, []
        from .metrics import session_delta_applies

        try:
            with tracing.span("queued-delta-apply", "delta-apply",
                              n=len(deltas), batch=batch) as sp:
                if devtime.enabled():
                    # measured delta apply: the fused patch launch gets
                    # its own submit->ready interval via an explicit
                    # block (decision-inert; the block is the
                    # documented KTPU_DEVTIME=1 measurement cost — the
                    # next dispatch would synchronize on the carry
                    # anyway)
                    import jax

                    lt = devtime.launch("kernel", "delta-apply",
                                        n=len(deltas))
                    self._session.apply_deltas(deltas)
                    # ktpu: allow-sync(devtime fence: delta-apply is timed in-window; the fence is the measurement)
                    jax.block_until_ready(
                        getattr(self._session, "_carry", None))
                    lt.done()
                    self._feed_device_time(
                        "kernel", _time.perf_counter() - lt.submit)
                else:
                    self._session.apply_deltas(deltas)
                # what the launch was shaped by: the carry entries the
                # deltas came to and the bucket they were padded to (one
                # compiled program a bucket)
                entries, bucket = getattr(
                    self._session, "last_delta_shape", (None, None))
                sp.set(entries=entries, bucket=bucket)
        except Exception:  # noqa: BLE001 — rebuild is always correct
            logger.warning(
                "session delta apply failed; falling back to a rebuild",
                exc_info=True,
            )
            self._invalidate_session("delta-apply-failed")
            return
        for d in deltas:
            session_delta_applies.inc(kind=d["kind"])

    # -- scheduling --------------------------------------------------------

    def schedule(self, pod: v1.Pod) -> ScheduleResult:
        """One pod against every node; raises FitError when none fit
        (generic_scheduler.go:95 Schedule semantics)."""
        with self._lock:
            # an outstanding pipelined batch must land in the encoding
            # first (its decisions are part of the ground truth this
            # dispatch evaluates against)
            self._flush_pending()
            # device_state() with dirty rows DONATES the previous device
            # buffers (encoding.py fused scatter) — exactly the statics a
            # live session still references. Tear the session down first;
            # this also covers schedule_many's bound-pod path and the
            # scheduler core's unschedulable re-dispatch (scheduler.py
            # _schedule_batch_tpu), whose enc.add_pod()s would otherwise
            # leave a surviving session's carry missing those pods.
            self._invalidate_session("single-pod-dispatch")
            try:
                p = {k: v for k, v in self.pe.encode(pod).items()
                     if not k.startswith("_")}
            except VolumeResolutionChanged:
                # gate/encode race: fail this attempt; the retry re-gates
                raise FitError(pod, self.enc.n_nodes, {})
            def attempt(p=p):
                self._check_dispatch_fault()
                c = self.enc.device_state()
                if self.mesh is not None:
                    from ..parallel import sharded

                    c = sharded.shard_cluster(c, self.mesh)
                    p = sharded.replicate_pod(p, self.mesh)
                out = schedule_pod_jit(c, p, self.weights)
                if not self._wait_ready(out, self.watchdog_timeout):
                    raise DeviceFault(
                        "single-pod dispatch exceeded the watchdog",
                        kind="timeout")
                total = np.asarray(out["total"])
                feasible = np.asarray(out["feasible"])
                if total.dtype.kind == "f" and not np.isfinite(total).all():
                    raise DeviceFault("non-finite scores", kind="invalid")
                return out, total, feasible

            # raises DeviceFault when retries exhaust or the ladder sits
            # at oracle (callers requeue; the scheduler routes the
            # re-pop through the oracle path)
            out, total, feasible = self._dispatch_with_retry(attempt)
            n_nodes = self.enc.n_nodes
            n_feasible = int(feasible.sum())
            if n_feasible == 0:
                # statuses walk the LANE space (kernel outputs are
                # lane-indexed); the FitError count stays the live count
                raise FitError(
                    pod, n_nodes, self._statuses(out, self.enc.n_lanes))
            best = self._select_host(total, feasible)
            return ScheduleResult(self.enc.node_names[best], n_nodes, n_feasible)

    def reevaluate(self, pods: List[v1.Pod]) -> List[Tuple[Optional[str], Dict]]:
        """Batched re-evaluation of FAILED pods against current state:
        per pod, (best node | None, per-node failure statuses). One
        vmapped kernel dispatch per shape group instead of a per-pod
        schedule() (each of which was a session teardown + a full
        launch — the preemption-workload crawl).
        Statuses feed the DefaultPreemption dry-run
        (default_preemption.go:320); a pod that now fits (state moved
        since its batch was dispatched) gets its node directly."""
        from ..ops.kernel import schedule_pods_jit

        results: List[Tuple[Optional[str], Dict]] = []
        with self._lock:
            self._flush_pending()
            if self.ladder.rung() <= RUNG_ORACLE:
                # fully demoted: no device dispatch at all — the pods
                # re-gate via the queue and ride the oracle there
                return [(RETRY_NODE, {}) for _ in pods]
            # device_state() with dirty rows donates buffers a live
            # session still references — same discipline as schedule()
            self._invalidate_session("reevaluate")
            c = self.enc.device_state()
            if self.mesh is not None:
                from ..parallel import sharded

                c = sharded.shard_cluster(c, self.mesh)
            n_nodes = self.enc.n_lanes  # kernel outputs are lane-indexed
            encoded = []
            skipped = set()
            for idx, p in enumerate(pods):
                try:
                    encoded.append({
                        k: v for k, v in self.pe.encode(p).items()
                        if not k.startswith("_")
                    })
                except VolumeResolutionChanged:
                    encoded.append(None)
                    skipped.add(idx)
            # group by shape signature so each group stacks; chunk to a
            # FIXED width — the kernel's per-pod PTS/IPA sweeps are
            # [P]-sized, so an unbounded vmap width makes XLA chew on a
            # [B, P, ...] program (a 500-wide vmap at 500 nodes compiled
            # for minutes); 32-wide chunks bound the program and reuse
            # one compile across waves (rows are padded by repeating row
            # 0 — outputs for pads are discarded)
            CHUNK = 32
            out_rows: List[Tuple[Dict, int]] = [None] * len(pods)
            # group by shape via a sort (results are written back by
            # original index, so order is free): interleaved shapes must
            # not produce one padded chunk per 1-2 pods
            by_shape: Dict[Tuple, List[int]] = {}
            for idx, e in enumerate(encoded):
                if idx in skipped:
                    continue
                by_shape.setdefault(shape_signature(e), []).append(idx)
            for group in by_shape.values():
                for lo in range(0, len(group), CHUNK):
                    chunk = group[lo:lo + CHUNK]
                    pad = CHUNK - len(chunk)
                    stacked = {
                        k: np.stack(
                            [np.asarray(encoded[g][k]) for g in chunk]
                            + [np.asarray(encoded[chunk[0]][k])] * pad
                        )
                        for k in encoded[chunk[0]]
                    }
                    if self.mesh is not None:
                        from ..parallel import sharded

                        stacked = sharded.replicate_pod(stacked, self.mesh)
                    try:
                        if self.ladder.rung() <= RUNG_ORACLE:
                            continue  # demoted mid-loop: rest re-gates
                        self._check_dispatch_fault()
                        outs = schedule_pods_jit(c, stacked, self.weights)
                        if not self._wait_ready(outs, self.watchdog_timeout):
                            raise DeviceFault(
                                "re-evaluation dispatch exceeded the "
                                "watchdog", kind="timeout")
                        outs = {k: np.asarray(v) for k, v in outs.items()}
                    except DeviceFault as e:
                        # chunk pods re-gate via the queue; the retry
                        # lands after the session-rebuild/demotion the
                        # fault just triggered
                        self._device_fault_locked(e.kind)
                        continue
                    except Exception:  # noqa: BLE001 — device-path error
                        self._device_fault_locked("raise")
                        continue
                    for row, g in enumerate(chunk):
                        out_rows[g] = (outs, row)
            for g, pod in enumerate(pods):
                if g in skipped:
                    results.append((RETRY_NODE, {}))  # prompt re-gate
                    continue
                if out_rows[g] is None:
                    results.append((RETRY_NODE, {}))  # faulted chunk
                    continue
                outs, row = out_rows[g]
                feasible = outs["feasible"][row][:n_nodes]
                if feasible.any():
                    total = outs["total"][row][:n_nodes]
                    best = self._select_host(total, feasible)
                    results.append((self.enc.node_names[best], {}))
                else:
                    results.append(
                        (None, self._statuses(outs, n_nodes, row=row))
                    )
        return results

    # -- pipelined batch API -----------------------------------------------
    # The session dispatch is ASYNC (HoistedSession.schedule returns device
    # arrays without blocking; batch k+1's scan chains on k's carry as a
    # pure data dependency). dispatch_many/harvest expose that to the
    # scheduler loop's three-stage pipeline (scheduler.py): the scheduler
    # thread encodes + dispatches batch k+1, the device scans batch k
    # (double-buffered — up to max_pending enqueued scans), and the
    # completion worker harvests + assumes + binds batch k-1. Exactness
    # rides the PERF_NOTES invariant: batchable assumes touch only the
    # carry (utilization + PTS pair counts), so the prologue stays valid
    # and no host pod-table sync is needed between pipelined batches.

    def dispatch_many(self, pods: List[v1.Pod],
                      batch: Optional[int] = None) -> "_BatchHandle":
        """Dispatch a batch; returns a handle for harvest(). Up to
        `max_pending` batches may be outstanding (the device double
        buffer) — a dispatch beyond that harvests the OLDEST first.
        Falls back to the synchronous path (ready handle) when the batch
        can't ride the live session (bound pods, mixed shapes, specs the
        session can neither hold nor admit, or no session yet — the
        session builds on the synchronous path and subsequent batches
        pipeline)."""
        h = _BatchHandle(list(pods), batch)
        with self._lock:
            while len(self._pending) >= max(1, self.max_pending):
                if self.async_harvest_drain:
                    # back-pressure WITHOUT charging harvest+assume+
                    # decode to the dispatch critical path: the
                    # completion worker drains the FIFO and signals;
                    # the timeout re-checks liveness (a crashed worker
                    # is restarted by the Scheduler's supervision, and
                    # abandon_pending also signals)
                    self._pending_cv.wait(0.2)
                    continue
                self._harvest_locked()
            if pods and not self.speculation:
                # KTPU_SPECULATION=0: never chain a scan on a carry
                # whose decisions have not been harvested + validated —
                # land everything first (serializes the device)
                self._flush_pending()
            if pods and self._session is not None \
                    and self.ladder.rung() > RUNG_ORACLE and all(
                not p.spec.node_name for p in pods
            ):
                try:
                    with tracing.span("encode", "encode", n=len(pods),
                                      batch=batch):
                        clean = [
                            {k: v for k, v in self.pe.encode(p).items()
                             if not k.startswith("_")}
                            for p in pods
                        ]
                except VolumeResolutionChanged:
                    clean = None  # schedule_many handles it per pod
                if clean is None:
                    h.results = self.schedule_many(pods)
                    return h
                sig0 = shape_signature(clean[0])
                rides = all(shape_signature(a) == sig0 for a in clean[1:])
                if rides:
                    uniq: Dict = {}
                    for a in clean:
                        uniq.setdefault(template_fingerprint(a), a)
                    # specs this session has not met: a table takes
                    # them in and the batch stays on the pipeline; what
                    # cannot is torn down here and rebuilt on the
                    # synchronous path below
                    if any(fp not in getattr(self._session, "_fps", uniq)
                           for fp in uniq):
                        self._remember_templates(uniq)
                    self._admit_templates_locked(uniq)
                    rides = self._session is not None
                if rides:
                    try:
                        # queued cluster-event deltas land first (one
                        # fused launch chained on the carry) so this
                        # scan evaluates the reconciled state
                        self._apply_session_deltas_locked(batch)
                        if self._session is None:
                            # delta apply failed: structural fallback
                            h.results = self.schedule_many(pods)
                            return h
                        self._check_dispatch_fault()
                        # span attrs (incl. the ladder-lock rung read)
                        # are only evaluated when tracing is on: the
                        # disabled dispatch path stays one predicate
                        # check per instrumentation point
                        sp = tracing.span(
                            "dispatch", "dispatch", n=len(pods),
                            rung=self.ladder.rung(),
                            speculative=bool(self._pending),
                            pipelined=True,
                            group_pos=len(self._pending),
                            batch=batch,
                        ) if tracing.enabled() else tracing.NOOP_SPAN
                        with sp, devtime.TIMELINE.maybe_profile(
                                "dispatch"):
                            ys = self._session.schedule(clean)  # async
                            # the decisions' copy to the host starts
                            # behind the launch: the harvest is woken
                            # for bytes that are already there
                            rows = ys.get("rows") \
                                if isinstance(ys, dict) else None
                            if hasattr(rows, "copy_to_host_async"):
                                rows.copy_to_host_async()
                            if isinstance(ys, dict) and "templates" in ys:
                                sp.set(templates=ys["templates"],
                                       term_pods=ys["term_pods"],
                                       rows=ys["count_rows"],
                                       score_pods=ys["score_pods"],
                                       score_rows=ys["score_rows"])
                        if devtime.enabled():
                            # submit stamps at the enqueue; harvest
                            # stamps ready after the pipeline's own
                            # wait — no extra synchronization on the
                            # dispatch path
                            h.dt = devtime.launch(
                                "kernel", "dispatch",
                                h2d_bytes=devtime.payload_bytes(clean),
                                n=len(pods),
                            )
                    except Exception:  # noqa: BLE001 — dispatch-time fault:
                        # the enqueue failed BEFORE the scan chained onto
                        # the carry, so earlier pending batches stay
                        # valid; this batch re-drives synchronously
                        # through the guarded (retrying) path
                        self._device_fault_locked("raise")
                        h.results = self.schedule_many(pods)
                        return h
                    h.ys = ys
                    if isinstance(ys, dict):
                        h.bucket = ys.get("bucket")
                    h.decide = type(self._session).decisions
                    h.node_names = list(self.enc.node_names)
                    h.deadline = _time.monotonic() + self.watchdog_timeout
                    # chained on a not-yet-harvested carry: speculative
                    h.speculative = bool(self._pending)
                    if tracing.RECORDER.pod_level():
                        h.prov = {
                            "rung": self.ladder.mode(),
                            "session": type(self._session).__name__,
                            "build_reason": self._last_build,
                            "bucket": h.bucket,
                            "speculative": h.speculative,
                        }
                    self._pending.append(h)
                    return h
            h.results = self.schedule_many(pods)  # re-entrant: RLock
        return h

    def harvest(self, handle: "_BatchHandle") -> List[Tuple[v1.Pod, Optional[str]]]:
        ys = handle.ys
        if ys is not None and handle.results is None:
            # wait for the device OUTSIDE the backend lock: the
            # completion worker parking here must not block the
            # scheduler thread's next dispatch (the whole point of the
            # pipeline). The ys arrays are plain outputs — only the
            # carry is donated — so waiting on them unlocked is safe.
            # The wait is watchdog-bounded: a wedged device marks the
            # handle timed out and the locked harvest runs recovery.
            with tracing.span("wait", "wait", n=len(handle.group),
                              bucket=handle.bucket,
                              speculative=handle.speculative,
                              batch=handle.batch) as sp:
                if self._wait_ready(ys, self.watchdog_timeout, sp):
                    handle.waited = True
                else:
                    handle.timed_out = True
                    sp.set(timed_out=True)
        with self._lock:
            # strictly FIFO: older batches' decisions are ground truth
            # for this one — land them first
            while handle.results is None and self._pending:
                self._harvest_locked()
        assert handle.results is not None, "harvest of an abandoned handle"
        return handle.results

    def _flush_pending(self) -> None:
        """Apply every outstanding batch's assumes to the host encoding.
        MUST run (under the lock) before anything treats the encoding as
        ground truth — session rebuilds and the one-pod schedule() path —
        or the rebuilt carry would miss those pods."""
        while self._pending:
            self._harvest_locked()

    def _apply_decisions_locked(
        self, pods: List[v1.Pod], decisions: List[int],
        node_names: List[str], prov: Optional[Dict] = None,
        explain: Optional[List[Dict]] = None,
    ) -> List[Tuple[v1.Pod, Optional[str]]]:
        """Land a batch's harvested decisions in the host encoding (the
        host half of the assume; the device carry already holds them).
        `prov` carries the dispatch-time provenance for KTPU_TRACE=2
        per-pod records (rung, session kind, build reason, bucket,
        speculation) — None below level 2 keeps this loop allocation-free.
        `explain` (index-aligned with pods) adds the top-k candidate
        attribution to each pod's provenance record."""
        results: List[Tuple[v1.Pod, Optional[str]]] = []
        rec = tracing.RECORDER
        pod_level = rec.pod_level()
        live = self._session is not None
        record_assume = self._session_assumed.add
        enc_add = self.enc.add_pod
        append = results.append
        reserved = self._reserved
        for i, (g, best) in enumerate(zip(pods, decisions)):
            if best < 0:
                append((g, None))
                node = None
            else:
                node = node_names[best]
                if reserved and v1.pod_key(g) in reserved:
                    # a held preemptor the launch placed: the hold goes
                    # (the carry holds the launch's assume)
                    self._unreserve_locked(g, reserved.pop(v1.pod_key(g)))
                if live:
                    record_assume(
                        (g.metadata.namespace, g.metadata.name, node)
                    )
                enc_add(g, node)
                append((g, node))
            if pod_level:
                if explain is not None and i < len(explain):
                    rec.provenance(
                        v1.pod_key(g), node=node,
                        explain_topk=_explain_topk(explain[i], node_names),
                        **(prov or {}),
                    )
                else:
                    rec.provenance(
                        v1.pod_key(g), node=node, **(prov or {}),
                    )
        return results

    def _miss_speculative(self, handles) -> None:
        """Speculation-miss accounting for handles whose chained-on
        carry was invalidated before they could harvest."""
        from .metrics import speculative_dispatches

        n = sum(1 for h in handles if h.speculative)
        if n:
            speculative_dispatches.inc(n, outcome="miss")
            tracing.event("speculation-miss", "fault", n=n)
            for _ in range(n):
                # constant message: repeats AGGREGATE on the recorder
                # side (count bumps), so a miss storm is one event with
                # a large count, not an event flood
                self._notify_health(
                    "Warning", "SpeculationMissRedrive",
                    "speculative dispatch re-driven: the carry it "
                    "chained on was invalidated",
                )

    def _close_launch_devtime(self, h, ys) -> None:
        """Commit a dispatched batch's device-timeline record: ready is
        stamped when the pipeline's own watchdog-bounded wait returned
        (no extra synchronization — the pipeline already paid it), D2H
        bytes are the harvest outputs' array sizes (readable without
        forcing a transfer). Faulted batches never commit: their launch
        never became ready, and the fault seam dumps the timeline
        instead."""
        lt = h.dt
        if lt is None:
            return
        h.dt = None
        if not devtime.enabled():
            return  # shed mid-flight: drop, don't record a torn window
        ready = _time.perf_counter()
        lt.done(
            d2h_bytes=devtime.payload_bytes(ys) if isinstance(ys, dict)
            else 0,
            bucket=h.bucket, speculative=h.speculative,
        )
        self._feed_device_time("kernel", ready - lt.submit)

    def _harvest_locked(self) -> None:
        h = self._pending.popleft()
        self._pending_cv.notify_all()  # back-pressured dispatchers
        hsp = tracing.span("harvest", "harvest", n=len(h.group),
                           bucket=h.bucket, speculative=h.speculative,
                           batch=h.batch)
        try:
            with hsp:
                if h.timed_out or not (h.waited or self._wait_ready(
                    h.ys, self.watchdog_timeout
                    if h.deadline is None
                    else h.deadline - _time.monotonic()
                )):
                    raise DeviceFault(
                        "device wait exceeded the dispatch watchdog",
                        kind="timeout")
                ys = h.ys
                if self.faults is not None:
                    ys = self.faults.corrupt_harvest(
                        ys, rung=self.ladder.rung())
                decisions = h.decide(ys)
                self._validate_decisions(decisions, len(h.node_names), ys)
        except DeviceFault as e:
            self._recover_dispatches_locked(e.kind, h)
            return
        except Exception:  # noqa: BLE001 — decode blew up on garbage
            logger.warning("harvest decode failed", exc_info=True)
            self._recover_dispatches_locked("invalid", h)
            return
        self._close_launch_devtime(h, ys)
        self.ladder.record_success()
        if h.bucket is not None:
            # the bucket proved itself (through jit while quarantined):
            # future session rebuilds may AOT it again
            self._suspect_buckets.discard(h.bucket)
        if (self.explain and self.explain_harvest
                and isinstance(ys, dict) and "expl_bits" in ys):
            try:
                h.explain = HoistedSession.explain_payload(ys)
            except Exception:  # noqa: BLE001 — attribution must never
                # fail a harvest that already produced valid decisions
                logger.warning("explain decode failed", exc_info=True)
            else:
                from .metrics import explain_harvests

                explain_harvests.inc()
        from .metrics import speculative_dispatches

        if h.speculative:
            speculative_dispatches.inc(outcome="hit")
        if h.prov is not None:
            h.prov["spec_outcome"] = "hit" if h.speculative else None
        h.results = self._apply_decisions_locked(
            h.group, decisions, h.node_names, prov=h.prov,
            explain=h.explain)

    def schedule_many(self, pods: List[v1.Pod]) -> List[Tuple[v1.Pod, Optional[str]]]:
        """Batched sequential scheduling: groups batchable same-shape pods
        into single scan dispatches (ops/batch.py); falls back to per-pod
        dispatch for pods whose assume mutates term/port tables. Decisions
        are applied to the encoding as if each pod was assumed; callers
        MUST follow up with cache.assume_pod for each bound pod (which
        re-syncs the same rows idempotently via the listener hooks)."""
        results: List[Tuple[v1.Pod, Optional[str]]] = []
        with self._lock:
            self._flush_pending()
            i = 0
            while i < len(pods):
                pod = pods[i]
                try:
                    p = self.pe.encode(pod)
                except VolumeResolutionChanged:
                    results.append((pod, RETRY_NODE))  # prompt re-gate
                    i += 1
                    continue
                # bound pods (spec.nodeName already set) go one-at-a-time;
                # everything else — including affinity/host-port pods,
                # whose assume effects the session carries dynamically
                # (ops/hoisted.py term machinery) — rides the batch path
                if pod.spec.node_name:
                    try:
                        # schedule() invalidates the session at entry, so the
                        # term/port-table writes of this add_pod cannot leak
                        # into a stale device carry.
                        r = self.schedule(pod)
                        node = r.suggested_host
                        # NOTE: never mutate the caller's pod (it aliases the
                        # informer cache); the node rides the result tuple and
                        # enc.add_pod takes the node explicitly
                        self.enc.add_pod(pod, node)
                        results.append((pod, node))
                    except FitError:
                        results.append((pod, None))
                    except DeviceFault:
                        # single-pod retries exhausted: back to the
                        # queue exactly once (prompt re-gate); the
                        # ladder already recorded the faults
                        results.append((pod, RETRY_NODE))
                    i += 1
                    continue
                # group a maximal run of pending, shape-identical pods
                group = [pod]
                arrays = [p]
                sig = shape_signature({k: v for k, v in p.items() if not k.startswith("_")})
                j = i + 1
                while j < len(pods):
                    if pods[j].spec.node_name:
                        break
                    try:
                        q = self.pe.encode(pods[j])
                    except VolumeResolutionChanged:
                        break  # handled when the outer loop reaches j
                    qa = {k: v for k, v in q.items() if not k.startswith("_")}
                    if shape_signature(qa) != sig:
                        break
                    group.append(pods[j])
                    arrays.append(q)
                    j += 1

                # pending pods: the hoisted SESSION — carry stays
                # on-device across batches and scheduler cycles; the
                # prologue is paid for the specs of a (re)build and for
                # each spec a table session admits later. Only a foreign
                # cluster mutation, or a spec the session can neither
                # hold nor admit, tears it down.
                # NOTE: no device_state() here — with dirty rows the
                # fused scatter DONATES the old device arrays, which
                # are exactly the live session's statics (the session
                # is self-consistent without the sync; its exactness
                # argument is in ops/hoisted.py)
                sp = tracing.span(
                    "dispatch-sync", "dispatch", n=len(group),
                    rung=self.ladder.rung(), pipelined=False,
                ) if tracing.enabled() else tracing.NOOP_SPAN
                with sp:
                    decisions = self._session_schedule_guarded([
                        {k: v for k, v in a.items()
                         if not k.startswith("_")}
                        for a in arrays
                    ])
                    if self._launch_stats is not None:
                        sp.set(templates=self._launch_stats[0],
                               term_pods=self._launch_stats[1])
                if decisions is None:
                    # retries exhausted (or fully demoted): the whole
                    # group re-gates via the queue exactly once; while
                    # the ladder sits at oracle the scheduler routes the
                    # re-pop through _schedule_one_oracle
                    results.extend((g, RETRY_NODE) for g in group)
                    i = j
                    continue
                prov = None
                if tracing.RECORDER.pod_level():
                    prov = {
                        "rung": self.ladder.mode(),
                        "session": type(self._session).__name__
                        if self._session is not None else "",
                        "build_reason": self._last_build,
                        "speculative": False,
                    }
                results.extend(self._apply_decisions_locked(
                    group, decisions, self.enc.node_names, prov=prov))
                i = j
        return results

    def _session_schedule(self, arrays: List[Dict]) -> List[int]:
        """Schedule a batchable pending group through the cross-cycle
        session: built when there is none, and torn down first when it
        cannot take the batch's specs in (_admit_templates_locked)."""
        uniq: Dict = {}
        for a in arrays:
            uniq.setdefault(template_fingerprint(a), a)
        self._remember_templates(uniq)
        if self._session is not None:
            self._admit_templates_locked(uniq)
        if self._session is None:
            self._session = self._build_session()
        else:
            # a surviving session may carry queued cluster-event deltas:
            # reconcile before this scan chains on the carry (a FRESH
            # build needs none — the encoding it built from already
            # holds every mutation, and _invalidate_session cleared the
            # queue)
            self._apply_session_deltas_locked()
            if self._session is None:  # apply failed -> rebuild now
                self._session = self._build_session()
        self._launch_stats = None
        ys = self._session.schedule(arrays)
        if isinstance(ys, dict) and "templates" in ys:
            self._launch_stats = (ys["templates"], ys["term_pods"])
        # decisions() decodes through np.asarray, an UNBOUNDED device
        # wait — bound it with the watchdog first or the synchronous
        # re-decide path (fault recovery!) could hang on the very
        # device wedge it is recovering from, with the backend lock
        # held
        if not self._wait_ready(ys, self.watchdog_timeout):
            raise DeviceFault(
                "synchronous dispatch exceeded the watchdog",
                kind="timeout")
        return type(self._session).decisions(ys)

    def _remember_templates(self, uniq: Dict) -> None:
        """Note the batch's specs as the most recently used. Specs whose
        arrays have another shape than the batch's (the encoding grew a
        vocabulary bucket since) cannot stack with it in a build: they
        go, and are met again with their next pod."""
        known = self._known_templates
        sig = shape_signature(next(iter(uniq.values())))
        for fp in [fp for fp, a in known.items()
                   if fp not in uniq and shape_signature(a) != sig]:
            del known[fp]
        for fp, a in uniq.items():
            known[fp] = a
            known.move_to_end(fp)
        self._batch_specs = len(uniq)

    def _table_capacity(self) -> int:
        from ..ops.pallas_scan import table_capacity

        return table_capacity(self.enc._pod_reserve)

    def _admit_templates_locked(self, uniq: Dict) -> None:
        """Make the live session hold every spec of `uniq`, or tear it
        down. A table session (ops/pallas_scan.py PallasSession.admit)
        takes new specs in: no rebuild, no compile; batches still in
        flight are landed first only if their pods could count toward a
        new spec's rows. Every other session kind, and a table that
        cannot fit them, is torn down under a reason of its own."""
        sess = self._session
        held = getattr(sess, "_fps", None)
        if held is None:
            return  # a session that does not say what it holds (tests)
        new = [a for fp, a in uniq.items() if fp not in held]
        if not hasattr(sess, "admit"):
            # an encoding rebuild (vocab/table growth) changes array
            # shapes: the session's stacked templates no longer stack
            # with the batch's, whether it has met the specs or not
            if shape_signature(
                    next(iter(uniq.values()))) != self._session_sig:
                self._invalidate_session("shape-change")
            elif new:
                self._invalidate_session("new-template")
            return
        if not new:
            return
        from ..ops.pallas_scan import PallasUnsupported
        from .metrics import session_template_admits

        try:
            with tracing.span("template-admit", "template-admit",
                              n=len(new)) as sp:
                out = sess.admit(self.enc.host_state, new,
                                 flush=self._flush_pending)
                sp.set(rows=out["rows"], score_pairs=out["score_pairs"],
                       flush_s=out["flush_s"])
        except PallasUnsupported as e:
            # the table is used up (table-*), the spec needs what this
            # session was built without, or it cannot ride the kernel
            # at all: the rebuild decides which
            if self._session is sess:
                self._invalidate_session(e.reason)
            return
        if self._session is not sess:
            return  # a fault while landing the batches in flight
        session_template_admits.inc(len(new))
        self._export_table_gauges(sess)

    @staticmethod
    def _export_table_gauges(sess) -> None:
        from ..ops.pallas_scan import MAX_QUIRKS
        from .metrics import balanced_quirk_states, session_templates

        session_templates.set(float(sess.specs), what="specs")
        session_templates.set(float(sess.Tcap), what="capacity")
        session_templates.set(float(sess.count_rows), what="rows")
        session_templates.set(float(sess.RC), what="row_capacity")
        session_templates.set(float(sess.static_rows), what="static_rows")
        session_templates.set(float(sess.SRc), what="static_row_capacity")
        balanced_quirk_states.set(float(sess.quirk_states), what="listed")
        balanced_quirk_states.set(float(MAX_QUIRKS), what="capacity")

    def _build_session(self):
        """Span-wrapped _build_session_impl: records the build as a
        "session" span (builds are the seconds-scale cost rebuild storms
        are made of) and pins the session-kind/rebuild-reason pair the
        per-pod provenance records report."""
        with tracing.span("session-build", "session",
                          reason=self._last_invalidate) as sp:
            s = self._build_session_impl()
            self._session_sig = shape_signature(
                next(iter(self._known_templates.values())))
            self._last_build = (
                f"{type(s).__name__}/{self._last_invalidate or 'initial'}"
            )
            sp.set(kind=type(s).__name__)
            up, self._upload_seconds = self._upload_seconds, 0.0
            if up:
                # the impl measured the cluster upload before the
                # session kind existed; the slug comes from the session
                # it became
                self._feed_device_time("transfer", up, session=s)
            return s

    def _build_session_impl(self):
        """Pallas single-launch session when the cluster shape supports it
        (ops/pallas_scan.py), else the jnp lax.scan session — identical
        decisions either way (tests/test_pallas_scan.py). Downgrades are
        LOUD: a pallas->hoisted fallback costs ~2.4x throughput, so every
        build is counted in scheduler_tpu_session_builds_total{kind,reason}
        and downgrades are logged."""
        from .metrics import inexact_builds, session_builds

        sh = self._shards_label()
        # the specs met, most recent last. A table session has room for
        # _table_capacity() of them, and half is left free for those to
        # come; a session that is [T, ...] in its templates (the mesh's
        # dense layout: 16 fit its 128 match lanes, tests/
        # test_mesh_scaleout.py test_randomized_stream_parity; the jnp
        # session: one compile per T) keeps the hot few. The least
        # recently used wait for their next pod; the batch in hand
        # always stays.
        tabled = (self.use_pallas and not self.explain
                  and self.ladder.rung() >= self.ladder.top)
        keep = max(self._table_capacity() // 2 if tabled
                   else self.DENSE_SESSION_TEMPLATES, self._batch_specs, 1)
        while len(self._known_templates) > keep:
            self._known_templates.popitem(last=False)
        templates = list(self._known_templates.values())
        uploaded: List[Dict] = []

        def device_cluster() -> Dict:
            """The encoding on the device, uploaded when the first
            session kind that reads it there asks (the table session
            reads the host's arrays and never does)."""
            if uploaded:
                return uploaded[0]
            if devtime.enabled():
                # the cluster upload is the H2D transfer the mesh rows
                # care about: measured with an explicit block
                # (decision-inert — the session constructor would
                # synchronize on these arrays anyway), byte count from
                # the uploaded leaves
                import jax

                lt = devtime.launch("transfer", "session-upload")
                cluster = self.enc.device_state()
                # ktpu: allow-sync(devtime fence: session upload timed at build, not on the dispatch path)
                jax.block_until_ready(cluster)
                lt.h2d_bytes = devtime.payload_bytes(cluster)
                lt.done()
                self._upload_seconds = _time.perf_counter() - lt.submit
            else:
                cluster = self.enc.device_state()
            uploaded.append(cluster)
            return cluster

        # KTPU_EXPLAIN (or an armed shadow sentinel): per-plugin
        # attribution exists only on the hoisted session's scan outputs
        # — pallas/sharded builds demote, loudly, for as long as the
        # knob is on (the decisions themselves stay bit-identical; the
        # throughput cost is the explain mode's price)
        explain_k = self.explain_topk if self.explain else 0
        if explain_k:
            if self.mesh is not None:
                from ..parallel import sharded

                session_builds.inc(kind="hoisted", reason="explain", shards=sh)
                return HoistedSession(
                    sharded.shard_cluster(device_cluster(), self.mesh),
                    templates, self.weights, explain_k=explain_k,
                )
            if self.use_pallas:
                logger.warning(
                    "explain mode: hoisted session instead of pallas")
            session_builds.inc(kind="hoisted", reason="explain", shards=sh)
            return HoistedSession(
                device_cluster(), templates, self.weights,
                explain_k=explain_k)
        # degradation ladder: a DEMOTED backend (rung below the
        # platform's top — NOT merely a platform whose top is hoisted)
        # builds the hoisted session even on a TPU; the probe loop
        # re-promotes and invalidates, so the NEXT build climbs back
        demoted = self.ladder.rung() < self.ladder.top
        if self.mesh is not None and demoted:
            session_builds.inc(kind="hoisted", reason="mesh-ladder-demoted",
                               shards=sh)
            from ..parallel import sharded

            return HoistedSession(
                sharded.shard_cluster(device_cluster(), self.mesh),
                templates, self.weights,
            )
        if self.mesh is not None:
            # two-phase sharded session (ops/sharded_scan.py): the pallas
            # session's exact math with node-sharded carries and ICI
            # scalar collectives — the mesh path no longer pays the
            # hoisted tax (term templates included; VERDICT r4 #2)
            from ..ops.pallas_scan import PallasUnsupported
            from ..ops.sharded_scan import ShardedPallasSession

            try:
                s = ShardedPallasSession(
                    device_cluster(), templates, self.weights,
                    mesh=self.mesh)
                session_builds.inc(kind="pallas", reason="mesh-sharded", shards=sh)
                return s
            except PallasUnsupported as e:
                logger.warning(
                    "sharded two-phase session unsupported for this "
                    "workload shape (%s); mesh rides the GSPMD hoisted "
                    "session", e,
                )
                # mesh- prefix: a mesh downgrade is a different (bigger)
                # throughput cliff than a single-chip one — alerting must
                # tell them apart; slugs stay bounded
                session_builds.inc(kind="hoisted",
                                   reason=f"mesh-{e.reason}", shards=sh)
                inexact_builds.inc(what="demoted")
            from ..parallel import sharded

            return HoistedSession(
                sharded.shard_cluster(device_cluster(), self.mesh),
                templates, self.weights,
            )
        if self.use_pallas and demoted:
            logger.warning(
                "ladder-demoted session build: %s instead of pallas",
                self.ladder.mode(),
            )
            session_builds.inc(kind="hoisted", reason="ladder-demoted", shards=sh)
        elif self.use_pallas:
            from ..ops.pallas_scan import PallasSession, PallasUnsupported

            try:
                # the table session's prologue runs on the host, against
                # the encoding's own arrays
                s = PallasSession(
                    self.enc.host_state(), templates, self.weights,
                    interpret=self.pallas_interpret,
                    capacity=self._table_capacity(),
                    # reserve(anti_terms=...) says term pods will come:
                    # a session built without the term machinery would
                    # be rebuilt at the first of them
                    terms=self.enc._anti_reserve > 0)
                self._export_table_gauges(s)
                # re-apply the fault quarantine: suspect buckets stay
                # jit-only on the rebuilt session until they harvest
                # cleanly again
                for b in self._suspect_buckets:
                    s.retire_exec(bucket=b)
                session_builds.inc(kind="pallas", reason="", shards=sh)
                # a score the kernel cannot take exactly is said here,
                # not found in a decision
                if not s._cfg.bal_int:
                    inexact_builds.inc(what="balanced")
                if not s._cfg.pts_int:
                    inexact_builds.inc(what="spread")
                # AOT-warm the ragged-tail batch buckets OFF the serving
                # path: a daemon thread populates the (persistent)
                # compile caches so a mid-window first-tail batch never
                # pays a fresh Mosaic compile
                self._stop_warm_threads()
                wt = threading.Thread(
                    target=s.warm_buckets, name="pallas-bucket-warm",
                    daemon=True,
                )
                self._warm.append((s, wt))
                wt.start()
                return s
            except PallasUnsupported as e:
                logger.warning(
                    "pallas scan unsupported for this workload shape (%s); "
                    "downgrading to the jnp hoisted session (~2.4x slower)", e,
                )
                session_builds.inc(kind="hoisted", reason=e.reason, shards=sh)
                inexact_builds.inc(what="demoted")
        else:
            session_builds.inc(kind="hoisted", reason="platform is not tpu",
                               shards=sh)
        return HoistedSession(device_cluster(), templates, self.weights)

    # -- helpers -----------------------------------------------------------

    def _select_host(self, total: np.ndarray, feasible: np.ndarray) -> int:
        """selectHost, FIRST-MAX tie-break — the TPU build's convention on
        every kernel path (single-pod here; batch scan via jnp.argmax,
        ops/batch.py; pallas via explicit min-index-among-maxima,
        ops/pallas_scan.py:727; sharded via the same argmax under GSPMD).

        The reference reservoir-samples ties (generic_scheduler.go:152) —
        any tie member is a correct decision, but a randomized pick can
        never be bit-reproducible across differently-batched paths, so
        the deterministic lowest-index maximum is the A/B convention and
        the oracle is pinned to it in the parity harnesses
        (tests/test_kernel_parity.py first-max oracle,
        tests/test_hoisted_terms.py _sequential_reference). The oracle
        BACKEND (scheduler backend="oracle") keeps reference reservoir
        semantics."""
        masked = np.where(feasible, total, np.iinfo(np.int64).min)
        return int(np.argmax(masked))

    def _statuses(
        self, out: Dict, n_nodes: int, row: Optional[int] = None
    ) -> Dict[str, Status]:
        """row selects one pod of a batched (vmapped) output."""
        statuses: Dict[str, Status] = {}

        def arr(key):
            a = np.asarray(out[key])
            return a[row] if row is not None else a

        masks = {k: arr(k) for k, _ in MASK_PLUGINS}
        pts_unres = arr("pts_unresolvable")
        ipa_unres = arr("ipa_unresolvable")
        names = self.enc.node_names
        for i in range(n_nodes):
            if i >= len(names) or names[i] is None:
                continue  # tombstoned lane: no node to report on
            failed = [name for key, name in MASK_PLUGINS if not masks[key][i]]
            if not failed:
                continue
            unresolvable = (
                ("PodTopologySpread" in failed and pts_unres[i])
                or ("InterPodAffinity" in failed and ipa_unres[i])
                or "NodeName" in failed
                or "NodeAffinity" in failed
            )
            reasons = [f"{name}" for name in failed]
            statuses[names[i]] = (
                Status.unschedulable_and_unresolvable(*reasons)
                if unresolvable
                else Status.unschedulable(*reasons)
            )
        return statuses
