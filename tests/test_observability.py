"""Metrics registry, events, leader election, trace + flight-recorder
tests.

Reference models: component-base/metrics tests, client-go record/
leaderelection tests (leaderelection_test.go — acquire, renew, lose on
expiry, second elector takes over); the flight-recorder half covers
utils/tracing.py (ring wrap-around under concurrent writers, chrome
export, stage stats, thread CPU and steps inside a span, one pod
followed from pods.create to its bind), the backend-health k8s Events,
the /configz KTPU_* knob surface, and the perf harness's per-stage
latency fields."""

from __future__ import annotations

import json
import threading
import time

import pytest

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.apiserver import APIServer
from kubernetes_tpu.client import Clientset
from kubernetes_tpu.client.events import EventRecorder
from kubernetes_tpu.client.leaderelection import LeaderElectionConfig, LeaderElector
from kubernetes_tpu.utils import configz, tracing
from kubernetes_tpu.utils.metrics import Counter, Gauge, Histogram, Registry


def test_metrics_collect_and_expose():
    reg = Registry()
    c = reg.register(Counter("requests_total", "Total requests.", ("code",)))
    c.inc(code="200")
    c.inc(code="200")
    c.inc(code="500")
    g = reg.register(Gauge("pending", "Pending items.", ("queue",)))
    g.set(7, queue="active")
    h = reg.register(Histogram("latency_seconds", "Latency.", ()))
    for val in (0.004, 0.02, 0.02, 3.0):
        h.observe(val)
    text = reg.expose()
    assert 'requests_total{code="200"} 2.0' in text
    assert 'pending{queue="active"} 7' in text
    assert "latency_seconds_count 4" in text
    assert h.percentile(50) <= 0.05
    assert h.percentile(99) >= 2.5


def test_event_recorder_aggregates():
    api = APIServer()
    cs = Clientset(api)
    rec = EventRecorder(cs, "test-component")
    pod = v1.Pod(metadata=v1.ObjectMeta(name="p", namespace="default"))
    rec.event(pod, "Normal", "Scheduled", "assigned default/p to n1")
    rec.event(pod, "Normal", "Scheduled", "assigned default/p to n1")
    assert rec.flush()  # recording is async (broadcaster semantics)
    events, _ = cs.resource("events").list()
    assert len(events) == 1
    assert events[0].count == 2
    rec.event(pod, "Warning", "FailedScheduling", "0/3 nodes")
    assert rec.flush()
    events, _ = cs.resource("events").list()
    assert len(events) == 2


def test_event_firehose_sinks_every_event_through_the_bulk_route():
    """One bind wave's worth of Scheduled events: every one an object in
    the store, none dropped, none through a one-at-a-time create."""
    api = APIServer()
    cs = Clientset(api)
    calls = {"create": 0, "create_bulk": 0, "bulk_items": 0}
    real_create, real_bulk = api.create, api.create_bulk

    def create(resource, obj):
        calls["create"] += resource == "events"
        return real_create(resource, obj)

    def create_bulk(resource, objs):
        calls["create_bulk"] += 1
        calls["bulk_items"] += len(objs)
        return real_bulk(resource, objs)

    api.create, api.create_bulk = create, create_bulk
    rec = EventRecorder(cs, "test-component")
    watch = cs.resource("events").watch()
    n = 2048
    for i in range(n):
        pod = v1.Pod(metadata=v1.ObjectMeta(name=f"p-{i:05d}",
                                            namespace="default", uid=f"u{i}"))
        rec.event(pod, "Normal", "Scheduled",
                  f"Successfully assigned default/p-{i:05d} to n1")
    assert rec.flush(timeout=60.0)
    assert rec.dropped_events == 0
    events, _ = cs.resource("events").list()
    assert len(events) == n
    assert {e.involved_object.name for e in events} == {
        f"p-{i:05d}" for i in range(n)}
    assert all(e.count == 1 and e.metadata.uid and e.metadata.creation_timestamp
               and e.source_component == "test-component" for e in events)
    assert calls["create"] == 0
    assert calls["bulk_items"] == n and 1 <= calls["create_bulk"] <= n
    seen = 0
    while seen < n:
        ev = watch.poll(timeout=5.0)
        assert ev is not None and ev.type == "ADDED"
        seen += 1
    watch.stop()


def test_leader_election_failover():
    api = APIServer()
    cs = Clientset(api)
    log = []
    fast = LeaderElectionConfig(
        identity="a", lease_duration=1.0, renew_deadline=0.6, retry_period=0.2
    )
    ea = LeaderElector(
        cs, fast, lambda: log.append("a-start"), lambda: log.append("a-stop")
    )
    ea.start()
    assert ea.is_leader.wait(5)
    assert ea.leader_identity == "a"
    cfg_b = LeaderElectionConfig(
        identity="b", lease_duration=1.0, renew_deadline=0.6, retry_period=0.2
    )
    eb = LeaderElector(
        cs, cfg_b, lambda: log.append("b-start"), lambda: log.append("b-stop")
    )
    eb.start()
    time.sleep(1.0)
    assert not eb.is_leader.is_set(), "b must not steal a live lease"
    ea.stop()  # a stops renewing; lease expires; b adopts
    assert eb.is_leader.wait(10), "b must take over after expiry"
    assert eb.leader_identity == "b"
    eb.stop()
    assert "a-start" in log and "b-start" in log


def test_trace_threshold():
    """utiltrace's LogIfLong, on the one span recorder: nothing below the
    threshold, the step breakdown above it."""
    import io

    rec = tracing.FlightRecorder(capacity=16, level=tracing.TRACE_STAGES)
    buf = io.StringIO()
    with rec.span("cycle", "pop", pod="default/p") as tr:
        tr.step("filter")
        assert not tr.log_if_long(10.0, out=buf)
        time.sleep(0.02)
        tr.step("score")
        assert tr.log_if_long(0.01, out=buf)
    out = buf.getvalue()
    assert 'Trace "cycle" (pod=default/p)' in out
    assert "filter" in out and "score" in out and "cpu" not in out
    # the same call on a disabled trace point costs nothing and logs nothing
    assert not tracing.NOOP_SPAN.log_if_long(0.0, out=buf)
    assert buf.getvalue() == out


# -- flight recorder (utils/tracing.py) ------------------------------------


@pytest.fixture
def recorder():
    """A private recorder at level 1 (stage spans); the global RECORDER
    is restored untouched."""
    return tracing.FlightRecorder(capacity=64, level=tracing.TRACE_STAGES)


@pytest.fixture
def traced():
    """Enable the GLOBAL recorder for a test, restore + clear after."""
    old = tracing.set_level(tracing.TRACE_PODS)
    tracing.RECORDER.clear()
    yield tracing.RECORDER
    tracing.set_level(old)
    tracing.RECORDER.clear()


class TestFlightRecorder:
    def test_ring_wraparound_under_concurrent_writers(self, recorder):
        """4 writers x 200 events into a 64-slot ring: after the join
        the ring holds 64 unique, ordered, well-formed records from the
        newest window (the monotonic slot guard keeps lagging writers
        from clobbering newer records; only a pathological deschedule
        exactly between its check and store could leave a slot one
        revolution stale, so the window assertion allows a single
        straggler) — lock-light writes may race, torn state may not."""
        n_threads, per = 4, 200

        def write(t):
            for i in range(per):
                recorder.record(f"w{t}-{i}", "dispatch", 0.0, 0.001,
                                {"t": t})

        threads = [threading.Thread(target=write, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        events = recorder.snapshot()
        total = n_threads * per
        assert len(events) == 64
        seqs = [e[0] for e in events]
        assert seqs == sorted(seqs)
        assert len(set(seqs)) == 64
        newest = set(range(total - 64, total))
        assert seqs[-1] >= total - 2  # even the max slot may race once
        assert len(newest.intersection(seqs)) >= 63
        assert min(seqs) >= total - 2 * 64
        for e in events:
            assert e[2] == "dispatch" and e[6]["t"] in range(n_threads)

    def test_span_context_manager_and_stage_stats(self, recorder):
        with recorder.span("b0", "dispatch", n=4):
            time.sleep(0.005)
        with recorder.span("b0", "harvest") as sp:
            sp.set(bucket=8)
        recorder.event("device-fault", "fault", kind="timeout")
        events = recorder.snapshot()
        assert len(events) == 3
        stats = tracing.stage_stats(events)
        assert stats["dispatch"]["count"] == 1
        assert stats["dispatch"]["p50_s"] >= 0.005
        assert stats["fault"]["total_s"] == 0.0
        assert tracing.window_span(events) > 0.0
        # attrs set mid-span survive into the record
        harvest = [e for e in events if e[2] == "harvest"][0]
        assert harvest[6]["bucket"] == 8

    def test_chrome_trace_export_shape(self, recorder):
        with recorder.span("batch", "dispatch", n=2):
            pass
        chrome = tracing.chrome_trace(recorder.snapshot())
        assert len(chrome) == 1
        ev = chrome[0]
        assert ev["ph"] == "X" and ev["cat"] == "dispatch"
        assert ev["dur"] > 0 and ev["args"]["n"] == 2
        json.dumps(chrome)  # must be JSON-serializable as-is

    def test_disabled_level_is_noop_singleton(self):
        rec = tracing.FlightRecorder(capacity=16, level=0)
        assert rec.span("a", "dispatch") is tracing.NOOP_SPAN
        assert rec.span("b", "harvest", n=1) is tracing.NOOP_SPAN
        # every stage a trace point can name, the new ones with the old
        assert {"preemption-wave", "preemption-books", "evict",
                "preemption-wait", "nominated-place", "whatif-context",
                "template-admit"} <= set(tracing.STAGES)
        for stage in tracing.STAGES:
            assert rec.span(stage, stage, batch=7) is tracing.NOOP_SPAN
        # and what a site calls on its span is a no-op on the singleton
        sp = tracing.NOOP_SPAN
        assert sp.step("store") is sp and sp.set(got=True) is sp
        assert sp.log_if_long(0.0) is False
        rec.record("a", "dispatch", 0.0, 1.0)
        rec.provenance("default/p", rung="pallas")
        assert rec.snapshot() == []
        assert rec.dump("device-fault-timeout") == []
        assert rec.dump_history == []

    def test_switching_tracing_on_takes_the_capacity_asked_for(
            self, monkeypatch):
        """The recorder is built when the module is first imported; a
        launcher that asks for a larger ring afterwards, before it turns
        tracing on, must get it, or a long run keeps only its newest
        events."""
        rec = tracing.FlightRecorder(capacity=16, level=0)
        monkeypatch.setenv("KTPU_TRACE_CAPACITY", "64")
        assert rec.set_level(tracing.TRACE_STAGES) == 0
        assert rec.capacity == 64
        for i in range(100):
            rec.record(f"s{i}", "pop", 0.0, 0.0)
        assert [e[1] for e in rec.snapshot()][:2] == ["s36", "s37"]
        # raising a level that is already on keeps the ring and its events
        monkeypatch.setenv("KTPU_TRACE_CAPACITY", "8")
        assert rec.set_level(tracing.TRACE_PODS) == tracing.TRACE_STAGES
        assert rec.capacity == 64 and len(rec.snapshot()) == 64
        assert rec.set_level(0) == tracing.TRACE_PODS
        assert rec.capacity == 64

    def test_busy_span_records_thread_cpu_below_wall(self):
        def first_span_of_a_recorder(stage, body):
            # the first span of a recorder reads the CPU clock
            rec = tracing.FlightRecorder(capacity=4, level=1)
            with rec.span("s", stage):
                body()
            (ev,) = rec.snapshot()
            return ev

        def spin():
            t_end = time.perf_counter() + 0.05
            while time.perf_counter() < t_end:
                sum(range(200))

        busy = first_span_of_a_recorder("encode", spin)
        idle = first_span_of_a_recorder("wait", lambda: time.sleep(0.05))
        wall, cpu = busy[4], busy[6]["cpu_s"]
        # the thread CPU clock ticks in steps of 10 ms on some kernels
        assert 0.0 < cpu <= wall + 0.011
        # a sleeping thread's wall is waiting, not work
        assert idle[6]["cpu_s"] < 0.25 * idle[4]
        assert busy[6]["thread"] == threading.current_thread().name
        assert len(busy) == 7, "the ring tuple keeps its seven positions"

    def test_one_span_in_sixteen_reads_the_cpu_clock(self, recorder):
        """The read is a system call, twice a span: every span pays for
        a sixteenth of it."""
        for i in range(4 * tracing.CPU_EVERY):
            with recorder.span(f"s{i}", "assume"):
                pass
        with_cpu = [e[1] for e in recorder.snapshot() if "cpu_s" in e[6]]
        assert with_cpu == ["s0", "s16", "s32", "s48"]
        assert all("thread" in e[6] for e in recorder.snapshot())

    def test_steps_split_one_span_and_sum_to_it(self, recorder):
        with recorder.span("create pods", "apiserver", key="default/p") as sp:
            for part in ("admission", "encode", "store"):
                time.sleep(0.002)
                sp.step(part)
        (ev,) = recorder.snapshot()  # one ring event, not one per part
        attrs = ev[6]
        parts = [attrs[f"{p}_s"] for p in ("admission", "encode", "store")]
        assert all(v >= 0.002 for v in parts)
        # the steps tile the span up to its last step: what is left is
        # the exit of the with block
        assert 0.0 <= ev[4] - sum(parts) < 1e-3
        assert attrs["key"] == "default/p"

    def test_dump_writes_file_and_history(self, recorder, tmp_path):
        with recorder.span("batch", "dispatch", n=2):
            pass
        path = str(tmp_path / "dump.json")
        events = recorder.dump("device-fault-timeout", path=path,
                               kind="timeout", rung="hoisted")
        assert len(events) == 1
        assert recorder.dump_history[-1]["reason"] == "device-fault-timeout"
        assert recorder.dump_history[-1]["attrs"]["rung"] == "hoisted"
        with open(path) as f:
            rec = json.load(f)
        assert rec["events"][0]["stage"] == "dispatch"
        # the dump file renders through scripts/trace_report.py (the
        # drill's integrity check)
        import os
        import sys

        sys.path.insert(0, os.path.join(
            os.path.dirname(__file__), "..", "scripts"))
        import trace_report

        assert trace_report.render(path) == 0
        assert (tmp_path / "dump.chrome.json").exists()

    def test_provenance_only_at_level_2(self):
        rec = tracing.FlightRecorder(capacity=16, level=1)
        rec.provenance("default/p", rung="pallas")
        assert rec.snapshot() == []
        rec.level = 2
        rec.provenance("default/p", rung="pallas", planner="device")
        mix = tracing.provenance_mix(rec.snapshot())
        assert mix["rung"] == {"pallas": 1}
        assert mix["planner"] == {"device": 1}

    def test_threshold_trace_mirrors_into_recorder(self, traced):
        """What utils/trace.py's record_spans did with one ring event per
        step, a span's steps do with one event in all."""
        with tracing.span("cycle", "pop", pod="default/p") as tr:
            tr.step("filter")
            tr.step("score")
        (ev,) = [e for e in traced.snapshot() if e[1] == "cycle"]
        assert {"filter_s", "score_s", "pod"} <= set(ev[6])
        assert "filter_s" in tracing.event_dict(ev)  # and into the export


# -- one pod followed from pods.create to its bind ---------------------------

N_ROUND_PODS = 48


@pytest.fixture(scope="module")
def traced_round():
    """One create -> informer -> queue -> batch -> bind round of
    N_ROUND_PODS pods on the CPU backend at level 1; returns (events,
    keys of the pods, what a level-0 round left behind)."""
    from tests.util import make_pod, wait_until

    cs, factory, sched = _mini_scheduler(nodes=8, max_batch=8)
    sched.start()

    def round_of(prefix):
        keys = []
        for i in range(N_ROUND_PODS):
            cs.pods.create(make_pod(f"{prefix}-{i:03d}", namespace="default",
                                    cpu="10m"))
            keys.append(f"default/{prefix}-{i:03d}")
            if i % 6 == 5:
                time.sleep(0.01)  # several small batches, some idle time
        assert wait_until(lambda: all(
            p.spec.node_name for p in cs.pods.list(namespace="default")[0]),
            timeout=120)
        assert sched.recorder.flush(timeout=10)
        return keys

    class CountedSpan(tracing.Span):
        made = 0

        def __init__(self, *a):
            CountedSpan.made += 1
            super().__init__(*a)

    old = tracing.set_level(0)
    real_span, tracing.Span = tracing.Span, CountedSpan
    mark = on = 0
    try:
        round_of("warm")  # compiles; not traced
        # the round with tracing off: no trace point may build a span,
        # and the ring has to stay empty
        tracing.RECORDER.clear()
        CountedSpan.made = 0
        round_of("off")
        off = {"ring": len(tracing.RECORDER.snapshot()),
               "spans_built": CountedSpan.made}
        tracing.set_level(tracing.TRACE_STAGES)
        mark = tracing.RECORDER.mark()
        keys = round_of("on")
        on = CountedSpan.made
    finally:
        sched.shutdown()  # closes the idle spans that are open
        factory.stop()
        events = tracing.RECORDER.snapshot(since=mark)
        tracing.Span = real_span
        tracing.set_level(old)
        tracing.RECORDER.clear()
    assert on >= len(events) - 2 * len(keys), "the counter saw the spans"
    return events, keys, off


def test_level_0_round_allocates_nothing_and_records_nothing(traced_round):
    _, _, off = traced_round
    assert off == {"ring": 0, "spans_built": 0}


def test_a_pods_spans_join_from_create_to_bind(traced_round):
    """The control-plane half is keyed by `key`, the pipeline half by
    `batch`, and one pod-path event per bound batch joins them: for every
    pod the chain create -> admitted -> popped -> harvested -> bound
    exists and is in order."""
    events, keys, _ = traced_round
    created, admitted, batch_of = {}, {}, {}
    by_batch = {}
    for _, name, stage, t0, dur, _, attrs in events:
        attrs = attrs or {}
        if stage == "apiserver" and name == "create pods":
            created[attrs["key"]] = (t0, t0 + dur)
        elif stage == "informer" and name == "ADDED pods":
            admitted[attrs["key"]] = t0 + dur
        elif stage == "path":
            for k in attrs["keys"]:
                batch_of[k] = attrs["batch"]
        elif "batch" in attrs:
            by_batch.setdefault(attrs["batch"], {})[stage] = (t0, t0 + dur)
    for key in keys:
        assert key in created and key in admitted and key in batch_of, key
        spans = by_batch[batch_of[key]]
        # the loop's spans and the backend's carry the same number
        assert {"cycle", "pop", "prep", "encode", "dispatch", "complete",
                "wait", "harvest", "assume", "reserve-permit",
                "binder-queue", "bind"} <= set(spans), (key, set(spans))
        assert created[key][0] <= admitted[key]
        assert spans["pop"][0] <= spans["harvest"][1] <= spans["bind"][1]
        assert admitted[key] <= spans["harvest"][1]
    create = [e for e in events if e[1] == "create pods"][0][6]
    assert {"admission_s", "lock_s", "stamp_s", "encode_s", "store_s",
            "decode_s", "hooks_s"} <= set(create)
    timed = [e for e in events if e[6] and "thread" in e[6]]
    with_cpu = [e for e in timed if "cpu_s" in e[6]]
    assert len(timed) // 32 <= len(with_cpu) <= len(timed) // 8
    bind = [e for e in events if e[2] == "bind"][0][6]
    assert "posted_s" in bind
    # a span that starts on one thread and ends on another has no cpu_s
    waits = [e[6] for e in events if e[2] == "binder-queue"]
    assert waits and all("cpu_s" not in a for a in waits)


def test_ring_budget_per_pod_and_per_batch(traced_round):
    """At most 4 ring events per pod and 20 per batch, empty polls of an
    idle thread aside: run.py's 2^21 slots then hold a whole window."""
    events, keys, _ = traced_round
    per_pod, per_batch = {}, {}
    for _, name, stage, _, _, _, attrs in events:
        attrs = attrs or {}
        if "batch" in attrs:
            b = attrs["batch"]
            per_batch[b] = per_batch.get(b, 0) + 1
        elif "key" in attrs:
            # a pod's Scheduled event is `default/<pod>.<suffix>`
            k = attrs["key"].partition(".")[0]
            per_pod[k] = per_pod.get(k, 0) + 1
    assert set(keys) <= set(per_pod)
    assert max(per_pod[k] for k in keys) <= 4, per_pod
    batches = {a["batch"] for e in events if e[2] == "path"
               for a in [e[6]]}
    # the batch's own spans, its `bind pods` API call, and the polls
    # (`queue-empty`, `worker-idle`) that found it
    assert max(per_batch[b] for b in batches) + 3 <= 20, per_batch


def test_scheduler_threads_are_inside_a_span(traced_round):
    """The scheduler thread and the completion worker are inside some
    span for all of a traced drain but the microseconds between spans."""
    events, _, _ = traced_round
    # the drain: first batch handed to the worker -> last bind done (the
    # worker's idle span across the raise of the level was never opened)
    t_a = min(e[3] for e in events if e[2] == "complete")
    t_b = max(e[3] + e[4] for e in events if e[2] == "bind")
    by_thread = {}
    for e in events:
        thread = (e[6] or {}).get("thread")
        a, b = max(e[3], t_a), min(e[3] + e[4], t_b)
        if thread in ("scheduler-loop", "batch-completions") and b > a:
            by_thread.setdefault(thread, []).append((a, b))
    assert set(by_thread) == {"scheduler-loop", "batch-completions"}
    for thread, iv in by_thread.items():
        covered, end = 0.0, t_a
        for a, b in sorted(iv):
            if b > end:
                covered += b - max(a, end)
                end = b
        assert covered / (t_b - t_a) >= 0.97, (thread, covered, t_b - t_a)


# -- backend health -> k8s Events + /configz knobs -------------------------


def _mini_scheduler(nodes=1, **kw):
    from kubernetes_tpu.client import SharedInformerFactory
    from kubernetes_tpu.scheduler.scheduler import Scheduler
    from tests.util import make_node

    api = APIServer()
    cs = Clientset(api)
    for i in range(nodes):
        cs.nodes.create(make_node(f"node-{i}"))
    factory = SharedInformerFactory(cs)
    sched = Scheduler(cs, factory, backend="tpu", pipeline_depth=2, **kw)
    factory.start()
    assert factory.wait_for_cache_sync()
    return cs, factory, sched


def test_backend_health_transitions_emit_events():
    """Ladder demotion, speculation-miss re-drives and worker restarts
    surface as k8s Events on the scheduler pseudo-object — with repeats
    AGGREGATED (one Event, bumped count), so cluster-level observers see
    device health without scraping metrics."""
    cs, factory, sched = _mini_scheduler()
    try:
        tpu = sched.tpu
        tpu.ladder.threshold = 1  # demote on the first fault
        with tpu._lock:
            tpu._device_fault_locked("raise")
        # speculation misses: two identical re-drive notices aggregate
        class _H:  # minimal speculative handle stand-ins
            speculative = True

        tpu._miss_speculative([_H()])
        tpu._miss_speculative([_H()])
        assert sched.recorder.flush(timeout=10)
        events, _ = cs.resource("events").list()
        by_reason = {}
        for e in events:
            if e.involved_object.kind == "Scheduler":
                by_reason[e.reason] = by_reason.get(e.reason, 0) + e.count
        assert by_reason.get("BackendDemoted", 0) >= 1
        assert by_reason.get("SpeculationMissRedrive", 0) == 2
        demoted = [e for e in events if e.reason == "BackendDemoted"]
        assert demoted[0].type == "Warning"
        miss = [e for e in events if e.reason == "SpeculationMissRedrive"]
        assert len(miss) == 1 and miss[0].count == 2, "repeats must aggregate"
    finally:
        sched.shutdown()
        factory.stop()


def test_configz_registers_runtime_ktpu_knobs():
    """The runtime-effective KTPU_* surface is inspectable via /configz:
    the values the backend actually RESOLVED (platform defaults applied),
    not the raw env strings."""
    cs, factory, sched = _mini_scheduler()
    try:
        snap = configz.snapshot()
        assert "ktpu" in snap
        knobs = snap["ktpu"]
        for key in ("speculation", "whatif", "session_deltas",
                    "trace_level", "watchdog_timeout", "drain_timeout",
                    "pipeline_depth", "demote_threshold"):
            assert key in knobs, key
        assert isinstance(knobs["speculation"], bool)
        # the /configz body serializes (the handler contract)
        json.loads(configz.handler_body())
    finally:
        sched.shutdown()
        factory.stop()


# -- harness: per-stage latency attribution --------------------------------


def test_harness_stage_latency_attribution_and_reconciliation(traced):
    """With KTPU_TRACE on, a full-loop harness run reports per-stage
    p50/p99 fields that reconcile with the measured window; with it off,
    the fields are absent (None) and the recorder stays empty."""
    from kubernetes_tpu.perf import Workload, run_workload

    w = Workload("trace-ci", num_nodes=10, num_pods=30, timeout=120,
                 max_batch=16)
    r = run_workload(w)
    assert r.trace_level == tracing.TRACE_PODS
    assert r.stage_latency, "no stage breakdown with tracing enabled"
    stages = set(r.stage_latency)
    assert {"pop", "encode", "dispatch", "harvest", "assume",
            "bind"} <= stages
    for stats in r.stage_latency.values():
        assert stats["count"] >= 1
        assert stats["p50_s"] <= stats["p99_s"]
        assert stats["total_s"] <= max(r.duration_s, 1.0) * 8
    # reconciliation: the spans cover a window consistent with the
    # measured run (pipeline stages overlap across threads, so each
    # stage's total is bounded by the span-covered wall clock, and the
    # covered window cannot exceed the measured phase by more than the
    # post-pause drain slack)
    assert r.stage_window_s > 0
    assert r.stage_window_s <= r.duration_s + 35.0
    dispatch_total = r.stage_latency["dispatch"]["total_s"]
    assert dispatch_total <= r.stage_window_s + 1.0
    # per-pod provenance recorded one record per decided pod
    prov = r.stage_latency.get("provenance")
    assert prov is not None and prov["count"] >= r.num_bound
    # rows survive JSON round-trips for the bench artifacts
    json.dumps(r.to_dict())

    tracing.set_level(0)
    tracing.RECORDER.clear()
    r2 = run_workload(w)
    assert r2.trace_level == 0 and r2.stage_latency is None
    assert tracing.RECORDER.snapshot() == []
