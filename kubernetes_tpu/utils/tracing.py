"""Structured pipeline tracing: span recorder + bounded flight recorder.

The one span recorder of the program: WHERE a pod's time went, from
`pods.create` in the API server through the informer and the queue
(spans keyed by `key`, namespace/name) across the three-stage scheduling
pipeline (pop -> encode -> queued-delta apply -> dispatch -> wait ->
harvest -> validate -> assume -> reserve/permit -> bind; spans keyed by
`batch`, the scheduling cycle read after the gather) to the bind, with
one `pod-path` event per bound batch joining the two halves; the named
waits of the scheduler's threads; plus the failure seams' last-N-events
dump. A span records wall time and, one span in sixteen, the thread's
CPU time (`cpu_s`): wall - cpu_s is what the thread stood waiting
(interpreter lock, a lock, a condition). `Span.step(name)` splits one
span into parts without a ring event each, and
`Span.log_if_long(threshold)` is the reference's utiltrace threshold
log (k8s.io/utils/trace).

Levels (KTPU_TRACE):

  0  off — the default. A disabled trace point costs one predicate
     check plus trivially-cheap per-BATCH argument evaluation (sites
     whose attrs would take a lock guard on enabled() first), and
     allocates nothing per pod (span() returns a shared no-op
     singleton; tests pin this).
  1  per-stage spans — every pipeline stage records (name, stage, t0,
     dur, tid, attrs) into the ring. At most 20 events per dispatched
     batch and 4 per pod (today 18 and 3: the pod's create, the
     informer's ADDED, the Scheduled event's create), empty polls of
     an idle thread aside; a preemptor adds its `whatif` and its
     node's `preemption-wait`, a failure wave four (wave, plan, books,
     evict), a batch that places nominated pods one; bounded memory.
  2  per-pod provenance — additionally, every decided pod records a
     provenance event: backend rung, session kind, last build/rebuild
     reason, pallas bucket, speculative chaining, replay/re-drive
     state, planner-ladder path. Costly per pod; drills + traces only.

The FLIGHT RECORDER is a fixed-capacity ring written lock-light: slot
allocation is one itertools.count() increment (atomic under the GIL)
and the write is a single guarded list-item assignment, so concurrent
writers never block each other; events are immutable tuples, so a
reader sees whole records only, and a monotonic slot guard keeps a
lagging writer from clobbering a newer record (in the pathological
deschedule window a slot may briefly hold an older record — never a
torn one). Every fault seam
(watchdog timeout, harvest-validation fault, PipelineStalled, ladder
demotion, supervised-worker restart) dumps the last N events before
recovery proceeds — a `PipelineStalled` leaves a triageable record, not
just gauge values.

Export: Chrome-trace / Perfetto JSON (chrome://tracing "trace event
format", ph="X" complete events) via chrome_trace(); text stage-latency
summaries via stage_stats(). scripts/trace_report.py renders dumps.
"""

from __future__ import annotations

import itertools
import json
import logging
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from . import knobs

logger = logging.getLogger(__name__)

TRACE_OFF = 0
TRACE_STAGES = 1
TRACE_PODS = 2

# canonical pipeline stage names (the span naming scheme; README
# "Observability" documents the meaning of each)
STAGES = (
    "pop",          # scheduler thread: queue pop + batch gather
    "encode",       # pod -> dense arrays (PodEncoder)
    "delta-apply",  # queued cluster-event deltas fused into the carry
    "dispatch",     # scan enqueue on the session (incl. speculative)
    "wait",         # watchdog-bounded device wait
    "harvest",      # decode + validate + apply decisions
    "replay",       # sequential re-drives after a fault
    "assume",       # cache.assume (completion worker)
    "reserve-permit",  # Reserve + Permit plugin pass
    "bind",         # batched bind POST
    # the control plane, keyed by `key` (namespace/name)
    "apiserver",    # one span per create/update/delete: "<verb>
                    # <resource>", steps admission / stamp / encode /
                    # lock / store / decode / hooks; a bulk create's
                    # items end with `encode` and its one write is
                    # "create_bulk <resource>", steps store / hooks
    "informer",     # one span per delivered ADDED event: "ADDED
                    # <resource>", poll returning -> last handler
                    # returning (queue.add is inside, step `handlers`)
    # named waits and fill-ins of the scheduler's threads
    "queue-empty",  # scheduler thread: the first, blocking queue.pop
    "paused",       # scheduler thread held at the pause gate
    "cycle",        # scheduler thread, umbrella of one batch: pop ->
                    # handed to the pipeline; pop, prep, encode,
                    # delta-apply, dispatch, backpressure nest inside,
                    # and what none of them names is its own time
    "complete",     # completion worker, umbrella of one batch: wait,
                    # harvest, assume, reserve-permit nest inside
    "prep",         # _schedule_batch_tpu entry -> dispatch_many call
    "backpressure",  # scheduler thread waiting on a full completion FIFO
    "worker-idle",  # completion worker waiting for a batch
    "binder-queue",  # _binders.submit -> first line of _bind_batch
                    # (starts on one thread, ends on another: no cpu_s)
    "path",         # zero-duration `pod-path` event per bound batch:
                    # batch + the bound pods' keys (joins the halves)
    "planner",      # preemption planner ladder: the per-WAVE plan span
    "whatif",       # per-pod fused what-if launches (nested inside a
                    # planner span — a separate stage so stage_stats
                    # never double-counts the wave's wall-clock); steps
                    # prep / wait / pick as attrs prep_s, wait_s, pick_s;
                    # on the wave path (attr path="wave") one a
                    # preemptor's host replay of its step, pick_s
    "whatif-wave",  # one wave launch of up to 64 preemptors of one key
                    # (inside the planner span): n, steps, prep_s,
                    # wait_s, inputs, h2d_bytes, delta_lanes
    "whatif-context",  # one what-if view of the cluster built, once
                    # a wave and template (inside the planner span)
    "preemption-wave",  # completion worker, umbrella of one failure
                    # wave that has preemptable pods: batch, n, keys;
                    # steps snapshot / eligibility / plan / register /
                    # redispatch
    "preemption-books",  # the planner's wave books (_build), inside
                    # the planner span; steps base / lanes / victims /
                    # claimed / nominated
    "evict",        # binder thread: one wave's victim deletes and
                    # nominated-status patches; queued_s, keys,
                    # victims; steps deletes / gang / status
    "preemption-wait",  # one node's preemption: victims registered ->
                    # the last delete echo; keys of the preemptors it
                    # activates (starts on one thread, ends on another)
    "nominated-place",  # a nominated preemptor's feasibility on its
                    # node, assume and bind hand-off: batch, keys
    "template-admit",  # a live session taking new pod specs in
    "session",      # session builds / teardowns
    "fault",        # fault + recovery markers (zero-duration events)
    "provenance",   # per-pod provenance records (level 2)
)


# stages that are not work of the pipeline's own: the named waits, the
# umbrellas (their inner spans are counted themselves), the join marker,
# and the control plane's threads. A reader that sums the pipeline's
# host-busy time (devtime.overlap) leaves them out
NOT_PIPELINE_WORK = (
    "queue-empty", "paused", "backpressure", "worker-idle", "binder-queue",
    "cycle", "complete", "path", "apiserver", "informer",
    "preemption-wave",
)


class _NoopSpan:
    """Shared do-nothing span: the KTPU_TRACE=0 fast path returns THIS
    SINGLETON from span(), so a disabled trace point allocates nothing
    (pinned by the overhead test)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NoopSpan":
        return self

    def step(self, name: str) -> "_NoopSpan":
        return self

    def log_if_long(self, threshold: float, out=None) -> bool:
        return False


NOOP_SPAN = _NoopSpan()


# one span in CPU_EVERY also reads the thread's CPU clock. The read is a
# system call, twice a span: 0.3 us on a workstation, 5.8 us on the
# benchmark's host, where it was 11.5 of a span's 13.9 us and lifted the
# arrivals' median bind from 14 to 25 ms (PERF.md, PR 25). That host's
# thread CPU clock also ticks in steps of 10 ms, so one span's reading
# says little and only sums over many spans of a stage mean something:
# a sum over every sixteenth span says the same at a sixteenth of the
# price.
CPU_EVERY = 16


class Span:
    """One timed span. Besides wall time it records, in its attributes,
    `thread` (the recording thread's name: readers that keep only name,
    stage, t0, dur and attrs, as the benchmark does, still tell threads
    apart) and, for one span in CPU_EVERY, `cpu_s`: the thread's own
    CPU seconds inside the span, spans nested in it included. Over the
    spans of a stage that carry it, sum(cpu_s) / sum(dur) is the share
    of the stage's wall time that was work and not waiting."""

    __slots__ = ("_rec", "name", "stage", "attrs", "t0", "_cpu0", "_last")

    def __init__(self, rec: "FlightRecorder", name: str, stage: str,
                 attrs: Optional[dict]):
        self._rec = rec
        self.name = name
        self.stage = stage
        self.attrs = attrs
        self.t0 = self._last = 0.0
        self._cpu0: Optional[float] = None

    def set(self, **attrs) -> "Span":
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)
        return self

    def step(self, name: str) -> "Span":
        """Close one part of the span: `<name>_s` = seconds since the
        previous step (or the span's start). One ring event for a call
        with seven parts, not seven."""
        now = time.perf_counter()
        if self.attrs is None:
            self.attrs = {}
        self.attrs[name + "_s"] = now - self._last
        self._last = now
        return self

    def log_if_long(self, threshold: float, out=None) -> bool:
        """The reference's utiltrace.LogIfLong: print the span so far
        with its step breakdown when it has run for `threshold` seconds
        or more (generic_scheduler.go:96 logs cycles over 100 ms)."""
        total = time.perf_counter() - self.t0
        if total < threshold:
            return False
        out = out or sys.stderr
        attrs = self.attrs or {}
        fields = ",".join(f"{k}={v}" for k, v in attrs.items()
                          if not k.endswith("_s"))
        print(f'Trace "{self.name}" ({fields}): total {total * 1000:.1f}ms',
              file=out)
        for k, v in attrs.items():
            if k.endswith("_s"):
                print(f"  step {v * 1000:.1f}ms: {k[:-2]}", file=out)
        return True

    def __enter__(self) -> "Span":
        if next(self._rec._cpu_turn) % CPU_EVERY == 0:
            self._cpu0 = time.thread_time()
        self.t0 = self._last = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        dur = time.perf_counter() - self.t0
        attrs = self.attrs
        if attrs is None:
            attrs = self.attrs = {}
        if self._cpu0 is not None:
            attrs["cpu_s"] = time.thread_time() - self._cpu0
        attrs["thread"] = threading.current_thread().name
        self._rec.record(self.name, self.stage, self.t0, dur, attrs)
        return False


# event tuple layout: (seq, name, stage, t0, dur, tid, attrs)
Event = Tuple[int, str, str, float, float, int, Optional[dict]]


class FlightRecorder:
    """Bounded ring of span events; thread-safe, lock-light writes."""

    def __init__(self, capacity: Optional[int] = None,
                 level: Optional[int] = None):
        # defensive env parsing: the recorder is constructed at import
        # time (module-level RECORDER), so a malformed KTPU_TRACE=off or
        # KTPU_TRACE_CAPACITY=64k must degrade to the default, never
        # fail the scheduler's import; capacity is clamped >= 1 (a
        # zero-size ring would divide by zero on the first record)
        if capacity is None:
            capacity = knobs.get_int("KTPU_TRACE_CAPACITY")
        if level is None:
            level = knobs.get_int("KTPU_TRACE")
        self.capacity = max(1, int(capacity))
        self.level = max(0, int(level))
        self._buf: List[Optional[Event]] = [None] * self.capacity
        self._seq = itertools.count()
        self._cpu_turn = itertools.count()  # see CPU_EVERY
        # dump bookkeeping (tests + drills read these; the dump itself
        # is the observable for the fault-seam acceptance contract)
        self._dump_lock = threading.Lock()
        self.dump_history: List[dict] = []
        self.dump_dir = knobs.get_str("KTPU_TRACE_DUMP_DIR")

    # -- write side --------------------------------------------------------

    def record(self, name: str, stage: str, t0: float, dur: float,
               attrs: Optional[dict] = None) -> None:
        if not self.level:
            return
        seq = next(self._seq)
        ev = (seq, name, stage, t0, dur, threading.get_ident(), attrs)
        buf = self._buf  # read once: set_level may replace the ring
        i = seq % len(buf)
        # monotonic slot guard: a writer descheduled for a full ring
        # revolution between its seq draw and its store must not clobber
        # the newer occupant with its stale record (the check/store pair
        # is itself racy, but it shrinks the hazard from "any write
        # latency" to two adjacent bytecodes — in the worst case one
        # slot briefly holds an older record, which snapshot()'s sort
        # tolerates)
        cur = buf[i]
        if cur is None or cur[0] < seq:
            buf[i] = ev

    def set_level(self, n: int) -> int:
        """Set the live level; returns the old one. Switching tracing on
        reads KTPU_TRACE_CAPACITY again: a launcher that sets it after
        this module was first imported, and before it turns tracing on,
        gets the ring it asked for (a smaller ring keeps only the newest
        events of a run). Nothing is recorded while the level is 0."""
        old = self.level
        if not old and n:
            capacity = max(1, knobs.get_int("KTPU_TRACE_CAPACITY"))
            if capacity != self.capacity:
                self.capacity = capacity
                self._buf = [None] * capacity
        self.level = int(n)
        return old

    def event(self, name: str, stage: str, **attrs) -> None:
        """Zero-duration marker (fault seams, state transitions)."""
        self.record(name, stage, time.perf_counter(), 0.0, attrs or None)

    def span(self, name: str, stage: str, **attrs):
        """Context manager recording a timed span at exit. Returns the
        shared no-op singleton when tracing is off — no allocation."""
        if not self.level:
            return NOOP_SPAN
        return Span(self, name, stage, attrs or None)

    def pod_level(self) -> bool:
        return self.level >= TRACE_PODS

    def provenance(self, pod_key: str, **fields) -> None:
        """Level-2 per-pod provenance record (rung, session kind, build
        reason, bucket, speculative, replay, planner path, ...)."""
        if self.level >= TRACE_PODS:
            self.record(pod_key, "provenance",
                        time.perf_counter(), 0.0, fields)

    # -- read side ---------------------------------------------------------

    def mark(self) -> int:
        """Current sequence high-water mark (a window anchor: events
        with seq >= mark() were recorded after this call)."""
        seq = next(self._seq)
        return seq + 1

    def snapshot(self, last: Optional[int] = None,
                 since: Optional[int] = None) -> List[Event]:
        """Events currently in the ring, oldest first. `last` keeps only
        the newest N; `since` keeps seq >= since (a mark() anchor)."""
        events = [e for e in list(self._buf) if e is not None]
        events.sort(key=lambda e: e[0])
        if since is not None:
            events = [e for e in events if e[0] >= since]
        if last is not None:
            events = events[-last:]
        return events

    def clear(self) -> None:
        """Drop buffered events (tests; the seq counter keeps running so
        mark() anchors stay valid)."""
        self._buf = [None] * self.capacity

    # -- fault-seam dump ---------------------------------------------------

    def dump(self, reason: str, last: int = 512,
             path: Optional[str] = None, **attrs) -> List[Event]:
        """Snapshot the last N events for a fault seam: append to
        dump_history, log a one-line summary, and (when a path or
        KTPU_TRACE_DUMP_DIR is configured) write the full record as
        JSON. No-op at level 0 — the ring is empty there, and the fault
        path must stay cheap for untraced production runs."""
        if not self.level:
            return []
        events = self.snapshot(last=last)
        record = {
            "reason": reason,
            "ts": time.time(),
            "level": self.level,
            "attrs": attrs,
            "n_events": len(events),
            "events": [event_dict(e) for e in events],
        }
        out_path = path
        if out_path is None and self.dump_dir:
            out_path = os.path.join(
                self.dump_dir,
                f"ktpu-trace-{int(time.time() * 1000)}-{reason}.json",
            )
        if out_path:
            try:
                with open(out_path, "w") as f:
                    json.dump(record, f)
                record["path"] = out_path
            except OSError:
                logger.warning("flight-recorder dump write failed (%s)",
                               out_path, exc_info=True)
        stages: Dict[str, int] = {}
        for e in events:
            stages[e[2]] = stages.get(e[2], 0) + 1
        logger.warning(
            "flight recorder dump (%s): %d events %s%s%s",
            reason, len(events), stages,
            f" attrs={attrs}" if attrs else "",
            f" -> {out_path}" if out_path else "",
        )
        with self._dump_lock:
            self.dump_history.append(record)
            del self.dump_history[:-64]  # bounded
        return events


# the process-wide recorder (the instrumentation points all write here)
RECORDER = FlightRecorder()


def level() -> int:
    return RECORDER.level


def enabled() -> bool:
    return RECORDER.level > 0


def set_level(n: int) -> int:
    """Set the live trace level (tests, drills, the benchmark); returns
    the old level (FlightRecorder.set_level)."""
    return RECORDER.set_level(n)


span = RECORDER.span  # no second call, no second packing of the attrs


def event(name: str, stage: str, **attrs) -> None:
    RECORDER.event(name, stage, **attrs)


def provenance(pod_key: str, **fields) -> None:
    RECORDER.provenance(pod_key, **fields)


def dump(reason: str, **kw) -> List[Event]:
    return RECORDER.dump(reason, **kw)


# -- export / summaries ----------------------------------------------------


def event_dict(e: Event) -> dict:
    d = {
        "seq": e[0], "name": e[1], "stage": e[2],
        "t0": e[3], "dur": e[4], "tid": e[5],
    }
    if e[6]:
        d.update(e[6])
    return d


def chrome_trace(events: List) -> List[dict]:
    """Chrome-trace "trace event format" complete events (ph="X", µs
    timebase) — loadable in chrome://tracing and Perfetto. Accepts raw
    ring tuples or event_dict() dicts (dump files)."""
    out = []
    for e in events:
        d = e if isinstance(e, dict) else event_dict(e)
        args = {
            k: v for k, v in d.items()
            if k not in ("seq", "name", "stage", "t0", "dur", "tid")
        }
        args["seq"] = d["seq"]
        out.append({
            "name": d["name"],
            "cat": d["stage"],
            "ph": "X",
            "ts": d["t0"] * 1e6,
            "dur": max(d["dur"], 1e-7) * 1e6,
            "pid": 0,
            "tid": d["tid"],
            "args": args,
        })
    return out


def _pctile(samples: List[float], p: float) -> float:
    """Nearest-rank percentile: ceil(p/100 * n) - 1. (round(x + 0.5)
    would hit banker's rounding on exact .5 ties — p50 of two samples
    must be the lower rank, not the max.)"""
    if not samples:
        return 0.0
    s = sorted(samples)
    import math

    idx = min(len(s) - 1, max(0, math.ceil(p / 100.0 * len(s)) - 1))
    return s[idx]


def stage_stats(events: List) -> Dict[str, Dict[str, float]]:
    """Per-stage wall-clock summary over a window of events: count,
    total seconds, p50/p99 span duration. Zero-duration marker stages
    (fault, provenance) report counts with zero totals."""
    durs: Dict[str, List[float]] = {}
    for e in events:
        d = e if isinstance(e, dict) else event_dict(e)
        durs.setdefault(d["stage"], []).append(float(d["dur"]))
    out: Dict[str, Dict[str, float]] = {}
    for stage, vals in sorted(durs.items()):
        out[stage] = {
            "count": len(vals),
            "total_s": round(sum(vals), 6),
            "p50_s": round(_pctile(vals, 50), 6),
            "p99_s": round(_pctile(vals, 99), 6),
        }
    return out


def window_span(events: List) -> float:
    """Wall-clock coverage of a window of events: last span end minus
    first span start (seconds). The reconciliation anchor: with tracing
    on, the harness pins this against the measured first-bind ->
    last-bind window."""
    t0s, t1s = [], []
    for e in events:
        d = e if isinstance(e, dict) else event_dict(e)
        t0s.append(d["t0"])
        t1s.append(d["t0"] + d["dur"])
    if not t0s:
        return 0.0
    return max(t1s) - min(t0s)


def provenance_mix(events: List) -> Dict[str, Dict[str, int]]:
    """Distribution of the level-2 provenance fields over a window:
    {field: {value: count}} for rung / session / planner path /
    speculative — the "which path did pods actually ride" summary
    trace_report prints."""
    mix: Dict[str, Dict[str, int]] = {}
    for e in events:
        d = e if isinstance(e, dict) else event_dict(e)
        if d["stage"] != "provenance":
            continue
        for field in ("rung", "session", "build_reason", "planner",
                      "speculative", "redrive", "bucket"):
            if field in d and d[field] is not None:
                vals = mix.setdefault(field, {})
                key = str(d[field])
                vals[key] = vals.get(key, 0) + 1
    return mix
