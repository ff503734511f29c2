"""Device-fault-tolerant scheduling pipeline tests.

The pipelined loop (PR 3) assumed every XLA dispatch succeeds; this suite
pins the fault half of the contract: a raising launch, a NaN/garbage
harvest, and a wedged device wait are detected (watchdog + validation
guard), recovered (bounded retry with a rebuilt session), contained
(degradation ladder pallas -> hoisted -> oracle under persistent faults,
background-probe re-promotion), and survived by the pipeline workers
(supervised scheduler/completion threads, FIFO drained back to the queue
on a worker crash). Fault parity: transient faults recovered IN ORDER
must not change a single decision vs the clean depth-0 reference; worker
kills must preserve the bound SET (every pod bound exactly once or still
queued — zero lost, zero double-bound).
"""

from __future__ import annotations

import random
import threading
import time

import pytest

from kubernetes_tpu.scheduler import metrics
from kubernetes_tpu.scheduler.degradation import (
    RUNG_HOISTED,
    RUNG_ORACLE,
    RUNG_PALLAS,
    DegradationLadder,
)
from kubernetes_tpu.scheduler.scheduler import PipelineStalled
from kubernetes_tpu.testing.faults import FaultInjector, InjectedFault

from .test_pipeline_parity import (
    _bound_map,
    _cluster,
    _drive,
    _mk_scheduler,
    _pod_stream,
)
from .util import make_pod, wait_until


def _counter_snapshot():
    return {
        "faults": dict(metrics.device_faults.items()),
        "retries": metrics.dispatch_retries.value(),
        "restarts": dict(metrics.worker_restarts.items()),
    }


def _fault_delta(before, kind):
    after = dict(metrics.device_faults.items())
    return after.get((kind,), 0.0) - before["faults"].get((kind,), 0.0)


def _restart_delta(before, worker):
    after = dict(metrics.worker_restarts.items())
    return after.get((worker,), 0.0) - before["restarts"].get((worker,), 0.0)


# -- unit: injector ---------------------------------------------------------


class TestFaultInjector:
    def test_arm_shots_consume_and_count(self):
        inj = FaultInjector()
        inj.arm("raise-dispatch", shots=2)
        for _ in range(2):
            with pytest.raises(InjectedFault):
                inj.on_dispatch(rung=RUNG_HOISTED)
        inj.on_dispatch(rung=RUNG_HOISTED)  # shots exhausted: clean
        assert inj.injected["raise-dispatch"] == 2

    def test_min_rung_filter(self):
        """A pallas-only fault must not fire on hoisted dispatches —
        the shape the ladder demotion is supposed to escape."""
        inj = FaultInjector()
        inj.arm("raise-dispatch", shots=-1, min_rung=RUNG_PALLAS)
        inj.on_dispatch(rung=RUNG_HOISTED)  # below min_rung: clean
        with pytest.raises(InjectedFault):
            inj.on_dispatch(rung=RUNG_PALLAS)
        inj.disarm("raise-dispatch")
        inj.on_dispatch(rung=RUNG_PALLAS)

    def test_wedge_consume(self):
        inj = FaultInjector()
        inj.arm("wedge-wait", shots=1)
        assert inj.wedge_active()
        inj.consume_wedge()
        assert not inj.wedge_active()
        assert inj.injected["wedge-wait"] == 1

    def test_wedge_rejects_min_rung(self):
        """A rung-filtered wedge could never consume its shot (the wait
        loop has no rung context) — a permanent outage masquerading as
        transient; arm() must refuse it."""
        inj = FaultInjector()
        with pytest.raises(ValueError):
            inj.arm("wedge-wait", shots=1, min_rung=RUNG_PALLAS)

    def test_wedged_probe_consumes_shot(self):
        """A wedge armed while the backend is demoted (no dispatch
        traffic) must be consumed by the probe's own timed-out wait, or
        the backend could never re-promote."""
        from kubernetes_tpu.scheduler.tpu_backend import TPUBackend

        b = TPUBackend()
        b.watchdog_timeout = 0.1
        inj = FaultInjector()
        b.faults = inj
        inj.arm("wedge-wait", shots=1)
        assert b._probe_device() is False  # wedged canary
        assert not inj.wedge_active()
        assert b._probe_device() is True  # shot consumed: device answers

    def test_corrupt_harvest_saturates_ints_and_nans_floats(self):
        import numpy as np

        inj = FaultInjector()
        inj.arm("nan-harvest", shots=1)
        ys = {"rows": np.zeros((8, 4), np.int32), "score": np.ones(4), "n": 2}
        bad = inj.corrupt_harvest(ys)
        assert bad["n"] == 2  # host scalars steer decode: untouched
        assert (np.asarray(bad["rows"]) == np.iinfo(np.int32).max).all()
        assert np.isnan(np.asarray(bad["score"])).all()
        # one shot: the next harvest is clean
        assert inj.corrupt_harvest(ys) is ys


# -- unit: the device wait ---------------------------------------------------


class _FakeLeaf:
    """A result leaf whose readiness the test controls: is_ready() and a
    block_until_ready() that really blocks (on an event, so it releases
    the interpreter as the runtime's does)."""

    def __init__(self, ready=False, raises=False):
        self._ready = threading.Event()
        self.raises = raises
        self.ready_at = None
        if ready:
            self.make_ready()

    def make_ready(self):
        self.ready_at = time.monotonic()
        self._ready.set()

    def ready_after(self, seconds):
        t = threading.Timer(seconds, self.make_ready)
        t.daemon = True
        t.start()
        return self

    def is_ready(self):
        return self._ready.is_set()

    def block_until_ready(self):
        self._ready.wait()
        if self.raises:
            raise RuntimeError("device said no")
        return self


def _wait_backend(monkeypatch):
    """A backend whose wait must not sleep: the poll is the fallback, and
    a case that should not take it fails if it does."""
    from kubernetes_tpu.scheduler import tpu_backend

    class NoSleep:
        def __getattr__(self, name):
            return getattr(time, name)

        @staticmethod
        def sleep(_s):
            raise AssertionError("the device wait polled")

    monkeypatch.setattr(tpu_backend, "_time", NoSleep())
    return tpu_backend.TPUBackend()


def _waits_delta(before):
    after = dict(metrics.device_waits.items())
    return {k[0]: int(v - before.get(k, 0)) for k, v in after.items()
            if v != before.get(k, 0)}


def _case_ready(b, monkeypatch):
    monkeypatch.setattr(
        b, "_take_waiter",
        lambda: pytest.fail("ready at the first look took a waiter"))
    before = dict(metrics.device_waits.items())
    assert b._wait_ready({"rows": _FakeLeaf(ready=True), "n": 3}, 1.0)
    assert not b._idle_waiters
    assert _waits_delta(before) == {"ready": 1}


def _case_woken(b, monkeypatch):
    before = dict(metrics.device_waits.items())
    lags = []
    for _ in range(5):
        leaf = _FakeLeaf().ready_after(0.005)
        assert b._wait_ready({"rows": leaf}, 1.0)
        lags.append(time.monotonic() - leaf.ready_at)
    # woken when the launch ends: the 2 ms poll looked at 0, 2.1, 4.3
    # and 6.4 ms and came back 1.4 ms late, every time
    assert sorted(lags)[len(lags) // 2] < 0.001, lags
    assert _waits_delta(before) == {"woken": 5}
    # one long-lived waiter served all five, and close() stops it
    (w,) = b._idle_waiters
    b.close()
    w.thread.join(timeout=2)
    assert not w.thread.is_alive() and not b._idle_waiters


def _case_timed_out(b, monkeypatch):
    before = dict(metrics.device_waits.items())
    stuck = _FakeLeaf()
    t0 = time.monotonic()
    assert b._wait_ready({"rows": stuck}, 0.05) is False
    assert 0.05 <= time.monotonic() - t0 < 0.5
    assert not b._idle_waiters  # the pinned waiter is left behind …
    pinned = [t for t in threading.enumerate()
              if t.name == "tpu-device-waiter"]
    # … and the next wait, on a healthy device, gets a fresh one
    assert b._wait_ready({"rows": _FakeLeaf().ready_after(0.005)}, 1.0)
    assert _waits_delta(before) == {"timed_out": 1, "woken": 1}
    assert b._wait_ready({"rows": _FakeLeaf()}, 0.0) is False  # no budget
    # the runtime lets go at last: the abandoned thread exits
    stuck.make_ready()
    for t in pinned:
        t.join(timeout=2)
    assert not any(t.is_alive() for t in pinned)


def _case_wedged(b, monkeypatch):
    from kubernetes_tpu.scheduler import tpu_backend

    monkeypatch.setattr(tpu_backend, "_time", time)
    inj = FaultInjector()
    b.faults = inj
    inj.arm("wedge-wait", shots=1)
    before = dict(metrics.device_waits.items())
    t0 = time.monotonic()
    assert b._wait_ready({"rows": _FakeLeaf(ready=True)}, 0.05) is False
    assert time.monotonic() - t0 >= 0.05
    assert inj.wedge_active(), "the wait consumed the wedge shot"
    assert _waits_delta(before) == {"timed_out": 1}
    # armed while the caller sleeps on the waiter's event: held too
    inj.disarm()
    leaf = _FakeLeaf()

    def arm_then_ready():
        inj.arm("wedge-wait", shots=1)
        leaf.make_ready()

    threading.Timer(0.005, arm_then_ready).start()
    assert b._wait_ready({"rows": leaf}, 0.05) is False
    assert inj.wedge_active()


def _case_raises(b, monkeypatch):
    before = dict(metrics.device_waits.items())
    leaf = _FakeLeaf(raises=True).ready_after(0.005)
    assert b._wait_ready({"rows": leaf}, 1.0)  # decode will surface it

    class Sick:
        def is_ready(self):
            raise RuntimeError("device said no")

    assert b._wait_ready({"rows": Sick()}, 1.0)
    assert _waits_delta(before) == {"woken": 1, "ready": 1}


def _case_concurrent(b, monkeypatch):
    # the completion worker and a locked flush wait at once, the flush
    # on the OLDER launch: neither stands behind the other
    before = dict(metrics.device_waits.items())
    first, second = _FakeLeaf(), _FakeLeaf()
    got = {}

    def wait(name, leaf):
        got[name] = b._wait_ready({"rows": leaf}, 2.0)

    ts = [threading.Thread(target=wait, args=(n, leaf))
          for n, leaf in (("worker", second), ("flush", first))]
    for t in ts:
        t.start()
    assert wait_until(lambda: sum(
        t.name == "tpu-device-waiter" for t in threading.enumerate()) >= 2)
    second.make_ready()  # out of order: its waiter is its own
    ts[0].join(timeout=2)
    assert got == {"worker": True}
    first.make_ready()
    ts[1].join(timeout=2)
    assert got == {"worker": True, "flush": True}
    assert _waits_delta(before) == {"woken": 2}
    assert len(b._idle_waiters) == 2


def _case_polled(b, monkeypatch):
    from kubernetes_tpu.scheduler import tpu_backend

    def no_thread():
        raise RuntimeError("can't start new thread")

    monkeypatch.setattr(tpu_backend, "_DeviceWaiter", no_thread)
    monkeypatch.setattr(tpu_backend, "_time", time)
    before = dict(metrics.device_waits.items())
    assert b._wait_ready({"rows": _FakeLeaf().ready_after(0.005)}, 1.0)
    assert b._wait_ready({"rows": _FakeLeaf()}, 0.02) is False
    assert _waits_delta(before) == {"polled": 1, "timed_out": 1}


def _case_dead_waiter(b, monkeypatch):
    # a waiter whose thread died is dropped, not handed work
    assert b._wait_ready({"rows": _FakeLeaf().ready_after(0.002)}, 1.0)
    (dead,) = b._idle_waiters
    dead.stop()
    dead.thread.join(timeout=2)
    b._idle_waiters.append(dead)
    assert b._wait_ready({"rows": _FakeLeaf().ready_after(0.002)}, 1.0)
    assert dead not in b._idle_waiters and len(b._idle_waiters) == 1


_WAIT_CASES = {
    "ready-first-look": _case_ready,
    "woken-when-done": _case_woken,
    "timed-out-then-fresh-waiter": _case_timed_out,
    "wedge-holds-and-keeps-shot": _case_wedged,
    "raising-wait-reads-ready": _case_raises,
    "two-threads-at-once": _case_concurrent,
    "no-thread-polls": _case_polled,
    "dead-waiter-replaced": _case_dead_waiter,
}


@pytest.mark.parametrize("case", sorted(_WAIT_CASES))
def test_device_wait(case, monkeypatch):
    """TPUBackend._wait_ready on leaves a test controls: told when a
    launch is done, bounded by the watchdog, never a fault of its own."""
    b = _wait_backend(monkeypatch)
    try:
        _WAIT_CASES[case](b, monkeypatch)
    finally:
        b.close()


class TestExecQuarantine:
    def test_retire_exec_pre_pins_fresh_cache(self):
        """A quarantined bucket must stay jit-only on a REBUILT session:
        retire_exec(bucket=...) pins entries that do not exist yet, and
        the serving/warm paths never recompile a pinned (None) entry."""
        from types import SimpleNamespace

        from kubernetes_tpu.ops.pallas_scan import PallasSession

        fresh = SimpleNamespace(_exec={})
        n = PallasSession.retire_exec(fresh, bucket=128)
        assert n == 1
        assert fresh._exec == {(128, "full"): None}
        # idempotent; other buckets untouched
        assert PallasSession.retire_exec(fresh, bucket=128) == 0
        live = SimpleNamespace(_exec={(256, "full"): object(),
                                      (128, "full"): object()})
        assert PallasSession.retire_exec(live, bucket=128) == 1
        assert live._exec[(128, "full")] is None
        assert live._exec[(256, "full")] is not None
        # blanket retirement pins every existing entry
        assert PallasSession.retire_exec(live) == 1
        assert live._exec[(256, "full")] is None

    def test_backend_tracks_and_lifts_suspect_buckets(self):
        from kubernetes_tpu.scheduler.tpu_backend import TPUBackend

        b = TPUBackend()
        b._device_fault_locked("invalid", buckets={128, None})
        assert b._suspect_buckets == {128}
        # a clean harvest of that bucket lifts the quarantine
        # (_harvest_locked discards on success — exercised end-to-end in
        # the parity tests; here the bookkeeping contract)
        b._suspect_buckets.discard(128)
        assert not b._suspect_buckets


class TestScheduleRetryPaths:
    def test_zero_feasible_still_raises_fit_error(self):
        """The watchdog/retry refactor must keep schedule()'s FitError
        contract intact: an unfittable pod gets per-node statuses, not a
        crash (regression: `out` once leaked into the nested attempt)."""
        from kubernetes_tpu.scheduler.framework.interface import FitError
        from kubernetes_tpu.scheduler.tpu_backend import TPUBackend

        from .util import make_node

        b = TPUBackend()
        for i in range(3):
            b.on_add_node(make_node(f"n-{i}", cpu="2", memory="4Gi"))
        giant = make_pod("giant", cpu="64", memory="1Gi")
        with pytest.raises(FitError) as e:
            b.schedule(giant)
        assert len(e.value.filtered_nodes_statuses) == 3

    def test_oracle_rung_raises_device_fault_without_dispatch(self):
        """At the oracle rung schedule()/reevaluate() must not touch the
        device at all — raise/RETRY immediately (the scheduler routes
        the pods through the oracle)."""
        from kubernetes_tpu.scheduler.degradation import DeviceFault
        from kubernetes_tpu.scheduler.tpu_backend import RETRY_NODE, TPUBackend

        from .util import make_node

        b = TPUBackend()
        b.on_add_node(make_node("n-0", cpu="8", memory="16Gi"))
        while b.ladder.demote():
            pass
        assert b.ladder.rung() == RUNG_ORACLE
        inj = FaultInjector()
        b.faults = inj
        inj.arm("raise-dispatch", shots=-1)  # would fire on any dispatch
        with pytest.raises(DeviceFault):
            b.schedule(make_pod("p", cpu="100m"))
        nodes = b.reevaluate([make_pod("q", cpu="100m")])
        assert nodes == [(RETRY_NODE, {})]
        assert not inj.injected, "device was dispatched at the oracle rung"


# -- unit: degradation ladder ----------------------------------------------


class TestDegradationLadder:
    def test_demotes_pallas_hoisted_oracle_and_repromotes(self):
        """The full ladder walk the acceptance criterion names, with the
        scheduler_backend_mode gauge tracking every transition."""
        ladder = DegradationLadder(top=RUNG_PALLAS, threshold=3)
        assert ladder.mode() == "pallas"
        assert metrics.backend_mode.value() == RUNG_PALLAS
        for expected in ("hoisted", "oracle"):
            demoted = [ladder.record_fault("raise") for _ in range(3)]
            assert demoted == [False, False, True]
            assert ladder.mode() == expected
            assert metrics.backend_mode.value() == ladder.rung()
        # already at the floor: more faults cannot demote further
        for _ in range(5):
            assert not ladder.record_fault("raise")
        assert ladder.mode() == "oracle" and ladder.demotions == 2
        # probe recovery is stepwise: oracle -> hoisted -> pallas
        assert ladder.on_probe(True) and ladder.mode() == "hoisted"
        assert ladder.on_probe(True) and ladder.mode() == "pallas"
        assert not ladder.on_probe(True)  # at top: no-op
        assert ladder.promotions == 2
        assert metrics.backend_mode.value() == RUNG_PALLAS

    def test_success_resets_consecutive_count(self):
        ladder = DegradationLadder(top=RUNG_HOISTED, threshold=2)
        assert not ladder.record_fault()
        ladder.record_success()
        assert not ladder.record_fault()  # count restarted: no demotion
        assert ladder.mode() == "hoisted"

    def test_failed_probe_backs_off_capped(self):
        ladder = DegradationLadder(
            top=RUNG_HOISTED, threshold=1, probe_interval=0.1, probe_max=0.4,
            rng=random.Random(0),
        )
        ladder.record_fault()
        delays = []
        for _ in range(4):
            delays.append(ladder.probe_delay())
            ladder.on_probe(False)
        # base delay doubles each failure, capped (jitter <= 2x base)
        assert delays[0] < delays[-1] <= 0.4 * 2
        # promotion does NOT restore the cadence (flap hysteresis: the
        # canary vouches for the device, not the kernel at the target
        # rung — a fault right after re-promotion must find the probe
        # still backed off) …
        ladder.on_probe(True)
        assert ladder.probe_delay() > 0.1 * 2
        # … only a clean harvest at the top rung does
        ladder.record_success()
        assert ladder.probe_delay() <= 0.1 * 2

    def test_flap_hysteresis_decays_to_probe_max(self):
        """Kernel-level fault invisible to the canary: demote → clean
        probe → promote → demote … — each demotion doubles the cadence,
        so the whipsaw decays to once per probe_max instead of spinning
        at probe_interval forever."""
        ladder = DegradationLadder(
            top=RUNG_HOISTED, threshold=1, probe_interval=0.1, probe_max=0.4,
            rng=random.Random(0),
        )
        for _ in range(4):  # flap cycles
            ladder.record_fault()
            assert ladder.on_probe(True)
        assert ladder.probe_delay() >= 0.4  # pinned at the cap


# -- fault parity: transient faults, exact-decision recovery ----------------


def _drive_with_faults(seed, arm_plan, n=32, watchdog=0.5):
    """Run the same pod stream at depth 0 (clean) and depth 2 (faults
    armed per `arm_plan`: batch_index -> (kind, shots kwargs)); return
    both bound maps plus the injector."""
    rng = random.Random(seed)
    batch_sizes = [rng.choice([2, 3, 5]) for _ in range(32)]
    maps = {}
    inj = None
    for depth in (0, 2):
        _, cs = _cluster()
        sched = _mk_scheduler(cs, depth)
        try:
            if depth:
                inj = FaultInjector()
                sched.install_fault_injector(inj)
                sched.tpu.watchdog_timeout = watchdog
                orig = type(sched.tpu).dispatch_many
                count = {"batches": 0}

                def arming(self, pods, _orig=orig, _c=count, _inj=inj, **kw):
                    kind = arm_plan.get(_c["batches"])
                    if kind is not None:
                        _inj.arm(kind, shots=1)
                    _c["batches"] += 1
                    return _orig(self, pods, **kw)

                sched.tpu.dispatch_many = arming.__get__(sched.tpu)
            pods = _pod_stream(random.Random(seed), n)
            _drive(sched, cs, pods, batch_sizes)
            maps[depth] = _bound_map(cs)
        finally:
            sched.shutdown()
            sched.informers.stop()
    return maps, inj


class TestFaultParity:
    def test_raise_dispatch_recovers_bit_identical(self):
        before = _counter_snapshot()
        maps, inj = _drive_with_faults(3, {1: "raise-dispatch"})
        assert inj.injected.get("raise-dispatch", 0) >= 1
        assert maps[0] == maps[2], "raise-recovery changed decisions"
        assert _fault_delta(before, "raise") >= 1

    def test_nan_harvest_detected_and_recovered(self):
        """Garbage payloads must be caught by the validation guard BEFORE
        assume — silently corrupt placements are the worst outcome."""
        before = _counter_snapshot()
        maps, inj = _drive_with_faults(4, {2: "nan-harvest"})
        assert inj.injected.get("nan-harvest", 0) >= 1
        assert maps[0] == maps[2], "NaN harvest leaked into decisions"
        assert _fault_delta(before, "invalid") >= 1

    def test_wedged_wait_hits_watchdog_and_recovers(self):
        before = _counter_snapshot()
        maps, inj = _drive_with_faults(5, {1: "wedge-wait"}, watchdog=0.3)
        assert inj.injected.get("wedge-wait", 0) >= 1
        assert maps[0] == maps[2], "wedge recovery changed decisions"
        assert _fault_delta(before, "timeout") >= 1

    def test_fault_storm_parity(self):
        """Rotating transient faults across the stream: in-order
        synchronous re-drive keeps exact decision parity."""
        plan = {1: "raise-dispatch", 3: "nan-harvest", 5: "wedge-wait",
                7: "raise-dispatch"}
        before = _counter_snapshot()
        maps, inj = _drive_with_faults(6, plan, n=40, watchdog=0.3)
        assert sum(inj.injected.values()) >= 3
        assert maps[0] == maps[2]
        assert metrics.dispatch_retries.value() > before["retries"]
        # transient faults spaced out by clean batches never demote
        # (consecutive-fault accounting resets on every clean harvest)


# -- supervised workers ------------------------------------------------------


class TestSupervisedWorkers:
    def test_completion_worker_kill_drains_fifo_and_restarts(self):
        """Kill the completion worker mid-stream: the supervisor drains
        the in-flight FIFO back to the queue, restarts the worker, and
        every schedulable pod still binds exactly once (same bound SET
        as the clean reference; placements may legally differ because
        requeued pods re-enter in a different order)."""
        seed = 11
        rng = random.Random(seed)
        batch_sizes = [rng.choice([2, 3, 5]) for _ in range(32)]
        sets = {}
        before = _counter_snapshot()
        for depth in (0, 2):
            _, cs = _cluster()
            sched = _mk_scheduler(cs, depth)
            try:
                if depth:
                    inj = FaultInjector()
                    sched.install_fault_injector(inj)
                    orig = type(sched.tpu).dispatch_many
                    count = {"batches": 0}

                    def arming(self, pods, _orig=orig, _c=count, _inj=inj, **kw):
                        if _c["batches"] == 2:
                            _inj.arm("kill-completion", shots=1)
                        _c["batches"] += 1
                        return _orig(self, pods, **kw)

                    sched.tpu.dispatch_many = arming.__get__(sched.tpu)
                pods = _pod_stream(random.Random(seed), 32)
                _drive(sched, cs, pods, batch_sizes)
                if depth:
                    # requeued pods from the drained FIFO: keep popping
                    # until the queue is quiet again
                    deadline = time.monotonic() + 30
                    while time.monotonic() < deadline:
                        if not sched.schedule_one(timeout=0.2):
                            break
                    assert sched._drain_pipeline(timeout=30)
                    assert inj.injected.get("kill-completion", 0) == 1
                bound = _bound_map(cs)
                sets[depth] = {k for k, v in bound.items() if v}
            finally:
                sched.shutdown()
                sched.informers.stop()
        assert sets[0] == sets[2], "worker kill lost or duplicated pods"
        assert _restart_delta(before, "completion") >= 1

    def test_scheduler_thread_kill_restarts_and_schedules(self):
        before = _counter_snapshot()
        _, cs = _cluster()
        sched = _mk_scheduler(cs, 2)
        try:
            inj = FaultInjector()
            sched.install_fault_injector(inj)
            sched.start()
            inj.arm("kill-scheduler", shots=1)
            assert wait_until(
                lambda: inj.injected.get("kill-scheduler", 0) == 1, 10
            ), "kill never fired"
            for i in range(8):
                cs.pods.create(make_pod(
                    f"p-{i}", namespace="default", cpu="100m",
                    labels={"app": "plain"},
                ))
            assert wait_until(
                lambda: all(_bound_map(cs).values()) and len(_bound_map(cs)) == 8,
                30,
            ), f"pods not scheduled after restart: {_bound_map(cs)}"
            assert _restart_delta(before, "scheduler") >= 1
        finally:
            sched.shutdown()
            sched.informers.stop()


# -- degradation ladder end-to-end ------------------------------------------


class TestLadderIntegration:
    def test_demote_to_oracle_then_repromote(self):
        """Persistent dispatch faults walk the backend down to the
        oracle rung (scheduling continues!); disarming the fault lets
        the background probe re-promote — asserted through the
        scheduler_backend_mode gauge and the fault/retry counters, per
        the acceptance criteria."""
        before = _counter_snapshot()
        _, cs = _cluster()
        sched = _mk_scheduler(cs, 2)
        try:
            inj = FaultInjector()
            sched.install_fault_injector(inj)
            tpu = sched.tpu
            tpu.watchdog_timeout = 0.5
            tpu.retry_base = 0.01
            tpu.ladder.threshold = 2
            tpu.ladder._probe_interval = 0.05
            tpu.ladder._probe_delay = 0.05
            assert tpu.ladder.rung() == RUNG_HOISTED  # CPU top rung
            inj.arm("raise-dispatch", shots=-1)  # persistent device fault
            sched.start()
            for i in range(8):
                cs.pods.create(make_pod(
                    f"p-{i}", namespace="default", cpu="100m",
                    labels={"app": "plain"},
                ))
            # the ladder must hit the oracle rung and STILL schedule
            assert wait_until(
                lambda: tpu.ladder.rung() == RUNG_ORACLE, 30
            ), "never demoted to oracle"
            assert metrics.backend_mode.value() == RUNG_ORACLE
            assert wait_until(
                lambda: all(_bound_map(cs).values()) and len(_bound_map(cs)) == 8,
                30,
            ), f"oracle rung failed to bind: {_bound_map(cs)}"
            assert _fault_delta(before, "raise") >= 2
            assert metrics.dispatch_retries.value() > before["retries"]
            assert tpu.ladder.demotions >= 1
            # device heals: the probe must re-promote to the top rung
            inj.disarm("raise-dispatch")
            assert wait_until(
                lambda: tpu.ladder.rung() == RUNG_HOISTED, 30
            ), "probe never re-promoted"
            assert metrics.backend_mode.value() == RUNG_HOISTED
            assert tpu.ladder.promotions >= 1
            # and the kernel path serves again at the restored rung
            for i in range(8, 12):
                cs.pods.create(make_pod(
                    f"p-{i}", namespace="default", cpu="100m",
                    labels={"app": "plain"},
                ))
            assert wait_until(
                lambda: all(_bound_map(cs).values()) and len(_bound_map(cs)) == 12,
                30,
            )
        finally:
            sched.shutdown()
            sched.informers.stop()


# -- drain timeout + shutdown ------------------------------------------------


class TestDrainAndShutdown:
    def test_drain_pipeline_times_out_and_demotes(self):
        """A wedge that outlives even the watchdog budget must not hang
        _drain_pipeline (the oracle/nominated paths run through it):
        it demotes and raises instead."""
        _, cs = _cluster()
        sched = _mk_scheduler(cs, 2)
        try:
            inj = FaultInjector()
            sched.install_fault_injector(inj)
            sched.tpu.watchdog_timeout = 60  # wedge outlives the drain
            for i in range(4):
                cs.pods.create(make_pod(
                    f"p-{i}", namespace="default", cpu="100m",
                    labels={"app": "plain"},
                ))
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and sched.queue.num_active() < 4:
                time.sleep(0.02)
            infos = []
            while True:
                nxt = sched.queue.pop(timeout=0)
                if nxt is None:
                    break
                infos.append(nxt)
            # first batch rides the sync path and builds the session …
            sched._schedule_batch_tpu(infos[:2])
            assert sched._drain_pipeline(timeout=30)
            # … the second is a genuinely async dispatch that wedges
            inj.arm("wedge-wait", shots=-1)
            sched._schedule_batch_tpu(infos[2:])
            rung_before = sched.tpu.ladder.rung()
            with pytest.raises(PipelineStalled):
                sched._drain_pipeline(timeout=0.5)
            assert sched.tpu.ladder.rung() < rung_before
        finally:
            inj.disarm()
            sched.tpu.watchdog_timeout = 0.5
            sched.shutdown()
            sched.informers.stop()

    def test_shutdown_joins_workers_and_flushes_fifo(self):
        _, cs = _cluster()
        sched = _mk_scheduler(cs, 2)
        sched.start()
        try:
            for i in range(12):
                cs.pods.create(make_pod(
                    f"p-{i}", namespace="default", cpu="100m",
                    labels={"app": "plain"},
                ))
            assert wait_until(
                lambda: len(_bound_map(cs)) == 12 and
                all(_bound_map(cs).values()), 30)
        finally:
            assert sched.shutdown() is True
            sched.informers.stop()
        assert not sched._completions, "pending FIFO not flushed"
        for t in (sched._thread, sched._completion_thread,
                  sched._permit_thread):
            assert t is None or not t.is_alive(), f"leaked thread {t}"
        probe = sched.tpu._probe_thread
        assert probe is None or not probe.is_alive(), "leaked probe thread"
        assert sched.shutdown() is True  # idempotent


# -- what-if (device preemption planner) fault drills -----------------------


class TestWhatifFaults:
    """PR-7 drill: a device fault MID-WHAT-IF falls the preemptor one
    planner rung (device -> fast) with zero double-claimed victims, a
    clean BindIntegrityChecker, and ZERO live-session invalidations —
    the what-if runs on a scratch snapshot, so planning must never
    charge the session-rebuild counter."""

    def _preemption_cluster(self):
        from kubernetes_tpu.apiserver import APIServer
        from kubernetes_tpu.client import Clientset, SharedInformerFactory
        from kubernetes_tpu.scheduler.scheduler import Scheduler
        from kubernetes_tpu.testing.synth import make_node

        api = APIServer()
        cs = Clientset(api)
        cs.nodes.create(make_node("n0", cpu="4", pods=10))
        for j in range(4):
            cs.pods.create(make_pod(
                f"low{j}", namespace="default", cpu="900m", memory="64Mi",
                priority=1,
            ))
        factory = SharedInformerFactory(cs)
        sched = Scheduler(cs, factory, backend="tpu",
                          pod_initial_backoff=30.0, pod_max_backoff=30.0)
        sched.tpu.whatif = True  # platform default is off on CPU
        factory.start()
        assert factory.wait_for_cache_sync()
        return cs, factory, sched

    def _run_drill(self, arm_fault: bool):
        from kubernetes_tpu.testing.faults import BindIntegrityChecker

        cs, factory, sched = self._preemption_cluster()
        checker = BindIntegrityChecker().attach(
            factory.informer_for("pods"))
        inj = FaultInjector()
        sched.install_fault_injector(inj)
        sched.start()
        try:
            assert wait_until(
                lambda: sum(
                    1 for p in cs.pods.list(namespace="default")[0]
                    if p.spec.node_name
                ) == 4,
                timeout=30,
            ), "low pods did not bind"
            rebuilds0 = sum(
                v for _, v in metrics.session_rebuilds.items())
            paths0 = dict(metrics.preemption_planner.items())
            fb0 = dict(metrics.whatif_fallbacks.items())
            if arm_fault:
                inj.arm("raise-whatif", shots=1)
            hi = make_pod("hi", namespace="default", cpu="900m",
                          memory="64Mi", priority=100)
            cs.pods.create(hi)
            assert wait_until(
                lambda: bool(
                    cs.pods.get("hi", "default").spec.node_name),
                timeout=20,
            ), "preemptor did not bind"
            assert cs.pods.get("hi", "default").spec.node_name == "n0"
            # exactly one victim evicted (no double-claim): 3 low pods
            # survive bound
            pods, _ = cs.pods.list(namespace="default")
            survivors = [
                p for p in pods
                if p.metadata.name.startswith("low") and p.spec.node_name
            ]
            assert len(survivors) == 3
            assert checker.violations == []
            # planning never tore the live session down
            assert sum(
                v for _, v in metrics.session_rebuilds.items()
            ) == rebuilds0
            paths = {
                k: v - paths0.get(k, 0)
                for k, v in metrics.preemption_planner.items()
                if v - paths0.get(k, 0)
            }
            fb = {
                k: v - fb0.get(k, 0)
                for k, v in metrics.whatif_fallbacks.items()
                if v - fb0.get(k, 0)
            }
            return paths, fb, inj
        finally:
            sched.stop()
            factory.stop()

    def test_clean_run_plans_on_device_without_rebuilds(self):
        paths, fb, _ = self._run_drill(arm_fault=False)
        assert paths.get(("device",), 0) >= 1, paths
        assert not fb, fb

    def test_injected_fault_falls_one_rung_cleanly(self):
        before = _counter_snapshot()
        paths, fb, inj = self._run_drill(arm_fault=True)
        assert inj.injected.get("raise-whatif") == 1
        assert fb.get(("fault",), 0) >= 1, fb
        assert paths.get(("fast",), 0) >= 1, paths
        # the fault is a real device fault to the ladder/counters
        assert _fault_delta(before, "raise") >= 1


# -- flight-recorder dump-on-fault drills (observability PR) ----------------
# The fault seams must leave a TRIAGEABLE record, not just counters: a
# watchdog timeout / validation fault dumps the ring (with the faulted
# batch's bucket/rung/speculation state in the fault attrs and the
# faulted dispatch's spans in the events) BEFORE recovery proceeds, and
# the recovery re-drive itself lands in the ring after. With KTPU_TRACE=0
# the dispatch path allocates nothing for tracing (the overhead pin).


class TestFlightRecorderDumpDrills:
    @pytest.fixture(autouse=True)
    def _traced(self):
        from kubernetes_tpu.utils import tracing

        old = tracing.set_level(tracing.TRACE_PODS)
        tracing.RECORDER.clear()
        yield
        tracing.set_level(old)
        tracing.RECORDER.clear()

    def _dump_drill(self, seed, kind, watchdog=0.5):
        from kubernetes_tpu.utils import tracing

        h0 = len(tracing.RECORDER.dump_history)
        dumps0 = sum(v for _, v in metrics.trace_dumps.items())
        maps, inj = _drive_with_faults(seed, {1: kind}, watchdog=watchdog)
        assert inj.injected.get(kind, 0) >= 1
        assert maps[0] == maps[2], "fault recovery changed decisions"
        new_dumps = tracing.RECORDER.dump_history[h0:]
        assert sum(v for _, v in metrics.trace_dumps.items()) > dumps0
        return maps, new_dumps

    def test_wedge_dump_names_faulted_batch_and_redrives(self):
        from kubernetes_tpu.utils import tracing

        _, dumps = self._dump_drill(11, "wedge-wait", watchdog=0.3)
        timeout_dumps = [
            d for d in dumps if d["reason"] == "device-fault-timeout"
        ]
        assert timeout_dumps, "watchdog fault fired without a dump"
        d = timeout_dumps[0]
        # the dump names the faulted batch's bucket, rung, speculation
        assert d["attrs"]["kind"] == "timeout"
        assert d["attrs"]["rung"] in ("pallas", "hoisted", "oracle")
        assert "speculative" in d["attrs"] and "bucket" in d["attrs"]
        stages = {e["stage"] for e in d["events"]}
        assert "dispatch" in stages, "faulted dispatch's spans missing"
        assert any(
            e["stage"] == "fault" and e.get("kind") == "timeout"
            for e in d["events"]
        )
        # the recovery re-drive is recorded after the dump: a final
        # snapshot holds the synchronous replay span, and the snapshot
        # itself lands in the dump history like any other dump
        events = tracing.RECORDER.dump("drill-final")
        assert any(
            e[2] == "replay" and e[1] == "re-drive"
            and e[6] and e[6].get("kind") == "timeout"
            for e in events
        ), "recovery re-drive span missing from the record"
        assert tracing.RECORDER.dump_history[-1]["reason"] == "drill-final"

    def test_nan_harvest_dump_fires_on_validation_fault(self):
        _, dumps = self._dump_drill(4, "nan-harvest")
        invalid = [
            d for d in dumps if d["reason"] == "device-fault-invalid"
        ]
        assert invalid, "validation fault fired without a dump"
        assert invalid[0]["attrs"]["kind"] == "invalid"
        assert "rung" in invalid[0]["attrs"]
        stages = {e["stage"] for e in invalid[0]["events"]}
        assert "dispatch" in stages

    def test_disabled_trace_adds_no_per_pod_state_on_dispatch(self):
        """KTPU_TRACE=0 overhead pin: the dispatch path must not
        allocate tracing state — span() returns the shared no-op
        singleton, handles carry prov=None, the ring stays empty, and
        no dump fires on a clean run."""
        from kubernetes_tpu.utils import tracing

        tracing.set_level(0)
        tracing.RECORDER.clear()
        h0 = len(tracing.RECORDER.dump_history)
        assert tracing.span("dispatch", "dispatch", n=8) \
            is tracing.NOOP_SPAN
        assert tracing.span("harvest", "harvest") is tracing.NOOP_SPAN
        _, cs = _cluster()
        sched = _mk_scheduler(cs, 2)
        handles = []
        orig = type(sched.tpu).dispatch_many

        def capture(self, pods, _orig=orig, **kw):
            h = _orig(self, pods, **kw)
            handles.append(h)
            return h

        sched.tpu.dispatch_many = capture.__get__(sched.tpu)
        try:
            pods = _pod_stream(random.Random(3), 16)
            _drive(sched, cs, pods, [4, 4, 4, 4])
        finally:
            sched.shutdown()
            sched.informers.stop()
        assert handles, "no batches dispatched"
        assert all(h.prov is None for h in handles)
        assert tracing.RECORDER.snapshot() == []
        assert len(tracing.RECORDER.dump_history) == h0
