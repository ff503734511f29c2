"""Pipelined-vs-sequential parity gate for the scheduling loop.

The pipelined loop (scheduler.py pipeline_depth >= 1: double-buffered
device dispatch + the async completion/bind worker) must produce
BIT-IDENTICAL binding decisions to the sequential depth-0 path on the
same pod stream — the acceptance gate for the kernel-to-loop pipeline
work. Randomized churn: mixed templates (PTS spread terms make decisions
depend on the assumed-count carry, so ordering bugs surface as different
placements), permanently-unschedulable pods failing mid-stream, ragged
randomized batch boundaries, and a mid-stream foreign cluster mutation
that tears the session down while batches are still in flight.
"""

from __future__ import annotations

import copy
import random
import threading
import time

import jax
import pytest

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.apiserver import APIServer
from kubernetes_tpu.client import Clientset, SharedInformerFactory
from kubernetes_tpu.ops.hoisted import HoistedSession, template_fingerprint
from kubernetes_tpu.ops.pallas_scan import PallasSession
from kubernetes_tpu.ops.sharded_scan import ShardedPallasSession
from kubernetes_tpu.parallel.sharded import make_mesh
from kubernetes_tpu.scheduler import metrics
from kubernetes_tpu.scheduler.internal.cache import SchedulerCache
from kubernetes_tpu.scheduler.scheduler import Scheduler
from kubernetes_tpu.scheduler.tpu_backend import TPUBackend
from kubernetes_tpu.testing.faults import BindIntegrityChecker, FaultInjector
from kubernetes_tpu.testing.oracle import first_max_decisions

from .util import make_node, make_pod, spread_constraint


def _cluster(n_nodes=8):
    api = APIServer()
    cs = Clientset(api)
    for i in range(n_nodes):
        cs.nodes.create(make_node(
            f"node-{i}",
            cpu=str(4 + (i % 3) * 2), memory="16Gi", pods=64,
            labels={v1.LABEL_HOSTNAME: f"node-{i}", "zone": f"z{i % 3}"},
        ))
    return api, cs


def _mk_scheduler(cs, depth):
    factory = SharedInformerFactory(cs)
    sched = Scheduler(cs, factory, backend="tpu", pipeline_depth=depth)
    factory.start()
    assert factory.wait_for_cache_sync()
    return sched


def _pod_stream(rng: random.Random, n: int):
    """Deterministic randomized churn stream: three templates, one of
    them permanently unschedulable."""
    pods = []
    for i in range(n):
        kind = rng.random()
        if kind < 0.5:
            pods.append(make_pod(
                f"p-{i}", namespace="default", cpu="200m", memory="128Mi",
                labels={"app": "spread"},
                constraints=[spread_constraint(
                    1, "zone", "ScheduleAnyway", {"app": "spread"})],
            ))
        elif kind < 0.85:
            pods.append(make_pod(
                f"p-{i}", namespace="default", cpu="500m", memory="256Mi",
                labels={"app": "plain"},
            ))
        else:
            # can never fit: fails, parks in the unschedulable queue
            pods.append(make_pod(
                f"p-{i}", namespace="default", cpu="64", memory="1Gi",
                labels={"app": "hungry"},
            ))
    return pods


def _drive(sched, cs, pods, batch_sizes, mutate_at=None):
    """Create the pods, then pop + dispatch them through
    _schedule_batch_tpu in the given batch partition — the same pod
    stream and the same batch boundaries for every scheduler under
    comparison; only the pipeline depth differs. `mutate_at` injects a
    foreign cluster mutation (a directly-bound pod) after that many
    batches, while the pipelined scheduler still has dispatches in
    flight."""
    for p in pods:
        cs.pods.create(p)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if sched.queue.num_active() >= len(pods):
            break
        time.sleep(0.02)
    n_batches = 0
    sizes = list(batch_sizes)
    while True:
        info = sched.queue.pop(timeout=0.2)
        if info is None:
            break
        infos = [info]
        want = sizes.pop(0) if sizes else 4
        while len(infos) < want:
            nxt = sched.queue.pop(timeout=0)
            if nxt is None:
                break
            infos.append(nxt)
        sched._schedule_batch_tpu(infos)
        n_batches += 1
        if mutate_at is not None and n_batches == mutate_at:
            # foreign mutation: an externally-bound pod lands in the
            # cache via the informer and invalidates the live session
            # while the pipeline still holds undispatched completions
            squatter = make_pod(
                "squatter", namespace="default", cpu="1", memory="512Mi",
                node_name="node-0", labels={"app": "foreign"},
            )
            cs.pods.create(squatter)
            mdl = time.monotonic() + 10
            while time.monotonic() < mdl:
                if sched.cache.has_pod("default/squatter"):
                    break
                time.sleep(0.01)
    # land every completion, then wait for the binder pool to drain
    # (wait_idle won't do: churn pods park in the unschedulable queue
    # forever by design, and pending_pods() counts them)
    assert sched._drain_pipeline(timeout=30)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        with sched._inflight_lock:
            if sched._inflight == 0:
                break
        time.sleep(0.02)
    else:
        raise AssertionError("binder pool did not drain")


def _bound_map(cs):
    pods, _ = cs.pods.list(namespace="default")
    return {
        p.metadata.name: p.spec.node_name
        for p in pods if p.metadata.name.startswith("p-")
    }


def _maps_by_depth(seed, n, batch_sizes, mutate_at=None):
    """{depth: bound map} of the same stream and batch partition through
    a depth-0 and a depth-2 scheduler."""
    maps = {}
    for depth in (0, 2):
        _, cs = _cluster()
        sched = _mk_scheduler(cs, depth)
        try:
            pods = _pod_stream(random.Random(seed), n)
            _drive(sched, cs, pods, batch_sizes, mutate_at=mutate_at)
            maps[depth] = _bound_map(cs)
        finally:
            sched.stop()
            sched.informers.stop()
    return maps


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pipelined_matches_sequential(seed):
    rng = random.Random(seed)
    n = rng.randint(24, 48)
    batch_sizes = [rng.choice([1, 2, 3, 5, 8]) for _ in range(64)]
    maps = _maps_by_depth(seed, n, batch_sizes)
    assert maps[0] == maps[2], (
        "pipelined decisions diverged from the sequential path"
    )
    # the stream must actually exercise churn: some bound, some not
    assert any(maps[0].values())
    hungry_unbound = [k for k, nd in maps[0].items() if not nd]
    assert hungry_unbound, "stream produced no failures — churn untested"


def test_pipelined_matches_sequential_with_foreign_mutation():
    """A mid-stream session teardown (foreign bound pod) with batches in
    flight must not change any decision: the in-flight batches' decode
    was captured at dispatch, and the encoding applies decisions in
    dispatch order either way."""
    seed = 7
    rng = random.Random(seed)
    batch_sizes = [rng.choice([2, 3, 5]) for _ in range(32)]
    maps = _maps_by_depth(seed, 32, batch_sizes, mutate_at=2)
    assert maps[0] == maps[2]


# -- columnar cache A/B (round 14) -------------------------------------------


def _counter_total(counter) -> float:
    return sum(val for _, val in counter.items())


@pytest.mark.parametrize("seed", [0, 3])
def test_columnar_cache_matches_object_path(seed, monkeypatch):
    """KTPU_COLUMNAR_CACHE A/B through the FULL pipelined loop: the
    batched columnar assume (single delta-apply + batched listener
    echo + swap_pod_object fast path) vs the per-pod object writeback
    must produce bit-identical bindings over randomized churn. Run at
    depth 2 so the completion worker, speculation, and the batched
    bind fan-out are all on the measured path."""
    rng = random.Random(seed)
    n = rng.randint(24, 48)
    batch_sizes = [rng.choice([1, 2, 3, 5, 8]) for _ in range(64)]
    maps = {}
    for mode in ("0", "1"):
        monkeypatch.setenv("KTPU_COLUMNAR_CACHE", mode)
        _, cs = _cluster()
        sched = _mk_scheduler(cs, 2)
        assert sched.cache.columnar is (mode == "1")
        try:
            pods = _pod_stream(random.Random(seed), n)
            _drive(sched, cs, pods, batch_sizes)
            maps[mode] = _bound_map(cs)
        finally:
            sched.stop()
            sched.informers.stop()
    assert maps["0"] == maps["1"], (
        "columnar cache decisions diverged from the object path"
    )
    assert any(maps["0"].values())


def test_columnar_zero_drift_at_sample_rate(monkeypatch):
    """Acceptance gate: with the columnar audit view feeding the shadow
    sentinel at sample rate 0.1, a churn stream must audit without a
    single parity drift — the cheap O(changed) clone snapshot must be
    oracle-equivalent to the dump()-rebuilt one."""
    monkeypatch.setenv("KTPU_COLUMNAR_CACHE", "1")
    seed = 21
    rng = random.Random(seed)
    batch_sizes = [rng.choice([2, 3, 5]) for _ in range(64)]
    _, cs = _cluster()
    sched = _mk_scheduler(cs, 2)
    sched.tpu.set_shadow_sample(0.1)
    samples0 = _counter_total(metrics.shadow_samples)
    drift0 = _counter_total(metrics.parity_drift)
    try:
        pods = _pod_stream(random.Random(seed), 48)
        _drive(sched, cs, pods, batch_sizes)
    finally:
        sched.stop()
        sched.informers.stop()
    audited = _counter_total(metrics.shadow_samples) - samples0
    assert audited > 0, "sample rate 0.1 never fired — gate untested"
    assert _counter_total(metrics.parity_drift) - drift0 == 0, (
        "columnar audit view drifted from the oracle replay"
    )


# -- speculative dispatch (round 9) ------------------------------------------


def _label_counts(counter):
    out = {}
    for key, val in counter.items():
        slug = key[0] if key else "-"
        out[slug] = out.get(slug, 0) + int(val)
    return out


def _spec_counts():
    return _label_counts(metrics.speculative_dispatches)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_speculation_matches_depth0(seed):
    """Speculative pipelining (depth 2: scans chained on un-harvested
    carries) vs the depth-0 reference over randomized churn in SMALL
    batches (many launches in flight behind one another): decisions
    must be bit-identical, end to end through the scheduler loop."""
    rng = random.Random(100 + seed)
    n = rng.randint(32, 56)
    batch_sizes = [rng.choice([1, 2, 3]) for _ in range(96)]
    maps = _maps_by_depth(100 + seed, n, batch_sizes)
    assert maps[0] == maps[2], (
        "speculative decisions diverged from the depth-0 reference"
    )
    assert any(maps[0].values())


def test_speculation_kill_switch(monkeypatch):
    """KTPU_SPECULATION=0: no dispatch ever chains on a not-yet-
    harvested carry (every handle leaves dispatch_many non-speculative)
    and decisions still match the depth-0 reference."""
    seed = 11
    rng = random.Random(seed)
    batch_sizes = [rng.choice([2, 3, 5]) for _ in range(32)]
    maps = {}
    for depth in (0, 2):
        if depth:
            monkeypatch.setenv("KTPU_SPECULATION", "0")
        _, cs = _cluster()
        sched = _mk_scheduler(cs, depth)
        spec_flags = []
        if depth:
            assert sched.tpu.speculation is False
            orig = type(sched.tpu).dispatch_many

            def spy(self, pods, _orig=orig, _f=spec_flags, **kw):
                h = _orig(self, pods, **kw)
                _f.append(h.speculative)
                return h

            sched.tpu.dispatch_many = spy.__get__(sched.tpu)
        try:
            pods = _pod_stream(random.Random(seed), 32)
            _drive(sched, cs, pods, batch_sizes)
            maps[depth] = _bound_map(cs)
        finally:
            sched.stop()
            sched.informers.stop()
    assert maps[0] == maps[2]
    assert spec_flags and not any(spec_flags), (
        f"speculation off but a dispatch chained on an unharvested "
        f"carry: {spec_flags}"
    )


def _mini_backend(node_cpus, reserve=256):
    """Cache + backend with the given per-node cpu sizes (no apiserver:
    these tests pin BACKEND-level pipeline semantics)."""
    cache = SchedulerCache()
    be = TPUBackend()
    cache.add_listener(be)
    for i, cpu in enumerate(node_cpus):
        cache.add_node(make_node(
            f"node-{i}", cpu=cpu, memory="16Gi", pods=64,
            labels={v1.LABEL_HOSTNAME: f"node-{i}"},
        ))
    be.enc.reserve(pods=reserve)
    return cache, be


def _encode(be, pods):
    return [
        {k: v for k, v in be.pe.encode(p).items() if not k.startswith("_")}
        for p in pods
    ]


# -- directed two-pod cases: the second pod's answer depends on the first's
# commit, so a step that read a stale carry gets it wrong ---------------------


def _hostname_node(i, cpu, memory):
    return make_node(f"node-{i}", cpu=cpu, memory=memory, pods=64,
                     labels={v1.LABEL_HOSTNAME: f"node-{i}"})


def _case_last_slot():
    """Two pods of one spec race for the one free slot on the best
    node: the second must come back unschedulable, not double-booked."""
    nodes = [_hostname_node(0, "3", "16Gi"), _hostname_node(1, "1", "16Gi")]
    pending = [make_pod(f"race-{i}", cpu="2", memory="128Mi",
                        labels={"app": "race"}) for i in range(2)]
    return nodes, [], pending, ["node-0", None]


def _case_overtake():
    """The first pod lands on a node the second would NOT have picked,
    and rebalances its cpu/mem fractions enough (BalancedAllocation)
    that the node overtakes the second pod's stale winner."""
    nodes = [_hostname_node(i, "10", "10Gi") for i in range(2)]
    # node-0 cpu-heavy and mem-empty (poor balanced score); node-1
    # balanced and slightly fuller: a tiny pod alone picks node-1
    bound = [
        make_pod("fill0", cpu="4", memory="1Mi", labels={"app": "f"},
                 node_name="node-0"),
        make_pod("fill1", cpu="4300m", memory="4400Mi", labels={"app": "f"},
                 node_name="node-1"),
    ]
    pending = [
        make_pod("big", cpu="50m", memory="4Gi", labels={"app": "x"}),
        make_pod("small", cpu="100m", memory="100Mi", labels={"app": "y"}),
    ]
    return nodes, bound, pending, ["node-0", "node-0"]


def _case_term_behind_plain():
    """A plain pod of a service, then a pod of the same service that
    carries required hostname anti-affinity against it: the term pod
    must see the plain pod's commit and leave the best node to it."""
    nodes = [_hostname_node(0, "8", "16Gi"), _hostname_node(1, "4", "16Gi")]
    anti = v1.Affinity(pod_anti_affinity=v1.PodAntiAffinity(
        required_during_scheduling_ignored_during_execution=[
            v1.PodAffinityTerm(
                label_selector=v1.LabelSelector(match_labels={"app": "svc"}),
                topology_key=v1.LABEL_HOSTNAME)]))
    pending = [
        make_pod("plain", cpu="100m", memory="64Mi", labels={"app": "svc"}),
        make_pod("guarded", cpu="100m", memory="64Mi", labels={"app": "svc"},
                 affinity=anti),
    ]
    return nodes, [], pending, ["node-0", "node-1"]


_DIRECTED = {
    "last-slot": _case_last_slot,
    "overtake": _case_overtake,
    "term-behind-plain": _case_term_behind_plain,
}


def _directed_backend(nodes, bound):
    be = TPUBackend(pallas_interpret=True)
    be.enc.set_cluster(copy.deepcopy(nodes), copy.deepcopy(bound))
    be.enc.reserve(pods=64, anti_terms=64)
    return be


def _directed_oracle(nodes, bound, pending):
    return first_max_decisions(
        copy.deepcopy(nodes), copy.deepcopy(bound), copy.deepcopy(pending))


def _build_session(kind, cluster, templates, weights):
    if kind == "hoisted":
        return HoistedSession(cluster, templates, weights)
    if kind == "pallas":
        return PallasSession(cluster, templates, weights, interpret=True)
    nsh = int(kind.split("-")[1])
    if len(jax.devices()) < nsh:
        pytest.skip(f"needs {nsh} virtual devices")
    return ShardedPallasSession(cluster, templates, weights,
                                mesh=make_mesh(n_devices=nsh))


@pytest.mark.parametrize("case", sorted(_DIRECTED))
@pytest.mark.parametrize(
    "kind", ["hoisted", "pallas", "sharded-2", "sharded-4", "sharded-8"])
def test_directed_pair_matches_oracle(kind, case):
    """Every session kind decides one pod a step: both pods of ONE
    launch land where the host oracle puts them, the second against the
    carry the first just committed to."""
    nodes, bound, pending, expect = _DIRECTED[case]()
    want = _directed_oracle(nodes, bound, pending)
    assert want == expect, f"oracle surprised us: {want}"
    be = _directed_backend(nodes, bound)
    arrays = _encode(be, pending)
    templates = list({template_fingerprint(a): a for a in arrays}.values())
    sess = _build_session(kind, be.enc.device_state(), templates, be.weights)
    lanes = type(sess).decisions(sess.schedule(arrays))[:len(pending)]
    got = [be.enc.node_names[d] if d >= 0 else None for d in lanes]
    assert got == want


@pytest.mark.parametrize("case", sorted(_DIRECTED))
def test_directed_pair_across_chained_batches(case):
    """The same pairs split over two dispatch_many launches, the second
    enqueued BEFORE the first is harvested: it rides the first's
    un-harvested device carry to the oracle's answer. The one exception
    is a new spec whose rows COUNT the pod in flight (the term pod's
    selector matches the plain pod): its admission lands that batch
    first, so the second launch chains on a harvested carry."""
    nodes, bound, pending, expect = _DIRECTED[case]()
    want = _directed_oracle(nodes, bound, pending)
    assert want == expect, f"oracle surprised us: {want}"
    be = _directed_backend(nodes, bound)
    # a pod that fits nowhere builds the session (synchronous path) and
    # commits nothing; the table session admits the pair's specs later
    hungry = make_pod("hungry", cpu="64", memory="64Mi",
                      labels={"app": "hungry"})
    assert [n for _, n in be.schedule_many([hungry])] == [None]
    assert type(be._session) is PallasSession
    h1 = be.dispatch_many([copy.deepcopy(pending[0])])
    h2 = be.dispatch_many([copy.deepcopy(pending[1])])
    assert h1.ys is not None and h2.ys is not None, (
        "batches did not ride the pipelined session path")
    assert not h1.speculative
    assert h2.speculative is (case != "term-behind-plain")
    got = [n for _, n in be.harvest(h1)] + [n for _, n in be.harvest(h2)]
    assert got == want


def test_speculation_miss_redrives_bit_identical():
    """Deterministic speculation miss at the backend seam: batch 2 is
    dispatched chained on batch 1's unharvested carry, then batch 1's
    harvest is corrupted (nan-harvest). The recovery must count exactly
    one miss and re-drive BOTH batches to the same decisions a clean
    sequential backend makes."""
    warm = [
        make_pod(f"w-{i}", namespace="default", cpu="100m", memory="64Mi",
                 labels={"app": "m"})
        for i in range(4)
    ]
    b1 = [
        make_pod(f"a-{i}", namespace="default", cpu="100m", memory="64Mi",
                 labels={"app": "m"})
        for i in range(3)
    ]
    b2 = [
        make_pod(f"b-{i}", namespace="default", cpu="100m", memory="64Mi",
                 labels={"app": "m"})
        for i in range(3)
    ]

    def nodes_of(results):
        return [node for _, node in results]

    # clean sequential control (the depth-0 reference semantics)
    _, ctrl = _mini_backend(["4"] * 6)
    ctrl.schedule_many([make_pod(
        p.metadata.name, namespace="default", cpu="100m", memory="64Mi",
        labels={"app": "m"}) for p in warm])
    want = nodes_of(ctrl.schedule_many(list(b1))) \
        + nodes_of(ctrl.schedule_many(list(b2)))

    _, be = _mini_backend(["4"] * 6)
    be.schedule_many(warm)  # builds the session: later batches pipeline
    assert be._session is not None
    spec0 = _spec_counts()
    h1 = be.dispatch_many(b1)
    h2 = be.dispatch_many(b2)
    assert h1.ys is not None and h2.ys is not None, (
        "batches did not ride the pipelined session path"
    )
    assert not h1.speculative and h2.speculative, (
        "speculation flags wrong at dispatch"
    )
    inj = FaultInjector()
    be.faults = inj
    inj.arm("nan-harvest", shots=1)
    got = nodes_of(be.harvest(h1)) + nodes_of(be.harvest(h2))
    assert inj.injected.get("nan-harvest", 0) == 1
    spec1 = _spec_counts()
    assert spec1.get("miss", 0) - spec0.get("miss", 0) == 1, (
        "the dropped chained batch was not counted as a miss"
    )
    assert spec1.get("hit", 0) == spec0.get("hit", 0)
    assert got == want, "speculation-miss re-drive changed decisions"

    # clean second round: the chained batch now harvests as a HIT
    h3 = be.dispatch_many([make_pod(
        "c-0", namespace="default", cpu="100m", memory="64Mi",
        labels={"app": "m"})])
    h4 = be.dispatch_many([make_pod(
        "c-1", namespace="default", cpu="100m", memory="64Mi",
        labels={"app": "m"})])
    be.harvest(h3)
    be.harvest(h4)
    spec2 = _spec_counts()
    assert spec2.get("hit", 0) - spec1.get("hit", 0) >= 1
    assert spec2.get("miss", 0) == spec1.get("miss", 0)


def test_speculation_miss_drill_through_loop():
    """Speculation-miss drill through the FULL loop: depth 2, a wedged
    device wait injected mid-stream while later
    batches pile up behind it. The watchdog fault must roll the chained
    batches back through the re-drive path bit-identically, with the
    BindIntegrityChecker clean (no pod bound twice) and the misses
    counted."""
    seed = 13
    rng = random.Random(seed)
    batch_sizes = [rng.choice([2, 3, 5]) for _ in range(32)]
    maps = {}
    inj = None
    checker = None
    spec0 = _spec_counts()
    for depth in (0, 2):
        _, cs = _cluster()
        sched = _mk_scheduler(cs, depth)
        try:
            if depth:
                checker = BindIntegrityChecker().attach(
                    sched.informers.pods())
                inj = FaultInjector()
                sched.install_fault_injector(inj)
                sched.tpu.watchdog_timeout = 0.5
                orig = type(sched.tpu).dispatch_many
                count = {"batches": 0}

                def arming(self, pods, _orig=orig, _c=count, _inj=inj, **kw):
                    if _c["batches"] == 2:
                        _inj.arm("wedge-wait", shots=1)
                    _c["batches"] += 1
                    return _orig(self, pods, **kw)

                sched.tpu.dispatch_many = arming.__get__(sched.tpu)
            pods = _pod_stream(random.Random(seed), 32)
            _drive(sched, cs, pods, batch_sizes)
            maps[depth] = _bound_map(cs)
        finally:
            sched.shutdown()
            sched.informers.stop()
    assert inj.injected.get("wedge-wait", 0) >= 1
    assert maps[0] == maps[2], "speculation-miss recovery changed decisions"
    assert checker.violations == [], checker.violations
    spec1 = _spec_counts()
    assert spec1.get("miss", 0) - spec0.get("miss", 0) >= 1, (
        "wedge drill produced no speculation miss — nothing was chained"
    )


def test_backpressure_never_harvests_on_dispatch_thread():
    """dispatch_many back-pressure at depth >= 1 must WAIT for the
    completion worker instead of harvesting inline: the dispatching
    thread never decodes a harvest (the regression this pins used to
    charge harvest+assume+decode to the dispatch critical path)."""
    _, cs = _cluster()
    sched = _mk_scheduler(cs, 2)
    assert sched.tpu.async_harvest_drain is True
    sched.tpu.max_pending = 1  # force back-pressure on every overlap
    harvest_threads = []
    orig_h = type(sched.tpu)._harvest_locked

    def spy_h(self, _orig=orig_h, _t=harvest_threads):
        _t.append(threading.current_thread().name)
        return _orig(self)

    sched.tpu._harvest_locked = spy_h.__get__(sched.tpu)
    full_seen = []
    orig_d = type(sched.tpu).dispatch_many

    def spy_d(self, pods, _orig=orig_d, _f=full_seen, **kw):
        _f.append(len(self._pending))
        return _orig(self, pods, **kw)

    sched.tpu.dispatch_many = spy_d.__get__(sched.tpu)
    try:
        pods = [
            make_pod(f"p-{i}", namespace="default", cpu="100m",
                     labels={"app": "plain"})
            for i in range(24)
        ]
        _drive(sched, cs, pods, [3] * 8)
        assert all(v for v in _bound_map(cs).values())
        # back-pressure was actually exercised (a dispatch arrived with
        # the FIFO at max_pending) ...
        assert any(v >= 1 for v in full_seen), full_seen
        assert harvest_threads, "pipeline never harvested"
        # ... and every harvest ran on the completion worker
        bad = [t for t in harvest_threads if t != "batch-completions"]
        assert not bad, (
            f"harvest decoded on non-completion threads: {set(bad)}"
        )
    finally:
        sched.stop()
        sched.informers.stop()


def test_depth2_overlaps_dispatches():
    """Sanity: with depth 2 the backend genuinely holds more than one
    in-flight dispatch at some point (the double buffer is real, not
    silently serialized)."""
    _, cs = _cluster()
    sched = _mk_scheduler(cs, 2)
    seen = []
    orig = type(sched.tpu).dispatch_many

    def spy(self, pods, **kw):
        h = orig(self, pods, **kw)
        seen.append(len(self._pending))
        return h

    sched.tpu.dispatch_many = spy.__get__(sched.tpu)
    try:
        pods = [
            make_pod(f"p-{i}", namespace="default", cpu="100m",
                     labels={"app": "plain"})
            for i in range(24)
        ]
        _drive(sched, cs, pods, [4] * 6)
        assert all(v for v in _bound_map(cs).values())
        assert max(seen, default=0) >= 2, (
            f"never saw 2 in-flight dispatches: {seen}"
        )
    finally:
        sched.stop()
        sched.informers.stop()
