"""Incremental device-state deltas: live sessions survive cluster churn.

The tentpole contract (ISSUE 5): every CacheListener event classifies as
carry-delta (batchable pod add/remove on a known node), prologue-patch
(allocatable-only node update), or structural (full rebuild — node
add/remove, term/port pods, capacity growth), and a delta-patched
session produces BIT-IDENTICAL decisions to a fresh rebuild from the
mutated encoding.

Pinned here on the CPU hoisted path (the env tops out there; pallas
carry-patching gets the construction-level parity check below plus the
chip rerun):

  * property test over randomized interleavings of {batchable
    add/remove, affinity-pod add/remove, node update/heartbeat, victim
    evictions mid-pipeline} — delta-patched (KTPU_SESSION_DELTAS on) vs
    rebuild-everything (patching off) backends must decide identically;
  * the rebuild-storm regression: a preemption churn workload through
    the full loop keeps churn-reason session teardowns under a pinned
    bound while the victim-delete echoes apply as deltas;
  * pallas carry-layout parity: apply_deltas on PallasSession (numpy
    seed path AND the fused _carry_delta_scan device path) must equal a
    fresh session built from the mutated encoding, without running the
    Mosaic kernel (CPU-verifiable);
  * the on_remove_pod no-op gate and the GCD-compatibility envelope.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.models.encoding import ClusterEncoding
from kubernetes_tpu.models.pod_encoder import PodEncoder
from kubernetes_tpu.ops.hoisted import match_matrices_np
from kubernetes_tpu.scheduler import metrics
from kubernetes_tpu.scheduler.internal.cache import SchedulerCache
from kubernetes_tpu.scheduler.tpu_backend import TPUBackend

from .util import anti_affinity, make_node, make_pod, spread_constraint


def _counter_total(counter, kinds=None) -> float:
    return sum(
        val for key, val in counter.items()
        if kinds is None or (key and key[0] in kinds)
    )


def _mk_cluster(n_nodes: int = 6):
    cache = SchedulerCache()
    be = TPUBackend()
    cache.add_listener(be)
    for i in range(n_nodes):
        cache.add_node(make_node(
            f"node-{i}", cpu=str(4 + (i % 2) * 2), memory="16Gi", pods=64,
            labels={v1.LABEL_HOSTNAME: f"node-{i}", "zone": f"z{i % 3}"},
        ))
    return cache, be


def _spread_pod(name, cpu="150m", node=None, labels=None):
    labels = labels or {"app": "spread"}
    return make_pod(
        name, namespace="default", cpu=cpu, memory="64Mi", labels=labels,
        constraints=[spread_constraint(1, "zone", "ScheduleAnyway", labels)],
        node_name=node or "",
    )


def _plain_pod(name, cpu="100m", node=None, labels=None):
    return make_pod(
        name, namespace="default", cpu=cpu, memory="32Mi",
        labels=labels or {"app": "plain"}, node_name=node or "",
    )


def _anti_pod(name, node=None, labels=None):
    labels = labels or {"app": "anti"}
    return make_pod(
        name, namespace="default", cpu="100m", memory="32Mi", labels=labels,
        affinity=anti_affinity(v1.LABEL_HOSTNAME, labels),
        node_name=node or "",
    )


def _event_stream(seed: int):
    """Deterministic randomized interleaving of schedule batches and
    foreign cluster events. Yields (op, payload) tuples the driver
    replays identically against both backends."""
    rng = random.Random(seed)
    ops = []
    added = []  # names of foreign-bound pods currently in the cluster
    for step in range(10):
        kind = rng.random()
        batch = []
        for b in range(rng.randint(1, 4)):
            name = f"p{step}-{b}"
            r = rng.random()
            if r < 0.5:
                batch.append(("spread", name))
            elif r < 0.8:
                batch.append(("plain", name))
            else:
                batch.append(("anti", name))
        ops.append(("schedule", batch))
        if kind < 0.35:
            # foreign batchable add — half of them share the spread
            # template's labels (their counts must patch the carry)
            name = f"f{step}"
            labels = "spread" if rng.random() < 0.5 else "other"
            ops.append(("add", (name, f"node-{rng.randrange(6)}", labels)))
            added.append(name)
        elif kind < 0.55 and added:
            # victim eviction: remove a previously-added bound pod —
            # interleaved between dispatch and the next batch, i.e. the
            # delete echo arrives against a live session mid-stream
            ops.append(("remove", added.pop(rng.randrange(len(added)))))
        elif kind < 0.7:
            # affinity-pod add/remove: structural either way
            name = f"a{step}"
            ops.append(("add-anti", (name, f"node-{rng.randrange(6)}")))
            if rng.random() < 0.5:
                ops.append(("remove-anti", name))
        elif kind < 0.85:
            ops.append(("heartbeat", rng.randrange(6)))
        else:
            ops.append(("alloc-update", rng.randrange(6)))
    return ops


def _replay(ops, delta_patching: bool):
    """(decisions, backend, teardown reasons) of one replay. The
    teardowns are THIS backend's own — every live-session teardown goes
    through its _invalidate_session — not a delta of the process-wide
    scheduler_session_rebuilds_total, which any backend left running by
    an earlier test of the worker also moves."""
    cache, be = _mk_cluster()
    be.delta_patching = delta_patching
    teardowns = []
    invalidate = be._invalidate_session

    def counting(reason="unspecified"):
        if be._session is not None:
            teardowns.append(reason)
        invalidate(reason)

    be._invalidate_session = counting
    decisions = {}
    bound = {}
    alloc_bumped = set()
    for op, payload in ops:
        if op == "schedule":
            pods = []
            for tmpl, name in payload:
                mk = {"spread": _spread_pod, "plain": _plain_pod,
                      "anti": _anti_pod}[tmpl]
                pods.append(mk(name))
            handle = be.dispatch_many(pods)
            for p, node in be.harvest(handle):
                decisions[p.metadata.name] = node
        elif op == "add":
            name, node, labels = payload
            p = _plain_pod(
                name, node=node,
                labels={"app": "spread" if labels == "spread" else "x"},
            )
            bound[name] = p
            cache.add_pod(p)
        elif op == "remove":
            cache.remove_pod(bound.pop(payload))
        elif op == "add-anti":
            name, node = payload
            p = _anti_pod(name, node=node)
            bound[name] = p
            cache.add_pod(p)
        elif op == "remove-anti":
            cache.remove_pod(bound.pop(payload))
        elif op == "heartbeat":
            i = payload
            # identical scheduling-relevant fields: the fingerprint gate
            # must swallow it without touching the session
            cache.update_node(make_node(
                f"node-{i}", cpu=str(4 + (i % 2) * 2), memory="16Gi",
                pods=64,
                labels={v1.LABEL_HOSTNAME: f"node-{i}", "zone": f"z{i % 3}"},
            ))
        elif op == "alloc-update":
            i = payload
            # allocatable-only change (same labels/taints): the
            # prologue-patch class
            alloc_bumped.add(i)
            cache.update_node(make_node(
                f"node-{i}", cpu=str(8 + (i % 2) * 2), memory="16Gi",
                pods=64,
                labels={v1.LABEL_HOSTNAME: f"node-{i}", "zone": f"z{i % 3}"},
            ))
    return decisions, be, teardowns


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_delta_vs_rebuild_parity(seed):
    """Randomized event interleavings: the delta-patched session must
    decide bit-identically to the rebuild-everything control."""
    ops = _event_stream(seed)
    applies0 = _counter_total(metrics.session_delta_applies)
    with_deltas, be_patched, teardowns_patched = _replay(
        ops, delta_patching=True)
    applies = _counter_total(metrics.session_delta_applies) - applies0
    without, be_control, teardowns_control = _replay(
        ops, delta_patching=False)
    be_patched.close()
    be_control.close()
    assert with_deltas == without, (
        "delta-patched decisions diverged from fresh-rebuild decisions"
    )
    # the stream must actually exercise the fast path (not vacuous)
    assert applies > 0, "no event rode the carry-delta path"
    assert any(node for node in with_deltas.values())
    if teardowns_control:
        assert len(teardowns_patched) < len(teardowns_control)


def _aligned_copy(a: np.ndarray) -> np.ndarray:
    """`a` in a buffer that starts on a 64-byte boundary: the alignment
    at which the CPU backend's jnp.asarray takes a numpy buffer over
    WITHOUT copying. numpy's own allocations land there one time in
    four, by chance."""
    raw = np.zeros(a.nbytes + 64, np.uint8)
    start = -raw.ctypes.data % 64
    out = raw[start:start + a.nbytes].view(a.dtype).reshape(a.shape)
    out[...] = a
    return out


def test_device_state_owns_its_buffers():
    """The device dict must not share memory with the live host arrays.
    It did, on the CPU backend, whenever a host array happened to be
    64-byte aligned: update_node_alloc's in-place row write then showed
    through in the live session's alloc static, and the node-alloc
    delta added the same difference a second time —
    test_delta_vs_rebuild_parity[2] diverged in about one process in
    four."""
    _, be = _mk_cluster()
    enc = be.enc
    enc.rebuild()
    for k, a in enc._arrays.items():
        enc._arrays[k] = _aligned_copy(a)
    dev = enc.device_state()
    before = {k: np.array(v) for k, v in dev.items()}
    got = enc.update_node_alloc(make_node(
        "node-2", cpu="8", memory="16Gi", pods=64,
        labels={v1.LABEL_HOSTNAME: "node-2", "zone": "z2"}))
    assert got is not None and got[0].any(), "the update changed nothing"
    for k, v in dev.items():
        assert np.array_equal(np.asarray(v), before[k]), (
            f"device array {k!r} moved with an in-place host write")


def test_remove_unknown_pod_is_noop():
    """The on_remove_pod mirror of the assume-echo gate: removing a pod
    the encoding never contained (never encoded, or bound to no node)
    must not tear the session down."""
    _, be = _mk_cluster()
    be.schedule_many([_plain_pod("warm-0"), _plain_pod("warm-1")])
    assert be._session is not None
    sess = be._session
    ghost = _plain_pod("ghost", node="node-0")
    be.on_remove_pod(ghost, "node-0")   # never encoded
    be.on_remove_pod(ghost, "")         # no node
    assert be._session is sess
    assert not be._deltas


def test_batchable_events_keep_session_alive():
    """Foreign batchable add + its delete echo both ride the delta queue
    and the next dispatch applies them — no teardown, same decisions as
    the encoding ground truth."""
    cache, be = _mk_cluster()
    be.schedule_many([_spread_pod("warm-0"), _spread_pod("warm-1")])
    sess = be._session
    assert sess is not None
    squatter = _plain_pod("squatter", cpu="2", node="node-1",
                          labels={"app": "spread"})
    cache.add_pod(squatter)
    assert be._session is sess and len(be._deltas) == 1
    cache.remove_pod(squatter)
    assert be._session is sess and len(be._deltas) == 2
    applies0 = _counter_total(metrics.session_delta_applies)
    res = be.schedule_many([_spread_pod("after-0")])
    assert be._session is sess
    assert _counter_total(metrics.session_delta_applies) - applies0 == 2
    assert all(node for _, node in res)


def test_term_matching_pod_is_structural():
    """With a dyn-IPA session (anti-affinity templates), a foreign pod
    whose labels match a template's own term selector perturbs prologue
    STATICS — it must tear the session down, not ride the carry."""
    cache, be = _mk_cluster()
    be.schedule_many([_anti_pod("warm-0"), _anti_pod("warm-1")])
    sess = be._session
    assert sess is not None and sess.dyn_ipa
    # matching labels (the anti template selects app=anti): structural
    cache.add_pod(_plain_pod("match", node="node-3",
                             labels={"app": "anti"}))
    assert be._session is None
    # rebuild, then a NON-matching batchable pod rides the delta
    be.schedule_many([_anti_pod("warm-2")])
    sess = be._session
    cache.add_pod(_plain_pod("nomatch", node="node-4",
                             labels={"app": "bystander"}))
    assert be._session is sess and len(be._deltas) == 1


def test_node_alloc_update_is_prologue_patch():
    """An allocatable-only node update patches the session statics in
    place; any other fingerprint change stays structural."""
    cache, be = _mk_cluster()
    be.schedule_many([_plain_pod("warm-0")])
    sess = be._session
    assert sess is not None
    cache.update_node(make_node(
        "node-0", cpu="16", memory="16Gi", pods=64,
        labels={v1.LABEL_HOSTNAME: "node-0", "zone": "z0"},
    ))
    assert be._session is sess
    assert [d["kind"] for d in be._deltas] == ["node-alloc"]
    # label change: structural
    cache.update_node(make_node(
        "node-1", cpu="6", memory="16Gi", pods=64,
        labels={v1.LABEL_HOSTNAME: "node-1", "zone": "z9"},
    ))
    assert be._session is None


def test_rebuild_storm_regression():
    """The churn workload's acceptance gate at CI scale: a preemption
    wave's victim-delete echoes and the preemptors' nominated binds must
    NOT tear the session down per event — churn-reason teardowns stay
    under a pinned bound while the events apply as deltas. (The full
    Preemption-PDB/IPA-churn >=5x session_builds_total drop is the chip
    rerun's counter-based check; this pins the mechanism.)"""
    from kubernetes_tpu.perf.harness import PodTemplate, Workload, run_workload

    w = Workload(
        "delta-storm-ci", num_nodes=6, num_init_pods=24, num_pods=12,
        init_template=PodTemplate(cpu="900m", memory="64Mi", priority=1,
                                  labels={"app": "victim"}),
        # every 2nd measured pod is a high-priority preemptor; the rest
        # are small pods that keep dispatches (and so delta flushes)
        # flowing through the measured window
        template=PodTemplate(cpu="50m", memory="16Mi"),
        second_template=PodTemplate(cpu="900m", memory="64Mi",
                                    priority=100),
        second_every=2,
        timeout=180, stall_stop=30.0, max_batch=8,
    )
    r = run_workload(w)
    assert r.num_bound == 12, f"bound {r.num_bound}/12"
    # THE storm signal: on the old path every victim-delete echo (and
    # every preemptor's nominated bind) tore a live session down —
    # churn-reason teardowns tracked the event count. Now they stay
    # under a pinned bound...
    churn = sum(
        (r.session_rebuild_reasons or {}).get(k, 0)
        for k in ("pod-remove", "foreign-pod-add")
    )
    assert churn <= 2, (
        f"rebuild storm: {churn} churn-reason teardowns "
        f"(reasons={r.session_rebuild_reasons})"
    )
    # ...and so does the in-window session-build count (the ISSUE's
    # counter-based acceptance gate at CI scale)
    builds = sum((r.session_builds or {}).values())
    assert builds <= 6, (
        f"{builds} in-window session builds "
        f"(builds={r.session_builds}, reasons={r.session_rebuild_reasons})"
    )
    # NOTE: delta-APPLY counts here depend on dispatch cadence (a fast
    # run binds every preemptor through the nominated short-circuit and
    # never flushes the queue); the deterministic apply/flush assertions
    # live in test_batchable_events_keep_session_alive above.


def test_assume_expiry_is_a_listener_event():
    """Assume-TTL expiry (cleanup_expired_assumed_pods) must route
    through the cache listeners like any other remove: the live session
    SURVIVES, the expiries ride the carry-delta queue, the expired
    counter and assumed-pod gauges move, and post-expiry decisions are
    bit-identical to a fresh rebuild from the same cache state."""
    t = [0.0]
    cache = SchedulerCache(ttl=5.0, now=lambda: t[0])
    be = TPUBackend()
    cache.add_listener(be)
    for i in range(6):
        cache.add_node(make_node(
            f"node-{i}", cpu=str(4 + (i % 2) * 2), memory="16Gi", pods=64,
            labels={v1.LABEL_HOSTNAME: f"node-{i}", "zone": f"z{i % 3}"},
        ))
    res = be.schedule_many([_spread_pod(f"w{i}") for i in range(3)])
    assert all(node for _, node in res)
    sess = be._session
    assert sess is not None
    for p, node in res:
        assumed = _spread_pod(p.metadata.name, node=node)
        cache.assume_pod(assumed)
        cache.finish_binding(assumed)
    # mid-TTL sweep: nothing expires, the age gauge tracks the oldest
    t[0] = 2.0
    assert cache.cleanup_expired_assumed_pods() == 0
    assert metrics.assumed_pods.value() == 3
    assert abs(metrics.oldest_assume_age.value() - 2.0) < 1e-6
    # past the TTL: every assume expires THROUGH the listener
    exp0 = metrics.expired_assumes.value()
    t[0] = 10.0
    assert cache.cleanup_expired_assumed_pods() == 3
    assert metrics.expired_assumes.value() - exp0 == 3
    assert metrics.assumed_pods.value() == 0
    assert metrics.oldest_assume_age.value() == 0.0
    assert be._session is sess, "expiry tore the live session down"
    assert len(be._deltas) == 3, "expiries did not ride the delta queue"
    # parity: the delta-patched session vs a fresh rebuild over the
    # post-expiry cache state must decide identically
    live = {
        p.metadata.name: node
        for p, node in be.schedule_many(
            [_spread_pod(f"probe{i}") for i in range(4)])
    }
    assert be._session is sess
    cache2 = SchedulerCache()
    be2 = TPUBackend()
    cache2.add_listener(be2)
    for i in range(6):
        cache2.add_node(make_node(
            f"node-{i}", cpu=str(4 + (i % 2) * 2), memory="16Gi", pods=64,
            labels={v1.LABEL_HOSTNAME: f"node-{i}", "zone": f"z{i % 3}"},
        ))
    want = {
        p.metadata.name: node
        for p, node in be2.schedule_many(
            [_spread_pod(f"probe{i}") for i in range(4)])
    }
    assert live == want, "post-expiry decisions diverged from rebuild"
    assert any(live.values())


# ---------------------------------------------------------------------------
# pallas carry-layout parity (CPU-verifiable without running the kernel)


from kubernetes_tpu.ops.hoisted import template_fingerprint  # noqa: E402


def _pallas_fixture():
    from kubernetes_tpu.ops.pallas_scan import PallasSession

    enc = ClusterEncoding()
    nodes = [
        make_node(f"n{i}", labels={v1.LABEL_HOSTNAME: f"n{i}",
                                   "zone": f"z{i % 3}"})
        for i in range(5)
    ]
    bound = [_spread_pod(f"b{i}", node=f"n{i % 5}") for i in range(7)]
    enc.set_cluster(nodes, bound)
    pe = PodEncoder(enc)
    tmpl = {
        k: va for k, va in pe.encode(_spread_pod("t0")).items()
        if not k.startswith("_")
    }
    cluster = {k: np.asarray(va) for k, va in enc.device_state().items()}
    return PallasSession, enc, bound, tmpl, cluster


def _remove_delta(enc, victim):
    nidx = enc.node_index[victim.spec.node_name]
    A = enc._arrays
    before = (A["requested"][nidx].copy(), A["nz_requested"][nidx].copy(),
              int(A["pod_count"][nidx]))
    enc.remove_pod(victim)
    dres = A["requested"][nidx] - before[0]
    dnz = A["nz_requested"][nidx] - before[1]
    dcount = int(A["pod_count"][nidx]) - before[2]
    pp = np.zeros(enc.pod_pair_vocab.capacity, bool)
    pk = np.zeros(enc.pod_key_vocab.capacity, bool)
    for k, va in victim.metadata.labels.items():
        if enc.pod_key_vocab.get(k):
            pk[enc.pod_key_vocab.get(k)] = True
        if enc.pod_pair_vocab.get((k, va)):
            pp[enc.pod_pair_vocab.get((k, va))] = True
    rows = {"self_ppair": pp, "self_pkey": pk,
            "self_ns": np.int32(enc.ns_vocab.get("default"))}
    return nidx, dres, dnz, dcount, rows


@pytest.mark.parametrize("victim_idx", [3, 5])
def test_pallas_delta_carry_parity(victim_idx):
    """apply_deltas on the table session's carry (the fused _delta_scan)
    must equal a FRESH PallasSession built from the mutated encoding —
    utilization rows and the spec's count rows, on valid node lanes, bit
    for bit."""
    PallasSession, enc, bound, tmpl, cluster = _pallas_fixture()
    sess = PallasSession(cluster, [tmpl])
    victim = bound[victim_idx]
    nidx, dres, dnz, dcount, rows = _remove_delta(enc, victim)
    assert sess.delta_compatible(dres, dnz)
    mfa, msa = match_matrices_np(sess._tp_np, [rows])
    sess.apply_deltas([{
        "kind": "pod-remove", "node": nidx, "dres": dres, "dnz": dnz,
        "dcount": dcount,
        "mf": mfa[:, 0, :].astype(np.int32) * -1,
        "ms": msa[:, 0, :].astype(np.int32) * -1,
    }])
    fresh_cluster = {
        k: np.asarray(va) for k, va in enc.device_state().items()
    }
    fresh = PallasSession(fresh_cluster, [tmpl])
    valid = fresh_cluster["valid"].astype(bool)
    n = valid.shape[0]
    r = sess.R
    for key, unit in (("requested", lambda x: x._gcd[:, None]),
                      ("nzpc", lambda x: np.concatenate(
                          [x._gcd[:2], np.ones(6, np.int64)])[:, None])):
        a = np.asarray(sess._initial_carry()[key]).astype(np.int64)
        b = np.asarray(fresh._initial_carry()[key]).astype(np.int64)
        rows_n = r if key == "requested" else 8
        a = (a[:rows_n] * unit(sess)[:rows_n])[:, :n][:, valid]
        b = (b[:rows_n] * unit(fresh)[:rows_n])[:, :n][:, valid]
        assert (a == b).all(), f"carry {key} diverged from fresh build"
    fp = template_fingerprint(tmpl)
    checked = 0
    for side in ("f", "s"):
        for c in range(sess.C):
            if sess._rows[0][side][c]:
                checked += 1
                assert (sess.count_row(fp, side, c)[valid]
                        == fresh.count_row(fp, side, c)[valid]).all(), (
                    f"count row {side}{c} diverged from fresh build")
    assert checked


def test_pallas_gcd_incompatible_delta_rejected():
    """A utilization delta the build-time GCD rescale cannot divide
    exactly must be refused (the backend then takes the structural
    path) — never silently truncated."""
    PallasSession, enc, bound, tmpl, cluster = _pallas_fixture()
    sess = PallasSession(cluster, [tmpl])
    r = sess._gcd.shape[0]
    if int(sess._gcd[0]) <= 1:
        pytest.skip("cpu dimension has gcd 1 — every delta divides")
    dres = np.zeros(r, np.int64)
    dres[0] = int(sess._gcd[0]) + 1  # not a multiple
    assert not sess.delta_compatible(dres, np.zeros(2, np.int64))


def test_sharded_delta_carry_parity():
    """The sharded mirror's per-shard counts patch through the same
    fused delta scan: apply on an 8-device virtual mesh must equal a
    fresh sharded session from the mutated encoding."""
    import jax
    from jax.sharding import Mesh

    from kubernetes_tpu.ops.sharded_scan import ShardedPallasSession
    from kubernetes_tpu.parallel.sharded import NODE_AXIS

    PallasSession, enc, bound, tmpl, cluster = _pallas_fixture()
    mesh = Mesh(np.array(jax.devices("cpu")[:8]), (NODE_AXIS,))
    sess = ShardedPallasSession(cluster, [tmpl], mesh=mesh)
    victim = bound[2]
    nidx, dres, dnz, dcount, rows = _remove_delta(enc, victim)
    assert sess.delta_compatible(dres, dnz)
    mfa, msa = match_matrices_np(sess._tp_np, [rows])
    sess.apply_deltas([{
        "kind": "pod-remove", "node": nidx, "dres": dres, "dnz": dnz,
        "dcount": dcount,
        "mf": mfa[:, 0, :].astype(np.int32) * -1,
        "ms": msa[:, 0, :].astype(np.int32) * -1,
    }])
    fresh_cluster = {
        k: np.asarray(va) for k, va in enc.device_state().items()
    }
    fresh = ShardedPallasSession(fresh_cluster, [tmpl], mesh=mesh)
    valid = fresh_cluster["valid"].astype(bool)
    n = valid.shape[0]
    for key in ("requested", "nzpc", "cnt_fn", "cnt_sn"):
        a = np.asarray(sess._carry[key])[:, :n][:, valid]
        b = np.asarray(fresh._carry[key])[:, :n][:, valid]
        assert (a == b).all(), f"sharded carry {key} diverged"
