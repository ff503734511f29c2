"""The table kernel's WIDE form (ops/pallas_scan.py): LeastAllocated and
BalancedAllocation exact for rescaled capacities up to POS_BIG, as a
cluster of GKE node pools needs them (allocatable in Ki: a GCD of 1 Ki,
capacities of 10^8 units), and the balanced quirk list solved as a
congruence instead of scanned over a grid.

The arithmetic is checked on plain jnp arrays against numpy's int64 and
float64 (what the reference computes); the session end to end in
interpreter mode against the first-max oracle, at tiny clusters.
"""

import copy
import random

import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.ops import pallas_scan
from kubernetes_tpu.ops.pallas_scan import (
    POS_BIG,
    PallasSession,
    _balanced_quirks,
    _balanced_wide,
    _least_wide,
    _whole_states,
)
from kubernetes_tpu.scheduler import metrics as sched_metrics

from .test_pallas_table import _backend as _table_backend
from .test_pallas_table import _names, _nodes, _oracle, _pod
from .util import make_node, make_pod

_OPEN = []


def _backend(*args, **kw):
    be = _table_backend(*args, **kw)
    _OPEN.append(be)
    return be


@pytest.fixture(autouse=True)
def _close_backends():
    """A bucket-warm thread still compiling when the interpreter exits
    aborts it: every backend a test opens is closed after it."""
    yield
    while _OPEN:
        _OPEN.pop().close()

# GKE's allocatable for seven N2 machine types (benchmarks/configs/
# gke-pools-5000n.json), and the rescaled (cpu / 10m, memory / 1Ki) pairs
# a session over all seven holds
GKE_POOLS = [("3920m", "13948518Ki"), ("7910m", "29719101Ki"),
             ("15890m", "61260267Ki"), ("7910m", "61260267Ki"),
             ("15890m", "13948518Ki"), ("31850m", "124342599Ki"),
             ("15890m", "124342599Ki")]
RESCALED = [(int(c[:-1]) // 10, int(m[:-2])) for c, m in GKE_POOLS]


def _f64_balanced(c, m, C, M):
    """The reference's float64 BalancedAllocation (0 where full)."""
    cf, mf = c / np.float64(C), m / np.float64(M)
    out = ((1.0 - np.abs(cf - mf)) * 100).astype(np.int64)
    return np.where((c >= C) | (m >= M), 0, out)


def _f32_balanced(c, m, C, M):
    cf = c.astype(np.float32) / np.float32(C)
    mf = m.astype(np.float32) / np.float32(M)
    return ((np.float32(1.0) - np.abs(cf - mf)) * np.float32(100)).astype(
        np.int64)


def _states(C, M, seed):
    """Random states, every whole state, the corners, and full nodes."""
    rng = np.random.default_rng(seed)
    w = _whole_states(C, M)
    c = np.concatenate([rng.integers(0, C, 20000), w[:, 0],
                        [0, C - 1, 0, C - 1, C, 0, C + 5]])
    m = np.concatenate([rng.integers(0, M, 20000), w[:, 1],
                        [0, 0, M - 1, M - 1, 0, M, M + 5]])
    return c.astype(np.int64), m.astype(np.int64)


def _i32(x):
    return jnp.asarray(np.asarray(x, np.int64), jnp.int32)


@pytest.mark.parametrize("cap", RESCALED, ids=[c for c, _ in GKE_POOLS])
def test_wide_scores_match_float64(cap):
    """At every pool's capacity: LeastAllocated is numpy's int64 floor,
    BalancedAllocation is float64's reading once the listed quirks are
    taken off the exact floor, and where it is an integer it says so."""
    C, M = cap
    c, m = _states(C, M, seed=C)
    full = (c >= C) | (m >= M)
    n = len(c)
    bal, whole = _balanced_wide(_i32(c), _i32(m), _i32([C] * n),
                                _i32([M] * n), jnp.asarray(full))
    bal, whole = np.asarray(bal, np.int64), np.asarray(whole)
    q = _balanced_quirks(C, M)
    quirk = np.zeros(n, bool)
    for qc, qm in q:
        quirk |= (c == qc) & (m == qm)
    assert (bal - quirk == _f64_balanced(c, m, C, M)).all()
    exact_whole = ~full & (100 * np.abs(c * M - m * C) % (C * M) == 0)
    assert (whole == exact_whole).all() and exact_whole.any()
    for cap_r, req in ((C, c), (M, m)):
        got = np.asarray(_least_wide(_i32([cap_r] * n), _i32(req)))
        want = np.where(req > cap_r, 0, (cap_r - req) * 100 // cap_r)
        assert (got == want).all()


def test_float32_would_fail_the_pools():
    """The states above tell float32 from float64: a float32 balanced
    score is wrong at some of them (what the wide form replaces)."""
    wrong = 0
    for C, M in RESCALED:
        c, m = _states(C, M, seed=C)
        ok = (c < C) & (m < M)
        wrong += int((_f32_balanced(c[ok], m[ok], C, M)
                      != _f64_balanced(c[ok], m[ok], C, M)).sum())
    assert wrong > 0


@pytest.mark.parametrize("cap", [(0, 7), (7, 0), (0, 0)])
def test_wide_scores_of_a_node_without_capacity(cap):
    C, M = cap
    c, m = _i32([0, 1, 3]), _i32([0, 2, 1])
    full = jnp.asarray([True] * 3)
    bal, whole = _balanced_wide(c, m, _i32([C] * 3), _i32([M] * 3), full)
    assert np.asarray(bal).tolist() == [0, 0, 0]
    assert not np.asarray(whole).any()
    for cap_r, req in ((C, c), (M, m)):
        if cap_r == 0:
            assert np.asarray(_least_wide(_i32([0] * 3), req)).tolist() \
                == [0, 0, 0]


def test_wide_scores_at_the_largest_values():
    """Capacities and requests just below POS_BIG: no int32 overflows."""
    big = POS_BIG - 1
    c = np.array([0, 1, big // 3, big - 1, big // 2, 12345678], np.int64)
    m = np.array([big - 1, 0, big // 7, big - 1, big // 2, 87654321],
                 np.int64)
    n = len(c)
    bal, _ = _balanced_wide(_i32(c), _i32(m), _i32([big] * n),
                            _i32([big - 2] * n), jnp.zeros(n, bool))
    want = (100 * (big * (big - 2) - np.abs(c.astype(object) * (big - 2)
                                            - m.astype(object) * big))
            // (big * (big - 2)))
    assert np.asarray(bal).tolist() == [int(x) for x in want]
    least = np.asarray(_least_wide(_i32([big] * n), _i32(c)))
    assert least.tolist() == [int(x) for x in (big - c) * 100 // big]


def _grid_quirks(C, M):
    """The grid scan the congruence solver replaced: every state."""
    c = np.arange(C, dtype=np.int64)[:, None]
    m = np.arange(M, dtype=np.int64)[None, :]
    den = C * M
    num = 100 * (den - np.abs(c * M - m * C))
    ci, mi = np.nonzero(num % den == 0)
    f64 = ((1.0 - np.abs(ci / np.float64(C) - mi / np.float64(M)))
           * 100).astype(np.int64)
    exact = num[ci, mi] // den
    keep = f64 != exact
    return sorted(zip(ci[keep].tolist(), mi[keep].tolist()))


def _pairs(seed):
    rng = random.Random(seed)
    out = [(rng.randint(1, 1 << 12), rng.randint(1, 1 << 12))
           for _ in range(3)]
    # shared factors: gcd(C, M) large, many whole states
    g = rng.choice([4, 50, 64, 100, 128])
    out.append((g * rng.randint(1, (1 << 12) // g),
                g * rng.randint(1, (1 << 12) // g)))
    return out


@pytest.mark.parametrize("pairs", [[(80, 512), (40, 256), (64, 1000),
                                    (80, 500), (7, 13), (1, 1), (100, 100),
                                    (1 << 12, 1 << 12), (4000, 4096)]]
                         + [_pairs(s) for s in range(12)])
def test_quirk_solver_matches_the_grid_scan(pairs):
    for C, M in pairs:
        got = sorted(map(tuple, _balanced_quirks(C, M).tolist()))
        assert got == _grid_quirks(C, M), (C, M)


def test_whole_states_are_all_and_only_the_integer_states():
    for C, M in [(392, 13948518), (3185, 124342599), (300, 1200),
                 (97, 1 << 12)]:
        w = _whole_states(C, M)
        assert len({tuple(x) for x in w.tolist()}) == len(w)
        assert ((100 * np.abs(w[:, 0] * M - w[:, 1] * C)) % (C * M)
                == 0).all()
        assert ((w >= 0) & (w < [C, M])).all()
    # every state of a small grid that is whole is found
    C, M = 300, 1200
    c = np.arange(C)[:, None]
    m = np.arange(M)[None, :]
    n = int((100 * np.abs(c * M - m * C) % (C * M) == 0).sum())
    assert len(_whole_states(C, M)) == n


def test_too_many_whole_states_are_not_listed(monkeypatch):
    monkeypatch.setattr(pallas_scan, "QUIRK_SOLVE_MAX", 1000)
    pallas_scan._QUIRK_CACHE.pop((1 << 12, 1 << 12), None)
    try:
        assert _balanced_quirks(1 << 12, 1 << 12) is None
    finally:
        pallas_scan._QUIRK_CACHE.pop((1 << 12, 1 << 12), None)


def _pool_nodes(n, pools=GKE_POOLS):
    return [
        make_node(f"n-{i:03d}", cpu=pools[i % len(pools)][0],
                  memory=pools[i % len(pools)][1], pods=110, labels={
                      v1.LABEL_HOSTNAME: f"n-{i:03d}",
                      v1.LABEL_ZONE: f"zone-{i % 3}"})
        for i in range(n)
    ]


def _batch(name, cpu, mem, group):
    return make_pod(name, cpu=cpu, memory=mem,
                    labels={"app": f"batch-{group}"})


def _inexact():
    return dict(sched_metrics.inexact_builds.items())


@pytest.mark.parametrize("seed", range(2))
def test_seven_pools_ride_the_wide_table_session(seed):
    """The deployments' four shapes and a heavy-tailed batch tier (one
    request past the narrow form's ~20 GiB) on the seven pools: one wide
    table session, exact balanced, not hoisted, no inexact build, every
    bind the oracle's."""
    rng = random.Random(seed)
    nodes = _pool_nodes(21)
    batch = [("500m", "1536Mi"), ("2300m", "9420Mi"), ("7700m", "30208Mi"),
             ("11900m", "95000Mi"), ("1200m", "2500Mi")]
    pending = []
    for i in range(120):
        if rng.random() < 0.15:
            g = rng.randrange(len(batch))
            pending.append(_batch(f"p-{i:03d}", *batch[g], g))
        else:
            shape = rng.choice(["web", "small", "worker", "ha"])
            pending.append(_pod(f"p-{i:03d}", shape, rng.randrange(4)))
    be = _backend(nodes, pods=512, anti=512)
    i0 = _inexact()
    got = []
    for lo in range(0, len(pending), 40):
        got += _names(be.schedule_many(copy.deepcopy(pending[lo:lo + 40])))
    assert got == _oracle(nodes, [], pending, be)
    sess = be._session
    assert type(sess) is PallasSession
    assert sess._cfg.wide and sess._cfg.bal_int and sess._cfg.pts_int
    assert int(sess._alloc[1].max()) > pallas_scan.NARROW_MAX
    assert _inexact() == i0
    assert sched_metrics.balanced_quirk_states.value(
        what="listed") == sess.quirk_states
    assert sched_metrics.balanced_quirk_states.value(
        what="capacity") == pallas_scan.MAX_QUIRKS


def test_narrow_form_for_one_node_shape():
    """4 CPU / 32Gi nodes and round requests: the narrow form, as every
    cell before the pools compiled it."""
    nodes = _nodes(6)
    pods = [_pod(f"p-{i}", s, i % 2)
            for i, s in enumerate(["web", "small", "ha", "worker"] * 3)]
    be = _backend(nodes, pods=256, anti=256)
    got = _names(be.schedule_many(copy.deepcopy(pods)))
    assert got == _oracle(nodes, [], pods, be)
    cfg = be._session._cfg
    assert cfg.bal_int and not cfg.wide


def test_a_finer_unit_past_the_narrow_form_rebuilds_wide():
    """A narrow session (32Gi nodes, requests in Mi: 256 units a node)
    meets a pod whose memory is in Ki: the unit refined to 1 Ki puts a
    node at 33.5 M units, past the narrow form. One rebuild, under
    `resource-magnitude`, into the wide form; decisions still the
    oracle's."""
    nodes = _nodes(6)
    small = [_pod(f"s-{i}", "web", 0) for i in range(6)]
    odd = [make_pod(f"k-{i}", cpu="100m", memory="131073Ki",
                    labels={"app": "k"}) for i in range(4)]
    be = _backend(nodes, pods=256)
    got = _names(be.schedule_many(copy.deepcopy(small)))
    assert not be._session._cfg.wide
    r0 = dict(sched_metrics.session_rebuilds.items())
    got += _names(be.schedule_many(copy.deepcopy(odd)))
    assert got == _oracle(nodes, [], small + odd, be)
    assert be._session._cfg.wide and be._session._cfg.bal_int
    moved = {k[0] for k, v in sched_metrics.session_rebuilds.items()
             if v != r0.get(k, 0)}
    assert moved == {"resource-magnitude"}


def test_more_than_64_node_shapes_is_counted_inexact():
    """65 node capacities: balanced in float32, and the build says so."""
    nodes = [make_node(f"n-{i:03d}", cpu=f"{4000 + 10 * i}m", memory="32Gi",
                       labels={v1.LABEL_HOSTNAME: f"n-{i:03d}",
                               v1.LABEL_ZONE: f"zone-{i % 3}"})
             for i in range(65)]
    be = _backend(nodes, pods=128)
    i0 = _inexact()
    be.schedule_many([make_pod("p", cpu="100m", memory="128Mi")])
    assert not be._session._cfg.bal_int
    moved = {k: v - i0.get(k, 0) for k, v in _inexact().items()
             if v != i0.get(k, 0)}
    assert moved == {("balanced",): 1}


def test_a_demoted_build_is_counted(monkeypatch):
    """A cluster the table kernel cannot hold at all (a capacity past
    POS_BIG units): the hoisted session, counted as `demoted`."""
    nodes = _pool_nodes(3, pools=[("4", "1200Ti")])
    be = _backend(nodes, pods=128)
    i0 = _inexact()
    be.schedule_many([make_pod("p", cpu="100m", memory="1Ki")])
    assert type(be._session) is not PallasSession
    moved = {k: v - i0.get(k, 0) for k, v in _inexact().items()
             if v != i0.get(k, 0)}
    assert moved == {("demoted",): 1}
