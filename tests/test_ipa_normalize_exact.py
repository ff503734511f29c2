"""InterPodAffinity's score on the table kernel is upstream's, bit for bit.

Upstream (scoring.go NormalizeScore, and scheduler/plugins/interpodaffinity
.py here) truncates 100 * float64((s - min) / (max - min)). The kernel
takes the exact floor in int32 and reads one less at the whole values
where float64 does (ops/pallas_scan.py ipa_normalize). Checked here:

- every state 0 <= a <= b <= 2000, against Python's float64 expression,
  and states drawn up to the exact form's range;
- the states float32 gets wrong, pinned;
- end to end at 96 nodes on the CPU (the kernel interpreted): the table
  session, the host plugin and the benchmark's plain reference
  (benchmarks/references/affinity.py) give the same scores and the same
  binds on seeded random clusters with all five directions of
  processExistingPod and both topology keys.
"""

import copy
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.ops.hoisted import template_fingerprint
from kubernetes_tpu.ops.pallas_scan import (
    IPA_EXACT_MAX,
    IPA_F64_LOW,
    PallasSession,
    ipa_normalize,
)
from kubernetes_tpu.scheduler.framework.interface import CycleState, NodeScore
from kubernetes_tpu.scheduler.framework.snapshot import Snapshot
from kubernetes_tpu.scheduler.plugins.interpodaffinity import InterPodAffinity
from kubernetes_tpu.testing.oracle import first_max_decisions

from .test_hoisted import _encode_all, _presized_encoding

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def _bench(directory, name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)   # `from benchlib import ...`
    spec = importlib.util.spec_from_file_location(
        f"ipa_exact_{directory}_{name}", os.path.join(
            BENCH, directory, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _upstream(a, b):
    """int64(100 * (float64(a) / float64(b))), 0 where b == 0."""
    a = np.asarray(a, np.int64)
    b = np.asarray(b, np.int64)
    with np.errstate(invalid="ignore", divide="ignore"):
        out = (100.0 * (a / b.astype(np.float64))).astype(np.int64)
    return np.where(b > 0, out, 0)


def _kernel_form(a, b):
    with jax.enable_x64(False):
        return np.asarray(jax.jit(ipa_normalize)(
            jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32)))


class TestNormalizeExact:
    def test_every_state_to_2000(self):
        b = np.repeat(np.arange(2001), np.arange(2001) + 1)
        a = np.concatenate([np.arange(n + 1) for n in range(2001)])
        got = _kernel_form(a, b)
        want = _upstream(a, b)
        bad = np.flatnonzero(got != want)
        assert bad.size == 0, list(zip(a[bad][:5], b[bad][:5]))
        # float64 reads one less than the exact floor at these states
        low = want != np.where(b > 0, 100 * a // np.maximum(b, 1), 0)
        assert low.sum() == 80

    def test_pinned_states(self):
        a = np.array([29, 53, 58, 57, 59, 0, 100, 1])
        b = np.array([100, 100, 100, 100, 100, 7, 100, 3])
        assert _kernel_form(a, b).tolist() == [28, 53, 57, 56, 59, 0,
                                               100, 33]
        assert IPA_F64_LOW == (29, 57, 58)
        # float32, the form the kernel had, reads these the other way
        f32 = (100 * (a[:3].astype(np.float32) / b[:3].astype(np.float32))
               ).astype(np.int32)
        assert f32.tolist() == [29, 52, 58]

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_drawn_states_to_the_exact_range(self, seed):
        rng = np.random.default_rng(seed)
        b = rng.integers(1, IPA_EXACT_MAX, 200000)
        a = (rng.random(200000) * (b + 1)).astype(np.int64)
        # and whole values: a / b = k / 100
        k = rng.integers(0, 101, 20000)
        m = rng.integers(1, IPA_EXACT_MAX // 100, 20000)
        # ... and one either side of them, where a float32 quotient
        # falls on the wrong side of k
        near = np.clip(k * m + rng.choice([-1, 1], 20000), 0, 100 * m)
        a = np.concatenate([a, k * m, near, [0, IPA_EXACT_MAX - 1]])
        b = np.concatenate([b, 100 * m, 100 * m, [IPA_EXACT_MAX - 1] * 2])
        assert (_kernel_form(a, b) == _upstream(a, b)).all()


# ---------------------------------------------------------------------------
# end to end: table session, host plugin, plain reference

N_NODES = 96
KEYS = ("kubernetes.io/hostname", "topology.kubernetes.io/zone")


def _cluster_config(rng):
    """Four templates whose terms cover all five directions of
    processExistingPod and both topology keys, weights drawn."""
    def w():
        return int(rng.integers(1, 101))

    def key():
        return KEYS[int(rng.integers(2))]

    templates = {
        "a": {"cpu": "100m", "memory": "128Mi", "labels": {"app": "a-{group}"},
              "spread_zone_soft": True, "pod_affinity": [
                  {"kind": "preferred_anti", "weight": w(),
                   "topology_key": KEYS[0], "select_app": "a",
                   "group_mod": 3},
                  {"kind": "preferred", "weight": w(), "topology_key": KEYS[1],
                   "select_app": "b", "group_mod": 2}]},
        "b": {"cpu": "250m", "memory": "320Mi", "labels": {"app": "b-{group}"},
              "anti_affinity_hostname": True},
        "c": {"cpu": "350m", "memory": "768Mi", "labels": {"app": "c-{group}"},
              "pod_affinity": [
                  {"kind": "required", "topology_key": key(),
                   "select_app": "b", "group_mod": 2},
                  {"kind": "preferred_anti", "weight": w(),
                   "topology_key": key(), "select_app": "a",
                   "group_mod": 3}]},
        "d": {"cpu": "50m", "memory": "64Mi", "labels": {"app": "d-{group}"},
              "pod_affinity": [
                  {"kind": "preferred", "weight": w(), "topology_key": key(),
                   "select_app": "c", "group_mod": 2},
                  {"kind": "preferred", "weight": w(), "topology_key": key(),
                   "select_app": "a", "group_mod": 3}]},
    }
    return {"nodes": {"count": N_NODES, "cpu": "4", "memory": "32Gi",
                      "pods": 110, "zones": 3},
            "pod_templates": templates}


def _class(config, name, group):
    t = config["pod_templates"][name]
    return dict(t, labels={k: v.replace("{group}", str(group))
                           for k, v in t["labels"].items()})


def _draw(config, rng, n, builder, prefix):
    groups = {"a": 3, "b": 2, "c": 2, "d": 2}
    names = list(groups)
    out = []
    for i in range(n):
        t = names[int(rng.integers(len(names)))]
        cls = _class(config, t, int(rng.integers(groups[t])))
        out.append((cls, builder.build_pod(f"{prefix}-{i:03d}", cls)))
    return out


class _Handle:
    def __init__(self, snap):
        self.snap = snap

    def snapshot_shared_lister(self):
        return self.snap


def _host_ipa(nodes, bound, pod, feasible):
    """The host plugin's normalized score on the feasible nodes."""
    snap = Snapshot.from_objects(bound, nodes)
    plugin = InterPodAffinity(handle=_Handle(snap))
    state = CycleState()
    plugin.pre_filter(state, pod)
    passes = [plugin.filter(state, pod, ni) is None for ni in snap.list()]
    names = [nodes[i].metadata.name for i in np.flatnonzero(feasible)]
    plugin.pre_score(state, pod, [snap.get(n) for n in names])
    scores = [NodeScore(n, plugin.score(state, pod, n)[0]) for n in names]
    plugin.normalize_score(state, pod, scores)
    return np.array(passes), np.array([s.score for s in scores])


@pytest.mark.parametrize("seed", [3, 11, 2 ** 31 + 5])
def test_session_plugin_and_reference_agree(seed):
    rng = np.random.default_rng(seed)
    config = _cluster_config(rng)
    builder = _bench("builders", "affinity")
    ref_mod = _bench("references", "affinity")
    nodes = [builder.build_node(i, config) for i in range(N_NODES)]
    bound = []
    for cls, pod in _draw(config, rng, 60, builder, "bound"):
        pod.spec.node_name = nodes[int(rng.integers(N_NODES))].metadata.name
        bound.append((cls, pod))
    pending = _draw(config, rng, 40, builder, "pending")

    # the plain reference, the host plugin beside it at every decision
    ref = ref_mod.AffinityCluster.from_config(config)
    for cls, pod in bound:
        ref.place(ref_mod.reference.PodClass(cls),
                  int(pod.spec.node_name.rsplit("-", 1)[1]))
    placed = [p for _, p in bound]
    want, want_score, host_binds = [], [], []
    directions = set()
    for cls, pod in pending:
        pc = ref_mod.reference.PodClass(cls)
        c = ref._class_id(pc)
        ok = ref._feasible_of(c)
        directions |= {key for (_, key) in ref._relations(c)[3]}
        passes, host = _host_ipa(nodes, placed, pod, ok)
        assert (passes == ok).all()   # resources never bind here
        if ok.any():
            assert host.tolist() == ref.ipa_score(c, ok)[ok].tolist()
        host_binds += first_max_decisions(nodes, copy.deepcopy(placed),
                                          [copy.deepcopy(pod)])
        node = ref.decide(pc)
        want.append(node)
        want_score.append(ref.last_max if node is not None else -1)
        if node is not None:
            q = copy.deepcopy(pod)
            q.spec.node_name = nodes[node].metadata.name
            placed.append(q)
    assert directions == set(KEYS)
    assert host_binds == [None if n is None else nodes[n].metadata.name
                          for n in want]
    assert any(n is not None for n in want)

    # the table session, in two launches
    pods = [p for _, p in pending]
    enc, pe = _presized_encoding(copy.deepcopy(nodes),
                                 [copy.deepcopy(p) for _, p in bound],
                                 copy.deepcopy(pods))
    arrays = _encode_all(enc, pe, pods)
    seen, specs = set(), []
    for a in arrays:
        if template_fingerprint(a) not in seen:
            seen.add(template_fingerprint(a))
            specs.append(a)
    sess = PallasSession(enc.host_state(), specs, interpret=True,
                         terms=True)
    got, score = [], []
    for lo, hi in ((0, 17), (17, len(arrays))):
        ys = sess.schedule(arrays[lo:hi])
        got += [None if d < 0 else d
                for d in PallasSession.decisions(ys)[: hi - lo]]
        score += np.asarray(ys["rows"])[1, : hi - lo].tolist()
    assert got == want
    # the kernel's highest total is the reference's plus what the plugins
    # the reference leaves out add to every node alike
    offset = {g - w for g, w, n in zip(score, want_score, want)
              if n is not None}
    assert len(offset) == 1
