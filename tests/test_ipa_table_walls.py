"""The table session's walls for InterPodAffinity scores, and the
encoding's score-term table.

- A score reader keeps no presence row: upstream's empty topology map
  leaves every raw score at 0, and a score row no writer has reached reads
  0 on every lane, so its normalization is 0 either way.
- The Deployments of affinity-5000n, scaled down by 16 (32 specs in a
  table of 64, as 512 in 1024), admit into one live session: no
  `table-*` rebuild. With a presence row per reader they would not.
- An encoding given the reserve a benchmark makes (pods and anti terms)
  sizes its score-term table once, at the first pod with score terms,
  and takes no step as such pods keep coming.
"""

import copy
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.models.encoding import ClusterEncoding
from kubernetes_tpu.ops.hoisted import template_fingerprint
from kubernetes_tpu.ops.pallas_scan import PallasSession, table_capacity
from kubernetes_tpu.testing.oracle import first_max_decisions

from .test_hoisted import _encode_all, _presized_encoding
from .util import make_pod

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def _builder():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "walls_builder_affinity",
        os.path.join(BENCH, "builders", "affinity.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _specs(arrays):
    seen, out = set(), []
    for a in arrays:
        if template_fingerprint(a) not in seen:
            seen.add(template_fingerprint(a))
            out.append(a)
    return out


def _nodes(n):
    b = _builder()
    spec = {"nodes": {"count": n, "cpu": "4", "memory": "32Gi",
                      "pods": 110, "zones": 3}}
    return [b.build_node(i, spec) for i in range(n)]


def _term(app, key, weight=None):
    term = v1.PodAffinityTerm(
        label_selector=v1.LabelSelector(match_labels={"app": app}),
        topology_key=key)
    if weight is None:
        return term
    return v1.WeightedPodAffinityTerm(weight=weight, pod_affinity_term=term)


def test_presence_row_is_redundant():
    """Reader pods come first, before any pod of their writers; then the
    writers land with weights that cancel on a pair (+w preferred
    affinity, -w preferred anti-affinity: a topology map that is not
    empty and reads 0). The score row stays 0 on every lane until a
    writer lands, and every bind is the host plugin's."""
    zone = v1.LABEL_ZONE
    nodes = _nodes(12)
    reader = v1.Affinity(
        pod_affinity=v1.PodAffinity(
            preferred_during_scheduling_ignored_during_execution=[
                _term("w", zone, 40)]),
        pod_anti_affinity=v1.PodAntiAffinity(
            preferred_during_scheduling_ignored_during_execution=[
                _term("w", zone, 40)]))
    # x pods prefer r pods' nodes: their own term moves r's score
    writer = v1.Affinity(pod_affinity=v1.PodAffinity(
        preferred_during_scheduling_ignored_during_execution=[
            _term("r", v1.LABEL_HOSTNAME, 7)]))
    pending = ([make_pod(f"r-{i}", labels={"app": "r"}, affinity=reader)
                for i in range(4)]
               + [make_pod(f"w-{i}", labels={"app": "w"}) for i in range(3)]
               + [make_pod(f"x-{i}", labels={"app": "x"}, affinity=writer)
                  for i in range(3)]
               + [make_pod(f"r2-{i}", labels={"app": "r"}, affinity=reader)
                  for i in range(4)])
    want = first_max_decisions(copy.deepcopy(nodes), [],
                               copy.deepcopy(pending))
    enc, pe = _presized_encoding(copy.deepcopy(nodes), [],
                                 copy.deepcopy(pending))
    arrays = _encode_all(enc, pe, pending)
    sess = PallasSession(enc.host_state(), _specs(arrays), interpret=True,
                         terms=True)
    r = sess._fps[template_fingerprint(arrays[0])]
    score = sess._rows[r]["score"]
    assert score and "present" not in sess._rows[r]
    # no writer has landed: the row reads 0 on every lane
    assert not np.asarray(sess._initial_carry()["cnt"][score]).any()
    got = PallasSession.decisions(sess.schedule(arrays[:4]))[:4]
    assert not np.asarray(sess._carry["cnt"][score]).any()
    ys = sess.schedule(arrays[4:])
    got += PallasSession.decisions(ys)[: len(arrays) - 4]
    # the x writers landed: the row moved on their nodes
    assert np.asarray(sess._carry["cnt"][score]).any()
    assert [nodes[d].metadata.name for d in got] == want


def _affinity_classes(scale):
    """affinity-5000n's Deployments, 1/scale of each template, with the
    configuration's terms (group moduli scaled alike)."""
    with open(os.path.join(BENCH, "configs", "affinity-5000n.json")) as f:
        templates = json.load(f)["pod_templates"]
    out = []
    for name, t in templates.items():
        t = copy.deepcopy(t)
        n = t["deployments"] // scale
        for term in t.get("pod_affinity", []):
            term["group_mod"] = max(1, term["group_mod"] // scale)
        for g in range(n):
            out.append(dict(t, labels={"app": f"{name}-{g}"}))
    return out


def test_affinity_specs_admit_without_a_table_rebuild():
    b = _builder()
    classes = _affinity_classes(16)
    assert len(classes) == 32
    nodes = _nodes(96)
    rng = np.random.default_rng(5)
    bound = []
    for i in range(300):
        p = b.build_pod(f"b-{i}", classes[int(rng.integers(len(classes)))])
        p.spec.node_name = nodes[int(rng.integers(96))].metadata.name
        bound.append(p)
    pods = [b.build_pod(f"q-{i}", c) for i, c in enumerate(classes)]
    enc, pe = _presized_encoding(nodes, bound, copy.deepcopy(pods))
    arrays = _encode_all(enc, pe, pods)
    cap = table_capacity(120000 // 16)   # the cell's reserve, / 16
    assert cap == 64
    sess = PallasSession(enc.host_state(), arrays[:8], interpret=True,
                         capacity=cap, terms=True)
    sess.admit(enc.host_state, arrays[8:])   # raises TableFull if not
    readers = sum(1 for r in sess._rows if r["score"])
    assert sess.specs == 32 and readers == 20
    assert sess.count_rows <= sess.RC < sess.count_rows + readers
    assert sess.static_rows <= sess.SRc


@pytest.mark.parametrize("per_pod", [1, 2, 3])
def test_score_terms_sized_from_the_pod_reserve(per_pod):
    """reserve(pods, anti_terms) as a benchmark gives it: the score-term
    table steps once, at the first pod with `per_pod` score terms, to
    pods * per_pod, and holds every pod after it without a step."""
    nodes = _nodes(6)
    enc = ClusterEncoding()
    enc.reserve(pods=400, anti_terms=400)
    enc.set_cluster(nodes, [])
    enc.host_state()
    rebuilds = []
    real = enc.rebuild

    def counted():
        rebuilds.append(enc._score_terms.cap if enc._score_terms else 0)
        real()

    enc.rebuild = counted
    terms = v1.PodAffinity(
        preferred_during_scheduling_ignored_during_execution=[
            _term(f"s-{k}", v1.LABEL_ZONE, 10 + k) for k in range(per_pod)])
    caps = []
    for i in range(300):
        pod = make_pod(f"p-{i}", labels={"app": f"s-{i % 3}"},
                       affinity=v1.Affinity(pod_affinity=terms))
        pod.spec.node_name = nodes[i % 6].metadata.name
        enc.add_pod(pod)
        enc.host_state()
        caps.append(enc._score_terms.cap)
    assert len(rebuilds) == 1
    assert len(set(caps)) == 1 and caps[0] >= 400 * per_pod
    assert int(enc._score_terms.valid.sum()) == 300 * per_pod


def test_a_live_term_selecting_a_new_spec_lands_the_batches_in_flight():
    """A live spec's term that selects a new spec's labels makes the
    live spec's pods write the new spec's rows (its score, its repel
    row): pods of the live spec still in flight have to land before the
    snapshot is taken, as for a new spec's own selectors."""
    nodes = _nodes(12)
    live_aff = v1.Affinity(pod_affinity=v1.PodAffinity(
        preferred_during_scheduling_ignored_during_execution=[
            _term("cache", v1.LABEL_ZONE, 100)]))
    pods = [make_pod("web", labels={"app": "web"}, affinity=live_aff),
            make_pod("cache", labels={"app": "cache"}),
            make_pod("other", labels={"app": "other"})]
    enc, pe = _presized_encoding(copy.deepcopy(nodes), [],
                                 copy.deepcopy(pods))
    arrays = _encode_all(enc, pe, pods)
    sess = PallasSession(enc.host_state(), arrays[:1], interpret=True,
                         terms=True)
    assert sess._read_by_new([arrays[1]])        # web's term selects it
    assert not sess._read_by_new([arrays[2]])    # nothing selects it


def test_a_pod_with_more_terms_than_free_rows_grows_the_table():
    """With one score-term row left, a pod of two terms is a rebuild
    that grows the table, not a pop from an empty free list."""
    nodes = _nodes(6)
    enc = ClusterEncoding()
    enc.set_cluster(nodes, [])
    enc.host_state()

    def pod(i, n_terms):
        terms = v1.PodAffinity(
            preferred_during_scheduling_ignored_during_execution=[
                _term(f"s-{k}", v1.LABEL_ZONE, 10 + k)
                for k in range(n_terms)])
        p = make_pod(f"p-{i}", labels={"app": "s-0"},
                     affinity=v1.Affinity(pod_affinity=terms))
        p.spec.node_name = nodes[i % 6].metadata.name
        return p

    for i in range(2):   # two pods of two terms: the table knows 2
        enc.add_pod(pod(i, 2))
        enc.host_state()
    cap = enc._score_terms.cap
    i = 2
    while len(enc._score_terms.free) > 1:
        enc.add_pod(pod(i, 1))
        enc.host_state()
        i += 1
    assert enc._score_terms.cap == cap
    enc.add_pod(pod(i, 2))
    enc.host_state()
    assert enc._score_terms.cap > cap
    assert int(enc._score_terms.valid.sum()) == 2 * 2 + (i - 2) + 2


def test_a_score_bound_past_the_exact_range_is_refused():
    """Two lanes of a reader differ by at most twice its score bound: a
    bound at half of IPA_EXACT_MAX would leave the exact normalization,
    so the table session refuses it (the hoisted session takes the
    cluster) rather than score in another precision."""
    from kubernetes_tpu.ops.pallas_scan import (IPA_EXACT_MAX,
                                                PallasUnsupported)

    nodes = _nodes(12)
    aff = v1.Affinity(pod_affinity=v1.PodAffinity(
        preferred_during_scheduling_ignored_during_execution=[
            _term("cache", v1.LABEL_ZONE, 100)]))
    pods = [make_pod("web", labels={"app": "web"}, affinity=aff),
            make_pod("cache", labels={"app": "cache"})]
    enc, pe = _presized_encoding(copy.deepcopy(nodes), [],
                                 copy.deepcopy(pods))
    arrays = _encode_all(enc, pe, pods)
    sess = PallasSession(enc.host_state(), arrays, interpret=True,
                         terms=True)
    reader = next(r for r in sess._rows if r["score"])
    reader["static_bound"] = IPA_EXACT_MAX // 2 - 1 - reader["score_bound"]
    sess._score_bound(reader)   # the last bound inside the range
    reader["static_bound"] += 1
    with pytest.raises(PallasUnsupported) as e:
        sess._score_bound(reader)
    assert e.value.reason == "ipa-score-weights"


def test_one_pod_of_many_terms_among_none_keeps_the_table_small():
    """The reserve is scaled by the terms the pods seen carry on
    average, not by the most one pod carries: a pod of eight preferred
    terms among 200 of none sizes the score-term table for ~8 / 201 of
    a term a pod, where eight a pod would be 96 000 rows."""
    nodes = _nodes(6)
    enc = ClusterEncoding()
    enc.reserve(pods=12000, anti_terms=0)
    enc.set_cluster(nodes, [])
    enc.host_state()
    for i in range(200):
        pod = make_pod(f"p-{i}", labels={"app": "plain"})
        pod.spec.node_name = nodes[i % 6].metadata.name
        enc.add_pod(pod)
    enc.host_state()
    terms = v1.PodAffinity(
        preferred_during_scheduling_ignored_during_execution=[
            _term(f"s-{k}", v1.LABEL_ZONE, 10 + k) for k in range(8)])
    many = make_pod("many", labels={"app": "many"},
                    affinity=v1.Affinity(pod_affinity=terms))
    many.spec.node_name = nodes[0].metadata.name
    enc.add_pod(many)
    enc.host_state()
    assert int(enc._score_terms.valid.sum()) == 8
    assert 8 * 12000 // 201 <= enc._score_terms.cap < 1024
