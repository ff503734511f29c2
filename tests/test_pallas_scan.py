"""PallasSession decision parity with the jnp HoistedSession (which is
itself pinned to the generic scan and the Go oracle).

Runs the kernel in interpreter mode on CPU — semantics only; the
single-launch performance story is bench.py's job on real hardware.
"""

import copy

import numpy as np
import pytest

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.ops.hoisted import HoistedSession, template_fingerprint
from kubernetes_tpu.ops.pallas_scan import (
    IPA_EXACT_MAX,
    PallasSession,
    PallasUnsupported,
)
from kubernetes_tpu.testing.synth import synth_cluster, synth_pending_pods

from .test_hoisted import _encode_all, _presized_encoding
from .util import make_pod


def _templates_of(arrays):
    out, seen = [], set()
    for a in arrays:
        fp = template_fingerprint(a)
        if fp not in seen:
            seen.add(fp)
            out.append(a)
    return out


def _run_pair(nodes, init_pods, pending, batch):
    """(jnp session decisions, pallas session decisions) over batches."""
    enc, pe = _presized_encoding(
        copy.deepcopy(nodes), copy.deepcopy(init_pods), copy.deepcopy(pending))
    arrays = _encode_all(enc, pe, pending)
    templates = _templates_of(arrays)
    jsess = HoistedSession(enc.device_state(), templates)
    ref = []
    for i in range(0, len(pending), batch):
        b = arrays[i:i + batch]
        # decisions() returns the padded batch bucket; real entries first
        ref.extend(HoistedSession.decisions(jsess.schedule(b))[:len(b)])

    enc2, pe2 = _presized_encoding(nodes, init_pods, pending)
    arrays2 = _encode_all(enc2, pe2, pending)
    psess = PallasSession(enc2.device_state(), _templates_of(arrays2),
                          interpret=True)
    got = []
    for i in range(0, len(pending), batch):
        b = arrays2[i:i + batch]
        got.extend(PallasSession.decisions(psess.schedule(b))[:len(b)])
    return ref, got


class TestPallasParity:
    def test_spread_multi_batch(self):
        nodes, init_pods = synth_cluster(16, pods_per_node=2)
        pending = synth_pending_pods(36, spread=True)
        ref, got = _run_pair(nodes, init_pods, pending, batch=12)
        assert got == ref
        assert all(d >= 0 for d in got)

    def test_no_constraints(self):
        nodes, init_pods = synth_cluster(10, pods_per_node=1)
        pending = synth_pending_pods(16, spread=False)
        ref, got = _run_pair(nodes, init_pods, pending, batch=8)
        assert got == ref

    def test_capacity_exhaustion(self):
        nodes, init_pods = synth_cluster(3, pods_per_node=0)
        for node in nodes:
            node.status.allocatable["cpu"] = "350m"
            node.status.capacity["cpu"] = "350m"
        pending = synth_pending_pods(15, spread=True)
        ref, got = _run_pair(nodes, init_pods, pending, batch=5)
        assert got == ref
        assert -1 in got

    def test_hostname_hard_spread(self):
        nodes, init_pods = synth_cluster(6, pods_per_node=1)
        pending = []
        for i in range(10):
            pending.append(make_pod(
                f"hard-{i}", cpu="50m", labels={"app": "hard"},
                constraints=[v1.TopologySpreadConstraint(
                    max_skew=1,
                    topology_key=v1.LABEL_HOSTNAME,
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=v1.LabelSelector(
                        match_labels={"app": "hard"}),
                )]))
        ref, got = _run_pair(nodes, init_pods, pending, batch=5)
        assert got == ref
        assert len(set(got[:6])) == 6

    def test_mixed_templates_cross_counting(self):
        nodes, init_pods = synth_cluster(8, pods_per_node=1)
        pending = []
        for i in range(12):
            labels = {"tier": "web", "idx": f"t{i % 2}"}
            pending.append(make_pod(
                f"x-{i}", cpu="50m", labels=labels,
                constraints=[v1.TopologySpreadConstraint(
                    max_skew=1,
                    topology_key=v1.LABEL_ZONE,
                    when_unsatisfiable="ScheduleAnyway",
                    label_selector=v1.LabelSelector(
                        match_labels={"tier": "web"}),
                )]))
        ref, got = _run_pair(nodes, init_pods, pending, batch=6)
        assert got == ref

    def test_tainted_and_labeled_cluster(self):
        # synth_cluster taints some nodes and labels zones; spread pods
        # exercise taint counts + zone spread together
        nodes, init_pods = synth_cluster(12, pods_per_node=2)
        pending = synth_pending_pods(24, spread=True)
        ref, got = _run_pair(nodes, init_pods, pending, batch=24)
        assert got == ref


class TestPallasGuards:
    def test_large_weights_unsupported(self):
        from kubernetes_tpu.testing.synth import synth_cluster, synth_pending_pods
        nodes, init_pods = synth_cluster(4, pods_per_node=1)
        pending = synth_pending_pods(4, spread=True)
        enc, pe = _presized_encoding(nodes, init_pods, pending)
        arrays = _encode_all(enc, pe, pending)
        with pytest.raises(PallasUnsupported):
            PallasSession(enc.device_state(), _templates_of(arrays),
                          weights={"balanced": 1, "image": 1, "ipa": 1,
                                   "least": 1, "node_affinity": 1,
                                   "prefer_avoid": 10 ** 6, "pts": 2,
                                   "taint": 1}, interpret=True)

    def test_variable_batch_lengths_share_one_compile(self):
        """B_real is dynamic: batches of different lengths (same padded
        width) must hit the same compiled kernel and stay exact."""
        import copy
        nodes, init_pods = synth_cluster(8, pods_per_node=1)
        pending = synth_pending_pods(20, spread=True)
        ref, got = [], []
        enc, pe = _presized_encoding(
            copy.deepcopy(nodes), copy.deepcopy(init_pods),
            copy.deepcopy(pending))
        arrays = _encode_all(enc, pe, pending)
        js = HoistedSession(enc.device_state(), _templates_of(arrays))
        for lo, hi in ((0, 7), (7, 12), (12, 20)):  # lengths 7, 5, 8
            ref.extend(HoistedSession.decisions(js.schedule(arrays[lo:hi])))
        enc2, pe2 = _presized_encoding(nodes, init_pods, pending)
        arrays2 = _encode_all(enc2, pe2, pending)
        ps = PallasSession(enc2.device_state(), _templates_of(arrays2),
                           interpret=True)
        for lo, hi in ((0, 7), (7, 12), (12, 20)):
            got.extend(PallasSession.decisions(ps.schedule(arrays2[lo:hi])))
        assert got == ref


class TestPallasFuzz:
    """Random-shape fuzz of the pallas kernel (interpret mode) against
    the jnp session: the f32 in-kernel score math is fuzz-TESTED, not
    asserted (VERDICT r1 item 10). Since round 3 the kernel carries the
    IPA term machinery (D1-D5 deltas), so fuzz pods KEEP their random
    (anti-)affinity terms; only host ports are stripped (still a
    hoisted-session fallback). Spread constraints, taints, tolerations,
    priorities, images and extended resources all vary."""

    @pytest.mark.parametrize("seed", range(8))
    def test_fuzz_jnp_vs_pallas_interpret(self, seed):
        import random as _random

        from .test_kernel_parity import random_cluster, random_pending

        rng = _random.Random(1000 + seed)
        nodes, init_pods = random_cluster(rng)
        pending = []
        for i in range(10):
            p = random_pending(rng)
            p.metadata.name = f"fz-{seed}-{i}"
            for c in p.spec.containers:
                c.ports = None           # pallas: port-free templates only
            p.spec.node_name = ""
            pending.append(p)
        try:
            ref, got = _run_pair(nodes, init_pods, pending, batch=5)
        except PallasUnsupported as e:
            pytest.skip(f"shape unsupported by pallas: {e}")
        assert got == ref, f"seed={seed}: {got} != {ref}"


def _affinity(zone=False, anti=True, labels=None, pref=None):
    term = v1.PodAffinityTerm(
        label_selector=v1.LabelSelector(match_labels=dict(labels)),
        topology_key=v1.LABEL_ZONE if zone else v1.LABEL_HOSTNAME,
    )
    kw = {}
    if anti:
        kw["pod_anti_affinity"] = v1.PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[term])
    else:
        kw["pod_affinity"] = v1.PodAffinity(
            required_during_scheduling_ignored_during_execution=[term])
    if pref:
        w, plabels, pzone = pref
        pterm = v1.WeightedPodAffinityTerm(
            weight=w,
            pod_affinity_term=v1.PodAffinityTerm(
                label_selector=v1.LabelSelector(match_labels=dict(plabels)),
                topology_key=v1.LABEL_ZONE if pzone else v1.LABEL_HOSTNAME,
            ),
        )
        pa = kw.get("pod_affinity") or v1.PodAffinity()
        pa.preferred_during_scheduling_ignored_during_execution = [pterm]
        kw["pod_affinity"] = pa
    return v1.Affinity(**kw)


class TestPallasTerms:
    """Decision parity for TERM templates riding the pallas kernel (the
    r3 D1-D5 port): required anti-affinity (hostname + zone), required
    affinity incl. the first-pod-in-series escape, preferred terms, and
    cross-template D1 interactions — all vs the jnp hoisted session
    (itself pinned to the Go-semantics oracle in test_hoisted_terms).
    Existing bound pods with terms exercise the static parts."""

    def _nodes(self, n=16):
        from .util import make_node

        return [
            make_node(
                f"n-{i}",
                labels={
                    v1.LABEL_HOSTNAME: f"n-{i}",
                    "zone": f"zone-{i % 4}",
                    v1.LABEL_ZONE: f"zone-{i % 4}",
                },
            )
            for i in range(n)
        ]

    def _case(self, lbl, affinity, n_nodes=16, n_existing=6, n_pending=24,
              batch=10):
        nodes = self._nodes(n_nodes)
        existing = [
            make_pod(f"ex-{i}", labels=dict(lbl), affinity=affinity,
                     node_name=f"n-{i * 2}")
            for i in range(n_existing)
        ]
        pending = [
            make_pod(f"p-{i}", labels=dict(lbl), affinity=affinity)
            for i in range(n_pending)
        ]
        return _run_pair(nodes, existing, pending, batch)

    def test_hostname_required_anti(self):
        ref, got = self._case(
            {"app": "a"}, _affinity(zone=False, anti=True, labels={"app": "a"}))
        assert got == ref

    def test_zone_required_anti(self):
        ref, got = self._case(
            {"app": "z"}, _affinity(zone=True, anti=True, labels={"app": "z"}))
        assert got == ref

    def test_required_affinity_first_pod_escape(self):
        # no existing pods: the first pending pod only lands via the
        # counts-empty + self-match escape (filtering.go:357)
        ref, got = self._case(
            {"svc": "b"}, _affinity(zone=True, anti=False, labels={"svc": "b"}),
            n_existing=0)
        assert got == ref
        assert got[0] >= 0  # the escape must actually fire

    def test_preferred_terms_score(self):
        ref, got = self._case(
            {"w": "c"},
            _affinity(zone=False, anti=True, labels={"w": "c"},
                      pref=(40, {"w": "c"}, True)))
        assert got == ref

    @staticmethod
    def _pref_only_affinity(weight, labels, anti=False):
        """Preferred-only terms at harness weight (no required terms) —
        the SchedulingPreferredPod(Anti)Affinity template shape."""
        pterm = v1.WeightedPodAffinityTerm(
            weight=weight,
            pod_affinity_term=v1.PodAffinityTerm(
                label_selector=v1.LabelSelector(match_labels=dict(labels)),
                topology_key=v1.LABEL_ZONE,
            ),
        )
        if anti:
            return v1.Affinity(pod_anti_affinity=v1.PodAntiAffinity(
                preferred_during_scheduling_ignored_during_execution=[pterm]))
        return v1.Affinity(pod_affinity=v1.PodAffinity(
            preferred_during_scheduling_ignored_during_execution=[pterm]))

    @pytest.mark.parametrize("anti", [False, True])
    def test_weight100_preferred_rides_pallas(self, anti):
        """The bench Preferred-affinity templates (weight-100 preferred
        zone terms toward self labels) must BUILD a PallasSession — the
        w45 GCD rescale keeps the exact-f32 guard satisfied (these
        configs silently rode the ~4x-slower HoistedSession for two
        rounds) — and the decisions must stay bit-identical."""
        nodes = self._nodes(12)
        aff = self._pref_only_affinity(100, {"app": "aff"}, anti=anti)
        # plain init-template pods plus weighted-preferred pods, mixed:
        # cross-template D4/D5 weight rows are where the scale applies
        pending = []
        for i in range(18):
            if i % 3 == 0:
                pending.append(make_pod(f"pl-{i}", labels={"app": "aff"}))
            else:
                pending.append(make_pod(
                    f"pr-{i}", labels={"app": "aff"}, affinity=aff))
        ref, got = _run_pair(nodes, [], pending, batch=6)
        assert got == ref

    @pytest.mark.parametrize("anti", [False, True])
    def test_weight100_preferred_builds_pallas_session(self, anti):
        """Construction-level gate (no kernel launch — runs on any
        host): the weight-100 preferred template must not raise
        PallasUnsupported(ipa-score-weights), and the GCD scale must be
        recorded for the kernel's multiply-back."""
        nodes = self._nodes(12)
        aff = self._pref_only_affinity(100, {"app": "aff"}, anti=anti)
        pending = [make_pod("pl-0", labels={"app": "aff"})] + [
            make_pod(f"pr-{i}", labels={"app": "aff"}, affinity=aff)
            for i in range(3)
        ]
        enc, pe = _presized_encoding(nodes, [], pending)
        arrays = _encode_all(enc, pe, pending)
        sess = PallasSession(enc.device_state(), _templates_of(arrays),
                             interpret=True)
        assert sess.dyn_ipa
        # every preferred pod's score row takes weight 100 from each
        # writer; the most a raw score can read (weights times the pods a
        # domain holds) stays inside the exact normalization
        readers = [r for r in sess._rows if r["score"]]
        assert readers and all(
            0 < 2 * r["score_bound"] < IPA_EXACT_MAX for r in readers)
        # the score touches alone: no presence row is kept beside them
        assert {w for touch in sess._touch for _, _, w, _ in touch} == {
            -100 if anti else 100}

    def test_cross_template_anti(self):
        # template A's anti terms must repel template B pods assumed in
        # the SAME session (D1 across templates)
        nodes = self._nodes(12)
        aff_a = _affinity(zone=True, anti=True, labels={"grp": "x"})
        pending = []
        for i in range(16):
            if i % 2 == 0:
                pending.append(make_pod(
                    f"a-{i}", labels={"grp": "x"}, affinity=aff_a))
            else:
                # B pods carry the label A's terms select, but no terms
                pending.append(make_pod(f"b-{i}", labels={"grp": "x"}))
        ref, got = _run_pair(nodes, [], pending, batch=8)
        assert got == ref

    def test_term_session_survives_batches(self):
        # carry correctness across MANY small batches (u_cnt/k_cnt chain)
        ref, got = self._case(
            {"app": "m"}, _affinity(zone=False, anti=True, labels={"app": "m"}),
            n_nodes=10, n_existing=0, n_pending=20, batch=4)
        assert got == ref


class TestAdmit:
    """PallasSession.admit at the session level (the backend's use of it
    is in tests/test_pallas_table.py): a spec taken into a live session
    decides as a session built with it from the start."""

    def _split(self, pending, n_first):
        nodes, init_pods = synth_cluster(12, pods_per_node=1)
        enc, pe = _presized_encoding(
            copy.deepcopy(nodes), copy.deepcopy(init_pods),
            copy.deepcopy(pending))
        arrays = _encode_all(enc, pe, pending)
        whole = HoistedSession(enc.device_state(), _templates_of(arrays))
        ref = HoistedSession.decisions(whole.schedule(arrays))[:len(arrays)]
        enc2, pe2 = _presized_encoding(nodes, init_pods, pending)
        arrays2 = _encode_all(enc2, pe2, pending)
        return enc2, arrays2, ref, n_first

    def test_admit_matches_a_session_built_with_the_spec(self):
        pending = synth_pending_pods(10, spread=True) + [
            make_pod(f"late-{i}", cpu="300m", memory="200Mi",
                     labels={"app": "late"}) for i in range(6)]
        enc, arrays, ref, n = self._split(pending, 10)
        sess = PallasSession(enc.device_state(), _templates_of(arrays[:n]),
                             interpret=True)
        got = PallasSession.decisions(sess.schedule(arrays[:n]))[:n]
        # the encoding the prologue reads has to hold the first batch
        for a, lane, pod in zip(arrays[:n], got, pending[:n]):
            enc.add_pod(pod, enc.node_names[lane])
        out = sess.admit(enc.host_state, arrays[n:])
        assert out["n"] == 1 and sess.admits == 1
        got += PallasSession.decisions(sess.schedule(arrays[n:]))[:6]
        assert got == ref

    def test_full_table_raises_table_full(self):
        from kubernetes_tpu.ops.pallas_scan import TableFull

        pending = [make_pod(f"p-{i}", cpu="100m", labels={"app": f"a{i}"})
                   for i in range(66)]
        enc, arrays, _ref, _n = self._split(pending, 2)
        sess = PallasSession(enc.device_state(), arrays[:2], interpret=True)
        assert sess.Tcap == 64
        sess.admit(enc.host_state, arrays[2:64])
        assert sess.specs == 64
        with pytest.raises(TableFull) as e:
            sess.admit(enc.host_state, arrays[64:])
        assert e.value.reason == "table-full"
