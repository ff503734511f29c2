"""Wave launches: a run of preemptors of one view, template and priority
planned in one what-if launch, the pick and the claim inside the program
(ops/whatif._whatif_wave_run), the picks replayed on the host.

The device rung's answers must not move with how many preemptors a
launch plans: a planner on wave launches must return exactly the
candidates (node, victims in order) and `fits_now` verdicts of a planner
that launches every preemptor alone (`wave_launch=False`), which
tests/test_preemption_fast.py pins to the oracle, and leave the same
books. Where a claim is not lane-local (PDB-covered victims, victims or
preemptors that match the template's spread classes or required terms,
gang units) every preemptor launches alone, the reason counted in
scheduler_whatif_planned_total.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.ops import whatif
from kubernetes_tpu.scheduler import metrics
from kubernetes_tpu.scheduler.framework.snapshot import Snapshot
from kubernetes_tpu.scheduler.internal.nominator import PodNominator
from kubernetes_tpu.scheduler.preemption import FastPreemptionPlanner
from kubernetes_tpu.scheduler.preemption_device import (
    DevicePreemptionPlanner,
    _Inputs,
)
from kubernetes_tpu.testing.synth import make_node, make_pod

from .test_preemption_fast import TestGangVictimParity, _mk_backend
from .test_wave_books import _state
from .test_whatif_resident import _affinity, _burst, _pdb, _spread, _wave_case


def _planned() -> dict:
    return dict(metrics.whatif_planned.items())


def _moved(before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in _planned().items()
            if v != before.get(k, 0)}


def _plan(nodes, pods, wave, wave_launch=True, nominator=None,
          eligibility=None, cls=DevicePreemptionPlanner, **kw):
    planner = cls(
        Snapshot.from_objects(pods, nodes), nominator or PodNominator(),
        _mk_backend(nodes, pods),
        eligibility=eligibility or {v1.pod_key(p): (True, False)
                                    for p in wave},
        wave_launch=wave_launch, **kw)
    return planner, planner.plan(wave)


def _summary(cands):
    return [None if c is None else
            (c.node_name, [v1.pod_key(p) for p in c.victims],
             c.num_pdb_violations) for c in cands]


def _keys(planner):
    """{(template, priority): (view, its inputs)} of a planner."""
    views = {id(ctx): ctx for ctx in planner._ctx.values()}
    return {(tj, prio): (views[view], inp)
            for (view, tj, prio), inp in planner._inputs.items()}


def _fresh(planner, ctx, tj, prio, inp=None):
    """A key's running totals taken into `inp` (a new _Inputs by
    default) from the entries it has not taken yet."""
    nps = ctx.np_slices(tj)
    same_key = nps["f_same_key"].astype(np.int32)
    if inp is None:
        inp = _Inputs(ctx.n_lanes, planner._enc_r, same_key.shape[0],
                      nps["ipaaa_valid"].shape[0], ctx.vnp)
    planner._nom_take(ctx, nps, tj, prio, inp, same_key)
    planner._pre_take(ctx, nps, tj, inp, same_key)
    return inp, nps, same_key


def _plain(inp):
    return [{k: v.tolist() if isinstance(v, np.ndarray) else v
             for k, v in acc.items()} for acc in (inp.nom, inp.pre)]


def _assert_resident_is_the_books(planner):
    """Each key's running totals, with what it has not taken yet, are
    every entry of the books taken once. Where they are up to date (a
    wave launch takes its own claims in), its inputs on the device equal
    a whole upload built afresh: drains and nominated load exactly, each
    lane's victim slots as the sequence of its valid slots (the device
    leaves a claimed slot in place, zeroed; a whole upload packs the
    rest to the front)."""
    for (tj, prio), (ctx, inp) in _keys(planner).items():
        current = (inp.claims == len(planner._claimed_at)
                   and inp.nom["done"] == len(planner._nom_entries)
                   and inp.pre["done"] == len(planner._pre))
        caught = _Inputs(ctx.n_lanes, 1, 1, 1, 1)
        caught.nom = {k: np.copy(v) for k, v in inp.nom.items()}
        caught.pre = {k: np.copy(v) for k, v in inp.pre.items()}
        _fresh(planner, ctx, tj, prio, caught)
        fresh, nps, same_key = _fresh(planner, ctx, tj, prio)
        assert _plain(caught) == _plain(fresh), (tj, prio)
        if inp.x is None or not current:
            continue
        fresh.L = inp.L
        full, _ = planner._full_inputs(ctx, nps, tj, prio, fresh, same_key)
        dev = {k: np.asarray(a) for k, a in inp.x.items()}
        for k in whatif.INPUT_KEYS:
            if k.startswith("v_"):
                continue
            assert np.array_equal(dev[k], np.asarray(full[k])), k
        for lane in range(ctx.n_lanes):
            for k in ("v_cnt", "v_req", "v_mfs", "v_manti", "v_mall"):
                a = dev[k][lane][dev["v_valid"][lane].astype(bool)]
                b = full[k][lane][full["v_valid"][lane].astype(bool)]
                assert np.array_equal(a, b), (k, lane)


def _both(nodes, pods, wave, **kw):
    """Plan the wave alone-launched and on wave launches; assert the
    same candidates, fits_now and books. Returns (wave planner, its
    candidates, what scheduler_whatif_planned_total moved, and
    scheduler_whatif_launches_total under it as ("launches",))."""
    single, scands = _plan(nodes, pods, wave, wave_launch=False, **kw)
    before = _planned()
    launches0 = metrics.whatif_launches.value()
    waved, wcands = _plan(nodes, pods, wave, **kw)
    moved = _moved(before)
    moved["launches",] = metrics.whatif_launches.value() - launches0
    assert waved.planner_paths == single.planner_paths
    assert waved.fits_now == single.fits_now
    assert _summary(wcands) == _summary(scands)
    assert _state(waved) == _state(single)
    assert waved._claimed_at == single._claimed_at
    _assert_resident_is_the_books(waved)
    return waved, wcands, moved


def test_a_burst_of_300_plans_in_five_wave_launches():
    """The bursts cell in small, 300 preemptors of one template, four
    victims on every node: five launches of up to 64, each preemptor
    against every earlier claim."""
    nodes, pods, wave = _burst(310, 300)
    planner, cands, moved = _both(nodes, pods, wave)
    assert moved == {("wave", "lane-local"): 300, ("launches",): 5}
    assert all(c is not None and len(c.victims) == 3 for c in cands)
    assert len({c.node_name for c in cands}) == 300


@pytest.mark.parametrize("case", ["nominated", "claimed", "two-prio"])
def test_random_waves_match_single_launches(case):
    """Random nearly full clusters of mixed priorities and start times
    (the pick's later ladder rungs decide), with nominees above and
    below the wave's priority, victims an earlier wave claimed, or two
    priorities drawn pod by pod: every run on wave launches."""
    rng = random.Random(f"wave-{case}")
    planned = 0
    for _ in range(6):
        nodes, pods, wave, kwargs = _wave_case(case, rng)
        _, cands, moved = _both(nodes, pods, wave, **kwargs)
        assert moved.pop(("wave", "lane-local")) == len(wave)
        assert set(moved) == {("launches",)}
        planned += sum(c is not None for c in cands)
    assert planned >= 6


def test_two_priorities_interleaved():
    """Runs of two priorities in turn: each run is its own key's wave
    launch, and each key's inputs take the other's claims in. A
    priority-20 preemptor fits now beside the priority-10 nominees
    (nominated pods of a lower priority are no load to it) in the room
    their victims left; a priority-10 one does not."""
    nodes, pods, _ = _burst(40, 0)
    wave = []
    for run, (prio, n) in enumerate(((10, 5), (20, 4), (10, 7), (20, 3),
                                     (10, 2))):
        wave += [make_pod(f"hi-{run}-{k}", cpu="3000m", memory="500Mi",
                          priority=prio) for k in range(n)]
    planner, cands, moved = _both(nodes, pods, wave)
    assert moved == {("wave", "lane-local"): len(wave), ("launches",): 5}
    assert planner.fits_now == ([False] * 5 + [True] * 4 + [False] * 7
                                + [True] * 3 + [False] * 2)
    assert sum(c is not None for c in cands) == 14


def test_nominees_of_an_earlier_wave():
    """Nominated pods above and below the wave's priority: the ones at
    or above it are load on their nodes, the others are not."""
    rng = random.Random("wave-nominees")
    nodes, pods, wave = _burst(24, 20)
    nominator = PodNominator()
    for k, prio in enumerate((50, 10, 10, 1, 100)):
        ghost = make_pod(f"ghost{k}", cpu=f"{rng.choice([500, 1500])}m",
                         memory="256Mi", priority=prio)
        nominator.add_nominated_pod(ghost, f"n{rng.randrange(24)}")
    _, _, moved = _both(nodes, pods, wave, nominator=nominator)
    assert moved == {("wave", "lane-local"): 20, ("launches",): 1}


def test_a_preemptor_that_fits_now_in_the_middle_of_a_run():
    """A 1000m preemptor evicts a node's one 3500m pod: the next finds
    the room the claim left beside the nominee and fits now, claiming
    nothing, as does every one after it."""
    nodes = [make_node(f"n{i}", cpu="4", memory="32Gi", pods=110)
             for i in range(6)]
    pods = [make_pod(f"low-{i}", cpu="3500m", memory="500Mi",
                     node_name=f"n{i}", priority=1) for i in range(6)]
    wave = [make_pod(f"hi-{k}", cpu="1000m", memory="500Mi", priority=100)
            for k in range(5)]
    planner, cands, moved = _both(nodes, pods, wave)
    assert planner.fits_now == [False, True, True, True, True]
    assert _summary(cands)[0] == ("n0", ["default/low-0"], 0)
    assert moved == {("wave", "lane-local"): 5, ("launches",): 1}


def test_a_device_ineligible_pod_splits_a_run():
    """A pod the device rung may not plan goes to the fast rung between
    two wave launches of the same key, on the same books."""
    nodes, pods, wave = _burst(30, 20)
    elig = {v1.pod_key(p): (True, True) for p in wave}
    elig[v1.pod_key(wave[9])] = (False, True)
    planner, _, moved = _both(nodes, pods, wave, eligibility=elig)
    assert planner.planner_paths == ["device"] * 9 + ["fast"] + \
        ["device"] * 10
    assert moved == {("wave", "lane-local"): 19, ("launches",): 2}


class _Alternating(DevicePreemptionPlanner):
    """Every second run of the wave launches its preemptors alone."""

    def _single_reason(self, k, run):
        self._runs = getattr(self, "_runs", 0) + 1
        return "pairs" if self._runs % 2 == 0 else None


def test_a_wave_launch_then_single_launches_of_the_same_key():
    """Single launches after a wave launch of their key send a delta
    into the inputs the wave launch left: the claims it made there are
    taken in once, and a third run's wave launch after them agrees too."""
    nodes, pods, wave = _burst(170, 160)
    delta0 = metrics.whatif_inputs.value(path="delta", reason="resident")
    _, cands, moved = _both(nodes, pods, wave, cls=_Alternating)
    assert moved == {("wave", "lane-local"): 96, ("single", "pairs"): 64,
                     ("launches",): 66}
    assert metrics.whatif_inputs.value(
        path="delta", reason="resident") - delta0 >= 64
    assert all(c is not None for c in cands)


def test_a_failed_wave_launch_falls_its_first_pod_and_launches_the_rest():
    """A fault in the second wave launch of a 70-pod run: its first
    preemptor falls to the fast rung as its own launch's fault would
    fall it, the other five launch alone after the wave launch of their
    key, and the wave still plans what the fast rung plans."""
    from kubernetes_tpu.testing.faults import FaultInjector

    nodes, pods, wave = _burst(80, 70)
    backend = _mk_backend(nodes, pods)
    inj = FaultInjector()
    backend.faults = inj
    calls = []
    check = backend.check_whatif_fault

    def fault_at_second():
        calls.append(1)
        if len(calls) == 2:
            inj.arm("raise-whatif", shots=1)
        check()

    backend.check_whatif_fault = fault_at_second
    before = _planned()
    planner = DevicePreemptionPlanner(
        Snapshot.from_objects(pods, nodes), PodNominator(), backend,
        eligibility={v1.pod_key(p): (True, True) for p in wave})
    cands = planner.plan(wave)
    assert inj.injected.get("raise-whatif") == 1
    assert planner.planner_paths == ["device"] * 64 + ["fast"] + \
        ["device"] * 5
    assert _moved(before) == {("wave", "lane-local"): 64,
                              ("single", "fault"): 5}
    fast = FastPreemptionPlanner(
        Snapshot.from_objects(pods, nodes), PodNominator()).plan(wave)
    assert _summary(cands) == _summary(fast)
    _assert_resident_is_the_books(planner)


def _labelled(app_of, prio=1):
    """Six nearly full nodes of 900m pods labelled by `app_of(i, j)`."""
    nodes = [make_node(f"n{i}", cpu="4", memory="32Gi", pods=110,
                       labels={"zone": f"z{i % 3}",
                               "kubernetes.io/hostname": f"n{i}"})
             for i in range(6)]
    pods = [make_pod(f"low-{i}-{j}", cpu="900m", memory="500Mi",
                     node_name=f"n{i}", priority=prio,
                     labels={"app": app_of(i, j)})
            for i in range(6) for j in range(4)]
    return nodes, pods


def _fallback_case(case):
    if case == "gang":
        gangs = TestGangVictimParity()
        nodes, pods = _labelled(lambda i, j: "a")
        for j in range(2):
            gangs._stamp(pods[j], "gang-n0", 2)
        return nodes, pods, {}, None
    nodes, pods = _labelled(lambda i, j: "ab"[j % 2])
    if case == "pdb":
        return nodes, pods, {"pdbs": [_pdb("a", 1)]}, None
    if case == "spread":
        return nodes, pods, {}, {"labels": {"app": "a"},
                                 "spread": _spread("a")}
    return nodes, pods, {}, {"affinity": _affinity("kubernetes.io/hostname")}


@pytest.mark.parametrize("case,reason", [("pdb", "pdb"), ("spread", "pairs"),
                                         ("affinity", "pairs"),
                                         ("gang", "gang")])
def test_a_claim_that_is_not_lane_local_launches_alone(case, reason):
    nodes, pods, kw, tmpl = _fallback_case(case)
    wave = []
    for k in range(4):
        pod = make_pod(f"hi-{k}", cpu="1800m", memory="500Mi", priority=100,
                       labels=(tmpl or {}).get("labels"))
        if tmpl and "spread" in tmpl:
            pod.spec.topology_spread_constraints = tmpl["spread"]
        if tmpl and "affinity" in tmpl:
            pod.spec.affinity = tmpl["affinity"]
        wave.append(pod)
    if case == "affinity":
        # the required affinity to app=y needs a y somewhere
        pods[-1].metadata.labels = {"app": "y"}
    _, cands, moved = _both(nodes, pods, wave, **kw)
    assert moved == {("single", reason): 4, ("launches",): 4}
    assert any(c is not None for c in cands)


def test_a_spread_template_whose_counts_no_claim_moves_takes_wave_launches():
    """A template with a hard spread class reads pair counts, but where
    neither the victims nor the preemptors match its selector no claim
    moves one: the claims are lane-local and the wave launch takes them,
    the dry run's spread filter evaluated at every step."""
    nodes, pods = _labelled(lambda i, j: "ab"[j % 2])
    wave = []
    for k in range(6):
        pod = make_pod(f"hi-{k}", cpu="1800m", memory="500Mi", priority=100,
                       labels={"app": "p"})
        pod.spec.topology_spread_constraints = _spread("s")
        wave.append(pod)
    _, cands, moved = _both(nodes, pods, wave)
    assert moved == {("wave", "lane-local"): 6, ("launches",): 1}
    assert sum(c is not None for c in cands) == 6


def test_the_candidate_cut_holds_on_the_device():
    """The candidates are the first `limit` feasible nodes (100 of 120
    here): the 100th holds the cheapest victims among them and is
    picked, though nodes past the cut hold cheaper ones still. Its
    claim leaves it infeasible, so the cut of the next preemptor takes
    in the first node past it."""
    nodes, pods, wave = _burst(120, 2)
    for pod in pods:
        i = int(pod.spec.node_name[1:])
        pod.spec.priority = 5 if i < 99 else 3 if i == 99 else 0
    planner, cands, _ = _both(nodes, pods, wave)
    assert planner._num_candidates() == 100
    assert [c.node_name for c in cands] == ["n99", "n100"]


def test_a_second_wave_of_another_length_compiles_nothing():
    """The wave program is one per inputs' shape and template: a wave of
    70 preemptors (two launches, the second one part inert) and then a
    wave of 9 on the same cluster compile it once."""
    from kubernetes_tpu.utils.device import compile_meter

    nodes, pods, wave = _burst(80, 79)
    _plan(nodes, pods, wave[:70])
    meter = compile_meter()
    requests = meter.read()["requests"]
    cached = whatif._whatif_wave_run._cache_size()
    _, cands = _plan(nodes, pods, wave[70:])
    assert all(c is not None for c in cands)
    assert whatif._whatif_wave_run._cache_size() == cached
    assert meter.read()["requests"] == requests
