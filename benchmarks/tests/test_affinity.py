"""The affinity deployment, rehearsed on a CPU:
rehearsal-affinity-96n.rehearsal-scaleup-waves is the cell
affinity-5000n.scaleup-waves at a size the interpreter can hold (26
Deployments with the configuration's terms), and the reference is held to
the builder's pods and to what its score changes."""

import importlib.util
import json
import os

import pytest

from conftest import BENCH
from test_rehearsal import run_cell

CELL = "rehearsal-affinity-96n.rehearsal-scaleup-waves"


def _module(directory, name):
    spec = importlib.util.spec_from_file_location(
        f"{directory}_{name}".replace("-", "_"),
        os.path.join(BENCH, directory, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _classes(config):
    out = []
    for name, t in config["pod_templates"].items():
        for g in range(t["deployments"]):
            out.append(dict(t, labels={"app": f"{name}-{g}"}))
    return out


def req(side):
    return (side and side.required_during_scheduling_ignored_during_execution
            ) or []


def pref(side):
    return (side and side.preferred_during_scheduling_ignored_during_execution
            ) or []


@pytest.fixture(scope="module")
def run():
    p = run_cell(CELL, "--rehearse", trace=1, seconds=4)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_rehearsal_is_correct_on_one_session(run):
    assert run["correct"] is True, run["checks"]
    assert all(c["value"] == 0 for c in run["checks"].values())
    assert run["failed"] == 0 and run["attempted"] > 150
    assert run["detail"]["session_rebuilds"] == {}
    m = run["metrics"]
    assert m["inexact_builds"]["value"] == 0.0
    assert m["template_rebuilds"]["value"] == 0.0
    assert m["score_rows_per_launch"]["value"] > 0
    assert run["detail"]["notes"]["score_pods_per_launch"] > 0


@pytest.mark.parametrize("name", ["affinity-5000n", "rehearsal-affinity-96n"])
def test_builder_and_reference_read_the_same_terms(name):
    """The term rule is written twice, on purpose: the builder's pods
    carry exactly the terms the reference reads."""
    config = _config(name)
    builder = _module("builders", "affinity")
    ref = _module("references", "affinity")
    for c in _classes(config):
        pod = builder.build_pod("p", c)
        aff = pod.spec.affinity
        got = []
        pa = aff.pod_affinity if aff else None
        pn = aff.pod_anti_affinity if aff else None
        for kind, terms in (("required", req(pa)), ("preferred", pref(pa)),
                            ("preferred_anti", pref(pn))):
            for t in terms:
                w, t = (t.weight, t.pod_affinity_term) \
                    if kind != "required" else (0, t)
                got.append((kind, t.topology_key, w,
                            t.label_selector.match_labels["app"]))
        assert sorted(got) == sorted(ref.AffinityClass(c).terms)
        assert bool(req(pn)) == bool(c.get("anti_affinity_hostname"))


def test_every_worker_finds_its_cache_placed_in_set_up():
    config = _config("affinity-5000n")
    with open(os.path.join(BENCH, "traffic", "scaleup-waves.json")) as f:
        traffic = json.load(f)
    warm = [w["template"] for w in traffic["warm_batches"]]
    ha_warm = next(w for w in traffic["warm_batches"] if w["template"] == "ha")
    assert warm.index("ha") < warm.index("worker")
    (term,) = config["pod_templates"]["worker"]["pod_affinity"]
    assert term["kind"] == "required" and term["select_app"] == "ha"
    assert term["group_mod"] <= ha_warm["groups"]


def _replay(config, seq, variant="", **patch):
    ref = _module("references", "affinity")
    for k, v in patch.items():
        setattr(ref, k, v)
    classes = _classes(config)
    binds, _ = ref.replay(config, classes,
                          [("create", i, c) for i, c in enumerate(seq)],
                          variant)
    return [binds[i] for i in range(len(seq))]


def _seq(config, n):
    """Pods in the order set-up makes them: web, then caches, then the
    rest mixed."""
    classes = _classes(config)
    by = {}
    for i, c in enumerate(classes):
        by.setdefault(c["labels"]["app"].rsplit("-", 1)[0], []).append(i)
    head = [by["web"][i % 10] for i in range(60)] + \
        [by["ha"][i % 3] for i in range(30)] + \
        [by["worker"][i % 4] for i in range(40)]
    return head + [i % len(classes) for i in range(n - len(head))]


@pytest.mark.parametrize("variant", ["sampled", "last-max"])
def test_controls_differ_from_the_reference(variant):
    config = _config("rehearsal-affinity-96n")
    seq = _seq(config, 700)
    assert _replay(config, seq) != _replay(config, seq, variant)


def test_the_affinity_score_moves_binds():
    """A reference without InterPodAffinity's score decides otherwise: the
    cell checks the score, not only the filters."""
    config = _config("rehearsal-affinity-96n")
    seq = _seq(config, 700)
    assert _replay(config, seq) != _replay(config, seq, IPA_WEIGHT=0)
