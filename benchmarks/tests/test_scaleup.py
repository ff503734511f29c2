"""The scaleup-waves kind and the deployments cell, rehearsed on a CPU:
rehearsal-deployments-96n.rehearsal-scaleup-waves is the cell
deployments-5000n.scaleup-waves at a size the interpreter can hold (26
Deployments of the four request shapes, 4 of them first seen inside the
window)."""

import importlib.util
import json
import os

import pytest

from conftest import BENCH
from test_rehearsal import run_cell

CELL = "rehearsal-deployments-96n.rehearsal-scaleup-waves"


def _kind():
    spec = importlib.util.spec_from_file_location(
        "scaleup_waves", os.path.join(BENCH, "kinds", "scaleup-waves.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load(directory, name):
    with open(os.path.join(BENCH, directory, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run():
    p = run_cell(CELL, "--rehearse", trace=1, seconds=4)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_cell_is_correct_on_one_live_session(run):
    assert run["correct"] is True, run["checks"]
    assert all(c["value"] == 0 for c in run["checks"].values())
    assert run["failed"] == 0 and run["attempted"] > 150
    d = run["detail"]
    assert d["session_rebuilds"] == {}
    assert d["executables"] and set(d["executables"].values()) == {"aot"}


def test_specs_are_admitted_inside_the_window(run):
    m = run["metrics"]
    assert m["template_rebuilds"]["value"] == 0.0
    assert m["templates_per_launch"]["value"] > 8
    assert m["template_admit_ms"]["value"] > 0
    admits = run["detail"]["notes"]["template_admits"]
    # the 4 fresh Deployments, however the draw groups them
    assert admits["specs"] == 4 and 1 <= admits["admissions"] <= 4
    # a device_trace metric is never reported from a CPU
    assert "table_kernel_roofline" not in m


@pytest.mark.parametrize("control", ["sampled", "last-max"])
def test_a_control_is_not_correct(control):
    p = run_cell(CELL, "--rehearse", "--control", control, seconds=3)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["checks"]["mismatched_binds"]["value"] > 0


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11])
def test_every_seed_draws_the_same_scale_ups(seed):
    kind = _kind()
    templates = _load("configs", "deployments-5000n")["pod_templates"]
    traffic = _load("traffic", "scaleup-waves")
    base = sorted((t, s) for t, _, s in kind.scaleups(templates, traffic, 0))
    got = kind.scaleups(templates, traffic, seed)
    assert sorted((t, s) for t, _, s in got) == base
    pods = sum(s for _, _, s in got)
    assert traffic["max_pods"] - 30 < pods <= traffic["max_pods"]
    by_size = {s: sum(z for _, _, z in got if z == s) / pods
               for s in traffic["scaleup_sizes"]}
    assert abs(by_size[250] - .25) < .01 and abs(by_size[5] - .5) < .01
    by_t = {t: sum(z for u, _, z in got if u == t) / pods for t in templates}
    assert all(abs(by_t[t] - templates[t]["share"]) < .01 for t in templates)
    # a 2048-pod top-up carries about 220 distinct Deployments (fewer
    # when a 250-replica scale-up falls into it, more when none does)
    n, specs = 0, set()
    for t, g, s in got:
        if n >= 2048:
            break
        specs.add((t, g))
        n += s
    assert 150 <= len(specs) <= 330
    # round-robin: every Deployment of a template within one scale-up
    for t in templates:
        per = {}
        for u, g, _ in got:
            if u == t:
                per[g] = per.get(g, 0) + 1
        assert len(per) == templates[t]["deployments"]
        assert max(per.values()) - min(per.values()) <= 1


def test_fresh_deployments_are_the_highest_and_held_until_eligible():
    kind = _kind()
    templates = _load("configs", "deployments-5000n")["pod_templates"]
    fresh = kind.fresh_groups(templates, 32)
    assert {t: len(g) for t, g in fresh.items()} == {
        "web": 16, "small": 8, "ha": 4, "worker": 4}
    assert fresh["web"] == list(range(240, 256))
    # no pod of set-up names one: init pods and warm batches stay below
    cfg = _load("configs", "deployments-5000n")
    assert cfg["init_groups"] <= min(fresh[cfg["init_template"]])
    for wb in _load("traffic", "scaleup-waves")["warm_batches"]:
        assert wb["groups"] <= min(fresh[wb["template"]])
    runs = [(("web", 0), [0, 1]), (("web", 255), [2, 3, 4]),
            (("small", 1), [5]), (("web", 255), [6])]
    seq = kind.Draw(runs, {("web", 255): 0.0})
    assert len(seq) == 7
    assert seq[:3] == [0, 1, 5]          # the fresh one is passed over
    seq.t_open = 0.0                     # ... the window is open: eligible
    assert [seq[k] for k in range(3, 7)] == [2, 3, 4, 6]
