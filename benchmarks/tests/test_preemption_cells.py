"""preemption-5000n's rehearsal: a 96-node cluster of four low-priority
pods a node, and bursts of high-priority pods that fit nowhere and
preempt. The run is correct on both planner rungs; the controls, and a
program that reprieves the wrong victim, are not; and the reference on
hand-made logs."""

import json
import os

import numpy as np
import pytest

import run as bench_run
from conftest import REPO
from test_events import values

REHEARSAL = "rehearsal-preemption-96n.rehearsal-bursts"
REF = bench_run.load_module("references", "preemption")
CFG = {"nodes": {"count": 3, "cpu": "4", "memory": "32Gi", "pods": 110,
                 "zones": 1}}
LOW = {"cpu": "900m", "memory": "500Mi", "priority": 0,
       "labels": {"app": "batch"}}
HIGH = {"cpu": "3000m", "memory": "500Mi", "priority": 10,
        "labels": {"app": "prod"}}


def rehearse(capsys, *extra):
    rc = bench_run.main([
        "--workload", REHEARSAL, "--seed", str(2 ** 31 + 11),
        "--seconds", "3", "--trace", "1", "--rehearse", *extra])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("whatif", ["0", "1"])
def test_preemption_is_correct(capsys, monkeypatch, whatif):
    monkeypatch.setenv("KTPU_WHATIF", whatif)
    line = rehearse(capsys)
    assert line["correct"] is True and values(line) == {}
    assert line["failed"] == 0 and line["attempted"] >= 16
    m = line["metrics"]
    assert m["victim_wait_p50_s"]["value"] > 0
    assert m["preempt_plan_ms"]["value"] > 0
    # every preemptor of the window on the rung asked for
    assert m["device_plan_share"]["value"] == (1.0 if whatif == "1" else 0.0)
    if whatif == "1":
        assert m["whatif_launch_ms"]["value"] > 0
        assert m["whatif_context_ms"]["value"] > 0
        assert line["detail"]["notes"]["preemption_planner"][
            "whatif_fallbacks"] == {}


@pytest.mark.parametrize("variant", ["sampled", "last-max"])
def test_preemption_control_is_not_correct(capsys, variant):
    line = rehearse(capsys, "--control", variant)
    assert line["correct"] is False
    assert values(line)["mismatched_binds"] > 0


def test_a_wrong_victim_is_not_correct(capsys, monkeypatch):
    """The program reprieves the LAST-placed of a node's equal pods, not
    the first: every preemptor still takes the reference's node, so only
    the deletes tell."""
    from kubernetes_tpu.scheduler.preemption import FastPreemptionPlanner

    real = FastPreemptionPlanner._build

    def planted(self, wave):
        real(self, wave)
        for i, k in enumerate(self._valive.sum(axis=1)):
            self._vsort[i, :k] = self._vsort[i, :k][::-1]

    monkeypatch.setenv("KTPU_WHATIF", "0")
    monkeypatch.setattr(FastPreemptionPlanner, "_build", planted)
    line = rehearse(capsys)
    assert line["correct"] is False
    bad = values(line)
    assert bad["unasked_deletes"] > 0 and "mismatched_binds" not in bad


# -- the reference on hand-made logs --------------------------------------------

def lows(n):
    return [("create", i, 0) for i in range(n)]


def test_three_victims_a_node_the_first_placed_reprieved():
    # twelve lows, four a node (pods i, i+3, i+6, i+9 on node i); a high
    # fits nowhere, and node 0 keeps the pod that was placed there first
    binds, evicted = REF.replay(CFG, [LOW, HIGH], lows(12) + [("create", 12, 1)])
    assert [binds[i] for i in range(12)] == [0, 1, 2] * 4
    assert binds[12] == 0 and evicted == [3, 6, 9]


def test_candidates_in_index_order():
    log = lows(12) + [("create", 12 + k, 1) for k in range(3)]
    binds, evicted = REF.replay(CFG, [LOW, HIGH], log)
    assert [binds[12 + k] for k in range(3)] == [0, 1, 2]
    assert sorted(evicted) == list(range(3, 12))
    # a fourth fits nowhere and no node has a lower-priority pod enough
    binds, _ = REF.replay(CFG, [LOW, HIGH], log + [("create", 15, 1)])
    assert binds[15] is None


def test_lowest_priority_victims_picked_first():
    # node 0's pods outrank node 1's: the preemptor goes to node 1
    mid = dict(LOW, priority=5)
    log = ([("create", i, 1) for i in range(0, 8, 2)]
           + [("create", i, 0) for i in range(1, 8, 2)])
    cfg = dict(CFG, nodes=dict(CFG["nodes"], count=2))
    binds, evicted = REF.replay(cfg, [LOW, mid, HIGH],
                                sorted(log, key=lambda e: e[1])
                                + [("create", 8, 2)])
    assert binds[8] == 1 and all(binds[i] == 1 for i in evicted)


def test_room_for_a_preemptor_class_after_an_event_is_refused():
    log = lows(12) + [("create", 12, 1)]
    # node 1 loses three of its pods: a pod of the class that preempted
    # would now fit there, so upstream's re-run could have gone there
    events = [("delete", i, 13, 0.0) for i in (4, 7, 10)]
    with pytest.raises(REF.reference.LogError) as e:
        REF.replay(CFG, [LOW, HIGH], log + events)
    assert e.value.binds[12] == 0 and e.value.evicted == [3, 6, 9]
    # one delete leaves no such room
    binds, _ = REF.replay(CFG, [LOW, HIGH], log + events[:1])
    assert binds[12] == 0


def test_the_victims_sum_counts_each_victim():
    """pickOneNodeForPreemption adds MaxInt32 + 1 to each victim's
    priority before summing: two victims of priority 1 beat three of
    priorities 1, 0, 0."""
    c = REF.PreemptionCluster.from_config(
        {"nodes": {"count": 2, "cpu": "4", "memory": "32Gi", "pods": 110,
                   "zones": 1}})
    one, zero = REF.PrioClass(dict(LOW, priority=1, cpu="1300m")), \
        REF.PrioClass(dict(LOW, cpu="600m"))
    for i, (pc, node) in enumerate([(one, 0), (zero, 0), (zero, 0),
                                    (one, 1), (one, 1)]):
        c.place(pc, node)
        c.bind(i, pc, node)
    high = REF.PrioClass(dict(HIGH, cpu="3900m"))
    node, victims = c.preempt(high)
    assert node == 1 and sorted(victims) == [3, 4]
    assert int(np.sum(c.n_pods)) == 3


NEW_READERS = ("preempt_plan_ms", "whatif_launch_ms", "whatif_context_ms",
               "victim_wait_p50_s", "device_plan_share")


def test_the_cell_is_listed_as_the_issue_gives_it():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"]
                if w["name"] == "preemption-5000n.bursts")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "preemption-5000n", "bursts", 1)
    p50 = next(m for m in bench["end_to_end"] if m["name"] == "bind_p50_s")
    assert cell["name"] in p50["workloads"]
    per = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert per[name]["workloads"] == [cell["name"]]
        mod = bench_run.load_module("metrics", name)
        assert {k: per[name][k] for k in mod.META} == mod.META
        assert mod.META["layer"] == "preemption"
        assert mod.META["moves"] == "bind_p50_s"
    config = bench_run.load_json("configs", "preemption-5000n")
    base = bench_run.load_json("configs", "default-5000n")
    assert config["nodes"] == base["nodes"]
    assert config["reduced"] == ["measure_pods"]
    assert config["init_pods"] == 20000 and config["init_template"] == "low"
    t = config["pod_templates"]
    assert (t["low"]["cpu"], t["low"]["priority"]) == ("900m", 0)
    assert (t["high"]["cpu"], t["high"]["priority"]) == ("3000m", 10)
    traffic = bench_run.load_json("traffic", "bursts")
    assert traffic["kind"] == "open-loop" and traffic["burst_pods"] == 250
    assert traffic["burst_template"] == "high"
    assert traffic["burst_every_s"] >= 4.0
    # every high pod takes one node: the window's pods leave room
    n_pods = (sum(w["pods"] for w in traffic["warm_batches"])
              + 250 * int(bench["run_seconds"] // traffic["burst_every_s"]))
    assert n_pods <= config["nodes"]["count"]
    assert 20000 + n_pods <= traffic["pod_ceiling"]


def test_a_preemptor_launched_after_its_victims_left_binds_where_nominated(
        capsys, monkeypatch):
    """The race of a loaded chip: a nominated preemptor is popped before its
    last victim's echo, misses its node, and is launched after the echo;
    its own hold on the node fails it there. It must bind where it was
    nominated, not be planned a second time."""
    from kubernetes_tpu.scheduler.scheduler import Scheduler

    real = Scheduler._place_nominated
    missed = set()

    def late(self, infos):
        first = [i for i in infos if i.pod.metadata.name not in missed]
        missed.update(i.pod.metadata.name for i in first)
        return real(self, [i for i in infos if i not in first])

    monkeypatch.setenv("KTPU_WHATIF", "1")
    monkeypatch.setattr(Scheduler, "_place_nominated", late)
    line = rehearse(capsys)
    assert missed
    assert line["correct"] is True and values(line) == {}
