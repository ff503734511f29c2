"""The deployment `churn-5000n` and its two cells, rehearsed on the CPU:
`traffic/rehearsal-node-rollover.json` (three nodes drained, removed and
joined a cycle, one of them a new name) on `configs/rehearsal-churn-96n.json`
is `correct` since node order is name order in the program (PR 34), its
controls are not, and the six readers the cells add each find a number.
"""

import json
import os

import pytest

import run as bench_run
from conftest import BENCH, REPO
from test_rehearsal import run_cell

ROLLOVER = "rehearsal-churn-96n.rehearsal-node-rollover"
NEW_READERS = ("churn_rebuilds_per_cycle", "delta_pods_per_cycle",
               "session_rebuild_ms", "delta_apply_ms", "event_ms_per_kevent",
               "barrier_ms")
CELLS = ("churn-5000n.node-rollover", "churn-5000n.scale-downs")


def rehearse(capsys, seed, *extra):
    rc = bench_run.main([
        "--workload", ROLLOVER, "--seed", str(seed), "--seconds", "3",
        "--trace", "0", "--rehearse", *extra])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def values(line):
    return {k: c["value"] for k, c in line["checks"].items() if c["value"]}


@pytest.mark.parametrize("seed", [11, 5, 2 ** 31 + 7])
def test_several_nodes_leaving_and_joining_a_cycle_is_correct(capsys, seed):
    """Seed 11 is the witness PERF.md section 7 kept until PR 34: pod 363,
    the first decided after node-00097 joined, went to node-00097 where the
    first of the maxima by index is node-00061."""
    line = rehearse(capsys, seed)
    assert line["correct"] is True and values(line) == {}
    assert line["failed"] == 0
    w = line["detail"]["window"]
    # two cycles (max_pods ends the loop): 3 nodes gone in each, 1 new name
    # in the first, 1 new name and 2 of the first cycle's back in the second
    assert len(line["detail"]["waves"]) == 2
    assert w["events"]["node_remove"] == 6 and w["events"]["node_add"] == 4
    assert w["events"]["delete"] == 16 + 2 * 56
    assert set(w["session_rebuilds"]) <= {"node-add", "node-remove"}


@pytest.mark.parametrize("variant", ["sampled", "last-max"])
def test_the_controls_are_not_correct(capsys, variant):
    line = rehearse(capsys, 11, "--control", variant)
    assert line["correct"] is False
    assert values(line)["mismatched_binds"] > 0
    assert values(line).keys() <= {"mismatched_binds", "unbound_pods"}


@pytest.fixture(scope="module")
def traced():
    """The rollover rehearsal, and the same with no node change (the shape
    of `scale-downs`: only there does a delete reach the live session as a
    carry delta, a node event tears the session down first)."""
    lines = []
    # warm_deletes at the cycle's count: the delta program is shaped by it
    for extra in ((), ("--set", "remove_nodes=0", "--set", "fresh_nodes=0",
                       "--set", "warm_deletes=48")):
        p = run_cell(ROLLOVER, "--rehearse", *extra, trace=1, seed=11)
        assert p.returncode == 0, p.stderr[-2000:]
        lines.append(json.loads(p.stdout.strip().splitlines()[-1]))
    return lines


def test_the_new_readers_each_find_a_number(traced):
    rollover, deletes_only = traced
    assert rollover["correct"] is True and deletes_only["correct"] is True
    got = {k: m["value"] for k, m in rollover["metrics"].items()}
    # a node event a cycle tears the session down; the deletes queued
    # before it die with it
    assert got["churn_rebuilds_per_cycle"] == 1.0
    assert got["delta_pods_per_cycle"] == 0.0
    assert "delta_apply_ms" not in got
    assert got["session_rebuild_ms"] > 0
    assert got["event_ms_per_kevent"] > 0 and got["barrier_ms"] > 0
    notes = rollover["detail"]["notes"]
    assert notes["session_builds"]["by_reason"] == {"node-add": 2}
    assert notes["churn_rebuilds"]["by_reason"] == {"node-add": 2}
    got = {k: m["value"] for k, m in deletes_only["metrics"].items()}
    assert got["churn_rebuilds_per_cycle"] == 0.0
    assert got["delta_pods_per_cycle"] == 48.0
    assert got["delta_apply_ms"] > 0
    assert "session_rebuild_ms" not in got
    assert got["event_ms_per_kevent"] > 0 and got["barrier_ms"] > 0
    applies = deletes_only["detail"]["notes"]["delta_applies"]
    assert applies["applies"] == 2 and applies["deltas"] == 96
    assert applies["buckets"] == [64] and applies["entries"] >= 96
    for line in traced:
        for m in line["metrics"].values():
            assert m["value"] is not None


def test_the_cells_are_listed_as_the_issue_gives_them():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cells = {w["name"]: w for w in bench["workloads"]}
    assert set(CELLS) <= set(cells)
    pps = next(m for m in bench["end_to_end"] if m["name"] == "pods_per_s")
    assert set(CELLS) <= set(pps["workloads"])
    per = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert set(per[name]["workloads"]) <= set(CELLS)
        assert per[name]["moves"] == "pods_per_s"
        mod = bench_run.load_module("metrics", name)
        assert {k: per[name][k] for k in mod.META} == mod.META
    config = bench_run.load_json("configs", "churn-5000n")
    base = bench_run.load_json("configs", "default-5000n")
    for key in ("nodes", "scheduler", "pod_templates", "init_pods",
                "init_template"):
        assert config[key] == base[key]
    assert config["reduced"] == [] and "reference" not in config
    assert set(base["assumed"]) < set(config["assumed"])
    common = {"kind": "churn-waves", "backlog_pods": 10000,
              "wave_pods": 2048, "max_pods": 140000, "template": "default",
              "warm_deletes": 1024, "delete_bound_pods": 1024,
              "delete_run_pods": 32, "delete_pending_pods": 64,
              "return_after_cycles": 1, "park_s": 0,
              "pod_ceiling": 6144 + 1024 + 140000,
              "trace_start_s": 2, "trace_seconds": 8}
    for cell, nodes in (("node-rollover", (50, 25)), ("scale-downs", (0, 0))):
        traffic = bench_run.load_json("traffic", cell)
        want = dict(common, remove_nodes=nodes[0], fresh_nodes=nodes[1])
        assert {k: traffic[k] for k in want} == want
        assert os.path.exists(os.path.join(
            BENCH, "kinds", traffic["kind"] + ".py"))
