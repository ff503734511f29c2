"""`correct` has to be able to come out false: the controls (the reference
with one stated guarantee broken) and the faults a cell can have, planted
under the timed path of a whole run on the CPU."""

import json
import os

import pytest

import run as bench_run
from benchlib import reference
from conftest import BENCH

CONFIG = {"nodes": {"count": 96, "cpu": "4", "memory": "32Gi", "pods": 110,
                    "zones": 3}}
SPREAD = {"cpu": "100m", "memory": "128Mi", "labels": {"app": "perf"},
          "spread_zone_soft": True}
ANTI = [{"cpu": "100m", "memory": "128Mi", "labels": {"app": f"svc-{g}"},
         "anti_affinity_hostname": True} for g in range(4)]


def replay(classes, seq, variant=""):
    """The node of each pod of a log of creates, in creation order."""
    binds, evicted = reference.replay(
        CONFIG, classes, [("create", i, c) for i, c in enumerate(seq)],
        variant=variant)
    assert evicted == []
    return [binds[i] for i in range(len(seq))]


@pytest.mark.parametrize("variant", ["sampled", "last-max"])
def test_control_differs_from_the_reference(variant):
    seq = [0] * 1500
    want = replay([SPREAD], seq)
    got = replay([SPREAD], seq, variant=variant)
    assert sum(a != b for a, b in zip(want, got)) > 0


@pytest.mark.parametrize("variant", ["sampled", "last-max"])
def test_control_differs_under_anti_affinity(variant):
    seq = [i % 4 for i in range(300)]
    want = replay(ANTI, seq)
    got = replay(ANTI, seq, variant=variant)
    assert sum(a != b for a, b in zip(want, got)) > 0


def test_anti_affinity_never_doubles_a_service_on_a_node():
    seq = [i % 4 for i in range(4 * 96 + 8)]
    got = replay(ANTI, seq)
    placed = [(c, n) for c, n in zip(seq, got) if n is not None]
    assert len(set(placed)) == len(placed) == 4 * 96
    assert got[-8:] == [None] * 8  # every node already holds one of each


def _run(capsys, *extra):
    rc = bench_run.main([
        "--workload", "rehearsal-96n.rehearsal-waves", "--seed", "11",
        "--seconds", "3", "--trace", "0", "--rehearse", *extra])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    line = _run(capsys)
    assert line["correct"] is True
    assert line["checks"]["mismatched_binds"]["value"] == 0


def test_control_put_in_the_programs_place_is_not_correct(capsys):
    line = _run(capsys, "--control", "sampled")
    assert line["correct"] is False
    assert line["checks"]["mismatched_binds"]["value"] > 0


def test_bind_altered_where_it_is_produced(capsys, monkeypatch):
    from kubernetes_tpu.apiserver.server import APIServer

    real = APIServer.bind_pods
    state = {"n": 0}

    def altered(self, bindings, **kw):
        out = []
        for ns, name, node in bindings:
            state["n"] += 1
            if state["n"] == 100:  # one pod, sent to its neighbour
                node = f"node-{(int(node[5:]) + 1) % 96:05d}"
            out.append((ns, name, node))
        return real(self, out, **kw)

    monkeypatch.setattr(APIServer, "bind_pods", altered)
    line = _run(capsys)
    assert line["correct"] is False
    assert line["checks"]["mismatched_binds"]["value"] >= 1


def test_step_that_returns_its_state_unchanged(capsys, monkeypatch):
    """The launch decides, but the carry it hands to the next launch is the
    one it was given: later launches decide against a stale cluster."""
    from kubernetes_tpu.ops.pallas_scan import PallasSession

    real = PallasSession.schedule

    def stale(self, arrays):
        carry = self._carry
        ys = real(self, arrays)
        self._carry = carry
        return ys

    monkeypatch.setattr(PallasSession, "schedule", stale)
    line = _run(capsys)
    assert line["correct"] is False
    assert line["checks"]["mismatched_binds"]["value"] > 0


def test_lost_bind_counts_as_unbound(capsys, monkeypatch):
    from kubernetes_tpu.apiserver.server import APIServer

    real = APIServer.bind_pods
    state = {"n": 0}

    def lossy(self, bindings, **kw):
        keep, dropped = [], []
        for b in bindings:
            state["n"] += 1
            (dropped if state["n"] == 100 else keep).append(b)
        got = iter(real(self, keep, **kw))
        return [None if b in dropped else next(got) for b in bindings]

    monkeypatch.setattr(APIServer, "bind_pods", lossy)
    monkeypatch.setattr(bench_run, "SETTLE_S", 3.0)
    line = _run(capsys)
    assert line["correct"] is False
    assert line["checks"]["unbound_pods"]["value"] == 1
    assert line["failed"] == 1
