"""The traffic kinds' own promises: the open loop never lets one client
stand in another's way, and the closed loop's rate keeps all of the time."""

import json
import time

import run as bench_run
from conftest import BENCH  # noqa: F401  (puts benchmarks/ on the path)

open_loop = bench_run.load_module("kinds", "open-loop")


class SlowCluster:
    """Stands in for the system: a create call that takes `create_s`."""

    def __init__(self, create_s):
        self.create_s = create_s
        self.n = 0

    def pod_class(self, template, group=None):
        return 0

    def prebuild(self, class_ids):
        first = self.n
        self.n += len(class_ids)
        return list(range(first, self.n))

    def create(self, i):
        time.sleep(self.create_s)


def test_singles_do_not_queue_behind_a_scale_up():
    traffic = {"rate_pods_per_s": 50, "single_template": "t",
               "burst_every_s": 1, "burst_pods": 60, "burst_template": "t"}
    cluster = SlowCluster(0.005)  # a scale-up's create loop: 0.3 s and more
    plan = open_loop.prepare(cluster, traffic, seed=3, seconds=2.0)
    singles, bursts = set(plan["singles"][1]), plan["bursts"][1]
    assert len(singles) > 60 and len(bursts) == 120
    rec = bench_run.Record()
    t_open = time.perf_counter()
    open_loop.drive(cluster, plan, rec, t_open, t_open + 2.0)
    assert sorted(rec.created) == list(range(cluster.n))
    late = {i: rec.issued[i] - rec.ready[i] for i in rec.created}
    # a single waits for the call in progress at the most, never for the
    # rest of the scale-up (which would be up to 0.3 s)
    assert max(late[i] for i in singles) < 0.05
    # a scale-up's pods all fall due at once, and the controller makes one
    # call after another: ready follows the return of its previous call,
    # so its own loop is not counted as the generator's lateness
    assert max(rec.issued[i] - rec.due[i] for i in bursts) > 0.25
    assert max(late[i] for i in bursts) < 0.05
    for a, b in zip(bursts, bursts[1:]):
        if rec.due[a] == rec.due[b]:
            assert rec.ready[b] >= rec.create_done[a]


def test_every_seed_gets_the_same_work():
    traffic = {"rate_pods_per_s": 40, "burst_every_s": 2,
               "burst_pods": [30, 50]}
    shapes = set()
    for seed in (1, 2 ** 31 + 5):
        singles, bursts = open_loop.schedule(traffic, seed, 10.0)
        shapes.add((len(singles), tuple(sorted(n for _, _, n in bursts))))
    assert len(shapes) == 1


def _waves(capsys, *extra):
    rc = bench_run.main([
        "--workload", "rehearsal-96n.rehearsal-waves", "--seed", "12",
        "--seconds", "2", "--trace", "0", "--rehearse",
        # half a second a cycle, so that the 900 pods built last the window
        "--set", "park_s=0.5", *extra])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _whole_count_over_whole_time(line, backlog):
    """pods_per_s x the measured time is a whole number of binds: every pod
    of the window but the standing backlog (the scheduler may be a batch
    into that when the last cycle ends)."""
    binds = line["metrics"]["pods_per_s"]["value"] * line["detail"][
        "measured_s"]
    assert abs(binds - round(binds)) < 1e-6 * binds
    assert line["attempted"] - backlog <= round(binds) <= line["attempted"]


def test_rate_is_all_binds_over_whole_cycles(capsys):
    line = _waves(capsys)
    d = line["detail"]
    backlog = 150  # traffic/rehearsal-waves.json: still pending at the end
    assert line["correct"] is True
    assert d["measured_s"] >= 2.0
    assert d["waves"][-1][3] == round(d["measured_s"], 4)  # last cycle's end
    assert len(d["waves"]) >= 2
    assert all(w[3] < 2.0 for w in d["waves"][:-1])
    _whole_count_over_whole_time(line, backlog)


def test_stall_at_the_tail_lengthens_the_time(capsys, monkeypatch):
    """A scheduler that stops binding just before the close: the cycle in
    progress ends late, and the rate is divided by all of that time."""
    from kubernetes_tpu.apiserver.server import APIServer

    real = APIServer.bind_pods
    state = {"t_open": None, "stalled": False}

    def stalling(self, bindings, **kw):
        # nothing binds from 1.2 s to 3.2 s into the window (set-up binds
        # before it opens, so the stall is planted on the window's clock)
        t, t_open = time.perf_counter(), state["t_open"]
        if t_open is not None and t_open + 1.2 < t < t_open + 3.2:
            state["stalled"] = True
            time.sleep(t_open + 3.2 - t)
        return real(self, bindings, **kw)

    waves = bench_run.load_module("kinds", "waves")
    real_drive = waves.drive

    def drive(cluster, plan, rec, t_open, t_close):
        state["t_open"] = t_open
        return real_drive(cluster, plan, rec, t_open, t_close)

    real_load = bench_run.load_module

    def load(directory, name):
        mod = real_load(directory, name)
        if (directory, name) == ("kinds", "waves"):
            mod.drive = drive
        return mod

    monkeypatch.setattr(APIServer, "bind_pods", stalling)
    monkeypatch.setattr(bench_run, "load_module", load)
    line = _waves(capsys)
    d = line["detail"]
    assert state["stalled"]
    assert line["correct"] is True  # late is late, not wrong
    assert d["measured_s"] > 3.1
    _whole_count_over_whole_time(line, 150)
