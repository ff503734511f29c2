"""CPU rehearsal of the harness: one cell end to end, as the driver runs
it, at a size the interpreter can hold."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, REPO

REQUIRED = ["correct", "attempted", "failed", "metrics", "device"]
DEVICE_TRACE_METRICS = {"launch_ms", "kernel_us_per_pod",
                        "scan_kernel_roofline"}


def run_cell(workload, *extra, seconds=3, trace=0, seed=2 ** 31 + 7):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   REPO, ".xla_cache", "rehearsal"))
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace), *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)


@pytest.fixture(scope="module")
def traced():
    p = run_cell("rehearsal-96n.rehearsal-arrivals", "--rehearse", trace=1)
    assert p.returncode == 0, p.stderr[-2000:]
    return p, json.loads(p.stdout.strip().splitlines()[-1])


def test_last_line_has_the_contracts_keys(traced):
    p, line = traced
    keys = list(line)
    assert keys[:5] == REQUIRED
    assert keys[-1] == "checks"  # each number compared, beside its limit
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    for name, c in line["checks"].items():
        assert f"check {name}: {c['value']} (limit {c['limit']})" in p.stderr


def test_no_device_metric_is_reported_from_a_cpu(traced):
    _, line = traced
    assert line["device"]["platform"] == "cpu"
    assert not DEVICE_TRACE_METRICS & set(line["metrics"])
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert {"pods_per_launch", "gen_late_p95_s",
            "create_us_per_pod"} <= set(line["metrics"])


def test_without_a_tpu_nothing_is_printed():
    p = run_cell("rehearsal-96n.rehearsal-waves")  # no --rehearse
    assert p.returncode == 4
    assert p.stdout == ""


def test_a_cell_is_added_by_adding_files():
    """A configuration, a traffic mix and a per-layer metric that nothing
    lists: three new files and no edit."""
    added = {
        "configs/zz-added.json": json.load(
            open(os.path.join(BENCH, "configs", "rehearsal-ipa-96n.json"))),
        "traffic/zz-added.json": {
            "kind": "open-loop", "burst_every_s": 1, "burst_pods": 12,
            "burst_template": "rollout", "groups": 4, "pod_ceiling": 512},
    }
    reader = ('"""Pods created in the window."""\n\n'
              "META = {'name': 'zz_created', 'unit': 'pods', 'better': "
              "'higher', 'source': 'host_clock', 'layer': 'generator', "
              "'moves': 'bind_p50_s'}\nKIND = 'per_layer'\n\n\n"
              "def read(run):\n    return float(len(run.created))\n")
    paths = [os.path.join(BENCH, rel) for rel in added]
    paths.append(os.path.join(BENCH, "metrics", "zz_created.py"))
    try:
        for rel, body in added.items():
            with open(os.path.join(BENCH, rel), "w") as f:
                json.dump(body, f)
        with open(paths[-1], "w") as f:
            f.write(reader)
        p = run_cell("zz-added.zz-added", "--rehearse", trace=1)
        assert p.returncode == 0, p.stderr[-2000:]
        line = json.loads(p.stdout.strip().splitlines()[-1])
        assert line["correct"] is True
        assert line["metrics"]["zz_created"]["value"] == line["attempted"]
    finally:
        for path in paths:
            if os.path.exists(path):
                os.remove(path)
