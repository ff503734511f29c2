"""chip_smoke.py's contract on a host without a chip, and the rule it
enforces everywhere: a run that finished below the top rung is a failure,
however many pods it bound."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run_smoke(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run(
        [sys.executable, SMOKE, *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=timeout)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    proc = _run_smoke(timeout=120)
    assert proc.returncode not in (0, 2, 3), proc.stderr[-2000:]
    assert "no TPU" in proc.stderr
    assert "cpu" in proc.stderr  # names what it found instead
    assert "{" not in proc.stdout  # no result line of any kind


def test_alone_in_a_directory_it_fails(tmp_path):
    """The driver also runs the script without the program."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(SMOKE).read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, str(alone), "--cpu-dry-run"], cwd=tmp_path,
        env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


@pytest.mark.slow  # ~40 s of interpreted Pallas compiles; tier-1 is at its time limit
def test_cpu_dry_run_passes_its_own_checks():
    """Tiny size, Pallas interpreter, the same phases and checks."""
    proc = _run_smoke("--cpu-dry-run")
    assert proc.returncode == 0, proc.stderr[-4000:]
    detail, verdict = proc.stdout.strip().splitlines()[-2:]
    # the contract's last line: the verdict and the device, nothing else
    verdict = json.loads(verdict)
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    out = json.loads(detail)
    assert out["ok"] is True and out["dry_run"] is True
    assert out["device"] == verdict["device"]
    assert out["session_kind"] == "PallasSession"
    assert out["session_build_reasons"] == {"pallas/-": 1}
    assert out["pods_bound"] == out["pods"]
    assert set(out["executables"].values()) == {"aot"}
    assert out["compile_window"]["requests"] == 0
    assert out["after_window"]["parity_equal"] is True
    assert out["after_window"]["delta_applies"] >= 1


def test_programming_error_cannot_pass_as_a_device_fault(monkeypatch):
    """A NameError inside the session's schedule() is caught by the
    backend as a device fault, retried, and after three the ladder
    demotes to the host oracle — which then binds every pod. The harness
    result must say so, and the smoke's checks must fail on it."""
    import chip_smoke
    from kubernetes_tpu.ops.hoisted import HoistedSession
    from kubernetes_tpu.perf.harness import PodTemplate, Workload, run_workload

    def broken(self, pod_arrays_list):
        raise NameError("name 'ucnt' is not defined")

    monkeypatch.setattr(HoistedSession, "schedule", broken)
    monkeypatch.setenv("KTPU_RETRY_BASE", "0.001")
    monkeypatch.setenv("KTPU_RETRY_MAX", "0.002")
    w = Workload(
        "NameError-as-fault", num_nodes=16, num_pods=24,
        template=PodTemplate(spread_zone=True), max_batch=8, timeout=120.0)
    r = run_workload(w)
    # the old green: every pod bound, on the host
    assert r.num_bound == w.num_pods
    assert r.backend_mode == "oracle"
    # the row is not a measurement of the device path, and says why
    assert r.device_faults.get("raise", 0) >= 3
    assert r.ladder_demotions >= 1
    assert any("device faults" in f for f in r.failures)
    assert any("demoted" in f for f in r.failures)
    assert chip_smoke._checks(r, dry_run=True)
