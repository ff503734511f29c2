"""Chip smoke: the product's main path, once, on one TPU chip.

One process drives `kubernetes_tpu.perf.harness.run_workload` on the
north-star workload — Default-5000n-10k: 5000 nodes, 6144 init pods,
10000 measured zone-spread pods, batch 2048 — with product defaults:
APIServer + informers + queue + cache + Scheduler(backend="tpu", pipeline
depth 2, speculation on, AOT on) + binder, riding the PallasSession that
Mosaic compiled. Then, on the same live cluster, it deletes one bound pod
(the session absorbs it as a carry delta) and schedules a further prefix
of pods whose placements must equal the first-max Go-semantics oracle's on
the same state — when every zone already holds thousands of matching pods,
which is where an inexact f32 count would first show.

It exits non-zero — and prints no result — unless JAX reports a TPU, every
pod bound, the session that served was the compiled AOT PallasSession
throughout (no hoisted build, no fault, retry, demotion, worker restart,
failed or retired executable), nothing compiled inside the measured window,
a delta was applied without a rebuild, and the parity prefix is equal. On a
pass stdout ends with two JSON lines: the run's detail (session kind, build
reasons, pods bound, smoke pods/s, compile seconds, cache directory, ...),
then, last, the verdict alone with the device as JAX reports it:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
The detail's pods/s is a smoke figure, not a benchmark.

    python chip_smoke.py                 # on the chip
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-dry-run
                                         # tiny size, Pallas interpreter:
                                         # checks this script, not the chip
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import faulthandler
import json
import os
import sys
import time

os.environ.setdefault("JAX_ENABLE_X64", "1")

PARITY_PODS = 64  # >= 32; one bucket-128 launch
WATCHDOG_S = 1150  # the contract allows 1200 s, compilation included
EXIT_FAILED, EXIT_NO_CHIP = 1, 4  # 2 and 3 are the chip tool's own


def _after_window(workload, n_parity: int):
    """The harness hook: one delta apply, then the oracle-parity prefix."""

    def hook(cs, sched, stage):
        from kubernetes_tpu.api import types as v1
        from kubernetes_tpu.perf.harness import bind_more
        from kubernetes_tpu.scheduler.metrics import session_delta_applies
        from kubernetes_tpu.testing.oracle import first_max_decisions

        tpu = sched.tpu
        session = tpu._session
        deltas0 = session_delta_applies.value(kind="pod-remove")
        pods, _ = cs.pods.list(namespace="default")
        victim = next(p for p in pods if p.spec.node_name
                      and p.metadata.name.startswith("measure-"))
        cs.pods.delete(victim.metadata.name, namespace="default")
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            with tpu._lock:
                if tpu._deltas or tpu._session is not session:
                    break
            time.sleep(0.02)

        pods, _ = cs.pods.list(namespace="default")
        nodes, _ = cs.nodes.list()
        bound = [p for p in pods if p.spec.node_name]
        zone_of = {n.metadata.name: n.metadata.labels[v1.LABEL_ZONE]
                   for n in nodes}
        zones = collections.Counter(zone_of[p.spec.node_name] for p in bound)
        t0 = time.perf_counter()
        want = first_max_decisions(
            nodes, bound,
            [workload.template.build(f"parity-{i}") for i in range(n_parity)])
        oracle_s = time.perf_counter() - t0

        got = bind_more(cs, sched, stage, workload.template, n_parity,
                        "parity", timeout=120.0)
        live = tpu._session
        return {
            "parity_pods": n_parity,
            "parity_bound": len(got),
            # the pods are identical, so which pod took which node is
            # queue order; the multiset of nodes is the decision sequence
            "parity_equal": (
                collections.Counter(got.values())
                == collections.Counter(want)),
            "parity_want_head": want[:4],
            "parity_got_head": sorted(
                got.values(), key=tpu.enc.node_index.get)[:4],
            "oracle_s": round(oracle_s, 2),
            "min_zone_count": min(zones.values()),
            "delta_applies": int(
                session_delta_applies.value(kind="pod-remove") - deltas0),
            "session_survived_delta": live is session,
            "session_kind": type(live).__name__ if live is not None else "",
            "interpret": bool(getattr(live, "interpret", False)),
        }

    return hook


def _checks(r, dry_run: bool) -> list:
    """Every reason this run is not a pass (empty = pass)."""
    bad = list(r.failures)
    aw = r.after_window or {}
    if r.num_bound != r.num_pods:
        bad.append(f"measured pods bound {r.num_bound} of {r.num_pods}")
    if r.session_kind != "PallasSession" \
            or aw.get("session_kind") != "PallasSession":
        bad.append(f"live session is {r.session_kind!r} / "
                   f"{aw.get('session_kind')!r}, not PallasSession")
    reasons = r.session_build_reasons or {}
    if set(reasons) != {"pallas/-"}:
        bad.append(f"session builds other than pallas/-: {reasons}")
    if r.backend_mode != "pallas":
        bad.append(f"backend mode {r.backend_mode!r}, not pallas")
    if aw.get("interpret") != dry_run:
        bad.append(f"kernel interpret={aw.get('interpret')} "
                   f"(expected {dry_run})")
    execs = r.executables or {}
    if not execs or set(execs.values()) != {"aot"}:
        bad.append(f"executables not all AOT: {execs}")
    if r.compile_window["requests"]:
        bad.append(f"{r.compile_window['requests']} compilations inside "
                   f"the measured window")
    if aw.get("delta_applies", 0) < 1 or not aw.get("session_survived_delta"):
        bad.append(f"no carry-delta apply on the live session: {aw}")
    if aw.get("parity_bound") != aw.get("parity_pods") \
            or not aw.get("parity_equal"):
        bad.append(f"oracle parity prefix differs: {aw}")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="tiny cluster through the Pallas interpreter on "
                         "a CPU asked for by name; checks this script")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)

    import jax

    jax.config.update("jax_enable_x64", True)

    from kubernetes_tpu.utils.device import NoAccelerator, require_device

    try:
        dev = require_device(allow_cpu=args.cpu_dry_run)
    except NoAccelerator as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    if args.cpu_dry_run and dev["platform"] == "tpu":
        print("chip_smoke: --cpu-dry-run on a TPU host; run without the "
              "flag", file=sys.stderr)
        return EXIT_FAILED
    print(f"chip_smoke: platform={dev['platform']} "
          f"device_kind={dev['kind']} count={dev['count']}", flush=True)

    from kubernetes_tpu.perf.harness import DEFAULT_5000N_10K, run_workload
    from kubernetes_tpu.utils.compilation_cache import (
        enable_persistent_cache,
    )

    cache_dir = enable_persistent_cache()
    w, backend = DEFAULT_5000N_10K, None
    if args.cpu_dry_run:
        from kubernetes_tpu.scheduler.tpu_backend import TPUBackend

        w = dataclasses.replace(
            w, name="Default-dry-run", num_nodes=96, num_init_pods=128,
            num_pods=256, max_batch=128, timeout=300.0)
        backend = TPUBackend(pallas_interpret=True)
    t0 = time.perf_counter()
    r = run_workload(w, after_window=_after_window(w, PARITY_PODS),
                     tpu_backend=backend)
    wall = time.perf_counter() - t0
    bad = _checks(r, args.cpu_dry_run)
    aw = r.after_window or {}
    verdict = {"ok": not bad, "device": dev}
    detail = {
        **verdict,
        "workload": w.name,
        "dry_run": args.cpu_dry_run,
        "session_kind": r.session_kind,
        "session_build_reasons": r.session_build_reasons,
        "backend_mode": r.backend_mode,
        "executables": r.executables,
        "nodes": w.num_nodes,
        "init_pods_bound": w.num_init_pods,
        "pods_bound": r.num_bound,
        "pods": r.num_pods,
        "smoke_pods_per_sec": r.throughput_avg,
        "smoke_window_s": r.duration_s,
        "compile_setup": r.compile_setup,
        "compile_window": r.compile_window,
        "cache_dir": cache_dir,
        "device_faults": r.device_faults,
        "dispatch_retries": r.dispatch_retries,
        "ladder_demotions": r.ladder_demotions,
        "worker_restarts": r.worker_restarts,
        "exec_errors": r.exec_errors,
        "after_window": aw,
        "wall_s": round(wall, 1),
        "failures": bad,
    }
    faulthandler.cancel_dump_traceback_later()
    if bad:
        for b in bad:
            print(f"chip_smoke: FAILED: {b}", file=sys.stderr)
        print(json.dumps(detail), file=sys.stderr)
        return EXIT_FAILED
    print(json.dumps(detail))
    # the last line is the verdict and nothing else: exactly these keys
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
