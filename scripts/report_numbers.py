"""Print the canonical numbers FROM the committed artifacts.

Every figure quoted in README.md / PERF_NOTES.md must be reproducible by
running this script — prose that contradicts it is a bug (VERDICT r4
weak #3: claims diverging from artifacts). Reads BENCH_CONFIGS.json,
BENCH_WIRE_CONFIGS.json and BENCH_SHARDED.json, whichever exist. Rows
written before PR 21 name no device: they are CPU runs of the build box
(their session_build_reasons say "platform is not tpu", or they are the
8-virtual-device mesh rows). Chip measurements are the driver's, in
PERF_LEDGER.jsonl.
"""

from __future__ import annotations

import json
import os

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def _rows(path):
    try:
        with open(os.path.join(ROOT, path)) as f:
            return [json.loads(ln) for ln in f if ln.strip()]
    except OSError:
        return []


def _newest_round(rows):
    """{name: that config's newest-round row} (later rows win ties).
    PER CONFIG, not globally newest: a partial-matrix rerun (e.g. the
    round-6 Gang-* staging) must not hide every config it didn't
    re-measure, nor empty the wire-tax intersection below."""
    newest = max((r.get("round", 0) for r in rows), default=0)
    out = {}
    for r in rows:
        prev = out.get(r["name"])
        if prev is None or r.get("round", 0) >= prev.get("round", 0):
            out[r["name"]] = r
    return newest, out


def main() -> None:
    for path, label in (("BENCH_CONFIGS.json", "in-proc"),
                        ("BENCH_WIRE_CONFIGS.json", "wire")):
        rows = _rows(path)
        if not rows:
            continue
        rnd, by_name = _newest_round(rows)
        print(f"\n-- {label} full-loop matrix ({path}, round {rnd}, "
              f"{len(by_name)} configs) --")
        for name in sorted(by_name):
            r = by_name[name]
            key = "attempts_per_sec" if r.get("headline_metric") == \
                "attempts_per_sec" or r.get("saturating") else "throughput_avg"
            print(f"  {name} [{r.get('platform') or 'cpu, unrecorded'}]: "
                  f"{r['throughput_avg']} pods/s avg "
                  f"(p50 {r['throughput_p50']}, attempts/s "
                  f"{r.get('attempts_per_sec')}, attempt_p50 "
                  f"{r.get('attempt_p50')}, reps {r.get('reps')}, "
                  f"runs {r.get('throughput_avg_runs')})")
            if r.get("gang_admitted"):
                print(f"    gangs: admitted {r.get('gang_admitted_runs')}, "
                      f"rollbacks {r.get('gang_rollbacks_runs')}, "
                      f"admission p50 {r.get('gang_admission_p50')}s / "
                      f"p99 {r.get('gang_admission_p99')}s "
                      f"(p99 runs {r.get('gang_admission_p99_runs')})")
    rows = _rows("BENCH_SHARDED.json")
    if rows:
        print("\n-- sharded session (BENCH_SHARDED.json) --")
        for r in rows:
            print(f"  [{r['platform']}] {r['session']} @{r['nodes']}n: "
                  f"{r['pods_per_sec_median']} pods/s median "
                  f"(runs {r['pods_per_sec_runs']})")
    # wire tax from matching configs
    inp = _newest_round(_rows("BENCH_CONFIGS.json"))[1]
    wire = _newest_round(_rows("BENCH_WIRE_CONFIGS.json"))[1]
    common = sorted(set(inp) & set(wire))
    if common:
        print("\n-- wire tax (same config, in-proc vs wire) --")
        for name in common:
            a, b = inp[name]["throughput_avg"], wire[name]["throughput_avg"]
            if a:
                print(f"  {name}: {a} -> {b} pods/s "
                      f"({100 * (a - b) / a:.1f}% tax)")


if __name__ == "__main__":
    main()
