"""From a jax.profiler trace to the numbers the benchmark reports.

Two steps, kept apart so that the second can be checked on a small
recorded trace (benchmarks/testdata/) without a chip:

  extract(xplane.pb)  ->  {"ops": [[name, start_s, dur_s, device], ...],
                           "anchors": {name: start_s}}
  reduce(trace, host spans on the same clock)  ->  busy/idle, kernel time,
                           top device operations, idle seconds by host span

Clocks: the program's spans and the benchmark's own are perf_counter
times. The benchmark drops a `bench_anchor` TraceAnnotation into the
profile at a perf_counter instant it notes; the difference puts the
device events on the perf_counter clock.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

ANCHOR = "bench_anchor"
# lines of a TPU device plane that hold single operations (the others
# repeat them as modules, steps or framework scopes)
OP_LINES = ("XLA Ops",)
SKIP_LINES = ("XLA Modules", "Steps", "XLA TraceMe", "Framework Ops",
              "Framework Name Scope", "Source code")
KERNEL_MARKS = ("custom-call", "custom_call", "pallas", "mosaic")
# innermost first: a span later in this list is painted over by an earlier
SPAN_ORDER = ("stage", "mutate", "barrier", "bind", "assume", "harvest", "wait", "dispatch",
              "encode", "pop")
CELL_S = 50e-6


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def extract(path: str) -> Dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops: List[List] = []
    anchors: Dict[str, float] = {}
    layout: Dict[str, Dict[str, int]] = {}
    for plane in data.planes:
        lines = list(plane.lines)
        layout[plane.name] = {}
        is_device = plane.name.startswith("/device:TPU")
        names = [ln.name for ln in lines]
        for ln in lines:
            events = list(ln.events)
            layout[plane.name][ln.name] = len(events)
            if is_device:
                if any(n in names for n in OP_LINES):
                    if ln.name not in OP_LINES:
                        continue
                elif ln.name in SKIP_LINES:
                    continue
                dev = plane.name.rsplit(":", 1)[-1]
                for ev in events:
                    ops.append([ev.name, ev.start_ns / 1e9,
                                ev.duration_ns / 1e9, dev])
            else:
                for ev in events:
                    if ev.name.startswith(ANCHOR) and ev.name not in anchors:
                        anchors[ev.name] = ev.start_ns / 1e9
    return {"ops": ops, "anchors": anchors, "layout": layout}


def short_name(name: str) -> str:
    """A TPU op event is named by its whole HLO instruction; keep the
    result's name and the opcode: `_dispatch.1 custom-call`."""
    lhs, eq, rhs = name.partition(" = ")
    if not eq:
        return name[:120]
    op = ""
    for tok in rhs.split():
        head = tok.split("(", 1)[0]
        if "(" in tok and head and head[0].isalpha() and "[" not in head:
            op = head
            break
    return f"{lhs.lstrip('%')} {op}".strip()[:120]


def is_kernel(name: str) -> bool:
    low = name.lower()
    return any(m in low for m in KERNEL_MARKS)


def _union_seconds(iv: Sequence[Tuple[float, float]]) -> float:
    total, end = 0.0, -float("inf")
    for a, b in sorted(iv):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def reduce(trace: Dict, t_start: float, t_stop: float,
           anchor_perf: Optional[float],
           spans: Sequence[Tuple[str, float, float]] = ()) -> Dict:
    """`t_start`/`t_stop`/`anchor_perf` and `spans` [(stage, t0, dur)] are
    perf_counter times; returns busy_s (mean over devices), window_s,
    kernel_s, kernel_events, device_ops and idle_gaps."""
    offset = None
    if anchor_perf is not None and ANCHOR in trace["anchors"]:
        offset = trace["anchors"][ANCHOR] - anchor_perf
    window = t_stop - t_start
    by_dev: Dict[str, List[Tuple[float, float]]] = {}
    by_name: Dict[str, float] = {}
    kernel_s, kernel_n = 0.0, 0
    for name, start, dur, dev in trace["ops"]:
        by_dev.setdefault(dev, []).append((start, start + dur))
        short = short_name(name)
        by_name[short] = by_name.get(short, 0.0) + dur
        if is_kernel(name):
            kernel_s += dur
            kernel_n += 1
    out = {
        "window_s": window,
        "busy_s": (sum(_union_seconds(iv) for iv in by_dev.values())
                   / len(by_dev)) if by_dev else 0.0,
        "devices": len(by_dev),
        "kernel_s": kernel_s,
        "kernel_events": kernel_n,
        "device_ops": [[n, s] for n, s in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [],
        "aligned": offset is not None,
    }
    if offset is None or window <= 0 or not by_dev:
        return out
    # idle seconds of device 0 by the host span that covers them
    n = int(window / CELL_S) + 1
    busy = np.zeros(n + 1)
    first = sorted(by_dev)[0]
    for a, b in by_dev[first]:
        lo = (a - offset - t_start) / CELL_S
        hi = (b - offset - t_start) / CELL_S
        i, j = int(max(0, min(n, lo))), int(max(0, min(n, hi)))
        if i == j:
            busy[i] += max(0.0, hi - lo)
        else:
            busy[i] += (i + 1) - lo
            busy[i + 1:j] += 1.0
            busy[j] += hi - j
    idle = np.clip(1.0 - busy[:n], 0.0, 1.0) * CELL_S
    label = np.full(n, len(SPAN_ORDER), np.int64)
    for rank in range(len(SPAN_ORDER) - 1, -1, -1):
        stage = SPAN_ORDER[rank]
        for st, t0, dur in spans:
            if st != stage:
                continue
            i = int(max(0, (t0 - t_start) / CELL_S))
            j = int(min(n, (t0 + dur - t_start) / CELL_S + 1))
            if j > i:
                label[i:j] = rank
    names = list(SPAN_ORDER) + ["uncovered"]
    sums = np.bincount(label, weights=idle, minlength=len(names))
    out["idle_gaps"] = [[names[k], float(sums[k])] for k in
                        np.argsort(-sums)[:10] if sums[k] > 0]
    return out
