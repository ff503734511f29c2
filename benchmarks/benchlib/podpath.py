"""One pod's path from `pods.create` to its bind, and where each of the
program's threads spent the window: the join of the program's spans
(`run.spans`) with the benchmark's own records (`run.issued`,
`run.bound_t`), computed once per run.

The program keys the control-plane half of the path by `key`
(namespace/name: `apiserver` and `informer` spans) and the pipeline half
by `batch` (`pop` ... `bind` spans); one `pod-path` event per bound batch
lists the keys of the batch's pods. With them a pod's wait from due to
seen tiles exactly into

  generator   due -> create issued                    (the benchmark's)
  admit_lag   issued -> end of its `informer ADDED pods` span: the pod
              is in the scheduling queue
  queue_wait  -> start of its batch's `pop` span
  decide      -> end of its batch's `harvest` span (pop, prep, encode,
              dispatch, the FIFO, wait, harvest)
  commit      -> the benchmark's watch sees the bind (assume,
              reserve-permit, the binder's queue, bind, store, fan-out)

The first reader to ask writes two tables into `run.notes`, printed as
`detail.notes`: `pod_path` (every segment at p50, at p95 and as the mean
over the pods at or above the 95th percentile of the whole wait) and
`threads` (per thread and stage inside the window: own wall seconds,
nested spans taken out of their parent, and the share of the stage's
wall that was CPU time, from the one span in sixteen that reads the
thread's CPU clock; the share of the window no span covers; the steps of
the `apiserver`, `informer` and `bind` spans summed). A program without
these spans (the parent of the PR that brought them) gives None
everywhere.

As a command it splits the device's idle time that `breakdown.idle_gaps`
calls `uncovered` by the named waits, from a `--dump-trace` file:

    python3 benchmarks/benchlib/podpath.py DUMP.json
"""

from __future__ import annotations

import json
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

SEGMENTS = ("generator", "admit_lag", "queue_wait", "decide", "commit")
TILE_TOLERANCE_S = 1e-6
# the waits that `uncovered` is split by, innermost first (a later one is
# painted over by an earlier one)
WAIT_ORDER = ("backpressure", "prep", "paused", "queue-empty",
              "worker-idle", "binder-queue", "cycle", "complete",
              "informer", "apiserver")


def _percentile(samples: Sequence[float], p: float) -> float:
    from benchlib.stats import percentile

    return percentile(samples, p)


def pod_index(key: str) -> Optional[int]:
    """`default/p-0000042` -> 42 (benchlib/cluster.py names pods so)."""
    name = key.rpartition("/")[2]
    if name.startswith("p-") and name[2:].isdigit():
        return int(name[2:])
    return None


def of(run) -> Optional[Dict]:
    """The join, made once and kept on `run`; None without the spans."""
    if "_podpath" not in run.__dict__:
        run._podpath = _join(run)
        if run._podpath is not None:
            run.notes["pod_path"] = _pod_path_table(run._podpath)
            run.notes["threads"] = threads_table(
                run.spans, run.t_open, run.t_end)
    return run._podpath


def _join(run) -> Optional[Dict]:
    admitted: Dict[int, float] = {}
    batch_of: Dict[int, int] = {}
    pop_t0: Dict[int, float] = {}
    harvest_end: Dict[int, float] = {}
    bind_end: Dict[int, float] = {}
    for name, stage, t0, dur, attrs in run.spans or []:
        if not attrs:
            continue
        if stage == "informer":
            if name == "ADDED pods":
                i = pod_index(attrs.get("key", ""))
                if i is not None:
                    admitted.setdefault(i, t0 + dur)
        elif stage == "path":
            for key in attrs.get("keys") or ():
                i = pod_index(key)
                if i is not None:
                    batch_of[i] = attrs.get("batch")
        elif stage == "pop":
            pop_t0[attrs.get("batch")] = t0
        elif stage == "harvest":
            harvest_end[attrs.get("batch")] = t0 + dur
        elif stage == "bind":
            bind_end[attrs.get("batch")] = t0 + dur
    if not admitted and not batch_of:
        return None
    pods = [i for i in run.created
            if run.bound_node[i] is not None and i in run.issued]
    rows: List[Tuple[float, ...]] = []
    worst = 0.0
    for i in pods:
        b = batch_of.get(i)
        if i not in admitted or b not in pop_t0 or b not in harvest_end:
            continue
        cuts = (run.due[i], run.issued[i], admitted[i], pop_t0[b],
                harvest_end[b], run.bound_t[i])
        seg = tuple(cuts[k + 1] - cuts[k] for k in range(5))
        total = run.bound_t[i] - run.due[i]
        residual = abs(sum(seg) - total)
        worst = max(worst, residual)
        if residual <= TILE_TOLERANCE_S:
            rows.append(seg + (total,))
    turnaround = [bind_end[b] - t0 for b, t0 in pop_t0.items()
                  if b in bind_end and run.t_open <= t0 < run.t_end]
    return {
        "pods": len(pods), "tiled": len(rows), "worst_residual_s": worst,
        "segments": {s: [r[k] for r in rows]
                     for k, s in enumerate(SEGMENTS)},
        "totals": [r[5] for r in rows],
        "batch_turnaround_s": turnaround,
    }


def segment_p50(run, segment: str) -> Optional[float]:
    pp = of(run)
    if pp is None or not pp["segments"][segment]:
        return None
    return _percentile(pp["segments"][segment], 50)


def batch_turnaround_p50(run) -> Optional[float]:
    pp = of(run)
    if pp is None or not pp["batch_turnaround_s"]:
        return None
    return _percentile(pp["batch_turnaround_s"], 50)


def named_spans(run, stage: str, name: str) -> List[Tuple[float, Dict]]:
    """[(dur, attrs)] of the window's spans of one stage and name; the
    tables are written as a side effect, whichever reader comes first."""
    of(run)
    return [(d, a or {}) for n, _, d, a in run.window_spans(stage)
            if n == name]


def _pod_path_table(pp: Dict) -> Dict:
    out: Dict = {
        "pods": pp["pods"], "tiled": pp["tiled"],
        "tiled_share": pp["tiled"] / pp["pods"] if pp["pods"] else None,
        "worst_residual_s": pp["worst_residual_s"],
    }
    totals = pp["totals"]
    if not totals:
        return out
    cut = _percentile(totals, 95)
    tail = [k for k, t in enumerate(totals) if t >= cut]
    cols = dict(pp["segments"], total=totals)
    for seg, vals in cols.items():
        out[seg] = {
            "p50_s": round(_percentile(vals, 50), 6),
            "p95_s": round(_percentile(vals, 95), 6),
            "tail_mean_s": round(sum(vals[k] for k in tail) / len(tail), 6),
        }
    return out


# -- threads ------------------------------------------------------------------


def _role(thread: str) -> str:
    """binder_0 .. binder_7 are one row, `binder`."""
    return re.sub(r"[_-]\d+$", "", thread)


def _own_wall(spans: List[Tuple]) -> Tuple[Dict[str, float], float]:
    """Spans of ONE thread [(a, b, stage)], nested or apart -> ({stage:
    own wall seconds}, seconds covered). A span's own time is its extent
    less the spans inside it."""
    own: Dict[str, float] = {}
    covered = 0.0
    stack: List[List] = []  # [a, b, stage, inner wall]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            a, b, stage, inner = stack.pop()
            own[stage] = own.get(stage, 0.0) + max(0.0, (b - a) - inner)

    for a, b, stage in sorted(spans, key=lambda s: (s[0], -s[1])):
        close(a)
        if stack:
            b = min(b, stack[-1][1])
            stack[-1][3] += b - a
        else:
            covered += b - a
        stack.append([a, b, stage, 0.0])
    close(float("inf"))
    return own, covered


def threads_table(spans: Sequence, t0: float, t1: float) -> Dict:
    """Per thread (binder_0..7 as one row `binder`) and stage, inside
    [t0, t1]: the spans, their own wall seconds (nested spans taken out
    of their parent) and `cpu_share`: of the stage's spans that read the
    thread's CPU clock (the program reads it on one span in sixteen),
    sum(cpu_s) / sum(wall), nested spans included; None where none did.
    Beside them the share of the window no span of the thread covers,
    and the summed steps of the spans that have steps."""
    window = t1 - t0
    per_thread: Dict[str, List[Tuple]] = {}
    rows: Dict[Tuple[str, str], List[float]] = {}  # n, cpu, sampled wall
    steps: Dict[str, Dict[str, float]] = {}
    for name, stage, s0, dur, attrs in spans or []:
        thread = (attrs or {}).get("thread")
        if thread is None or dur <= 0:
            continue
        a, b = max(s0, t0), min(s0 + dur, t1)
        if b <= a:
            continue
        per_thread.setdefault(thread, []).append((a, b, stage))
        row = rows.setdefault((_role(thread), stage), [0, 0.0, 0.0])
        row[0] += 1
        if "cpu_s" in attrs:
            row[1] += attrs["cpu_s"]
            row[2] += dur
        parts = {k: v for k, v in attrs.items()
                 if k.endswith("_s") and k != "cpu_s"}
        if parts:
            srow = steps.setdefault(f"{stage} {name}",
                                    {"n": 0, "wall_s": 0.0})
            srow["n"] += 1
            srow["wall_s"] += dur
            for k, v in parts.items():
                srow[k] = srow.get(k, 0.0) + v
    roles: Dict[str, Dict] = {}
    for thread, sp in per_thread.items():
        own, covered = _own_wall(sp)
        role = roles.setdefault(_role(thread), {
            "threads": 0, "covered_s": 0.0, "own": {}})
        role["threads"] += 1
        role["covered_s"] += covered
        for stage, wall in own.items():
            role["own"][stage] = role["own"].get(stage, 0.0) + wall
    out: Dict = {"window_s": round(window, 6), "by_thread": {}, "steps": {
        k: {kk: round(v, 6) for kk, v in row.items()}
        for k, row in sorted(steps.items())}}
    for name, role in sorted(roles.items()):
        stages = {}
        for stage, wall in sorted(role["own"].items(), key=lambda kv: -kv[1]):
            n, cpu, sampled = rows[(name, stage)]
            stages[stage] = {
                "n": n, "own_wall_s": round(wall, 6),
                "cpu_share": round(cpu / sampled, 4) if sampled else None}
        out["by_thread"][name] = {
            "threads": role["threads"],
            "uncovered_share": round(
                1.0 - role["covered_s"] / (role["threads"] * window), 6),
            "stages": stages,
        }
    return out


# -- the `uncovered` of breakdown.idle_gaps, split by the named waits ---------


def split_uncovered(dump: Dict) -> Dict[str, float]:
    """`dump` is run.py's --dump-trace file. Device 0's idle seconds
    that none of profile.SPAN_ORDER's spans covers, by the named wait
    that does (WAIT_ORDER, innermost first), else `nothing`."""
    import numpy as np

    from benchlib import profile

    raw, t_start, t_stop = dump["raw"], dump["t_start"], dump["t_stop"]
    offset = raw["anchors"][profile.ANCHOR] - dump["anchor"]
    cell = profile.CELL_S
    n = int((t_stop - t_start) / cell) + 1
    busy = np.zeros(n + 1)
    devices = sorted({op[3] for op in raw["ops"]})
    for _, start, dur, dev in raw["ops"]:
        if dev != devices[0]:
            continue
        lo = (start - offset - t_start) / cell
        hi = (start + dur - offset - t_start) / cell
        i, j = int(max(0, min(n, lo))), int(max(0, min(n, hi)))
        if i == j:
            busy[i] += max(0.0, hi - lo)
        else:
            busy[i] += (i + 1) - lo
            busy[i + 1:j] += 1.0
            busy[j] += hi - j
    idle = np.clip(1.0 - busy[:n], 0.0, 1.0) * cell

    def paint(order: Sequence[str]) -> "np.ndarray":
        label = np.full(n, len(order), np.int64)
        for rank in range(len(order) - 1, -1, -1):
            for st, t0, dur in dump["spans"]:
                if st != order[rank]:
                    continue
                i = int(max(0, (t0 - t_start) / cell))
                j = int(min(n, (t0 + dur - t_start) / cell + 1))
                if j > i:
                    label[i:j] = rank
        return label

    uncovered = paint(profile.SPAN_ORDER) == len(profile.SPAN_ORDER)
    label = paint(WAIT_ORDER)
    names = list(WAIT_ORDER) + ["nothing"]
    sums = np.bincount(label[uncovered], weights=idle[uncovered],
                       minlength=len(names))
    out = {"uncovered_s": float(idle[uncovered].sum())}
    out.update({names[k]: float(sums[k]) for k in np.argsort(-sums)
                if sums[k] > 0})
    return out


if __name__ == "__main__":
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(sys.argv[1]) as f:
        print(json.dumps(split_uncovered(json.load(f))))
