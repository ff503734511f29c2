"""A preemptor's path from due to its bind: the join of the program's
preemption spans with the benchmark's own records, computed once per run.

A pod that fits nowhere binds through a second pass: its launch fails,
the completion worker plans the failure wave (`preemption-wave`, keyed
by the failed launch's `batch`), the what-if launch picks its node and
victims (`whatif`, attr `pod`), a binder thread deletes the wave's
victims (`evict`, attrs `batch` and `keys`), the last delete echo on its
node sends it back to the queue (`preemption-wait`, attr `keys`), and it
binds on its nominated node. Tiled by those cuts, its wait from due to
seen is

  generator    due -> create issued                  (the benchmark's)
  admit_lag    -> end of its `informer ADDED pods` span
  queue_wait   -> start of the `pop` span of the batch whose launch failed,
                  or the pod's own admission where that pop was already
                  gathering the burst (its queue_wait is then 0)
  decide       -> end of that batch's `harvest` span
  plan_wait    -> start of its own `whatif` span: the wave's books and the
                  preemptors planned before it
  plan         -> end of its `whatif` span
  wave_hold    -> start of its wave's `evict` span: the preemptors planned
                  after it, the registration and the binder's queue
  evict        -> end of its node's `preemption-wait`: its victims' deletes
                  in turn and their echoes
  rebind       -> the benchmark's watch sees the bind: the requeue, the
                  second pop, `nominated-place`, the bind

A preemptor planned on the fast rung has no `whatif`: plan_wait, plan and
wave_hold are one segment there, `plan_hold`. A pod is kept only where
the cuts are in order and the segments sum to bind seen - due within
TILE_TOLERANCE_S. The program's overload monitor may switch tracing off
for stretches of a run (`trace_sheds`): a preemptor whose spans fell in
one is counted among `pods` (its eviction was recorded) or not at all,
but not among `joined` (every cut has its span), and `tiled_share` is
taken over `joined`.

The first reader to ask writes `run.notes["preemptor_path"]` (every
segment at p50, at p95 and as the mean over the pods at or above the 95th
percentile of the whole wait, and `tiled_share`) and
`run.notes["preemption_waves"]` (the summed steps of the window's
`preemption-wave`, `preemption-books`, `whatif` and `evict` spans). A
program without these spans gives None everywhere.

As a command it splits the device's idle time that `breakdown.idle_gaps`
calls `uncovered` by the preemption path's stages (innermost first, then
podpath.py's named waits), from a `--dump-trace` file:

    python3 benchmarks/benchlib/preemptpath.py DUMP.json
"""

from __future__ import annotations

import bisect
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchlib.podpath import TILE_TOLERANCE_S, WAIT_ORDER, pod_index  # noqa: E402

HEAD = ("generator", "admit_lag", "queue_wait", "decide")
DEVICE_RUNG = ("plan_wait", "plan", "wave_hold")
TAIL = ("evict", "rebind")
SEGMENTS = HEAD + DEVICE_RUNG + ("plan_hold",) + TAIL
# the stages whose summed steps `preemption_waves` keeps
STEPPED = ("preemption-wave", "preemption-books", "whatif", "evict")
# the preemption path's stages, innermost first: a whatif-context is
# built inside a whatif, which runs inside the planner span, inside the
# wave; evict runs on a binder thread, nominated-place on the scheduler
# thread or the completion worker
PREEMPT_ORDER = ("whatif-context", "preemption-books", "whatif", "planner",
                 "preemption-wave", "evict", "nominated-place")


def _percentile(samples: Sequence[float], p: float) -> float:
    from benchlib.stats import percentile

    return percentile(samples, p)


def of(run) -> Optional[Dict]:
    """The join, made once and kept on `run`; None without the spans."""
    if "_preemptpath" not in run.__dict__:
        run._preemptpath = join(run)
        if run._preemptpath is not None:
            run.notes["preemptor_path"] = _table(run._preemptpath)
            run.notes["preemption_waves"] = wave_steps(run)
    return run._preemptpath


def _pod_keys(attrs: Dict) -> List[int]:
    return [i for i in map(pod_index, attrs.get("keys") or ())
            if i is not None]


def join(run) -> Optional[Dict]:
    """{bound, pods, joined, tiled, out_of_order, worst_residual_s,
    fast_rung, rows, trace_sheds}: `bound` the window's bound pods, `pods`
    those whose victims' eviction was recorded, `joined` those with a span
    at every cut, `out_of_order` the joined pods left untiled by the first
    segment of theirs that runs backwards, one row per tiled preemptor,
    {segment: seconds, "total": seconds}."""
    admitted: Dict[int, float] = {}
    pop_t0: Dict[int, float] = {}
    harvest_end: Dict[int, float] = {}
    waves: Dict[int, Tuple[float, float]] = {}
    whatifs: Dict[int, List[Tuple[float, float]]] = {}
    evicts: Dict[int, List[Tuple[float, int]]] = {}
    waits: Dict[int, List[float]] = {}
    for name, stage, t0, dur, attrs in run.spans or []:
        if not attrs:
            continue
        if stage == "informer":
            if name == "ADDED pods":
                i = pod_index(attrs.get("key", ""))
                if i is not None:
                    admitted.setdefault(i, t0 + dur)
        elif stage == "pop":
            pop_t0[attrs.get("batch")] = t0
        elif stage == "harvest":
            harvest_end[attrs.get("batch")] = t0 + dur
        elif stage == "preemption-wave":
            waves[attrs.get("batch")] = (t0, t0 + dur)
        elif stage == "whatif":
            i = pod_index(attrs.get("pod", ""))
            if i is not None:
                whatifs.setdefault(i, []).append((t0, t0 + dur))
        elif stage == "evict":
            for i in _pod_keys(attrs):
                evicts.setdefault(i, []).append((t0, attrs.get("batch")))
        elif stage == "preemption-wait":
            for i in _pod_keys(attrs):
                waits.setdefault(i, []).append(t0 + dur)
    if not evicts:
        return None
    for ends in waits.values():
        ends.sort()
    seen = [i for i in run.created
            if run.bound_node[i] is not None and i in run.issued]
    pods = [i for i in seen if i in evicts]
    rows: List[Dict[str, float]] = []
    worst = 0.0
    fast = joined = 0
    out_of_order: Dict[str, int] = {}
    for i in pods:
        bound = run.bound_t[i]
        # the last eviction of the pod's victims before its bind
        done = [e for e in evicts[i] if e[0] < bound]
        if not done:
            continue
        t_evict, b = max(done)
        wave = waves.get(b)
        ends = waits.get(i, [])
        k = bisect.bisect_left(ends, t_evict)
        if (i not in admitted or b not in pop_t0 or b not in harvest_end
                or wave is None or k == len(ends)):
            continue
        joined += 1
        # a pod that the pop gathered while the informer was still
        # admitting the burst joins its batch when it is admitted
        cuts = [run.due[i], run.issued[i], admitted[i],
                max(admitted[i], pop_t0[b]), harvest_end[b]]
        names = list(HEAD)
        own = [w for w in whatifs.get(i, ())
               if wave[0] <= w[0] and w[1] <= wave[1]]
        if own:
            cuts += list(own[-1])
            names += DEVICE_RUNG
        else:
            names.append("plan_hold")
        cuts += [t_evict, ends[k], bound]
        names += TAIL
        seg = [cuts[n + 1] - cuts[n] for n in range(len(names))]
        total = bound - run.due[i]
        residual = abs(sum(seg) - total)
        worst = max(worst, residual)
        if residual <= TILE_TOLERANCE_S and min(seg) >= 0.0:
            row = dict(zip(names, seg))
            row["total"] = total
            rows.append(row)
            fast += not own
        else:
            # the first segment that runs backwards names the cut at fault
            first = next((n for n, v in zip(names, seg) if v < 0.0),
                         "residual")
            out_of_order[first] = out_of_order.get(first, 0) + 1
    return {"bound": len(seen), "pods": len(pods), "joined": joined,
            "tiled": len(rows), "out_of_order": out_of_order,
            "worst_residual_s": worst, "fast_rung": fast, "rows": rows,
            "trace_sheds": _trace_sheds(run)}


def _trace_sheds(run) -> int:
    """Times the program's overload monitor switched tracing off inside
    the window (scheduler_overload_sheds_total{what="trace"}): the spans
    of those stretches are missing."""
    name = "scheduler_overload_sheds_total"
    was, now = ((getattr(run, c, None) or {}).get("registry", {}).get(name, {})
                for c in ("counters0", "counters1"))
    return now.get("trace", 0) - was.get("trace", 0)


def segment_p50(run, segment: str) -> Optional[float]:
    pp = of(run)
    vals = [r[segment] for r in pp["rows"] if segment in r] if pp else []
    return _percentile(vals, 50) if vals else None


def _table(pp: Dict) -> Dict:
    out: Dict = {
        "bound": pp["bound"], "pods": pp["pods"], "joined": pp["joined"],
        "tiled": pp["tiled"],
        "tiled_share": pp["tiled"] / pp["joined"] if pp["joined"] else None,
        "out_of_order": pp["out_of_order"],
        "worst_residual_s": pp["worst_residual_s"],
        "fast_rung": pp["fast_rung"], "trace_sheds": pp["trace_sheds"],
    }
    rows = pp["rows"]
    if not rows:
        return out
    cut = _percentile([r["total"] for r in rows], 95)
    tail = [r for r in rows if r["total"] >= cut]
    for seg in SEGMENTS + ("total",):
        vals = [r[seg] for r in rows if seg in r]
        if not vals:
            continue
        in_tail = [r[seg] for r in tail if seg in r]
        out[seg] = {
            "p50_s": round(_percentile(vals, 50), 6),
            "p95_s": round(_percentile(vals, 95), 6),
            "tail_mean_s": round(sum(in_tail) / len(in_tail), 6)
            if in_tail else None,
        }
    return out


def wave_steps(run) -> Dict:
    """{stage: {n, wall_s, <step>_s summed}} over the window's spans of
    STEPPED, with `queued_s` of the evict spans summed alike."""
    out: Dict[str, Dict[str, float]] = {}
    for stage in STEPPED:
        for _, _, dur, attrs in run.window_spans(stage):
            row = out.setdefault(stage, {"n": 0, "wall_s": 0.0})
            row["n"] += 1
            row["wall_s"] += dur
            for k, v in (attrs or {}).items():
                if k.endswith("_s") and k != "cpu_s":
                    row[k] = row.get(k, 0.0) + v
    return {st: {k: round(v, 6) for k, v in row.items()}
            for st, row in out.items()}


def books_ms(run) -> Optional[float]:
    """Per wave of the window, its `snapshot_s` + `eligibility_s` and the
    `preemption-books` spans inside it, in ms: the mean."""
    of(run)
    waves = [(t0, t0 + d, a or {}) for _, t0, d, a
             in run.window_spans("preemption-wave")]
    if not waves:
        return None
    books = sorted((t0, t0 + d) for _, t0, d, _ in
                   run.window_spans("preemption-books"))
    total = 0.0
    for a, b, attrs in waves:
        total += attrs.get("snapshot_s", 0.0) + attrs.get("eligibility_s", 0.0)
        total += sum(e - s for s, e in books if a <= s and e <= b)
    return 1e3 * total / len(waves)


# -- the `uncovered` of breakdown.idle_gaps, split by the preemption path -----


def split_uncovered(dump: Dict,
                    order: Sequence[str] = PREEMPT_ORDER + WAIT_ORDER
                    ) -> Dict[str, float]:
    """`dump` is run.py's --dump-trace file. Device 0's idle seconds that
    none of profile.SPAN_ORDER's spans covers, by the stage of `order`
    (innermost first) that does, else `nothing`."""
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

    def paint(stages: Sequence[str]) -> "np.ndarray":
        rank = {st: r for r, st in enumerate(stages)}
        label = np.full(n, len(stages), np.int64)
        # outermost first, so that an inner span paints over its parent
        for st, t0, dur in sorted(
                (s for s in dump["spans"] if s[0] in rank),
                key=lambda s: -rank[s[0]]):
            i = int(max(0, (t0 - t_start) / cell))
            j = int(min(n, (t0 + dur - t_start) / cell + 1))
            if j > i:
                label[i:j] = rank[st]
        return label

    uncovered = paint(profile.SPAN_ORDER) == len(profile.SPAN_ORDER)
    label = paint(order)
    names = list(order) + ["nothing"]
    sums = np.bincount(label[uncovered], weights=idle[uncovered],
                       minlength=len(names))
    out = {"uncovered_s": float(idle[uncovered].sum())}
    out.update({names[k]: float(sums[k]) for k in np.argsort(-sums)
                if sums[k] > 0})
    return out


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        print(json.dumps(split_uncovered(json.load(f))))
