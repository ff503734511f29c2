"""One run of one cell: build the cluster, warm it, measure for --seconds,
check every bind against the plain reference, print one JSON line.

    python3 benchmarks/run.py --workload <config>.<traffic> --seed <n> \
        --seconds <s> --trace <0|1>

A cell is data: `configs/<config>.json` (the deployment; it may name its
own `reference` in `references/` and its own `builder` of node and pod
objects in `builders/`), `traffic/<traffic>.json` (the mix, whose `kind`
names a generator in `kinds/`) and the metric readers in `metrics/`.
Nothing here knows a cell by name. See README.md beside this file.

Exit codes: 0 a result was printed (read its `correct`); 4 no TPU, or
fewer chips than the cell asks for (nothing is printed); 1 anything else.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import bisect  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
EXIT_NO_CHIP = 4
SETTLE_S = 60.0  # how long past the close a late bind is waited for
TRACE_DIR = os.path.join(REPO, ".bench_trace")
VARIANTS = ("sampled", "last-max")


def load_module(directory: str, name: str):
    path = os.path.join(HERE, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{directory}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(directory: str, name: str) -> Dict:
    with open(os.path.join(HERE, directory, name + ".json")) as f:
        return json.load(f)


def barrier_not_a_prefix(log: List[tuple], bound_t: List[float],
                         bound_node: List[Optional[str]]) -> int:
    """Pods whose bind the watch saw on the other side of an event that is
    not a create than a FIFO queue puts it. Such an event carries the
    number of pods bound at that instant; with one priority those are the
    first that many pods created and not deleted while pending. 0 on a log
    of creates."""
    times = [ev[3] for ev in log if ev[0] != "create"]
    if not times:
        return 0
    pending: Dict[int, None] = {}  # insertion order is creation order
    want: Dict[int, int] = {}  # pod -> events that came before its bind
    n_decided = n_events = 0
    for ev in log:
        if ev[0] == "create":
            pending[ev[1]] = None
            continue
        while pending and n_decided < ev[2]:
            first = next(iter(pending))
            del pending[first]
            want[first] = n_events
            n_decided += 1
        if ev[0] == "delete":
            pending.pop(ev[1], None)
        n_events += 1
    for i in pending:
        want[i] = n_events
    return sum(1 for i, n in want.items() if (
        bisect.bisect_left(times, bound_t[i]) if bound_node[i] is not None
        else n_events) != n)


def resolve(workload: str) -> Dict:
    """The cell's entry of BENCHMARK.json; a cell that is not listed there
    (one being tried out) is `<config>.<traffic>` split at the first dot
    and reports every metric whose reader finds something."""
    try:
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            bench = json.load(f)
    except FileNotFoundError:
        bench = {"workloads": [], "end_to_end": [], "per_layer": []}
    for w in bench["workloads"]:
        if w["name"] == workload:
            e2e = [m["name"] for m in bench["end_to_end"]
                   if workload in m.get("workloads", [workload])]
            # a per-layer metric with no cell list belongs to every cell
            # that reports the end-to-end metric it moves
            per = [m["name"] for m in bench["per_layer"]
                   if (workload in m["workloads"] if "workloads" in m
                       else m["moves"] in e2e)]
            return {**w, "metrics": {"end_to_end": e2e, "per_layer": per}}
    config, _, traffic = workload.partition(".")
    return {"name": workload, "config": config, "traffic": traffic,
            "chips": 1, "metrics": None}


class Record:
    """What the benchmark itself noted about the pods of the window."""

    def __init__(self):
        self.created: List[int] = []
        self.due: Dict[int, float] = {}
        self.issued: Dict[int, float] = {}
        # when the pod's own client was free to create it: its due time,
        # or the return of that client's previous create if that is later
        self.ready: Dict[int, float] = {}
        self.create_done: Dict[int, float] = {}


class RunData:
    """Everything a metric reader may read; readers return None where
    they find nothing."""

    def __init__(self, **kw):
        self.notes: Dict = {}
        self.__dict__.update(kw)

    def binds_in_window(self) -> List[float]:
        """When the watch saw each bind of the window's pods, up to
        `t_end`: the close of the window, or, where the traffic is a
        closed loop of cycles, the end of the cycle the close fell in."""
        return [self.bound_t[i] for i in self.created
                if self.bound_node[i] is not None
                and self.bound_t[i] <= self.t_end]

    def window_spans(self, stage: str) -> List:
        """[(name, t0, dur, attrs)] of the program's spans of `stage` that
        started between the open and `t_end`; [] when spans were not
        recorded."""
        return [(n, t0, d, a) for n, st, t0, d, a in self.spans or []
                if st == stage and self.t_open <= t0 < self.t_end]

    def traced_launches(self) -> List:
        """[(pods, terms)] of the launches dispatched inside the traced
        part of the window."""
        if not self.trace:
            return []
        a, b = self.trace["t_start"], self.trace["t_stop"]
        return [((at or {}).get("n", 0), self.terms)
                for _, t0, _, at in self.window_spans("dispatch")
                if a <= t0 < b]


class GcClock:
    """What the collector cost inside the window: every collection stops
    the interpreter that the generator shares with the scheduler."""

    def __init__(self):
        self.pauses: List = []  # (start, seconds, generation)
        self._t0 = 0.0

    def __call__(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((self._t0, time.perf_counter() - self._t0,
                                info["generation"]))

    def summary(self, t0: float, t1: float) -> Dict:
        inside = [(d, g) for t, d, g in self.pauses if t0 <= t < t1]
        full = [d for d, g in inside if g == 2]
        return {"collections": len(inside),
                "seconds": sum(d for d, _ in inside),
                "full_collections": len(full), "full_seconds": sum(full),
                "longest_s": max((d for d, _ in inside), default=0.0)}


class Profiler:
    """A jax.profiler trace of `seconds` starting `start` into the window,
    python tracing off, with the anchor that ties its clock to ours."""

    def __init__(self, t_open: float, start: float, seconds: float,
                 log_dir: str):
        self.log_dir = log_dir
        self.t_start = self.t_stop = self.anchor = None
        self._t_open, self._start, self._seconds = t_open, start, seconds
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-profiler")
        self._thread.start()

    def _run(self) -> None:
        import jax

        time.sleep(max(0.0, self._t_open + self._start - time.perf_counter()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench_anchor"):
            self.anchor = time.perf_counter()
        time.sleep(self._seconds)
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def result(self, spans, dump: str = "") -> Optional[Dict]:
        from benchlib import profile

        self._thread.join(timeout=120.0)
        path = profile.find_xplane(self.log_dir)
        if self._thread.is_alive() or path is None:
            return None
        raw = profile.extract(path)
        if dump:
            with open(dump, "w") as f:
                json.dump({"raw": raw, "t_start": self.t_start,
                           "t_stop": self.t_stop, "anchor": self.anchor,
                           "spans": [(st, t0, d) for _, st, t0, d, _ in spans
                                     if t0 < self.t_stop
                                     and t0 + d > self.t_start]}, f)
        out = profile.reduce(
            raw, self.t_start, self.t_stop, self.anchor,
            [(st, t0, d) for _, st, t0, d, _ in spans])
        out.update(t_start=self.t_start, t_stop=self.t_stop,
                   layout=raw["layout"])
        return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # not for the driver: the rehearsal on a CPU asked for by name and the
    # controls of README.md
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", choices=VARIANTS, default="")
    ap.add_argument("--dump-trace", default="", metavar="PATH",
                    help="write the extracted device trace as JSON")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override one traffic parameter (rate sweeps)")
    args = ap.parse_args(argv)

    sys.path[:0] = [HERE, REPO]
    cell = resolve(args.workload)
    config = load_json("configs", cell["config"])
    traffic = load_json("traffic", cell["traffic"])
    for kv in args.set:
        key, _, val = kv.partition("=")
        traffic[key] = json.loads(val)
    kind = load_module("kinds", traffic["kind"])
    builder = (load_module("builders", config["builder"])
               if config.get("builder") else None)

    os.environ.setdefault("JAX_ENABLE_X64", "1")
    if args.trace:
        os.environ.setdefault("KTPU_TRACE_CAPACITY", str(1 << 21))
    import jax

    jax.config.update("jax_enable_x64", True)
    devices = jax.devices()
    platform = devices[0].platform
    on_cpu_by_name = (platform == "cpu" and args.rehearse and (
        jax.config.jax_platforms or "").split(",")[0] == "cpu")
    if not on_cpu_by_name and (
            platform != "tpu" or len(devices) < cell["chips"]):
        print(f"run.py: needs {cell['chips']} TPU chip(s); jax reports "
              f"{len(devices)} x {platform}", file=sys.stderr)
        return EXIT_NO_CHIP
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}

    if config.get("reference"):
        reference = load_module("references", config["reference"])
    else:
        from benchlib import reference
    from benchlib.stats import percentile
    from benchlib.cluster import Cluster, node_name
    from kubernetes_tpu.utils import tracing
    from kubernetes_tpu.utils.compilation_cache import (
        enable_persistent_cache,
    )
    from kubernetes_tpu.utils.device import compile_meter

    enable_persistent_cache()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.xla_cache
    meter = compile_meter()
    now = time.perf_counter

    # -- set-up ---------------------------------------------------------------
    cluster = Cluster(config, traffic["pod_ceiling"], interpret=on_cpu_by_name,
                      builder=builder)
    cluster.build()
    groups = config.get("init_groups", 0)
    cluster.stage(cluster.prebuild([
        cluster.pod_class(config["init_template"],
                          i % groups if groups else None)
        for i in range(config["init_pods"])]))
    for wb in traffic.get("warm_batches", []):
        g = wb.get("groups", 0)
        cluster.stage(cluster.prebuild([
            cluster.pod_class(wb["template"], i % g if g else None)
            for i in range(wb["pods"])]))
    cluster.sched.tpu.wait_warm()
    plan = kind.prepare(cluster, traffic, args.seed, args.seconds)
    plan["traffic"] = traffic
    plan["settle_s"] = SETTLE_S
    rec = Record()
    if args.trace:
        tracing.set_level(1)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    # set-up leaves millions of live objects (nodes, pods built ahead, the
    # caches); a full collection that walks them stops the interpreter for
    # some tenths of a second at a moment no seed fixes. What set-up built
    # is taken out of the collector's sight; what the window allocates is
    # collected as ever, and `detail.gc` says what that cost (PERF.md §6)
    gc.collect()
    gc.freeze()
    gc_clock = GcClock()
    gc.callbacks.append(gc_clock)
    trace_mark = tracing.RECORDER.mark() if args.trace else 0
    counters0 = cluster.counters()
    meter0 = meter.read()
    n_setup_pods = len(cluster.order)

    # -- the window -------------------------------------------------------------
    t_open = now()
    t_close = t_open + args.seconds
    prof = None
    if args.trace:
        prof = Profiler(t_open, traffic.get("trace_start_s", 2.0),
                        min(traffic.get("trace_seconds", 6.0),
                            max(0.5, args.seconds - 2.5)), TRACE_DIR)
    kind_out = kind.drive(cluster, plan, rec, t_open, t_close)
    time.sleep(max(0.0, t_close - now()))
    # a closed loop measures whole cycles: it ends with the cycle that the
    # close fell in, so that no second of the window drops out of a rate
    t_end = max(t_close, kind_out.get("t_end", t_close))
    cluster.sched.resume()
    # the live pods: a pod deleted while it was pending never binds
    cluster.wait_bound(len(cluster.order) - cluster.n_deleted_pending,
                       t_close + SETTLE_S)
    t_settled = now()
    gc.callbacks.remove(gc_clock)

    # -- what the program says of itself, then let go of it ---------------------
    meter1 = meter.read()
    cluster.sched.pause()
    cluster.sched._drain_pipeline(timeout=30.0)
    counters1 = cluster.counters()
    spans = [(e[1], e[2], e[3], e[4], e[6]) for e in
             tracing.RECORDER.snapshot(since=trace_mark)] if args.trace else []
    # the benchmark's own spans (staging) beside the program's
    spans += [(st, st, t0, d, None) for st, t0, d in kind_out.get("spans", [])]
    stats = [d.memory_stats() or {} for d in devices[: cell["chips"]]]
    device["memory_peak_bytes"] = max(
        (s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    trace = prof.result(spans, args.dump_trace) if prof is not None else None
    stored = cluster.stored()
    watch_node = list(cluster.bound_node)
    rebinds = cluster.rebinds
    cluster.close()
    order, log, classes = cluster.order, cluster.log, cluster.classes
    bound_t, deleted = cluster.bound_t, cluster.deleted
    watch_deleted = set(cluster.deleted_t)
    del cluster, plan
    gc.unfreeze()
    gc.collect()

    # -- correct: every bind against the plain reference ------------------------
    t_ref = now()

    def replay(variant: str = ""):
        """(binds, evicted, why the reference refused the log)."""
        try:
            return reference.replay(config, classes, log, variant) + ("",)
        except ValueError as e:  # LogError: what was decided till then
            return e.binds, e.evicted, str(e)

    want, evicted, refused = replay()
    gone = set(deleted) | set(evicted)
    live = [i for i in order if i not in gone]
    # what happened: a live pod's node as the store holds it, and for a pod
    # that was deleted the first bind the watch saw
    got = {}
    for i in order:
        node = watch_node[i] if i in gone else stored.get(i)
        if node:
            got[i] = node
    if args.control:
        # the control: the reference with one guarantee broken, put in the
        # program's place (the log drained the nodes the PROGRAM had filled:
        # a control that cannot follow it stops there)
        ctl = replay(args.control)[0]
        got = {i: node_name(n) for i, n in ctl.items() if n is not None}
    mismatched = sum(
        1 for i, n in want.items()
        if i in got and (n is None or got[i] != node_name(n)))
    reference_s = now() - t_ref
    unbound = sum(1 for i in live if i not in got)
    faults0, faults1 = counters0["device_faults"], counters1["device_faults"]
    # the session the configuration asks for, and the build kind it is
    # counted under
    session = config["scheduler"].get("session", "PallasSession")
    builds = {k: v for k, v in counters1["session_builds"].items()
              if not k.startswith(
                  config["scheduler"].get("session_builds", "pallas"))}
    checks = [
        ("mismatched_binds", mismatched, 0),
        ("unbound_pods", unbound, 0),
        ("rebound_pods", rebinds, 0),
        ("barriers_not_reached", kind_out.get("barriers_not_reached", 0), 0),
        ("log_refused_by_reference", int(bool(refused)), 0),
        ("watch_differs_from_store", sum(
            1 for i in live if watch_node[i] != stored.get(i)), 0),
        ("deleted_pods_still_stored", sum(1 for i in gone if i in stored), 0),
        ("unasked_deletes", len(watch_deleted ^ gone), 0),
        ("barrier_not_a_prefix",
         barrier_not_a_prefix(log, bound_t, watch_node), 0),
        ("device_faults", sum(faults1.values()) - sum(faults0.values()), 0),
        ("dispatch_retries", counters1["dispatch_retries"], 0),
        ("ladder_demotions", counters1["ladder_demotions"]
         + int(counters1["rung_below_top"]), 0),
        ("worker_restarts", counters1["worker_restarts"], 0),
        ("failed_executables", len(counters1["exec_errors"]), 0),
        ("sessions_not_as_configured", sum(builds.values()) + int(
            counters1["session_kind"] != session), 0),
        ("compiles_in_window", meter1["requests"] - meter0["requests"], 0),
    ]
    correct = all(v <= lim for _, v, lim in checks)

    # -- metrics -------------------------------------------------------------------
    run = RunData(
        seconds=args.seconds, t_open=t_open, t_close=t_close, t_end=t_end,
        created=rec.created, due=rec.due, issued=rec.issued, ready=rec.ready,
        create_done=rec.create_done, bound_t=bound_t, bound_node=watch_node,
        latencies=[(bound_t[i] if watch_node[i] is not None else t_settled)
                   - rec.due[i] for i in rec.created
                   if deleted.get(i, True)],  # not: deleted while pending
        kind_out=kind_out, spans=spans, trace=trace, counters0=counters0,
        counters1=counters1, setup_s=t_open - T_PROCESS,
        setup_compile_s=meter0["seconds"], config=config, traffic=traffic,
        device=device, n_nodes=config["nodes"]["count"],
        terms=int(any(c.get("anti_affinity_hostname") for c in classes)))
    which = "per_layer" if args.trace else "end_to_end"
    names = cell["metrics"][which] if cell["metrics"] else sorted(
        f[:-3] for f in os.listdir(os.path.join(HERE, "metrics"))
        if f.endswith(".py"))
    metrics = {}
    for name in names:
        mod = load_module("metrics", name)
        if not cell["metrics"] and mod.KIND != which:
            continue
        value = mod.read(run)
        if value is not None and not (
                on_cpu_by_name and mod.META["source"] == "device_trace"):
            metrics[name] = {"value": value, "unit": mod.META["unit"]}

    result = {
        "correct": correct,
        "attempted": len(rec.created),
        "failed": sum(1 for i in rec.created
                      if watch_node[i] is None and i not in deleted),
        "metrics": metrics,
        "device": device,
    }
    if args.trace and trace is not None and not on_cpu_by_name:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    def moved(name: str) -> Dict:
        """By label, what the window added to a counter of the registry."""
        was = counters0["registry"].get(name, {})
        return {k: v - was.get(k, 0) for k, v in
                counters1["registry"].get(name, {}).items()
                if v != was.get(k, 0)}

    half = len(run.latencies) // 2
    result["detail"] = {
        "workload": args.workload, "seed": args.seed, "overrides": args.set,
        # the sweep's readings: a backlog that grows shows as a second
        # half slower than the first and a long settle
        "p50_first_half_s": percentile(run.latencies[:half], 50)
        if half else None,
        "p50_second_half_s": percentile(run.latencies[half:], 50)
        if half else None,
        "gen_late_p95_s": percentile(
            [rec.issued[i] - rec.ready[i] for i in rec.created
             if i in rec.ready], 95) if rec.ready else None,
        "measured_s": t_end - t_open,
        "gc": gc_clock.summary(t_open, t_end),
        "rehearsal": on_cpu_by_name, "control": args.control,
        "setup_pods": n_setup_pods, "pods_total": len(order),
        "reference_s": reference_s, "settle_s": t_settled - t_close,
        "reference_refused": refused,
        "notes": run.notes,
        "trace_layout": trace["layout"] if trace else None,
        "trace_aligned": trace["aligned"] if trace else None,
        "session_rebuilds": counters1["session_rebuilds"],
        # the events of the run, and what the window added to the two
        # counters that say how the backend took them
        "window": {
            "events": {op: sum(1 for ev in log if ev[0] == op)
                       for op in ("delete", "node_remove", "node_add")},
            "deleted_pending": sum(1 for b in deleted.values() if not b),
            "session_rebuilds": moved("scheduler_session_rebuilds_total"),
            "session_delta_applies": moved(
                "scheduler_session_delta_applies_total"),
        },
        "executables": counters1["executables"],
        "compile_setup": meter0,
        "waves": [[round(w[k] - t_open, 4) for k in
                   ("t_create0", "t_create1", "t_resume", "t_done")]
                  + [w["pods"], w["bound_at_resume"], w["bound_after"]]
                  for w in kind_out.get("waves", [])],
    }
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    print(json.dumps(result), flush=True)
    for n, v, lim in checks:
        print(f"check {n}: {v} (limit {lim})", file=sys.stderr)
    if refused:
        print(f"reference refused the log: {refused}", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
