"""The system under test, built and driven by the benchmark.

Everything the program contributes is imported here and nowhere else in
`benchmarks/`: the API server, the clientset, the informers, the scheduler
with its TPU backend, and the counters the program keeps. The benchmark
owns the cluster's shape (from the configuration file), every pod object,
the order pods are created in, the pod watch and every clock.

Pods are named `p-<index>`; index i is the i-th pod ever created, which is
also the order the scheduling queue pops them in (one priority, creation
order) and therefore the order the plain reference decides them in.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.apiserver import APIServer
from kubernetes_tpu.client import Clientset, SharedInformerFactory
from kubernetes_tpu.scheduler import metrics as sched_metrics
from kubernetes_tpu.scheduler.scheduler import Scheduler

NAMESPACE = "default"
STAGE_PARK_S = 0.3  # lets a pop already blocked in the queue park (it
# would otherwise leak one tiny batch past the pause)


def node_name(i: int) -> str:
    """Zero-padded, so creation order, name order and the informer's
    initial-list order are one order: "first of the maxima" means the
    same node to the program and to the reference."""
    return f"node-{i:05d}"


def build_node(i: int, spec: Dict) -> v1.Node:
    name = node_name(i)
    zone = i % spec["zones"]
    alloc = {"cpu": str(spec["cpu"]), "memory": str(spec["memory"]),
             "pods": str(spec["pods"])}
    return v1.Node(
        metadata=v1.ObjectMeta(name=name, labels={
            v1.LABEL_HOSTNAME: name,
            v1.LABEL_ZONE: f"zone-{zone}",
            v1.LABEL_REGION: f"region-{zone % 2}",
        }),
        spec=v1.NodeSpec(),
        status=v1.NodeStatus(capacity=dict(alloc), allocatable=alloc),
    )


def build_pod(name: str, cls: Dict) -> v1.Pod:
    """One pod of a class (a template of the configuration with its
    labels filled in). Constraints select the pod's own labels."""
    labels = dict(cls["labels"])
    constraints = None
    if cls.get("spread_zone_soft"):
        constraints = [v1.TopologySpreadConstraint(
            max_skew=1, topology_key=v1.LABEL_ZONE,
            when_unsatisfiable="ScheduleAnyway",
            label_selector=v1.LabelSelector(match_labels=dict(labels)))]
    affinity = None
    if cls.get("anti_affinity_hostname"):
        affinity = v1.Affinity(pod_anti_affinity=v1.PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[
                v1.PodAffinityTerm(
                    label_selector=v1.LabelSelector(
                        match_labels=dict(labels)),
                    topology_key=v1.LABEL_HOSTNAME)]))
    return v1.Pod(
        metadata=v1.ObjectMeta(name=name, namespace=NAMESPACE, labels=labels),
        spec=v1.PodSpec(
            containers=[v1.Container(
                name="c0", image="registry.example/app:v1",
                resources=v1.ResourceRequirements(requests={
                    "cpu": cls["cpu"], "memory": cls["memory"]}))],
            affinity=affinity,
            topology_spread_constraints=constraints,
        ),
    )


class Cluster:
    def __init__(self, config: Dict, pod_ceiling: int,
                 interpret: bool = False):
        self.config = config
        self.pod_ceiling = pod_ceiling
        self.interpret = interpret
        self.classes: List[Dict] = []
        self._class_index: Dict[tuple, int] = {}
        # per pod, by index
        self.pods: List[v1.Pod] = []
        self.cls: List[int] = []
        self.order: List[int] = []  # indices in creation order
        self.bound_t: List[float] = []
        self.bound_node: List[Optional[str]] = []
        self.rebinds = 0
        self._n_bound = 0
        self._watch = None
        self._watch_thread: Optional[threading.Thread] = None
        self.sched: Optional[Scheduler] = None

    # -- pod classes and objects -------------------------------------------

    def pod_class(self, template: str, group: Optional[int] = None) -> int:
        """Index of the class `template` of the configuration makes; a
        `{group}` in a label value is filled with `group`."""
        key = (template, group)
        idx = self._class_index.get(key)
        if idx is None:
            t = self.config["pod_templates"][template]
            cls = dict(t)
            cls["labels"] = {
                k: str(val).replace("{group}", str(group))
                for k, val in t["labels"].items()}
            idx = self._class_index[key] = len(self.classes)
            self.classes.append(cls)
        return idx

    def prebuild(self, class_ids: Sequence[int]) -> List[int]:
        """Build pod objects ahead of their creation; returns their
        indices. The API call is all the window pays for a pod."""
        first = len(self.pods)
        for c in class_ids:
            self.pods.append(build_pod(f"p-{len(self.pods):07d}",
                                       self.classes[c]))
            self.cls.append(c)
            self.bound_t.append(0.0)
            self.bound_node.append(None)
        if len(self.pods) > self.pod_ceiling:
            raise RuntimeError(
                f"{len(self.pods)} pods over the cell's ceiling of "
                f"{self.pod_ceiling}: the encoding was reserved for less")
        return list(range(first, len(self.pods)))

    def create(self, i: int) -> None:
        self.order.append(i)
        self.cs.pods.create(self.pods[i])

    # -- build and teardown --------------------------------------------------

    def build(self) -> None:
        cfg = self.config
        self.api = APIServer()
        self.cs = Clientset(self.api)
        for i in range(cfg["nodes"]["count"]):
            self.cs.nodes.create(build_node(i, cfg["nodes"]))
        self.factory = SharedInformerFactory(self.cs)
        backend = None
        if self.interpret:
            from kubernetes_tpu.scheduler.tpu_backend import TPUBackend

            backend = TPUBackend(pallas_interpret=True)
        self.sched = Scheduler(
            self.cs, self.factory, backend="tpu",
            max_batch=cfg["scheduler"]["max_batch"], tpu_backend=backend)
        n_anti = self.pod_ceiling if any(
            t.get("anti_affinity_hostname")
            for t in cfg["pod_templates"].values()) else 0
        # one size for the whole run, a constant of the cell: no capacity
        # step (rebuild + compile) inside the window, one compile-cache key
        self.sched.tpu.enc.reserve(pods=self.pod_ceiling, anti_terms=n_anti)
        self._watch = self.cs.pods.watch(namespace=NAMESPACE)
        self._watch_thread = threading.Thread(
            target=self._watch_loop, name="bench-pod-watch", daemon=True)
        self._watch_thread.start()
        self.factory.start()
        if not self.factory.wait_for_cache_sync(timeout=180.0):
            raise RuntimeError("informer sync failed")
        self.sched.start()

    def close(self) -> None:
        if self.sched is not None:
            self.sched.stop()
            self.factory.stop()
        if self._watch is not None:
            self._watch.stop()
            self._watch_thread.join(timeout=30.0)

    # -- the benchmark's own pod watch ----------------------------------------

    def _watch_loop(self) -> None:
        """Binds as the API server publishes them, stamped on arrival.
        Raw events: hydrating every pod would be the benchmark taxing the
        interpreter it shares with the scheduler."""
        now = time.perf_counter
        for ev in self._watch.raw_events():
            if ev.type != "MODIFIED":
                continue
            node = ev.value["spec"].get("nodeName")
            if not node:
                continue
            i = int(ev.value["metadata"]["name"][2:])
            if self.bound_node[i] is None:
                self.bound_node[i] = node
                self.bound_t[i] = now()
                self._n_bound += 1  # this thread alone writes it
            elif self.bound_node[i] != node:
                self.rebinds += 1

    def n_bound(self) -> int:
        return self._n_bound

    def wait_bound(self, n: int, deadline: float) -> bool:
        """Until the watch has seen `n` pods bound in all, or `deadline`
        (perf_counter) passes."""
        while self._n_bound < n:
            if time.perf_counter() >= deadline:
                return False
            time.sleep(0.002)
        return True

    # -- staging (perf/harness._stage, copied) ---------------------------------

    def stage_end(self, n_queued: int, deadline: float,
                  resume: bool = True, settle_s: float = 2.0) -> None:
        """Resume once the queue holds `n_queued` pods (or has stopped
        growing for `settle_s`: the informer has delivered), so the drain
        runs in full batches."""
        last, settled = -1, time.perf_counter()
        while time.perf_counter() < deadline:
            n = self.sched.queue.num_active()
            if n >= n_queued:
                break
            if n != last:
                last, settled = n, time.perf_counter()
            elif time.perf_counter() - settled > settle_s:
                break
            time.sleep(0.005)
        if resume:
            self.sched.resume()

    def stage(self, indices: Sequence[int], timeout: float = 300.0) -> None:
        """Set-up: one staged batch, bound before this returns."""
        self.sched.pause()
        time.sleep(STAGE_PARK_S)
        for i in indices:
            self.create(i)
        self.stage_end(len(indices), time.perf_counter() + 60.0)
        want = len(self.order)
        if not self.wait_bound(want, time.perf_counter() + timeout):
            raise RuntimeError(
                f"set-up: {self.n_bound()} of {want} pods bound")

    # -- what the program counts ----------------------------------------------

    def counters(self) -> Dict:
        def by_label(counter) -> Dict[str, int]:
            out: Dict[str, int] = {}
            for key, val in counter.items():
                slug = "/".join(str(k) for k in key if k) or "-"
                out[slug] = out.get(slug, 0) + int(val)
            return out

        tpu = self.sched.tpu
        sess = tpu._session
        return {
            "device_faults": by_label(sched_metrics.device_faults),
            "dispatch_retries": sum(
                by_label(sched_metrics.dispatch_retries).values()),
            "worker_restarts": sum(
                by_label(sched_metrics.worker_restarts).values()),
            "session_rebuilds": by_label(sched_metrics.session_rebuilds),
            "session_builds": by_label(sched_metrics.session_builds),
            "ladder_demotions": tpu.ladder.demotions,
            "backend_mode": tpu.ladder.mode(),
            "rung_below_top": tpu.ladder.rung() < tpu.ladder.top,
            "session_kind": type(sess).__name__ if sess is not None else "",
            "exec_errors": {
                f"{k[0]}/{k[1]}": str(v) for k, v in
                dict(getattr(sess, "exec_errors", {})).items()},
            "executables": {
                f"{k[0]}/{k[1]}": "aot" if v is not None else "jit"
                for k, v in dict(getattr(sess, "_exec", {})).items()},
        }

    def stored_binds(self) -> Dict[int, str]:
        """pod index -> node, as the API server holds them now."""
        pods, _ = self.cs.pods.list(namespace=NAMESPACE)
        return {int(p.metadata.name[2:]): p.spec.node_name
                for p in pods if p.spec.node_name}
