"""The system under test, built and driven by the benchmark.

Everything the program contributes is imported here and nowhere else in
`benchmarks/`: the API server, the clientset, the informers, the scheduler
with its TPU backend, and the counters the program keeps. The benchmark
owns the cluster's shape (from the configuration file), every pod object,
the order pods are created in, the pod watch and every clock.

Pods are named `p-<index>`; index i is the i-th pod ever created, which is
also the order the scheduling queue pops them in (one priority, creation
order) and therefore the order the plain reference decides them in.

What the benchmark did to the cluster is `Cluster.log`, in order: creates
(`order`), and the events that are not creates (`delete`, `remove_node`, `add_node`),
which are issued only inside a `barrier` (scheduler paused, every pod it
has popped bound) and carry the number of pods bound at that instant. The
objects are built by the module the configuration names as its `builder`
(`benchmarks/builders/<name>.py`), or by the two functions below.
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
from kubernetes_tpu.utils.metrics import legacy_registry

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
                 interpret: bool = False, builder=None):
        self.config = config
        self.pod_ceiling = pod_ceiling
        self.interpret = interpret
        # `builder`: a module with build_node(i, config), build_pod(name,
        # cls); None: the two functions of this file
        self._build_node = (
            (lambda i: builder.build_node(i, config)) if builder is not None
            else (lambda i: build_node(i, config["nodes"])))
        self._build_pod = builder.build_pod if builder is not None \
            else build_pod
        self.classes: List[Dict] = []
        self._class_index: Dict[tuple, int] = {}
        # per pod, by index
        self.pods: List[v1.Pod] = []
        self.cls: List[int] = []
        self.order: List[int] = []  # indices in creation order
        # the events that are not creates: (creates issued before it, event,
        # index, pods bound in all at that instant, time)
        self._events: List[tuple] = []
        self.bound_t: List[float] = []
        self.bound_node: List[Optional[str]] = []
        self.rebinds = 0
        self._n_bound = 0
        # pods the benchmark deleted -> were they bound; pods the watch
        # saw deleted -> when
        self.deleted: Dict[int, bool] = {}
        self.deleted_t: Dict[int, float] = {}
        self.n_deleted_pending = 0
        self.nodes: set = set()  # indices of the nodes there are
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
            self.pods.append(self._build_pod(f"p-{len(self.pods):07d}",
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

    # -- events that are not creates: inside a barrier only ------------------

    def _event(self, op: str, index: int) -> None:
        self._events.append((len(self.order), op, index, self._n_bound,
                             time.perf_counter()))

    @property
    def log(self) -> List[tuple]:
        """Everything the benchmark did to the cluster, in order:
        ("create", i, class) or (event, index, pods bound, time). Put
        together when it is asked for: a create pays nothing for it."""
        out: List[tuple] = []
        done = 0
        for at, *event in self._events + [(len(self.order),)]:
            out += [("create", i, self.cls[i]) for i in self.order[done:at]]
            out += [tuple(event)] if event else []
            done = at
        return out

    def delete(self, i: int) -> None:
        """Pod `i`, bound (a rolling update ends it, its node is drained)
        or still pending."""
        bound = self.deleted[i] = self.bound_node[i] is not None
        self.n_deleted_pending += not bound
        self._event("delete", i)
        self.cs.pods.delete(self.pods[i].metadata.name, NAMESPACE)

    def remove_node(self, n: int) -> None:
        self.nodes.remove(n)
        self._event("node_remove", n)
        self.cs.nodes.delete(node_name(n))

    def add_node(self, n: int) -> None:
        """A removed index again, or a new one."""
        self.nodes.add(n)
        self._event("node_add", n)
        self.cs.nodes.create(self._build_node(n))

    node_name = staticmethod(node_name)

    def live_pods(self) -> tuple:
        """(bound, pending): the pods created and not deleted by the
        benchmark, in creation order, by whether the watch has seen their
        bind."""
        bound, pending = [], []
        for i in self.order:
            if i not in self.deleted:
                (bound if self.bound_node[i] is not None
                 else pending).append(i)
        return bound, pending

    def n_unbound(self) -> int:
        """Pods created, not deleted while pending, and not seen bound."""
        return len(self.order) - self.n_deleted_pending - self._n_bound

    def barrier(self, deadline: float) -> bool:
        """With the scheduler paused: until every pod it has popped is
        bound on the benchmark's own watch, so that the pods bound are a
        prefix of creation order and `n_bound()` says how long a one. A
        pod is pending in the scheduler's queue, or popped; all popped
        pods are bound when queue + bound = created."""
        self.sched._drain_pipeline(timeout=30.0)
        while sum(self.sched.queue.depths()) != self.n_unbound():
            if time.perf_counter() >= deadline:
                return False
            time.sleep(0.002)
        return True

    def settle(self, deadline: float) -> bool:
        """Until the scheduler's informers have delivered every event
        issued so far: its cache holds the nodes there are and the bound
        pods that are left. (A pending pod's delete rides the stream of
        the creates that follow it, which `stage_end` waits for.)"""
        cache = self.sched.cache
        live_bound = self._n_bound - (
            len(self.deleted) - self.n_deleted_pending)
        while (cache.node_count() != len(self.nodes)
               or cache.pod_count() != live_bound):
            if time.perf_counter() >= deadline:
                return False
            time.sleep(0.001)
        return True

    # -- build and teardown --------------------------------------------------

    def build(self) -> None:
        cfg = self.config
        self.api = APIServer()
        self.cs = Clientset(self.api)
        for i in range(cfg["nodes"]["count"]):
            self.cs.nodes.create(self._build_node(i))
        self.nodes = set(range(cfg["nodes"]["count"]))
        self.factory = SharedInformerFactory(self.cs)
        backend = None
        n_mesh = cfg["scheduler"].get("mesh")
        if self.interpret or n_mesh:
            from kubernetes_tpu.scheduler.tpu_backend import TPUBackend

            mesh = None
            if n_mesh:  # the node axis sharded over this many chips
                from kubernetes_tpu.parallel.sharded import make_mesh

                mesh = make_mesh(n_devices=n_mesh)
            backend = TPUBackend(mesh=mesh, pallas_interpret=self.interpret)
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
        """Binds as the API server publishes them, stamped on arrival,
        and deletes. Raw events: hydrating every pod would be the
        benchmark taxing the interpreter it shares with the scheduler."""
        now = time.perf_counter
        for ev in self._watch.raw_events():
            if ev.type != "MODIFIED":
                if ev.type == "DELETED":
                    self.deleted_t[int(ev.value["metadata"]["name"][2:])] \
                        = now()
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
            # a gauge has no items() of its own
            with counter._lock:
                items = list(counter._values.items())
            for key, val in items:
                slug = "/".join(str(k) for k in key if k) or "-"
                out[slug] = out.get(slug, 0) + (
                    int(val) if float(val).is_integer() else val)
            return out

        tpu = self.sched.tpu
        sess = tpu._session
        return {
            # every counter and gauge the program registers, for a metric
            # reader that a later PR adds: {name: {label slug: value}}
            "registry": {
                m.name: by_label(m) for m in
                list(legacy_registry._metrics.values())
                if m.type_name in ("counter", "gauge")},
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

    def stored(self) -> Dict[int, Optional[str]]:
        """pod index -> node (None: not bound), as the API server holds
        them now."""
        pods, _ = self.cs.pods.list(namespace=NAMESPACE)
        return {int(p.metadata.name[2:]): p.spec.node_name or None
                for p in pods}
