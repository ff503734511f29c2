"""Unit tests for the api layer: quantity, labels, taints, serde.

Case values mirror the reference's table tests
(staging/src/k8s.io/apimachinery/pkg/api/resource/quantity_test.go,
staging/src/k8s.io/apimachinery/pkg/labels/selector_test.go).
"""

import os
import sys
import threading
import time
import uuid

import pytest

from kubernetes_tpu.api.quantity import Quantity, parse_quantity
from kubernetes_tpu.api.labels import (
    Selector,
    match_node_selector_terms,
    pod_matches_node_selector_and_affinity,
)
from kubernetes_tpu.api.taints import (
    find_matching_untolerated_taint,
    toleration_tolerates_taint,
)
from kubernetes_tpu.api import types as t
from kubernetes_tpu.apiserver.admission import resource_quota
from kubernetes_tpu.apiserver.server import APIServer, Invalid
from kubernetes_tpu.utils import serde, tracing


class TestQuantity:
    @pytest.mark.parametrize(
        "s,value",
        [
            ("0", 0),
            ("100m", 1),  # ceil(0.1)
            ("1", 1),
            ("1500m", 2),  # ceil(1.5)
            ("2k", 2000),
            ("2Ki", 2048),
            ("1Gi", 1073741824),
            ("32Gi", 34359738368),
            ("12e6", 12000000),
            ("1.5Gi", 1610612736),
            ("100M", 100000000),
        ],
    )
    def test_value(self, s, value):
        assert Quantity(s).value() == value

    @pytest.mark.parametrize(
        "s,milli",
        [
            ("100m", 100),
            ("1", 1000),
            ("4", 4000),
            ("2500m", 2500),
            ("1u", 1),  # ceil(0.001)
            ("500n", 1),
            ("0", 0),
        ],
    )
    def test_milli_value(self, s, milli):
        assert Quantity(s).milli_value() == milli

    def test_invalid(self):
        for bad in ["", "abc", "1.5.2", "--1", "1Kii"]:
            with pytest.raises(ValueError):
                parse_quantity(bad)

    def test_compare(self):
        assert Quantity("1000m") == Quantity("1")
        assert Quantity("999m") < Quantity("1")


class TestSelector:
    def test_nil_matches_nothing(self):
        assert not Selector.from_label_selector(None).matches({"a": "b"})
        assert not Selector.from_label_selector(None).matches({})

    def test_empty_matches_everything(self):
        sel = Selector.from_label_selector(t.LabelSelector())
        assert sel.matches({}) and sel.matches({"a": "b"})

    def test_match_labels(self):
        sel = Selector.from_label_selector(t.LabelSelector(match_labels={"a": "b"}))
        assert sel.matches({"a": "b", "c": "d"})
        assert not sel.matches({"a": "x"})
        assert not sel.matches({})

    def test_expressions(self):
        sel = Selector.from_label_selector(
            t.LabelSelector(
                match_expressions=[
                    t.LabelSelectorRequirement(key="env", operator="In", values=["p", "q"]),
                    t.LabelSelectorRequirement(key="gone", operator="DoesNotExist"),
                ]
            )
        )
        assert sel.matches({"env": "p"})
        assert not sel.matches({"env": "z"})
        assert not sel.matches({"env": "p", "gone": "1"})

    def test_not_in_absent_key_matches(self):
        sel = Selector.from_label_selector(
            t.LabelSelector(
                match_expressions=[
                    t.LabelSelectorRequirement(key="k", operator="NotIn", values=["v"])
                ]
            )
        )
        assert sel.matches({})
        assert sel.matches({"k": "other"})
        assert not sel.matches({"k": "v"})

    def test_node_selector_terms_or_semantics(self):
        terms = [
            t.NodeSelectorTerm(
                match_expressions=[
                    t.NodeSelectorRequirement(key="zone", operator="In", values=["z1"])
                ]
            ),
            t.NodeSelectorTerm(
                match_expressions=[
                    t.NodeSelectorRequirement(key="zone", operator="In", values=["z2"])
                ]
            ),
        ]
        assert match_node_selector_terms(terms, {"zone": "z2"}, {})
        assert not match_node_selector_terms(terms, {"zone": "z3"}, {})
        # empty term matches nothing
        assert not match_node_selector_terms([t.NodeSelectorTerm()], {"a": "b"}, {})

    def test_gt_lt(self):
        terms = [
            t.NodeSelectorTerm(
                match_expressions=[
                    t.NodeSelectorRequirement(key="cores", operator="Gt", values=["4"])
                ]
            )
        ]
        assert match_node_selector_terms(terms, {"cores": "8"}, {})
        assert not match_node_selector_terms(terms, {"cores": "4"}, {})
        assert not match_node_selector_terms(terms, {"cores": "abc"}, {})

    def test_match_fields(self):
        terms = [
            t.NodeSelectorTerm(
                match_fields=[
                    t.NodeSelectorRequirement(
                        key="metadata.name", operator="In", values=["node-1"]
                    )
                ]
            )
        ]
        assert match_node_selector_terms(terms, {}, {"metadata.name": "node-1"})
        assert not match_node_selector_terms(terms, {}, {"metadata.name": "node-2"})

    def test_pod_node_selector(self):
        pod = t.Pod(spec=t.PodSpec(node_selector={"disk": "ssd"}))
        node = t.Node(metadata=t.ObjectMeta(name="n", labels={"disk": "ssd"}))
        assert pod_matches_node_selector_and_affinity(pod, node)
        node2 = t.Node(metadata=t.ObjectMeta(name="n2", labels={"disk": "hdd"}))
        assert not pod_matches_node_selector_and_affinity(pod, node2)


class TestTaints:
    def test_exists_empty_key_matches_all(self):
        tol = t.Toleration(operator="Exists")
        assert toleration_tolerates_taint(tol, t.Taint(key="k", value="v", effect="NoSchedule"))

    def test_effect_mismatch(self):
        tol = t.Toleration(key="k", operator="Exists", effect="NoSchedule")
        assert not toleration_tolerates_taint(tol, t.Taint(key="k", effect="NoExecute"))

    def test_equal(self):
        tol = t.Toleration(key="k", operator="Equal", value="v")
        assert toleration_tolerates_taint(tol, t.Taint(key="k", value="v", effect="NoSchedule"))
        assert not toleration_tolerates_taint(tol, t.Taint(key="k", value="w", effect="NoSchedule"))

    def test_find_untolerated_with_filter(self):
        taints = [
            t.Taint(key="a", effect="PreferNoSchedule"),
            t.Taint(key="b", effect="NoSchedule"),
        ]
        # filter only NoSchedule/NoExecute (the Filter plugin predicate)
        pred = lambda taint: taint.effect in ("NoSchedule", "NoExecute")
        taint, found = find_matching_untolerated_taint(taints, [], pred)
        assert found and taint.key == "b"
        tol = [t.Toleration(key="b", operator="Exists")]
        _, found = find_matching_untolerated_taint(taints, tol, pred)
        assert not found


class TestSerde:
    def test_pod_roundtrip(self):
        pod = t.Pod(
            metadata=t.ObjectMeta(name="p", namespace="ns", labels={"app": "web"}),
            spec=t.PodSpec(
                containers=[
                    t.Container(
                        name="c",
                        resources=t.ResourceRequirements(
                            requests={"cpu": "500m", "memory": "1Gi"}
                        ),
                        ports=[t.ContainerPort(host_port=8080, container_port=80)],
                    )
                ],
                tolerations=[t.Toleration(key="k", operator="Exists")],
                priority=100,
            ),
        )
        d = serde.to_dict(pod)
        assert d["metadata"]["name"] == "p"
        assert d["spec"]["containers"][0]["resources"]["requests"]["cpu"] == "500m"
        assert d["spec"]["containers"][0]["ports"][0]["hostPort"] == 8080
        pod2 = serde.from_dict(t.Pod, d)
        assert pod2 == pod

    def test_omitempty(self):
        d = serde.to_dict(t.Pod())
        assert "nodeName" not in d["spec"]
        assert "labels" not in d["metadata"]


# -- APIServer.create: what it locks, what it stamps, the bulk route ---------


def _cm(name: str, uid: str = "") -> t.ConfigMap:
    return t.ConfigMap(
        metadata=t.ObjectMeta(name=name, namespace="default", uid=uid))


def _quota_server(pods: int, pause_s: float = 0.0) -> APIServer:
    """A server with the quota hook, the tree's one `atomic` hook;
    `pause_s` lets go of the interpreter between its usage check and the
    write, where a create that held no lock would be overtaken."""
    api = APIServer()
    quota = resource_quota(api)

    def admit(resource, op, obj):
        quota(resource, op, obj)
        time.sleep(pause_s)

    admit.atomic = quota.atomic
    api._validating.append(admit)
    api.create("resourcequotas", t.ResourceQuota(
        metadata=t.ObjectMeta(name="rq", namespace="default"),
        spec=t.ResourceQuotaSpec(hard={"pods": str(pods)})))
    return api


class TestCreatePath:
    @pytest.mark.parametrize("route", ["create", "create_bulk"])
    def test_no_atomic_hook_takes_no_server_lock(self, route):
        api = APIServer()
        call = {"create": lambda: api.create("configmaps", _cm("a")),
                "create_bulk": lambda: api.create_bulk("configmaps",
                                                       [_cm("a")])}[route]
        th = threading.Thread(target=call, daemon=True)
        with api._lock:  # held by this thread for the whole create
            th.start()
            th.join(timeout=10.0)
            assert not th.is_alive(), "create waited for APIServer._lock"
        assert api.get("configmaps", "a", "default").metadata.uid

    def test_atomic_hook_still_waits_for_the_server_lock(self):
        api = _quota_server(pods=5)
        th = threading.Thread(
            target=lambda: api.create("pods", t.Pod(
                metadata=t.ObjectMeta(name="p", namespace="default"))),
            daemon=True)
        with api._lock:
            th.start()
            th.join(timeout=0.3)
            assert th.is_alive(), "quota check + write ran outside _lock"
        th.join(timeout=10.0)
        assert not th.is_alive()
        assert api.get("pods", "p", "default").metadata.name == "p"

    @pytest.mark.parametrize("k,n", [(1, 8), (5, 16)])
    def test_quota_admits_exactly_the_hard_limit_under_a_race(self, k, n):
        api = _quota_server(pods=k, pause_s=0.002)
        start = threading.Barrier(n)
        outcomes = []

        def one(i):
            start.wait()
            try:
                api.create("pods", t.Pod(
                    metadata=t.ObjectMeta(name=f"p{i}", namespace="default")))
                outcomes.append("ok")
            except Invalid:
                outcomes.append("refused")

        threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)  # a switch inside every check-then-write
        try:
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=30.0)
        finally:
            sys.setswitchinterval(interval)
        assert not any(th.is_alive() for th in threads)
        assert sorted(outcomes) == ["ok"] * k + ["refused"] * (n - k)
        assert len(api.list("pods", "default")[0]) == k

    def test_bulk_create_under_quota_is_exact_and_meets_no_deadlock(self):
        """With an atomic hook every item of a bulk is a create of its
        own under _lock: both routes race here against one hard limit."""
        k, n = 7, 40
        api = _quota_server(pods=k, pause_s=0.0005)
        made = {"bulk": 0, "single": 0}

        def pod(name):
            return t.Pod(metadata=t.ObjectMeta(name=name, namespace="default"))

        def bulk():
            made["bulk"] = api.create_bulk(
                "pods", [pod(f"b{i}") for i in range(n)])

        def single():
            for i in range(n):
                try:
                    api.create("pods", pod(f"s{i}"))
                    made["single"] += 1
                except Invalid:
                    pass

        threads = [threading.Thread(target=bulk, daemon=True),
                   threading.Thread(target=single, daemon=True)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60.0)
        assert not any(th.is_alive() for th in threads), "deadlock"
        assert made["bulk"] + made["single"] == k
        assert len(api.list("pods", "default")[0]) == k

    def test_bulk_create_is_one_write_of_the_store(self):
        api = APIServer()
        calls = []
        real_many = api.store.create_many
        api.store.create_many = lambda items: (
            calls.append(len(items)), real_many(items))[1]
        assert api.create_bulk(
            "configmaps", [_cm(f"c{i}") for i in range(130)]) == 130
        assert calls == [130]
        assert len(api.list("configmaps", "default")[0]) == 130

    @pytest.mark.parametrize("hook", ["mutating", "validating", "post_write"])
    def test_a_blocking_hook_in_a_bulk_create_holds_no_lock(self, hook):
        """A webhook does blocking HTTP and its backend may read or write
        this very server: every hook of a bulk create runs with neither
        _lock nor the store's lock held, so another thread's get, list,
        watch and create go through while the hook is still waiting."""
        api = APIServer()
        api.create("configmaps", _cm("there"))
        others = []

        def backend():
            # what a webhook's backend does from a thread of its own
            # while the hook's caller waits for its answer
            api.get("configmaps", "there", "default")
            api.list("configmaps", "default")
            api.watch("configmaps", "default").stop()
            api.create("configmaps", _cm(f"backend-{len(others)}"))

        def admit(resource, op, obj):
            if resource != "configmaps" or not obj.metadata.name.startswith("b"):
                return
            if obj.metadata.name.startswith("backend"):
                return
            th = threading.Thread(target=backend, daemon=True)
            th.start()
            th.join(timeout=10.0)
            others.append(not th.is_alive())

        {"mutating": api._mutating, "validating": api._validating,
         "post_write": api._post_write}[hook].append(admit)
        done = []
        bulk = threading.Thread(
            target=lambda: done.append(api.create_bulk(
                "configmaps", [_cm(f"b{i}") for i in range(3)])),
            daemon=True)
        bulk.start()
        bulk.join(timeout=60.0)
        assert not bulk.is_alive()
        assert others == [True] * 3, "a hook ran under a lock readers need"
        assert done == [3]
        assert sorted(cm.metadata.name for cm in
                      api.list("configmaps", "default")[0]) == [
            "b0", "b1", "b2", "backend-0", "backend-1", "backend-2", "there"]

    def test_a_hook_that_refuses_one_item_of_a_bulk_skips_that_item(self):
        api = APIServer()

        def admit(resource, op, obj):
            if obj.metadata.name == "c2":
                raise Invalid("not c2")

        api._validating.append(admit)
        assert api.create_bulk(
            "configmaps", [_cm(f"c{i}") for i in range(4)] + [_cm("")]) == 3
        assert sorted(cm.metadata.name for cm in
                      api.list("configmaps", "default")[0]) == ["c0", "c1", "c3"]

    def test_atomic_is_decided_once_a_bulk(self):
        """A quota hook registered while a bulk is on its way (hooks are
        appended late) changes the next call, not this one: the bulk
        never takes _lock half way, whatever it holds."""
        api = APIServer()

        def register(resource, op, obj):
            if obj.metadata.name == "c1":
                api._validating.append(resource_quota(api))

        api._mutating.append(register)
        th = threading.Thread(
            target=lambda: api.create_bulk(
                "configmaps", [_cm(f"c{i}") for i in range(4)]),
            daemon=True)
        with api._lock:
            th.start()
            th.join(timeout=10.0)
            assert not th.is_alive(), "the bulk took _lock half way"
        assert len(api.list("configmaps", "default")[0]) == 4
        assert api._atomic_hooks()

    def test_bulk_bind_is_n_binds_with_their_own_outcomes(self):
        api = APIServer()
        for i in range(70):
            api.create("pods", t.Pod(
                metadata=t.ObjectMeta(name=f"p{i}", namespace="default")))
        api.bind_pod("default", "p5", "elsewhere")
        watch = api.watch("pods", "default")
        outcomes = api.bind_pods(
            [("default", f"p{i}", "n1") for i in range(70)]
            + [("default", "missing", "n1")])
        assert [i for i, o in enumerate(outcomes) if o is not None] == [5, 70]
        assert "already assigned" in str(outcomes[5])
        bound = {p.metadata.name: p.spec.node_name
                 for p in api.list("pods", "default")[0]}
        assert bound.pop("p5") == "elsewhere"
        assert set(bound.values()) == {"n1"} and len(bound) == 69
        revs = []
        for _ in range(69):
            ev = watch.poll(timeout=5.0)
            assert ev is not None and ev.type == "MODIFIED"
            revs.append(int(ev.object.metadata.resource_version))
        assert revs == sorted(revs) and len(set(revs)) == 69
        watch.stop()

    def test_uids_from_four_threads_are_unique_version_4(self):
        api = APIServer()
        uids = [[] for _ in range(4)]

        def one(j):
            for i in range(5000):
                uids[j].append(api.create(
                    "configmaps", _cm(f"c{j}-{i}")).metadata.uid)

        threads = [threading.Thread(target=one, args=(j,)) for j in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120.0)
        assert not any(th.is_alive() for th in threads)
        flat = [u for part in uids for u in part]
        assert len(flat) == 20000 and len(set(flat)) == 20000
        for u in flat:
            parsed = uuid.UUID(u)
            assert parsed.version == 4 and parsed.variant == uuid.RFC_4122
            assert str(parsed) == u

    def test_a_callers_uid_and_timestamp_are_kept(self):
        api = APIServer()
        cm = _cm("mine", uid="caller-chose-this")
        cm.metadata.creation_timestamp = 12.5
        created = api.create("configmaps", cm)
        assert created.metadata.uid == "caller-chose-this"
        assert created.metadata.creation_timestamp == 12.5
        stored = api.get("configmaps", "mine", "default")
        assert stored.metadata.uid == "caller-chose-this"

    @pytest.mark.parametrize("route", ["create", "create_bulk"])
    def test_create_asks_the_kernel_for_no_randomness(self, route,
                                                      monkeypatch):
        calls = []
        real_urandom, real_uuid4 = os.urandom, uuid.uuid4
        monkeypatch.setattr(
            os, "urandom",
            lambda n: (calls.append("urandom"), real_urandom(n))[1])
        monkeypatch.setattr(
            uuid, "uuid4",
            lambda: (calls.append("uuid4"), real_uuid4())[1])
        api = APIServer()
        if route == "create":
            for i in range(64):
                api.create("configmaps", _cm(f"c{i}"))
        else:
            assert api.create_bulk(
                "configmaps", [_cm(f"c{i}") for i in range(64)]) == 64
        assert calls == []
        items, _ = api.list("configmaps", "default")
        assert len({cm.metadata.uid for cm in items}) == 64

    def test_create_bulk_is_every_create_but_the_decode(self):
        api = APIServer()
        hooked = []
        api._post_write.append(
            lambda resource, op, obj: hooked.append(
                (resource, op, obj.metadata.name,
                 obj.metadata.resource_version)))
        api.create("configmaps", _cm("c3"))  # the one that already exists
        watch = api.watch("configmaps", "default")
        t0 = time.time()
        objs = [_cm(f"c{i}") for i in range(6)]
        assert api.create_bulk("configmaps", objs) == 5
        items, _ = api.list("configmaps", "default")
        assert sorted(cm.metadata.name for cm in items) == [
            f"c{i}" for i in range(6)]
        for cm in items:
            assert uuid.UUID(cm.metadata.uid).version == 4
            assert cm.metadata.creation_timestamp >= t0 - 60.0
        added = []
        while len(added) < 5:
            ev = watch.poll(timeout=5.0)
            assert ev is not None, f"only {len(added)} ADDED events"
            assert ev.type == "ADDED"
            added.append(ev.object.metadata.name)
        assert watch.poll(timeout=0.05) is None
        watch.stop()
        assert added == ["c0", "c1", "c2", "c4", "c5"]
        assert [(h[0], h[1], h[2]) for h in hooked] == [
            ("configmaps", "CREATE", n)
            for n in ["c3", "c0", "c1", "c2", "c4", "c5"]]
        assert all(h[3] for h in hooked)  # the hook got the stored object

    def test_create_bulk_decodes_nothing_without_a_hook(self, monkeypatch):
        api = APIServer()
        decoded = []
        real = serde.from_dict
        monkeypatch.setattr(
            serde, "from_dict",
            lambda tp, d: (decoded.append(tp), real(tp, d))[1])
        assert api.create_bulk(
            "configmaps", [_cm(f"c{i}") for i in range(8)]) == 8
        assert decoded == []
        assert api.create("configmaps", _cm("c8")).metadata.resource_version
        assert decoded == [t.ConfigMap]

    @pytest.mark.parametrize("with_quota", [False, True])
    def test_a_create_that_took_the_lock_says_so_in_its_span(self,
                                                             with_quota):
        api = _quota_server(pods=4) if with_quota else APIServer()
        old = tracing.set_level(1)
        try:
            mark = tracing.RECORDER.mark()
            api.create("pods", t.Pod(
                metadata=t.ObjectMeta(name="p", namespace="default")))
            spans = [e for e in tracing.RECORDER.snapshot(since=mark)
                     if e[1] == "create pods" and e[2] == "apiserver"]
        finally:
            tracing.set_level(old)
        assert len(spans) == 1
        attrs = spans[0][6]
        for step in ("admission", "stamp", "encode", "lock", "store",
                     "decode", "hooks"):
            assert attrs[step + "_s"] >= 0.0
        assert attrs.get("locked", False) is with_quota

    def test_a_bulk_create_times_its_store_write_in_a_span_of_its_own(self):
        """Each item's "create <resource>" span ends with its encode; the
        write of all of them, the waits for the store's lock included, is
        the step `store` of ONE "create_bulk <resource>" span."""
        api = APIServer()
        old = tracing.set_level(1)
        try:
            mark = tracing.RECORDER.mark()
            assert api.create_bulk(
                "configmaps", [_cm(f"c{i}") for i in range(70)]) == 70
            spans = [e for e in tracing.RECORDER.snapshot(since=mark)
                     if e[2] == "apiserver"]
        finally:
            tracing.set_level(old)
        items = [e for e in spans if e[1] == "create configmaps"]
        bulk = [e for e in spans if e[1] == "create_bulk configmaps"]
        assert len(items) == 70 and len(bulk) == 1
        for e in items:
            assert {"admission_s", "stamp_s", "encode_s"} <= set(e[6])
            assert "store_s" not in e[6] and "locked" not in e[6]
        attrs = bulk[0][6]
        assert attrs["n"] == 70
        assert attrs["store_s"] >= 0.0 and attrs["hooks_s"] >= 0.0
