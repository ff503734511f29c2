"""KV store semantics, run identically over ALL backends: the pure-Python
store (store/kv.py), the native C++ library (store/native.py over
native/kvstore.cpp), and the WAL+snapshot durable store (store/kv.py
DurableKVStore) — the etcd-equivalent semantics must be
indistinguishable (reference: staging/src/k8s.io/apiserver/pkg/storage/
etcd3 store semantics; SURVEY.md §2.4.2). Recovery/crash semantics of
the durable backend live in tests/test_durable_store.py.
"""

import threading

import pytest

from kubernetes_tpu.store import kv
from kubernetes_tpu.store.native import NativeKVStore


@pytest.fixture(params=["python", "native", "durable"])
def store(request):
    if request.param == "python":
        return kv.KVStore(history_limit=50)
    if request.param == "durable":
        tmp = request.getfixturevalue("tmp_path")
        return kv.DurableKVStore(str(tmp / "db"), history_limit=50)
    return NativeKVStore(history_limit=50)


class TestCRUD:
    def test_create_get(self, store):
        rev = store.create("/registry/pods/default/a", {"x": 1})
        assert rev == 1
        got = store.get("/registry/pods/default/a")
        assert got.value == {"x": 1}
        assert got.create_revision == got.mod_revision == 1
        with pytest.raises(kv.KeyExists):
            store.create("/registry/pods/default/a", {})

    def test_get_missing(self, store):
        with pytest.raises(kv.KeyNotFound):
            store.get("/nope")

    def test_update_revisions_and_conflict(self, store):
        store.create("/k", {"v": 0})
        rev = store.update("/k", {"v": 1})
        assert rev == 2
        got = store.get("/k")
        assert got.create_revision == 1 and got.mod_revision == 2
        with pytest.raises(kv.Conflict):
            store.update("/k", {"v": 2}, expected_mod_revision=1)
        rev = store.update("/k", {"v": 2}, expected_mod_revision=2)
        assert rev == 3
        with pytest.raises(kv.KeyNotFound):
            store.update("/missing", {})

    def test_delete(self, store):
        store.create("/k", 1)
        with pytest.raises(kv.Conflict):
            store.delete("/k", expected_mod_revision=99)
        store.delete("/k", expected_mod_revision=1)
        with pytest.raises(kv.KeyNotFound):
            store.get("/k")
        with pytest.raises(kv.KeyNotFound):
            store.delete("/k")

    def test_list_prefix_ordered(self, store):
        store.create("/registry/pods/ns2/b", 2)
        store.create("/registry/pods/ns1/a", 1)
        store.create("/registry/nodes/n1", 3)
        items, rev = store.list("/registry/pods/")
        assert [i.key for i in items] == [
            "/registry/pods/ns1/a",
            "/registry/pods/ns2/b",
        ]
        assert rev == store.revision == 3
        items, _ = store.list("/registry/")
        assert len(items) == 3

    def test_guaranteed_update(self, store):
        store.create("/k", {"n": 0})
        store.guaranteed_update("/k", lambda v: {"n": v["n"] + 1})
        assert store.get("/k").value == {"n": 1}


class TestWatch:
    def test_replay_from_revision(self, store):
        store.create("/a", 1)
        store.create("/b", 2)
        w = store.watch("/", since_revision=1)
        ev = w.poll(timeout=1)
        assert ev.type == kv.ADDED and ev.key == "/b" and ev.revision == 2
        store.update("/a", 10)
        ev = w.poll(timeout=1)
        assert ev.type == kv.MODIFIED and ev.key == "/a" and ev.value == 10
        store.delete("/b")
        ev = w.poll(timeout=1)
        assert ev.type == kv.DELETED and ev.key == "/b" and ev.value == 2
        w.stop()
        assert w.poll(timeout=0.05) is None

    def test_default_watch_is_live_only(self, store):
        store.create("/a", 1)
        w = store.watch("/")  # since_revision=None -> from now
        assert w.poll(timeout=0.05) is None
        store.create("/b", 2)
        ev = w.poll(timeout=1)
        assert ev.key == "/b"
        w.stop()

    def test_since_revision_zero_replays_from_start(self, store):
        # an informer listing an EMPTY store sees revision 0; its watch
        # from 0 must replay anything written between list and watch or
        # the event is lost forever (no informer resync) — the flake this
        # pins down
        w = store.watch("/", since_revision=0)
        store.create("/a", 1)
        got = store.watch("/", since_revision=0)  # created after the write
        assert got.poll(timeout=1).key == "/a"
        assert w.poll(timeout=1).key == "/a"
        w.stop(), got.stop()

    def test_prefix_filter(self, store):
        w = store.watch("/registry/pods/", since_revision=0)
        # explicit 0 on an empty store: replay-from-start (nothing yet)
        w2 = store.watch("/registry/pods/")
        store.create("/registry/nodes/n", 1)
        store.create("/registry/pods/default/p", 2)
        ev = w2.poll(timeout=1)
        assert ev.key == "/registry/pods/default/p"
        w.stop(), w2.stop()

    def test_compaction(self, store):
        # history_limit=50: blow past it, then ask for an ancient revision
        for i in range(60):
            store.create(f"/k{i:03d}", i)
        with pytest.raises(kv.Compacted):
            store.watch("/", since_revision=1)
        # recent revision still watchable
        w = store.watch("/", since_revision=store.revision)
        store.create("/fresh", 1)
        assert w.poll(timeout=1).key == "/fresh"
        w.stop()

    def test_concurrent_writers_one_revision_stream(self, store):
        errs = []

        def writer(base):
            try:
                for i in range(50):
                    store.create(f"/w/{base}/{i}", i)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=writer, args=(b,)) for b in range(4)]
        w = store.watch("/w/")
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        revs = []
        while True:
            ev = w.poll(timeout=0.3)
            if ev is None:
                break
            revs.append(ev.revision)
        assert len(revs) == 200
        assert revs == sorted(revs) and len(set(revs)) == 200
        w.stop()


class TestMany:
    """create_many / guaranteed_update_many: N writes, each its own step,
    the store's lock waited for once a run of them."""

    def test_create_many_is_n_creates_with_their_own_outcomes(self, store):
        store.create("/m/3", "there first")
        w = store.watch("/m/")
        n = 150  # more than two runs
        revs = store.create_many([(f"/m/{i}", i) for i in range(n)])
        assert revs[3] is None  # KeyExists: that item alone
        made = [r for r in revs if r is not None]
        assert len(made) == n - 1 and made == sorted(set(made))
        assert store.get("/m/3").value == "there first"
        seen = [w.poll(timeout=1) for _ in range(n - 1)]
        assert [(e.type, e.key, e.value, e.revision) for e in seen] == [
            ("ADDED", f"/m/{i}", i, revs[i]) for i in range(n) if i != 3]
        assert w.poll(timeout=0.05) is None
        w.stop()

    def test_update_many_keeps_an_items_refusal_and_goes_on(self, store):
        class Refused(Exception):
            pass

        def bump(value):
            if value == 2:
                raise Refused("not this one")
            return value + 100

        for i in range(4):
            store.create(f"/u/{i}", i)
        w = store.watch("/u/")
        out = store.guaranteed_update_many(
            [(f"/u/{i}", bump) for i in (0, 1, 2, 9, 3)],
            item_errors=(Refused,))
        assert isinstance(out[2], Refused)
        assert isinstance(out[3], kv.KeyNotFound)
        revs = [out[0], out[1], out[4]]
        assert revs == sorted(set(revs))
        assert [store.get(f"/u/{i}").value for i in range(4)] == [
            100, 101, 2, 103]
        seen = [w.poll(timeout=1) for _ in range(3)]
        assert [(e.type, e.key, e.revision) for e in seen] == [
            ("MODIFIED", "/u/0", revs[0]), ("MODIFIED", "/u/1", revs[1]),
            ("MODIFIED", "/u/3", revs[2])]
        w.stop()

    def test_update_many_lets_any_other_error_out_at_once(self, store):
        def bump(value):
            if value == 1:
                raise ZeroDivisionError("a bug, not a refusal")
            return value + 100

        for i in range(3):
            store.create(f"/x/{i}", i)
        with pytest.raises(ZeroDivisionError):
            store.guaranteed_update_many([(f"/x/{i}", bump) for i in range(3)])
        assert [store.get(f"/x/{i}").value for i in range(3)] == [100, 1, 2]

    def test_update_many_checks_the_precondition_every_item(self, store):
        class Stale(Exception):
            pass

        calls = []

        def precondition():
            calls.append(1)
            if len(calls) == 2:
                raise Stale("fence lost")

        for i in range(3):
            store.create(f"/p/{i}", i)
        out = store.guaranteed_update_many(
            [(f"/p/{i}", lambda v: v + 100) for i in range(3)],
            precondition=precondition, item_errors=(Stale,))
        assert isinstance(out[1], Stale) and len(calls) == 3
        assert [store.get(f"/p/{i}").value for i in range(3)] == [100, 1, 102]

    @pytest.mark.parametrize("backend", ["python", "durable"])
    @pytest.mark.parametrize("many", ["create_many", "update_many"])
    def test_another_writer_waits_for_the_run(self, backend, many, tmp_path):
        s = (kv.KVStore() if backend == "python"
             else kv.DurableKVStore(str(tmp_path / "db")))
        th = threading.Thread(target=lambda: s.create("/other", 1),
                              daemon=True)
        s.create("/mine/0", 0)
        inside = []

        def second_write(*_):
            # inside the run, after its first write: the other writer
            # starts now and must still be waiting when the run goes on
            th.start()
            th.join(timeout=0.3)
            inside.append(th.is_alive())
            return 1

        if many == "create_many":
            real = s.create
            s.create = lambda key, value: (
                second_write() if key == "/mine/2" else None,
                real(key, value))[1]
            revs = s.create_many([("/mine/1", 1), ("/mine/2", 2)])
            s.create = real
        else:
            revs = s.guaranteed_update_many(
                [("/mine/0", lambda v: v + 1), ("/mine/0", second_write)])
        th.join(timeout=10.0)
        assert inside == [True], "a write got in between two of a run"
        assert not th.is_alive()
        assert revs[1] == revs[0] + 1
        assert s.get("/other").mod_revision == revs[1] + 1


class TestNativeBackedAPIServer:
    def test_cluster_on_native_store(self):
        """The whole apiserver + informer stack over the C++ store."""
        from kubernetes_tpu.api import types as v1
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.client.clientset import Clientset
        from kubernetes_tpu.client.informer import SharedInformerFactory

        from .util import make_node, make_pod, wait_until

        api = APIServer(store=NativeKVStore())
        cs = Clientset(api)
        factory = SharedInformerFactory(cs)
        informer = factory.informer_for("pods")
        factory.start()
        assert factory.wait_for_cache_sync()
        try:
            cs.nodes.create(make_node("n1"))
            cs.pods.create(make_pod("p1", node_name="n1"))
            assert wait_until(lambda: informer.get("default/p1") is not None)
            live = cs.pods.get("p1", "default")
            live.status.phase = "Running"
            cs.pods.update_status(live)
            assert wait_until(
                lambda: (informer.get("default/p1") or make_pod("x")).status.phase
                == "Running"
            )
            # optimistic concurrency through the full stack
            stale = cs.pods.get("p1", "default")
            cs.pods.update(cs.pods.get("p1", "default"))
            from kubernetes_tpu.apiserver.server import Conflict

            with pytest.raises(Conflict):
                cs.pods.update(stale)
        finally:
            factory.stop()


class TestParityExtras:
    @pytest.mark.parametrize("backend", ["python", "native"])
    def test_explicit_compact(self, backend):
        store = (
            kv.KVStore(history_limit=1000)
            if backend == "python"
            else NativeKVStore(history_limit=1000)
        )
        for i in range(10):
            store.create(f"/k{i}", i)
        store.compact(5)
        with pytest.raises(kv.Compacted):
            store.watch("/", since_revision=3)
        w = store.watch("/", since_revision=7)
        assert w.poll(timeout=0.5).revision == 8
        w.stop()

    def test_native_poll_none_blocks_until_event(self):
        import threading
        import time as _time

        store = NativeKVStore()
        w = store.watch("/")
        got = []

        def waiter():
            got.append(w.poll())  # timeout=None must block, not spin/return

        t = threading.Thread(target=waiter, daemon=True)
        t.start()
        _time.sleep(0.2)
        assert not got  # still blocked
        store.create("/x", 1)
        t.join(timeout=2)
        assert got and got[0].key == "/x"
        w.stop()
