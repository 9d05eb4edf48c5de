"""Tests for the native control-plane core bindings (libkftpu_core).

The C++-level semantics are covered in native/src/core_test.cc (ctest);
these tests cover the ctypes layer, the NativeApiServer adapter, and —
most importantly — that real controllers run unmodified on the compiled
control plane. (test_fake_apiserver.py additionally runs the full
storage-semantics suite against both backends.)
"""

import threading
import time

import pytest

from kubeflow_tpu.api import new_resource
from kubeflow_tpu.controllers.runtime import Controller, Result, _PyWorkQueue
from kubeflow_tpu.native.apiserver import NativeApiServer
from kubeflow_tpu.native.core import WorkQueue


@pytest.fixture(params=["native", "python"])
def wq(request):
    if request.param == "native":
        return WorkQueue(base_backoff=0.01, max_backoff=0.08)
    return _PyWorkQueue(base_backoff=0.01, max_backoff=0.08)


class TestWorkQueue:
    def test_dedup_and_fifo(self, wq):
        wq.add("a")
        wq.add("a")
        wq.add("b")
        assert len(wq) == 2
        assert wq.get() == "a"
        assert wq.get() == "b"
        assert wq.get() is None
        wq.done("a")
        wq.done("b")

    def test_inflight_readd_lands_after_done(self, wq):
        wq.add("k")
        assert wq.get() == "k"
        wq.add("k")  # arrives while processing
        assert wq.get() is None  # not concurrently reconcilable
        wq.done("k")
        assert wq.get() == "k"  # dirty re-add surfaces now
        wq.done("k")

    def test_sooner_supersedes(self, wq):
        wq.add("k", after=60.0)
        assert wq.get() is None
        wq.add("k")  # sooner wins
        assert wq.get() == "k"
        wq.done("k")

    def test_error_backoff_doubles_and_caps(self, wq):
        assert wq.requeue_error("k") == pytest.approx(0.01)
        assert wq.requeue_error("k") == pytest.approx(0.02)
        assert wq.requeue_error("k") == pytest.approx(0.04)
        assert wq.requeue_error("k") == pytest.approx(0.08)
        assert wq.requeue_error("k") == pytest.approx(0.08)
        wq.forget("k")
        assert wq.requeue_error("k") == pytest.approx(0.01)

    def test_blocking_get_sees_delayed_key(self, wq):
        wq.add("k", after=0.05)
        t0 = time.monotonic()
        assert wq.get(timeout=2.0) == "k"
        assert time.monotonic() - t0 >= 0.04
        wq.done("k")

    def test_next_ready_in(self, wq):
        assert wq.next_ready_in() is None
        wq.add("k", after=10.0)
        eta = wq.next_ready_in()
        assert 9.0 < eta <= 10.0

    def test_threaded_workers_cover_all_keys(self, wq):
        seen = set()
        lock = threading.Lock()

        def worker():
            while True:
                key = wq.get(timeout=0.2)
                if key is None:
                    return
                with lock:
                    seen.add(key)
                wq.done(key)

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for i in range(100):
            wq.add(f"k{i}")
        for t in threads:
            t.join()
        assert seen == {f"k{i}" for i in range(100)}


class TestControllerOnNativeApiServer:
    """A real reconcile loop on the compiled store + compiled workqueue."""

    def test_reconcile_creates_owned_child(self):
        api = NativeApiServer()

        def reconcile(api, key):
            ns, name = key
            from kubeflow_tpu.testing.fake_apiserver import NotFound

            try:
                job = api.get("TpuJob", name, ns)
            except NotFound:
                return None
            from kubeflow_tpu.api.objects import owner_ref

            child = new_resource("Pod", f"{name}-0", ns)
            child.metadata.owner_references = [owner_ref(job)]
            try:
                api.create(child)
            except Exception:
                pass
            return Result()

        c = Controller(api, "TpuJob", reconcile, owns=("Pod",))
        api.create(new_resource("TpuJob", "j", "ml", spec={"workers": 1}))
        c.run_until_idle()
        assert api.get("Pod", "j-0", "ml") is not None
        # Deleting the job cascades to the pod through the C++ store.
        api.delete("TpuJob", "j", "ml")
        assert api.list("Pod", "ml") == []

    def test_error_backoff_then_recovery(self):
        api = NativeApiServer()
        calls = {"n": 0}

        def flaky(api, key):
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("transient")
            return None

        c = Controller(
            api, "Widget", flaky,
            workqueue=WorkQueue(base_backoff=0.005, max_backoff=0.02),
        )
        api.create(new_resource("Widget", "w"))
        deadline = time.monotonic() + 5.0
        while calls["n"] < 3 and time.monotonic() < deadline:
            c.process_one(timeout=0.05)
        assert calls["n"] == 3

    def test_requeue_after_is_delayed(self):
        api = NativeApiServer()

        def periodic(api, key):
            return Result(requeue_after=30.0)

        c = Controller(api, "Widget", periodic)
        api.create(new_resource("Widget", "w"))
        assert c.run_until_idle() == 1  # second pass not yet due
        assert c.has_pending()


def test_native_store_lease_fencing_parity():
    """Write fencing holds on the native backend exactly as on
    FakeApiServer (shared check_lease_guard contract): a stale guard is
    fenced on every write form, the current term's guard passes, and
    Lease writes are exempt."""
    from kubeflow_tpu.controllers.leader import LeaderElector
    from kubeflow_tpu.testing.fake_apiserver import Conflict

    api = NativeApiServer()
    a = LeaderElector(api, "native-ctl", "a",
                      lease_duration=5.0, renew_deadline=3.0,
                      retry_period=0.05)
    assert a._try_acquire_or_renew()
    guard_a = ("", "native-ctl", "a", a.transitions)
    api.create(new_resource("Widget", "w1", spec={"v": 1}),
               lease_guard=guard_a)

    # Depose a (backdate) and let b acquire a new term. The backdating
    # update deliberately carries a guard that is ABOUT to be stale:
    # Lease-kind writes must be exempt from fencing (the election
    # protocol has to stay able to transfer ownership) — this is the
    # exemption actually exercised, not just claimed.
    lease = api.get("Lease", "native-ctl", "").thaw()
    lease.spec = dict(lease.spec)
    lease.spec["renewTime"] = 0.0
    api.update(lease, lease_guard=("", "native-ctl", "zombie", 99))
    b = LeaderElector(api, "native-ctl", "b",
                      lease_duration=5.0, renew_deadline=3.0,
                      retry_period=0.05)
    assert b._try_acquire_or_renew()

    with pytest.raises(Conflict, match="fenced"):
        api.create(new_resource("Widget", "w2"), lease_guard=guard_a)
    w1 = api.get("Widget", "w1").thaw()
    w1.spec["v"] = 2
    with pytest.raises(Conflict, match="fenced"):
        api.update(w1, lease_guard=guard_a)
    with pytest.raises(Conflict, match="fenced"):
        api.delete("Widget", "w1", lease_guard=guard_a)
    guard_b = ("", "native-ctl", "b", b.transitions)
    api.create(new_resource("Widget", "w2"), lease_guard=guard_b)
    assert {w.metadata.name for w in api.list("Widget")} == {"w1", "w2"}


def test_native_backend_behind_http_facade():
    """Drop-in means behind the FACADE too: the native store serves the
    HTTP apiserver's list (rv bookmark), streaming watch, cluster-scope
    CRUD, and lease fencing — previously list/watch 500'd (no
    current_rv/events_since surface) and cluster-scoped gets missed
    (namespace '' was coerced to 'default' in C++)."""
    import time

    from kubeflow_tpu.testing.apiserver_http import (
        ApiServerApp,
        HttpApiClient,
    )
    from kubeflow_tpu.web.wsgi import serve

    api = NativeApiServer()
    server, _ = serve(ApiServerApp(api), host="127.0.0.1", port=0)
    client = HttpApiClient(
        f"http://127.0.0.1:{server.server_port}",
        watch_poll_timeout=1.0, watch_retry=0.05,
    )
    try:
        client.create(new_resource("Node", "n0", "",
                                   spec={"pool": "v5e", "chips": 4}))
        assert client.get("Node", "n0", "").spec["chips"] == 4
        # "" lists exactly the cluster scope.
        assert [n.metadata.name
                for n in client.list("Node", namespace="")] == ["n0"]
        seen = []
        client.watch(lambda ev, o: seen.append((ev, o.metadata.name)),
                     "Widget")
        time.sleep(0.3)
        client.create(new_resource("Widget", "streamed", "default",
                                   spec={}))
        deadline = time.monotonic() + 10
        while ("ADDED", "streamed") not in seen \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert ("ADDED", "streamed") in seen, seen
    finally:
        client.close()
        server.shutdown()


def test_native_deleted_events_get_fresh_rv():
    """FakeApiServer parity pinned at the C++ boundary: a watcher whose
    bookmark is the object's last-seen rv must still observe its
    deletion — the DELETED event carries a FRESH resourceVersion, not
    the stale one (events_since(bookmark) would otherwise skip it and
    the watcher caches the object forever)."""
    api = NativeApiServer()
    a = api.create(new_resource("Widget", "a", spec={}))
    api.create(new_resource("Widget", "b", spec={}))
    bookmark = api.current_rv
    api.delete("Widget", "a")
    events, rv = api.events_since(bookmark)
    assert [(e, o.metadata.name) for _, e, o in events] == [
        ("DELETED", "a")
    ]
    assert rv > bookmark > a.metadata.resource_version
