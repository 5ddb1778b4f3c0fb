"""Watch-stream resume + wire robustness tests (ISSUE 4 tentpole).

Acceptance:
  - a dropped watch with no intervening history overflow resumes at
    last_sync_rv with ZERO list calls (request-counting client), exactly
    once per event delivered;
  - a 410 (history-window overflow while disconnected) triggers exactly
    ONE relist, with event-sequence parity against an uninterrupted
    control run — no dropped or duplicated deltas;
  - _HTTPWatch records the terminal stream error (reset vs clean close
    are distinguishable) and the staleness watchdog kills silently-dead
    streams instead of hanging forever.
"""

import queue
import threading
import time

import pytest

from kubernetes_tpu import api
from kubernetes_tpu.api import Quantity
from kubernetes_tpu.state import Client, SharedInformerFactory
from kubernetes_tpu.state.informer import EventHandlers, SharedInformer
from kubernetes_tpu.state.store import ExpiredError, Store
from kubernetes_tpu.utils.metrics import InformerMetrics


def make_pod(name, ns="default"):
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace=ns),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="img",
            resources=api.ResourceRequirements(
                requests={"cpu": Quantity("100m"),
                          "memory": Quantity("64Mi")}))]))


class CountingRC:
    """ResourceClient proxy that counts list/watch calls and can block
    watch connects (to hold an informer disconnected while the test
    mutates the store)."""

    def __init__(self, inner):
        self._inner = inner
        self.lists = 0
        self.watches = 0
        self.block_watch = False

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def list_rv(self, *a, **kw):
        self.lists += 1
        return self._inner.list_rv(*a, **kw)

    def watch(self, *a, **kw):
        if self.block_watch:
            raise ConnectionError("watch blocked by test")
        self.watches += 1
        return self._inner.watch(*a, **kw)


class Recorder:
    """Collects handler deliveries as (type, key, rv) tuples."""

    def __init__(self):
        self.events = []
        self._lock = threading.Lock()

    def handlers(self):
        return EventHandlers(
            on_add=lambda o: self._rec("ADD", o),
            on_update=lambda old, new: self._rec("UPD", new),
            on_delete=lambda o: self._rec("DEL", o))

    def _rec(self, etype, obj):
        with self._lock:
            self.events.append((etype, obj.metadata.key(),
                                obj.metadata.resource_version))

    def snapshot(self):
        with self._lock:
            return list(self.events)


def _wait(cond, timeout=5.0, interval=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


def _sever(inf):
    """Stop the informer's current watch stream (the connection-drop
    analog for in-process watches) and wait for the round to end."""
    assert _wait(lambda: inf._watch is not None)
    w = inf._watch
    w.stop()
    return w


class TestWatchResume:
    def test_dropped_watch_resumes_with_zero_lists(self):
        """ACCEPTANCE: resume at last_sync_rv — no LIST, no lost or
        duplicated deltas."""
        client = Client()
        client.pods("default").create(make_pod("p0"))
        rc = CountingRC(client.pods())
        metrics = InformerMetrics()
        inf = SharedInformer(rc, metrics=metrics)
        rec = Recorder()
        inf.add_event_handlers(rec.handlers())
        inf.start()
        try:
            assert inf.wait_for_sync()
            assert rc.lists == 1 and rc.watches == 1
            rv0 = inf.last_sync_rv
            assert rv0 is not None
            # hold the informer disconnected while the cluster moves on
            rc.block_watch = True
            _sever(inf)
            for i in range(1, 4):
                client.pods("default").create(make_pod(f"p{i}"))
            rc.block_watch = False
            assert _wait(lambda: len(inf.indexer.list()) == 4)
            assert _wait(lambda: inf.last_sync_rv > rv0)
            # ZERO additional lists; exactly one reconnect consumed
            assert rc.lists == 1, "resume must not relist"
            assert rc.watches == 2
            assert metrics.relists.value(resource="pods") == 1
            assert metrics.watch_reconnects.value(resource="pods") == 1
            # every delta delivered exactly once
            adds = [e for e in rec.snapshot() if e[0] == "ADD"]
            assert sorted(k for _, k, _ in adds) == \
                ["default/p0", "default/p1", "default/p2", "default/p3"]
            assert len(adds) == len(set(adds))
        finally:
            inf.stop()

    def test_history_overflow_relists_exactly_once(self):
        """ACCEPTANCE (410 path): shrink the store's history window,
        overflow it while the watch is down — the informer relists
        exactly once and the delivered event sequence has parity with an
        uninterrupted control run (nothing dropped, nothing doubled)."""
        store = Store()
        store.HISTORY_WINDOW = 8  # instance override; _publish honors it
        client = Client(store)
        control_client = Client()  # mirror cluster, never disconnected
        for c in (client, control_client):
            c.pods("default").create(make_pod("seed"))

        metrics = InformerMetrics()
        rc = CountingRC(client.pods())
        inf = SharedInformer(rc, metrics=metrics)
        rec = Recorder()
        inf.add_event_handlers(rec.handlers())

        control = SharedInformer(control_client.pods(),
                                 metrics=InformerMetrics())
        control_rec = Recorder()
        control.add_event_handlers(control_rec.handlers())

        inf.start()
        control.start()
        try:
            assert inf.wait_for_sync() and control.wait_for_sync()
            rc.block_watch = True
            _sever(inf)
            # 12 creates > window of 8: the informer's resume rv is gone
            for i in range(12):
                client.pods("default").create(make_pod(f"p{i}"))
                control_client.pods("default").create(make_pod(f"p{i}"))
            rc.block_watch = False
            assert _wait(lambda: len(inf.indexer.list()) == 13)
            assert _wait(lambda: len(control.indexer.list()) == 13)
            # exactly one relist beyond the initial sync
            assert metrics.relists.value(resource="pods") == 2
            assert rc.lists == 2
            # event parity with the control: same delta multiset (rvs
            # differ only through creation order, which is identical)
            mine = sorted(rec.snapshot())
            theirs = sorted(control_rec.snapshot())
            assert [e[:2] for e in mine] == [e[:2] for e in theirs]
            assert len(mine) == len(set(mine)), "duplicated delta"
        finally:
            inf.stop()
            control.stop()

    def test_watch_at_fresh_rv_does_not_expire(self):
        """A resume rv still inside the window replays history instead of
        raising (the store-side half of the resume contract)."""
        store = Store()
        store.HISTORY_WINDOW = 8
        client = Client(store)
        client.pods("default").create(make_pod("a"))
        rv = store.resource_version
        for i in range(4):  # fewer than the window
            client.pods("default").create(make_pod(f"b{i}"))
        w = store.watch("pods", None, resource_version=rv)
        got = [w.events.get(timeout=1) for _ in range(4)]
        assert [e.object.metadata.name for e in got] == \
            [f"b{i}" for i in range(4)]
        w.stop()
        # overflow the window, then the old rv is gone
        for i in range(10):
            client.pods("default").create(make_pod(f"c{i}"))
        with pytest.raises(ExpiredError):
            store.watch("pods", None, resource_version=rv)


class _StaleWatch:
    """A watch whose stream went silent long ago (no bytes, no close)."""

    def __init__(self):
        self.events = queue.Queue()
        self.error = None
        self.last_activity = time.monotonic() - 3600.0
        self.killed = False

    def kill(self, reason=""):
        self.killed = True
        if self.error is None:
            from kubernetes_tpu.apiserver.httpclient import WatchStaleError
            self.error = WatchStaleError(reason)
        self.events.put(None)

    def stop(self):
        self.events.put(None)


class _StaleThenLiveRC:
    """First watch connect returns a silently-dead stream; later ones
    delegate to the real in-process client."""

    def __init__(self, inner):
        self._inner = inner
        self.stale = _StaleWatch()
        self.connects = 0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def watch(self, *a, **kw):
        self.connects += 1
        if self.connects == 1:
            return self.stale
        return self._inner.watch(*a, **kw)


class TestStalenessWatchdog:
    def test_silently_dead_stream_is_killed_and_resumed(self):
        client = Client()
        client.pods("default").create(make_pod("p0"))
        rc = _StaleThenLiveRC(client.pods())
        metrics = InformerMetrics()
        inf = SharedInformer(rc, metrics=metrics)
        inf._POLL = 0.05
        inf.staleness_timeout = 0.2
        inf.start()
        try:
            assert inf.wait_for_sync()
            # the watchdog kills the dead stream and the informer
            # resumes on a live one — events flow again
            assert _wait(lambda: rc.stale.killed, timeout=5.0)
            client.pods("default").create(make_pod("p1"))
            assert _wait(lambda: len(inf.indexer.list()) == 2)
            assert metrics.watch_stale_kills.value(resource="pods") == 1
            assert metrics.watch_stream_errors.value(
                resource="pods", reason="WatchStaleError") == 1
        finally:
            inf.stop()


class TestHTTPWatchWire:
    """The real wire: _HTTPWatch against a live APIServer."""

    @pytest.fixture()
    def server(self):
        from kubernetes_tpu.apiserver import APIServer
        srv = APIServer().start()
        yield srv
        srv.stop()

    def test_stream_error_recorded_and_resume_zero_lists(self, server):
        """kill() severs the socket mid-stream: the watch reports a
        WatchStaleError (not a clean close) and the informer resumes at
        last_sync_rv without a LIST."""
        from kubernetes_tpu.apiserver import HTTPClient
        admin = HTTPClient(server.address)
        admin.pods("default").create(make_pod("p0"))
        rc = CountingRC(HTTPClient(server.address).pods())
        metrics = InformerMetrics()
        inf = SharedInformer(rc, metrics=metrics)
        inf.start()
        try:
            assert inf.wait_for_sync()
            assert rc.lists == 1
            assert _wait(lambda: inf._watch is not None)
            w = inf._watch
            w.kill("test-induced reset")
            admin.pods("default").create(make_pod("p1"))
            assert _wait(lambda: len(inf.indexer.list()) == 2, timeout=10)
            assert rc.lists == 1, "wire resume must not relist"
            assert metrics.relists.value(resource="pods") == 1
            assert metrics.watch_stream_errors.value(
                resource="pods", reason="WatchStaleError") == 1
            assert type(w.error).__name__ == "WatchStaleError"
        finally:
            inf.stop()

    def test_clean_close_leaves_no_error(self, server):
        from kubernetes_tpu.apiserver import HTTPClient
        client = HTTPClient(server.address)
        client.pods("default").create(make_pod("p0"))
        w = client.pods().watch(resource_version=0)
        ev = w.events.get(timeout=5)
        assert ev.object.metadata.name == "p0"
        assert w.last_rv == ev.resource_version
        w.stop()
        # stop() is a clean close: the queue ends with None and no
        # terminal error is recorded (the heartbeat turns the read over)
        assert _wait(lambda: w.error is None, timeout=0.1)
        for got in iter(lambda: w.events.get(timeout=3), None):
            pass
        assert w.error is None

    def test_injected_watch_drop_counts_as_stream_error(self, server):
        """A drop_after budget severs the stream after K events with a
        ConnectionResetError recorded — reset and clean close are now
        distinguishable (the old blanket except hid both)."""
        from kubernetes_tpu.apiserver import HTTPClient
        from kubernetes_tpu.apiserver.httpclient import WATCH_STREAM_ERRORS
        client = HTTPClient(
            server.address,
            wire_hook=lambda kind, op, res, path:
                1 if kind == "watch" else None)
        admin = HTTPClient(server.address)
        before = WATCH_STREAM_ERRORS.value(
            resource="pods", reason="ConnectionResetError")
        w = client.pods().watch()
        admin.pods("default").create(make_pod("d0"))
        ev = w.events.get(timeout=5)
        assert ev.object.metadata.name == "d0"
        admin.pods("default").create(make_pod("d1"))
        # the second event trips the 1-event budget: the stream dies
        assert _wait(lambda: w.error is not None, timeout=5)
        assert isinstance(w.error, ConnectionResetError)
        assert WATCH_STREAM_ERRORS.value(
            resource="pods", reason="ConnectionResetError") == before + 1


class TestWatchBookmarks:
    """allowWatchBookmarks (ISSUE 7 satellite): the hub's heartbeat
    frames carry the current resourceVersion; the informer advances
    last_sync_rv on them, so a QUIET resource's resume point keeps pace
    with other resources' churn and a reconnect after the history window
    overflowed costs a reconnect, not a 410 relist."""

    @pytest.fixture()
    def server(self):
        from kubernetes_tpu.apiserver import APIServer
        store = Store()
        store.HISTORY_WINDOW = 16
        srv = APIServer(store=store).start()
        srv._test_store = store
        yield srv
        srv.stop()

    def test_raw_watch_negotiates_bookmark_frames(self, server):
        from kubernetes_tpu.apiserver import HTTPClient
        from kubernetes_tpu.state.store import BOOKMARK
        client = HTTPClient(server.address)
        client.pods("default").create(make_pod("p0"))
        w = client.pods().watch(resource_version=0, bookmarks=True)
        ev = w.events.get(timeout=5)
        assert ev.object.metadata.name == "p0"
        bm = w.events.get(timeout=5)  # idle stream: next frame is the
        assert bm.type == BOOKMARK    # rv-carrying heartbeat
        assert bm.object is None
        assert bm.resource_version >= ev.resource_version
        assert w.last_rv == bm.resource_version
        w.stop()
        # non-negotiating streams keep the bare heartbeat: no BOOKMARK
        # frames ever reach a raw consumer that didn't opt in
        w2 = client.pods().watch(resource_version=0)
        ev2 = w2.events.get(timeout=5)
        assert ev2.object.metadata.name == "p0"
        assert _wait(lambda: not w2.events.empty(), timeout=2.5) is False
        w2.stop()

    def test_bookmark_shrinks_410_relist_window(self, server):
        """The informer sits quiet on pods while nodes churn the GLOBAL
        rv past the bounded history window. A bookmark advances
        last_sync_rv through the quiet period, so killing the stream
        resumes with ZERO additional lists — where the pre-bookmark
        resume point is provably ExpiredError."""
        from kubernetes_tpu.apiserver import HTTPClient
        admin = HTTPClient(server.address)
        admin.pods("default").create(make_pod("p0"))
        rc = CountingRC(HTTPClient(server.address).pods())
        metrics = InformerMetrics()
        inf = SharedInformer(rc, metrics=metrics)
        inf.start()
        try:
            assert inf.wait_for_sync()
            rv0 = inf.last_sync_rv
            # other-resource churn: overflow the (global) history window
            for i in range(24):
                admin.nodes().create(api.Node(
                    metadata=api.ObjectMeta(name=f"bm-n{i}")))
            # the old resume point is now truly gone...
            with pytest.raises(ExpiredError):
                server._test_store.watch("pods", None,
                                         resource_version=rv0)
            # ...but the idle stream's bookmark advances past the churn
            # (ALL of it: a heartbeat that fell between two of the 24
            # creates carries an rv the window has since dropped)
            assert _wait(lambda: inf.last_sync_rv >= rv0 + 24, timeout=5.0)
            assert _wait(
                lambda: metrics.watch_bookmarks.value(resource="pods") > 0)
            assert _wait(lambda: inf._watch is not None)
            inf._watch.kill("test-induced reset")
            admin.pods("default").create(make_pod("p1"))
            assert _wait(lambda: len(inf.indexer.list()) == 2, timeout=10)
            assert rc.lists == 1, "bookmarked resume must not relist"
            assert metrics.relists.value(resource="pods") == 1
        finally:
            inf.stop()


class TestFactoryWiring:
    def test_factory_shares_metrics_and_removes_handlers(self):
        client = Client()
        client.pods("default").create(make_pod("x"))
        factory = SharedInformerFactory(client)
        inf = factory.informer_for(api.Pod)
        assert inf.metrics is factory.metrics
        seen = []
        handlers = EventHandlers(on_add=lambda o: seen.append(1))
        factory.start()
        assert factory.wait_for_cache_sync()
        inf.add_event_handlers(handlers)
        assert _wait(lambda: len(seen) == 1)  # synthetic replay
        inf.remove_event_handlers(handlers)
        client.pods("default").create(make_pod("y"))
        assert _wait(lambda: len(inf.indexer.list()) == 2)
        assert len(seen) == 1  # detached: no further deliveries
        factory.stop()
