"""API server flow control: APF classification, fair queues, client-side
token bucket / retry budget, legacy max-inflight (read/write split) 429s,
CORS.

Ref: the DefaultBuildHandlerChain slots the reference wires in
apiserver/pkg/server/config.go:545-552 (max-in-flight, timeout, CORS) and
the API Priority & Fairness filter that replaced bare max-in-flight.
"""

import http.client
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from kubernetes_tpu import api
from kubernetes_tpu.apiserver import APIServer, HTTPClient
from kubernetes_tpu.apiserver import flowcontrol as fc
from kubernetes_tpu.apiserver.httpclient import (HTTPResourceClient,
                                                 TooManyRequestsError)
from kubernetes_tpu.utils.clock import FakeClock
from kubernetes_tpu.utils.metrics import FlowControlMetrics


def make_pod(name):
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace="default"),
        spec=api.PodSpec(containers=[api.Container(name="c", image="img")]))


class TestClassify:
    """The flow-schema table, in precedence order."""

    def test_system_identities(self):
        class U:
            name = "system:kube-scheduler"
            groups = ()
        c = fc.classify("list", "pods", "", "default", user=U())
        assert c.level == fc.SYSTEM and c.schema == "system-components"

        class N:
            name = "kubelet-7"
            groups = ("system:nodes",)
        c = fc.classify("update", "pods", "", "default", user=N())
        assert c.level == fc.SYSTEM

    def test_leases_and_binds_are_system(self):
        assert fc.classify("update", "leases", "", "kube-system").level \
            == fc.SYSTEM
        assert fc.classify("create", "bindings", "", "default").flow \
            == "scheduler-binds"
        assert fc.classify("create", "pods", "binding", "default").level \
            == fc.SYSTEM

    def test_node_heartbeats_are_system(self):
        assert fc.classify("patch", "nodes", "", "").level == fc.SYSTEM
        assert fc.classify("update", "nodes", "status", "").level \
            == fc.SYSTEM
        # node READS are not heartbeats
        assert fc.classify("get", "nodes", "", "").level == fc.CATCH_ALL

    def test_tenant_traffic_split(self):
        # namespaced LIST -> workload-low; namespaced create -> high
        lo = fc.classify("list", "pods", "", "team-a")
        hi = fc.classify("create", "pods", "", "team-a")
        assert lo.level == fc.WORKLOAD_LOW and lo.schema == "tenant-bulk"
        assert hi.level == fc.WORKLOAD_HIGH

    def test_priority_hint_demotes_to_workload_low(self):
        c = fc.classify("create", "configmaps", "", "team-a",
                        headers={fc.PRIORITY_HINT_HEADER: "workload-low"})
        assert c.level == fc.WORKLOAD_LOW

    def test_flow_key_is_tenant_label_when_resolvable(self):
        c = fc.classify("list", "pods", "", "ns-1",
                        tenant_of=lambda ns: "acme")
        assert c.flow == "acme"
        # resolver failure falls back to the namespace, never raises
        def boom(ns):
            raise RuntimeError("store down")
        c = fc.classify("list", "pods", "", "ns-1", tenant_of=boom)
        assert c.flow == "ns-1"

    def test_cluster_scope_is_catch_all(self):
        c = fc.classify("list", "podgroups", "", "")
        assert c.level == fc.CATCH_ALL


class TestDrainEstimator:
    def test_retry_after_from_observed_drain_rate(self):
        clock = FakeClock()
        d = fc.DrainEstimator(clock)
        # 5 dispatches, one per 2s -> rate = 4 dispatches / 8s = 0.5/s
        for _ in range(5):
            d.note_dispatch()
            clock.step(2.0)
        assert d.rate() == pytest.approx(0.5)
        # 4 queued at 0.5/s -> 8s to drain
        assert d.retry_after(4) == 8
        # clamped to [1, 30]
        assert d.retry_after(0) == 1
        assert d.retry_after(1000) == 30

    def test_cold_start_assumes_one_per_seat_second(self):
        d = fc.DrainEstimator(FakeClock())
        assert d.rate() == 0.0
        assert d.retry_after(3, seats=1) == 3
        assert d.retry_after(8, seats=4) == 2


class TestFairQueues:
    def _ctl(self, seed=0, **kw):
        kw.setdefault("read_pool", 4)
        kw.setdefault("write_pool", 4)
        kw.setdefault("queue_timeout", 0.2)
        return fc.FlowController(seed=seed, clock=FakeClock(), **kw)

    def test_shares_carve_seats_with_floor(self):
        ctl = self._ctl(read_pool=10, write_pool=2)
        assert ctl._levels[(fc.SYSTEM, "read")].seats == 4
        assert ctl._levels[(fc.CATCH_ALL, "read")].seats == 1
        # tiny pool: every level keeps the >= 1 seat floor
        assert ctl._levels[(fc.WORKLOAD_LOW, "write")].seats == 1

    def test_shuffle_shard_hand_is_pure_function_of_seed(self):
        a = self._ctl(seed=7)._levels[(fc.WORKLOAD_LOW, "read")]
        b = self._ctl(seed=7)._levels[(fc.WORKLOAD_LOW, "read")]
        c = self._ctl(seed=8)._levels[(fc.WORKLOAD_LOW, "read")]
        flows = [f"tenant-{i}" for i in range(16)]
        assert [a.hand_for(f) for f in flows] == \
            [b.hand_for(f) for f in flows]
        assert [a.hand_for(f) for f in flows] != \
            [c.hand_for(f) for f in flows]

    def test_dispatch_log_deterministic_for_same_seed(self):
        """Same seed + same admission sequence -> byte-identical
        dispatch order (the chaos reproducibility contract). Waiters
        park one at a time (each confirmed queued before the next
        starts), so the queue state the round-robin dispatcher walks is
        identical across runs."""
        import queue as queuemod

        def run(seed):
            ctl = self._ctl(seed=seed, write_pool=1, record=True,
                            queue_timeout=10.0)
            flows = ["t-a", "t-b", "t-c", "t-a", "t-b", "t-c"]
            done: queuemod.Queue = queuemod.Queue()
            first = ctl.admit(
                fc.FlowClassification(fc.WORKLOAD_LOW, flows[0], "s"),
                "write")
            lvl = ctl._levels[(fc.WORKLOAD_LOW, "write")]
            threads = []
            for i, flow in enumerate(flows[1:]):
                th = threading.Thread(
                    target=lambda f=flow: done.put(ctl.admit(
                        fc.FlowClassification(fc.WORKLOAD_LOW, f, "s"),
                        "write")))
                th.start()
                threads.append(th)
                for _ in range(500):
                    with ctl._lock:
                        if lvl.depth() == i + 1:
                            break
                    time.sleep(0.005)
            ctl.release(first)
            for _ in flows[1:]:
                # each release hands the seat to exactly one waiter
                ctl.release(done.get(timeout=5))
            for th in threads:
                th.join(timeout=5)
            return list(ctl.dispatch_log)
        assert run(3) == run(3)

    def test_system_never_starved_by_saturated_workload_low(self):
        """Seats are per level: a workload-low level at queue overflow
        neither blocks nor rejects a system request — the non-starvation
        invariant the overload drill asserts end to end."""
        import queue as queuemod
        ctl = self._ctl(write_pool=4, n_queues=1, queue_length=1,
                        queue_timeout=5.0)
        lo = fc.FlowClassification(fc.WORKLOAD_LOW, "burst", "s")
        held = ctl.admit(lo, "write")  # the 1 floor seat, now busy
        done: queuemod.Queue = queuemod.Queue()
        th = threading.Thread(
            target=lambda: done.put(ctl.admit(lo, "write")))
        th.start()
        lvl = ctl._levels[(fc.WORKLOAD_LOW, "write")]
        for _ in range(500):
            with ctl._lock:
                if lvl.depth() == 1:
                    break
            time.sleep(0.005)
        # the single queue is full: the next workload-low admit sheds...
        with pytest.raises(fc.Rejected):
            ctl.admit(lo, "write")
        # ...while system still dispatches immediately on its own seats
        t0 = time.monotonic()
        t = ctl.admit(
            fc.FlowClassification(fc.SYSTEM, "leader-election", "s"),
            "write")
        assert time.monotonic() - t0 < 0.5
        ctl.release(t)
        ctl.release(held)           # hands the seat to the queued waiter
        ctl.release(done.get(timeout=5))
        th.join(timeout=5)

    def test_queue_timeout_rejects_with_retry_after(self):
        ctl = self._ctl(write_pool=1, queue_timeout=0.05)
        lo = fc.FlowClassification(fc.WORKLOAD_LOW, "t", "s")
        held = ctl.admit(lo, "write")
        with pytest.raises(fc.Rejected) as ei:
            ctl.admit(lo, "write")
        assert ei.value.reason == "queue timeout"
        assert 1 <= ei.value.retry_after <= 30
        ctl.release(held)

    def test_overflow_rejects_and_counts(self):
        m = FlowControlMetrics()
        ctl = fc.FlowController(read_pool=2, write_pool=2,
                                queue_length=0, queue_timeout=0.05,
                                clock=FakeClock(), metrics=m)
        lo = fc.FlowClassification(fc.WORKLOAD_LOW, "t", "s")
        held = ctl.admit(lo, "write")
        with pytest.raises(fc.Rejected) as ei:
            ctl.admit(lo, "write")
        assert ei.value.reason == "queue full"
        assert m.rejected.value(priority_level=fc.WORKLOAD_LOW,
                                reason="queue-full") == 1
        ctl.release(held)
        assert m.dispatched.value(priority_level=fc.WORKLOAD_LOW) == 1


class TestClientFlowControl:
    def test_token_bucket_reservation_math(self):
        clock = FakeClock()
        tb = fc.TokenBucket(qps=2.0, burst=2, clock=clock)
        assert tb.wait() == 0.0
        assert tb.wait() == 0.0
        # burst exhausted: third take reserves 1 token deficit = 0.5s
        assert tb.wait() == pytest.approx(0.5)
        # FakeClock.sleep advanced time, so a fourth take reserves the
        # same deficit again — steady state is exactly qps
        assert tb.wait() == pytest.approx(0.5)

    def test_retry_budget_caps_then_refills(self):
        clock = FakeClock()
        rb = fc.RetryBudget(cap=2, refill_per_s=0.5, clock=clock)
        assert rb.try_spend() and rb.try_spend()
        assert not rb.try_spend()  # dry
        clock.step(2.0)  # +1 token
        assert rb.try_spend()
        assert not rb.try_spend()

    def test_client_429_retry_honors_server_retry_after(self, monkeypatch):
        """The client's 429 loop floors its backoff delay at the parsed
        Retry-After and stops when the budget is dry."""
        clock = FakeClock()
        calls = {"n": 0}

        def flaky(self, method, url, body=None, content_type=None):
            calls["n"] += 1
            if calls["n"] < 3:
                raise TooManyRequestsError("shed", retry_after=4.0)
            return {"ok": True}
        monkeypatch.setattr(HTTPResourceClient, "_request_once", flaky)
        c = HTTPClient("http://hub.invalid", retry_429=3, clock=clock)
        rc = c.resource(api.Node)
        t0 = clock.now()
        assert rc._request("GET", "http://hub.invalid/x") == {"ok": True}
        assert calls["n"] == 3
        # two retries, each slept >= the server's 4s hint
        assert clock.now() - t0 >= 8.0

    def test_client_429_budget_dry_surfaces_the_429(self, monkeypatch):
        clock = FakeClock()

        def always_shed(self, method, url, body=None, content_type=None):
            raise TooManyRequestsError("shed", retry_after=1.0)
        monkeypatch.setattr(HTTPResourceClient, "_request_once",
                            always_shed)
        budget = fc.RetryBudget(cap=1, refill_per_s=0.0, clock=clock)
        c = HTTPClient("http://hub.invalid", retry_429=10,
                       retry_budget=budget, clock=clock)
        rc = c.resource(api.Node)
        with pytest.raises(TooManyRequestsError):
            rc._request("GET", "http://hub.invalid/x")
        # one budgeted retry happened, then the budget stopped the herd
        assert not budget.try_spend()

    def test_limiter_smooths_offered_load(self, monkeypatch):
        clock = FakeClock()

        def ok(self, method, url, body=None, content_type=None):
            return {}
        monkeypatch.setattr(HTTPResourceClient, "_request_once", ok)
        c = HTTPClient("http://hub.invalid", qps=1.0, burst=1,
                       clock=clock)
        rc = c.resource(api.Node)
        t0 = clock.now()
        for _ in range(4):
            rc._request("GET", "http://hub.invalid/x")
        # 1 burst token + 3 reservations at 1 qps
        assert clock.now() - t0 >= 3.0


class TestAPFServer:
    """End-to-end APF on the live hub."""

    def test_429_labeled_with_resource_and_priority_level(self):
        """A shed answer carries a computed Retry-After and lands in
        apiserver_request_total with the REAL resource + priority level
        (satellite: no more bare code-only shed rows); the SAME
        keep-alive connection keeps working afterwards."""
        srv = APIServer(max_nonmutating_inflight=1, apf=True,
                        flow_queue_length=0, flow_queue_timeout=0.05)
        orig = srv._handle

        def slow(h, method, req, cls, user=None):
            if method == "GET" and req.resource == "pods" \
                    and not req.name:
                time.sleep(0.6)
            return orig(h, method, req, cls, user)
        srv._handle = slow
        srv.start()
        try:
            hold = threading.Thread(target=lambda: urllib.request.urlopen(
                f"{srv.address}/api/v1/namespaces/default/pods",
                timeout=10))
            hold.start()
            time.sleep(0.2)
            host = srv.address.split("//", 1)[1]
            conn = http.client.HTTPConnection(host, timeout=5)
            # catch-all read seat is held? no — the slow LIST is
            # workload-low; flood the same level to draw a 429
            conn.request("GET", "/api/v1/namespaces/default/pods",
                         headers={"Connection": "keep-alive"})
            resp = conn.getresponse()
            body = resp.read()
            assert resp.status == 429, body
            ra = resp.getheader("Retry-After")
            assert ra is not None and int(ra) >= 1
            hold.join(timeout=10)
            # the keep-alive connection survives the 429
            conn.request("GET", "/api/v1/namespaces/default/pods")
            resp2 = conn.getresponse()
            assert resp2.status == 200
            resp2.read()
            conn.close()
            assert srv.request_metrics.requests.value(
                verb="GET", resource="pods", code="429",
                priority_level=fc.WORKLOAD_LOW) == 1
            assert srv.flow_metrics.rejected.value(
                priority_level=fc.WORKLOAD_LOW, reason="queue-full") == 1
        finally:
            srv.stop()

    def test_debug_flows_surface(self):
        srv = APIServer(max_nonmutating_inflight=4,
                        max_mutating_inflight=4, apf=True)
        srv.start()
        try:
            with urllib.request.urlopen(f"{srv.address}/debug/flows",
                                        timeout=5) as resp:
                state = json.loads(resp.read())
            assert state["apf"] is True
            levels = {(e["priority_level"], e["class"])
                      for e in state["priority_levels"]}
            assert (fc.SYSTEM, "write") in levels
            assert (fc.CATCH_ALL, "read") in levels
        finally:
            srv.stop()

    def test_flowcontrol_metrics_exposed(self):
        srv = APIServer(max_nonmutating_inflight=4, apf=True)
        srv.start()
        try:
            HTTPClient(srv.address).nodes().list()
            with urllib.request.urlopen(f"{srv.address}/metrics",
                                        timeout=5) as resp:
                text = resp.read().decode()
            assert "flowcontrol_dispatched_total" in text
            assert "flowcontrol_queue_wait_seconds" in text
        finally:
            srv.stop()

    def test_ktpu_apf_env_kill_switch(self, monkeypatch):
        monkeypatch.setenv("KTPU_APF", "0")
        srv = APIServer(max_nonmutating_inflight=4)
        assert srv.apf is False and srv._flow is None
        monkeypatch.delenv("KTPU_APF")
        srv2 = APIServer(max_nonmutating_inflight=4)
        assert srv2.apf is True and srv2._flow is not None
        # unlimited pools (0/0) -> nothing to negotiate, APF stays off
        assert APIServer(max_mutating_inflight=0,
                         max_nonmutating_inflight=0).apf is False


class TestMaxInflight:
    """The LEGACY instant-shed path (apf=False): kept as the KTPU_APF=0
    fallback and the overload bench's control."""

    def test_slow_reads_429_but_writes_proceed(self):
        """With the read pool saturated by slow GETs, excess reads get 429
        + Retry-After while WRITES still go through their own pool — the
        reference's mutating/non-mutating split."""
        srv = APIServer(max_nonmutating_inflight=2, apf=False)
        orig = srv._handle

        def slow(h, method, req, cls, user=None):
            if method == "GET" and req.resource == "pods" and not req.name:
                time.sleep(1.5)
            return orig(h, method, req, cls, user)
        srv._handle = slow
        srv.start()
        try:
            client = HTTPClient(srv.address)
            results = []

            def read():
                code = 200
                try:
                    urllib.request.urlopen(
                        f"{srv.address}/api/v1/namespaces/default/pods",
                        timeout=10)
                except urllib.error.HTTPError as e:
                    code = e.code
                results.append(code)
            readers = [threading.Thread(target=read) for _ in range(4)]
            for t in readers:
                t.start()
            time.sleep(0.4)  # readers hold the 2 read slots
            # a write lands promptly despite the saturated read pool
            t0 = time.time()
            client.pods("default").create(make_pod("w"))
            assert time.time() - t0 < 1.0
            for t in readers:
                t.join(timeout=15)
            assert results.count(429) >= 1
            assert results.count(200) >= 2
        finally:
            srv.stop()

    def test_429_carries_computed_retry_after(self):
        """The legacy shed path no longer hardcodes Retry-After: 1 — it
        estimates from the observed drain rate (still clamped >= 1)."""
        srv = APIServer(max_nonmutating_inflight=1, apf=False)
        orig = srv._handle

        def slow(h, method, req, cls, user=None):
            if method == "GET":
                time.sleep(1.0)
            return orig(h, method, req, cls, user)
        srv._handle = slow
        srv.start()
        try:
            t = threading.Thread(target=lambda: urllib.request.urlopen(
                f"{srv.address}/api/v1/nodes", timeout=10))
            t.start()
            time.sleep(0.3)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{srv.address}/api/v1/nodes",
                                       timeout=5)
            assert ei.value.code == 429
            ra = ei.value.headers.get("Retry-After")
            assert ra is not None and int(ra) >= 1
            t.join(timeout=10)
            # the shed row is labeled with the real resource + level
            # (asserted after join: the server thread counts the shed a
            # beat after the client has already read the 429)
            assert srv.request_metrics.requests.value(
                verb="GET", resource="nodes", code="429",
                priority_level=fc.CATCH_ALL) >= 1
        finally:
            srv.stop()

    def test_watch_exempt_from_inflight(self):
        """Watches are long-running and must not consume read slots —
        and the exemption comes from PARSED query params, so a
        suffix like ?watch=false (or a label selector mentioning
        watch) does not slip past the limits."""
        srv = APIServer(max_nonmutating_inflight=1, apf=False)
        srv.start()
        try:
            client = HTTPClient(srv.address)
            watches = [client.pods("default").watch() for _ in range(3)]
            # the read pool is untouched: a plain GET still succeeds
            assert client.nodes().list() == []
            # watch=false is NOT a watch: it must go through the pool
            # (and succeed here, since the pool is idle — once the LIST
            # above has given its seat back, which its handler does after
            # the client already holds the response)
            deadline = time.monotonic() + 5
            while True:
                try:
                    with urllib.request.urlopen(
                            f"{srv.address}/api/v1/nodes?watch=false",
                            timeout=5) as resp:
                        assert resp.status == 200
                    break
                except urllib.error.HTTPError as e:
                    if e.code != 429 or time.monotonic() > deadline:
                        raise
                    time.sleep(0.01)
            for w in watches:
                w.stop()
        finally:
            srv.stop()


class TestCORS:
    def test_preflight_and_header_echo(self):
        srv = APIServer(cors_allowed_origins=["http://ui.example.com"])
        srv.start()
        try:
            req = urllib.request.Request(
                f"{srv.address}/api/v1/nodes", method="OPTIONS",
                headers={"Origin": "http://ui.example.com"})
            resp = urllib.request.urlopen(req, timeout=5)
            assert resp.status == 204
            assert resp.headers["Access-Control-Allow-Origin"] == \
                "http://ui.example.com"
            req = urllib.request.Request(
                f"{srv.address}/api/v1/nodes",
                headers={"Origin": "http://ui.example.com"})
            resp = urllib.request.urlopen(req, timeout=5)
            assert resp.headers["Access-Control-Allow-Origin"] == \
                "http://ui.example.com"
            # a disallowed origin gets no CORS grant
            req = urllib.request.Request(
                f"{srv.address}/api/v1/nodes",
                headers={"Origin": "http://evil.example.com"})
            resp = urllib.request.urlopen(req, timeout=5)
            assert "Access-Control-Allow-Origin" not in resp.headers
        finally:
            srv.stop()
