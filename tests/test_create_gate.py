"""The hub's single-writer section for create requests
(apiserver.server._CreateGate): one create at a time decodes, admits,
validates and commits; binds, reads and watch frames never enter it;
every way out of a create leaves it."""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest
from test_webhooks import _WebhookServer

from kubernetes_tpu import api
from kubernetes_tpu.apiserver import APIServer, HTTPClient
from kubernetes_tpu.apiserver.server import AdmissionDenied, _CreateGate
from kubernetes_tpu.state import ReplicaNotPromoted
from kubernetes_tpu.state.store import Store
from kubernetes_tpu.utils.metrics import APIServerMetrics, StoreMetrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from harness.children import parse_metrics  # noqa: E402  (the benchmark's own parser)

PODS = "/api/v1/namespaces/default/pods"
BINDINGS = "/api/v1/namespaces/default/bindings"


def pod(name):
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": "default"},
            "spec": {"containers": [{"name": "c", "image": "i"}]}}


def pod_list(names):
    return {"apiVersion": "v1", "kind": "List",
            "items": [pod(n) for n in names]}


def call(srv, method, path, body=None, timeout=30):
    """(status code, decoded JSON body) of one request."""
    req = urllib.request.Request(
        srv.address + path, method=method,
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def scrape(srv):
    with urllib.request.urlopen(srv.address + "/metrics", timeout=30) as r:
        return parse_metrics(r.read().decode())


def gate_series(srv):
    """(create requests that asked for the section, those that found it
    taken), as a scrape reads them."""
    s = scrape(srv)
    return (s["apiserver_create_gate_wait_seconds_count"],
            s["apiserver_create_gate_contended_total"])


@pytest.fixture
def server():
    srv = APIServer().start()
    yield srv
    srv.stop()


class Blocker:
    """An admission validator that parks the create of one named pod
    inside the gate until the test lets it go."""

    def __init__(self, srv, name="held"):
        self.name = name
        self.entered = threading.Event()
        self.release = threading.Event()
        srv.admission.validators.append(self)

    def __call__(self, operation, resource, obj):
        if operation == "CREATE" and resource == "pods" and \
                obj.metadata.name == self.name:
            self.entered.set()
            assert self.release.wait(30), "the test never released the gate"


def in_thread(fn, *args):
    """Run fn(*args) on a thread; .result holds (value, error)."""
    box = {}

    def run():
        try:
            box["value"] = fn(*args)
        except BaseException as e:  # noqa: BLE001  (re-raised by the test)
            box["error"] = e
    t = threading.Thread(target=run, daemon=True)
    t.box = box
    t.start()
    return t


def joined(t, timeout=30):
    t.join(timeout)
    assert not t.is_alive(), "request still in flight"
    if "error" in t.box:
        raise t.box["error"]
    return t.box["value"]


# ------------------------------------- (a) four creators, one at a time


class TestFourCreators:
    def test_bulk_creates_do_not_interleave(self, tmp_path):
        store = Store(wal_path=str(tmp_path / "wal"), metrics=StoreMetrics())
        srv = APIServer(store=store).start()
        try:
            watch = HTTPClient(srv.address).pods("default").watch(
                resource_version=srv.store.resource_version)
            creators, chunks, chunk = 4, 4, 64  # 4 x 256 pods
            start = threading.Barrier(creators)

            def create(c):
                start.wait(10)
                out = []
                for k in range(chunks):
                    names = [f"c{c}-{k}-{i}" for i in range(chunk)]
                    code, body = call(srv, "POST", PODS, pod_list(names))
                    assert code == 200
                    out.append((names, body["items"]))
                return out
            threads = [in_thread(create, c) for c in range(creators)]
            acked = {}
            for t in threads:
                for names, items in joined(t, 60):
                    assert [it["status"] for it in items] == \
                        ["Success"] * chunk
                    assert [it["metadata"]["name"] for it in items] == names
                    rvs = [int(it["metadata"]["resourceVersion"])
                           for it in items]
                    # one consecutive run: nobody else wrote in between
                    assert rvs == list(range(rvs[0], rvs[0] + chunk))
                    acked.update(zip(names, rvs))
            assert len(acked) == creators * chunks * chunk
            code, listed = call(srv, "GET", PODS)
            assert code == 200
            assert {it["metadata"]["name"]: int(
                it["metadata"]["resourceVersion"])
                for it in listed["items"]} == acked
            seen = []
            while len(seen) < len(acked):
                ev = watch.events.get(timeout=10)
                assert ev is not None and ev.type == "ADDED"
                seen.append((ev.object.metadata.name,
                             int(ev.object.metadata.resource_version)))
            watch.stop()
            assert [rv for _, rv in seen] == sorted(acked.values())
            assert dict(seen) == acked
            count, contended = gate_series(srv)
            assert count == creators * chunks
            assert 0 < contended <= count
            # the store's lock was asked for once a create, as before
            assert store.metrics.store_lock_wait.count() == count
        finally:
            srv.stop()

    def test_waiters_enter_in_arrival_order(self):
        gate = _CreateGate(APIServerMetrics())
        order, threads = [], []
        gate.__enter__()
        for i in range(6):
            def wait(i=i):
                with gate:
                    order.append(i)
            t = threading.Thread(target=wait, daemon=True)
            t.start()
            threads.append(t)
            deadline = time.monotonic() + 10
            while len(gate._waiters) <= i and time.monotonic() < deadline:
                time.sleep(0.001)
            assert len(gate._waiters) == i + 1
        gate.__exit__(None, None, None)
        for t in threads:
            t.join(10)
        assert order == list(range(6))
        assert gate._owner is None and not gate._waiters

    def test_never_two_inside_with_more_threads_than_cores(self):
        metrics = APIServerMetrics()
        gate = _CreateGate(metrics)
        workers, rounds = 4 * (os.cpu_count() or 4), 50
        state = {"inside": 0, "entries": 0, "overlaps": 0}

        def enter_once():
            # a lost update or a second thread inside breaks the sums
            state["inside"] += 1
            state["overlaps"] += state["inside"] != 1
            n = state["entries"]
            time.sleep(0)  # hand the interpreter over while inside
            state["entries"] = n + 1
            state["inside"] -= 1

        def work(w):
            for r in range(rounds):
                with gate:
                    enter_once()
                    if (w + r) % 7 == 0:
                        with gate.released():
                            time.sleep(0)
                        enter_once()
        threads = [in_thread(work, w) for w in range(workers)]
        for t in threads:
            joined(t, 60)
        extra = sum(1 for w in range(workers) for r in range(rounds)
                    if (w + r) % 7 == 0)
        assert state == {"inside": 0, "overlaps": 0,
                         "entries": workers * rounds + extra}
        assert metrics.create_gate_wait.count() == workers * rounds
        assert 0 < metrics.create_gate_contended.value() <= workers * rounds
        assert gate._owner is None and not gate._waiters

    def test_a_lone_create_never_waits(self, server):
        for i in range(5):
            assert call(server, "POST", PODS, pod(f"solo-{i}"))[0] == 201
        assert call(server, "POST", PODS,
                    pod_list(["solo-a", "solo-b"]))[0] == 200
        assert gate_series(server) == (6, 0)


# --------------------------- (b) what stays outside a held section


class TestOutsideTheSection:
    def test_bind_get_and_watch_pass_a_held_section(self, server):
        assert call(server, "POST", "/api/v1/nodes", {
            "apiVersion": "v1", "kind": "Node",
            "metadata": {"name": "n0"}})[0] == 201
        assert call(server, "POST", PODS, pod_list(["a", "b"]))[0] == 200
        watch = HTTPClient(server.address).pods("default").watch(
            resource_version=server.store.resource_version)
        blocker = Blocker(server)
        held = in_thread(call, server, "POST", PODS, pod("held"))
        assert blocker.entered.wait(10)
        # a second create queues behind it ...
        second = in_thread(call, server, "POST", PODS,
                           pod_list(["late-0", "late-1"]))
        deadline = time.monotonic() + 10
        while not server._create_gate._waiters and \
                time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(server._create_gate._waiters) == 1
        # ... while the scheduler's bind, a read and the bind's watch
        # frame go through
        code, body = call(server, "POST", BINDINGS, {
            "kind": "BindList", "items": [["a", "n0"], ["b", "n0"]]},
            timeout=10)
        assert code == 200
        assert [it["status"] for it in body["items"]] == ["Success"] * 2
        code, got = call(server, "GET", PODS + "/a", timeout=10)
        assert code == 200 and got["spec"]["nodeName"] == "n0"
        code, listed = call(server, "GET", PODS, timeout=10)
        assert code == 200 and len(listed["items"]) == 2
        bound = set()
        while len(bound) < 2:
            ev = watch.events.get(timeout=10)
            assert ev is not None and ev.type == "MODIFIED"
            assert ev.object.spec.node_name == "n0"
            bound.add(ev.object.metadata.name)
        assert bound == {"a", "b"}
        assert second.is_alive() and held.is_alive()
        blocker.release.set()
        assert joined(held)[0] == 201
        code, body = joined(second)
        assert code == 200
        assert [it["status"] for it in body["items"]] == ["Success"] * 2
        names = []
        while len(names) < 3:
            ev = watch.events.get(timeout=10)
            assert ev is not None and ev.type == "ADDED"
            names.append(ev.object.metadata.name)
        watch.stop()
        assert names == ["held", "late-0", "late-1"]
        assert gate_series(server) == (4, 1)

    def test_a_webhook_call_leaves_the_section_for_the_round_trip(
            self, server):
        """The one step of a create that waits on a socket: the create
        gives the section up for the call and queues for it again."""
        entered, release = threading.Event(), threading.Event()

        def allow(review):
            if review["request"]["object"]["metadata"]["name"] == "slow":
                entered.set()
                release.wait(30)
            return {"allowed": True}
        hook = _WebhookServer(allow)
        try:
            HTTPClient(server.address).resource(
                api.ValidatingWebhookConfiguration).create(
                api.ValidatingWebhookConfiguration(
                    metadata=api.ObjectMeta(name="slow-hook"),
                    webhooks=[api.Webhook(
                        name="slow.example.com",
                        client_config=api.WebhookClientConfig(
                            url=hook.url),
                        rules=[api.RuleWithOperations(
                            operations=["CREATE"], resources=["pods"])],
                        failure_policy="Fail", timeout_seconds=20)]))
            slow = in_thread(call, server, "POST", PODS, pod("slow"))
            assert entered.wait(10)
            assert server._create_gate._owner is None
            # other creates, through the same webhook, finish meanwhile
            assert call(server, "POST", PODS, pod("quick"),
                        timeout=10)[0] == 201
            assert call(server, "POST", PODS, pod_list(["q1", "q2"]),
                        timeout=10)[0] == 200
            assert slow.is_alive()
            release.set()
            assert joined(slow)[0] == 201
            assert server._create_gate._owner is None
            # one observation a request: coming back is not a second one
            assert gate_series(server) == (4, 0)
        finally:
            release.set()
            hook.stop()

    def test_released_is_a_no_op_outside_and_queues_to_come_back(self):
        gate = _CreateGate(APIServerMetrics())
        with gate.released():  # an UPDATE's admission: never inside
            assert gate._owner is None
        inside, leave = threading.Event(), threading.Event()

        def other():
            with gate:
                inside.set()
                leave.wait(10)
        with gate:
            me = gate._owner
            with gate.released():
                t = in_thread(other)
                assert inside.wait(10) and gate._owner == t.ident
                threading.Timer(0.05, leave.set).start()
            assert gate._owner == me  # waited for the other to leave
            joined(t, 10)
        assert gate._owner is None and not gate._waiters


# ------------------------------- (c) every way out leaves the section


def deny_pods_named_bad(operation, resource, obj):
    if resource == "pods" and obj.metadata.name.startswith("bad"):
        raise AdmissionDenied("pods named bad are not admitted")


class TestEveryWayOutLeavesTheSection:
    def test_refusals_inside_release_it(self, server):
        server.admission.validators.append(deny_pods_named_bad)
        code, body = call(server, "POST", PODS, pod("bad-1"), timeout=10)
        assert (code, body["reason"]) == (422, "Invalid")
        assert server._create_gate._owner is None
        assert call(server, "POST", PODS, pod("dup"), timeout=10)[0] == 201
        code, body = call(server, "POST", PODS, pod("dup"), timeout=10)
        assert (code, body["reason"]) == (409, "AlreadyExists")
        wrong_ns = pod("elsewhere")
        wrong_ns["metadata"]["namespace"] = "kube-system"
        code, body = call(server, "POST", PODS, wrong_ns, timeout=10)
        assert code == 422 and "does not match" in body["message"]
        code, body = call(server, "POST", PODS, {
            "apiVersion": "v1", "kind": "Node",
            "metadata": {"name": "not-a-pod"}}, timeout=10)
        assert code == 422 and "does not match resource" in body["message"]
        # the same answer where the namespace is stamped outside the
        # section: an update and a bind
        wrong_ns["metadata"]["name"] = "dup"
        code, body = call(server, "PUT", PODS + "/dup", wrong_ns, timeout=10)
        assert code == 422 and "does not match" in body["message"]
        code, body = call(server, "POST", PODS + "/dup/binding", {
            "apiVersion": "v1", "kind": "Binding",
            "metadata": {"name": "dup", "namespace": "kube-system"},
            "target": {"kind": "Node", "name": "n0"}}, timeout=10)
        assert code == 422 and "does not match" in body["message"]
        # in a bulk create a refused slot is an answer, not an exception
        code, body = call(server, "POST", PODS,
                          pod_list(["bad-2", "dup", "fine"]), timeout=10)
        assert code == 200
        assert [it["status"] for it in body["items"]] == \
            ["Failure", "Failure", "Success"]

        # a store that refuses the write underneath (a replica that lost
        # its promotion between the handler's check and the commit)
        def refuse(*args, **kwargs):
            raise ReplicaNotPromoted("replica is read-only until promote()")
        create, create_bulk = server.store.create, server.store.create_bulk
        server.store.create = server.store.create_bulk = refuse
        try:
            assert call(server, "POST", PODS, pod("r1"), timeout=10)[0] == 503
            assert call(server, "POST", PODS, pod_list(["r2"]),
                        timeout=10)[0] == 503
        finally:
            server.store.create = create
            server.store.create_bulk = create_bulk
        assert server._create_gate._owner is None
        # a read-only store answers before the section is asked for
        before = gate_series(server)[0]
        server.store.read_only = True
        try:
            assert call(server, "POST", PODS, pod("r3"), timeout=10)[0] == 503
            assert call(server, "POST", PODS, pod_list(["r4"]),
                        timeout=10)[0] == 503
        finally:
            server.store.read_only = False
        assert gate_series(server)[0] == before == 8
        # and after all of it the next creates get in at once
        assert call(server, "POST", PODS, pod("after"), timeout=10)[0] == 201
        assert call(server, "POST", PODS, pod_list(["after-2"]),
                    timeout=10)[0] == 200
        assert gate_series(server) == (10, 0)
        code, listed = call(server, "GET", PODS, timeout=10)
        assert sorted(it["metadata"]["name"] for it in listed["items"]) == \
            ["after", "after-2", "dup", "fine"]

    def test_a_waiter_gets_in_when_the_holder_is_denied(self, server):
        blocker = Blocker(server, name="bad-held")
        server.admission.validators.append(deny_pods_named_bad)
        held = in_thread(call, server, "POST", PODS, pod("bad-held"))
        assert blocker.entered.wait(10)
        waiter = in_thread(call, server, "POST", PODS, pod("next"))
        deadline = time.monotonic() + 10
        while not server._create_gate._waiters and \
                time.monotonic() < deadline:
            time.sleep(0.005)
        blocker.release.set()
        assert joined(held)[0] == 422
        assert joined(waiter)[0] == 201
        assert gate_series(server) == (2, 1)


# ---------------------------------------- (d) both families from the start


class TestGateSeries:
    def test_both_families_render_at_zero_before_any_request(self, server):
        s = scrape(server)
        assert s["apiserver_create_gate_wait_seconds_count"] == 0
        assert s["apiserver_create_gate_wait_seconds_sum"] == 0
        assert s["apiserver_create_gate_contended_total"] == 0

    def test_they_survive_a_reset_of_the_metrics(self, server):
        assert call(server, "POST", PODS, pod("one"))[0] == 201
        assert gate_series(server) == (1, 0)
        req = urllib.request.Request(server.address + "/metrics",
                                     method="DELETE")
        urllib.request.urlopen(req, timeout=30).read()
        assert gate_series(server) == (0, 0)

    def test_the_benchmark_reads_them_from_data_files_alone(self):
        repo = REPO
        with open(os.path.join(repo, "BENCHMARK.json")) as f:
            bench = json.load(f)
        entries = {m["name"]: m for m in bench["per_layer"]}
        cells = [w["name"] for w in bench["workloads"]]
        for name, numerator, denominator in (
                ("hub_create_gate_wait_ms_per_pod",
                 "apiserver_create_gate_wait_seconds_sum",
                 "apiserver_pods_bound_total"),
                ("hub_create_gate_contended_share",
                 "apiserver_create_gate_contended_total",
                 "apiserver_create_gate_wait_seconds_count")):
            with open(os.path.join(repo, "benchmarks", "metrics",
                                   name + ".json")) as f:
                spec = json.load(f)
            assert spec["kind"] == "scrape_ratio"
            assert spec["process"] == "kube_apiserver"
            assert (spec["numerator"], spec["denominator"]) == \
                (numerator, denominator)
            assert not os.path.exists(os.path.join(
                repo, "benchmarks", "metrics", name + ".py"))
            entry = entries[name]
            assert entry["layer"] == spec["layer"] == "hub"
            assert entry["moves"] == spec["moves"] == "pods_bound_per_s"
            assert entry["unit"] == spec["unit"]
            assert "workloads" not in entry or \
                set(entry["workloads"]) <= set(cells)
