"""The served hub's collector policy (utils/gcpolicy.py): every
generation-2 collection freezes its survivors, so a full collection walks
what is new since the last one and not the whole stored cluster. Every
test here puts the process's collector back as it found it (frozen set,
callbacks, thresholds): xdist runs many files in one worker."""

import gc
import http.client
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from kubernetes_tpu.apiserver import APIServer
from kubernetes_tpu.utils import gcpolicy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from harness.children import free_port, parse_metrics  # noqa: E402  (the benchmark's own parser)

PODS = "/api/v1/namespaces/default/pods"
SERIES = ("apiserver_gc_freezes_total", "apiserver_gc_frozen_objects") + tuple(
    f'apiserver_gc_{family}{{generation="{g}"}}'
    for family in ("collections_total", "pause_seconds_total")
    for g in gcpolicy.GENERATIONS)


@pytest.fixture
def collector():
    """Install the policy; afterwards unfreeze and restore the callbacks
    and thresholds whatever the test did. What the worker holds already
    is frozen first, so that install's collection walks a heap of a
    hub's start-up size whatever other files this worker ran, and the
    next full collection comes as soon as in a fresh hub."""
    callbacks = list(gc.callbacks)
    thresholds = gc.get_threshold()
    gc.collect()
    gc.freeze()
    try:
        yield gcpolicy.install()
    finally:
        gc.callbacks[:] = callbacks
        gc.set_threshold(*thresholds)
        gc.unfreeze()


def scrape(srv):
    with urllib.request.urlopen(srv.address + "/metrics", timeout=30) as r:
        return parse_metrics(r.read().decode())


class Hub:
    """An in-process hub driven over its HTTP surface by one connection."""

    def __init__(self, port, srv=None):
        self.srv = srv
        self.port = port
        self.conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                               timeout=30)

    def call(self, method, path, body=None):
        self.conn.request(method, path, headers={
            "Content-Type": "application/json"},
            body=None if body is None else json.dumps(body).encode())
        resp = self.conn.getresponse()
        raw = resp.read()
        assert resp.status < 300, raw[:300]
        return json.loads(raw)

    def watch(self):
        """Open a pod watch and read its first frame; the caller closes."""
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=30)
        conn.request("GET", PODS + "?watch=true&resourceVersion=0")
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.fp.readline()
        return conn

    def step(self, k, n, collect):
        """Create n pods in bulk and bind them in bulk over HTTP under a
        live watch, then delete them in the hub's own client (a DELETE
        over HTTP waits out a delayed ACK); `collect` forces a full
        collection mid-step."""
        names = [f"pod-{k}-{i}" for i in range(n)]
        self.call("POST", PODS, {"apiVersion": "v1", "kind": "List",
                                 "items": [_pod(name) for name in names]})
        watch = self.watch()
        out = self.call("POST", "/api/v1/namespaces/default/bindings", {
            "kind": "BindList",
            "items": [[name, f"node-{i % 7}"] for i, name in enumerate(names)]})
        assert all(item["status"] == "Success" for item in out["items"])
        if collect:
            gc.collect()
        watch.close()
        pods = self.srv.client.pods("default")
        for name in names:
            pods.delete(name)

    def close(self):
        self.conn.close()


@pytest.fixture
def hub():
    srv = APIServer().start()
    h = Hub(int(srv.address.rsplit(":", 1)[1]), srv)
    yield h
    h.close()
    srv.stop()


def test_a_full_collection_freezes_its_survivors_and_is_counted(collector):
    policy = collector.policy
    assert policy.freezes == 1 and policy.collections[2] == 1
    assert gc.get_freeze_count() > 0
    kept = [{"pod": [i]} for i in range(1000)]
    before = gc.get_freeze_count()
    gc.collect()
    assert policy.freezes == 2 and policy.collections[2] == 2
    assert policy.pause_s[2] > 0.0
    assert gc.get_freeze_count() >= before + len(kept)
    # the young generations are left empty: only survivors were frozen
    assert gc.get_count()[0] < 50


def test_the_series_ride_a_live_hubs_metrics(collector):
    srv = APIServer().start()
    try:
        before = scrape(srv)
        srv.metrics.add_registry("gc", collector.registry)
        gc.collect()
        after = scrape(srv)
    finally:
        srv.stop()
    assert not any(name.startswith("apiserver_gc_") for name in before)
    assert set(SERIES) <= set(after)
    assert after["apiserver_gc_freezes_total"] == collector.policy.freezes >= 2
    assert after['apiserver_gc_collections_total{generation="2"}'] >= 2
    assert after["apiserver_gc_frozen_objects"] > 0


def _garbage_left(hub, steps, n):
    """Cyclic garbage that the frozen set hid after `steps` steps, each
    with a full collection while its watch and its pods are alive."""
    for k in range(steps):
        hub.step(k, n, collect=True)
    # let the hub's watch threads see their clients gone and end
    hub.step(steps, 1, collect=False)
    time.sleep(0.2)
    gc.unfreeze()
    return gc.collect()


def test_freezing_leaks_nothing_that_grows_with_the_work(collector, hub):
    hub.step(0, 8, collect=True)  # first-use caches of the request path
    small = _garbage_left(hub, 4, 32)
    gc.freeze()
    large = _garbage_left(hub, 16, 32)
    assert small < 1000 and large < small + 200, (small, large)


def test_what_stays_outside_the_frozen_set_is_a_small_share(collector, hub):
    for k in range(20):
        hub.step(k, 64, collect=False)
    assert collector.policy.freezes >= 2
    outside = len(gc.get_objects())
    assert outside < 0.25 * (outside + gc.get_freeze_count()), outside


def test_the_callback_takes_no_lock(collector):
    """A collection that starts inside a held metric lock (any allocation
    can start one) must not wait for that lock."""
    held = [collector.registry._lock] + [
        m._lock for m in (collector.collections, collector.pause,
                          collector.freezes, collector.frozen)]
    for lock in held:
        lock.acquire()
    try:
        t = threading.Thread(target=gc.collect, daemon=True)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        gc.collect()  # and on the holding thread itself
    finally:
        for lock in held:
            lock.release()
    assert collector.policy.freezes == 3


def test_an_in_process_hub_keeps_the_interpreters_collector():
    frozen, callbacks = gc.get_freeze_count(), list(gc.callbacks)
    srv = APIServer().start()
    srv.stop()
    assert gc.get_freeze_count() == frozen
    assert gc.callbacks == callbacks


def _bench_run():
    """benchmarks/run.py as a module: its read_metric is the one reader of
    every kind of data file."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "ktpu_bench_run_gc", os.path.join(REPO, "benchmarks", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _served_hub_scrapes(data_dir, pods):
    """Two scrapes of a served hub's /metrics around `pods` pods created
    and bound in bulks of 512."""
    port = free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubernetes_tpu.cmd.kube_apiserver",
         "--port", str(port), "--data-dir", data_dir],
        cwd=REPO, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)

    def scrape_hub():
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
            return parse_metrics(r.read().decode())
    try:
        deadline = time.monotonic() + 60
        while True:
            try:
                before = scrape_hub()
                break
            except OSError:
                assert proc.poll() is None and time.monotonic() < deadline
                time.sleep(0.1)
        hub = Hub(port)
        for lo in range(0, pods, 512):
            names = [f"pod-{i}" for i in range(lo, lo + 512)]
            hub.call("POST", PODS, {"apiVersion": "v1", "kind": "List",
                                    "items": [_pod(n) for n in names]})
            hub.call("POST", "/api/v1/namespaces/default/bindings", {
                "kind": "BindList", "items": [[n, "node-0"] for n in names]})
        hub.close()
        after = scrape_hub()
    finally:
        proc.terminate()
        proc.wait(timeout=30)
    return before, after


def _pod(name):
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": "default",
                         "labels": {"name": "test"}},
            "spec": {"containers": [{"name": "c", "image": "i"}]}}


def test_the_served_hub_installs_the_policy_and_the_benchmark_reads_it(
        tmp_path):
    before, after = _served_hub_scrapes(str(tmp_path / "data"), 4096)
    for s in (before, after):
        assert set(SERIES) <= set(s)
        assert s["apiserver_gc_freezes_total"] >= 1
        assert s["apiserver_gc_frozen_objects"] > 0
    assert after["apiserver_pods_bound_total"] - \
        before["apiserver_pods_bound_total"] == 4096
    name = "hub_gc_full_pause_ms_per_pod"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == name)
    assert "workloads" not in entry  # every cell reports it
    with open(os.path.join(REPO, "benchmarks", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert (spec["layer"], spec["moves"], spec["unit"]) == \
        (entry["layer"], entry["moves"], entry["unit"]) == \
        ("hub", "pods_bound_per_s", "ms/pod")
    run = _bench_run()

    def ctx(b, a):
        return {"probe0": {"scrape": {"kube_apiserver": b}},
                "probe1": {"scrape": {"kube_apiserver": a}}}
    pause = after['apiserver_gc_pause_seconds_total{generation="2"}'] - \
        before['apiserver_gc_pause_seconds_total{generation="2"}']
    assert run.read_metric(name, spec, ctx(before, after)) == \
        pytest.approx(1000.0 * pause / 4096)
    # a hub without the series (the parent commit): nothing, no raise
    bare = {k: v for k, v in after.items()
            if not k.startswith("apiserver_gc_")}
    assert run.read_metric(name, spec, ctx(bare, bare)) is None
