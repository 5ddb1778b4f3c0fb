"""The served scheduler's collector policy (utils/gcpolicy.py, installed by
cmd/kube_scheduler.py `main`): every generation-2 collection freezes its
survivors, so a full collection walks what is new since the last one and
not every pod of the informer store and the cache. In-process schedulers
keep the interpreter's collector. Every test here puts the process's
collector back as it found it (frozen set, callbacks, thresholds): xdist
runs many files in one worker."""

import gc
import json
import os
import sys
import urllib.request

import pytest

from kubernetes_tpu.apiserver import APIServer
from kubernetes_tpu.cmd import kube_scheduler
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.scheduler.config import (KubeSchedulerConfiguration,
                                             build_scheduler)
from kubernetes_tpu.scheduler.metrics import SchedulerMetrics
from kubernetes_tpu.scheduler.tensorize import precompute_pod_features
from kubernetes_tpu.state import Client
from kubernetes_tpu.utils import gcpolicy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from fakecluster import make_node, make_pod  # noqa: E402
from harness.children import free_port, parse_metrics  # noqa: E402  (the benchmark's own parser)

SERIES = ("scheduler_gc_freezes_total", "scheduler_gc_frozen_objects") + tuple(
    f'scheduler_gc_{family}{{generation="{g}"}}'
    for family in ("collections_total", "pause_seconds_total")
    for g in gcpolicy.GENERATIONS)
SCHEDULED = 'scheduler_schedule_attempts_total{result="scheduled"}'


@pytest.fixture
def restore_collector():
    """Afterwards unfreeze and restore the callbacks and thresholds
    whatever the test did. What the worker holds already is frozen first,
    so that an install's collection walks a heap of a scheduler's start-up
    size whatever other files this worker ran."""
    callbacks = list(gc.callbacks)
    thresholds = gc.get_threshold()
    gc.collect()
    gc.freeze()
    try:
        yield
    finally:
        gc.callbacks[:] = callbacks
        gc.set_threshold(*thresholds)
        gc.unfreeze()


@pytest.fixture
def collector(restore_collector):
    """The policy installed as the served scheduler installs it, on a
    scheduler's registry."""
    return gcpolicy.install(prefix="scheduler", process="scheduler",
                            registry=SchedulerMetrics().registry)


def _policies(callbacks):
    """The collector policies among `gc.callbacks` (JAX's backend adds a
    callback of its own when it starts)."""
    return [cb.__self__ for cb in callbacks
            if isinstance(getattr(cb, "__self__", None),
                          gcpolicy.CollectorPolicy)]


@pytest.fixture
def hub():
    srv = APIServer().start()
    yield srv
    srv.stop()


def _served_main(monkeypatch, master, port):
    """kube_scheduler.main as the process runs it, up to the point where
    it starts the scheduler: what the collector and the scheduler's
    /metrics look like then. The start stops the process at once through
    the scheduler's own fatal hook, so main returns."""
    seen = {}
    built = build_scheduler

    def build(client, cfg):
        sched = built(client, cfg)

        def start():
            seen["callbacks"] = list(gc.callbacks)
            seen["frozen"] = gc.get_freeze_count()
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
                seen["text"] = r.read().decode()
            sched.on_fatal()
        sched.start = start
        seen["sched"] = sched
        return sched
    import kubernetes_tpu.scheduler as scheduler_pkg
    monkeypatch.setattr(kube_scheduler, "build_scheduler", build)
    # the process's own settings stay out of this test process
    monkeypatch.setattr(kube_scheduler.signal, "signal", lambda *_: None)
    monkeypatch.setattr(scheduler_pkg, "enable_compile_cache", lambda: None)
    assert kube_scheduler.main(["--master", master,
                                "--healthz-port", str(port)]) == 0
    return seen


def test_main_installs_exactly_one_callback_before_the_start(
        restore_collector, hub, monkeypatch):
    assert _policies(gc.callbacks) == []
    seen = _served_main(monkeypatch, hub.address, free_port())
    policies = _policies(seen["callbacks"])
    assert len(policies) == 1
    policy = policies[0]
    # install's own collection froze what the built scheduler holds
    assert policy.freezes >= 1 and seen["frozen"] > 0


def test_the_series_are_declared_from_install_and_on_metrics(
        restore_collector, hub, monkeypatch):
    seen = _served_main(monkeypatch, hub.address, free_port())
    scrape = parse_metrics(seen["text"])
    assert set(SERIES) <= set(scrape)
    policy, = _policies(seen["callbacks"])
    for g in gcpolicy.GENERATIONS:
        assert scrape[f'scheduler_gc_collections_total{{generation="{g}"}}'] \
            >= 0.0
    assert scrape['scheduler_gc_collections_total{generation="2"}'] == \
        scrape["scheduler_gc_freezes_total"] >= 1
    assert scrape["scheduler_gc_frozen_objects"] > 0
    assert policy.freezes >= scrape["scheduler_gc_freezes_total"]
    # the hub's names are not on the scheduler, nor the scheduler's on
    # the hub's own families
    assert not any(k.startswith("apiserver_gc_") for k in scrape)
    # the series are the scheduler's registry's, beside its own families
    text = seen["sched"].metrics.registry.expose()
    assert "scheduler_gc_freezes_total" in text and SCHEDULED in text


def test_every_generation_reads_zero_before_the_first_collection(
        restore_collector):
    policy = gcpolicy.CollectorPolicy()
    metrics = gcpolicy.CollectorMetrics(
        policy, SchedulerMetrics().registry, "scheduler", "scheduler")
    scrape = parse_metrics(metrics.registry.expose())
    for family in ("collections_total", "pause_seconds_total"):
        for g in gcpolicy.GENERATIONS:
            assert scrape[f'scheduler_gc_{family}{{generation="{g}"}}'] == 0
    assert scrape["scheduler_gc_freezes_total"] == 0


def test_a_forced_full_collection_counts_one_freeze_and_grows_the_frozen_set(
        collector):
    policy = collector.policy
    assert policy.freezes == 1 and policy.collections[2] == 1
    kept = [{"pod": [i]} for i in range(1000)]
    before = gc.get_freeze_count()
    gc.collect()
    assert policy.freezes == 2 and policy.collections[2] == 2
    assert gc.get_freeze_count() >= before + len(kept)
    scrape = parse_metrics(collector.registry.expose())
    assert scrape["scheduler_gc_freezes_total"] == 2
    assert scrape["scheduler_gc_frozen_objects"] == gc.get_freeze_count()
    assert scrape['scheduler_gc_pause_seconds_total{generation="2"}'] > 0.0


def test_the_hubs_series_keep_their_names_and_help(restore_collector):
    text = gcpolicy.install().registry.expose()
    for line in (
            "# HELP apiserver_gc_collections_total Garbage collections of "
            "the hub process, by generation",
            "# HELP apiserver_gc_pause_seconds_total Seconds the hub "
            "process spent in garbage collections, by generation",
            "# HELP apiserver_gc_freezes_total Generation-2 collections "
            "whose survivors were frozen",
            "# HELP apiserver_gc_frozen_objects Objects in the collector's "
            "permanent generation"):
        assert line in text.splitlines()
    assert "scheduler_gc_" not in text


def test_an_in_process_scheduler_keeps_the_interpreters_collector():
    frozen, callbacks = gc.get_freeze_count(), list(gc.callbacks)
    cfg = KubeSchedulerConfiguration()
    for sched in (build_scheduler(Client(), cfg), Scheduler(Client())):
        sched.stop()
    assert gc.get_freeze_count() == frozen
    assert gc.callbacks == callbacks
    assert _policies(gc.callbacks) == []


def _schedule(n_nodes, n_pods, batch, collect):
    """Decide n_pods pods on n_nodes nodes in pops of `batch` with a
    fresh in-process scheduler; `collect` forces a full collection after
    every cycle. Returns ({pod: node}, the scheduler)."""
    client = Client(validate=False)
    sched = Scheduler(client, batch_size=batch)
    for i in range(n_nodes):
        node = client.nodes().create(make_node(i))
        sched.cache.add_node(node)
    for i in range(n_pods):
        pod = client.pods().create(make_pod(i))
        precompute_pod_features(pod)
        sched.queue.add(pod)
    placed = {}
    while True:
        results = sched.schedule_pending(max_pods=batch)
        if not results:
            break
        placed.update((r.pod.metadata.name, r.node_name) for r in results)
        if collect:
            gc.collect()
    return placed, sched


def test_the_policy_changes_no_decision(restore_collector):
    plain, sched = _schedule(24, 200, 32, collect=True)
    sched.stop()
    metrics = gcpolicy.install(prefix="scheduler", process="scheduler",
                               registry=SchedulerMetrics().registry)
    frozen, sched = _schedule(24, 200, 32, collect=True)
    sched.stop()
    assert metrics.policy.freezes >= 7
    assert len(plain) == 200 and all(plain.values())
    assert frozen == plain


def _garbage_left(n_pods):
    """Cyclic garbage that the frozen set hid after a schedule of n_pods
    pods with a full collection after every cycle, the scheduler alive.
    What an earlier scheduler left is collected first: its death after
    the reading's own collection froze it is not this schedule's."""
    gc.unfreeze()
    gc.collect()
    placed, sched = _schedule(16, n_pods, 16, collect=True)
    assert all(placed.values())
    gc.unfreeze()
    freed = gc.collect()
    sched.stop()
    return freed


def test_freezing_leaks_nothing_that_grows_with_the_pods(collector):
    _schedule(16, 16, 16, collect=True)  # first-use caches of the cycle
    small = _garbage_left(48)
    large = _garbage_left(192)
    assert small < 1000 and large < small + 200, (small, large)


def test_the_benchmark_reads_the_scheduler_pause_per_pod():
    name = "sched_gc_full_pause_ms_per_pod"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == name)
    assert "workloads" not in entry  # every cell reports it
    assert (entry["source"], entry["better"]) == ("program_span", "lower")
    with open(os.path.join(REPO, "benchmarks", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert (spec["layer"], spec["moves"], spec["unit"]) == \
        (entry["layer"], entry["moves"], entry["unit"]) == \
        ("scheduler host", "pods_bound_per_s", "ms/pod")
    assert spec["process"] == "kube_scheduler"
    import importlib.util
    spec_ = importlib.util.spec_from_file_location(
        "ktpu_bench_run_sched_gc", os.path.join(REPO, "benchmarks", "run.py"))
    run = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(run)
    pause = 'scheduler_gc_pause_seconds_total{generation="2"}'
    before = {pause: 0.25, SCHEDULED: 1000.0}
    after = {pause: 1.75, SCHEDULED: 31000.0}

    def ctx(b, a):
        return {"probe0": {"scrape": {"kube_scheduler": b}},
                "probe1": {"scrape": {"kube_scheduler": a}}}
    assert run.read_metric(name, spec, ctx(before, after)) == \
        pytest.approx(1000.0 * 1.5 / 30000)
    # a scheduler without the series (the parent commit): nothing, no raise
    bare = {SCHEDULED: 31000.0}
    assert run.read_metric(name, spec, ctx({SCHEDULED: 1000.0}, bare)) \
        is None
