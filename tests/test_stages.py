"""The stage timer of the served path (observability.SpanTracer.stage):
one timing site per boundary, feeding the /metrics histograms, the
profiler's trace and, where a harness attaches one, the flight recorder.
And the per-layer metrics of benchmarks/metrics/ that read them."""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from kubernetes_tpu import api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.observability import FlightRecorder, SpanTracer
from kubernetes_tpu.observability import tracer as tracer_mod
from kubernetes_tpu.scheduler.metrics import (STAGE_LEAVES, STAGE_PARENTS,
                                              SchedulerMetrics)
from kubernetes_tpu.state import Client
from kubernetes_tpu.utils.clock import FakeClock
from kubernetes_tpu.utils.metrics import Histogram, StoreMetrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

from harness.children import parse_metrics  # noqa: E402  (the benchmark's own parser)


def make_node(name, cpu="4", mem="8Gi"):
    alloc = {"cpu": Quantity(cpu), "memory": Quantity(mem),
             "pods": Quantity("110")}
    return api.Node(
        metadata=api.ObjectMeta(name=name),
        status=api.NodeStatus(capacity=dict(alloc), allocatable=dict(alloc),
                              conditions=[api.NodeCondition(
                                  type="Ready", status="True")]))


def make_pod(name):
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace="default"),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="pause", resources=api.ResourceRequirements(
                requests={"cpu": Quantity("100m"),
                          "memory": Quantity("64Mi")}))]))


def wait_pending(sched, n):
    """Creations reach the queue through the informer's thread."""
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and sched.queue.num_pending() < n:
        time.sleep(0.01)
    assert sched.queue.num_pending() == n


def cluster(n_nodes=3, n_pods=12, **sched_kwargs):
    """An in-process store with nodes and pending pods, and a scheduler
    whose informers have delivered them all."""
    from kubernetes_tpu.scheduler import Scheduler
    client = Client()
    for i in range(n_nodes):
        client.nodes().create(make_node(f"n{i}"))
    sched = Scheduler(client, batch_size=64, **sched_kwargs)
    sched.informers.start()
    sched.informers.wait_for_cache_sync()
    for i in range(n_pods):
        client.pods("default").create(make_pod(f"p{i}"))
    wait_pending(sched, n_pods)
    return client, sched


# ------------------------------------------------------------ (a) helper


class TestStageHelper:
    def _run(self, enabled=True):
        clock = FakeClock(start=50.0)
        rec = FlightRecorder()
        tr = SpanTracer(clock=clock, recorder=rec, pod_sample=1,
                        enabled=enabled)
        hist = Histogram("t_seconds", buckets=(1.0, 4.0))
        with tr.stage("tensorize", hist, labels={"operation": "tensorize"},
                      trace="sched.tensorize", pods=3) as st:
            clock.step(2.5)
            st.attrs["late"] = True
        return tr, hist, st

    def test_one_stage_one_observation_one_span(self):
        tr, hist, st = self._run()
        assert hist.count(operation="tensorize") == 1
        assert hist.sum(operation="tensorize") == 2.5
        assert (st.start, st.seconds) == (50.0, 2.5)
        (span,) = tr.recorder.spans()
        assert (span.component, span.name, span.trace_id) == \
            ("scheduler", "tensorize", "")
        assert (span.start, span.end) == (50.0, 52.5)
        assert span.attrs == {"pods": 3, "late": True}

    def test_same_run_twice_gives_identical_span_logs(self):
        a = self._run()[0].recorder.export_jsonl()
        b = self._run()[0].recorder.export_jsonl()
        assert a and a == b

    def test_ring_false_keeps_the_histogram_only(self):
        tr = SpanTracer(clock=FakeClock(), pod_sample=1)
        hist = Histogram("t_seconds")
        with tr.stage("pop_wait", hist, ring=False):
            pass
        assert hist.count() == 1 and len(tr.recorder) == 0

    def test_disabled_tracer_still_times_but_rings_and_hashes_nothing(
            self, monkeypatch):
        calls = []
        monkeypatch.setattr(tracer_mod.zlib, "crc32",
                            lambda data: calls.append(data) or 0)
        tr, hist, _ = self._run(enabled=False)
        tr.pod_event("scheduler", "bound", make_pod("p"))
        assert hist.count(operation="tensorize") == 1
        assert len(tr.recorder) == 0 and calls == []

    def test_stage_without_histogram_or_jax_name(self):
        # a bare algorithm: no metrics object, nothing to observe into
        with tracer_mod.NULL_TRACER.stage("refresh") as st:
            pass
        assert st.seconds >= 0.0

    def test_histogram_declare_renders_zero_and_survives_clear(self):
        hist = Histogram("t_seconds", buckets=(1.0,))
        hist.declare(operation="x")
        assert 't_seconds_sum{operation="x"} 0.0' in hist.expose()
        hist.observe(0.5, operation="x")
        hist.observe(0.5, operation="y")
        hist.clear()
        text = "\n".join(hist.expose())
        assert 't_seconds_count{operation="x"} 0' in text
        assert 'operation="y"' not in text

    def test_served_scheduler_runs_with_the_recorder_off(self, monkeypatch):
        """config.build_scheduler (what kube_scheduler.main calls) hands
        the scheduler a disabled tracer: a cycle observes its stages and
        makes no pod_event crc32 call."""
        from kubernetes_tpu.scheduler.config import (
            KubeSchedulerConfiguration, build_scheduler)
        client = Client()
        client.nodes().create(make_node("n0"))
        sched = build_scheduler(client, KubeSchedulerConfiguration())
        assert not sched.tracer.enabled
        calls = []
        monkeypatch.setattr(tracer_mod.zlib, "crc32",
                            lambda data: calls.append(data) or 0)
        sched.informers.start()
        sched.informers.wait_for_cache_sync()
        try:
            for i in range(5):
                client.pods("default").create(make_pod(f"p{i}"))
            wait_pending(sched, 5)
            assert len(sched.schedule_pending(timeout=1.0)) == 5
        finally:
            sched.informers.stop()
        assert calls == [] and len(sched.tracer.recorder) == 0
        assert sched.metrics.scheduling_duration.count(
            operation="tensorize") == 1


# ------------------------------------------------- (b) the served cycle


class TestServedCycleStages:
    def test_stages_of_schedule_pending(self):
        declared = SchedulerMetrics().registry.expose()
        for op in STAGE_PARENTS + STAGE_LEAVES:
            assert ("scheduler_scheduling_duration_seconds_count"
                    f'{{operation="{op}"}} 0') in declared, op
        assert "scheduler_binding_duration_seconds_count 0" in declared
        assert "scheduler_queue_popped_pods_total 0.0" in declared
        assert "scheduler_queue_wait_seconds_total 0.0" in declared

        client, sched = cluster(n_pods=12)
        try:
            before = parse_metrics(sched.metrics.registry.expose())
            assert before['informer_deliver_seconds_count'
                          '{resource="pods"}'] > 0
            results = sched.schedule_pending(timeout=1.0)
        finally:
            sched.informers.stop()
        assert len(results) == 12
        m = sched.metrics
        d = m.scheduling_duration
        leaves = ("refresh", "tensorize", "dispatch", "scan_wait", "repair",
                  "assume")
        for op in ("pop_wait", "algorithm", "commit") + leaves:
            assert d.count(operation=op) == 1, op
        # an in-process client binds synchronously, inside commit
        assert m.binding_duration.count() == 1
        assert d.count(operation="bind_backlog") == 0
        e2e = m.e2e_scheduling_duration.sum()
        inside = sum(d.sum(operation=op) for op in leaves) \
            + m.binding_duration.sum()
        assert 0.0 < inside <= e2e
        assert e2e == pytest.approx(d.sum(operation="algorithm")
                                    + d.sum(operation="commit"))
        assert m.queue_popped_pods.value() == 12
        assert m.queue_wait_seconds.value() > 0.0
        assert m.schedule_attempts.value(result="scheduled") == 12

    def test_same_stage_names_in_the_pipelined_drain(self):
        client, sched = cluster(n_pods=12)
        try:
            assert sched.drain_pipelined() == 12
        finally:
            sched.informers.stop()
        d = sched.metrics.scheduling_duration
        for op in ("pop_wait", "launch", "fetch", "commit", "refresh",
                   "tensorize", "dispatch", "scan_wait", "repair", "assume"):
            assert d.count(operation=op) >= 1, op
        assert d.count(operation="algorithm") == 0
        assert sched.metrics.queue_popped_pods.value() == 12

    def test_async_bind_times_the_backlog_on_the_scheduling_thread(self):
        """Over HTTP the bind runs on a binder thread; the scheduling
        thread's own wait for the hub is bind_backlog, entered only when
        max_inflight_binds transactions are already in flight."""
        client, sched = cluster(n_pods=12, async_bind=True,
                                max_inflight_binds=1)
        release = threading.Event()
        inner = sched._bind_items_inner

        def slow(items, backoff):
            release.wait(10)
            return inner(items, backoff)
        sched._bind_items_inner = slow
        stage = sched._stage

        def at_the_backlog(name, **kwargs):
            # the held bind is let go by the scheduling thread itself, as
            # it arrives at the backlog: however long the cycle before
            # took (a first compile), the bind is still in flight then
            if name == "bind_backlog":
                release.set()
            return stage(name, **kwargs)
        sched._stage = at_the_backlog
        try:
            assert len(sched.schedule_pending(max_pods=6, timeout=1.0)) == 6
            d = sched.metrics.scheduling_duration
            assert d.count(operation="bind_backlog") == 0
            assert not release.is_set()
            # the second cycle decides its batch, then finds the first
            # bind still in flight and waits for it
            assert len(sched.schedule_pending(max_pods=6, timeout=1.0)) == 6
            assert d.count(operation="bind_backlog") == 1
            assert d.sum(operation="bind_backlog") > 0.0
            assert d.count(operation="assume") == 2
            sched._flush_binds()
            assert sched.metrics.binding_duration.count() == 2
        finally:
            release.set()
            sched.stop()


# ------------------------------------- (c) on the profiler's own clock


class TestStagesOnTheTrace:
    def test_a_cycle_leaves_its_stages_on_the_host_plane(self):
        """Under a profiler session started as the benchmark's launcher
        starts it (host_tracer_level 1, no python tracer), one cycle
        leaves its leaf stages on /host:CPU, between two marks written
        the way the launcher writes its own."""
        import jax
        from jax.profiler import ProfileData
        from harness import sched_entry
        client, sched = cluster(n_pods=8)
        session = sched_entry._start_trace()
        try:
            with jax.profiler.TraceAnnotation(sched_entry.MARK):
                pass
            assert len(sched.schedule_pending(timeout=1.0)) == 8
            # one more delivery by the informer's thread, seen to its end
            client.pods("default").create(make_pod("late"))
            wait_pending(sched, 1)
            with jax.profiler.TraceAnnotation(sched_entry.MARK):
                pass
        finally:
            data = session.stop()
            sched.informers.stop()
        events = {}
        for plane in ProfileData.from_serialized_xspace(data).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
        marks = sorted(events[sched_entry.MARK])
        assert len(marks) == 2
        lo, hi = marks[0][1], marks[1][0]
        for op in ("pop_wait", "refresh", "tensorize", "dispatch",
                   "scan_wait", "repair", "bind_txn", "assume"):
            spans = events.get("sched." + op)
            assert spans, (op, sorted(events))
            for s, e in spans:
                assert lo <= s <= e <= hi, op
        # a parent has no annotation: it would cover the host time that
        # its leaves leave unexplained
        assert "sched.algorithm" not in events
        assert "sched.commit" not in events
        assert "informer.deliver" in events

    def test_the_hub_serves_create_and_bind_without_jax(self, tmp_path):
        """kube_apiserver.main itself, with the store's stages on its
        /metrics: one bulk create, one bulk bind (one pod of them
        missing), and JAX was never imported."""
        script = textwrap.dedent("""
            import json, os, signal, sys, threading, time, urllib.request
            from kubernetes_tpu.cmd import kube_apiserver
            port = int(sys.argv[1])
            base = f"http://127.0.0.1:{port}"
            out = {}

            def post(path, body):
                req = urllib.request.Request(
                    base + path, data=json.dumps(body).encode(),
                    method="POST",
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=30) as r:
                    return json.loads(r.read())

            def drive():
                try:
                    for _ in range(200):
                        try:
                            urllib.request.urlopen(base + "/healthz",
                                                   timeout=1)
                            break
                        except OSError:
                            time.sleep(0.05)
                    post("/api/v1/nodes", {
                        "apiVersion": "v1", "kind": "Node",
                        "metadata": {"name": "n0"}})
                    pods = [{"apiVersion": "v1", "kind": "Pod",
                             "metadata": {"name": f"p{i}",
                                          "namespace": "default"},
                             "spec": {"containers": [
                                 {"name": "c", "image": "i"}]}}
                            for i in range(3)]
                    post("/api/v1/namespaces/default/pods",
                         {"apiVersion": "v1", "kind": "List",
                          "items": pods})
                    out["bind"] = post(
                        "/api/v1/namespaces/default/bindings",
                        {"kind": "BindList", "items": [
                            ["p0", "n0"], ["p1", "n0"], ["ghost", "n0"]]})
                    with urllib.request.urlopen(base + "/metrics",
                                                timeout=30) as r:
                        out["metrics"] = r.read().decode()
                except BaseException as e:
                    out["error"] = repr(e)
                finally:
                    os.kill(os.getpid(), signal.SIGTERM)

            threading.Thread(target=drive, daemon=True).start()
            rc = kube_apiserver.main(["--port", str(port),
                                      "--data-dir", sys.argv[2]])
            out["rc"] = rc
            out["jax"] = sorted(m for m in sys.modules
                                if m == "jax" or m.startswith("jax."))
            print("RESULT " + json.dumps(out))
        """)
        from harness.children import free_port
        env = dict(os.environ, PYTHONPATH=REPO)
        proc = subprocess.run(
            [sys.executable, "-c", script, str(free_port()),
             str(tmp_path / "hub")],
            capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
        line = next((ln for ln in proc.stdout.splitlines()
                     if ln.startswith("RESULT ")), None)
        assert line is not None, proc.stderr[-3000:]
        out = json.loads(line[len("RESULT "):])
        assert "error" not in out, out.get("error")
        assert out["rc"] == 0 and out["jax"] == []
        statuses = [it["status"] for it in out["bind"]["items"]]
        assert statuses == ["Success", "Success", "Failure"]
        scraped = parse_metrics(out["metrics"])
        assert scraped["apiserver_pods_bound_total"] == 2
        # node create is a single write; the bulk create and the bulk
        # bind each took the lock once
        assert scraped["store_lock_wait_seconds_count"] == 2
        assert scraped["store_compaction_seconds_count"] == 0
        assert "wal_append_errors_total" in scraped


# ------------------------------------------------------- (d) the store


class TestStoreStages:
    def _store(self, tmp_path):
        from kubernetes_tpu.state.store import Store
        return Store(wal_path=str(tmp_path / "store.wal"),
                     metrics=StoreMetrics())

    def test_lock_wait_once_per_outermost_bulk_write(self, tmp_path):
        store = self._store(tmp_path)
        m = store.metrics
        try:
            client = Client(store)
            client.nodes().create(make_node("n0"))       # a single write
            assert m.store_lock_wait.count() == 0
            client.pods("default").create_bulk(
                [make_pod(f"p{i}") for i in range(4)])
            assert m.store_lock_wait.count() == 1
            outs = client.pods("default").bind_bulk_pairs(
                "default", [("p0", "n0"), ("p1", "n0")])
            assert not any(isinstance(o, Exception) for o in outs)
            assert m.store_lock_wait.count() == 2
            with store._lock:       # re-entrant: this thread waits for nothing
                client.pods("default").bind_bulk_pairs(
                    "default", [("p2", "n0")])
            assert m.store_lock_wait.count() == 2
        finally:
            store.close()

    def test_compaction_counts_and_says_so(self, tmp_path, capfd):
        store = self._store(tmp_path)
        m = store.metrics
        try:
            client = Client(store)
            client.pods("default").create_bulk(
                [make_pod(f"p{i}") for i in range(4)])
            locks = m.store_lock_wait.count()
            for n in (1, 2):
                store.compact()
                assert m.store_compaction.count() == n
                assert m.store_lock_wait.count() == locks + n
            err = capfd.readouterr().err
            assert err.count("store: compacted the WAL in ") == 2
            assert "4 live objects" in err
            assert client.pods("default").get("p3") is not None
        finally:
            store.close()

    def test_a_store_without_metrics_times_nothing(self, tmp_path):
        from kubernetes_tpu.state.store import Store
        store = Store(wal_path=str(tmp_path / "store.wal"))
        try:
            Client(store).pods("default").create_bulk([make_pod("p0")])
            store.compact()
        finally:
            store.close()

    def test_pods_bound_counts_what_a_bulk_bind_bound(self):
        from kubernetes_tpu.apiserver.httpclient import HTTPClient
        from kubernetes_tpu.apiserver.server import APIServer
        srv = APIServer(port=0)
        srv.start()
        try:
            http = HTTPClient(srv.address)
            http.nodes().create(make_node("n0"))
            http.pods("default").create_bulk(
                [make_pod(f"p{i}") for i in range(3)])
            bound = srv.request_metrics.pods_bound
            assert bound.value() == 0
            outs = http.pods("default").bind_bulk_pairs(
                "default", [("p0", "n0"), ("p1", "n0")])
            assert not any(isinstance(o, Exception) for o in outs)
            assert bound.value() == 2
            outs = http.pods("default").bind_bulk_pairs(
                "default", [("ghost", "n0"), ("ghost2", "n0")])
            assert all(isinstance(o, Exception) for o in outs)
            assert bound.value() == 2       # a failed bind adds 0
        finally:
            srv.stop()


# ------------------------------- (e) the metrics that read the stages

NEW_RATIOS = [
    "sched_pop_wait_ms_per_pod", "sched_refresh_ms_per_pod",
    "sched_tensorize_ms_per_pod", "sched_dispatch_ms_per_pod",
    "sched_scan_wait_ms_per_pod", "sched_repair_ms_per_pod",
    "sched_bind_wait_ms_per_pod", "sched_bind_backlog_ms_per_pod",
    "sched_assume_ms_per_pod", "sched_queue_wait_ms_per_pod",
    "informer_deliver_ms_per_pod", "hub_create_ms_per_pod",
    "hub_bind_ms_per_pod", "hub_watch_encode_ms_per_pod",
    "hub_lock_wait_ms_per_pod", "hub_compaction_ms_per_pod",
    "sched_pods_per_cycle",
    # PR 30: the parts of refresh, tensorize and dispatch that the
    # inter-pod affinity machinery takes, and what sizes them
    "sched_topology_apply_ms_per_pod", "sched_affinity_masks_ms_per_pod",
    "sched_affinity_scores_ms_per_pod", "sched_templates_per_cycle",
    "sched_inscan_fallback_share",
    # PR 32: what a launch costs in host-to-device transfers
    "sched_h2d_transfers_per_cycle",
    # PR 34: rows of cached node vectors a cycle recomputes
    "sched_node_rows_recomputed_per_cycle",
    # PR 35: the static masks a batch builds and the classes it scans
    "sched_static_masks_ms_per_pod", "sched_static_masks_per_cycle",
    "sched_scan_classes_per_cycle"]
NEW_READERS = ["idle_waiting_for_pods_share", "idle_waiting_for_hub_share",
               "idle_unattributed_share"]


@pytest.fixture(scope="module")
def bench_run():
    """benchmarks/run.py as a module (its read_metric is the one reader
    of every kind of data file)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "ktpu_bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def fresh_scrapes():
    """What each process's /metrics holds before any traffic: a freshly
    built scheduler's registry, and a hub's with the store's mounted as
    cmd/kube_apiserver mounts it."""
    from kubernetes_tpu.apiserver.server import APIServer
    from kubernetes_tpu.scheduler import Scheduler
    sched = Scheduler(Client())
    srv = APIServer(port=0)
    srv.metrics.add_registry("store", StoreMetrics().registry)
    return {"kube_scheduler": parse_metrics(sched.metrics.registry.expose()),
            "kube_apiserver": parse_metrics(srv.metrics.expose())}


def _declared(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    cells = [w["name"] for w in bench["workloads"]]
    # an entry without the key holds in every cell; one with it names
    # cells that exist
    assert "workloads" not in entry or \
        set(entry["workloads"]) <= set(cells)
    assert entry["moves"] in [m["name"] for m in bench["end_to_end"]]
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    assert (spec["layer"], spec["moves"], spec["unit"]) == \
        (entry["layer"], entry["moves"], entry["unit"])
    return entry, spec


@pytest.mark.parametrize("name", NEW_RATIOS)
def test_ratio_metric_reads_series_that_exist_from_the_start(
        name, bench_run, fresh_scrapes):
    entry, spec = _declared(name)
    assert spec["kind"] == "scrape_ratio"
    scrape = fresh_scrapes[spec["process"]]
    assert scrape.get(spec["numerator"]) == 0.0, spec["numerator"]
    assert scrape.get(spec["denominator"]) == 0.0, spec["denominator"]
    later = {k: v + 1.0 for k, v in scrape.items()}
    later[spec["numerator"]] = 0.5
    later[spec["denominator"]] = 4.0
    ctx = {"probe0": {"scrape": fresh_scrapes},
           "probe1": {"scrape": {spec["process"]: later}}}
    assert bench_run.read_metric(name, spec, ctx) == \
        spec["scale"] * (0.5 - 0.0) / (4.0 - 0.0)
    # a program without the series (the parent commit): nothing, no raise
    bare = {"probe0": {"scrape": {spec["process"]: {}}},
            "probe1": {"scrape": {spec["process"]: {}}}}
    assert bench_run.read_metric(name, spec, bare) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_idle_share_reader(name, bench_run, monkeypatch):
    entry, spec = _declared(name)
    assert spec["kind"] == "reader" and entry["source"] == "device_trace"
    gaps = [["host outside the runtime's spans", 1.5],
            ["sched.pop_wait", 6.0], ["sched.bind_txn", 3.0],
            ["shard_args", 0.3]]
    ctx = {"trace": {"window_s": 12.0, "idle_gaps": gaps}}
    expected = {"idle_waiting_for_pods_share": 50.0,
                "idle_waiting_for_hub_share": 25.0,
                "idle_unattributed_share": 12.5}[name]
    assert bench_run.read_metric(name, spec, ctx) == expected
    # under the ten largest the harness keeps: the trace is reduced once
    # more with every gap listed, and once for all three readers
    calls = []

    def reduce(path, top=10, mark=None):
        calls.append((path, top, mark))
        return {"idle_gaps": every}

    monkeypatch.setattr(bench_run.trace_reduce, "reduce", reduce)
    every = gaps
    ctx = {"trace": {"window_s": 12.0, "xplane": "t.xplane.pb",
                     "idle_gaps": [["shard_args", 0.3]]}}
    assert bench_run.read_metric(name, spec, ctx) == expected
    assert bench_run.read_metric(name, spec, ctx) == expected
    assert calls == [("t.xplane.pb", None, bench_run.MARK)]
    # a program without the stages (the parent commit): nothing, no raise
    every = [["host outside the runtime's spans", 1.5], ["shard_args", 0.3]]
    del ctx["trace"]["every_idle_gap"]
    assert bench_run.read_metric(name, spec, ctx) == \
        (12.5 if name == "idle_unattributed_share" else None)
    assert bench_run.read_metric(name, spec, {}) is None    # untraced
    assert bench_run.read_metric(name, spec, {"trace": None}) is None


def test_every_new_metric_file_is_listed():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        declared = {m["name"] for m in json.load(f)["per_layer"]}
    files = {f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics"))
             if f.endswith(".json")}
    assert set(NEW_RATIOS + NEW_READERS) <= declared & files
    for name in NEW_READERS:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
