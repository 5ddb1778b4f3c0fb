"""Observability layer tests (ISSUE 11): span tracer + flight recorder,
the aggregated /metrics scrape surface, unschedulable attribution behind
/debug/pending, component health behind /readyz, and the trace
determinism contract.

Tier-1 acceptance covered here:
  - GET /metrics on a LIVE APIServer returns valid text exposition
    containing scheduler, informer, serving, and robustness families —
    and the scrape ROUND-TRIPS: parsed back into families/samples, every
    histogram's _sum/_count/+Inf invariants hold;
  - /debug/pending names a concrete reason for an intentionally
    unschedulable pod;
  - two same-seed FakeClock chaos runs yield byte-identical span logs,
    and a wall-clock run's spans are monotone;
  - the registry-completeness check: every *Metrics class registers into
    the MetricsRegistry without signature collisions.
"""

import inspect
import json
import time
import urllib.error
import urllib.request

import pytest

from kubernetes_tpu import api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.observability import (FlightRecorder, MetricsRegistry,
                                          SpanTracer, parse_exposition)
from kubernetes_tpu.state.client import Client
from kubernetes_tpu.state.store import Store
from kubernetes_tpu.utils import healthz as healthz_mod
from kubernetes_tpu.utils import metrics as metrics_mod
from kubernetes_tpu.utils.clock import FakeClock
from kubernetes_tpu.utils.metrics import Registry


def make_node(name, cpu="4", mem="32Gi"):
    alloc = {"cpu": Quantity(cpu), "memory": Quantity(mem),
             "pods": Quantity("110")}
    return api.Node(
        metadata=api.ObjectMeta(name=name),
        status=api.NodeStatus(capacity=dict(alloc),
                              allocatable=dict(alloc),
                              conditions=[api.NodeCondition(
                                  type="Ready", status="True")]))


def make_pod(name, cpu="100m", mem="128Mi"):
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace="default"),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="pause",
            resources=api.ResourceRequirements(
                requests={"cpu": Quantity(cpu),
                          "memory": Quantity(mem)}))]))


# ---------------------------------------------------------------- tracer


class TestSpanTracer:
    def test_spans_ride_the_injected_clock(self):
        clock = FakeClock(start=100.0)
        tr = SpanTracer(clock=clock, pod_sample=1)
        t0 = tr.now()
        clock.step(2.5)
        tr.record("sched", "batch", t0, tr.now(), pods=3)
        (span,) = tr.recorder.spans()
        assert span.start == 100.0 and span.end == 102.5
        assert span.duration == 2.5
        assert span.attrs == {"pods": 3}

    def test_pod_sampling_is_deterministic(self):
        tr = SpanTracer(clock=FakeClock(), pod_sample=4)
        picks = [tr.sampled(f"uid-{i:08x}") for i in range(256)]
        tr2 = SpanTracer(clock=FakeClock(), pod_sample=4)
        assert picks == [tr2.sampled(f"uid-{i:08x}") for i in range(256)]
        assert any(picks) and not all(picks)

    def test_ring_evicts_oldest_and_counts_drops(self):
        rec = FlightRecorder(capacity=4)
        tr = SpanTracer(clock=FakeClock(), recorder=rec, pod_sample=1)
        for i in range(7):
            tr.event("c", f"e{i}")
        spans = rec.spans(component="c")
        assert [s.name for s in spans] == ["e3", "e4", "e5", "e6"]
        assert rec.dropped["c"] == 3

    def test_export_is_canonical_jsonl(self):
        clock = FakeClock()
        tr = SpanTracer(clock=clock, pod_sample=1)
        tr.event("b", "later")
        tr.event("a", "earlier")
        out = tr.recorder.export_jsonl()
        lines = [json.loads(ln) for ln in out.strip().splitlines()]
        assert [d["component"] for d in lines] == ["a", "b"]
        # byte-stable: re-export is identical
        assert out == tr.recorder.export_jsonl()

    def test_disabled_tracer_records_nothing(self):
        tr = SpanTracer(clock=FakeClock(), pod_sample=1, enabled=False)
        tr.event("c", "e")
        tr.record("c", "s", 0.0, 1.0)
        assert len(tr.recorder) == 0


class TestTraceInjectableClock:
    def test_total_uses_clock_and_logs_via_logging(self, caplog):
        import logging
        from kubernetes_tpu.utils.trace import Trace
        clock = FakeClock()
        t = Trace("unit", clock=clock, pods=2)
        clock.step(0.05)
        t.step("phase one")
        assert abs(t.total_ms() - 50.0) < 1e-6
        assert t.log_if_long(100.0) is None  # below threshold: silent
        clock.step(0.2)
        with caplog.at_level(logging.WARNING, "kubernetes_tpu.trace"):
            text = t.log_if_long(100.0)
        assert text is not None and "phase one" in text
        assert any("phase one" in r.message for r in caplog.records)

    def test_nested_inherits_clock(self):
        from kubernetes_tpu.utils.trace import Trace
        clock = FakeClock()
        t = Trace("outer", clock=clock)
        n = t.nest("inner")
        assert n.clock is clock


# ------------------------------------------------------- metrics registry


class TestMetricsRegistry:
    def test_collision_different_help_raises(self):
        a, b = Registry(), Registry()
        a.counter("x_total", "one thing")
        b.counter("x_total", "another thing")
        mr = MetricsRegistry()
        mr.add_registry("a", a)
        with pytest.raises(ValueError, match="collision"):
            mr.add_registry("b", b)

    def test_collision_different_buckets_raises(self):
        a, b = Registry(), Registry()
        a.histogram("h_seconds", "h", buckets=(1.0, 2.0))
        b.histogram("h_seconds", "h", buckets=(1.0, 2.0, 4.0))
        mr = MetricsRegistry()
        mr.add_registry("a", a)
        with pytest.raises(ValueError, match="collision"):
            mr.add_registry("b", b)

    def test_same_signature_merges_label_wise(self):
        from kubernetes_tpu.utils.metrics import RobustnessMetrics
        m1, m2 = RobustnessMetrics(), RobustnessMetrics()
        m1.api_retries.inc(component="scheduler")
        m2.api_retries.inc(component="scheduler")
        m2.api_retries.inc(component="nodelifecycle")
        m1.wal_recovery_records_replayed.inc(5)
        mr = MetricsRegistry()
        mr.add_registry("sched", m1.registry)
        mr.add_registry("cm", m2.registry)
        text = mr.expose()
        # exactly ONE header per family, values summed per label set
        assert text.count("# TYPE api_request_retries_total counter") == 1
        assert 'api_request_retries_total{component="scheduler"} 2.0' \
            in text
        assert 'api_request_retries_total{component="nodelifecycle"} 1.0' \
            in text
        assert "wal_recovery_records_replayed_total 5.0" in text

    def test_histograms_merge(self):
        a, b = Registry(), Registry()
        ha = a.histogram("lat_seconds", "l", buckets=(1.0, 2.0))
        hb = b.histogram("lat_seconds", "l", buckets=(1.0, 2.0))
        ha.observe(0.5)
        hb.observe(1.5)
        hb.observe(9.0)
        mr = MetricsRegistry()
        mr.add_registry("a", a)
        mr.add_registry("b", b)
        fams = parse_exposition(mr.expose())
        samples = {(n, tuple(sorted(l.items()))): v
                   for n, l, v in fams["lat_seconds"]["samples"]}
        assert samples[("lat_seconds_bucket", (("le", "1.0"),))] == 1
        assert samples[("lat_seconds_bucket", (("le", "2.0"),))] == 2
        assert samples[("lat_seconds_bucket", (("le", "+Inf"),))] == 3
        assert samples[("lat_seconds_count", ())] == 3
        assert abs(samples[("lat_seconds_sum", ())] - 11.0) < 1e-9

    def test_registry_completeness(self):
        """CI check: every *Metrics class in utils.metrics (plus the
        scheduler's) registers into one MetricsRegistry with no
        signature collisions, and every family it declares reaches the
        exposition."""
        from kubernetes_tpu.autoscaler import AutoscalerMetrics
        from kubernetes_tpu.scheduler.metrics import SchedulerMetrics
        from kubernetes_tpu.tenancy import QuotaMetrics, TenancyMetrics
        classes = [obj for name, obj in
                   inspect.getmembers(metrics_mod, inspect.isclass)
                   if name.endswith("Metrics") and name != "_Metric"]
        assert len(classes) >= 5  # Gang/Informer/Robustness/Serving/APIServer
        mr = MetricsRegistry()
        declared = set()
        for cls in classes + [SchedulerMetrics, AutoscalerMetrics,
                              QuotaMetrics, TenancyMetrics]:
            inst = cls()
            mr.add_registry(cls.__name__, inst.registry)
            with inst.registry._lock:
                declared.update(inst.registry._metrics)
        assert mr.check_collisions() == []
        fams = parse_exposition(mr.expose())
        missing = declared - set(fams)
        assert not missing, f"families missing from exposition: {missing}"

    def test_reset_zeroes_every_component(self):
        a = Registry()
        c = a.counter("y_total", "y")
        c.inc(3)
        mr = MetricsRegistry()
        mr.add_registry("a", a)
        mr.reset()
        assert c.value() == 0.0
        assert "y_total 0.0" in mr.expose()


# ------------------------------------------------- live-server acceptance


class TestLiveScrapeSurface:
    def _cluster(self):
        """APIServer + scheduler over one store, observability attached
        the way a deployment wires it."""
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.utils.metrics import ServingMetrics
        store = Store()
        server = APIServer(store=store).start()
        client = Client(store)
        tracer = SpanTracer(pod_sample=1)
        sched = Scheduler(client, batch_size=8, tracer=tracer)
        server.metrics.add_registry("scheduler", sched.metrics.registry)
        server.metrics.add_registry("scheduler-informers",
                                    sched.informers.metrics.registry)
        serving = ServingMetrics()
        serving.pod_bind_seconds.observe(0.125, cls="deployment")
        server.metrics.add_registry("serving", serving.registry)
        server.flight = tracer.recorder
        server.pending_providers.append(sched.debugger.pending_report)
        server.health.add_all(
            healthz_mod.scheduler_contributors(sched))
        sched.informers.start()
        sched.informers.wait_for_cache_sync()
        return store, server, client, sched

    def _get(self, url):
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.read().decode()

    def test_metrics_debug_and_readyz(self):
        store, server, client, sched = self._cluster()
        try:
            client.nodes().create(make_node("n1", cpu="1"))
            client.pods("default").create(make_pod("fits", cpu="100m"))
            client.pods("default").create(
                make_pod("hog", cpu="100"))  # never fits: 100 CPUs
            deadline = time.time() + 30
            while time.time() < deadline:
                if sched.queue.num_pending() >= 2 and \
                        len(sched.cache.node_names()) >= 1:
                    break
                time.sleep(0.02)
            sched.schedule_pending(timeout=1.0)
            deadline = time.time() + 30
            while time.time() < deadline:
                if client.pods("default").get("fits").spec.node_name:
                    break
                sched.schedule_pending(timeout=0.2)

            # ---- GET /metrics: one exposition, all four family groups
            text = self._get(server.address + "/metrics")
            for family in ("scheduler_schedule_attempts_total",
                           "scheduler_unschedulable_reasons_total",
                           "informer_relists_total",
                           "serving_pod_bind_seconds_bucket",
                           "wal_recovery_records_replayed_total",
                           "apiserver_request_total"):
                assert family in text, f"{family} missing from scrape"

            # ---- scrape ROUND-TRIP: parse back, histogram invariants
            fams = parse_exposition(text)
            checked = 0
            for name, fam in fams.items():
                if fam["type"] != "histogram":
                    continue
                by_series = {}
                for sample_name, labels, value in fam["samples"]:
                    rest = tuple(sorted((k, v) for k, v in labels.items()
                                        if k != "le"))
                    d = by_series.setdefault(rest, {"buckets": [],
                                                    "sum": None,
                                                    "count": None})
                    if sample_name == f"{name}_bucket":
                        le = labels["le"]
                        d["buckets"].append(
                            (float("inf") if le == "+Inf" else float(le),
                             value))
                    elif sample_name == f"{name}_sum":
                        d["sum"] = value
                    elif sample_name == f"{name}_count":
                        d["count"] = value
                for rest, d in by_series.items():
                    assert d["sum"] is not None, (name, rest)
                    assert d["count"] is not None, (name, rest)
                    buckets = sorted(d["buckets"])
                    assert buckets, (name, rest)
                    counts = [c for _, c in buckets]
                    assert counts == sorted(counts), \
                        f"{name}{rest}: buckets not cumulative"
                    assert buckets[-1][0] == float("inf")
                    assert buckets[-1][1] == d["count"], \
                        f"{name}{rest}: +Inf != _count"
                    checked += 1
            assert checked > 5

            # ---- /debug/pending names the hog's concrete reason
            pending = json.loads(self._get(
                server.address + "/debug/pending"))
            pods = pending["pending"][0]["pods"]
            hog = next(p for p in pods if p["pod"] == "default/hog")
            assert "Insufficient cpu" in hog["reason"]
            assert "0/1 nodes are available" in hog["message"]
            assert hog["attempts"] >= 1
            # the reason tally rode /metrics too
            assert 'scheduler_unschedulable_reasons_total{' \
                   'reason="Insufficient cpu"}' in text

            # ---- /debug/traces serves the flight recorder
            traces = self._get(server.address + "/debug/traces")
            names = {json.loads(ln)["name"]
                     for ln in traces.strip().splitlines()}
            assert {"admit", "drain_member", "bound"} <= names

            # ---- /readyz reflects the scheduler contributors (all
            # healthy here; /healthz stays liveness-only)
            assert self._get(server.address + "/readyz") == "ok"

            # ---- DELETE /metrics resets values, families survive
            req = urllib.request.Request(server.address + "/metrics",
                                         method="DELETE")
            urllib.request.urlopen(req, timeout=10)
            text2 = self._get(server.address + "/metrics")
            assert "scheduler_schedule_attempts_total" in text2
            assert 'result="scheduled"} 1.0' not in text2
        finally:
            sched.informers.stop()
            server.stop()
            store.close()

    def test_secured_hub_gates_observability_endpoints(self):
        """On a hub with an authenticator, /metrics (incl. the mutating
        DELETE reset) and /debug/* require credentials; liveness stays
        open. An open hub keeps the insecure-port shape (tested above)."""
        from kubernetes_tpu.apiserver.auth import (TokenAuthenticator,
                                                   UserInfo)
        from kubernetes_tpu.apiserver.server import APIServer
        server = APIServer()
        server.authenticator = TokenAuthenticator({
            "ops-token": UserInfo("ops", ("system:masters",))})
        server.start()
        try:
            for path in ("/metrics", "/debug/traces", "/debug/pending"):
                with pytest.raises(urllib.error.HTTPError) as e:
                    self._get(server.address + path)
                assert e.value.code == 401, path
            req = urllib.request.Request(server.address + "/metrics",
                                         method="DELETE")
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=10)
            assert e.value.code == 401
            # credentialed caller gets the scrape; liveness needs none
            req = urllib.request.Request(
                server.address + "/metrics",
                headers={"Authorization": "Bearer ops-token"})
            with urllib.request.urlopen(req, timeout=10) as r:
                assert b"apiserver_request_total" in r.read()
            assert self._get(server.address + "/healthz") == "ok"
        finally:
            server.stop()

    def test_readyz_fails_on_stuck_component(self):
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.scheduler import Scheduler
        store = Store()
        server = APIServer(store=store).start()
        clock = FakeClock()
        sched = Scheduler(Client(store), batch_size=8, clock=clock)
        server.health.add_all(healthz_mod.scheduler_contributors(
            sched, stuck_after=60.0))
        try:
            assert self._get(server.address + "/readyz") == "ok"
            # a pod sits in the queue but no scheduling cycle ever runs:
            # after stuck_after of (virtual) silence readiness drops
            sched.queue.add(make_pod("waiting"))
            self._get(server.address + "/readyz")  # arms the progress probe
            clock.step(120.0)
            with pytest.raises(urllib.error.HTTPError) as e:
                self._get(server.address + "/readyz")
            assert e.value.code == 500
            assert b"queue-progress" in e.value.read()
            # a drain cycle (even an empty-handed one) restores readiness
            sched.queue.pop_batch(8, timeout=0)
            assert self._get(server.address + "/readyz") == "ok"
        finally:
            server.stop()
            store.close()


# -------------------------------------------- attribution + event wiring


class TestUnschedulableAttribution:
    def test_record_evicts_and_counts(self):
        from kubernetes_tpu.scheduler.debugger import \
            UnschedulableAttribution
        clock = FakeClock()
        attr = UnschedulableAttribution(clock=clock, max_records=2)
        attr.record("a", "Insufficient cpu", "msg", cycle=1)
        attr.record("a", "Insufficient cpu", "msg", cycle=2)
        assert attr.get("a")["count"] == 2
        attr.record("a", "Insufficient memory", "msg", cycle=3)
        assert attr.get("a")["count"] == 1  # reason changed: count resets
        attr.record("b", "r", "m")
        attr.record("c", "r", "m")
        assert attr.get("a") is None  # oldest evicted at the bound
        attr.discard("b")
        assert attr.get("b") is None

    def test_bound_pod_clears_attribution(self):
        from kubernetes_tpu.scheduler import Scheduler
        client = Client()
        client.nodes().create(make_node("n1"))
        sched = Scheduler(client, batch_size=8)
        sched.informers.start()
        sched.informers.wait_for_cache_sync()
        try:
            client.pods("default").create(make_pod("p1"))
            deadline = time.time() + 30
            while time.time() < deadline and sched.queue.num_pending() < 1:
                time.sleep(0.02)
            sched.attribution.record("default/p1", "Stale", "stale", 0)
            sched.schedule_pending(timeout=1.0)
            assert client.pods("default").get("p1").spec.node_name
            assert sched.attribution.get("default/p1") is None
        finally:
            sched.informers.stop()


class TestSLOStageBreakdown:
    def test_exact_stage_percentiles_from_spans(self):
        from kubernetes_tpu.serving.slo import SLOTracker

        class FakePod:
            class M:
                pass

            def __init__(self, uid):
                self.metadata = FakePod.M()
                self.metadata.uid = uid
                self.metadata.key = lambda u=uid: f"default/{u}"
        clock = FakeClock()
        tr = SpanTracer(clock=clock, pod_sample=1)
        for i, (q, s, r) in enumerate([(1.0, 2.0, 3.0), (2.0, 4.0, 6.0)]):
            pod = FakePod(f"uid-{i}")
            tr.pod_event("queue", "admit", pod)
            clock.step(q)
            tr.pod_event("scheduler", "drain_member", pod)
            clock.step(s)
            tr.pod_event("scheduler", "bound", pod)
            clock.step(r)
            tr.pod_event("kubelet", "running", pod)
            clock.step(10.0)  # gap between pods
        out = SLOTracker.stage_breakdown(tr.recorder)
        assert out["queue_wait"]["count"] == 2
        assert out["queue_wait"]["p50_s"] == 1.0
        assert out["queue_wait"]["p99_s"] == 2.0
        assert out["schedule_to_bound"]["p99_s"] == 4.0
        assert out["bound_to_running"]["p99_s"] == 6.0
        assert out["e2e"]["p50_s"] == 6.0
        assert out["e2e"]["p99_s"] == 12.0


# ------------------------------------------------------ trace determinism


class TestTraceDeterminism:
    def test_same_seed_identical_span_logs(self):
        """ACCEPTANCE: the chaos determinism contract extends to traces —
        two same-seed FakeClock runs yield BYTE-identical span logs."""
        from kubernetes_tpu.chaos.harness import ChaosHarness
        logs = []
        for _ in range(2):
            h = ChaosHarness(seed=23, nodes=6, nodes_per_slice=3,
                             error_rate=0.08)
            try:
                h.run(n_events=12, quiesce_steps=8)
                logs.append(h.span_log())
            finally:
                h.close()
        assert logs[0] == logs[1]
        names = {json.loads(ln)["name"]
                 for ln in logs[0].strip().splitlines()}
        # the pod's cross-component trail is present end to end
        assert {"admit", "drain_member", "bound", "running"} <= names
        comps = {json.loads(ln)["component"]
                 for ln in logs[0].strip().splitlines()}
        assert {"queue", "scheduler", "kubelet"} <= comps

    def test_wall_clock_spans_are_monotone(self):
        """A REAL_CLOCK run's spans have end >= start, and each
        single-writer component's trail is start-ordered."""
        from kubernetes_tpu.scheduler import Scheduler
        client = Client()
        for i in range(2):
            client.nodes().create(make_node(f"n{i}"))
        tracer = SpanTracer(pod_sample=1)
        sched = Scheduler(client, batch_size=8, tracer=tracer)
        sched.informers.start()
        sched.informers.wait_for_cache_sync()
        try:
            for i in range(20):
                client.pods("default").create(make_pod(f"p{i}"))
            deadline = time.time() + 30
            while time.time() < deadline and sched.queue.num_pending() < 20:
                time.sleep(0.02)
            for _ in range(4):  # 20 pods at batch_size=8: several cycles
                sched.schedule_pending(timeout=0.5)
            spans = tracer.recorder.spans(component="scheduler")
            assert spans
            for s in spans:
                assert s.end >= s.start
            # spans are recorded at COMPLETION (an outer span lands after
            # its nested stages), so the monotone claim is per-name: each
            # stage's successive batches move forward in time
            by_name = {}
            for s in spans:
                if not s.trace_id:
                    by_name.setdefault(s.name, []).append(s.start)
            assert {"tensorize", "scan_wait", "algorithm",
                    "commit", "bind_txn"} <= set(by_name)
            for name, starts in by_name.items():
                assert starts == sorted(starts), name
                assert len(starts) >= 2, name  # several batches ran
        finally:
            sched.informers.stop()
