"""The thread's CPU beside its wall: SpanTracer.stage reads the entering
thread's CPU clock into a counter (scheduler_scheduling_cpu_seconds_total
for the scheduler's stages), the scheduler exports the CPU of its threads
by role (scheduler_thread_cpu_seconds), and the benchmark's readers turn
both into the off-core and CPU metrics of benchmarks/metrics/. On the
real clocks: what the interpreter lock does is the thing measured."""

import json
import os
import sys
import threading
import time

import pytest

from kubernetes_tpu import api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.observability import (FlightRecorder, SpanTracer,
                                          thread_cpu_by_role)
from kubernetes_tpu.observability import tracer as tracer_mod
from kubernetes_tpu.scheduler.metrics import (STAGE_LEAVES, STAGE_PARENTS,
                                              STAGE_PARTS, THREAD_ROLES,
                                              SchedulerMetrics)
from kubernetes_tpu.state import Client
from kubernetes_tpu.utils.clock import FakeClock
from kubernetes_tpu.utils.metrics import Counter, Histogram

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

from harness.children import parse_metrics  # noqa: E402  (the benchmark's own parser)

SPIN_S = 0.02


def spin(seconds=SPIN_S):
    """Pure Python that holds the interpreter lock for `seconds` of this
    thread's CPU."""
    end = time.thread_time() + seconds
    n = 0
    while time.thread_time() < end:
        n += 1
    return n


def timed(work):
    """(wall, CPU) of one stage around `work`, on the real clock."""
    hist, cpu = Histogram("t_seconds"), Counter("t_cpu_seconds_total")
    with SpanTracer(enabled=False).stage("s", hist, cpu=cpu,
                                         labels={"operation": "s"}):
        work()
    return hist.sum(operation="s"), cpu.value(operation="s")


class TestStageReadsTheThreadsCPU:
    def test_a_python_spin_reads_its_wall_in_cpu(self):
        # the best of five: another process may take the core from this
        # thread, which is off-core time too, and not what is tested
        best = max(cpu / wall for wall, cpu in (timed(spin)
                                                for _ in range(5)))
        assert 0.8 <= best <= 1.2

    def test_a_sleep_reads_almost_no_cpu(self):
        wall, cpu = timed(lambda: time.sleep(SPIN_S))
        assert wall >= SPIN_S and cpu < 0.1 * wall

    def test_a_thread_that_holds_the_lock_puts_the_stage_off_the_core(self):
        def off_core_share():
            wall, cpu = timed(lambda: spin(0.03))
            return (wall - cpu) / wall

        alone = min(off_core_share() for _ in range(3))
        stop = threading.Event()

        def hog():
            while not stop.is_set():
                pass

        t = threading.Thread(target=hog, daemon=True)
        t.start()
        try:
            contended = off_core_share()
        finally:
            stop.set()
            t.join(timeout=10)
        assert not t.is_alive()
        # the spin takes the lock back once a switch interval at best
        assert contended > alone + 0.2, (alone, contended)

    def test_the_cpu_never_reaches_the_span(self):
        """Same-seed span logs stay byte-identical: the CPU figure goes
        into the counter alone."""
        def run(cpu):
            clock = FakeClock(start=50.0)
            tr = SpanTracer(clock=clock, recorder=FlightRecorder(),
                            pod_sample=1)
            with tr.stage("tensorize", Histogram("t_seconds"), cpu=cpu,
                          labels={"operation": "tensorize"}, pods=3):
                clock.step(2.5)
                spin(0.002)
            return tr.recorder.export_jsonl()

        cpu = Counter("t_cpu_seconds_total")
        assert run(cpu) == run(None)
        assert cpu.value(operation="tensorize") > 0.0


class TestScrapedSeries:
    def test_every_operation_is_declared_at_zero(self):
        scrape = parse_metrics(SchedulerMetrics().registry.expose())
        for op in STAGE_PARENTS + STAGE_LEAVES + STAGE_PARTS:
            key = f'scheduler_scheduling_cpu_seconds_total{{operation="{op}"}}'
            assert scrape.get(key) == 0.0, op

    def test_the_role_gauge_sums_named_threads_within_the_process(self):
        stop, ready = threading.Event(), threading.Barrier(5)

        def work():
            spin(0.005)
            ready.wait(timeout=30)
            stop.wait(timeout=30)

        names = ("scheduling", "binder_0", "informer-pods", "watch_pump")
        threads = [threading.Thread(target=work, name=n, daemon=True)
                   for n in names]
        for t in threads:
            t.start()
        try:
            ready.wait(timeout=30)
            scrape = parse_metrics(SchedulerMetrics().registry.expose())
            process = time.process_time()
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
        assert not any(t.is_alive() for t in threads)
        roles = {r: scrape[f'scheduler_thread_cpu_seconds{{role="{r}"}}']
                 for r in THREAD_ROLES}
        assert set(roles) == {"scheduling", "binder", "informer",
                              "watch_pump"}
        assert all(v >= 0.005 for v in roles.values()), roles
        assert sum(roles.values()) <= process

    def test_the_clock_is_the_one_pthread_getcpuclockid_gives(self):
        assert tracer_mod._thread_cpu_clock(threading.get_native_id()) == \
            time.pthread_getcpuclockid(threading.get_ident())
        # an exited thread is read as nothing, not as an error
        t = threading.Thread(target=spin, name="binder_gone")
        t.start()
        t.join(timeout=10)
        assert thread_cpu_by_role(["binder_gone"]) == {"binder_gone": 0.0}

    def test_the_served_cycle_counts_its_stages_cpu(self):
        from kubernetes_tpu.scheduler import Scheduler
        client = Client()
        alloc = {"cpu": Quantity("4"), "memory": Quantity("8Gi"),
                 "pods": Quantity("110")}
        for i in range(3):
            client.nodes().create(api.Node(
                metadata=api.ObjectMeta(name=f"n{i}"),
                status=api.NodeStatus(
                    capacity=dict(alloc), allocatable=dict(alloc),
                    conditions=[api.NodeCondition(type="Ready",
                                                  status="True")])))
        sched = Scheduler(client, batch_size=64)
        sched.start()
        try:
            assert sched._thread.name == "scheduling"
            for i in range(8):
                client.pods("default").create(api.Pod(
                    metadata=api.ObjectMeta(name=f"p{i}",
                                            namespace="default"),
                    spec=api.PodSpec(containers=[api.Container(
                        name="c", image="pause")])))
            deadline = time.monotonic() + 60
            m = sched.metrics
            while m.schedule_attempts.value(result="scheduled") < 8 and \
                    time.monotonic() < deadline:
                time.sleep(0.05)
            scrape = parse_metrics(m.registry.expose())
        finally:
            sched.stop()
        assert m.schedule_attempts.value(result="scheduled") == 8
        assert scrape['scheduler_thread_cpu_seconds{role="scheduling"}'] > 0
        assert scrape['scheduler_thread_cpu_seconds{role="informer"}'] > 0
        for op in ("refresh", "tensorize", "dispatch", "assume"):
            wall = m.scheduling_duration.sum(operation=op)
            cpu = m.scheduling_cpu.value(operation=op)
            # one clock_gettime's resolution of room on a short stage
            assert 0.0 < cpu <= wall + 1e-4, op


# ----------------------------------------------- the benchmark's readers


@pytest.fixture(scope="module")
def bench_run():
    """benchmarks/run.py as a module (its read_metric is the one reader
    of every kind of data file)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "ktpu_bench_run_cpu", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def declared(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert "workloads" not in entry   # every cell reports it
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    assert (spec["layer"], spec["moves"], spec["unit"]) == \
        (entry["layer"], entry["moves"], entry["unit"])
    return entry, spec


def scrapes(before, after):
    return {"probe0": {"scrape": {"kube_scheduler": before}},
            "probe1": {"scrape": {"kube_scheduler": after}}}


SCHEDULED = 'scheduler_schedule_attempts_total{result="scheduled"}'


def stage_scrape(pods, **wall_cpu):
    out = {SCHEDULED: float(pods)}
    for op, (wall, cpu) in wall_cpu.items():
        out['scheduler_scheduling_duration_seconds_sum'
            f'{{operation="{op}"}}'] = wall
        out[f'scheduler_scheduling_cpu_seconds_total{{operation="{op}"}}'] \
            = cpu
    return out


OPS = ("refresh", "tensorize", "dispatch", "repair", "assume",
       "static_masks")


@pytest.mark.parametrize("name,expected", [
    # (0.4-0.1) + (0.6-0.3) + (0.5-0.5) + 0 + (0.2-0.1), x 1000 / 200
    ("sched_offcore_ms_per_pod", 3.5),
    ("sched_static_masks_offcore_ms_per_pod", 0.25),
    ("sched_dispatch_offcore_ms_per_pod", 0.0),
])
def test_the_off_core_reader(name, expected, bench_run):
    entry, spec = declared(name)
    assert spec["kind"] == "reader" and entry["source"] == "program_span"
    before = stage_scrape(100, **{op: (1.0, 1.0) for op in OPS})
    after = stage_scrape(300, refresh=(1.4, 1.1), tensorize=(1.6, 1.3),
                         dispatch=(1.5, 1.5), repair=(1.0, 1.0),
                         assume=(1.2, 1.1), static_masks=(1.1, 1.05))
    assert bench_run.read_metric(name, spec, scrapes(before, after)) == \
        pytest.approx(expected)
    # the parent's program has the wall and not the CPU: nothing, no raise
    bare = [{k: v for k, v in s.items() if "cpu_seconds" not in k}
            for s in (before, after)]
    assert bench_run.read_metric(name, spec, scrapes(*bare)) is None
    # no pod scheduled: nothing to divide by
    assert bench_run.read_metric(name, spec, scrapes(before, before)) is None


def role_scrape(pods, **roles):
    out = {SCHEDULED: float(pods)}
    out.update({f'scheduler_thread_cpu_seconds{{role="{r}"}}': v
                for r, v in roles.items()})
    return out


@pytest.mark.parametrize("name,expected", [
    ("sched_loop_cpu_ms_per_pod", 1000.0 * 2.0 / 400),
    ("sched_side_threads_cpu_ms_per_pod", 1000.0 * (0.5 + 0.3 + 0.1) / 400),
])
def test_the_thread_cpu_readers(name, expected, bench_run):
    entry, spec = declared(name)
    assert entry["source"] == "program_counter"
    before = role_scrape(0, scheduling=1.0, binder=1.0, informer=2.0,
                         watch_pump=0.5)
    after = role_scrape(400, scheduling=3.0, binder=1.5, informer=2.3,
                        watch_pump=0.6)
    assert bench_run.read_metric(name, spec, scrapes(before, after)) == \
        pytest.approx(expected)
    bare = {SCHEDULED: 0.0}, {SCHEDULED: 400.0}
    assert bench_run.read_metric(name, spec, scrapes(*bare)) is None
