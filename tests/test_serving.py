"""Serving-mode tests (ISSUE 7): open-loop churn with latency SLOs.

Tier-1 acceptance:
  - fixed-seed loadgen on FakeClock is deterministic — same seed =>
    identical arrival log and identical bind event log;
  - the SLO tracker's reported percentiles match a scalar replay of its
    own samples (exact nearest-rank, not bucket approximations);
  - adaptive drain batch caps are recorded and monotone in queue depth
    (and exactly the documented clamp(pow2) policy);
  - priority-lane arrivals bind ahead of the bulk backlog;
  - queue release paths re-sort by (priority, arrival): a released gang
    can never starve a newer high-priority singleton.

The chaos soak variant (loadgen + wire faults + a scheduler restart,
InvariantChecker green, no pod permanently stuck) runs behind -m slow.
"""

import math

import pytest

from kubernetes_tpu import api
from kubernetes_tpu.api.core import Pod
from kubernetes_tpu.api.meta import ObjectMeta
from kubernetes_tpu.api.scheduling import PodGroup, PodGroupSpec
from kubernetes_tpu.api.wellknown import LABEL_POD_GROUP
from kubernetes_tpu.scheduler.gang import GangManager
from kubernetes_tpu.scheduler.queue import (
    DEFAULT_UNSCHEDULABLE_DURATION, SchedulingQueue)
from kubernetes_tpu.serving import (CLASS_LABEL, LoadGen, SLOTracker,
                                    ServingHarness, percentile)
from kubernetes_tpu.serving.slo import BIND, STARTUP
from kubernetes_tpu.utils.clock import FakeClock

pytestmark = pytest.mark.serving

SMOKE_SEED = 7


# --------------------------------------------------------------- loadgen


class TestLoadGenSchedule:
    def test_schedule_is_pure_function_of_seed(self):
        a = LoadGen(None, seed=42, rate=25.0).make_schedule(200)
        b = LoadGen(None, seed=42, rate=25.0).make_schedule(200)
        assert [(e.t, e.cls, e.params) for e in a] == \
            [(e.t, e.cls, e.params) for e in b]
        c = LoadGen(None, seed=43, rate=25.0).make_schedule(200)
        assert [(e.t, e.cls) for e in a] != [(e.t, e.cls) for e in c]

    def test_poisson_mean_gap(self):
        sched = LoadGen(None, seed=1, rate=50.0).make_schedule(2000)
        # mean inter-arrival ~ 1/rate (law of large numbers, loose band)
        assert 0.8 / 50.0 < sched[-1].t / len(sched) < 1.25 / 50.0

    def test_offsets_monotone(self):
        sched = LoadGen(None, seed=9, rate=10.0).make_schedule(100)
        assert all(a.t <= b.t for a, b in zip(sched, sched[1:]))


# ------------------------------------------------------------------- slo


def _mk_pod(name, cls, node=None, phase=None):
    p = Pod(metadata=ObjectMeta(name=name, namespace="d",
                                labels={CLASS_LABEL: cls}))
    if node:
        p.spec.node_name = node
    if phase:
        p.status.phase = phase
    return p


class TestSLOTrackerScalarReplay:
    def test_percentiles_match_scalar_replay(self):
        clock = FakeClock()
        tr = SLOTracker(clock=clock)
        # 20 pods across two classes, bound/running at staggered times
        for i in range(20):
            cls = "a" if i % 3 else "b"
            tr.observe(_mk_pod(f"p{i}", cls))
            clock.step(0.5 + (i % 7) * 0.25)
            tr.observe(_mk_pod(f"p{i}", cls, node="n1"))
            clock.step(0.5)
            tr.observe(_mk_pod(f"p{i}", cls, node="n1", phase="Running"))
        report = tr.report()
        for kind in (BIND, STARTUP):
            for cls, vals in tr.samples(kind).items():
                assert vals == sorted(vals)
                got = report["classes"][cls][kind]
                # the scalar replay: exact nearest-rank over the samples
                for q, field in ((0.50, "p50_s"), (0.95, "p95_s"),
                                 (0.99, "p99_s")):
                    rank = max(1, math.ceil(q * len(vals)))
                    assert got[field] == round(vals[rank - 1], 6)
                assert got["count"] == len(vals)
                assert got["max_s"] == round(vals[-1], 6)

    def test_transitions_stamped_once(self):
        clock = FakeClock()
        tr = SLOTracker(clock=clock)
        tr.observe(_mk_pod("x", "a", node="n1"))
        t0 = tr._bound["d/x"]
        clock.step(5.0)
        tr.observe(_mk_pod("x", "a", node="n1"))  # duplicate event
        assert tr._bound["d/x"] == t0
        assert tr.bind_log == [("d/x", "n1")]

    def test_percentile_nearest_rank(self):
        vals = [1.0, 2.0, 3.0, 4.0]
        assert percentile(vals, 0.50) == 2.0
        assert percentile(vals, 0.95) == 4.0
        assert percentile([5.0], 0.99) == 5.0
        assert percentile([], 0.5) == 0.0


# ------------------------------------------ queue release-order contract


def _pod(name, prio=None, group=None):
    labels = {LABEL_POD_GROUP: group} if group else {}
    p = Pod(metadata=ObjectMeta(name=name, namespace="d", labels=labels))
    p.spec.priority = prio
    return p


def _gang_queue(clock, min_member=3):
    groups = {"d/g1": PodGroup(
        metadata=ObjectMeta(name="g1", namespace="d"),
        spec=PodGroupSpec(min_member=min_member))}
    gm = GangManager(lambda ns, name: groups.get(f"{ns}/{name}"),
                     clock=clock)
    q = SchedulingQueue(clock=clock)
    q.gang = gm
    return q


class TestQueueReleaseOrdering:
    """The satellite fix pin: every held-pod release path re-sorts by
    (priority, arrival)."""

    def test_released_gang_cannot_starve_newer_high_prio_singleton(self):
        clock = FakeClock()
        q = _gang_queue(clock)
        q.add(_pod("g1-a", 0, "g1"))
        q.add(_pod("g1-b", 0, "g1"))
        assert q.pop_batch(10, timeout=0) == []  # both park (2 < 3)
        clock.step(1)
        q.add(_pod("hi", 100))          # newer, higher priority
        clock.step(1)
        q.add(_pod("g1-c", 0, "g1"))    # completes the gang -> release
        out = [p.metadata.name for p in q.pop_batch(10, timeout=0)]
        assert out[0] == "hi", out
        assert set(out[1:]) == {"g1-a", "g1-b", "g1-c"}

    def test_backoff_release_resorts_by_priority_then_arrival(self):
        clock = FakeClock()
        q = SchedulingQueue(clock=clock)
        q.add(_pod("lo", 0))
        lo = q.pop_batch(1, timeout=0)[0]
        q.add_unschedulable_if_not_present(lo, q.scheduling_cycle)
        q.move_all_to_active_queue()    # still in backoff window
        clock.step(0.5)
        q.add(_pod("hi", 100))          # arrives while lo backs off
        clock.step(2.0)                 # backoff expires
        out = [p.metadata.name for p in q.pop_batch(10, timeout=0)]
        assert out == ["hi", "lo"]

    def test_priority_raised_while_parked_is_honored_on_release(self):
        clock = FakeClock()
        q = _gang_queue(clock, min_member=2)
        q.add(_pod("g1-a", 0, "g1"))
        assert q.pop_batch(10, timeout=0) == []  # parks
        clock.step(1)
        q.add(_pod("solo", 50))
        # raise the parked member's priority above the singleton's
        q.update(_pod("g1-a", 0, "g1"), _pod("g1-a", 200, "g1"))
        q.add(_pod("g1-b", 200, "g1"))  # completes the gang
        out = [p.metadata.name for p in q.pop_batch(10, timeout=0)]
        assert out[:2] == ["g1-a", "g1-b"], out
        assert out[2] == "solo"

    def test_unschedulable_stay_measured_from_entry_not_arrival(self):
        clock = FakeClock()
        q = SchedulingQueue(clock=clock)
        q.add(_pod("old", 0))
        # the pod ages in the ACTIVE queue far past the leftover interval
        clock.step(DEFAULT_UNSCHEDULABLE_DURATION + 10)
        old = q.pop_batch(1, timeout=0)[0]
        q.add_unschedulable_if_not_present(old, q.scheduling_cycle)
        clock.step(1.0)
        # 1s into its unschedulable STAY: must still be parked (the old
        # arrival-keyed timer released it instantly here)
        assert q.pop_batch(1, timeout=0) == []
        clock.step(DEFAULT_UNSCHEDULABLE_DURATION)
        out = [p.metadata.name for p in q.pop_batch(1, timeout=0)]
        assert out == ["old"]

    def test_lane_census_tracks_live_heap(self):
        clock = FakeClock()
        q = SchedulingQueue(clock=clock)
        for i in range(5):
            q.add(_pod(f"lo{i}", 0))
        q.add(_pod("hi1", 1000))
        q.add(_pod("hi2", 2000))
        assert q.active_depth() == 7
        assert q.lane_depth(1000) == 2
        assert q.top_priority() == 2000
        # popping consumes the census; re-prioritizing moves it
        got = q.pop_batch(2, timeout=0)
        assert [p.metadata.name for p in got] == ["hi2", "hi1"]
        assert q.lane_depth(1000) == 0
        q.update(_pod("lo0", 0), _pod("lo0", 5000))
        assert q.lane_depth(1000) == 1
        assert q.top_priority() == 5000
        q.delete(_pod("lo0", 5000))
        assert q.lane_depth(1000) == 0
        assert q.active_depth() == 4


# ------------------------------------------------------- serving smoke


@pytest.fixture(scope="module")
def smoke_runs():
    """Two same-seed FakeClock serving runs (the second reuses the
    process-global XLA compile cache, so the pair stays in tier-1
    budget). Module-scoped: every smoke assertion reads these."""
    runs = []
    for _ in range(2):
        h = ServingHarness(seed=SMOKE_SEED, nodes=6, rate=12.0,
                           batch_size=64, min_batch=4)
        try:
            runs.append(h.run(n_events=40, max_ticks=60,
                              quiesce_ticks=5))
        finally:
            h.close()
    return runs


class TestServingSmoke:
    def test_same_seed_identical_event_logs(self, smoke_runs):
        r1, r2 = smoke_runs
        assert r1.arrival_log == r2.arrival_log
        assert r1.arrival_log, "schedule applied nothing"
        assert r1.bind_log == r2.bind_log
        assert r1.bind_log, "nothing bound"
        assert r1.slo == r2.slo

    def test_converged_and_green(self, smoke_runs):
        r = smoke_runs[0]
        assert r.ok, (r.violations, r.stuck)
        assert r.pods_bound > 0
        slo = r.slo
        assert slo["bound"] == slo["created"]
        # every exercised class reports percentiles
        for cls in ("singleton", "priority", "gang"):
            assert cls in slo["classes"], slo["classes"].keys()
            assert slo["classes"][cls][BIND]["count"] > 0

    def test_adaptive_caps_recorded_and_monotone_in_depth(self, smoke_runs):
        r = smoke_runs[0]
        bulk = [(d, cap) for d, lane, pressure, cap in r.batch_caps
                if lane == 0 and pressure == 0]
        assert bulk, "no adaptive cycles recorded"
        for depth, cap in bulk:
            # the documented policy, exactly: clamp(pow2ceil(depth))
            want = 1 << max(0, depth - 1).bit_length()
            assert cap == max(4, min(64, want)), (depth, cap)
        bulk.sort()
        caps = [c for _, c in bulk]
        assert all(a <= b for a, b in zip(caps, caps[1:])), \
            "caps not monotone in queue depth"

    def test_priority_lane_beats_bulk_backlog(self, smoke_runs):
        r = smoke_runs[0]
        lanes = [t for t in r.batch_caps if 0 < t[1] < t[0]]
        assert lanes, "no express-lane cycle fired"
        for depth, lane, _pressure, cap in lanes:
            want = 1 << max(0, lane - 1).bit_length()
            assert cap == max(4, min(64, want)), (lane, cap)
        pri = r.slo["classes"]["priority"][BIND]
        single = r.slo["classes"]["singleton"][BIND]
        # lane arrivals never wait out the bulk backlog
        assert pri["p95_s"] <= single["p95_s"]


class TestAdaptiveCapUnit:
    """_drain_cap policy directly on the shell (no kernel launches)."""

    def _sched(self):
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.state import Client
        return Scheduler(Client(validate=False), batch_size=1024,
                         adaptive_batch=True, min_batch=16,
                         async_bind=False)

    def test_cap_follows_depth_and_pressure(self):
        sched = self._sched()
        assert sched._drain_cap() == 16            # empty -> floor
        for i in range(100):
            sched.queue.add(_pod(f"p{i}", 0))
        assert sched._drain_cap() == 128           # pow2ceil(100)
        for i in range(1500):
            sched.queue.add(_pod(f"q{i}", 0))
        assert sched._drain_cap() == 1024          # clamped to batch_size
        with sched._count_lock:
            sched._binds_inflight = 2              # backlog beyond first
        assert sched._drain_cap() == 512           # one halving
        sched._commit_lagging = True
        assert sched._drain_cap() == 256           # two units
        with sched._count_lock:
            sched._binds_inflight = 0
        sched._commit_lagging = False

    def test_lane_cohort_sizes_express_batch(self):
        sched = self._sched()
        for i in range(1000):
            sched.queue.add(_pod(f"p{i}", 0))
        sched.queue.add(_pod("hi", sched.lane_priority))
        before = sched.metrics.lane_batches.value()
        assert sched._drain_cap() == 16            # lane of 1 -> floor
        assert sched.metrics.lane_batches.value() == before + 1
        # the express pop drains the lane first (heap top)
        got = sched.queue.pop_batch(16, timeout=0)
        assert got[0].metadata.name == "hi"

    def test_fixed_batch_when_adaptive_off(self):
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.state import Client
        sched = Scheduler(Client(validate=False), batch_size=1024,
                          async_bind=False)
        assert not sched.adaptive_batch
        assert sched._drain_cap() == 1024
        assert len(sched.batch_cap_log) == 0


# ------------------------------------------------------- chaos soak


class TestDrainCapContention:
    """_drain_cap's contention pressure — preemption-attempt
    deltas and the express-band occupancy EWMA each shrink BULK caps one
    notch (express caps stay exempt: urgency wins over pacing)."""

    def _sched(self):
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.state import Client
        return Scheduler(Client(validate=False), batch_size=1024,
                         adaptive_batch=True, min_batch=16,
                         async_bind=False)

    def _pod(self, name, priority):
        return api.Pod(
            metadata=api.ObjectMeta(name=name, namespace="default"),
            spec=api.PodSpec(priority=priority,
                             containers=[api.Container(name="c",
                                                       image="img")]))

    def test_preemption_delta_shrinks_one_cycle(self):
        sched = self._sched()
        for i in range(1500):
            sched.queue.add(self._pod(f"p{i}", 0))
        assert sched._drain_cap() == 1024
        before = sched.metrics.backpressure_shrinks.value()
        sched.metrics.preemption_attempts.inc()
        # the delta since the last sized cycle is live contention: one
        # halving, logged as a pressure unit
        assert sched._drain_cap() == 512
        assert sched.metrics.backpressure_shrinks.value() == before + 1
        assert sched.batch_cap_log[-1][2] == 1
        # no new attempts -> the pressure unit is gone next cycle
        assert sched._drain_cap() == 1024

    def test_express_occupancy_ewma_shrinks_bulk(self):
        sched = self._sched()
        for i in range(100):
            sched.queue.add(self._pod(f"b{i}", 0))
        for i in range(100):
            sched.queue.add(self._pod(f"hi{i}", sched.lane_priority))
        # express cycle: lane-sized cap, NEVER shrunk, EWMA goes hot
        assert sched._drain_cap() == 128
        assert sched._express_ewma > 0.05
        got = sched.queue.pop_batch(128, timeout=0)
        assert sum(1 for p in got if (p.spec.priority or 0) > 0) == 100
        # bulk cycles right after the express burst: one EWMA shrink
        # unit while hot, decaying back to the exact depth policy
        caps = [sched._drain_cap() for _ in range(6)]
        assert caps[0] == 64            # pow2ceil(72)=128, one halving
        assert caps[3] == 128           # EWMA decayed below the knee
        assert caps[-1] == 128
        assert sched.metrics.backpressure_shrinks.value() > 0


@pytest.mark.slow
class TestServingChaosSoak:
    def test_wire_faults_and_restart_converge_green(self):
        """Loadgen + wire faults (latency, resets, watch drops, API
        errors) + one scheduler crash-restart mid-churn: the run must
        still converge — InvariantChecker green and NO pod permanently
        stuck (every arrival bound or terminal)."""
        h = ServingHarness(seed=29, nodes=8, rate=15.0,
                           batch_size=64, min_batch=4, http=True,
                           error_rate=0.05, reset_rate=0.03,
                           latency_rate=0.10, watch_drop_rate=0.25)
        try:
            r = h.run(n_events=120, max_ticks=240, quiesce_ticks=10,
                      restart_scheduler_at=6)
            assert r.scheduler_restarts == 1
            assert r.violations == []
            assert r.stuck == [], r.stuck
            assert r.pods_bound > 0
            assert r.slo["bound"] > 0
        finally:
            h.close()
