"""ISSUE 20: speculative cohort assignment on the class scan.

The contract under test is BIT-EXACT serial equivalence, not a tolerated
approximation: KTPU_SPECULATIVE=1 routes unsharded class-table batches
through kernels/speculative.py (vmapped cohort argmax + exact collision
detection + serial repair) and every decision must equal the serial
class scan's, pod for pod, on randomized mixed fixtures — while the
scheduler_speculative_* counters attribute how much speculation actually
paid (accepted cohorts) vs was repaired (collisions). Satellites ride
along: the adaptive drain cap's contention pressure (preemption deltas +
express-band occupancy EWMA) and the sharded scan's x64 packed argmax.
"""

import os
import subprocess
import sys

import pytest

from kubernetes_tpu import api
from kubernetes_tpu.scheduler.cache import Cache
from kubernetes_tpu.scheduler.core import BatchScheduler
from kubernetes_tpu.scheduler.metrics import SchedulerMetrics
from kubernetes_tpu.scheduler.queue import NominatedPodMap

from test_class_fastpath import (WEIGHTS, _bind, _spread_listers, mk_node,
                                 mk_pod, req_anti, soft_anti)

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(TESTS_DIR)


def _mk_mixed_pod(rng, i):
    """Spread carriers + soft credits + required anti colors + plain
    pods across two tenant namespaces — every carry the collision
    detector must fence plus the plain pods it may speculate on."""
    kind = rng.randrange(5)
    ns = ("default", "tenant-b")[i % 2]
    if kind == 0:
        p = mk_pod(i, {"app": "web"})
    elif kind == 1:
        g = f"g{rng.randrange(3)}"
        p = soft_anti(mk_pod(i, {"grp": g}), g)
    elif kind == 2:
        c = f"c{rng.randrange(6)}"
        p = req_anti(mk_pod(i, {"color": c}), c)
    else:
        p = mk_pod(i, {"plain": "x"})
    p.metadata.namespace = ns
    return p


def _run_batches(speculative, pod_factory, n_nodes=16, batches=(60, 60),
                 oracle=True, nominate=False, seed=9):
    """Drive BatchScheduler over consecutive batches (binding winners
    between them) and return ((pod, node) decisions, metrics, sched)."""
    import random
    svc = api.Service(
        metadata=api.ObjectMeta(name="web", namespace="default"),
        spec=api.ServiceSpec(selector={"app": "web"}))
    listers = _spread_listers([svc])
    rng = random.Random(seed)
    cache = Cache()
    for i in range(n_nodes):
        cache.add_node(mk_node(i, zone=f"z{i % 3}"))
    nominated = NominatedPodMap()
    if nominate:
        ghost = mk_pod(900, {}, cpu="6", mem="12Gi")
        ghost.status.nominated_node_name = "n1"
        nominated.add(ghost)
    sched = BatchScheduler(cache, listers=listers, weights=dict(WEIGHTS),
                           nominated=nominated)
    sched.speculative = speculative
    sched.spec_oracle = oracle and speculative
    sched.sched_metrics = SchedulerMetrics()
    decisions = []
    next_i = [0]
    for n_pods in batches:
        pods = [pod_factory(rng, next_i[0] + j) for j in range(n_pods)]
        next_i[0] += n_pods
        if nominate:
            for p in pods[:2]:
                p.status.nominated_node_name = f"n{2 + next_i[0] % 5}"
                nominated.add(p)
        for res in sched.schedule(pods):
            decisions.append((res.pod.metadata.name, res.node_name))
            if res.node_name is not None:
                nominated.delete(res.pod)
                _bind(res.pod, res.node_name, cache, None)
    return decisions, sched.sched_metrics, sched


class TestSpeculativeParity:
    def test_randomized_mixed_parity(self, monkeypatch):
        """ACCEPTANCE: speculative decisions == serial decisions on
        randomized mixed batches (anti colors, spread groups, soft
        credits, two tenants, nominated overlays), with the divergence
        oracle replaying every batch and counting zero. The contention
        gate is forced open (KTPU_SPEC_MIN_PLAIN=0): once soft credits
        exist, every class carries a base row and the whole batch reads
        as non-plain, so the default gate would route these batches
        serial and the fence/repair machinery under test would never
        run."""
        from kubernetes_tpu.scheduler.kernels import speculative as smod
        monkeypatch.setattr(smod, "_SPEC_MIN_PLAIN", 0.0)
        spec, m, sched = _run_batches(True, _mk_mixed_pod, nominate=True)
        serial, _, _ = _run_batches(False, _mk_mixed_pod, nominate=True)
        assert len(spec) == 120
        assert spec == serial
        assert m.speculative_cohorts.value() > 0
        assert m.speculative_divergences.value() == 0
        assert list(sched.spec_divergence_log) == []

    def test_conflict_cohorts_repair_and_still_match(self):
        """Plain uniform pods over TWO nodes: every cohort's picks
        contend (type-1 collisions), the serial repair replays them, and
        the decisions still equal the serial scan's exactly."""
        plain = lambda rng, i: mk_pod(i, {"plain": "x"})
        spec, m, _ = _run_batches(True, plain, n_nodes=2, batches=(64,))
        serial, _, _ = _run_batches(False, plain, n_nodes=2, batches=(64,))
        assert spec == serial
        assert m.speculative_collisions.value() > 0
        assert m.speculative_repaired.value() > 0
        assert m.speculative_divergences.value() == 0

    def test_contention_gate_routes_serial(self):
        """A batch that is all carry-coupled pods (every pod carries a
        required anti-affinity color) would trip the structural fence on
        every cohort, so the launch-time plain-fraction gate
        (KTPU_SPEC_MIN_PLAIN) skips speculation entirely: flag on, zero
        cohorts attempted, decisions still equal the serial scan's."""
        anti = lambda rng, i: req_anti(mk_pod(i, {"color": f"c{i % 6}"}),
                                       f"c{i % 6}")
        spec, m, sched = _run_batches(True, anti, batches=(48,))
        serial, _, _ = _run_batches(False, anti, batches=(48,))
        assert spec == serial
        assert m.speculative_cohorts.value() == 0
        assert list(sched.spec_batch_log) == []

    def test_clean_cohorts_accepted(self, monkeypatch):
        """Cohort-friendly shape (narrow cohorts, wide node fleet): some
        cohorts clear collision detection and land in one vectorized
        shot — the counter distinguishes paid speculation from repair."""
        from kubernetes_tpu.scheduler.kernels import speculative
        monkeypatch.setattr(speculative, "_SPEC_COHORT", 4)
        plain = lambda rng, i: mk_pod(i, {"plain": "x"})
        spec, m, _ = _run_batches(True, plain, n_nodes=256, batches=(64,))
        serial, _, _ = _run_batches(False, plain, n_nodes=256,
                                    batches=(64,))
        assert spec == serial
        accepted = (m.speculative_cohorts.value()
                    - m.speculative_collisions.value())
        assert accepted > 0
        assert m.speculative_divergences.value() == 0

    def test_flag_off_is_inert(self):
        """With the flag off nothing speculative ships: no spec_plain
        vector on the batch, no stats on the pending handle, no counter
        movement — the serial path's pytrees are byte-identical to a
        build without this feature."""
        cache = Cache()
        for i in range(4):
            cache.add_node(mk_node(i))
        sched = BatchScheduler(cache, weights=dict(WEIGHTS))
        assert sched.speculative is False
        sched.sched_metrics = SchedulerMetrics()
        pending = sched.schedule_launch(
            [mk_pod(i, {"plain": "x"}) for i in range(12)])
        assert pending.batch.spec_plain is None
        assert pending.spec_stats is None
        sched.schedule_finish(pending)
        assert sched.sched_metrics.speculative_cohorts.value() == 0


class TestSpeculativeScheduler:
    def test_constructor_param_overrides_env(self, monkeypatch):
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.state import Client
        monkeypatch.delenv("KTPU_SPECULATIVE", raising=False)
        s = Scheduler(Client(validate=False), async_bind=False,
                      speculative=True)
        assert s.algorithm.speculative is True
        monkeypatch.setenv("KTPU_SPECULATIVE", "1")
        s = Scheduler(Client(validate=False), async_bind=False,
                      speculative=False)
        assert s.algorithm.speculative is False

    def test_chaos_same_seed_identical_with_speculation(self, monkeypatch,
                                                        tmp_path):
        """ACCEPTANCE: the chaos determinism contract (same seed =>
        identical event logs) survives KTPU_SPECULATIVE=1 — collision
        repair and cohort accounting add no nondeterminism."""
        from kubernetes_tpu.chaos import ChaosHarness
        monkeypatch.setenv("KTPU_SPECULATIVE", "1")
        logs = []
        for i in range(2):
            h = ChaosHarness(seed=23, nodes=6, nodes_per_slice=3,
                             error_rate=0.08,
                             wal_path=str(tmp_path / f"s{i}.wal"))
            try:
                assert h.scheduler.algorithm.speculative is True
                r = h.run(n_events=12, quiesce_steps=8)
                logs.append(r.events)
                assert r.ok, r.violations
            finally:
                h.close()
        assert logs[0] == logs[1]


class TestDrainCapContention:
    """Satellite: _drain_cap's contention pressure — preemption-attempt
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


class TestX64PackedArgmax:
    """Satellite: KTPU_X64_ARGMAX=1 folds the sharded scan's cross-shard
    pmax(score)+pmin(row) pair into ONE int64-key pmax when x64 is on,
    bit-identical winners; with x64 off the knob is inert."""

    def test_x64_sharded_parity_subprocess(self, tmp_path):
        """x64 flips global dtype defaults, so the packed-argmax leg
        runs in a subprocess: sharded(8 devices, x64, packed) binds ==
        single-device binds on uniform and anti-affinity fixtures."""
        script = tmp_path / "x64_parity.py"
        script.write_text(
            "import os\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "os.environ.setdefault('XLA_FLAGS',"
            " '--xla_force_host_platform_device_count=8')\n"
            "os.environ['JAX_ENABLE_X64'] = '1'\n"
            "os.environ['KTPU_X64_ARGMAX'] = '1'\n"
            "import sys\n"
            f"sys.path.insert(0, {REPO_DIR!r})\n"
            f"sys.path.insert(0, {TESTS_DIR!r})\n"
            "import jax\n"
            "assert jax.config.jax_enable_x64\n"
            "from test_sharded import _drain, _mesh\n"
            "for variant in ('uniform', 'anti-affinity'):\n"
            "    n1, single, _ = _drain(1, variant)\n"
            "    mesh = _mesh(8)\n"
            "    with mesh:\n"
            "        n2, sharded, sched = _drain(mesh, variant)\n"
            "    assert n1 == n2 > 0, (variant, n1, n2)\n"
            "    assert single == sharded, variant\n"
            "    assert sched.metrics.sharded_batches.value() > 0\n"
            "print('X64_PARITY_OK')\n")
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.pop("PYTEST_CURRENT_TEST", None)
        # one process per chip: a child with its own JAX is pinned to the
        # CPU from its first instruction, whatever this process runs on
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run([sys.executable, str(script)], env=env,
                             capture_output=True, text=True, timeout=540)
        assert out.returncode == 0, out.stderr[-4000:]
        assert "X64_PARITY_OK" in out.stdout

    def test_knob_inert_without_x64(self, monkeypatch):
        """The trace-time gate: knob on, x64 off -> the two-collective
        path lowers (int64 keys never materialize) and sharded binds
        still equal single-device (fresh shapes force a re-trace)."""
        import jax
        from kubernetes_tpu.scheduler.kernels import batch as kbatch
        assert not jax.config.jax_enable_x64
        monkeypatch.setattr(kbatch, "_X64_ARGMAX", True)
        from test_sharded import _drain, _mesh
        n1, single, _ = _drain(1, "uniform", n_pods=64)
        mesh = _mesh(8)
        with mesh:
            n2, sharded, sched = _drain(mesh, "uniform", n_pods=64)
        assert n1 == n2 > 0
        assert single == sharded
        assert sched.metrics.sharded_batches.value() > 0


class TestMetricFamiliesRegistered:
    def test_speculative_counter_families_in_registry(self):
        names = set(SchedulerMetrics().registry._metrics)
        for fam in ("scheduler_speculative_cohorts_total",
                    "scheduler_speculative_collisions_total",
                    "scheduler_speculative_repaired_pods_total",
                    "scheduler_speculative_divergences_total"):
            assert fam in names, fam
