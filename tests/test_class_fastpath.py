"""ISSUE 14: every non-gang batch shape rides the class-indexed scan.

Spread groups, in-scan soft credits, and nominated reservations used to
demote a batch to the classic per-pod kernel (and to GSPMD under a mesh);
they are now carried state / a phantom overlay of the class-indexed scan.
These tests pin:

  - ROUTING: such batches build class tables (core no longer demotes),
  - PARITY: class-scan decisions == classic kernel (`class_scan` off,
    the control, bit-identical) == the serial numpy oracle (predicates/
    priorities replayed pod-by-pod with the kernel's tie-break), on
    randomized >=100-pod fixtures with node add/delete/relabel churn
    between batches,
  - CHAINING: spread/soft batches keep chaining in the pipelined drain
    (the carried counts ride the chained usage handle; the old
    recompute-from-batch-start flush is gone) with decisions identical
    to the unchained drain,
  - the soft_gang fallback counter stays wired for the one remaining
    overflow path (gang batch whose channel union blows the caps).
"""

import random

import numpy as np
import pytest

from kubernetes_tpu import api
from kubernetes_tpu.api import Quantity
from kubernetes_tpu.scheduler import predicates as preds
from kubernetes_tpu.scheduler import priorities as prios
from kubernetes_tpu.scheduler.cache import Cache
from kubernetes_tpu.scheduler.core import BatchScheduler
from kubernetes_tpu.scheduler.nodeinfo import NodeInfo
from kubernetes_tpu.scheduler.queue import NominatedPodMap

WEIGHTS = {"LeastRequestedPriority": 1, "BalancedResourceAllocation": 1,
           "SelectorSpreadPriority": 1, "InterPodAffinityPriority": 1}


def mk_node(i, zone=None, cpu="8", mem="16Gi"):
    labels = {api.wellknown.LABEL_HOSTNAME: f"n{i}"}
    if zone is not None:
        labels[api.wellknown.LABEL_ZONE] = zone
    alloc = {"cpu": Quantity(cpu), "memory": Quantity(mem),
             "pods": Quantity(110)}
    return api.Node(
        metadata=api.ObjectMeta(name=f"n{i}", labels=labels),
        status=api.NodeStatus(capacity=dict(alloc), allocatable=dict(alloc),
                              conditions=[api.NodeCondition(
                                  type="Ready", status="True")]))


def mk_pod(i, labels, cpu="100m", mem="64Mi"):
    return api.Pod(
        metadata=api.ObjectMeta(name=f"p{i}", namespace="default",
                                labels=dict(labels)),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="img",
            resources=api.ResourceRequirements(
                requests={"cpu": Quantity(cpu), "memory": Quantity(mem)}))]))


def soft_anti(pod, group, weight=10):
    pod.spec.affinity = api.Affinity(
        pod_anti_affinity=api.PodAntiAffinity(
            preferred_during_scheduling_ignored_during_execution=[
                api.WeightedPodAffinityTerm(
                    weight=weight,
                    pod_affinity_term=api.PodAffinityTerm(
                        label_selector=api.LabelSelector(
                            match_labels={"grp": group}),
                        topology_key=api.wellknown.LABEL_HOSTNAME))]))
    return pod


def req_anti(pod, color):
    pod.spec.affinity = api.Affinity(
        pod_anti_affinity=api.PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[
                api.PodAffinityTerm(
                    label_selector=api.LabelSelector(
                        match_labels={"color": color}),
                    topology_key=api.wellknown.LABEL_HOSTNAME)]))
    return pod


def _spread_listers(services):
    return prios.SpreadListers(services=lambda ns: services)


def _serial_oracle_step(pod, infos, listers, row_of, seq, weights=WEIGHTS):
    """One serial-reference decision with the kernel's tie-break, or None
    when the pod fits nowhere."""
    meta = preds.PredicateMetadata(pod, infos)
    feasible = {nm: ni for nm, ni in infos.items()
                if preds.pod_fits_on_node(pod, meta, ni)[0]}
    if not feasible:
        return None
    pmeta = prios.PriorityMetadata(pod, listers=listers)
    scores = prios.prioritize_nodes(pod, pmeta, feasible, weights,
                                    all_node_infos=infos)

    def penalty(nm):
        h = (row_of[nm] * -1640531527 + (seq & 0x7FFFFFFF) * 40503) & 0xFFFF
        return float(h) * (0.5 / 65536.0)
    return max(feasible, key=lambda nm: scores.get(nm, 0) - penalty(nm))


def _bind(pod, node_name, cache, infos):
    bound = api.serde.deepcopy_obj(pod)
    bound.spec.node_name = node_name
    cache.add_pod(bound)
    if infos is not None:
        infos[node_name].add_pod(bound)


class TestClassScanRouting:
    """The three formerly demoted shapes build class tables and their
    decisions replay the serial oracle exactly."""

    def test_spread_batch_rides_class_scan(self):
        svc = api.Service(
            metadata=api.ObjectMeta(name="web", namespace="default"),
            spec=api.ServiceSpec(selector={"app": "web"}))
        listers = _spread_listers([svc])
        cache = Cache()
        infos = {}
        for i in range(6):
            n = mk_node(i, zone=f"z{i % 2}")
            cache.add_node(n)
            infos[n.metadata.name] = NodeInfo(n)
        sched = BatchScheduler(cache, listers=listers,
                               weights=dict(WEIGHTS))
        pods = [mk_pod(i, {"app": "web"}) for i in range(18)]
        pending = sched.schedule_launch(pods)
        # ACCEPTANCE: the spread batch was NOT demoted to the classic path
        assert pending.batch._class_tables is not None
        assert pending.batch.spread_slots is not None
        assert pending.spread_sig is not None
        results = sched.schedule_finish(pending)
        row_of = dict(sched.mirror.row_of)
        for j, res in enumerate(results):
            best = _serial_oracle_step(res.pod, infos, listers, row_of, j)
            assert res.node_name == best, (res.pod.metadata.name,
                                           res.node_name, best)
            _bind(res.pod, best, cache, infos)

    def test_soft_batch_rides_class_scan(self):
        cache = Cache()
        infos = {}
        for i in range(6):
            n = mk_node(i)
            cache.add_node(n)
            infos[n.metadata.name] = NodeInfo(n)
        sched = BatchScheduler(cache, weights=dict(WEIGHTS))
        pods = [soft_anti(mk_pod(i, {"grp": f"g{i % 3}"}), f"g{i % 3}")
                for i in range(15)]
        pending = sched.schedule_launch(pods)
        assert pending.batch._class_tables is not None
        assert pending.batch.soft_dom is not None
        assert pending.soft_sig is not None
        results = sched.schedule_finish(pending)
        row_of = dict(sched.mirror.row_of)
        for j, res in enumerate(results):
            best = _serial_oracle_step(res.pod, infos, None, row_of, j)
            assert res.node_name == best, (res.pod.metadata.name,
                                           res.node_name, best)
            _bind(res.pod, best, cache, infos)

    def test_nominated_batch_rides_class_scan(self):
        """The phantom overlay shields a nominated node from everyone but
        the nominee — on the class path, identically to the classic
        kernel (which is the pinned oracle for the nom deviation)."""
        def build():
            nominated = NominatedPodMap()
            cache = Cache()
            for i in range(4):
                cache.add_node(mk_node(i, cpu="1", mem="1Gi"))
            # a phantom preemptor reserves ALL of n0
            ghost = mk_pod(900, {}, cpu="1", mem="1Gi")
            ghost.status.nominated_node_name = "n0"
            nominated.add(ghost)
            sched = BatchScheduler(cache, nominated=nominated)
            pods = [mk_pod(i, {}, cpu="600m", mem="256Mi")
                    for i in range(6)]
            # one batch pod holds its own nomination (self-exemption row)
            pods[0].status.nominated_node_name = "n2"
            nominated.add(pods[0])
            return sched, pods

        sched, pods = build()
        pending = sched.schedule_launch(pods)
        assert pending.batch._class_tables is not None  # not demoted
        assert sched._nom_dev is not None               # overlay active
        results = sched.schedule_finish(pending)
        by_name = {r.pod.metadata.name: r.node_name for r in results}
        # nobody lands on the fully reserved n0
        assert "n0" not in by_name.values()
        # classic-kernel control: bit-identical decisions
        sched_c, pods_c = build()
        sched_c.class_scan = False
        results_c = sched_c.schedule(pods_c)
        assert pending.batch._class_tables is not None
        assert sched_c._nom_dev is not None
        assert by_name == {r.pod.metadata.name: r.node_name
                           for r in results_c}


class TestRandomizedChurnParity:
    """Randomized >=100-pod mixed batches (spread carriers + soft credits
    + required anti-affinity + nominated reservations) with node
    add/delete/relabel churn between batches: class scan == classic
    kernel, decision for decision."""

    def _mk_mixed_pod(self, rng, i):
        kind = rng.randrange(4)
        if kind == 0:
            return mk_pod(i, {"app": "web"})                 # spread
        if kind == 1:
            g = f"g{rng.randrange(3)}"
            return soft_anti(mk_pod(i, {"grp": g}), g)       # soft
        if kind == 2:
            c = f"c{rng.randrange(6)}"
            return req_anti(mk_pod(i, {"color": c}), c)      # required anti
        return mk_pod(i, {"plain": "x"})                     # uniform

    def _run(self, class_scan):
        svc = api.Service(
            metadata=api.ObjectMeta(name="web", namespace="default"),
            spec=api.ServiceSpec(selector={"app": "web"}))
        listers = _spread_listers([svc])
        rng = random.Random(77)
        cache = Cache()
        for i in range(24):
            cache.add_node(mk_node(i, zone=f"z{i % 3}"))
        nominated = NominatedPodMap()
        ghost = mk_pod(900, {}, cpu="6", mem="12Gi")
        ghost.status.nominated_node_name = "n1"
        nominated.add(ghost)
        sched = BatchScheduler(cache, listers=listers,
                               weights=dict(WEIGHTS), nominated=nominated)
        sched.class_scan = class_scan
        decisions = []
        next_i = [0]

        def one_batch(n_pods):
            pods = [self._mk_mixed_pod(rng, next_i[0] + j)
                    for j in range(n_pods)]
            next_i[0] += n_pods
            # a couple of batch pods carry their own nomination
            for p in pods[:2]:
                p.status.nominated_node_name = f"n{2 + next_i[0] % 5}"
                nominated.add(p)
            results = sched.schedule(pods)
            for res in results:
                decisions.append((res.pod.metadata.name, res.node_name))
                if res.node_name is not None:
                    nominated.delete(res.pod)
                    _bind(res.pod, res.node_name, cache, None)
            return results

        one_batch(60)
        # epoch churn: add two nodes, delete one, relabel one's zone
        for i in (50, 51):
            cache.add_node(mk_node(i, zone=f"z{i % 3}"))
        names = cache.node_names()
        gone = sched.snapshot.node_infos["n7"].node
        cache.remove_node(gone)
        assert "n7" in names
        old = sched.snapshot.node_infos["n11"].node
        relabeled = api.serde.deepcopy_obj(old)
        relabeled.metadata.labels[api.wellknown.LABEL_ZONE] = "z9"
        cache.update_node(old, relabeled)
        one_batch(60)
        return decisions

    def test_class_equals_classic_under_churn(self):
        fast = self._run(class_scan=True)
        classic = self._run(class_scan=False)
        assert len(fast) == 120
        assert fast == classic

    def test_spread_soft_serial_replay(self):
        """Spread + soft mixed batches replayed against the serial numpy
        oracle (predicates/priorities pod-by-pod), 100+ pods with an
        epoch boundary mid-stream."""
        svc = api.Service(
            metadata=api.ObjectMeta(name="web", namespace="default"),
            spec=api.ServiceSpec(selector={"app": "web"}))
        listers = _spread_listers([svc])
        rng = random.Random(5)
        cache = Cache()
        infos = {}
        for i in range(12):
            n = mk_node(i, zone=f"z{i % 2}")
            cache.add_node(n)
            infos[n.metadata.name] = NodeInfo(n)
        sched = BatchScheduler(cache, listers=listers,
                               weights=dict(WEIGHTS))
        next_i = [0]

        def one_batch(n_pods):
            base = sched._seq_base
            pods = []
            for j in range(n_pods):
                i = next_i[0] + j
                if rng.random() < 0.5:
                    pods.append(mk_pod(i, {"app": "web"}))
                else:
                    g = f"g{rng.randrange(3)}"
                    pods.append(soft_anti(mk_pod(i, {"grp": g}), g))
            next_i[0] += n_pods
            results = sched.schedule(pods)
            row_of = dict(sched.mirror.row_of)
            for j, res in enumerate(results):
                best = _serial_oracle_step(res.pod, infos, listers, row_of,
                                           base + j)
                assert res.node_name == best, (res.pod.metadata.name,
                                               res.node_name, best)
                _bind(res.pod, best, cache, infos)

        one_batch(52)
        for i in (30, 31):
            n = mk_node(i, zone=f"z{i % 2}")
            cache.add_node(n)
            infos[n.metadata.name] = NodeInfo(n)
        gone = infos.pop("n3").node
        cache.remove_node(gone)
        one_batch(52)


class TestChainedSpreadParity:
    """Satellite: the chaining hysteresis special case is gone — spread
    batches chain in the pipelined drain (carried counts ride the usage
    handle) and the chained drain's binds equal the unchained drain's."""

    def _drain(self, chaining):
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.state import Client
        from kubernetes_tpu.utils.features import DEFAULT_FEATURE_GATE
        import time as _time
        DEFAULT_FEATURE_GATE.set("SchedulerDeviceChaining", chaining)
        sched = None
        try:
            client = Client()
            client.services().create(api.Service(
                metadata=api.ObjectMeta(name="web", namespace="default"),
                spec=api.ServiceSpec(selector={"app": "web"})))
            sched = Scheduler(client, batch_size=16)
            sched.informers.start()
            sched.informers.wait_for_cache_sync()
            for i in range(8):
                client.nodes().create(mk_node(i, zone=f"z{i % 2}"))
            for i in range(48):
                client.pods().create(mk_pod(i, {"app": "web"}))
            deadline = _time.time() + 60
            while sched.queue.num_pending() < 48 or \
                    len(sched.cache.node_names()) < 8:
                if _time.time() > deadline:
                    raise RuntimeError("informer sync stalled")
                _time.sleep(0.01)
            sched.algorithm.refresh()
            n = sched.drain_pipelined()
            binds = {p.metadata.name: p.spec.node_name
                     for p in client.pods().list()}
            return n, binds, sched.algorithm.chained_launches
        finally:
            DEFAULT_FEATURE_GATE.set("SchedulerDeviceChaining", True)
            if sched is not None:
                sched.informers.stop()

    def test_chained_equals_unchained_with_spread_groups(self):
        n_seq, seq_binds, _ = self._drain(chaining=False)
        n_chn, chn_binds, chained = self._drain(chaining=True)
        assert n_seq == n_chn == 48
        # the spread batches really chained (the old special case would
        # have flushed every launch back to the sequential path)
        assert chained > 0
        assert seq_binds == chn_binds


class TestSoftGangFallbackCounter:
    """The unconditional gang chunk is gone; the counter stays wired for
    the remaining overflow path."""

    def _sched(self):
        from kubernetes_tpu.scheduler.metrics import SchedulerMetrics
        cache = Cache()
        for i in range(4):
            cache.add_node(mk_node(i))
        sched = BatchScheduler(cache, weights=dict(WEIGHTS))
        sched.sched_metrics = SchedulerMetrics()
        sched.SOFT_SCORE_CHUNK = 8
        sched.gang = object()   # soft_batch_limit only checks presence
        return sched

    def _gang_pod(self, i, group):
        p = soft_anti(mk_pod(i, {"grp": group}), group)
        p.metadata.labels[api.wellknown.LABEL_POD_GROUP] = "tpu-slice"
        return p

    def test_small_union_gang_batch_no_longer_chunks(self):
        sched = self._sched()
        pods = [self._gang_pod(i, f"g{i % 3}") for i in range(24)]
        assert sched.soft_batch_limit(pods) == 24
        assert sched.sched_metrics.topo_inscan_fallbacks.value(
            reason="soft_gang") == 0

    def test_overflowing_gang_batch_counts_soft_gang(self):
        sched = self._sched()
        pods = [self._gang_pod(i, f"u{i}")
                for i in range(sched.SOFT_TERM_CAP + 8)]
        assert sched.soft_batch_limit(pods) == 8
        assert sched.sched_metrics.topo_inscan_fallbacks.value(
            reason="soft_gang") >= 1


class TestGangSoftKernel:
    """Gang batches run the in-scan soft credit tables (trial/committed
    accumulators) — the launch installs them and the whole-batch drain
    still matches the serial expectations for committed gangs."""

    def test_gang_batch_installs_soft_tables(self):
        cache = Cache()
        for i in range(6):
            cache.add_node(mk_node(i))
        sched = BatchScheduler(cache, weights=dict(WEIGHTS))

        class _Gang:
            def batch_groups(self, pods):
                # every pod its own unit (singleton gangs): exercises the
                # gang kernel with soft tables without PodGroup plumbing
                return [([i], None, False, None)
                        for i in range(len(pods))]
        sched.gang = _Gang()
        pods = [soft_anti(mk_pod(i, {"grp": f"g{i % 3}"}), f"g{i % 3}")
                for i in range(12)]
        pending = sched.schedule_launch(pods)
        assert pending.gang_units is not None
        assert pending.batch.soft_dom is not None   # soft tables ride
        results = sched.schedule_finish(pending)
        assert all(r.node_name is not None for r in results)
        # singleton-gang decisions == the plain serial oracle
        infos = {nm: ni for nm, ni in sched.snapshot.node_infos.items()}
        row_of = dict(sched.mirror.row_of)
        replay = {nm: NodeInfo(ni.node) for nm, ni in infos.items()}
        for j, res in enumerate(results):
            best = _serial_oracle_step(res.pod, replay, None, row_of, j)
            assert res.node_name == best, (res.pod.metadata.name,
                                           res.node_name, best)
            bound = api.serde.deepcopy_obj(res.pod)
            bound.spec.node_name = best
            replay[best].add_pod(bound)
