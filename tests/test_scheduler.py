"""M2 scheduler tests: cache assume/expire + O(delta) snapshots, queue
ordering/backoff, kernel parity against the python predicate/priority oracle,
and the end-to-end slice (store -> informers -> batch kernel -> bind).

Modeled on pkg/scheduler/internal/{cache,queue} tests and
core/generic_scheduler_test.go.
"""

import time

import numpy as np
import pytest

import fakecluster
from kubernetes_tpu import api
from kubernetes_tpu.api import Quantity
from kubernetes_tpu.scheduler import (BatchScheduler, Cache, Scheduler,
                                      SchedulingQueue, Snapshot)
from kubernetes_tpu.scheduler import predicates as preds
from kubernetes_tpu.scheduler import priorities as prios
from kubernetes_tpu.scheduler.nodeinfo import NodeInfo
from kubernetes_tpu.state import Client, SharedInformerFactory
from kubernetes_tpu.utils.clock import FakeClock


def make_pod(name, cpu="100m", mem="200Mi", ns="default", node="",
             priority=None, labels=None):
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace=ns, labels=labels or {}),
        spec=api.PodSpec(
            node_name=node, priority=priority,
            containers=[api.Container(
                name="c", image="img",
                resources=api.ResourceRequirements(
                    requests={"cpu": Quantity(cpu), "memory": Quantity(mem)}))]))


def make_node(name, cpu="4", mem="32Gi", pods=110, labels=None, taints=None):
    alloc = {"cpu": Quantity(cpu), "memory": Quantity(mem),
             "pods": Quantity(pods)}
    return api.Node(
        metadata=api.ObjectMeta(name=name, labels=labels or {}),
        spec=api.NodeSpec(taints=taints or []),
        status=api.NodeStatus(
            capacity=dict(alloc), allocatable=dict(alloc),
            conditions=[api.NodeCondition(type="Ready", status="True")]))


class TestNodeInfo:
    def test_accounting(self):
        ni = NodeInfo(make_node("n1"))
        assert ni.allocatable.milli_cpu == 4000
        assert ni.allocatable.allowed_pod_number == 110
        ni.add_pod(make_pod("p1", cpu="500m", mem="1Gi", node="n1"))
        assert ni.requested.milli_cpu == 500
        assert ni.requested.memory == 1024**3
        assert len(ni.pods) == 1
        assert ni.remove_pod(make_pod("p1", cpu="500m", mem="1Gi", node="n1"))
        assert ni.requested.milli_cpu == 0
        assert not ni.remove_pod(make_pod("nope"))

    def test_nonzero_defaults(self):
        ni = NodeInfo(make_node("n1"))
        pod = api.Pod(metadata=api.ObjectMeta(name="empty", namespace="default"),
                      spec=api.PodSpec(containers=[api.Container(name="c")]))
        ni.add_pod(pod)
        # DefaultMilliCPURequest / DefaultMemoryRequest (non_zero.go)
        assert ni.non_zero_requested.milli_cpu == 100
        assert ni.non_zero_requested.memory == 200 * 1024 * 1024
        assert ni.requested.milli_cpu == 0


class TestCache:
    def test_assume_confirm(self):
        cache = Cache()
        cache.add_node(make_node("n1"))
        pod = make_pod("p1", node="n1")
        cache.assume_pod(pod)
        assert cache.is_assumed_pod(pod)
        cache.finish_binding(pod)
        cache.add_pod(pod)  # informer confirmation
        assert not cache.is_assumed_pod(pod)
        snap = Snapshot()
        cache.update_snapshot(snap)
        assert snap.node_infos["n1"].requested.milli_cpu == 100

    def test_assume_expire(self):
        clock = FakeClock()
        cache = Cache(clock=clock, ttl=30)
        cache.add_node(make_node("n1"))
        pod = make_pod("p1", node="n1")
        cache.assume_pod(pod)
        cache.finish_binding(pod)
        clock.step(31)
        assert cache.cleanup_expired_assumed_pods() == 1
        snap = Snapshot()
        cache.update_snapshot(snap)
        assert snap.node_infos["n1"].requested.milli_cpu == 0

    def test_forget(self):
        cache = Cache()
        cache.add_node(make_node("n1"))
        pod = make_pod("p1", node="n1")
        cache.assume_pod(pod)
        cache.forget_pod(pod)
        snap = Snapshot()
        cache.update_snapshot(snap)
        assert len(snap.node_infos["n1"].pods) == 0

    def test_snapshot_is_incremental(self):
        cache = Cache()
        for i in range(10):
            cache.add_node(make_node(f"n{i}"))
        snap = Snapshot()
        dirty = cache.update_snapshot(snap)
        assert len(dirty) == 10
        # no changes -> no dirty nodes
        assert cache.update_snapshot(snap) == []
        cache.assume_pod(make_pod("p1", node="n3"))
        dirty = cache.update_snapshot(snap)
        assert dirty == ["n3"]
        # snapshot is a frozen clone: cache mutations don't leak in
        cache.assume_pod(make_pod("p2", node="n3"))
        assert len(snap.node_infos["n3"].pods) == 1

    def test_node_tree_zone_round_robin(self):
        from kubernetes_tpu.scheduler.cache import NodeTree
        tree = NodeTree()
        for i in range(4):
            tree.add(make_node(f"a{i}", labels={api.wellknown.LABEL_ZONE: "za"}))
        for i in range(2):
            tree.add(make_node(f"b{i}", labels={api.wellknown.LABEL_ZONE: "zb"}))
        order = tree.ordered_names()
        assert tree.num_nodes() == 6
        # zones interleave round-robin (node_tree.go semantics)
        assert order[:4] == ["a0", "b0", "a1", "b1"]
        tree.remove(make_node("a0", labels={api.wellknown.LABEL_ZONE: "za"}))
        assert tree.num_nodes() == 5

    def test_remove_node(self):
        cache = Cache()
        cache.add_node(make_node("n1"))
        snap = Snapshot()
        cache.update_snapshot(snap)
        cache.remove_node(make_node("n1"))
        dirty = cache.update_snapshot(snap)
        assert "n1" in dirty
        assert "n1" not in snap.node_infos


class TestSchedulingQueue:
    def test_priority_then_fifo(self):
        q = SchedulingQueue(clock=FakeClock())
        q.add(make_pod("low1", priority=1))
        q.add(make_pod("high", priority=10))
        q.add(make_pod("low2", priority=1))
        batch = q.pop_batch(10, timeout=0)
        assert [p.metadata.name for p in batch] == ["high", "low1", "low2"]

    def test_pop_batch_limit(self):
        q = SchedulingQueue(clock=FakeClock())
        for i in range(5):
            q.add(make_pod(f"p{i}"))
        assert len(q.pop_batch(3, timeout=0)) == 3
        assert len(q.pop_batch(3, timeout=0)) == 2

    def test_unschedulable_backoff_flush(self):
        clock = FakeClock()
        q = SchedulingQueue(clock=clock)
        q.add(make_pod("p1"))
        (pod,) = q.pop_batch(1, timeout=0)
        cycle = q.scheduling_cycle
        q.add_unschedulable_if_not_present(pod, cycle)
        # parked: no event, not retried yet
        assert q.pop_batch(1, timeout=0) == []
        # a cluster event moves it (still backing off -> backoffQ -> flush)
        q.move_all_to_active_queue()
        clock.step(1.1)  # initial backoff 1s
        batch = q.pop_batch(1, timeout=0)
        assert [p.metadata.name for p in batch] == ["p1"]

    def test_unschedulable_60s_flush(self):
        clock = FakeClock()
        q = SchedulingQueue(clock=clock)
        q.add(make_pod("p1"))
        (pod,) = q.pop_batch(1, timeout=0)
        q.add_unschedulable_if_not_present(pod, q.scheduling_cycle)
        clock.step(61)
        assert len(q.pop_batch(1, timeout=0)) == 1

    def test_move_request_cycle_race(self):
        """A pod failing in a cycle that started before a move request goes to
        backoff, not unschedulable (scheduling_queue.go:294-325)."""
        clock = FakeClock()
        q = SchedulingQueue(clock=clock)
        q.add(make_pod("p1"))
        (pod,) = q.pop_batch(1, timeout=0)
        cycle = q.scheduling_cycle
        q.move_all_to_active_queue()  # event arrives mid-cycle
        q.add_unschedulable_if_not_present(pod, cycle)
        clock.step(1.1)
        assert len(q.pop_batch(1, timeout=0)) == 1

    def test_delete(self):
        q = SchedulingQueue(clock=FakeClock())
        pod = make_pod("p1")
        q.add(pod)
        q.delete(pod)
        assert q.pop_batch(1, timeout=0) == []

    def test_update_reheapifies_on_priority_change(self):
        """activeQ.Update must reorder the heap when priority changes
        (scheduling_queue.go:268; advisor round-1 low finding)."""
        q = SchedulingQueue(clock=FakeClock())
        q.add(make_pod("a", priority=1))
        q.add(make_pod("b", priority=5))
        raised = make_pod("a", priority=50)
        q.update(make_pod("a", priority=1), raised)
        batch = q.pop_batch(2, timeout=0)
        assert [p.metadata.name for p in batch] == ["a", "b"]
        assert batch[0].spec.priority == 50

    def test_deleting_pod_never_pops(self):
        """Pods with a deletion timestamp are dropped at pop time
        (ref: scheduleOne skips DeletionTimestamp pods)."""
        q = SchedulingQueue(clock=FakeClock())
        doomed = make_pod("doomed")
        doomed.metadata.deletion_timestamp = "2026-01-01T00:00:00Z"
        q.add(doomed)
        q.add(make_pod("ok"))
        batch = q.pop_batch(5, timeout=0)
        assert [p.metadata.name for p in batch] == ["ok"]


def build_scheduler_state(nodes, existing_pods):
    cache = Cache()
    for n in nodes:
        cache.add_node(n)
    for p in existing_pods:
        cache.add_pod(p)
    return cache


class TestKernelParity:
    """The TPU kernel must agree with the python predicate/priority oracle
    (the reference's semantics) on feasibility and resource scores."""

    def _random_cluster(self, seed, n_nodes=17, n_existing=40):
        rng = np.random.RandomState(seed)
        nodes = []
        for i in range(n_nodes):
            nodes.append(make_node(
                f"n{i}", cpu=str(int(rng.choice([2, 4, 8]))),
                mem=f"{int(rng.choice([8, 16, 32]))}Gi",
                pods=int(rng.choice([5, 110]))))
        existing = []
        for i in range(n_existing):
            existing.append(make_pod(
                f"e{i}", cpu=f"{int(rng.randint(50, 2000))}m",
                mem=f"{int(rng.randint(64, 4096))}Mi",
                node=f"n{int(rng.randint(0, n_nodes))}"))
        return nodes, existing

    def test_filter_score_parity(self):
        nodes, existing = self._random_cluster(seed=7)
        cache = build_scheduler_state(nodes, existing)
        sched = BatchScheduler(cache)
        sched.refresh()
        rng = np.random.RandomState(1)
        pods = [make_pod(f"p{i}", cpu=f"{int(rng.randint(100, 3000))}m",
                         mem=f"{int(rng.randint(100, 8000))}Mi")
                for i in range(23)]
        from kubernetes_tpu.scheduler.kernels import filter_score
        from kubernetes_tpu.scheduler.tensorize import PodBatchTensors
        batch = PodBatchTensors(pods, sched.mirror, sched.terms)
        node_cfg, usage = sched.mirror.device_cfg_usage()
        fits, score = filter_score(node_cfg, usage, batch.device())
        fits = np.asarray(fits)
        score = np.asarray(score)
        weights = {"LeastRequestedPriority": 1, "BalancedResourceAllocation": 1}
        for i, pod in enumerate(pods):
            meta = preds.PredicateMetadata(pod, sched.snapshot.node_infos)
            pmeta = prios.PriorityMetadata(pod)
            oracle_scores = prios.prioritize_nodes(
                pod, pmeta, sched.snapshot.node_infos, weights)
            for name, ni in sched.snapshot.node_infos.items():
                row = sched.mirror.row_of[name]
                ok, _ = preds.pod_fits_on_node(pod, meta, ni)
                assert fits[i, row] == ok, (pod.metadata.name, name)
                if ok:
                    assert int(score[i, row]) == oracle_scores[name], \
                        (pod.metadata.name, name)

    def test_schedule_batch_serial_parity(self):
        """The scan must equal a serial python loop: schedule one pod at a
        time against an updating cache (the reference's semantics)."""
        nodes, existing = self._random_cluster(seed=13, n_nodes=9)
        rng = np.random.RandomState(3)
        pods = [make_pod(f"p{i}", cpu=f"{int(rng.randint(200, 2500))}m",
                         mem=f"{int(rng.randint(200, 6000))}Mi")
                for i in range(31)]
        # kernel path: one batch
        cache_k = build_scheduler_state(nodes, existing)
        sched_k = BatchScheduler(cache_k)
        results = sched_k.schedule(pods)
        # oracle path: serial greedy with the same scoring
        cache_o = build_scheduler_state(nodes, existing)
        snap = Snapshot()
        cache_o.update_snapshot(snap)
        weights = {"LeastRequestedPriority": 1, "BalancedResourceAllocation": 1}
        for res in results:
            pod = res.pod
            meta = preds.PredicateMetadata(pod, snap.node_infos)
            pmeta = prios.PriorityMetadata(pod)
            feasible = {}
            for name, ni in snap.node_infos.items():
                ok, _ = preds.pod_fits_on_node(pod, meta, ni)
                if ok:
                    feasible[name] = ni
            if not feasible:
                assert res.node_name is None, res.pod.metadata.name
                continue
            scores = prios.prioritize_nodes(pod, pmeta, snap.node_infos, weights)
            best = max(scores[n] for n in feasible)
            # kernel must pick some max-score feasible node (tie order differs:
            # argmax-first vs the reference's round-robin)
            assert res.node_name in feasible
            assert scores[res.node_name] == best
            # apply the kernel's actual choice to the oracle cache so both
            # sides see identical subsequent state
            bound = api.serde.deepcopy_obj(pod)
            bound.spec.node_name = res.node_name
            cache_o.add_pod(bound)
            cache_o.update_snapshot(snap)

    def test_taints_and_selector(self):
        n_ok = make_node("ok", labels={"disk": "ssd"})
        n_taint = make_node("tainted", labels={"disk": "ssd"},
                            taints=[api.Taint(key="k", value="v", effect="NoSchedule")])
        n_label = make_node("hdd", labels={"disk": "hdd"})
        cache = build_scheduler_state([n_ok, n_taint, n_label], [])
        sched = BatchScheduler(cache)
        pod = make_pod("p")
        pod.spec.node_selector = {"disk": "ssd"}
        (res,) = sched.schedule([pod])
        assert res.node_name == "ok"
        # a toleration opens the tainted node
        pod2 = make_pod("p2")
        pod2.spec.node_selector = {"disk": "ssd"}
        pod2.spec.tolerations = [api.Toleration(key="k", operator="Equal", value="v",
                                                effect="NoSchedule")]
        # fill "ok" so the tainted node wins
        for i in range(3):
            cache.add_pod(make_pod(f"filler{i}", cpu="1000m", mem="4Gi", node="ok"))
        (res2,) = sched.schedule([pod2])
        assert res2.node_name == "tainted"

    def test_unschedulable_when_full(self):
        node = make_node("n1", cpu="1", mem="1Gi")
        cache = build_scheduler_state([node], [])
        sched = BatchScheduler(cache)
        (res,) = sched.schedule([make_pod("big", cpu="2", mem="512Mi")])
        assert res.node_name is None
        err = sched.explain(res.pod)
        assert "Insufficient cpu" in err.error()

    def test_host_name_pin(self):
        nodes = [make_node(f"n{i}") for i in range(4)]
        cache = build_scheduler_state(nodes, [])
        sched = BatchScheduler(cache)
        pod = make_pod("pinned")
        pod.spec.node_name = ""  # scheduled normally first
        pod2 = make_pod("pinned2")
        pod2.spec.node_name = "n2"
        results = sched.schedule([pod2])
        assert results[0].node_name == "n2"


def test_floor_tenths_is_the_reference_integer_division():
    """LeastRequested's (cap-req)*10 // cap without a divide: exact on
    every boundary, for benign divisors (4000) and for ones whose f32
    reciprocal rounds down (3900, 110 — where the TPU's divide scored
    every exact multiple one point low)."""
    from kubernetes_tpu.scheduler.kernels.batch import _floor_tenths
    for den, step in ((1, 1), (7, 1), (110, 1), (3900, 1), (4000, 1),
                      (31 << 30, 1 << 20)):
        num = np.arange(0, den + 1, step, dtype=np.int64)
        num = num[:: max(1, len(num) // 20000)]
        got = np.asarray(_floor_tenths(num.astype(np.float32),
                                       np.float32(den)))
        assert (got == (10 * num) // den).all(), den


class TestFullPriorityParity:
    """M3: all 8 default priorities — kernel+ScoreCompiler choice must land on
    an oracle-max node (prioritize_nodes over the feasible set)."""

    def _cluster(self):
        nodes, existing, services = [], [], []
        rng = np.random.RandomState(42)
        for i in range(12):
            labels = {"kubernetes.io/hostname": f"n{i}",
                      api.wellknown.LABEL_ZONE: f"zone-{i % 3}",
                      "tier": "gold" if i % 2 == 0 else "silver"}
            taints = []
            if i % 4 == 0:
                taints.append(api.Taint(key="soft", value="x",
                                        effect="PreferNoSchedule"))
            n = make_node(f"n{i}", cpu=str(int(rng.choice([4, 8]))),
                          mem=f"{int(rng.choice([16, 32]))}Gi",
                          labels=labels, taints=taints)
            if i % 3 == 0:
                n.status.images = [api.ContainerImage(
                    names=["img"], size_bytes=500 * 1024 * 1024)]
            nodes.append(n)
        for i in range(30):
            existing.append(make_pod(
                f"e{i}", cpu=f"{int(rng.randint(100, 1500))}m",
                mem=f"{int(rng.randint(128, 2048))}Mi",
                node=f"n{int(rng.randint(0, 12))}",
                labels={"app": "web" if i % 2 == 0 else "db"}))
        svc = api.Service(metadata=api.ObjectMeta(name="web", namespace="default"),
                          spec=api.ServiceSpec(selector={"app": "web"}))
        services.append(svc)
        return nodes, existing, services

    def _make_test_pods(self):
        pods = []
        p = make_pod("plain", cpu="300m", mem="256Mi")
        pods.append(p)
        p = make_pod("spread", cpu="200m", mem="256Mi", labels={"app": "web"})
        pods.append(p)
        p = make_pod("nodeaff", cpu="200m", mem="256Mi")
        p.spec.affinity = api.Affinity(node_affinity=api.NodeAffinity(
            preferred_during_scheduling_ignored_during_execution=[
                api.PreferredSchedulingTerm(
                    weight=80,
                    preference=api.NodeSelectorTerm(match_expressions=[
                        api.NodeSelectorRequirement(
                            key="tier", operator="In", values=["gold"])]))]))
        pods.append(p)
        p = make_pod("podaff", cpu="200m", mem="256Mi")
        p.spec.affinity = api.Affinity(pod_affinity=api.PodAffinity(
            preferred_during_scheduling_ignored_during_execution=[
                api.WeightedPodAffinityTerm(
                    weight=50,
                    pod_affinity_term=api.PodAffinityTerm(
                        label_selector=api.LabelSelector(match_labels={"app": "db"}),
                        topology_key=api.wellknown.LABEL_ZONE))]))
        pods.append(p)
        p = make_pod("imgpod", cpu="200m", mem="256Mi")
        p.spec.containers[0].image = "img"
        pods.append(p)
        return pods

    def test_choice_matches_oracle(self):
        nodes, existing, services = self._cluster()
        for pod in self._make_test_pods():
            cache = build_scheduler_state(nodes, existing)
            listers = prios.SpreadListers(services=lambda ns: services)
            sched = BatchScheduler(cache, listers=listers)
            (res,) = sched.schedule([pod])
            assert res.node_name is not None, pod.metadata.name
            # oracle: feasible set, then full default prioritization over it
            snap = Snapshot()
            cache.update_snapshot(snap)
            meta = preds.PredicateMetadata(pod, snap.node_infos)
            feasible = {n: ni for n, ni in snap.node_infos.items()
                        if preds.pod_fits_on_node(pod, meta, ni)[0]}
            assert res.node_name in feasible, pod.metadata.name
            pmeta = prios.PriorityMetadata(pod, listers)
            scores = prios.prioritize_nodes(pod, pmeta, feasible,
                                            all_node_infos=snap.node_infos)
            best = max(scores.values())
            assert scores[res.node_name] == best, (
                pod.metadata.name, res.node_name, scores)


class TestResidualPredicates:
    """MatchInterPodAffinity / NoDiskConflict / host-port conflicts run on the
    host (pre-kernel mask + in-batch repair) and must hold through the real
    scheduling path."""

    def test_required_anti_affinity_blocks_node(self):
        n1 = make_node("n1", labels={"kubernetes.io/hostname": "n1"})
        n2 = make_node("n2", labels={"kubernetes.io/hostname": "n2"})
        existing = make_pod("web", node="n1", labels={"app": "web"})
        cache = build_scheduler_state([n1, n2], [existing])
        sched = BatchScheduler(cache)
        pod = make_pod("p", labels={"app": "web"})
        pod.spec.affinity = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[
                api.PodAffinityTerm(
                    label_selector=api.LabelSelector(match_labels={"app": "web"}),
                    topology_key="kubernetes.io/hostname")]))
        (res,) = sched.schedule([pod])
        assert res.node_name == "n2"

    def test_existing_pod_anti_affinity_blocks_incoming(self):
        """An EXISTING pod's required anti-affinity must repel matching
        incoming pods (the symmetric case)."""
        n1 = make_node("n1", labels={"kubernetes.io/hostname": "n1"})
        n2 = make_node("n2", labels={"kubernetes.io/hostname": "n2"})
        guard = make_pod("guard", node="n1", labels={"app": "guard"})
        guard.spec.affinity = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[
                api.PodAffinityTerm(
                    label_selector=api.LabelSelector(match_labels={"app": "web"}),
                    topology_key="kubernetes.io/hostname")]))
        cache = build_scheduler_state([n1, n2], [guard])
        sched = BatchScheduler(cache)
        (res,) = sched.schedule([make_pod("p", labels={"app": "web"})])
        assert res.node_name == "n2"

    def test_required_affinity_needs_match(self):
        n1 = make_node("n1", labels={"kubernetes.io/hostname": "n1"})
        n2 = make_node("n2", labels={"kubernetes.io/hostname": "n2"})
        buddy = make_pod("buddy", node="n2", labels={"app": "db"})
        cache = build_scheduler_state([n1, n2], [buddy])
        sched = BatchScheduler(cache)
        pod = make_pod("p")
        pod.spec.affinity = api.Affinity(pod_affinity=api.PodAffinity(
            required_during_scheduling_ignored_during_execution=[
                api.PodAffinityTerm(
                    label_selector=api.LabelSelector(match_labels={"app": "db"}),
                    topology_key="kubernetes.io/hostname")]))
        (res,) = sched.schedule([pod])
        assert res.node_name == "n2"

    def test_in_batch_host_port_conflict(self):
        """Two pods wanting the same hostPort in ONE batch may not share a
        node; the loser retries and lands on the second node next cycle."""
        cache = build_scheduler_state([make_node("n1"), make_node("n2")], [])
        sched = BatchScheduler(cache)

        def port_pod(name):
            p = make_pod(name)
            p.spec.containers[0].ports = [api.ContainerPort(container_port=80,
                                                            host_port=8080)]
            return p

        results = sched.schedule([port_pod("a"), port_pod("b")])
        placed = [r for r in results if r.node_name]
        retried = [r for r in results if r.retry]
        # same score class -> the kernel may pick the same node for both;
        # repair must then demote exactly one
        if len(placed) == 2:
            assert placed[0].node_name != placed[1].node_name
        else:
            assert len(placed) == 1 and len(retried) == 1
            # loser schedules cleanly once the winner is in the cache
            bound = api.serde.deepcopy_obj(placed[0].pod)
            bound.spec.node_name = placed[0].node_name
            cache.add_pod(bound)
            (res2,) = sched.schedule([retried[0].pod])
            assert res2.node_name is not None
            assert res2.node_name != placed[0].node_name

    def test_in_batch_anti_affinity(self):
        """Pod B's required anti-affinity against pod A must hold even when A
        was bound earlier in the same batch."""
        n1 = make_node("n1", labels={"kubernetes.io/hostname": "n1"})
        n2 = make_node("n2", labels={"kubernetes.io/hostname": "n2"})
        cache = build_scheduler_state([n1, n2], [])
        sched = BatchScheduler(cache)
        a = make_pod("a", labels={"app": "web"})
        b = make_pod("b", labels={"app": "web"})
        b.spec.affinity = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[
                api.PodAffinityTerm(
                    label_selector=api.LabelSelector(match_labels={"app": "web"}),
                    topology_key="kubernetes.io/hostname")]))
        results = sched.schedule([a, b])
        ra, rb = results
        assert ra.node_name is not None
        if rb.node_name is not None:
            assert rb.node_name != ra.node_name
        else:
            assert rb.retry

    def test_plain_pod_after_anti_affinity_winner(self):
        """A winner's required anti-affinity constrains LATER pods in the
        batch even when those pods carry no constraints of their own."""
        n1 = make_node("n1", labels={"kubernetes.io/hostname": "n1"})
        cache = build_scheduler_state([n1], [])
        sched = BatchScheduler(cache)
        a = make_pod("a")
        a.spec.affinity = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[
                api.PodAffinityTerm(
                    label_selector=api.LabelSelector(match_labels={"app": "x"}),
                    topology_key="kubernetes.io/hostname")]))
        b = make_pod("b", labels={"app": "x"})
        ra, rb = sched.schedule([a, b])
        assert ra.node_name == "n1"
        # the in-scan carry counters (direction 2: winner CARRIES the anti
        # term, b merely matches it) block b inside the kernel itself —
        # the serial semantics directly, with no repair demotion, so b
        # parks as unschedulable instead of burning a retry round
        assert rb.node_name is None and not rb.retry

    def test_disk_conflict(self):
        n1 = make_node("n1")
        existing = make_pod("holder", node="n1")
        existing.spec.volumes = [api.Volume(
            name="d", gce_persistent_disk={"pdName": "disk-1"})]
        cache = build_scheduler_state([n1], [existing])
        sched = BatchScheduler(cache)
        pod = make_pod("p")
        pod.spec.volumes = [api.Volume(
            name="d", gce_persistent_disk={"pdName": "disk-1"})]
        (res,) = sched.schedule([pod])
        assert res.node_name is None

    def test_max_gce_pd_volume_count(self):
        """MaxGCEPDVolumeCount (defaults.go:40-56): a node at the 16-disk
        attach limit rejects another PD pod."""
        n1 = make_node("n1")
        existing = []
        for i in range(16):
            holder = make_pod(f"h{i}", cpu="10m", mem="8Mi", node="n1")
            holder.spec.volumes = [api.Volume(
                name="d", gce_persistent_disk={"pdName": f"disk-{i}",
                                               "readOnly": True})]
            existing.append(holder)
        cache = build_scheduler_state([n1], existing)
        sched = BatchScheduler(cache)
        pod = make_pod("p", cpu="10m", mem="8Mi")
        pod.spec.volumes = [api.Volume(
            name="d", gce_persistent_disk={"pdName": "disk-new"})]
        (res,) = sched.schedule([pod])
        assert res.node_name is None
        # a shared, already-attached disk does not add to the count
        # (read-only on both sides, so NoDiskConflict permits the share)
        pod2 = make_pod("p2", cpu="10m", mem="8Mi")
        pod2.spec.volumes = [api.Volume(
            name="d", gce_persistent_disk={"pdName": "disk-0",
                                           "readOnly": True})]
        (res2,) = sched.schedule([pod2])
        assert res2.node_name == "n1"

    def test_max_volume_count_in_batch(self):
        """Attach limits count earlier winners in the SAME batch (the serial
        reference sees them via assume between iterations)."""
        n1 = make_node("n1")
        existing = []
        for i in range(15):
            holder = make_pod(f"h{i}", cpu="10m", mem="8Mi", node="n1")
            holder.spec.volumes = [api.Volume(
                name="d", gce_persistent_disk={"pdName": f"disk-{i}"})]
            existing.append(holder)
        cache = build_scheduler_state([n1], existing)
        sched = BatchScheduler(cache)
        a = make_pod("a", cpu="10m", mem="8Mi")
        a.spec.volumes = [api.Volume(
            name="d", gce_persistent_disk={"pdName": "disk-a"})]
        b = make_pod("b", cpu="10m", mem="8Mi")
        b.spec.volumes = [api.Volume(
            name="d", gce_persistent_disk={"pdName": "disk-b"})]
        ra, rb = sched.schedule([a, b])
        assert ra.node_name == "n1"          # 16th disk fits
        assert rb.node_name is None and rb.retry  # 17th demoted

    def test_csi_volume_count(self):
        """MaxCSIVolumeCountPred: per-driver limit from node allocatable
        attachable-volumes-csi-<driver> (csi_volume_predicate.go)."""
        from kubernetes_tpu.scheduler.predicates import (
            PredicateMetadata, csi_max_volume_count_factory)
        n1 = make_node("n1")
        n1.status.allocatable["attachable-volumes-csi-d1"] = Quantity(1)
        pvs = {}
        pvcs = {}
        for i in range(2):
            pvs[f"pv{i}"] = api.PersistentVolume(
                metadata=api.ObjectMeta(name=f"pv{i}"),
                spec=api.PersistentVolumeSpec(
                    csi={"driver": "d1", "volumeHandle": f"h{i}"}))
            pvcs[("default", f"c{i}")] = api.PersistentVolumeClaim(
                metadata=api.ObjectMeta(name=f"c{i}", namespace="default"),
                spec=api.PersistentVolumeClaimSpec(volume_name=f"pv{i}"))
        pred = csi_max_volume_count_factory(
            lambda ns, name: pvcs.get((ns, name)),
            lambda name: pvs.get(name))
        holder = make_pod("holder", node="n1")
        holder.spec.volumes = [api.Volume(
            name="v", persistent_volume_claim=
            api.PersistentVolumeClaimVolumeSource(claim_name="c0"))]
        ni = NodeInfo(n1)
        ni.add_pod(holder)
        pod = make_pod("p")
        pod.spec.volumes = [api.Volume(
            name="v", persistent_volume_claim=
            api.PersistentVolumeClaimVolumeSource(claim_name="c1"))]
        ok, reasons = pred(pod, None, ni)
        assert not ok and "max volume count" in reasons[0]
        # same volume already attached -> fits
        pod2 = make_pod("p2")
        pod2.spec.volumes = [api.Volume(
            name="v", persistent_volume_claim=
            api.PersistentVolumeClaimVolumeSource(claim_name="c0"))]
        ok2, _ = pred(pod2, None, ni)
        assert ok2


class TestPreemption:
    """Mirrors generic_scheduler.go Preempt/selectVictimsOnNode/
    pickOneNodeForPreemption semantics (:310-369, :837-962, :1054-1128)."""

    def _fits(self, pod, meta, ni):
        ok, _ = preds.pod_fits_on_node(pod, meta, ni)
        return ok

    def test_select_victims_basic(self):
        from kubernetes_tpu.scheduler.preemption import select_victims_on_node
        node = make_node("n1", cpu="1", mem="1Gi")
        ni = NodeInfo(node)
        low = make_pod("low", cpu="800m", priority=1, node="n1")
        ni.add_pod(low)
        pod = make_pod("high", cpu="500m", priority=100)
        sel = select_victims_on_node(pod, ni, {"n1": ni}, self._fits, [])
        assert sel is not None
        victims, nviol = sel
        assert [v.metadata.name for v in victims] == ["low"]
        assert nviol == 0

    def test_select_victims_reprieves_what_fits(self):
        """Only as many victims as needed are evicted; the rest are
        reprieved, most important first."""
        from kubernetes_tpu.scheduler.preemption import select_victims_on_node
        node = make_node("n1", cpu="2", mem="4Gi")
        ni = NodeInfo(node)
        for name, cpu, prio in (("a", "800m", 5), ("b", "800m", 3),
                                ("c", "300m", 1)):
            ni.add_pod(make_pod(name, cpu=cpu, priority=prio, node="n1"))
        # needs 900m; freeing c (300m) is not enough, b (800m) suffices
        pod = make_pod("high", cpu="900m", priority=100)
        sel = select_victims_on_node(pod, ni, {"n1": ni}, self._fits, [])
        assert sel is not None
        victims, _ = sel
        # a (most important) reprieved first, then b can't come back
        # (a + b + 900m > 2 CPU), then c fits again
        assert [v.metadata.name for v in victims] == ["b"]

    def test_select_victims_no_lower_priority(self):
        from kubernetes_tpu.scheduler.preemption import select_victims_on_node
        ni = NodeInfo(make_node("n1", cpu="1"))
        ni.add_pod(make_pod("peer", cpu="800m", priority=100, node="n1"))
        pod = make_pod("high", cpu="500m", priority=100)
        assert select_victims_on_node(pod, ni, {"n1": ni},
                                      self._fits, []) is None

    def test_pdb_violation_accounting(self):
        from kubernetes_tpu.scheduler.preemption import \
            filter_pods_with_pdb_violation
        pdb = api.PodDisruptionBudget(
            metadata=api.ObjectMeta(name="pdb", namespace="default"),
            spec=api.PodDisruptionBudgetSpec(
                selector=api.LabelSelector(match_labels={"app": "x"})),
            status=api.PodDisruptionBudgetStatus(disruptions_allowed=1))
        pods = [make_pod(f"p{i}", labels={"app": "x"}) for i in range(3)]
        violating, ok = filter_pods_with_pdb_violation(pods, [pdb])
        # one disruption allowed: first pod ok, the rest violate
        assert [p.metadata.name for p in ok] == ["p0"]
        assert [p.metadata.name for p in violating] == ["p1", "p2"]

    def test_pick_one_node_tiebreaks(self):
        from kubernetes_tpu.scheduler.preemption import \
            pick_one_node_for_preemption
        v = lambda prio, start="2026-01-01T00:00:00Z": api.Pod(
            metadata=api.ObjectMeta(name=f"v{prio}-{start[-3:]}",
                                    namespace="default"),
            spec=api.PodSpec(priority=prio),
            status=api.PodStatus(start_time=start))
        # fewest PDB violations wins
        assert pick_one_node_for_preemption(
            {"a": ([v(5)], 1), "b": ([v(5)], 0)}) == "b"
        # lowest highest-victim priority wins
        assert pick_one_node_for_preemption(
            {"a": ([v(9)], 0), "b": ([v(5)], 0)}) == "b"
        # smallest priority sum wins
        assert pick_one_node_for_preemption(
            {"a": ([v(5), v(4)], 0), "b": ([v(5), v(1)], 0)}) == "b"
        # fewest victims wins
        assert pick_one_node_for_preemption(
            {"a": ([v(5), v(5)], 0), "b": ([v(5)], 0)}) == "b"
        # latest start of highest-priority victim wins
        assert pick_one_node_for_preemption(
            {"a": ([v(5, "2026-01-01T00:00:00Z")], 0),
             "b": ([v(5, "2026-06-01T00:00:00Z")], 0)}) == "b"

    def test_eligibility_waits_for_terminating_victims(self):
        from kubernetes_tpu.scheduler.preemption import \
            pod_eligible_to_preempt_others
        ni = NodeInfo(make_node("n1"))
        dying = make_pod("dying", priority=1, node="n1")
        dying.metadata.deletion_timestamp = "2026-01-01T00:00:00Z"
        ni.add_pod(dying)
        pod = make_pod("high", priority=100)
        pod.status.nominated_node_name = "n1"
        assert not pod_eligible_to_preempt_others(pod, {"n1": ni})
        pod2 = make_pod("fresh", priority=100)
        assert pod_eligible_to_preempt_others(pod2, {"n1": ni})

    def test_batch_preempt_picks_min_victim_node(self):
        """BatchScheduler.preempt: candidates screened by tensors, victims
        chosen per node, tie-breaks applied."""
        cache = Cache()
        n1, n2 = make_node("n1", cpu="1"), make_node("n2", cpu="1")
        cache.add_node(n1)
        cache.add_node(n2)
        # n1 holds a priority-5 pod, n2 a priority-2 pod: n2's victim set
        # has lower max priority
        cache.add_pod(make_pod("v1", cpu="800m", priority=5, node="n1"))
        cache.add_pod(make_pod("v2", cpu="800m", priority=2, node="n2"))
        sched = BatchScheduler(cache)
        sched.refresh()
        pod = make_pod("high", cpu="500m", priority=100)
        plan = sched.preempt(pod)
        assert plan is not None
        assert plan.node_name == "n2"
        assert [v.metadata.name for v in plan.victims] == ["v2"]

    def test_nominated_reservation_shields_space(self):
        """A nominated pod's space is invisible to other pods (kernel
        reservation tensors) but usable by the nominee itself."""
        from kubernetes_tpu.scheduler.queue import NominatedPodMap
        cache = Cache()
        cache.add_node(make_node("only", cpu="1", mem="1Gi", pods=10))
        nominated = NominatedPodMap()
        nominee = make_pod("nominee", cpu="600m", priority=100)
        nominee.status.nominated_node_name = "only"
        nominated.add(nominee)
        sched = BatchScheduler(cache, nominated=nominated)
        # an unrelated pod that needs the reserved space must NOT fit
        (res,) = sched.schedule([make_pod("thief", cpu="600m", priority=1)])
        assert res.node_name is None
        # the nominee itself lands (its own reservation is subtracted)
        (res2,) = sched.schedule([nominee])
        assert res2.node_name == "only"

    def test_end_to_end_preemption(self):
        """High-priority pod evicts a low-priority pod and lands
        (ref: test/integration/scheduler preemption tests)."""
        client = Client()
        client.nodes().create(make_node("only", cpu="1", mem="1Gi", pods=5))
        sched = Scheduler(client, batch_size=8)
        sched.start()
        try:
            client.pods().create(make_pod("low", cpu="700m", priority=1))
            deadline = time.time() + 30
            while time.time() < deadline:
                if client.pods().get("low").spec.node_name:
                    break
                time.sleep(0.05)
            assert client.pods().get("low").spec.node_name == "only"
            client.pods().create(make_pod("high", cpu="700m", priority=100))
            deadline = time.time() + 30
            high_bound = False
            while time.time() < deadline:
                try:
                    high = client.pods().get("high")
                except Exception:
                    break
                if high.spec.node_name:
                    high_bound = True
                    break
                time.sleep(0.05)
            assert high_bound, "high-priority pod never landed"
            assert client.pods().get("high").spec.node_name == "only"
            # the victim is gone
            names = [p.metadata.name for p in client.pods().list()]
            assert "low" not in names
            # the bare preemption_count attribute is gone: the registry
            # family is the one source of preemption accounting
            assert sched.metrics.preemption_attempts.value() == 1
            events = client.events("default").list()
            assert any(e.reason == "Preempted" for e in events)
        finally:
            sched.stop()


class TestDecisionParity:
    @pytest.mark.parametrize("variant", fakecluster.PARITY_VARIANTS)
    def test_batch_matches_serial_oracle(self, variant):
        """The north star's bind-decision-parity claim, measured: the batch
        path's decisions equal a serial python oracle replaying the
        reference's per-pod loop (predicates + priorities + the kernel's
        tie-break) over the same fixture in the same order — on every
        fixture variant."""
        rate, _, _ = fakecluster.measure_parity(variant, n_pods=120,
                                                n_nodes=40)
        assert rate == 1.0, f"{variant} parity {rate:.4f} < 1.0"


class TestEndToEnd:
    """The aha-slice: store -> informers -> queue -> TPU kernel -> bind."""

    def test_schedules_all_pending_pods(self):
        client = Client()
        for i in range(6):
            client.nodes().create(make_node(f"n{i}", cpu="4", mem="8Gi"))
        sched = Scheduler(client, batch_size=64)
        sched.start()
        try:
            for i in range(40):
                client.pods().create(make_pod(f"p{i}", cpu="100m", mem="128Mi"))
            deadline = time.time() + 30
            while time.time() < deadline:
                pods = client.pods().list()
                if all(p.spec.node_name for p in pods) and len(pods) == 40:
                    break
                time.sleep(0.05)
            pods = client.pods().list()
            assert len(pods) == 40
            assert all(p.spec.node_name for p in pods)
            # every pod's PodScheduled condition is set by the bind subresource
            for p in pods:
                assert any(c.type == "PodScheduled" and c.status == "True"
                           for c in p.status.conditions)
            # spreading: least-requested balances across the 6 nodes
            per_node = {}
            for p in pods:
                per_node[p.spec.node_name] = per_node.get(p.spec.node_name, 0) + 1
            assert len(per_node) == 6
            # tie-break is uniform-random within a score class (vs the
            # reference's strict round-robin), so allow a little skew
            assert max(per_node.values()) - min(per_node.values()) <= 4
        finally:
            sched.stop()

    def test_unschedulable_then_node_arrives(self):
        client = Client()
        sched = Scheduler(client, batch_size=8)
        sched.start()
        try:
            client.pods().create(make_pod("stuck", cpu="2", mem="1Gi"))
            time.sleep(0.3)
            pod = client.pods().get("stuck")
            assert pod.spec.node_name == ""
            # a node arriving moves the pod back to active and it schedules
            client.nodes().create(make_node("late", cpu="4", mem="8Gi"))
            deadline = time.time() + 30
            while time.time() < deadline:
                if client.pods().get("stuck").spec.node_name:
                    break
                time.sleep(0.05)
            assert client.pods().get("stuck").spec.node_name == "late"
            # and the failure left a FailedScheduling event
            events = client.events("default").list()
            assert any(e.reason == "FailedScheduling" for e in events)
        finally:
            sched.stop()

    def test_priority_ordering_under_scarcity(self):
        """Higher-priority pods get the scarce node."""
        client = Client()
        client.nodes().create(make_node("only", cpu="1", mem="1Gi", pods=2))
        # create pods BEFORE the scheduler starts so one batch sees both
        client.pods().create(make_pod("low", cpu="600m", mem="256Mi", priority=1))
        client.pods().create(make_pod("high", cpu="600m", mem="256Mi", priority=100))
        sched = Scheduler(client, batch_size=8)
        sched.start()
        try:
            deadline = time.time() + 30
            while time.time() < deadline:
                high = client.pods().get("high")
                if high.spec.node_name:
                    break
                time.sleep(0.05)
            assert client.pods().get("high").spec.node_name == "only"
            assert client.pods().get("low").spec.node_name == ""
        finally:
            sched.stop()

    def test_wait_for_first_consumer_binds_pv(self):
        """Delayed binding end-to-end (ref: scheduler.go:499 assumeVolumes,
        :524 bindVolumes): scheduling a pod with an unbound WFC claim writes
        PV.claimRef and PVC.volumeName; a second pod contending for the same
        single PV stays pending."""
        client = Client()
        client.nodes().create(make_node("n1"))
        client.storage_classes().create(api.StorageClass(
            metadata=api.ObjectMeta(name="wfc"),
            volume_binding_mode="WaitForFirstConsumer"))
        client.persistent_volumes().create(api.PersistentVolume(
            metadata=api.ObjectMeta(name="pv1"),
            spec=api.PersistentVolumeSpec(
                capacity={"storage": Quantity("10Gi")},
                access_modes=["ReadWriteOnce"],
                storage_class_name="wfc")))
        for cname in ("c1", "c2"):
            client.persistent_volume_claims("default").create(
                api.PersistentVolumeClaim(
                    metadata=api.ObjectMeta(name=cname, namespace="default"),
                    spec=api.PersistentVolumeClaimSpec(
                        access_modes=["ReadWriteOnce"],
                        storage_class_name="wfc",
                        resources=api.ResourceRequirements(
                            requests={"storage": Quantity("5Gi")}))))
        sched = Scheduler(client, batch_size=8)
        sched.start()
        try:
            for pname, cname in (("pa", "c1"), ("pb", "c2")):
                pod = make_pod(pname)
                pod.spec.volumes = [api.Volume(
                    name="data", persistent_volume_claim=
                    api.PersistentVolumeClaimVolumeSource(claim_name=cname))]
                client.pods().create(pod)
            deadline = time.time() + 30
            while time.time() < deadline:
                if client.persistent_volumes().get("pv1").spec.claim_ref:
                    break
                time.sleep(0.05)
            pv = client.persistent_volumes().get("pv1")
            assert pv.spec.claim_ref is not None
            winner_claim = pv.spec.claim_ref["name"]
            pvc = client.persistent_volume_claims("default").get(winner_claim)
            assert pvc.spec.volume_name == "pv1"
            bound = [p for p in client.pods().list() if p.spec.node_name]
            assert len(bound) == 1  # the loser found no PV and stays pending
        finally:
            sched.stop()


class TestPreemptionCostBound:
    """VERDICT r2 #7: a high-priority burst onto a large full cluster must
    not pay O(nodes x pods x predicates) host python per pod. The victim
    search runs on at most PREEMPT_CANDIDATE_CAP proxy-ranked candidates."""

    def _full_cluster(self, n_nodes):
        cache = Cache()
        for i in range(n_nodes):
            cache.add_node(make_node(f"n{i}", cpu="1", pods=10))
            # two victims per node, priorities varying so ranking matters
            cache.add_pod(make_pod(f"v{i}a", cpu="500m",
                                   priority=(i % 7) + 1, node=f"n{i}"))
            cache.add_pod(make_pod(f"v{i}b", cpu="400m",
                                   priority=(i % 5) + 1, node=f"n{i}"))
        return cache

    def test_burst_completes_in_seconds(self):
        import time as _t
        cache = self._full_cluster(5000)
        sched = BatchScheduler(cache)
        # pin the SERIAL path: the cap + proxy under test here are its
        # cost bound (the kernel path has no cap — tests/test_preempt.py)
        sched.preempt_kernel = False
        sched.refresh()
        start = _t.time()
        n_preempted = 0
        for i in range(50):
            plan = sched.preempt(make_pod(f"hp{i}", cpu="600m",
                                          priority=1000))
            if plan is not None:
                n_preempted += 1
        elapsed = _t.time() - start
        assert n_preempted == 50
        # uncapped this is minutes (5000 nodes x clone + reprieve per pod);
        # capped at 100 candidates it is well under a second per pod
        assert elapsed < 20.0, f"preemption burst took {elapsed:.1f}s"

    def test_cap_picks_low_priority_candidates(self):
        """With more viable candidates than the cap, the searched subset
        must include the globally best (lowest max-victim-priority) nodes,
        so the final decision matches the uncapped search."""
        cache = Cache()
        for i in range(150):
            cache.add_node(make_node(f"n{i}", cpu="1"))
            # node 120 has the lowest-priority victim in the cluster
            prio = 1 if i == 120 else 5 + (i % 3)
            cache.add_pod(make_pod(f"v{i}", cpu="800m", priority=prio,
                                   node=f"n{i}"))
        sched = BatchScheduler(cache)
        sched.preempt_kernel = False  # the cap is a serial-path concept
        sched.refresh()
        assert sched.PREEMPT_CANDIDATE_CAP < 150
        plan = sched.preempt(make_pod("hp", cpu="500m", priority=100))
        assert plan is not None
        assert plan.node_name == "n120"


class TestPreemptionProxyEquivalence:
    """VERDICT r4 weak #8: the capped preemption path ranks candidates by
    a cheap proxy before running the full victim search on the best CAP.
    These fixtures assert the proxy-capped search picks the SAME node as
    the uncapped full search across adversarial and randomized clusters
    (ref: the full-cluster search in generic_scheduler.go:996 that the
    cap replaces)."""

    def _cluster(self, seed, n_nodes=60):
        import random
        rng = random.Random(seed)
        cache = Cache()
        for i in range(n_nodes):
            cache.add_node(make_node(f"n{i}", cpu="2"))
            # 1-3 victims per node with varied priorities and sizes so
            # victim sets differ in max-priority, sum, and count
            used = 0
            for j in range(rng.randint(1, 3)):
                cpu = rng.choice([400, 600, 800])
                if used + cpu > 1800:
                    break
                used += cpu
                cache.add_pod(make_pod(
                    f"v{i}-{j}", cpu=f"{cpu}m",
                    priority=rng.choice([1, 2, 5, 10]),
                    node=f"n{i}"))
        return cache

    def _plan(self, cache, cap):
        sched = BatchScheduler(cache)
        # the proxy ranking under test only exists on the serial path
        sched.preempt_kernel = False
        sched.PREEMPT_CANDIDATE_CAP = cap
        sched.refresh()
        # 1800m on 2000m nodes with >=400m always in use: the preemptor
        # NEVER fits without victims (the precondition under which
        # preempt runs — it is only called after scheduling failed)
        return sched.preempt(make_pod("boss", cpu="1800m", priority=100))

    def test_capped_matches_full_search_randomized(self):
        for seed in range(6):
            cache = self._cluster(seed)
            full = self._plan(cache, 10_000)   # uncapped: every candidate
            capped = self._plan(cache, 8)      # aggressive cap
            assert full is not None and capped is not None, seed
            assert capped.node_name == full.node_name, (
                f"seed {seed}: proxy-capped pick {capped.node_name} != "
                f"full search {full.node_name}")
            assert sorted(v.metadata.name for v in capped.victims) == \
                sorted(v.metadata.name for v in full.victims), seed

    def test_proxy_prefers_pdb_clean_nodes(self):
        """The proxy's FIRST criterion mirrors pick_one_node's: a node
        whose victims are PDB-covered ranks behind a clean one even when
        its victims are smaller."""
        from kubernetes_tpu.api.policy import (PodDisruptionBudget,
                                               PodDisruptionBudgetSpec)
        cache = Cache()
        cache.add_node(make_node("pdbn", cpu="1"))
        cache.add_node(make_node("clean", cpu="1"))
        guarded = make_pod("g1", cpu="800m", priority=1, node="pdbn")
        guarded.metadata.labels["app"] = "db"
        cache.add_pod(guarded)
        cache.add_pod(make_pod("c1", cpu="800m", priority=5, node="clean"))
        pdb = PodDisruptionBudget(
            metadata=api.ObjectMeta(name="db", namespace="default"),
            spec=PodDisruptionBudgetSpec(
                selector=api.LabelSelector(match_labels={"app": "db"})))
        pdb.status.disruptions_allowed = 0
        sched = BatchScheduler(cache, pdb_lister=lambda: [pdb])
        sched.preempt_kernel = False
        sched.PREEMPT_CANDIDATE_CAP = 1  # the proxy ALONE picks the pool
        sched.refresh()
        plan = sched.preempt(make_pod("boss", cpu="500m", priority=100))
        assert plan is not None
        # despite clean's victim having HIGHER priority (worse by the
        # second criterion), the PDB-free node must win — matching
        # pick_one_node's criterion order
        assert plan.node_name == "clean"


class TestPreemptionProxyScalars:
    def test_tpu_bound_preemptor_ranks_by_tpu_victims(self):
        """The greedy victim estimate must consult extended scalars: a
        preemptor needing google.com/tpu on cpu-rich nodes would
        otherwise estimate empty victim sets everywhere and the cap
        would keep an arbitrary slice."""
        TPU = "google.com/tpu"

        def tpu_node(name, chips):
            n = make_node(name, cpu="16")
            n.status.capacity[TPU] = Quantity(chips)
            n.status.allocatable[TPU] = Quantity(chips)
            return n

        def tpu_pod(name, chips, priority, node=""):
            p = make_pod(name, cpu="100m", priority=priority, node=node)
            p.spec.containers[0].resources.requests[TPU] = Quantity(chips)
            return p
        cache = Cache()
        # many nodes whose TPUs are held by HIGH-priority pods, one node
        # held by a priority-1 pod — the full search must pick that one,
        # and so must the capped proxy
        for i in range(12):
            cache.add_node(tpu_node(f"n{i}", 4))
            cache.add_pod(tpu_pod(f"hold{i}", 4, priority=50,
                                  node=f"n{i}"))
        cache.add_node(tpu_node("cheap", 4))
        cache.add_pod(tpu_pod("cheapie", 4, priority=1, node="cheap"))
        boss = tpu_pod("boss", 4, priority=100)
        full = BatchScheduler(cache)
        full.refresh()
        plan_full = full.preempt(boss)
        capped = BatchScheduler(cache)
        capped.PREEMPT_CANDIDATE_CAP = 3
        capped.refresh()
        plan_capped = capped.preempt(boss)
        assert plan_full is not None and plan_capped is not None
        assert plan_full.node_name == "cheap"
        assert plan_capped.node_name == "cheap"
        assert [v.metadata.name for v in plan_capped.victims] == \
            ["cheapie"]


class TestAlignSplitGate:
    def test_topo_scan_likely_anti_only(self):
        """The drain's power-of-two alignment split applies exactly to
        required-ANTI-affinity batches (measured +30% there, -17% on
        required-affinity batches, -20% on plain ones)."""
        cache = Cache()
        cache.add_node(make_node(
            "n1", labels={api.wellknown.LABEL_HOSTNAME: "n1"}))
        sched = BatchScheduler(cache)
        plain = make_pod("p")
        assert not sched.topo_scan_likely([plain])
        aff = make_pod("a")
        aff.spec.affinity = api.Affinity(pod_affinity=api.PodAffinity(
            required_during_scheduling_ignored_during_execution=[
                api.PodAffinityTerm(
                    label_selector=api.LabelSelector(
                        match_labels={"x": "y"}),
                    topology_key=api.wellknown.LABEL_ZONE)]))
        assert not sched.topo_scan_likely([aff])
        anti = make_pod("z")
        anti.spec.affinity = api.Affinity(
            pod_anti_affinity=api.PodAntiAffinity(
                required_during_scheduling_ignored_during_execution=[
                    api.PodAffinityTerm(
                        label_selector=api.LabelSelector(
                            match_labels={"x": "y"}),
                        topology_key=api.wellknown.LABEL_HOSTNAME)]))
        assert sched.topo_scan_likely([plain, anti])
        # a bound anti carrier in the cluster flips the gate for every
        # batch (the index's carriers constrain any new pod)
        bound = make_pod("carrier", node="n1")
        bound.spec.affinity = anti.spec.affinity
        cache.add_pod(bound)
        sched.refresh()
        assert sched.topo_scan_likely([plain])


class TestCommitOverlaps:
    """Where the pipelined drain's commit stage runs is decided from what
    the scheduler can observe, and from nothing else: an asynchronous
    bind, a backend that is not the CPU, or at least 4 cores put it on
    its own thread; a small CPU-only host keeps it inline."""

    @pytest.mark.parametrize("async_bind, backend, cores, threaded", [
        (True, "cpu", 1, True),
        (False, "cpu", 2, False),
        (False, "cpu", 8, True),
        (False, "tpu", 1, True),
    ])
    def test_table(self, monkeypatch, async_bind, backend, cores,
                   threaded):
        import os
        import jax
        monkeypatch.setenv("KTPU_COMMIT_THREAD", "0" if threaded else "1")
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        monkeypatch.setattr(os, "cpu_count", lambda: cores)
        sched = Scheduler(Client(), batch_size=8, async_bind=async_bind)
        try:
            assert sched._commit_async is None
            assert sched._commit_overlaps() is threaded
            assert sched._commit_async is threaded   # decided once
        finally:
            sched.stop()


class TestRunLoopFailures:
    """A failed scheduling cycle is counted, keeps its pods, and — when it
    repeats — ends the loop, instead of a traceback loop under a green
    /healthz (what a scan the device compiler refuses used to become)."""

    def _cluster(self, n_pods=3):
        client = Client()
        node = client.nodes().create(make_node("n0"))
        sched = Scheduler(client, batch_size=8)
        sched.cache.add_node(node)
        for i in range(n_pods):
            sched.queue.add(client.pods().create(make_pod(f"p{i}")))
        return client, sched

    def test_failed_cycle_requeues_its_pods(self, monkeypatch):
        _, sched = self._cluster()

        def boom(pods):
            raise RuntimeError("scan refused")
        monkeypatch.setattr(sched.algorithm, "schedule", boom)
        with pytest.raises(RuntimeError, match="scan refused"):
            sched.schedule_pending()
        # popped pods live only in the cycle: none may be dropped
        assert sched.queue.num_pending() == 3

    def test_repeated_failure_stops_the_loop(self, monkeypatch):
        from kubernetes_tpu.scheduler.scheduler import MAX_LOOP_ERROR_STREAK
        _, sched = self._cluster()
        calls = []

        def boom(pods):
            calls.append(len(pods))
            raise RuntimeError("scan refused")
        monkeypatch.setattr(sched.algorithm, "schedule", boom)
        fatal = []
        sched.on_fatal = fatal.append
        sched._run_loop()          # returns by itself: no spinning
        assert len(calls) == MAX_LOOP_ERROR_STREAK
        assert isinstance(sched.fatal_error, RuntimeError)
        assert fatal == [sched.fatal_error]
        assert sched.metrics.loop_errors.value() == MAX_LOOP_ERROR_STREAK
        assert "scheduler_loop_errors_total 3" in \
            sched.metrics.registry.expose()

    def test_transient_failure_recovers(self, monkeypatch):
        client, sched = self._cluster()
        real = sched.algorithm.schedule
        state = {"n": 0}

        def flaky(pods):
            state["n"] += 1
            if state["n"] == 1:
                raise RuntimeError("transient")
            out = real(pods)
            sched._stop.set()      # one good cycle, then leave the loop
            return out
        monkeypatch.setattr(sched.algorithm, "schedule", flaky)
        sched._run_loop()
        assert sched.fatal_error is None
        assert sched.metrics.loop_errors.value() == 1
        assert all(p.spec.node_name == "n0" for p in client.pods().list())

    def test_pod_requeued_after_assume_is_not_scheduled_twice(self):
        """Ref skipPodSchedule: an update event that lands while a pod is
        in flight re-adds it to the queue; once the first attempt has
        assumed it, the duplicate must be dropped at its next pop (a gang
        member re-scheduled against its own permit-gate reservation
        failed its whole gang)."""
        client, sched = self._cluster(n_pods=1)
        results = sched.schedule_pending()
        assert [r.node_name for r in results] == ["n0"]
        pod = client.pods().get("p0")
        assert sched.cache.is_assumed_pod(pod)   # no informer confirms it
        sched.queue.update(None, pod)            # the late update event
        assert sched.queue.num_pending() == 1
        assert sched.schedule_pending() == []
        sched.queue.update(None, pod)            # same, pipelined drain
        assert sched.drain_pipelined() == 0
        assert sched.queue.num_pending() == 0 and sched._in_flight == 0

    def test_commit_stage_exception_surfaces_from_drain(self, monkeypatch):
        """drain_pipelined used to swallow the commit thread's exception
        at the end of the drain."""
        _, sched = self._cluster()
        sched._commit_async = True

        def boom(results, cycle):
            raise RuntimeError("commit failed")
        monkeypatch.setattr(sched, "_commit_results", boom)
        with pytest.raises(RuntimeError, match="commit failed"):
            sched.drain_pipelined()
        assert sched._in_flight == 0
