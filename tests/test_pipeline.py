"""Pipelined drain (scheduler.drain_pipelined): device/host overlap with
usage chained on device ahead of the host commit.

Parity property: for residual-free batches the chained usage handle equals
the usage a sequential drain would upload, so the pipelined drain must make
IDENTICAL bind decisions to schedule_pending run to exhaustion. Chain-refusal
paths (foreign cache mutations, static scores, repairable batches) must fall
back to the sequential semantics, never drop pods.
"""

import pytest

from kubernetes_tpu import api
from kubernetes_tpu.api import Quantity
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.state import Client


def make_pod(i, cpu="100m", mem="128Mi"):
    return api.Pod(
        metadata=api.ObjectMeta(name=f"pod-{i}", namespace="default"),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="img",
            resources=api.ResourceRequirements(
                requests={"cpu": Quantity(cpu), "memory": Quantity(mem)}))]))


def make_node(i, cpu="2", mem="4Gi", pods=16):
    alloc = {"cpu": Quantity(cpu), "memory": Quantity(mem),
             "pods": Quantity(pods)}
    return api.Node(
        metadata=api.ObjectMeta(
            name=f"node-{i}",
            labels={api.wellknown.LABEL_HOSTNAME: f"node-{i}"}),
        status=api.NodeStatus(capacity=dict(alloc), allocatable=dict(alloc),
                              conditions=[api.NodeCondition(type="Ready",
                                                            status="True")]))


def build(n_nodes, n_pods, batch_size, shapes=(("100m", "128Mi"),
                                               ("250m", "512Mi"),
                                               ("500m", "1Gi"))):
    client = Client(validate=False)
    sched = Scheduler(client, batch_size=batch_size)
    for i in range(n_nodes):
        node = make_node(i)
        client.nodes().create(node)
        sched.cache.add_node(node)
    for i in range(n_pods):
        cpu, mem = shapes[i % len(shapes)]
        pod = client.pods().create(make_pod(i, cpu, mem))
        sched.queue.add(pod)
    return client, sched


def bind_map(client):
    pods, _ = client.pods().list_rv(namespace=None)
    return {p.metadata.name: p.spec.node_name for p in pods}


def test_pipelined_drain_matches_sequential():
    """Multi-batch drain: pipelined decisions == sequential decisions."""
    client_a, sched_a = build(16, 96, batch_size=16)
    while sched_a.schedule_pending(timeout=0):
        pass
    client_b, sched_b = build(16, 96, batch_size=16)
    n = sched_b.drain_pipelined()
    assert n == 96
    assert bind_map(client_a) == bind_map(client_b)


def test_pipelined_drain_respects_capacity():
    """More pods than capacity: winners fill every slot, losers park."""
    # 4 nodes x 4 pod slots = 16 slots, 40 pods
    client = Client(validate=False)
    sched = Scheduler(client, batch_size=8)
    for i in range(4):
        node = make_node(i, pods=4)
        client.nodes().create(node)
        sched.cache.add_node(node)
    shapes = (("100m", "128Mi"), ("250m", "512Mi"), ("500m", "1Gi"))
    for i in range(40):
        cpu, mem = shapes[i % 3]
        pod = client.pods().create(make_pod(i, cpu, mem))
        sched.queue.add(pod)
    n = sched.drain_pipelined()
    assert n == 16
    bound = [v for v in bind_map(client).values() if v]
    assert len(bound) == 16
    per_node = {}
    for node in bound:
        per_node[node] = per_node.get(node, 0) + 1
    assert all(c == 4 for c in per_node.values())
    assert sched.queue.num_pending() == 40 - 16


def test_pipelined_drain_chain_broken_by_foreign_mutation():
    """A cache mutation from outside the drain must not poison decisions:
    run a drain, mutate, drain again — final state honors the mutation."""
    client, sched = build(8, 24, batch_size=8)
    assert sched.drain_pipelined() == 24
    # foreign mutation: a new empty node joins
    node = make_node(100)
    client.nodes().create(node)
    sched.cache.add_node(node)
    for i in range(200, 208):
        pod = client.pods().create(make_pod(i, "500m", "1Gi"))
        sched.queue.add(pod)
    assert sched.drain_pipelined() == 8
    # the fresh node is emptiest: LeastRequested must put pods there
    assert any(v == "node-100" for v in bind_map(client).values())


def test_pipelined_drain_with_host_port_pods_falls_back():
    """Port-carrying pods make batches non-chainable (repair may demote);
    the drain must still schedule correctly via the sequential fallback."""
    client = Client(validate=False)
    sched = Scheduler(client, batch_size=4)
    for i in range(3):
        node = make_node(i, pods=32)
        client.nodes().create(node)
        sched.cache.add_node(node)
    for i in range(6):
        pod = api.Pod(
            metadata=api.ObjectMeta(name=f"port-{i}", namespace="default"),
            spec=api.PodSpec(containers=[api.Container(
                name="c", image="img",
                ports=[api.ContainerPort(container_port=80, host_port=8080)],
                resources=api.ResourceRequirements(
                    requests={"cpu": Quantity("100m")}))]))
        pod = client.pods().create(pod)
        sched.queue.add(pod)
    n = sched.drain_pipelined()
    # only 3 nodes -> only 3 pods can hold hostPort 8080
    assert n == 3
    holders = [v for v in bind_map(client).values() if v]
    assert sorted(holders) == ["node-0", "node-1", "node-2"]


class TestChainedAffinity:
    """Cross-batch affinity over the chained pipeline: a batch launched
    against its predecessor's UNCOMMITTED state must still honor the
    predecessor's winners — repair validates against stale_winners via the
    BatchOverlay (core.schedule_finish), never by flushing the pipeline."""

    def _anti_pod(self, i):
        pod = make_pod(i)
        pod.metadata.labels["grp"] = "x"
        pod.spec.affinity = api.Affinity(
            pod_anti_affinity=api.PodAntiAffinity(
                required_during_scheduling_ignored_during_execution=[
                    api.PodAffinityTerm(
                        label_selector=api.LabelSelector(
                            match_labels={"grp": "x"}),
                        topology_key=api.wellknown.LABEL_HOSTNAME)]))
        return pod

    def test_cross_batch_anti_affinity_distinct_hosts(self):
        client = Client(validate=False)
        sched = Scheduler(client, batch_size=2)
        for i in range(6):
            node = make_node(i)
            client.nodes().create(node)
            sched.cache.add_node(node)
        for i in range(4):
            sched.queue.add(client.pods().create(self._anti_pod(i)))
        sched.algorithm.refresh()
        n = sched.drain_pipelined()
        assert n == 4
        binds = bind_map(client)
        hosts = [binds[f"pod-{i}"] for i in range(4)]
        assert all(hosts), binds
        assert len(set(hosts)) == 4, f"anti-affinity violated: {binds}"

    def test_cross_batch_waived_affinity_colocates(self):
        """First pod of a self-affine group lands anywhere (waived term);
        every later pod — including ones whose batch chained on the
        first's uncommitted bind — must co-locate in its topology domain."""
        client = Client(validate=False)
        sched = Scheduler(client, batch_size=2)
        for i in range(6):
            node = make_node(i)
            node.metadata.labels[api.wellknown.LABEL_ZONE] = f"zone-{i % 3}"
            client.pods()  # no-op; keep structure clear
            client.nodes().create(node)
            sched.cache.add_node(node)
        for i in range(4):
            pod = make_pod(i)
            pod.metadata.labels["grp"] = "y"
            pod.spec.affinity = api.Affinity(pod_affinity=api.PodAffinity(
                required_during_scheduling_ignored_during_execution=[
                    api.PodAffinityTerm(
                        label_selector=api.LabelSelector(
                            match_labels={"grp": "y"}),
                        topology_key=api.wellknown.LABEL_ZONE)]))
            sched.queue.add(client.pods().create(pod))
        sched.algorithm.refresh()
        n = sched.drain_pipelined()
        assert n == 4
        binds = bind_map(client)
        zones = {binds[f"pod-{i}"] for i in range(4)}
        zone_labels = {f"node-{i}": f"zone-{i % 3}" for i in range(6)}
        assert len({zone_labels[h] for h in zones if h}) == 1, binds


def test_perf_smoke_pipelined_parity_200x1000():
    """Tier-1 perf smoke (small wire-shape fixture on CPU): 200 nodes x
    1000 pods through the PIPELINED drain — commit stage on its own
    thread, device usage chained across batches — must schedule every
    pod and make bit-identical decisions to the serial path
    (schedule_pending run to exhaustion), the same parity bar
    fakecluster.measure_parity's oracle holds the full shape to."""
    n_nodes, n_pods, batch = 200, 1000, 256
    client_a, sched_a = build(n_nodes, n_pods, batch_size=batch)
    while sched_a.schedule_pending(timeout=0):
        pass
    client_b, sched_b = build(n_nodes, n_pods, batch_size=batch)
    # force the commit THREAD even on the CPU backend (where the drain
    # would otherwise run the stage inline): the smoke must cover the
    # overlapped path's chain-validity protocol, not just its bookkeeping
    sched_b._commit_async = True
    n = sched_b.drain_pipelined()
    assert n == n_pods, f"pipelined drain scheduled {n}/{n_pods}"
    serial, piped = bind_map(client_a), bind_map(client_b)
    mismatches = {k: (serial[k], piped.get(k))
                  for k in serial if serial[k] != piped.get(k)}
    assert not mismatches, f"{len(mismatches)} decisions diverged: " \
        f"{dict(list(mismatches.items())[:5])}"
    assert all(v for v in piped.values()), "some pod failed to schedule"
    # the overlap actually engaged: commit stages ran on the commit thread
    assert sched_b.metrics.commit_overlap_duration.count() > 0


def test_pipelined_drain_chains_across_gang_batches():
    """Gang batches chain in BOTH directions now: a singleton batch
    launched after a gang batch rides the gang kernel's post-batch usage
    (trial/commit carry isolates rejected gangs), and the permit-gate
    reservations keep the chain account balanced."""
    from kubernetes_tpu.api.scheduling import PodGroup, PodGroupSpec
    from kubernetes_tpu.api.wellknown import LABEL_POD_GROUP
    client = Client(validate=False)
    sched = Scheduler(client, batch_size=4)
    for i in range(8):
        node = make_node(i, pods=8)
        client.nodes().create(node)
        sched.cache.add_node(node)
    pg = PodGroup(metadata=api.ObjectMeta(name="g1", namespace="default"),
                  spec=PodGroupSpec(min_member=4))
    client.pod_groups("default").create(pg)
    sched.informers.informer_for(PodGroup).indexer.add(pg)
    # batch 1: the whole gang; batches 2-3: singletons chained on it
    for i in range(4):
        pod = make_pod(100 + i)
        pod.metadata.labels[LABEL_POD_GROUP] = "g1"
        sched.queue.add(client.pods().create(pod))
    for i in range(8):
        sched.queue.add(client.pods().create(make_pod(200 + i)))
    sched.algorithm.refresh()
    chained_calls = []
    orig = sched.algorithm.mirror.apply_chained
    sched.algorithm.mirror.apply_chained = \
        lambda *a, **k: (chained_calls.append(1), orig(*a, **k))[1]
    n = sched.drain_pipelined()
    assert n == 12
    binds = bind_map(client)
    assert all(binds[f"pod-{100 + i}"] for i in range(4)), binds
    assert all(binds[f"pod-{200 + i}"] for i in range(8)), binds
    # at least one successor batch launched CHAINED on a predecessor
    # (the gang batch is first in queue order, so the first chained
    # launch necessarily chained across it)
    assert chained_calls, "no launch ever chained across the gang batch"


class TestMirrorGrowAndDirtyScatter:
    """TensorMirror._grow and the apply_dirty packed scatter's
    out-of-range pad-row handling (the pad index is `capacity`, one past
    the last row — it must be DROPPED, never clamped onto the last real
    row or aliased to row 0)."""

    def _snapshot_of(self, nodes):
        from kubernetes_tpu.scheduler.cache import Snapshot
        from kubernetes_tpu.scheduler.nodeinfo import NodeInfo
        snap = Snapshot()
        for n in nodes:
            snap.node_infos[n.metadata.name] = NodeInfo(n)
        return snap

    def test_grow_preserves_rows_and_drops_device_state(self):
        import numpy as np
        from kubernetes_tpu.scheduler.tensorize import TensorMirror
        mirror = TensorMirror(min_capacity=4)
        nodes = [make_node(i) for i in range(4)]
        snap = self._snapshot_of(nodes)
        mirror.apply(snap, [n.metadata.name for n in nodes])
        assert mirror.t.capacity == 4
        mirror.device_cfg_usage()
        assert mirror.device_ready()
        before = {name: mirror.t.alloc[row].copy()
                  for name, row in mirror.row_of.items()}
        # a fifth node forces _grow to the next bucket
        extra = [make_node(10 + i) for i in range(3)]
        for n in extra:
            snap.node_infos[n.metadata.name] = \
                self._snapshot_of([n]).node_infos[n.metadata.name]
        mirror.apply(snap, [n.metadata.name for n in extra])
        # _grow buckets to the default minimum (128), not the next power
        assert mirror.t.capacity == 128
        # grow dropped device handles (shapes changed): full re-upload due
        assert not mirror.device_ready()
        for name, alloc_row in before.items():
            row = mirror.row_of[name]
            assert np.array_equal(mirror.t.alloc[row], alloc_row), name
            assert mirror.t.valid[row]
        assert len(mirror.row_of) == 7
        assert sorted(mirror.name_of[r] for r in mirror.row_of.values()) \
            == sorted(mirror.row_of)
        # and the next device upload serves consistent full-state tensors
        cfg, usage = mirror.device_cfg_usage()
        assert np.array_equal(np.asarray(cfg["alloc"]), mirror.t.alloc)
        assert np.array_equal(np.asarray(usage["used"]), mirror.t.used)

    def test_dirty_scatter_pad_rows_dropped(self):
        """device_cfg_usage pads the dirty index to a power-of-two bucket
        with `capacity` (out of range). The padded scatter must write ONLY
        the real dirty rows — pad slots carry zeros that would wipe row
        state if clamped or wrapped."""
        import numpy as np
        from kubernetes_tpu.scheduler.tensorize import TensorMirror
        mirror = TensorMirror(min_capacity=8)
        nodes = [make_node(i) for i in range(8)]
        snap = self._snapshot_of(nodes)
        mirror.apply(snap, [n.metadata.name for n in nodes])
        mirror.device_cfg_usage()   # full upload; dirty set cleared
        # dirty exactly ONE row -> bucket of 8 means 7 pad slots
        name = nodes[3].metadata.name
        ni = snap.node_infos[name]
        ni.requested.milli_cpu += 500
        mirror._write_row(name, ni)
        assert len(mirror._dirty_rows) == 1
        cfg, usage = mirror.device_cfg_usage()
        assert np.array_equal(np.asarray(usage["used"]), mirror.t.used)
        assert np.array_equal(np.asarray(cfg["alloc"]), mirror.t.alloc)
        # row 0 and the LAST row kept their values (no alias, no clamp)
        assert np.asarray(cfg["valid"])[0] and np.asarray(cfg["valid"])[7]

    @pytest.mark.parametrize("packed", [False, True],
                             ids=["plain_dict", "packed_buffer"])
    def test_apply_dirty_out_of_range_index_is_noop(self, packed):
        """kernels.apply_dirty directly: an all-pad index vector (every
        slot out of range) must leave the device state untouched, as a
        plain dict of rows and cut out of the one packed buffer."""
        import jax.numpy as jnp
        import numpy as np
        from kubernetes_tpu.scheduler.kernels.batch import (apply_dirty,
                                                            pack_inputs)
        N, R = 8, 4
        cfg = {"alloc": jnp.arange(N * R, dtype=jnp.float32).reshape(N, R)}
        usage = {"used": jnp.ones((N, R), jnp.float32)}
        rows = {"idx": np.full((4,), N, np.int32),      # all out of range
                "alloc_rows": np.full((4, R), -7.0, np.float32),  # poison
                "used_rows": np.full((4, R), -7.0, np.float32)}
        if packed:
            rows = pack_inputs(lambda name, a: jnp.asarray(a), rows)
            assert rows.rest == {} and len(rows.layout) == 3
        before_cfg = np.asarray(cfg["alloc"]).copy()
        before_usage = np.asarray(usage["used"]).copy()
        new_cfg, new_usage = apply_dirty(cfg, usage, rows)
        assert np.array_equal(np.asarray(new_cfg["alloc"]), before_cfg)
        assert np.array_equal(np.asarray(new_usage["used"]), before_usage)
