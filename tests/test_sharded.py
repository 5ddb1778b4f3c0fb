"""Tier-1 CPU-sharded smoke: the mesh as the drain's execution substrate.

conftest.py forces 8 virtual CPU devices, so the shard-mapped class scan
(kernels/batch.py schedule_batch_sharded — per-shard filter+score with a
cross-shard argmax over (score, global node id)) runs in tier-1 without a
TPU. The contract under test: sharding NEVER changes a decision — binds
are bit-identical to the single-device drain across uniform,
node-affinity, and anti-affinity fixtures; the chaos determinism contract
(same seed => identical event logs) survives the mesh; and TensorMirror
pads its capacity to a shard-divisible size with the padding counted,
including a grow forced by nodes added mid-drain.
"""

import numpy as np
import pytest


def _mesh(n):
    import jax
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices")
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:n]), ("nodes",))


def _fixture(client_cls, variant, n_nodes=24, n_pods=96):
    """Nodes + pending pods per decision-parity fixture variant."""
    from kubernetes_tpu import api
    from kubernetes_tpu.api import Quantity
    client = client_cls()
    nodes = []
    for i in range(n_nodes):
        alloc = {"cpu": Quantity("4"), "memory": Quantity("8Gi"),
                 "pods": Quantity(110)}
        nodes.append(client.nodes().create(api.Node(
            metadata=api.ObjectMeta(
                name=f"n{i}",
                labels={api.wellknown.LABEL_HOSTNAME: f"n{i}",
                        api.wellknown.LABEL_ZONE: f"z{i % 4}"}),
            status=api.NodeStatus(
                capacity=dict(alloc), allocatable=dict(alloc),
                conditions=[api.NodeCondition(type="Ready",
                                              status="True")]))))
    pods = []
    for i in range(n_pods):
        pod = api.Pod(
            metadata=api.ObjectMeta(name=f"p{i}", namespace="default",
                                    labels={"app": "m", "g": f"g{i % 8}"}),
            spec=api.PodSpec(containers=[api.Container(
                name="c", image="img",
                resources=api.ResourceRequirements(requests={
                    "cpu": Quantity(["100m", "250m", "500m"][i % 3]),
                    "memory": Quantity("128Mi")}))]))
        if variant == "node-affinity":
            pod.spec.affinity = api.Affinity(node_affinity=api.NodeAffinity(
                required_during_scheduling_ignored_during_execution=api.NodeSelector(
                    node_selector_terms=[api.NodeSelectorTerm(
                        match_expressions=[api.NodeSelectorRequirement(
                            key=api.wellknown.LABEL_ZONE, operator="In",
                            values=["z0", "z1"])])])))
        elif variant == "anti-affinity":
            pod.spec.affinity = api.Affinity(
                pod_anti_affinity=api.PodAntiAffinity(
                    required_during_scheduling_ignored_during_execution=[
                        api.PodAffinityTerm(
                            label_selector=api.LabelSelector(
                                match_labels={"g": f"g{i % 8}"}),
                            topology_key=api.wellknown.LABEL_HOSTNAME)]))
        elif variant == "soft-affinity":
            # preferred (soft) inter-pod anti-affinity: the in-scan credit
            # accumulators ride the shard_map carry, min-max normalized
            # with a cross-shard pmin/pmax pair
            pod.spec.affinity = api.Affinity(
                pod_anti_affinity=api.PodAntiAffinity(
                    preferred_during_scheduling_ignored_during_execution=[
                        api.WeightedPodAffinityTerm(
                            weight=10,
                            pod_affinity_term=api.PodAffinityTerm(
                                label_selector=api.LabelSelector(
                                    match_labels={"g": f"g{i % 8}"}),
                                topology_key=api.wellknown
                                .LABEL_HOSTNAME))]))
        elif variant == "anti-affinity-dir2" and i % 2 == 0:
            # carriers anti-affine to the app label every pod wears: the
            # odd pods are PURE MATCHERS, so the direction-2 carry table
            # ships and its sharded dom broadcast is exercised
            pod.spec.affinity = api.Affinity(
                pod_anti_affinity=api.PodAntiAffinity(
                    required_during_scheduling_ignored_during_execution=[
                        api.PodAffinityTerm(
                            label_selector=api.LabelSelector(
                                match_labels={"app": "m"}),
                            topology_key=api.wellknown.LABEL_HOSTNAME)]))
        pods.append(client.pods().create(pod))
    return client, nodes, pods


def _drain(mesh, variant, batch_size=32, n_nodes=24, n_pods=96,
           commit_async=None):
    """mesh=1 is the EXPLICIT single-device baseline (resolve_mesh maps
    n<=1 to no mesh without consulting KTPU_MESH — a mesh-flipped
    environment must not contaminate the bit-identity control).
    `commit_async` pins the commit stage inline (False) or onto its
    thread (True); None leaves it to Scheduler._commit_overlaps."""
    from kubernetes_tpu import api
    from kubernetes_tpu.api import Quantity
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.state import Client
    client, nodes, pods = _fixture(Client, variant, n_nodes, n_pods)
    sched = Scheduler(client, batch_size=batch_size, mesh=mesh)
    sched._commit_async = commit_async
    for n in nodes:
        sched.cache.add_node(n)
    if variant == "nominated":
        # a phantom preemptor reserves most of n0; two queued pods hold
        # their own nominations (the self-exemption rows) — the overlay
        # shards P("nodes") with the mirror
        ghost = api.Pod(
            metadata=api.ObjectMeta(name="ghost", namespace="default"),
            spec=api.PodSpec(containers=[api.Container(
                name="c", image="img",
                resources=api.ResourceRequirements(requests={
                    "cpu": Quantity("3500m"),
                    "memory": Quantity("7Gi")}))]))
        sched.queue.nominated.add(ghost, "n0")
        sched.queue.nominated.add(pods[0], "n1")
        sched.queue.nominated.add(pods[1], "n2")
    for p in pods:
        sched.queue.add(p)
    sched.algorithm.refresh()
    n = sched.drain_pipelined()
    binds = {p.metadata.name: p.spec.node_name
             for p in client.pods().list()}
    return n, binds, sched


@pytest.mark.parametrize("variant",
                         ["uniform", "node-affinity", "anti-affinity",
                          "anti-affinity-dir2"])
def test_sharded_drain_bit_identical(variant):
    """ACCEPTANCE: the shard-mapped drain's binds == the single-device
    drain's, pod for pod, on every parity fixture — and the sharded
    kernel really ran (no silent single-device fallback)."""
    n1, single, _ = _drain(1, variant)
    mesh = _mesh(8)
    with mesh:
        n2, sharded, sched = _drain(mesh, variant)
    assert n1 == n2 > 0
    assert single == sharded
    assert sched.metrics.sharded_batches.value() > 0
    cfg, usage = sched.algorithm.mirror.device_cfg_usage()
    assert len(next(iter(usage.values())).sharding.device_set) == 8


@pytest.mark.parametrize("variant", ["anti-affinity", "anti-affinity-dir2"])
def test_drain_independent_of_commit_thread_timing(variant, monkeypatch):
    """The bit-identity above compares two drains, so each drain must be
    a function of its queue and cluster alone. On a host with >= 4 cores
    (and on any accelerator) the commit stage runs on its own thread; a
    launch that read the snapshot while that thread was part-way through
    its assumes gave binds that depended on the interleaving (the
    anti-affinity-dir2 failures: repair of a chained batch re-assigned
    against a half-committed predecessor). Inline commit, a fast commit
    thread and a commit thread slowed per assume must agree pod for
    pod."""
    import time as _time
    from kubernetes_tpu.scheduler import Scheduler
    _, inline, _ = _drain(1, variant, commit_async=False)
    _, threaded, _ = _drain(1, variant, commit_async=True)
    tracked = Scheduler._tracked_assume

    def slow_assume(self, pod):
        _time.sleep(0.002)
        tracked(self, pod)
    monkeypatch.setattr(Scheduler, "_tracked_assume", slow_assume)
    _, slowed, _ = _drain(1, variant, commit_async=True)
    assert inline == threaded
    assert inline == slowed


@pytest.mark.parametrize("shards", [4, 8])
@pytest.mark.parametrize("variant", ["soft-affinity", "nominated"])
def test_new_shapes_sharded_bit_identical(variant, shards):
    """ISSUE 14: soft credits and nominated reservations route the
    shard_map class scan now (they used to fall back to GSPMD / the
    classic kernel) — binds bit-identical to the single-device drain on
    4- and 8-shard CPU meshes, and the shard kernel really ran."""
    n1, single, s1 = _drain(1, variant)
    if variant == "nominated":
        assert s1.algorithm._nom_dev is not None   # overlay engaged
    mesh = _mesh(shards)
    with mesh:
        n2, sharded, sched = _drain(mesh, variant)
    assert n1 == n2 > 0
    assert single == sharded
    assert sched.metrics.sharded_batches.value() > 0


@pytest.mark.parametrize("shards", [4, 8])
def test_spread_sharded_bit_identical(shards):
    """Spread groups on the shard_map class scan: running group counts
    shard on the node axis with a psum/pmax zone reduce — binds must be
    bit-identical to the single-device drain."""
    import time as _time
    from kubernetes_tpu import api
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.state import Client

    def run(mesh):
        client = Client()
        client.services().create(api.Service(
            metadata=api.ObjectMeta(name="m", namespace="default"),
            spec=api.ServiceSpec(selector={"app": "m"})))
        sched = Scheduler(client, batch_size=32, mesh=mesh)
        sched.informers.start()
        try:
            sched.informers.wait_for_cache_sync()
            _c, nodes, pods = _fixture(lambda: client, "uniform")
            deadline = _time.time() + 60
            while sched.queue.num_pending() < len(pods) or \
                    len(sched.cache.node_names()) < len(nodes):
                if _time.time() > deadline:
                    raise RuntimeError("informer sync stalled")
                _time.sleep(0.01)
            # the Service's selector really makes these spread carriers
            assert sched.algorithm.scorer.listers.selectors_for_pod(
                pods[0])
            sched.algorithm.refresh()
            n = sched.drain_pipelined()
            binds = {p.metadata.name: p.spec.node_name
                     for p in client.pods().list()}
            return n, binds, sched.metrics.sharded_batches.value()
        finally:
            sched.informers.stop()

    n1, single, _ = run(1)
    mesh = _mesh(shards)
    with mesh:
        n2, sharded, n_shard_batches = run(mesh)
    assert n1 == n2 > 0
    assert single == sharded
    assert n_shard_batches > 0


def test_shard_map_vs_gspmd_selection(monkeypatch):
    """KTPU_SHARD_MAP=0 pins mesh batches to the GSPMD path (the
    pjit-vs-shard_map selection knob) — decisions still identical, but
    the shard-kernel counter stays at zero."""
    mesh = _mesh(8)
    monkeypatch.delenv("KTPU_SHARD_MAP", raising=False)
    with mesh:
        _, sharded, sm_sched = _drain(mesh, "uniform")
    # the control really took the shard_map path (not GSPMD-vs-GSPMD)
    assert sm_sched.metrics.sharded_batches.value() > 0
    monkeypatch.setenv("KTPU_SHARD_MAP", "0")
    with mesh:
        n, gspmd, sched = _drain(mesh, "uniform")
    assert n > 0 and sharded == gspmd
    assert sched.metrics.sharded_batches.value() == 0


def test_grow_pads_shard_divisible_mid_drain(monkeypatch):
    """A non-power-of-two mesh (3 shards): the mirror pads its row
    capacity to a shard-divisible size, nodes added MID-DRAIN grow it
    shard-divisibly, the padding is counted in the gauge, and the binds
    keep matching the GSPMD control ON THE SAME MESH. (A plain
    single-device control would sit at capacity 128 vs the padded 129 —
    different row numbering, different tie-break hashes — so the
    equal-layout control is the pjit path, and the 8-shard tests above
    pin mesh == no-mesh where capacities coincide.)"""
    from kubernetes_tpu import api
    from kubernetes_tpu.api import Quantity
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.state import Client

    def run(mesh):
        client, nodes, pods = _fixture(Client, "uniform", 24, 64)
        sched = Scheduler(client, batch_size=32, mesh=mesh)
        for n in nodes:
            sched.cache.add_node(n)
        for p in pods[:32]:
            sched.queue.add(p)
        sched.algorithm.refresh()
        n1 = sched.drain_pipelined()
        # grow past the initial capacity between drains of one workload
        alloc = {"cpu": Quantity("4"), "memory": Quantity("8Gi"),
                 "pods": Quantity(110)}
        for i in range(24, 140):
            node = client.nodes().create(api.Node(
                metadata=api.ObjectMeta(
                    name=f"n{i}",
                    labels={api.wellknown.LABEL_HOSTNAME: f"n{i}",
                            api.wellknown.LABEL_ZONE: f"z{i % 4}"}),
                status=api.NodeStatus(
                    capacity=dict(alloc), allocatable=dict(alloc),
                    conditions=[api.NodeCondition(type="Ready",
                                                  status="True")])))
            sched.cache.add_node(node)
        for p in pods[32:]:
            sched.queue.add(p)
        sched.algorithm.refresh()
        n2 = sched.drain_pipelined()
        binds = {p.metadata.name: p.spec.node_name
                 for p in client.pods().list()}
        return n1 + n2, binds, sched

    mesh = _mesh(3)
    monkeypatch.setenv("KTPU_SHARD_MAP", "0")
    with mesh:
        n_ctrl, ctrl, _ = run(mesh)
    monkeypatch.delenv("KTPU_SHARD_MAP")
    with mesh:
        n_mesh, sharded, sched = run(mesh)
    m = sched.algorithm.mirror
    assert m.t.capacity % 3 == 0
    assert m.shard_pad_rows > 0              # 256 -> 258 needs 2 pad rows
    assert sched.metrics.mirror_shard_pad_rows.value() == m.shard_pad_rows
    assert sched.metrics.sharded_batches.value() > 0
    assert n_ctrl == n_mesh == 64
    assert ctrl == sharded


def test_chaos_determinism_with_mesh(tmp_path):
    """The chaos determinism contract survives sharding: same seed =>
    identical event logs with the scheduler's drain on the mesh."""
    from kubernetes_tpu.chaos import ChaosHarness
    mesh = _mesh(8)
    logs = []
    with mesh:
        for i in range(2):
            h = ChaosHarness(seed=23, nodes=6, nodes_per_slice=3,
                             error_rate=0.08, mesh=mesh,
                             wal_path=str(tmp_path / f"c{i}.wal"))
            try:
                r = h.run(n_events=10, quiesce_steps=8)
                logs.append(r.events)
            finally:
                h.close()
    assert logs[0] == logs[1]


def test_resolve_mesh_env(monkeypatch):
    """KTPU_MESH makes the mesh the drain's default substrate without
    code changes; unset/0 keeps the single-device path."""
    import jax
    from kubernetes_tpu.scheduler.sharding import resolve_mesh
    monkeypatch.delenv("KTPU_MESH", raising=False)
    assert resolve_mesh(None) is None
    monkeypatch.setenv("KTPU_MESH", "0")
    assert resolve_mesh(None) is None
    if len(jax.devices()) >= 8:
        monkeypatch.setenv("KTPU_MESH", "auto")
        m = resolve_mesh(None)
        assert m is not None and m.shape["nodes"] == len(jax.devices())
        monkeypatch.setenv("KTPU_MESH", "4")
        assert resolve_mesh(None).shape["nodes"] == 4
    with pytest.raises(ValueError):
        resolve_mesh(10_000)  # more shards than devices must refuse
