"""The main path's kernels compile for the TPU v5e at real widths — asked of
the TPU compiler here, without the chip (the `on-chip-measurement` guide
§2: a described, not attached, `v5e:2x2`).

What this guards: a scan the chip's compiler refuses (a 16k-step scan with
an 8-pod unrolled body, gathers/scatters through the carry, per-pod
collectives), a program that does not fit one chip's memory, and a sharded
scan that loses its collectives or puts everything on one device. Nothing
runs, so nothing here speaks about results or times.

Shapes are the real drain's: the fake-node cluster of fakecluster.py at
5,000 nodes (mirror capacity 8192), pod buckets 16384 and 1024, every table as
`tensorize` lays it out — captured from `BatchScheduler.schedule_launch`
itself, with the kernel swapped for a spy, so a layout change moves these
shapes with it. The sharded scan takes the same batch with the node axis
widened to capacity 65536 over a 4-device "nodes" mesh.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may hold the TPU's library, and every
xdist worker imports every test file. All such compiles stay in THIS file,
in the test's own process.
"""

import os

import numpy as np
import pytest

import fakecluster
from kubernetes_tpu import api

N_NODES = 5000           # BASELINE.json's north-star cluster
CAPACITY = 8192          # tensorize._bucket(5000)
SHARDED_CAPACITY = 65536  # tensorize._bucket(50000), BENCH_r07's large shape


class _Captured(Exception):
    """Raised by the spy in place of running the kernel."""


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices), ("nodes",))


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip; keep the cache out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _scheduler(class_scan=True):
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.scheduler import priorities as prios_mod
    from kubernetes_tpu.state import Client
    sched = Scheduler(Client(validate=False), batch_size=16384)
    sched.algorithm.class_scan = class_scan
    svc = api.Service(
        metadata=api.ObjectMeta(name="bench", namespace="default"),
        spec=api.ServiceSpec(selector={"app": "bench"}))
    sched.algorithm.scorer.listers = prios_mod.SpreadListers(
        services=lambda ns: [svc])
    for i in range(N_NODES):
        sched.cache.add_node(fakecluster.make_node(i))
    # bound anti-affinity carriers: every later pod gets a residual mask
    # row, and the in-scan term tables ship
    for i in range(100):
        p = fakecluster.make_pod(3_000_000 + i, "pod-anti-affinity")
        p.spec.node_name = f"node-{i}"
        sched.cache.add_pod(p)
    sched.algorithm.refresh()
    assert sched.algorithm.mirror.t.capacity == CAPACITY
    return sched


def _mixed_pods(n):
    """Half pod-anti-affinity, half plain, all selected by the spread
    Service: the batch carries topology terms AND a spread group."""
    return [fakecluster.make_pod(
        i, "pod-anti-affinity" if i % 2 else "uniform") for i in range(n)]


def _capture(sched, pods, module, name):
    """The positional arguments production code hands kernel `name`."""
    got = {}

    def spy(*args, **kwargs):
        got["args"], got["kwargs"] = args, kwargs
        raise _Captured
    orig = getattr(module, name)
    setattr(module, name, spy)
    try:
        with pytest.raises(_Captured):
            sched.algorithm.schedule_launch(pods)
    finally:
        setattr(module, name, orig)
    return got["args"], got["kwargs"]


def _shapes(tree, sharding, widen=None):
    """ShapeDtypeStructs of a pytree of arrays, placed by `sharding` (one
    sharding, or a function of (path-leaf-name, ndim)); `widen` maps a
    dimension size to its replacement (the node axis, 8192 -> 65536)."""
    import jax

    def one(path, a):
        shape = tuple((widen or {}).get(d, d) for d in np.shape(a))
        # a dict's key, or PackedInputs' own field ("words": replicated)
        s = sharding(getattr(path[-1], "key", None) or path[-1].name,
                     len(shape)) if callable(sharding) else sharding
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=s)
    return jax.tree_util.tree_map_with_path(one, tree)


@pytest.fixture(scope="module")
def class_batch():
    """(node_cfg, usage, pod_batch, nom) of a 16384-pod topology + spread
    batch on the class-indexed scan."""
    from kubernetes_tpu.scheduler.kernels import batch as kb
    args, _ = _capture(_scheduler(), _mixed_pods(16384), kb,
                       "schedule_batch")
    batch = kb.unpack_inputs(args[2])
    assert batch["class_req"].ndim == 2 and "anti_dom" in batch \
        and "spread_slots" in batch
    assert batch["req"].shape[0] == 16384
    assert batch["unique_masks"].shape[1] == CAPACITY
    # what a rule places on the node axis crosses on its own, and so do
    # the 4 MB of anti_cnt0 (128 terms x 8192 hostnames: over
    # PACK_MAX_BYTES); the pod-axis vectors and the class tables ride
    # the one buffer
    assert set(args[2].rest) == {"unique_masks", "unique_scores",
                                 "anti_dom", "spread_zone", "spread_tab",
                                 "anti_cnt0"}
    return args


def _compile(jitted, *shapes, **static):
    compiled = jitted.lower(*shapes, **static).compile()
    mem = compiled.memory_analysis()
    # one v5e chip holds 16 GB; the scan programs are nowhere near it,
    # and a program that is would fail on the chip
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes < 8 << 30
    return compiled


def test_class_scan_full_bucket(class_batch, one_chip):
    from kubernetes_tpu.scheduler.kernels.batch import schedule_batch
    _compile(schedule_batch, *_shapes(class_batch, one_chip))


def test_class_scan_tail_bucket(one_chip):
    from kubernetes_tpu.scheduler.kernels import batch as kb
    args, _ = _capture(_scheduler(), _mixed_pods(1000), kb,
                       "schedule_batch")
    assert kb.unpack_inputs(args[2])["req"].shape[0] == 1024
    _compile(kb.schedule_batch, *_shapes(args, one_chip))


def test_classic_scan(one_chip):
    """The per-pod recompute scan (`class_scan` off, the parity
    control): same batch, no class tables."""
    from kubernetes_tpu.scheduler.kernels import batch as kb
    args, _ = _capture(_scheduler(class_scan=False), _mixed_pods(16384),
                       kb, "schedule_batch")
    names = kb.unpack_inputs(args[2])
    assert "class_req" not in names and "anti_dom" in names
    _compile(kb.schedule_batch, *_shapes(args, one_chip))


def test_gang_scan(one_chip):
    """1024 gangs of 16 members, each pinned to one zone."""
    from kubernetes_tpu.scheduler.kernels import gang as kg
    sched = _scheduler()

    class _Gangs:
        metrics = None

        def batch_groups(self, pods):
            return [(list(range(g, g + 16)), api.wellknown.LABEL_ZONE,
                     True, None) for g in range(0, len(pods), 16)]
    sched.algorithm.gang = _Gangs()
    pods = [fakecluster.make_pod(i) for i in range(16384)]
    args, _ = _capture(sched, pods, kg, "gang_schedule_batch")
    assert args[3].rest["dom_tab"].shape[1] == CAPACITY
    _compile(kg.gang_schedule_batch, *_shapes(args, one_chip))


def test_apply_dirty_and_pack_results(class_batch, one_chip):
    import jax
    from kubernetes_tpu.scheduler.kernels.batch import (apply_dirty,
                                                        pack_inputs,
                                                        pack_results)
    cfg, usage = class_batch[0], class_batch[1]
    D = 1024
    rows = {"idx": np.zeros((D,), np.int32)}
    for k, v in (*cfg.items(), *usage.items()):
        rows[k + "_rows"] = np.zeros((D,) + np.shape(v)[1:], v.dtype)
    rows = pack_inputs(lambda name, a: a, rows)
    assert rows.rest == {}
    _compile(apply_dirty, *_shapes((cfg, usage, rows), one_chip))
    _compile(pack_results,
             jax.ShapeDtypeStruct((16384,), np.int32, sharding=one_chip),
             jax.ShapeDtypeStruct((16384,), np.float32, sharding=one_chip))


def test_affinity_template_matmuls(one_chip):
    """[U, T] x [T, N] template evaluation at 128 templates x 128 terms."""
    from kubernetes_tpu.scheduler.kernels import affinity as ka
    U = T = 128
    tn = np.zeros((T, CAPACITY), bool)
    ut = np.zeros((U, T), np.float32)
    _compile(ka._affinity_masks_jit,
             *_shapes((tn, tn, ut, ut, ut), one_chip))
    _compile(ka._affinity_scores_jit, *_shapes(
        (ut, np.zeros((T, CAPACITY), np.float32)), one_chip))


def _preempt_arrays(build, widen):
    """A small real table set from the production builder, its row and
    victim axes widened to the real cluster's."""
    arrays = build().arrays
    return {k: np.zeros(tuple(widen.get(d, d) for d in np.shape(v)),
                        np.asarray(v).dtype) for k, v in arrays.items()}


def test_price_nodes(one_chip):
    """Victim pricing over every node: [8192, 128] unit tables (110 pods
    a node buckets to 128)."""
    from kubernetes_tpu.scheduler.kernels import preempt as pk
    from kubernetes_tpu.scheduler.nodeinfo import NodeInfo

    def build():
        infos = {}
        for i in range(16):
            ni = NodeInfo(fakecluster.make_node(i))
            for j in range(5 + (i == 0) * 3):      # widest row: 8 units
                p = fakecluster.make_pod(100 * i + j)
                p.spec.node_name, p.spec.priority = f"node-{i}", j
                ni.add_pod(p)
            infos[f"node-{i}"] = ni
        pod = fakecluster.make_pod(9999)
        pod.spec.priority = 100
        pod.spec.containers[0].resources.requests["cpu"] = \
            api.Quantity("3900m")
        return pk.build_victim_tables(pod, sorted(infos.items()), infos, [])
    a = _preempt_arrays(build, {16: CAPACITY, 8: 128})
    assert a["valid"].shape == (CAPACITY, 128)
    _compile(pk.price_nodes, *_shapes(tuple(a[k] for k in (
        "free0", "cfree0", "need", "need_cnt", "freed", "fcnt", "valid",
        "pdb", "top", "psum", "gcnt", "startr", "row_valid")), one_chip))


def test_price_domains(one_chip):
    """Whole-gang pricing: 512 domains x 1024 victim units."""
    from kubernetes_tpu.scheduler.kernels import preempt as pk
    from kubernetes_tpu.scheduler.nodeinfo import NodeInfo

    def build():
        infos, cands = {}, []
        for i in range(16):
            ni = NodeInfo(fakecluster.make_node(i))
            for j in range(8 if i < 2 else 1):     # widest domain: 16 units
                p = fakecluster.make_pod(100 * i + j)
                p.spec.node_name, p.spec.priority = f"node-{i}", j % 7
                ni.add_pod(p)
            infos[f"node-{i}"] = ni
            cands.append((f"node-{i}", ni, f"slice-{i // 2}"))
        members = []
        for m in range(4):
            p = fakecluster.make_pod(9000 + m)
            p.spec.priority = 100
            p.spec.containers[0].resources.requests["cpu"] = \
                api.Quantity("3900m")
            members.append(p)
        return pk.build_domain_tables(members, cands, infos, [],
                                      min_member=4)
    a = _preempt_arrays(build, {8: 512, 16: 1024})
    assert a["valid"].shape == (512, 1024)
    _compile(pk.price_domains, *_shapes(tuple(a[k] for k in (
        "base", "need", "dslots", "valid", "pdb", "top", "psum", "gcnt",
        "startr", "row_valid")), one_chip))


def test_drf_ordering(one_chip):
    """The drain order of a 16384-pod pop over a 64-tenant ledger
    (dominant shares, per-pod gather and the sort in one program)."""
    from kubernetes_tpu.tenancy import drf
    R = drf.DRFAccount()._capacity.shape[0]
    _compile(drf._jit(drf._order_kernel), *_shapes(
        (np.zeros((64, R), np.float32), np.zeros((R,), np.float32),
         np.zeros((16384,), np.int32), np.zeros((16384,), np.int32),
         np.int32(0)), one_chip))


def test_sharded_class_scan(class_batch, mesh4):
    """The shard-mapped scan at capacity 65536 on a 4-device "nodes"
    mesh: per-pod collectives survive, and every node-axis tensor is
    split four ways (memory_analysis is per device)."""
    from jax.sharding import NamedSharding
    from kubernetes_tpu.scheduler.kernels.batch import schedule_batch_sharded
    from kubernetes_tpu.scheduler.sharding import spec_for
    cfg, usage, batch, nom = class_batch
    assert nom is None
    place = lambda name, ndim: NamedSharding(mesh4, spec_for(name, ndim))
    shapes = _shapes((cfg, usage, batch), place,
                     widen={CAPACITY: SHARDED_CAPACITY})
    assert shapes[2].rest["anti_dom"].shape[1] == SHARDED_CAPACITY
    compiled = _compile(schedule_batch_sharded, mesh4, *shapes)
    text = compiled.as_text()
    # winner election (pmax + pmin), the owner's score broadcast, the
    # spread reduce and the topology dom broadcast, per pod
    assert text.count("all-reduce") >= 5
    import jax
    size = lambda s: int(np.prod(s.shape)) * s.dtype.itemsize
    leaves = jax.tree_util.tree_leaves(shapes)
    whole = sum(size(s) for s in leaves)
    split = sum(size(s) for s in leaves
                if not s.sharding.is_fully_replicated)
    per_device = compiled.memory_analysis().argument_size_in_bytes
    # every node-axis table is an argument a quarter a device; what is
    # replicated (the packed per-pod arrays, anti_cnt0's [T, hostnames])
    # is there whole. Since every spread group rides the scan no static
    # score row is left to dominate the arguments (256 rows before PR 37)
    assert split > 0.4 * whole
    assert per_device <= 1.02 * (whole - split + split / 4), \
        (per_device, whole, split)

