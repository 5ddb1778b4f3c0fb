"""ISSUE 32: a launch ships its host inputs in one buffer, not one an array.

`PodBatchTensors.device()`, `BatchScheduler._gang_device_table()` and
`TensorMirror.device_cfg_usage()` lay every small replicated array into one
int32 buffer (`kernels.batch.pack_inputs`) that crosses to the device once
and is cut apart again inside the jitted kernel (`unpack_inputs`). These
tests pin, per scan flavour, on batches captured from
`BatchScheduler.schedule_launch` itself:

  - the dict a kernel sees after unpacking equals — name by name, dtype,
    shape and bits, and placement for what crosses on its own — the dict
    `device()` built before this change (`_device_as_before`, the old
    body kept here as the reference: one transfer an array);
  - `assign` / `scores` / `new_usage` are bit-identical between the packed
    inputs and that dict;
  - a plain launch after a bind issues at most 6 transfers by
    `scheduler_host_to_device_transfers_total`, which is on /metrics at 0
    before the first cycle.
"""

import numpy as np
import pytest

import fakecluster
from kubernetes_tpu import api
from kubernetes_tpu.scheduler import sharding

N_NODES = 48
N_PODS = 96


def _device_as_before(b, mesh):
    """`PodBatchTensors.device()` as it stood before PR 32: one array,
    one transfer (`sharding.put`: the node-axis names by the rule table,
    the rest replicated)."""
    import jax.numpy as jnp
    put = lambda name: sharding.put(mesh, name, getattr(b, name))
    out = {k: put(k) for k in (
        "req", "nonzero_req", "mem_pressure_blocked", "active", "seq",
        "mask_idx", "score_idx", "nom_row", "unique_masks", "unique_scores",
        "resource_weights")}
    if b.spread_slots is not None:
        for k in ("spread_gidx", "spread_mg", "spread_slots", "spread_nz",
                  "spread_zone", "spread_zinit"):
            out[k] = put(k)
        out["spread_tab"] = b.spread_tab
        out["spread_weight"] = jnp.float32(b.spread_weight)
    if b.anti_dom is not None:
        out["anti_dom"] = b.anti_dom_dev if b.anti_dom_dev is not None \
            else put("anti_dom")
        for k in ("anti_cnt0", "anti_tids", "aff_tids", "match_tids"):
            out[k] = put(k)
        if b.cmatch_tids is not None:
            out["cmatch_tids"] = put("cmatch_tids")
            out["canti_tids"] = put("canti_tids")
    if b.soft_dom is not None:
        for k in ("soft_dom", "soft_cnt0", "soft_base", "soft_base_idx",
                  "soft_read_tids", "soft_read_w", "soft_write_tids",
                  "soft_write_w"):
            out[k] = put(k)
        out["soft_weight"] = jnp.float32(b.soft_weight)
    if b._class_tables is not None:
        for k, v in b._class_tables.items():
            out[k] = sharding.put(mesh, k, v)
    return out


class _Gangs:
    """Gangs of 4, each pinned to one zone (test_chip_compile's stub)."""
    metrics = None

    def batch_groups(self, pods):
        return [(list(range(g, g + 4)), api.wellknown.LABEL_ZONE, True, None)
                for g in range(0, len(pods), 4)]


def _scheduler(variant, mesh=None, spread=False):
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.scheduler import priorities as prios_mod
    from kubernetes_tpu.state import Client
    sched = Scheduler(Client(validate=False), batch_size=256, mesh=mesh)
    if spread:
        svc = api.Service(
            metadata=api.ObjectMeta(name="bench", namespace="default"),
            spec=api.ServiceSpec(selector={"app": "bench"}))
        sched.algorithm.scorer.listers = prios_mod.SpreadListers(
            services=lambda ns: [svc])
    for i in range(N_NODES):
        sched.cache.add_node(fakecluster.make_node(i))
    # bound carriers of the variant's terms, so that the batch's rows
    # and term tables have something to read
    for i in range(12 if variant != "uniform" else 0):
        p = fakecluster.make_pod(3_000_000 + i, variant)
        p.spec.node_name = f"node-{i}"
        sched.cache.add_pod(p)
    sched.algorithm.refresh()
    return sched


#: flavour -> (scheduler keywords, pod variant, kernel module, kernel name,
#: names the unpacked batch must hold besides the eleven of a plain one)
FLAVOURS = {
    "plain": (dict(variant="uniform"), "uniform", "batch",
              "schedule_batch", {"class_idx", "class_req"}),
    "anti_affinity": (dict(variant="pod-anti-affinity"),
                      "pod-anti-affinity", "batch", "schedule_batch",
                      {"anti_dom", "anti_cnt0", "anti_tids", "aff_tids",
                       "match_tids"}),
    "spread": (dict(variant="uniform", spread=True), "uniform", "batch",
               "schedule_batch",
               {"spread_gidx", "spread_mg", "spread_slots", "spread_nz",
                "spread_tab", "spread_zone", "spread_zinit",
                "spread_weight"}),
    "soft_terms": (dict(variant="preferred-affinity"), "preferred-affinity",
                   "batch", "schedule_batch",
                   {"soft_dom", "soft_cnt0", "soft_base", "soft_base_idx",
                    "soft_read_tids", "soft_read_w", "soft_write_tids",
                    "soft_write_w", "soft_weight"}),
    "gang": (dict(variant="uniform"), "uniform", "gang",
             "gang_schedule_batch", set()),
    "sharded": (dict(variant="pod-anti-affinity", mesh=4, spread=True),
                "pod-anti-affinity", "batch", "schedule_batch_sharded",
                {"anti_dom", "spread_zone", "class_idx"}),
}
PLAIN_NAMES = {"req", "nonzero_req", "mem_pressure_blocked", "active", "seq",
               "mask_idx", "score_idx", "nom_row", "unique_masks",
               "unique_scores", "resource_weights"}
#: what a rule of sharding.py places on the node axis: its own transfer
NODE_AXIS_NAMES = {"unique_masks", "unique_scores",
                   "spread_zone", "anti_dom", "soft_dom", "soft_base",
                   "dom_tab"}


#: device arrays a batch is handed: passed through, no transfer a launch
ON_DEVICE = {"spread_tab"}


def _launch(flavour):
    """(kernel, its positional and keyword arguments as production handed
    them over, the PendingBatch) of one launch of the flavour."""
    import importlib
    kw, variant, module, name, _ = FLAVOURS[flavour]
    sched = _scheduler(**kw)
    if flavour == "gang":
        sched.algorithm.gang = _Gangs()
    mod = importlib.import_module(
        "kubernetes_tpu.scheduler.kernels." + module)
    kernel = getattr(mod, name)
    got = {}

    def spy(*args, **kwargs):
        got["args"], got["kwargs"] = args, kwargs
        return kernel(*args, **kwargs)
    setattr(mod, name, spy)
    try:
        pending = sched.algorithm.schedule_launch(
            [fakecluster.make_pod(i, variant) for i in range(N_PODS)])
    finally:
        setattr(mod, name, kernel)
    assert pending is not None and "args" in got, \
        f"{flavour}: the launch did not reach {name}"
    return sched, kernel, got["args"], got["kwargs"], pending


def _same_bits(name, a, b):
    a_np, b_np = np.asarray(a), np.asarray(b)
    assert a_np.dtype == b_np.dtype, (name, a_np.dtype, b_np.dtype)
    assert a_np.shape == b_np.shape, (name, a_np.shape, b_np.shape)
    assert a_np.tobytes() == b_np.tobytes(), name


def _same_tree(name, a, b):
    import jax
    la, ta = jax.tree_util.tree_flatten_with_path(a)
    lb, tb = jax.tree_util.tree_flatten_with_path(b)
    assert ta == tb, name
    for (path, x), (_, y) in zip(la, lb):
        _same_bits(name + jax.tree_util.keystr(path), x, y)


@pytest.mark.parametrize("flavour", sorted(FLAVOURS))
def test_kernel_sees_the_same_dict_and_decides_the_same(flavour):
    from kubernetes_tpu.scheduler.kernels.batch import (PackedInputs,
                                                        unpack_inputs)
    sched, kernel, args, kwargs, pending = _launch(flavour)
    mesh = sched.algorithm.mirror.mesh
    at = 3 if flavour == "sharded" else 2      # the mesh is argument 0
    packed = args[at]
    assert isinstance(packed, PackedInputs)
    seen = unpack_inputs(packed)
    before = _device_as_before(pending.batch, mesh)
    assert set(seen) == set(before)
    assert PLAIN_NAMES | FLAVOURS[flavour][4] <= set(seen)
    for name in sorted(seen):
        _same_bits(name, seen[name], before[name])
    # on their own: exactly what a rule places on the node axis, placed
    # as before, and what was on the device already (the spread score's
    # round table, shipped once a size); everything else rode the one
    # buffer
    assert set(packed.rest) == set(seen) & (NODE_AXIS_NAMES | ON_DEVICE)
    for name, a in packed.rest.items():
        assert a.sharding.is_equivalent_to(before[name].sharding, a.ndim), \
            name
    assert packed.words.dtype == np.int32 and packed.words.ndim == 1
    if mesh is not None:
        assert packed.words.sharding.is_fully_replicated
    plain = list(args)
    plain[at] = before
    if flavour == "gang":
        # the gang table's entry vectors ride a buffer of their own;
        # dom_tab, node-axis, does not
        tab = args[3]
        assert isinstance(tab, PackedInputs) and set(tab.rest) == {"dom_tab"}
        plain[3] = {k: sharding.put(mesh, k, v)
                    for k, v in unpack_inputs(tab).items()}
        assert set(plain[3]) == {"pod_idx", "start", "end", "gang_id",
                                 "entry_dom_idx", "pin_dom", "need", "greq",
                                 "dom_tab"}
    _same_tree(flavour, kernel(*args, **kwargs), kernel(*plain, **kwargs))


def test_the_layout_is_a_function_of_names_and_shapes():
    """Two batches of one shape share one layout (so one program), and
    arrays over PACK_MAX_BYTES, of another dtype or already on the device
    stay out of the buffer."""
    import jax.numpy as jnp
    from kubernetes_tpu.scheduler.kernels import batch as kb
    puts = []

    def put(name, a):
        puts.append(name)
        return jnp.asarray(a)
    arrays = lambda fill: {
        "seq": np.full((8,), fill, np.int32),
        "active": np.arange(8) % 2 == fill % 2,
        "req": np.full((8, 3), fill + 0.5, np.float32),
        "weight": np.float32(fill),
        "unique_masks": np.ones((2, 16), bool),        # node axis by rule
        "wide": np.zeros((kb.PACK_MAX_BYTES // 4 + 1,), np.float32),
        "halves": np.zeros((4,), np.float16),
        "resident": jnp.ones((4,), jnp.float32)}
    a, b = kb.pack_inputs(put, arrays(1)), kb.pack_inputs(put, arrays(2))
    assert a.layout == b.layout
    assert [n for n, _, _, _ in a.layout] == ["seq", "active", "req",
                                              "weight"]
    assert all(off % 128 == 0 for _, _, _, off in a.layout)
    assert set(a.rest) == {"unique_masks", "wide", "halves", "resident"}
    assert puts == ["unique_masks", "wide", "halves", "packed_inputs"] * 2
    for src, packed in ((arrays(1), a), (arrays(2), b)):
        seen = kb.unpack_inputs(packed)
        for name, v in src.items():
            _same_bits(name, seen[name], v)
    # a hand-built dict passes through untouched
    d = {"seq": jnp.zeros((8,), jnp.int32)}
    assert kb.unpack_inputs(d) is d


def test_a_plain_launch_after_a_bind_issues_at_most_six_transfers():
    sched = _scheduler("uniform")
    series = "scheduler_host_to_device_transfers_total"
    counter = sched.metrics.host_to_device_transfers
    assert sched.algorithm.mirror.transfers is counter
    # on /metrics at 0 from process start
    assert f"{series} 0.0" in sched.metrics.registry.expose().splitlines()
    algo = sched.algorithm
    first = [fakecluster.make_pod(i) for i in range(8)]
    for r in algo.schedule(first):
        assert r.node_name is not None
        r.pod.spec.node_name = r.node_name
        sched.cache.assume_pod(r.pod)
    full_upload = counter.value()
    assert full_upload >= 8          # cfg and usage, one transfer a key
    pending = algo.schedule_launch([fakecluster.make_pod(100 + i)
                                    for i in range(8)])
    issued = counter.value() - full_upload
    # the packed dirty-row scatter, the packed batch, unique_masks and
    # unique_scores
    assert algo.mirror.device_ready() and 1 <= issued <= 6, issued
    assert issued == 4
    assert all(r.node_name for r in algo.schedule_finish(pending))
