"""ISSUE 34, ISSUE 36: cached node vectors catch up with the mirror by
row, and by the side of the row they read.

`TensorMirror` stamps each row with the epoch of its last write and, apart,
with the epoch at which its node side last changed (a bind moves the first
alone); `tensorize.NodeVectorCache` (behind `TermCompiler._vector` and
`ScoreCompiler._vec`) and `ScoreCompiler._refresh_epoch` recompute the rows
stamped, on the side they read, since a vector was last true and nothing
else. These tests pin

  - equality: over seeded random sequences of cache events, after every
    `refresh` what the long-lived compilers hold equals what compilers
    built fresh over the same mirror compute by the full walk;
  - the bound: the cache keeps every key of the batch in hand and drops
    keys by disuse and by bytes, without changing an answer;
  - the sides: a bind recomputes no row of a vector that reads the node
    alone and its row of one that reads the pods; every node event (in
    place or by a new object, a delete, a retaken row, a resize) reaches
    the vectors that read it;
  - engagement: through `Scheduler.schedule_pending`, a cycle that follows
    a bind recomputes no row at all, by the counters on /metrics.
"""

import copy
import json
import os
import random
import sys

import numpy as np
import pytest

from kubernetes_tpu import api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.scheduler import priorities as prios
from kubernetes_tpu.scheduler import tensorize
from kubernetes_tpu.scheduler.cache import Cache, Snapshot
from kubernetes_tpu.scheduler.scorer import ScoreCompiler
from kubernetes_tpu.scheduler.tensorize import TensorMirror, TermCompiler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from harness.children import parse_metrics  # noqa: E402  (the benchmark's own parser)

ZONE = api.wellknown.LABEL_ZONE
AVOID = prios.PREFER_AVOID_PODS_ANNOTATION
ROWS = "scheduler_node_vector_rows_recomputed_total"
REBUILDS = "scheduler_node_vector_rebuilds_total"
WRITES = "scheduler_mirror_row_writes_total"


def make_node(name, zone=None):
    alloc = {"cpu": Quantity("4"), "memory": Quantity("32Gi"),
             "pods": Quantity("110")}
    labels = {api.wellknown.LABEL_HOSTNAME: name}
    if zone is not None:
        labels[ZONE] = zone
    return api.Node(
        metadata=api.ObjectMeta(name=name, labels=labels),
        status=api.NodeStatus(capacity=dict(alloc), allocatable=dict(alloc),
                              conditions=[api.NodeCondition(
                                  type="Ready", status="True")]))


def make_pod(name, labels=None, host_port=0, **spec):
    ports = [api.ContainerPort(host_port=host_port, container_port=80)] \
        if host_port else []
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace="default",
                                labels=dict(labels or {})),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image=spec.pop("image", "pause"), ports=ports,
            resources=api.ResourceRequirements(
                requests={"cpu": Quantity("100m"),
                          "memory": Quantity("64Mi")}))], **spec))


def _probes():
    """Pods that between them ask for every kind of cached vector."""
    req = lambda key, op, *vals: api.NodeSelectorRequirement(
        key=key, operator=op, values=list(vals))
    in_zones = api.Affinity(node_affinity=api.NodeAffinity(
        required_during_scheduling_ignored_during_execution=api.NodeSelector(
            node_selector_terms=[api.NodeSelectorTerm(
                match_expressions=[req(ZONE, "In", "z1", "z2")])])))
    prefers = api.Affinity(node_affinity=api.NodeAffinity(
        preferred_during_scheduling_ignored_during_execution=[
            api.PreferredSchedulingTerm(weight=3, preference=api.NodeSelectorTerm(
                match_expressions=[req("disk", "In", "ssd")])),
            api.PreferredSchedulingTerm(weight=1, preference=api.NodeSelectorTerm(
                match_expressions=[req(ZONE, "NotIn", "z0")]))]))
    owned = make_pod("owned", labels={"app": "web"})
    owned.metadata.owner_references = [api.OwnerReference(
        kind="ReplicationController", name="rc-1", controller=True)]
    return [
        make_pod("plain"),
        make_pod("tolerates", tolerations=[api.Toleration(
            key="dedicated", operator="Exists", effect="NoSchedule")]),
        make_pod("tolerates-soft", tolerations=[api.Toleration(
            key="soft", operator="Exists", effect="PreferNoSchedule")]),
        make_pod("selects", node_selector={"disk": "ssd"}),
        make_pod("in-zones", affinity=in_zones),
        make_pod("prefers", affinity=prefers),
        make_pod("port-8080", host_port=8080),
        make_pod("port-9090", host_port=9090),
        make_pod("image-a", image="img-a"),
        make_pod("web", labels={"app": "web"}),
        owned,
    ]


def _held(cache):
    """(key, fn) of every vector a NodeVectorCache holds, least recently
    used first."""
    return [(key, entry.fn) for key, entry in cache._entries.items()]


class Cluster:
    """A scheduler cache, its snapshot and mirror, the long-lived compilers
    under test, and a seeded stream of cache events."""

    def __init__(self, seed, n_nodes):
        self.rng = random.Random(seed)
        self.cache = Cache()
        self.snapshot = Snapshot()
        self.mirror = TensorMirror()
        svc = api.Service(
            metadata=api.ObjectMeta(name="web", namespace="default"),
            spec=api.ServiceSpec(selector={"app": "web"}))
        self.listers = prios.SpreadListers(services=lambda ns: [svc])
        self.terms = TermCompiler(self.mirror)
        self.scorer = ScoreCompiler(self.mirror, self.terms, self.listers)
        self.nodes = {}
        self.pods = {}
        self.n_made = 0
        self.n_pods = 0
        self.probes = _probes()
        for _ in range(n_nodes):
            self.add_node()

    # ------------------------------------------------------------ events

    def add_node(self):
        name = f"n{self.n_made}"
        self.n_made += 1
        node = make_node(name, self.rng.choice([None, "z0", "z1", "z2", "z3"]))
        self.nodes[name] = node
        self.cache.add_node(node)

    def delete_node(self):
        if len(self.nodes) > 8:
            name = self.rng.choice(sorted(self.nodes))
            self.cache.remove_node(self.nodes.pop(name))
            for key in [k for k, p in self.pods.items()
                        if p.spec.node_name == name]:
                self.cache.remove_pod(self.pods.pop(key))

    def replace_node(self):
        """A deleted node's row is the next one a new node takes."""
        self.delete_node()
        self.refresh()
        self.add_node()

    def grow(self):
        """Past the mirror's capacity bucket."""
        for _ in range(self.mirror.t.capacity - len(self.nodes) + 3):
            self.add_node()

    def _update(self, change, in_place=False):
        name = self.rng.choice(sorted(self.nodes))
        old = self.nodes[name]
        new = old if in_place else copy.deepcopy(old)
        change(new)
        self.nodes[name] = new
        self.cache.update_node(old, new)

    def relabel_zone(self):
        zone = self.rng.choice([None, "z0", "z1", "z2", "z3", "z4"])

        def change(node):
            if zone is None:
                node.metadata.labels.pop(ZONE, None)
            else:
                node.metadata.labels[ZONE] = zone
        self._update(change, in_place=self.rng.random() < 0.3)

    def relabel_disk(self):
        disk = self.rng.choice(["ssd", "hdd", None])

        def change(node):
            if disk is None:
                node.metadata.labels.pop("disk", None)
            else:
                node.metadata.labels["disk"] = disk
        self._update(change, in_place=self.rng.random() < 0.3)

    def taint(self):
        key, effect = self.rng.choice(
            [("dedicated", "NoSchedule"), ("soft", "PreferNoSchedule"),
             ("other", "PreferNoSchedule")])

        def change(node):
            kept = [t for t in node.spec.taints if t.key != key]
            if len(kept) == len(node.spec.taints):
                kept.append(api.Taint(key=key, value="x", effect=effect))
            node.spec.taints = kept
        self._update(change, in_place=self.rng.random() < 0.3)

    def annotate(self):
        def change(node):
            if AVOID in node.metadata.annotations:
                del node.metadata.annotations[AVOID]
            else:
                node.metadata.annotations[AVOID] = json.dumps(
                    {"preferAvoidPods": [{"podSignature": {"podController": {
                        "kind": "ReplicationController", "name": "rc-1"}}}]})
        self._update(change)

    def images(self):
        def change(node):
            node.status.images = [] if node.status.images else [
                api.ContainerImage(names=["img-a"],
                                   size_bytes=500 * 1024 * 1024)]
        self._update(change)

    def add_pod(self):
        name = f"p{self.n_pods}"
        self.n_pods += 1
        pod = make_pod(
            name, labels=self.rng.choice([{}, {"app": "web"}]),
            host_port=self.rng.choice([0, 0, 8080, 9090]),
            node_name=self.rng.choice(sorted(self.nodes)))
        self.pods[pod.metadata.key()] = pod
        self.cache.add_pod(pod)

    def remove_pod(self):
        if self.pods:
            key = self.rng.choice(sorted(self.pods))
            self.cache.remove_pod(self.pods.pop(key))

    EVENTS = ("add_node", "delete_node", "replace_node", "relabel_zone",
              "relabel_disk", "taint", "taint", "annotate", "images",
              "add_pod", "add_pod", "add_pod", "remove_pod")

    def step(self):
        for _ in range(self.rng.choice([1, 1, 2, 5])):
            getattr(self, self.rng.choice(self.EVENTS))()
        self.refresh()

    def refresh(self):
        self.mirror.apply(self.snapshot,
                          self.cache.update_snapshot(self.snapshot))

    # ----------------------------------------------------------- reading

    def ask(self, share=1.0):
        """What a batch of some of the probe pods asks the compilers for."""
        self.scorer._refresh_epoch()
        for pod in self.probes:
            if self.rng.random() >= share:
                continue
            self.terms.tolerations_vector(pod)
            self.terms.node_selector_vector(pod)
            self.terms.host_ports_vector(pod)
            meta = prios.PriorityMetadata(pod, self.listers)
            self.scorer._node_affinity_raw(pod, meta)
            self.scorer._taint_raw(pod, meta)
            self.scorer._image_raw(pod, meta)
            self.scorer._avoid_raw(pod, meta)
            self.scorer._spread_counts(pod, meta)

    def check(self, share=1.0):
        """Every vector the long-lived compilers hold (or `share` of them:
        the rest lag on, several epochs behind) against compilers built
        now, which take the full walk over the same mirror."""
        terms = TermCompiler(self.mirror)
        scorer = ScoreCompiler(self.mirror, terms, self.listers)
        scorer._refresh_epoch()
        self.scorer._refresh_epoch()
        assert np.array_equal(self.scorer._zone_ids, scorer._zone_ids)
        assert self.scorer._zone_ids.dtype == scorer._zone_ids.dtype
        assert self.scorer._n_zones == scorer._n_zones
        for flag in ("_any_prefer_taints", "_any_avoid_annotations",
                     "_any_images"):
            assert getattr(self.scorer, flag) is getattr(scorer, flag), flag
        n = 0
        for held, fresh in ((self.terms._cache, terms._cache),
                            (self.scorer._vec_cache, scorer._vec_cache)):
            for key, fn in _held(held):
                if self.rng.random() >= share:
                    continue
                got, want = held.vector(key, fn), fresh.vector(key, fn)
                assert got.dtype == want.dtype and got.shape == want.shape
                assert np.array_equal(got, want), key
                n += 1
        return n


@pytest.mark.parametrize("n_nodes", [64, 120, 300])
@pytest.mark.parametrize("seed", range(8))
def test_cached_vectors_equal_a_fresh_build_after_every_refresh(seed, n_nodes):
    c = Cluster(1000 * n_nodes + seed, n_nodes)
    c.refresh()
    c.ask()
    kinds = {key[0] for cache in (c.terms._cache, c.scorer._vec_cache)
             for key, _ in _held(cache)}
    assert {"tol", "sel", "ports", "nodeaff", "spread"} <= kinds
    capacity = c.mirror.t.capacity
    grow_at = c.rng.randrange(5, 30)
    for i in range(40):
        if i == grow_at:
            c.grow()
        c.step()
        c.ask(share=0.5)
        c.check(share=0.5)
    assert c.mirror.t.capacity > capacity
    c.ask()
    assert c.check() >= 12
    kinds = {key[0] for cache in (c.terms._cache, c.scorer._vec_cache)
             for key, _ in _held(cache)}
    assert {"tol", "sel", "ports", "nodeaff", "tainttol", "img", "avoid",
            "spread"} <= kinds, kinds


def test_a_patch_recomputes_the_stamped_rows_and_no_others():
    c = Cluster(7, 100)
    c.refresh()
    pod, port_pod = c.probes[0], c.probes[6]
    rows, rebuilds = c.mirror.vector_rows_recomputed, c.mirror.vector_rebuilds

    def use():
        c.terms.tolerations_vector(pod)
        after_tol = rows.value()
        c.terms.host_ports_vector(port_pod)
        return after_tol, rows.value(), rebuilds.value(cache="terms")
    assert use() == (100, 200, 2)
    # same epoch: a hit
    assert use() == (200, 200, 2)
    for _ in range(3):
        c.add_pod()
    touched = {p.spec.node_name for p in c.pods.values()}
    c.refresh()
    # a bind changes no taint: no row of `tol`, its rows of `ports`
    assert use() == (200, 200 + len(touched), 2)
    # at REBUILD_SHARE of the live rows the full walk is taken: every
    # update_node passes set_node, also with the object it already holds
    for name in sorted(c.nodes)[:50]:
        c.cache.update_node(c.nodes[name], c.nodes[name])
    c.refresh()
    assert use() == (300 + len(touched), 400 + len(touched), 4)
    c.check()


def test_zone_ids_rescan_on_a_relabel_and_patch_on_a_bind():
    c = Cluster(11, 100)
    c.refresh()
    c.scorer._refresh_epoch()
    rows, rebuilds = c.mirror.vector_rows_recomputed, c.mirror.vector_rebuilds
    assert (rows.value(), rebuilds.value(cache="zones")) == (100, 1)
    # a bind leaves the zones and the flags alone: not one row
    c.add_pod()
    c.refresh()
    c.scorer._refresh_epoch()
    assert (rows.value(), rebuilds.value(cache="zones")) == (100, 1)
    assert c.scorer._epoch < c.mirror.epoch
    # a taint keeps the row's zone: its flags are patched, one row
    c.add_pod()
    c.taint()
    c.refresh()
    c.scorer._refresh_epoch()
    assert (rows.value(), rebuilds.value(cache="zones")) == (101, 1)
    name = sorted(c.nodes)[0]
    new = copy.deepcopy(c.nodes[name])
    new.metadata.labels[ZONE] = "z-new"
    c.cache.update_node(c.nodes[name], new)
    c.nodes[name] = new
    c.refresh()
    c.scorer._refresh_epoch()
    assert rebuilds.value(cache="zones") == 2
    c.check()


def test_chained_rows_are_seen_when_the_epoch_next_moves():
    """apply_chained leaves the epoch and the cached vectors alone (the
    chain carries those binds on the device); the next apply() stamps what
    it wrote, as the parent's wholesale rebuild at that epoch saw it."""
    c = Cluster(13, 64)
    c.refresh()
    web = c.probes[9]
    meta = prios.PriorityMetadata(web, c.listers)
    before = c.scorer._spread_counts(web, meta).copy()
    name = sorted(c.nodes)[0]
    bound = make_pod("chained", labels={"app": "web"}, node_name=name)
    c.cache.add_pod(bound)
    epoch = c.mirror.epoch
    c.mirror.apply_chained(c.snapshot, c.cache.update_snapshot(c.snapshot))
    assert c.mirror.epoch == epoch
    assert np.array_equal(c.scorer._spread_counts(web, meta), before)
    c.add_pod()
    c.refresh()
    after = c.scorer._spread_counts(web, meta)
    assert after[c.mirror.row_of[name]] == before[c.mirror.row_of[name]] + 1
    c.check()


def test_the_cache_bound_evicts_without_changing_an_answer(monkeypatch):
    """The bound follows the queue: one batch keeps all 200 keys it uses
    (past the 128 that used to bound the cache by count); a key unused
    for NODE_VECTOR_IDLE_BATCHES batches goes as the next batch opens;
    past NODE_VECTOR_CACHE_BYTES the least recently used keys of earlier
    batches go, and a key of the batch in hand never; a dropped key comes
    back with the answer a fresh compiler gives."""
    c = Cluster(3, 64)
    c.taint()
    c.refresh()
    dropped = c.mirror.vector_evictions
    cache = c.terms._cache

    def held():
        return {key for key, _ in _held(cache)}

    def key(pod):
        return ("tol", tensorize._canon_tolerations(pod))
    pods = [make_pod(f"t{i}", tolerations=[api.Toleration(
        key=f"k{i}", operator="Exists", effect="NoSchedule")])
        for i in range(200)]
    c.terms.new_batch()
    first = [c.terms.tolerations_vector(p).copy() for p in pods]
    assert len(cache._entries) == 200 and dropped.value(cache="terms") == 0
    assert cache.nbytes == sum(e.vec.nbytes for e in cache._entries.values())
    # the first hundred stay in use, the second go idle
    for _ in range(tensorize.NODE_VECTOR_IDLE_BATCHES):
        c.terms.new_batch()
        for p in pods[:100]:
            c.terms.tolerations_vector(p)
    assert len(cache._entries) == 200
    c.terms.new_batch()
    assert held() == {key(p) for p in pods[:100]}
    assert dropped.value(cache="terms") == 100
    for _ in range(4):
        c.taint()
        c.add_pod()
    c.refresh()
    fresh = TermCompiler(c.mirror)
    for pod in pods:
        assert np.array_equal(c.terms.tolerations_vector(pod),
                              fresh.tolerations_vector(pod))
    assert len(cache._entries) == 200 and dropped.value(cache="terms") == 100
    # bytes for 150 vectors: a new key of a batch that uses 161 sends the
    # earlier batch's 40 away and keeps all 161 of its own; the next
    # batch's new key sends the oldest of the rest away, down to 150
    size = cache._entries[key(pods[0])].vec.nbytes
    monkeypatch.setattr(tensorize, "NODE_VECTOR_CACHE_BYTES", 150 * size)
    extra = [make_pod(f"x{i}", tolerations=[api.Toleration(
        key=f"x{i}", operator="Exists", effect="NoSchedule")])
        for i in range(2)]
    c.terms.new_batch()
    for p in pods[40:] + extra[:1]:
        c.terms.tolerations_vector(p)
    assert held() == {key(p) for p in pods[40:] + extra[:1]}
    assert dropped.value(cache="terms") == 140
    c.terms.new_batch()
    c.terms.tolerations_vector(extra[1])
    assert held() == {key(p) for p in pods[52:] + extra}
    assert cache.nbytes == 150 * size
    assert dropped.value(cache="terms") == 152
    for pod in pods[:60]:
        assert np.array_equal(c.terms.tolerations_vector(pod),
                              fresh.tolerations_vector(pod))


def test_a_service_event_starts_the_spread_vectors_over():
    c = Cluster(5, 64)
    for _ in range(6):
        c.add_pod()
    c.refresh()
    web = c.probes[9]
    counts = c.scorer._spread_counts(
        web, prios.PriorityMetadata(web, c.listers))
    assert counts.sum() == sum(
        1 for p in c.pods.values() if p.metadata.labels.get("app") == "web")
    # the Service goes: the shell's handler invalidates, and the vector
    # that outlived the epoch is not the one a later batch reads
    c.scorer.listers = c.listers = prios.SpreadListers()
    c.scorer.invalidate_spread_selectors()
    assert c.scorer._spread_counts(
        web, prios.PriorityMetadata(web, c.listers)) is None
    c.check()


# -------------------------------------------------------------- sides


def _sided(seed=17, n_nodes=100):
    """A cluster in which every kind of vector is alive before the event
    under test: n1 carries a PreferNoSchedule taint, an image and the
    prefer-avoid annotation, so the three flags are up; n0 is plain."""
    c = Cluster(seed, n_nodes)
    other = c.nodes["n1"]
    other.spec.taints = [api.Taint(key="other", value="x",
                                   effect="PreferNoSchedule")]
    other.status.images = [api.ContainerImage(
        names=["img-a"], size_bytes=500 * 1024 * 1024)]
    other.metadata.annotations[AVOID] = json.dumps(
        {"preferAvoidPods": [{"podSignature": {"podController": {
            "kind": "ReplicationController", "name": "rc-1"}}}]})
    for label in (ZONE, "disk"):
        c.nodes["n0"].metadata.labels.pop(label, None)
    for name in ("n0", "n1"):
        c.cache.update_node(c.nodes[name], c.nodes[name])
    c.refresh()
    c.ask()
    return c


def _probe(c, name):
    return next(p for p in c.probes if p.metadata.name == name)


def _meta(c, pod):
    return prios.PriorityMetadata(pod, c.listers)


def _relabel(node):
    node.metadata.labels["disk"] = "ssd"
    node.metadata.labels[ZONE] = "z1"


def _taint(node):
    node.spec.taints = [
        api.Taint(key="dedicated", value="x", effect="NoSchedule"),
        api.Taint(key="soft", value="x", effect="PreferNoSchedule")]


def _images(node):
    node.status.images = [api.ContainerImage(
        names=["img-a"], size_bytes=900 * 1024 * 1024)]


#: event -> (the change to n0's Node, what the vectors that read it
#: answered for n0's row before, and after)
NODE_EVENTS = {
    "relabel": (_relabel, {"sel": False, "sel-zones": False, "nodeaff": 1},
                {"sel": True, "sel-zones": True, "nodeaff": 4}),
    "taint": (_taint, {"tol": True, "tol-tolerated": True, "tainttol": 0,
                       "tainttol-tolerated": 0},
              {"tol": False, "tol-tolerated": True, "tainttol": 1,
               "tainttol-tolerated": 0}),
    "images": (_images, {"img": 0}, {"img": 8}),
}


def _answers(c, row):
    """n0's row of one vector of each node-side kind, by what NODE_EVENTS
    calls it."""
    plain, image = _probe(c, "plain"), _probe(c, "image-a")
    tolerates, soft = _probe(c, "tolerates"), _probe(c, "tolerates-soft")
    prefers = _probe(c, "prefers")
    c.scorer._refresh_epoch()
    vectors = {
        "sel": c.terms.node_selector_vector(_probe(c, "selects")),
        "sel-zones": c.terms.node_selector_vector(_probe(c, "in-zones")),
        "tol": c.terms.tolerations_vector(plain),
        "tol-tolerated": c.terms.tolerations_vector(tolerates),
        "nodeaff": c.scorer._node_affinity_raw(prefers, _meta(c, prefers)),
        "tainttol": c.scorer._taint_raw(plain, _meta(c, plain)),
        "tainttol-tolerated": c.scorer._taint_raw(soft, _meta(c, soft)),
        "img": c.scorer._image_raw(image, _meta(c, image)),
    }
    return {kind: vec[row].item() for kind, vec in vectors.items()}


@pytest.mark.parametrize("in_place", [True, False],
                         ids=["in-place", "by-copy"])
@pytest.mark.parametrize("event", sorted(NODE_EVENTS))
def test_a_node_event_reaches_the_vectors_that_read_the_node(event, in_place):
    """update_node(old, old) after an in-place change passes set_node like
    a new object does, and set_node is what the mirror's node stamp
    follows: the row is recomputed, once a vector, and no other row."""
    c = _sided()
    change, before, after = NODE_EVENTS[event]
    row = c.mirror.row_of["n0"]
    was = _answers(c, row)
    assert {k: was[k] for k in before} == before
    rows, rebuilds = c.mirror.vector_rows_recomputed, c.mirror.vector_rebuilds
    held = len(c.terms._cache._entries) + len(c.scorer._vec_cache._entries)
    old = c.nodes["n0"]
    new = old if in_place else copy.deepcopy(old)
    change(new)
    c.nodes["n0"] = new
    c.cache.update_node(old, new)
    node_epoch = c.mirror.node_epoch
    c.refresh()
    assert c.mirror.node_epoch == c.mirror.epoch == node_epoch + 1
    assert c.mirror.row_node_epoch[row] == c.mirror.epoch
    rows0, rebuilds0 = rows.value(), sum(rebuilds.snapshot().values())
    now = _answers(c, row)
    assert {k: now[k] for k in after} == after
    assert {k: v for k, v in now.items() if k not in after} == \
        {k: v for k, v in was.items() if k not in after}
    c.ask()
    # one row of every vector held (both sides: a node event is a write)
    # and of the flags; the relabel moves a zone, which rescans those
    zones = 100 if event == "relabel" else 1
    assert rows.value() - rows0 == held + zones
    assert sum(rebuilds.snapshot().values()) - rebuilds0 == \
        (event == "relabel")
    c.check()


def _bound(c, name, node_name):
    """A pod on `node_name` that holds host port 8080 and that the web
    Service selects."""
    pod = make_pod(name, labels={"app": "web"}, host_port=8080,
                   node_name=node_name)
    c.cache.add_pod(pod)
    return pod


def _pods_side(c, kind):
    if kind == "ports":
        return c.terms.host_ports_vector(_probe(c, "port-8080"))
    web = _probe(c, "web")
    return c.scorer._spread_counts(web, _meta(c, web))


@pytest.mark.parametrize("kind", ["ports", "spread"])
def test_a_vector_that_reads_the_pods_follows_a_bind_and_a_delete(kind):
    """Declared node-side, either would keep its answer through both."""
    c = _sided()
    row = c.mirror.row_of["n0"]
    free = {"ports": True, "spread": 0}[kind]
    taken = {"ports": False, "spread": 1}[kind]
    assert _pods_side(c, kind)[row] == free
    rows = c.mirror.vector_rows_recomputed
    node_epoch = c.mirror.node_epoch
    pod = _bound(c, "binds", "n0")
    c.refresh()
    rows0 = rows.value()
    assert _pods_side(c, kind)[row] == taken
    assert rows.value() - rows0 == 1
    c.cache.remove_pod(pod)
    c.refresh()
    assert _pods_side(c, kind)[row] == free
    assert rows.value() - rows0 == 2
    # neither was a node event: the node-side vectors were hits throughout
    assert c.mirror.node_epoch == node_epoch < c.mirror.epoch
    c.ask()
    assert rows.value() - rows0 == 2 + 2    # the other of the two kinds
    c.check()


def test_a_removed_rows_answer_is_0_and_the_node_that_retakes_it_is_computed():
    c = _sided()
    row = c.mirror.row_of["n2"]
    selects, plain = _probe(c, "selects"), _probe(c, "plain")
    c.nodes["n2"].metadata.labels.pop("disk", None)
    c.nodes["n2"].spec.taints = [api.Taint(key="soft", value="x",
                                           effect="PreferNoSchedule")]
    c.cache.update_node(c.nodes["n2"], c.nodes["n2"])
    _bound(c, "held", "n2")
    c.refresh()

    def read():
        c.scorer._refresh_epoch()
        return (bool(c.terms.tolerations_vector(plain)[row]),
                bool(c.terms.node_selector_vector(selects)[row]),
                bool(_pods_side(c, "ports")[row]),
                int(_pods_side(c, "spread")[row]),
                int(c.scorer._taint_raw(plain, _meta(c, plain))[row]),
                int(c.scorer._zone_ids[row]))
    assert read()[:5] == (True, False, False, 1, 1)
    c.cache.remove_node(c.nodes.pop("n2"))   # ni.node = None, no set_node
    c.refresh()
    assert "n2" not in c.mirror.row_of and c.mirror.infos[row] is None
    assert c.mirror.row_node_epoch[row] == c.mirror.epoch
    assert read() == (False, False, False, 0, 0, 0)
    # the freed row is the next one taken
    node = make_node("retakes", "z2")
    node.metadata.labels["disk"] = "ssd"
    node.spec.taints = [api.Taint(key="other", value="x",
                                  effect="PreferNoSchedule")]
    c.nodes["retakes"] = node
    c.cache.add_node(node)
    rows = c.mirror.vector_rows_recomputed
    c.refresh()
    assert c.mirror.row_of["retakes"] == row
    rows0 = rows.value()
    assert read()[:5] == (True, True, True, 0, 1)
    assert c.scorer._zone_ids[row] > 0
    # one row of each of the five vectors, and a rescan of the zones
    assert rows.value() - rows0 == 5 + len(c.nodes)
    c.check()


def test_a_resize_walks_every_vector_whatever_it_reads():
    c = _sided(n_nodes=100)
    capacity = c.mirror.t.capacity
    rows, rebuilds = c.mirror.vector_rows_recomputed, c.mirror.vector_rebuilds
    held = len(c.terms._cache._entries) + len(c.scorer._vec_cache._entries)
    c.grow()
    c.refresh()
    assert c.mirror.t.capacity > capacity
    assert (c.mirror.row_node_epoch == c.mirror.epoch).all()
    assert (c.mirror.row_epoch == c.mirror.epoch).all()
    assert c.mirror.node_epoch == c.mirror.epoch
    rows0, rebuilds0 = rows.value(), sum(rebuilds.snapshot().values())
    c.ask()
    assert sum(rebuilds.snapshot().values()) - rebuilds0 == held + 1
    assert rows.value() - rows0 == (held + 1) * len(c.nodes)
    c.check()


def test_chained_rows_reach_a_ports_vector_at_the_next_apply_and_no_sel():
    """apply_chained's rows are usage-only by construction: the next
    apply() stamps them on row_epoch alone."""
    c = _sided()
    row, other = c.mirror.row_of["n0"], c.mirror.row_of["n2"]
    selects = _probe(c, "selects")
    rows = c.mirror.vector_rows_recomputed
    _bound(c, "chained", "n0")
    epoch = c.mirror.epoch
    c.mirror.apply_chained(c.snapshot, c.cache.update_snapshot(c.snapshot))
    assert (c.mirror.epoch, c.mirror.node_epoch) == (epoch, epoch)
    rows0 = rows.value()
    assert _pods_side(c, "ports")[row]          # not seen yet: a hit
    c.terms.node_selector_vector(selects)
    assert rows.value() == rows0
    _bound(c, "applied", "n2")
    c.refresh()
    assert (c.mirror.epoch, c.mirror.node_epoch) == (epoch + 1, epoch)
    assert c.mirror.row_epoch[[row, other]].tolist() == [epoch + 1] * 2
    assert (c.mirror.row_node_epoch[[row, other]] <= epoch).all()
    ports = _pods_side(c, "ports")
    assert not ports[row] and not ports[other]
    assert rows.value() - rows0 == 2
    c.terms.node_selector_vector(selects)
    assert rows.value() - rows0 == 2
    c.check()


def test_the_mirror_counts_a_write_by_the_side_it_changed():
    c = Cluster(19, 100)
    writes = c.mirror.row_writes
    sides = lambda: (writes.value(side="node"), writes.value(side="usage"))
    assert sides() == (0, 0)
    c.refresh()
    assert sides() == (100, 0)                  # rows newly taken
    for _ in range(5):
        c.add_pod()
    touched = len({p.spec.node_name for p in c.pods.values()})
    c.refresh()
    assert sides() == (100, touched)            # binds
    c.remove_pod()
    c.refresh()
    assert sides() == (100, touched + 1)        # a delete
    c.cache.update_node(c.nodes["n3"], c.nodes["n3"])   # set_node
    c.add_pod()
    c.refresh()
    assert sides()[0] == 101 and sides()[1] in (touched + 1, touched + 2)
    c.cache.remove_node(c.nodes.pop("n4"))      # _remove_row
    c.pods = {k: p for k, p in c.pods.items() if p.spec.node_name != "n4"}
    c.refresh()
    assert sides()[0] == 102
    # a clone carries its set_node's name, a second set_node gets a new one
    ni = c.snapshot.node_infos["n3"]
    assert ni.clone().node_generation == ni.node_generation > 0
    gen = ni.node_generation
    ni.set_node(ni.node)
    assert ni.node_generation > gen


def test_a_mask_row_is_the_and_of_its_pods_term_vectors():
    """A row of unique_masks is what its pod's own term vectors (`tol`,
    `sel`, `ports`, hostname) and the caller's extra row AND to, built
    from the long-lived compiler after binds it did not have to see."""
    from kubernetes_tpu.scheduler.tensorize import PodBatchTensors
    c = _sided()
    _bound(c, "holds-8080", "n3")
    c.refresh()
    pods = list(c.probes) + [make_pod("pinned", node_name="n5"),
                             make_pod("pinned-selects", node_name="n0",
                                      node_selector={"disk": "ssd"})]
    rng = np.random.RandomState(5)
    extra = rng.random_sample((len(pods), c.mirror.t.capacity)) > 0.3
    extra[::2] = True                          # every other pod: no row
    for extra_mask in (None, extra):
        batch = PodBatchTensors(pods, c.mirror, c.terms,
                                extra_mask=extra_mask)
        fresh = TermCompiler(c.mirror)
        assert batch.n_unique_masks >= 7
        for i, pod in enumerate(pods):
            want = fresh.tolerations_vector(pod) & \
                fresh.node_selector_vector(pod)
            for vec in (fresh.host_ports_vector(pod),
                        fresh.hostname_vector(pod),
                        None if extra_mask is None else extra_mask[i]):
                if vec is not None:
                    want = want & vec
            assert np.array_equal(batch.unique_masks[batch.mask_idx[i]],
                                  want), pod.metadata.name
        assert not batch.unique_masks[batch.n_unique_masks:].any()
    row = c.mirror.row_of["n3"]
    assert not batch.unique_masks[batch.mask_idx[6], row]    # port-8080


# --------------------------------------------------------- engagement


def _served(n_nodes=200):
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.state import Client
    client = Client(validate=False)
    sched = Scheduler(client, batch_size=64)
    nodes = {}
    for i in range(n_nodes):
        node = make_node(f"n{i}", f"z{i % 4}")
        client.nodes().create(node)
        sched.cache.add_node(node)
        nodes[node.metadata.name] = node
    return client, sched, nodes


def _cycle(client, sched, names):
    for name in names:
        pod = client.pods("default").create(make_pod(name))
        tensorize.precompute_pod_features(pod)
        sched.queue.add(pod)
    results = sched.schedule_pending()
    assert len(results) == len(names) and all(r.node_name for r in results)


def test_the_series_are_declared_at_zero_on_a_fresh_registry():
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.state import Client
    sched = Scheduler(Client())
    scrape = parse_metrics(sched.metrics.registry.expose())
    assert scrape[ROWS] == 0
    for cache in ("terms", "scores", "zones"):
        assert scrape[f'{REBUILDS}{{cache="{cache}"}}'] == 0
    for side in ("node", "usage"):
        assert scrape[f'{WRITES}{{side="{side}"}}'] == 0
    mirror = sched.algorithm.mirror
    assert mirror.row_writes is sched.metrics.mirror_row_writes
    assert mirror.vector_rows_recomputed is \
        sched.metrics.node_vector_rows_recomputed
    assert mirror.vector_rebuilds is sched.metrics.node_vector_rebuilds


def test_a_cycle_after_a_bind_only_cycle_walks_no_cluster():
    client, sched, nodes = _served()
    rows = sched.metrics.node_vector_rows_recomputed
    rebuilds = sched.metrics.node_vector_rebuilds
    total = lambda: sum(rebuilds.snapshot().values())
    # the nodes arrive: every vector a plain pod uses takes the full walk
    _cycle(client, sched, [f"a{i}" for i in range(20)])
    assert rows.value() == 3 * 200
    assert (rebuilds.value(cache="terms"), rebuilds.value(cache="zones"),
            rebuilds.value(cache="scores")) == (2, 1, 0)
    # a bind-only cycle, and the one that follows it
    for prefix in "bc":
        rows0, rebuilds0 = rows.value(), total()
        epoch = sched.algorithm.mirror.epoch
        _cycle(client, sched, [f"{prefix}{i}" for i in range(20)])
        mirror = sched.algorithm.mirror
        assert mirror.epoch == epoch + 1
        dirtied = int((mirror.row_epoch == mirror.epoch).sum())
        assert 1 <= dirtied <= 20
        assert total() == rebuilds0
        # tol, sel, the zone ids and the flags read the node alone, and
        # a bind changes no node
        assert rows.value() - rows0 == 0
        writes = sched.metrics.mirror_row_writes
        assert writes.value(side="node") == 200
        assert writes.value(side="usage") >= dirtied
    # a zone relabel: one rescan of the zones, one row a term vector
    rows0 = rows.value()
    new = copy.deepcopy(nodes["n7"])
    new.metadata.labels[ZONE] = "z9"
    sched.cache.update_node(nodes["n7"], new)
    _cycle(client, sched, [f"d{i}" for i in range(20)])
    assert (rebuilds.value(cache="terms"), rebuilds.value(cache="zones"),
            rebuilds.value(cache="scores")) == (2, 2, 0)
    assert rows.value() - rows0 == 200 + 2
    assert sched.metrics.mirror_row_writes.value(side="node") == 201
    # and what the benchmark's metric divides: cycles
    scrape = parse_metrics(sched.metrics.registry.expose())
    assert scrape[ROWS] == rows.value()
    assert scrape["scheduler_e2e_scheduling_duration_seconds_count"] == 4
