"""ISSUE 34: cached node vectors catch up with the mirror by row.

`TensorMirror` stamps each row with the epoch of its last write;
`tensorize.NodeVectorCache` (behind `TermCompiler._vector` and
`ScoreCompiler._vec`) and `ScoreCompiler._refresh_epoch` recompute the rows
stamped since a vector was last true and nothing else. These tests pin

  - equality: over seeded random sequences of cache events, after every
    `refresh` what the long-lived compilers hold equals what compilers
    built fresh over the same mirror compute by the full walk;
  - the bound: the cache evicts by count without changing an answer;
  - engagement: through `Scheduler.schedule_pending`, a cycle that follows
    a bind walks no cluster, by the two counters on /metrics.
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
    pod = c.probes[0]
    c.terms.tolerations_vector(pod)
    rows, rebuilds = c.mirror.vector_rows_recomputed, c.mirror.vector_rebuilds
    assert (rows.value(), rebuilds.value(cache="terms")) == (100, 1)
    # same epoch: a hit
    c.terms.tolerations_vector(pod)
    assert (rows.value(), rebuilds.value(cache="terms")) == (100, 1)
    for _ in range(3):
        c.add_pod()
    touched = {p.spec.node_name for p in c.pods.values()}
    c.refresh()
    c.terms.tolerations_vector(pod)
    assert rows.value() == 100 + len(touched)
    assert rebuilds.value(cache="terms") == 1
    # at REBUILD_SHARE of the live rows the full walk is taken
    for name in sorted(c.nodes)[:50]:
        c.cache.update_node(c.nodes[name], c.nodes[name])
    c.refresh()
    c.terms.tolerations_vector(pod)
    assert rows.value() == 200 + len(touched)
    assert rebuilds.value(cache="terms") == 2
    c.check()


def test_zone_ids_rescan_on_a_relabel_and_patch_on_a_bind():
    c = Cluster(11, 100)
    c.refresh()
    c.scorer._refresh_epoch()
    rebuilds = c.mirror.vector_rebuilds
    assert rebuilds.value(cache="zones") == 1
    c.add_pod()
    c.taint()
    c.refresh()
    c.scorer._refresh_epoch()
    assert rebuilds.value(cache="zones") == 1
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


def test_the_cache_bound_evicts_without_changing_an_answer():
    c = Cluster(3, 64)
    c.taint()
    c.refresh()
    bound = tensorize.NODE_VECTOR_CACHE_SIZE
    pods = [make_pod(f"t{i}", tolerations=[api.Toleration(
        key=f"k{i}", operator="Exists", effect="NoSchedule")])
        for i in range(bound + 12)]
    first = [c.terms.tolerations_vector(p).copy() for p in pods]
    assert len(c.terms._cache._entries) == bound
    held = {key for key, _ in _held(c.terms._cache)}
    assert ("tol", tensorize._canon_tolerations(pods[0])) not in held
    assert ("tol", tensorize._canon_tolerations(pods[-1])) in held
    for _ in range(4):
        c.taint()
        c.add_pod()
    c.refresh()
    fresh = TermCompiler(c.mirror)
    for pod, was in zip(pods, first):
        got = c.terms.tolerations_vector(pod)
        assert np.array_equal(got, fresh.tolerations_vector(pod))
        assert len(c.terms._cache._entries) == bound
    # a use moves a key to the young end: the oldest goes, not it
    c.terms.tolerations_vector(pods[12])
    c.terms.tolerations_vector(make_pod("one-more", tolerations=[
        api.Toleration(key="one-more", operator="Exists")]))
    held = {key for key, _ in _held(c.terms._cache)}
    assert ("tol", tensorize._canon_tolerations(pods[12])) in held
    assert ("tol", tensorize._canon_tolerations(pods[13])) not in held


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
    mirror = sched.algorithm.mirror
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
        # tol, sel and the zone ids: vectors used + 1
        assert 0 < rows.value() - rows0 <= dirtied * 3
    # a zone relabel: one rescan of the zones, the term vectors patched
    rows0 = rows.value()
    new = copy.deepcopy(nodes["n7"])
    new.metadata.labels[ZONE] = "z9"
    sched.cache.update_node(nodes["n7"], new)
    _cycle(client, sched, [f"d{i}" for i in range(20)])
    assert (rebuilds.value(cache="terms"), rebuilds.value(cache="zones"),
            rebuilds.value(cache="scores")) == (2, 2, 0)
    assert 200 < rows.value() - rows0 <= 200 + 2 * 21
    # and what the benchmark's metric divides: cycles
    scrape = parse_metrics(sched.metrics.registry.expose())
    assert scrape[ROWS] == rows.value()
    assert scrape["scheduler_e2e_scheduling_duration_seconds_count"] == 4
