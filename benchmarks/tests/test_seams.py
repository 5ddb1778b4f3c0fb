"""The benchmark's own tests of its seams (not tier-1; numpy only, a few
seconds: `python -m pytest benchmarks/tests/test_seams.py -q`): a new
deployment is new files, found by the names its configuration gives.

  - a configuration without the keys gets what stood in the harness
    before there were any: harness/reference.py and the plain node, byte
    for byte;
  - the base reference reads by whitelist: it refuses every key it does
    not read, on a pod, on a node and among the set-up objects, and its
    whitelist is what the accepted variants and the plain node carry,
    key for key;
  - the scan's byte model is harness/roofline.py's alone, over facts the
    reference exposes, and `scan_roofline` raises where it has none;
  - a reference that extends it (fixtures/references/) judges what the
    base refuses, through the very compare() that judges a run;
  - a node builder (fixtures/nodes/) and set-up objects reach the
    reference.

The fixtures stand where a later PR's files would (references/, nodes/,
variants/ under benchmarks/), with cluster.BENCH_DIR pointed at them.
"""

import copy
import hashlib
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH_DIR)
FIXTURES = os.path.join(HERE, "fixtures")
sys.path.insert(0, BENCH_DIR)

from harness import cluster, control, reference, roofline, verdict  # noqa: E402

ACCEPTED = ["sched-perf-5000n-basic", "sched-perf-5000n-antiaffinity"]
ZONE = cluster.ZONE
_AFF = "requiredDuringSchedulingIgnoredDuringExecution"
_PREF = "preferredDuringSchedulingIgnoredDuringExecution"


def _config(name):
    return cluster.load_json(BENCH_DIR, "configs", f"{name}.json")


def _fixture_config():
    """What a later PR's configuration file would hold, cut to a test."""
    config = _config("sched-perf-5000n-basic")
    config.update({
        "reference": "zone-node-affinity", "node_variant": "reserved",
        "pod_mix": [{"variant": "zone-pinned", "share": 1.0}],
        "setup_objects": [{
            "path": "/api/v1/namespaces/default/services",
            "manifest": {"apiVersion": "v1", "kind": "Service",
                         "metadata": {"name": "test",
                                      "namespace": "default"},
                         "spec": {"selector": {"name": "test"},
                                  "ports": [{"port": 80}]}}}]})
    config["node"] = dict(config["node"], zones=4, allocatable={
        "cpu": "3900m", "memory": "31Gi"})
    return config


@pytest.fixture
def fixture_files(monkeypatch):
    monkeypatch.setattr(cluster, "BENCH_DIR", FIXTURES)


# ------------------------------------------------ defaults, byte for byte

@pytest.mark.parametrize("name", ACCEPTED)
def test_accepted_configuration_has_the_base_reference_and_plain_node(name):
    config = _config(name)
    assert not {"reference", "node_variant", "setup_objects"} & set(config)
    assert cluster.load_reference(config) is reference
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        files = [c["file"] for c in json.load(f)["configs"]]
    assert f"benchmarks/configs/{name}.json" in files


# sha256 of json.dumps(make_nodes(config, 5000, seed), sort_keys=True) at
# d4cecf9, before make_nodes took a builder
NODES_BEFORE = {
    0: "5464569ad342650df226618c8cff8707f57f1650e5609a75e9f46df1210098fa",
    7: "5f870644220e7a797b9c961ce1fbd8e1d2adc9e54f07b871ab3af06a55ebe722",
    2147483659:
        "975232ed501414c337f83e2c850702b54abdb3530930f8c26457020c3efff073",
}


@pytest.mark.parametrize("seed", sorted(NODES_BEFORE))
def test_nodes_without_a_node_variant_are_what_they_were(seed):
    for name in ACCEPTED:
        config = _config(name)
        nodes = cluster.make_nodes(config, config["nodes"], seed)
        assert hashlib.sha256(json.dumps(nodes, sort_keys=True).encode()) \
            .hexdigest() == NODES_BEFORE[seed]


@pytest.mark.parametrize("name, per_node", zip(ACCEPTED, [24, 28]))
def test_accepted_variants_replay_and_count_their_bytes(name, per_node):
    config = _config(name)
    pods = cluster.PodStream(config, 2147483659).take(64)
    assert {roofline.scan_bytes_per_node(reference.PodFacts(m))
            for m in pods} == {per_node}
    compared, correct, said = control.run_control(
        config, 2147483659, 600, "exact", n_nodes=64)
    assert correct is True and said["replayed"] == 600, compared
    # the least time is those bytes over the table's HBM rate, as before
    assert roofline.scan_least_seconds("TPU v5 lite", 10, 5000, per_node) \
        == 10 * 5000 * per_node / roofline.peaks(
            "TPU v5 lite")["hbm_bytes_per_s"]


# ------------------------------------- the base refuses what it does not read

def _pod(**spec):
    m = cluster.PodStream(_config("sched-perf-5000n-basic"), 1).take(1)[0]
    m["spec"].update(copy.deepcopy(spec))
    return m


def _container(**more):
    m = _pod()
    c = m["spec"]["containers"][0]
    for key, value in more.items():
        if isinstance(value, dict):
            c.setdefault(key, {})
            for k, v in value.items():
                if isinstance(v, dict):
                    c[key].setdefault(k, {}).update(v)
                else:
                    c[key][k] = v
        else:
            c[key] = value
    return m


def _meta(**more):
    m = _pod()
    m["metadata"].update(more)
    return m


_TERM = {"labelSelector": {"matchLabels": {"color": "blue"}},
         "topologyKey": "kubernetes.io/hostname"}
_NODE_TERM = {"nodeSelectorTerms": [{"matchExpressions": [{
    "key": ZONE, "operator": "In", "values": ["zone-1"]}]}]}
_ANTI = "spec.affinity.podAntiAffinity"
#: case -> (the path the refusal names, the manifest); the first twelve
#: are the fields the issue lists, the rest what a blacklist of those
#: would still let through
REFUSED_PODS = {
    "nodeSelector": ("spec.nodeSelector", _pod(nodeSelector={ZONE: "zone-1"})),
    "nodeName": ("spec.nodeName", _pod(nodeName="node-3")),
    "tolerations": ("spec.tolerations",
                    _pod(tolerations=[{"operator": "Exists"}])),
    "priority": ("spec.priority", _pod(priority=1000)),
    "priorityClassName": ("spec.priorityClassName",
                          _pod(priorityClassName="high")),
    "volumes": ("spec.volumes", _pod(volumes=[{"name": "v", "emptyDir": {}}])),
    "nodeAffinity-required": ("spec.affinity.nodeAffinity", _pod(
        affinity={"nodeAffinity": {_AFF: _NODE_TERM}})),
    "nodeAffinity-preferred": ("spec.affinity.nodeAffinity", _pod(
        affinity={"nodeAffinity": {_PREF: [{
            "weight": 1,
            "preference": _NODE_TERM["nodeSelectorTerms"][0]}]}})),
    "podAffinity-required": ("spec.affinity.podAffinity", _pod(
        affinity={"podAffinity": {_AFF: [_TERM]}})),
    "podAffinity-preferred": ("spec.affinity.podAffinity", _pod(
        affinity={"podAffinity": {_PREF: [{
            "weight": 1, "podAffinityTerm": _TERM}]}})),
    "podAntiAffinity-preferred": (f"{_ANTI}.{_PREF}", _pod(
        affinity={"podAntiAffinity": {_PREF: [{
            "weight": 1, "podAffinityTerm": _TERM}]}})),
    "topologyKey-zone": (f"{_ANTI}.{_AFF}.topologyKey", _pod(
        affinity={"podAntiAffinity": {_AFF: [
            dict(_TERM, topologyKey=ZONE)]}})),
    "term-matchExpressions": (
        f"{_ANTI}.{_AFF}.labelSelector.matchExpressions", _pod(
            affinity={"podAntiAffinity": {_AFF: [dict(_TERM, labelSelector={
                "matchExpressions": [{"key": "color", "operator": "In",
                                      "values": ["blue"]}]})]}})),
    "term-namespaces": (f"{_ANTI}.{_AFF}.namespaces", _pod(
        affinity={"podAntiAffinity": {_AFF: [
            dict(_TERM, namespaces=["other"])]}})),
    "hostPort": ("spec.containers.ports.hostPort", _container(
        ports=[{"containerPort": 80, "hostPort": 8080}])),
    "extended-resource": ("spec.containers.resources.requests.example.com/gpu",
                          _container(resources={"requests": {
                              "example.com/gpu": "1"}})),
    "ephemeral-storage": (
        "spec.containers.resources.requests.ephemeral-storage",
        _container(resources={"requests": {"ephemeral-storage": "1Gi"}})),
    "initContainers": ("spec.initContainers", _pod(initContainers=[{
        "name": "init", "image": "busybox",
        "resources": {"requests": {"cpu": "2"}}}])),
    "overhead": ("spec.overhead", _pod(overhead={"cpu": "250m"})),
    "topologySpreadConstraints": ("spec.topologySpreadConstraints", _pod(
        topologySpreadConstraints=[{
            "maxSkew": 1, "topologyKey": ZONE,
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"name": "test"}}}])),
    "schedulerName": ("spec.schedulerName", _pod(schedulerName="other")),
    "namespace": ("metadata.namespace", _meta(namespace="tenant-a")),
    "annotations": ("metadata.annotations", _meta(annotations={
        "scheduler.alpha.kubernetes.io/affinity": "{}"})),
    "ownerReferences": ("metadata.ownerReferences", _meta(ownerReferences=[{
        "kind": "ReplicaSet", "name": "rs", "uid": "u"}])),
}
THE_ISSUE_LISTS = [
    "spec.nodeSelector", "spec.nodeName", "spec.affinity.nodeAffinity",
    "spec.affinity.podAffinity", f"{_ANTI}.{_PREF}", "spec.tolerations",
    "spec.priority", "spec.priorityClassName", "spec.volumes"]


def _node(**parts):
    node = cluster.plain_node(3, _config("sched-perf-5000n-basic"))
    for part, more in parts.items():
        node.setdefault(part, {}).update(more)
    return node


REFUSED_NODES = {
    "taints": ("spec.taints", _node(spec={"taints": [{
        "key": "dedicated", "value": "x", "effect": "NoSchedule"}]})),
    "unschedulable": ("spec.unschedulable",
                      _node(spec={"unschedulable": True})),
    "images": ("status.images", _node(status={"images": [{
        "names": ["k8s.gcr.io/pause:3.1"], "sizeBytes": 700000}]})),
    "extended-allocatable": ("status.allocatable.example.com/gpu", _node(
        status={"allocatable": {"cpu": "4", "memory": "32Gi", "pods": "110",
                                "example.com/gpu": "8"}})),
    "not-ready": ("status.conditions", _node(status={"conditions": [{
        "type": "Ready", "status": "False"}]})),
    "pressure": ("status.conditions", _node(status={"conditions": [
        {"type": "Ready", "status": "True"},
        {"type": "MemoryPressure", "status": "True"}]})),
    "node-annotations": ("metadata.annotations", _node(metadata={
        "annotations": {"scheduler.alpha.kubernetes.io/preferAvoidPods":
                        "{}"}})),
}


def _carried(manifests, reads):
    """{(path, key)} of the keys these manifests carry, walked as `admit`
    walks them: below a path the whitelist has no entry for, a value is
    taken whole."""
    seen = set()

    def walk(at, path):
        if isinstance(at, list):
            for item in at:
                walk(item, path)
        elif isinstance(at, dict) and path in reads:
            for key, value in at.items():
                seen.add((path, key))
                walk(value, f"{path}.{key}" if path else key)
    for m in manifests:
        walk(m, "")
    return seen


def _listed(reads):
    return {(path, key) for path, keys in reads.items() for key in keys}


def test_the_whitelists_are_what_the_accepted_cluster_carries():
    pods = [m for name in ACCEPTED
            for m in cluster.PodStream(_config(name), 3).take(4)]
    assert _carried(pods, reference.PodFacts.reads) \
        == _listed(reference.PodFacts.reads)
    nodes = cluster.make_nodes(_config(ACCEPTED[0]), 4, 3)
    # `spec` is listed, with no key under it, so that a taint is named
    assert _listed(reference.Reference.reads) \
        - _carried(nodes, reference.Reference.reads) == {("", "spec")}
    assert {path for path, _ in REFUSED_PODS.values()} >= set(THE_ISSUE_LISTS)


@pytest.mark.parametrize("case", sorted(REFUSED_PODS) + sorted(REFUSED_NODES)
                         + ["set-up objects"])
def test_base_reference_refuses(case):
    config = _config("sched-perf-5000n-basic")
    nodes = cluster.make_nodes(config, 8, 1)
    path = (REFUSED_PODS.get(case) or REFUSED_NODES.get(case)
            or ("set-up objects",))[0]
    with pytest.raises(ValueError, match=re.escape(path)):
        if case in REFUSED_PODS:
            reference.PodFacts(copy.deepcopy(REFUSED_PODS[case][1]))
        elif case in REFUSED_NODES:
            nodes[3] = copy.deepcopy(REFUSED_NODES[case][1])
            reference.Reference(nodes)
        else:
            reference.replay(nodes, [], {}, objects=_fixture_config()[
                "setup_objects"][0:1])
    # what says nothing (the API's omitempty) is read as before
    assert reference.PodFacts(_pod(
        priority=0, volumes=[], nodeName="", nodeSelector={},
        hostNetwork=False, affinity=None)).cpu == 100
    assert reference.Reference(cluster.make_nodes(config, 8, 1)).names


def test_scan_roofline_raises_where_a_scan_has_no_byte_count():
    spec = cluster.load_json(BENCH_DIR, "metrics", "scan_roofline.json")
    read = cluster.load_module(os.path.join(
        BENCH_DIR, "metrics", "scan_roofline.py")).read
    ctx = {"trace": {"programs": {"jit_schedule_batch.7": {"seconds": 0.5}}},
           "slice_pods_scheduled": 20000, "nodes": 5000,
           "device": {"kind": "TPU v5 lite"}}
    for missing in ({}, {"scan_bytes_per_pod_node": 0.0}):
        with pytest.raises(ValueError, match="scan_bytes_per_pod_node"):
            read(dict(ctx, **missing), spec)
    share = read(dict(ctx, scan_bytes_per_pod_node=28.0), spec)
    assert share == pytest.approx(100 * 20000 * 5000 * 28 / roofline.peaks(
        "TPU v5 lite")["hbm_bytes_per_s"] / 0.5)
    # no scan in the slice, or an untraced run: nothing to read
    assert read(dict(ctx, trace={"programs": {}}), spec) is None
    assert read({"scan_bytes_per_pod_node": 28.0}, spec) is None


# ------------------------------- a deployment of new files (the fixtures)

def _facts(ref, nodes, pods, bound, objects=()):
    """What a run hands compare(), from a table pod name -> node."""
    listed = [{**m, "spec": {**m["spec"], "nodeName": bound[
        m["metadata"]["name"]]}, "status": {"conditions": [{
            "type": "PodScheduled", "status": "True"}]}} for m in pods]
    return verdict.compare(
        ref, nodes, pods,
        {m["metadata"]["name"]: rv for rv, m in enumerate(pods, 1)},
        dict(bound), [], listed, {verdict.SCHEDULED: len(bound)}, [0, 0],
        "", objects=objects)


def test_extended_reference_judges_what_the_base_refuses(fixture_files):
    config = _fixture_config()
    ref = cluster.load_reference(config)
    assert ref is not reference and ref.replay != reference.replay
    objects = [o["manifest"] for o in config["setup_objects"]]
    nodes = cluster.make_nodes(config, 32, 7)
    pods = cluster.PodStream(config, 7).take(200)
    with pytest.raises(ValueError, match="nodeAffinity"):
        reference.PodFacts(pods[0])
    assert roofline.scan_bytes_per_node(ref.PodFacts(pods[0])) == 28
    assert not hasattr(ref, "scan_bytes_per_node")
    serial = ref.Reference(nodes, "exact", objects)
    bound = {}
    for m in pods:
        pod = ref.PodFacts(m)
        bound[pod.name] = serial.decide(pod)
        serial.bind(pod, bound[pod.name])
    zone_of = {n["metadata"]["name"]: n["metadata"]["labels"][ZONE]
               for n in nodes}
    assert all(zone_of[bound[f"pod-{i}"]] in
               (f"zone-{i % 4}", f"zone-{(i + 1) % 4}") for i in range(200))
    sound = _facts(ref, nodes, pods, bound, objects)
    assert verdict.correct(sound), sound
    assert len(sound) == 11 and all(c["limit"] == 0 for c in sound.values())
    # the last pod outside its zones: it does not fit, whatever it scores
    last = pods[-1]["metadata"]["name"]
    outside = next(n for n, z in zone_of.items()
                   if z not in ("zone-3", "zone-0"))
    wrong = _facts(ref, nodes, pods, dict(bound, **{last: outside}), objects)
    assert wrong["binds_that_do_not_fit"]["value"] == 1
    assert wrong["score_gap_max"]["value"] == 0
    assert not verdict.correct(wrong)
    # inside its zones on a fuller node: it fits, and a better one was there
    fullest = max((n for n, z in zone_of.items()
                   if z in ("zone-3", "zone-0")),
                  key=lambda n: serial.cpu[serial.row[n]])
    worse = _facts(ref, nodes, pods, dict(bound, **{last: fullest}), objects)
    assert worse["binds_that_do_not_fit"]["value"] == 0
    assert worse["score_gap_max"]["value"] > 0


def test_node_variant_builds_the_nodes_in_the_seeded_order(fixture_files):
    config = _fixture_config()
    nodes = cluster.make_nodes(config, 50, 2147483659)
    plain = cluster.make_nodes(_config("sched-perf-5000n-basic"), 50,
                               2147483659)
    assert [n["metadata"]["name"] for n in nodes] == \
        [n["metadata"]["name"] for n in plain]
    assert all(n["status"]["allocatable"] == {
        "cpu": "3900m", "memory": "31Gi", "pods": "110"} for n in nodes)
    assert all(n["status"]["capacity"]["cpu"] == "4" for n in nodes)
    ref = cluster.load_reference(config).Reference(nodes)
    assert set(ref.cap_cpu) == {3900} and set(ref.cap_mem) == {31 << 30}


def test_setup_objects_reach_the_reference(fixture_files, monkeypatch):
    config = _fixture_config()
    ref = cluster.load_reference(config)
    service = config["setup_objects"][0]["manifest"]
    assert ref.Reference(cluster.make_nodes(config, 4, 1), "exact",
                         [service]).services == [service]
    with pytest.raises(ValueError, match="PriorityClass"):
        ref.Reference(cluster.make_nodes(config, 4, 1), "exact",
                      [{"kind": "PriorityClass"}])
    seen = []
    read = ref.Reference.read_objects
    monkeypatch.setattr(cluster, "load_reference", lambda config: ref)
    monkeypatch.setattr(ref.Reference, "read_objects", lambda self, objects: (
        seen.append(list(objects)), read(self, objects))[1])
    compared, correct, _ = control.run_control(config, 5, 300, "exact",
                                               n_nodes=40)
    assert correct is True, compared
    # once by the control's own scheduler, once by compare()'s replay
    assert seen == [[service], [service]]


def test_control_reads_not_correct_through_an_extended_reference(
        fixture_files):
    compared, correct, _ = control.run_control(
        _fixture_config(), 7, 6000, "int8", n_nodes=400)
    assert correct is False
    assert compared["score_gap_max"]["value"] > 0, compared
