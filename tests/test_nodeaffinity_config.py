"""The support of `sched-perf-5000n-nodeaffinity` (PR 35), small and on
the CPU: (a) its plain reference alone, on hand cases; (b) the program
against that reference and against its own oracle on seeded random
clusters, through the very compare() that judges a run; (c) the
control; (d) what a pop of every selector makes of the mask table and
the class axis; (e) the series and the data files that read them."""

import copy
import json
import os
import random
import sys

import numpy as np
import pytest
from test_podaffinity_config import judged as judged_by, parse_metrics, \
    run_program

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

from harness import cluster, control, roofline, verdict  # noqa: E402

ZONE = cluster.ZONE
REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"
CONFIG = cluster.load_json(BENCH, "configs",
                           "sched-perf-5000n-nodeaffinity.json")
REHEARSAL = dict(CONFIG, **CONFIG["rehearse"])
ref = cluster.load_reference(CONFIG)
variant = cluster.load_named("variants", "node-affinity")

_AFF = "spec.affinity.nodeAffinity"
_TERMS = f"{_AFF}.{REQUIRED}.nodeSelectorTerms"


def node(i, zone):
    n = cluster.plain_node(i, {"node": dict(CONFIG["node"], zones=1)})
    if zone is None:
        del n["metadata"]["labels"][ZONE]
    else:
        n["metadata"]["labels"][ZONE] = zone
    return n


def pod(i, *terms, cpu="100m"):
    """MakePodSpec's pod requiring one of `terms` (ORed), each a list of
    `In` value lists on the zone label (ANDed); no term: no affinity."""
    p = variant.build(i, None, {"pod": dict(CONFIG["pod"], cpu=cpu),
                                "seed": 0, "zones": 2, "selectors": 1})
    if not terms:
        del p["spec"]["affinity"]
        return p
    p["spec"]["affinity"]["nodeAffinity"][REQUIRED]["nodeSelectorTerms"] = [
        {"matchExpressions": [{"key": ZONE, "operator": "In",
                               "values": list(values)} for values in term]}
        for term in terms]
    return p


def expression(p):
    return p["spec"]["affinity"]["nodeAffinity"][REQUIRED][
        "nodeSelectorTerms"][0]["matchExpressions"][0]


# ------------------------------------------------ (a) the reference alone


class TestReferenceAlone:
    def cluster(self):
        return ref.Reference([node(0, "a"), node(1, "b"), node(2, "c"),
                              node(3, "a"), node(4, None)])

    def test_it_is_the_configurations_and_imports_nothing_of_the_program(
            self):
        assert CONFIG["reference"] == "node-affinity"
        assert ref.__name__.endswith("node_affinity")
        assert issubclass(ref.Reference, cluster.reference.Reference)
        assert issubclass(ref.PodFacts, cluster.reference.PodFacts)
        assert ref.replay.__self__ is ref.Reference
        with open(ref.__file__) as f:
            imports = [ln for ln in f
                       if ln.lstrip().startswith(("import", "from"))]
        assert "kubernetes_tpu" not in "".join(imports)
        # a file of its own, not the fixture no configuration may name
        assert not any("fixtures" in ln or "zone-node" in ln
                       for ln in imports)

    def test_in_on_the_zone_label_and_a_node_without_it(self):
        r = self.cluster()
        assert r.fits(ref.PodFacts(pod(0, [["a", "b"]]))).tolist() == \
            [True, True, False, True, False]
        assert r.fits(ref.PodFacts(pod(1, [["c"]]))).tolist() == \
            [False, False, True, False, False]
        # a zone no node carries, and a pod without the field
        assert not r.fits(ref.PodFacts(pod(2, [["z"]]))).any()
        assert r.fits(ref.PodFacts(pod(3))).all()

    def test_terms_are_ored_and_a_terms_expressions_anded(self):
        r = self.cluster()
        either = ref.PodFacts(pod(0, [["a"]], [["c"]]))
        assert r.fits(either).tolist() == [True, False, True, True, False]
        both = ref.PodFacts(pod(1, [["a", "b"], ["b", "c"]]))
        assert r.fits(both).tolist() == [False, True, False, False, False]
        # an empty term matches no node, beside one that matches some
        p = pod(2, [["b"]])
        p["spec"]["affinity"]["nodeAffinity"][REQUIRED][
            "nodeSelectorTerms"].append({"matchExpressions": []})
        assert r.fits(ref.PodFacts(p)).tolist() == \
            [False, True, False, False, False]
        # a required field without terms matches nothing at all
        p["spec"]["affinity"]["nodeAffinity"][REQUIRED][
            "nodeSelectorTerms"] = []
        assert not r.fits(ref.PodFacts(p)).any()

    def test_the_predicate_is_a_fit_and_the_score_is_the_bases(self):
        r = self.cluster()
        confined = ref.PodFacts(pod(0, [["a", "b"]], cpu="1"))
        assert r.judge(confined, "node-2") == (False, 0)
        assert r.judge(confined, "node-4") == (False, 0)
        assert r.judge(confined, "node-1") == (True, 0)
        assert r.decide(confined) == "node-0"
        r.bind(confined, "node-0")
        # the emptier node of the pod's zones scores higher; the empty
        # node of zone c is none of its business
        fit, gap = r.judge(confined, "node-0")
        assert fit and gap > 0
        assert r.judge(confined, "node-3") == (True, 0)
        assert (r.scores(confined)
                == cluster.reference.Reference.scores(r, confined)).all()

    @pytest.mark.parametrize("path, change", [
        (f"{_TERMS}.matchExpressions.operator",
         lambda p: expression(p).update(operator="NotIn")),
        (f"{_TERMS}.matchExpressions.operator",
         lambda p: expression(p).update(operator="Exists", values=[])),
        (f"{_TERMS}.matchExpressions.key",
         lambda p: expression(p).update(key=cluster.HOSTNAME)),
        (f"{_TERMS}.matchFields",
         lambda p: p["spec"]["affinity"]["nodeAffinity"][REQUIRED][
             "nodeSelectorTerms"][0].update(matchFields=[{
                 "key": "metadata.name", "operator": "In",
                 "values": ["node-0"]}])),
        (f"{_AFF}.preferredDuringSchedulingIgnoredDuringExecution",
         lambda p: p["spec"]["affinity"]["nodeAffinity"].update(
             preferredDuringSchedulingIgnoredDuringExecution=[{
                 "weight": 1, "preference": {"matchExpressions": [{
                     "key": ZONE, "operator": "In",
                     "values": ["a"]}]}}])),
        ("spec.nodeSelector",
         lambda p: p["spec"].update(nodeSelector={ZONE: "a"})),
        ("spec.affinity.podAffinity",
         lambda p: p["spec"]["affinity"].update(podAffinity={
             REQUIRED: [{"topologyKey": ZONE, "labelSelector": {
                 "matchLabels": {"color": "blue"}}}]})),
        ("spec.tolerations",
         lambda p: p["spec"].update(tolerations=[{"operator": "Exists"}])),
    ])
    def test_the_whitelist_refuses(self, path, change):
        p = pod(0, [["a", "b"]])
        ref.PodFacts(p)
        change(p)
        with pytest.raises(ValueError, match=path.replace(".", r"\.")):
            ref.PodFacts(p)

    def test_it_still_answers_for_what_the_base_answers_for(self):
        anti = cluster.load_named("variants", "pod-anti-affinity").build(
            0, None, dict(CONFIG, seed=0, colours=4))
        assert len(ref.PodFacts(anti).anti) == 1
        with pytest.raises(ValueError, match="set-up objects"):
            ref.Reference([node(0, "a")], objects=[{"kind": "Service"}])
        tainted = node(0, "a")
        tainted["spec"] = {"taints": [{"key": "k", "effect": "NoSchedule"}]}
        with pytest.raises(ValueError, match=r"spec\.taints"):
            ref.Reference([tainted])

    def test_extra_words_and_the_scan_bytes(self):
        confined, plain = ref.PodFacts(pod(0, [["a"]])), ref.PodFacts(pod(1))
        assert (confined.extra_words, plain.extra_words) == (1, 0)
        assert roofline.scan_bytes_per_node(confined) == 28
        assert roofline.scan_bytes_per_node(plain) == 24
        stream = cluster.PodStream(CONFIG, 11).take(64)
        assert {roofline.scan_bytes_per_node(ref.PodFacts(m))
                for m in stream} == {28}

    def test_replay_counts_the_pods_outside_their_zones(self):
        nodes = [node(0, "a"), node(1, "b"), node(2, "c"), node(3, None)]
        pods = [pod(0, [["a", "b"]]), pod(1, [["a", "b"]]),
                pod(2, [["a", "b"]]), pod(3, [["a", "b"]]), pod(4)]
        bound = {"pod-0": "node-0", "pod-1": "node-1", "pod-2": "node-2",
                 "pod-3": "node-3", "pod-4": "node-2"}
        out = ref.replay(nodes, pods, bound)
        assert out["pods_outside_their_zones"] == 2
        assert out["binds_that_do_not_fit"] == 2
        bound.update({"pod-2": "node-0", "pod-3": "node-1"})
        out = ref.replay(nodes, pods, bound)
        assert (out["pods_outside_their_zones"],
                out["binds_that_do_not_fit"]) == (0, 0)


class TestVariant:
    def test_it_cycles_every_pair_from_the_seed(self):
        a = cluster.PodStream(CONFIG, 5).take(241)
        lists = [tuple(expression(m)["values"]) for m in a]
        assert CONFIG["selectors"] == 120 == len(set(lists))
        assert lists[0] == lists[120] == lists[240]
        assert set(lists[:120]) == set(lists[7:127])
        pairs = variant.zone_pairs(16)
        assert len(pairs) == 120 and list(pairs) == sorted(pairs)
        assert all(a < b for a, b in pairs)
        assert lists[0] == tuple(f"zone-{z}" for z in pairs[5])
        for m in a[:3]:
            term, = m["spec"]["affinity"]["nodeAffinity"][REQUIRED][
                "nodeSelectorTerms"]
            assert term == {"matchExpressions": [{
                "key": ZONE, "operator": "In",
                "values": expression(m)["values"]}]}
        assert a == cluster.PodStream(CONFIG, 5).take(241)

    def test_every_seed_gives_the_same_kinds_of_pods(self):
        def kinds(seed):
            return sorted(json.dumps(dict(m, metadata=None), sort_keys=True)
                          for m in cluster.PodStream(CONFIG, seed).take(120))
        assert kinds(5) == kinds(2147483659) == kinds(0)
        # every zone lies in 15 pairs: the load is symmetric
        zones = [z for m in cluster.PodStream(CONFIG, 3).take(120)
                 for z in expression(m)["values"]]
        assert {zones.count(f"zone-{k}") for k in range(16)} == {15}

    def test_the_rehearsal_has_its_own_six(self):
        lists = {tuple(expression(m)["values"])
                 for m in cluster.PodStream(REHEARSAL, 9).take(40)}
        assert len(lists) == REHEARSAL["selectors"] == 6 \
            == len(variant.zone_pairs(REHEARSAL["zones"]))
        with pytest.raises(ValueError, match="selectors"):
            variant.build(0, None, dict(REHEARSAL, seed=0, selectors=7))


# ------------------------- (b) the program against the reference, seeded


def random_cluster(seed):
    """The rehearsal's zones and selectors over 64-200 nodes, every ninth
    without the label, created in an order drawn from the seed; pods of
    the variant at 500m (a node is full at eight, so the scores move
    through their levels and the zones fill unevenly), every eleventh
    with two terms, every thirteenth with none."""
    rng = random.Random(seed)
    config = dict(REHEARSAL, pod=dict(CONFIG["pod"], cpu="500m"))
    nodes = cluster.make_nodes(config, rng.randrange(64, 201), seed)
    for n in nodes:
        if int(n["metadata"]["name"].split("-")[1]) % 9 == 8:
            del n["metadata"]["labels"][ZONE]
    pods = cluster.PodStream(config, seed).take(rng.randrange(180, 300))
    for i, p in enumerate(pods):
        if i % 11 == 10:
            p["spec"]["affinity"]["nodeAffinity"][REQUIRED][
                "nodeSelectorTerms"].append({"matchExpressions": [{
                    "key": ZONE, "operator": "In",
                    "values": [f"zone-{rng.randrange(4)}"]}]})
        elif i % 13 == 12:
            del p["spec"]["affinity"]
    return nodes, pods


def judged(nodes, pods, listed, scrape):
    return judged_by(nodes, pods, listed, scrape, reference=ref)


@pytest.fixture(scope="module", params=[35, 36, 37])
def seeded_run(request):
    nodes, pods = random_cluster(request.param)
    rng = random.Random(request.param)
    batches = [rng.choice((1, 7, 24, 60)) for _ in range(5)]
    listed, scrape = run_program(nodes, pods, batches)
    return nodes, pods, listed, scrape


class TestProgramAgainstReference:
    def test_every_number_compared_is_zero(self, seeded_run):
        nodes, pods, listed, scrape = seeded_run
        compared, said = judged(nodes, pods, listed, scrape)
        assert len(compared) == 11
        assert {k: c["value"] for k, c in compared.items()
                if c["value"]} == {}, said
        assert verdict.correct(compared)
        assert said["replayed"] == len(pods)
        assert said["pods_outside_their_zones"] == 0
        # the predicate had something to say: the confined pods left
        # the nodes of no zone to the pods without a term
        zone_of = {n["metadata"]["name"]: n["metadata"]["labels"].get(ZONE)
                   for n in nodes}
        on_unlabelled = [p for p in listed
                         if zone_of[p["spec"]["nodeName"]] is None]
        assert on_unlabelled and not any(
            "affinity" in p["spec"] and p["spec"]["affinity"]
            for p in on_unlabelled)
        # one static-mask row a distinct selector, every batch
        assert scrape["scheduler_static_mask_rows_total"] \
            >= scrape["scheduler_scan_classes_total"] > 0
        assert scrape['scheduler_scheduling_duration_seconds_count'
                      '{operation="static_masks"}'] == \
            scrape['scheduler_scheduling_duration_seconds_count'
                   '{operation="tensorize"}'] > 0

    def test_every_bind_passes_the_programs_own_oracle(self, seeded_run):
        from kubernetes_tpu.runtime import SCHEME
        from kubernetes_tpu.scheduler import predicates
        from kubernetes_tpu.scheduler.nodeinfo import NodeInfo
        nodes, pods, listed, _ = seeded_run
        info = {n["metadata"]["name"]: NodeInfo(SCHEME.decode_any(n))
                for n in nodes}
        by_name = {p["metadata"]["name"]: p for p in listed}
        r = ref.Reference(nodes)
        for m in pods:
            p = SCHEME.decode_any(m)
            chosen = by_name[m["metadata"]["name"]]["spec"]["nodeName"]
            assert predicates.pod_match_node_selector(
                p, None, info[chosen]) == (True, [])
            # and the oracle draws the reference's line, node by node
            allowed = ref.PodFacts(m).terms
            want = r.allowed(allowed) if allowed is not None \
                else np.ones(len(nodes), bool)
            got = [predicates.pod_match_node_selector(
                p, None, info[name])[0] for name in r.names]
            assert got == want.tolist()

    def test_an_answer_moved_out_of_its_zones_does_not_fit(self, seeded_run):
        nodes, pods, listed, scrape = seeded_run
        listed = copy.deepcopy(listed)
        zone_of = {n["metadata"]["name"]: n["metadata"]["labels"].get(ZONE)
                   for n in nodes}
        moved = next(p for p in reversed(listed) if "affinity" in p["spec"]
                     and p["spec"]["affinity"])
        named = {v for t in moved["spec"]["affinity"]["nodeAffinity"][
            REQUIRED]["nodeSelectorTerms"]
            for e in t["matchExpressions"] for v in e["values"]}
        moved["spec"]["nodeName"] = next(
            name for name, z in sorted(zone_of.items())
            if z is not None and z not in named)
        compared, said = judged(nodes, pods, listed, scrape)
        assert compared["binds_that_do_not_fit"]["value"] == 1
        assert said["pods_outside_their_zones"] == 1
        assert not verdict.correct(compared)


# ----------------------------------------------------- (c) the control


def test_the_control_reads_not_correct_at_rehearsal_size():
    compared, correct, said = control.run_control(
        REHEARSAL, 7, 4000, "int8", n_nodes=REHEARSAL["nodes"])
    assert not correct and compared["score_gap_max"]["value"] >= 1
    assert compared["binds_that_do_not_fit"]["value"] == 0
    assert said["pods_outside_their_zones"] == 0
    compared, correct, _ = control.run_control(
        REHEARSAL, 7, 600, "exact", n_nodes=REHEARSAL["nodes"])
    assert correct


# ------------------- (d) a pop that holds every selector of the cell


def test_a_batch_of_240_pods_has_120_mask_rows_and_120_classes():
    """Two pods of each of the 120 selectors in one batch, on the cell's
    16 zones: one row of unique_masks and one class of the class scan a
    selector, 625 nodes of 5,000 in the proportion a row admits, and the
    decisions the reference would make."""
    config = dict(CONFIG, nodes=160)
    nodes = cluster.make_nodes(config, 160, 4)
    pods = cluster.PodStream(config, 4).take(240)
    seen = {}

    def watch(sched):
        launch = sched.algorithm.schedule_launch

        def launching(batch_pods, *a, **kw):
            pending = launch(batch_pods, *a, **kw)
            seen["batch"] = pending.batch
            return pending
        sched.algorithm.schedule_launch = launching
        seen["terms"] = sched.algorithm.terms

    listed, scrape = run_program(nodes, pods, [240], prepare=watch)
    batch = seen["batch"]
    assert len(batch.pods) == 240
    assert batch.n_unique_masks == 120 == batch.n_classes
    assert batch.unique_masks.shape[0] == 128
    assert batch._class_tables["class_req"].shape[0] == 128
    assert sorted(batch.unique_masks[:120].sum(axis=1).tolist()) == \
        [20] * 120          # 160 nodes in 16 zones: 2 x 10 a pair
    assert scrape["scheduler_static_mask_rows_total"] == 120
    assert scrape["scheduler_scan_classes_total"] == 120
    # 120 `sel` keys and one `tol`, all kept: the batch in hand loses
    # none of its keys, whatever their number
    assert len(seen["terms"]._cache._entries) == 121
    assert sum(sched_evictions(scrape).values()) == 0
    compared, said = judged(nodes, pods, listed, scrape)
    assert verdict.correct(compared), said
    assert said["pods_outside_their_zones"] == 0


def test_bind_only_cycles_of_120_selectors_recompute_no_row():
    """The cell's shape, served: 120 selectors alive in every pop, and
    after the first cycle nothing but binds. The 121 cached term vectors
    (and the zones and flags) read the node alone, so the three cycles
    that follow binds recompute not one row (PR 36), while each still
    stacks its 120 mask rows."""
    config = dict(CONFIG, nodes=160)
    nodes = cluster.make_nodes(config, 160, 4)
    pods = cluster.PodStream(config, 4).take(960)
    series = ("scheduler_node_vector_rows_recomputed_total",
              "scheduler_static_mask_rows_total",
              'scheduler_mirror_row_writes_total{side="node"}',
              'scheduler_mirror_row_writes_total{side="usage"}')
    cycles = []

    def watch(sched):
        cycle = sched.schedule_pending

        def counted(*a, **kw):
            results = cycle(*a, **kw)
            scrape = parse_metrics(sched.metrics.registry.expose())
            rebuilds = sum(sched.metrics.node_vector_rebuilds
                           .snapshot().values())
            cycles.append([scrape[name] for name in series] + [rebuilds])
            return results
        sched.schedule_pending = counted

    listed, scrape = run_program(nodes, pods, [240] * 4, prepare=watch)
    assert len(cycles) == 4
    first = cycles[0]
    # the first cycle walks: 120 `sel`, one `tol`, the zones, 160 rows each
    assert first == [122 * 160, 120, 160, 0, 122]
    for before, after in zip(cycles, cycles[1:]):
        rows, masks, node_writes, usage_writes, rebuilds = \
            (b - a for a, b in zip(before, after))
        assert (rows, masks, node_writes, rebuilds) == (0, 120, 0, 0)
        assert 1 <= usage_writes <= 160
    compared, said = judged(nodes, pods, listed, scrape)
    assert verdict.correct(compared), said
    assert said["pods_outside_their_zones"] == 0


def sched_evictions(scrape):
    """cache -> scheduler_node_vector_evictions_total{cache} of a scrape."""
    from kubernetes_tpu.scheduler.metrics import NODE_VECTOR_CACHES
    return {cache: scrape[f'scheduler_node_vector_evictions_total'
                          f'{{cache="{cache}"}}']
            for cache in NODE_VECTOR_CACHES}


# ------------------------------------ (e) the series and the data files

NEW_METRICS = ("sched_static_masks_ms_per_pod",
               "sched_static_masks_per_cycle",
               "sched_scan_classes_per_cycle")


def test_the_new_series_are_there_at_zero_from_process_start():
    from kubernetes_tpu.scheduler.metrics import (STAGE_PARTS,
                                                  SchedulerMetrics)
    assert "static_masks" in STAGE_PARTS
    scrape = parse_metrics(SchedulerMetrics().registry.expose())
    for part in ("sum", "count"):
        assert scrape["scheduler_scheduling_duration_seconds_"
                      f'{part}{{operation="static_masks"}}'] == 0
    for name in ("scheduler_static_mask_rows_total",
                 "scheduler_scan_classes_total"):
        assert scrape[name] == 0
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    # appended in this order; later PRs append after them
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 3] == list(NEW_METRICS)
    for name in NEW_METRICS:
        spec = cluster.load_json(BENCH, "metrics", f"{name}.json")
        assert spec["kind"] == "scrape_ratio"
        assert scrape[spec["numerator"]] == 0 == scrape[spec["denominator"]]
        entry = entries[name]
        assert "workloads" not in entry     # holds in every cell
        assert entry["better"] == "lower"
        assert (entry["layer"], entry["moves"], entry["unit"]) == \
            (spec["layer"], spec["moves"], spec["unit"]) == \
            ("scheduler host", "pods_bound_per_s", entry["unit"])
        assert not os.path.exists(os.path.join(BENCH, "metrics",
                                               f"{name}.py"))


def test_the_cell_and_the_configuration_are_declared_as_data():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert len(bench["workloads"]) >= 5 and len(bench["configs"]) >= 4
    cell = next(w for w in bench["workloads"]
                if w["name"] == "nodeaff5k.wave4096")
    assert cell == dict(cell, name="nodeaff5k.wave4096",
                        config=CONFIG["name"], traffic="wave4096", chips=1)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    assert entry["name"] == CONFIG["name"] == "sched-perf-5000n-nodeaffinity"
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    for word in ("BenchmarkSchedulingNodeAffinity",
                 "makeBasePodWithNodeAffinity"):
        assert word in entry["source"]
    assert entry["reduced"] == CONFIG["reduced"] == ["selectors", "zones"]
    assert set(CONFIG["reduced"]) <= set(CONFIG["departures"])
    assert CONFIG["architecture"] is None
    assert CONFIG["guarantees"]["every_pod_on_a_node_of_its_zones"] is True
    assert CONFIG["pod_mix"] == [{"variant": "node-affinity", "share": 1.0}]
    assert (CONFIG["nodes"], CONFIG["existing_pods"], CONFIG["selectors"],
            CONFIG["zones"], CONFIG["node"]["zones"]) == \
        (5000, 1000, 120, 16, 16)
    basic = cluster.load_json(BENCH, "configs",
                              "sched-perf-5000n-basic.json")
    for key in ("node", "pod", "scheduler_config", "processes"):
        assert CONFIG[key] == basic[key]
    assert (REHEARSAL["nodes"], REHEARSAL["zones"], REHEARSAL["selectors"],
            REHEARSAL["existing_pods"]) == (800, 4, 6, 100)
    # the loaded cell is the one run.py would run
    _, loaded, config, mix = cluster.load_cell("nodeaff5k.wave4096")
    assert loaded == cell and config == CONFIG and mix["in_flight"] == 4096
    assert cluster.load_reference(config).__file__ == ref.__file__
