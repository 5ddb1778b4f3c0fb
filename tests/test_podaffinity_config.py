"""The support of `sched-perf-5000n-podaffinity` (PR 30), small and on
the CPU: (a) its plain reference alone, on hand cases; (b) the program
against that reference on seeded random clusters, through the very
compare() that judges a run; (c) the in-scan waiver on a batch that
holds the first two pods of a colour; (d) the series and the metric
files that read them."""

import copy
import json
import os
import random
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

from harness import cluster, control, roofline, verdict  # noqa: E402

ZONE = cluster.ZONE
REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"
CONFIG = cluster.load_json(BENCH, "configs",
                           "sched-perf-5000n-podaffinity.json")
ref = cluster.load_reference(CONFIG)


def node(i, zone, cpu="4"):
    n = cluster.plain_node(i, {"node": {"cpu": cpu, "memory": "32Gi",
                                        "pods": 110, "zones": 1}})
    if zone is None:
        del n["metadata"]["labels"][ZONE]
    else:
        n["metadata"]["labels"][ZONE] = zone
    return n


def pod(i, colour, wants=None, cpu="100m"):
    """MakePodSpec's pod with label `colour` and, unless `wants` is
    False, a required zone affinity term selecting `wants` (its own
    colour by default)."""
    p = cluster.load_named("variants", "pod-affinity").build(
        i, None, {"pod": dict(CONFIG["pod"], cpu=cpu), "seed": 0,
                  "colours": 1})
    p["metadata"]["labels"]["color"] = colour
    term = p["spec"]["affinity"]["podAffinity"][REQUIRED][0]
    if wants is False:
        del p["spec"]["affinity"]
    else:
        term["labelSelector"]["matchLabels"]["color"] = wants or colour
    return p


# ------------------------------------------------ (a) the reference alone


class TestReferenceAlone:
    def two_zones(self):
        return ref.Reference([node(0, "a"), node(1, "a"), node(2, "b"),
                              node(3, "b"), node(4, None)])

    def test_it_is_the_configurations_and_imports_nothing_of_the_program(
            self):
        assert CONFIG["reference"] == "interpod-affinity"
        assert ref.__name__.endswith("interpod_affinity")
        assert issubclass(ref.Reference, cluster.reference.Reference)
        assert issubclass(ref.PodFacts, cluster.reference.PodFacts)
        assert ref.replay.__self__ is ref.Reference
        with open(ref.__file__) as f:
            assert "kubernetes_tpu" not in "".join(
                ln for ln in f if ln.lstrip().startswith(("import", "from")))

    def test_the_waiver_then_the_zone_of_the_first(self):
        r = self.two_zones()
        first = ref.PodFacts(pod(0, "red"))
        # matches nothing anywhere, matches itself: every node with the key
        assert r.fits(first).tolist() == [True, True, True, True, False]
        r.bind(first, "node-2")
        second = ref.PodFacts(pod(1, "red"))
        assert r.fits(second).tolist() == [False, False, True, True, False]
        assert r.judge(second, "node-0") == (False, 0)
        assert r.judge(second, "node-3")[0]

    def test_a_term_matched_elsewhere_but_not_by_the_pod_itself(self):
        r = self.two_zones()
        chaser = ref.PodFacts(pod(0, "red", wants="blue"))
        assert not r.fits(chaser).any()     # no blue pod, and it is not one
        r.bind(ref.PodFacts(pod(1, "blue")), "node-1")
        assert r.fits(chaser).tolist() == [True, True, False, False, False]

    def test_a_selector_first_seen_late_counts_what_was_bound_before(self):
        r = self.two_zones()
        r.bind(ref.PodFacts(pod(0, "blue", wants=False)), "node-3")
        r.bind(ref.PodFacts(pod(1, "blue", wants=False)), "node-4")
        chaser = ref.PodFacts(pod(2, "red", wants="blue"))
        # the blue pod on the node without a label is in no zone
        assert r.fits(chaser).tolist() == [False, False, True, True, False]

    def test_the_priority_moves_the_argmax(self):
        """Two zones, unequal carriers: 10 and 0. Three red pods that
        require red sit in zone b, a CPU each; zone a is empty. A red
        pod without a term fits everywhere: resources alone send it to
        zone a, the credit of the three carriers to zone b."""
        r = ref.Reference([node(0, "a"), node(1, "a"), node(2, "b"),
                           node(3, "b"), node(4, "b"), node(5, None)])
        for i, name in ((0, "node-2"), (1, "node-3"), (2, "node-4")):
            r.bind(ref.PodFacts(pod(i, "red", cpu="1")), name)
        plain = ref.PodFacts(pod(3, "red", wants=False))
        ok = r.fits(plain)
        assert ok.all()
        assert r.interpod(plain, ok).tolist() == [0, 0, 10, 10, 10, 0]
        without = cluster.reference.Reference.scores(r, plain)
        assert r.names[int(np.argmax(without))] in ("node-0", "node-1",
                                                    "node-5")
        assert r.decide(plain) == "node-2"
        fit, gap = r.judge(plain, "node-0")
        assert fit and 0 < gap <= 10
        assert r.judge(plain, "node-3") == (True, 0)
        # unequal and both above 0: the steps between min and max
        r.bind(ref.PodFacts(pod(4, "red")), "node-0")
        assert r.interpod(plain, ok).tolist() == [3, 3, 10, 10, 10, 0]
        # a blue pod reads no credit at all: computed, and flat
        blue = ref.PodFacts(pod(5, "blue", wants=False))
        assert not r.interpod(blue, r.fits(blue)).any()

    def test_min_and_max_are_over_the_fitting_nodes(self):
        r = self.two_zones()
        r.bind(ref.PodFacts(pod(0, "red")), "node-0")
        r.bind(ref.PodFacts(pod(1, "red")), "node-1")
        follower = ref.PodFacts(pod(2, "red"))
        ok = r.fits(follower)
        assert ok.tolist() == [True, True, False, False, False]
        assert not r.interpod(follower, ok).any()   # one zone: max = min

    @pytest.mark.parametrize("path, change", [
        ("spec.affinity.podAffinity.preferredDuringSchedulingIgnored"
         "DuringExecution", lambda p: p["spec"]["affinity"][
             "podAffinity"].update(
                 preferredDuringSchedulingIgnoredDuringExecution=[{
                     "weight": 1, "podAffinityTerm": {}}])),
        (f"spec.affinity.podAffinity.{REQUIRED}.namespaces",
         lambda p: p["spec"]["affinity"]["podAffinity"][REQUIRED][0]
         .update(namespaces=["other"])),
        (f"spec.affinity.podAffinity.{REQUIRED}.labelSelector."
         "matchExpressions",
         lambda p: p["spec"]["affinity"]["podAffinity"][REQUIRED][0][
             "labelSelector"].update(matchExpressions=[{
                 "key": "color", "operator": "Exists"}])),
        (f"spec.affinity.podAffinity.{REQUIRED}.topologyKey",
         lambda p: p["spec"]["affinity"]["podAffinity"][REQUIRED][0]
         .update(topologyKey=cluster.HOSTNAME)),
        ("spec.nodeSelector",
         lambda p: p["spec"].update(nodeSelector={"a": "b"})),
    ])
    def test_the_whitelist_refuses(self, path, change):
        p = pod(0, "red")
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

    def test_extra_words_and_the_scan_bytes(self):
        variant = ref.PodFacts(pod(0, "red"))
        plain = ref.PodFacts(pod(1, "red", wants=False))
        assert (variant.extra_words, plain.extra_words) == (2, 1)
        assert roofline.scan_bytes_per_node(variant) == 32
        assert roofline.scan_bytes_per_node(plain) == 28
        stream = cluster.PodStream(CONFIG, 11).take(64)
        assert {roofline.scan_bytes_per_node(ref.PodFacts(m))
                for m in stream} == {32}

    def test_the_variant_cycles_its_colours_from_the_seed(self):
        a = cluster.PodStream(CONFIG, 5).take(801)
        colours = [m["metadata"]["labels"]["color"] for m in a]
        assert colours[0] == "c5" and colours[400] == "c5"
        assert len(set(colours)) == CONFIG["colours"] == 400
        for m in a[:3]:
            term, = m["spec"]["affinity"]["podAffinity"][REQUIRED]
            assert term == {"labelSelector": {"matchLabels": {
                "color": m["metadata"]["labels"]["color"]}},
                "topologyKey": ZONE}
        assert a == cluster.PodStream(CONFIG, 5).take(801)

    def test_replay_reports_the_fullest_zone(self):
        nodes = [node(0, "a"), node(1, "b"), node(2, "b")]
        pods = [pod(i, "red") for i in range(4)]
        bound = {f"pod-{i}": "node-0" for i in range(4)}
        out = ref.replay(nodes, pods, bound)
        assert out["binds_that_do_not_fit"] == 0
        assert out["fullest_zone_fill"] == pytest.approx(0.1)


# ------------------------- (b) the program against the reference, seeded


def random_cluster(seed):
    """64-200 nodes in 4 zones, every ninth without the label; pods of
    the variant and, one in four, pods whose term selects the colour
    before theirs (which has a pod by then, perhaps of the same batch),
    so that a colour comes to sit in several zones and the priority
    differs across them."""
    rng = random.Random(seed)
    n_nodes = rng.randrange(64, 201)
    nodes = [node(i, None if i % 9 == 8 else f"zone-{i % 4}")
             for i in range(n_nodes)]
    rng.shuffle(nodes)
    colours = rng.randrange(4, 9)
    pods = []
    for i in range(rng.randrange(150, 260)):
        k = i % colours
        chases = i >= colours and rng.random() < 0.25
        pods.append(pod(i, f"c{k}",
                        wants=f"c{(k - 1) % colours}" if chases else None))
    return nodes, pods, colours


def run_program(nodes, pods, batches, prepare=None):
    """The pods through Scheduler.schedule_pending over the in-process
    client, `batches` pods a cycle; what compare() is handed.
    `prepare(sched)` sees the scheduler before its first cycle."""
    from kubernetes_tpu.api import serde
    from kubernetes_tpu.runtime import SCHEME
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.state import Client
    client = Client()
    for n in nodes:
        client.nodes().create(SCHEME.decode_any(n))
    sched = Scheduler(client, batch_size=1024)
    if prepare is not None:
        prepare(sched)
    sched.informers.start()
    sched.informers.wait_for_cache_sync()
    try:
        client.pods("default").create_bulk(
            [SCHEME.decode_any(m) for m in pods])
        deadline = time.monotonic() + 30
        while sched.queue.num_pending() < len(pods):
            assert time.monotonic() < deadline
            time.sleep(0.01)
        done = 0
        for size in batches:
            done += len(sched.schedule_pending(max_pods=size, timeout=1.0))
        while done < len(pods):
            got = sched.schedule_pending(timeout=1.0)
            assert got, f"{len(pods) - done} pods never popped"
            done += len(got)
        listed = [serde.encode(p) for p in client.pods("default").list()]
        scrape = parse_metrics(sched.metrics.registry.expose())
    finally:
        sched.informers.stop()
    return listed, scrape


def parse_metrics(lines):
    from harness.children import parse_metrics as parse
    return parse("\n".join(lines) if not isinstance(lines, str) else lines)


def judged(nodes, pods, listed, scrape, reference=None):
    """compare() over a run of run_program, under this configuration's
    reference or the one given."""
    created = {m["metadata"]["name"]: i + 1 for i, m in enumerate(pods)}
    watch = {p["metadata"]["name"]: p["spec"].get("nodeName")
             for p in listed}
    said = {}
    compared = verdict.compare(
        reference or ref, nodes, pods, created, watch, [], listed, scrape,
        [0, 0], "",
        say=lambda phase, **fields: said.update(fields))
    return compared, said


@pytest.fixture(scope="module", params=[30, 31, 32])
def seeded_run(request):
    nodes, pods, colours = random_cluster(request.param)
    rng = random.Random(request.param)
    batches = [rng.choice((1, 7, 24, 60)) for _ in range(6)]
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
        # the clusters are such that the priority has something to say:
        # some colour sits in more than one zone
        zone_of = {n["metadata"]["name"]: n["metadata"]["labels"].get(ZONE)
                   for n in nodes}
        spread = {}
        for p in listed:
            spread.setdefault(p["metadata"]["labels"]["color"], set()).add(
                zone_of[p["spec"]["nodeName"]])
        assert max(len(z) for z in spread.values()) > 1
        # a pod that chases a colour of its own batch widens a mask row
        # taken at the batch's start: the batch is cut before it, and
        # that is the one way these batches leave the scan
        assert {k for k, v in scrape.items()
                if k.startswith(FALLBACKS + "{") and v} <= {
            FALLBACKS + '{reason="aff_growth"}'}
        assert scrape[FALLBACKS + '{reason="aff_growth"}'] \
            >= scrape[FALLBACK_BATCHES] > 0

    def test_an_answer_moved_to_the_wrong_zone_does_not_fit(self,
                                                            seeded_run):
        nodes, pods, listed, scrape = seeded_run
        listed = copy.deepcopy(listed)
        zone_of = {n["metadata"]["name"]: n["metadata"]["labels"].get(ZONE)
                   for n in nodes}
        last = listed[-1]
        for p in listed:    # the last pod that requires its own colour
            term = p["spec"]["affinity"]["podAffinity"][REQUIRED][0]
            if term["labelSelector"]["matchLabels"]["color"] == \
                    p["metadata"]["labels"]["color"]:
                last = p
        colour = last["metadata"]["labels"]["color"]
        taken = {zone_of[p["spec"]["nodeName"]] for p in listed
                 if p["metadata"]["labels"]["color"] == colour}
        wrong = next(name for name, z in sorted(zone_of.items())
                     if z is not None and z not in taken)
        last["spec"]["nodeName"] = wrong
        compared, _ = judged(nodes, pods, listed, scrape)
        assert compared["binds_that_do_not_fit"]["value"] > 0
        assert not verdict.correct(compared)


def test_the_control_reads_not_correct_at_rehearsal_size():
    config = dict(CONFIG, **CONFIG["rehearse"])
    compared, correct, said = control.run_control(
        config, 7, 4000, "int8", n_nodes=config["nodes"])
    assert not correct and compared["score_gap_max"]["value"] >= 1
    compared, correct, _ = control.run_control(
        config, 7, 600, "exact", n_nodes=config["nodes"])
    assert correct


# --------------------------------------------- (c) the in-scan waiver


FALLBACKS = "scheduler_topo_inscan_fallbacks_total"
FALLBACK_BATCHES = "scheduler_topo_inscan_fallback_batches_total"


def test_the_first_two_pods_of_a_colour_in_one_batch_share_a_zone():
    """A rehearsal-sized cluster with every colour of the rehearsal
    bound, then one batch that holds the first two pods of a new colour
    among pods of the old ones: the second follows the first inside the
    scan, and no batch leaves it."""
    config = dict(CONFIG, **CONFIG["rehearse"], seed=0)
    nodes = cluster.make_nodes(config, 160, 3)
    stream = cluster.PodStream(config, 3)
    old = stream.take(64)
    batch = stream.take(30)
    for i, m in ((7, 1000), (19, 1001)):
        batch[i] = pod(m, "new")
    pods = old + batch
    listed, scrape = run_program(nodes, pods, [64, 30])
    zone_of = {n["metadata"]["name"]: n["metadata"]["labels"][ZONE]
               for n in nodes}
    new = [zone_of[p["spec"]["nodeName"]] for p in listed
           if p["metadata"]["labels"]["color"] == "new"]
    assert len(new) == 2 and new[0] == new[1]
    assert scrape[FALLBACK_BATCHES] == 0
    assert not any(v for k, v in scrape.items() if k.startswith(FALLBACKS))
    assert scrape['scheduler_scheduling_duration_seconds_count'
                  '{operation="repair"}'] == 2
    compared, said = judged(nodes, pods, listed, scrape)
    assert verdict.correct(compared), said
    # every batch was decided by the class-indexed scan
    assert scrape["scheduler_constraint_templates_total"] >= 32
    assert scrape['scheduler_affinity_evaluations_total'
                  '{route="host",stage="masks"}'] == 2
    assert scrape['scheduler_affinity_evaluations_total'
                  '{route="device",stage="masks"}'] == 0


# ------------------------------------ (d) the series and the data files


def test_the_new_series_are_there_at_zero_from_process_start():
    from kubernetes_tpu.scheduler.metrics import (INSCAN_FALLBACK_REASONS,
                                                  STAGE_PARTS,
                                                  SchedulerMetrics)
    scrape = parse_metrics(SchedulerMetrics().registry.expose())
    for op in STAGE_PARTS:
        for part in ("sum", "count"):
            assert scrape["scheduler_scheduling_duration_seconds_"
                          f'{part}{{operation="{op}"}}'] == 0
    for reason in INSCAN_FALLBACK_REASONS:
        assert scrape[f'{FALLBACKS}{{reason="{reason}"}}'] == 0
    for name in (FALLBACK_BATCHES, "scheduler_constraint_templates_total",
                 "scheduler_constraint_terms_total"):
        assert scrape[name] == 0
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in ("sched_topology_apply_ms_per_pod",
                 "sched_affinity_masks_ms_per_pod",
                 "sched_affinity_scores_ms_per_pod",
                 "sched_templates_per_cycle", "sched_inscan_fallback_share"):
        spec = cluster.load_json(BENCH, "metrics", f"{name}.json")
        assert spec["kind"] == "scrape_ratio"
        assert scrape[spec["numerator"]] == 0 == scrape[spec["denominator"]]
        entry = entries[name]
        assert "workloads" not in entry     # holds in every cell
        assert (entry["layer"], entry["moves"], entry["unit"]) == \
            (spec["layer"], spec["moves"], spec["unit"])
        assert not os.path.exists(os.path.join(BENCH, "metrics",
                                               f"{name}.py"))


def test_the_cells_and_the_configuration_are_declared_as_data():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells["podaff5k.wave4096"]["config"] == CONFIG["name"]
    assert cells["podaff5k.wave4096"]["traffic"] == "wave4096"
    assert cells["basic5k.trickle64"]["config"] == "sched-perf-5000n-basic"
    assert {cells[c]["chips"] for c in ("podaff5k.wave4096",
                                        "basic5k.trickle64")} == {1}
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"] == ["colours", "zones"]
    assert set(CONFIG["reduced"]) <= set(CONFIG["departures"])
    mix = cluster.load_json(BENCH, "traffic", "trickle64.json")
    assert (mix["in_flight"], mix["creators"], mix["chunk"]) == (64, 1, 8)
    assert max(mix["warm_bursts"]) <= mix["in_flight"]
    # the loaded cell is the one run.py would run
    _, cell, config, mix = cluster.load_cell("podaff5k.wave4096")
    assert config == CONFIG and mix["in_flight"] == 4096
    assert cluster.load_reference(config).__file__ == ref.__file__
