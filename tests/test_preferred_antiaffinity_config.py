"""The support of `sched-perf-5000n-preferred-antiaffinity`, small
and on the CPU: (a) its plain reference alone, on hand cases; (b) the
program's copies of InterPodAffinityPriority's normalisation against
hand rows; (c) the program against that reference on seeded clusters,
through the very compare() that judges a run, once with the variant
alone and once on nodes that plain pods fill unevenly, where the old
normalisation (min and max over the fitting nodes alone) picks other
nodes; (d) the channel plan of a 4,096-pod pop; (e) the new series;
(f) the data files; (g) the control."""

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

NAME = "sched-perf-5000n-preferred-antiaffinity"
CELL = "prefanti5k.wave4096"
PREFERRED = "preferredDuringSchedulingIgnoredDuringExecution"
CONFIG = cluster.load_json(BENCH, "configs", f"{NAME}.json")
ref = cluster.load_reference(CONFIG)
variant = cluster.load_named("variants", "preferred-anti-affinity")

SOFT_CHANNELS = "scheduler_soft_channels_total"
FALLBACKS = "scheduler_topo_inscan_fallbacks_total"
FALLBACK_BATCHES = "scheduler_topo_inscan_fallback_batches_total"
COUNT = 'scheduler_scheduling_duration_seconds_count{operation="%s"}'


def node(i, cpu="4", memory="32Gi"):
    return cluster.plain_node(i, {"node": {"cpu": cpu, "memory": memory,
                                           "pods": 110, "zones": 4}})


def pod(i, weight=1, colour="green", cpu="100m", memory="500Mi",
        term=True):
    """The variant's pod labelled `colour`, its one term (which selects
    green) of weight `weight`, or without the term (`term=False`)."""
    p = variant.build(i, None, {"pod": dict(CONFIG["pod"], cpu=cpu,
                                            memory=memory)})
    p["metadata"]["labels"]["color"] = colour
    if not term:
        del p["spec"]["affinity"]
        return p
    t, = p["spec"]["affinity"]["podAntiAffinity"][PREFERRED]
    t["weight"] = weight
    return p


def facts(*a, **kw):
    return ref.PodFacts(pod(*a, **kw))


# ------------------------------------------------ (a) the reference alone


class TestReferenceAlone:
    def test_it_is_the_configurations_and_imports_nothing_of_the_program(
            self):
        assert CONFIG["reference"] == "preferred-anti-affinity"
        assert issubclass(ref.Reference, cluster.reference.Reference)
        assert issubclass(ref.PodFacts, cluster.reference.PodFacts)
        assert ref.replay.__self__ is ref.Reference
        with open(ref.__file__) as f:
            source = f.read()
        assert "kubernetes_tpu" not in "".join(
            ln for ln in source.splitlines(True)
            if ln.lstrip().startswith(("import", "from")))
        assert "var maxCount, minCount int64" in source
        assert "interpod_affinity.go" in source

    def test_both_directions_of_the_soft_term(self):
        r = ref.Reference([node(0), node(1), node(2)])
        # a plain green pod on node-0: the incoming pod's term selects it
        r.bind(facts(0, term=False), "node-0")
        assert r.counts(facts(1)).tolist() == [-1, 0, 0]
        # a pod with the term on node-1 that selects a plain green pod:
        # the symmetry, read by a pod without a term of its own
        r.bind(facts(2), "node-1")
        plain = facts(3, term=False)
        assert r.counts(plain).tolist() == [0, -1, 0]
        # a pod with the term reads both: node-1 holds a green pod that
        # carries the term
        assert r.counts(facts(4)).tolist() == [-1, -2, 0]
        # a blue pod neither selects nor is selected
        assert not r.counts(facts(5, term=False, colour="blue")).any()

    def test_the_weight(self):
        r = ref.Reference([node(0), node(1), node(2)])
        r.bind(facts(0, weight=3), "node-0")
        assert r.counts(facts(1, weight=5)).tolist() == [-8, 0, 0]
        assert r.counts(facts(2, term=False)).tolist() == [-3, 0, 0]

    def test_min_and_max_from_zero_where_every_host_holds_the_colour(self):
        """Three hosts with 1, 2 and 3 pods of the variant: counts -2,
        -4, -6 (each pod both ways). Upstream's maximum is 0, so the
        emptiest host scores 6, not the 10 that min and max over the
        fitting nodes gave."""
        r = ref.Reference([node(0), node(1), node(2)])
        k = 0
        for host, n in (("node-0", 1), ("node-1", 2), ("node-2", 3)):
            for _ in range(n):
                r.bind(facts(k), host)
                k += 1
        incoming = facts(k)
        ok = r.fits(incoming)
        assert ok.all()
        assert r.counts(incoming).tolist() == [-2, -4, -6]
        assert r.interpod(incoming, ok).tolist() == [6, 3, 0]
        # the first two hosts alone fit: min -4, max 0
        assert r.interpod(incoming, np.array([True, True, False]))[
            :2].tolist() == [5, 0]

    def test_a_free_host_gives_the_old_answer(self):
        r = ref.Reference([node(0), node(1), node(2)])
        r.bind(facts(0), "node-0")
        r.bind(facts(1), "node-0")
        r.bind(facts(2), "node-1")
        incoming = facts(3)
        ok = r.fits(incoming)
        assert r.interpod(incoming, ok).tolist() == [0, 5, 10]
        assert r.decide(incoming) == "node-2"

    @pytest.mark.parametrize("bound, fitting", [
        ((), (True, True, True)),                   # nothing bound: flat
        ((0, 1, 2), (True, True, True)),            # one pod a host: flat
        ((0,), (True, False, False)),               # one fitting host
        ((1,), (True, False, False)),               # ... that is empty
    ])
    def test_flat_and_one_node_cases_score_zero(self, bound, fitting):
        r = ref.Reference([node(0), node(1), node(2)])
        for k, host in enumerate(bound):
            r.bind(facts(k), f"node-{host}")
        incoming = facts(9)
        ok = np.array(fitting)
        assert r.interpod(incoming, ok)[ok].tolist() == [0] * ok.sum()

    def test_it_is_computed_for_every_pod_and_moves_the_argmax(self):
        """Resources alone send the pod to the host that already holds
        pods of its colour (two small ones against one large plain
        pod elsewhere); the soft term sends it away."""
        r = ref.Reference([node(0), node(1)])
        r.bind(facts(0), "node-0")
        r.bind(facts(1), "node-0")
        r.bind(facts(2, term=False, colour="blue", cpu="2"), "node-1")
        incoming = facts(3)
        without = cluster.reference.Reference.scores(r, incoming)
        assert r.names[int(np.argmax(without))] == "node-0"
        assert r.decide(incoming) == "node-1"
        fit, gap = r.judge(incoming, "node-0")
        assert fit and gap > 0

    @pytest.mark.parametrize("path, change", [
        (f"spec.affinity.podAntiAffinity.{PREFERRED}.podAffinityTerm."
         "topologyKey",
         lambda p: p["spec"]["affinity"]["podAntiAffinity"][PREFERRED][0][
             "podAffinityTerm"].update(topologyKey=cluster.ZONE)),
        (f"spec.affinity.podAntiAffinity.{PREFERRED}.podAffinityTerm."
         "namespaces",
         lambda p: p["spec"]["affinity"]["podAntiAffinity"][PREFERRED][0][
             "podAffinityTerm"].update(namespaces=["other"])),
        (f"spec.affinity.podAntiAffinity.{PREFERRED}.podAffinityTerm."
         "labelSelector.matchExpressions",
         lambda p: p["spec"]["affinity"]["podAntiAffinity"][PREFERRED][0][
             "podAffinityTerm"]["labelSelector"].update(matchExpressions=[{
                 "key": "color", "operator": "Exists"}])),
        ("spec.affinity.podAffinity",
         lambda p: p["spec"]["affinity"].update(podAffinity={
             PREFERRED: [{"weight": 1, "podAffinityTerm": {
                 "labelSelector": {"matchLabels": {"color": "green"}},
                 "topologyKey": cluster.HOSTNAME}}]})),
        ("spec.nodeSelector",
         lambda p: p["spec"].update(nodeSelector={"a": "b"})),
    ])
    def test_the_whitelist_refuses(self, path, change):
        p = pod(0)
        ref.PodFacts(p)
        change(p)
        with pytest.raises(ValueError, match=path.replace(".", r"\.")):
            ref.PodFacts(p)

    def test_it_still_answers_for_what_the_base_answers_for(self):
        anti = cluster.load_named("variants", "pod-anti-affinity").build(
            0, None, dict(CONFIG, seed=0, colours=4))
        assert len(ref.PodFacts(anti).anti) == 1
        with pytest.raises(ValueError, match="set-up objects"):
            ref.Reference([node(0)], objects=[{"kind": "Service"}])

    def test_extra_words_and_the_scan_bytes(self):
        assert facts(0).extra_words == 1
        assert roofline.scan_bytes_per_node(facts(0)) == 28
        stream = cluster.PodStream(CONFIG, 11).take(64)
        assert {roofline.scan_bytes_per_node(ref.PodFacts(m))
                for m in stream} == {28}

    def test_the_variant_is_upstreams_template_and_the_seed_sets_nothing(
            self):
        a = cluster.PodStream(CONFIG, 5).take(3)
        assert a == [dict(m, metadata=dict(m["metadata"], name=f"pod-{i}"))
                     for i, m in enumerate(cluster.PodStream(
                         CONFIG, 2147483999).take(3))]
        m = a[0]
        assert m["metadata"]["labels"] == {"name": "test", "color": "green"}
        assert m["spec"]["affinity"] == {"podAntiAffinity": {PREFERRED: [{
            "weight": 1, "podAffinityTerm": {
                "labelSelector": {"matchLabels": {"color": "green"}},
                "topologyKey": cluster.HOSTNAME}}]}}

    def test_replay_reports_the_fullest_and_the_emptiest_host(self):
        nodes = [node(0), node(1), node(2)]
        pods = [pod(i) for i in range(4)]
        bound = {"pod-0": "node-0", "pod-1": "node-1", "pod-2": "node-2",
                 "pod-3": "node-0"}
        out = ref.replay(nodes, pods, bound)
        assert out["binds_that_do_not_fit"] == 0
        assert out["score_gap_max"] == 0
        assert (out["pods_on_fullest_host"],
                out["pods_on_emptiest_host"]) == (2, 1)
        # the fourth pod on a host that holds one, where one holds none
        # left: a gap
        bound["pod-2"] = "node-0"
        bound["pod-3"] = "node-1"
        assert ref.replay(nodes, pods, bound)["score_gap_max"] > 0


# ------------------------------ (b) the program's four copies, by hand

#: (raw row, fitting nodes, upstream's score at each fitting node)
HAND_ROWS = {
    "all_negative": ([-2.0, -4.0, -6.0], [1, 1, 1], [6, 3, 0]),
    "all_positive": ([2.0, 4.0, 6.0], [1, 1, 1], [3, 6, 10]),
    "mixed": ([-3.0, 0.0, 2.0, 7.0], [1, 1, 1, 1], [0, 3, 5, 10]),
    "flat_zero": ([0.0, 0.0, 0.0], [1, 1, 1], [0, 0, 0]),
    "flat_negative": ([-2.0, -2.0], [1, 1], [0, 0]),
    "flat_positive": ([3.0, 3.0], [1, 1], [10, 10]),
    "one_fitting_node": ([-4.0, -1.0, 5.0], [0, 1, 0], [0]),
    "one_fitting_positive": ([-4.0, 3.0, 5.0], [0, 1, 0], [10]),
    "unfitting_extremes": ([-9.0, -2.0, -1.0, 9.0], [0, 1, 1, 0], [0, 5]),
}


@pytest.mark.parametrize("case", sorted(HAND_ROWS))
def test_the_four_copies_of_the_normalisation(case):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from kubernetes_tpu.scheduler import priorities, scorer
    from kubernetes_tpu.scheduler.kernels import batch as kbatch
    raw, fits, want = HAND_ROWS[case]
    raw = np.array(raw, np.float32)
    fits = np.array(fits, bool)
    names = [f"n{i}" for i in range(len(raw))]
    # priorities.minmax_normalize over the fitting nodes' map
    got = priorities.minmax_normalize(
        {n: float(v) for n, v, f in zip(names, raw, fits) if f})
    assert [got[n] for n, f in zip(names, fits) if f] == want
    # scorer's row reduce over the node axis: a row of one value over
    # the feasible nodes is left off (it moves no argmax)
    row = scorer.interpod_reduce(raw, fits)
    if row is None:
        assert len(set(want)) == 1
    else:
        assert row[fits].tolist() == want
    # the kernels' in-scan step, weight 1
    kern = np.asarray(kbatch._soft_score(jnp.asarray(raw),
                                         jnp.asarray(fits), 1.0))
    assert kern[fits].tolist() == want
    # the sharded step, the node axis over as many devices as nodes
    n = len(raw)
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices")
    mesh = Mesh(np.array(jax.devices()[:n]), ("nodes",))
    step = jax.shard_map(
        lambda r, f: kbatch._soft_score_sharded(r, f, 1.0), mesh=mesh,
        in_specs=(P("nodes"), P("nodes")), out_specs=P("nodes"))
    sharded = np.asarray(step(jnp.asarray(raw), jnp.asarray(fits)))
    assert sharded[fits].tolist() == want


# ------------------ (c) the program against the reference, seeded


def parse_metrics(lines):
    from harness.children import parse_metrics as parse
    return parse("\n".join(lines) if not isinstance(lines, str) else lines)


def run_program(nodes, phases, pop):
    """Each phase's pods created through the in-process client and
    decided by Scheduler.schedule_pending in pops of at most `pop`, the
    next phase created once the last is bound; what compare() is
    handed."""
    from kubernetes_tpu.api import serde
    from kubernetes_tpu.runtime import SCHEME
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.state import Client
    client = Client()
    for n in nodes:
        client.nodes().create(SCHEME.decode_any(n))
    sched = Scheduler(client, batch_size=pop)
    sched.informers.start()
    sched.informers.wait_for_cache_sync()
    try:
        for pods in phases:
            client.pods("default").create_bulk(
                [SCHEME.decode_any(m) for m in pods])
            deadline = time.monotonic() + 60
            while sched.queue.num_pending() < len(pods):
                assert time.monotonic() < deadline
                time.sleep(0.01)
            done = 0
            while done < len(pods):
                got = sched.schedule_pending(max_pods=pop, timeout=1.0)
                assert got, f"{len(pods) - done} pods never popped"
                done += len(got)
        listed = [serde.encode(p) for p in client.pods("default").list()]
        scrape = parse_metrics(sched.metrics.registry.expose())
    finally:
        sched.informers.stop()
    return listed, scrape


def judged(nodes, pods, listed, scrape, reference=ref):
    created = {m["metadata"]["name"]: i + 1 for i, m in enumerate(pods)}
    watch = {p["metadata"]["name"]: p["spec"].get("nodeName")
             for p in listed}
    said = {}
    compared = verdict.compare(
        reference, nodes, pods, created, watch, [], listed, scrape,
        [0, 0], "", say=lambda phase, **fields: said.update(fields))
    return compared, said


class OldNormalisation:
    """The reference with min and max over the fitting nodes alone, as
    the program computed them before: the arithmetic the uneven run
    tells apart."""

    class Reference(ref.Reference):
        def interpod(self, pod, ok):
            count = self.counts(pod)
            if not ok.any():
                return self._zero.copy()
            lo, hi = int(count[ok].min()), int(count[ok].max())
            if hi == lo:
                return self._zero.copy()
            q = (count - lo).astype(np.float64) / float(hi - lo)
            return (10.0 * q).astype(np.int64)

    Facts = ref.PodFacts
    replay = Reference.replay


def seeded_cluster(seed, uneven):
    """80-200 nodes and 2,000-2,600 pods of the variant. `uneven` first
    fills the nodes with plain blue pods of mixed sizes (one phase, a
    quarter of the cluster's pod count), decided by the scheduler too,
    so that the resource scores differ from host to host."""
    rng = random.Random(seed)
    nodes = cluster.make_nodes(
        dict(CONFIG, node=dict(CONFIG["node"], zones=4)),
        rng.randrange(80, 201), seed)
    phases = []
    k = 0
    if uneven:
        plain = []
        for _ in range(len(nodes) // 4 * 3):
            plain.append(pod(k, term=False, colour="blue",
                             cpu=rng.choice(("100m", "300m", "700m")),
                             memory=rng.choice(("200Mi", "2Gi", "6Gi"))))
            k += 1
        phases.append(plain)
    phases.append([pod(k + i) for i in range(rng.randrange(2000, 2601))])
    return nodes, phases


@pytest.fixture(scope="module", params=[(45, False), (46, True)],
                ids=["variant_alone", "uneven_fill"])
def seeded_run(request):
    seed, uneven = request.param
    nodes, phases = seeded_cluster(seed, uneven)
    listed, scrape = run_program(nodes, phases, 512)
    return uneven, nodes, [m for p in phases for m in p], listed, scrape


class TestProgramAgainstReference:
    def test_every_number_compared_is_zero(self, seeded_run):
        uneven, nodes, pods, listed, scrape = seeded_run
        compared, said = judged(nodes, pods, listed, scrape)
        assert len(compared) == 11
        assert {k: c["value"] for k, c in compared.items()
                if c["value"]} == {}, said
        assert verdict.correct(compared)
        assert said["replayed"] == len(pods)
        # every host took pods of the colour, so upstream's maximum was
        # 0 where the fitting nodes' own was below it
        assert said["pods_on_emptiest_host"] > 0

    def test_the_soft_credits_ran_in_the_scan(self, seeded_run):
        uneven, nodes, pods, listed, scrape = seeded_run
        cycles = scrape["scheduler_e2e_scheduling_duration_seconds_count"]
        launches = scrape[COUNT % "dispatch"]
        # every launch enters the leaf; a pop of more than 256 pods with
        # preferred terms is planned once more before its cycle opens
        assert scrape[COUNT % "soft_terms"] >= launches == cycles > 0
        # two channels a launch of the variant (the plain pods of the
        # uneven fill come first and install none), no fallback
        channels = scrape[SOFT_CHANNELS]
        assert channels % 2 == 0 and 0 < channels <= 2 * cycles
        if not uneven:
            assert channels == 2 * cycles
        assert scrape[FALLBACK_BATCHES] == 0
        assert not any(v for k, v in scrape.items()
                       if k.startswith(FALLBACKS + "{"))
        # soft_terms is a leaf beside tensorize, not inside it: the
        # cycle's other leaves fit in the cycle with room for it
        leaves = sum(scrape['scheduler_scheduling_duration_seconds_sum'
                            f'{{operation="{op}"}}']
                     for op in ("refresh", "tensorize", "dispatch",
                                "scan_wait", "repair", "assume"))
        assert leaves <= scrape[
            "scheduler_e2e_scheduling_duration_seconds_sum"]

    def test_the_old_normalisation_reads_a_gap(self, seeded_run):
        """Judged with min and max over the fitting nodes, the program's
        binds read gaps. Once every host holds the colour, upstream's
        maximum of 0 flattens the soft score (hosts with 20 and 21 pods
        both score 0 at a maximum of 22), the resource scores take over
        and a host may take a pod more than the emptiest; the old
        arithmetic still scored the emptiest 10. Where plain pods fill
        the hosts unevenly the two disagree more often."""
        uneven, nodes, pods, listed, scrape = seeded_run
        compared, said = judged(nodes, pods, listed, scrape,
                                reference=OldNormalisation)
        assert compared["score_gap_max"]["value"] > 0, said
        assert said["binds_with_gap"] > 0
        assert said["pods_on_fullest_host"] \
            > said["pods_on_emptiest_host"] + (0 if uneven else 1)


# ----------------------------------------- (d) a 4,096-pod pop's plan


def test_a_pop_of_4096_plans_two_channels_and_launches_whole():
    from kubernetes_tpu.runtime import SCHEME
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.state import Client
    client = Client()
    for n in cluster.make_nodes(CONFIG, 64, 3):
        client.nodes().create(SCHEME.decode_any(n))
    sched = Scheduler(client, batch_size=16384)
    pods = [SCHEME.decode_any(m)
            for m in cluster.PodStream(CONFIG, 3).take(4096)]
    algo = sched.algorithm
    assert algo.soft_batch_limit(pods) == len(pods) == 4096
    plan = algo._soft_plan_memo[1]
    assert algo._soft_plan_memo[0] is pods
    assert len(plan["chan_list"]) == 2
    assert sorted(kind for kind, _ in plan["chan_list"]) == ["cn", "m"]
    assert plan["kmax"] == 2 and len(plan["tmpl_pods"]) == 1
    assert len(plan["chan_list"]) <= algo.SOFT_TERM_CAP
    scrape = parse_metrics(sched.metrics.registry.expose())
    assert scrape[COUNT % "soft_terms"] == 1     # the plan, at the limit
    assert scrape[FALLBACK_BATCHES] == 0
    assert not any(v for k, v in scrape.items()
                   if k.startswith(FALLBACKS + "{"))


# ---------------------------------------------- (e) the new series


def test_the_new_series_are_there_at_zero_from_process_start():
    from kubernetes_tpu.scheduler.metrics import (STAGE_LEAVES,
                                                  STAGE_PARTS,
                                                  SchedulerMetrics)
    assert "soft_terms" in STAGE_LEAVES and "soft_terms" not in STAGE_PARTS
    scrape = parse_metrics(SchedulerMetrics().registry.expose())
    for part in ("sum", "count"):
        assert scrape["scheduler_scheduling_duration_seconds_"
                      f'{part}{{operation="soft_terms"}}'] == 0
    assert scrape['scheduler_scheduling_cpu_seconds_total'
                  '{operation="soft_terms"}'] == 0
    assert scrape[SOFT_CHANNELS] == 0


# ------------------------------------------------ (f) the data files

NEW_METRICS = ("sched_soft_terms_ms_per_pod",
               "sched_soft_channels_per_cycle")


def test_the_configuration_cell_and_metrics_are_data():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert entry["reduced"] == [] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert len(entry["source"]) <= 200 and "departures" not in CONFIG
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "wave4096", 1)
    # appended after the taints cell and its configuration
    assert bench["workloads"].index(cell) > [
        w["name"] for w in bench["workloads"]].index("taints5k.wave4096")
    assert bench["configs"].index(entry) > [
        c["name"] for c in bench["configs"]].index(
            "dedicated-pools-5000n-taints")
    assert len(cell["why"]) <= 200
    assert CONFIG["pod_mix"] == [{"variant": "preferred-anti-affinity",
                                  "share": 1.0}]
    assert CONFIG["scheduler_config"] == {"batchSize": 16384}
    assert (CONFIG["nodes"], CONFIG["existing_pods"]) == (5000, 1000)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert names[first:first + 2] == list(NEW_METRICS)
    assert first > names.index("sched_gc_full_pause_ms_per_pod")
    from kubernetes_tpu.scheduler.metrics import SchedulerMetrics
    scrape = parse_metrics(SchedulerMetrics().registry.expose())
    for name in NEW_METRICS:
        spec = cluster.load_json(BENCH, "metrics", f"{name}.json")
        entry = bench["per_layer"][names.index(name)]
        assert "workloads" not in entry     # holds in every cell
        assert spec["kind"] == "scrape_ratio"
        assert spec["process"] == "kube_scheduler"
        assert not os.path.exists(os.path.join(BENCH, "metrics",
                                               f"{name}.py"))
        assert (entry["layer"], entry["moves"], entry["unit"]) == \
            (spec["layer"], spec["moves"], spec["unit"]) == \
            ("scheduler host", "pods_bound_per_s", entry["unit"])
        assert entry["better"] == "lower"
        assert scrape[spec["numerator"]] == 0 == scrape[spec["denominator"]]


# ----------------------------------------------------- (g) the control


def test_the_control_reads_not_correct_at_rehearsal_size():
    """Below the configuration's precision every rung keeps the argmax
    among the hosts with the fewest pods of the colour, so int8 reads
    correct here; the priority at weight 0 is the control that reads not
    correct."""
    config = dict(CONFIG, **CONFIG["rehearse"])
    compared, correct, said = control.run_control(
        config, 7, 4000, ref.WITHOUT_INTERPOD_PRIORITY,
        n_nodes=config["nodes"])
    assert not correct and compared["score_gap_max"]["value"] >= 1
    compared, correct, said = control.run_control(
        config, 7, 4000, "int8", n_nodes=config["nodes"])
    assert correct and said["pods_on_fullest_host"] \
        == said["pods_on_emptiest_host"] == 5
    compared, correct, _ = control.run_control(
        config, 7, 1200, "exact", n_nodes=config["nodes"])
    assert correct
