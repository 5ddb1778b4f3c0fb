"""The support of `dedicated-pools-5000n-taints`, small and on the CPU:
(a) its plain reference alone, on hand cases; (b) the node and pod
variants; (c) the program against that reference on seeded clusters,
through the very compare() that judges a run; (d) the control; (e) a
pop of more pools than the node-vector cache used to hold; (f) the
series and the data files that read them, and the cell as data."""

import copy
import json
import os
import random
import sys

import pytest
from test_podaffinity_config import judged as judged_by, parse_metrics, \
    run_program

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

from harness import cluster, control, roofline, verdict  # noqa: E402

REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"
CONFIG = cluster.load_json(BENCH, "configs",
                           "dedicated-pools-5000n-taints.json")
REHEARSAL = dict(CONFIG, **CONFIG["rehearse"])
ref = cluster.load_reference(CONFIG)
variant = cluster.load_named("variants", "dedicated-pool")
nodes_variant = cluster.load_named("nodes", "dedicated-pools")
POOL = nodes_variant.POOL
SOFT = nodes_variant.SOFT_TAINT
EVICTIONS = "scheduler_node_vector_evictions_total"

_TOL = "spec.tolerations"
_TERMS = f"spec.affinity.nodeAffinity.{REQUIRED}.nodeSelectorTerms"


def node(i, pool, *taints):
    """The plain node in `pool` (None: no label), with `taints` as
    (key, value, effect)."""
    n = cluster.plain_node(i, {"node": dict(CONFIG["node"], zones=1)})
    if pool is not None:
        n["metadata"]["labels"][POOL] = pool
    if taints:
        n["spec"] = {"taints": [{"key": k, "value": v, "effect": e}
                                for k, v, e in taints]}
    return n


def pod(i, pool, *tolerations, cpu="100m"):
    """The variant's pod of `pool`; `tolerations` as (key, operator,
    value, effect) replace its own where given."""
    p = variant.build(i, None, {"pod": dict(CONFIG["pod"], cpu=cpu),
                                "seed": 0, "pools": 1})
    values = p["spec"]["affinity"]["nodeAffinity"][REQUIRED][
        "nodeSelectorTerms"][0]["matchExpressions"][0]["values"]
    values[:] = [pool]
    p["spec"]["tolerations"][0]["value"] = pool
    if tolerations:
        p["spec"]["tolerations"] = [
            {k: v for k, v in zip(("key", "operator", "value", "effect"), t)
             if v} for t in tolerations]
    return p


def soft(n):
    return any(t["effect"] == "PreferNoSchedule"
               for t in n.get("spec", {}).get("taints", []))


# ------------------------------------------------ (a) the reference alone


class TestReferenceAlone:
    def test_it_is_the_configurations_and_imports_nothing_of_the_program(
            self):
        assert CONFIG["reference"] == "taint-toleration"
        assert ref.__name__.endswith("taint_toleration")
        node_affinity = cluster.load_named("references", "node-affinity")
        assert issubclass(ref.Reference, cluster.reference.Reference)
        assert issubclass(ref.PodFacts, cluster.reference.PodFacts)
        # node-affinity.py's term logic, reused and not copied
        assert ref.Reference.allowed.__qualname__ == \
            node_affinity.Reference.allowed.__qualname__
        assert ref.Reference.__mro__[1].__module__ == \
            node_affinity.Reference.__module__
        assert ref.replay.__self__ is ref.Reference
        with open(ref.__file__) as f:
            imports = [ln for ln in f
                       if ln.lstrip().startswith(("import", "from"))]
        assert "kubernetes_tpu" not in "".join(imports)
        # the copy it points at the pool label is its own
        assert node_affinity.ZONE == cluster.ZONE

    @pytest.mark.parametrize("toleration, taint, answer", [
        (("k", "Equal", "v", "NoSchedule"), ("k", "v", "NoSchedule"), True),
        (("k", "Equal", "w", "NoSchedule"), ("k", "v", "NoSchedule"), False),
        (("k", "", "v", "NoSchedule"), ("k", "v", "NoSchedule"), True),
        (("k", "Exists", "", "NoSchedule"), ("k", "v", "NoSchedule"), True),
        (("k", "Exists", "", "NoSchedule"), ("j", "v", "NoSchedule"), False),
        (("", "Exists", "", ""), ("j", "v", "NoExecute"), True),
        (("k", "Equal", "v", ""), ("k", "v", "PreferNoSchedule"), True),
        (("k", "Equal", "v", "NoExecute"), ("k", "v", "NoSchedule"), False),
        (("k", "Gt", "v", "NoSchedule"), ("k", "v", "NoSchedule"), False),
    ])
    def test_tolerates_reads_a_toleration_as_upstream(self, toleration,
                                                      taint, answer):
        assert ref.tolerates(toleration, taint) is answer

    def test_no_schedule_and_no_execute_refuse_and_prefer_does_not(self):
        r = ref.Reference([
            node(0, "pool-a", (POOL, "pool-a", "NoSchedule")),
            node(1, "pool-a", (POOL, "pool-b", "NoSchedule")),
            node(2, "pool-a", ("evict", "x", "NoExecute")),
            node(3, "pool-a", (SOFT, "1", "PreferNoSchedule")),
            node(4, "pool-a")])
        own = ref.PodFacts(pod(0, "pool-a"))
        assert r.fits(own).tolist() == [True, False, False, True, True]
        anything = ref.PodFacts(pod(1, "pool-a", ("", "Exists", "", "")))
        assert r.fits(anything).all()
        # the required term holds beside the taints: no label, no fit
        r = ref.Reference([node(0, None), node(1, "pool-b"),
                           node(2, "pool-a")])
        assert r.fits(own).tolist() == [False, False, True]

    def test_the_priority_is_reversed_and_normalised_over_the_fitting(
            self):
        r = ref.Reference([
            node(0, "pool-a"),
            node(1, "pool-a", (SOFT, "1", "PreferNoSchedule")),
            node(2, "pool-a", (SOFT, "1", "PreferNoSchedule"),
                 ("other", "x", "PreferNoSchedule")),
            node(3, "pool-b", (SOFT, "1", "PreferNoSchedule"),
                 ("other", "x", "PreferNoSchedule"),
                 ("third", "x", "PreferNoSchedule"))])
        p = ref.PodFacts(pod(0, "pool-a"))
        base = cluster.reference.Reference.scores(r, p)
        # maxCount 2 over the fitting three, int64: 10 - 10 * 1 // 2
        assert r.taint_scores(p).tolist() == [10, 5, 0, -5]
        assert (r.scores(p) - base).tolist() == [10, 5, 0, -5]
        assert r.decide(p) == "node-0"
        # a toleration with the soft effect (or none) lifts its taint; one
        # with another effect does not
        lifted = ref.PodFacts(pod(1, "pool-a", (POOL, "Equal", "pool-a",
                                                "NoSchedule"),
                                  ("other", "Exists", "", "")))
        assert r.taint_scores(lifted)[:3].tolist() == [10, 0, 0]
        kept = ref.PodFacts(pod(2, "pool-a", (POOL, "Equal", "pool-a",
                                              "NoSchedule"),
                                ("other", "Exists", "", "NoSchedule")))
        assert r.taint_scores(kept)[:3].tolist() == [10, 5, 0]

    def test_no_fitting_soft_taint_scores_ten_everywhere(self):
        r = ref.Reference([
            node(0, "pool-a"),
            node(1, "pool-b", (SOFT, "1", "PreferNoSchedule")),
            node(2, "pool-a", (SOFT, "1", "PreferNoSchedule"))])
        p = ref.PodFacts(pod(0, "pool-a", cpu="3"))
        assert r.taint_scores(p).tolist() == [10, 0, 0]
        r.bind(p, "node-2")
        # node-2 no longer fits a pod of 3 CPU: maxCount over the fitting
        # is 0, and every node scores 10
        assert r.fits(p).tolist() == [True, False, False]
        assert r.taint_scores(p).tolist() == [10, 10, 10]
        assert r.judge(p, "node-0") == (True, 0)

    def test_the_control_rung_without_the_priority(self):
        nodes = [node(0, "pool-a"),
                 node(1, "pool-a", (SOFT, "1", "PreferNoSchedule"))]
        p = ref.PodFacts(pod(0, "pool-a", cpu="1"))
        r = ref.Reference(nodes, "no-taint-priority")
        assert (r.taint_weight, r.precision) == (0, "exact")
        assert (r.scores(p) ==
                cluster.reference.Reference.scores(r, p)).all()
        r.bind(p, "node-0")
        assert r.decide(p) == "node-1"
        r = ref.Reference(nodes)
        r.bind(p, "node-0")
        assert r.decide(p) == "node-0"

    @pytest.mark.parametrize("path, change", [
        (f"{_TOL}.tolerationSeconds",
         lambda p, n: p["spec"]["tolerations"][0].update(
             tolerationSeconds=30)),
        (f"{_TERMS}.matchExpressions.key",
         lambda p, n: p["spec"]["affinity"]["nodeAffinity"][REQUIRED][
             "nodeSelectorTerms"][0]["matchExpressions"][0].update(
                 key=cluster.ZONE)),
        (f"{_TERMS}.matchExpressions.operator",
         lambda p, n: p["spec"]["affinity"]["nodeAffinity"][REQUIRED][
             "nodeSelectorTerms"][0]["matchExpressions"][0].update(
                 operator="NotIn")),
        ("spec.nodeSelector",
         lambda p, n: p["spec"].update(nodeSelector={POOL: "pool-a"})),
        ("spec.taints.timeAdded",
         lambda p, n: n["spec"]["taints"][0].update(
             timeAdded="2019-10-13T21:33:20Z")),
        ("spec.unschedulable",
         lambda p, n: n["spec"].update(unschedulable=True)),
        ("status.images",
         lambda p, n: n["status"].update(images=[{"names": ["x"]}])),
    ])
    def test_the_whitelist_refuses(self, path, change):
        p, n = pod(0, "pool-a"), node(0, "pool-a",
                                      (POOL, "pool-a", "NoSchedule"))
        ref.Reference([n])
        ref.PodFacts(p)
        change(p, n)
        with pytest.raises(ValueError, match=path.replace(".", r"\.")):
            ref.Reference([n])
            ref.PodFacts(p)

    def test_set_up_objects_are_refused(self):
        with pytest.raises(ValueError, match="set-up objects"):
            ref.Reference([node(0, "pool-a")], objects=[{"kind": "Service"}])

    def test_extra_words_and_the_scan_bytes(self):
        pooled = ref.PodFacts(pod(0, "pool-a"))
        assert pooled.extra_words == 2
        assert roofline.scan_bytes_per_node(pooled) == 32
        stream = cluster.PodStream(CONFIG, 11).take(64)
        assert {roofline.scan_bytes_per_node(ref.PodFacts(m))
                for m in stream} == {32}

    def test_replay_counts_pods_outside_their_pools_and_soft_binds(self):
        nodes = [node(0, "pool-a", (POOL, "pool-a", "NoSchedule")),
                 node(1, "pool-a", (POOL, "pool-a", "NoSchedule"),
                      (SOFT, "1", "PreferNoSchedule")),
                 node(2, "pool-b", (POOL, "pool-b", "NoSchedule"))]
        pods = [pod(i, "pool-a") for i in range(3)]
        bound = {"pod-0": "node-0", "pod-1": "node-1", "pod-2": "node-2"}
        out = ref.replay(nodes, pods, bound)
        assert out["pods_outside_their_pools"] == 1
        assert "pods_outside_their_zones" not in out
        assert out["binds_that_do_not_fit"] == 1
        assert out["binds_on_soft_tainted_nodes"] == 1
        # pod-1 took the soft-tainted node while node-0 scored 10 more
        assert out["score_gap_max"] == 10
        bound.update({"pod-1": "node-0", "pod-2": "node-0"})
        out = ref.replay(nodes, pods, bound)
        assert (out["pods_outside_their_pools"], out["binds_that_do_not_fit"],
                out["binds_on_soft_tainted_nodes"], out["score_gap_max"]) == \
            (0, 0, 0, 0)


# --------------------------------------------------- (b) the two variants


class TestVariants:
    def test_the_nodes_are_a_hundred_pools_of_fifty_five_soft(self):
        nodes = cluster.make_nodes(CONFIG, CONFIG["nodes"], 2147483701)
        assert len(nodes) == 5000
        pools = {}
        for n in nodes:
            i = int(n["metadata"]["name"].split("-")[1])
            pool = n["metadata"]["labels"][POOL]
            assert pool == f"pool-{i % 100}"
            hard = [t for t in n["spec"]["taints"]
                    if t["effect"] == "NoSchedule"]
            assert hard == [{"key": POOL, "value": pool,
                             "effect": "NoSchedule"}]
            assert soft(n) is ((i // 100) % 10 == 0)
            pools.setdefault(pool, []).append(soft(n))
        assert len(pools) == 100
        assert {len(v) for v in pools.values()} == {50}
        assert {sum(v) for v in pools.values()} == {5}
        # the plain node underneath, its shape and labels
        plain = cluster.plain_node(7, CONFIG)
        built = nodes_variant.build(7, CONFIG)
        assert built["status"] == plain["status"]
        assert plain["metadata"]["labels"].items() \
            <= built["metadata"]["labels"].items()
        # the seed orders the creates and changes nothing else
        assert sorted(json.dumps(n, sort_keys=True) for n in nodes) == \
            sorted(json.dumps(n, sort_keys=True) for n in
                   cluster.make_nodes(CONFIG, CONFIG["nodes"], 5))

    def test_the_rehearsal_has_eighty_pools_of_ten_one_soft(self):
        nodes = cluster.make_nodes(REHEARSAL, REHEARSAL["nodes"], 3)
        by_pool = {}
        for n in nodes:
            by_pool.setdefault(n["metadata"]["labels"][POOL], []).append(
                soft(n))
        assert len(by_pool) == 80 > 64
        assert {(len(v), sum(v)) for v in by_pool.values()} == {(10, 1)}

    def test_the_pods_cycle_every_pool_from_the_seed(self):
        a = cluster.PodStream(CONFIG, 5).take(201)
        pools = [m["spec"]["tolerations"][0]["value"] for m in a]
        assert len(set(pools)) == 100 == CONFIG["pools"]
        assert pools[0] == pools[100] == pools[200] == "pool-5"
        assert set(pools[:100]) == set(pools[37:137])
        for m in a[:3]:
            pool = m["spec"]["tolerations"][0]["value"]
            assert m["spec"]["tolerations"] == [{
                "key": POOL, "operator": "Equal", "value": pool,
                "effect": "NoSchedule"}]
            term, = m["spec"]["affinity"]["nodeAffinity"][REQUIRED][
                "nodeSelectorTerms"]
            assert term == {"matchExpressions": [{
                "key": POOL, "operator": "In", "values": [pool]}]}
            assert m["spec"]["containers"][0]["resources"]["requests"] == \
                {"cpu": "100m", "memory": "500Mi"}
        assert a == cluster.PodStream(CONFIG, 5).take(201)
        # a seed past 32 signed bits
        big = cluster.PodStream(CONFIG, 2 ** 31 + 7).take(100)
        assert len({m["spec"]["tolerations"][0]["value"] for m in big}) == 100

    def test_every_seed_gives_the_same_kinds_of_pods(self):
        def kinds(seed):
            return sorted(json.dumps(dict(m, metadata=None), sort_keys=True)
                          for m in cluster.PodStream(CONFIG, seed).take(100))
        assert kinds(5) == kinds(2147483659) == kinds(0)


# ------------------------- (c) the program against the reference, seeded


def random_cluster(seed, n_pools=12):
    """`n_pools` pools over 48-120 nodes made by the node variant, created
    in an order drawn from the seed; the variant's pods at 500m (a node
    is full at eight, so the soft-tainted nodes fill too), every seventh
    tolerating the soft taint as well (its own score row)."""
    rng = random.Random(seed)
    config = dict(REHEARSAL, pools=n_pools,
                  pod=dict(CONFIG["pod"], cpu="500m"))
    n_nodes = rng.randrange(4, 11) * n_pools
    nodes = cluster.make_nodes(config, n_nodes, seed)
    pods = cluster.PodStream(config, seed).take(
        int(n_nodes * rng.uniform(4.0, 6.5)))
    for i, p in enumerate(pods):
        if i % 7 == 6:
            p["spec"]["tolerations"].append({
                "key": SOFT, "operator": "Exists",
                "effect": "PreferNoSchedule"})
    return nodes, pods


def judged(nodes, pods, listed, scrape, reference=None):
    return judged_by(nodes, pods, listed, scrape, reference=reference or ref)


@pytest.fixture(scope="module", params=[42, 43])
def seeded_run(request):
    nodes, pods = random_cluster(request.param)
    rng = random.Random(request.param)
    batches = [rng.choice((1, 7, 24, 60, 150)) for _ in range(6)]
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
        assert said["pods_outside_their_pools"] == 0
        # the clusters fill far enough that the soft-tainted nodes take
        # pods too, and the rows decided where
        assert said["binds_on_soft_tainted_nodes"] > 0
        assert scrape["scheduler_static_score_rows_total"] > 0
        assert scrape[f'{EVICTIONS}{{cache="terms"}}'] == 0

    def test_without_the_row_the_same_binds_read_gaps(self, seeded_run):
        """The reference without TaintTolerationPriority judges the
        program's binds: it would have taken the emptier soft-tainted
        nodes, so the row decided placements."""
        nodes, pods, listed, scrape = seeded_run

        class Without(ref.Reference):
            def __init__(self, nodes, precision="exact", objects=()):
                super().__init__(nodes, ref.WITHOUT_TAINT_PRIORITY, objects)

        class Module:
            PodFacts = ref.PodFacts
            replay = Without.replay
        compared, _ = judged(nodes, pods, listed, scrape, reference=Module)
        assert compared["score_gap_max"]["value"] >= 1
        assert compared["binds_that_do_not_fit"]["value"] == 0

    def test_an_answer_moved_out_of_its_pool_does_not_fit(self, seeded_run):
        nodes, pods, listed, scrape = seeded_run
        listed = copy.deepcopy(listed)
        pool_of = {n["metadata"]["name"]: n["metadata"]["labels"][POOL]
                   for n in nodes}
        moved = listed[-1]
        own = moved["spec"]["tolerations"][0]["value"]
        moved["spec"]["nodeName"] = next(
            name for name, p in sorted(pool_of.items()) if p != own)
        compared, said = judged(nodes, pods, listed, scrape)
        assert compared["binds_that_do_not_fit"]["value"] == 1
        assert said["pods_outside_their_pools"] == 1
        assert not verdict.correct(compared)


def test_the_program_at_rehearsal_size():
    """800 nodes in 80 pools of ten, one soft-tainted a pool, 4,000 pods
    in pops of 1,024: correct, every pop's 80 score rows computed."""
    nodes = cluster.make_nodes(REHEARSAL, REHEARSAL["nodes"], 2147483701)
    pods = cluster.PodStream(REHEARSAL, 2147483701).take(4000)
    listed, scrape = run_program(nodes, pods, [1024] * 4)
    compared, said = judged(nodes, pods, listed, scrape)
    assert verdict.correct(compared), said
    assert compared["score_gap_max"]["value"] == 0
    assert said["pods_outside_their_pools"] == 0
    cycles = scrape["scheduler_e2e_scheduling_duration_seconds_count"]
    assert scrape["scheduler_static_score_rows_total"] == 80 * cycles
    assert scrape["scheduler_static_mask_rows_total"] == 80 * cycles
    assert sum(scrape[f'{EVICTIONS}{{cache="{c}"}}']
               for c in ("terms", "scores", "zones")) == 0


# ----------------------------------------------------- (d) the control


def test_the_control_reads_not_correct_at_rehearsal_size():
    for rung in ("int8", "no-taint-priority"):
        compared, correct, said = control.run_control(
            REHEARSAL, 7, 4000, rung, n_nodes=REHEARSAL["nodes"])
        assert not correct and compared["score_gap_max"]["value"] >= 1
        assert compared["binds_that_do_not_fit"]["value"] == 0
        assert said["pods_outside_their_pools"] == 0
    # without the row, the soft-tainted nodes are the emptiest and fill
    assert compared["score_gap_max"]["value"] == 10
    assert said["binds_on_soft_tainted_nodes"] > 0
    compared, correct, said = control.run_control(
        REHEARSAL, 7, 4000, "exact", n_nodes=REHEARSAL["nodes"])
    assert correct and said["binds_on_soft_tainted_nodes"] == 0


# ---------- (e) a pop of more pools than the cache used to hold by count


def test_bind_only_cycles_of_eighty_pools_rebuild_nothing_and_evict_nothing():
    """80 pools on 160 nodes, one soft-tainted node a pool: every pop
    holds 80 `tol` and 80 `sel` keys (160, past the 128 that bounded the
    cache by count, under which each use was an eviction and a walk) and
    80 `tainttol` score vectors. After the first cycle, cycles that follow
    binds recompute no row, rebuild no vector and evict nothing, while
    each builds its 80 mask rows and 80 score rows."""
    config = dict(REHEARSAL, nodes=160)
    nodes = cluster.make_nodes(config, 160, 4)
    pods = cluster.PodStream(config, 4).take(960)
    series = ("scheduler_node_vector_rows_recomputed_total",
              "scheduler_static_mask_rows_total",
              "scheduler_static_score_rows_total")
    cycles = []

    def watch(sched):
        cycle = sched.schedule_pending

        def counted(*a, **kw):
            results = cycle(*a, **kw)
            scrape = parse_metrics(sched.metrics.registry.expose())
            rebuilds = sum(sched.metrics.node_vector_rebuilds
                           .snapshot().values())
            evictions = sum(sched.metrics.node_vector_evictions
                            .snapshot().values())
            cycles.append([scrape[name] for name in series]
                          + [rebuilds, evictions])
            return results
        sched.schedule_pending = counted

    listed, scrape = run_program(nodes, pods, [240] * 4, prepare=watch)
    assert len(cycles) == 4
    # the first cycle walks: 80 `tol`, 80 `sel`, 80 `tainttol`, the zones
    assert cycles[0] == [241 * 160, 80, 80, 241, 0]
    for before, after in zip(cycles, cycles[1:]):
        assert [b - a for a, b in zip(before, after)] == [0, 80, 80, 0, 0]
    compared, said = judged(nodes, pods, listed, scrape)
    assert verdict.correct(compared), said


# ------------------------------------ (f) the series and the data files

NEW_METRICS = ("sched_static_scores_ms_per_pod",
               "sched_static_score_rows_per_cycle",
               "sched_node_vector_evictions_per_cycle")


def test_the_new_series_are_there_at_zero_from_process_start():
    from kubernetes_tpu.scheduler.metrics import (NODE_VECTOR_CACHES,
                                                  STAGE_PARTS,
                                                  SchedulerMetrics)
    assert "static_scores" in STAGE_PARTS
    scrape = parse_metrics(SchedulerMetrics().registry.expose())
    for part in ("sum", "count"):
        assert scrape["scheduler_scheduling_duration_seconds_"
                      f'{part}{{operation="static_scores"}}'] == 0
    assert scrape['scheduler_scheduling_cpu_seconds_total'
                  '{operation="static_scores"}'] == 0
    assert scrape["scheduler_static_score_rows_total"] == 0
    for cache in NODE_VECTOR_CACHES:
        assert scrape[f'{EVICTIONS}{{cache="{cache}"}}'] == 0
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    # appended in this order, after every entry that was there before
    # them (later metrics follow them)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert names[first:first + 3] == list(NEW_METRICS)
    assert names[first - 1] == "hub_gc_full_pause_ms_per_pod"
    for name in NEW_METRICS:
        spec = cluster.load_json(BENCH, "metrics", f"{name}.json")
        entry = entries[name]
        assert "workloads" not in entry     # holds in every cell
        assert entry["better"] == "lower"
        assert (entry["layer"], entry["moves"], entry["unit"]) == \
            (spec["layer"], spec["moves"], spec["unit"])
        assert entry["layer"] == "scheduler host"
        if spec["kind"] == "scrape_ratio":
            assert scrape[spec["numerator"]] == 0 \
                == scrape[spec["denominator"]]
    assert cluster.load_json(BENCH, "metrics", f"{NEW_METRICS[2]}.json")[
        "caches"] == list(NODE_VECTOR_CACHES)


def _ctx(before, after):
    return {"probe0": {"scrape": {"kube_scheduler": before}},
            "probe1": {"scrape": {"kube_scheduler": after}}}


def test_the_eviction_reader_sums_the_caches_and_is_silent_without_them():
    name = "sched_node_vector_evictions_per_cycle"
    reader = cluster.load_module(os.path.join(BENCH, "metrics",
                                              f"{name}.py"))
    spec = cluster.load_json(BENCH, "metrics", f"{name}.json")
    cycles = "scheduler_e2e_scheduling_duration_seconds_count"
    before = {cycles: 10.0}
    after = {cycles: 14.0}
    for k, cache in enumerate(spec["caches"]):
        before[f'{EVICTIONS}{{cache="{cache}"}}'] = 1.0
        after[f'{EVICTIONS}{{cache="{cache}"}}'] = 1.0 + 2 * k
    assert reader.read(_ctx(before, after), spec) == (0 + 2 + 4) / 4
    # a program without the counter (the parent's), or no cycle
    assert reader.read(_ctx({cycles: 1.0}, {cycles: 2.0}), spec) is None
    assert reader.read(_ctx(before, dict(after, **{cycles: 10.0})),
                       spec) is None


def test_static_scores_is_timed_where_affinity_scores_is_not():
    """Every batch of a cluster without inter-pod score carriers enters
    static_scores once inside dispatch; a cluster with them enters
    affinity_scores instead, as before, and static_scores never."""
    from test_podaffinity_config import random_cluster as podaff_cluster
    count = 'scheduler_scheduling_duration_seconds_count{operation="%s"}'
    nodes, pods = random_cluster(44)
    _, scrape = run_program(nodes, pods, [60, 60])
    assert scrape[count % "static_scores"] == scrape[count % "dispatch"] > 0
    assert scrape[count % "affinity_scores"] == 0
    nodes, pods, _ = podaff_cluster(30)
    _, scrape = run_program(nodes, pods, [60, 60])
    assert scrape[count % "affinity_scores"] > 0
    assert scrape[count % "static_scores"] + \
        scrape[count % "affinity_scores"] == scrape[count % "dispatch"]


def test_the_cell_and_the_configuration_are_declared_as_data():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # appended after every cell and configuration before it; later PRs
    # append theirs after these
    cells = [w["name"] for w in bench["workloads"]]
    cell = bench["workloads"][cells.index("taints5k.wave4096")]
    assert cells.index("taints5k.wave4096") \
        > cells.index("svcspread5k.wave4096")
    assert cell == {"name": "taints5k.wave4096", "config": CONFIG["name"],
                    "traffic": "wave4096", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    configs = [c["name"] for c in bench["configs"]]
    entry = bench["configs"][configs.index(CONFIG["name"])]
    assert configs.index(CONFIG["name"]) \
        > configs.index("e2e-load-5000n-services")
    assert entry["name"] == CONFIG["name"] == "dedicated-pools-5000n-taints"
    assert entry["file"] == \
        "benchmarks/configs/dedicated-pools-5000n-taints.json"
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    for word in ("Dedicated Nodes", "DeletionCandidateOfClusterAutoscaler",
                 "PreferNoSchedule", "5000 nodes"):
        assert word in entry["source"]
    assert entry["reduced"] == CONFIG["reduced"] == []
    assert CONFIG["architecture"] is None
    assert CONFIG["guarantees"]["every_pod_on_a_node_of_its_own_pool"] is True
    assert CONFIG["pod_mix"] == [{"variant": "dedicated-pool", "share": 1.0}]
    assert (CONFIG["nodes"], CONFIG["existing_pods"], CONFIG["pools"]) == \
        (5000, 1000, 100)
    assert (CONFIG["reference"], CONFIG["node_variant"]) == \
        ("taint-toleration", "dedicated-pools")
    assert {"pools", "soft_taint_share", "soft_taint_value",
            "existing_pods", "node_order"} <= set(CONFIG["assumed"])
    basic = cluster.load_json(BENCH, "configs",
                              "sched-perf-5000n-basic.json")
    for key in ("node", "pod", "scheduler_config", "processes"):
        assert CONFIG[key] == basic[key]
    assert (REHEARSAL["nodes"], REHEARSAL["pools"],
            REHEARSAL["existing_pods"]) == (800, 80, 100)
    # the loaded cell is the one run.py would run
    _, loaded, config, mix = cluster.load_cell("taints5k.wave4096")
    assert loaded == cell and config == CONFIG and mix["in_flight"] == 4096
    assert cluster.load_reference(config).__file__ == ref.__file__
