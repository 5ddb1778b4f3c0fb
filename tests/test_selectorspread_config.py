"""The support of `e2e-load-5000n-services` (PR 37), small and on the
CPU: (a) the configuration's stream and its Services against the rule
they were written out from; (b) its plain reference alone, on hand cases;
(c) the program against that reference on served rehearsals with more
groups in one pop than the old cap (7) and the old cache (128) held,
through the very compare() that judges a run; (d) the kernel's score and
the oracle's against upstream's float64 over a sweep; (e) the lookup of a
pod's selectors, the count index, the pop's cut and the series and data
files that read them."""

import collections
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
from harness.children import parse_metrics as _parse      # noqa: E402

ZONE = cluster.ZONE
CONFIG = cluster.load_json(BENCH, "configs", "e2e-load-5000n-services.json")
REHEARSAL = dict(CONFIG, **CONFIG["rehearse"])
ref = cluster.load_reference(CONFIG)
variant = cluster.load_named("variants", "service-member")

FALLBACKS = "scheduler_topo_inscan_fallbacks_total"
FALLBACK_BATCHES = "scheduler_topo_inscan_fallback_batches_total"
GROUPS = "scheduler_spread_groups_total"
WALKED = "scheduler_spread_rows_walked_total"
CYCLES = "scheduler_e2e_scheduling_duration_seconds_count"


def upstream(max_n, n, max_z, z, zones=True):
    """selector_spreading.go CalculateSpreadPriorityReduce for one node,
    in upstream's float64 and operand order, written out."""
    f = 10.0
    if max_n > 0:
        f = 10.0 * (float(max_n - n) / float(max_n))
    if zones:
        zone_score = 10.0
        if max_z > 0:
            zone_score = 10.0 * (float(max_z - z) / float(max_z))
        f = (f * (1.0 - 2.0 / 3.0)) + ((2.0 / 3.0) * zone_score)
    return int(f)


# ------------------------------------------- (a) the stream and the Services


@pytest.mark.parametrize("config", [CONFIG, REHEARSAL],
                         ids=["full", "rehearse"])
class TestTheConfiguration:
    def test_setup_objects_are_what_the_rule_writes_out(self, config):
        assert config["setup_objects"] == variant.service_manifests(config)
        names = [o["manifest"]["metadata"]["name"]
                 for o in config["setup_objects"]]
        assert len(set(names)) == len(names)
        assert all(o["path"] == "/api/v1/namespaces/default/services"
                   for o in config["setup_objects"])

    def test_the_sizes_are_the_sources(self, config):
        groups = config["groups"]
        assert sum(s["pods"] * s["count"] for s in groups["sizes"]) \
            == groups["block_pods"]
        assert config["pods"] % groups["block_pods"] == 0
        # 30 pods a node
        assert config["pods"] == 30 * config["nodes"]
        if config is CONFIG:
            assert [(s["pods"], s["count"]) for s in groups["sizes"]] == \
                [(5, 300), (30, 25), (250, 3)]
            shares = [s["pods"] * s["count"] / groups["block_pods"]
                      for s in groups["sizes"]]
            assert shares == [0.5, 0.25, 0.25]
            assert len(config["setup_objects"]) == 16400
            assert config["pods"] == 150000 and config["nodes"] == 5000
            assert config["reduced"] == ["namespaces"]
            assert config["architecture"] is None
            assert config["pod_mix"] == [{"variant": "service-member",
                                          "share": 1.0}]

    @pytest.mark.parametrize("seed", [0, 7, 2147483659])
    def test_a_groups_pods_are_consecutive_and_every_seed_the_same_work(
            self, config, seed):
        n = min(config["pods"], 4 * config["groups"]["block_pods"])
        pods = cluster.PodStream(config, seed).take(n)
        names = [p["metadata"]["labels"]["name"] for p in pods]
        runs = [names[0]]
        for name in names[1:]:
            if name != runs[-1]:
                runs.append(name)
        # consecutive: a group is one run of the stream
        assert len(runs) == len(set(runs))
        sizes = collections.Counter(names)
        want = dict(variant.all_groups(config)[:len(sizes)])
        assert sizes == want
        # another seed: the same multiset of groups in another order
        other = [p["metadata"]["labels"]["name"]
                 for p in cluster.PodStream(config, seed + 1).take(n)]
        assert collections.Counter(other) == sizes and other != names
        # a Service for every group, selecting it alone
        selectors = {o["manifest"]["spec"]["selector"]["name"]
                     for o in config["setup_objects"]}
        assert set(names) <= selectors
        assert all(p["metadata"]["labels"] == {"name": g}
                   for p, g in zip(pods, names))

    def test_the_variant_raises_past_the_last_pod(self, config):
        cfg = dict(config, seed=3)
        last = config["pods"] - 1
        assert variant.build(last, None, cfg)["metadata"]["name"] \
            == f"pod-{last}"
        with pytest.raises(IndexError, match="does not wrap"):
            variant.build(config["pods"], None, cfg)
        with pytest.raises(IndexError):
            variant.build(-1, None, cfg)


# ------------------------------------------------ (b) the reference alone


def node(i, zone):
    n = cluster.plain_node(i, {"node": dict(CONFIG["node"], zones=1)})
    n["metadata"]["labels"][ZONE] = zone
    return n


def pod(i, group, cpu="100m"):
    p = variant.build(0, None, dict(REHEARSAL, seed=0, pod=dict(
        CONFIG["pod"], cpu=cpu)))
    p["metadata"]["name"] = f"pod-{i}"
    p["metadata"]["labels"] = {"name": group} if group else {}
    return p


def service(group, **selector):
    return {"apiVersion": "v1", "kind": "Service",
            "metadata": {"name": f"{group}-svc", "namespace": "default"},
            "spec": {"selector": selector or {"name": group},
                     "ports": [{"port": 80, "targetPort": 80}]}}


class TestReferenceAlone:
    def cluster(self, *groups):
        return ref.Reference(
            [node(0, "a"), node(1, "a"), node(2, "b"), node(3, "b")],
            objects=[service(g) for g in groups or ("web",)])

    def test_it_is_the_configurations_and_imports_nothing_of_the_program(
            self):
        assert CONFIG["reference"] == "selector-spread"
        assert issubclass(ref.Reference, cluster.reference.Reference)
        assert issubclass(ref.PodFacts, cluster.reference.PodFacts)
        assert ref.replay.__self__ is ref.Reference
        with open(ref.__file__) as f:
            imports = [ln for ln in f
                       if ln.lstrip().startswith(("import", "from"))]
        assert "kubernetes_tpu" not in "".join(imports)

    def test_the_priority_spreads_over_nodes_then_zones(self):
        r = self.cluster()
        web = ref.PodFacts(pod(0, "web"))
        base = cluster.reference.Reference.scores(r, web)
        assert (r.scores(web) == base + 10).all()
        r.bind(web, "node-0")
        # node-0 holds one: 0 of 10 for the node, its zone a holds the
        # most; node-1 shares the zone; zone b is empty
        got = r.scores(web) - cluster.reference.Reference.scores(r, web)
        assert got.tolist() == [upstream(1, 1, 1, 1), upstream(1, 0, 1, 1),
                                upstream(1, 0, 1, 0), upstream(1, 0, 1, 0)]
        assert got.tolist() == [0, 3, 10, 10]
        assert r.judge(web, "node-1") == (True, 7)
        assert r.decide(web) in ("node-2", "node-3")

    def test_a_pod_no_service_selects_scores_as_the_base(self):
        r = self.cluster()
        r.bind(ref.PodFacts(pod(0, "web")), "node-0")
        for other in (pod(1, "db"), pod(2, None)):
            facts = ref.PodFacts(other)
            assert (r.scores(facts)
                    == cluster.reference.Reference.scores(r, facts)).all()
        assert ref.PodFacts(pod(1, "db")).extra_words == 2
        assert ref.PodFacts(pod(2, None)).extra_words == 0
        assert roofline.scan_bytes_per_node(
            ref.PodFacts(pod(1, "db"))) == 32

    def test_the_maximum_is_over_the_fitting_nodes(self):
        r = self.cluster()
        big = ref.PodFacts(pod(0, "web", cpu="3"))
        r.bind(big, "node-0")
        r.bind(big, "node-2")
        r.bind(ref.PodFacts(pod(1, "web", cpu="100m")), "node-2")
        # node-0 and node-2 no longer fit a 3-CPU pod: the counts of the
        # nodes that do are all 0, so every fitting node scores 10
        ok = r.fits(big)
        assert ok.tolist() == [False, True, False, True]
        assert r.spread(big, ok)[ok].tolist() == [10, 10]
        small = ref.PodFacts(pod(2, "web"))
        assert r.spread(small, r.fits(small)).tolist() == [
            upstream(2, 1, 2, 1), upstream(2, 0, 2, 1),
            upstream(2, 2, 2, 2), upstream(2, 0, 2, 2)]

    def test_a_pod_counts_for_every_selector_that_matches_it(self):
        r = ref.Reference(
            [node(0, "a"), node(1, "b")],
            objects=[service("web"), service("all", tier="front")])
        both = pod(0, "web")
        both["metadata"]["labels"]["tier"] = "front"
        front = pod(1, None)
        front["metadata"]["labels"] = {"tier": "front"}
        r.bind(ref.PodFacts(both), "node-0")
        r.bind(ref.PodFacts(front), "node-1")
        # `front` is selected by one Service: both pods match it
        assert r.counts(r.selectors(ref.PodFacts(front))).tolist() == [1, 1]
        # `both` is selected by two: a pod counts if it matches every one
        assert len(r.selectors(ref.PodFacts(both))) == 2
        assert r.counts(r.selectors(ref.PodFacts(both))).tolist() == [1, 0]

    @pytest.mark.parametrize("what, objects, nodes", [
        ("kind", [{"apiVersion": "v1", "kind": "ReplicationController",
                   "metadata": {"name": "rc"}}], None),
        ("spec.selector", [service("web", **{})
                           | {"spec": {"selector": {}}}], None),
        ("namespace", [service("web") | {"metadata": {
            "name": "web-svc", "namespace": "other"}}], None),
        ("spec.clusterIP", [service("web") | {"spec": {
            "selector": {"name": "web"}, "clusterIP": "10.0.0.1"}}], None),
        ("without", [service("web")], "zoneless"),
    ])
    def test_it_refuses_what_it_does_not_answer_for(self, what, objects,
                                                    nodes):
        ns = [node(0, "a"), node(1, "b")]
        if nodes == "zoneless":
            del ns[1]["metadata"]["labels"][ZONE]
        with pytest.raises(ValueError, match=what):
            ref.Reference(ns, objects=objects)

    def test_the_whitelist_of_the_pod_is_the_bases(self):
        p = pod(0, "web")
        p["spec"]["nodeSelector"] = {"disk": "ssd"}
        with pytest.raises(ValueError, match="nodeSelector"):
            ref.PodFacts(p)


# ------------------------------- (c) the program against the reference


def parse_metrics(lines):
    return _parse("\n".join(lines) if not isinstance(lines, str) else lines)


def run_program(nodes, pods, services, batches):
    """The pods through Scheduler.schedule_pending over the in-process
    client, the Services there before the scheduler lists, `batches`
    pods a cycle; what compare() is handed, and the series after each
    cycle."""
    from kubernetes_tpu.api import serde
    from kubernetes_tpu.runtime import SCHEME
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.state import Client
    client = Client()
    for n in nodes:
        client.nodes().create(SCHEME.decode_any(n))
    for s in services:
        client.services("default").create(SCHEME.decode_any(s))
    sched = Scheduler(client, batch_size=16384)
    sched.informers.start()
    sched.informers.wait_for_cache_sync()
    scrapes = []
    try:
        client.pods("default").create_bulk(
            [SCHEME.decode_any(m) for m in pods])
        deadline = time.monotonic() + 60
        while sched.queue.num_pending() < len(pods):
            assert time.monotonic() < deadline
            time.sleep(0.01)
        done = 0
        for size in batches:
            done += len(sched.schedule_pending(max_pods=size, timeout=1.0))
            scrapes.append(parse_metrics(sched.metrics.registry.expose()))
        while done < len(pods):
            got = sched.schedule_pending(timeout=1.0)
            assert got, f"{len(pods) - done} pods never popped"
            done += len(got)
            scrapes.append(parse_metrics(sched.metrics.registry.expose()))
        listed = [serde.encode(p) for p in client.pods("default").list()]
    finally:
        sched.informers.stop()
    return listed, scrapes


def judged(nodes, pods, listed, scrape, objects):
    created = {m["metadata"]["name"]: i + 1 for i, m in enumerate(pods)}
    watch = {p["metadata"]["name"]: p["spec"].get("nodeName")
             for p in listed}
    said = {}
    compared = verdict.compare(
        ref, nodes, pods, created, watch, [], listed, scrape, [0, 0], "",
        say=lambda phase, **fields: said.update(fields), objects=objects)
    return compared, said


def rehearsal(seed, n_nodes, n_pods, zones):
    config = dict(REHEARSAL, nodes=n_nodes,
                  node=dict(REHEARSAL["node"], zones=zones))
    nodes = cluster.make_nodes(config, n_nodes, seed)
    pods = cluster.PodStream(config, seed).take(n_pods)
    objects = [o["manifest"] for o in config["setup_objects"]]
    return nodes, pods, objects


#: (seed, nodes, zones, pods, the pops' sizes): the first case pops more
#: groups at once than the old cap of 7, the second more than the old
#: cache of 128 keys (some 150 groups in a pop of 1,300), the third goes
#: on until a node holds several pods of one group
SERVED = {
    "over-the-old-cap": (11, 40, 4, 600, [100, 50, 200]),
    "over-the-old-cache": (12, 96, 4, 2100, [300, 1300]),
    "deep-counts": (13, 8, 2, 300, [7, 120, 60]),
}


@pytest.fixture(scope="module", params=sorted(SERVED))
def served(request):
    seed, n_nodes, zones, n_pods, batches = SERVED[request.param]
    nodes, pods, objects = rehearsal(seed, n_nodes, n_pods, zones)
    listed, scrapes = run_program(nodes, pods, objects, batches)
    return request.param, nodes, pods, objects, listed, scrapes, batches


class TestProgramAgainstReference:
    def test_every_number_compared_is_zero(self, served):
        name, nodes, pods, objects, listed, scrapes, _ = served
        compared, said = judged(nodes, pods, listed, scrapes[-1], objects)
        assert len(compared) == 11
        assert {k: c["value"] for k, c in compared.items()
                if c["value"]} == {}, said
        assert verdict.correct(compared)
        assert said["replayed"] == len(pods)
        assert said["spread_groups_bound"] == len(
            {p["metadata"]["labels"]["name"] for p in pods})
        if name == "deep-counts":
            # 300 pods of 37.5 a node: some node took several of a group
            assert said["group_pods_on_one_node_max"] >= 3

    def test_every_group_of_a_pop_rides_and_no_row_is_walked(self, served):
        name, nodes, pods, objects, listed, scrapes, batches = served
        at, per_pop = 0, []
        for size in batches:
            per_pop.append(len({p["metadata"]["labels"]["name"]
                                for p in pods[at:at + size]}))
            at += size
        if name == "over-the-old-cap":
            assert max(per_pop) > 7
        if name == "over-the-old-cache":
            assert max(per_pop) > 128
        before = 0
        for groups, scrape in zip(per_pop, scrapes):
            # no cut: one cycle a pop, and every group of it got a slot
            assert scrape[GROUPS] - before == groups
            before = scrape[GROUPS]
        assert scrapes[len(batches) - 1][CYCLES] == len(batches)
        last = scrapes[-1]
        assert not any(v for k, v in last.items()
                       if k.startswith(FALLBACKS + "{"))
        assert last[FALLBACK_BATCHES] == 0
        # the pass that switched the index on, in the first cycle, and
        # nothing after it
        assert scrapes[0][WALKED] == len(nodes)
        assert last[WALKED] == len(nodes)

    def test_an_answer_moved_onto_its_groups_node_is_a_gap(self, served):
        name, nodes, pods, objects, listed, scrapes, _ = served
        listed = json.loads(json.dumps(listed))
        by_name = {p["metadata"]["name"]: p for p in listed}
        group = pods[-1]["metadata"]["labels"]["name"]
        mates = [m["metadata"]["name"] for m in pods
                 if m["metadata"]["labels"]["name"] == group]
        if len(mates) < 2 or name == "deep-counts":
            pytest.skip("the stream ends on a group's first pod, or its "
                        "eight nodes hold the group evenly")
        # the last pod of the stream goes where its group's first sits
        by_name[mates[-1]]["spec"]["nodeName"] = \
            by_name[mates[0]]["spec"]["nodeName"]
        compared, said = judged(nodes, pods, listed, scrapes[-1], objects)
        assert compared["score_gap_max"]["value"] > 0 \
            or compared["binds_that_do_not_fit"]["value"] > 0
        assert not verdict.correct(compared)


class TestTheControl:
    def test_one_precision_down_is_not_correct(self):
        config = dict(REHEARSAL)
        compared, correct, said = control.run_control(
            config, 7, 6000, "int8", 200)
        assert not correct and compared["score_gap_max"]["value"] >= 1

    def test_float32_alone_reads_gaps_here(self):
        compared, correct, said = control.run_control(
            dict(REHEARSAL), 7, 6000, "float32", 200)
        assert not correct
        assert compared["score_gap_max"]["value"] >= 1
        assert compared["binds_that_do_not_fit"]["value"] == 0

    def test_exact_is_correct(self):
        compared, correct, said = control.run_control(
            dict(REHEARSAL), 7, 3000, "exact", 200)
        assert correct, compared


# -------------------------------------------------- (d) the score's sweep


def sweep_cases():
    """Every (maxN, n, maxZ, z) with maxN <= 8 and maxZ <= 64, the two
    cases the issue names, and seeded samples up to 250 / 3,000."""
    cases = [(mn, n, mz, z) for mn in range(9) for mz in range(65)
             for n in range(mn + 1) for z in range(mz + 1)]
    cases += [(1, 0, 10, 9), (3, 2, 60, 7)]
    rng = np.random.default_rng(37)
    for _ in range(200000):
        mn = int(rng.integers(0, 251))
        mz = int(rng.integers(0, 3001))
        cases.append((mn, int(rng.integers(0, mn + 1)), mz,
                      int(rng.integers(0, mz + 1))))
    return np.array(cases)


@pytest.fixture(scope="module")
def sweep():
    cases = sweep_cases()
    return cases, {
        zones: np.array([upstream(*c, zones=zones) for c in cases.tolist()])
        for zones in (True, False)}


class TestTheScoreIsUpstreamsFloat64:
    def test_the_named_cases(self):
        assert upstream(1, 0, 10, 9) == 4
        assert upstream(3, 2, 60, 7) == 6

    @pytest.mark.parametrize("zones", [True, False],
                             ids=["zones", "no-zones"])
    def test_the_kernels_arithmetic_over_the_sweep(self, sweep, zones):
        import jax
        import jax.numpy as jnp
        from kubernetes_tpu.scheduler.kernels import batch as kb
        cases, want = sweep
        tab = jnp.asarray(kb.spread_round_table(256))
        f32 = lambda col: jnp.asarray(cases[:, col], jnp.float32)
        got = np.asarray(jax.jit(
            lambda n, mn, z, mz: kb._spread_exact(
                n, mn, z, mz, jnp.ones(n.shape, bool), zones, tab))(
            f32(1), f32(0), f32(3), f32(2)))
        wrong = np.flatnonzero(got != want[zones])
        assert wrong.size == 0, (cases[wrong[:5]], got[wrong[:5]],
                                 want[zones][wrong[:5]])

    @pytest.mark.parametrize("max_n, n, max_z, z", [
        (1, 0, 10, 9), (3, 2, 60, 7), (2, 1, 7, 3), (0, 0, 0, 0),
        (1, 1, 1, 1), (5, 0, 64, 64)])
    def test_the_kernels_score_on_a_cluster_that_holds_the_case(
            self, max_n, n, max_z, z):
        """_spread_score end to end: node 0 holds n of the group in a
        zone that holds z, another zone holds maxZ, some node maxN."""
        import jax.numpy as jnp
        from kubernetes_tpu.scheduler.kernels import batch as kb
        # zone 1: node 0 (n) and filler nodes up to z; zone 2: nodes
        # summing to maxZ, one of them holding maxN
        counts, zone = [n], [1]
        rest = z - n
        while rest > 0:
            c = min(rest, max_n)
            counts.append(c), zone.append(1)
            rest -= c
        rest, first = max_z, True
        while rest > 0 or first:
            c = min(rest, max_n)
            counts.append(c), zone.append(2)
            rest -= c
            first = False
        if z > n and max_n == 0 or (max_z and not max_n):
            pytest.skip("no such cluster")
        pad = 16 - len(counts) % 16
        infeasible = [99] * pad        # a full node's count is not read
        cnt = jnp.asarray(counts + infeasible, jnp.float32)
        fits = jnp.asarray([True] * len(counts) + [False] * pad)
        zone_of = jnp.asarray(zone + [1] * pad, jnp.int32)
        zinit = jnp.zeros((8,), jnp.float32)
        got = kb._spread_score(
            cnt, fits, zone_of, zinit, kb._zone_onehot(zone_of, zinit),
            jnp.asarray(kb.spread_round_table(128)))
        mn = max(counts)
        zs = {k: sum(c for c, zz in zip(counts, zone) if zz == k)
              for k in (1, 2)}
        assert int(got[0]) == upstream(mn, n, max(zs.values()), zs[1])

    @pytest.mark.parametrize("zones", [True, False],
                             ids=["zones", "no-zones"])
    def test_the_oracles_reduce_over_the_sweep(self, sweep, zones):
        """priorities.selector_spread_reduce on two nodes that hold the
        case: node `a` (n, in a zone that sums to z) is what is read."""
        from kubernetes_tpu import api
        from kubernetes_tpu.scheduler import priorities as prios
        from kubernetes_tpu.scheduler.nodeinfo import NodeInfo
        cases, want = sweep

        def info(name, zone):
            labels = {ZONE: zone} if zones else {}
            return NodeInfo(api.Node(metadata=api.ObjectMeta(
                name=name, labels=labels)))
        infos = {"a": info("a", "z1"), "a2": info("a2", "z1"),
                 "b": info("b", "z2"), "b2": info("b2", "z2")}
        # the exhaustive part and every 40th sample: the reduce is Python
        pick = np.r_[0:cases.shape[0] - 200000,
                     cases.shape[0] - 200000:cases.shape[0]:40]
        for i in pick.tolist():
            mn, n, mz, z = cases[i].tolist()
            if z < n or mz < mn or (mz - mn) < 0:
                continue            # two zones of two nodes cannot hold it
            counts = {"a": n, "a2": z - n, "b": mn, "b2": mz - mn}
            if zones and (max(counts.values()) != mn
                          or max(z, mz) != mz):
                continue
            if not zones and max(counts.values()) != mn:
                continue
            got = prios.selector_spread_reduce(None, None, infos, counts)
            assert got["a"] == want[zones][i], (mn, n, mz, z)

    def test_the_table_is_small_and_mostly_zero(self):
        from kubernetes_tpu.scheduler.kernels import batch as kb
        tab = kb.spread_round_table(128)
        assert tab.shape == (129, 129) and tab.dtype == np.int32
        assert 0 < np.count_nonzero(tab) < tab.size // 4
        # maxN = 3, a = 1 (n = 2): float64 lands under 7 at q = 53/60
        assert (tab[3, 1] >> 7) & 1 == 1


# ------------------------- (e) lookup, index, the pop's cut, the series


def api_pod(name, labels, node_name="", ns="default", rv="1"):
    from kubernetes_tpu.runtime import SCHEME
    p = SCHEME.decode_any(pod(0, None))
    p.metadata.name, p.metadata.namespace = name, ns
    p.metadata.labels, p.metadata.resource_version = dict(labels), rv
    p.spec.node_name = node_name
    return p


def api_node(i, zone):
    from kubernetes_tpu.runtime import SCHEME
    return SCHEME.decode_any(node(i, zone))


def api_service(name, selector, ns="default"):
    from kubernetes_tpu import api
    return api.Service(metadata=api.ObjectMeta(name=name, namespace=ns),
                       spec=api.ServiceSpec(selector=selector))


class TestSelectorsByLookup:
    def listers(self, n=500):
        from kubernetes_tpu.scheduler import priorities as prios
        calls = []
        services = [api_service(f"s{i}", {"name": f"g{i}"})
                    for i in range(n)]
        services.append(api_service("front", {"tier": "front"}))
        services.append(api_service("none", {}))

        def lister(ns):
            calls.append(ns)
            return services if ns == "default" else []
        return prios.SpreadListers(services=lister), services, calls

    def test_a_pod_finds_its_services_without_walking_them(self):
        listers, services, calls = self.listers()
        for i in (3, 250, 499):
            sels = listers.selectors_for_pod(
                api_pod("p", {"name": f"g{i}"}))
            assert [s.items for s in sels] == [(("name", f"g{i}"),)]
        both = listers.selectors_for_pod(
            api_pod("p", {"name": "g7", "tier": "front"}))
        assert sorted(s.items for s in both) == [
            (("name", "g7"),), (("tier", "front"),)]
        assert listers.selectors_for_pod(api_pod("p", {"name": "x"})) == []
        assert listers.selectors_for_pod(api_pod("p", {})) == []
        assert listers.selectors_for_pod(
            api_pod("p", {"name": "g7"}, ns="other")) == []
        # the sources of a namespace were listed once
        assert calls == ["default", "other"]

    def test_a_service_event_files_them_again(self):
        listers, services, calls = self.listers(4)
        late = api_pod("p", {"name": "late"})
        assert listers.selectors_for_pod(late) == []
        services.append(api_service("late", {"name": "late"}))
        assert listers.selectors_for_pod(late) == []     # remembered
        listers.invalidate()
        assert len(listers.selectors_for_pod(late)) == 1
        assert calls == ["default", "default"]

    def test_the_answers_are_the_walks(self):
        from kubernetes_tpu.scheduler import priorities as prios
        rng = random.Random(5)
        keys, values = ["a", "b", "c"], ["0", "1", "2"]
        services = [api_service(f"s{i}", {
            k: rng.choice(values)
            for k in rng.sample(keys, rng.randint(1, 3))})
            for i in range(60)]
        listers = prios.SpreadListers(services=lambda ns: services)
        for _ in range(200):
            labels = {k: rng.choice(values)
                      for k in rng.sample(keys, rng.randint(0, 3))}
            got = sorted(s.items for s in listers.selectors_for_pod(
                api_pod("p", labels)))
            want = sorted(tuple(sorted(s.spec.selector.items()))
                          for s in services if all(
                labels.get(k) == v for k, v in s.spec.selector.items()))
            assert got == want


class TestTheCountIndex:
    def snapshot(self, pods_by_node):
        from kubernetes_tpu.scheduler.nodeinfo import NodeInfo
        infos = {}
        for name, pods in pods_by_node.items():
            ni = NodeInfo(api_node(int(name[1:]), "a"))
            for p in pods:
                ni.add_pod(p)
            infos[name] = ni
        return type("Snap", (), {"node_infos": infos})()

    def test_it_follows_binds_deletes_and_terminating_pods(self):
        from kubernetes_tpu.scheduler import priorities as prios
        from kubernetes_tpu.scheduler.scorer import SpreadIndex
        web = prios._MapSelector({"app": "web"})
        idx = SpreadIndex()
        a = api_pod("a", {"app": "web"}, "n0")
        b = api_pod("b", {"app": "web", "v": "2"}, "n0")
        c = api_pod("c", {"app": "db"}, "n1")
        snap = self.snapshot({"n0": [a, b], "n1": [c]})
        idx.apply(snap, ["n0", "n1"])       # not switched on: nothing
        assert idx.counts("default", [web]) == {}
        idx.activate(snap)
        assert idx.rows_walked == 2
        assert idx.counts("default", [web]) == {"n0": 2}
        assert idx.counts("other", [web]) == {}
        v2 = prios._MapSelector({"v": "2"})
        assert idx.counts("default", [web, v2]) == {"n0": 1}
        # a bind elsewhere, a delete, and a pod that starts terminating
        d = api_pod("d", {"app": "web"}, "n1")
        gone = api_pod("b", {"app": "web", "v": "2"}, "n0", rv="2")
        gone.metadata.deletion_timestamp = "2026-10-04T00:00:00Z"
        snap = self.snapshot({"n0": [gone], "n1": [c, d]})
        idx.apply(snap, ["n0", "n1"])
        assert idx.counts("default", [web]) == {"n1": 1}
        assert idx.rows_walked == 2
        # a node that went takes its pods with it
        snap = self.snapshot({"n0": [gone]})
        idx.apply(snap, ["n1"])
        assert idx.counts("default", [web]) == {}
        assert not idx._counts and not idx._by_ns["default"]


class TestThePopIsCutAtTheCapAndCounted:
    def scheduler(self, n_groups):
        from kubernetes_tpu.scheduler import priorities as prios
        from kubernetes_tpu.scheduler.cache import Cache
        from kubernetes_tpu.scheduler.core import BatchScheduler
        from kubernetes_tpu.scheduler.metrics import SchedulerMetrics
        cache = Cache()
        for i in range(4):
            cache.add_node(api_node(i, f"z{i % 2}"))
        services = [api_service(f"s{i}", {"name": f"g{i}"})
                    for i in range(n_groups)]
        sched = BatchScheduler(cache, listers=prios.SpreadListers(
            services=lambda ns: services))
        sched.sched_metrics = SchedulerMetrics()
        return sched

    def test_a_pop_past_the_cap_is_cut_before_the_group_past_it(self):
        sched = self.scheduler(12)
        sched.SPREAD_GROUP_CAP = 5
        pods = [api_pod(f"p{i}", {"name": f"g{i // 2}"})
                for i in range(24)]
        # groups 0..4 ride; the first pod of group 5 is pod 10
        assert sched.soft_batch_limit(pods) == 10
        scrape = parse_metrics(sched.sched_metrics.registry.expose())
        assert scrape[FALLBACKS + '{reason="spread_groups"}'] == 1
        assert sched.soft_batch_limit(pods[:10]) == 10
        # a caller that does not cut: the groups past the cap are scored
        # from the batch-start row, and that is counted too
        pending = sched.schedule_launch(pods)
        assert pending.batch.spread_gidx[:10].min() >= 0
        assert (pending.batch.spread_gidx[10:24] == -1).all()
        sched.schedule_finish(pending)
        scrape = parse_metrics(sched.sched_metrics.registry.expose())
        assert scrape[FALLBACKS + '{reason="spread_groups"}'] == 2
        assert scrape[FALLBACK_BATCHES] == 1
        assert scrape[GROUPS] == 5

    def test_under_the_cap_nothing_is_cut_or_counted(self):
        sched = self.scheduler(12)
        pods = [api_pod(f"p{i}", {"name": f"g{i // 2}"})
                for i in range(24)]
        assert sched.soft_batch_limit(pods) == 24
        pending = sched.schedule_launch(pods)
        assert pending.batch.spread_gidx[:24].min() >= 0
        assert pending.batch.spread_slots.shape == (512,)
        assert pending.batch.spread_nz.shape == (3, 4096)
        assert pending.batch.spread_mg.shape[1] == 1
        scrape = parse_metrics(sched.sched_metrics.registry.expose())
        assert not any(v for k, v in scrape.items()
                       if k.startswith(FALLBACKS + "{"))
        assert scrape[GROUPS] == 12 and scrape[WALKED] == 4


class TestTheSeriesAndTheDataFiles:
    def test_every_series_is_there_at_zero_from_the_start(self):
        from kubernetes_tpu.scheduler.metrics import (
            INSCAN_FALLBACK_REASONS, STAGE_PARTS, SchedulerMetrics)
        scrape = parse_metrics(SchedulerMetrics().registry.expose())
        assert scrape[GROUPS] == 0 and scrape[WALKED] == 0
        assert "spread_groups" in STAGE_PARTS
        assert scrape['scheduler_scheduling_duration_seconds_sum'
                      '{operation="spread_groups"}'] == 0
        for reason in ("spread_groups", "spread_range"):
            assert reason in INSCAN_FALLBACK_REASONS
            assert scrape[FALLBACKS + '{reason="%s"}' % reason] == 0

    @pytest.mark.parametrize("metric, numerator", [
        ("sched_spread_groups_ms_per_pod",
         'scheduler_scheduling_duration_seconds_sum'
         '{operation="spread_groups"}'),
        ("sched_spread_groups_per_cycle", GROUPS),
        ("sched_spread_rows_walked_per_cycle", WALKED)])
    def test_the_benchmark_reads_them_from_data_files_alone(
            self, metric, numerator):
        bench = cluster.load_json(REPO, "BENCHMARK.json")
        entry = next(m for m in bench["per_layer"] if m["name"] == metric)
        assert "workloads" not in entry
        assert entry["layer"] == "scheduler host"
        assert entry["moves"] == "pods_bound_per_s"
        spec = cluster.load_json(BENCH, "metrics", f"{metric}.json")
        assert spec["kind"] == "scrape_ratio"
        assert spec["numerator"] == numerator
        assert spec["unit"] == entry["unit"]
        assert not os.path.exists(
            os.path.join(BENCH, "metrics", f"{metric}.py"))

    def test_the_cell_is_one_entry_on_one_chip(self):
        bench, cell, config, mix = cluster.load_cell("svcspread5k.wave4096")
        assert cell == {
            "name": "svcspread5k.wave4096",
            "config": "e2e-load-5000n-services", "traffic": "wave4096",
            "chips": 1, "why": cell["why"]}
        assert config["name"] == "e2e-load-5000n-services"
        entry = next(c for c in bench["configs"]
                     if c["name"] == config["name"])
        assert entry["reduced"] == config["reduced"] == ["namespaces"]
        assert entry["source"] == config["source"]
        assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200


class TestTheHubListsTheServicesOnceARunOfCreates:
    """state/client.py: the cluster IP's uniqueness check listed every
    Service on every Service create (16,400 creates: 134 million reads).
    The answer is kept beside the resourceVersion it is true for and
    carried over the client's own creates."""

    def client(self):
        from kubernetes_tpu.state import Client
        client = Client()
        lists = []
        store = client.store
        real = store.list

        def counted(resource, *a, **kw):
            lists.append(resource)
            return real(resource, *a, **kw)
        store.list = counted
        return client, lists

    def test_a_run_of_creates_lists_once_and_ips_stay_unique(self):
        from kubernetes_tpu.runtime import SCHEME
        client, lists = self.client()
        for o in REHEARSAL["setup_objects"][:300]:
            client.services("default").create(
                SCHEME.decode_any(o["manifest"]))
        assert lists.count("services") == 1
        ips = [s.spec.cluster_ip for s in client.services("default").list()]
        assert len(ips) == 300 == len(set(ips)) and all(ips)

    def test_another_write_in_between_lists_again(self):
        from kubernetes_tpu.runtime import SCHEME
        client, lists = self.client()
        svcs = [SCHEME.decode_any(o["manifest"])
                for o in REHEARSAL["setup_objects"][:4]]
        client.services("default").create(svcs[0])
        client.services("default").create(svcs[1])
        assert lists.count("services") == 1
        client.nodes().create(api_node(0, "a"))
        client.services("default").create(svcs[2])
        assert lists.count("services") == 2
        # a bulk of creates carries it too
        client.services("default").create_bulk([svcs[3]])
        more = SCHEME.decode_any(service("late"))
        client.services("default").create(more)
        assert lists.count("services") == 2

    def test_a_taken_ip_is_salted_away_from(self):
        from kubernetes_tpu.runtime import SCHEME
        client, _ = self.client()
        first = client.services("default").create(
            SCHEME.decode_any(service("one")))
        clash = SCHEME.decode_any(service("two"))
        clash.spec.cluster_ip = first.spec.cluster_ip
        second = client.services("default").create(clash)
        assert second.spec.cluster_ip != first.spec.cluster_ip
        # the same again once the answer is kept
        again = SCHEME.decode_any(service("three"))
        again.spec.cluster_ip = first.spec.cluster_ip
        third = client.services("default").create(again)
        assert len({first.spec.cluster_ip, second.spec.cluster_ip,
                    third.spec.cluster_ip}) == 3


def test_pods_that_waited_before_the_scheduler_listed_are_served_in_order():
    """A LIST comes sorted by name (pod-100 before pod-2). The informer
    dispatches a relist in resourceVersion order, so pods that were
    pending before the scheduler started are queued, and decided, in the
    order of their creation: the order the reference replays."""
    from kubernetes_tpu.api import serde
    from kubernetes_tpu.runtime import SCHEME
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.state import Client
    nodes, pods, objects = rehearsal(11, 40, 300, 4)
    client = Client()
    for n in nodes:
        client.nodes().create(SCHEME.decode_any(n))
    for s in objects:
        client.services("default").create(SCHEME.decode_any(s))
    client.pods("default").create_bulk([SCHEME.decode_any(m) for m in pods])
    sched = Scheduler(client, batch_size=16384)
    seen = []
    sched.informers.start()
    sched.informers.wait_for_cache_sync()
    try:
        done = 0
        while done < len(pods):
            got = sched.schedule_pending(max_pods=64, timeout=1.0)
            assert got
            seen += [r.pod.metadata.name for r in got]
            done += len(got)
        listed = [serde.encode(p) for p in client.pods("default").list()]
        scrape = parse_metrics(sched.metrics.registry.expose())
    finally:
        sched.informers.stop()
    assert seen == [m["metadata"]["name"] for m in pods]
    compared, said = judged(nodes, pods, listed, scrape, objects)
    assert verdict.correct(compared), said
