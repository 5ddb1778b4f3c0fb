"""Batched victim-pricing preemption: kernel-vs-oracle parity, routing,
whole-gang preemption, and the capacity-aware gang domain reduction.

The contract under test (ISSUE 15): the device kernel's decisions
(winner node + victim set) are bit-identical to the serial numpy oracle
on randomized fixtures mixing priorities, PDBs, gang victims, and
nominated pods; `preempt_kernel` off keeps the reference's serial
reprieve path as the control; gang members route to whole-gang
preemption (one ICI domain priced for minMember placements, nominations
across every freed node) instead of being skipped.
"""

import time

import numpy as np
import pytest

from kubernetes_tpu import api
from kubernetes_tpu.api import Quantity
from kubernetes_tpu.api.policy import PodDisruptionBudget, \
    PodDisruptionBudgetSpec, PodDisruptionBudgetStatus
from kubernetes_tpu.api.scheduling import PodGroup, PodGroupSpec
from kubernetes_tpu.api.wellknown import LABEL_POD_GROUP
from kubernetes_tpu.scheduler.cache import Cache
from kubernetes_tpu.scheduler.core import BatchScheduler
from kubernetes_tpu.scheduler.nodeinfo import NodeInfo
from kubernetes_tpu.state import Client

SLICE = "tpu/slice"


def make_pod(name, cpu="100m", mem="200Mi", ns="default", node="",
             priority=None, labels=None, group=None, start=None):
    labels = dict(labels or {})
    if group is not None:
        labels[LABEL_POD_GROUP] = group
    pod = api.Pod(
        metadata=api.ObjectMeta(name=name, namespace=ns, labels=labels),
        spec=api.PodSpec(
            node_name=node, priority=priority,
            containers=[api.Container(
                name="c", image="img",
                resources=api.ResourceRequirements(
                    requests={"cpu": Quantity(cpu),
                              "memory": Quantity(mem)}))]))
    if start is not None:
        pod.status.start_time = start
    return pod


def make_node(name, cpu="4", mem="32Gi", pods=110, labels=None):
    alloc = {"cpu": Quantity(cpu), "memory": Quantity(mem),
             "pods": Quantity(pods)}
    return api.Node(
        metadata=api.ObjectMeta(name=name, labels=labels or {}),
        status=api.NodeStatus(
            capacity=dict(alloc), allocatable=dict(alloc),
            conditions=[api.NodeCondition(type="Ready", status="True")]))


def make_pdb(name, match, allowed, ns="default"):
    return PodDisruptionBudget(
        metadata=api.ObjectMeta(name=name, namespace=ns),
        spec=PodDisruptionBudgetSpec(
            selector=api.LabelSelector(match_labels=dict(match))),
        status=PodDisruptionBudgetStatus(disruptions_allowed=allowed))


def _rand_cluster(rng, n_nodes=12, pods_per_node=5, n_groups=3):
    """Random bound cluster: mixed priorities, some pods in PodGroups,
    start times shuffled."""
    infos = {}
    group_names = [f"g{j}" for j in range(n_groups)]
    k = 0
    for i in range(n_nodes):
        node = make_node(f"n{i}", cpu="4", mem="8Gi", pods=12)
        ni = NodeInfo(node)
        for _ in range(int(rng.integers(0, pods_per_node + 1))):
            grp = None
            if rng.random() < 0.3:
                grp = group_names[int(rng.integers(0, n_groups))]
            p = make_pod(
                f"v{k}", cpu=f"{int(rng.integers(2, 12)) * 100}m",
                mem=f"{int(rng.integers(1, 8)) * 128}Mi",
                node=f"n{i}",
                priority=int(rng.integers(0, 50)),
                labels={"band": f"b{int(rng.integers(0, 3))}"},
                group=grp,
                start=f"2026-08-0{int(rng.integers(1, 5))}T00:00:0"
                      f"{int(rng.integers(0, 10))}Z")
            ni.add_pod(p)
            k += 1
        infos[f"n{i}"] = ni
    return infos


class TestKernelOracleParity:
    def test_price_nodes_randomized(self):
        """Winner + chosen victim set + PDB-violation count identical
        between the jitted kernel and the numpy oracle on randomized
        clusters with mixed priorities, PDBs, and gang victims."""
        from kubernetes_tpu.scheduler.kernels import preempt as pk
        for seed in range(12):
            rng = np.random.default_rng(seed)
            infos = _rand_cluster(rng)
            pdbs = [make_pdb("pdb0", {"band": "b0"},
                             int(rng.integers(0, 3))),
                    make_pdb("pdb1", {"band": "b1"}, 0)]
            pod = make_pod("high",
                           cpu=f"{int(rng.integers(10, 40)) * 100}m",
                           mem="1Gi", priority=100)
            cands = [(n, ni) for n, ni in sorted(infos.items())]
            tabs = pk.build_victim_tables(pod, cands, infos, pdbs)
            if tabs is None:
                continue
            a = tabs.arrays
            w_k, ch_k, k_k, nv_k = pk.price_nodes(
                a["free0"], a["cfree0"], a["need"], a["need_cnt"],
                a["freed"], a["fcnt"], a["valid"], a["pdb"], a["top"],
                a["psum"], a["gcnt"], a["startr"], a["row_valid"])
            w_r, ch_r, k_r, nv_r = pk.price_nodes_reference(a)
            assert int(w_k) == int(w_r), f"seed {seed}: winner differs"
            np.testing.assert_array_equal(np.asarray(ch_k), ch_r)
            np.testing.assert_array_equal(np.asarray(nv_k), nv_r)
            if int(w_r) >= 0:
                victims = tabs.expand(int(w_r), ch_r[int(w_r)])
                assert victims, "feasible winner must evict something"
                prio = 100
                assert all(
                    p.spec.priority < prio for p in victims)

    def test_price_domains_randomized(self):
        """Whole-gang pricing parity: winner domain + victim set."""
        from kubernetes_tpu.scheduler.kernels import preempt as pk
        for seed in range(10):
            rng = np.random.default_rng(1000 + seed)
            infos = _rand_cluster(rng, n_nodes=9)
            pdbs = [make_pdb("pdb0", {"band": "b0"}, 1)]
            members = [make_pod(f"m{i}", cpu="900m", mem="512Mi",
                                priority=100, group="gx")
                       for i in range(4)]
            cands = [(n, ni, f"s{int(i) // 3}")
                     for i, (n, ni) in enumerate(sorted(infos.items()))]
            tabs = pk.build_domain_tables(members, cands, infos, pdbs,
                                          min_member=4)
            assert tabs is not None
            a = tabs.arrays
            w_k, ch_k, nv_k = pk.price_domains(
                a["base"], a["need"], a["dslots"], a["valid"], a["pdb"],
                a["top"], a["psum"], a["gcnt"], a["startr"],
                a["row_valid"])
            w_r, ch_r, nv_r = pk.price_domains_reference(a)
            assert int(w_k) == int(w_r), f"seed {seed}: domain differs"
            np.testing.assert_array_equal(np.asarray(ch_k), ch_r)
            np.testing.assert_array_equal(np.asarray(nv_k), nv_r)
            if int(w_r) >= 0:
                # the freed slots must actually cover the gang
                slots = sum(s for _, s in
                            tabs.node_slots(int(w_r), ch_r[int(w_r)]))
                assert slots >= 4

    def test_pdb_units_ride_the_last_resort_band(self):
        """A PDB-protected victim is evicted only when the clean units
        alone cannot fit the preemptor."""
        from kubernetes_tpu.scheduler.kernels import preempt as pk
        node = make_node("n0", cpu="2", pods=10)
        ni = NodeInfo(node)
        ni.add_pod(make_pod("clean", cpu="900m", node="n0", priority=1))
        ni.add_pod(make_pod("guarded", cpu="900m", node="n0", priority=1,
                            labels={"app": "db"}))
        infos = {"n0": ni}
        pdbs = [make_pdb("pdb", {"app": "db"}, 0)]
        # fits after evicting just the clean pod -> zero violations
        pod = make_pod("high", cpu="1", priority=50)
        tabs = pk.build_victim_tables(pod, [("n0", ni)], infos, pdbs)
        w, ch, k, nv = pk.price_nodes_reference(tabs.arrays)
        assert int(w) == 0 and int(nv[0]) == 0
        assert [p.metadata.name for p in tabs.expand(0, ch[0])] == \
            ["clean"]
        # needs both -> the guarded pod joins, counted as a violation
        pod2 = make_pod("high2", cpu="1900m", priority=50)
        tabs2 = pk.build_victim_tables(pod2, [("n0", ni)], infos, pdbs)
        w2, ch2, _k2, nv2 = pk.price_nodes_reference(tabs2.arrays)
        assert int(w2) == 0 and int(nv2[0]) == 1
        assert {p.metadata.name for p in tabs2.expand(0, ch2[0])} == \
            {"clean", "guarded"}

    def test_gang_victim_priced_as_whole_group(self):
        """Evicting one member of a bound gang charges the whole group:
        a node holding a lone singleton beats a node where the only
        victim is one worker of a 3-member group (fewer victims)."""
        from kubernetes_tpu.scheduler.kernels import preempt as pk
        infos = {}
        n0 = NodeInfo(make_node("n0", cpu="1", pods=10))
        n0.add_pod(make_pod("solo", cpu="900m", node="n0", priority=1))
        infos["n0"] = n0
        n1 = NodeInfo(make_node("n1", cpu="1", pods=10))
        n1.add_pod(make_pod("w0", cpu="900m", node="n1", priority=1,
                            group="gv"))
        infos["n1"] = n1
        n2 = NodeInfo(make_node("n2", cpu="4", pods=10))
        for i in (1, 2):
            n2.add_pod(make_pod(f"w{i}", cpu="200m", node="n2",
                                priority=1, group="gv"))
        infos["n2"] = n2
        pod = make_pod("high", cpu="900m", priority=50)
        tabs = pk.build_victim_tables(
            pod, [("n0", infos["n0"]), ("n1", infos["n1"])], infos, [])
        w, ch, _k, _nv = pk.price_nodes_reference(tabs.arrays)
        assert tabs.names[int(w)] == "n0"
        # forced onto n1, the plan must expand to the ENTIRE group,
        # including the members bound on n2
        tabs1 = pk.build_victim_tables(pod, [("n1", infos["n1"])], infos,
                                       [])
        w1, ch1, _k1, _nv1 = pk.price_nodes_reference(tabs1.arrays)
        victims = {p.metadata.name for p in
                   tabs1.expand(int(w1), ch1[int(w1)])}
        assert victims == {"w0", "w1", "w2"}


class TestUnitCache:
    def test_group_units_never_cached(self):
        """Regression (review finding): a group unit with ONE bound
        member must not be cached — a sibling binding on another node
        changes its cluster-wide expansion without bumping this node's
        generation, and a stale cache entry would price (and evict) a
        partial group."""
        from kubernetes_tpu.scheduler.kernels import preempt as pk
        ni = NodeInfo(make_node("n0", cpu="2", pods=10))
        ni.add_pod(make_pod("w0", cpu="1800m", node="n0", priority=1,
                            group="gv"))
        infos = {"n0": ni}
        pod = make_pod("high", cpu="1", priority=50)
        cache = {}
        tabs = pk.build_victim_tables(pod, [("n0", ni)], infos, [],
                                      unit_cache=cache)
        assert cache == {}  # the lone unit is a group: not cacheable
        w, ch, _k, _nv = pk.price_nodes_reference(tabs.arrays)
        assert {p.metadata.name
                for p in tabs.expand(int(w), ch[int(w)])} == {"w0"}
        # a sibling binds on another node WITHOUT touching n0
        n1 = NodeInfo(make_node("n1", cpu="4", pods=10))
        n1.add_pod(make_pod("w1", cpu="200m", node="n1", priority=1,
                            group="gv"))
        infos["n1"] = n1
        tabs2 = pk.build_victim_tables(pod, [("n0", ni)], infos, [],
                                       unit_cache=cache)
        w2, ch2, _k2, _nv2 = pk.price_nodes_reference(tabs2.arrays)
        victims = {p.metadata.name
                   for p in tabs2.expand(int(w2), ch2[int(w2)])}
        assert victims == {"w0", "w1"}, \
            "stale cached unit priced a partial group"

    def test_singleton_units_cached_and_invalidated_by_generation(self):
        from kubernetes_tpu.scheduler.kernels import preempt as pk
        ni = NodeInfo(make_node("n0", cpu="2", pods=10))
        ni.add_pod(make_pod("v0", cpu="1800m", node="n0", priority=1))
        infos = {"n0": ni}
        pod = make_pod("high", cpu="1", priority=50)
        cache = {}
        pk.build_victim_tables(pod, [("n0", ni)], infos, [],
                               unit_cache=cache)
        assert len(cache) == 1
        # eviction mutates the node -> generation moves -> fresh key
        ni.remove_pod(make_pod("v0", cpu="1800m", node="n0", priority=1))
        ni.generation += 1
        ni.add_pod(make_pod("v1", cpu="1700m", node="n0", priority=2))
        tabs = pk.build_victim_tables(pod, [("n0", ni)], infos, [],
                                      unit_cache=cache)
        w, ch, _k, _nv = pk.price_nodes_reference(tabs.arrays)
        assert {p.metadata.name
                for p in tabs.expand(int(w), ch[int(w)])} == {"v1"}


class TestRouting:
    def _cluster(self):
        cache = Cache()
        cache.add_node(make_node("n1", cpu="1"))
        cache.add_node(make_node("n2", cpu="1"))
        cache.add_pod(make_pod("v1", cpu="800m", priority=5, node="n1"))
        cache.add_pod(make_pod("v2", cpu="800m", priority=2, node="n2"))
        return cache

    def test_kernel_and_serial_agree_on_reference_fixture(self):
        """The routing flag: default (kernel) and `preempt_kernel` off
        (serial control) produce the same plan on the reference's
        min-victim fixture."""
        plans = {}
        for kernel in (True, False):
            sched = BatchScheduler(self._cluster())
            sched.preempt_kernel = kernel
            sched.refresh()
            plan = sched.preempt(make_pod("high", cpu="500m",
                                          priority=100))
            assert plan is not None
            plans[kernel] = plan
        assert plans[True].node_name == plans[False].node_name == "n2"
        assert [v.metadata.name for v in plans[True].victims] == \
            [v.metadata.name for v in plans[False].victims] == ["v2"]
        assert plans[True].num_pdb_violations == 0

    def test_kernel_no_candidate_cap(self):
        """The serial path truncates at PREEMPT_CANDIDATE_CAP; the
        kernel prices every candidate (no silent cap to count)."""
        cache = Cache()
        for i in range(120):
            cache.add_node(make_node(f"n{i}", cpu="1"))
            cache.add_pod(make_pod(f"v{i}", cpu="800m",
                                   priority=1 if i == 113 else 7,
                                   node=f"n{i}"))
        sched = BatchScheduler(cache)
        sched.refresh()
        plan = sched.preempt(make_pod("high", cpu="500m", priority=100))
        # the cheapest victim sits beyond the serial path's cap ordering
        # games: the kernel sees all 120 rows and picks it directly
        assert plan is not None and plan.node_name == "n113"


class TestWholeGangPreemption:
    def test_preempt_gang_prices_one_domain(self):
        """A parked gang prices minMember placements against one ICI
        domain; the plan evicts victim groups whole and nominates every
        member inside the winning domain."""
        cache = Cache()
        for i in range(2):
            cache.add_node(make_node(f"a{i}", cpu="2", pods=10,
                                     labels={SLICE: "sa"}))
            cache.add_node(make_node(f"b{i}", cpu="2", pods=10,
                                     labels={SLICE: "sb"}))
        # slice sa is cheap to clear (priority-1 singletons), sb holds a
        # higher-priority gang
        for i in range(2):
            cache.add_pod(make_pod(f"lo{i}", cpu="1800m", priority=1,
                                   node=f"a{i}"))
            cache.add_pod(make_pod(f"gw{i}", cpu="1800m", priority=8,
                                   node=f"b{i}", group="old"))
        sched = BatchScheduler(cache)
        sched.refresh()
        members = [make_pod(f"m{i}", cpu="1500m", priority=100,
                            group="newg") for i in range(2)]
        plan = sched.preempt_gang(members, 2, SLICE)
        assert plan is not None
        assert plan.domain == "sa"
        assert {v.metadata.name for v in plan.victims} == {"lo0", "lo1"}
        assert sorted(n for _, n in plan.nominations) == ["a0", "a1"]
        assert {m.metadata.name for m, _ in plan.nominations} == \
            {"m0", "m1"}

    def test_scheduler_routes_gang_members(self):
        """e2e: an unschedulable gang triggers whole-gang preemption —
        the skip counter family records the routing, victims evict, every
        member is nominated, and the gang binds into the freed slice."""
        from kubernetes_tpu.scheduler.scheduler import Scheduler
        client = Client()
        for i in range(2):
            client.nodes().create(make_node(f"a{i}", cpu="2", pods=10,
                                            labels={SLICE: "sa"}))
        client.pod_groups("default").create(PodGroup(
            metadata=api.ObjectMeta(name="newg", namespace="default"),
            spec=PodGroupSpec(min_member=2, topology_key=SLICE)))
        sched = Scheduler(client, batch_size=8)
        sched.start()
        try:
            for i in range(2):
                client.pods().create(make_pod(f"lo{i}", cpu="1800m",
                                              priority=1, node=""))
            deadline = time.time() + 30
            while time.time() < deadline:
                pods = client.pods().list()
                if sum(1 for p in pods if p.spec.node_name) == 2:
                    break
                time.sleep(0.05)
            for i in range(2):
                client.pods().create(make_pod(f"m{i}", cpu="1500m",
                                              priority=100, group="newg"))
            deadline = time.time() + 30
            bound = {}
            while time.time() < deadline:
                bound = {p.metadata.name: p.spec.node_name
                         for p in client.pods().list()
                         if p.metadata.name.startswith("m")
                         and p.spec.node_name}
                if len(bound) == 2:
                    break
                time.sleep(0.05)
            assert len(bound) == 2, f"gang never bound: {bound}"
            assert set(bound.values()) == {"a0", "a1"}
            names = [p.metadata.name for p in client.pods().list()]
            assert "lo0" not in names and "lo1" not in names
            assert sched.metrics.preemption_gang_routed.value() >= 1
            assert sched.metrics.preemption_attempts.value() >= 1
            # no victim evicted without a recorded nomination: every
            # member carries the nomination the plan stamped
            for p in client.pods().list():
                if p.metadata.name.startswith("m"):
                    assert p.status.nominated_node_name in ("a0", "a1")
            events = client.events("default").list()
            assert any(e.reason == "Preempted" for e in events)
        finally:
            sched.stop()


class TestGangDomainFeasibility:
    def test_capacity_aware_domain_reduction(self):
        """The gang kernel no longer pins the domain off the first
        member's greedy pick: a big free node in a too-small domain
        loses to a domain that can hold ALL members."""
        cache = Cache()
        # domain "small": one empty 8-cpu node — the greedy first pick
        # (most free cpu) but only 2 member-slots for 3-cpu members
        cache.add_node(make_node("big", cpu="8", pods=20,
                                 labels={SLICE: "small"}))
        # domain "wide": four 4-cpu nodes with 1 cpu used — lower score,
        # but 4 member-slots
        for i in range(4):
            cache.add_node(make_node(f"w{i}", cpu="4", pods=20,
                                     labels={SLICE: "wide"}))
            cache.add_pod(make_pod(f"f{i}", cpu="1", node=f"w{i}"))
        sched = BatchScheduler(cache)

        class _Gang:
            metrics = None

            def batch_groups(self, pods):
                return [(list(range(len(pods))), SLICE, True, None)]
        sched.gang = _Gang()
        members = [make_pod(f"m{i}", cpu="3", mem="512Mi")
                   for i in range(4)]
        results = sched.schedule(members)
        placed = {r.pod.metadata.name: r.node_name for r in results}
        assert all(n is not None for n in placed.values()), placed
        assert set(placed.values()) == {"w0", "w1", "w2", "w3"}

    def test_greedy_pick_without_capacity_keys_regresses(self):
        """Control: the same fixture through the raw kernel WITHOUT
        need/greq keys reproduces the old first-member greedy pin (the
        gang wedges on the big node's domain and rejects)."""
        cache = Cache()
        cache.add_node(make_node("big", cpu="8", pods=20,
                                 labels={SLICE: "small"}))
        for i in range(4):
            cache.add_node(make_node(f"w{i}", cpu="4", pods=20,
                                     labels={SLICE: "wide"}))
            cache.add_pod(make_pod(f"f{i}", cpu="1", node=f"w{i}"))
        sched = BatchScheduler(cache)

        class _Gang:
            metrics = None

            def batch_groups(self, pods):
                return [(list(range(len(pods))), SLICE, True, None)]
        sched.gang = _Gang()
        import kubernetes_tpu.scheduler.core as core_mod
        orig = sched._gang_device_table

        def no_cap(units, batch):
            from kubernetes_tpu.scheduler.kernels.batch import unpack_inputs
            tab = unpack_inputs(orig(units, batch))
            tab.pop("need")
            tab.pop("greq")
            return tab
        sched._gang_device_table = no_cap
        members = [make_pod(f"m{i}", cpu="3", mem="512Mi")
                   for i in range(4)]
        results = sched.schedule(members)
        assert all(r.node_name is None for r in results)

    def test_randomized_capacity_parity(self):
        """Randomized gang fixtures WITH the capacity keys: kernel and
        numpy oracle stay bit-identical (the satellite must not fork the
        parity contract)."""
        import jax.numpy as jnp
        from kubernetes_tpu.scheduler.kernels.gang import (
            gang_schedule_batch, gang_schedule_reference)
        from test_gang import _random_instance
        dev = lambda d: {k: jnp.asarray(v) for k, v in d.items()}
        for seed in range(8):
            rng = np.random.default_rng(7000 + seed)
            nc, us, pb, gt = _random_instance(
                rng, N=16, P=16, gang_sizes=[4, 3, 2],
                constrained={0, 1}, n_domains=3)
            # derive need/greq from the entry stream like core does
            P = len(gt["pod_idx"])
            need = np.zeros((P,), np.float32)
            greq = np.zeros((P, pb["req"].shape[1]), np.float32)
            t = 0
            while t < P:
                if gt["pod_idx"][t] < 0:
                    t += 1
                    continue
                t0 = t
                idxs = [int(gt["pod_idx"][t])]
                while not gt["end"][t]:
                    t += 1
                    idxs.append(int(gt["pod_idx"][t]))
                t += 1
                for tt in range(t0, t):
                    need[tt] = len(idxs)
                    greq[tt] = pb["req"][idxs].max(axis=0)
            gt = dict(gt, need=need, greq=greq)
            a_ref, s_ref, u_ref = gang_schedule_reference(nc, us, pb, gt)
            a_k, s_k, u_k = gang_schedule_batch(dev(nc), dev(us),
                                                dev(pb), dev(gt))
            np.testing.assert_array_equal(np.asarray(a_k), a_ref,
                                          err_msg=f"seed {seed}")
            np.testing.assert_allclose(np.asarray(u_k["used"]),
                                       u_ref["used"], rtol=0, atol=0)
