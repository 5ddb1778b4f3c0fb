"""The fake cluster of upstream's scheduler_perf, as fixtures.

Reference harness: test/integration/scheduler_perf/scheduler_test.go — fake
nodes of 110 pods, 4 CPU, 32Gi each (:49-60) and one-container pods, in the
variants of scheduler_bench_test.go. `chip_smoke.py` and the tier-1 tests
build their clusters from here; the benchmark is `benchmarks/run.py`
(BENCHMARK.json), which keeps copies of its own (ROADMAP D11). Sizes are
arguments: nothing here reads the environment.

    make_node, make_pod        one fake node / pod of a variant
    run_config                 fill a scheduler in process, warm every pod
                               bucket, drain it pipelined
    bulk_create                mass load through a hub's bulk-create endpoint
    measure_parity             share of batch decisions equal to a serial
                               oracle over predicates.py / priorities.py
"""

import time
from contextlib import contextmanager

from kubernetes_tpu import api
from kubernetes_tpu.api import Quantity
from kubernetes_tpu.state import Client

#: pods per scan: 16k amortizes the per-batch fixed costs (launch, fetch,
#: host commit) over more pods; not re-derived on the chip yet (ROADMAP D9)
BATCH = 16384


def make_node(i, variant="uniform", cpu="4", memory="32Gi"):
    alloc = {"cpu": Quantity(cpu), "memory": Quantity(memory),
             "pods": Quantity(110)}
    node = api.Node(
        metadata=api.ObjectMeta(
            name=f"node-{i}",
            labels={api.wellknown.LABEL_HOSTNAME: f"node-{i}",
                    api.wellknown.LABEL_ZONE: f"zone-{i % 16}"}),
        status=api.NodeStatus(capacity=dict(alloc), allocatable=dict(alloc),
                              conditions=[api.NodeCondition(type="Ready",
                                                            status="True")]))
    if variant == "taints" and i % 2:
        # half the cluster dedicated (ref: BenchmarkSchedulingWithTaints'
        # tainted-node shape)
        node.spec.taints = [api.Taint(key="dedicated", value="gpu",
                                      effect="NoSchedule")]
    return node


def make_pod(i, variant="uniform"):
    # mixed shapes like the reference's perf configs
    cpu = ["100m", "250m", "500m"][i % 3]
    mem = ["128Mi", "512Mi", "1Gi"][i % 3]
    pod = api.Pod(
        metadata=api.ObjectMeta(name=f"pod-{i}", namespace="default",
                                labels={"app": "bench", "color": "blue"}),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="pause",
            resources=api.ResourceRequirements(
                requests={"cpu": Quantity(cpu), "memory": Quantity(mem)}))]))
    if variant == "node-affinity":
        # ref: BenchmarkSchedulingNodeAffinity — required affinity matching
        # half the nodes (zone labels)
        pod.spec.affinity = api.Affinity(node_affinity=api.NodeAffinity(
            required_during_scheduling_ignored_during_execution=api.NodeSelector(
                node_selector_terms=[api.NodeSelectorTerm(
                    match_expressions=[api.NodeSelectorRequirement(
                        key=api.wellknown.LABEL_ZONE, operator="In",
                        values=[f"zone-{z}" for z in range(8)])])])))
    elif variant == "pod-affinity":
        # ref: BenchmarkSchedulingPodAffinity — required affinity to pods
        # sharing the app label, zone topology
        pod.spec.affinity = api.Affinity(pod_affinity=api.PodAffinity(
            required_during_scheduling_ignored_during_execution=[
                api.PodAffinityTerm(
                    label_selector=api.LabelSelector(
                        match_labels={"app": "bench"}),
                    topology_key=api.wellknown.LABEL_ZONE)]))
    elif variant == "pod-anti-affinity":
        # ref: BenchmarkSchedulingPodAntiAffinity — anti-affinity on a label
        # only a seeded subset carries, hostname topology
        pod.metadata.labels["color"] = f"c{i % 100}"
        pod.spec.affinity = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[
                api.PodAffinityTerm(
                    label_selector=api.LabelSelector(
                        match_labels={"color": f"c{i % 100}"}),
                    topology_key=api.wellknown.LABEL_HOSTNAME)]))
    elif variant == "preferred-affinity":
        # soft-heavy: preferred inter-pod anti-affinity on a 16-color
        # group label — the in-scan credit-channel workload (the batch
        # shape that used to disable the class route)
        pod.metadata.labels["grp"] = f"g{i % 16}"
        pod.spec.affinity = api.Affinity(
            pod_anti_affinity=api.PodAntiAffinity(
                preferred_during_scheduling_ignored_during_execution=[
                    api.WeightedPodAffinityTerm(
                        weight=10,
                        pod_affinity_term=api.PodAffinityTerm(
                            label_selector=api.LabelSelector(
                                match_labels={"grp": f"g{i % 16}"}),
                            topology_key=api.wellknown.LABEL_HOSTNAME))]))
    elif variant == "taints":
        # two thirds tolerate the dedicated taint; one third is confined
        # to the untainted half
        if i % 3 != 2:
            pod.spec.tolerations = [api.Toleration(
                key="dedicated", operator="Equal", value="gpu",
                effect="NoSchedule")]
    return pod


def _install_variant_extras(client, sched, variant, n_nodes):
    """Post-construction wiring for the spread-heavy and nominated-heavy
    variants (shared by run_config and the sharded parity harness).

    spread: a Service selecting every bench pod, handed to the scorer as
    a direct lister (the informer wiring is measure_parity's job; the
    throughput configs feed the cache directly). nominated: phantom
    preemptor reservations on a quarter of the nodes — the kernel's
    phantom-usage overlay is live for every batch."""
    if variant == "spread":
        from kubernetes_tpu.scheduler import priorities as prios_mod
        svc = api.Service(
            metadata=api.ObjectMeta(name="bench", namespace="default"),
            spec=api.ServiceSpec(selector={"app": "bench"}))
        client.services().create(svc)
        sched.algorithm.scorer.listers = prios_mod.SpreadListers(
            services=lambda ns: [svc])
    elif variant == "nominated":
        for i in range(0, n_nodes, 4):
            ghost = make_pod(4_000_000 + i, "uniform")
            ghost.metadata.name = f"ghost-{i}"
            sched.queue.nominated.add(ghost, f"node-{i}")


def run_config(n_nodes, n_pods, variant, batch=None, seed_pods=0,
               warm_all_buckets=True, mesh=None):
    """One scheduler_perf config. Returns (pods/s, scheduled, sched,
    setup_s, elapsed) — the ONE fixture/warmup scaffold every config runs
    through, so warmup strategies cannot drift between configs;
    `sched.compiles_in_drain` counts the programs the timed drain built.

    Warmup compiles with the SAME variant (the unique-mask bucket U is part
    of the kernel shape). warm_all_buckets walks every power-of-two pod
    bucket the drain can produce — needed when in-batch (anti-)affinity
    repair demotes losers into shrinking retry batches; uniform configs
    produce no retries, so they warm just the full + final-partial buckets.

    `mesh` shards the drain over the device mesh.
    """
    from kubernetes_tpu.scheduler import Scheduler
    client = Client(validate=False)
    b = batch or BATCH
    sched = Scheduler(client, batch_size=b, mesh=mesh)
    t_setup = time.time()
    _install_variant_extras(client, sched, variant, n_nodes)
    for i in range(n_nodes):
        node = make_node(i)
        client.nodes().create(node)
        sched.cache.add_node(node)
    # seeded existing pods give (anti-)affinity terms something to match
    for i in range(seed_pods):
        p = make_pod(1_000_000 + i, variant="uniform")
        p.spec.node_name = f"node-{i % n_nodes}"
        sched.cache.add_pod(p)
    if variant in ("pod-affinity", "pod-anti-affinity"):
        # bound variant pods make the cluster affinity-carrying from the
        # start, so warmup compiles the SAME kernel shapes the drain hits
        # after its first batch binds: the static-score bucket S flips once
        # affinity pods exist, and the unique-mask bucket U collapses to 1
        # when every template's mask row is trivially all-true (no term has
        # matches yet) — either way the drain would recompile in the timed
        # region. One pod per anti-affinity color / one affine pod gives
        # every warm template a non-trivial row.
        n_seed_variant = 100 if variant == "pod-anti-affinity" else 1
        for i in range(min(n_seed_variant, n_nodes)):
            p = make_pod(3_000_000 + i, variant)
            p.spec.node_name = f"node-{i}"
            sched.cache.add_pod(p)
    pods = [client.pods().create(make_pod(i, variant))
            for i in range(n_pods)]
    from kubernetes_tpu.scheduler.tensorize import precompute_pod_features
    for pod in pods:
        # the production wiring precomputes per-pod features on the
        # informer thread as pods enter the queue (scheduler._on_pod_add);
        # this direct-queue harness does the same at add time
        precompute_pod_features(pod)
        sched.queue.add(pod)
    setup_s = time.time() - t_setup
    sched.algorithm.refresh()
    if warm_all_buckets:
        warm_sizes = []
        sz = min(b, n_pods)
        while sz >= 1:
            warm_sizes.append(sz)
            sz //= 2
    else:
        warm_sizes = [min(b, n_pods)]
        if n_pods % b:
            warm_sizes.append(n_pods % b)
    for sz in warm_sizes:
        warm = [make_pod(2_000_000 + i, variant) for i in range(sz)]
        # the drain orders every pop by DRF share on the device before
        # it tensorizes: that program is bucketed like the scan's
        sched._drf_order(warm)
        sched.algorithm.schedule(warm)
        sched.algorithm.mirror.invalidate_usage()
    _warm_dirty_scatter(sched)
    from kubernetes_tpu.scheduler import compile_log
    compiles = compile_log()
    programs0 = compiles.programs
    t0 = time.time()
    with _gc_paused():
        scheduled = sched.drain_pipelined()
    elapsed = time.time() - t0
    # programs built or loaded inside the timed drain: every one is a
    # bucket the warm-up above missed
    sched.compiles_in_drain = compiles.programs - programs0
    rate = scheduled / elapsed if elapsed else 0.0
    return rate, scheduled, sched, setup_s, elapsed


def bulk_create(rc, objs, chunk=2000):
    """Mass load through the bulk-create endpoint: one POST per chunk,
    one store transaction per chunk, four POSTs in flight (was: one HTTP
    round trip per object — 49s of setup at 20k pods in round 3)."""
    from concurrent.futures import ThreadPoolExecutor

    def one(lo):
        bad = next((r for r in rc.create_bulk(objs[lo:lo + chunk])
                    if isinstance(r, Exception)), None)
        if bad is not None:
            raise bad
    with ThreadPoolExecutor(max_workers=4) as ex:
        list(ex.map(one, range(0, len(objs), chunk)))



@contextmanager
def _gc_paused():
    """Pause the CYCLE collector for a timed drain: a gen-2 collection
    walks the whole 50k-pod heap mid-commit (~0.7s — the r05 per-batch
    p99 outlier, and +19% on the headline when it lands in the timed
    region). Refcounting still frees the per-batch clones; only cycles
    wait for the re-enabled collector (the caller gc.collect()s between
    fills). The Go reference pays a concurrent GC instead — pausing the
    stop-the-world walker is the Python deployment's equivalent tuning."""
    import gc as _gc
    was = _gc.isenabled()
    _gc.disable()
    try:
        yield
    finally:
        if was:
            _gc.enable()


def _warm_dirty_scatter(sched):
    """Compile the O(delta) row-scatter (kernels.apply_dirty) for every
    dirty-bucket size the drain can hit — the first real batch's assumes
    would otherwise compile it inside the timed region."""
    mirror = sched.algorithm.mirror
    mirror.device_cfg_usage()  # full upload path
    cap = mirror.t.capacity
    d = 1
    while d <= cap:
        mirror._dirty_rows = set(range(min(d, cap)))
        mirror.device_cfg_usage()
        d *= 2


#: fixture variants the parity harness replays. What the oracle PROVES:
#: it calls this repo's own predicates.py/priorities.py serially (pod by
#: pod, assuming between iterations) with the kernel's tie-break hash —
#: so parity measures BATCHING correctness (the device pipeline equals a
#: serial replay of the same semantics), not reference-Go parity. A skew
#: below 1.0 on soft-scoring variants quantifies the documented batch
#: drift: spread counts and soft-affinity credits freeze at batch start.
PARITY_VARIANTS = ("uniform", "node-affinity", "pod-affinity",
                   "pod-anti-affinity", "taints", "spread")


def measure_parity(variant, n_pods, n_nodes, node_cpu="4",
                   node_memory="32Gi"):
    """% of batch bind decisions identical to the serial oracle for one
    fixture variant. Returns (parity_rate, oracle_scheduled, extra).
    `node_cpu`/`node_memory` swap the fake node's round allocatable for
    one with reserved resources (e.g. "3900m"): the integer-floor scores
    then sit on boundaries a backend's divide can miss."""
    from kubernetes_tpu.api.serde import deepcopy_obj
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.scheduler import predicates as preds
    from kubernetes_tpu.scheduler import priorities as prios
    from kubernetes_tpu.scheduler.nodeinfo import NodeInfo

    pod_variant = "uniform" if variant == "spread" else variant
    nodes = [make_node(i, variant, node_cpu, node_memory)
             for i in range(n_nodes)]
    pods = [make_pod(i, pod_variant) for i in range(n_pods)]
    # seeded bound pods give required (anti-)affinity terms something to
    # match from pod one (same seeding run_config uses)
    seeds = []
    if variant == "pod-affinity":
        seeds = [(make_pod(1_000_000, "uniform"), "node-0")]
    elif variant == "pod-anti-affinity":
        seeds = [(make_pod(1_000_000 + i, "uniform"), f"node-{i}")
                 for i in range(min(100, n_nodes))]

    # batch decisions
    client = Client(validate=False)
    services = []
    if variant == "spread":
        svc = api.Service(
            metadata=api.ObjectMeta(name="bench", namespace="default"),
            spec=api.ServiceSpec(selector={"app": "bench"}))
        client.services().create(svc)
        services = [svc]
    sched = Scheduler(client, batch_size=BATCH)
    if variant == "spread":
        # the spread priority reads Service selectors through the
        # scheduler's informer indexers — run the real informer wiring so
        # the batch path sees the same selector source the oracle gets
        # (nodes/pods then arrive via event handlers, not manual adds)
        sched.informers.start()
        sched.informers.wait_for_cache_sync()
    for n in nodes:
        client.nodes().create(n)
        if variant != "spread":
            sched.cache.add_node(n)
    for sp, node_name in seeds:
        sp = deepcopy_obj(sp)
        sp.spec.node_name = node_name
        sched.cache.add_pod(sp)
    try:
        created = [client.pods().create(p) for p in pods]
        if variant == "spread":
            deadline = time.time() + 60
            while (sched.queue.num_pending() < n_pods or
                   len(sched.cache.node_names()) < n_nodes):
                if time.time() > deadline:
                    raise RuntimeError("informer sync stalled")
                time.sleep(0.01)
        else:
            for p in created:
                sched.queue.add(p)
        sched.algorithm.refresh()
        sched.drain_pipelined()
        batch_decision = {p.metadata.name: p.spec.node_name
                          for p in client.pods().list()}
        row_of = dict(sched.algorithm.mirror.row_of)
    finally:
        if variant == "spread":
            sched.informers.stop()

    # serial oracle: one pod at a time, assume between iterations
    infos = {n.metadata.name: NodeInfo(n) for n in nodes}
    for sp, node_name in seeds:
        sp = deepcopy_obj(sp)
        sp.spec.node_name = node_name
        infos[node_name].add_pod(sp)
    listers = prios.SpreadListers(services=lambda ns: services) \
        if services else None
    oracle_decision = {}
    for seq, pod in enumerate(pods):
        meta = preds.PredicateMetadata(pod, infos)
        feasible = {name: ni for name, ni in infos.items()
                    if preds.pod_fits_on_node(pod, meta, ni)[0]}
        if not feasible:
            oracle_decision[pod.metadata.name] = ""
            continue
        pmeta = prios.PriorityMetadata(pod, listers=listers)
        scores = prios.prioritize_nodes(pod, pmeta, feasible,
                                        all_node_infos=infos)
        # the kernel's tie-break, bit-exact (kernels/batch.py): the low 16
        # bits are invariant under 32-bit wraparound, so plain python ints
        # match the kernel's int32 arithmetic without overflow warnings
        def penalty(name):
            h = (row_of[name] * -1640531527 + seq * 40503) & 0xFFFF
            return float(h) * (0.5 / 65536.0)
        best = max(feasible, key=lambda nm: scores.get(nm, 0) - penalty(nm))
        oracle_decision[pod.metadata.name] = best
        bound = deepcopy_obj(pod)
        bound.spec.node_name = best
        infos[best].add_pod(bound)
    matches = sum(1 for name, nn in oracle_decision.items()
                  if batch_decision.get(name, "") == nn)
    scheduled = sum(1 for nn in oracle_decision.values() if nn)
    extra = {}
    if variant == "spread":
        # per-decision skew is the wrong lens for a SOFT spreading score
        # (the batch freezes counts at batch start, so individual picks
        # diverge); what matters is aggregate balance — report both
        # placements' max-min pods-per-node so the drift's EFFECT is
        # visible, not just its rate
        def imbalance(decision):
            counts = {}
            for nn in decision.values():
                if nn:
                    counts[nn] = counts.get(nn, 0) + 1
            return (max(counts.values()) - min(counts.values())) \
                if counts else 0
        extra = {"batch_imbalance": imbalance(batch_decision),
                 "oracle_imbalance": imbalance(oracle_decision)}
    return matches / max(1, len(oracle_decision)), scheduled, extra


# ------------------------------------------------------ sharded section
#
# The mesh-sharded drain (ISSUE 13): run the SAME uniform fill with the
# node axis sharded over 1..K devices (shard_map class scan, cross-shard
# argmax) and report the device-scaling curve, plus bit-identity parity
# fixtures against the single-device kernel. Runs on CPU via
# XLA_FLAGS=--xla_force_host_platform_device_count=8 (make bench-sharded);
# on a single-core host the virtual devices timeshare, so wall-clock
# scaling there measures sharding OVERHEAD — the honest number is still
