#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts, and
decides correctly, on the chip.

    python chip_smoke.py              one TPU chip: three phases (below)
    python chip_smoke.py --chips 4    four chips: ONLY the sharded-vs-single
                                      comparison, and nothing else
    python chip_smoke.py --rehearse   the same code on the CPU at a tiny
                                      size (with --chips 4: four virtual
                                      CPU devices) — for the sandbox

Default phases, one chip, the north-star deployment (BASELINE.json: 50,000
pending pods x 5,000 nodes; fake-node shape 4 CPU / 32 Gi / 110 pods):

  1. served   A real `kube_apiserver` process (WAL + validation on, pinned
              to the CPU) and a real `kube_scheduler` process, the ONE
              owner of the chip, with batchSize from a --config file. This
              parent stays off JAX, bulk-creates the cluster over HTTP and
              waits until every pod is bound, then checks the placement
              from the hub's own LIST: every pod bound, no node over its
              allocatable, node-affinity and pod-anti-affinity honoured.
              The scheduler must have named a TPU at start-up, logged no
              traceback and counted no run-loop error.
  2. parity   With both children gone, this process takes the chip: bind
              decisions equal the serial oracle on all six fixture variants,
              and the gang / preemption-pricing / DRF / affinity-template
              kernels equal their references.
  3. drain    fakecluster.run_config(5000, 50000, "uniform") through the
              pipelined drain: all bound, nothing compiled inside the
              timed drain, compile and drain seconds and peak device
              memory printed as smoke output (not benchmark results).

Every earlier line is one JSON object describing a phase. The LAST line is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}` as
JAX reports the device, or `{"ok": false, ...}` with a non-zero exit when
anything failed — a phase failure ends the run, none is caught to go on.
Without --rehearse any platform but `tpu` is a failure: JAX_PLATFORMS=tpu
is set for the scheduler child and for this process, so a missing chip
raises instead of falling back.
"""

import argparse
import glob
import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

#: (nodes, uniform, node-affinity, pod-anti-affinity, spread) pods
FULL_SIZE = (5000, 40000, 5000, 2500, 2500)
REHEARSE_SIZE = (100, 800, 100, 50, 50)
#: the served scheduler's batchSize: the repo's drain default
#: (fakecluster.BATCH) — at a 50k backlog every full pop lands in the one
#: 16384 pod bucket that phase 3 compiles too, so the second process's
#: compile-cache hits can be seen; a smaller batch would only add buckets
SERVED_BATCH = 16384


def say(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond, message):
    if not cond:
        raise AssertionError(message)


# ---------------------------------------------------------------- phase 1


class Child:
    """One child process with its output in files; stopped and waited for
    by close(), whatever happened."""

    def __init__(self, name, argv, env, workdir):
        self.name = name
        self.out_path = os.path.join(workdir, f"{name}.out")
        self.err_path = os.path.join(workdir, f"{name}.err")
        self._out = open(self.out_path, "wb")
        self._err = open(self.err_path, "wb")
        self.proc = subprocess.Popen(argv, cwd=REPO, env=env,
                                     stdout=self._out, stderr=self._err)

    def _read(self, path):
        with open(path, "rb") as f:
            return f.read().decode(errors="replace")

    def stdout(self):
        return self._read(self.out_path)

    def stderr(self):
        return self._read(self.err_path)

    def wait_line(self, prefix, timeout):
        """The first stdout line starting with `prefix`; fails when the
        process dies or the deadline passes first."""
        deadline = time.time() + timeout
        while True:
            for line in self.stdout().splitlines():
                if line.startswith(prefix):
                    return line
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"{self.name} exited with {self.proc.returncode} "
                    f"before printing {prefix!r}; stderr tail:\n"
                    f"{self.stderr()[-3000:]}")
            if time.time() > deadline:
                raise RuntimeError(
                    f"{self.name} did not print {prefix!r} within "
                    f"{timeout}s; stderr tail:\n{self.stderr()[-3000:]}")
            time.sleep(0.1)

    def close(self):
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._out.close()
        self._err.close()
        return self.proc.returncode


def free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def scrape(url):
    """{metric line name-with-labels: value} of a /metrics exposition."""
    with urllib.request.urlopen(url, timeout=10) as resp:
        text = resp.read().decode()
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


#: fakecluster.make_pod's three request shapes, picked by the seeded
#: generator
REQUEST_SHAPES = (("100m", "128Mi"), ("250m", "512Mi"), ("500m", "1Gi"))


def seeded_pod(i, variant, rng):
    """fakecluster.make_pod(i, variant), its request shape drawn from rng."""
    import fakecluster
    from kubernetes_tpu.api import Quantity
    pod = fakecluster.make_pod(i, variant)
    cpu, mem = REQUEST_SHAPES[rng.randrange(len(REQUEST_SHAPES))]
    pod.spec.containers[0].resources.requests = {
        "cpu": Quantity(cpu), "memory": Quantity(mem)}
    return pod


def build_cluster(size, seed):
    """(nodes, service, pods, kinds): the fakecluster.py fake-node cluster
    and a seeded pod mix — request shapes drawn from fakecluster.make_pod's
    three, the constrained pods shuffled together behind the uniform block
    (so the class scan runs without, then with, in-scan topology/spread
    carry).
    kinds maps pod name -> "uniform" | "node-affinity" |
    "pod-anti-affinity" | "spread"."""
    import fakecluster
    from kubernetes_tpu import api
    n_nodes, n_uniform, n_nodeaff, n_anti, n_spread = size
    rng = random.Random(seed)
    nodes = [fakecluster.make_node(i) for i in range(n_nodes)]
    # spread pods wear a label of their own; the Service that selects it
    # reaches the scorer through the scheduler's informer-fed lister
    service = api.Service(
        metadata=api.ObjectMeta(name="web", namespace="default"),
        spec=api.ServiceSpec(selector={"tier": "web"}))
    plan = [("uniform", "uniform")] * n_uniform
    constrained = ([("node-affinity", "node-affinity")] * n_nodeaff
                   + [("pod-anti-affinity", "pod-anti-affinity")] * n_anti
                   + [("spread", "uniform")] * n_spread)
    rng.shuffle(constrained)
    pods, kinds = [], {}
    for i, (kind, variant) in enumerate(plan + constrained):
        pod = seeded_pod(i, variant, rng)
        if kind == "spread":
            pod.metadata.labels["tier"] = "web"
        pods.append(pod)
        kinds[pod.metadata.name] = kind
    return nodes, service, pods, kinds


def check_placement(listed, nodes, kinds, n_expected):
    """The placement invariants, on the hub's own LIST of the pods."""
    from kubernetes_tpu import api
    check(len(listed) == n_expected and
          len({p.metadata.name for p in listed}) == n_expected,
          f"hub lists {len(listed)} pods, expected {n_expected}")
    unbound = [p.metadata.name for p in listed if not p.spec.node_name]
    check(not unbound, f"{len(unbound)} pods unbound, e.g. {unbound[:5]}")
    alloc = {n.metadata.name: n.status.allocatable for n in nodes}
    zone = {n.metadata.name: n.metadata.labels[api.wellknown.LABEL_ZONE]
            for n in nodes}
    cpu, mem, count, colours = {}, {}, {}, {}
    for p in listed:
        nn = p.spec.node_name
        check(nn in alloc, f"{p.metadata.name} bound to unknown node {nn}")
        check(any(c.type == "PodScheduled" and c.status == "True"
                  for c in p.status.conditions),
              f"{p.metadata.name} carries no PodScheduled condition")
        req = p.spec.containers[0].resources.requests
        cpu[nn] = cpu.get(nn, 0) + req["cpu"].milli_value()
        mem[nn] = mem.get(nn, 0) + req["memory"].value()
        count[nn] = count.get(nn, 0) + 1
        kind = kinds[p.metadata.name]
        if kind == "node-affinity":
            # fakecluster.make_pod: required zone in zone-0 .. zone-7
            check(int(zone[nn].split("-")[1]) < 8,
                  f"{p.metadata.name} (node-affinity) in {zone[nn]}")
        elif kind == "pod-anti-affinity":
            key = (nn, p.metadata.labels["color"])
            check(key not in colours,
                  f"{p.metadata.name} and {colours.get(key)} share "
                  f"colour {key[1]} on {nn}")
            colours[key] = p.metadata.name
    for nn, a in alloc.items():
        check(cpu.get(nn, 0) <= a["cpu"].milli_value()
              and mem.get(nn, 0) <= a["memory"].value()
              and count.get(nn, 0) <= a["pods"].value(),
              f"node {nn} over allocatable: cpu {cpu.get(nn)}m "
              f"mem {mem.get(nn)} pods {count.get(nn)}")
    return {"bound": len(listed), "nodes_used": len(count),
            "max_pods_on_a_node": max(count.values())}


def phase_served(args, platform, workdir):
    check("jax" not in sys.modules, "the parent touched JAX before phase 1")
    size = REHEARSE_SIZE if args.rehearse else FULL_SIZE
    n_pods = sum(size[1:]) + (1 if args.inject_unschedulable else 0)
    if not args.rehearse:
        # nothing the program loads may come from a file git would not
        # commit: the hub rebuilds the native WAL from walcore.cc. (A
        # rehearsal shares its tree with whatever else runs in the
        # sandbox, and native/build.py only ever loads a library named
        # after the committed source's hash.)
        for so in glob.glob(os.path.join(REPO, "kubernetes_tpu", "native",
                                         "*.so")):
            os.remove(so)
    config = os.path.join(workdir, "scheduler-config.json")
    with open(config, "w") as f:
        json.dump({"batchSize": SERVED_BATCH}, f)
    hub_port, metrics_port = free_port(), free_port()
    hub_env = dict(os.environ, JAX_PLATFORMS="cpu")  # never grabs the chip
    sched_env = dict(os.environ, JAX_PLATFORMS=platform)
    hub = sched = None
    try:
        hub = Child("kube_apiserver", [
            sys.executable, "-m", "kubernetes_tpu.cmd.kube_apiserver",
            "--port", str(hub_port),
            "--data-dir", os.path.join(workdir, "hub")], hub_env, workdir)
        wal_line = hub.wait_line("wal ", 120)
        hub.wait_line("serving on", 60)
        check(wal_line.endswith("native=True"),
              f"the native WAL did not build: {wal_line!r}\n"
              f"{hub.stderr()[-2000:]}")
        base = f"http://127.0.0.1:{hub_port}"
        sched = Child("kube_scheduler", [
            sys.executable, "-m", "kubernetes_tpu.cmd.kube_scheduler",
            "--master", base, "--config", config,
            "--healthz-port", str(metrics_port)], sched_env, workdir)
        device = json.loads(sched.wait_line(
            "kube-scheduler device ", 300).split(" ", 2)[2])
        check(device["platform"] == platform,
              f"the scheduler runs on {device}, not on {platform}")
        say("served.start", wal=wal_line, scheduler_device=device,
            batch_size=SERVED_BATCH)

        import fakecluster
        from kubernetes_tpu.apiserver import HTTPClient
        from kubernetes_tpu.api import Quantity
        client = HTTPClient(base)
        nodes, service, pods, kinds = build_cluster(size, args.seed)
        if args.inject_unschedulable:
            # a pod no node can hold: the bound-all wait must fail
            giant = fakecluster.make_pod(len(pods))
            giant.metadata.name = "never-fits"
            giant.spec.containers[0].resources.requests["cpu"] = \
                Quantity("64")
            pods.append(giant)
            kinds["never-fits"] = "uniform"
        t0 = time.time()
        client.services("default").create(service)
        fakecluster.bulk_create(client.nodes(), nodes)
        fakecluster.bulk_create(client.pods("default"), pods)
        created_s = time.time() - t0
        metrics_url = f"http://127.0.0.1:{metrics_port}/metrics"
        scheduled_key = 'scheduler_schedule_attempts_total' \
                        '{result="scheduled"}'
        deadline = t0 + args.bind_deadline
        listed = []
        while True:
            # the scheduler counts a pod at assume time, its bind POST
            # still in flight: the cheap counter says when to look, the
            # hub's LIST says when every bind has landed
            m = scrape(metrics_url)
            if m.get(scheduled_key, 0) >= n_pods:
                listed = client.pods("default").list()
                if all(p.spec.node_name for p in listed):
                    break
            check(sched.proc.poll() is None,
                  f"kube_scheduler exited with {sched.proc.returncode}; "
                  f"stderr tail:\n{sched.stderr()[-3000:]}")
            check(time.time() < deadline,
                  f"{int(m.get(scheduled_key, 0))}/{n_pods} pods scheduled"
                  f" after {args.bind_deadline}s")
            time.sleep(0.5)
        bound_s = time.time() - t0
        m = scrape(metrics_url)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{metrics_port}/healthz",
                timeout=10) as resp:
            check(resp.status == 200, "scheduler /healthz is not ok")
        placement = check_placement(listed, nodes, kinds, n_pods)
        check(m[scheduled_key] == n_pods,
              f"{m[scheduled_key]} binds for {n_pods} pods: some pod was "
              f"bound more than once")
        check(m.get("scheduler_loop_errors_total", 0) == 0,
              "the scheduling loop recorded errors")
        check(m.get('scheduler_schedule_attempts_total{result="error"}',
                    0) == 0, "bind errors were recorded")
        say("served.bound", pods=n_pods, nodes=size[0],
            create_seconds=round(created_s, 1),
            all_bound_seconds=round(bound_s, 1), **placement,
            loop_errors=0, device=device)
    finally:
        # both children are stopped and waited for before this process
        # may touch JAX: one process per chip
        sched_rc = sched.close() if sched is not None else None
        hub_rc = hub.close() if hub is not None else None
    check("Traceback" not in sched.stderr(),
          f"kube_scheduler logged a traceback:\n{sched.stderr()[-3000:]}")
    check(sched_rc == 0 and hub_rc == 0,
          f"children exited with {sched_rc} (scheduler), {hub_rc} (hub)")
    compiles = next((line for line in sched.stdout().splitlines()
                     if line.startswith("kube-scheduler compiles ")), None)
    check(compiles is not None, "the scheduler printed no compile summary")
    say("served.compiles", first_process=json.loads(
        compiles.split(" ", 2)[2]), compile_cache=device["compile_cache"])


# ---------------------------------------------------------------- phase 2


def take_device(platform):
    """First JAX touch of this process: the device it really runs on."""
    from kubernetes_tpu.scheduler import (compile_log, device_report,
                                          enable_compile_cache)
    cache_dir = enable_compile_cache()
    compile_log()
    device = device_report()
    check(device["platform"] == platform,
          f"this process runs on {device}, not on {platform}")
    say("device", **device, compile_cache=cache_dir)
    return device


def kernel_parity(seed):
    """The gang, preemption-pricing, DRF and affinity-template kernels
    against their references, on seeded input (the randomized fixtures of
    the repo's own tests, reseeded)."""
    import numpy as np
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import jax.numpy as jnp
    dev = lambda d: {k: jnp.asarray(v) for k, v in d.items()}
    out = {}

    from test_gang import _random_instance
    from kubernetes_tpu.scheduler.kernels.gang import (
        gang_schedule_batch, gang_schedule_reference)
    for trial in range(6):
        rng = np.random.default_rng([seed, trial])
        nc, us, pb, gt = _random_instance(
            rng, N=64, P=64, gang_sizes=(8, 4, 3, 2, 1), constrained=(0, 2))
        a_ref, s_ref, u_ref = gang_schedule_reference(nc, us, pb, gt)
        a_k, s_k, u_k = gang_schedule_batch(dev(nc), dev(us), dev(pb),
                                            dev(gt))
        check((np.asarray(a_k) == a_ref).all(),
              f"gang kernel differs from its reference (trial {trial})")
        placed = a_ref >= 0
        check(np.allclose(np.asarray(s_k)[placed], s_ref[placed]) and all(
            np.allclose(np.asarray(u_k[k]), u_ref[k]) for k in u_ref),
            f"gang kernel scores/usage differ (trial {trial})")
    out["gang"] = "6 randomized instances (64 nodes x 64 pods) equal"

    from test_preempt import _rand_cluster, make_pdb, make_pod
    from kubernetes_tpu.scheduler.kernels import preempt as pk
    priced = 0
    for trial in range(8):
        rng = np.random.default_rng([seed, 100 + trial])
        infos = _rand_cluster(rng, n_nodes=48)
        pdbs = [make_pdb("pdb0", {"band": "b0"}, int(rng.integers(0, 3))),
                make_pdb("pdb1", {"band": "b1"}, 0)]
        pod = make_pod("high", cpu=f"{int(rng.integers(10, 40)) * 100}m",
                       mem="1Gi", priority=100)
        tabs = pk.build_victim_tables(pod, sorted(infos.items()), infos,
                                      pdbs)
        if tabs is None:
            continue
        a = tabs.arrays
        w_k, ch_k, _k, nv_k = pk.price_nodes(*(a[k] for k in (
            "free0", "cfree0", "need", "need_cnt", "freed", "fcnt",
            "valid", "pdb", "top", "psum", "gcnt", "startr", "row_valid")))
        w_r, ch_r, _kr, nv_r = pk.price_nodes_reference(a)
        check(int(w_k) == int(w_r) and (np.asarray(ch_k) == ch_r).all()
              and (np.asarray(nv_k) == nv_r).all(),
              f"price_nodes differs from its reference (trial {trial})")
        priced += 1
    check(priced >= 4, "too few preemption fixtures priced")
    for trial in range(6):
        rng = np.random.default_rng([seed, 200 + trial])
        infos = _rand_cluster(rng, n_nodes=48)
        members = [make_pod(f"m{i}", cpu="900m", mem="512Mi", priority=100,
                            group="gx") for i in range(4)]
        cands = [(n, ni, f"s{i // 4}")
                 for i, (n, ni) in enumerate(sorted(infos.items()))]
        a = pk.build_domain_tables(
            members, cands, infos, [make_pdb("pdb0", {"band": "b0"}, 1)],
            min_member=4).arrays
        w_k, ch_k, nv_k = pk.price_domains(*(a[k] for k in (
            "base", "need", "dslots", "valid", "pdb", "top", "psum",
            "gcnt", "startr", "row_valid")))
        w_r, ch_r, nv_r = pk.price_domains_reference(a)
        check(int(w_k) == int(w_r) and (np.asarray(ch_k) == ch_r).all()
              and (np.asarray(nv_k) == nv_r).all(),
              f"price_domains differs from its reference (trial {trial})")
    out["preempt"] = f"price_nodes x{priced}, price_domains x6 equal"

    from test_tenancy import make_pod as tenant_pod
    from kubernetes_tpu.tenancy import DRFAccount
    rng = np.random.default_rng([seed, 300])
    acct = DRFAccount()
    acct.set_capacity([64_000.0, 512 << 30, 64.0])
    tenants = [f"t{j}" for j in range(7)]
    for j, t in enumerate(tenants):
        for k in range(int(rng.integers(1, 6))):
            acct.charge(tenant_pod(
                f"std-{j}-{k}", tenant=t,
                cpu=f"{int(rng.integers(1, 40))}00m",
                mem=f"{int(rng.integers(1, 65))}Mi"))
    batch = [tenant_pod(f"b-{i}", tenant=tenants[int(rng.integers(0, 7))],
                        priority=int(rng.choice((0, 0, 0, 1000))))
             for i in range(1000)]
    check(len(batch) >= DRFAccount.DEVICE_FLOOR, "DRF batch under the floor")
    check([p.metadata.name for p in acct.order_batch(batch)]
          == [p.metadata.name for p in acct.order_batch_reference(batch)],
          "DRFAccount.order_batch differs from order_batch_reference")
    out["drf"] = "order of 1000 pods over 7 tenants equal"

    # the template matmuls, with per-(term, domain) counts in the
    # thousands and weights up to 100: exact only at full precision
    from kubernetes_tpu.scheduler.kernels.affinity import (affinity_masks,
                                                           affinity_scores)
    rng = np.random.default_rng([seed, 400])
    U, T, N = 24, 40, 700
    weights = rng.integers(0, 101, (U, T)).astype(np.float32)
    counts = rng.integers(0, 5000, (T, N)).astype(np.float32)
    check((affinity_scores(weights, counts)
           == (weights.astype(np.int64) @ counts.astype(np.int64))).all(),
          "affinity_scores is not exact")
    has_dom, present = rng.random((T, N)) > 0.1, rng.random((T, N)) > 0.5
    sel = [(rng.random((U, T)) > 0.8).astype(np.float32) for _ in range(3)]
    pr = present & has_dom
    viol = (sel[0].astype(np.int64) @ (~has_dom).astype(np.int64)
            + sel[1].astype(np.int64) @ (~pr).astype(np.int64)
            + sel[2].astype(np.int64) @ pr.astype(np.int64))
    check((affinity_masks(has_dom, present, *sel) == (viol == 0)).all(),
          "affinity_masks differs from the integer reference")
    out["affinity"] = "template scores (counts to 5000) and masks exact"

    # the integer-floor score arithmetic against the reference's integer
    # and f64 formulas (priorities.py), over every request level of a
    # node with reserved resources — 3900m / 31Gi, a divisor the chip's
    # f32 divide returns exact multiples of just UNDER their integer
    import jax
    from kubernetes_tpu.scheduler.kernels import batch as kb
    cc, cm = 3900, 31 << 30
    RC, RM = [a.ravel() for a in np.meshgrid(
        np.arange(0, cc + 1, 50), np.arange(0, cm + 1, 128 << 20),
        indexing="ij")]
    nz = np.stack([RC, RM], 1).astype(np.float32)
    caps = (np.full(len(RC), cc, np.float32),
            np.full(len(RC), cm, np.float32))
    zero = np.zeros(2, np.float32)
    lr = np.asarray(jax.jit(kb._least_requested)(nz, zero, *caps))
    ba = np.asarray(jax.jit(kb._balanced_allocation)(nz, zero, *caps))
    lr_ref = [((cc - a) * 10 // cc + (cm - b) * 10 // cm) // 2
              for a, b in zip(RC.tolist(), RM.tolist())]
    ba_ref = [0 if a >= cc or b >= cm
              else int((1 - abs(a / cc - b / cm)) * 10.0)
              for a, b in zip(RC.tolist(), RM.tolist())]
    check((lr == lr_ref).all() and (ba == ba_ref).all(),
          f"score arithmetic differs from the reference on a 3900m/31Gi "
          f"node: LeastRequested {int((lr != lr_ref).sum())}, "
          f"BalancedAllocation {int((ba != ba_ref).sum())} of {len(RC)}")
    # SelectorSpread's count inversion against upstream's float64
    # int(10 * ((maxc - c) / maxc)), for max counts whose reciprocal
    # rounds down in f32 (25, 49, 110) as well as up (3)
    tab = kb.spread_round_table(128)
    for maxc in (3, 25, 49, 110):
        cnt = np.arange(maxc + 1, dtype=np.float32)
        n = len(cnt)
        got = np.asarray(jax.jit(kb._spread_score)(
            cnt, np.ones(n, bool), np.zeros(n, np.int32),
            np.zeros(1, np.float32), np.zeros((1, n), np.float32), tab))
        check((got == [int(10.0 * (float(maxc - c) / float(maxc)))
                       for c in range(n)]).all(),
              f"spread scores differ from the reference at max count {maxc}")
    out["scores"] = (f"LeastRequested/BalancedAllocation over {len(RC)} "
                     f"request levels of a 3900m/31Gi node, spread "
                     f"inversion at max counts 3/25/49/110: equal")
    return out


def phase_parity(args):
    import fakecluster
    size = (200, 50) if args.rehearse else (2000, 500)
    rates = {}
    for variant in fakecluster.PARITY_VARIANTS:
        rate, scheduled, _ = fakecluster.measure_parity(variant, *size)
        rates[variant] = rate
        check(scheduled > 0, f"{variant}: the oracle scheduled nothing")
    # the fake node's 4000m / 32Gi are benign divisors; a node with
    # reserved resources puts the integer-floor scores on boundaries the
    # chip's (not correctly rounded) f32 divide misses
    for variant in ("uniform", "pod-affinity"):
        rate, scheduled, _ = fakecluster.measure_parity(
            variant, *size, node_cpu="3900m", node_memory="31Gi")
        rates[f"{variant} @ 3900m/31Gi nodes"] = rate
        check(scheduled > 0, f"{variant}: the oracle scheduled nothing")
    say("parity.decisions", fixture=f"{size[0]} pods x {size[1]} nodes",
        oracle="serial numpy/int64 replay (fakecluster.measure_parity)",
        rates=rates)
    check(set(rates.values()) == {1.0},
          f"bind decisions differ from the serial oracle: {rates}")
    say("parity.kernels", **kernel_parity(args.seed))


# ---------------------------------------------------------------- phase 3


def phase_drain(args, device):
    import jax
    import fakecluster
    from kubernetes_tpu.scheduler import compile_log
    n_nodes, n_pods = (100, 1000) if args.rehearse else (5000, 50000)
    compiles = compile_log()
    before = compiles.summary()
    rate, scheduled, sched, setup_s, elapsed = fakecluster.run_config(
        n_nodes, n_pods, "uniform", warm_all_buckets=False)
    after = compiles.summary()
    in_drain = sched.compiles_in_drain
    stats = jax.devices()[0].memory_stats()
    say("drain", nodes=n_nodes, pods=n_pods, bound=scheduled,
        batch=fakecluster.BATCH, drain_seconds=round(elapsed, 3),
        setup_seconds=round(setup_s, 1),
        # this process is the second to compile: what it loaded from the
        # cache the scheduler child wrote (hits) vs compiled itself
        warmup_programs=after["programs"] - before["programs"],
        warmup_cache_hits=after["cache_hits"] - before["cache_hits"],
        warmup_cache_misses=after["cache_misses"] - before["cache_misses"],
        warmup_compile_seconds=round(
            after["seconds"] - before["seconds"], 2),
        compiles_in_timed_drain=in_drain,
        peak_bytes_in_use=(stats or {}).get(
            "peak_bytes_in_use", "not reported by this backend"),
        device=device, note="smoke output, not a benchmark result")
    check(scheduled == n_pods, f"{scheduled}/{n_pods} pods bound")
    check(in_drain == 0,
          f"{in_drain} programs were compiled inside the timed drain")


# --------------------------------------------------------- --chips 4 phase


def sharded_fixture(n_nodes, n_pods, seed):
    """tests/test_sharded.py's node and pod shapes at scale, one mixed
    queue: half plain, and an eighth each of anti-affinity-dir2 carriers,
    their pure matchers, soft (preferred) anti-affinity and spread pods,
    shuffled by the seed so both batches carry every kind."""
    from kubernetes_tpu import api
    from kubernetes_tpu.api import Quantity
    hostname, zone = api.wellknown.LABEL_HOSTNAME, api.wellknown.LABEL_ZONE
    alloc = {"cpu": Quantity("4"), "memory": Quantity("8Gi"),
             "pods": Quantity(110)}
    nodes = [api.Node(
        metadata=api.ObjectMeta(name=f"n{i}", labels={
            hostname: f"n{i}", zone: f"z{i % 4}"}),
        status=api.NodeStatus(
            capacity=dict(alloc), allocatable=dict(alloc),
            conditions=[api.NodeCondition(type="Ready", status="True")]))
        for i in range(n_nodes)]

    def anti(selector, preferred=False):
        term = api.PodAffinityTerm(
            label_selector=api.LabelSelector(match_labels=selector),
            topology_key=hostname)
        if preferred:
            return api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
                preferred_during_scheduling_ignored_during_execution=[
                    api.WeightedPodAffinityTerm(weight=10,
                                                pod_affinity_term=term)]))
        return api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[term]))
    kinds = (["plain"] * (n_pods // 2) + ["carrier", "matcher", "soft",
                                          "spread"] * (n_pods // 8))
    random.Random(seed).shuffle(kinds)
    pods = []
    for i, kind in enumerate(kinds):
        labels = {"plain": {"app": "plain"},
                  "carrier": {"app": "m"}, "matcher": {"app": "m"},
                  "soft": {"app": "soft", "g": f"g{i % 8}"},
                  "spread": {"app": "web"}}[kind]
        pod = api.Pod(
            metadata=api.ObjectMeta(name=f"p{i}", namespace="default",
                                    labels=labels),
            spec=api.PodSpec(containers=[api.Container(
                name="c", image="img",
                resources=api.ResourceRequirements(requests={
                    "cpu": Quantity(["100m", "250m", "500m"][i % 3]),
                    "memory": Quantity("128Mi")}))]))
        if kind == "carrier":
            # anti-affine to the label carriers AND pure matchers wear:
            # the direction-2 carry table ships
            pod.spec.affinity = anti({"app": "m"})
        elif kind == "soft":
            pod.spec.affinity = anti({"g": labels["g"]}, preferred=True)
        pods.append(pod)
    service = api.Service(
        metadata=api.ObjectMeta(name="web", namespace="default"),
        spec=api.ServiceSpec(selector={"app": "web"}))
    return nodes, service, pods


def phase_sharded(args, device):
    """ONLY the path that exists across chips and what it is compared
    with: one fixture drained on a single device and on a 4-device
    "nodes" mesh; the binds must be bit-identical."""
    import gc
    import jax
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.scheduler import priorities as prios_mod
    from kubernetes_tpu.scheduler.tensorize import precompute_pod_features
    from kubernetes_tpu.state import Client
    check(device["count"] == 4, f"--chips 4 found {device['count']} devices")
    n_nodes, n_pods, batch = (96, 256, 128) if args.rehearse \
        else (50000, 32768, 16384)
    nodes, service, pods = sharded_fixture(n_nodes, n_pods, args.seed)

    def drain(mesh):
        client = Client(validate=False)
        sched = Scheduler(client, batch_size=batch, mesh=mesh)
        sched.algorithm.scorer.listers = prios_mod.SpreadListers(
            services=lambda ns: [service])
        for node in nodes:
            sched.cache.add_node(node)
        for pod in pods:
            created = client.pods().create(pod)
            precompute_pod_features(created)
            sched.queue.add(created)
        sched.algorithm.refresh()
        t0 = time.time()
        n = sched.drain_pipelined()
        seconds = time.time() - t0
        binds = {p.metadata.name: p.spec.node_name
                 for p in client.pods().list()}
        _, usage = sched.algorithm.mirror.device_cfg_usage()
        device_sets = {k: len(v.sharding.device_set)
                       for k, v in usage.items()}
        return n, binds, sched.metrics.sharded_batches.value(), \
            device_sets, seconds
    n1, single, sb1, sets1, s1 = drain(1)
    gc.collect()
    n4, sharded, sb4, sets4, s4 = drain(4)
    differing = [k for k in single if single[k] != sharded.get(k)]
    say("sharded", nodes=n_nodes, pods=n_pods, batch=batch,
        single={"bound": n1, "sharded_batches": sb1,
                "usage_device_sets": sets1, "drain_seconds": round(s1, 2)},
        mesh4={"bound": n4, "sharded_batches": sb4,
               "usage_device_sets": sets4, "drain_seconds": round(s4, 2)},
        differing_binds=len(differing), device=device,
        note="smoke output (both drains compile as they go), "
             "not a benchmark result")
    check(n1 == n4 > 0, f"bound {n1} on one device, {n4} on the mesh")
    check(not differing,
          f"{len(differing)} binds differ between one device and the "
          f"mesh, e.g. {[(k, single[k], sharded.get(k)) for k in differing[:5]]}")
    check(sb1 == 0 and sb4 > 0,
          f"sharded_batches: {sb1} on one device, {sb4} on the mesh")
    check(all(n == 4 for n in sets4.values()),
          f"usage tensors are not spread over 4 devices: {sets4}")


# -------------------------------------------------------------------- main


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--rehearse", action="store_true",
                   help="run on the CPU at a tiny size; never says tpu")
    p.add_argument("--seed", type=int, default=0,
                   help="seed of every generated workload")
    p.add_argument("--bind-deadline", type=float, default=None,
                   help="seconds phase 1 waits for every pod to be bound "
                        "(default 540, 120 with --rehearse)")
    p.add_argument("--inject-unschedulable", action="store_true",
                   help="add a pod no node can hold, so phase 1 must fail "
                        "(the smoke's own failure test)")
    args = p.parse_args(argv)
    if args.bind_deadline is None:
        args.bind_deadline = 120.0 if args.rehearse else 540.0
    platform = "cpu" if args.rehearse else "tpu"
    # for this process too: a missing chip raises, it never falls back
    os.environ["JAX_PLATFORMS"] = platform
    if args.rehearse and args.chips > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")   # data, never cache
    try:
        if args.chips == 4:
            device = take_device(platform)
            phase_sharded(args, device)
        else:
            phase_served(args, platform, workdir)
            device = take_device(platform)
            phase_parity(args)
            phase_drain(args, device)
    except Exception as e:
        traceback.print_exc()
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
