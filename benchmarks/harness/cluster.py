"""A configuration's cluster as plain manifests, made from the seed: the
fake nodes, created in an order drawn from the seed, and an endless
stream of pods drawn from the pod mix. One variant = one file under
benchmarks/variants/, found by name. Every seed gives the same nodes and
the same kinds of pods, in another order: the seed never changes the
amount of work.

What else a configuration's file may name, each a file found by that
name and each with what stood here before as the default: its node
(`"node_variant"`: benchmarks/nodes/<name>.py) and the module that
decides `correct` for it (`"reference"`: benchmarks/references/<name>.py).
"""

import importlib.util
import json
import os
import random

from . import reference

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)

HOSTNAME = "kubernetes.io/hostname"
ZONE = "failure-domain.beta.kubernetes.io/zone"


def load_module(path):
    """A Python file found by its path (names here may hold `-` and `.`)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + "".join(c if c.isalnum() else "_"
                           for c in os.path.basename(path)[:-3]), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_named(folder, name):
    """benchmarks/<folder>/<name>.py, the file a configuration names."""
    return load_module(os.path.join(BENCH_DIR, folder, f"{name}.py"))


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload):
    """(BENCHMARK.json, the cell's entry, its configuration, its mix)."""
    repo = os.path.dirname(BENCH_DIR)
    bench = load_json(repo, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (bench, cell, load_json(repo, entry["file"]),
            load_json(BENCH_DIR, "traffic", f"{cell['traffic']}.json"))


def load_reference(config):
    """The module that decides `correct` for this configuration (its
    contract: harness/reference.py)."""
    if "reference" not in config:
        return reference
    return load_named("references", config["reference"])


def plain_node(i, config):
    """scheduler_perf's fake node: one shape, allocatable = capacity, a
    hostname and a zone label, no taint."""
    shape = config["node"]
    alloc = {"cpu": shape["cpu"], "memory": shape["memory"],
             "pods": str(shape["pods"])}
    return {
        "apiVersion": "v1", "kind": "Node",
        "metadata": {"name": f"node-{i}", "labels": {
            HOSTNAME: f"node-{i}", ZONE: f"zone-{i % shape['zones']}"}},
        "status": {"capacity": dict(alloc), "allocatable": dict(alloc),
                   "conditions": [{"type": "Ready", "status": "True"}]},
    }


def make_nodes(config, n_nodes, seed):
    """node-0 ... in an order drawn from the seed, each built by the
    configuration's `node_variant` (`build(i, config) -> manifest`)."""
    build = plain_node
    if "node_variant" in config:
        build = load_named("nodes", config["node_variant"]).build
    order = list(range(n_nodes))
    random.Random(seed ^ 0x0DE5).shuffle(order)
    return [build(i, config) for i in order]


class PodStream:
    """pod-0, pod-1, ...: the variant of each drawn from the mix's shares
    by one generator seeded with --seed, which the variant may draw from
    too. A variant reads its sizes (the source's pod, its label values)
    from the configuration, which also carries the seed. The same seed
    gives the same pods in the same order."""

    def __init__(self, config, seed):
        self._rng = random.Random(seed)
        self._config = dict(config, seed=seed)
        mix = config["pod_mix"]
        self._builders = [load_named("variants", m["variant"]).build
                          for m in mix]
        self._shares = [float(m["share"]) for m in mix]
        self._next = 0

    def take(self, n):
        out = []
        for _ in range(n):
            if len(self._builders) == 1:
                build = self._builders[0]
            else:
                build = self._rng.choices(self._builders, self._shares)[0]
            out.append(build(self._next, self._rng, self._config))
            self._next += 1
        return out
