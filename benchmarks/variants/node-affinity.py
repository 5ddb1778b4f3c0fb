"""Pod variant `node-affinity`: scheduler_perf's
BenchmarkSchedulingNodeAffinity pod (makeBasePodWithNodeAffinity):
MakePodSpec's one fixed shape (the configuration's `pod`) with a
required node-affinity term, one `In` expression on
failure-domain.beta.kubernetes.io/zone. Upstream lists `zone1, zone2`
on every pod over nodes that all carry `zone1`; here the list is one of
the unordered pairs (zone-a, zone-b), a < b, of the configuration's
`zones`, in lexicographic order of (a, b): pair number
(i + seed) mod `selectors`, cycled by pod index from a start the seed
sets exactly as variants/pod-affinity.py cycles its colours, so any
`selectors` consecutive pods hold every selector under every seed (the
scheduler's mask table and class axis are shaped by the number of
distinct selectors in a batch)."""

from functools import lru_cache
from itertools import combinations

ZONE = "failure-domain.beta.kubernetes.io/zone"


@lru_cache(maxsize=None)
def zone_pairs(zones):
    """((a, b), ...) with a < b, lexicographic: 120 for 16 zones."""
    return tuple(combinations(range(int(zones)), 2))


def build(i, rng, config):
    size = {"cpu": config["pod"]["cpu"], "memory": config["pod"]["memory"]}
    pairs = zone_pairs(config["zones"])
    selectors = int(config["selectors"])
    if not 0 < selectors <= len(pairs):
        raise ValueError(f"selectors {selectors}: {config['zones']} zones "
                         f"have {len(pairs)} pairs")
    a, b = pairs[(i + config["seed"]) % selectors]
    return {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": f"pod-{i}", "namespace": "default",
                     "labels": {"name": "test", "color": "blue"}},
        "spec": {
            "containers": [{
                "name": "pause", "image": "k8s.gcr.io/pause:3.1",
                "ports": [{"containerPort":
                           config["pod"]["container_port"]}],
                "resources": {"requests": dict(size),
                              "limits": dict(size)}}],
            "affinity": {"nodeAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": {
                    "nodeSelectorTerms": [{"matchExpressions": [{
                        "key": ZONE, "operator": "In",
                        "values": [f"zone-{a}", f"zone-{b}"]}]}]}}}},
    }
