"""Pod variant `dedicated-pool`: a team's pod under the Kubernetes
documentation's "Dedicated Nodes" recipe (Taints and Tolerations):
MakePodSpec's one fixed shape (the configuration's `pod`) with a
toleration of its pool's taint `dedicated=pool-<k>:NoSchedule` and a
required node affinity, one `In` expression on the `dedicated` label
with the one value `pool-<k>`, so that it may use its pool's nodes and
only those. Pool k = (i + seed) mod `pools`, cycled by pod index from a
start the seed sets exactly as variants/node-affinity.py cycles its
pairs, so any `pools` consecutive pods hold every pool under every seed.
It tolerates nothing else: the autoscaler's PreferNoSchedule taint on a
pool's scale-down candidates counts against every pod."""

POOL = "dedicated"


def build(i, rng, config):
    size = {"cpu": config["pod"]["cpu"], "memory": config["pod"]["memory"]}
    pool = f"pool-{(i + config['seed']) % int(config['pools'])}"
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
            "tolerations": [{"key": POOL, "operator": "Equal",
                             "value": pool, "effect": "NoSchedule"}],
            "affinity": {"nodeAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": {
                    "nodeSelectorTerms": [{"matchExpressions": [{
                        "key": POOL, "operator": "In",
                        "values": [pool]}]}]}}}},
    }
