"""Pod variant `pod-affinity`: scheduler_perf's
BenchmarkSchedulingPodAffinity pod (makeBasePodWithPodAffinity):
MakePodSpec's one fixed shape (the configuration's `pod`) with a label
`color` and a required affinity term on
failure-domain.beta.kubernetes.io/zone selecting that same label.
Upstream gives every test pod one label value; the configuration's
`colours` says how many there are here. They are cycled by pod index
from a start the seed sets, exactly as variants/pod-anti-affinity.py
cycles its own, so any run of consecutive pods holds as many colours
under every seed (the scheduler's programs are shaped by the number of
distinct terms in a batch)."""


def build(i, rng, config):
    size = {"cpu": config["pod"]["cpu"], "memory": config["pod"]["memory"]}
    colour = f"c{(i + config['seed']) % int(config['colours'])}"
    return {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": f"pod-{i}", "namespace": "default",
                     "labels": {"name": "test", "color": colour}},
        "spec": {
            "containers": [{
                "name": "pause", "image": "k8s.gcr.io/pause:3.1",
                "ports": [{"containerPort":
                           config["pod"]["container_port"]}],
                "resources": {"requests": dict(size),
                              "limits": dict(size)}}],
            "affinity": {"podAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [{
                    "labelSelector": {"matchLabels": {"color": colour}},
                    "topologyKey":
                        "failure-domain.beta.kubernetes.io/zone"}]}}},
    }
