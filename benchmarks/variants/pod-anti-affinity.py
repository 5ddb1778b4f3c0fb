"""Pod variant `pod-anti-affinity`: scheduler_perf's
BenchmarkSchedulingPodAntiAffinity pod (makeBasePodWithPodAntiAffinity):
MakePodSpec's one fixed shape (the configuration's `pod`) with a
required anti-affinity term on kubernetes.io/hostname against the pod's
own `color`. Upstream gives every test pod the one colour `green`; the
configuration's `colours` says how many there are here. They are cycled
by pod index from a start the seed sets, so any run of consecutive pods
holds as many colours under every seed (the scheduler's programs are
shaped by the number of distinct terms in a batch)."""


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
            "affinity": {"podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [{
                    "labelSelector": {"matchLabels": {"color": colour}},
                    "topologyKey": "kubernetes.io/hostname"}]}}},
    }
