"""Pod variant `preferred-anti-affinity`: upstream scheduler_perf's
SchedulingPreferredPodAntiAffinity pod (the pod template
config/pod-with-preferred-pod-anti-affinity.yaml of the later
performance-config.yaml): MakePodSpec's one fixed shape (the
configuration's `pod`) with the labels `name: test, color: green` and
one preferred anti-affinity term of weight 1 on kubernetes.io/hostname
that selects `color: green`. Every pod carries that label, so every pod
repels every other, softly, per host: one colour, as upstream writes
it. No required term and no colour cycle; `rng` draws nothing, so the
seed sets only the order the nodes are created in."""


def build(i, rng, config):
    size = {"cpu": config["pod"]["cpu"], "memory": config["pod"]["memory"]}
    return {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": f"pod-{i}", "namespace": "default",
                     "labels": {"name": "test", "color": "green"}},
        "spec": {
            "containers": [{
                "name": "pause", "image": "k8s.gcr.io/pause:3.1",
                "ports": [{"containerPort":
                           config["pod"]["container_port"]}],
                "resources": {"requests": dict(size),
                              "limits": dict(size)}}],
            "affinity": {"podAntiAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [{
                    "weight": 1,
                    "podAffinityTerm": {
                        "labelSelector": {"matchLabels": {
                            "color": "green"}},
                        "topologyKey": "kubernetes.io/hostname"}}]}}},
    }
