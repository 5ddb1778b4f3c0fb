"""A test's pod (benchmarks/tests/test_seams.py; no configuration names
it): the `uniform` pod with required node affinity to two of the
configuration's zones, drawn by the pod's index."""


def build(i, rng, config):
    size = {"cpu": config["pod"]["cpu"], "memory": config["pod"]["memory"]}
    zones = int(config["node"]["zones"])
    return {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": f"pod-{i}", "namespace": "default",
                     "labels": {"name": "test"}},
        "spec": {
            "containers": [{
                "name": "pause", "image": "k8s.gcr.io/pause:3.1",
                "resources": {"requests": dict(size),
                              "limits": dict(size)}}],
            "affinity": {"nodeAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": {
                    "nodeSelectorTerms": [{"matchExpressions": [{
                        "key": "failure-domain.beta.kubernetes.io/zone",
                        "operator": "In",
                        "values": [f"zone-{i % zones}",
                                   f"zone-{(i + 1) % zones}"]}]}]}}}},
    }
