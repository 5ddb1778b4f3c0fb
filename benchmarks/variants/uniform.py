"""Pod variant `uniform`: scheduler_perf's BenchmarkScheduling pod, the
one fixed shape of test/utils/runners.go MakePodSpec (requests and
limits of the configuration's `pod`: 100m CPU, 500Mi) and nothing else."""


def build(i, rng, config):
    size = {"cpu": config["pod"]["cpu"], "memory": config["pod"]["memory"]}
    return {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": f"pod-{i}", "namespace": "default",
                     "labels": {"name": "test", "color": "blue"}},
        "spec": {"containers": [{
            "name": "pause", "image": "k8s.gcr.io/pause:3.1",
            "ports": [{"containerPort": config["pod"]["container_port"]}],
            "resources": {"requests": dict(size), "limits": dict(size)}}]},
    }
