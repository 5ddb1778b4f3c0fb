"""Pod variant `service-member`: the pod of upstream's scalability e2e
Load test (test/e2e/scalability/load.go), one replica of one of the
groups that `computePodCounts` cuts a namespace's pods into, behind the
Service that `generateServicesForConfigs` makes for its group:
the configuration's one pod shape with `labels {name: <group>}`, which is
the group's ReplicationController selector and its Service's selector.

The stream, from the configuration's `groups` (`block_pods` pods a block,
which is the source's namespace of 100 nodes, and the block's `sizes`:
how many groups of how many pods):

  pod i belongs to block i // block_pods;
  a block's groups come in an order drawn from --seed (one shuffle a
  block, `random.Random(f"{seed}/{block}")`), the pods of a group
  consecutive, as a controller's burst is;
  group k of size class c in block b is `load-<c>-<b * count(c) + k>`:
  the names are unique across blocks.

Every seed gives the same groups in another order, never another amount
of work. Past the last pod (`pods`: blocks x block_pods) the
configuration has no pod: `build` raises, it never wraps.

`service_manifests(config)` is the rule the configuration's
`setup_objects` were written out from (one Service a group, name
`<group>-svc`, selector `{name: <group>}`, port 80 -> 80), in block
order and then size-class order: tests/test_selectorspread_config.py
regenerates the list and compares."""

import random
from bisect import bisect_right
from functools import lru_cache


def block_groups(groups, block):
    """[(group name, pods)] of one block, in size-class order."""
    out = []
    for size in groups["sizes"]:
        first = block * int(size["count"])
        out += [(f"load-{size['name']}-{first + k}", int(size["pods"]))
                for k in range(int(size["count"]))]
    return out


def all_groups(config):
    """[(group name, pods)] of the whole configuration, block by block."""
    groups = config["groups"]
    blocks, rest = divmod(int(config["pods"]), int(groups["block_pods"]))
    if rest:
        raise ValueError(f"pods {config['pods']} are no whole number of "
                         f"blocks of {groups['block_pods']}")
    return [g for b in range(blocks) for g in block_groups(groups, b)]


def service_manifests(config):
    """The configuration's Services, one a group, as `setup_objects`
    entries: posted before the first pod, as load.go creates them."""
    return [{"path": "/api/v1/namespaces/default/services", "manifest": {
        "apiVersion": "v1", "kind": "Service",
        "metadata": {"name": f"{name}-svc", "namespace": "default"},
        "spec": {"selector": {"name": name},
                 "ports": [{"port": 80, "targetPort": 80}]}}}
        for name, _ in all_groups(config)]


@lru_cache(maxsize=64)
def _block_order(seed, block, block_pods, sizes):
    """(first pod offset of each group, group names) of one block in its
    seeded order. `sizes` is ((name, pods, count), ...): hashable."""
    groups = {"sizes": [{"name": n, "pods": p, "count": c}
                        for n, p, c in sizes]}
    order = block_groups(groups, block)
    if sum(p for _, p in order) != block_pods:
        raise ValueError(f"the groups of a block hold "
                         f"{sum(p for _, p in order)} pods, not {block_pods}")
    random.Random(f"{seed}/{block}").shuffle(order)
    starts, at = [], 0
    for _, pods in order:
        starts.append(at)
        at += pods
    return starts, [name for name, _ in order]


def group_of(i, config):
    """The group of pod i under the configuration's seed."""
    if not 0 <= i < int(config["pods"]):
        raise IndexError(f"pod {i}: the configuration has "
                         f"{config['pods']} pods and does not wrap")
    groups = config["groups"]
    block_pods = int(groups["block_pods"])
    block, offset = divmod(i, block_pods)
    starts, names = _block_order(
        config["seed"], block, block_pods,
        tuple((s["name"], int(s["pods"]), int(s["count"]))
              for s in groups["sizes"]))
    return names[bisect_right(starts, offset) - 1]


def build(i, rng, config):
    size = {"cpu": config["pod"]["cpu"], "memory": config["pod"]["memory"]}
    return {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": f"pod-{i}", "namespace": "default",
                     "labels": {"name": group_of(i, config)}},
        "spec": {"containers": [{
            "name": "pause", "image": "k8s.gcr.io/pause:3.1",
            "ports": [{"containerPort": config["pod"]["container_port"]}],
            "resources": {"requests": dict(size), "limits": dict(size)}}]},
    }
