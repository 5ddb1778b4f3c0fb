"""Node variant `dedicated-pools`: the Kubernetes documentation's
"Dedicated Nodes" recipe (Taints and Tolerations) over scheduler_perf's
fake node, with the cluster autoscaler's soft taint on its scale-down
candidates.

node-i is harness/cluster.py plain_node (its shape, hostname and zone
labels) in pool k = i mod `pools`: the label `dedicated=pool-<k>` and the
taint `dedicated=pool-<k>:NoSchedule`, so that only the pool's own pods
fit there and those pods, by their required node affinity on the label,
fit nowhere else. Every node with (i // pools) % 10 == 0, the first of
each ten of a pool's nodes, also carries
`DeletionCandidateOfClusterAutoscaler=<SOFT_TAINT_SINCE>:PreferNoSchedule`,
the taint the autoscaler puts on a node it may scale down, so that the
scheduler steers new pods off it (TaintTolerationPriority). Which nodes
are chosen by index, never by the seed: the seed orders the creates and
never changes the amount of work."""

from harness.cluster import plain_node

POOL = "dedicated"
SOFT_TAINT = "DeletionCandidateOfClusterAutoscaler"
#: the taint's value, the unix time at which the autoscaler marked the
#: node; fixed, so that every seed gives the same nodes
SOFT_TAINT_SINCE = "1571000000"


def soft_tainted(i, pools):
    """node-i is one of its pool's scale-down candidates."""
    return (i // pools) % 10 == 0


def build(i, config):
    pools = int(config["pools"])
    node = plain_node(i, config)
    pool = f"pool-{i % pools}"
    node["metadata"]["labels"][POOL] = pool
    taints = [{"key": POOL, "value": pool, "effect": "NoSchedule"}]
    if soft_tainted(i, pools):
        taints.append({"key": SOFT_TAINT, "value": SOFT_TAINT_SINCE,
                       "effect": "PreferNoSchedule"})
    node["spec"] = {"taints": taints}
    return node
