"""A test's node (benchmarks/tests/test_seams.py; no configuration names
it): the plain node with resources reserved for the system, so that
allocatable is under capacity (the configuration's `node` gives both)."""

from harness.cluster import plain_node


def build(i, config):
    node = plain_node(i, config)
    node["status"]["allocatable"].update(config["node"]["allocatable"])
    return node
