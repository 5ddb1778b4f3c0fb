"""The least a chip can take for the scan, from shapes alone.

Deciding one pod against N nodes has to read so many bytes for every
node: what the node has requested and what it can allocate of cpu,
memory and pods, six f32, 24 bytes; for each required hostname
anti-affinity term of the pod one more f32, the count of pods the term
matches there; and one f32 for each further word that the
configuration's reference says its own predicates and priorities need
a node (`extra_words` on its PodFacts; 0 in harness/reference.py). The
byte model is this file's alone: a reference states counts of words,
never bytes or seconds. Nothing here looks at how the program lays its
tensors out; a program that reads less than this per pod has changed
the algorithm, and the number is then redefined in a benchmark PR, not
bent.

The scan does about as many f32 operations as it reads words, so the
memory side bounds it: least time = bytes / HBM bytes per second."""

import json
import os


def peaks(device_kind):
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json: add it with its source")
    return table[device_kind]


BYTES_PER_NODE = 24
BYTES_PER_WORD = 4


def scan_bytes_per_node(facts):
    """The least bytes a node to decide the pod with these facts (the
    PodFacts of the configuration's reference)."""
    return BYTES_PER_NODE + BYTES_PER_WORD * (
        len(facts.anti) + facts.extra_words)


def scan_bytes(pods, nodes, bytes_per_pod_node):
    return pods * nodes * bytes_per_pod_node


def scan_least_seconds(device_kind, pods, nodes, bytes_per_pod_node):
    return scan_bytes(pods, nodes, bytes_per_pod_node) \
        / peaks(device_kind)["hbm_bytes_per_s"]
