"""The least a chip can take for the scan, from shapes alone.

Deciding one pod against N nodes has to read, for every node, what it
has requested and what it can allocate of cpu, memory and pods: six f32,
24 bytes. A pod that carries a required hostname anti-affinity term
reads one more f32 per node, the count of pods its term matches there.
Nothing here looks at how the program lays its tensors out; a program
that reads less than this per pod has changed the algorithm, and the
number is then redefined in a benchmark PR, not bent.

The scan does about as many f32 operations as it reads words, so the
memory side bounds it: least time = bytes / HBM bytes per second."""

import json
import os

BYTES_PER_NODE = 24
BYTES_PER_NODE_PER_ANTI_TERM = 4


def peaks(device_kind):
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json: add it with its source")
    return table[device_kind]


def scan_bytes(pods, nodes, anti_terms_per_pod=0.0):
    return pods * nodes * (BYTES_PER_NODE + BYTES_PER_NODE_PER_ANTI_TERM
                           * anti_terms_per_pod)


def scan_least_seconds(device_kind, pods, nodes, anti_terms_per_pod=0.0):
    return scan_bytes(pods, nodes, anti_terms_per_pod) \
        / peaks(device_kind)["hbm_bytes_per_s"]
