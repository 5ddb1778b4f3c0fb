"""Cached node vectors dropped per scheduling cycle: the growth over the
window of `scheduler_node_vector_evictions_total{cache}`
(kubernetes_tpu/scheduler/metrics.py, counted where
tensorize.NodeVectorCache drops a key), summed over the caches that the
metric's data file names (`caches`), / the growth of the scheduling
cycles. None where a cache's series is absent (a program without the
counter) or no cycle ran."""

import os

from harness.cluster import load_module

_HERE = os.path.dirname(os.path.abspath(__file__))
EVICTIONS = 'scheduler_node_vector_evictions_total{cache="%s"}'
CYCLES = "scheduler_e2e_scheduling_duration_seconds_count"


def read(ctx, spec):
    offcore = load_module(os.path.join(_HERE, "sched_offcore_ms_per_pod.py"))
    process = spec["process"]
    cycles = offcore.growth(ctx, process, CYCLES)
    dropped = 0.0
    for cache in spec["caches"]:
        grown = offcore.growth(ctx, process, EVICTIONS % cache)
        if grown is None:
            return None
        dropped += grown
    if not cycles:
        return None
    return dropped / cycles
