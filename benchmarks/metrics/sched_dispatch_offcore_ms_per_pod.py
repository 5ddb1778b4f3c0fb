"""The reader of sched_offcore_ms_per_pod, on the operations that this
metric's data file names."""

import os

from harness.cluster import load_module

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(ctx, spec):
    return load_module(os.path.join(
        _HERE, "sched_offcore_ms_per_pod.py")).read(ctx, spec)
