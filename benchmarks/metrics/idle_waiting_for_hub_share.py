"""The reader of idle_waiting_for_pods_share, on the span that this
metric's data file names."""

import os

from harness.cluster import load_module

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(ctx, spec):
    return load_module(os.path.join(
        _HERE, "idle_waiting_for_pods_share.py")).read(ctx, spec)
