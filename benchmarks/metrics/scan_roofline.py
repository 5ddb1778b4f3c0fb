"""Share of the HBM roofline the scan reaches: the least time the chip
could take for the pods scheduled in the traced slice (harness/roofline.py,
from shapes alone) over the device time of the scan programs."""

import os

from harness import roofline
from harness.cluster import load_module

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(ctx, spec):
    seconds = load_module(os.path.join(
        _HERE, "scan_device_ms_per_pod.py")).scan_seconds(ctx, spec)
    pods = ctx.get("slice_pods_scheduled")
    if seconds is None or not pods:
        return None
    least = roofline.scan_least_seconds(
        ctx["device"]["kind"], pods, ctx["nodes"],
        ctx.get("anti_terms_per_pod", 0.0))
    return 100.0 * least / seconds
