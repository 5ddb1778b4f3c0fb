"""Share of the HBM roofline the scan reaches: the least time the chip
could take for the pods scheduled in the traced slice (harness/roofline.py
over the facts of the run's last pods as the configuration's reference
reads them, from shapes alone) over the device time of the scan programs.
No scan in the slice: nothing to read. A scan and no byte count: a fault
of the harness, raised and never passed over in silence."""

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
    per_pod_node = ctx.get("scan_bytes_per_pod_node")
    if not per_pod_node:
        raise ValueError(f"scan_roofline: {pods} pods scanned in the slice "
                         f"and no scan_bytes_per_pod_node ({per_pod_node!r})")
    least = roofline.scan_least_seconds(
        ctx["device"]["kind"], pods, ctx["nodes"], per_pod_node)
    return 100.0 * least / seconds
