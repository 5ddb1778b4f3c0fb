"""Device time of the scan programs per pod scheduled in the traced
slice. The programs are picked by the regular expression in the
metric's data file, against the module names of the device trace."""

import re


def scan_seconds(ctx, spec):
    trace = ctx.get("trace")
    if not trace:
        return None
    pattern = re.compile(spec["programs"])
    hits = [p["seconds"] for name, p in trace["programs"].items()
            if pattern.search(name)]
    total = sum(hits)
    return total if hits and total > 0 else None


def read(ctx, spec):
    seconds = scan_seconds(ctx, spec)
    pods = ctx.get("slice_pods_scheduled")
    if seconds is None or not pods:
        return None
    return 1000.0 * seconds / pods
