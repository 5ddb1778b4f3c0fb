"""CPU of the scheduler's threads of the roles that the metric's data
file names (`roles`), per pod scheduled: the growth over the window of
`scheduler_thread_cpu_seconds{role}` (kubernetes_tpu/scheduler/metrics.py,
read off each live thread's CPU clock at the scrape), summed over the
roles, x 1000 / the growth of the pods scheduled. None where a role's
series is absent (a program without the gauge) or no pod was
scheduled."""

import os

from harness.cluster import load_module

_HERE = os.path.dirname(os.path.abspath(__file__))
ROLE = 'scheduler_thread_cpu_seconds{role="%s"}'


def read(ctx, spec):
    offcore = load_module(os.path.join(_HERE, "sched_offcore_ms_per_pod.py"))
    process = spec["process"]
    pods = offcore.growth(ctx, process, offcore.SCHEDULED)
    cpu = 0.0
    for role in spec["roles"]:
        grown = offcore.growth(ctx, process, ROLE % role)
        if grown is None:
            return None
        cpu += grown
    if not pods:
        return None
    return 1000.0 * cpu / pods
