"""Serving mode — open-loop churn with latency SLOs over the live
control plane.

    LoadGen        loadgen.py  — seeded Poisson arrivals of mixed
                                 workload classes (deployments scaling,
                                 jobs, cronjob firings, gangs, singletons)
    SLOTracker     slo.py      — created→bound→running stamps, exact
                                 per-class p50/p95/p99 + sustained pods/s
    ServingHarness harness.py  — the FakeClock-deterministic (or chaotic)
                                 control-plane driver tying them together

The scheduler-side half of serving mode lives in scheduler/scheduler.py
(adaptive drain batch sizing, priority lanes, hub backpressure —
`adaptive_batch=True`) and scheduler/queue.py (lane census).
"""

from .loadgen import ArrivalEvent, CLASS_LABEL, DEFAULT_MIX, LoadGen
from .slo import BIND, STARTUP, SLOTracker, percentile
from .harness import ServingHarness, ServingReport

__all__ = ["ArrivalEvent", "CLASS_LABEL", "DEFAULT_MIX", "LoadGen",
           "BIND", "STARTUP", "SLOTracker", "percentile",
           "ServingHarness", "ServingReport"]
