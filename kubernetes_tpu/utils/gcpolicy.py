"""The garbage collector's policy in a process that holds the cluster state.

CPython runs a full (generation-2) collection whenever the objects that
survived into the oldest generation have grown by a quarter, and each full
collection walks every tracked object. The live set of a hub or of a
scheduler only grows within a window of work. In the hub every stored pod
is an object tree, and `serde.encode_cached` hangs a dict tree beside it.
In the scheduler the informer store holds every pod decoded from its
watch frame with its precomputed features, and the cache keeps an
assumed clone of each until the informer confirms the bind. So each full
collection re-walks every pod seen so far and frees next to nothing,
stopping every thread of the process inside whatever section the
allocating thread holds: the hub's create gate, the scheduler's cycle,
its binders and its informers.

`install()` freezes what is alive (`gc.freeze()`), and on the stop of every
generation-2 collection it freezes that collection's survivors. Right
after a full collection the young generations are empty, so only reachable
objects are frozen, never garbage, and each object is walked by at most
one full collection after it reaches the oldest generation. Generations 0
and 1 run as before, and so do full collections over what is not frozen
yet: cyclic garbage is still collected, but for a cycle that was alive at
a full collection and died after it. Frozen objects are still freed by
their reference counts.

The callback takes no lock: a collection can start inside any
allocation, a metric's locked section included. It adds to plain numbers,
and the callback series of `CollectorMetrics` read them at the scrape.

Two entry points install it, once each: `cmd/kube_apiserver` (series
`apiserver_gc_*`) and `cmd/kube_scheduler` (series `scheduler_gc_*` on the
scheduler's registry). In-process hubs and schedulers keep the
interpreter's collector: those of the tests, of `fakecluster.py`,
`chip_smoke.py` and the chaos and serving harnesses.
"""

from __future__ import annotations

import gc
import time
from typing import Optional

from .metrics import Registry

GENERATIONS = (0, 1, 2)


class CollectorPolicy:
    """The callback and the plain numbers it keeps."""

    def __init__(self):
        #: collections and their seconds by generation, since install
        self.collections = [0, 0, 0]
        self.pause_s = [0.0, 0.0, 0.0]
        #: generation-2 collections whose survivors were frozen
        self.freezes = 0
        self._started = 0.0

    def on_collection(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._started = time.perf_counter()
            return
        g = info["generation"]
        self.collections[g] += 1
        self.pause_s[g] += time.perf_counter() - self._started
        if g == 2:
            gc.freeze()
            self.freezes += 1


class CollectorMetrics:
    """The collector's series on the process's /metrics: callback series
    over the policy's plain numbers, every generation declared from
    install. `prefix` names the series (`<prefix>_gc_...`), `process` the
    process in their help text."""

    def __init__(self, policy: CollectorPolicy,
                 registry: Optional[Registry] = None,
                 prefix: str = "apiserver", process: str = "hub"):
        self.policy = policy
        self.registry = registry if registry is not None else Registry()
        r = self.registry

        def by_generation(values):
            return lambda: {(("generation", str(g)),): values[g]
                            for g in GENERATIONS}
        self.collections = r.counter(
            f"{prefix}_gc_collections_total",
            f"Garbage collections of the {process} process, by generation",
            fn=by_generation(policy.collections))
        self.pause = r.counter(
            f"{prefix}_gc_pause_seconds_total",
            f"Seconds the {process} process spent in garbage collections, "
            "by generation",
            fn=by_generation(policy.pause_s))
        self.freezes = r.counter(
            f"{prefix}_gc_freezes_total",
            "Generation-2 collections whose survivors were frozen",
            fn=lambda: policy.freezes)
        self.frozen = r.gauge(
            f"{prefix}_gc_frozen_objects",
            "Objects in the collector's permanent generation",
            fn=gc.get_freeze_count)


def install(prefix: str = "apiserver", process: str = "hub",
            registry: Optional[Registry] = None) -> CollectorMetrics:
    """Install the policy in this process: declare its series (on
    `registry`, else a registry of their own), register the callback, then
    run one full collection, whose stop freezes every object alive now.
    Call it once, from the entry point, after the process has built its
    state (the hub's store has replayed its WAL, the scheduler is built)
    and before it serves or schedules."""
    policy = CollectorPolicy()
    metrics = CollectorMetrics(policy, registry, prefix, process)
    gc.callbacks.append(policy.on_collection)
    gc.collect()
    return metrics
