"""TPU-native scheduler.

The reference's kube-scheduler (pkg/scheduler, 17.7k LoC) schedules ONE pod
per iteration: scheduleOne -> findNodesThatFit -> PrioritizeNodes -> bind, with
16-way goroutine fan-out inside each phase (core/generic_scheduler.go:518,725).

This package replaces that with a batched TPU design:
  - the scheduler cache mirrors cluster state into dense host tensors with
    generation-based O(delta) incremental updates (cache.py, tensorize.py)
  - Filter becomes a pods x nodes feasibility mask and Score a pods x nodes
    score matrix, computed by jax kernels in one shot (kernels/batch.py)
  - host-side assignment binds a whole batch while preserving the reference's
    serial decision semantics (core.py); an on-device lax.scan assignment
    kernel removes the host loop entirely (kernels/batch.py)

Python implementations of every predicate/priority (predicates.py,
priorities.py) are the semantic source of truth the kernels are parity-tested
against, and serve preemption's host-side victim search.
"""

import os

from .cache import Cache, Snapshot
from .core import BatchScheduler, FitError, ScheduleResult
from .gang import GangManager
from .nodeinfo import NodeInfo, Resource
from .queue import SchedulingQueue
from .scheduler import Scheduler

__all__ = ["BatchScheduler", "Cache", "FitError", "GangManager", "NodeInfo",
           "Resource", "ScheduleResult", "Scheduler", "SchedulingQueue",
           "Snapshot", "compile_log", "device_report",
           "enable_compile_cache"]

#: where compiled programs persist when JAX_COMPILATION_CACHE_DIR is unset:
#: a fixed path under the checkout (the path is part of the cache key, so
#: a directory that moves — tempfile, pid, timestamp — never hits)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process; the
    entry points (cmd/kube_scheduler, chip_smoke.py) call it
    before their first jit. The drain compiles one program per
    power-of-two pod bucket x kernel variant, and every start would
    otherwise pay all of them again. JAX_COMPILATION_CACHE_DIR, when set,
    is the ONLY setting (JAX reads it itself; no directory is set here);
    unset, the cache goes to DEFAULT_COMPILE_CACHE_DIR. The thresholds
    drop to zero so the sub-second programs (dirty-row scatters, result
    packing — a dozen buckets each) are written too. Returns the
    directory in effect."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


class CompileLog:
    """Counts what this process compiles, from JAX's own monitoring
    events: `programs` executables built or loaded, `cache_hits` of them
    read from the persistent cache, `cache_misses` compiled and written,
    `seconds` spent in either. A program built inside a timed window is a
    stall the warm-up missed; hits on a second process show the cache
    placed where enable_compile_cache says."""

    def __init__(self):
        self.programs = self.cache_hits = self.cache_misses = 0
        self.seconds = 0.0
        import jax.monitoring as monitoring
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += seconds

    def summary(self) -> dict:
        return {"programs": self.programs, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "seconds": round(self.seconds, 2)}


_COMPILE_LOG = None


def compile_log() -> CompileLog:
    """The process-wide CompileLog (JAX's listeners are process-global,
    so one instance is registered, on first use)."""
    global _COMPILE_LOG
    if _COMPILE_LOG is None:
        _COMPILE_LOG = CompileLog()
    return _COMPILE_LOG


def device_report() -> dict:
    """{"platform", "kind", "count"} as JAX reports the backend this
    process computes on — printed by every entry point so no result is
    read without the device that produced it. Initialises the backend,
    and raises if it cannot."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
