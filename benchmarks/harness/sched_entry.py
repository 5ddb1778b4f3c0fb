"""Launcher of the scheduler child: the one process that owns the chip.

    python benchmarks/harness/sched_entry.py <control dir> [--fault NAME]
        -- <kube_scheduler arguments>

It calls kubernetes_tpu.cmd.kube_scheduler.main(argv) unchanged. Beside
it runs one thread that the benchmark's parent talks to through files in
the control directory, because only the process that holds the chip can
trace it or read its compile log:

    <n>.trace_start   start a profiler session
    <n>.mark          read this process's own /metrics inside a span named
                      MARK of the running trace: the answer's "scrape" is
                      then a reading at a time the trace itself knows
    <n>.trace_stop    stop it; the XSpace goes to <control dir>/trace.xplane.pb
    <n>.snapshot      nothing but the answer
    -> <n>.done       {"t": monotonic, "compiles": compile_log().summary(),
                       "memory": device memory_stats of the fullest chip}

--fault plants a fault of the program's timed path for the benchmark's
own tests (benchmarks/tests): the rest of a run must then read
`correct: false`. It is refused unless BENCH_REHEARSAL=1 is set (run.py
sets it for --rehearse only), so no measured run can carry one.
"""

import json
import os
import sys
import threading
import time
import urllib.request

#: the name of the spans that `mark` writes into the trace
MARK = "bench_mark"

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


#: (monotonic time at its end, seconds) of every backend compile or
#: cache load of this process, from JAX's own monitoring events
_COMPILE_EVENTS = []


def _watch_compiles():
    import jax.monitoring as monitoring

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE_EVENTS.append((time.monotonic(), seconds))
    monitoring.register_event_duration_secs_listener(on_duration)


def _answer(ctrl, n, extra=None):
    import jax
    from kubernetes_tpu.scheduler import compile_log
    memory = {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if stats.get("peak_bytes_in_use", 0) >= \
                memory.get("peak_bytes_in_use", -1):
            memory = {k: stats[k] for k in
                      ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")
                      if k in stats}
    out = {"t": time.monotonic(), "compiles": compile_log().summary(),
           "compile_events": list(_COMPILE_EVENTS),
           "memory": memory, **(extra or {})}
    tmp = os.path.join(ctrl, f"{n}.tmp")
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, os.path.join(ctrl, f"{n}.done"))


def _start_trace():
    """Device events and the runtime's own host spans; no per-call Python
    tracing (it slows the scheduler's host loop several-fold and swells
    the trace)."""
    import jax
    from jax._src.lib import _profiler
    jax.devices()           # the backend before the tracer, as start_trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    return _profiler.ProfilerSession(options)


def _mark(metrics_url):
    import jax
    with jax.profiler.TraceAnnotation(MARK):
        with urllib.request.urlopen(metrics_url, timeout=30) as r:
            return r.read().decode()


def _serve(ctrl, metrics_url):
    seen = set()
    session = None
    while True:
        for name in sorted(os.listdir(ctrl)):
            n, _, verb = name.partition(".")
            if name in seen or verb not in ("trace_start", "trace_stop",
                                            "mark", "snapshot"):
                continue
            seen.add(name)
            extra = {}
            try:
                if verb == "trace_start":
                    session = _start_trace()
                elif verb == "mark":
                    extra["scrape"] = _mark(metrics_url)
                elif verb == "trace_stop":
                    t0 = time.monotonic()
                    extra["stop_requested_t"] = t0
                    # the collected XSpace, written as it is: the public
                    # stop_trace() also converts it to trace.json.gz,
                    # which held this process for 24 s on a 3 s slice
                    data = session.stop()
                    with open(os.path.join(ctrl, "trace.xplane.pb"),
                              "wb") as f:
                        f.write(data)
                    extra["stop_seconds"] = time.monotonic() - t0
                    extra["trace_bytes"] = len(data)
            except Exception as e:  # the parent fails the run on this
                extra["error"] = repr(e)
            _answer(ctrl, n, extra)
        time.sleep(0.02)


# ------------------------------------------------------------- faults
#
# Each breaks the timed path where it produces its answer; none touches
# the benchmark's side of the comparison.

def _fault_alter_answer():
    """Every 7th bind of a batch goes to the node chosen for its
    neighbour: an answer altered where it is produced."""
    from kubernetes_tpu.scheduler.scheduler import Scheduler
    inner = Scheduler._bind_items_inner

    def altered(self, items, backoff):
        items = list(items)
        for i in range(6, len(items), 7):
            ns, name, _ = items[i]
            items[i] = (ns, name, items[i - 1][2])
        return inner(self, items, backoff)
    Scheduler._bind_items_inner = altered


def _fault_drop_half():
    """Once 1,000 pods are bound, half of every batch is decided and then
    left out of the bind: those pods never get a node."""
    from kubernetes_tpu.scheduler.scheduler import Scheduler
    inner = Scheduler._bind_items_inner
    seen = [0]

    def halved(self, items, backoff):
        items = list(items)
        seen[0] += len(items)
        if seen[0] <= 1000:
            return inner(self, items, backoff)
        full = [True] * len(items)
        full[::2] = inner(self, items[::2], backoff)
        return full
    Scheduler._bind_items_inner = halved


def _fault_stale_state():
    """The scan decides every batch against the cluster as it was before
    the batch before it: a step that returns its state unchanged."""
    from kubernetes_tpu.scheduler.scheduler import Scheduler
    Scheduler._tracked_assume = lambda self, pod: None


FAULTS = {"alter_answer": _fault_alter_answer, "drop_half": _fault_drop_half,
          "stale_state": _fault_stale_state}


def main():
    args = sys.argv[1:]
    split = args.index("--")
    mine, argv = args[:split], args[split + 1:]
    ctrl = mine[0]
    sys.path.insert(0, REPO)
    if "--fault" in mine:
        if os.environ.get("BENCH_REHEARSAL") != "1":
            raise SystemExit("--fault is for the benchmark's tests only")
        FAULTS[mine[mine.index("--fault") + 1]]()
    _watch_compiles()
    port = argv[argv.index("--healthz-port") + 1]
    threading.Thread(target=_serve, daemon=True, args=(
        ctrl, f"http://127.0.0.1:{port}/metrics")).start()
    from kubernetes_tpu.cmd.kube_scheduler import main as scheduler_main
    return scheduler_main(argv)


if __name__ == "__main__":
    sys.exit(main())
