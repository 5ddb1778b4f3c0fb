"""The reader of the three off-core metrics: the time a stage of the
cycle spent off the core, on the thread that entered it, per pod
scheduled. For the operations that the metric's data file names
(`operations`), the growth over the window of the stage's wall
(`scheduler_scheduling_duration_seconds_sum`) minus that of its thread's
CPU clock (`scheduler_scheduling_cpu_seconds_total`, read on entry and
exit by kubernetes_tpu/observability/tracer.py SpanTracer.stage), summed,
x 1000 / the growth of the pods scheduled. For a compute stage that is
the wait to take the interpreter lock back and any call that blocks.
None where a series is absent (a program without the CPU counter) or no
pod was scheduled."""

SCHEDULED = 'scheduler_schedule_attempts_total{result="scheduled"}'
WALL = 'scheduler_scheduling_duration_seconds_sum{operation="%s"}'
CPU = 'scheduler_scheduling_cpu_seconds_total{operation="%s"}'


def growth(ctx, process, key):
    a = ctx["probe0"]["scrape"][process].get(key)
    b = ctx["probe1"]["scrape"][process].get(key)
    return None if a is None or b is None else b - a


def read(ctx, spec):
    process = spec["process"]
    pods = growth(ctx, process, SCHEDULED)
    off = 0.0
    for op in spec["operations"]:
        wall = growth(ctx, process, WALL % op)
        cpu = growth(ctx, process, CPU % op)
        if wall is None or cpu is None:
            return None
        off += wall - cpu
    if not pods:
        return None
    return 1000.0 * off / pods
