"""Share of the traced slice in which the device was idle while a host
span of the name in the metric's data file (`span`) was open: the reduced
trace's idle gaps by host activity (harness/trace_reduce), over the slice.
The program's own spans are jax.profiler.TraceAnnotations on the trace's
clock (kubernetes_tpu/observability/tracer.py, SpanTracer.stage). Spans of
different threads may cover the same gap, so such shares need not sum to
100. The reduction the harness keeps lists the ten largest gaps only;
where the span is not among them, the trace is reduced once more with
every gap listed (kept on the reduced trace for the next reader), so a
span the program writes always reads a number, however small. None
without a device trace, and where the trace holds no span of the name (a
program without the stages)."""

from harness import trace_reduce
from harness.sched_entry import MARK


def every_gap(trace):
    if "every_idle_gap" not in trace:
        full = trace_reduce.reduce(trace["xplane"], top=None, mark=MARK)
        trace["every_idle_gap"] = full["idle_gaps"]
    return trace["every_idle_gap"]


def read(ctx, spec):
    trace = ctx.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    gaps = dict(trace["idle_gaps"])
    if spec["span"] not in gaps:
        gaps = dict(every_gap(trace))
    if spec["span"] not in gaps:
        return None
    return 100.0 * gaps[spec["span"]] / trace["window_s"]
