"""1 - busy / traced slice, from the reduced trace (harness/trace_reduce)."""


def read(ctx, spec):
    trace = ctx.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
