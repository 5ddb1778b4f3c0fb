"""From a profiler trace (.xplane.pb) to numbers. Read with
jax.profiler.ProfileData, which needs JAX but no device: the parent
calls this only after both children have exited.

A device plane is one named /device:TPU:<n>. On it the line "XLA
Modules" holds one event per run of a compiled program (named
jit_<function>(<fingerprint>)), and "XLA Ops" the operations inside
them. Busy time is the union of the intervals of the "XLA Ops" events
(of the modules where a plane has no such line), averaged over the
device planes. The traced window is the span between two marks: host
events of a given name that the launcher wrote into the trace while it
read the scheduler's counters (sched_entry.py `mark`), each taken at its
middle. Every event is cut to that window, so the device seconds and the
pods they are divided by cover the same interval, and what the profiler
records after the load has stopped is left out. Without a mark name the
window is the span from the first to the last event of any plane, host
threads included (the self-check's recorded trace).

    python benchmarks/harness/trace_reduce.py <trace dir or .xplane.pb>
prints the planes, lines and heaviest events: look before you reduce.
"""

import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(path):
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def union_ns(intervals):
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def op_name(event_name):
    """'%fusion.3 = f32[...] fusion(...)' -> 'fusion.3'"""
    return event_name.split(" = ", 1)[0].lstrip("%")[:120]


def gaps_by_host_activity(busy, host_spans, lo, hi, top):
    """The device's idle time inside [lo, hi] by what the host was doing
    in it: each idle gap is shared out among the runtime's own host spans
    that overlap it (transfers, dispatch, np.asarray of a result; nested
    spans each count), and what no span covers goes to the program's
    Python outside the runtime (informers, queue, tensorize, commit,
    waiting for pods), which the trace cannot split further."""
    gaps, cur = [], lo
    for s, e in sorted(busy):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    host_spans = sorted(host_spans)
    outside = "host outside the runtime's spans"
    totals = {outside: 0.0}
    first = 0
    for gs, ge in gaps:
        while first < len(host_spans) and host_spans[first][1] <= gs \
                and host_spans[first][0] <= gs:
            first += 1
        covered = []
        for s, e, name in host_spans[first:]:
            if s >= ge:
                break
            overlap = min(e, ge) - max(s, gs)
            if overlap > 0:
                totals[name] = totals.get(name, 0.0) + overlap
                covered.append((max(s, gs), min(e, ge)))
        totals[outside] += (ge - gs) - union_ns(covered)
    return [[n, t / 1e9] for n, t in sorted(
        totals.items(), key=lambda kv: -kv[1])[:top]]


def program_name(event_name):
    """jit_foo(123456) -> jit_foo"""
    return event_name.split("(", 1)[0]


def reduce(path, top=10, mark=None, clip=None):
    """None when the trace holds no device plane (a CPU rehearsal).
    `mark`: cut to the window between the first two host events of that
    name (an error if there are fewer); `clip`: (lo_ns, hi_ns) given."""
    xplane = find_xplane(path)
    if xplane is None:
        return None
    data = load(xplane)
    lo, hi = None, None
    device_lines, host_spans, marks = [], [], []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        lines = {}
        for line in plane.lines:
            spans = []
            for ev in line.events:
                s = ev.start_ns
                e = s + ev.duration_ns
                lo = s if lo is None or s < lo else lo
                hi = e if hi is None or e > hi else hi
                if is_device:
                    spans.append((s, e, ev.name))
                elif plane.name == "/host:CPU":
                    if mark is not None and ev.name == mark:
                        marks.append((s, e))
                    elif e > s:
                        host_spans.append((s, e, ev.name[:120]))
            if is_device:
                lines[line.name] = spans
        if is_device:
            device_lines.append(lines)
    if not device_lines or lo is None:
        return None
    mark_spans = None
    if mark is not None:
        marks.sort()
        if len(marks) < 2:
            raise ValueError(f"{len(marks)} events named {mark!r} in "
                             f"{xplane}: the traced window has no edges")
        clip = ((marks[0][0] + marks[0][1]) / 2,
                (marks[1][0] + marks[1][1]) / 2)
        mark_spans = [(e - s) / 1e9 for s, e in marks[:2]]
    if clip is not None:
        lo, hi = clip

    def cut(spans):
        return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in spans
                if min(e, hi) > max(s, lo)]

    host_spans = cut(host_spans)
    per_device, busy_spans = [], []
    ops_total, modules = {}, {}
    for lines in device_lines:
        lines = {name: cut(spans) for name, spans in lines.items()}
        busy_line = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        per_device.append(union_ns((s, e) for s, e, _ in busy_line))
        if not busy_spans:
            busy_spans = [(s, e) for s, e, _ in busy_line]
        for s, e, name in lines.get(OPS_LINE, []):
            name = op_name(name)
            ops_total[name] = ops_total.get(name, 0.0) + (e - s)
        for s, e, name in lines.get(MODULES_LINE, []):
            rec = modules.setdefault(program_name(name), [0, 0.0])
            rec[0] += 1
            rec[1] += e - s
    return {
        "xplane": xplane,
        "window_s": (hi - lo) / 1e9,
        "mark_spans_s": mark_spans,
        "busy_s": sum(per_device) / len(per_device) / 1e9,
        "devices": len(per_device),
        "device_ops": [[n, t / 1e9] for n, t in sorted(
            ops_total.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": gaps_by_host_activity(busy_spans, host_spans, lo, hi,
                                           top),
        "programs": {n: {"runs": c, "seconds": t / 1e9 / len(per_device)}
                     for n, (c, t) in modules.items()},
    }


def dump(path):
    xplane = find_xplane(path)
    data = load(xplane)
    print("file", xplane, os.path.getsize(xplane), "bytes")
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            tot = {}
            n = 0
            for ev in line.events:
                n += 1
                rec = tot.setdefault(ev.name, [0, 0.0])
                rec[0] += 1
                rec[1] += ev.duration_ns
            heavy = sorted(tot.items(), key=lambda kv: -kv[1][1])[:8]
            print("  LINE", repr(line.name), n, "events;", [
                (k[:70], c, round(t / 1e6, 3)) for k, (c, t) in heavy])


if __name__ == "__main__":
    dump(sys.argv[1])
    print(reduce(sys.argv[1]))
