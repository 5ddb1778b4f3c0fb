#!/usr/bin/env python3
"""The benchmark's command: one process tree, one cell, one run.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
                              --trace <0|1> [--rehearse]

Everything that belongs to one cell, configuration, pod variant, traffic
mix or metric is a file found by the name BENCHMARK.json gives it (see
benchmarks/README.md); this file holds none of those names.

A run: start a real kube_apiserver (JAX_PLATFORMS=cpu, so it never
takes the chip; no CPU affinity is set; WAL and validation on); create
the configuration's nodes and its set-up objects; start the scheduler
through harness/sched_entry.py, the one owner of the chip; create the
existing pods and the mix's warm bursts; ramp the mix's loop; measure
for --seconds on this process's monotonic clock with binds read from
its own watch of pods; stop creating and wait for the pods created in
the window; read the hub's LIST; stop both children; replay every bind
in the configuration's plain reference (harness/verdict.py over
harness/reference.py, or over the module the configuration names);
print one JSON line.

This process never imports JAX while a child holds the chip. Without
--rehearse a scheduler that names any platform but `tpu` ends the run
with a non-zero exit and no result line. --rehearse runs the same code
on the CPU at the tiny sizes the data files give under "rehearse" and
marks every line "rehearsal": true.
"""

import time
T_START = time.monotonic()

import argparse
import json
import os
import shutil
import sys
import tempfile
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from harness import cluster, roofline, trace_reduce, verdict  # noqa: E402
from harness.children import (Child, free_port, parse_metrics,  # noqa: E402
                              proc_cpu_s, scrape)
from harness.sched_entry import MARK                        # noqa: E402
from harness.hubclient import Hub, PodWatch                # noqa: E402
from harness.loadgen import Traffic                        # noqa: E402
from harness.observe import Observer                       # noqa: E402


load_json = cluster.load_json


def quantile(values, q):
    """Linear interpolation between closest ranks."""
    v = sorted(values)
    if not v:
        return None
    pos = q * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


class Run:
    def __init__(self, args):
        self.args = args
        self.rehearse = args.rehearse
        self.bench, self.cell, self.config, self.mix = \
            cluster.load_cell(args.workload)
        if self.rehearse:
            self.config.update(self.config.get("rehearse", {}))
            self.mix.update(self.mix.get("rehearse", {}))
        self.reference = cluster.load_reference(self.config)
        self.device = None
        self.workdir = tempfile.mkdtemp(prefix="ktpu-bench-")
        self.ctrl = os.path.join(self.workdir, "ctrl")
        os.makedirs(self.ctrl)
        self._ctrl_n = 0
        self.hub = self.sched = self.watch = None

    # ------------------------------------------------------------ output

    def say(self, phase, **fields):
        line = {"phase": phase, **fields, "wall": time.time(),
                "device": self.device}
        if self.rehearse:
            line["rehearsal"] = True
        print(json.dumps(line), flush=True)

    def metrics_for(self, section):
        """[(name, spec)] of the section's metrics that this cell reports."""
        out = []
        for m in self.bench[section]:
            if "workloads" in m and self.cell["name"] not in m["workloads"]:
                continue
            out.append((m["name"],
                        load_json(HERE, "metrics", f"{m['name']}.json")))
        return out

    # ---------------------------------------------------------- children

    def ask(self, verb, timeout=120):
        """One command to the scheduler child's side thread."""
        self._ctrl_n += 1
        n = f"{self._ctrl_n:04d}"
        open(os.path.join(self.ctrl, f"{n}.{verb}"), "w").close()
        done = os.path.join(self.ctrl, f"{n}.done")
        deadline = time.monotonic() + timeout
        while not os.path.exists(done):
            if not self.sched.alive():
                raise RuntimeError("kube_scheduler died; stderr tail:\n"
                                   + self.sched.stderr()[-3000:])
            if time.monotonic() > deadline:
                raise RuntimeError(f"the scheduler child did not answer "
                                   f"{verb} within {timeout}s")
            time.sleep(0.01)
        answer = load_json(done)
        if "error" in answer:
            raise RuntimeError(f"{verb}: {answer['error']}")
        return answer

    def start_hub(self):
        self.hub_port = free_port()
        env = dict(os.environ, JAX_PLATFORMS="cpu")  # never takes the chip
        self.hub = Child("kube_apiserver", [
            sys.executable, "-m", "kubernetes_tpu.cmd.kube_apiserver",
            "--port", str(self.hub_port),
            "--data-dir", os.path.join(self.workdir, "hub")],
            env, self.workdir, REPO)
        wal = self.hub.wait_line("wal ", 180)
        self.hub.wait_line("serving on", 60)
        if not wal.endswith("native=True"):
            raise RuntimeError(f"the native WAL did not build: {wal!r}\n"
                               + self.hub.stderr()[-2000:])
        self.base = f"http://127.0.0.1:{self.hub_port}"
        self.client = Hub(self.base)

    def start_scheduler(self):
        platform = "cpu" if self.rehearse else "tpu"
        cfg = os.path.join(self.workdir, "scheduler-config.json")
        with open(cfg, "w") as f:
            json.dump(self.config["scheduler_config"], f)
        self.sched_port = free_port()
        env = dict(os.environ, JAX_PLATFORMS=platform)
        env.pop("BENCH_REHEARSAL", None)
        argv = [sys.executable, os.path.join(HERE, "harness",
                                             "sched_entry.py"), self.ctrl]
        if self.rehearse:
            env["BENCH_REHEARSAL"] = "1"
            if self.args.fault:
                argv += ["--fault", self.args.fault]
        elif self.args.fault:
            raise SystemExit("--fault needs --rehearse")
        argv += ["--", "--master", self.base, "--config", cfg,
                 "--healthz-port", str(self.sched_port)]
        self.sched = Child("kube_scheduler", argv, env, self.workdir, REPO)
        line = self.sched.wait_line("kube-scheduler device ", 600)
        device = json.loads(line.split(" ", 2)[2])
        if device["platform"] != platform:
            raise RuntimeError(f"the scheduler runs on {device}, "
                               f"not on {platform}")
        if not self.rehearse and device["count"] < self.cell["chips"]:
            raise RuntimeError(f"{device['count']} chips, the cell asks "
                               f"for {self.cell['chips']}")
        self.device = {k: device[k] for k in ("platform", "kind", "count")}
        self.sched.wait_line("healthz+metrics on", 120)
        self.sched_metrics = f"http://127.0.0.1:{self.sched_port}/metrics"
        # Scheduler.start() lists before it loops: healthz turns ok once
        # the informers have synced, nodes included
        deadline = time.monotonic() + 300
        while True:
            try:
                import urllib.request
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{self.sched_port}/healthz",
                        timeout=5) as r:
                    if r.status == 200:
                        break
            except Exception:
                pass
            if not self.sched.alive() or time.monotonic() > deadline:
                raise RuntimeError("kube_scheduler never became healthy:\n"
                                   + self.sched.stderr()[-3000:])
            time.sleep(0.1)

    def probe(self):
        """CPU seconds and /metrics of both children, now."""
        return {"t": time.monotonic(),
                "cpu": {"kube_apiserver": proc_cpu_s(self.hub.pid),
                        "kube_scheduler": proc_cpu_s(self.sched.pid),
                        "loadgen": proc_cpu_s(os.getpid())},
                "scrape": {"kube_apiserver": scrape(self.base + "/metrics"),
                           "kube_scheduler": scrape(self.sched_metrics)}}

    # --------------------------------------------------------------- run

    def execute(self):
        a, mix, cfg = self.args, self.mix, self.config
        self.start_hub()
        nodes = self.nodes = cluster.make_nodes(cfg, cfg["nodes"], a.seed)
        self.client.create_all("/api/v1/nodes", nodes)
        objects = []
        for o in cfg.get("setup_objects", []):
            self.client.create_all(o["path"], [o["manifest"]])
            objects.append(o["manifest"])
        # nodes first, scheduler second: its first LIST holds every node,
        # so no pod is ever decided over a part of the cluster
        self.start_scheduler()
        self.say("children", nodes=len(nodes),
                 at_s=time.monotonic() - T_START)

        obs = Observer()
        self.watch = PodWatch(self.client, "default", obs.on_bind)
        stream = RecordingStream(cluster.PodStream(cfg, a.seed))
        traffic = Traffic(self.client, stream, obs, mix)
        alive = self.sched.alive

        def settle(what, timeout=600):
            if not obs.wait_all_bound(timeout, alive):
                raise RuntimeError(
                    f"{what}: {obs.count_pending()} pods still unbound; "
                    f"scheduler stderr tail:\n{self.sched.stderr()[-3000:]}")

        if cfg["existing_pods"]:
            traffic.burst(cfg["existing_pods"])
            settle("existing pods")
        for n in mix.get("warm_bursts", []):
            small = n < int(mix.get("warm_blocker_below", 0))
            traffic.burst(n, int(mix["warm_blocker"]) if small else 0)
            settle(f"warm burst of {n}")
        self.say("warm", pods=len(stream.taken),
                 at_s=time.monotonic() - T_START)
        traffic.start()
        time.sleep(float(mix.get("ramp_s", 0)))

        # ---- the window. A traced run ends its window with the traced
        # slice: the host numbers are read over the part before it (the
        # profiler slows the host and stopping it stalls the scheduler),
        # the device numbers and the pods they are divided by between two
        # marks that the launcher writes into the trace itself.
        slice_s = 0.0
        if a.trace:
            slice_s = min(float(mix["trace_slice_s"]), a.seconds / 2)
        snap0 = self.ask("snapshot")
        p0 = self.probe()
        t0 = p0["t"]
        setup_s = t0 - T_START
        time.sleep(max(0.0, t0 + a.seconds - slice_s - time.monotonic()))
        p1 = self.probe()
        t1 = p1["t"]
        snap1 = self.ask("snapshot")
        slice_marks = None
        if a.trace:
            self.ask("trace_start")
            s0 = parse_metrics(self.ask("mark")["scrape"])
            time.sleep(slice_s)
            s1 = parse_metrics(self.ask("mark")["scrape"])
            traffic.stop()      # stopping the profiler holds the scheduler
            stop = self.ask("trace_stop", timeout=300)
            slice_marks = (s0, s1, stop)
        else:
            traffic.stop()
        t_stopped = time.monotonic()
        drained = obs.wait_all_bound(float(mix["drain_deadline_s"]), alive)
        if not drained:
            # late is late, not wrong: a minute more before a pod counts
            # as never bound
            obs.wait_all_bound(float(mix.get("late_grace_s", 60)), alive)
        t_drained = time.monotonic()
        if traffic.error is not None:
            raise RuntimeError(f"the load generator failed: "
                               f"{traffic.error!r}")
        if self.watch.error is not None:
            raise RuntimeError(f"the watch broke: {self.watch.error!r}")
        self.say("window", seconds=t1 - t0, drain_s=t_drained - t_stopped,
                 pods_created_total=len(stream.taken),
                 creates_refused=len(traffic.refused),
                 first_refusals=traffic.refused[:3])

        # ---- what the hub says, then stop the children
        listed = self.client.list("/api/v1/namespaces/default/pods")
        final = self.probe()
        self.watch.stop()
        sched_rc = self.sched.close()
        hub_rc = self.hub.close()
        sched_err = self.sched.stderr()
        self.say("disk", workdir_bytes=sum(
            os.path.getsize(os.path.join(d, f))
            for d, _, files in os.walk(self.workdir) for f in files))
        compiles_line = next(
            (ln for ln in self.sched.stdout().splitlines()
             if ln.startswith("kube-scheduler compiles ")), None)

        # ---- end-to-end numbers
        in_window = {n: r for n, r in obs.pods.items() if t0 <= r[0] < t1}
        latencies = [r[2] - r[0] for r in in_window.values()
                     if r[2] is not None]
        binds_in_window = sum(1 for r in obs.pods.values()
                              if r[2] is not None and t0 <= r[2] < t1)
        never_bound = sum(1 for r in in_window.values() if r[2] is None)
        refused = sum(1 for r in in_window.values() if r[1] == -1)
        rebound = sum(1 for name, _, _ in obs.rebinds if name in in_window)
        ctx = {
            "t0": t0, "t1": t1, "seconds": t1 - t0, "setup_s": setup_s,
            "latencies": latencies, "binds_in_window": binds_in_window,
            "observer": obs, "mix": mix, "config": cfg,
            "nodes": cfg["nodes"], "device": self.device,
            "probe0": p0, "probe1": p1, "snap0": snap0, "snap1": snap1,
        }
        self.say("compiles_in_window", window_start_wall=time.time()
                 - (time.monotonic() - t0), programs=[
            [t - snap0["t"], secs] for t, secs in snap1["compile_events"]
            if t > snap0["t"]])
        self.say("latency", samples=len(latencies),
                 bind_p50_s=quantile(latencies, 0.5),
                 bind_p95_s=quantile(latencies, 0.95),
                 bind_p99_s=quantile(latencies, 0.99),
                 bind_max_s=max(latencies) if latencies else None,
                 binds_in_window=binds_in_window,
                 late_after_deadline=not drained)

        # ---- correct: the hub's LIST against the plain reference
        compared = verdict.compare(
            self.reference, self.nodes, stream.taken,
            {name: r[1] for name, r in obs.pods.items()},
            {name: r[3] for name, r in obs.pods.items()},
            obs.rebinds, listed, final["scrape"]["kube_scheduler"],
            [sched_rc, hub_rc], sched_err, say=self.say, objects=objects)
        correct = verdict.correct(compared)

        # ---- per-layer numbers (a traced run)
        section = "per_layer" if a.trace else "end_to_end"
        device_out = dict(self.device)
        device_out["memory_peak_bytes"] = \
            snap1["memory"].get("peak_bytes_in_use")
        breakdown = None
        if a.trace:
            reduced = trace_reduce.reduce(
                os.path.join(self.ctrl, "trace.xplane.pb"), mark=MARK)
            ctx["trace"] = reduced
            s0, s1, _ = slice_marks
            ctx["slice_pods_scheduled"] = s1.get(verdict.SCHEDULED, 0) \
                - s0.get(verdict.SCHEDULED, 0)
            last = stream.taken[-512:]
            ctx["scan_bytes_per_pod_node"] = sum(
                roofline.scan_bytes_per_node(self.reference.PodFacts(m))
                for m in last) / max(1, len(last))
            if reduced is not None:
                device_out["busy_s"] = reduced["busy_s"]
                device_out["window_s"] = reduced["window_s"]
                breakdown = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"],
                             "programs": sorted(
                                 ([n, p["seconds"], p["runs"]]
                                  for n, p in reduced["programs"].items()),
                                 key=lambda r: -r[1])[:10]}
                self.say("trace", stop_seconds=slice_marks[2].get(
                             "stop_seconds"),
                         trace_bytes=slice_marks[2].get("trace_bytes"),
                         window_s=reduced["window_s"],
                         mark_spans_s=reduced["mark_spans_s"],
                         busy_s=reduced["busy_s"],
                         slice_pods_scheduled=ctx["slice_pods_scheduled"],
                         scan_bytes_per_pod_node=ctx[
                             "scan_bytes_per_pod_node"],
                         programs=breakdown["programs"])
        metrics = {}
        for name, spec in self.metrics_for(section):
            value = read_metric(name, spec, ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": spec["unit"]}

        result = {
            "correct": bool(correct),
            "attempted": len(in_window),
            "failed": never_bound + refused + rebound,
            "metrics": metrics,
            "device": device_out,
        }
        if breakdown is not None:
            result["breakdown"] = breakdown
        if self.rehearse:
            result["rehearsal"] = True
        result["workload"] = self.cell["name"]
        result["seed"] = a.seed
        result["scheduler_compiles"] = json.loads(
            compiles_line.split(" ", 2)[2]) if compiles_line else None
        result["compared"] = compared
        for name, c in compared.items():
            print(f"compared {name}: {c['value']} (limit {c['limit']})",
                  file=sys.stderr)
        sys.stderr.flush()
        return result

    def close(self):
        if self.watch is not None:
            self.watch.stop()
        for child in (self.sched, self.hub):
            if child is not None:
                child.close()
        shutil.rmtree(self.workdir, ignore_errors=True)


class RecordingStream:
    """A PodStream that keeps every manifest it handed out, in order."""

    def __init__(self, stream):
        self._stream = stream
        self.taken = []

    def take(self, n):
        pods = self._stream.take(n)
        self.taken.extend(pods)
        return pods


# ------------------------------------------------------------- metrics
#
# One reader per `kind` of metric data file; kind "reader" hands over to
# benchmarks/metrics/<name>.py read(ctx, spec). A reader that finds
# nothing to read returns None and the metric is left out of the line.

def _delta(ctx, process, key):
    a = ctx["probe0"]["scrape"][process].get(key)
    b = ctx["probe1"]["scrape"][process].get(key)
    return None if a is None or b is None else b - a


def read_metric(name, spec, ctx):
    kind = spec["kind"]
    if kind == "window_bind_rate":
        return ctx["binds_in_window"] / ctx["seconds"]
    if kind == "bind_latency_quantile":
        return quantile(ctx["latencies"], float(spec["q"]))
    if kind == "setup_seconds":
        return ctx["setup_s"]
    if kind == "loadgen_fill":
        limit = ctx["mix"].get("in_flight")
        return None if not limit else ctx["observer"].mean_fill(
            ctx["t0"], ctx["t1"], int(limit))
    if kind == "cpu_per_pod":
        if not ctx["binds_in_window"]:
            return None
        cpu = ctx["probe1"]["cpu"][spec["process"]] \
            - ctx["probe0"]["cpu"][spec["process"]]
        return 1000.0 * cpu / ctx["binds_in_window"]
    if kind == "scrape_ratio":
        num = _delta(ctx, spec["process"], spec["numerator"])
        den = _delta(ctx, spec["process"], spec["denominator"])
        if num is None or not den:
            return None
        return float(spec.get("scale", 1.0)) * num / den
    if kind == "compiles_in_window":
        return ctx["snap1"]["compiles"]["programs"] \
            - ctx["snap0"]["compiles"]["programs"]
    if kind == "reader":
        return cluster.load_module(os.path.join(
            HERE, "metrics", f"{name}.py")).read(ctx, spec)
    raise ValueError(f"metric {name}: unknown kind {kind!r}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--fault", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if "jax" in sys.modules:
        raise SystemExit("the parent imported JAX before its children ran")
    run = Run(args)
    if args.seconds is None:
        args.seconds = float(run.bench["run_seconds"])
    try:
        result = run.execute()
    except BaseException:
        traceback.print_exc()
        run.close()
        sys.exit(1)
    run.close()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
