"""A check of the yardstick itself, by hand, on the CPU, under a minute
(not part of tests/):

    python benchmarks/harness/selfcheck.py

  1. the plain reference agrees with the served path: one rehearsal of
     the first cell of BENCHMARK.json (real hub, real scheduler, tiny
     seeded cluster) reads `correct: true` with a score gap of 0;
  2. the reference catches a pod bound to a worse node (score gap) and
     a node filled past its allocatable (binds that do not fit);
  3. trace_reduce gives the busy and idle share recorded beside
     selfcheck_trace.xplane.pb, a small trace taken on a TPU v5e with
         python benchmarks/harness/selfcheck.py --record <file>
     (20 runs of one matmul program with 5 ms of host sleep between),
     that busy time agrees with the plain sum of the module events, and
     that cutting the trace to its first half (as a traced run cuts it
     to the window between its two marks) leaves half the runs;
  4. the seams hold (benchmarks/tests/test_seams.py, numpy, seconds: no
     tier-1 test guards them yet, PERF.md Open question 0): defaults
     byte for byte, the reference's whitelists, an extended reference,
     a node builder and set-up objects, the scan's byte model.
"""

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

TRACE = os.path.join(HERE, "selfcheck_trace.xplane.pb")
EXPECTED = os.path.join(HERE, "selfcheck_trace.expected.json")


def record(path):
    import jax
    import jax.numpy as jnp
    from jax._src.lib import _profiler
    step = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((1024, 1024), jnp.float32)
    step(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    session = _profiler.ProfilerSession(options)
    for _ in range(20):
        step(x).block_until_ready()
        time.sleep(0.005)
    data = session.stop()
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "wb") as f:
        f.write(data)
    print("recorded", path, len(data), "bytes on",
          jax.devices()[0].device_kind)


def check_rehearsal():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         cell, "--seed", "41", "--seconds", "3", "--trace", "0",
         "--rehearse"], cwd=REPO, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"], line
    assert line["compared"]["score_gap_max"]["value"] == 0
    return f"rehearsal of {cell}: correct, {line['attempted']} pods in window"


def check_reference_catches():
    from harness import cluster
    with open(os.path.join(BENCH_DIR, "configs",
                           "sched-perf-5000n-basic.json")) as f:
        config = json.load(f)
    reference = cluster.load_reference(config)
    nodes = cluster.make_nodes(config, 50, 5)
    pods = cluster.PodStream(config, 5).take(400)
    ref = reference.Reference(nodes)
    bound = {}
    for m in pods:
        pod = reference.PodFacts(m)
        bound[pod.name] = ref.decide(pod)
        ref.bind(pod, bound[pod.name])
    sound = reference.replay(nodes, pods, bound)
    assert sound["score_gap_max"] == 0 and \
        sound["binds_that_do_not_fit"] == 0, sound
    # the last pod goes where the most pods already are: a worse score
    crowded = max(ref.names, key=lambda n: ref.cpu[ref.row[n]]
                  if n != bound[pods[-1]["metadata"]["name"]] else -1)
    misbound = dict(bound)
    misbound[pods[-1]["metadata"]["name"]] = crowded
    out = reference.replay(nodes, pods, misbound)
    assert out["score_gap_max"] > 0, out
    # every pod on one node: it is over its allocatable after a few
    overfull = {name: "node-0" for name in bound}   # 4 CPU: 40 pods
    out2 = reference.replay(nodes, pods, overfull)
    assert out2["binds_that_do_not_fit"] > 0 and \
        out2["nodes_over_allocatable"] == 1, out2
    return (f"reference: mis-bound pod reads gap {out['score_gap_max']}, "
            f"over-full node reads {out2['binds_that_do_not_fit']} misfits")


def check_seams():
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         os.path.join(BENCH_DIR, "tests", "test_seams.py")], cwd=REPO,
        capture_output=True, text=True, timeout=600)
    tail = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-1000:]
    return f"seams: {tail.strip('= ')}"


def check_trace():
    from harness import trace_reduce
    with open(EXPECTED) as f:
        want = json.load(f)
    got = trace_reduce.reduce(TRACE)
    assert got is not None, "no device plane in the recorded trace"
    modules = sum(p["seconds"] for p in got["programs"].values())
    assert abs(got["busy_s"] - modules) <= 0.02 * modules, (got, modules)
    for key in ("busy_s", "window_s"):
        assert abs(got[key] - want[key]) <= 1e-9 + 1e-6 * want[key], \
            (key, got[key], want[key])
    idle = 100.0 * (1 - got["busy_s"] / got["window_s"])
    assert abs(idle - want["idle_share_pct"]) < 1e-3
    data = trace_reduce.load(TRACE)
    starts = [ev.start_ns for plane in data.planes for line in plane.lines
              for ev in line.events]
    lo = min(starts)
    half = trace_reduce.reduce(TRACE, clip=(
        lo, lo + want["window_s"] * 1e9 / 2))
    runs = sum(p["runs"] for p in half["programs"].values())
    assert runs == want["runs_in_first_half"], half["programs"]
    assert abs(half["window_s"] - want["window_s"] / 2) < 1e-9
    assert 0.4 * got["busy_s"] < half["busy_s"] < 0.6 * got["busy_s"]
    return f"trace: busy {got['busy_s']:.6f} s of {got['window_s']:.6f} s"


def main():
    if len(sys.argv) == 3 and sys.argv[1] == "--record":
        return record(sys.argv[2])
    t = time.monotonic()
    for check in (check_reference_catches, check_seams, check_trace,
                  check_rehearsal):
        print("ok:", check(), flush=True)
    print(f"selfcheck passed in {time.monotonic() - t:.0f} s")


if __name__ == "__main__":
    main()
