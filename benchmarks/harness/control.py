"""The control of `correct`: the configuration's plain reference put in
the program's place and computed below the precision the configurations
state, its binds judged by the very compare() and correct() of
harness/verdict.py that judge a measured run. It has to come out as not
correct.

The configurations state upstream's arithmetic (int64 floors, float64
fractions). Down the ladder from there, on the source's one pod shape:
float32 and bfloat16 change no decision (every node of one fill level
scores alike, and a rounding that keeps the score monotone in the fill
level keeps the argmax: a program that computed so would be right on
these cells, and reads correct); int8 (fractions in steps of 1/127)
moves the boundaries between score levels and is the first rung that
reads a score gap. That rung is the control. PERF.md gives the readings.

    python benchmarks/harness/control.py --workload <cell> --seed <n>
                [--pods <n>] [--precision int8|bfloat16|float32]
                [--nodes <n>]

prints every number compared beside its limit, and `correct`."""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path.insert(0, BENCH_DIR)

from harness import cluster, verdict  # noqa: E402


def run_control(config, seed, n_pods, precision, n_nodes=None):
    """The verdict on the control: (compared, correct, what the replay
    said). What a run hands compare() is made here from the control's own
    decisions: each pod acknowledged in order, listed with its node and a
    PodScheduled condition, seen once on the watch; no process to fail."""
    ref = cluster.load_reference(config)
    objects = [o["manifest"] for o in config.get("setup_objects", [])]
    nodes = cluster.make_nodes(config, n_nodes or config["nodes"], seed)
    pods = cluster.PodStream(config, seed).take(n_pods)
    low = ref.Reference(nodes, precision, objects)
    listed, watch_node, created_rv = [], {}, {}
    for rv, m in enumerate(pods, 1):
        pod = ref.PodFacts(m)
        created_rv[pod.name] = rv
        node = low.decide(pod)
        listed.append({**m, "spec": {**m["spec"], "nodeName": node or ""},
                       "status": {"conditions": [{
                           "type": "PodScheduled",
                           "status": "True" if node else "False"}]}})
        if node is not None:
            watch_node[pod.name] = node
            low.bind(pod, node)
    said = {}
    compared = verdict.compare(
        ref, nodes, pods, created_rv, watch_node, [], listed,
        {verdict.SCHEDULED: len(watch_node)}, [0, 0], "",
        say=lambda phase, **fields: said.update(fields), objects=objects)
    return compared, verdict.correct(compared), said


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--pods", type=int, default=90000)
    ap.add_argument("--nodes", type=int, default=None)
    ap.add_argument("--precision", default="int8")
    a = ap.parse_args()
    _, _, config, _ = cluster.load_cell(a.workload)
    t = time.monotonic()
    compared, correct, said = run_control(config, a.seed, a.pods,
                                          a.precision, a.nodes)
    print(json.dumps({
        "control": a.precision, "workload": a.workload, "seed": a.seed,
        "pods": a.pods, "seconds": round(time.monotonic() - t, 1),
        "correct": correct, "binds_with_gap": said.get("binds_with_gap"),
        "compared": compared}))


if __name__ == "__main__":
    main()
