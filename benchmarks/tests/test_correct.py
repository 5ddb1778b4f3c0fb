"""The benchmark's own tests of what decides `correct` (not tier-1; run
by hand: `python -m pytest benchmarks/tests -q`, CPU, a few minutes).

  - the control (the reference in int8, in the program's place)
    comes out as not correct by the very compare() and correct() that
    judge a measured run (harness/verdict.py), at a size a test run can
    hold;
  - the rest of a run, with the timed path broken underneath by each
    fault the cells can have, reads `correct: false`;
  - a sound rehearsal reads `correct: true`.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

from harness import control  # noqa: E402


def _config(name):
    with open(os.path.join(BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("config", ["sched-perf-5000n-basic",
                                    "sched-perf-5000n-antiaffinity"])
@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5, 77])
def test_control_is_not_correct(config, seed):
    compared, correct, _ = control.run_control(
        _config(config), seed, 8000, "int8", n_nodes=500)
    assert correct is False
    assert compared["score_gap_max"]["value"] > 0 or \
        compared["binds_that_do_not_fit"]["value"] > 0, compared


@pytest.mark.parametrize("config", ["sched-perf-5000n-basic",
                                    "sched-perf-5000n-antiaffinity"])
def test_exact_reference_agrees_with_itself(config):
    compared, correct, _ = control.run_control(
        _config(config), 3, 4000, "exact", n_nodes=300)
    assert correct is True, compared


def rehearse(workload, seed, fault=None, trace=0, seconds=4):
    argv = [sys.executable, os.path.join(BENCH_DIR, "run.py"),
            "--workload", workload, "--seed", str(seed),
            "--seconds", str(seconds),
            "--trace", str(trace), "--rehearse"]
    if fault:
        argv += ["--fault", fault]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(argv, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["basic5k.wave4096",
                                      "antiaff5k.wave4096"])
def test_sound_rehearsal_is_correct(workload):
    line = rehearse(workload, 11)
    assert line["rehearsal"] is True and line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0


@pytest.mark.parametrize("workload", ["basic5k.wave4096",
                                      "antiaff5k.wave4096"])
@pytest.mark.parametrize("fault", ["alter_answer", "drop_half",
                                   "stale_state"])
def test_fault_is_not_correct(fault, workload):
    # a stale state shows once nodes differ by a score level: the tiny
    # anti-affinity rehearsal needs a longer window to get there
    slow = fault == "stale_state" and workload.startswith("antiaff")
    line = rehearse(workload, 12, fault=fault, seconds=12 if slow else 4)
    assert line["correct"] is False, line
    bad = {k for k, c in line["compared"].items() if c["value"] > c["limit"]}
    assert bad, line
