"""chip_smoke.py rehearsed on the CPU: the script the driver runs on the
chip must keep working end to end (real hub + scheduler processes, parity,
pipelined drain), name the platform it really ran on, and turn a failed
phase into a non-zero exit — plus the compile-cache placement rule."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*argv, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)                 # the script sets its own
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        env=env, capture_output=True, text=True, timeout=timeout)
    lines = [json.loads(line) for line in out.stdout.splitlines()]
    return out, lines


def test_rehearse_runs_every_phase_on_the_cpu():
    out, lines = _smoke("--rehearse")
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": lines[-1]["device"]["kind"],
        "count": 1}}
    phases = {line["phase"]: line for line in lines[:-1]}
    assert phases["served.start"]["wal"].endswith("native=True")
    assert phases["served.bound"]["bound"] == phases["served.bound"]["pods"]
    assert set(phases["parity.decisions"]["rates"].values()) == {1.0}
    assert {"gang", "preempt", "drf", "affinity",
            "scores"} <= set(phases["parity.kernels"])
    assert phases["drain"]["compiles_in_timed_drain"] == 0
    # unset JAX_COMPILATION_CACHE_DIR: both processes cache under the
    # checkout, and the second one finds what the first one wrote
    cache = os.path.join(REPO, ".jax_cache")
    assert phases["served.start"]["scheduler_device"]["compile_cache"] \
        == phases["device"]["compile_cache"] == cache
    assert phases["drain"]["warmup_cache_hits"] > 0


def test_rehearse_four_devices_runs_only_the_sharded_comparison():
    out, lines = _smoke("--rehearse", "--chips", "4")
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    assert [line.get("phase") for line in lines[:-1]] == ["device",
                                                          "sharded"]
    assert lines[-1]["ok"] is True
    assert lines[-1]["device"]["platform"] == "cpu"
    assert lines[-1]["device"]["count"] == 4
    sharded = lines[1]
    assert sharded["differing_binds"] == 0
    assert sharded["mesh4"]["sharded_batches"] > 0
    assert set(sharded["mesh4"]["usage_device_sets"].values()) == {4}


def test_a_failed_phase_fails_the_run():
    """A pod no node can hold never binds: phase 1 passes its deadline,
    the run ends there with ok false and a non-zero exit."""
    out, lines = _smoke("--rehearse", "--inject-unschedulable",
                        "--bind-deadline", "10")
    assert out.returncode != 0
    assert lines[-1]["ok"] is False
    assert "device" not in lines[-1]
    assert not any(line.get("phase") == "drain" for line in lines)


@pytest.fixture
def config_updates(monkeypatch):
    """jax.config.update calls, recorded instead of applied (the helper
    under test would otherwise re-place this process's cache)."""
    import jax
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_cache_dir_from_the_environment_is_the_only_setting(
        monkeypatch, config_updates):
    from kubernetes_tpu.scheduler import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    enable_compile_cache()
    names = [name for name, _ in config_updates]
    assert "jax_compilation_cache_dir" not in names
    # the thresholds still drop, so sub-second programs are written too
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) \
        in config_updates


def test_cache_dir_defaults_to_a_fixed_path_in_the_checkout(
        monkeypatch, config_updates):
    from kubernetes_tpu.scheduler import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    enable_compile_cache()
    assert ("jax_compilation_cache_dir",
            os.path.join(REPO, ".jax_cache")) in config_updates
