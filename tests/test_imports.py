"""Import smoke test: every module under kubernetes_tpu/ must import.

A missing OPTIONAL dependency (cryptography, jax extras, ...) must degrade
to a clear runtime error at the call site, never to an ImportError at
module load — at seed, a top-level `cryptography` import took out eight
test files as collection errors. This test makes such regressions fail
loudly at tier-1 instead.
"""

import importlib
import pkgutil

import pytest

import kubernetes_tpu


# native/walcore-<source hash>.so is a ctypes-loaded shared library
# (native/build.py), not a Python extension module; pkgutil still lists
# it — and it appears mid-run, so every xdist worker must leave it out
# whether or not it exists yet
NOT_PYTHON_MODULES = "kubernetes_tpu.native.walcore"


def _all_modules():
    mods = []
    for info in pkgutil.walk_packages(kubernetes_tpu.__path__,
                                      prefix="kubernetes_tpu."):
        if not info.name.startswith(NOT_PYTHON_MODULES):
            mods.append(info.name)
    return sorted(mods)


@pytest.mark.parametrize("name", _all_modules())
def test_module_imports(name):
    importlib.import_module(name)


def test_walk_found_the_tree():
    mods = _all_modules()
    # guard the walker itself: the tree has dozens of modules across all
    # subpackages; an empty/partial walk would vacuously pass above
    assert len(mods) > 50
    for sub in ("api", "apiserver", "controllers", "node", "scheduler",
                "scheduler.kernels", "state", "utils"):
        assert f"kubernetes_tpu.{sub}" in mods
