"""The benchmark's own tests of its seams, run by the tier-1 suite: the
45 cases of benchmarks/tests/test_seams.py (numpy only, a few seconds),
imported and collected here under their own names. They guard the node
digest, the whitelists of the base reference, 24.0 / 28.0 bytes a pod
and node, and that both accepted configurations load harness.reference."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "tests", "test_seams.py")
_spec = importlib.util.spec_from_file_location("bench_test_seams", _PATH)
_seams = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_seams)

# tests and the fixtures they ask for, as pytest finds them in a module
globals().update({name: value for name, value in vars(_seams).items()
                  if not name.startswith("_")})
