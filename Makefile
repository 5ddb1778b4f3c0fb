# Convenience entries (the reference's hack/ equivalents). The benchmark is
# benchmarks/run.py (BENCHMARK.json, benchmarks/README.md); it needs the chip.

.PHONY: lint lint-changed test

# full contract lint (tools/ktpulint; exit 1 on findings)
lint:
	python -m tools.ktpulint

# pre-commit fast path: lint only files touched vs main
lint-changed:
	python -m tools.ktpulint --changed

# tier-1 suite (what the roadmap's verify line runs)
test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow'
