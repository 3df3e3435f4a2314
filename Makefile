# Developer entry points.

.PHONY: test test-fast bench chip-smoke native docs clean autotune autotune-plan

test:
	python -m pytest tests/ -q

test-fast:          # skip multiprocess gang tests (each worker imports jax/tf)
	python -m pytest tests/ -q -m "not gang"

bench:              # single-chip headline bench (run on a TPU host)
	python bench.py

chip-smoke:         # the main path once on one TPU chip (run on a TPU host)
	python chip_smoke.py

autotune:           # search the knob space; emit the per-device-kind profile
	python -m sparkdl_tpu.perf.autotune --bench cpu-proxy

autotune-plan:      # show the (pruned) trial plan without measuring
	python -m sparkdl_tpu.perf.autotune --bench cpu-proxy --dry-run

native:             # build the C++ control-plane transport
	$(MAKE) -C native

docs:
	cd docs && PYTHONPATH=.. $(MAKE) html

clean:
	rm -rf native/build docs/_build
	find . -name __pycache__ -type d -exec rm -rf {} +
