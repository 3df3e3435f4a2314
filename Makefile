# Developer entry points.

.PHONY: test test-fast chip-smoke native docs clean

test:
	python -m pytest tests/ -q

test-fast:          # skip multiprocess gang tests (each worker imports jax/tf)
	python -m pytest tests/ -q -m "not gang"

chip-smoke:         # the main path once on one TPU chip (run on a TPU host)
	python chip_smoke.py

native:             # build the C++ control-plane transport
	$(MAKE) -C native

docs:
	cd docs && PYTHONPATH=.. $(MAKE) html

clean:
	rm -rf native/build docs/_build
	find . -name __pycache__ -type d -exec rm -rf {} +
