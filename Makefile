# Tier-1 verification — the commands CI runs, documented here so they are
# reproducible locally.
#
#   make test        — the tier-1 suite on the CPU (JAX_PLATFORMS=cpu,
#                      Pallas kernels in interpret mode; single CPU device
#                      in the main process; distributed tests spawn
#                      subprocesses with 8 fake devices via
#                      tests/dist_helper.py)
#   make lint        — ruff (config in pyproject.toml) + the CI shard
#                      coverage assertion (the CI lint job)
#
# The one benchmark runs on a TPU, one cell of BENCHMARK.json at a time:
#
#   python3 -m bench.run --workload <cell> --seed <n> --seconds <s> \
#       --trace <0|1>

PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test lint

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest -x -q

lint:
	ruff check .
	ruff format --check .
	$(PY) scripts/check_ci_shards.py
