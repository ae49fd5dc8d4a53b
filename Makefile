# Tier-1 verification and benchmarks — the commands CI runs, documented
# here so they are reproducible locally.
#
#   make test        — the tier-1 suite on the CPU (JAX_PLATFORMS=cpu,
#                      Pallas kernels in interpret mode; single CPU device
#                      in the main process; distributed tests spawn
#                      subprocesses with 8 fake devices via
#                      tests/dist_helper.py)
#   make bench       — the benchmark driver (CSV to stdout)
#   make bench-smoke — tiny-shapes pass of every suite + JSON artifact
#                      (what the CI bench-smoke job runs)
#   make bench-trend — bench-smoke + trend compare vs the newest committed
#                      baseline in benchmarks/trends/ (the CI compare step)
#   make lint        — ruff (config in pyproject.toml) + the CI shard
#                      coverage assertion (the CI lint job)

PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test bench bench-smoke bench-trend lint

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest -x -q

bench:
	$(PY) -m benchmarks.run

bench-smoke:
	$(PY) -m benchmarks.run --smoke --out bench-smoke.json

bench-trend:
	$(PY) -m benchmarks.run --smoke --out bench-smoke.json --compare \
		$$(ls benchmarks/trends/BENCH_*.json | sort -V | tail -1)

lint:
	ruff check .
	ruff format --check .
	$(PY) scripts/check_ci_shards.py
