"""Runs of a cell on the CPU at a small size, for the tests: everything a
run does after the harness has looked for the chip.  Cells are read from
``BENCHMARK.json`` alone; only their sizes are cut here."""
from __future__ import annotations

import sys
import time

from bench import spec

SRC = str(spec.ROOT / "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

# small sizes, by configuration name
SMALL = {"dense32k": {"n": 256, "r": 32}}


def small_cell(workload: str) -> spec.Cell:
    cell = spec.resolve(workload)
    cell.config = {**cell.config, **SMALL[cell.config["name"]]}
    return cell


def drive(workload: str, seed: int = 3, seconds: float = 1.0,
          trace: bool = False, devices=None):
    """One run of ``workload`` at its small size, up to the driver's
    outcome; returns the run's context and that outcome."""
    import jax
    from bench import harness
    cell = small_cell(workload)
    devices = devices if devices is not None else jax.devices()[:cell.chips]
    ctx = harness.Context(cell=cell, seed=seed, seconds=seconds, trace=trace,
                          devices=list(devices), t0=time.perf_counter(),
                          compiles=harness.CompileCounter())
    driver = spec.load_module(cell.driver_file,
                              "bench_driver_" + cell.traffic["driver"])
    return ctx, driver.run(ctx)


def run_small(workload: str, seed: int = 3, seconds: float = 1.0,
              trace: bool = False, devices=None):
    """One run of ``workload`` at its small size; returns the result line's
    object and the driver's outcome."""
    from bench import harness
    ctx, out = drive(workload, seed, seconds, trace, devices)
    return harness.result(ctx, out), out
