"""Benchmark helpers: timing + multi-device subprocess runner."""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import time

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def is_smoke() -> bool:
    """True when REPRO_BENCH_SMOKE=1 (set by ``run.py --smoke`` and the CI
    bench-smoke job): every suite shrinks to tiny shapes and minimal iters
    so one full pass finishes in CI minutes while still walking the exact
    measurement paths.  Subprocess snippets inherit the flag through the
    environment (``run_with_devices`` copies ``os.environ``)."""
    return os.environ.get("REPRO_BENCH_SMOKE", "") == "1"


def pick(full, smoke):
    """``full`` in normal runs, ``smoke`` under REPRO_BENCH_SMOKE=1."""
    return smoke if is_smoke() else full


def time_us(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall time of fn(*args) in microseconds (block_until_ready)."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def run_with_devices(code: str, ndev: int = 8, timeout: int = 900) -> str:
    """Run a snippet in a subprocess with N fake CPU devices; returns
    stdout.  The child is a CPU correctness harness and is told so
    (``JAX_PLATFORMS=cpu``): a parent that has touched JAX holds the
    chip, and a child that reached for it would fail or hang."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{proc.stderr[-2000:]}")
    return proc.stdout


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")
