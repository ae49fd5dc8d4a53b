"""Run a python snippet in a subprocess with N fake XLA host devices.

The main pytest process must keep the default single CPU device (smoke tests
and benches see 1 device), so every multi-device test executes in its own
subprocess with XLA_FLAGS set before jax initializes.
"""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")
TESTS = str(pathlib.Path(__file__).resolve().parent)


def run_distributed(code: str, ndev: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    # src for the package, tests for the shared assertion helpers
    # (f32_bounds)
    env["PYTHONPATH"] = os.pathsep.join(
        [SRC, TESTS] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"distributed subprocess failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")
    return proc.stdout
