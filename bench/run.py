"""Run one cell of the benchmark on the chip and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration, traffic, limits and metric readers are found
by name from ``BENCHMARK.json``.  The run builds its inputs from
``--seed``, warms every shape the cell uses (set-up), measures for
``--seconds`` seconds, checks the outputs against a plain reference, and
prints as its last line of standard output one JSON object.  It exits
non-zero, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for, and when anything compiled inside the window.  JAX's
compilation cache lives in ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from . import spec  # noqa: E402


def _prepare_environment() -> None:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(spec.ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = str(spec.ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        print("bench: --seed must be >= 0 and --seconds > 0", file=sys.stderr)
        return 2
    try:
        cell = spec.resolve(args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: cannot resolve {args.workload!r}: {e}",
              file=sys.stderr)
        return 2
    _prepare_environment()
    try:
        import jax
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"bench: cannot import the system under test ({e})",
              file=sys.stderr)
        return 2
    from . import harness
    compile_cache.enable()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform}); the "
              f"benchmark measures the chip and has no fallback",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    ctx = harness.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace),
                          devices=list(devices[:cell.chips]), t0=T0,
                          compiles=harness.CompileCounter())
    ctx.step("jax_start_s")
    driver = spec.load_module(cell.driver_file,
                              "bench_driver_" + cell.traffic["driver"])
    return report(ctx, driver.run(ctx))


def report(ctx, out) -> int:
    """Print the run's notes and the numbers compared on standard error,
    then its result line; a window that compiled gives no result."""
    from . import harness
    line = harness.result(ctx, out)
    for note in out.notes:
        harness.log(note)
    harness.log("set-up: " + ", ".join(f"{k} {v:.3f}"
                                       for k, v in ctx.steps.items()))
    if out.window_compiles:
        harness.log(f"bench: {out.window_compiles} compiles inside the "
                    f"measured window; its timings are not the warm "
                    f"program's, so the run gives no result")
        return 3
    for name, v in line["checks"].items():
        harness.log(f"check {name}: {v['value']:.6g} (limit {v['limit']:.6g})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
