"""Readings that set a cell's correctness limits, on the chip.

    python3 -m bench.control --workload sketch.dense32k --seeds 1,2,3

For each seed, at the cell's own size and in one process, it prints the
numbers a run compares for two things put in the same place:

  * ``program``: the timed path itself, one call of the cell's entry point;
  * ``control``: the plain reference computed in the nearest precision
    below the configuration's, the three-pass bfloat16 product
    (``Precision.HIGH`` on the chip); ``control_emulated`` the same product
    written out, as the tests run it on the CPU.

A limit lies between the largest program reading over a dozen seeds or
more (the lower reading) and the smallest control reading (the upper);
``bench/limits/<workload>.json`` keeps both with the limit.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

from . import gen, spec
from .run import _prepare_environment


def oneshot_readings(cell, seed, devices):
    import jax
    from . import reference
    from .drivers import oneshot
    cfg, traffic = cell.config, cell.traffic
    op = traffic["op"]
    omega_seed = traffic.get("omega_seed")
    omega_seed = seed if omega_seed is None else int(omega_seed)
    A = gen.MATRICES[cfg["matrix"]](seed, cfg, oneshot._sharding(devices))
    call, _ = oneshot.CALLS[op](A, omega_seed, cfg, traffic, devices)
    out = jax.block_until_ready(call())
    program = oneshot.compare(op, out, A, omega_seed, cfg, devices)
    del out
    got = {"program": program}
    for name, precision in (("control", "high3"),
                            ("control_emulated", "high3_emulated")):
        ctrl = (reference.dense(A, omega_seed, cfg["r"], precision,
                                devices=devices),)
        got[name] = oneshot.compare(op, ctrl, A, omega_seed, cfg, devices)
        del ctrl
    return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    _prepare_environment()
    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    for seed in (int(s) for s in args.seeds.split(",")):
        got = oneshot_readings(cell, seed, devices)
        print(json.dumps({"workload": cell.name, "seed": seed, **got}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
