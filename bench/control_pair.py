"""Readings that set the Nyström pair's correctness limits, on the chip.

    python3 -m bench.control_pair --workload nystrom.dense56k --seeds 1,2,3

The pair's counterpart of ``bench/control.py``: for each seed, at the
cell's own size and in one process, it prints ``B_row_gap`` and
``C_row_gap`` against the plain reference at ``Precision.HIGHEST`` for

  * ``program``: one call of the cell's entry point, the timed path;
  * ``control``: the reference's own pair in the nearest precision below
    the configuration's, the three-pass bfloat16 product
    (``Precision.HIGH`` on the chip), in both stages;
    ``control_emulated`` the same product written out, as the tests run
    it on the CPU;
  * ``control_stage2`` and ``control_stage2_emulated``: the same two
    products in the second stage alone, applied to the reference's B
    (``C_row_gap`` only), as a second stage cut to three passes would
    compute.

A limit lies between the largest program reading over a dozen seeds or
more and the smallest reading of any control; ``bench/limits/<workload>.json``
keeps both with the limit.
"""
from __future__ import annotations

import argparse
import json
import sys

from . import gen, spec
from .run import _prepare_environment


def pair_readings(cell, seed, devices):
    import jax
    from . import pair
    from .drivers import oneshot, oneshot_pair
    cfg = cell.config
    omega_seed = oneshot_pair.omega_seed(seed, cell.traffic)
    A = gen.MATRICES[cfg["matrix"]](seed, cfg, oneshot._sharding(devices))
    call, _ = oneshot_pair.pair_call(A, omega_seed, cfg, devices)
    out = jax.block_until_ready(call())
    ref = pair.nystrom(A, omega_seed, cfg["r"], devices=devices)
    got = {"program": pair.compare(out, ref)}
    del out
    for name, precision in (("control", "high3"),
                            ("control_emulated", "high3_emulated")):
        ctrl = pair.nystrom(A, omega_seed, cfg["r"], precision,
                            devices=devices)
        got[name] = pair.compare(ctrl, ref)
        del ctrl
        c = pair.stage2(ref[0], omega_seed, precision)
        got[name.replace("control", "control_stage2")] = {
            "C_row_gap": pair.compare((ref[0], c), ref)["C_row_gap"]}
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
        print("control_pair: needs the cell's TPU chips", file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    for seed in (int(s) for s in args.seeds.split(",")):
        got = pair_readings(cell, seed, devices)
        print(json.dumps({"workload": cell.name, "seed": seed, **got}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
