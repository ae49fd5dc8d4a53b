"""Closed loop, one caller: back-to-back calls of one entry point on one A.

Each call's outputs are ready before the next call starts.  ``call_ms`` is
the window's milliseconds over the calls it completed.  The outputs of the
window's last call are compared with the plain reference.

Traffic parameters: ``op`` (``sketch`` through ``plan_sketch(n, n, r)
.execute``), ``omega_seed`` (a fixed Omega seed, or null to take the run's
seed).
"""
from __future__ import annotations

import time

from bench import gen, harness, reference, work


def _sharding(devices):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    return NamedSharding(Mesh(np.asarray(devices), ("x",)), P("x", None))


def sketch_call(A, seed, cfg, traffic, devices):
    from repro.plan import plan_sketch
    n, r = cfg["n"], cfg["r"]
    plan = plan_sketch(n, n, r, P=len(devices))
    note = (f"sketch: plan_sketch({n}, {n}, {r}) variant {plan.variant}, "
            f"backend {plan.backend}, blocks {plan.blocks}")
    return (lambda: (plan.execute(A, seed, devices=devices),)), note


CALLS = {"sketch": sketch_call}


def compare(op, out, A, seed, cfg, devices, precision="highest"):
    """The numbers compared: the worst row's gap of each output."""
    ref = reference.dense(A, seed, cfg["r"], precision, devices=devices)
    return {"B_row_gap": reference.worst_row(out[0], ref)}


def run(ctx: harness.Context) -> harness.Outcome:
    import jax
    cfg, traffic = ctx.config, ctx.traffic
    op = traffic["op"]
    omega_seed = traffic.get("omega_seed")
    omega_seed = ctx.seed if omega_seed is None else int(omega_seed)
    A = gen.MATRICES[cfg["matrix"]](ctx.seed, cfg, _sharding(ctx.devices))
    A.block_until_ready()
    ctx.step("inputs_s")
    call, note = CALLS[op](A, omega_seed, cfg, traffic, ctx.devices)
    jax.block_until_ready(call())       # compiles, or loads from the cache
    ctx.step("first_call_s")
    jax.block_until_ready(call())
    ctx.step("warm_call_s")
    setup_s = time.perf_counter() - ctx.t0
    calls = 0
    with ctx.window():
        t_start = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.call"):
                out = call()
                jax.block_until_ready(out)
            calls += 1
            elapsed = time.perf_counter() - t_start
            if elapsed >= ctx.seconds:
                break
    compiles = ctx.compiles.count
    mem = harness.memory_peak(ctx.devices)
    checks = compare(op, out, A, omega_seed, cfg, ctx.devices)
    return harness.Outcome(
        setup_s=setup_s, attempted=calls, failed=0,
        end_to_end={"call_ms": elapsed * 1e3 / calls}, checks=checks,
        memory_peak_bytes=mem, calls=calls, window_compiles=compiles,
        work=work.OPS[op](cfg),
        notes=[note, f"window: {calls} calls in {elapsed:.3f} s, "
                     f"{compiles} compiles inside it"])
