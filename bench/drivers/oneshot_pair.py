"""Closed loop, one caller: back-to-back Nyström pairs of one A.

Each call is ``plan_nystrom(n, r, P).execute(A, omega_seed)`` and returns
(B, C); both are ready before the next call starts.  ``call_ms`` is the
window's milliseconds over the calls it completed.  The window's last
(B, C) is compared with the plain reference (``bench/pair.py``).

Traffic parameters: ``omega_seed`` (a fixed Omega seed, or null to take
the run's seed).
"""
from __future__ import annotations

import time

from bench import gen, harness, pair
from bench.drivers.oneshot import _sharding


def pair_call(A, seed, cfg, devices):
    """The cell's call and a note naming what the planner chose."""
    from repro.kernels.ops import sketch_matmul_launch
    from repro.plan import plan_nystrom
    n, r = cfg["n"], cfg["r"]
    plan = plan_nystrom(n, r, P=len(devices))
    note = (f"nystrom: plan_nystrom({n}, {r}, P={len(devices)}) variant "
            f"{plan.variant}, backend {plan.backend}, blocks {plan.blocks}")
    if plan.variant == "pallas_fused":
        panel = sketch_matmul_launch(n, n, r, **plan.blocks).panel
        note += f", stage 1 on its {'panel' if panel else 'per-step'} path"
    return (lambda: plan.execute(A, seed, devices=devices)), note


def omega_seed(ctx_seed, traffic):
    seed = traffic.get("omega_seed")
    return ctx_seed if seed is None else int(seed)


def run(ctx: harness.Context) -> harness.Outcome:
    import jax
    cfg = ctx.config
    seed = omega_seed(ctx.seed, ctx.traffic)
    A = gen.MATRICES[cfg["matrix"]](ctx.seed, cfg, _sharding(ctx.devices))
    A.block_until_ready()
    ctx.step("inputs_s")
    call, note = pair_call(A, seed, cfg, ctx.devices)
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
    ref = pair.nystrom(A, seed, cfg["r"], devices=ctx.devices)
    checks = pair.compare(out, ref)
    return harness.Outcome(
        setup_s=setup_s, attempted=calls, failed=0,
        end_to_end={"call_ms": elapsed * 1e3 / calls}, checks=checks,
        memory_peak_bytes=mem, calls=calls, window_compiles=compiles,
        work=pair.work(cfg["n"], cfg["r"]),
        notes=[note, f"window: {calls} calls in {elapsed:.3f} s, "
                     f"{compiles} compiles inside it"])
