"""One pass of the sketching system's main path on a TPU, checked.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the four chips of a 2x2 host

One chip runs three phases, each through the entry point a user calls:

  * sketch  — ``plan_sketch(n, n, r).execute(A, seed)``, A 32768x32768 f32
              (4 GiB), r = 256;
  * nystrom — ``nystrom_auto(A, seed, r)``, A a symmetric 32768x32768 f32
              kernel matrix, r = 256;
  * stream  — 1024 tenants of ``StreamConfig(n1=4096, n2=768, r=48)``
              (about 1 GiB of resident sketch state) behind
              ``make_sketch_service`` + ``make_ingest_queue``: a few hundred
              1-64-row slabs from Zipf-skewed tenants, then a few streams
              finalized.

``--four-chips`` runs only the paths that exist across chips: Alg. 1
(``rand_matmul``) on a (4,1,1) and a (1,2,2) grid with 2 GiB of A per chip,
the fused two-grid Nyström with P = 4, and a ``ShardedStreamingSketch``
resharded 4 -> 2 -> 4 chips, compared bitwise with the run never resharded.

Every input is generated on the device from ``--seed``.  Each phase is
compared with a plain float32 reference computed under
``jax.default_matmul_precision("highest")`` and prints its variant and
backend, whether ``tpu_custom_call`` is in its compiled program (required
when the backend is Pallas), its error against the tolerance, and its wall
times, labelled set-up or informational.  The script exits non-zero, with
no result line, when JAX finds no TPU, when the repository's package is not
beside it, or when any phase fails.  Its last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

# Relative Frobenius error allowed against the f32 reference.  Both sides
# form the same f32 products and add them in different orders (MXU tiles
# against XLA's dot, a vmapped lane against one GEMM), so the expected
# error is about u*sqrt(n) (u = 2^-24): ~1e-5 for the n = 32768
# contractions here, less for the stream's n2 = 768.  Products taken in
# one bf16 pass instead of f32 would be off by ~1e-3.  1e-4 holds the f32
# contract and catches a precision loss.
F32_TOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


class Clock:
    """Wall-time sections, each labelled set-up or informational."""

    def __init__(self):
        self.parts = []

    def section(self, label: str, kind: str, fn):
        t0 = time.perf_counter()
        out = fn()
        import jax
        jax.block_until_ready(out)
        self.parts.append((label, kind, time.perf_counter() - t0))
        return out

    def text(self) -> str:
        return ", ".join(f"{lab} {dt:.3f} s ({kind})"
                         for lab, kind, dt in self.parts)


def rel_err(x, ref) -> float:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x, ref):
        x = x.astype(jnp.float32)
        ref = ref.astype(jnp.float32)
        return jnp.linalg.norm(x - ref) / jnp.linalg.norm(ref)
    return float(f(x, ref))


def has_kernel(fn, *args) -> bool:
    """Whether the compiled program of ``fn(*args)`` calls a Mosaic
    kernel (``tpu_custom_call``)."""
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def normal(seed: int, shape, sharding=None):
    """f32 N(0, 1) entries made on the device (placed by ``sharding``)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32),
                out_shardings=sharding)
    return f(jax.random.key(seed))


def kernel_matrix(seed: int, n: int, d: int = 4, sharding=None):
    """A symmetric positive-definite n x n f32 matrix made on the device:
    the L1-Laplace kernel exp(-|x_i - x_j|_1 / d) of n Gaussian points in
    d dimensions.  Entry (i, j) and (j, i) are the same operations on the
    same numbers, so A is exactly symmetric."""
    import jax
    import jax.numpy as jnp

    def f(k):
        x = jax.random.normal(k, (n, d), jnp.float32)
        dist = jnp.sum(jnp.abs(x[:, None, :] - x[None, :, :]), axis=-1)
        return jnp.exp(-dist / d)
    return jax.jit(f, out_shardings=sharding)(jax.random.key(seed))


def check(name: str, err: float, tol: float, kernel_needed: bool = False,
          kernel=None) -> bool:
    """Log one comparison; ``kernel`` None means the line checks numbers
    only (the phase checks its program elsewhere)."""
    ok = err <= tol and (kernel or not kernel_needed)
    ktext = ("" if kernel is None else
             f"; tpu_custom_call {'present' if kernel else 'absent'}"
             f"{' (required: Pallas backend)' if kernel_needed else ''}")
    log(f"[{name}] error {err:.3e} against tolerance {tol:.0e}{ktext} -> "
        f"{'PASS' if ok else 'FAIL'}")
    return ok


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_sketch(seed: int, n: int = 32768, r: int = 256) -> bool:
    """B = A·Omega through the planner's chosen single-device variant."""
    import jax
    from repro.core.sketch import sketch_reference
    from repro.plan import plan_sketch

    clk = Clock()
    A = clk.section("make A", "set-up", lambda: normal(seed, (n, n)))
    plan = plan_sketch(n, n, r)
    log(f"[sketch] A {n}x{n} f32, r={r}: variant {plan.variant}, "
        f"backend {plan.backend}, blocks {plan.blocks}")
    kernel = clk.section("compile (kernel check)", "set-up",
                         lambda: has_kernel(lambda a: plan.execute(a, seed),
                                            A))
    clk.section("first execute (compiles)", "set-up",
                lambda: plan.execute(A, seed))
    B = clk.section("execute", "informational",
                    lambda: plan.execute(A, seed))
    with jax.default_matmul_precision("highest"):
        ref = clk.section("reference", "set-up", lambda: jax.jit(
            sketch_reference, static_argnums=(1, 2))(A, seed, r))
    ok = check("sketch", rel_err(B, ref), F32_TOL,
               plan.backend == "pallas", kernel)
    log(f"[sketch] wall: {clk.text()}")
    return ok


def phase_nystrom(seed: int, n: int = 32768, r: int = 256) -> bool:
    """(B, C) of a symmetric A through ``nystrom_auto``."""
    import jax
    from repro.core.nystrom import nystrom_auto, nystrom_reference
    from repro.kernels.local import resolve_backend

    clk = Clock()
    A = clk.section("make A", "set-up", lambda: kernel_matrix(seed, n))
    backend = resolve_backend("auto")
    kernel = clk.section(
        "compile (kernel check)", "set-up",
        lambda: has_kernel(lambda a: nystrom_auto(a, seed, r)[:2], A))
    first = clk.section("first call (compiles)", "set-up",
                        lambda: nystrom_auto(A, seed, r))
    variant = first[3]
    del first
    B, C = clk.section("call", "informational",
                       lambda: nystrom_auto(A, seed, r)[:2])
    log(f"[nystrom] A {n}x{n} f32 symmetric, r={r}: variant {variant}, "
        f"backend {backend}")
    with jax.default_matmul_precision("highest"):
        Bref, Cref = clk.section("reference", "set-up", lambda: jax.jit(
            nystrom_reference, static_argnums=(1, 2))(A, seed, r))
    ok = check("nystrom B", rel_err(B, Bref), F32_TOL,
               backend == "pallas", kernel)
    ok &= check("nystrom C", rel_err(C, Cref), F32_TOL,
                backend == "pallas", kernel)
    log(f"[nystrom] wall: {clk.text()}")
    return ok


def phase_stream(seed: int, tenants: int = 1024, n1: int = 4096,
                 n2: int = 768, r: int = 48, slabs: int = 384,
                 max_rows: int = 64, finalize: int = 4) -> bool:
    """Multi-tenant ragged ingest through the service and its queue."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.sketch import sketch_reference
    from repro.obs import install_ledger, uninstall_ledger
    from repro.serve.engine import make_ingest_queue, make_sketch_service
    from repro.stream.state import StreamConfig, psi_matrix

    clk = Clock()
    svc = make_sketch_service()
    cfgs = [StreamConfig(n1=n1, n2=n2, r=r, seed=seed * 4099 + t)
            for t in range(tenants)]
    sids = clk.section("open streams", "set-up",
                       lambda: [svc.open(c) for c in cfgs])
    resident = tenants * 4 * (n1 * r + cfgs[0].sketch_l * n2)
    log(f"[stream] {tenants} tenants of StreamConfig(n1={n1}, n2={n2}, "
        f"r={r}), resident sketch state {resident / 2 ** 30:.3f} GiB; "
        f"variant update_ragged behind IngestQueue, fold backend "
        f"{svc.backend}")
    # traffic: Zipf-skewed tenants, uniform 1..max_rows heights, uniform
    # offsets; payloads made on the device in one call, fetched once, and
    # sliced on the host the way clients send them
    rng = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, tenants + 1) ** 1.1
    who = rng.choice(tenants, size=slabs, p=w / w.sum())
    ks = rng.integers(1, max_rows + 1, size=slabs)
    row0s = [int(rng.integers(0, n1 - k + 1)) for k in ks]
    bulk = clk.section("make payloads", "set-up", lambda: np.asarray(
        normal(seed + 1, (slabs, max_rows, n2))))
    q = make_ingest_queue(svc, depth=256, window=64,
                          expected_ks=[int(k) for k in ks])
    log(f"[stream] {slabs} slabs, bucket edges {q.bucket_edges}")
    sent = {}

    def send():
        for i in range(slabs):
            H = bulk[i, :ks[i]]
            q.submit(sids[who[i]], H, row0s[i])
            sent.setdefault(int(who[i]), []).append((row0s[i], H))
        q.flush(raise_errors=True)
        svc.sync()
        return []
    led = install_ledger()
    try:
        clk.section("ingest (compiles each bucket program on first use)",
                    "informational", send)
    finally:
        uninstall_ledger()
    st = q.stats()
    log(f"[stream] {st['rounds']} fused rounds, {svc.num_compiled} "
        f"compiled update programs, pad waste {st['pad_waste']:.1%}")
    hot = sorted(sent, key=lambda t: -len(sent[t]))[:finalize]
    ok = True
    for t in hot:
        Y, W = q.close_stream(sids[t])
        A = np.zeros((n1, n2), np.float32)
        for row0, H in sent[t]:
            A[row0:row0 + H.shape[0]] += H
        with jax.default_matmul_precision("highest"):
            Yref = sketch_reference(jnp.asarray(A), cfgs[t].seed, r)
            Wref = jnp.matmul(psi_matrix(cfgs[t]), jnp.asarray(A))
        ok &= check(f"stream tenant {t} ({len(sent[t])} slabs) Y",
                    rel_err(Y, Yref), F32_TOL)
        ok &= check(f"stream tenant {t} W", rel_err(W, Wref), F32_TOL)
    q.shutdown()
    # the kernel check reads every update program the window dispatched:
    # the ledger keeps one site per executable signature (bucket height x
    # lane count), re-lowered at exactly the shapes that ran
    sites = [s for s in led.sites() if s.name == "service.update_ragged"]
    ok &= bool(sites)
    needed = svc.backend == "pallas"
    for s in sorted(sites, key=lambda s: s.sig[2][0]):
        lanes, kb = s.sig[2][0][:2]
        kernel = "tpu_custom_call" in s.compiled_text()
        ok &= kernel or not needed
        log(f"[stream] update program bucket {kb} x {lanes} lanes "
            f"({s.calls} calls): tpu_custom_call "
            f"{'present' if kernel else 'absent'}"
            f"{' (required: Pallas backend)' if needed else ''}")
    log(f"[stream] wall: {clk.text()}")
    return ok


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def spread(x, devices) -> bool:
    """Whether ``x`` has one shard on each of ``devices``."""
    got = sorted(s.device.id for s in x.addressable_shards)
    return got == sorted(d.id for d in devices)


def phase_alg1_grids(seed: int, devs, n1: int = 32768, n2: int = 65536,
                     r: int = 256) -> bool:
    """Alg. 1 on (4,1,1) (no collectives) and (1,2,2) (all-gather +
    reduce-scatter), 2 GiB of A per chip."""
    import jax
    from repro.core.sketch import (input_sharding, make_grid_mesh,
                                   rand_matmul, sketch_reference)
    from repro.kernels.local import resolve_backend
    from repro.roofline.hlo import collective_bytes_of

    ok = True
    backend = resolve_backend("auto")
    for grid in ((4, 1, 1), (1, 2, 2)):
        clk = Clock()
        mesh = make_grid_mesh(*grid, devices=devs)
        A = clk.section("make A", "set-up",
                        lambda: normal(seed, (n1, n2), input_sharding(mesh)))
        hlo = clk.section("compile", "set-up", lambda: jax.jit(
            lambda a: rand_matmul(a, seed, r, mesh)).lower(A).compile()
            .as_text())
        cb = collective_bytes_of(hlo)
        B = clk.section("first call (compiles)", "set-up",
                        lambda: rand_matmul(A, seed, r, mesh))
        B = clk.section("call", "informational",
                        lambda: rand_matmul(A, seed, r, mesh))
        with jax.default_matmul_precision("highest"):
            ref = clk.section("reference", "set-up", lambda: jax.jit(
                sketch_reference, static_argnums=(1, 2))(A, seed, r))
        sp = spread(A, devs) and spread(B, devs)
        log(f"[alg1 {grid}] A {n1}x{n2} f32 ({n1 * n2 * 4 / 4 / 2 ** 30:.1f}"
            f" GiB per chip), r={r}, backend {backend}; collectives "
            f"{dict(cb.counts)}; shards on 4 chips: {sp}")
        ok &= sp
        ok &= check(f"alg1 {grid}", rel_err(B, ref), F32_TOL,
                    backend == "pallas", "tpu_custom_call" in hlo)
        log(f"[alg1 {grid}] wall: {clk.text()}")
        del A, B, ref
    return ok


def phase_nystrom_fused(seed: int, devs, n: int = 32768,
                        r: int = 256) -> bool:
    """The single-jit two-grid Nyström on P = 4."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.nystrom import nystrom_auto, nystrom_reference
    from repro.plan import plan_nystrom

    clk = Clock()
    rows = NamedSharding(Mesh(np.asarray(devs), ("x",)), P("x", None))
    A = clk.section("make A", "set-up",
                    lambda: kernel_matrix(seed, n, sharding=rows))
    plan = plan_nystrom(n, r, P=4, variant="bound_driven_fused")
    log(f"[nystrom P=4] A {n}x{n} f32 symmetric, r={r}: variant "
        f"{plan.variant}, p={plan.grid}, q={plan.q_grid}, backend "
        f"{plan.backend}")

    def run():
        return nystrom_auto(A, seed, r, plan=plan, devices=devs)[:2]
    kernel = clk.section("compile (kernel check)", "set-up",
                         lambda: has_kernel(lambda a: nystrom_auto(
                             a, seed, r, plan=plan, devices=devs)[:2], A))
    clk.section("first call (compiles)", "set-up", run)
    B, C = clk.section("call", "informational", run)
    with jax.default_matmul_precision("highest"):
        Bref, Cref = clk.section("reference", "set-up", lambda: jax.jit(
            nystrom_reference, static_argnums=(1, 2))(A, seed, r))
    sp = spread(B, devs)
    log(f"[nystrom P=4] B shards on 4 chips: {sp}")
    ok = sp
    ok &= check("nystrom P=4 B", rel_err(B, Bref), F32_TOL,
                plan.backend == "pallas", kernel)
    ok &= check("nystrom P=4 C", rel_err(C, Cref), F32_TOL,
                plan.backend == "pallas", kernel)
    log(f"[nystrom P=4] wall: {clk.text()}")
    return ok


def phase_reshard(seed: int, devs, n1: int = 32768, n2: int = 4096,
                  r: int = 256, k: int = 1024, slabs: int = 8) -> bool:
    """A sharded stream resharded 4 -> 2 -> 4 chips mid-stream must
    finalize bitwise the never-resharded run."""
    import jax
    import numpy as np
    from repro.core.sketch import make_grid_mesh
    from repro.stream import ShardedStreamingSketch, StreamConfig
    from repro.stream.elastic import reshard_stream

    clk = Clock()
    cfg = StreamConfig(n1=n1, n2=n2, r=r, seed=seed)
    rng = np.random.default_rng(seed)
    bulk = clk.section("make payloads", "set-up", lambda: np.asarray(
        normal(seed + 2, (slabs, k, n2))))
    row0s = [int(rng.integers(0, n1 - k + 1)) for _ in range(slabs)]
    mesh4 = make_grid_mesh(4, 1, 1, devices=devs)

    def never():
        ref = ShardedStreamingSketch(cfg, mesh4)
        for i in range(slabs):
            ref.update_rows(row0s[i], bulk[i])
        return ref
    ref = clk.section("never-resharded run (compiles)", "set-up",
                      lambda: never())

    def resized():
        sk = ShardedStreamingSketch(cfg, mesh4)
        for i in range(slabs):
            if i == slabs // 3:
                sk = reshard_stream(sk, (2, 1, 1), devices=devs)
            if i == 2 * slabs // 3:
                sk = reshard_stream(sk, (4, 1, 1), devices=devs)
            sk.update_rows(row0s[i], bulk[i])
        return sk
    sk = clk.section("4 -> 2 -> 4 run", "informational", resized)
    jax.block_until_ready((ref.Y, ref.W, sk.Y, sk.W))
    same_y = np.array_equal(np.asarray(sk.Y), np.asarray(ref.Y))
    same_w = np.array_equal(np.asarray(sk.W), np.asarray(ref.W))
    sp = spread(sk.Y, devs)
    log(f"[reshard] StreamConfig(n1={n1}, n2={n2}, r={r}), {slabs} slabs "
        f"of {k} rows, backend {sk.backend}; Y shards on 4 chips: {sp}; "
        f"4 -> 2 -> 4 vs never resharded: Y bitwise {same_y}, W bitwise "
        f"{same_w} -> {'PASS' if same_y and same_w and sp else 'FAIL'}")
    log(f"[reshard] wall: {clk.text()}")
    return same_y and same_w and sp


# ---------------------------------------------------------------------------

def compile_counter():
    """Counts of JAX's persistent-cache hits and misses in this process."""
    import jax
    counts = {"hits": 0, "misses": 0}

    def on_event(name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1
    jax.monitoring.register_event_listener(on_event)
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip paths (2x2 host)")
    args = ap.parse_args(argv)
    try:
        import jax
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the sketching package ({e}); "
              f"run from the repository root", file=sys.stderr)
        return 2
    cache_dir = compile_cache.enable()
    counts = compile_counter()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); this "
              f"script measures the chip and has no CPU fallback",
              file=sys.stderr)
        return 2
    log(f"devices: {len(devices)} x {dev.device_kind}; compile cache "
        f"{cache_dir}")
    if args.four_chips:
        if len(devices) < 4:
            print(f"chip_smoke: --four-chips needs 4 TPUs, have "
                  f"{len(devices)}", file=sys.stderr)
            return 2
        devs = devices[:4]
        phases = [("alg1 grids", lambda: phase_alg1_grids(args.seed, devs)),
                  ("nystrom P=4",
                   lambda: phase_nystrom_fused(args.seed, devs)),
                  ("reshard 4->2->4", lambda: phase_reshard(args.seed, devs))]
    else:
        phases = [("sketch", lambda: phase_sketch(args.seed)),
                  ("nystrom", lambda: phase_nystrom(args.seed)),
                  ("stream", lambda: phase_stream(args.seed))]
    ok = True
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            good = run()
        except Exception:
            traceback.print_exc()
            good = False
        log(f"== phase {name}: {'PASS' if good else 'FAIL'} "
            f"({time.perf_counter() - t0:.1f} s wall, set-up included)")
        ok &= good
    log(f"compile cache: {counts['hits']} hits, {counts['misses']} misses")
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
