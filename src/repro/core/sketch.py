"""Algorithm 1 — communication-optimal parallel B = A·Omega (paper §4.2).

The processor grid is a JAX mesh with three named axes (p1, p2, p3).  The
algorithm is *exactly* the paper's: one All-Gather of A over the p3 fibers,
local regeneration of the Omega block (zero communication — the paper's
point), one local GEMM, one Reduce-Scatter of B over the p2 fibers.

Data layout contract (paper §4.2):
  in : A is evenly divided into a (p1 x p2) grid of blocks; each block A_ij
       is split column-wise across the p3 fiber -> in_specs P(p1, (p2, p3)).
  out: B is evenly divided into a (p1 x p3) grid of blocks; each block B_ik
       is split row-wise across the p2 fiber -> out_specs P((p1, p2), p3).

Omega entries are generated with the Philox-4x32-10 counter-based generator
keyed by *global* coordinates, so every processor-grid decomposition of the
same (seed, n2, r) produces bitwise-identical sketches — the distributed
result equals the single-device reference exactly, which is the executable
form of the paper's regenerate-don't-communicate claim.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import rng
from .compat import shard_map
from .grid import MatmulGrid, select_matmul_grid

DEFAULT_AXES = ("p1", "p2", "p3")

# The precision every sketch GEMM asks for: f32 products on f32 operands,
# the contract the paths state.  On a TPU a default-precision f32 dot
# runs single bf16 passes (about three decimal digits); HIGHEST asks for
# the full f32 product.  XLA:CPU computes f32 dots in f32 either way.
F32 = jax.lax.Precision.HIGHEST

# The kind registry (DENSE_KINDS dense entry distributions applied by
# GEMM; SPARSE_KINDS one-nonzero-per-row families applied in O(nnz) by
# scatter-add) lives in the jax-free core/kinds.py so the plan layer can
# consult it without importing the runtime; re-exported here because this
# module is where executable code looks for it.
from .kinds import (DENSE_KINDS, SPARSE_KINDS,  # noqa: F401,E402
                    VALID_KINDS, validate_kind)


# ---------------------------------------------------------------------------
# Omega tile generation (shared by local + distributed paths)
# ---------------------------------------------------------------------------

def omega_tile(seed, row0, col0, rows: int, cols: int,
               kind: str = "normal", dtype=jnp.float32, salt: int = 0,
               r_total: Optional[int] = None,
               n_total: Optional[int] = None):
    """Tile [row0:row0+rows, col0:col0+cols] of the global Omega.

    Entry values depend only on global coordinates + seed, never on the
    tiling, so this is safe to call from any shard with traced offsets.
    ``seed`` may be traced (see :func:`repro.core.rng.seed_keys`).

    The sparse kinds need the GLOBAL Omega shape, which a tile call does
    not otherwise carry: ``r_total`` is the global column count (the
    bucket modulus; defaults to ``cols``, i.e. a full-width tile — pass
    it explicitly for column sub-tiles) and ``n_total`` the global row
    count (the ``rowsample`` membership probability r_total/n_total;
    defaults to ``rows``, i.e. a full-height tile — row-sliced callers
    like ``stream.state.psi_cols`` pass the stream's n1).  Dense kinds
    ignore both.
    """
    validate_kind(kind)
    keys = rng.seed_keys(seed)
    if kind in DENSE_KINDS:
        return rng.omega_grid(keys, row0, col0, rows, cols, kind,
                              salt).astype(dtype)
    key0, key1 = keys
    if kind == "countsketch":
        t = rng.philox_countsketch_grid(key0, key1, row0, col0, rows, cols,
                                        r_total if r_total is not None
                                        else cols, salt)
    else:  # rowsample
        t = rng.philox_rowsample_grid(key0, key1, row0, col0, rows, cols,
                                      r_total if r_total is not None
                                      else cols,
                                      n_total if n_total is not None
                                      else rows, salt)
    return t.astype(dtype)


def sparse_omega_map(seed, n_rows: int, width: int, kind: str,
                     dtype=jnp.float32, salt: int = 0, row0=0,
                     n_total: Optional[int] = None):
    """Per-row (bucket, value) arrays defining a sparse Omega row range:
    ``Omega[row0 + i, bucket[i]] = value[i]`` for i < n_rows (every other
    entry 0; value 0 means the row was not sampled).  ``width`` is the
    GLOBAL column count of Omega; ``n_total`` its global row count (the
    ``rowsample`` membership denominator — defaults to ``n_rows``, i.e. a
    full-height call; row-sliced callers must pass it); ``row0`` offsets
    the returned range (may be traced).  This is the O(n) form the
    scatter-add apply paths consume — materializing the dense tile is
    :func:`omega_tile`'s job.
    """
    validate_kind(kind)
    if kind not in SPARSE_KINDS:
        raise ValueError(f"kind {kind!r} is dense; sparse_omega_map serves "
                         f"{', '.join(SPARSE_KINDS)}")
    g = (jnp.asarray(row0, jnp.uint32)
         + jax.lax.broadcasted_iota(jnp.uint32, (n_rows,), 0))
    return sparse_omega_rows(seed, g, width, kind, dtype, salt,
                             n_total if n_total is not None else n_rows)


def sparse_omega_rows(seed, g, width: int, kind: str, dtype=jnp.float32,
                      salt: int = 0, n_total: Optional[int] = None):
    """Gather form of :func:`sparse_omega_map`: (bucket, value) draws at an
    arbitrary (possibly repeated, possibly traced) array ``g`` of global
    row indices.  Counter-based, so ``bucket[i]``/``value[i]`` depend only
    on ``g[i]`` — gathering draws per stored entry of a sparse operand is
    bitwise-identical to slicing them out of the full map.  ``n_total`` is
    the global row count of Omega (the rowsample membership denominator;
    required for ``rowsample``).
    """
    validate_kind(kind)
    if kind not in SPARSE_KINDS:
        raise ValueError(f"kind {kind!r} is dense; sparse_omega_rows serves "
                         f"{', '.join(SPARSE_KINDS)}")
    key0, key1 = rng.seed_keys(seed)
    g = jnp.asarray(g, jnp.uint32)
    bucket, sign = rng.philox_countsketch_rows(key0, key1, g, width, salt)
    if kind == "countsketch":
        value = sign
    else:
        import math
        if n_total is None:
            raise ValueError("rowsample draws need n_total (global rows)")
        p = min(1.0, float(width) / float(n_total))
        u = rng.philox_rowsample_uniform(key0, key1, g, salt)
        value = jnp.where(u < np.float32(p),
                          sign * np.float32(1.0 / math.sqrt(p)),
                          jnp.float32(0.0))
    return bucket.astype(jnp.int32), value.astype(dtype)


def sketch_sparse_apply(A, seed, r: int, kind: str = "countsketch",
                        salt: int = 0):
    """B = A @ Omega for a sparse-structured Omega, WITHOUT materializing
    it: one scatter-add per stored entry of A (O(nnz) work — the
    Clarkson-Woodruff property; 2 flops per entry instead of the dense
    GEMM's 2·r).  Bitwise-equal to ``A @ omega_tile(...)`` up to
    summation order (the draws themselves are bitwise; the accumulation
    order differs from a GEMM's), pinned to tolerance by
    tests/test_sparse.py.
    """
    validate_kind(kind)
    if kind not in SPARSE_KINDS:
        raise ValueError(f"kind {kind!r} is dense; use sketch_reference "
                         f"or rand_matmul")
    n2 = A.shape[-1]
    bucket, value = sparse_omega_map(seed, n2, r, kind, A.dtype, salt)
    out = jnp.zeros((*A.shape[:-1], r), A.dtype)
    return out.at[..., bucket].add(A * value)


def sketch_reference(A, seed, r: int, kind: str = "normal",
                     scale: Optional[float] = None):
    """Single-device oracle: B = A @ Omega with the full Omega materialized."""
    validate_kind(kind)
    n2 = A.shape[-1]
    om = omega_tile(seed, 0, 0, n2, r, kind, A.dtype)
    if scale is not None:
        om = om * jnp.asarray(scale, A.dtype)
    return jnp.matmul(A, om, precision=F32)


# ---------------------------------------------------------------------------
# Mesh helpers
# ---------------------------------------------------------------------------

def make_grid_mesh(p1: int, p2: int, p3: int,
                   axis_names: Tuple[str, str, str] = DEFAULT_AXES,
                   devices=None) -> Mesh:
    """A (p1, p2, p3) mesh for the paper's processor grid."""
    if devices is None:
        devices = jax.devices()
    n = p1 * p2 * p3
    if len(devices) < n:
        raise ValueError(f"grid {p1}x{p2}x{p3} needs {n} devices, "
                         f"have {len(devices)}")
    devs = np.asarray(devices[:n]).reshape(p1, p2, p3)
    return Mesh(devs, axis_names)


def input_sharding(mesh: Mesh, axes=DEFAULT_AXES) -> NamedSharding:
    """Sharding of A per the Alg. 1 layout contract."""
    return NamedSharding(mesh, P(axes[0], (axes[1], axes[2])))


def output_sharding(mesh: Mesh, axes=DEFAULT_AXES) -> NamedSharding:
    """Sharding of B per the Alg. 1 layout contract."""
    return NamedSharding(mesh, P((axes[0], axes[1]), axes[2]))


# ---------------------------------------------------------------------------
# Algorithm 1
# ---------------------------------------------------------------------------

def rand_matmul(A, seed, r: int, mesh: Mesh,
                axes: Tuple[str, str, str] = DEFAULT_AXES,
                kind: str = "normal",
                scale: Optional[float] = None,
                precision=None, salt: int = 0,
                backend: str = "auto", blocks=None):
    """B = A @ Omega on the (p1, p2, p3) grid ``mesh`` (paper Alg. 1).

    A must be shardable as P(p1, (p2, p3)); the result is sharded
    P((p1, p2), p3).  Communication: one tiled All-Gather over p3 and one
    tiled Reduce-Scatter over p2 — matching the paper's optimal bandwidth
    ``(1-1/p3)·n1n2/(p1p2) + (1-1/p2)·n1r/(p1p3)`` exactly.

    ``backend`` selects the *local* GEMM body (``repro.kernels.local``):
    ``"jnp"`` materializes the per-shard Omega block in HBM; ``"pallas"``
    generates it in VMEM inside the fused kernel, dropping the n2·r/(p2·p3)
    HBM stream — the memory-roofline analogue of the zero-communication
    claim; ``"auto"`` picks pallas on TPU.  Both backends are bitwise-
    identical wherever the local contraction is not tiled (the interpret-
    mode default — see kernels/local.py).  ``blocks`` optionally fixes the
    Pallas (bm, bn, bk) tile shape (autotunable via plan.autotune).

    The compiled program is cached per (r, mesh, axes, kind, scale,
    precision, backend, blocks) with the seed *traced* as a Philox key
    pair, so repeated calls — any seed, any A of the same shape — reuse
    one executable.  (Eager ``shard_map`` would otherwise pay a
    per-primitive SPMD dispatch on every call, which is minutes for the
    Philox graph.)
    """
    from repro.kernels.local import resolve_backend
    validate_kind(kind)
    if kind in SPARSE_KINDS:
        raise NotImplementedError(
            f"kind {kind!r}: distributed sparse shard_map bodies are "
            f"deferred (ROADMAP item 3) — use sketch_sparse_apply / the "
            f"local streaming paths, or a dense kind here")
    ax1, ax2, ax3 = axes
    p1, p2, p3 = (mesh.shape[a] for a in axes)
    n1, n2 = A.shape
    # n1 % (p1*p2): the output layout P((p1, p2), p3) reduce-scatters each
    # n1/p1 row block p2 ways (previously surfaced as an opaque XLA
    # reduce_scatter divisibility error).
    if n1 % (p1 * p2) or n2 % (p2 * p3) or n2 % p2 or r % p3:
        raise ValueError(f"shape ({n1},{n2},r={r}) not divisible by grid "
                         f"({p1},{p2},{p3})")
    keys = jnp.stack(rng.seed_keys(seed))
    fn = _rand_matmul_prog(r, mesh, tuple(axes), kind,
                           None if scale is None else float(scale),
                           precision, salt, resolve_backend(backend),
                           None if blocks is None else tuple(blocks))
    return fn(A, keys)


# Bounded caches: a long-lived serving process may construct meshes
# dynamically; evicting a program merely costs a recompile on next use.
_PROG_CACHE_SIZE = 64


@functools.lru_cache(maxsize=_PROG_CACHE_SIZE)
def _rand_matmul_prog(r: int, mesh: Mesh, axes: Tuple[str, str, str],
                      kind: str, scale, precision, salt: int,
                      backend: str = "jnp", blocks=None):
    from repro.kernels.local import sketch_block
    ax1, ax2, ax3 = axes
    p2 = mesh.shape[ax2]
    p3 = mesh.shape[ax3]

    def impl(A, keys):
        n2 = A.shape[1]
        blk_rows = n2 // p2   # Omega block rows  (contraction dim)
        blk_cols = r // p3    # Omega block cols

        def body(a_blk):
            j = jax.lax.axis_index(ax2)
            k = jax.lax.axis_index(ax3)
            # All-Gather A_ij over the p3 fiber (tiled along columns).
            if p3 == 1:
                a_ij = a_blk                  # regime-1 grids: no collective
            else:
                a_ij = jax.lax.all_gather(a_blk, ax3, axis=1, tiled=True)
            # Regenerate Omega_jk locally — zero communication.  The
            # backend decides whether the block lives in HBM (jnp) or only
            # in VMEM inside the fused kernel (pallas).
            b_partial = sketch_block(
                a_ij, keys, blk_cols, row0=j * blk_rows, col0=k * blk_cols,
                kind=kind, salt=salt, scale=scale, precision=precision,
                backend=backend, blocks=blocks)
            # Reduce-Scatter B_ik over the p2 fiber (tiled along rows).
            if p2 == 1:
                return b_partial
            return jax.lax.psum_scatter(b_partial, ax2, scatter_dimension=0,
                                        tiled=True)

        kw = {} if backend == "jnp" else {"check_vma": False}
        return shard_map(
            body, mesh=mesh,
            in_specs=P(ax1, (ax2, ax3)),
            out_specs=P((ax1, ax2), ax3), **kw)(A)

    return jax.jit(impl)


def rand_matmul_auto(A, seed: int, r: int, P_procs: Optional[int] = None,
                     kind: str = "normal", devices=None, grid="auto",
                     plan=None, backend: str = "auto", blocks=None):
    """Alg. 1 with the grid chosen automatically.

    grid:
      * ``"auto"`` — the paper's §4.3 optimal grid (``select_matmul_grid``),
        snapped to an executable factorization by the planner when the ideal
        grid does not divide the shape;
      * ``"plan"`` — full cost-model dispatch via :mod:`repro.plan`
        (equivalent to passing ``plan=plan_sketch(...)``);
      * an explicit ``(p1, p2, p3)`` tuple.
    plan: a precomputed :class:`repro.plan.Plan` (wins over ``grid``; its
    backend/blocks decision also wins over the ``backend``/``blocks`` args).
    backend: local GEMM backend (see :func:`rand_matmul`).

    Returns (B, MatmulGrid, mesh).
    """
    from .grid import alg1_bandwidth_words, alg1_latency_hops
    from .lower_bounds import matmul_regime
    validate_kind(kind)
    devices = devices if devices is not None else jax.devices()
    P_procs = P_procs or len(devices)
    n1, n2 = A.shape
    if plan is not None or grid == "plan":
        if plan is None:
            from repro.plan import plan_sketch
            plan = plan_sketch(n1, n2, r, P=P_procs, kind=kind)
        if not plan.executable:
            raise ValueError(
                f"plan {plan.variant!r} for dims={plan.dims}, "
                f"P={plan.n_procs} is analytic-only (no executable grid "
                f"divides the shape)")
        if plan.variant == "alg1" and plan.grid is not None:
            grid = plan.grid
            backend = getattr(plan, "backend", backend) or backend
            if plan.blocks:
                blocks = tuple(plan.blocks[k] for k in ("bm", "bn", "bk"))
        elif plan.variant == "local_xla":
            grid = (1, 1, 1)          # degenerate Alg.-1 grid, same GEMM
        else:
            # kernel variants (pallas_fused) are not mesh programs and are
            # documented as non-bitwise vs the XLA GEMM — don't silently
            # substitute one for the other.
            raise ValueError(f"plan variant {plan.variant!r} is not an "
                             f"Alg.-1 grid plan; call plan.execute instead")
    if grid == "auto":
        g: MatmulGrid = select_matmul_grid(n1, n2, r, P_procs)
        if n1 % (g.p1 * g.p2) or n2 % (g.p2 * g.p3) or n2 % g.p2 or r % g.p3:
            # the §4.3 grid satisfies p_i <= dim_i but not necessarily the
            # entry point's divisibility contract — snap to the cheapest
            # executable factorization (same fallback the planner uses)
            from repro.plan.planner import _best_executable_alg1_grid
            shape = _best_executable_alg1_grid(n1, n2, r, P_procs)
            if shape is None:
                raise ValueError(
                    f"no factorization of P={P_procs} divides "
                    f"({n1}, {n2}, r={r}); pad the shape or change P")
            g = MatmulGrid(*shape, g.regime,
                           alg1_bandwidth_words(n1, n2, r, *shape),
                           alg1_latency_hops(shape[1], shape[2]))
    else:
        p1, p2, p3 = grid
        g = MatmulGrid(p1, p2, p3, matmul_regime(n1, n2, r, P_procs),
                       alg1_bandwidth_words(n1, n2, r, p1, p2, p3),
                       alg1_latency_hops(p2, p3))
    mesh = make_grid_mesh(g.p1, g.p2, g.p3, devices=devices)
    A = jax.device_put(A, input_sharding(mesh))
    return rand_matmul(A, seed, r, mesh, kind=kind, backend=backend,
                       blocks=blocks), g, mesh


# ---------------------------------------------------------------------------
# The anti-pattern, for the Fig.-3 comparison: communicate Omega instead of
# regenerating it.  Only rank (j==0, k==0) "owns" Omega; everyone else
# receives it via All-Gather over (p2, p3) fibers.
# ---------------------------------------------------------------------------

def rand_matmul_communicating(A, seed, r: int, mesh: Mesh,
                              axes: Tuple[str, str, str] = DEFAULT_AXES,
                              kind: str = "normal"):
    """Baseline that COMMUNICATES Omega (paper Fig. 3's losing strategy).

    Omega starts distributed over the full mesh (one copy in the system) and
    is all-gathered by every processor before the local GEMM.  Same result,
    strictly more communication; the tests compare its collective bytes
    with Alg. 1's (``tests/test_sketch_distributed.py``).
    """
    keys = jnp.stack(rng.seed_keys(seed))
    return _rand_matmul_communicating_prog(r, mesh, tuple(axes), kind)(A, keys)


@functools.lru_cache(maxsize=_PROG_CACHE_SIZE)
def _rand_matmul_communicating_prog(r: int, mesh: Mesh,
                                    axes: Tuple[str, str, str], kind: str):
    ax1, ax2, ax3 = axes
    p2 = mesh.shape[ax2]
    p3 = mesh.shape[ax3]

    def impl(A, keys):
        n2 = A.shape[1]
        # Build Omega once, sharded across the whole mesh (the "one copy").
        om_global = omega_tile(keys, 0, 0, n2, r, kind, A.dtype)
        om_sharding = NamedSharding(mesh, P((ax1, ax2, ax3), None))
        om_global = jax.lax.with_sharding_constraint(om_global, om_sharding)

        blk_rows = n2 // p2
        blk_cols = r // p3

        def body(a_blk, om_blk):
            j = jax.lax.axis_index(ax2)
            k = jax.lax.axis_index(ax3)
            a_ij = jax.lax.all_gather(a_blk, ax3, axis=1, tiled=True)
            # Omega arrives over the network instead of being regenerated:
            om_full = jax.lax.all_gather(om_blk, (ax1, ax2, ax3), axis=0,
                                         tiled=True)
            om = jax.lax.dynamic_slice(
                om_full, (j * blk_rows, k * blk_cols), (blk_rows, blk_cols))
            b_partial = jnp.matmul(a_ij, om, precision=F32)
            if p2 == 1:
                return b_partial
            return jax.lax.psum_scatter(b_partial, ax2, scatter_dimension=0,
                                        tiled=True)

        return shard_map(
            body, mesh=mesh,
            in_specs=(P(ax1, (ax2, ax3)), P((ax1, ax2, ax3), None)),
            out_specs=P((ax1, ax2), ax3))(A, om_global)

    return jax.jit(impl)
