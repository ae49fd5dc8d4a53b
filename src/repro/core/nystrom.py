"""Algorithm 2 — parallel Nyström approximation (paper §5).

Computes the pair  B = A·Omega  (n x r)  and  C = Omega^T·B  (r x r)  for a
symmetric A (n x n), then reconstructs  Ã = B · C† · B^T.

Two 1-D variants exactly as implemented in the paper (§5.3, Fig. 1):

  * ``no_redist`` — p = q = (P, 1, 1).  A is row-sharded; every processor
    regenerates the full Omega; B_i = A_i·Omega needs no communication; the
    second product is a partial-sum C_i = Omega_i^T·B_i reduced with one
    Reduce-Scatter of O(r^2) words.  Best when P < n/r.

  * ``redist`` — p = (P, 1, 1), q = (1, 1, P).  Same first stage, then B is
    re-laid out row-sharded -> column-sharded with one All-to-All of
    O(nr/P) words per processor, and the second product is entirely local.
    Best when P > n/r (the paper's empirical crossover, Fig. 7).

Plus two general two-grid forms of §5.3:

  * ``nystrom_general`` — one mesh: the (q1,q2,q3) grid is a permutation of
    the mesh axes, with XLA inserting the B redistribution (§5.2's
    ``Redistribute``) via a sharding constraint.
  * ``nystrom_two_grid`` — two independent factorizations of the same P
    devices (the form Theorem 3's bound-driven grids take): Alg. 1 on a
    p-grid mesh, an explicit cross-grid redistribution of B (<= nr/P words
    per processor), then the second multiply on a q-grid mesh.  This is the
    executable form of §5.3 approach 1, dispatched by the planner's
    ``alg2_bound_driven`` plans.
  * ``nystrom_two_grid_fused`` — the same algorithm compiled into ONE
    executable: both stages plus the §5.2 ``Redistribute`` (expressed as an
    in-program resharding) over one mesh whose device order serves both
    grids (``core.grid.two_grid_shared_mesh``), so XLA can schedule and
    overlap the redistribution instead of paying ``nystrom_two_grid``'s
    host-mediated ``device_put``.  Dispatched by ``alg2_bound_driven_fused``
    plans; falls back to the cross-mesh path when no shared mesh exists.

The second stages are factored out (``nystrom_second_stage_no_redist`` /
``nystrom_second_stage_redist``) so they can consume any row-sharded B —
the one-shot variants above produce B with the zero-communication first
stage, and the streaming subsystem (``repro.stream``) feeds its accumulated
Y straight into the same code at finalize time.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs import ledger as obs_ledger
from repro.obs import trace as obs_trace

from .compat import shard_map
from .sketch import (DEFAULT_AXES, F32, _PROG_CACHE_SIZE, SPARSE_KINDS,
                     input_sharding, make_grid_mesh, omega_tile, rand_matmul,
                     validate_kind)
from .rng import seed_keys

X_AXIS = "x"


def _check_dense_kind(kind: str) -> None:
    """Eagerly reject bad/sparse kinds before any tracing or device work."""
    validate_kind(kind)
    if kind in SPARSE_KINDS:
        raise NotImplementedError(
            f"omega kind {kind!r}: distributed sparse shard_map bodies are "
            "deferred (ROADMAP item 3); use nystrom_reference, "
            "sketch_sparse_apply, or the local streaming path")


def _fused_audit(n: int, r: int, p, q, backend: str):
    """(predicted words, Theorem-3 floor) of the fused two-grid program —
    the ledger's reference numbers.  The prediction is
    ``plan.model.alg2_fused_cost``: stage collectives plus the in-program
    §5.2 Redistribute min-cut (stage 1 contributes zero words on the
    streamed-finalize (P, 1, 1) p-grid)."""
    from repro.plan import model as M
    from .lower_bounds import nystrom_lower_bound
    try:
        floor = nystrom_lower_bound(n, r, p[0] * p[1] * p[2])
    except ValueError:                  # paper assumes r < n
        floor = 0.0
    return float(M.alg2_fused_cost(n, r, tuple(p), tuple(q),
                                   backend=backend).words), float(floor)


# ---------------------------------------------------------------------------
# Reference (single device)
# ---------------------------------------------------------------------------

def nystrom_reference(A, seed: int, r: int, kind: str = "normal"):
    """(B, C) on one device with the same Philox Omega as distributed runs."""
    validate_kind(kind)
    n = A.shape[0]
    om = omega_tile(seed, 0, 0, n, r, kind, A.dtype)
    B = jnp.matmul(A, om, precision=F32)
    C = jnp.matmul(om.T, B, precision=F32)
    return B, C


def _default_rcond(dtype) -> float:
    """Paper §6.2 uses 1e-12 — appropriate for their FP64 runs.  In reduced
    precision the cutoff must sit above the noise floor of the dtype."""
    if dtype == jnp.float64:
        return 1e-12
    return 1e-6


def reconstruct(B, C, rcond: Optional[float] = None):
    """Ã = B C† B^T with a numerically-tolerant pseudoinverse.

    C = Omega^T A Omega is symmetric (A symmetric), so the pseudoinverse is
    computed by eigendecomposition with a relative eigenvalue cutoff —
    cheaper and more stable than SVD-based pinv for the PSD-dominated case.
    """
    rcond = _default_rcond(C.dtype) if rcond is None else rcond
    Cs = (C + C.T) / 2
    w, V = jnp.linalg.eigh(Cs)
    cutoff = rcond * jnp.max(jnp.abs(w))
    w_inv = jnp.where(jnp.abs(w) > cutoff, 1.0 / w, 0.0)
    Cd = (V * w_inv[None, :]) @ V.T
    return B @ Cd @ B.T


def relative_error(A, B, C, rcond: Optional[float] = None):
    """|| A - Ã ||_F / || A ||_F  (the paper's Tab. 2 metric)."""
    At = reconstruct(B, C, rcond)
    return jnp.linalg.norm(A - At) / jnp.linalg.norm(A)


# ---------------------------------------------------------------------------
# First stage (shared): B_i = A_i·Omega on a 1-D row-sharded layout
# ---------------------------------------------------------------------------

def _sketch_rows_1d(A, seed, r: int, mesh: Mesh, axis: str, kind: str,
                    backend: str = "jnp", blocks=None):
    """B = A·Omega with A row-sharded; every rank regenerates the full Omega
    (zero communication — the Case-1 grid p=(P,1,1) of Alg. 1)."""
    keys = jnp.stack(seed_keys(seed))
    return _sketch_rows_1d_prog(r, mesh, axis, kind, backend, blocks)(A, keys)


@functools.lru_cache(maxsize=_PROG_CACHE_SIZE)
def _sketch_rows_1d_prog(r: int, mesh: Mesh, axis: str, kind: str,
                         backend: str = "jnp", blocks=None):
    from repro.kernels.local import sketch_block

    def impl(A, keys):
        def body(a_i):                            # a_i: (n/P, n2)
            # full Omega consumed locally; the pallas backend never
            # materializes it in HBM (kernels/local.py)
            return sketch_block(a_i, keys, r, kind=kind, backend=backend,
                                blocks=blocks)    # (n/P, r) — no comm

        kw = {} if backend == "jnp" else {"check_vma": False}
        return shard_map(body, mesh=mesh,
                         in_specs=P(axis, None), out_specs=P(axis, None),
                         **kw)(A)

    return jax.jit(impl)


# ---------------------------------------------------------------------------
# Second stages (shared with the streaming subsystem, repro.stream):
# C = Omega^T·B from a row-sharded B.  The streaming accumulator finalizes
# its Nyström pair by feeding the accumulated Y (= B) straight into these.
# ---------------------------------------------------------------------------

def nystrom_second_stage_no_redist(B, seed, r: int, mesh: Mesh,
                                   axis: str = X_AXIS, kind: str = "normal",
                                   salt: int = 0, backend: str = "jnp",
                                   blocks=None):
    """No-Redist second stage: C = Omega^T·B with B row-sharded (§5.3).

    Each rank forms the partial product Omega_i^T·B_i against its local row
    block and one Reduce-Scatter of r^2 words produces C row-sharded —
    B never moves.  Omega_i is regenerated from global coordinates, so this
    composes bitwise with any producer of B (one-shot or streamed).
    ``backend``: local GEMM body (kernels/local.py) — the pallas backend
    keeps Omega_i out of HBM too.
    """
    _check_dense_kind(kind)
    from repro.kernels.local import resolve_backend
    Pn = mesh.shape[axis]
    n = B.shape[0]
    if n % Pn or r % Pn:
        raise ValueError(f"n={n}, r={r} must divide P={Pn}")
    keys = jnp.stack(seed_keys(seed))
    return _second_stage_no_redist_prog(
        r, mesh, axis, kind, salt, resolve_backend(backend),
        None if blocks is None else tuple(blocks))(B, keys)


@functools.lru_cache(maxsize=_PROG_CACHE_SIZE)
def _second_stage_no_redist_prog(r: int, mesh: Mesh, axis: str, kind: str,
                                 salt: int, backend: str = "jnp",
                                 blocks=None):
    from repro.kernels.local import sketch_t_block
    Pn = mesh.shape[axis]

    def impl(B, keys):
        rows = B.shape[0] // Pn

        def body(b_i):                            # b_i: (n/P, r2)
            i = jax.lax.axis_index(axis)
            c_part = sketch_t_block(b_i, keys, r, row0=i * rows, kind=kind,
                                    salt=salt, backend=backend,
                                    blocks=blocks)    # (r, r2) partial sum
            return jax.lax.psum_scatter(c_part, axis, scatter_dimension=0,
                                        tiled=True)   # (r/P, r2)

        kw = {} if backend == "jnp" else {"check_vma": False}
        return shard_map(body, mesh=mesh,
                         in_specs=P(axis, None), out_specs=P(axis, None),
                         **kw)(B)

    return jax.jit(impl)


def nystrom_second_stage_redist(B, seed, r: int, mesh: Mesh,
                                axis: str = X_AXIS, kind: str = "normal",
                                salt: int = 0, backend: str = "jnp",
                                blocks=None):
    """Redist second stage: re-lay out B and finish locally (§5.3).

    One All-to-All moves nr/P words per processor (row-shard -> column-shard
    re-layout of B); the product C = Omega^T·B is then entirely local.
    Returns (B column-sharded, C column-sharded).
    """
    _check_dense_kind(kind)
    from repro.kernels.local import resolve_backend
    Pn = mesh.shape[axis]
    n = B.shape[0]
    if n % Pn or r % Pn:
        raise ValueError(f"n={n}, r={r} must divide P={Pn}")
    keys = jnp.stack(seed_keys(seed))
    return _second_stage_redist_prog(
        r, mesh, axis, kind, salt, resolve_backend(backend),
        None if blocks is None else tuple(blocks))(B, keys)


@functools.lru_cache(maxsize=_PROG_CACHE_SIZE)
def _second_stage_redist_prog(r: int, mesh: Mesh, axis: str, kind: str,
                              salt: int, backend: str = "jnp", blocks=None):
    from repro.kernels.local import sketch_t_block

    def impl(B, keys):
        def body(b_i):                            # b_i: (n/P, r)
            # Redistribute B: rows-sharded -> cols-sharded (All-to-All).
            b_k = jax.lax.all_to_all(b_i, axis, split_axis=1, concat_axis=0,
                                     tiled=True)  # (n, r/P)
            c_k = sketch_t_block(b_k, keys, r, kind=kind, salt=salt,
                                 backend=backend, blocks=blocks)
            return b_k, c_k                       # (r, r/P) — local

        kw = {} if backend == "jnp" else {"check_vma": False}
        return shard_map(body, mesh=mesh,
                         in_specs=P(axis, None),
                         out_specs=(P(None, axis), P(None, axis)), **kw)(B)

    return jax.jit(impl)


# ---------------------------------------------------------------------------
# 1-D No-Redist  (p = q = (P,1,1))
# ---------------------------------------------------------------------------

def nystrom_no_redist(A, seed, r: int, mesh: Mesh,
                      axis: str = X_AXIS, kind: str = "normal",
                      backend: str = "auto", blocks=None):
    """Paper's No-Redist variant.

    in : A row-sharded P(x, None)
    out: B row-sharded P(x, None); C row-sharded P(x, None)
    comm: one Reduce-Scatter of r^2 words (the (1-1/P)·r^2 term).
    backend: local GEMM body for both stages (kernels/local.py).
    """
    _check_dense_kind(kind)
    from repro.kernels.local import resolve_backend
    backend = resolve_backend(backend)
    blocks = None if blocks is None else tuple(blocks)
    Pn = mesh.shape[axis]
    n = A.shape[0]
    if n % Pn or r % Pn:
        raise ValueError(f"n={n}, r={r} must divide P={Pn}")
    B = _sketch_rows_1d(A, seed, r, mesh, axis, kind, backend, blocks)
    C = nystrom_second_stage_no_redist(B, seed, r, mesh, axis, kind,
                                       backend=backend, blocks=blocks)
    return B, C


# ---------------------------------------------------------------------------
# 1-D Redist  (p = (P,1,1), q = (1,1,P))
# ---------------------------------------------------------------------------

def nystrom_redist(A, seed, r: int, mesh: Mesh,
                   axis: str = X_AXIS, kind: str = "normal",
                   backend: str = "auto", blocks=None):
    """Paper's Redist variant.

    in : A row-sharded P(x, None)
    out: B column-sharded P(None, x); C column-sharded P(None, x)
    comm: one All-to-All moving nr/P words per processor (B row-shard ->
    column-shard re-layout), second multiply fully local.
    backend: local GEMM body for both stages (kernels/local.py).
    """
    _check_dense_kind(kind)
    from repro.kernels.local import resolve_backend
    backend = resolve_backend(backend)
    blocks = None if blocks is None else tuple(blocks)
    Pn = mesh.shape[axis]
    n = A.shape[0]
    if n % Pn or r % Pn:
        raise ValueError(f"n={n}, r={r} must divide P={Pn}")
    B = _sketch_rows_1d(A, seed, r, mesh, axis, kind, backend, blocks)
    return nystrom_second_stage_redist(B, seed, r, mesh, axis, kind,
                                       backend=backend, blocks=blocks)


# ---------------------------------------------------------------------------
# General two-grid Alg. 2
# ---------------------------------------------------------------------------

def nystrom_general(A, seed: int, r: int, mesh: Mesh,
                    p_axes: Tuple[str, str, str] = DEFAULT_AXES,
                    q_axes: Optional[Tuple[str, str, str]] = None,
                    kind: str = "normal", backend: str = "auto",
                    blocks=None):
    """Alg. 2 on arbitrary (p1,p2,p3) / (q1,q2,q3) grids over one mesh.

    Stage 1 is Alg. 1 (``rand_matmul``).  The ``Redistribute`` of §5.2 is
    expressed as a sharding constraint — XLA emits the all-to-all /
    collective-permute exactly where the paper's algorithm places it.
    Stage 2 (C = Omega^T B) mirrors Alg. 1 with the roles of the grid axes
    shifted: all-gather B over q2, generate Omega_{i'j'}, local GEMM,
    reduce-scatter C over q1.  ``backend`` selects the local GEMM body for
    both stages (kernels/local.py).
    """
    _check_dense_kind(kind)
    from repro.kernels.local import resolve_backend
    q_axes = tuple(q_axes or p_axes)
    p_axes = tuple(p_axes)
    q1, q2, q3 = (mesh.shape[a] for a in q_axes)
    n = A.shape[0]
    if n % q1 or r % (q2 * q3) or r % q2 or r % q3:
        raise ValueError(f"(n={n}, r={r}) not divisible by q-grid "
                         f"({q1},{q2},{q3})")
    keys = jnp.stack(seed_keys(seed))
    return _nystrom_general_prog(
        r, mesh, p_axes, q_axes, kind, resolve_backend(backend),
        None if blocks is None else tuple(blocks))(A, keys)


@functools.lru_cache(maxsize=_PROG_CACHE_SIZE)
def _nystrom_general_prog(r: int, mesh: Mesh,
                          p_axes: Tuple[str, str, str],
                          q_axes: Tuple[str, str, str], kind: str,
                          backend: str = "jnp", blocks=None):
    from repro.kernels.local import sketch_t_block
    a1, a2, a3 = q_axes
    q1, q2, q3 = (mesh.shape[a] for a in q_axes)

    def impl(A, keys):
        n = A.shape[0]
        B = rand_matmul(A, keys, r, mesh, axes=p_axes, kind=kind,
                        backend=backend, blocks=blocks)

        # Redistribute B into the stage-2 layout: rows over q1, cols over
        # (q3, q2) — each block B_{i'k'} split column-wise across q2.
        B = jax.lax.with_sharding_constraint(
            B, NamedSharding(mesh, P(a1, (a3, a2))))
        om_rows = n // q1
        om_cols = r // q2

        def stage2(b_blk):                        # (n/q1, r/(q3 q2))
            i = jax.lax.axis_index(a1)
            j = jax.lax.axis_index(a2)
            b_ik = jax.lax.all_gather(b_blk, a2, axis=1, tiled=True)
            c_part = sketch_t_block(b_ik, keys, om_cols, row0=i * om_rows,
                                    col0=j * om_cols, kind=kind,
                                    backend=backend, blocks=blocks)
            if q1 == 1:                           # (r/q2, r/q3) partial
                return c_part
            return jax.lax.psum_scatter(c_part, a1, scatter_dimension=0,
                                        tiled=True)

        kw = {} if backend == "jnp" else {"check_vma": False}
        C = shard_map(stage2, mesh=mesh,
                      in_specs=P(a1, (a3, a2)),
                      out_specs=P((a2, a1), a3), **kw)(B)
        return B, C

    return jax.jit(impl)


# ---------------------------------------------------------------------------
# Bound-driven general two-grid Alg. 2 (§5.3 approach 1): stage 1 on a
# (p1,p2,p3) grid, stage 2 on an arbitrary (q1,q2,q3) grid over the SAME
# devices, with the §5.2 ``Redistribute`` of B made explicit between them.
# Unlike ``nystrom_general`` (one mesh, q a permutation of p's axes), the two
# grids here are independent factorizations of P — the form Theorem 3's
# bound-driven grids actually take.
# ---------------------------------------------------------------------------

Q_AXES = ("q1", "q2", "q3")


def _two_grid_devices(mesh, devices):
    if devices is not None:
        return list(devices)
    if mesh is not None:
        return list(mesh.devices.flat)
    return jax.devices()


def nystrom_second_stage_two_grid(B, seed, r: int, q: Tuple[int, int, int],
                                  mesh: Optional[Mesh] = None, devices=None,
                                  kind: str = "normal", salt: int = 0,
                                  backend: str = "auto", blocks=None):
    """Stage 2 of Alg. 2 on an arbitrary (q1, q2, q3) grid (§5.3).

    Accepts B = A·Omega in ANY sharding (one-shot stage-1 output or a
    streamed accumulator's Y) and re-lays it out P(q1, (q3, q2)) — the
    cross-grid ``Redistribute`` of §5.2, at most nr/P words per processor.
    Then, mirroring Alg. 1 with the grid roles shifted: All-Gather B over
    q2, regenerate Omega_{i'j'} from global coordinates (zero
    communication), local GEMM, Reduce-Scatter C over q1.

    Returns (B sharded P(q1, (q3, q2)), C sharded P((q2, q1), q3)) on the
    q-grid mesh.  Bitwise note: with q1 == 1 the stage-2 contraction is
    never split, so C is blockwise-bitwise against the single-device
    reference (given a bitwise B).  ``backend`` selects the local GEMM
    body (kernels/local.py) — both backends honor the bitwise note.
    """
    _check_dense_kind(kind)
    from repro.kernels.local import resolve_backend
    q1, q2, q3 = (int(x) for x in q)
    n = B.shape[0]
    if B.shape[1] != r:
        raise ValueError(f"B must be (n, r); got {B.shape} with r={r}")
    if n % q1 or r % (q1 * q2) or r % (q2 * q3):
        raise ValueError(f"(n={n}, r={r}) not divisible by q-grid "
                         f"({q1},{q2},{q3}): needs q1 | n, q1*q2 | r, "
                         f"q2*q3 | r")
    devices = _two_grid_devices(mesh, devices)
    mesh_q = make_grid_mesh(q1, q2, q3, axis_names=Q_AXES, devices=devices)
    # Redistribute: whatever layout B arrives in -> the stage-2 layout.
    B = jax.device_put(
        B, NamedSharding(mesh_q, P(Q_AXES[0], (Q_AXES[2], Q_AXES[1]))))
    keys = jnp.stack(seed_keys(seed))
    C = _two_grid_stage2_prog(
        r, mesh_q, kind, salt, resolve_backend(backend),
        None if blocks is None else tuple(blocks))(B, keys)
    return B, C


@functools.lru_cache(maxsize=_PROG_CACHE_SIZE)
def _two_grid_stage2_prog(r: int, mesh: Mesh, kind: str, salt: int,
                          backend: str = "jnp", blocks=None):
    from repro.kernels.local import sketch_t_block
    a1, a2, a3 = Q_AXES
    q1, q2, q3 = (mesh.shape[a] for a in Q_AXES)

    def impl(B, keys):
        n = B.shape[0]
        om_rows = n // q1
        om_cols = r // q2

        def body(b_blk):                          # (n/q1, r/(q3 q2))
            i = jax.lax.axis_index(a1)
            j = jax.lax.axis_index(a2)
            if q2 == 1:
                b_ik = b_blk
            else:
                b_ik = jax.lax.all_gather(b_blk, a2, axis=1, tiled=True)
            c_part = sketch_t_block(b_ik, keys, om_cols, row0=i * om_rows,
                                    col0=j * om_cols, kind=kind, salt=salt,
                                    backend=backend, blocks=blocks)
            if q1 == 1:                           # (r/q2, r/q3) partial
                return c_part
            return jax.lax.psum_scatter(c_part, a1, scatter_dimension=0,
                                        tiled=True)

        kw = {} if backend == "jnp" else {"check_vma": False}
        return shard_map(body, mesh=mesh,
                         in_specs=P(a1, (a3, a2)),
                         out_specs=P((a2, a1), a3), **kw)(B)

    return jax.jit(impl)


def nystrom_two_grid(A, seed, r: int, mesh: Optional[Mesh] = None,
                     p: Tuple[int, int, int] = None,
                     q: Tuple[int, int, int] = None,
                     kind: str = "normal", devices=None,
                     backend: str = "auto", blocks=None):
    """Alg. 2 with stage 1 on grid ``p`` and stage 2 on grid ``q`` (§5.3).

    The grids are independent factorizations of the same P devices (taken
    from ``mesh``, ``devices``, or ``jax.devices()``), so this executes the
    bound-driven (p, q) pairs of Theorem 3 that ``nystrom_general`` — one
    mesh, shared axis sizes — cannot express.  Stage 1 is Alg. 1 on the
    p-grid mesh; B is then redistributed to the q-grid layout (the §5.2
    ``Redistribute``, <= nr/P words per processor, zero when the layouts
    coincide); stage 2 runs on the q-grid mesh.

    in : A (n x n) in any sharding (re-laid out to the Alg. 1 contract)
    out: B sharded P(q1, (q3, q2)); C sharded P((q2, q1), q3), both on the
         q-grid mesh.
    Bitwise note: with p2 == 1 and q1 == 1 neither contraction is split, so
    (B, C) are bitwise-identical to ``nystrom_reference`` on this backend.
    """
    if p is None or q is None:
        raise ValueError("nystrom_two_grid needs explicit p and q grids "
                         "(use nystrom_auto(variant='bound_driven') to pick "
                         "them from the bound)")
    _check_dense_kind(kind)
    from .grid import alg2_two_grid_executable
    p = tuple(int(x) for x in p)
    q = tuple(int(x) for x in q)
    if p[0] * p[1] * p[2] != q[0] * q[1] * q[2]:
        raise ValueError(f"grids must factor the same P: {p} vs {q}")
    n = A.shape[0]
    if A.shape[1] != n:
        raise ValueError(f"Nyström needs a square A; got {A.shape}")
    if not alg2_two_grid_executable(n, r, p, q):
        raise ValueError(f"(n={n}, r={r}) not divisible by grids p={p}, "
                         f"q={q} (see alg2_two_grid_executable)")
    devices = _two_grid_devices(mesh, devices)
    mesh_p = make_grid_mesh(*p, devices=devices)
    A = jax.device_put(A, input_sharding(mesh_p))
    B = rand_matmul(A, seed, r, mesh_p, kind=kind, backend=backend,
                    blocks=blocks)
    return nystrom_second_stage_two_grid(B, seed, r, q, devices=devices,
                                         kind=kind, backend=backend,
                                         blocks=blocks)


# ---------------------------------------------------------------------------
# Fused single-jit two-grid Alg. 2: stage 1, the §5.2 ``Redistribute``, and
# stage 2 compiled into ONE executable over ONE mesh whose device order
# serves both grids (``core.grid.two_grid_shared_mesh``).  The cross-mesh
# ``device_put`` of ``nystrom_two_grid`` is a host-mediated transfer XLA
# cannot overlap or fuse; here the Redistribute is an in-program
# ``with_sharding_constraint`` the SPMD partitioner lowers to a
# collective-permute / all-to-all inside the compiled program.
# ---------------------------------------------------------------------------

def _spec_entry(names: Tuple[str, ...]):
    """PartitionSpec entry for an axis-name group (None when empty)."""
    if not names:
        return None
    return names[0] if len(names) == 1 else tuple(names)


def _axes_index(mesh: Mesh, names: Tuple[str, ...]):
    """Row-major linear index over an axis-name group (0 when empty) —
    the grouped-axes analogue of ``jax.lax.axis_index`` on a fused axis."""
    if not names:
        return jnp.int32(0)
    idx = None
    for nm in names:
        i = jax.lax.axis_index(nm)
        idx = i if idx is None else idx * mesh.shape[nm] + i
    return idx


def _two_grid_stage2_body(shared, r: int, n: int, kind: str, salt: int,
                          backend: str, blocks, keys):
    """Stage-2 shard_map body + specs on a shared mesh's q-axis groups.

    Mirrors ``_two_grid_stage2_prog`` with every single-axis collective /
    axis_index generalized to the q group; grouped collectives concatenate
    and reduce in the same row-major participant order as the standalone
    q-grid mesh, preserving the bitwise contract.
    """
    from repro.kernels.local import sketch_t_block
    mesh = shared.mesh
    qa1, qa2, qa3 = shared.q_axes
    q1, q2, q3 = shared.q
    om_rows = n // q1
    om_cols = r // q2

    def body(b_blk):                              # (n/q1, r/(q3 q2))
        i = _axes_index(mesh, qa1)
        j = _axes_index(mesh, qa2)
        if q2 == 1:
            b_ik = b_blk
        else:
            b_ik = jax.lax.all_gather(b_blk, qa2, axis=1, tiled=True)
        c_part = sketch_t_block(b_ik, keys, om_cols, row0=i * om_rows,
                                col0=j * om_cols, kind=kind, salt=salt,
                                backend=backend, blocks=blocks)
        if q1 == 1:                               # (r/q2, r/q3) partial
            return c_part
        return jax.lax.psum_scatter(c_part, qa1, scatter_dimension=0,
                                    tiled=True)

    in_spec = P(_spec_entry(qa1), _spec_entry(qa3 + qa2))
    out_spec = P(_spec_entry(qa2 + qa1), _spec_entry(qa3))
    return body, in_spec, out_spec


@functools.lru_cache(maxsize=_PROG_CACHE_SIZE)
def _nystrom_two_grid_fused_prog(r: int, shared, kind: str,
                                 backend: str = "jnp", blocks=None):
    """One jitted program: Alg. 1 on the p-axis groups, the in-program
    Redistribute of B, and stage 2 on the q-axis groups."""
    from repro.kernels.local import sketch_block
    mesh = shared.mesh
    pa1, pa2, pa3 = shared.p_axes
    p1, p2, p3 = shared.p
    in_spec = P(_spec_entry(pa1), _spec_entry(pa2 + pa3))
    b_p_spec = P(_spec_entry(pa1 + pa2), _spec_entry(pa3))
    kw = {} if backend == "jnp" else {"check_vma": False}

    def impl(A, keys):
        n = A.shape[0]
        blk_rows = n // p2
        blk_cols = r // p3

        def stage1(a_blk):
            j = _axes_index(mesh, pa2)
            k = _axes_index(mesh, pa3)
            if p3 == 1:
                a_ij = a_blk
            else:
                a_ij = jax.lax.all_gather(a_blk, pa3, axis=1, tiled=True)
            b_partial = sketch_block(a_ij, keys, blk_cols,
                                     row0=j * blk_rows, col0=k * blk_cols,
                                     kind=kind, backend=backend,
                                     blocks=blocks)
            if p2 == 1:
                return b_partial
            return jax.lax.psum_scatter(b_partial, pa2,
                                        scatter_dimension=0, tiled=True)

        B = shard_map(stage1, mesh=mesh, in_specs=in_spec,
                      out_specs=b_p_spec, **kw)(A)

        body, s2_in, s2_out = _two_grid_stage2_body(
            shared, r, n, kind, 0, backend, blocks, keys)
        # §5.2 Redistribute, in-program: p-layout of B -> q-layout, one
        # resharding the partitioner compiles into this executable (no
        # host-mediated device_put between the stages).
        B = jax.lax.with_sharding_constraint(
            B, NamedSharding(mesh, s2_in))
        C = shard_map(body, mesh=mesh, in_specs=s2_in, out_specs=s2_out,
                      **kw)(B)
        return B, C

    return jax.jit(impl)


@functools.lru_cache(maxsize=_PROG_CACHE_SIZE)
def _two_grid_stage2_fused_prog(r: int, n: int, shared, kind: str,
                                salt: int, backend: str = "jnp",
                                blocks=None):
    """Redistribute + stage 2 in one jit (streamed-Y finalize: stage 1's B
    is the accumulated Y, already resident on the p-grid layout)."""
    mesh = shared.mesh
    pa1, pa2, pa3 = shared.p_axes
    b_p_spec = P(_spec_entry(pa1 + pa2), _spec_entry(pa3))
    kw = {} if backend == "jnp" else {"check_vma": False}

    def impl(B, keys):
        body, s2_in, s2_out = _two_grid_stage2_body(
            shared, r, n, kind, salt, backend, blocks, keys)
        B = jax.lax.with_sharding_constraint(B, NamedSharding(mesh, s2_in))
        C = shard_map(body, mesh=mesh, in_specs=s2_in, out_specs=s2_out,
                      **kw)(B)
        return B, C

    return jax.jit(impl), b_p_spec


def nystrom_second_stage_two_grid_fused(B, seed, r: int,
                                        q: Tuple[int, int, int],
                                        p: Optional[Tuple[int, int, int]]
                                        = None,
                                        mesh: Optional[Mesh] = None,
                                        devices=None, kind: str = "normal",
                                        salt: int = 0,
                                        backend: str = "auto", blocks=None):
    """Stage 2 of Alg. 2 on the q-grid with the Redistribute in-program.

    Like :func:`nystrom_second_stage_two_grid` but the §5.2 re-layout of B
    and the stage-2 collectives compile into ONE executable on the shared
    mesh of (p, q) — ``p`` names the layout B arrives in (default the
    streamed accumulator's (P, 1, 1) row-sharded grid, for which the
    shared mesh always exists).  Falls back to the cross-mesh path when no
    single device assignment serves both grids.
    """
    _check_dense_kind(kind)
    from repro.kernels.local import resolve_backend
    from .grid import two_grid_shared_mesh
    q = tuple(int(x) for x in q)
    n = B.shape[0]
    if B.shape[1] != r:
        raise ValueError(f"B must be (n, r); got {B.shape} with r={r}")
    q1, q2, q3 = q
    if n % q1 or r % (q1 * q2) or r % (q2 * q3):
        raise ValueError(f"(n={n}, r={r}) not divisible by q-grid "
                         f"({q1},{q2},{q3}): needs q1 | n, q1*q2 | r, "
                         f"q2*q3 | r")
    devices = _two_grid_devices(mesh, devices)
    Pn = q1 * q2 * q3
    p = (Pn, 1, 1) if p is None else tuple(int(x) for x in p)
    shared = two_grid_shared_mesh(p, q, devices=devices)
    if shared is None:
        return nystrom_second_stage_two_grid(B, seed, r, q, devices=devices,
                                             kind=kind, salt=salt,
                                             backend=backend, blocks=blocks)
    backend = resolve_backend(backend)
    blocks = None if blocks is None else tuple(blocks)
    fn, b_p_spec = _two_grid_stage2_fused_prog(r, n, shared, kind, salt,
                                               backend, blocks)
    # placement onto the shared mesh in the p-grid layout.  When B already
    # lives in that layout — the streamed-finalize case: nystrom_finalize
    # gates on a (P,1,1) accumulator grid, whose Y layout P((p1,p2),p3)
    # IS b_p_spec — the shared mesh assigns devices exactly as the p-grid
    # mesh does, so this moves no bytes between devices and the actual
    # re-layout happens inside the compiled program.  A B arriving in some
    # other sharding gets re-laid out by this device_put first (same
    # host-mediated cost the cross-mesh path pays on every call).
    B = jax.device_put(B, NamedSharding(shared.mesh, b_p_spec))
    keys = jnp.stack(seed_keys(seed))
    led = obs_ledger.get_ledger()
    if led is not None:
        pred, floor = _fused_audit(n, r, p, q, backend)
        led.observe("nystrom.stage2_two_grid_fused", fn, (B, keys),
                    predicted_words=pred, lower_bound_words=floor,
                    itemsize=jnp.dtype(B.dtype).itemsize)
    with obs_trace.span("nystrom.stage2_two_grid_fused", cat="nystrom",
                        n=n, r=r, p=list(p), q=list(q)):
        return fn(B, keys)


def nystrom_two_grid_fused(A, seed, r: int, mesh: Optional[Mesh] = None,
                           p: Tuple[int, int, int] = None,
                           q: Tuple[int, int, int] = None,
                           kind: str = "normal", devices=None,
                           backend: str = "auto", blocks=None):
    """Alg. 2 with both stages AND the §5.2 Redistribute in one jit (§5.3).

    Same contract as :func:`nystrom_two_grid` — independent (p, q)
    factorizations of P, B returned in the q layout, bitwise
    ``nystrom_reference`` when p2 == 1 and q1 == 1 — but compiled as a
    single executable over the shared mesh of
    :func:`repro.core.grid.two_grid_shared_mesh`: the cross-grid
    redistribution of B is an in-program resharding (still <= nr/P words
    per processor, emitted as an all-to-all / collective-permute the
    compiler can overlap) instead of a host-mediated ``device_put``.
    Falls back to :func:`nystrom_two_grid` when no single device
    assignment serves both grids (``two_grid_shared_mesh`` returns None).
    """
    if p is None or q is None:
        raise ValueError("nystrom_two_grid_fused needs explicit p and q "
                         "grids (use nystrom_auto(variant='bound_driven') "
                         "to pick them from the bound)")
    _check_dense_kind(kind)
    from repro.kernels.local import resolve_backend
    from .grid import alg2_two_grid_executable, two_grid_shared_mesh
    p = tuple(int(x) for x in p)
    q = tuple(int(x) for x in q)
    if p[0] * p[1] * p[2] != q[0] * q[1] * q[2]:
        raise ValueError(f"grids must factor the same P: {p} vs {q}")
    n = A.shape[0]
    if A.shape[1] != n:
        raise ValueError(f"Nyström needs a square A; got {A.shape}")
    if not alg2_two_grid_executable(n, r, p, q):
        raise ValueError(f"(n={n}, r={r}) not divisible by grids p={p}, "
                         f"q={q} (see alg2_two_grid_executable)")
    devices = _two_grid_devices(mesh, devices)
    shared = two_grid_shared_mesh(p, q, devices=devices)
    if shared is None:
        # no device-order reconciliation: the two-mesh path with its
        # explicit cross-mesh Redistribute is the only executable form
        return nystrom_two_grid(A, seed, r, p=p, q=q, kind=kind,
                                devices=devices, backend=backend,
                                blocks=blocks)
    backend = resolve_backend(backend)
    blocks = None if blocks is None else tuple(blocks)
    pa1, pa2, pa3 = shared.p_axes
    A = jax.device_put(
        A, NamedSharding(shared.mesh,
                         P(_spec_entry(pa1), _spec_entry(pa2 + pa3))))
    keys = jnp.stack(seed_keys(seed))
    fn = _nystrom_two_grid_fused_prog(r, shared, kind, backend, blocks)
    led = obs_ledger.get_ledger()
    if led is not None:
        pred, floor = _fused_audit(n, r, p, q, backend)
        led.observe("nystrom.two_grid_fused", fn, (A, keys),
                    predicted_words=pred, lower_bound_words=floor,
                    itemsize=jnp.dtype(A.dtype).itemsize)
    with obs_trace.span("nystrom.two_grid_fused", cat="nystrom",
                        n=n, r=r, p=list(p), q=list(q)):
        return fn(A, keys)


# ---------------------------------------------------------------------------
# Convenience driver
# ---------------------------------------------------------------------------

def nystrom_auto(A, seed: int, r: int, variant: str = "auto", devices=None,
                 kind: str = "normal", plan=None, backend: str = "auto",
                 blocks=None):
    """Run the paper-preferred variant on a 1-D mesh over all devices.

    variant:
      * ``"auto"``   — the paper's empirical rule (redist iff P > n/r);
      * ``"plan"``   — cost-model dispatch via :mod:`repro.plan` (prices the
        redist all-to-all against the no_redist reduce-scatter on the
        machine model, so latency-dominated small problems may legitimately
        deviate from the bandwidth-only rule);
      * ``"bound_driven"`` — the §5.3 general two-grid algorithm on the
        Theorem-3 bound-driven (p, q) pair, snapped to the min-words
        executable factorization pair when the ideal grids do not divide
        (``core.grid.select_two_grid_executable``); runs the single-jit
        fused program (``nystrom_two_grid_fused`` — in-program §5.2
        Redistribute) whenever the pair admits a shared mesh, else the
        cross-mesh two-grid path;
      * ``"redist"`` / ``"no_redist"`` — explicit.
    plan: a precomputed :class:`repro.plan.Plan` (wins over ``variant``;
    its backend decision also wins over the ``backend`` arg).
    backend: local GEMM body for every stage (kernels/local.py).
    """
    _check_dense_kind(kind)
    devices = devices if devices is not None else jax.devices()
    Pn = len(devices)
    n = A.shape[0]
    if plan is not None or variant == "plan":
        if plan is None:
            from repro.plan import plan_nystrom
            plan = plan_nystrom(n, r, P=Pn, kind=kind)
        if not plan.executable:
            raise ValueError(
                f"plan {plan.variant!r} for dims={plan.dims}, "
                f"P={plan.n_procs} is analytic-only (no executable grid "
                f"pair divides the shape)")
        backend = getattr(plan, "backend", backend) or backend
        if plan.blocks and plan.variant != "pallas_fused":
            blocks = tuple(plan.blocks[k] for k in ("bm", "bn", "bk"))
        if plan.variant in ("alg2_bound_driven", "alg2_bound_driven_fused"):
            fn = (nystrom_two_grid_fused
                  if plan.variant == "alg2_bound_driven_fused"
                  else nystrom_two_grid)
            B, C = fn(A, seed, r, p=plan.grid, q=plan.q_grid, kind=kind,
                      devices=list(devices[: plan.n_procs]),
                      backend=backend, blocks=blocks)
            mesh_q = make_grid_mesh(*plan.q_grid, axis_names=Q_AXES,
                                    devices=list(devices[: plan.n_procs]))
            return B, C, mesh_q, "bound_driven"
        variant = {"alg2_no_redist": "no_redist", "alg2_redist": "redist",
                   "local_xla": "no_redist"}.get(plan.variant)
        if variant is None:
            # pallas_fused is a kernel variant (non-bitwise vs the XLA
            # path), not a 1-D mesh program — dispatch it via the plan.
            raise ValueError(f"plan variant {plan.variant!r} has no 1-D "
                             f"mesh execution here; call plan.execute "
                             f"instead (or pass variant='auto' to force "
                             f"the mesh path)")
    if variant == "bound_driven":
        from .grid import select_two_grid_executable
        got = select_two_grid_executable(n, r, Pn)
        if got is None:
            raise ValueError(f"no (p, q) factorization pair of P={Pn} "
                             f"divides (n={n}, r={r}); pad the shape or "
                             f"change P")
        p, q, _exact = got
        # prefer the single-jit fused program; it falls back to the
        # cross-mesh two-grid path itself when no shared mesh exists
        B, C = nystrom_two_grid_fused(A, seed, r, p=p, q=q, kind=kind,
                                      devices=list(devices), backend=backend,
                                      blocks=blocks)
        mesh_q = make_grid_mesh(*q, axis_names=Q_AXES, devices=list(devices))
        return B, C, mesh_q, "bound_driven"
    if variant == "auto":
        variant = "redist" if Pn > max(1, n // max(r, 1)) else "no_redist"
    mesh = Mesh(np.asarray(devices), (X_AXIS,))
    A = jax.device_put(A, NamedSharding(mesh, P(X_AXIS, None)))
    if variant == "no_redist":
        B, C = nystrom_no_redist(A, seed, r, mesh, kind=kind,
                                 backend=backend, blocks=blocks)
    elif variant == "redist":
        B, C = nystrom_redist(A, seed, r, mesh, kind=kind,
                              backend=backend, blocks=blocks)
    else:
        raise ValueError(variant)
    return B, C, mesh, variant
