"""Pallas TPU kernel: fused sketch-matmul with in-VMEM Omega generation.

The paper removes Omega from the *network*; this kernel removes it from
*HBM*: each (bk x bn) tile of Omega is generated inside the kernel with
Philox-4x32-10 keyed by its global coordinates, lives only in VMEM/VREGs,
and is consumed immediately by the MXU accumulation.  HBM traffic drops from
``n1*n2 + n2*r + n1*r`` words (classic GEMM) to ``n1*n2 + n1*r`` — the
memory-roofline analogue of the paper's zero-communication claim.

Kernels (each ``pallas_call`` is named, and the name is the device op's
name in a profile):
  * ``sketch_a_omega``    — B = A @ Omega          (A: n1 x n2)
  * ``sketch_omega_t_b``  — C = Omega^T @ B        (B: n x r2)
  * ``gen_omega``         — materialize an Omega tile (bitwise oracle
                            check for the in-kernel generator)

Tiling: grid (n1/bm, r/bn, n2/bk) with the contraction dim innermost; an
f32 VMEM scratch accumulates across k-steps so inputs/outputs may be bf16.
Block shapes default to MXU-aligned multiples of 128 on TPU; tests sweep
small blocks in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import rng
from repro.core.sketch import F32


# ---------------------------------------------------------------------------
# In-kernel Omega tile (shared with the jnp reference — bitwise identical)
# ---------------------------------------------------------------------------

def _omega_tile_kernel(seed: int, row0, col0, rows: int, cols: int,
                       kind: str, salt: int = 0):
    key0 = jnp.uint32(seed & 0xFFFFFFFF)
    key1 = jnp.uint32((seed >> 32) & 0xFFFFFFFF)
    row0 = jnp.asarray(row0, jnp.uint32)
    col0 = jnp.asarray(col0, jnp.uint32)
    if kind == "normal":
        return rng.philox_normal_grid(key0, key1, row0, col0, rows, cols, salt)
    if kind == "uniform":
        return rng.philox_uniform_grid(key0, key1, row0, col0, rows, cols, salt)
    if kind == "rademacher":
        u = rng.philox_uniform_grid(key0, key1, row0, col0, rows, cols, salt)
        return jnp.where(u < 0.5, jnp.float32(-1), jnp.float32(1))
    raise ValueError(f"unknown omega kind {kind!r}")


# ---------------------------------------------------------------------------
# B = A @ Omega
# ---------------------------------------------------------------------------

def _sketch_matmul_body(a_ref, o_ref, acc_ref, *, seed: int, bk: int, bn: int,
                        nsteps_k: int, kind: str, salt: int):
    k = pl.program_id(2)
    j = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    om = _omega_tile_kernel(seed, k * bk, j * bn, bk, bn, kind, salt)
    a = a_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(a, om, precision=F32,
                                preferred_element_type=jnp.float32)

    @pl.when(k == nsteps_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def sketch_matmul_pallas(A, seed: int, r: int, *,
                         bm: int = 256, bn: int = 128, bk: int = 512,
                         kind: str = "normal", salt: int = 0,
                         out_dtype=None, interpret: bool = False):
    """B = A @ Omega with Omega generated in-kernel. Shapes must be multiples
    of the block sizes (use :func:`repro.kernels.ops.sketch_matmul` for the
    padded general wrapper)."""
    n1, n2 = A.shape
    assert n1 % bm == 0 and n2 % bk == 0 and r % bn == 0, (A.shape, r, (bm, bn, bk))
    out_dtype = out_dtype or A.dtype
    nsteps_k = n2 // bk
    grid = (n1 // bm, r // bn, nsteps_k)

    return pl.pallas_call(
        functools.partial(_sketch_matmul_body, seed=seed, bk=bk, bn=bn,
                          nsteps_k=nsteps_k, kind=kind, salt=salt),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n1, r), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="sketch_a_omega",
    )(A)


# ---------------------------------------------------------------------------
# C = Omega^T @ B    (contraction over Omega rows: the Nystrom second stage)
# ---------------------------------------------------------------------------

def _sketch_t_matmul_body(b_ref, o_ref, acc_ref, *, seed: int, bk: int,
                          bm: int, nsteps_k: int, kind: str, salt: int):
    k = pl.program_id(2)
    i = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Omega tile rows k*bk..k*bk+bk map to the contraction; cols i*bm..
    om = _omega_tile_kernel(seed, k * bk, i * bm, bk, bm, kind, salt)
    b = b_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(om.T, b, precision=F32,
                                preferred_element_type=jnp.float32)

    @pl.when(k == nsteps_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def sketch_t_matmul_pallas(B, seed: int, r: int, *,
                           bm: int = 128, bn: int = 128, bk: int = 512,
                           kind: str = "normal", salt: int = 0,
                           out_dtype=None, interpret: bool = False):
    """C = Omega^T @ B where Omega is (n x r) and B is (n x r2), generated
    in-kernel.  Output (r, r2)."""
    n, r2 = B.shape
    assert n % bk == 0 and r % bm == 0 and r2 % bn == 0, (B.shape, r, (bm, bn, bk))
    out_dtype = out_dtype or B.dtype
    nsteps_k = n // bk
    grid = (r // bm, r2 // bn, nsteps_k)

    return pl.pallas_call(
        functools.partial(_sketch_t_matmul_body, seed=seed, bk=bk, bm=bm,
                          nsteps_k=nsteps_k, kind=kind, salt=salt),
        grid=grid,
        in_specs=[pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, r2), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="sketch_omega_t_b",
    )(B)


# ---------------------------------------------------------------------------
# Omega materialization kernel (oracle check of the in-kernel generator)
# ---------------------------------------------------------------------------

def _gen_omega_body(o_ref, *, seed: int, br: int, bc: int, kind: str,
                    salt: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    o_ref[...] = _omega_tile_kernel(seed, i * br, j * bc, br, bc, kind,
                                    salt).astype(o_ref.dtype)


def gen_omega_pallas(seed: int, n2: int, r: int, *,
                     br: int = 256, bc: int = 128, kind: str = "normal",
                     salt: int = 0, dtype=jnp.float32,
                     interpret: bool = False):
    assert n2 % br == 0 and r % bc == 0
    return pl.pallas_call(
        functools.partial(_gen_omega_body, seed=seed, br=br, bc=bc, kind=kind,
                          salt=salt),
        grid=(n2 // br, r // bc),
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n2, r), dtype),
        interpret=interpret,
        name="gen_omega",
    )()
