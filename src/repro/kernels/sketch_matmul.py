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

Tiling: ``sketch_a_omega``'s grid is (r/bn, n1/bm, n2/bk), column block
outermost and the contraction innermost; an f32 VMEM scratch accumulates
across k-steps so inputs/outputs may be bf16.  Its (bk, bn) Omega tile
depends only on (k, j), so where A has at least two row blocks and the
column block's whole Omega panel (n2 x bn) fits the VMEM budget, the
first row block generates each tile into a VMEM panel and every later row
block reads it back: Omega is generated once per call, not once per row
block.  Otherwise (one row block, or a contraction too long for the
panel) each step generates its tile.  Both paths feed the dot the same
values, so B is bitwise the same.  ``sketch_omega_t_b``'s grid is (r/bm,
r2/bn, n/bk) and each step generates its (bk, bm) tile, which depends
only on (k, i): where the step fits, one column block covers all of B
(:func:`t_block_n`), so each tile is generated once per call.  Block
shapes default to MXU-aligned multiples of 128 on TPU; tests sweep small
blocks in interpret mode.
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
# B = A @ Omega
# ---------------------------------------------------------------------------

# Scoped VMEM ``sketch_a_omega`` asks for besides the panel: Mosaic's
# default scope, in which the per-step kernel has always compiled.  At
# the default blocks the v5e compiler allocates 1.38 MiB of it (A's and
# the output's double buffers and the accumulator).
STEP_VMEM = 16 * 2 ** 20
# The most scoped VMEM the panel path may ask for (v5e has 128 MiB per
# core): at bn = 128 a panel of up to 80 MiB, a contraction of 163,840.
PANEL_VMEM_BUDGET = 96 * 2 ** 20


def panel_bytes(n2: int, bn: int) -> int:
    """VMEM bytes of an f32 (n2, bn) Omega panel, lanes padded to 128."""
    return n2 * -(-bn // 128) * 128 * 4


def uses_panel(n1: int, n2: int, bm: int, bn: int) -> bool:
    """Whether ``sketch_a_omega`` on a padded (n1, n2) A keeps its Omega
    panel: at least two row blocks share it, and it fits the budget."""
    return (n1 // bm >= 2
            and panel_bytes(n2, bn) + STEP_VMEM <= PANEL_VMEM_BUDGET)


def _sketch_matmul_body(a_ref, o_ref, acc_ref, *panel_ref, seed: int,
                        bk: int, bn: int, nsteps_k: int, kind: str,
                        salt: int):
    j = pl.program_id(0)
    i = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile():
        return rng.omega_grid(rng.seed_keys(seed), k * bk, j * bn, bk, bn,
                              kind, salt)

    if panel_ref:
        (panel_ref,) = panel_ref
        rows = pl.ds(pl.multiple_of(k * bk, bk), bk)

        @pl.when(i == 0)
        def _fill():
            panel_ref[rows, :] = tile()

        om = panel_ref[rows, :]
    else:
        om = tile()
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
    """B = A @ Omega with Omega generated in-kernel, through the Omega
    panel where :func:`uses_panel` says so.  Shapes must be multiples of
    the block sizes (use :func:`repro.kernels.ops.sketch_matmul` for the
    padded general wrapper)."""
    n1, n2 = A.shape
    assert n1 % bm == 0 and n2 % bk == 0 and r % bn == 0, (A.shape, r, (bm, bn, bk))
    out_dtype = out_dtype or A.dtype
    nsteps_k = n2 // bk
    grid = (r // bn, n1 // bm, nsteps_k)
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    vmem = STEP_VMEM
    if uses_panel(n1, n2, bm, bn):
        scratch.append(pltpu.VMEM((n2, bn), jnp.float32))
        vmem += panel_bytes(n2, bn)

    return pl.pallas_call(
        functools.partial(_sketch_matmul_body, seed=seed, bk=bk, bn=bn,
                          nsteps_k=nsteps_k, kind=kind, salt=salt),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda j, i, k: (i, k))],
        out_specs=pl.BlockSpec((bm, bn), lambda j, i, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n1, r), out_dtype),
        scratch_shapes=scratch,
        # the row blocks run in order: row block 0 fills the panel
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="sketch_a_omega",
    )(A)


# ---------------------------------------------------------------------------
# C = Omega^T @ B    (contraction over Omega rows: the Nystrom second stage)
# ---------------------------------------------------------------------------

# The most VMEM a ``sketch_omega_t_b`` step's own buffers may take: half
# the default scope, the rest left to Mosaic's (the Omega tile, its
# transpose, the dot's operands).  At bn = 256 the v5e compiler allocates
# 2.66 MiB in all; at bn = 1408, the widest that fits, 8.85 MiB.
T_STEP_BUDGET = STEP_VMEM // 2


def t_step_bytes(bm: int, bn: int, bk: int) -> int:
    """VMEM bytes of one ``sketch_omega_t_b`` step's f32 buffers: B's
    double-buffered (bk, bn) block, the output's double-buffered (bm, bn)
    block and the (bm, bn) accumulator."""
    return 4 * bn * (2 * bk + 3 * bm)


def t_block_n(r2: int, bm: int, bk: int) -> int:
    """Column block of B for ``sketch_omega_t_b``: B's whole width, in
    lanes of 128, where the step fits :data:`T_STEP_BUDGET`, so each Omega
    tile is generated once.  Otherwise the fewest column blocks that fit,
    as even as lanes of 128 allow; each tile is generated once per block."""
    lanes = -(-r2 // 128)
    fit = max(T_STEP_BUDGET // t_step_bytes(bm, 128, bk), 1)
    blocks = -(-lanes // fit)
    return -(-lanes // blocks) * 128


def _sketch_t_matmul_body(b_ref, o_ref, acc_ref, *, seed: int, bk: int,
                          bm: int, nsteps_k: int, kind: str, salt: int):
    k = pl.program_id(2)
    i = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Omega tile rows k*bk..k*bk+bk map to the contraction; cols i*bm..
    om = rng.omega_grid(rng.seed_keys(seed), k * bk, i * bm, bk, bm, kind,
                        salt)
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
    o_ref[...] = rng.omega_grid(rng.seed_keys(seed), i * br, j * bc, br, bc,
                                kind, salt).astype(o_ref.dtype)


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
