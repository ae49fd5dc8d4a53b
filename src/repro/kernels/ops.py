"""Public wrappers around the Pallas sketch kernels.

Handles arbitrary (non-block-aligned) shapes by zero-padding A up to block
multiples (zero rows of A contribute nothing to B; zero *columns* of A would
pair with extra Omega rows, so the contraction dim must instead clamp the
generated Omega — we pad the contraction with zeros in A AND generate the
padded Omega rows anyway: zero x anything = 0, so the result is exact).
Block sizes default to MXU-aligned values for the TPU target; interpret=True
executes the kernel body in Python on CPU for validation.

Each product wrapper sets up its launch (blocks, padding, grid) with one
function, ``sketch_matmul_launch`` / ``sketch_t_matmul_launch``, which
also counts the Omega entries that launch generates; the wrapper publishes
them as ``omega_entries_generated_total{kernel}`` and
``omega_entries_needed_total{kernel}`` in the process-wide metrics
registry, then runs the jitted, padded launch.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.obs.metrics import get_metrics
from repro.obs.trace import span

from .sketch_matmul import (
    gen_omega_pallas,
    sketch_matmul_pallas,
    sketch_t_matmul_pallas,
    t_block_n,
    uses_panel,
)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


class Launch(NamedTuple):
    """One launch of a fused sketch kernel, as its wrapper sets it up."""
    blocks: Tuple[int, int, int]    # (bm, bn, bk), clamped to the shape
    padded: Tuple[int, int, int]    # the grid's dims padded to the blocks
    generated: int                  # Omega entries the kernel generates
    needed: int                     # distinct Omega entries the product uses
    panel: bool = False             # sketch_a_omega keeps its Omega panel


def _launch(dims, blocks, omega_axis: int, needed: int) -> Launch:
    """Blocks clamped to ``dims`` (rounded up to 8), ``dims`` padded to
    them; the grid has ``padded / blocks`` steps, the contraction last.
    ``generated`` counts every step's whole Omega tile, (bk, the block of
    axis ``omega_axis``)."""
    bs = tuple(min(b, _round_up(d, 8)) for d, b in zip(dims, blocks))
    padded = tuple(_round_up(d, b) for d, b in zip(dims, bs))
    steps = math.prod(p // b for p, b in zip(padded, bs))
    return Launch(bs, padded, steps * bs[2] * bs[omega_axis], needed)


@functools.lru_cache(maxsize=None)
def sketch_matmul_launch(n1: int, n2: int, r: int, bm: int = 256,
                         bn: int = 128, bk: int = 512) -> Launch:
    """:func:`sketch_matmul` on A n1 x n2: grid ``(rp/bn, n1p/bm, n2p/bk)``,
    a (bk, bn) tile a step.  The tile depends only on the step's column and
    contraction blocks: on the panel path
    (:func:`~repro.kernels.sketch_matmul.uses_panel`) the first row block
    generates each tile once, ``n2p·rp`` entries; otherwise each row block
    regenerates all of Omega."""
    launch = _launch((n1, r, n2), (bm, bn, bk), 1, n2 * r)
    (bm, bn, _), (n1p, rp, n2p) = launch.blocks, launch.padded
    if uses_panel(n1p, n2p, bm, bn):
        return launch._replace(generated=n2p * rp, panel=True)
    return launch


@functools.lru_cache(maxsize=None)
def sketch_t_matmul_launch(n: int, r2: int, r: int, bm: int = 128,
                           bn: int | None = None, bk: int = 512) -> Launch:
    """:func:`sketch_t_matmul` on B n x r2: grid ``(rp/bm, r2p/bn, np/bk)``,
    a (bk, bm) tile a step, generated once per column block of B.  Unless
    the caller gives ``bn``, one column block covers B where the step fits
    (:func:`~repro.kernels.sketch_matmul.t_block_n`): ``np·rp`` entries."""
    if bn is None:
        bn = t_block_n(r2, bm, bk)
    return _launch((r, r2, n), (bm, bn, bk), 0, n * r)


def _count_omega(kernel: str, x, launch: Launch) -> None:
    """Publish one launch's Omega entries under ``kernel``, its
    ``pallas_call`` name.  A launch traced into an enclosing program
    (``x`` a tracer) is not counted: it runs on each call of that
    program, which this host code does not see."""
    if isinstance(x, jax.core.Tracer):
        return
    reg = get_metrics()
    reg.counter("omega_entries_generated_total",
                "Omega entries the fused sketch kernels generated").inc(
                    launch.generated, kernel=kernel)
    reg.counter("omega_entries_needed_total",
                "distinct Omega entries their products used").inc(
                    launch.needed, kernel=kernel)


def sketch_matmul(A, *, seed: int, r: int,
                  bm: int = 256, bn: int = 128, bk: int = 512,
                  kind: str = "normal", salt: int = 0,
                  interpret: bool = False):
    """B = A @ Omega(n2, r) with in-kernel Omega generation; any shape.
    Counts the launch's Omega entries (``sketch_a_omega``)."""
    launch = sketch_matmul_launch(*A.shape, r, bm, bn, bk)
    _count_omega("sketch_a_omega", A, launch)
    return _sketch_matmul(A, seed=seed, r=r, blocks=launch.blocks,
                          padded=launch.padded, kind=kind, salt=salt,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("seed", "r", "blocks", "padded",
                                             "kind", "salt", "interpret"))
def _sketch_matmul(A, *, seed, r, blocks, padded, kind, salt, interpret):
    n1, n2 = A.shape
    (bm, bn, bk), (n1p, rp, n2p) = blocks, padded
    Ap = jnp.pad(A, ((0, n1p - n1), (0, n2p - n2)))
    # NOTE: padded contraction rows of Omega multiply zero columns of A.
    # Padded output columns [r:rp] are generated but sliced away.
    Bp = sketch_matmul_pallas(Ap, seed, rp, bm=bm, bn=bn, bk=bk,
                              kind=kind, salt=salt, interpret=interpret)
    return Bp[:n1, :r]


def sketch_t_matmul(B, *, seed: int, r: int,
                    bm: int = 128, bn: int | None = None, bk: int = 512,
                    kind: str = "normal", salt: int = 0,
                    interpret: bool = False):
    """C = Omega(n, r)^T @ B with in-kernel Omega generation; any shape.
    Counts the launch's Omega entries (``sketch_omega_t_b``).

    CAUTION: the contraction dim (rows of B / rows of Omega) must not be
    padded with generated Omega rows against zero B rows — zeros kill them,
    so padding is exact here too.
    """
    launch = sketch_t_matmul_launch(*B.shape, r, bm, bn, bk)
    _count_omega("sketch_omega_t_b", B, launch)
    return _sketch_t_matmul(B, seed=seed, r=r, blocks=launch.blocks,
                            padded=launch.padded, kind=kind, salt=salt,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("seed", "r", "blocks", "padded",
                                             "kind", "salt", "interpret"))
def _sketch_t_matmul(B, *, seed, r, blocks, padded, kind, salt, interpret):
    n, r2 = B.shape
    (bm, bn, bk), (rp, r2p, np_) = blocks, padded
    Bp = jnp.pad(B, ((0, np_ - n), (0, r2p - r2)))
    Cp = sketch_t_matmul_pallas(Bp, seed, rp, bm=bm, bn=bn, bk=bk,
                                kind=kind, salt=salt, interpret=interpret)
    return Cp[:r, :r2]


@functools.partial(jax.jit, static_argnames=("n2", "r", "br", "bc", "kind",
                                             "salt", "interpret", "seed",
                                             "dtype"))
def gen_omega(*, seed: int, n2: int, r: int, br: int = 256, bc: int = 128,
              kind: str = "normal", salt: int = 0, dtype=jnp.float32,
              interpret: bool = False):
    """Materialize Omega via the kernel's generator (oracle parity checks)."""
    br_ = min(br, _round_up(n2, 8))
    bc_ = min(bc, _round_up(r, 8))
    n2p, rp = _round_up(n2, br_), _round_up(r, bc_)
    om = gen_omega_pallas(seed, n2p, rp, br=br_, bc=bc_, kind=kind,
                          salt=salt, dtype=dtype, interpret=interpret)
    return om[:n2, :r]


def nystrom_fused(A, *, seed: int, r: int, kind: str = "normal",
                  interpret: bool = False, **blocks):
    """(B, C) of the Nyström pair with Omega never materialized in HBM:
    B = A·Omega via the fused kernel, then C = Omega^T·B likewise.  Each
    launch's dispatch is a span, ``nystrom.stage1`` / ``nystrom.stage2``,
    with its shapes and blocks."""
    n = A.shape[0]
    blocks = {k: v for k, v in blocks.items() if k in ("bm", "bn", "bk")}
    with span("nystrom.stage1", cat="nystrom", n=n, r=r,
              blocks=list(sketch_matmul_launch(*A.shape, r, **blocks)
                          .blocks)):
        B = sketch_matmul(A, seed=seed, r=r, kind=kind, interpret=interpret,
                          **blocks)
    with span("nystrom.stage2", cat="nystrom", n=n, r=r,
              blocks=list(sketch_t_matmul_launch(n, r, r).blocks)):
        C = sketch_t_matmul(B, seed=seed, r=r, kind=kind, interpret=interpret)
    return B, C
