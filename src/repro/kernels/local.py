"""Offset-aware fused local GEMM backends for every distributed hot path.

The paper's Theorems 2/3 remove Omega from the *network*; the Pallas
kernels remove it from *HBM*.  Until now only the single-device entry
points (``kernels/ops.py``) got the fused treatment — every shard_map body
(Alg. 1's ``rand_matmul``, both Nyström stages, the streaming updates)
still materialized its per-shard Omega block via ``omega_tile`` and paid
the full ``n1·n2 + n2·r + n1·r`` local HBM traffic.  This module closes
that gap: it exposes the two local GEMM bodies those paths need,

  * ``sketch_block``    —  acc? + A · Omega[row0:, col0:col0+cols]
  * ``sketch_t_block``  —  acc? + Omega[row0:, col0:col0+cols]^T · B

with the Omega (or Psi) tile generated at *global* Philox coordinates —
``row0``/``col0`` and the key pair may be **traced** (they are
``axis_index`` products inside shard_map bodies), entering the kernel as
scalar-prefetch operands.  ``acc`` fuses the streaming accumulation
``Y += H·Omega`` into the kernel accumulator so Y makes one HBM round trip
(read into VMEM at k==0, written at the flush) instead of two.

Backends:

  * ``"jnp"``    — the expression the shard_map bodies have always
                   inlined (``omega_tile`` + ``jnp.matmul``), normalized
                   to f32 accumulation: bit-identical to the historical
                   bodies for f32 inputs; for bf16 inputs the historical
                   bodies accumulated in bf16 (see the jnp-backend
                   section below).  The reference semantics.
  * ``"pallas"`` — the fused kernel; native on TPU, interpret mode
                   elsewhere (a correctness tool, not a fast path).
  * ``"auto"``   — ``"pallas"`` on TPU, else ``"jnp"``.

Bitwise contract (pinned by tests/test_local_backend.py): whenever the
contraction dimension is not tiled (``nsteps_k == 1`` — guaranteed by the
default block policy in interpret mode, which takes the whole operand as
one tile), the interpreted ``sketch_block`` reproduces the jnp backend bit
for bit: the Irwin–Hall generator makes the Omega *entries* invariant to
tiling and compilation context (core/rng.py), and an un-split ``lax.dot``
on the same f32 operands is the same reduction.  ``sketch_t_block``,
native kernels and tilings that split the contraction agree to the f32
summation-order bound (~1e-6 relative), same as any re-ordered GEMM: XLA
orders a transposed-operand dot differently, and the MXU sums in its own
order.  Every GEMM asks for f32 products (``core.sketch.F32``).

HBM roofline (the point): per local GEMM the jnp backend touches
``m·k + k·n + m·n`` words (+ ``2·m·n`` more for a read-modify-write
accumulation); the fused backend touches ``m·k + m·n`` — the ``k·n``
Omega stream never exists.  ``plan.model`` prices both so the planner
picks the backend analytically.

Profiles: each ``pallas_call`` is named after its entry point
(``sketch_block``, ``sketch_t_block``, ``gemm_block``,
``fold_rows_block``), which is the device op's name in a profile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import rng
from repro.core.sketch import F32, omega_tile

BACKENDS = ("jnp", "pallas", "auto")


def resolve_backend(backend: str) -> str:
    """Normalize a backend knob to a concrete backend name.

    ``auto`` resolves to the fused Pallas path only where it is a fast
    path (native TPU); everywhere else the jnp body is both the fastest
    and the reference-bitwise choice.  ``xla`` is accepted as an alias of
    ``jnp`` (the streaming accumulator's historical name for it).
    """
    if backend in ("xla", None):
        return "jnp"
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown backend {backend!r} (want jnp|pallas|auto)")
    return backend


def _interpret() -> bool:
    """Pallas interpret mode everywhere but native TPU."""
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# Scoped VMEM the native kernels ask Mosaic for (v5e has 128 MiB per
# core; Mosaic's default scope is 16 MiB), and the part of it the default
# block policy and the autotuner's block filter may fill.
VMEM_LIMIT = 40 * 2 ** 20
VMEM_BUDGET = 32 * 2 ** 20

# Omega entries generated per in-kernel step.  The Philox-4x32-10 /
# Irwin-Hall generator keeps a dozen or so uint32 temporaries live per
# entry; generated over a whole (bk, bn) tile at once they overflow VMEM
# (a 4096x256 tile asked Mosaic for 58 MiB), so the kernels fill their
# Omega tile in (gen_rows, bn) row slices inside a loop, and the slice's
# working set is bounded no matter how large the tile is.
_GEN_ENTRIES = 8192


def gen_rows(bk: int, bn: int) -> int:
    """Omega rows generated per in-kernel step for a (bk, bn) tile: the
    largest multiple of 8 dividing ``bk`` with at most ``_GEN_ENTRIES``
    entries (the whole tile when ``bk`` is not a multiple of 8, which only
    an interpret-mode exact tile can be).
    """
    if bk % 8:
        return bk
    sk = min(bk, max(8, _GEN_ENTRIES // bn // 8 * 8))
    while bk % sk:
        sk -= 8
    return sk


def vmem_fit_bytes(bm: int, bn: int, bk: int, itemsize: int = 4) -> int:
    """Scoped VMEM bytes Mosaic allocates for one fused-GEMM kernel with
    (bm, bn, bk) tiles — an upper bound fitted to the allocation the v5e
    compiler asks for at eleven block shapes of ``sketch_block`` and
    ``sketch_t_block`` (within 15% at the shapes the default policy picks).

    Per entry of the A (or B) panel: its double buffer plus the f32 copy
    and the three bf16 parts of the f32-precision dot.  Per entry of the
    Omega tile: the scratch plus its copy and dot parts (the
    ``gen_rows`` slice's generator working set is in these terms).  Per
    output entry: the double-buffered accumulator input and output tiles,
    the f32 accumulator and the dot's product.  ``sketch_t_block``'s
    Omega tile is (bk, bm), so the Omega term takes the wider of bm and
    bn.  Single source of truth for the default block policy here and the
    autotuner's block-sweep filter (plan/autotune.py).
    """
    om = max(bm, bn)
    return ((12 + 2 * itemsize) * bm * bk + 12 * bk * om
            + 24 * bm * bn)


def default_local_blocks(m: int, n: int, k: int,
                         interpret: bool) -> tuple:
    """(bm, bn, bk) for a local fused GEMM.

    Interpret mode: one exact tile — no padding, no k split — so the
    kernel performs literally the same single ``lax.dot`` as the jnp
    body (the bitwise default the backend matrix tests pin).  Native TPU:
    MXU-aligned tiles shrunk to the VMEM budget.  The contraction is split
    first (down to 512): every row block regenerates its Omega tiles, so
    a tall ``bm`` is what keeps generation from scaling with ``m``; then
    ``bm`` (down to 256), ``bn`` (down to 128), and ``bk`` to the floor.
    """
    if interpret:
        return (m, n, k)
    bm, bn, bk = _round_up(m, 8), _round_up(n, 128), _round_up(k, 128)

    def fit(bm, bn, bk):
        return vmem_fit_bytes(bm, bn, bk) <= VMEM_BUDGET

    while not fit(bm, bn, bk) and bk > 512:
        bk = _round_up(bk // 2, 128)
    while not fit(bm, bn, bk) and bm > 256:
        bm = _round_up(bm // 2, 8)
    while not fit(bm, bn, bk) and bn > 128:
        bn = _round_up(bn // 2, 128)
    while not fit(bm, bn, bk) and bk > 128:
        bk = _round_up(bk // 2, 128)
    return (bm, bn, bk)


def _compiler_params(interpret: bool):
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)


# ---------------------------------------------------------------------------
# jnp backend — the expression the shard_map bodies always inlined, with
# one deliberate normalization: accumulation is f32 on every input dtype
# (Omega drawn at f32, operands upcast, output cast back).  For f32 inputs
# — the dtype every bitwise contract in this repo covers — this is
# bit-identical to the historical inline bodies (astype is the identity);
# for sub-f32 inputs (bf16) the historical bodies quantized Omega to the
# input dtype and accumulated there, so their bits differ from this path.
# The normalization is what makes the two backends comparable at all:
# the Pallas kernel accumulates in f32 by construction (MXU), and the
# backend-parity matrix (tests/test_local_backend.py) pins jnp == pallas
# bitwise for bf16 under exactly this rule.
# ---------------------------------------------------------------------------

def _prec(precision):
    """The caller's precision, or the f32 contract (core/sketch.py F32)."""
    return F32 if precision is None else precision


def _omega_f32(seed, row0, col0, rows: int, cols: int, kind: str, salt: int,
               scale):
    om = omega_tile(seed, row0, col0, rows, cols, kind, jnp.float32,
                    salt=salt)
    if scale is not None:
        om = om * jnp.float32(scale)
    return om


def _sketch_block_jnp(A, seed, cols, row0, col0, kind, salt, scale,
                      precision, acc, out_dtype):
    om = _omega_f32(seed, row0, col0, A.shape[1], cols, kind, salt, scale)
    out = jnp.matmul(A.astype(jnp.float32), om, precision=_prec(precision))
    if acc is not None:
        out = acc.astype(jnp.float32) + out
    return out.astype(out_dtype)


def _sketch_t_block_jnp(B, seed, cols, row0, col0, kind, salt, scale,
                        precision, acc, out_dtype):
    om = _omega_f32(seed, row0, col0, B.shape[0], cols, kind, salt, scale)
    out = jnp.matmul(om.T, B.astype(jnp.float32),
                     precision=_prec(precision))
    if acc is not None:
        out = acc.astype(jnp.float32) + out
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# pallas backend — Omega generated in VMEM at global coordinates; the key
# pair and base offsets arrive as scalar-prefetch operands so shard_map
# bodies can pass traced axis_index products.
# ---------------------------------------------------------------------------

def _om_block(meta_ref, r_off, c_off, rows: int, cols: int, kind: str,
              salt: int, scale):
    """An Omega tile inside the kernel at meta's base + static tile offset."""
    keys = (meta_ref[0], meta_ref[1])
    row0 = meta_ref[2] + jnp.uint32(r_off)
    col0 = meta_ref[3] + jnp.uint32(c_off)
    om = rng.omega_grid(keys, row0, col0, rows, cols, kind, salt)
    if scale is not None:
        om = om * jnp.float32(scale)
    return om


def _fill_omega(meta_ref, om_ref, r_off, c_off, sk: int, kind: str,
                salt: int, scale):
    """Write the Omega tile at (r_off, c_off) into ``om_ref`` in (sk, cols)
    row slices, so the generator's working set is one slice, not the
    tile.  Entry bits depend only on global coordinates (core/rng.py), so
    the slicing never changes them."""
    import jax.experimental.pallas as pl
    rows, cols = om_ref.shape

    def step(s, carry):
        r0 = pl.multiple_of(s * sk, 8)
        om_ref[pl.ds(r0, sk), :] = _om_block(meta_ref, r_off + r0, c_off,
                                             sk, cols, kind, salt, scale)
        return carry

    jax.lax.fori_loop(0, rows // sk, step, 0)


def _fwd_body(meta_ref, a_ref, *refs, bk, bn, sk, nsteps_k, kind, salt,
              scale, acc):
    """acc? + A·Omega on grid (i, j, k): Omega tile (k, j) in VMEM."""
    import jax.experimental.pallas as pl
    if acc:
        y_ref, o_ref, acc_ref, om_ref = refs
    else:
        o_ref, acc_ref, om_ref = refs
    k = pl.program_id(2)
    j = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        # the fused accumulation: Y enters the VMEM accumulator once...
        acc_ref[...] = (y_ref[...].astype(jnp.float32) if acc
                        else jnp.zeros_like(acc_ref))

    _fill_omega(meta_ref, om_ref, k * bk, j * bn, sk, kind, salt, scale)
    a = a_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(a, om_ref[...], precision=F32,
                                preferred_element_type=jnp.float32)

    @pl.when(k == nsteps_k - 1)
    def _flush():
        # ...and leaves once — one HBM round trip instead of two.
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _t_body(meta_ref, b_ref, *refs, bk, bm, sk, nsteps_k, kind, salt, scale,
            acc):
    """acc? + Omega^T·B on grid (i, j, k): Omega tile (k, i) in VMEM."""
    import jax.experimental.pallas as pl
    if acc:
        w_ref, o_ref, acc_ref, om_ref = refs
    else:
        o_ref, acc_ref, om_ref = refs
    k = pl.program_id(2)
    i = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = (w_ref[...].astype(jnp.float32) if acc
                        else jnp.zeros_like(acc_ref))

    _fill_omega(meta_ref, om_ref, k * bk, i * bm, sk, kind, salt, scale)
    b = b_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(om_ref[...].T, b, precision=F32,
                                preferred_element_type=jnp.float32)

    @pl.when(k == nsteps_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _meta(seed, row0, col0):
    """(4,) uint32 scalar-prefetch vector: key pair + global base offsets."""
    k0, k1 = rng.seed_keys(seed)
    return jnp.stack([k0, k1,
                      jnp.asarray(row0, jnp.uint32),
                      jnp.asarray(col0, jnp.uint32)])


def _pad2(X, m: int, n: int):
    if X.shape == (m, n):
        return X
    return jnp.pad(X, ((0, m - X.shape[0]), (0, n - X.shape[1])))


def _sketch_block_pallas(A, seed, cols, row0, col0, kind, salt, scale,
                         acc, out_dtype, blocks, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = A.shape
    bm, bn, bk = blocks or default_local_blocks(m, cols, k, interpret)
    bm, bn, bk = min(bm, _round_up(m, 8)), min(bn, _round_up(cols, 8)), \
        min(bk, _round_up(k, 8))
    mp, np_, kp = _round_up(m, bm), _round_up(cols, bn), _round_up(k, bk)
    # Padding contract (see kernels/ops.py): padded contraction rows of
    # Omega draw at their own global coordinates but multiply zero columns
    # of A; padded output columns are drawn and sliced away.  In-range
    # entries keep their global coordinates, so padding never shifts draws.
    Ap = _pad2(A, mp, kp)
    meta = _meta(seed, row0, col0)
    grid = (mp // bm, np_ // bn, kp // bk)
    kernel = functools.partial(
        _fwd_body, bk=bk, bn=bn, sk=gen_rows(bk, bn),
        nsteps_k=kp // bk, kind=kind, salt=salt, scale=scale,
        acc=acc is not None)
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, kk, m_: (i, kk))]
    operands = [meta, Ap]
    aliases = {}
    if acc is not None:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk, m_: (i, j)))
        operands.append(_pad2(acc.astype(out_dtype), mp, np_))
        aliases = {2: 0}        # acc operand (after meta, A) aliases the out
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, m_: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bk, bn), jnp.float32)])
    out = pl.pallas_call(
        kernel, grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        input_output_aliases=aliases,
        compiler_params=_compiler_params(interpret),
        interpret=interpret, name="sketch_block")(*operands)
    return out[:m, :cols]


def _sketch_t_block_pallas(B, seed, cols, row0, col0, kind, salt, scale,
                           acc, out_dtype, blocks, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k, r2 = B.shape           # contraction over rows of B / rows of Omega
    bm, bn, bk = blocks or default_local_blocks(cols, r2, k, interpret)
    bm, bn, bk = min(bm, _round_up(cols, 8)), min(bn, _round_up(r2, 8)), \
        min(bk, _round_up(k, 8))
    mp, np_, kp = _round_up(cols, bm), _round_up(r2, bn), _round_up(k, bk)
    Bp = _pad2(B, kp, np_)
    meta = _meta(seed, row0, col0)
    grid = (mp // bm, np_ // bn, kp // bk)
    kernel = functools.partial(
        _t_body, bk=bk, bm=bm, sk=gen_rows(bk, bm),
        nsteps_k=kp // bk, kind=kind, salt=salt, scale=scale,
        acc=acc is not None)
    in_specs = [pl.BlockSpec((bk, bn), lambda i, j, kk, m_: (kk, j))]
    operands = [meta, Bp]
    aliases = {}
    if acc is not None:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk, m_: (i, j)))
        operands.append(_pad2(acc.astype(out_dtype), mp, np_))
        aliases = {2: 0}
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, m_: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bk, bm), jnp.float32)])
    out = pl.pallas_call(
        kernel, grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        input_output_aliases=aliases,
        compiler_params=_compiler_params(interpret),
        interpret=interpret, name="sketch_t_block")(*operands)
    return out[:cols, :r2]


# ---------------------------------------------------------------------------
# Dense fused GEMM: acc? + alpha·(A·B) with both operands resident in HBM.
# The gradient-compression backward pass needs two GEMMs whose right-hand
# side is DATA-DEPENDENT (P̂ᵀ·M and P̂·Qᵀ) — not a Philox-generated tile, so
# ``sketch_block`` cannot express them.  What the fused backend still buys
# is the accumulator aliasing: the error-feedback update
# ``E' = M − P̂·Q_locᵀ`` is exactly ``gemm_block(P̂, Q_loc, acc=M, alpha=-1)``
# with M aliased in-place — one HBM round trip instead of the jnp body's
# materialized delta + read-modify-write (``plan.model.grad_compress_cost``
# prices the 4·m·n → 2·m·n halving).  Bitwise-when-untiled for free: both
# backends run one identical ``lax.dot`` on the same f32 operands, scale by
# the same static alpha, then add the accumulator.
# ---------------------------------------------------------------------------

def _gemm_jnp(A, B, alpha, precision, acc, out_dtype):
    out = jnp.matmul(A.astype(jnp.float32), B.astype(jnp.float32),
                     precision=_prec(precision))
    if alpha != 1.0:
        out = out * jnp.float32(alpha)
    if acc is not None:
        out = acc.astype(jnp.float32) + out
    return out.astype(out_dtype)


def _gemm_body(a_ref, b_ref, o_ref, acc_ref, *, nsteps_k, alpha):
    import jax.experimental.pallas as pl
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(a, b, precision=F32,
                                preferred_element_type=jnp.float32)

    @pl.when(k == nsteps_k - 1)
    def _flush():
        d = acc_ref[...]
        if alpha != 1.0:
            d = d * jnp.float32(alpha)
        o_ref[...] = d.astype(o_ref.dtype)


def _gemm_acc_body(a_ref, b_ref, y_ref, o_ref, acc_ref, *, nsteps_k, alpha):
    import jax.experimental.pallas as pl
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(a, b, precision=F32,
                                preferred_element_type=jnp.float32)

    @pl.when(k == nsteps_k - 1)
    def _flush():
        # same association as the jnp body: acc + (dot · alpha) — the
        # accumulator enters once at the flush and leaves through the
        # aliased output, one HBM round trip.
        d = acc_ref[...]
        if alpha != 1.0:
            d = d * jnp.float32(alpha)
        o_ref[...] = (y_ref[...].astype(jnp.float32) + d).astype(o_ref.dtype)


def _gemm_pallas(A, B, alpha, acc, out_dtype, blocks, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = A.shape
    _, n = B.shape
    bm, bn, bk = blocks or default_local_blocks(m, n, k, interpret)
    bm, bn, bk = min(bm, _round_up(m, 8)), min(bn, _round_up(n, 8)), \
        min(bk, _round_up(k, 8))
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    Ap, Bp = _pad2(A, mp, kp), _pad2(B, kp, np_)
    grid = (mp // bm, np_ // bn, kp // bk)
    body = _gemm_acc_body if acc is not None else _gemm_body
    kernel = functools.partial(body, nsteps_k=kp // bk, alpha=alpha)
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))]
    operands = [Ap, Bp]
    aliases = {}
    if acc is not None:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
        operands.append(_pad2(acc.astype(out_dtype), mp, np_))
        aliases = {2: 0}        # acc operand aliases the output in-place
    out = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        input_output_aliases=aliases,
        compiler_params=_compiler_params(interpret),
        interpret=interpret, name="gemm_block")(*operands)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Row-slab fold: Y += zero-padded dY placed at a traced row offset — the
# streaming ``update_rows`` accumulation (stream/distributed.py) and the
# ragged lanes of stream/state.py.  The jnp body materializes the
# zero-padded frame [0_m; d; 0_m] in HBM ((k + 2m)·c words) before the
# slice-add.  The pallas body grids Y in ``bm``-row blocks (Y aliased
# in-place, one HBM round trip) and fetches each block's window of the
# slab from a frame padded by only ``bm`` rows a side: the window start is
# clipped into [0, k + bm], and a clipped window lies wholly in the zero
# pad, exactly as the unclipped one lies wholly outside d.  Mosaic slices
# rows only at multiples of 8 when the offset is traced, so the kernel
# DMAs the 8-aligned (bm + 8)-row window that holds it and selects the
# shift (0..7) among eight static slices.  Bitwise-identical to the jnp
# body: both add the same slab rows (or +0.0) to the same Y rows, and the
# masked form selects with the same ``where``.
# ---------------------------------------------------------------------------

def _fold_rows_jnp(y, d, start, nvalid=None):
    m, c = y.shape
    pad = jnp.zeros((m, c), d.dtype)
    dpad = jnp.concatenate([pad, d, pad], axis=0)
    win = jax.lax.dynamic_slice(dpad, (start, jnp.int32(0)), (m, c))
    if nvalid is None:
        return y + win
    # masked fold: only y rows whose frame coordinate lands inside the
    # first ``nvalid`` rows of d change — every other row keeps y's EXACT
    # bits (a ragged bucket's padded tail must not even add +0.0, which
    # would flip a resident -0.0)
    idx = jnp.int32(start) + jnp.arange(m, dtype=jnp.int32)
    live = (idx >= m) & (idx < m + jnp.int32(nvalid))
    return jnp.where(live[:, None], y + win, y)


def _fold_rows_body(meta_ref, y_ref, d_hbm, o_ref, buf, sem, *, m, k, bm,
                    masked):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    start = meta_ref[0]
    g0 = pl.program_id(0) * bm            # first Y row of this block
    src = jnp.clip(start + g0 - m + bm, 0, k + bm)
    base = pl.multiple_of(src // 8 * 8, 8)
    cp = pltpu.make_async_copy(d_hbm.at[pl.ds(base, bm + 8)], buf, sem)
    cp.start()
    cp.wait()
    y = y_ref[...]
    c = y.shape[1]
    win = buf[0:bm, :c]
    for s in range(1, 8):
        win = jnp.where(src - base == s, buf[s:s + bm, :c], win)
    win = win.astype(y.dtype)
    if masked:
        idx = start + g0 + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        live = (idx >= m) & (idx < m + meta_ref[1])
        o_ref[...] = jnp.where(live, y + win, y)
    else:
        o_ref[...] = y + win


def _fold_rows_pallas(y, d, start, interpret, nvalid=None, block_rows=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, c = y.shape
    k = d.shape[0]
    # interpret mode: one block, the frame of the jnp body; natively
    # 512-row blocks.  ``block_rows`` forces a tiling (tests).
    bm = block_rows or (m if interpret else min(_round_up(m, 8), 512))
    # the slab frame: bm zero rows above, bm + 8 below (the aligned
    # window's overhang), rows to a multiple of 8, lanes to 128, and
    # sub-f32 slabs held in f32 (bf16 -> f32 -> bf16 is exact)
    fdt = jnp.float32 if jnp.dtype(d.dtype).itemsize < 4 else d.dtype
    dp = jnp.pad(d.astype(fdt), ((bm, bm + 8 + _round_up(k, 8) - k),
                                 (0, _round_up(c, 128) - c)))
    masked = nvalid is not None
    meta = jnp.stack([jnp.asarray(start, jnp.int32),
                      jnp.asarray(nvalid if masked else k, jnp.int32)])
    kernel = functools.partial(_fold_rows_body, m=m, k=k, bm=bm,
                               masked=masked)
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(pl.cdiv(m, bm),),
        in_specs=[pl.BlockSpec((bm, c), lambda i, m_: (i, 0)),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((bm, c), lambda i, m_: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bm + 8, dp.shape[1]), fdt),
                        pltpu.SemaphoreType.DMA(())])
    return pl.pallas_call(
        kernel, grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((m, c), y.dtype),
        input_output_aliases={1: 0},    # y aliases the output in-place
        compiler_params=_compiler_params(interpret),
        interpret=interpret, name="fold_rows_block")(meta, y, dp)


def fold_rows_block(y, d, start, backend: str = "jnp", interpret=None,
                    nvalid=None):
    """``y + [0_m; d; 0_m][start : start + m]`` — the row-slab Y fold.

    ``y``: (m, c) resident shard; ``d``: (k, c) slab delta; ``start`` may
    be traced (the shard-relative clipped offset, see
    ``stream/distributed.py``).  Shards outside the slab slice pure zeros,
    so row-disjoint ingest reproduces the full-shape path bitwise.  The
    pallas backend never builds the (k + 2m)-row frame: it DMAs each Y
    block's slab window and aliases ``y`` in-place — 2·m·c accumulate HBM
    words instead of the jnp body's materialized-frame traffic
    (``plan.model``'s ``stream_update_cost`` prices both).

    ``nvalid`` (may be traced) restricts the fold to the first ``nvalid``
    rows of ``d``: y rows fed by rows >= nvalid keep their EXACT input
    bits — not even a +0.0 is added, which is what makes a ragged bucket's
    padded tail provably dead (stream/service.py ``update_ragged``; a +0.0
    add would flip a resident -0.0).  Both backends run the same
    mask + where on the same operands, so the fold stays bitwise-identical
    across backends, and this entry point vmaps over a leading lane axis
    (the batched ragged programs vmap it directly — the lane axis becomes
    one more grid dimension of the same kernel).
    """
    b = resolve_backend(backend)
    if b == "jnp":
        return _fold_rows_jnp(y, d, start, nvalid=nvalid)
    interpret = _interpret() if interpret is None else interpret
    return _fold_rows_pallas(y, d, start, interpret, nvalid=nvalid)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def sketch_block(A, seed, cols: int, *, row0=0, col0=0, kind: str = "normal",
                 salt: int = 0, scale=None, precision=None, acc=None,
                 out_dtype=None, backend: str = "jnp", blocks=None,
                 interpret=None):
    """``acc? + A @ Omega[row0:row0+k, col0:col0+cols]`` (k = A.shape[1]).

    The local body of Alg. 1 / the streaming range update.  ``seed`` may be
    an int or a traced (2,) uint32 key pair; ``row0``/``col0`` may be
    traced (shard offsets).  Accumulation is f32 on both backends; the
    result is cast to ``out_dtype`` (default: A's dtype).  ``acc`` fuses an
    accumulation into the kernel (``Y += ...``); with the Pallas backend
    the accumulator is aliased in-place, one HBM round trip.
    """
    b = resolve_backend(backend)
    out_dtype = out_dtype or A.dtype
    if b == "jnp":
        return _sketch_block_jnp(A, seed, cols, row0, col0, kind, salt,
                                 scale, precision, acc, out_dtype)
    interpret = _interpret() if interpret is None else interpret
    return _sketch_block_pallas(A, seed, cols, row0, col0, kind, salt,
                                scale, acc, out_dtype, blocks, interpret)


def sketch_t_block(B, seed, cols: int, *, row0=0, col0=0,
                   kind: str = "normal", salt: int = 0, scale=None,
                   precision=None, acc=None, out_dtype=None,
                   backend: str = "jnp", blocks=None, interpret=None):
    """``acc? + Omega[row0:row0+n, col0:col0+cols]^T @ B`` (n = B.shape[0]).

    The local body of the Nyström second stages (C = Omega^T·B) and the
    streaming co-range update (W += Psi·H, with Psi's salt).  Same traced
    seed/offset and f32-accumulation contract as :func:`sketch_block`.
    """
    b = resolve_backend(backend)
    out_dtype = out_dtype or B.dtype
    if b == "jnp":
        return _sketch_t_block_jnp(B, seed, cols, row0, col0, kind, salt,
                                   scale, precision, acc, out_dtype)
    interpret = _interpret() if interpret is None else interpret
    return _sketch_t_block_pallas(B, seed, cols, row0, col0, kind, salt,
                                  scale, acc, out_dtype, blocks, interpret)


def gemm_block(A, B, *, alpha: float = 1.0, precision=None, acc=None,
               out_dtype=None, backend: str = "jnp", blocks=None,
               interpret=None):
    """``acc? + alpha · (A @ B)`` — dense fused local GEMM.

    The data-dependent sibling of :func:`sketch_block` for bodies whose
    right operand is NOT a Philox tile — the gradient-compression factors
    ``P̂ᵀ·M``, ``P̂·Qᵀ`` and the error-feedback update
    ``E' = gemm_block(P̂, Q_loc, acc=M, alpha=-1)`` (the accumulator is
    aliased in-place on the pallas backend: one HBM round trip, the
    2·m·n vs 4·m·n term in ``plan.model.grad_compress_cost``).

    ``alpha`` must be static (baked into the kernel body).  Accumulation
    is f32 on both backends and the association is fixed as
    ``acc + (dot · alpha)``, so an untiled contraction (the interpret-mode
    default block policy) is bitwise-identical across backends — the same
    single ``lax.dot`` on the same operands.
    """
    b = resolve_backend(backend)
    out_dtype = out_dtype or A.dtype
    alpha = float(alpha)
    if b == "jnp":
        return _gemm_jnp(A, B, alpha, precision, acc, out_dtype)
    interpret = _interpret() if interpret is None else interpret
    return _gemm_pallas(A, B, alpha, acc, out_dtype, blocks, interpret)
