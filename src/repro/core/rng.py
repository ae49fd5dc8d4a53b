"""Counter-based pseudorandom generation for communication-free sketching.

The paper's central systems insight (§6.3) is that a dense random sketching
matrix Omega never needs to be *communicated*: any processor can regenerate
exactly the block it consumes from a shared seed using a counter-based PRNG
(they use Philox-4x32-10 via MKL/cuRAND).  This module is the one Omega
generator: a pure-jnp Philox-4x32-10 (the paper's exact generator, Salmon
et al. SC'11), written only with uint32 ops and 16-bit-limb multiplies so
the identical bitstream is reproducible inside a Pallas TPU kernel (no
64-bit multiply on the TPU VPU).  Entries are keyed by *global*
coordinates, so any tiling or processor grid regenerates bit-identical
blocks.

``seed_keys`` turns a seed into the Philox key pair and ``omega_grid``
maps (key pair, global offset, tile shape, kind, salt) to a dense f32
tile.  ``core/sketch.py``'s ``omega_tile``, the kernel bodies of
``kernels/sketch_matmul.py`` and ``kernels/local.py``, and the oracle
``kernels/ref.py`` all generate dense Omega through ``omega_grid``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Philox-4x32-10 in pure jnp uint32 ops (TPU-VPU compatible: no 64-bit mult)
# ---------------------------------------------------------------------------

PHILOX_M0 = np.uint32(0xD2511F53)
PHILOX_M1 = np.uint32(0xCD9E8D57)
PHILOX_W0 = np.uint32(0x9E3779B9)  # golden ratio
PHILOX_W1 = np.uint32(0xBB67AE85)  # sqrt(3) - 1
PHILOX_ROUNDS = 10


def _u32(x):
    return jnp.asarray(x, jnp.uint32)


def _mulhilo32(a, b):
    """(hi, lo) of the 32x32->64 bit product using 16-bit limbs.

    TPU VPU has no 64-bit integer multiply; CUDA's ``mulhi.u32`` must be
    re-derived via schoolbook 16-bit limbs so the same code runs in a Pallas
    kernel body and in plain jnp.
    """
    a = _u32(a)
    b = _u32(b)
    a_lo = a & 0xFFFF
    a_hi = a >> 16
    b_lo = b & 0xFFFF
    b_hi = b >> 16

    ll = a_lo * b_lo                     # <= (2^16-1)^2 < 2^32, exact in u32
    lh = a_lo * b_hi
    hl = a_hi * b_lo
    hh = a_hi * b_hi

    # low 32 bits: ll + ((lh + hl) << 16)  (mod 2^32)
    mid = lh + hl                         # may wrap; handle carry manually
    mid_carry = _u32(mid < lh)            # wrapped iff result < an addend
    lo = ll + (mid << 16)
    lo_carry = _u32(lo < ll)
    # high 32 bits: hh + (mid >> 16) + (mid_carry << 16) + carry from lo
    hi = hh + (mid >> 16) + (mid_carry << 16) + lo_carry
    return hi, lo


def _philox_round(c0, c1, c2, c3, k0, k1):
    hi0, lo0 = _mulhilo32(PHILOX_M0, c0)
    hi1, lo1 = _mulhilo32(PHILOX_M1, c2)
    n0 = hi1 ^ c1 ^ k0
    n1 = lo1
    n2 = hi0 ^ c3 ^ k1
    n3 = lo0
    return n0, n1, n2, n3


def philox_4x32(counter: Tuple[jnp.ndarray, ...], key: Tuple[jnp.ndarray, jnp.ndarray],
                rounds: int = PHILOX_ROUNDS):
    """Philox-4x32 with ``rounds`` rounds (default 10, the standard).

    ``counter`` is a 4-tuple and ``key`` a 2-tuple of uint32 arrays of any
    broadcastable shape. Returns 4 uint32 arrays of the broadcast shape.
    """
    c0, c1, c2, c3 = (_u32(c) for c in counter)
    k0, k1 = _u32(key[0]), _u32(key[1])
    for _ in range(rounds):
        c0, c1, c2, c3 = _philox_round(c0, c1, c2, c3, k0, k1)
        k0 = k0 + PHILOX_W0
        k1 = k1 + PHILOX_W1
    return c0, c1, c2, c3


def _uniform_from_u32(bits):
    """uint32 -> float32 uniform in [0, 1) with 24-bit mantissa usage.

    The convert goes through int32: after ``>> 8`` the value is below
    2^24, so both converts are exact (same bits as a direct uint32 ->
    float32), and Mosaic lowers int32 -> float32 but not uint32 -> float32.
    """
    return ((bits >> 8).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))


# ---------------------------------------------------------------------------
# Bit-exact normal generation (Irwin-Hall / CLT-12).
#
# Why not Box-Muller: jnp.log / jnp.cos lower to backend libm or SIMD
# approximations whose rounding differs between vector widths — the same
# input value can yield different low bits depending on the *shape* of the
# array it sits in (vector body vs. scalar remainder lane).  And any
# hand-rolled polynomial replacement is context-dependent instead: inside a
# jit fusion XLA's CPU backend contracts mul+add chains into FMAs, so even
# plain `a*b + c` rounds differently eager vs. jitted.  Either way the tile
# shape or the consumer's compilation context leaks into Omega's bits,
# breaking the regenerate-don't-communicate determinism contract.
#
# The Irwin-Hall transform has NO roundable float arithmetic at all:
#
#     z = (sum of 12 uniform 24-bit integers - 6*2^24) * 2^-24
#
# Integer adds are exact; the int->float convert is correctly rounded by
# IEEE on every backend; the final scale is a power of two (exponent shift,
# exact).  The entry bits therefore depend on nothing but (seed, salt,
# global coordinate) — invariant to tiling, fusion, vectorization, and
# backend.  Statistically: mean 0, variance 12 * (1/12) = 1, support
# [-6, 6] (subgaussian), which preserves every sketching guarantee used
# here (JL-type embeddings need only subgaussian entries).  Costs 3 Philox
# invocations per entry (12 lanes) instead of Box-Muller's 1.
# ---------------------------------------------------------------------------


def philox_uniform_grid(key0: jnp.ndarray, key1: jnp.ndarray,
                        row0: jnp.ndarray, col0: jnp.ndarray,
                        rows: int, cols: int,
                        salt: int = 0) -> jnp.ndarray:
    """A (rows, cols) float32 uniform[0,1) tile.

    Entry (i, j) depends only on the *global* coordinates
    (row0 + i, col0 + j) and the key — independent of the tiling — so any
    tile decomposition regenerates identical values (the paper's
    regenerate-don't-communicate invariant at tile granularity).
    """
    gi = row0 + jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
    gj = col0 + jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    r0, r1, r2, r3 = philox_4x32(
        (gi, gj, _u32(salt) + jnp.zeros_like(gi), jnp.zeros_like(gi)),
        (key0, key1))
    del r1, r2, r3
    return _uniform_from_u32(r0)


def philox_normal_grid(key0: jnp.ndarray, key1: jnp.ndarray,
                       row0: jnp.ndarray, col0: jnp.ndarray,
                       rows: int, cols: int,
                       salt: int = 0) -> jnp.ndarray:
    """A (rows, cols) float32 ~N(0,1) tile, bit-exact on every backend.

    Irwin-Hall: the sum of 12 uniform 24-bit lanes, centered and scaled —
    see the block comment above for why this beats Box-Muller here (zero
    roundable float ops => entry bits depend only on seed/salt/global
    coordinate, never on tile shape or fusion context).  Three Philox
    invocations per entry; the sub-counter lives in counter lane c3
    (offset by 1 so the normal stream never aliases the uniform stream's
    c3 = 0 block).
    """
    gi = row0 + jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
    gj = col0 + jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    salt_c = _u32(salt) + jnp.zeros_like(gi)
    total = jnp.zeros_like(gi)                         # uint32; max 12*2^24
    for sub in range(3):
        r0, r1, r2, r3 = philox_4x32(
            (gi, gj, salt_c, _u32(sub + 1) + jnp.zeros_like(gi)),
            (key0, key1))
        total = total + (r0 >> 8) + (r1 >> 8) + (r2 >> 8) + (r3 >> 8)
    d = total.astype(jnp.int32) - jnp.int32(6 * (1 << 24))   # exact
    return d.astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


# ---------------------------------------------------------------------------
# The dense Omega generator: seed -> key pair, (kind, offset, shape) -> tile
# ---------------------------------------------------------------------------

def seed_keys(seed):
    """The Philox (key0, key1) pair for a seed.

    ``seed`` may be a Python int (split into two uint32 halves, as the
    one-shot APIs have always done) or a JAX value — a scalar or a shape-(2,)
    uint32 array — so the streaming sketch service can trace the seed and
    share one compiled update executable across every concurrent stream.
    A Python int < 2**32 and the equivalent traced uint32 scalar produce
    bitwise-identical Omega entries.
    """
    if isinstance(seed, (int, np.integer)):
        seed = int(seed)
        return (jnp.uint32(seed & 0xFFFFFFFF),
                jnp.uint32((seed >> 32) & 0xFFFFFFFF))
    seed = jnp.asarray(seed)
    if seed.shape == (2,):
        return seed[0].astype(jnp.uint32), seed[1].astype(jnp.uint32)
    if seed.shape == ():
        return seed.astype(jnp.uint32), jnp.zeros((), jnp.uint32)
    raise ValueError(f"seed must be an int, a scalar, or a (2,) key pair; "
                     f"got shape {seed.shape}")


def omega_grid(keys, row0, col0, rows: int, cols: int,
               kind: str = "normal", salt: int = 0) -> jnp.ndarray:
    """The (rows, cols) f32 tile at global offset (row0, col0) of a dense
    Omega: ``normal`` (Irwin-Hall), ``uniform`` on [0, 1), or
    ``rademacher`` (+-1, the uniform grid's ``u < 0.5``).

    ``keys`` is a (key0, key1) uint32 pair (:func:`seed_keys`); the
    offsets may be traced.  Only uint32 and exact ops, so the jnp paths
    and the Pallas kernel bodies get the same bits from one definition.
    """
    key0, key1 = keys
    row0 = jnp.asarray(row0, jnp.uint32)
    col0 = jnp.asarray(col0, jnp.uint32)
    if kind == "normal":
        return philox_normal_grid(key0, key1, row0, col0, rows, cols, salt)
    if kind == "uniform":
        return philox_uniform_grid(key0, key1, row0, col0, rows, cols, salt)
    if kind == "rademacher":
        u = philox_uniform_grid(key0, key1, row0, col0, rows, cols, salt)
        return jnp.where(u < 0.5, jnp.float32(-1), jnp.float32(1))
    raise ValueError(f"unknown omega kind {kind!r}")


# ---------------------------------------------------------------------------
# Sparse sketch family draws (CountSketch buckets/signs + coordinated
# sampling membership).  Same determinism contract as the grids above:
# pure uint32 Philox on GLOBAL coordinates, zero roundable float ops, so
# every draw is bitwise invariant to tiling, shard offsets, and fusion
# context.  Counter-lane budget under one salt: c3 = 0 is the uniform
# grid, c3 in {1, 2, 3} the Irwin-Hall sub-draws, c3 = 4 the bucket/sign
# stream, c3 = 5 the sampling-membership stream — the five streams never
# alias.  Draws are PER ROW (counter (g, 0, salt, c3) with g the global
# row index), which is what makes a sparse Omega tile-decomposable: any
# column slice of row g sees the same (bucket, sign, membership).
# ---------------------------------------------------------------------------

COUNTSKETCH_LANE = 4   # c3 lane of the bucket/sign stream
ROWSAMPLE_LANE = 5     # c3 lane of the coordinated-membership stream


def philox_countsketch_rows(key0: jnp.ndarray, key1: jnp.ndarray,
                            g, r: int, salt: int = 0):
    """(bucket, sign) draws for global Omega rows ``g`` (uint32 array or a
    scalar offset; any shape).

    One Philox invocation per row at counter ``(g, 0, salt, 4)``: bucket
    is ``r0 mod r`` (uint32 — the ~r/2^32 modulo bias is negligible and
    deterministic, the same convention scipy's Clarkson-Woodruff transform
    uses), sign is the low bit of ``r1`` mapped to float32 +-1.  Row g's
    draw depends only on (key, salt, g) — never on which tile asked.
    """
    g = _u32(g)
    z = jnp.zeros_like(g)
    r0, r1, r2, r3 = philox_4x32(
        (g, z, _u32(salt) + z, _u32(COUNTSKETCH_LANE) + z), (key0, key1))
    del r2, r3
    bucket = r0 % _u32(r)
    sign = jnp.where((r1 & 1) == 1, jnp.float32(1.0), jnp.float32(-1.0))
    return bucket, sign


def philox_rowsample_uniform(key0: jnp.ndarray, key1: jnp.ndarray,
                             g, salt: int = 0) -> jnp.ndarray:
    """Coordinated membership draw u in [0, 1) for global rows ``g``.

    Counter ``(g, 0, salt, 5)``.  "Coordinated" (Daliri-Freire-Li-Musco,
    arXiv 2501.17836): u depends only on (key, salt, g), so two parties
    sketching DIFFERENT matrices under the same seed keep exactly the
    same row subset ``{g : u_g < p}`` — the property their inner-product
    estimators need — without exchanging a byte.
    """
    g = _u32(g)
    z = jnp.zeros_like(g)
    r0, r1, r2, r3 = philox_4x32(
        (g, z, _u32(salt) + z, _u32(ROWSAMPLE_LANE) + z), (key0, key1))
    del r1, r2, r3
    return _uniform_from_u32(r0)


def philox_countsketch_grid(key0: jnp.ndarray, key1: jnp.ndarray,
                            row0, col0, rows: int, cols: int,
                            r_total: int, salt: int = 0) -> jnp.ndarray:
    """Materialized (rows, cols) tile of the CountSketch Omega
    (Clarkson-Woodruff): row g carries a single +-1 at column bucket(g)
    of the GLOBAL width ``r_total``; this tile sees the part of it that
    lands in [col0, col0+cols)."""
    g = _u32(row0) + jax.lax.broadcasted_iota(jnp.uint32, (rows,), 0)
    bucket, sign = philox_countsketch_rows(key0, key1, g, r_total, salt)
    gj = _u32(col0) + jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    return jnp.where(bucket[:, None] == gj, sign[:, None], jnp.float32(0.0))


def philox_rowsample_grid(key0: jnp.ndarray, key1: jnp.ndarray,
                          row0, col0, rows: int, cols: int,
                          r_total: int, n_total: int,
                          salt: int = 0) -> jnp.ndarray:
    """Materialized (rows, cols) tile of the coordinated row-sampling
    Omega: row g participates iff its coordinated uniform u_g < p with
    p = min(1, r_total / n_total) (expected r_total sampled rows out of
    the global n_total), and a participating row carries
    sign(g) / sqrt(p) at column bucket(g) — an unbiased sampled
    CountSketch (E[Omega Omega^T] = I) whose row subset is seed-
    coordinated across matrices.  p and 1/sqrt(p) are Python-side
    constants of (r_total, n_total): no traced float op depends on tile
    shape, so entry bits stay tile/context invariant.
    """
    import math
    p = min(1.0, float(r_total) / float(n_total))
    scale = np.float32(1.0 / math.sqrt(p))
    g = _u32(row0) + jax.lax.broadcasted_iota(jnp.uint32, (rows,), 0)
    bucket, sign = philox_countsketch_rows(key0, key1, g, r_total, salt)
    u = philox_rowsample_uniform(key0, key1, g, salt)
    val = jnp.where(u < np.float32(p), sign * scale, jnp.float32(0.0))
    gj = _u32(col0) + jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    return jnp.where(bucket[:, None] == gj, val[:, None], jnp.float32(0.0))
