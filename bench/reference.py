"""Plain references of what each cell computes, and the numbers compared.

Nothing here imports the program.  Omega is defined by the paper's
generator (Philox-4x32-10 keyed by the 64-bit seed split into two words,
counter = (row, column, salt, lane), and an Irwin-Hall normal from twelve
24-bit uniforms), written out again below in plain ``jax.numpy``.

Two precisions of the same arithmetic:

  * ``highest`` — float32 products at ``Precision.HIGHEST``, the precision
    the configurations state: the reference;
  * ``high3``   — the three-pass bfloat16 product, ``Precision.HIGH`` on a
    TPU and written out on other backends (whose HIGH is full float32):
    the control, the nearest precision below the stated one.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

shard_map = jax.shard_map

M0, M1 = np.uint32(0xD2511F53), np.uint32(0xCD9E8D57)
W0, W1 = np.uint32(0x9E3779B9), np.uint32(0xBB67AE85)
OMEGA_SALT = 0


def seed_words(seed: int) -> Tuple[int, int]:
    """The Philox key of a 64-bit seed: its low and high 32-bit words."""
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def _mul_wide(a, b):
    """High and low words of the 64-bit product of two uint32 arrays, from
    16-bit halves (no 64-bit integers needed)."""
    a0, a1 = a & 0xFFFF, a >> 16
    b0, b1 = b & 0xFFFF, b >> 16
    p00, p01, p10, p11 = a0 * b0, a0 * b1, a1 * b0, a1 * b1
    cross = (p00 >> 16) + (p01 & 0xFFFF) + (p10 & 0xFFFF)
    lo = (p00 & 0xFFFF) | (cross << 16)
    hi = p11 + (p01 >> 16) + (p10 >> 16) + (cross >> 16)
    return hi, lo


def philox(c0, c1, c2, c3, k0, k1):
    """Philox-4x32 with ten rounds."""
    for _ in range(10):
        h0, l0 = _mul_wide(jnp.uint32(M0), c0)
        h1, l1 = _mul_wide(jnp.uint32(M1), c2)
        c0, c1, c2, c3 = h1 ^ c1 ^ k0, l1, h0 ^ c3 ^ k1, l0
        k0, k1 = k0 + W0, k1 + W1
    return c0, c1, c2, c3


def omega(key, rows: int, cols: int, salt: int, row0=0):
    """Rows [row0, row0 + rows) of the dense normal Omega of ``key``, a
    (2,) uint32 array: entry (i, j) depends only on (key, salt, i, j)."""
    u32 = jnp.uint32
    i = jnp.asarray(row0, u32) + jax.lax.broadcasted_iota(u32, (rows, cols), 0)
    j = jax.lax.broadcasted_iota(u32, (rows, cols), 1)
    k0 = jnp.broadcast_to(key[0].astype(u32), (rows, cols))
    k1 = jnp.broadcast_to(key[1].astype(u32), (rows, cols))
    s = jnp.full((rows, cols), salt, u32)
    total = jnp.zeros((rows, cols), u32)
    for lane in (1, 2, 3):
        words = philox(i, j, s, jnp.full((rows, cols), lane, u32), k0, k1)
        for w in words:
            total = total + (w >> 8)
    centred = total.astype(jnp.int32) - jnp.int32(6 << 24)
    return centred.astype(jnp.float32) * jnp.float32(2.0 ** -24)


def key_array(seed: int):
    return jnp.asarray(seed_words(seed), jnp.uint32)


# ---------------------------------------------------------------------------
# products at the two precisions
# ---------------------------------------------------------------------------

def _split(x):
    """x = hi + lo + O(2^-17 |x|), hi and lo bfloat16 values.  The rounding
    is ``reduce_precision``, which XLA keeps: a float32 -> bfloat16 ->
    float32 round trip may be folded away on a TPU."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def dot(a, b, precision: str):
    """a @ b for float32 operands at ``highest``, ``high3`` (three bfloat16
    passes: ``Precision.HIGH`` on a TPU, written out elsewhere) or
    ``high3_emulated`` (written out everywhere)."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "high3" and jax.default_backend() == "tpu":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    if precision not in ("high3", "high3_emulated"):
        raise ValueError(precision)
    ah, al = _split(a)
    bh, bl = _split(b)
    mm = functools.partial(jnp.matmul, preferred_element_type=jnp.float32)
    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


# ---------------------------------------------------------------------------
# dense cells: B = A·Omega, row-sharded over the devices
# ---------------------------------------------------------------------------

def _row_blocks(f, a, block: int):
    """f applied to each ``block`` rows of ``a``, rows concatenated."""
    n = a.shape[0]
    block = min(block, n)
    out = jax.lax.map(f, a.reshape(n // block, block, a.shape[1]))
    return out.reshape(n, -1)


@functools.lru_cache(maxsize=None)
def _dense_prog(devices: Tuple, n: int, r: int, precision: str, block: int):
    mesh = Mesh(np.asarray(devices), ("x",))

    def body(a, key):
        om = omega(key, n, r, OMEGA_SALT)
        return _row_blocks(lambda blk: dot(blk, om, precision), a, block)

    fn = shard_map(body, mesh=mesh, in_specs=(P("x", None), P()),
                   out_specs=P("x", None), check_vma=False)
    return jax.jit(fn, in_shardings=(NamedSharding(mesh, P("x", None)),
                                     NamedSharding(mesh, P())))


def dense(A, seed: int, r: int, precision: str = "highest", devices=None,
          block: int = 4096):
    """B = A·Omega of a square A that is row-sharded over ``devices``."""
    devices = tuple(devices if devices is not None else jax.devices()[:1])
    fn = _dense_prog(devices, A.shape[0], r, precision, block)
    return fn(A, key_array(seed))


# ---------------------------------------------------------------------------
# the numbers compared
# ---------------------------------------------------------------------------

@jax.jit
def row_gaps(x, ref):
    """Per-row gap ||x_i - ref_i|| over the larger of ||ref_i|| and the
    median row norm of ref, so that rows near zero do not decide."""
    x = x.astype(jnp.float32)
    ref = ref.astype(jnp.float32)
    num = jnp.linalg.norm(x - ref, axis=1)
    den = jnp.linalg.norm(ref, axis=1)
    return num / jnp.maximum(den, jnp.median(den))


def worst_row(x, ref) -> float:
    """The worst row's gap (see :func:`row_gaps`)."""
    return float(jnp.max(row_gaps(x, ref)))

