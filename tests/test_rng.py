"""Philox-4x32 correctness + the tile-decomposition-invariance property that
makes regeneration communication-free."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

from repro.core import rng
from repro.core.sketch import omega_tile
from repro.kernels import gen_omega
from repro.kernels.ref import omega_ref


# ---------------------------------------------------------------------------
# 16-bit-limb mulhilo vs native 64-bit reference
# ---------------------------------------------------------------------------

@settings(max_examples=200, deadline=None)
@given(a=st.integers(0, 2**32 - 1), b=st.integers(0, 2**32 - 1))
def test_mulhilo32_matches_uint64_reference(a, b):
    hi, lo = rng._mulhilo32(jnp.uint32(a), jnp.uint32(b))
    prod = (a * b) & 0xFFFFFFFFFFFFFFFF
    assert int(lo) == prod & 0xFFFFFFFF
    assert int(hi) == prod >> 32


def test_mulhilo32_vectorized():
    an = np.arange(0, 2**32 - 1, 104729, dtype=np.uint64)
    bn = np.arange(1, 2**32, 99991, dtype=np.uint64)[: an.shape[0]]
    hi, lo = rng._mulhilo32(jnp.asarray(an.astype(np.uint32)),
                            jnp.asarray(bn.astype(np.uint32)))
    prod = an * bn
    np.testing.assert_array_equal(
        np.asarray(lo).astype(np.uint64), prod & np.uint64(0xFFFFFFFF))
    np.testing.assert_array_equal(
        np.asarray(hi).astype(np.uint64), prod >> np.uint64(32))


# ---------------------------------------------------------------------------
# Philox known-answer test (Random123 reference vectors)
# ---------------------------------------------------------------------------

def test_philox_4x32_10_known_answer():
    """Reference vectors from the Random123 distribution (kat_vectors):
    philox4x32-10 with counter=0, key=0 and all-ones inputs."""
    out = rng.philox_4x32(
        (jnp.uint32(0), jnp.uint32(0), jnp.uint32(0), jnp.uint32(0)),
        (jnp.uint32(0), jnp.uint32(0)))
    got = [int(x) for x in out]
    assert got == [0x6627E8D5, 0xE169C58D, 0xBC57AC4C, 0x9B00DBD8]

    out = rng.philox_4x32(
        tuple(jnp.uint32(0xFFFFFFFF) for _ in range(4)),
        (jnp.uint32(0xFFFFFFFF), jnp.uint32(0xFFFFFFFF)))
    got = [int(x) for x in out]
    assert got == [0x408F276D, 0x41C83B0E, 0xA20BC7C6, 0x6D5451FD]

    out = rng.philox_4x32(
        (jnp.uint32(0x243F6A88), jnp.uint32(0x85A308D3),
         jnp.uint32(0x13198A2E), jnp.uint32(0x03707344)),
        (jnp.uint32(0xA4093822), jnp.uint32(0x299F31D0)))
    got = [int(x) for x in out]
    assert got == [0xD16CFE09, 0x94FDCCEB, 0x5001E420, 0x24126EA1]


# ---------------------------------------------------------------------------
# Tile-decomposition invariance: the regenerate-don't-communicate invariant
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(
    rows=st.integers(2, 48), cols=st.integers(2, 48),
    r0=st.integers(0, 1000), c0=st.integers(0, 1000),
    seed=st.integers(0, 2**63 - 1),
)
def test_tile_decomposition_invariance(rows, cols, r0, c0, seed):
    k0 = jnp.uint32(seed & 0xFFFFFFFF)
    k1 = jnp.uint32(seed >> 32)
    full = rng.philox_normal_grid(k0, k1, jnp.uint32(r0), jnp.uint32(c0),
                                  rows, cols)
    # split into 4 quadrants generated independently
    rh, ch = rows // 2, cols // 2
    q00 = rng.philox_normal_grid(k0, k1, jnp.uint32(r0), jnp.uint32(c0), rh, ch)
    q01 = rng.philox_normal_grid(k0, k1, jnp.uint32(r0), jnp.uint32(c0 + ch),
                                 rh, cols - ch)
    q10 = rng.philox_normal_grid(k0, k1, jnp.uint32(r0 + rh), jnp.uint32(c0),
                                 rows - rh, ch)
    q11 = rng.philox_normal_grid(k0, k1, jnp.uint32(r0 + rh),
                                 jnp.uint32(c0 + ch), rows - rh, cols - ch)
    reassembled = jnp.block([[q00, q01], [q10, q11]])
    np.testing.assert_array_equal(np.asarray(full), np.asarray(reassembled))


def test_uniform_range_and_moments():
    u = rng.philox_uniform_grid(jnp.uint32(1), jnp.uint32(2),
                                jnp.uint32(0), jnp.uint32(0), 512, 512)
    u = np.asarray(u)
    assert u.min() >= 0.0 and u.max() < 1.0
    assert abs(u.mean() - 0.5) < 5e-3
    assert abs(u.var() - 1 / 12) < 5e-3


def test_normal_moments_and_independence_across_salt():
    g1 = np.asarray(rng.philox_normal_grid(jnp.uint32(1), jnp.uint32(2),
                                           jnp.uint32(0), jnp.uint32(0),
                                           512, 512, salt=0))
    g2 = np.asarray(rng.philox_normal_grid(jnp.uint32(1), jnp.uint32(2),
                                           jnp.uint32(0), jnp.uint32(0),
                                           512, 512, salt=1))
    assert abs(g1.mean()) < 5e-3
    assert abs(g1.std() - 1.0) < 5e-3
    corr = np.corrcoef(g1.ravel(), g2.ravel())[0, 1]
    assert abs(corr) < 5e-3
    assert not np.array_equal(g1, g2)


# ---------------------------------------------------------------------------
# One dense Omega generator: every front end returns omega_grid's bits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["normal", "uniform", "rademacher"])
def test_omega_grid_is_every_front_end(kind):
    """``rng.omega_grid`` at a non-zero offset is bitwise the tile that
    ``core.sketch.omega_tile``, a slice of ``kernels.ref.omega_ref`` and
    a slice of the interpreted ``gen_omega`` kernel give; an int seed, the
    same seed as a traced uint32 scalar and as a (2,) key pair give the
    same bits, and seed + 1 does not."""
    seed, salt = 123, 2
    r0, c0, rows, cols = 24, 8, 24, 16
    n2, r = r0 + rows, c0 + cols
    got = np.asarray(rng.omega_grid(rng.seed_keys(seed), r0, c0, rows, cols,
                                    kind, salt))
    assert got.dtype == np.float32

    tile = omega_tile(seed, r0, c0, rows, cols, kind, salt=salt)
    ref = omega_ref(seed, n2, r, kind, salt)[r0:, c0:]
    kern = gen_omega(seed=seed, n2=n2, r=r, br=8, bc=8, kind=kind,
                     salt=salt, interpret=True)[r0:, c0:]
    for other in (tile, ref, kern):
        np.testing.assert_array_equal(np.asarray(other), got)

    def traced(s):
        return rng.omega_grid(rng.seed_keys(s), r0, c0, rows, cols, kind,
                              salt)

    scalar = jax.jit(traced)(jnp.uint32(seed))
    pair = jax.jit(traced)(jnp.array([seed, 0], jnp.uint32))
    np.testing.assert_array_equal(np.asarray(scalar), got)
    np.testing.assert_array_equal(np.asarray(pair), got)

    big = 2 ** 33 + seed
    np.testing.assert_array_equal(
        np.asarray(traced(big)),
        np.asarray(jax.jit(traced)(jnp.array([seed, 2], jnp.uint32))))
    assert not np.array_equal(np.asarray(traced(seed + 1)), got)


def test_omega_grid_rejects_an_unknown_kind():
    with pytest.raises(ValueError, match="unknown omega kind"):
        rng.omega_grid(rng.seed_keys(0), 0, 0, 8, 8, "countsketch")
