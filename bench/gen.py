"""Inputs made from the run's seed: dense matrices on the device.

The matrix generator is the one ``chip_smoke.py`` runs, copied here so that
the benchmark's inputs do not move when that script or the program does.
"""
from __future__ import annotations


def jax_key(seed: int):
    """A JAX key from any non-negative seed (folded to 32 bits per half)."""
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def kernel_matrix(seed: int, n: int, d: int = 4, sharding=None):
    """A symmetric positive-definite n x n f32 matrix made on the device in
    one program: the L1-Laplace kernel exp(-|x_i - x_j|_1 / d) of n
    Gaussian points in d dimensions.  A is symmetric up to the rounding of
    ``exp``, which a vectorised backend may take differently for (i, j)
    and (j, i)."""
    import jax
    import jax.numpy as jnp

    def f(k):
        x = jax.random.normal(k, (n, d), jnp.float32)
        dist = jnp.sum(jnp.abs(x[:, None, :] - x[None, :, :]), axis=-1)
        return jnp.exp(-dist / d)
    return jax.jit(f, out_shardings=sharding)(jax_key(seed))


MATRICES = {
    "kernel_l1": lambda seed, cfg, sh: kernel_matrix(
        seed, cfg["n"], cfg["points_dim"], sh),
}
