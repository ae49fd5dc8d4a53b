"""Pure-jnp oracles for the Pallas kernels.

The Omega construction calls the *same* generator as the kernel bodies
(``core.rng.omega_grid``), keyed by global coordinates, so oracle and
kernel agree bitwise on Omega; results agree to float accumulation order.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import rng


def omega_ref(seed: int, n2: int, r: int, kind: str = "normal",
              salt: int = 0, dtype=jnp.float32):
    """The whole (n2, r) Omega, generated as one tile at offset (0, 0)."""
    return rng.omega_grid(rng.seed_keys(seed), 0, 0, n2, r, kind,
                          salt).astype(dtype)


def sketch_matmul_ref(A, seed: int, r: int, kind: str = "normal",
                      salt: int = 0, out_dtype=None):
    """B = A @ Omega, f32 accumulation."""
    n2 = A.shape[-1]
    om = omega_ref(seed, n2, r, kind, salt)
    out = jnp.matmul(A.astype(jnp.float32), om)
    return out.astype(out_dtype or A.dtype)


def sketch_t_matmul_ref(B, seed: int, r: int, kind: str = "normal",
                        salt: int = 0, out_dtype=None):
    """C = Omega^T @ B, f32 accumulation; Omega is (n x r)."""
    n = B.shape[0]
    om = omega_ref(seed, n, r, kind, salt)
    out = jnp.matmul(om.T, B.astype(jnp.float32))
    return out.astype(out_dtype or B.dtype)
