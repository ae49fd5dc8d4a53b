"""The Nyström pair (the paper's Alg. 2): its work and its plain reference.

B = A·Omega, then C = Omega^T·B with the same Omega.  Like
``bench/reference.py``, nothing here imports the program: B's reference
is :func:`bench.reference.dense` and Omega is
:func:`bench.reference.omega`, the paper's generator written out again.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax

from . import reference


def work(n: int, r: int, itemsize: int = 4) -> Dict[str, float]:
    """What one pair needs, from its shapes (as ``bench/work.py`` counts):
    A read once, B and C written once, Omega never read.  ``flops`` and
    ``bytes`` are the whole pair's; ``stage2_flops`` and ``stage2_bytes``
    those of C = Omega^T·B alone, B read once and C written once."""
    return {"flops": 2.0 * n * n * r + 2.0 * n * r * r,
            "bytes": float(itemsize) * (n * n + n * r + r * r),
            "stage2_flops": 2.0 * n * r * r,
            "stage2_bytes": float(itemsize) * (n * r + r * r)}


@functools.partial(jax.jit, static_argnames=("precision",))
def _omega_t(B, key, precision: str):
    n, r = B.shape
    om = reference.omega(key, n, r, reference.OMEGA_SALT)
    return reference.dot(om.T, B, precision)


def nystrom(A, seed: int, r: int, precision: str = "highest", devices=None):
    """(B, C) of a square A: B = A·Omega in row blocks, C = Omega^T·B,
    both products at ``precision`` (see :func:`bench.reference.dot`)."""
    B = reference.dense(A, seed, r, precision, devices=devices)
    return B, stage2(B, seed, precision)


def stage2(B, seed: int, precision: str = "highest"):
    """C = Omega^T·B alone, at ``precision``."""
    return _omega_t(B, reference.key_array(seed), precision)


def compare(out, ref) -> Dict[str, float]:
    """The numbers compared: the worst row's gap of B and of C."""
    return {"B_row_gap": reference.worst_row(out[0], ref[0]),
            "C_row_gap": reference.worst_row(out[1], ref[1])}
