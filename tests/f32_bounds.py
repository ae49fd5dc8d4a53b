"""Summation-order bounds for comparing two f32 GEMM programs.

Two programs that form the same f32 products but add them in different
orders — XLA:CPU's dot against the Pallas interpreter's, a one-row gemv
against a many-row gemm, a per-shard GEMM against the whole-matrix one —
agree only to rounding.  For a length-k f32 dot product the forward error
bound |fl(x.y) - x.y| <= gamma_k * sum|x_i * y_i|, with
gamma_k = k*u / (1 - k*u) and u = 2^-24, holds for ANY summation order,
so two such programs differ by at most 2 * gamma_k * (|X| @ |Y|)
elementwise.  These helpers assert that bound and nothing looser: a
wrong Omega entry, a dropped row or a misplaced tile breaks it by orders
of magnitude.
"""
from __future__ import annotations

import numpy as np

U = 2.0 ** -24


def gamma(k: int) -> float:
    return k * U / (1.0 - k * U)


def _abs64(x):
    return np.abs(np.asarray(x, np.float64))


def gemm_diff_bound(X, Y, acc=None):
    """Largest elementwise gap between two orders of ``acc? + X @ Y``."""
    X, Y = _abs64(X), _abs64(Y)
    terms = X @ Y
    k = X.shape[-1]
    if acc is not None:
        terms = terms + _abs64(acc)
        k += 1
    return 2.0 * gamma(k) * terms


def nystrom_diff_bounds(S, om):
    """(B, C) gaps between two orders of B = S @ om, C = om^T @ B: B's
    own gap, and C's — its own rounding on both sides plus om^T carried
    over B's gap (|B| <= |S| @ |om|)."""
    S, om = _abs64(S), _abs64(om)
    SO = S @ om
    g = gamma(S.shape[-1] + 1)
    return 2.0 * g * SO, 4.0 * g * (om.T @ SO)


def assert_orders_agree(got, ref, bound, msg=""):
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    excess = np.abs(got - ref) - bound
    assert (excess <= 0).all(), (
        f"{msg}: |got - ref| exceeds the summation-order bound by "
        f"{excess.max():.3g} (max |got - ref| = "
        f"{np.abs(got - ref).max():.3g})")
