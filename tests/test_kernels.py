"""Pallas kernel validation (interpret mode): shape/dtype sweeps against the
pure-jnp oracle, bitwise Omega parity, and padding correctness."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

from repro.kernels import (
    gen_omega, nystrom_fused, sketch_matmul, sketch_t_matmul,
)
from repro.kernels.ops import sketch_matmul_launch, sketch_t_matmul_launch
from repro.kernels.sketch_matmul import T_STEP_BUDGET, t_step_bytes
from repro.kernels.ref import (
    omega_ref, sketch_matmul_ref, sketch_t_matmul_ref,
)

I = dict(interpret=True)


# ---------------------------------------------------------------------------
# Bitwise Omega parity: the kernel's in-VMEM generator == oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["normal", "uniform", "rademacher"])
@pytest.mark.parametrize("br,bc", [(8, 8), (16, 8), (32, 16)])
def test_gen_omega_bitwise(kind, br, bc):
    om_k = gen_omega(seed=123, n2=64, r=32, br=br, bc=bc, kind=kind, **I)
    om_r = omega_ref(123, 64, 32, kind)
    np.testing.assert_array_equal(np.asarray(om_k), np.asarray(om_r))


def test_gen_omega_nonaligned_shapes():
    om_k = gen_omega(seed=5, n2=37, r=13, br=16, bc=8, **I)
    om_r = omega_ref(5, 37, 13)
    np.testing.assert_array_equal(np.asarray(om_k), np.asarray(om_r))


# ---------------------------------------------------------------------------
# Padding invariance (ops.py contract): rounding r / n2 up to block
# multiples must not SHIFT the Philox draws of in-range entries — padded
# tail columns/rows draw at their own global coordinates and are sliced
# off, so the padded run is bitwise the unpadded one.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["normal", "uniform", "rademacher"])
def test_gen_omega_padding_never_shifts_draws(kind):
    """The padded generator's in-range block equals the same block of a
    larger unpadded generation — draws are a pure function of global
    coordinates, bitwise."""
    big = np.asarray(gen_omega(seed=5, n2=64, r=32, br=16, bc=8, kind=kind,
                               **I))
    # n2=37 pads to 48, r=13 pads to 16: in-range entries must be the
    # corresponding prefix of the bigger generation, bit for bit
    pad = np.asarray(gen_omega(seed=5, n2=37, r=13, br=16, bc=8, kind=kind,
                               **I))
    np.testing.assert_array_equal(pad, big[:37, :13])


def test_sketch_matmul_r_padding_bitwise():
    """Padding only the output columns (r up to bn multiples) leaves the
    contraction untouched, so in-range columns are bitwise the run whose
    blocks divide r exactly."""
    A = jax.random.normal(jax.random.key(1), (32, 64))
    padded = sketch_matmul(A, seed=7, r=11, bm=32, bn=8, bk=64, **I)
    exact = sketch_matmul(A, seed=7, r=16, bm=32, bn=16, bk=64, **I)
    np.testing.assert_array_equal(np.asarray(padded),
                                  np.asarray(exact)[:, :11])


def test_sketch_matmul_row_padding_bitwise():
    """Zero-padded A rows produce zero output rows that are sliced away;
    in-range rows see the identical contraction."""
    A = jax.random.normal(jax.random.key(1), (30, 64))
    Ap = jnp.pad(A, ((0, 2), (0, 0)))
    padded = sketch_matmul(A, seed=7, r=16, bm=16, bn=16, bk=64, **I)
    exact = sketch_matmul(Ap, seed=7, r=16, bm=16, bn=16, bk=64, **I)
    np.testing.assert_array_equal(np.asarray(padded),
                                  np.asarray(exact)[:30])


def test_sketch_t_matmul_r_padding_bitwise():
    """Same invariance for the transposed kernel: padded Omega columns
    (output rows of C) draw at their own coordinates and are sliced off."""
    B = jax.random.normal(jax.random.key(2), (64, 16))
    padded = sketch_t_matmul(B, seed=9, r=13, bm=8, bn=16, bk=64, **I)
    exact = sketch_t_matmul(B, seed=9, r=16, bm=16, bn=16, bk=64, **I)
    np.testing.assert_array_equal(np.asarray(padded),
                                  np.asarray(exact)[:13])


# ---------------------------------------------------------------------------
# sketch_matmul: B = A @ Omega
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 0.1)])
@pytest.mark.parametrize("shape,r,blocks", [
    ((32, 64), 16, (16, 8, 16)),
    ((40, 72), 24, (8, 8, 24)),      # block-aligned after min()
    ((33, 50), 11, (16, 8, 16)),     # needs padding in every dim
    ((8, 8), 4, (8, 8, 8)),
    ((128, 96), 32, (32, 16, 32)),
])
def test_sketch_matmul_vs_ref(dtype, tol, shape, r, blocks):
    bm, bn, bk = blocks
    A = jax.random.normal(jax.random.key(1), shape).astype(dtype)
    B = sketch_matmul(A, seed=7, r=r, bm=bm, bn=bn, bk=bk, **I)
    ref = sketch_matmul_ref(A, 7, r)
    assert B.shape == (shape[0], r)
    assert B.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(B, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("kind", ["uniform", "rademacher"])
def test_sketch_matmul_kinds(kind):
    A = jax.random.normal(jax.random.key(2), (32, 48))
    B = sketch_matmul(A, seed=3, r=16, bm=16, bn=8, bk=16, kind=kind, **I)
    ref = sketch_matmul_ref(A, 3, 16, kind)
    np.testing.assert_allclose(np.asarray(B), np.asarray(ref),
                               rtol=2e-5, atol=2e-4)


@settings(max_examples=12, deadline=None)
@given(
    n1=st.integers(4, 70), n2=st.integers(4, 70), r=st.integers(2, 40),
    bm=st.sampled_from([8, 16, 32]), bn=st.sampled_from([8, 16]),
    bk=st.sampled_from([8, 16, 32]),
    seed=st.integers(0, 2**62),
)
def test_sketch_matmul_property(n1, n2, r, bm, bn, bk, seed):
    A = jax.random.normal(jax.random.key(0), (n1, n2))
    B = sketch_matmul(A, seed=seed, r=r, bm=bm, bn=bn, bk=bk, **I)
    ref = sketch_matmul_ref(A, seed, r)
    np.testing.assert_allclose(np.asarray(B), np.asarray(ref),
                               rtol=3e-5, atol=3e-4)


def test_block_shape_independence():
    """The kernel result must not depend on the tiling (the in-kernel
    generator is keyed by global coordinates)."""
    A = jax.random.normal(jax.random.key(4), (64, 96))
    outs = [np.asarray(sketch_matmul(A, seed=11, r=32, bm=bm, bn=bn, bk=bk, **I))
            for (bm, bn, bk) in [(8, 8, 8), (16, 16, 32), (32, 8, 96), (64, 32, 48)]]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=2e-5, atol=2e-4)


# ---------------------------------------------------------------------------
# sketch_a_omega's Omega panel: with two or more row blocks the first row
# block generates each (k, j) tile into VMEM and the others read it back.
# One row block alone takes the per-step path (a tile generated each step),
# so each row block of A, sketched on its own, is the per-step kernel's
# answer for those rows — the panel path must equal it bit for bit.
# ---------------------------------------------------------------------------

def _per_row_block(A, bm, **kw):
    """B computed one row block at a time (each zero-padded to bm rows),
    every launch on the per-step path."""
    n1 = A.shape[0]
    out = []
    for i0 in range(0, n1, bm):
        slab = jnp.pad(A[i0:i0 + bm], ((0, bm - min(bm, n1 - i0)), (0, 0)))
        assert not sketch_matmul_launch(*slab.shape, kw["r"], bm, kw["bn"],
                                        kw["bk"]).panel
        out.append(np.asarray(sketch_matmul(slab, bm=bm, **kw, **I)))
    return np.concatenate(out)[:n1]


@pytest.mark.parametrize("salt", [0, 3])
@pytest.mark.parametrize("kind", ["normal", "uniform", "rademacher"])
@pytest.mark.parametrize("shape,r,blocks", [
    ((64, 96), 32, (16, 16, 32)),    # block-aligned, 4 x 2 x 3 grid
    ((50, 70), 13, (16, 8, 16)),     # padded on n1, n2 and r
])
def test_sketch_matmul_panel_bitwise_per_step(kind, salt, shape, r, blocks):
    bm, bn, bk = blocks
    A = jax.random.normal(jax.random.key(8), shape)
    assert sketch_matmul_launch(*shape, r, bm, bn, bk).panel
    kw = dict(seed=2**40 + 17, r=r, bn=bn, bk=bk, kind=kind, salt=salt)
    B = np.asarray(sketch_matmul(A, bm=bm, **kw, **I))
    np.testing.assert_array_equal(B, _per_row_block(A, bm, **kw))
    np.testing.assert_allclose(
        B, np.asarray(sketch_matmul_ref(A, kw["seed"], r, kind, salt)),
        rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("n1,n2,r,bm,bn,bk,panel", [
    (64, 96, 32, 16, 16, 32, True),        # four row blocks
    (32, 96, 32, 16, 16, 32, True),        # two
    (16, 96, 32, 16, 16, 32, False),       # one
    (12, 96, 32, 16, 16, 32, False),       # one, bm clamped to 16
    (512, 32768, 256, 256, 128, 512, True),     # the dense32k shape, cut
    (512, 262144, 256, 256, 128, 512, False),   # a 128 MiB panel
])
def test_sketch_matmul_path_follows_the_shape(n1, n2, r, bm, bn, bk, panel):
    """The launch and the pallas_call agree on the path, picked from the
    shapes alone: the panel is the kernel's second VMEM scratch, (n2p, bn)
    f32, and its launch counts each (k, j) tile once."""
    launch = sketch_matmul_launch(n1, n2, r, bm, bn, bk)
    (bm_, bn_, bk_), (n1p, rp, n2p) = launch.blocks, launch.padded
    assert launch.panel == panel
    assert launch.generated == n2p * rp * (1 if panel else n1p // bm_)
    A = jax.ShapeDtypeStruct((n1, n2), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda a: sketch_matmul(
        a, seed=1, r=r, bm=bm, bn=bn, bk=bk, **I))(A)
    (call,) = [e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
               if e.primitive.name == "pallas_call"]
    gm = call.params["grid_mapping"]
    assert gm.grid == (rp // bn_, n1p // bm_, n2p // bk_)
    scratch = [a.shape for a in gm.scratch_avals]
    assert scratch == [(bm_, bn_)] + ([(n2p, bn_)] if panel else [])


# ---------------------------------------------------------------------------
# sketch_t_matmul: C = Omega^T @ B
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,r", [((64, 32), 16), ((50, 21), 13), ((16, 16), 8)])
def test_sketch_t_matmul_vs_ref(shape, r):
    B = jax.random.normal(jax.random.key(5), shape)
    C = sketch_t_matmul(B, seed=13, r=r, bm=8, bn=8, bk=16, **I)
    ref = sketch_t_matmul_ref(B, 13, r)
    assert C.shape == (r, shape[1])
    np.testing.assert_allclose(np.asarray(C), np.asarray(ref),
                               rtol=3e-5, atol=3e-4)


@pytest.mark.parametrize("kind", ["normal", "uniform", "rademacher"])
@pytest.mark.parametrize("salt", [0, 3])
@pytest.mark.parametrize("shape,r,bn", [
    ((128, 48), 16, 16),       # aligned: three column blocks of 16
    ((100, 70), 13, 24),       # padded in n, r and r2: 70 -> 72
])
def test_sketch_t_matmul_one_column_block_is_bitwise_the_split(kind, salt,
                                                               shape, r, bn):
    """The default launch covers B with one column block, generating each
    Omega tile once; C is bitwise the C of a narrow ``bn`` that splits B
    into several column blocks and regenerates each tile for each."""
    B = jax.random.normal(jax.random.key(8), shape)
    kw = dict(seed=2**40 + 5, r=r, kind=kind, salt=salt, **I)
    launch = sketch_t_matmul_launch(*shape, r)
    assert launch.padded[1] == launch.blocks[1]
    assert sketch_t_matmul_launch(*shape, r, bn=bn).padded[1] // bn > 1
    C = np.asarray(sketch_t_matmul(B, **kw))
    np.testing.assert_array_equal(C, np.asarray(sketch_t_matmul(B, bn=bn,
                                                                **kw)))
    np.testing.assert_allclose(
        C, np.asarray(sketch_t_matmul_ref(B, kw["seed"], r, kind, salt)),
        rtol=3e-5, atol=3e-4)


@pytest.mark.parametrize("n,r2,r", [
    (57344, 256, 256),         # the dense56k pair's stage 2
    (96, 200, 16),             # small: blocks clamped to the shape
    (57344, 8192, 256),        # a step over the budget at full width
])
def test_sketch_t_matmul_launch_picks_the_widest_fitting_block(n, r2, r):
    """One column block covers B where the step's buffers fit the budget,
    so the launch generates np·rp entries; a wider B gets the widest
    multiple of 128 that fits and regenerates each tile once per block."""
    launch = sketch_t_matmul_launch(n, r2, r)
    (bm, bn, bk), (rp, r2p, np_) = launch.blocks, launch.padded
    blocks = -(-r2p // bn)
    if t_step_bytes(128, -(-r2 // 128) * 128, 512) <= T_STEP_BUDGET:
        assert bn == r2p and launch.generated == np_ * rp
    else:
        assert bn % 128 == 0 and blocks > 1
        assert t_step_bytes(bm, bn, bk) <= T_STEP_BUDGET \
            < t_step_bytes(bm, bn + 128, bk)
        assert launch.generated == blocks * np_ * rp
    assert launch.needed == n * r


def test_nystrom_fused_pair_matches_core():
    """Fused-kernel Nyström == core (shard-map-free) reference path."""
    from repro.core.nystrom import nystrom_reference
    n, r = 48, 16
    X = jax.random.normal(jax.random.key(6), (n, 8))
    S = X @ X.T
    Bk, Ck = nystrom_fused(S, seed=21, r=r, bm=16, bn=8, bk=16, **I)
    Br, Cr = nystrom_reference(S, 21, r)
    np.testing.assert_allclose(np.asarray(Bk), np.asarray(Br),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(Ck), np.asarray(Cr),
                               rtol=1e-4, atol=1e-2)


def test_kernel_lowers_for_tpu_structurally():
    """The pallas_call must trace and lower (abstract eval) without running —
    catches BlockSpec/grid mistakes that interpret mode can hide."""
    A = jax.ShapeDtypeStruct((512, 1024), jnp.bfloat16)
    fn = lambda a: sketch_matmul(a, seed=1, r=256, bm=256, bn=128, bk=512,
                                 interpret=True)
    jax.eval_shape(fn, A)  # abstract evaluation only
