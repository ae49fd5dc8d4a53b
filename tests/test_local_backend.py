"""Backend-matrix tests for the fused local GEMM layer (kernels/local.py).

Pins the tentpole contract of the zero-Omega-HBM work:

  (a) interpret-mode Pallas vs jnp **bitwise** parity for ``sketch_block``
      across all three omega kinds, nonzero row0/col0 offsets, bf16
      inputs with f32 accumulation, non-divisible shapes, and the fused
      ``acc`` accumulation; ``sketch_t_block`` to the f32 summation-order
      bound (tests/f32_bounds.py) where XLA:CPU orders its transposed dot
      differently;
  (b) ``backend="auto"`` never changes numerics (property test);
  (c) every distributed path (Alg. 1 grids, both Nyström 1-D variants,
      the general and bound-driven two-grid forms, the sharded streaming
      updates incl. row slabs and the co-range sketch) produces bitwise-
      identical results on both backends — so the existing Theorem-audit
      and two-grid bitwise contracts hold for the Pallas backend too;
  (d) the Theorem-2 zero-communication audit passes on the Pallas
      backend: the compiled (P,1,1) update has zero collective bytes, and
      the 2x2x2 collective schedule (bytes moved) is identical to jnp's —
      the backend changes the HBM roofline, never the network;
  (e) the planner picks the backend analytically (HBM roofline) and
      ``Plan.execute`` dispatches it.
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from dist_helper import run_distributed

import jax
import jax.numpy as jnp

from repro.kernels.local import (
    default_local_blocks, resolve_backend, sketch_block, sketch_t_block,
)

KINDS = ("normal", "uniform", "rademacher")
OFFSETS = ((0, 0), (32, 5))


# ---------------------------------------------------------------------------
# (a) local bitwise parity matrix
# ---------------------------------------------------------------------------

def test_resolve_backend():
    assert resolve_backend("jnp") == "jnp"
    assert resolve_backend("xla") == "jnp"          # stream alias
    assert resolve_backend("pallas") == "pallas"
    assert resolve_backend("auto") in ("jnp", "pallas")
    if jax.default_backend() != "tpu":
        assert resolve_backend("auto") == "jnp"
    with pytest.raises(ValueError):
        resolve_backend("mkl")


def test_default_blocks_interpret_exact():
    """Interpret mode takes one exact tile: no padding, no k split — the
    bitwise default."""
    assert default_local_blocks(33, 11, 50, interpret=True) == (33, 11, 50)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("off", OFFSETS)
def test_sketch_block_backend_parity(kind, off):
    A = jax.random.normal(jax.random.key(0), (16, 48))
    r0, c0 = off
    j = sketch_block(A, 7, 8, row0=r0, col0=c0, kind=kind, backend="jnp")
    p = sketch_block(A, 7, 8, row0=r0, col0=c0, kind=kind, backend="pallas")
    np.testing.assert_array_equal(np.asarray(j), np.asarray(p))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("off", OFFSETS)
def test_sketch_t_block_backend_parity(kind, off):
    """Omega^T·B on both backends agrees to the f32 summation-order bound.
    Not bitwise: XLA:CPU runs the jnp body's transposed-operand dot in a
    different reduction order than the kernel's in-interpreter dot (the
    Omega entries themselves are bitwise — test_rng pins them)."""
    from f32_bounds import assert_orders_agree, gemm_diff_bound
    from repro.core.sketch import omega_tile
    B = jax.random.normal(jax.random.key(2), (48, 16))
    r0, c0 = off
    j = sketch_t_block(B, 7, 8, row0=r0, col0=c0, kind=kind, salt=1,
                       backend="jnp")
    p = sketch_t_block(B, 7, 8, row0=r0, col0=c0, kind=kind, salt=1,
                       backend="pallas")
    om = omega_tile(7, r0, c0, 48, 8, kind, salt=1)
    assert_orders_agree(p, j, gemm_diff_bound(om.T, B), kind)


def test_fused_acc_parity_and_semantics():
    """sketch_block(acc=Y) == Y + sketch_block() on both backends, bitwise
    — the fused accumulator adds in the same order as the jnp body.  The
    transposed form is bitwise on the jnp backend (the same program) and
    within the f32 summation-order bound on the pallas backend (see
    test_sketch_t_block_backend_parity)."""
    from f32_bounds import assert_orders_agree, gemm_diff_bound
    from repro.core.sketch import omega_tile
    A = jax.random.normal(jax.random.key(0), (16, 48))
    Y = jax.random.normal(jax.random.key(1), (16, 8))
    base = Y + sketch_block(A, 7, 8, backend="jnp")
    for backend in ("jnp", "pallas"):
        got = sketch_block(A, 7, 8, acc=Y, backend=backend)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
    W = jax.random.normal(jax.random.key(3), (8, 16))
    B = jax.random.normal(jax.random.key(2), (48, 16))
    tbase = W + sketch_t_block(B, 7, 8, backend="jnp")
    got = sketch_t_block(B, 7, 8, acc=W, backend="jnp")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(tbase))
    got = sketch_t_block(B, 7, 8, acc=W, backend="pallas")
    om = omega_tile(7, 0, 0, 48, 8)
    assert_orders_agree(got, tbase, gemm_diff_bound(om.T, B, acc=W),
                        "acc + Omega^T B")


def test_bf16_inputs_f32_accumulation_parity():
    A = jax.random.normal(jax.random.key(0), (16, 48)).astype(jnp.bfloat16)
    j = sketch_block(A, 7, 8, backend="jnp")
    p = sketch_block(A, 7, 8, backend="pallas")
    assert j.dtype == jnp.bfloat16 and p.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(j, np.float32),
                                  np.asarray(p, np.float32))
    B = A.T
    j = sketch_t_block(B, 7, 8, backend="jnp")
    p = sketch_t_block(B, 7, 8, backend="pallas")
    np.testing.assert_array_equal(np.asarray(j, np.float32),
                                  np.asarray(p, np.float32))


def test_nondivisible_shapes_parity():
    A = jax.random.normal(jax.random.key(4), (33, 50))
    np.testing.assert_array_equal(
        np.asarray(sketch_block(A, 9, 11, backend="jnp")),
        np.asarray(sketch_block(A, 9, 11, backend="pallas")))
    B = jax.random.normal(jax.random.key(5), (50, 21))
    np.testing.assert_array_equal(
        np.asarray(sketch_t_block(B, 9, 13, backend="jnp")),
        np.asarray(sketch_t_block(B, 9, 13, backend="pallas")))


def test_explicit_blocks_k_unsplit_parity_and_scale():
    """m/n tiling keeps bitwise parity as long as the contraction is not
    split; scale multiplies the in-kernel tile identically."""
    A = jax.random.normal(jax.random.key(0), (16, 48))
    j = sketch_block(A, 7, 8, scale=0.25, backend="jnp")
    p = sketch_block(A, 7, 8, scale=0.25, backend="pallas",
                     blocks=(8, 4, 48))
    np.testing.assert_array_equal(np.asarray(j), np.asarray(p))


def test_k_split_blocks_tolerance():
    """Splitting the contraction regroups the f32 reduction — documented
    as tolerance-level, not bitwise."""
    A = jax.random.normal(jax.random.key(0), (16, 48))
    j = sketch_block(A, 7, 8, backend="jnp")
    p = sketch_block(A, 7, 8, backend="pallas", blocks=(16, 8, 16))
    np.testing.assert_allclose(np.asarray(j), np.asarray(p),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("split", [False, True], ids=["one-k-tile",
                                                      "k-split"])
def test_omega_fill_slices_bitwise(kind, split):
    """A kernel Omega tile larger than one generator slice
    (``_GEN_ENTRIES``) is filled by the in-kernel slice loop the chip
    runs, at global rows ``row0 + k-tile offset + slice start``.  With an
    identity operand every output entry is one product 1·omega plus exact
    zeros, so both kernels return their Omega tiles in any summation
    order: bitwise ``omega_tile`` (and the jnp backend) at the same
    coordinates, across k tiles and slices."""
    from repro.core.sketch import omega_tile
    from repro.kernels.local import _GEN_ENTRIES, gen_rows
    k, cols, r0, c0 = 1024, 32, 40, 3
    eye = jnp.eye(k, dtype=jnp.float32)
    om = np.asarray(omega_tile(7, r0, c0, k, cols, kind))
    fwd = (512, cols, 512) if split else None
    tb = (cols, 512, 512) if split else None
    bk = 512 if split else k
    assert bk * cols > _GEN_ENTRIES and gen_rows(bk, cols) < bk
    for backend, blocks in (("jnp", None), ("pallas", fwd)):
        got = sketch_block(eye, 7, cols, row0=r0, col0=c0, kind=kind,
                           backend=backend, blocks=blocks)
        np.testing.assert_array_equal(np.asarray(got), om, err_msg=backend)
    for backend, blocks in (("jnp", None), ("pallas", tb)):
        got = sketch_t_block(eye, 7, cols, row0=r0, col0=c0, kind=kind,
                             backend=backend, blocks=blocks)
        np.testing.assert_array_equal(np.asarray(got), om.T, err_msg=backend)


def test_fold_rows_block_backend_parity():
    """The row-slab Y fold (stream ``update_rows``) is backend-dispatched
    (``fold_rows_block``): the pallas body adds the same slab rows to the
    same Y rows (slab windows DMA'd per Y block, Y aliased in-place) and
    must be BITWISE the jnp body across in-range, clipped-left,
    clipped-right, and fully-out-of-overlap offsets."""
    from repro.kernels.local import fold_rows_block
    y = jax.random.normal(jax.random.key(0), (8, 6))
    d = jax.random.normal(jax.random.key(1), (5, 6))
    m, k = y.shape[0], d.shape[0]
    for start in (0, 1, 3, m, k + m):      # clip range is [0, k + m]
        j = fold_rows_block(y, d, jnp.int32(start), backend="jnp")
        p = fold_rows_block(y, d, jnp.int32(start), backend="pallas")
        np.testing.assert_array_equal(np.asarray(j), np.asarray(p))
    # start == m places d exactly at the top of y
    top = fold_rows_block(y, d, jnp.int32(m), backend="pallas")
    np.testing.assert_array_equal(
        np.asarray(top)[:k], np.asarray(y[:k] + d))
    # fully outside the overlap: both backends add exact zeros
    out = fold_rows_block(y, d, jnp.int32(0), backend="pallas")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(y))
    # traced start under jit, and bf16 state
    f = jax.jit(lambda y, d, s: fold_rows_block(y, d, s, backend="pallas"))
    np.testing.assert_array_equal(
        np.asarray(f(y, d, jnp.int32(7))),
        np.asarray(fold_rows_block(y, d, 7, backend="jnp")))
    yb, db = y.astype(jnp.bfloat16), d.astype(jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(fold_rows_block(yb, db, jnp.int32(9), backend="pallas"),
                   np.float32),
        np.asarray(fold_rows_block(yb, db, jnp.int32(9), backend="jnp"),
                   np.float32))


def test_fold_rows_block_padded_path_parity():
    """The native-TPU fold grids Y in row blocks (a ragged last block
    included) and DMAs each block's slab window from a frame padded by
    one block a side, clipping the window start.  Forced through
    interpret mode with 8-row blocks so CI pins the clipping contract the
    compiled path relies on: every start in [0, k + m], masked and not,
    is bitwise the jnp fold."""
    from repro.kernels.local import _fold_rows_jnp, _fold_rows_pallas
    y = jax.random.normal(jax.random.key(0), (13, 6))
    d = jax.random.normal(jax.random.key(1), (5, 6))
    for start in range(0, 19):        # clip range is [0, k + m]
        for nvalid in (None, 0, 3):
            ref = _fold_rows_jnp(y, d, jnp.int32(start), nvalid=nvalid)
            got = _fold_rows_pallas(y, d, jnp.int32(start), interpret=True,
                                    nvalid=nvalid, block_rows=8)
            np.testing.assert_array_equal(
                np.asarray(ref), np.asarray(got),
                err_msg=f"start={start} nvalid={nvalid}")


def test_traced_seed_and_offsets_under_jit():
    A = jax.random.normal(jax.random.key(0), (16, 48))
    keys = jnp.array([7, 0], jnp.uint32)
    f = jax.jit(lambda a, k, r0: sketch_block(a, k, 8, row0=r0,
                                              backend="pallas"))
    g = jax.jit(lambda a, k, r0: sketch_block(a, k, 8, row0=r0,
                                              backend="jnp"))
    np.testing.assert_array_equal(
        np.asarray(f(A, keys, jnp.uint32(32))),
        np.asarray(g(A, keys, jnp.uint32(32))))


# ---------------------------------------------------------------------------
# (b) backend="auto" never changes numerics
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(n1=st.integers(4, 40), n2=st.integers(4, 60), r=st.integers(2, 16),
       seed=st.integers(0, 2 ** 62),
       kind=st.sampled_from(list(KINDS)))
def test_auto_backend_property(n1, n2, r, seed, kind):
    A = jax.random.normal(jax.random.key(1), (n1, n2))
    ref = sketch_block(A, seed, r, kind=kind, backend="jnp")
    auto = sketch_block(A, seed, r, kind=kind, backend="auto")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(auto))
    # and the explicitly-forced fused kernel agrees bitwise too
    fused = sketch_block(A, seed, r, kind=kind, backend="pallas")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fused))


# ---------------------------------------------------------------------------
# (c) distributed paths, both backends, bitwise (8 fake devices)
# ---------------------------------------------------------------------------

COMMON = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import (rand_matmul, sketch_reference, make_grid_mesh,
                        nystrom_no_redist, nystrom_redist, nystrom_general,
                        nystrom_reference)
from repro.core.nystrom import nystrom_two_grid
from repro.core.sketch import input_sharding, output_sharding
assert len(jax.devices()) == 8
"""


def test_distributed_backends_bitwise():
    run_distributed(COMMON + r"""
seed, n1, n2, r = 11, 16, 48, 8
A = jax.random.normal(jax.random.key(1), (n1, n2))
ref = sketch_reference(A, seed, r)
for shape in [(8,1,1), (2,2,2), (1,4,2), (4,2,1), (1,1,8)]:
    mesh = make_grid_mesh(*shape)
    Ash = jax.device_put(A, input_sharding(mesh))
    Bj = rand_matmul(Ash, seed, r, mesh, backend="jnp")
    Bp = rand_matmul(Ash, seed, r, mesh, backend="pallas")
    assert np.array_equal(np.asarray(Bj), np.asarray(Bp)), shape
    assert float(jnp.abs(Bj - ref).max()) < 1e-4, shape

n, rr = 64, 16
S = jax.random.normal(jax.random.key(2), (n, n)); S = S @ S.T / n
Bref, Cref = nystrom_reference(S, 5, rr)
mesh = Mesh(np.asarray(jax.devices()), ("x",))
Ssh = jax.device_put(S, NamedSharding(mesh, P("x", None)))
for fn in (nystrom_no_redist, nystrom_redist):
    Bj, Cj = fn(Ssh, 5, rr, mesh, backend="jnp")
    Bp, Cp = fn(Ssh, 5, rr, mesh, backend="pallas")
    assert np.array_equal(np.asarray(Bj), np.asarray(Bp)), fn
    assert np.array_equal(np.asarray(Cj), np.asarray(Cp)), fn

# §5.3 bound-driven two-grid: the pair that splits no contraction
# (p2==1, q1==1) is bitwise across backends, and agrees with the
# single-device reference to the f32 summation-order bound (a per-shard
# GEMM and the whole-matrix GEMM add in different orders on XLA:CPU)
from f32_bounds import assert_orders_agree, nystrom_diff_bounds
from repro.core.sketch import omega_tile
Bj, Cj = nystrom_two_grid(S, 5, rr, p=(8,1,1), q=(1,1,8), backend="jnp")
Bp, Cp = nystrom_two_grid(S, 5, rr, p=(8,1,1), q=(1,1,8), backend="pallas")
assert np.array_equal(np.asarray(Bj), np.asarray(Bp))
assert np.array_equal(np.asarray(Cj), np.asarray(Cp))
dB, dC = nystrom_diff_bounds(S, omega_tile(5, 0, 0, n, rr))
assert_orders_agree(Bp, Bref, dB, "B two-grid vs reference")
assert_orders_agree(Cp, Cref, dC, "C two-grid vs reference")

# one-mesh general two-grid
mesh2 = make_grid_mesh(2, 2, 2)
Ssh2 = jax.device_put(S, input_sharding(mesh2))
Bj, Cj = nystrom_general(Ssh2, 5, rr, mesh2, backend="jnp")
Bp, Cp = nystrom_general(Ssh2, 5, rr, mesh2, backend="pallas")
assert np.array_equal(np.asarray(Bj), np.asarray(Bp))
assert np.array_equal(np.asarray(Cj), np.asarray(Cp))
print("OK")
""", timeout=900)


def test_sharded_stream_backends_bitwise():
    run_distributed(COMMON + r"""
from repro.stream import ShardedStreamingSketch
from repro.stream.state import StreamConfig

cfg = StreamConfig(n1=16, n2=48, r=8, seed=3, corange=True)
mesh = make_grid_mesh(4, 1, 2)
H1 = jax.random.normal(jax.random.key(3), (16, 48))
H2 = jax.random.normal(jax.random.key(4), (16, 48))
stj = ShardedStreamingSketch(cfg, mesh, backend="jnp")
stp = ShardedStreamingSketch(cfg, mesh, backend="pallas")
for st in (stj, stp):
    st.update(H1)
    st.update(H2)
    st.update_rows(4, np.asarray(H1)[4:8])       # row slab + corange
assert np.array_equal(np.asarray(stj.Y), np.asarray(stp.Y))
assert np.array_equal(np.asarray(stj.W), np.asarray(stp.W))

# fused Y accumulate (p2 == 1) and the scatter path (p2 > 1); row-slab
# ingest exercises the fused traced-offset Y fold (fold_rows_block) on
# every grid shape — shards left of, inside, and right of the slab
for g in ((8,1,1), (2,2,2)):
    c2 = StreamConfig(n1=16, n2=48, r=8, seed=3, corange=False)
    meshg = make_grid_mesh(*g)
    a = ShardedStreamingSketch(c2, meshg, backend="jnp").update(H1)
    b = ShardedStreamingSketch(c2, meshg, backend="pallas").update(H1)
    for st in (a, b):
        st.update_rows(6, np.asarray(H2)[6:12])
        st.update_rows(0, np.asarray(H2)[0:2])
    assert np.array_equal(np.asarray(a.Y), np.asarray(b.Y)), g

# symmetric stream: Nyström finalize on both backends, bitwise
S = jax.random.normal(jax.random.key(2), (16, 16)); S = S @ S.T / 16
c3 = StreamConfig(n1=16, n2=16, r=8, seed=5, corange=False)
m1 = make_grid_mesh(8, 1, 1)
fj = ShardedStreamingSketch(c3, m1, backend="jnp").update(S)
fp = ShardedStreamingSketch(c3, m1, backend="pallas").update(S)
for variant in ("no_redist", "redist", "bound_driven"):
    Bj, Cj = fj.nystrom(variant)
    Bp, Cp = fp.nystrom(variant)
    assert np.array_equal(np.asarray(Bj), np.asarray(Bp)), variant
    assert np.array_equal(np.asarray(Cj), np.asarray(Cp)), variant
print("OK")
""", timeout=900)


def test_zero_comm_and_schedule_pallas():
    """Theorem-2 audits hold on the Pallas backend: zero collective bytes
    on the (P,1,1) grid, and the 2x2x2 collective schedule moves exactly
    the same bytes as the jnp backend — fusing the local GEMM must not
    change the network schedule."""
    run_distributed(COMMON + r"""
from repro.roofline.hlo import collective_bytes_of
seed, n1, n2, r = 3, 16, 32, 8
mesh = make_grid_mesh(8, 1, 1)
A = jax.device_put(jax.random.normal(jax.random.key(0), (n1, n2)),
                   input_sharding(mesh))
fn = jax.jit(lambda a: rand_matmul(a, seed, r, mesh, backend="pallas"))
cb = collective_bytes_of(fn.lower(A).compile().as_text())
assert cb.total == 0, f"expected zero collective bytes, got {cb}"

n1, n2, r = 8, 64, 16
mesh = make_grid_mesh(2, 2, 2)
A = jax.device_put(jax.random.normal(jax.random.key(0), (n1, n2)),
                   input_sharding(mesh))
texts = {}
for backend in ("jnp", "pallas"):
    fn = jax.jit(lambda a, b=backend: rand_matmul(a, seed, r, mesh,
                                                  backend=b))
    texts[backend] = collective_bytes_of(fn.lower(A).compile().as_text())
assert texts["jnp"].by_kind == texts["pallas"].by_kind, texts
assert texts["pallas"].counts.get("all-gather", 0) == 1
assert texts["pallas"].counts.get("reduce-scatter", 0) == 1
print("OK")
""", timeout=900)


# ---------------------------------------------------------------------------
# (e) planner integration
# ---------------------------------------------------------------------------

def test_planner_picks_pallas_on_hbm_roofline():
    from repro.plan import PRESETS, plan_nystrom, plan_sketch, plan_stream
    t = plan_sketch(4096, 4096, 256, P=8, machine=PRESETS["tpu_v5e"])
    assert t.variant == "alg1" and t.backend == "pallas"
    jn = [c for c in t.candidates
          if c.variant == "alg1" and c.backend == "jnp"][0]
    pl = [c for c in t.candidates
          if c.variant == "alg1" and c.backend == "pallas"][0]
    assert pl.cost.words == jn.cost.words          # network untouched
    assert pl.cost.hbm_words < jn.cost.hbm_words   # Omega stream elided
    assert plan_nystrom(4096, 256, P=8,
                        machine=PRESETS["tpu_v5e"]).backend == "pallas"
    assert plan_stream(4096, 4096, 256, P=8,
                       machine=PRESETS["tpu_v5e"]).backend == "pallas"
    # CPU machine: pallas rows reported but never chosen
    c = plan_sketch(64, 128, 16, P=8, machine=PRESETS["cpu"])
    assert c.backend == "jnp"
    assert any(x.backend == "pallas" and not x.executable
               for x in c.candidates)


def test_plan_execute_dispatches_backend():
    """A pallas-backend distributed plan executes (interpret mode on CPU)
    bitwise-identically to the jnp plan."""
    run_distributed(r"""
import dataclasses
import jax, numpy as np
from repro.plan import PRESETS, plan_sketch
A = jax.random.normal(jax.random.key(0), (16, 48))
pj = plan_sketch(16, 48, 8, P=8, machine=PRESETS["cpu"])
assert pj.backend == "jnp"
pp_c = [c for c in pj.candidates if c.backend == "pallas"][0]
pp = dataclasses.replace(pj, backend="pallas", grid=pp_c.grid,
                         executable=True)
Bj = pj.execute(A, seed=11)
Bp = pp.execute(A, seed=11)
assert np.array_equal(np.asarray(Bj), np.asarray(Bp))
print("OK")
""", timeout=900)


def test_hbm_roofline_words_table():
    from repro.plan.model import hbm_roofline_words
    # plain GEMM: jnp moves A + Omega + B, pallas drops the k·n Omega term
    assert hbm_roofline_words(64, 128, 16, "jnp") == 64 * 128 + 128 * 16 \
        + 64 * 16
    assert hbm_roofline_words(64, 128, 16, "pallas") == 64 * 128 + 64 * 16
    # accumulate consumers: 4 m·n round-trip words vs the fused kernel's 2
    dj = hbm_roofline_words(64, 128, 16, "jnp", accumulate=True)
    dp = hbm_roofline_words(64, 128, 16, "pallas", accumulate=True)
    assert dj - hbm_roofline_words(64, 128, 16, "jnp") == 3 * 64 * 16
    assert dp - hbm_roofline_words(64, 128, 16, "pallas") == 64 * 16
