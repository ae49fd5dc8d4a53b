"""Streaming one-pass sketch subsystem (repro.stream).

Contract pillars:
  (a) streamed row-block updates are **bitwise** invariant to arrival
      order for a given chunking, reproduce the one-shot
      ``sketch_reference`` **bitwise** when every slab has many rows, and
      to the f32 summation-order bound when a slab has one row (a
      one-row gemv and a many-row gemm add in different orders) — the
      distributed row-slab path matches the full-shape additive path
      bitwise;
  (b) one-pass reconstruction matches the one-shot low-rank baseline;
  (c) updates add zero Omega/Psi communication — the compiled update step
      moves exactly the Alg.-1 collective bytes (zero on regime-1 grids),
      plus only the data-derived co-range psum when enabled;
  (d) checkpoints round-trip bitwise (sketch state + seed IS the stream);
  (e) batched multi-stream ingest is bitwise N independent streams.

Distributed assertions run in a subprocess with 8 fake XLA devices (same
isolation rule as test_sketch_distributed.py).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dist_helper import run_distributed

from repro.core import nystrom_reference, sketch_reference
from repro.stream import (
    SketchService,
    StreamConfig,
    StreamingSketch,
    psi_matrix,
    reconstruction_error,
)


# ---------------------------------------------------------------------------
# (a) bitwise equality under arbitrary row chunking
# ---------------------------------------------------------------------------

CHUNKINGS = [
    [(0, 48)],                                    # one-shot as a stream
    [(0, 16), (16, 32), (32, 48)],                # equal blocks, in order
    [(32, 48), (0, 7), (7, 32)],                  # ragged, out of order
    [(i, i + 1) for i in range(48)],              # one row at a time
    [(1, 48), (0, 1)],                            # pathological split
]


@pytest.mark.parametrize("chunks", CHUNKINGS,
                         ids=["oneshot", "equal", "ragged", "rowwise", "tail"])
def test_rowblock_stream_bitwise_equals_reference(chunks):
    """Each Y row is written by exactly one full-contraction update, so a
    chunking replayed in reverse arrival order is BITWISE the same stream,
    and a chunking of many-row slabs is BITWISE the one-shot reference
    (the same packed GEMM per row).  A chunking with a one-row slab
    matches the reference to the f32 summation-order bound: XLA:CPU adds
    a one-row slab (gemv) in a different order than the 48-row GEMM."""
    from f32_bounds import assert_orders_agree, gemm_diff_bound
    from repro.core.sketch import omega_tile
    n1, n2, r, seed = 48, 64, 8, 11
    A = jax.random.normal(jax.random.key(0), (n1, n2))
    ref = np.asarray(sketch_reference(A, seed, r))
    cfg = StreamConfig(n1=n1, n2=n2, r=r, seed=seed)
    st = StreamingSketch(cfg, backend="xla")
    rev = StreamingSketch(cfg, backend="xla")
    for (i0, i1) in chunks:
        st.update_rows(i0, A[i0:i1])
    for (i0, i1) in reversed(chunks):
        rev.update_rows(i0, A[i0:i1])
    np.testing.assert_array_equal(np.asarray(st.sketch),
                                  np.asarray(rev.sketch))
    if all(i1 - i0 > 1 for (i0, i1) in chunks):
        np.testing.assert_array_equal(np.asarray(st.sketch), ref)
    else:
        assert_orders_agree(st.sketch, ref,
                            gemm_diff_bound(A, omega_tile(seed, 0, 0, n2, r)),
                            "stream vs one-shot")


@pytest.mark.parametrize("kind", ["normal", "uniform", "rademacher"])
def test_rowblock_stream_bitwise_all_kinds(kind):
    n1, n2, r, seed = 32, 40, 8, 5
    A = jax.random.normal(jax.random.key(2), (n1, n2))
    ref = np.asarray(sketch_reference(A, seed, r, kind))
    st = StreamingSketch(StreamConfig(n1=n1, n2=n2, r=r, seed=seed,
                                      kind=kind), backend="xla")
    for i0 in range(0, n1, 8):
        st.update_rows(i0, A[i0:i0 + 8])
    np.testing.assert_array_equal(np.asarray(st.sketch), ref)


def test_colblock_and_additive_streams_match_reference():
    """Column/overlapping updates split the contraction, so they match to FP
    tolerance (documented), not bitwise."""
    n1, n2, r, seed = 32, 64, 8, 3
    A = jax.random.normal(jax.random.key(1), (n1, n2))
    ref = np.asarray(sketch_reference(A, seed, r))

    st = StreamingSketch(StreamConfig(n1=n1, n2=n2, r=r, seed=seed))
    for j in range(0, n2, 16):
        st.update_cols(j, A[:, j:j + 16])
    np.testing.assert_allclose(np.asarray(st.sketch), ref,
                               rtol=1e-5, atol=1e-4)

    st2 = StreamingSketch(StreamConfig(n1=n1, n2=n2, r=r, seed=seed))
    half = jnp.concatenate([A[:16], jnp.zeros((16, n2))], axis=0)
    st2.update(half)
    st2.update(jnp.asarray(A) - half)       # overlapping additive deltas
    np.testing.assert_allclose(np.asarray(st2.sketch), ref,
                               rtol=1e-5, atol=1e-4)


def test_corange_sketch_matches_oneshot():
    n1, n2, r, seed = 48, 64, 8, 11
    cfg = StreamConfig(n1=n1, n2=n2, r=r, seed=seed)
    A = jax.random.normal(jax.random.key(0), (n1, n2))
    st = StreamingSketch(cfg)
    for (i0, i1) in [(24, 48), (0, 13), (13, 24)]:
        st.update_rows(i0, A[i0:i1])
    Wref = np.asarray(psi_matrix(cfg) @ A)
    np.testing.assert_allclose(np.asarray(st.corange_sketch), Wref,
                               rtol=1e-5, atol=1e-4)


def test_pallas_backend_matches_reference():
    """The fused-kernel ingest path (interpret mode on CPU)."""
    n1, n2, r, seed = 32, 32, 8, 2
    A = jax.random.normal(jax.random.key(9), (n1, n2))
    st = StreamingSketch(StreamConfig(n1=n1, n2=n2, r=r, seed=seed,
                                      corange=False), backend="interpret")
    st.update_rows(0, A[:16])
    st.update_rows(16, A[16:])
    np.testing.assert_allclose(np.asarray(st.sketch),
                               np.asarray(sketch_reference(A, seed, r)),
                               rtol=2e-5, atol=2e-4)


# ---------------------------------------------------------------------------
# (b) one-pass reconstruction vs. the one-shot baseline
# ---------------------------------------------------------------------------

def test_one_pass_reconstruction_matches_oneshot_baseline():
    n1, n2, k = 64, 96, 6
    M = (jax.random.normal(jax.random.key(1), (n1, k))
         @ jax.random.normal(jax.random.key(2), (k, n2)))
    cfg = StreamConfig(n1=n1, n2=n2, r=24, seed=3)

    streamed = StreamingSketch(cfg)
    for i in range(0, n1, 12):
        streamed.update_rows(i, M[i:i + 12])
    oneshot = StreamingSketch(cfg).update_rows(0, M)

    err_s = float(reconstruction_error(M, streamed.reconstruct()))
    err_o = float(reconstruction_error(M, oneshot.reconstruct()))
    # exact-rank input: both must hit ~machine precision, and agree
    assert err_s < 1e-4, err_s
    assert abs(err_s - err_o) < 1e-5, (err_s, err_o)

    # fixed-rank truncation keeps the target rank and the error floor
    lr = streamed.reconstruct(rank=k)
    assert lr.rank == k
    assert float(reconstruction_error(M, lr)) < 1e-4


def test_streaming_nystrom_matches_reference():
    n, r, seed = 48, 16, 5
    X = jax.random.normal(jax.random.key(4), (n, 6))
    S = X @ X.T
    st = StreamingSketch(StreamConfig(n1=n, n2=n, r=r, seed=seed,
                                      corange=False))
    for i in range(0, n, 16):
        st.update_rows(i, S[i:i + 16])
    B, C = st.nystrom()
    Bref, Cref = nystrom_reference(S, seed, r)
    np.testing.assert_allclose(np.asarray(B), np.asarray(Bref),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(C), np.asarray(Cref),
                               rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# sketch service: many streams, one mesh, shared executables
# ---------------------------------------------------------------------------

def test_service_streams_share_one_executable():
    n1, n2, r = 48, 64, 8
    A = jax.random.normal(jax.random.key(0), (n1, n2))
    svc = SketchService()
    sa = svc.open(StreamConfig(n1=n1, n2=n2, r=r, seed=11))
    sb = svc.open(StreamConfig(n1=n1, n2=n2, r=r, seed=999))
    for i in range(0, n1, 16):
        svc.update(sa, A[i:i + 16], row0=i)
        svc.update(sb, A[i:i + 16], row0=i)
    np.testing.assert_array_equal(np.asarray(svc.sketch(sa)),
                                  np.asarray(sketch_reference(A, 11, r)))
    np.testing.assert_array_equal(np.asarray(svc.sketch(sb)),
                                  np.asarray(sketch_reference(A, 999, r)))
    # different seeds, same shape signature -> ONE compiled update
    assert svc.num_compiled == 1, svc.stats()
    assert svc.num_streams == 2
    svc.close(sa)
    assert svc.num_streams == 1


def test_service_reconstruct_and_validation():
    svc = SketchService()
    cfg = StreamConfig(n1=32, n2=48, r=16, seed=7)
    sid = svc.open(cfg)
    M = (jax.random.normal(jax.random.key(5), (32, 4))
         @ jax.random.normal(jax.random.key(6), (4, 48)))
    svc.update(sid, M[:16], row0=0)
    svc.update(sid, M[16:], row0=16)
    assert float(reconstruction_error(M, svc.reconstruct(sid))) < 1e-4
    with pytest.raises(ValueError):
        svc.update(sid, M[:16], row0=20)    # overruns n1
    with pytest.raises(ValueError):
        svc.open(StreamConfig(n1=0, n2=4, r=2))


# ---------------------------------------------------------------------------
# (d) checkpointing: save/restore round-trips bitwise
# ---------------------------------------------------------------------------

def test_streaming_checkpoint_round_trip_bitwise(tmp_path):
    n1, n2, r, seed = 48, 64, 8, 5
    A = jax.random.normal(jax.random.key(0), (n1, n2))
    st = StreamingSketch(StreamConfig(n1=n1, n2=n2, r=r, seed=seed))
    st.update_rows(0, A[:24])
    st.update_rows(24, A[24:])
    path = st.save(str(tmp_path))
    assert "step_" in path

    st2 = StreamingSketch.restore(str(tmp_path))
    assert st2.cfg == st.cfg and st2.num_updates == 2
    # the backend travels with the checkpoint ("auto" re-resolution could
    # silently continue a stream on a non-bitwise kernel path)
    assert st2.backend == st.backend
    np.testing.assert_array_equal(np.asarray(st.Y), np.asarray(st2.Y))
    np.testing.assert_array_equal(np.asarray(st.W), np.asarray(st2.W))

    # bitwise-identical finalize: restored stream reconstructs the same
    lr1, lr2 = st.reconstruct(rank=4), st2.reconstruct(rank=4)
    np.testing.assert_array_equal(np.asarray(lr1.Q), np.asarray(lr2.Q))
    np.testing.assert_array_equal(np.asarray(lr1.X), np.asarray(lr2.X))

    # ...and further updates continue bitwise-identically to an unbroken run
    extra = jax.random.normal(jax.random.key(9), (16, n2))
    st.update_rows(8, extra)
    st2.update_rows(8, extra)
    np.testing.assert_array_equal(np.asarray(st.Y), np.asarray(st2.Y))


def test_streaming_checkpoint_no_corange(tmp_path):
    cfg = StreamConfig(n1=32, n2=48, r=8, seed=3, corange=False)
    A = jax.random.normal(jax.random.key(1), (32, 48))
    st = StreamingSketch(cfg)
    st.update_rows(0, A)
    st.save(str(tmp_path), step=7)
    st2 = StreamingSketch.restore(str(tmp_path))
    assert st2.W is None and st2.num_updates == 1
    np.testing.assert_array_equal(np.asarray(st.Y), np.asarray(st2.Y))


# ---------------------------------------------------------------------------
# (e) batched multi-stream fused ingest (one compiled call, N streams)
# ---------------------------------------------------------------------------

def test_service_update_batch_bitwise_vs_independent_streams():
    n1, n2, r, N = 48, 64, 8, 4
    seeds = [11, 99, 7, 2 ** 40 + 3]          # incl. a >32-bit key pair
    A = jax.random.normal(jax.random.key(0), (n1, n2))
    chunks = [(0, 16), (16, 32), (32, 48)]   # uniform height: one program

    batched = SketchService()
    sids = [batched.open(StreamConfig(n1=n1, n2=n2, r=r, seed=s))
            for s in seeds]
    singles = []
    for s in seeds:
        st = StreamingSketch(StreamConfig(n1=n1, n2=n2, r=r, seed=s),
                             backend="xla")
        singles.append(st)
    for (i0, i1) in chunks:
        batched.update_batch(sids, jnp.stack([A[i0:i1]] * N), row0=i0)
        for st in singles:
            st.update_rows(i0, A[i0:i1])

    for sid, st, s in zip(sids, singles, seeds):
        np.testing.assert_array_equal(np.asarray(batched.sketch(sid)),
                                      np.asarray(st.sketch))
        np.testing.assert_array_equal(np.asarray(batched.corange(sid)),
                                      np.asarray(st.corange_sketch))
        np.testing.assert_array_equal(np.asarray(batched.sketch(sid)),
                                      np.asarray(sketch_reference(A, s, r)))

    # N streams, any number of batched calls: ONE compiled batch program
    assert batched.num_compiled == 1, batched.stats()


def test_service_update_batch_per_lane_offsets_and_validation():
    n1, n2, r = 32, 48, 8
    A = jax.random.normal(jax.random.key(5), (n1, n2))
    svc = SketchService()
    sids = [svc.open(StreamConfig(n1=n1, n2=n2, r=r, seed=s,
                                  corange=False)) for s in (1, 2)]
    # per-lane row offsets: lane 0 ingests the top half, lane 1 the bottom
    svc.update_batch(sids, jnp.stack([A[:16], A[16:]]), row0=[0, 16])
    ref0 = np.asarray(sketch_reference(A, 1, r))
    got0 = np.asarray(svc.sketch(sids[0]))
    np.testing.assert_array_equal(got0[:16], ref0[:16])
    assert np.all(got0[16:] == 0)

    with pytest.raises(ValueError):
        svc.update_batch(sids, jnp.stack([A[:16], A[16:]]), row0=[0])
    with pytest.raises(ValueError):   # mixed shape signatures
        other = svc.open(StreamConfig(n1=n1, n2=n2, r=r + 8, seed=3,
                                      corange=False))
        svc.update_batch([sids[0], other],
                         jnp.stack([A[:16], A[:16]]), row0=0)
    with pytest.raises(ValueError):   # duplicate lanes would clobber
        svc.update_batch([sids[0], sids[0]],
                         jnp.stack([A[:16], A[16:]]), row0=[0, 16])
    with pytest.raises(NotImplementedError):
        from repro.core.sketch import make_grid_mesh
        SketchService(mesh=make_grid_mesh(1, 1, 1)).update_batch(
            [0], A[None, :16], row0=0)


# ---------------------------------------------------------------------------
# distributed: bitwise vs one-shot Alg. 1, and (c) zero Omega communication
# ---------------------------------------------------------------------------

_COMMON = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import (rand_matmul, rand_matmul_communicating,
                        sketch_reference, nystrom_reference, make_grid_mesh)
from repro.core.sketch import input_sharding
from repro.roofline.hlo import collective_bytes_of
from repro.stream import (StreamConfig, ShardedStreamingSketch, SketchService,
                          psi_matrix)
assert len(jax.devices()) == 8
"""


def test_sharded_stream_bitwise_and_zero_omega_comm():
    run_distributed(_COMMON + r"""
seed, n1, n2, r = 7, 16, 48, 8
A = jax.random.normal(jax.random.key(1), (n1, n2))
ref = np.asarray(sketch_reference(A, seed, r))

for shape in [(8,1,1), (2,2,2)]:
    mesh = make_grid_mesh(*shape)
    cfg = StreamConfig(n1=n1, n2=n2, r=r, seed=seed)
    st = ShardedStreamingSketch(cfg, mesh)
    rows = ShardedStreamingSketch(cfg, mesh)
    for (i0, i1) in [(0, 4), (4, 12), (12, 16)]:
        H = jnp.zeros((n1, n2)).at[i0:i1].set(A[i0:i1])
        st.update(H)
        rows.update_rows(i0, A[i0:i1])          # slab only, no zero frame
    oneshot = rand_matmul(jax.device_put(A, input_sharding(mesh)),
                          seed, r, mesh)
    # row-disjoint streamed updates == one-shot Alg. 1, bitwise
    assert np.array_equal(np.asarray(st.sketch), np.asarray(oneshot)), shape
    assert np.allclose(np.asarray(st.sketch), ref, atol=1e-4), shape
    Wref = np.asarray(psi_matrix(cfg) @ A)
    assert np.allclose(np.asarray(st.corange_sketch), Wref, atol=1e-4), shape
    # row-slab ingest == the full-shape additive path, bitwise on Y
    assert np.array_equal(np.asarray(rows.sketch), np.asarray(st.sketch)), shape
    assert np.allclose(np.asarray(rows.corange_sketch), Wref,
                       atol=1e-4), shape
print("OK bitwise")

# out-of-order, ragged slabs also reproduce the one-shot result bitwise,
# and slabs aligned to p1 row blocks keep W bitwise too
mesh = make_grid_mesh(8, 1, 1)
cfg = StreamConfig(n1=n1, n2=n2, r=r, seed=seed)
ragged = ShardedStreamingSketch(cfg, mesh)
for (i0, i1) in [(12, 16), (0, 7), (7, 12)]:
    ragged.update_rows(i0, A[i0:i1])
oneshot = rand_matmul(jax.device_put(A, input_sharding(mesh)), seed, r, mesh)
assert np.array_equal(np.asarray(ragged.sketch), np.asarray(oneshot))
aligned_full = ShardedStreamingSketch(cfg, mesh)
aligned_rows = ShardedStreamingSketch(cfg, mesh)
for i0 in range(0, n1, 2):          # p1-block-aligned slabs (n1/p1 = 2)
    H = jnp.zeros((n1, n2)).at[i0:i0+2].set(A[i0:i0+2])
    aligned_full.update(H)
    aligned_rows.update_rows(i0, A[i0:i0+2])
assert np.array_equal(np.asarray(aligned_rows.sketch),
                      np.asarray(aligned_full.sketch))
assert np.array_equal(np.asarray(aligned_rows.corange_sketch),
                      np.asarray(aligned_full.corange_sketch))
# same (cfg, mesh) -> accumulators share ONE compiled update executable
# (module-level program cache; keeps autotune trials compile-free too)
assert aligned_rows._upd is aligned_full._upd
print("OK update_rows")

# sharded checkpoint: save on one grid, restore on another, bitwise state
import tempfile
ckdir = tempfile.mkdtemp()
ragged.save(ckdir)
restored = ShardedStreamingSketch.restore(ckdir, make_grid_mesh(2, 2, 2))
assert np.array_equal(np.asarray(restored.Y), np.asarray(ragged.Y))
assert np.array_equal(np.asarray(restored.W), np.asarray(ragged.W))
assert restored.num_updates == ragged.num_updates
print("OK sharded checkpoint")

# omega_salt is honored on the distributed path (independent salted streams)
from repro.stream import StreamConfig as SC
from repro.stream.state import omega_matrix
mesh = make_grid_mesh(2, 2, 2)
cfgs = SC(n1=n1, n2=n2, r=r, seed=seed, omega_salt=2, psi_salt=5)
sts = ShardedStreamingSketch(cfgs, mesh)
sts.update(jax.device_put(A, input_sharding(mesh)))
assert np.allclose(np.asarray(sts.sketch),
                   np.asarray(A @ omega_matrix(cfgs)), atol=1e-4)
assert not np.allclose(np.asarray(sts.sketch), ref, atol=1e-3)
print("OK salt")

# ---- (c) communication accounting of the compiled update step ----------
# Regime-1 grid (P,1,1): Theorem 2 says zero; the streaming update must
# also be zero — Omega/Psi regenerated, B/W shards resident.
mesh = make_grid_mesh(8, 1, 1)
cfg = StreamConfig(n1=16, n2=32, r=8, seed=3, corange=False)
st = ShardedStreamingSketch(cfg, mesh)
H = jax.device_put(jnp.zeros((16, 32)), input_sharding(mesh))
cb = collective_bytes_of(st._upd.lower(st.Y, st.W, H).compile().as_text())
assert cb.total == 0, cb
print("OK regime1 zero bytes")

# General grid: the update moves EXACTLY the one-shot Alg.-1 bytes (the
# all-gather of H + reduce-scatter of dY) — i.e. zero *additional* Omega
# communication — and strictly fewer bytes than the Omega-communicating
# baseline.
mesh = make_grid_mesh(2, 2, 2)
cfg = StreamConfig(n1=16, n2=64, r=8, seed=3, corange=False)
st = ShardedStreamingSketch(cfg, mesh)
H = jax.device_put(jnp.zeros((16, 64)), input_sharding(mesh))
cb_up = collective_bytes_of(st._upd.lower(st.Y, st.W, H).compile().as_text())
cb_one = collective_bytes_of(
    jax.jit(lambda a: rand_matmul(a, 3, 8, mesh)).lower(H).compile().as_text())
assert cb_up.total == cb_one.total, (cb_up, cb_one)
assert cb_up.counts == cb_one.counts, (cb_up, cb_one)
cb_com = collective_bytes_of(
    jax.jit(lambda a: rand_matmul_communicating(a, 3, 8, mesh))
    .lower(A := H).compile().as_text())
assert cb_up.total < cb_com.total, (cb_up, cb_com)
print("OK update == alg1 bytes")

# Co-range tracking adds exactly the data-derived psum of the W partial
# (l x n2/(p2 p3) f32 words per device) — still zero Omega/Psi bytes.
cfg2 = StreamConfig(n1=16, n2=64, r=8, seed=3, corange=True)
st2 = ShardedStreamingSketch(cfg2, mesh)
cb2 = collective_bytes_of(st2._upd.lower(st2.Y, st2.W, H).compile().as_text())
expect = cfg2.sketch_l * (64 // 4) * 4
assert cb2.total - cb_up.total == expect, (cb2, cb_up, expect)
print("OK corange accounting")

# ---- streaming Nystrom + service sharing (same subprocess: one jax init,
# same 8 fake devices) --------------------------------------------------
X = jax.random.normal(jax.random.key(4), (64, 8)); S = X @ X.T
mesh = make_grid_mesh(8, 1, 1)
svc = SketchService(mesh=mesh)
sid = svc.open(StreamConfig(n1=64, n2=64, r=16, seed=5, corange=False))
for (i0, i1) in [(0, 32), (32, 64)]:
    svc.update(sid, jnp.zeros((64, 64)).at[i0:i1].set(S[i0:i1]))
Bref, Cref = nystrom_reference(S, 5, 16)
for variant in ("no_redist", "redist"):
    B, C = svc.nystrom(sid, variant=variant)
    assert np.allclose(np.asarray(B), np.asarray(Bref), atol=1e-4), variant
    assert np.allclose(np.asarray(C), np.asarray(Cref), atol=1e-3), variant
print("OK nystrom variants")

# many distributed streams share one compiled update
sid2 = svc.open(StreamConfig(n1=64, n2=64, r=16, seed=77, corange=False))
svc.update(sid2, jnp.asarray(S))
assert svc.num_compiled == 1, svc.stats()
assert np.allclose(np.asarray(svc.sketch(sid2)),
                   np.asarray(sketch_reference(S, 77, 16)), atol=1e-4)
print("OK service sharing")
""")
