"""One-pass streaming sketch state (Tropp et al. 2017; paper §4.2 + §6.3).

The sketches the paper parallelizes are *linear* in A, so they support the
one-pass streaming model of Tropp et al., *Practical sketching algorithms
for low-rank matrix approximation* (see PAPERS.md): for any additive update

    A  <-  A + H      =>      Y  <-  Y + H·Omega ,   W  <-  W + Psi·H

where Y = A·Omega (n1 x r) is the range sketch and W = Psi·A (l x n2) the
co-range sketch.  A never has to be resident; only the O((n1 + n2)·r) sketch
state is stored.  Because Omega and Psi are regenerated from a counter-based
seed (the source paper's central claim, §6.3), streaming updates inherit the
zero-communication property for free: no processor ever sends or receives a
byte of Omega or Psi, no matter how many updates arrive.

Update granularities:

  * ``update_rows(row0, H)`` — a block of rows arrives (the classic
    streaming model).  Each row of Y is produced by one full-contraction
    GEMM, so a row-partitioned stream reproduces the one-shot
    ``core.sketch.sketch_reference`` **bitwise**, for any chunking and any
    arrival order.
  * ``update_cols(col0, H)`` — a block of columns arrives; Y accumulates
    partial contractions (equal to one-shot up to FP summation order).
  * ``update(H)`` — general additive update of the full matrix.

Determinism contract: Omega/Psi entries are bitwise-invariant to tiling and
compilation context by construction (see ``core/rng.py``), and each Y row is
written by exactly one row-block update (0 + x == x in IEEE-754), so a given
row chunking produces identical bits in ANY arrival order.  Equality with
the one-shot ``sketch_reference`` is additionally bitwise whenever the
backend computes a dot's rows identically across GEMM heights — true at
small/moderate contraction sizes (pinned by tests/test_stream.py), but CPU
BLAS may switch blocking for very short chunks against a large contraction
(e.g. 64-row chunks at n2=1024), where agreement drops to reduction-order
tolerance (~1e-5).  W and overlapping/column updates accumulate in arrival
order, so they match one-shot results to FP tolerance, not bitwise.

The local accumulator here runs on one device; ``distributed.py`` holds the
mesh-sharded version and ``service.py`` the many-streams serving front end.
On TPU the local GEMM can run through the fused Pallas kernel
(``kernels/sketch_matmul.py``), which also keeps Omega out of HBM.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sketch import (F32, SPARSE_KINDS, omega_tile, seed_keys,
                               sparse_omega_rows, validate_kind)

OMEGA_SALT = 0   # salt stream for Omega (range sketch)
PSI_SALT = 1     # salt stream for Psi (co-range sketch); must differ


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Shape/seed contract of one stream.

    n1, n2 : global shape of the streamed matrix A
    r      : range-sketch size (columns of Omega)
    l      : co-range-sketch size (rows of Psi); default 2r+1 per Tropp
             et al.'s l >= 2k+1 guidance, clipped to n1
    seed   : Philox seed; Omega and Psi come from the same seed under
             different salts, so one uint32 pair keys the whole stream
    kind   : Omega/Psi family — dense entry distributions ("normal" |
             "uniform" | "rademacher") or the sparse families
             ("countsketch" | "rowsample", one nonzero per row; see
             core/sketch.py SPARSE_KINDS)
    corange: track W = Psi·A (needed for general low-rank reconstruction;
             unnecessary for sketch-only and Nyström workloads)
    """
    n1: int
    n2: int
    r: int
    l: Optional[int] = None
    seed: int = 0
    kind: str = "normal"
    dtype: Any = jnp.float32
    corange: bool = True
    omega_salt: int = OMEGA_SALT
    psi_salt: int = PSI_SALT

    @property
    def sketch_l(self) -> int:
        return self.l if self.l is not None else min(2 * self.r + 1, self.n1)

    def validate(self):
        validate_kind(self.kind)
        if self.r <= 0 or self.n1 <= 0 or self.n2 <= 0:
            raise ValueError(f"bad stream shape {self}")
        if self.omega_salt == self.psi_salt and self.corange:
            raise ValueError("omega_salt and psi_salt must differ")

    # -- JSON round trip (checkpoint manifests) -----------------------------

    def to_json_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["dtype"] = jnp.dtype(self.dtype).name
        return d

    @classmethod
    def from_json_dict(cls, d: dict) -> "StreamConfig":
        d = dict(d)
        d["dtype"] = jnp.dtype(d["dtype"])
        return cls(**d)


def omega_matrix(cfg: StreamConfig, seed=None):
    """The full (n2, r) Omega of a stream (reference/inspection path)."""
    return omega_tile(cfg.seed if seed is None else seed, 0, 0,
                      cfg.n2, cfg.r, cfg.kind, cfg.dtype, salt=cfg.omega_salt)


def psi_matrix(cfg: StreamConfig, seed=None):
    """The full (l, n1) Psi.  Generated as the transpose of an (n1, l) tile
    so column slices Psi[:, i0:i1] share global row coordinates with the
    row-block updates that consume them (tile-decomposition invariance)."""
    return omega_tile(cfg.seed if seed is None else seed, 0, 0,
                      cfg.n1, cfg.sketch_l, cfg.kind, cfg.dtype,
                      salt=cfg.psi_salt, n_total=cfg.n1).T


def psi_cols(cfg: StreamConfig, row0, rows: int, seed=None):
    """Psi[:, row0:row0+rows] as an (rows, l) tile (pre-transpose layout);
    row0 may be traced.  ``n_total=cfg.n1`` pins the rowsample membership
    probability to the stream's global height, row slice or not."""
    return omega_tile(cfg.seed if seed is None else seed, row0, 0,
                      rows, cfg.sketch_l, cfg.kind, cfg.dtype,
                      salt=cfg.psi_salt, n_total=cfg.n1)


def validate_row_block(cfg: StreamConfig, row0: int, shape: Tuple[int, int]):
    """Bounds check shared by the accumulator and the service."""
    k, n2 = shape
    if n2 != cfg.n2 or row0 < 0 or row0 + k > cfg.n1:
        raise ValueError(f"row block ({row0}, {shape}) outside "
                         f"({cfg.n1}, {cfg.n2})")


@dataclasses.dataclass(frozen=True)
class SparseRows:
    """A sparse row slab in COO form: ``A[row0 + row[e], col[e]] += val[e]``.

    ``shape = (k, n2)`` is the DENSE slab shape the entries live in; the
    wire format is (indices, values) — ``2·nnz`` words instead of the
    dense slab's ``k·n2`` — which is exactly what the sparse ledger site
    and ``plan.model.sparse_payload_words`` price.
    """
    row: Any                   # (nnz,) int32, local row within the slab
    col: Any                   # (nnz,) int32, global column in [0, n2)
    val: Any                   # (nnz,) values
    shape: Tuple[int, int]     # (k, n2)

    @property
    def nnz(self) -> int:
        return int(np.shape(self.row)[0])

    @classmethod
    def from_dense(cls, H) -> "SparseRows":
        """COO of a dense slab (entry order: row-major, as np.nonzero)."""
        H = np.asarray(H)
        r, c = np.nonzero(H)
        return cls(row=np.asarray(r, np.int32), col=np.asarray(c, np.int32),
                   val=H[r, c], shape=tuple(H.shape))

    def to_dense(self, dtype=None):
        out = np.zeros(self.shape,
                       dtype or np.asarray(self.val).dtype)
        np.add.at(out, (np.asarray(self.row), np.asarray(self.col)),
                  np.asarray(self.val))
        return out

    def validate(self, cfg: StreamConfig, row0: int) -> None:
        validate_row_block(cfg, row0, self.shape)
        k, n2 = self.shape
        row = np.asarray(self.row)
        col = np.asarray(self.col)
        if row.shape != col.shape or row.shape != np.shape(self.val):
            raise ValueError(f"ragged COO arrays: {row.shape} / "
                             f"{col.shape} / {np.shape(self.val)}")
        if row.size and (row.min() < 0 or row.max() >= k
                         or col.min() < 0 or col.max() >= n2):
            raise ValueError(f"COO indices outside slab shape {self.shape}")

    def padded(self, nnz_b: int):
        """(row, col, val) padded to ``nnz_b`` entries.  Pads carry
        ``row == k`` / ``col == n2`` / ``val == 0`` and are routed into
        sacrificial accumulator rows/columns that the update program drops
        before folding — a pad can never touch a real partial sum, so
        padding cannot perturb a single result bit."""
        k, n2 = self.shape
        nnz = self.nnz
        if nnz > nnz_b:
            raise ValueError(f"nnz={nnz} exceeds bucket {nnz_b}")
        pad = nnz_b - nnz
        row = np.concatenate([np.asarray(self.row, np.int32),
                              np.full(pad, k, np.int32)])
        col = np.concatenate([np.asarray(self.col, np.int32),
                              np.full(pad, n2, np.int32)])
        val = np.concatenate([np.asarray(self.val),
                              np.zeros(pad, np.asarray(self.val).dtype)])
        return row, col, val


def nystrom_local(Y, cfg: StreamConfig):
    """(B, C) of a symmetric stream on one device: C = Omega^T·Y needs no
    second pass over A — it is computable from the sketch alone."""
    om = omega_tile(cfg.seed, 0, 0, cfg.n2, cfg.r, cfg.kind, Y.dtype,
                    salt=cfg.omega_salt)
    return Y, jnp.matmul(om.T, Y, precision=F32)


@functools.lru_cache(maxsize=4096)
def _local_sig(cfg: StreamConfig) -> Tuple:
    """Executable signature of the local row-block update — NOT the seed.
    Cached: it sits on the per-lane hot path of ragged batched ingest."""
    return (cfg.n1, cfg.n2, cfg.r, cfg.sketch_l if cfg.corange else None,
            cfg.kind, jnp.dtype(cfg.dtype).name, cfg.corange,
            cfg.omega_salt, cfg.psi_salt)


def _slab_times_omega(H, om):
    """``H @ om`` for a (k, n2) row slab.  A one-row slab is summed as an
    explicit multiply-reduce: XLA:CPU lowers a one-row matmul through a
    gemv whose summation order differs from the batched dot of a vmapped
    ragged lane, while the reduce lowers the same way in both, so the
    solo update and the lane stay bitwise equal.  It sums in f32, as the
    matmul does."""
    if H.shape[0] == 1:
        f32 = jnp.float32
        return jnp.sum(H.T.astype(f32) * om.astype(f32), axis=0,
                       keepdims=True).astype(H.dtype)
    return jnp.matmul(H, om, precision=F32)


def _local_rowblock_update(sig: Tuple, k: int):
    """The pure local row-block update (shared single-stream/batched)."""
    n1, n2, r, l, kind, dtype_name, corange, omega_salt, psi_salt = sig
    dtype = jnp.dtype(dtype_name)

    def upd(Y, W, H, keys, row0):
        om = omega_tile(keys, 0, 0, n2, r, kind, dtype, salt=omega_salt)
        dY = _slab_times_omega(H, om)                 # full contraction
        Yk = jax.lax.dynamic_slice(Y, (row0, 0), (k, r))
        Y = jax.lax.dynamic_update_slice(Y, Yk + dY, (row0, 0))
        if corange:
            psi_c = omega_tile(keys, row0, 0, k, l, kind, dtype,
                               salt=psi_salt, n_total=n1)  # (k, l)
            W = W + jnp.matmul(psi_c.T, H, precision=F32)
        return Y, W

    return upd


@functools.lru_cache(maxsize=256)
def local_rowblock_prog(sig: Tuple, k: int):
    """Compiled local row-block update, shared by every StreamingSketch and
    SketchService stream with the same shape signature: the seed enters as
    a traced uint32 key pair and the row offset as a traced int32, so one
    executable serves all seeds and offsets at chunk height ``k``.

    (Eager per-update dispatch of the Philox graph costs orders of
    magnitude more than this cached program — see core/sketch.py.)
    """
    return jax.jit(_local_rowblock_update(sig, k))


def pow2_bucket(k: int) -> int:
    """Smallest power of two >= k — the default ragged bucket snap (keeps
    the number of distinct compiled bucket programs logarithmic in the
    spread of lane heights)."""
    if k <= 1:
        return 1
    return 1 << (k - 1).bit_length()


def snap_bucket(k: int, edges=None) -> int:
    """Bucket height for a k-row lane: the smallest edge >= k when
    ``edges`` (ascending bucket tops, e.g. from
    ``repro.plan.choose_bucket_edges``) is given — a lane taller than
    every edge falls back to the pow2 snap (NOT its exact height, which
    would compile one ragged program per distinct over-tall height and
    stall live traffic for seconds per new height; the pow2 fallback
    keeps the over-tall program count logarithmic, pinned by
    tests/test_sparse.py::test_snap_bucket_overtall_*) — else the pow2
    snap.

    Height-1 lanes are never padded into a taller bucket: XLA-CPU lowers
    an M=1 matmul through a gemv kernel whose K-reduction order differs
    from the packed M>=2 gemm loop, so padding a single-row slab would
    break the lane-vs-solo bitwise contract at large contractions
    (pinned by tests/test_service_scale.py)."""
    if k <= 1:
        return 1
    if edges is None:
        return pow2_bucket(k)
    for e in edges:
        if e >= k:
            return int(e)
    return pow2_bucket(k)


def _local_ragged_update(sig: Tuple, kb: int, backend: str = "jnp"):
    """One lane of the shape-bucketed ragged update: a (kb, n2) padded slab
    whose first ``kvalid`` rows are real, folded at traced ``row0``.

    Pad rows are masked dead IN-PROGRAM — the H tail is zeroed before
    either GEMM (so a NaN pad probe never reaches Y or W) and the Y fold
    is windowed to ``kvalid`` rows (``fold_rows_block(nvalid=...)``), so
    rows outside [row0, row0 + kvalid) keep their exact input bits.  For
    the valid rows the expressions are literally those of
    :func:`_local_rowblock_update` (native-dtype GEMM against the same
    regenerated Omega/Psi tiles), which is what makes lane i of a bucketed
    batch bitwise the result of updating stream i alone (pinned by
    tests/test_service_scale.py).  ``backend`` dispatches the fold body
    (kernels/local.py): the pallas fold never builds the padded frame
    and aliases Y in-place; both backends add the same slab rows to the
    same Y rows, so the fold is bitwise across backends.
    """
    from repro.kernels.local import fold_rows_block
    n1, n2, r, l, kind, dtype_name, corange, omega_salt, psi_salt = sig
    dtype = jnp.dtype(dtype_name)

    def upd(Y, W, H, keys, row0, kvalid):
        rows = jax.lax.broadcasted_iota(jnp.int32, (kb, 1), 0)
        Hm = jnp.where(rows < kvalid, H, jnp.zeros_like(H))
        om = omega_tile(keys, 0, 0, n2, r, kind, dtype, salt=omega_salt)
        dY = _slab_times_omega(Hm, om)                # full contraction
        start = jnp.int32(n1) - jnp.asarray(row0, jnp.int32)
        Y = fold_rows_block(Y, dY, start, backend=backend, nvalid=kvalid)
        if corange:
            # Psi columns at global rows [row0, row0 + kb): the tail draws
            # beyond kvalid (possibly beyond n1) multiply zeroed H rows,
            # so they contribute exact ±0 terms only
            psi_c = omega_tile(keys, row0, 0, kb, l, kind, dtype,
                               salt=psi_salt, n_total=n1)  # (kb, l)
            W = W + jnp.matmul(psi_c.T, Hm, precision=F32)
        return Y, W

    return upd


@functools.lru_cache(maxsize=128)
def local_rowblock_ragged_prog(sig: Tuple, kb: int, n_streams: int,
                               backend: str = "jnp"):
    """Compiled shape-bucketed ragged batch update: ONE call ingests
    ``n_streams`` heterogeneous lanes padded to bucket height ``kb``, each
    under its own traced Philox key pair, row offset and valid-row count.

    The stacked (Y, W) accumulator buffers are DONATED: the program
    updates them in place, so batched ingest never holds two copies of the
    fleet's sketch state in HBM (the service stacks fresh buffers per
    call, which is exactly the aliasing-safe donation case).
    """
    corange = sig[6]
    upd = _local_ragged_update(sig, kb, backend)
    batched = jax.vmap(upd, in_axes=(0, 0 if corange else None, 0, 0, 0, 0))
    return jax.jit(batched, donate_argnums=(0, 1) if corange else (0,))


@functools.lru_cache(maxsize=128)
def local_rowblock_batch_prog(sig: Tuple, k: int, n_streams: int):
    """Batched (vmapped) row-block update: one compiled call ingests the
    same-shape chunk into ``n_streams`` independent streams at once, each
    lane running under its own traced Philox key pair and row offset —
    the generated Omega/Psi lanes are bitwise those of ``n_streams``
    separate single-stream updates (counter-based generation depends only
    on (keys, global coordinates), never on the batching context).
    """
    corange = sig[6]
    upd = _local_rowblock_update(sig, k)
    batched = jax.vmap(upd, in_axes=(0, 0 if corange else None, 0, 0, 0))
    return jax.jit(batched)


def _local_sparse_update(sig: Tuple, k: int, nnz_b: int):
    """Pure sparse row-slab update: H arrives as ``nnz_b`` COO entries
    (row, col, val) of a (k, n2) slab — O(nnz) scatter-adds when the
    Omega/Psi family is itself sparse, O(nnz·r) gathered FMAs against a
    regenerated dense Omega otherwise.  Never densifies H.

    Pad entries (``row == k`` / ``col == n2`` / ``val == 0``, appended by
    :meth:`SparseRows.padded`) scatter into one sacrificial dY row / W
    column that is dropped before the fold, so they cannot flip even a
    -0.0 in a real accumulator.
    """
    n1, n2, r, l, kind, dtype_name, corange, omega_salt, psi_salt = sig
    dtype = jnp.dtype(dtype_name)
    sparse_om = kind in SPARSE_KINDS

    def upd(Y, W, row, col, val, keys, row0):
        val = val.astype(dtype)
        if sparse_om:
            # Omega row ``col`` has ONE nonzero: (bucket, value) drawn at
            # counter g = col — gathered per stored entry (bitwise equal
            # to slicing the full map; counter-based draws see only g).
            b, v = sparse_omega_rows(keys, col, r, kind, dtype,
                                     salt=omega_salt, n_total=n2)
            dY = jnp.zeros((k + 1, r), dtype).at[row, b].add(val * v)
        else:
            om = omega_tile(keys, 0, 0, n2, r, kind, dtype,
                            salt=omega_salt)
            om = jnp.concatenate([om, jnp.zeros((1, r), dtype)])  # col==n2
            dY = jnp.zeros((k + 1, r), dtype).at[row].add(
                val[:, None] * om[col])
        dY = dY[:k]
        Yk = jax.lax.dynamic_slice(Y, (row0, 0), (k, r))
        Y = jax.lax.dynamic_update_slice(Y, Yk + dY, (row0, 0))
        if corange:
            g = jnp.asarray(row0, jnp.uint32) + row.astype(jnp.uint32)
            Wp = jnp.concatenate([W, jnp.zeros((l, 1), dtype)], axis=1)
            if sparse_om:
                pb, pv = sparse_omega_rows(keys, g, l, kind, dtype,
                                           salt=psi_salt, n_total=n1)
                Wp = Wp.at[pb, col].add(pv * val)
            else:
                # dense Psi columns at the entries' global rows: (k+1, l)
                # tile rows gathered by local row (row == k pads gather a
                # real draw that lands in the dropped column)
                psi_c = omega_tile(keys, row0, 0, k + 1, l, kind, dtype,
                                   salt=psi_salt, n_total=n1)
                Wp = Wp.at[:, col].add((psi_c[row] * val[:, None]).T)
            W = Wp[:, :n2]
        return Y, W

    return upd


@functools.lru_cache(maxsize=256)
def local_sparse_prog(sig: Tuple, k: int, nnz_b: int):
    """Compiled sparse row-slab update, cached per (signature, slab height,
    nnz bucket) — ``nnz_b`` is pow2-snapped by the callers so the number
    of distinct compiled programs stays logarithmic in payload spread."""
    return jax.jit(_local_sparse_update(sig, k, nnz_b))


@functools.lru_cache(maxsize=128)
def local_sparse_batch_prog(sig: Tuple, k: int, nnz_b: int, n_streams: int):
    """Batched (vmapped) sparse row-slab update: the single-stream sparse
    program vmapped over a leading lane axis with per-lane keys, offsets
    and COO payloads — lane i's bits are those of updating stream i alone
    (counter-based draws see only (keys, global coordinates))."""
    corange = sig[6]
    upd = _local_sparse_update(sig, k, nnz_b)
    batched = jax.vmap(upd,
                       in_axes=(0, 0 if corange else None, 0, 0, 0, 0, 0))
    return jax.jit(batched)


class StreamingSketch:
    """Single-device streaming accumulator for (Y, W).

    backend:
      * ``"xla"``     — plain jnp GEMM against a regenerated Omega tile
                        (bitwise-stable vs. ``sketch_reference``).
                        ``"jnp"`` is accepted as an alias (the name the
                        distributed entry points use — kernels/local.py).
      * ``"pallas"``  — the fused TPU kernel (Omega generated in VMEM,
                        never materialized in HBM).  Numerically equal to
                        within f32-accumulation tolerance, not bitwise.
      * ``"interpret"`` — the Pallas kernel in interpret mode (CPU tests).
      * ``"auto"``    — "pallas" on TPU, else "xla".
    """

    def __init__(self, cfg: StreamConfig, backend: str = "auto"):
        cfg.validate()
        if backend == "auto":
            backend = "pallas" if jax.default_backend() == "tpu" else "xla"
        if backend == "jnp":
            backend = "xla"
        if backend not in ("xla", "pallas", "interpret"):
            raise ValueError(f"unknown backend {backend!r}")
        self.cfg = cfg
        self.backend = backend
        self.Y = jnp.zeros((cfg.n1, cfg.r), cfg.dtype)
        self.W = (jnp.zeros((cfg.sketch_l, cfg.n2), cfg.dtype)
                  if cfg.corange else None)
        self._keys = jnp.stack(seed_keys(cfg.seed))
        self.num_updates = 0

    # -- sketch kernels ----------------------------------------------------

    def _range_delta(self, H):
        """H @ Omega over the full contraction (H: (k, n2))."""
        cfg = self.cfg
        if self.backend == "xla":
            om = omega_tile(cfg.seed, 0, 0, cfg.n2, cfg.r, cfg.kind,
                            H.dtype, salt=cfg.omega_salt)
            return jnp.matmul(H, om, precision=F32)
        from repro.kernels.ops import sketch_matmul
        return sketch_matmul(H, seed=cfg.seed, r=cfg.r, kind=cfg.kind,
                             salt=cfg.omega_salt,
                             interpret=(self.backend == "interpret"))

    # -- updates -----------------------------------------------------------

    def update_rows(self, row0: int, H):
        """Rows [row0, row0+k) arrive (additively).  Bitwise-reproduces the
        one-shot sketch for row-partitioned streams."""
        cfg = self.cfg
        validate_row_block(cfg, row0, H.shape)
        H = jnp.asarray(H, cfg.dtype)
        if self.backend == "xla":
            fn = local_rowblock_prog(_local_sig(cfg), H.shape[0])
            self.Y, self.W = fn(self.Y, self.W, H, self._keys,
                                jnp.int32(row0))
        else:
            k = H.shape[0]
            self.Y = self.Y.at[row0:row0 + k, :].add(self._range_delta(H))
            if self.W is not None:
                self.W = self.W + jnp.matmul(psi_cols(cfg, row0, k).T, H,
                                             precision=F32)
        self.num_updates += 1
        return self

    def update_rows_sparse(self, row0: int, sp: SparseRows):
        """Rows [row0, row0+k) arrive as a COO slab (additively).

        Folds exactly the numbers :meth:`update_rows` would fold for the
        densified slab up to scatter-accumulation order, moves only
        ``2·nnz`` words of payload, and never materializes the dense slab
        on device.  The compiled program is cached per (signature, k,
        pow2(nnz)); the pad entries are routed into sacrificial
        rows/columns so bucket padding is bitwise-invisible.
        """
        cfg = self.cfg
        sp.validate(cfg, row0)
        nnz_b = pow2_bucket(max(1, sp.nnz))
        row, col, val = sp.padded(nnz_b)
        fn = local_sparse_prog(_local_sig(cfg), sp.shape[0], nnz_b)
        self.Y, self.W = fn(self.Y, self.W, jnp.asarray(row),
                            jnp.asarray(col), jnp.asarray(val, cfg.dtype),
                            self._keys, jnp.int32(row0))
        self.num_updates += 1
        return self

    def update_cols(self, col0: int, H):
        """Columns [col0, col0+k) arrive (additively)."""
        cfg = self.cfg
        n1, k = H.shape
        if n1 != cfg.n1 or col0 < 0 or col0 + k > cfg.n2:
            raise ValueError(f"col block ({col0}, {H.shape}) outside "
                             f"({cfg.n1}, {cfg.n2})")
        H = jnp.asarray(H, cfg.dtype)
        om_rows = omega_tile(cfg.seed, col0, 0, k, cfg.r, cfg.kind,
                             H.dtype, salt=cfg.omega_salt,
                             n_total=cfg.n2)                 # Omega[col0:,:]
        self.Y = self.Y + jnp.matmul(H, om_rows, precision=F32)
        if self.W is not None:
            self.W = self.W.at[:, col0:col0 + k].add(
                jnp.matmul(psi_matrix(cfg), H, precision=F32))
        self.num_updates += 1
        return self

    def update(self, H):
        """General additive update A <- A + H with H of full shape."""
        if H.shape != (self.cfg.n1, self.cfg.n2):
            raise ValueError(f"update shape {H.shape} != "
                             f"({self.cfg.n1}, {self.cfg.n2})")
        return self.update_rows(0, H)

    # -- finalization ------------------------------------------------------

    @property
    def sketch(self):
        """The accumulated range sketch Y = A·Omega (the Alg.-1 output B)."""
        return self.Y

    @property
    def corange_sketch(self):
        return self.W

    def nystrom(self):
        """(B, C) Nyström pair of a symmetric stream — C from the sketch
        alone, no second pass over A (see :func:`nystrom_local`)."""
        cfg = self.cfg
        if cfg.n1 != cfg.n2:
            raise ValueError("Nyström needs a square (symmetric) stream")
        if self.backend in ("pallas", "interpret"):
            from repro.kernels.ops import sketch_t_matmul
            C = sketch_t_matmul(self.Y, seed=cfg.seed, r=cfg.r,
                                kind=cfg.kind, salt=cfg.omega_salt,
                                interpret=(self.backend == "interpret"))
            return self.Y, C
        return nystrom_local(self.Y, cfg)

    def reconstruct(self, rank: Optional[int] = None, rcond=None):
        """One-pass fixed-rank approximation A ~= Q·(Psi Q)†·W."""
        from .reconstruct import one_pass_reconstruct
        if self.W is None:
            raise ValueError("reconstruction needs corange=True")
        return one_pass_reconstruct(self.Y, self.W, self.cfg, rank=rank,
                                    rcond=rcond)

    # -- checkpointing ------------------------------------------------------

    def save(self, directory: str, step: Optional[int] = None,
             keep: int = 3) -> str:
        """Checkpoint the sketch state via ``checkpoint.ckpt`` (atomic,
        mesh-agnostic): (Y, W) as arrays, (config, seed, num_updates) in
        the manifest's ``extra``.  A long-running stream that restarts from
        this checkpoint finalizes bitwise-identically to one that never
        stopped — the sketch state plus the seed IS the whole stream.
        """
        from repro.checkpoint import ckpt
        step = self.num_updates if step is None else step
        tree = {"Y": self.Y}
        if self.W is not None:
            tree["W"] = self.W
        extra = {"config": self.cfg.to_json_dict(),
                 "num_updates": self.num_updates,
                 "backend": self.backend,
                 "layout": "local"}
        return ckpt.save(directory, step, tree, extra=extra, keep=keep)

    @classmethod
    def restore(cls, directory: str, step: Optional[int] = None,
                backend: Optional[str] = None) -> "StreamingSketch":
        """Rebuild a stream (config + state) from a checkpoint.

        The saved backend is restored by default (``backend="auto"`` would
        otherwise re-resolve per machine and could continue a stream on a
        non-bitwise kernel path); pass ``backend=`` explicitly to migrate.
        """
        from repro.checkpoint import ckpt
        extra, step = ckpt.load_extra(directory, step)
        cfg = StreamConfig.from_json_dict(extra["config"])
        st = cls(cfg, backend=backend or extra.get("backend", "auto"))
        tree = {"Y": st.Y}
        if st.W is not None:
            tree["W"] = st.W
        tree, _, extra = ckpt.restore(directory, tree, step)
        st.Y = tree["Y"]
        st.W = tree.get("W")
        st.num_updates = int(extra["num_updates"])
        return st
