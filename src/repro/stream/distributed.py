"""Mesh-sharded streaming sketch state (paper Alg. 1 applied per update).

State layout on the (p1, p2, p3) grid — the streaming extension of the
Alg.-1 contract (see docs/ARCHITECTURE.md):

  Y (n1 x r)  : sharded P((p1, p2), p3)   — the Alg.-1 *output* layout, so
                every update's Reduce-Scatter lands exactly on the resident
                shard; accumulation is local adds, zero extra movement.
  W (l  x n2) : sharded P(None, (p2, p3)) — column-split like A's blocks,
                replicated over p1; each update psums the per-p1 partial
                Psi_i^T·H_i over the p1 fiber.

Per additive update A <- A + H the communication is exactly the Alg.-1 cost
of sketching H (All-Gather over p3 + Reduce-Scatter over p2; zero in the
regime-1 grids p2 = p3 = 1) plus, when the co-range sketch is enabled, one
All-Reduce of l·n2/(p2·p3) words over p1.  No Omega or Psi entries are ever
communicated — both are regenerated per update from the stream seed.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.compat import shard_map
from repro.core.nystrom import (
    nystrom_second_stage_no_redist,
    nystrom_second_stage_redist,
    nystrom_second_stage_two_grid_fused,
)
from repro.core.sketch import (
    DEFAULT_AXES,
    F32,
    input_sharding,
    omega_tile,
    output_sharding,
    rand_matmul,
)
from repro.obs import ledger as obs_ledger
from repro.obs import trace as obs_trace

from .state import StreamConfig, psi_cols, validate_row_block


def corange_sharding(mesh: Mesh, axes=DEFAULT_AXES) -> NamedSharding:
    """Sharding of W per the streaming state layout."""
    return NamedSharding(mesh, P(None, (axes[1], axes[2])))


def stream_shardings(cfg: StreamConfig, mesh: Mesh,
                     axes=DEFAULT_AXES) -> dict:
    """NamedShardings of a stream's accumulator tree ({"Y", "W"?}) — the
    single source of truth for placement at open, eviction-restore and
    checkpoint-restore time (service and ShardedStreamingSketch agree by
    construction)."""
    sh = {"Y": output_sharding(mesh, axes)}
    if cfg.corange:
        sh["W"] = corange_sharding(mesh, axes)
    return sh


def nystrom_finalize(Y, cfg: StreamConfig, mesh: Mesh,
                     axes: Tuple[str, str, str] = DEFAULT_AXES,
                     variant: str = "auto", backend: str = "auto"):
    """(B, C) of a symmetric stream from its accumulated Y, reusing the
    Alg.-2 second stages.

    Needs a 1-D (P, 1, 1) grid so Y is row-sharded — exactly the layout the
    paper's Redist / No-Redist second stages consume.  ``auto`` follows the
    paper's crossover: redist iff P > n/r (Fig. 7).  ``bound_driven`` runs
    the §5.3 general two-grid second stage: the accumulated Y plays stage
    1's B (already on the (P, 1, 1) grid), and the bound's q-grid — snapped
    to the min-words executable factorization — consumes it via
    :func:`repro.core.nystrom.nystrom_second_stage_two_grid_fused`, which
    compiles the §5.2 Redistribute and the stage-2 collectives into one
    program on the shared mesh (the (P, 1, 1) accumulator grid always
    admits one).
    ``backend`` selects the second stage's local GEMM body
    (kernels/local.py) — the pallas backend keeps Omega out of HBM at
    finalize time too.
    """
    with obs_trace.span("stream.nystrom_finalize", cat="stream",
                        variant=variant):
        return _nystrom_finalize(Y, cfg, mesh, axes, variant, backend)


def _nystrom_finalize(Y, cfg, mesh, axes, variant, backend):
    ax1, ax2, ax3 = axes
    if cfg.n1 != cfg.n2:
        raise ValueError("Nyström needs a square (symmetric) stream")
    if mesh.shape[ax2] != 1 or mesh.shape[ax3] != 1:
        raise ValueError("streaming Nyström finalize needs a (P,1,1) grid; "
                         f"have {tuple(mesh.shape.values())}")
    Pn = mesh.shape[ax1]
    if variant == "auto":
        variant = ("redist" if Pn > max(1, cfg.n1 // max(cfg.r, 1))
                   else "no_redist")
    Y = jax.device_put(Y, NamedSharding(mesh, P(ax1, None)))
    if variant == "no_redist":
        C = nystrom_second_stage_no_redist(Y, cfg.seed, cfg.r, mesh,
                                           axis=ax1, kind=cfg.kind,
                                           salt=cfg.omega_salt,
                                           backend=backend)
        return Y, C
    if variant == "redist":
        return nystrom_second_stage_redist(Y, cfg.seed, cfg.r, mesh,
                                           axis=ax1, kind=cfg.kind,
                                           salt=cfg.omega_salt,
                                           backend=backend)
    if variant == "bound_driven":
        from repro.core.grid import select_two_grid_executable
        got = select_two_grid_executable(cfg.n1, cfg.r, Pn, p=(Pn, 1, 1))
        if got is None:
            raise ValueError(f"no q-grid factorization of P={Pn} divides "
                             f"(n={cfg.n1}, r={cfg.r})")
        _, q, _exact = got
        # prefer the single-jit fused second stage: the §5.2 Redistribute
        # of the accumulated Y and the q-grid stage-2 collectives compile
        # into one program (the (P,1,1) accumulator grid always admits a
        # shared mesh; the helper falls back to the cross-mesh path
        # otherwise)
        return nystrom_second_stage_two_grid_fused(
            Y, cfg.seed, cfg.r, q, p=(Pn, 1, 1),
            devices=list(mesh.devices.flat),
            kind=cfg.kind, salt=cfg.omega_salt, backend=backend)
    raise ValueError(variant)


def corange_update(W, H, cfg: StreamConfig, mesh: Mesh,
                   axes: Tuple[str, str, str] = DEFAULT_AXES, seed=None,
                   backend: str = "jnp", blocks=None):
    """W + Psi·H with H in the Alg.-1 input layout and W in the streaming
    co-range layout.  Psi columns are regenerated per p1 block — the only
    traffic is the psum of the data-derived partial products.  The pallas
    backend generates the Psi block in VMEM inside the fused kernel
    (kernels/local.py ``sketch_t_block`` under the Psi salt)."""
    from repro.kernels.local import resolve_backend, sketch_t_block
    backend = resolve_backend(backend)
    ax1, ax2, ax3 = axes
    br = cfg.n1 // mesh.shape[ax1]

    def body(w_blk, h_blk):              # (l, n2/(p2p3)), (n1/p1, n2/(p2p3))
        i = jax.lax.axis_index(ax1)
        if backend == "jnp":
            psi_c = psi_cols(cfg, i * br, br, seed=seed)   # (br, l)
            part = jnp.matmul(psi_c.T.astype(h_blk.dtype), h_blk,
                              precision=F32)
        else:
            part = sketch_t_block(
                h_blk, cfg.seed if seed is None else seed, cfg.sketch_l,
                row0=i * br, kind=cfg.kind, salt=cfg.psi_salt,
                backend=backend, blocks=blocks)
        return w_blk + jax.lax.psum(part, ax1)

    kw = {} if backend == "jnp" else {"check_vma": False}
    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(None, (ax2, ax3)), P(ax1, (ax2, ax3))),
                   out_specs=P(None, (ax2, ax3)), **kw)
    return fn(W, H)


# ---------------------------------------------------------------------------
# Compiled update programs — module-level lru caches so every accumulator
# (services, autotune trials, restored checkpoints) with the same
# (cfg, mesh, axes) shares one executable instead of re-tracing the
# shard_map graph per instance.  cfg is a frozen dataclass and Mesh is
# hashable, so the tuple is a valid cache key; cfg.seed is baked in
# statically, matching the original per-instance behavior.
# ---------------------------------------------------------------------------

_PROG_CACHE = 64


@functools.lru_cache(maxsize=_PROG_CACHE)
def _sharded_update_prog(cfg: StreamConfig, mesh: Mesh,
                         axes: Tuple[str, str, str], backend: str = "jnp",
                         blocks=None):
    """Full-shape additive update: Y += Alg.-1 sketch of H (+ W psum).

    jnp backend: the original program — sketch H with ``rand_matmul`` and
    add the result into the resident Y shard (dY makes an HBM round trip
    between the kernel and the add).  pallas backend: the accumulation is
    fused into the kernel accumulator via ``sketch_block(acc=y)`` — on
    regime-1 grids (p2 == 1, where the local partial IS the resident
    shard's delta) Y enters VMEM once and is written once, one HBM round
    trip instead of two; with p2 > 1 the reduce-scatter sits between the
    GEMM and the add, so only the Omega stream is elided.  Both backends
    are bitwise-identical where the local contraction is not tiled
    (kernels/local.py).
    """
    if backend == "jnp":
        def upd(Y, W, H):
            Y = Y + rand_matmul(H, cfg.seed, cfg.r, mesh, axes=axes,
                                kind=cfg.kind, salt=cfg.omega_salt,
                                backend="jnp")
            if W is not None:
                W = corange_update(W, H, cfg, mesh, axes, backend="jnp")
            return Y, W

        return jax.jit(upd)

    from repro.kernels.local import sketch_block
    ax1, ax2, ax3 = axes
    p2, p3 = mesh.shape[ax2], mesh.shape[ax3]
    blk_rows = cfg.n2 // p2
    blk_cols = cfg.r // p3

    def body(y_blk, a_blk):
        j = jax.lax.axis_index(ax2)
        k = jax.lax.axis_index(ax3)
        if p3 == 1:
            a_ij = a_blk
        else:
            a_ij = jax.lax.all_gather(a_blk, ax3, axis=1, tiled=True)
        if p2 == 1:
            # fused accumulate: Y += A_ij · Omega_jk in one kernel pass
            return sketch_block(a_ij, cfg.seed, blk_cols,
                                row0=j * blk_rows, col0=k * blk_cols,
                                kind=cfg.kind, salt=cfg.omega_salt,
                                acc=y_blk, backend=backend, blocks=blocks)
        b_partial = sketch_block(a_ij, cfg.seed, blk_cols,
                                 row0=j * blk_rows, col0=k * blk_cols,
                                 kind=cfg.kind, salt=cfg.omega_salt,
                                 backend=backend, blocks=blocks)
        return y_blk + jax.lax.psum_scatter(b_partial, ax2,
                                            scatter_dimension=0, tiled=True)

    fused = shard_map(body, mesh=mesh,
                      in_specs=(P((ax1, ax2), ax3), P(ax1, (ax2, ax3))),
                      out_specs=P((ax1, ax2), ax3), check_vma=False)

    def upd(Y, W, H):
        Y = fused(Y, H)
        if W is not None:
            W = corange_update(W, H, cfg, mesh, axes, backend=backend,
                               blocks=blocks)
        return Y, W

    return jax.jit(upd)


@functools.lru_cache(maxsize=_PROG_CACHE)
def _sharded_rowblock_prog(cfg: StreamConfig, mesh: Mesh,
                           axes: Tuple[str, str, str], k: int,
                           backend: str = "jnp", blocks=None):
    """Compiled ingest of a (k, n2) row slab at traced offset row0.

    Layout: the slab is column-sharded over (p2, p3) and replicated over
    p1 — in_specs P(None, (p2, p3)) — so the communication is one
    All-Gather of the slab over p3 plus one All-Reduce of the (k, r/p3) dY
    partial over p2 (both zero on regime-1 grids), and the co-range update
    is entirely local (W is replicated over p1 and every p1 rank computes
    the identical Psi-slab product).  Omega/Psi entries are regenerated
    from global coordinates, never communicated.

    Each Y shard adds the rows of dY that land in its resident block by
    slicing a zero-padded dY at a traced offset: out-of-overlap shards
    slice pure zeros, so row-disjoint slabs reproduce the full-shape
    additive path bitwise (0 + x == x).

    ``backend``: local GEMM body for the slab sketch and the Psi-slab
    product (kernels/local.py) — pallas keeps the Omega/Psi blocks out of
    HBM, and the traced-offset Y fold itself is fused too
    (``fold_rows_block``: the zero-padded dY frame is never built and
    the Y shard is aliased in-place, one HBM round trip instead of the
    jnp body's materialized-frame traffic).  Both backends add the same
    slab rows to the same Y rows, so the fold is bitwise-identical.
    """
    from repro.kernels.local import (fold_rows_block, sketch_block,
                                     sketch_t_block)
    ax1, ax2, ax3 = axes
    p1, p2, p3 = (mesh.shape[a] for a in axes)
    y_rows = cfg.n1 // (p1 * p2)        # Y shard height, P((p1,p2), p3)
    r_cols = cfg.r // p3
    om_rows = cfg.n2 // p2

    def body(y_blk, w_blk, h_blk, row0):
        i = jax.lax.axis_index(ax1)
        j = jax.lax.axis_index(ax2)
        if p3 == 1:
            h_cols = h_blk                       # (k, n2/p2)
        else:
            h_cols = jax.lax.all_gather(h_blk, ax3, axis=1, tiled=True)
        kk = jax.lax.axis_index(ax3)
        if backend == "jnp":
            om = omega_tile(cfg.seed, j * om_rows, kk * r_cols,
                            om_rows, r_cols, cfg.kind, h_cols.dtype,
                            salt=cfg.omega_salt)
            part = jnp.matmul(h_cols, om,        # (k, r/p3) partial
                              precision=F32)
        else:
            part = sketch_block(h_cols, cfg.seed, r_cols,
                                row0=j * om_rows, col0=kk * r_cols,
                                kind=cfg.kind, salt=cfg.omega_salt,
                                backend=backend, blocks=blocks)
        dY = jax.lax.psum(part, ax2) if p2 > 1 else part
        # fold the overlap [g0, g0 + y_rows) n [row0, row0 + k) into the
        # resident shard: slice a zero-padded dY so that shards outside
        # the slab add exact zeros.  clip explicitly: lax.dynamic_slice
        # WRAPS negative starts (Python-style) instead of clamping, which
        # would alias the zero pad onto real dY rows for shards left of
        # the slab.  The fold itself is backend-dispatched
        # (kernels/local.py fold_rows_block): the pallas body DMAs each
        # Y block's slab window and aliases the Y shard in-place.
        g0 = (i * p2 + j) * y_rows
        start = jnp.clip(g0 - row0 + y_rows, 0, k + y_rows)
        y_new = fold_rows_block(y_blk, dY, start, backend=backend)
        if w_blk is None:
            return y_new
        if backend == "jnp":
            psi_c = psi_cols(cfg, row0, k)       # (k, l), traced row0
            w_new = w_blk + jnp.matmul(psi_c.T.astype(h_blk.dtype), h_blk,
                                       precision=F32)
        else:
            # fused accumulate: W += Psi[:, row0:row0+k] · H in one pass
            w_new = sketch_t_block(h_blk, cfg.seed, cfg.sketch_l,
                                   row0=row0, kind=cfg.kind,
                                   salt=cfg.psi_salt, acc=w_blk,
                                   backend=backend, blocks=blocks)
        return y_new, w_new

    in_h = P(None, (ax2, ax3))
    kw = {} if backend == "jnp" else {"check_vma": False}
    if cfg.corange:
        fn = shard_map(body, mesh=mesh,
                       in_specs=(P((ax1, ax2), ax3), in_h, in_h, P()),
                       out_specs=(P((ax1, ax2), ax3), in_h), **kw)

        def upd(Y, W, H, row0):
            return fn(Y, W, H, row0)
    else:
        fn = shard_map(lambda y, h, row0: body(y, None, h, row0),
                       mesh=mesh,
                       in_specs=(P((ax1, ax2), ax3), in_h, P()),
                       out_specs=P((ax1, ax2), ax3), **kw)

        def upd(Y, W, H, row0):
            return fn(Y, H, row0), W

    return jax.jit(upd)


class ShardedStreamingSketch:
    """Streaming (Y, W) accumulator over a (p1, p2, p3) processor grid.

    Updates arrive either as full-shape additive deltas H (zero
    rows/columns where nothing changed) via :meth:`update`, or as row
    slabs via :meth:`update_rows` — the classic streaming model, without
    materializing the n1 x n2 zero frame.  Both are sketched with the
    communication-optimal collectives and added into the resident sketch
    state; row-disjoint ingest reproduces the one-shot distributed sketch
    bitwise (untouched rows accumulate exact zeros).

    ``mesh`` may also be a :class:`repro.plan.Plan` (from ``plan_stream`` /
    ``plan_sketch``); its chosen grid places the state (and its backend
    decision wins over the ``backend`` arg).

    ``backend`` selects the local GEMM body of every update
    (``"jnp"`` | ``"pallas"`` | ``"auto"`` — kernels/local.py): the pallas
    backend generates Omega/Psi blocks in VMEM and fuses the Y
    accumulation into the kernel accumulator.
    """

    def __init__(self, cfg: StreamConfig, mesh,
                 axes: Tuple[str, str, str] = DEFAULT_AXES,
                 backend: str = "auto", blocks=None):
        from repro.kernels.local import resolve_backend
        cfg.validate()
        from repro.core.sketch import SPARSE_KINDS
        if cfg.kind in SPARSE_KINDS:
            raise NotImplementedError(
                f"kind {cfg.kind!r}: distributed sparse shard_map bodies "
                "are deferred (ROADMAP item 3) — stream sparse kinds "
                "through the local StreamingSketch / SketchService")
        if not isinstance(mesh, Mesh):      # a repro.plan.Plan
            from repro.core.sketch import make_grid_mesh
            if getattr(mesh, "grid", None) is None:
                raise ValueError(f"plan {getattr(mesh, 'variant', mesh)!r} "
                                 f"carries no processor grid")
            backend = getattr(mesh, "backend", backend) or backend
            if getattr(mesh, "blocks", None):
                blocks = tuple(mesh.blocks[k] for k in ("bm", "bn", "bk"))
            mesh = make_grid_mesh(*mesh.grid)
        ax1, ax2, ax3 = axes
        p1, p2, p3 = (mesh.shape[a] for a in axes)
        if (cfg.n1 % (p1 * p2) or cfg.n2 % (p2 * p3) or cfg.n2 % p2
                or cfg.r % p3):        # n1 % (p1*p2): Y is P((p1, p2), p3)
            raise ValueError(f"stream shape ({cfg.n1},{cfg.n2},r={cfg.r}) "
                             f"not divisible by grid ({p1},{p2},{p3})")
        self.cfg = cfg
        self.mesh = mesh
        self.axes = axes
        self.backend = resolve_backend(backend)
        self.blocks = None if blocks is None else tuple(blocks)
        self.Y = jax.device_put(jnp.zeros((cfg.n1, cfg.r), cfg.dtype),
                                output_sharding(mesh, axes))
        self.W = (jax.device_put(
                      jnp.zeros((cfg.sketch_l, cfg.n2), cfg.dtype),
                      corange_sharding(mesh, axes))
                  if cfg.corange else None)
        self.num_updates = 0
        # module-level lru cache: every accumulator (and every autotune
        # trial) with the same (cfg, mesh, axes, backend) shares one
        # executable
        self._upd = _sharded_update_prog(cfg, mesh, tuple(axes),
                                         self.backend, self.blocks)
        self._audits = {}   # slab rows k (or None) -> (pred words, floor)

    def _audit(self, k: Optional[int]) -> Tuple[float, float]:
        """Ledger reference numbers, memoized per slab height: planner-
        predicted words and the Theorem-2 floor of the sketch product.

        ``k=None`` prices the full-shape :meth:`update` program — Alg. 1 on
        this grid plus (when the co-range sketch is on) the psum over p1 of
        the Psi partial (corange_update).  Integer ``k`` prices the
        ``update_rows`` slab program via ``stream_update_cost``, whose W
        update is fully local.
        """
        hit = self._audits.get(k)
        if hit is None:
            from repro.core.lower_bounds import matmul_lower_bound
            from repro.plan import model as M
            cfg = self.cfg
            grid = tuple(int(self.mesh.shape[a]) for a in self.axes)
            if k is None:
                pred = M.alg1_cost(cfg.n1, cfg.n2, cfg.r, grid,
                                   backend=self.backend).words
                if cfg.corange:
                    p1, p2, p3 = grid
                    pred += (2.0 * (1.0 - 1.0 / p1)
                             * cfg.sketch_l * cfg.n2 / (p2 * p3))
                rows = cfg.n1
            else:
                pred = M.stream_update_cost(k, cfg.n2, cfg.r, cfg.sketch_l,
                                            grid=grid, corange=cfg.corange,
                                            backend=self.backend).words
                rows = k
            try:
                floor = matmul_lower_bound(rows, cfg.n2, cfg.r,
                                           self.mesh.devices.size)
            except ValueError:          # paper assumes r < n2
                floor = 0.0
            hit = self._audits[k] = (float(pred), float(floor))
        return hit

    def update(self, H):
        """A <- A + H; H must be the full (n1, n2) shape (sharded or host)."""
        if H.shape != (self.cfg.n1, self.cfg.n2):
            raise ValueError(f"update shape {H.shape} != "
                             f"({self.cfg.n1}, {self.cfg.n2})")
        H = jax.device_put(jnp.asarray(H, self.cfg.dtype),
                           input_sharding(self.mesh, self.axes))
        led = obs_ledger.get_ledger()
        if led is not None:
            pred, floor = self._audit(None)
            led.observe("stream.update", self._upd, (self.Y, self.W, H),
                        predicted_words=pred, lower_bound_words=floor,
                        itemsize=jnp.dtype(self.cfg.dtype).itemsize)
        with obs_trace.span("stream.update", cat="stream"):
            self.Y, self.W = self._upd(self.Y, self.W, H)
        self.num_updates += 1
        return self

    # -- row-slab ingest ---------------------------------------------------

    def update_rows(self, row0: int, H):
        """Rows [row0, row0 + k) arrive additively as a (k, n2) slab.

        Bitwise-equivalent to :meth:`update` with the slab embedded in a
        zero (n1, n2) frame, without materializing that frame.  (For W the
        equivalence is bitwise when the slab lies within one p1 row block —
        otherwise the full-shape path splits the Psi product across the p1
        psum and agreement is to FP summation order.)
        """
        validate_row_block(self.cfg, row0, H.shape)
        k = H.shape[0]
        H = jax.device_put(
            jnp.asarray(H, self.cfg.dtype),
            NamedSharding(self.mesh, P(None, (self.axes[1], self.axes[2]))))
        fn = _sharded_rowblock_prog(self.cfg, self.mesh, tuple(self.axes), k,
                                    self.backend, self.blocks)
        r0 = jnp.int32(row0)
        led = obs_ledger.get_ledger()
        if led is not None:
            pred, floor = self._audit(k)
            led.observe("stream.update_rows", fn, (self.Y, self.W, H, r0),
                        predicted_words=pred, lower_bound_words=floor,
                        itemsize=jnp.dtype(self.cfg.dtype).itemsize)
        with obs_trace.span("stream.update_rows", cat="stream", k=k):
            self.Y, self.W = fn(self.Y, self.W, H, r0)
        self.num_updates += 1
        return self

    # -- checkpointing -----------------------------------------------------

    def save(self, directory: str, step: Optional[int] = None,
             keep: int = 3) -> str:
        """Checkpoint (Y, W, config, num_updates) via ``checkpoint.ckpt``.

        Arrays are stored logically (host-gathered), so a restore may use a
        different mesh or device count.  Returns the checkpoint path.
        """
        from repro.checkpoint import ckpt
        step = self.num_updates if step is None else step
        tree = {"Y": self.Y}
        if self.W is not None:
            tree["W"] = self.W
        extra = {"config": self.cfg.to_json_dict(),
                 "num_updates": self.num_updates,
                 "backend": self.backend,
                 "layout": "sharded"}
        return ckpt.save(directory, step, tree, extra=extra, keep=keep)

    @classmethod
    def restore(cls, directory: str, mesh, step: Optional[int] = None,
                axes: Tuple[str, str, str] = DEFAULT_AXES,
                backend: Optional[str] = None) -> "ShardedStreamingSketch":
        """Rebuild a stream from a checkpoint onto ``mesh`` (any grid whose
        divisibility admits the stream shape — elastic restore).  The saved
        backend is restored by default; pass ``backend=`` to migrate."""
        from repro.checkpoint import ckpt
        extra, step = ckpt.load_extra(directory, step)
        cfg = StreamConfig.from_json_dict(extra["config"])
        st = cls(cfg, mesh, axes=axes,
                 backend=backend or extra.get("backend", "jnp"))
        tree = {"Y": st.Y}
        if st.W is not None:
            tree["W"] = st.W
        tree, _, extra = ckpt.restore(directory, tree, step,
                                      shardings=stream_shardings(
                                          cfg, st.mesh, axes))
        st.Y = tree["Y"]
        st.W = tree.get("W")
        st.num_updates = int(extra["num_updates"])
        return st

    # -- finalization ------------------------------------------------------

    @property
    def sketch(self):
        """Y = A·Omega in the Alg.-1 output layout P((p1, p2), p3)."""
        return self.Y

    @property
    def corange_sketch(self):
        return self.W

    def nystrom(self, variant: str = "auto"):
        """(B, C) of a symmetric stream — see :func:`nystrom_finalize`."""
        return nystrom_finalize(self.Y, self.cfg, self.mesh, self.axes,
                                variant, backend=self.backend)

    def reconstruct(self, rank: Optional[int] = None, rcond=None):
        """One-pass low-rank reconstruction (gathers the small factors)."""
        from .reconstruct import one_pass_reconstruct
        if self.W is None:
            raise ValueError("reconstruction needs corange=True")
        return one_pass_reconstruct(self.Y, self.W, self.cfg, rank=rank,
                                    rcond=rcond)
