"""Batched sketch service: many concurrent streams, one mesh (ROADMAP's
"heavy traffic" serving story applied to sketching).

Each client stream owns only its (Y, W) accumulator plus a Philox key pair.
All streams with the same shape signature — (n1, n2, r, l, kind, corange,
dtype, update-chunk shape) — share ONE compiled update executable: the
per-stream seed enters the computation *traced* (as a uint32 key pair, see
``core.rng.seed_keys``), and for local row-block ingest the row offset is
traced too.  Opening stream number 1000 therefore costs a dict insert, not
an XLA compile, which is what makes high stream fan-in viable.

Multi-tenant ingest (local mode):

  * ``update_batch``  — same-shape lanes, one vmapped dispatch.
  * ``update_ragged`` — heterogeneous row slabs.  Lanes are snapped to
    shape buckets (pow2 by default, or planner-chosen ``bucket_edges``
    from ``repro.plan.choose_bucket_edges``), padded-and-masked to the
    bucket height, and fused through one vmapped masked ``fold_rows_block``
    update per bucket with DONATED stacked (Y, W) accumulators — batched
    ingest never holds two copies of the fleet's sketch state.  Lane i is
    bitwise the result of updating stream i alone, including the
    padded/masked tail (the fixed oracle; pinned by
    tests/test_service_scale.py).

Admission/eviction: streams carry a QoS class (``pinned`` > ``standard`` >
``best_effort``).  With ``max_resident`` set, opening or touching a stream
beyond the budget evicts the coldest non-pinned resident — its (Y, W) is
checkpointed to host memory (or to disk under ``spill_dir`` via
``checkpoint/``) and restored transparently on next touch, bitwise.

Two placement modes:

  * ``mesh=None`` — local mode.  Streams live on the default device; updates
    are row-block or full-shape additive.  Row-partitioned ingest is
    bitwise-equal to the one-shot ``sketch_reference``.
  * ``mesh=Mesh(p1, p2, p3)`` — distributed mode.  Every stream's state is
    sharded per the Alg.-1 layout and each update runs the
    communication-optimal ``rand_matmul`` (plus the co-range psum); see
    ``distributed.py`` for the exact cost.

The service is the entry point wired into ``serve/engine.py``
(``make_sketch_service``); ``stream/ingest.py`` adds the async double-
buffered request queue on top.
"""
from __future__ import annotations

import dataclasses
import itertools
import os
import shutil
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.sketch import (
    DEFAULT_AXES,
    SPARSE_KINDS,
    input_sharding,
    rand_matmul,
)
from repro.core.rng import seed_keys
from repro.obs import ledger as obs_ledger
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

from .distributed import corange_update, stream_shardings
from .state import (SparseRows, StreamConfig, _local_sig,
                    local_rowblock_batch_prog, local_rowblock_prog,
                    local_rowblock_ragged_prog, local_sparse_batch_prog,
                    local_sparse_prog, nystrom_local, pow2_bucket,
                    snap_bucket, validate_row_block)

#: QoS classes, strongest first.  ``pinned`` streams are never auto-evicted;
#: among evictable residents the lowest class goes first, LRU within class.
QOS_CLASSES = ("pinned", "standard", "best_effort")
_EVICT_RANK = {"best_effort": 0, "standard": 1}


@dataclasses.dataclass
class _Stream:
    cfg: StreamConfig
    keys: jax.Array          # (2,) uint32 Philox key pair, traced into updates
    Y: jax.Array
    W: Optional[jax.Array]
    num_updates: int = 0
    qos: str = "standard"
    last_touch: int = 0
    # when set to (group_key, lane), the live (Y, W) rows reside inside the
    # service's stacked cohort buffer (``_stacks[group_key]``) and Y/W above
    # are None — see update_ragged's steady-state fast path
    stack_ref: Optional[Tuple] = None


@dataclasses.dataclass
class _Evicted:
    """A stream whose accumulators left the device: host-memory copies by
    default, or a ``checkpoint/`` directory when the service spills to
    disk.  Everything needed to rebuild the resident ``_Stream`` bitwise."""
    cfg: StreamConfig
    keys: np.ndarray
    qos: str
    num_updates: int
    host: Optional[Dict[str, np.ndarray]] = None
    path: Optional[str] = None


def _stream_sig(cfg: StreamConfig) -> Tuple:
    """Everything that forces a distinct executable — note: NOT the seed."""
    return (cfg.n1, cfg.n2, cfg.r, cfg.sketch_l, cfg.kind, cfg.corange,
            jnp.dtype(cfg.dtype).name, cfg.omega_salt, cfg.psi_salt)


class SketchService:
    """One mesh, many concurrent sketch streams.

    >>> svc = SketchService(max_resident=1000)
    >>> sid = svc.open(StreamConfig(n1=256, n2=512, r=32, seed=7),
    ...                qos="standard")
    >>> svc.update(sid, H, row0=0)          # rows arrive
    >>> svc.update_ragged([(sid, H2, 64)])  # or fused with other tenants
    >>> svc.sketch(sid)                     # the live Y = A·Omega
    >>> svc.reconstruct(sid, rank=16)       # one-pass low-rank estimate
    """

    def __init__(self, mesh: Optional[Mesh] = None,
                 axes: Tuple[str, str, str] = DEFAULT_AXES,
                 backend: str = "auto",
                 max_resident: Optional[int] = None,
                 spill_dir: Optional[str] = None):
        from repro.kernels.local import resolve_backend
        self.mesh = mesh
        self.axes = axes
        # the distributed updates' local GEMM body (kernels/local.py) and
        # the ragged fold body; single-stream local row-block ingest keeps
        # its own bitwise xla path
        self.backend = resolve_backend(backend)
        if max_resident is not None and max_resident < 1:
            raise ValueError("max_resident must be >= 1")
        self.max_resident = max_resident
        self.spill_dir = spill_dir
        self._streams: Dict[int, _Stream] = {}
        self._evicted: Dict[int, _Evicted] = {}
        # stacked ragged cohorts: group_key -> (Yb, Wb) so steady-state
        # ragged ingest feeds each round's donated output straight into the
        # next round with zero per-lane slicing (see update_ragged)
        self._stacks: Dict[Tuple, Tuple] = {}
        self._stack_keys: Dict[Tuple, jax.Array] = {}
        self._fns: Dict[Tuple, any] = {}
        self._sid = itertools.count()
        self._clock = itertools.count(1)    # LRU clock for eviction
        self._updates_total = 0             # service-lifetime, survives close
        self._audit: Dict[Tuple, Tuple[float, float]] = {}
        m = obs_metrics.get_metrics()
        self._m_updates = m.counter(
            "sketch_updates_total", "stream updates applied, by ingest path")
        self._m_evictions = m.counter(
            "sketch_evictions_total", "streams checkpointed off-device")
        self._m_spills = m.counter(
            "sketch_spills_total", "evictions written to disk (spill_dir)")
        self._m_restores = m.counter(
            "sketch_restores_total", "evicted streams restored from their "
            "checkpoint")
        self._m_resident = m.gauge(
            "sketch_resident_streams", "streams currently resident on device")
        self._m_real_rows = m.counter(
            "sketch_ragged_real_rows_total",
            "real rows folded by update_ragged")
        self._m_padded_rows = m.counter(
            "sketch_ragged_padded_rows_total",
            "pad rows dispatched by update_ragged (bucket + lane-snap waste)")

    # -- lifecycle ---------------------------------------------------------

    def open(self, cfg: StreamConfig, qos: str = "standard") -> int:
        if qos not in QOS_CLASSES:
            raise ValueError(f"qos {qos!r} not in {QOS_CLASSES}")
        cfg.validate()
        if self.mesh is not None:
            if cfg.kind in SPARSE_KINDS:
                raise NotImplementedError(
                    f"kind {cfg.kind!r}: distributed sparse shard_map "
                    "bodies are deferred (ROADMAP item 3) — open sparse-"
                    "kind streams on a local (mesh=None) service")
            p1, p2, p3 = (self.mesh.shape[a] for a in self.axes)
            if (cfg.n1 % (p1 * p2) or cfg.n2 % (p2 * p3) or cfg.n2 % p2
                    or cfg.r % p3):    # n1 % (p1*p2): Y is P((p1, p2), p3)
                raise ValueError(f"stream {cfg} not divisible by grid "
                                 f"({p1},{p2},{p3})")
        self._admit(need=1)
        if self.mesh is not None:
            sh = stream_shardings(cfg, self.mesh, self.axes)
            Y = jax.device_put(jnp.zeros((cfg.n1, cfg.r), cfg.dtype),
                               sh["Y"])
            W = (jax.device_put(jnp.zeros((cfg.sketch_l, cfg.n2), cfg.dtype),
                                sh["W"])
                 if cfg.corange else None)
        else:
            Y = jnp.zeros((cfg.n1, cfg.r), cfg.dtype)
            W = (jnp.zeros((cfg.sketch_l, cfg.n2), cfg.dtype)
                 if cfg.corange else None)
        k0, k1 = seed_keys(cfg.seed)
        sid = next(self._sid)
        self._streams[sid] = _Stream(cfg, jnp.stack([k0, k1]), Y, W,
                                     qos=qos, last_touch=next(self._clock))
        self._m_resident.set(len(self._streams))
        return sid

    def close(self, sid: int):
        """Finalize: returns the stream's final (Y, W) state — W is None
        for corange=False streams — and frees the slot (an evicted stream
        is restored from its checkpoint first, so the returned state is
        always live arrays)."""
        ev = self._evicted.pop(sid, None)
        if ev is not None:
            st = self._restore(ev)
            return st.Y, st.W
        st = self._streams.get(sid)
        if st is None:
            raise ValueError(f"unknown stream id {sid} (never opened, or "
                             f"already closed)")
        self._materialize(st)
        del self._streams[sid]
        self._m_resident.set(len(self._streams))
        return st.Y, st.W

    # -- admission / eviction ----------------------------------------------

    def _touch(self, sid: int, protect=frozenset()) -> _Stream:
        """Resolve ``sid`` to its resident stream, transparently restoring
        it from its eviction checkpoint if needed, and bump its LRU clock.
        Raises a clear ValueError for unknown (never-opened/closed) sids."""
        st = self._streams.get(sid)
        if st is None:
            ev = self._evicted.pop(sid, None)
            if ev is None:
                raise ValueError(f"unknown stream id {sid} (never opened, "
                                 f"or already closed)")
            try:
                self._admit(need=1, protect=protect)
            except RuntimeError:
                self._evicted[sid] = ev     # leave the stream restorable
                raise
            self._streams[sid] = self._restore(ev)
            st = self._streams[sid]
            self._m_resident.set(len(self._streams))
        st.last_touch = next(self._clock)
        return st

    def _admit(self, need: int, protect=frozenset()) -> None:
        """Evict coldest non-pinned residents (LRU within QoS class, lowest
        class first) until ``need`` more streams fit under ``max_resident``.
        Raises RuntimeError when the budget cannot be met (everything
        resident is pinned or belongs to the in-flight batch)."""
        if self.max_resident is None:
            return
        while len(self._streams) + need > self.max_resident:
            victims = [(sid, st) for sid, st in self._streams.items()
                       if st.qos != "pinned" and sid not in protect]
            if not victims:
                raise RuntimeError(
                    f"admission refused: all {len(self._streams)} resident "
                    f"streams are pinned or in-flight and max_resident="
                    f"{self.max_resident}")
            sid, _ = min(victims, key=lambda kv: (_EVICT_RANK[kv[1].qos],
                                                  kv[1].last_touch))
            self.evict(sid)

    def evict(self, sid: int) -> None:
        """Checkpoint a resident stream's (Y, W) off-device — to host
        memory, or to disk when the service has a ``spill_dir`` — and free
        its device slot.  Next touch restores it bitwise."""
        st = self._streams.get(sid)
        if st is None:
            if sid in self._evicted:
                return                      # idempotent
            raise ValueError(f"unknown stream id {sid} (never opened, or "
                             f"already closed)")
        with obs_trace.span("service.evict", cat="service", sid=sid,
                            spill=self.spill_dir is not None):
            self._materialize(st)
            del self._streams[sid]
            tree = {"Y": st.Y}
            if st.W is not None:
                tree["W"] = st.W
            host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)
            ev = _Evicted(cfg=st.cfg,
                          keys=np.asarray(jax.device_get(st.keys)),
                          qos=st.qos, num_updates=st.num_updates)
            if self.spill_dir is not None:
                from repro.checkpoint import ckpt
                path = os.path.join(self.spill_dir, f"stream_{sid:08d}")
                ckpt.save(path, step=st.num_updates, tree=host,
                          extra={"config": st.cfg.to_json_dict(),
                                 "qos": st.qos,
                                 "num_updates": st.num_updates}, keep=1)
                ev.path = path
                self._m_spills.inc()
            else:
                ev.host = host
            self._evicted[sid] = ev
        self._m_evictions.inc()
        self._m_resident.set(len(self._streams))

    def _restore(self, ev: _Evicted) -> _Stream:
        self._m_restores.inc()
        if ev.path is not None:
            from repro.checkpoint import ckpt
            cfg = ev.cfg
            like = {"Y": jnp.zeros((cfg.n1, cfg.r), cfg.dtype)}
            if cfg.corange:
                like["W"] = jnp.zeros((cfg.sketch_l, cfg.n2), cfg.dtype)
            sh = (stream_shardings(cfg, self.mesh, self.axes)
                  if self.mesh is not None else None)
            tree, _, _ = ckpt.restore(ev.path, like, shardings=sh)
            shutil.rmtree(ev.path, ignore_errors=True)
        elif self.mesh is not None:
            sh = stream_shardings(ev.cfg, self.mesh, self.axes)
            tree = {k: jax.device_put(v, sh[k]) for k, v in ev.host.items()}
        else:
            tree = {k: jnp.asarray(v) for k, v in ev.host.items()}
        return _Stream(ev.cfg, jnp.asarray(ev.keys), tree["Y"],
                       tree.get("W"), num_updates=ev.num_updates, qos=ev.qos)

    # -- stacked-cohort bookkeeping ----------------------------------------

    def _drop_stack(self, gkey: Tuple) -> None:
        """Unstack a cohort: hand each lane its (Y, W) rows back.  Called
        the moment any member is touched by a non-ragged path — a lane
        mutated outside the stack would make the cohort rows stale."""
        entry = self._stacks.pop(gkey, None)
        self._stack_keys.pop(gkey, None)
        if entry is None:
            return
        Yb, Wb = entry
        for i, sid in enumerate(gkey[2]):
            st = self._streams.get(sid)
            if st is None or st.stack_ref != (gkey, i):
                continue
            st.Y = Yb[i]
            st.W = None if Wb is None else Wb[i]
            st.stack_ref = None

    def _materialize(self, st: _Stream) -> None:
        if st.stack_ref is not None:
            self._drop_stack(st.stack_ref[0])

    def _lane_Y(self, st: _Stream):
        if st.stack_ref is None:
            return st.Y
        gkey, i = st.stack_ref
        return self._stacks[gkey][0][i]

    def _lane_W(self, st: _Stream):
        if st.stack_ref is None:
            return st.W
        gkey, i = st.stack_ref
        Wb = self._stacks[gkey][1]
        return None if Wb is None else Wb[i]

    # -- compiled-update cache ---------------------------------------------

    def _get_update_fn(self, cfg: StreamConfig, chunk_rows: int):
        key = (_stream_sig(cfg), chunk_rows,
               None if self.mesh is None else self.mesh)
        fn = self._fns.get(key)
        if fn is None:
            # local mode resolves through the module-level program cache,
            # so the executable is shared with StreamingSketch instances
            # and other services too; self._fns just tracks what this
            # service references (num_compiled).
            fn = (self._build_dist_update(cfg)
                  if self.mesh is not None
                  else local_rowblock_prog(_local_sig(cfg), chunk_rows))
            self._fns[key] = fn
        return fn

    def _build_dist_update(self, cfg: StreamConfig):
        mesh, axes, backend = self.mesh, self.axes, self.backend

        def upd(Y, W, H, keys, row0):
            del row0                      # distributed mode is additive-only
            Y = Y + rand_matmul(H, keys, cfg.r, mesh, axes=axes,
                                kind=cfg.kind, salt=cfg.omega_salt,
                                backend=backend)
            if W is not None:
                W = corange_update(W, H, cfg, mesh, axes, seed=keys,
                                   backend=backend)
            return Y, W

        return jax.jit(upd)

    def _dist_audit(self, cfg: StreamConfig) -> Tuple[float, float]:
        """(planner-predicted words, Theorem-2 floor) of ONE full-shape
        distributed update on this mesh — the ledger's reference numbers
        for ``service.update[dist]``.  Memoized per stream signature."""
        key = _stream_sig(cfg)
        hit = self._audit.get(key)
        if hit is None:
            from repro.core.lower_bounds import matmul_lower_bound
            from repro.plan import model as M
            grid = tuple(int(self.mesh.shape[a]) for a in self.axes)
            # the dist program is Alg. 1 plus (corange on) the psum over p1
            # of the Psi partial — same closed form as
            # ShardedStreamingSketch._audit(None)
            pred = M.alg1_cost(cfg.n1, cfg.n2, cfg.r, grid,
                               backend=self.backend).words
            if cfg.corange:
                p1, p2, p3 = grid
                pred += (2.0 * (1.0 - 1.0 / p1)
                         * cfg.sketch_l * cfg.n2 / (p2 * p3))
            try:
                floor = matmul_lower_bound(cfg.n1, cfg.n2, cfg.r,
                                           int(np.prod(grid)))
            except ValueError:          # paper assumes r < n2
                floor = 0.0
            hit = self._audit[key] = (float(pred), float(floor))
        return hit

    # -- ingest ------------------------------------------------------------

    def update(self, sid: int, H, row0: Optional[int] = None):
        """Apply one update to stream ``sid``.

        Local mode: ``row0`` selects a row-block update (H is (k, n2));
        ``row0=None`` means a full-shape additive delta.  Distributed mode
        accepts full-shape additive deltas only.
        """
        st = self._touch(sid)
        self._materialize(st)
        cfg = st.cfg
        H = jnp.asarray(H, cfg.dtype)
        if self.mesh is not None:
            if row0 is not None:
                raise ValueError("distributed streams take full-shape "
                                 "additive updates (row0 must be None)")
            if H.shape != (cfg.n1, cfg.n2):
                raise ValueError(f"{H.shape} != ({cfg.n1}, {cfg.n2})")
            H = jax.device_put(H, input_sharding(self.mesh, self.axes))
            fn = self._get_update_fn(cfg, -1)
            led = obs_ledger.get_ledger()
            if led is not None:
                pred, floor = self._dist_audit(cfg)
                led.observe("service.update[dist]", fn,
                            (st.Y, st.W, H, st.keys, 0),
                            predicted_words=pred, lower_bound_words=floor,
                            itemsize=jnp.dtype(cfg.dtype).itemsize)
            with obs_trace.span("service.update", cat="service", mode="dist"):
                st.Y, st.W = fn(st.Y, st.W, H, st.keys, 0)
            self._m_updates.inc(path="dist")
        else:
            if row0 is None:
                if H.shape != (cfg.n1, cfg.n2):
                    raise ValueError(f"{H.shape} != ({cfg.n1}, {cfg.n2})")
                row0 = 0
            validate_row_block(cfg, row0, H.shape)
            fn = self._get_update_fn(cfg, H.shape[0])
            r0 = jnp.int32(row0)
            led = obs_ledger.get_ledger()
            if led is not None:
                # local mode: predicted AND floor are 0 words (P = 1) —
                # the ledger asserts the compiled program moves nothing
                led.observe("service.update[local]", fn,
                            (st.Y, st.W, H, st.keys, r0),
                            itemsize=jnp.dtype(cfg.dtype).itemsize)
            with obs_trace.span("service.update", cat="service",
                                mode="local"):
                st.Y, st.W = fn(st.Y, st.W, H, st.keys, r0)
            self._m_updates.inc(path="single")
        st.num_updates += 1
        self._updates_total += 1
        return self

    def update_sparse(self, sid: int, sp: SparseRows, row0: int = 0):
        """Apply one COO row-slab update to stream ``sid`` (local mode).

        The payload on the wire is (indices + values) — ``2·nnz`` words,
        priced at the ``service.update[sparse]`` ledger site by
        ``plan.model.sparse_payload_words`` — instead of the dense slab's
        ``k·n2``; the fold is the O(nnz) scatter program of
        ``stream/state.py:_local_sparse_update`` (bitwise vs the dense
        path for sparse Omega kinds).  Distributed streams densify and go
        through :meth:`update` until the sparse shard_map bodies land
        (ROADMAP item 3).
        """
        if self.mesh is not None:
            raise NotImplementedError(
                "update_sparse is local-mode only: distributed sparse "
                "shard_map bodies are deferred (ROADMAP item 3) — densify "
                "and use update(), or open the stream on a local service")
        st = self._touch(sid)
        self._materialize(st)
        cfg = st.cfg
        sp.validate(cfg, row0)
        nnz_b = pow2_bucket(max(1, sp.nnz))
        row, col, val = sp.padded(nnz_b)
        fn = self._get_sparse_fn(cfg, sp.shape[0], nnz_b)
        args = (st.Y, st.W, jnp.asarray(row), jnp.asarray(col),
                jnp.asarray(val, cfg.dtype), st.keys, jnp.int32(row0))
        led = obs_ledger.get_ledger()
        if led is not None:
            from repro.plan.model import sparse_payload_words
            led.record("service.update[sparse]",
                       predicted_words=sparse_payload_words(sp.nnz),
                       lower_bound_words=float(sp.nnz),
                       itemsize=jnp.dtype(cfg.dtype).itemsize,
                       detail=("nnz", sp.nnz))
        with obs_trace.span("service.update", cat="service", mode="sparse"):
            st.Y, st.W = fn(*args)
        self._m_updates.inc(path="sparse")
        st.num_updates += 1
        self._updates_total += 1
        return self

    def update_sparse_batch(self, sids, sps, row0=0):
        """Fused multi-stream sparse ingest: one compiled call folds one
        COO slab into every stream in ``sids``.

        All lanes share one slab height; payloads are pow2-padded to the
        tallest lane's nnz bucket (pads are routed into sacrificial
        rows/columns — bitwise-invisible), so lane i's result is bitwise
        :meth:`update_sparse` applied to stream i alone.  Local mode only.
        """
        if self.mesh is not None:
            raise NotImplementedError(
                "update_sparse_batch is local-mode only (ROADMAP item 3)")
        sids = list(sids)
        if len(set(sids)) != len(sids):
            raise ValueError("update_sparse_batch sids must be distinct")
        protect = frozenset(sids)
        sts = [self._touch(s, protect) for s in sids]
        for st in sts:
            self._materialize(st)
        if not sts:
            raise ValueError("update_sparse_batch needs at least one stream")
        sps = list(sps)
        if len(sps) != len(sts):
            raise ValueError(f"need {len(sts)} payloads, got {len(sps)}")
        cfg0 = sts[0].cfg
        sig = _local_sig(cfg0)
        for st in sts[1:]:
            if _local_sig(st.cfg) != sig:
                raise ValueError(
                    f"streams must share one shape signature; "
                    f"{_local_sig(st.cfg)} != {sig}")
        n = len(sts)
        row0s = ([int(row0)] * n if jnp.ndim(row0) == 0 else
                 [int(x) for x in row0])
        if len(row0s) != n:
            raise ValueError(f"row0 needs {n} entries, got {len(row0s)}")
        k = sps[0].shape[0]
        for sp, r0 in zip(sps, row0s):
            if sp.shape[0] != k:
                raise ValueError(f"lanes must share one slab height; "
                                 f"{sp.shape[0]} != {k}")
            sp.validate(cfg0, r0)
        nnz_b = pow2_bucket(max(1, max(sp.nnz for sp in sps)))
        padded = [sp.padded(nnz_b) for sp in sps]
        rows = jnp.stack([jnp.asarray(p[0]) for p in padded])
        cols = jnp.stack([jnp.asarray(p[1]) for p in padded])
        vals = jnp.stack([jnp.asarray(p[2], cfg0.dtype) for p in padded])
        key = (sig, k, nnz_b, n, "sparse_batch")
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = local_sparse_batch_prog(sig, k, nnz_b, n)
        Yb = jnp.stack([st.Y for st in sts])
        Wb = (jnp.stack([st.W for st in sts]) if cfg0.corange else None)
        keys = jnp.stack([st.keys for st in sts])
        r0s = jnp.asarray(row0s, jnp.int32)
        led = obs_ledger.get_ledger()
        if led is not None:
            from repro.plan.model import sparse_payload_words
            tot = sum(sp.nnz for sp in sps)
            led.record("service.update[sparse]",
                       predicted_words=sparse_payload_words(tot),
                       lower_bound_words=float(tot),
                       itemsize=jnp.dtype(cfg0.dtype).itemsize,
                       detail=("nnz", tot, "lanes", n))
        with obs_trace.span("service.update_sparse_batch", cat="service",
                            lanes=n):
            Yb, Wb = fn(Yb, Wb, rows, cols, vals, keys, r0s)
        self._m_updates.inc(n, path="sparse")
        for i, st in enumerate(sts):
            st.Y = Yb[i]
            if cfg0.corange:
                st.W = Wb[i]
            st.num_updates += 1
        self._updates_total += n
        return self

    def _get_sparse_fn(self, cfg: StreamConfig, k: int, nnz_b: int):
        key = (_stream_sig(cfg), k, nnz_b, "sparse")
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = local_sparse_prog(_local_sig(cfg), k,
                                                    nnz_b)
        return fn

    def update_batch(self, sids, H, row0=0):
        """Fused multi-stream ingest: one compiled call applies the same-
        shape row-block update to every stream in ``sids``.

        H    : (N, k, n2) — lane i is the update for stream ``sids[i]``.
        row0 : int applied to all lanes, or a length-N sequence of
               per-lane offsets.

        The update is the single-stream program vmapped over a leading
        stream axis with per-lane Philox key pairs, so lane i's result is
        bitwise the result of updating stream i alone (pinned by
        tests/test_stream.py); N streams cost one dispatch instead of N.
        Local mode only — distributed streams batch at the mesh level
        instead (open one service per grid).  For heterogeneous lane
        shapes use :meth:`update_ragged`.
        """
        if self.mesh is not None:
            raise NotImplementedError(
                "update_batch is local-mode only; distributed streams "
                "already amortize dispatch through the shared mesh program")
        sids = list(sids)
        if len(set(sids)) != len(sids):
            raise ValueError("update_batch sids must be distinct (duplicate "
                             "lanes would overwrite each other's update)")
        protect = frozenset(sids)           # a batch lane must not evict
        sts = [self._touch(s, protect) for s in sids]   # a sibling lane
        for st in sts:
            self._materialize(st)
        if not sts:
            raise ValueError("update_batch needs at least one stream")
        cfg0 = sts[0].cfg
        sig = _local_sig(cfg0)
        for st in sts[1:]:
            if _local_sig(st.cfg) != sig:
                raise ValueError(
                    f"streams must share one shape signature; "
                    f"{_local_sig(st.cfg)} != {sig}")
        H = jnp.asarray(H, cfg0.dtype)
        n = len(sts)
        if H.ndim != 3 or H.shape[0] != n:
            raise ValueError(f"H must be (N={n}, k, n2); got {H.shape}")
        row0s = ([int(row0)] * n if jnp.ndim(row0) == 0 else
                 [int(x) for x in row0])
        if len(row0s) != n:
            raise ValueError(f"row0 needs {n} entries, got {len(row0s)}")
        for r0 in row0s:
            validate_row_block(cfg0, r0, H.shape[1:])
        key = (sig, H.shape[1], n, "batch")
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = local_rowblock_batch_prog(
                sig, H.shape[1], n)
        Yb = jnp.stack([st.Y for st in sts])
        Wb = (jnp.stack([st.W for st in sts]) if cfg0.corange else None)
        keys = jnp.stack([st.keys for st in sts])
        r0s = jnp.asarray(row0s, jnp.int32)
        led = obs_ledger.get_ledger()
        if led is not None:
            led.observe("service.update_batch", fn, (Yb, Wb, H, keys, r0s),
                        itemsize=jnp.dtype(cfg0.dtype).itemsize)
        with obs_trace.span("service.update_batch", cat="service", lanes=n):
            Yb, Wb = fn(Yb, Wb, H, keys, r0s)
        self._m_updates.inc(n, path="batch")
        for i, st in enumerate(sts):
            st.Y = Yb[i]
            if cfg0.corange:
                st.W = Wb[i]
            st.num_updates += 1
        self._updates_total += n
        return self

    def update_ragged(self, items: Sequence[Tuple[int, Any, int]], *,
                      bucket_edges: Optional[Sequence[int]] = None,
                      pad_value: float = 0.0,
                      backend: Optional[str] = None):
        """Fused HETEROGENEOUS multi-stream ingest (the multi-tenant hot
        path): each item is ``(sid, H, row0)`` with its own row-slab shape
        ``(k_i, n2)`` and offset.

        Lanes are grouped by (shape signature, bucket height) — bucket
        height is ``snap_bucket(k_i, bucket_edges)``: pow2 snap by default,
        or planner-chosen edges from ``repro.plan.choose_bucket_edges``
        which prices padded-lane waste against dispatch amortization.  Each
        bucket runs ONE vmapped masked update (``local_rowblock_ragged_prog``)
        with the stacked (Y, W) buffers donated, so N streams cost one
        dispatch per occupied bucket and batched ingest never doubles the
        fleet's HBM.

        Pad rows are masked dead in-program: lane i's result is bitwise
        the result of updating stream i alone via :meth:`update`, whatever
        ``pad_value`` holds (NaN included — that is how the contract is
        tested).  Local mode only.

        The LANE COUNT is snapped to pow2 as well (dummy lanes carry
        ``kvalid=0`` — all-masked, provably no-ops — and zero scratch
        accumulators): without it, every distinct bucket occupancy under
        live traffic would compile a fresh program, a multi-second stall
        per new count; with it, compiles are bounded at log2(window) per
        bucket.
        """
        if self.mesh is not None:
            raise NotImplementedError(
                "update_ragged is local-mode only; distributed streams "
                "already amortize dispatch through the shared mesh program")
        items = list(items)
        if not items:
            raise ValueError("update_ragged needs at least one item")
        sids = [it[0] for it in items]
        if len(set(sids)) != len(sids):
            raise ValueError("update_ragged sids must be distinct (duplicate "
                             "lanes would overwrite each other's update)")
        edges = None if bucket_edges is None else sorted(
            int(e) for e in bucket_edges)
        protect = frozenset(sids)
        # validate everything BEFORE mutating any stream: a bad lane must
        # not leave a half-applied batch behind.  H staging stays on the
        # HOST (numpy pad into the bucket frame) — per-lane device ops here
        # would cost a dispatch each and forfeit the amortization.
        buckets: Dict[Tuple, list] = {}
        for sid, H, row0 in items:
            st = self._touch(sid, protect)
            cfg = st.cfg
            H = np.asarray(H)
            row0 = int(row0)
            validate_row_block(cfg, row0, H.shape)
            k = H.shape[0]
            kb = snap_bucket(k, edges)
            if kb > cfg.n1:
                kb = k      # never compile a frame taller than the stream
            buckets.setdefault((_local_sig(cfg), kb), []).append(
                (sid, st, H, row0, k))
        for (sig, kb), group in buckets.items():
            corange = sig[6]
            dtype = jnp.dtype(sig[5])
            n = len(group)
            ns = pow2_bucket(n)
            fkey = (sig, kb, ns, self.backend if backend is None else backend,
                    "ragged")
            fn = self._fns.get(fkey)
            if fn is None:
                fn = self._fns[fkey] = local_rowblock_ragged_prog(
                    sig, kb, ns, backend=fkey[3])
            shape = (ns, kb, group[0][2].shape[1])
            Hb = (np.zeros(shape, dtype) if pad_value == 0.0
                  else np.full(shape, pad_value, dtype))
            for i, (_, _, H, _, k) in enumerate(group):
                Hb[i, :k] = H.astype(dtype, copy=False)
            row0s = np.zeros(ns, np.int32)
            row0s[:n] = [g[3] for g in group]
            kvalids = np.zeros(ns, np.int32)   # dummy lanes: all-masked
            kvalids[:n] = [g[4] for g in group]
            # steady-state fast path: if this exact cohort (same lanes,
            # same order, same bucket) ran before and nothing touched its
            # members since, its stacked (Y, W) is still live — feed it
            # straight back in (donated!), zero per-lane stack/unstack
            gkey = (sig, kb, tuple(g[0] for g in group))
            stack = self._stacks.pop(gkey, None)
            if stack is not None:
                Yb, Wb = stack
                keys = self._stack_keys[gkey]
            else:
                for _, st, *_ in group:
                    self._materialize(st)
                pad = ns - n
                Y0, W0 = group[0][1].Y, group[0][1].W
                Yb = jnp.stack([g[1].Y for g in group]
                               + [jnp.zeros_like(Y0)] * pad)
                Wb = (jnp.stack([g[1].W for g in group]
                                + [jnp.zeros_like(W0)] * pad)
                      if corange else None)
                k0 = group[0][1].keys
                keys = jnp.stack([g[1].keys for g in group]
                                 + [jnp.zeros_like(k0)] * pad)
                self._stack_keys[gkey] = keys
            led = obs_ledger.get_ledger()
            if led is not None:
                # observe BEFORE dispatch: the stacked (Yb, Wb) are DONATED
                # and the ledger abstractifies its args immediately
                led.observe("service.update_ragged", fn,
                            (Yb, Wb, Hb, keys, row0s, kvalids),
                            itemsize=dtype.itemsize)
            with obs_trace.span("service.update_ragged", cat="service",
                                lanes=n, bucket=kb):
                Yb, Wb = fn(Yb, Wb, Hb, keys, row0s, kvalids)
            self._m_updates.inc(n, path="ragged")
            real = int(sum(g[4] for g in group))
            self._m_real_rows.inc(real)
            self._m_padded_rows.inc(ns * kb - real)
            self._stacks[gkey] = (Yb, Wb)
            for i, (_, st, *_rest) in enumerate(group):
                st.Y = st.W = None          # rows live in the cohort stack
                st.stack_ref = (gkey, i)
                st.num_updates += 1
            self._updates_total += n
        return self

    def sync(self):
        """Block until every in-flight device update has landed — resident
        lane buffers and stacked ragged cohorts alike.  The serving loop's
        barrier (graceful drain) without per-lane slicing."""
        leaves = [e for Yb, Wb in self._stacks.values()
                  for e in (Yb, Wb) if e is not None]
        for st in self._streams.values():
            if st.stack_ref is None:
                leaves.append(st.Y)
                if st.W is not None:
                    leaves.append(st.W)
        jax.block_until_ready(leaves)
        return self

    # -- elastic resize ----------------------------------------------------

    def reshard(self, new_grid: Tuple[int, int, int],
                devices=None) -> int:
        """Move every RESIDENT stream onto ``new_grid`` in one resharding
        hop each — the service half of elastic resize (stream/elastic.py).

        Linearity makes this a pure data movement: no recompute, no
        replay, and every post-hop update folds into bitwise the numbers
        it would have folded into on the old grid.  Evicted streams are
        already mesh-agnostic (host / disk copies) and re-land on the new
        mesh at next touch.  Compiled update executables are mesh-specific
        and are dropped; the first post-resize update per signature
        recompiles.  Returns the number of streams moved.

        Callers pausing live ingest should go through
        ``stream.elastic.drain_reshard_resume`` (drain -> reshard ->
        resume), which quiesces the IngestQueue first.
        """
        if self.mesh is None:
            raise ValueError("reshard needs a distributed service "
                             "(mesh=None is single-device)")
        from repro.core.sketch import make_grid_mesh
        from . import elastic, faults
        old_grid = tuple(int(self.mesh.shape[a]) for a in self.axes)
        new_grid = tuple(int(g) for g in new_grid)
        faults.fire("elastic.reshard", old_grid=old_grid,
                    new_grid=new_grid)
        for st in self._streams.values():
            elastic._check_divisible(st.cfg, new_grid)
        for ev in self._evicted.values():
            elastic._check_divisible(ev.cfg, new_grid)
        new_mesh = make_grid_mesh(*new_grid, axis_names=self.axes,
                                  devices=devices)
        moved = 0
        with obs_trace.span("service.reshard", cat="service",
                            old="x".join(map(str, old_grid)),
                            new="x".join(map(str, new_grid))):
            self.sync()
            for st in self._streams.values():
                self._materialize(st)
                sh = stream_shardings(st.cfg, new_mesh, self.axes)
                arrays = (st.Y,) + (() if st.W is None else (st.W,))
                shards = (sh["Y"],) + (() if st.W is None else (sh["W"],))
                pred, floor = elastic.reshard_words(st.cfg, old_grid,
                                                    new_grid)
                out = elastic.reshard_tree(
                    arrays, shards, predicted_words=pred,
                    lower_bound_words=floor,
                    itemsize=jnp.dtype(st.cfg.dtype).itemsize,
                    old_grid=old_grid, new_grid=new_grid)
                st.Y = out[0]
                st.W = out[1] if st.W is not None else None
                moved += 1
        self.mesh = new_mesh
        self._fns.clear()       # executables were mesh-specific
        self._audit.clear()
        return moved

    # -- queries -----------------------------------------------------------

    def sketch(self, sid: int):
        return self._lane_Y(self._touch(sid))

    def corange(self, sid: int):
        return self._lane_W(self._touch(sid))

    def reconstruct(self, sid: int, rank: Optional[int] = None, rcond=None):
        from .reconstruct import one_pass_reconstruct
        st = self._touch(sid)
        W = self._lane_W(st)
        if W is None:
            raise ValueError("reconstruction needs corange=True")
        return one_pass_reconstruct(self._lane_Y(st), W, st.cfg, rank=rank,
                                    rcond=rcond)

    def nystrom(self, sid: int, variant: str = "auto"):
        """(B, C) for a symmetric stream (local mode: computed in place;
        distributed mode: via the Alg.-2 second stages on a (P,1,1) grid —
        ``variant`` is ``auto``/``no_redist``/``redist``/``bound_driven``,
        the last running the §5.3 general two-grid second stage; see
        :func:`repro.stream.distributed.nystrom_finalize`)."""
        st = self._touch(sid)
        cfg = st.cfg
        if cfg.n1 != cfg.n2:
            raise ValueError("Nyström needs a square stream")
        Y = self._lane_Y(st)
        if self.mesh is None:
            return nystrom_local(Y, cfg)
        from .distributed import nystrom_finalize
        return nystrom_finalize(Y, cfg, self.mesh, self.axes, variant,
                                backend=self.backend)

    # -- introspection -----------------------------------------------------

    @property
    def num_streams(self) -> int:
        """Open streams — resident plus evicted-but-restorable."""
        return len(self._streams) + len(self._evicted)

    @property
    def num_resident(self) -> int:
        return len(self._streams)

    @property
    def num_evicted(self) -> int:
        return len(self._evicted)

    @property
    def num_compiled(self) -> int:
        """Distinct compiled update executables currently cached."""
        return len(self._fns)

    def stats(self) -> Dict[str, int]:
        return {"streams": self.num_streams,
                "resident": self.num_resident,
                "evicted": self.num_evicted,
                "compiled_updates": self.num_compiled,
                # service-lifetime count: closing a stream must not make
                # its ingested updates vanish from the ledger
                "updates": self._updates_total}
