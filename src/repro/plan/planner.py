"""Cost-model-driven execution planner for sketch / Nyström / stream dispatch.

``plan_sketch`` / ``plan_nystrom`` / ``plan_stream`` enumerate every variant
the repo can actually execute for the given (shape, P, dtype), score each
with the analytic costs in :mod:`repro.plan.model`, compare the winner
against the paper's lower bound (Theorems 2/3), and return a :class:`Plan`
whose ``execute`` dispatches to the existing entry points — bitwise
identical to calling them directly, because it *is* the same call.

Planner invariants (pinned by tests/test_plan.py):

  * predicted words are never below the Theorem 2/3 lower bound;
  * when a shard_map variant wins, its words equal the closed forms
    ``alg1_bandwidth_words`` / ``alg2_bandwidth_words`` exactly;
  * in the Theorem-2 regime 1 (P <= n1) the planner picks the
    zero-communication local-regenerate grid (P, 1, 1);
  * the Alg.-1 grid agrees with ``core.grid.select_matmul_grid`` whenever
    that grid is executable (divisibility), and otherwise falls back to the
    cheapest executable factorization of P;
  * every Nyström candidate — including the §5.3 bound-driven general
    two-grid pair run by ``nystrom_two_grid`` — prices at
    ``alg2_bandwidth_words`` on its own (p, q) grids, so no candidate ever
    scores below the Theorem 3 floor.

The analytic ranking is refined by measured timings in ``plan.autotune``.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, Optional, Sequence, Tuple

from repro.core.grid import (
    MatmulGrid,
    factorizations_3d,
    select_matmul_grid,
    select_nystrom_grids,
    select_two_grid_executable,
)
from repro.core.lower_bounds import (
    matmul_lower_bound,
    matmul_regime,
    nystrom_lower_bound,
    nystrom_regime,
)
from repro.core.kinds import SPARSE_KINDS
from repro.obs.metrics import DEFAULT_BUCKETS

from . import model as M

# Default Pallas block sizes (MXU-aligned; kernels/sketch_matmul.py).
DEFAULT_BLOCKS = {"bm": 256, "bn": 128, "bk": 512}

# plan_execute_seconds buckets (s): a dispatch takes 10-1000 microseconds
_EXECUTE_BUCKETS = (1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4) + DEFAULT_BUCKETS


def _dtype_name(dtype) -> str:
    import jax.numpy as jnp
    return jnp.dtype(dtype).name


def _itemsize(dtype_name: str) -> int:
    import numpy as np
    return int(np.dtype(dtype_name).itemsize)


# ---------------------------------------------------------------------------
# Candidates and the Plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Candidate:
    """One scored dispatch option; ``executable=False`` entries are kept in
    the report (e.g. the Omega-communicating baseline, infeasible ideal
    grids) but never chosen.  ``backend`` is the local GEMM body
    (kernels/local.py) the shard_map variants would run with — same
    network words, different HBM roofline."""
    variant: str
    cost: M.Cost
    seconds: float
    grid: Optional[Tuple[int, int, int]] = None
    q_grid: Optional[Tuple[int, int, int]] = None
    blocks: Optional[Tuple[Tuple[str, int], ...]] = None
    executable: bool = True
    note: str = ""
    backend: str = "jnp"


@dataclasses.dataclass(frozen=True)
class Plan:
    """An executable dispatch decision plus everything needed to audit it."""
    task: str                       # "sketch" | "nystrom" | "stream"
    variant: str
    dims: Tuple[int, ...]           # sketch: (n1, n2, r); nystrom: (n, r)
    n_procs: int
    dtype: str
    kind: str                       # Omega entry distribution
    grid: Optional[Tuple[int, int, int]]
    q_grid: Optional[Tuple[int, int, int]]
    blocks: Optional[Dict[str, int]]
    predicted_words: float          # per-processor interconnect words
    predicted_flops: float
    predicted_hbm_words: float
    predicted_seconds: float
    lower_bound_words: float
    regime: int
    candidates: Tuple[Candidate, ...]
    machine: str
    executable: bool = True
    chunk_rows: Optional[int] = None
    corange: bool = False                      # stream plans only
    sketch_l: Optional[int] = None             # stream plans only
    measured_seconds: Optional[float] = None   # set by plan.autotune
    backend: str = "jnp"                       # local GEMM body (kernels/)

    # -- audit helpers ------------------------------------------------------

    @property
    def bound_gap_words(self) -> float:
        """Predicted words above the Theorem 2/3 floor (>= 0 by tightness)."""
        return self.predicted_words - self.lower_bound_words

    @property
    def bound_ratio(self) -> float:
        if self.lower_bound_words == 0.0:
            return 1.0 if self.predicted_words == 0.0 else math.inf
        return self.predicted_words / self.lower_bound_words

    # -- execution ----------------------------------------------------------

    def execute(self, A, seed=0, devices=None):
        """Dispatch to the underlying entry point.

        sketch : returns B = A·Omega (layout per the chosen variant)
        nystrom: returns (B, C)
        stream : builds an accumulator, ingests A in ``chunk_rows`` slabs,
                 and returns the accumulator (call .nystrom()/.reconstruct()
                 on it to finalize)

        Bitwise contract: for every variant this performs exactly the same
        call a user would make against core/kernels/stream directly.
        """
        if not self.executable:
            raise ValueError(
                f"plan {self.variant} for dims={self.dims}, P={self.n_procs} "
                f"is analytic-only (no executable grid divides the shape); "
                f"pad the shape or change P")
        from repro.obs import ledger as obs_ledger
        from repro.obs import metrics as obs_metrics
        from repro.obs import trace as obs_trace
        t0 = time.perf_counter()
        with obs_trace.span("plan.execute", cat="plan", task=self.task,
                            variant=self.variant, dims=list(self.dims),
                            P=self.n_procs):
            if self.task == "sketch":
                out = self._execute_sketch(A, seed, devices)
            elif self.task == "nystrom":
                out = self._execute_nystrom(A, seed, devices)
            elif self.task == "stream":
                out = self._execute_stream(A, seed, devices)
            else:
                raise ValueError(self.task)
        # host time to dispatch: the entry points return before the
        # device has finished
        wall_s = time.perf_counter() - t0
        reg = obs_metrics.get_metrics()
        reg.histogram(
            "plan_execute_seconds",
            "host seconds in Plan.execute (dispatch, not device completion)",
            buckets=_EXECUTE_BUCKETS).observe(
                wall_s, task=self.task, variant=self.variant)
        led = obs_ledger.get_ledger()
        if led is not None:
            # analytic site: execute dispatches into opaque entry points
            # (the instrumented layers below contribute the HLO-backed
            # sites); the cache_key ties drift flags back to plan.autotune
            from .autotune import cache_key
            import numpy as np
            led.record(f"plan.execute[{self.task}/{self.variant}]",
                       predicted_words=self.predicted_words,
                       lower_bound_words=self.lower_bound_words,
                       itemsize=np.dtype(self.dtype).itemsize,
                       cache_key=cache_key(self),
                       wall_s=wall_s,
                       detail=(self.dims, self.n_procs))
        return out

    def _mesh_1d(self, devices):
        import jax
        import numpy as np
        from jax.sharding import Mesh
        devices = devices if devices is not None else jax.devices()
        if len(devices) < self.n_procs:
            raise ValueError(f"plan needs {self.n_procs} devices, "
                             f"have {len(devices)}")
        return Mesh(np.asarray(devices[: self.n_procs]), ("x",))

    def _blocks_tuple(self):
        return (tuple(self.blocks[k] for k in ("bm", "bn", "bk"))
                if self.blocks else None)

    def _execute_sketch(self, A, seed, devices):
        import jax
        n1, n2, r = self.dims
        if self.variant == "alg1":
            from repro.core.sketch import (input_sharding, make_grid_mesh,
                                           rand_matmul)
            mesh = make_grid_mesh(*self.grid, devices=devices)
            A = jax.device_put(A, input_sharding(mesh))
            return rand_matmul(A, seed, r, mesh, kind=self.kind,
                               backend=self.backend,
                               blocks=self._blocks_tuple())
        if self.variant == "local_xla":
            from repro.core.sketch import sketch_reference
            return sketch_reference(A, seed, r, kind=self.kind)
        if self.variant == "local_sparse":
            from repro.core.sketch import sketch_sparse_apply
            return sketch_sparse_apply(A, seed, r, kind=self.kind)
        if self.variant == "pallas_fused":
            from repro.kernels.ops import sketch_matmul
            interpret = jax.default_backend() != "tpu"
            return sketch_matmul(A, seed=seed, r=r, kind=self.kind,
                                 interpret=interpret, **(self.blocks or {}))
        raise ValueError(self.variant)

    def _execute_nystrom(self, A, seed, devices):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        n, r = self.dims
        if self.variant in ("alg2_no_redist", "alg2_redist"):
            from repro.core.nystrom import nystrom_no_redist, nystrom_redist
            mesh = self._mesh_1d(devices)
            A = jax.device_put(A, NamedSharding(mesh, P("x", None)))
            fn = (nystrom_no_redist if self.variant == "alg2_no_redist"
                  else nystrom_redist)
            return fn(A, seed, r, mesh, axis="x", kind=self.kind,
                      backend=self.backend, blocks=self._blocks_tuple())
        if self.variant in ("alg2_bound_driven", "alg2_bound_driven_fused"):
            from repro.core.nystrom import (nystrom_two_grid,
                                            nystrom_two_grid_fused)
            devices = devices if devices is not None else jax.devices()
            if len(devices) < self.n_procs:
                raise ValueError(f"plan needs {self.n_procs} devices, "
                                 f"have {len(devices)}")
            fn = (nystrom_two_grid_fused
                  if self.variant == "alg2_bound_driven_fused"
                  else nystrom_two_grid)
            return fn(A, seed, r, p=self.grid, q=self.q_grid,
                      kind=self.kind,
                      devices=list(devices[: self.n_procs]),
                      backend=self.backend,
                      blocks=self._blocks_tuple())
        if self.variant == "local_xla":
            from repro.core.nystrom import nystrom_reference
            return nystrom_reference(A, seed, r, kind=self.kind)
        if self.variant == "pallas_fused":
            from repro.kernels.ops import nystrom_fused
            interpret = jax.default_backend() != "tpu"
            return nystrom_fused(A, seed=seed, r=r, kind=self.kind,
                                 interpret=interpret, **(self.blocks or {}))
        raise ValueError(self.variant)

    def _execute_stream(self, A, seed, devices):
        from repro.stream.state import StreamConfig
        n1, n2, r = self.dims
        cfg = StreamConfig(n1=n1, n2=n2, r=r, seed=seed, kind=self.kind,
                           corange=self.corange, l=self.sketch_l)
        k = self.chunk_rows or n1
        if self.variant == "stream_sparse":
            from repro.stream.state import SparseRows, StreamingSketch
            st = StreamingSketch(cfg, backend="xla")
            for row0 in range(0, n1, k):
                st.update_rows_sparse(
                    row0, SparseRows.from_dense(A[row0: row0 + k]))
            return st
        if self.variant == "stream_local":
            from repro.stream.state import StreamingSketch
            st = StreamingSketch(cfg, backend="xla")
        elif self.variant == "stream_sharded":
            from repro.core.sketch import make_grid_mesh
            from repro.stream.distributed import ShardedStreamingSketch
            mesh = make_grid_mesh(*self.grid, devices=devices)
            st = ShardedStreamingSketch(cfg, mesh, backend=self.backend,
                                        blocks=self._blocks_tuple())
        else:
            raise ValueError(self.variant)
        for row0 in range(0, n1, k):
            st.update_rows(row0, A[row0: row0 + k])
        return st


# ---------------------------------------------------------------------------
# plan_sketch
# ---------------------------------------------------------------------------

def _alg1_executable(n1: int, n2: int, r: int,
                     grid: Tuple[int, int, int]) -> bool:
    # n1 % (p1*p2): B is laid out P((p1, p2), p3) — the reduce-scatter
    # splits each n1/p1 row block p2 ways.
    p1, p2, p3 = grid
    return (n1 % (p1 * p2) == 0 and n2 % (p2 * p3) == 0 and n2 % p2 == 0
            and r % p3 == 0 and p1 <= n1 and p2 <= n2 and p3 <= r)


def _best_executable_alg1_grid(n1: int, n2: int, r: int, P: int):
    """Paper grid if it divides the shape, else the cheapest factorization
    of P that does (what select_matmul_grid does, restricted further to the
    entry point's divisibility contract)."""
    g: MatmulGrid = select_matmul_grid(n1, n2, r, P)
    if _alg1_executable(n1, n2, r, g.shape):
        return g.shape
    best = None
    for cand in factorizations_3d(P):
        if not _alg1_executable(n1, n2, r, cand):
            continue
        c = M.alg1_cost(n1, n2, r, cand)
        key = (c.words, c.messages)
        if best is None or key < best[0]:
            best = (key, cand)
    return best[1] if best else None


def plan_sketch(n1: int, n2: int, r: int, P: Optional[int] = None,
                dtype="float32", kind: str = "normal",
                machine: Optional[M.MachineModel] = None,
                allow_pallas: Optional[bool] = None,
                nnz: Optional[int] = None) -> Plan:
    """Plan B = A·Omega for an (n1 x n2) A on P processors.

    P defaults to ``len(jax.devices())``.  ``allow_pallas`` overrides the
    machine's capability flag (tests force the fused path on CPU, where it
    runs in interpret mode).

    ``nnz`` declares A stored-sparse with that many nonzeros and adds the
    sparse sketch family to the candidate list (``local_sparse`` —
    O(nnz) scatter ingest, COO (indices+values) payload): a sparse
    ``kind`` is kept, a dense ``kind`` is paired with CountSketch (a
    different sketch family — the chosen plan's ``kind`` reports what
    will actually run, and the candidate note says who lost and why).
    Dense candidates stay in the race at their dense cost: the planner
    picks per regime and density, it does not assume sparse wins.
    """
    if P is None:
        import jax
        P = len(jax.devices())
    machine = machine or M.probe_machine()
    if allow_pallas is None:
        allow_pallas = machine.supports_pallas
    dtype = _dtype_name(dtype)
    isz = _itemsize(dtype)
    lb = matmul_lower_bound(n1, n2, r, P)
    regime = matmul_regime(n1, n2, r, P)

    cands = []
    if P == 1:
        c = M.local_cost(n1, n2, r)
        cands.append(Candidate("local_xla", c, c.seconds(machine, isz)))
        cp = M.pallas_fused_cost(n1, n2, r)
        cands.append(Candidate(
            "pallas_fused", cp, cp.seconds(machine, isz),
            blocks=tuple(sorted(DEFAULT_BLOCKS.items())),
            executable=allow_pallas, backend="pallas",
            note="" if allow_pallas else "needs TPU (interpret-only here)"))
    else:
        grid = _best_executable_alg1_grid(n1, n2, r, P)
        if grid is not None:
            c = M.alg1_cost(n1, n2, r, grid)
            cands.append(Candidate("alg1", c, c.seconds(machine, isz),
                                   grid=grid))
            # same grid, fused local body: identical network words,
            # n2·r/(p2·p3) fewer HBM words per device
            cp = M.alg1_cost(n1, n2, r, grid, backend="pallas")
            cands.append(Candidate(
                "alg1", cp, cp.seconds(machine, isz), grid=grid,
                backend="pallas", executable=allow_pallas,
                note="" if allow_pallas else "needs TPU (interpret-only "
                                             "here)"))
            cc = M.alg1_communicating_cost(n1, n2, r, grid)
            cands.append(Candidate(
                "alg1_communicating", cc, cc.seconds(machine, isz),
                grid=grid, executable=False,
                note="Fig.-3 baseline: Omega over the wire, never chosen"))
        else:
            ideal = select_matmul_grid(n1, n2, r, P).shape
            c = M.alg1_cost(n1, n2, r, ideal)
            cands.append(Candidate(
                "alg1", c, c.seconds(machine, isz), grid=ideal,
                executable=False,
                note=f"no factorization of P={P} divides the shape"))

    if nnz is not None:
        skind = kind if kind in SPARSE_KINDS else "countsketch"
        grid = (1, 1, 1) if P == 1 else (_best_executable_alg1_grid(
            n1, n2, r, P) or select_matmul_grid(n1, n2, r, P).shape)
        cs = M.sparse_sketch_cost(n1, n2, r, nnz, grid, skind)
        cands.append(Candidate(
            "local_sparse" if P == 1 else "alg1_sparse",
            cs, cs.seconds(machine, isz),
            grid=None if P == 1 else grid, executable=(P == 1),
            note="" if P == 1 else "distributed sparse shard_map body "
                                   "deferred (ROADMAP item 3)"))
        cands = _note_sparse_losses(cands, kind, skind, nnz, n1 * n2)

    plan = _finish_plan("sketch", (n1, n2, r), P, dtype, kind, machine,
                        cands, lb, regime)
    if nnz is not None and plan.variant in ("local_sparse", "alg1_sparse"):
        plan = dataclasses.replace(plan, kind=skind)
    return plan


def _note_sparse_losses(cands, kind: str, skind: str, nnz: int,
                        dense_entries: int):
    """Honest notes on the sparse-vs-dense race: whoever loses gets told
    why, in words a report reader can check against the cost model."""
    ex = [c for c in cands if c.executable]
    if not ex:
        return cands
    best = min(ex, key=lambda c: c.seconds)
    density = nnz / max(dense_entries, 1)
    out = []
    for c in cands:
        sparse = c.variant in ("local_sparse", "alg1_sparse",
                               "stream_sparse")
        if sparse and c.executable and c is not best:
            note = (f"dense wins at density {density:.3g} "
                    f"({best.seconds:.3g}s vs {c.seconds:.3g}s)")
            if c.note:
                note = f"{c.note}; {note}"
            c = dataclasses.replace(c, note=note)
        elif sparse and c is best and kind not in SPARSE_KINDS:
            note = (f"substitutes {skind} for requested {kind!r} "
                    f"(different sketch family) at density {density:.3g}")
            if c.note:
                note = f"{c.note}; {note}"
            c = dataclasses.replace(c, note=note)
        out.append(c)
    return out


# ---------------------------------------------------------------------------
# plan_nystrom
# ---------------------------------------------------------------------------

def plan_nystrom(n: int, r: int, P: Optional[int] = None,
                 dtype="float32", kind: str = "normal",
                 machine: Optional[M.MachineModel] = None,
                 allow_pallas: Optional[bool] = None,
                 variant: str = "auto") -> Plan:
    """Plan the Nyström pair (B, C) for a symmetric (n x n) A on P procs.

    The redist / no_redist choice falls out of the cost model — redist's
    nr/P all-to-all beats no_redist's (1-1/P)·r² reduce-scatter exactly
    when P > ~n/r, the paper's Fig.-7 crossover.  The §5.3 bound-driven
    general two-grid algorithm is a third executable candidate
    (``alg2_bound_driven``, run by ``core.nystrom.nystrom_two_grid``); it
    wins whenever its (p, q) pair prices below both 1-D variants — in
    particular when P > n and no 1-D grid is runnable at all.

    When the bound-driven (p, q) pair admits a shared mesh
    (``core.grid.two_grid_shared_mesh``), a fourth executable candidate
    ``alg2_bound_driven_fused`` prices the single-jit program
    (``nystrom_two_grid_fused``): identical stage collectives, but the
    §5.2 Redistribute is an in-program min-cut resharding (<= nr/P words,
    one collective hop) instead of the cross-mesh host ``device_put`` —
    so it outranks the cross-mesh form whenever both can run.

    variant: ``"auto"`` lets the cost model choose; ``"no_redist"`` /
    ``"redist"`` / ``"bound_driven"`` / ``"bound_driven_fused"`` force
    that variant (the others stay in ``candidates`` for the audit trail).
    """
    requires = {"auto": None, "no_redist": "alg2_no_redist",
                "redist": "alg2_redist",
                "bound_driven": "alg2_bound_driven",
                "bound_driven_fused": "alg2_bound_driven_fused"}
    if variant not in requires:
        raise ValueError(f"unknown variant {variant!r}")
    require = requires[variant]
    forced = variant != "auto"
    if P is None:
        import jax
        P = len(jax.devices())
    machine = machine or M.probe_machine()
    if allow_pallas is None:
        allow_pallas = machine.supports_pallas
    dtype = _dtype_name(dtype)
    isz = _itemsize(dtype)
    lb = nystrom_lower_bound(n, r, P)
    regime = nystrom_regime(n, r, P)

    cands = []
    if P == 1:
        if forced:
            raise ValueError(f"variant={variant!r} needs P > 1")
        c = M.nystrom_local_cost(n, r, fused=False)
        cands.append(Candidate("local_xla", c, c.seconds(machine, isz)))
        cp = M.nystrom_local_cost(n, r, fused=True)
        cands.append(Candidate(
            "pallas_fused", cp, cp.seconds(machine, isz),
            blocks=tuple(sorted(DEFAULT_BLOCKS.items())),
            executable=allow_pallas, backend="pallas",
            note="" if allow_pallas else "needs TPU (interpret-only here)"))
    else:
        executable_1d = (n % P == 0 and r % P == 0 and P <= n)
        note = "" if executable_1d else f"needs P | n and P | r (P={P})"
        p = (P, 1, 1)
        for vname, q in (("alg2_no_redist", (P, 1, 1)),
                         ("alg2_redist", (1, 1, P))):
            c = M.alg2_cost(n, r, p, q)
            cands.append(Candidate(vname, c, c.seconds(machine, isz),
                                   grid=p, q_grid=q,
                                   executable=executable_1d, note=note))
            cp = M.alg2_cost(n, r, p, q, backend="pallas")
            pnote = note if not executable_1d else (
                "" if allow_pallas else "needs TPU (interpret-only here)")
            cands.append(Candidate(
                vname, cp, cp.seconds(machine, isz), grid=p, q_grid=q,
                backend="pallas",
                executable=executable_1d and allow_pallas, note=pnote))
        # §5.3 approach 1: the bound-driven general two-grid algorithm,
        # executed by core.nystrom.nystrom_two_grid.  When the ideal grids
        # do not divide (n, r), snap to the min-words executable pair of
        # factorizations (same policy as Alg. 1's grid="auto") and report
        # the gap; when no pair divides at all, keep the analytic row.
        ideal = select_nystrom_grids(n, r, P, variant="bound_driven")
        got = select_two_grid_executable(n, r, P)
        if got is not None:
            p_bd, q_bd, exact = got
            cb = M.alg2_cost(n, r, p_bd, q_bd)
            note = "" if exact else (
                f"snapped from ideal p={tuple(ideal.p)} q={tuple(ideal.q)} "
                f"(+{cb.words - M.alg2_cost(n, r, ideal.p, ideal.q).words:g}"
                f" words over the unrunnable ideal)")
            cands.append(Candidate(
                "alg2_bound_driven", cb, cb.seconds(machine, isz),
                grid=p_bd, q_grid=q_bd, executable=True, note=note))
            cbp = M.alg2_cost(n, r, p_bd, q_bd, backend="pallas")
            cands.append(Candidate(
                "alg2_bound_driven", cbp, cbp.seconds(machine, isz),
                grid=p_bd, q_grid=q_bd, backend="pallas",
                executable=allow_pallas,
                note=note if allow_pallas else
                (note + "; " if note else "") + "needs TPU (interpret-only "
                                               "here)"))
            # single-jit fused two-grid (nystrom_two_grid_fused): same
            # stage collectives, but the §5.2 Redistribute is an
            # in-program min-cut resharding instead of a host-mediated
            # cross-mesh device_put — only emitted when one device order
            # serves both grids (core.grid.two_grid_shared_mesh).
            from repro.core.grid import two_grid_axis_split
            if two_grid_axis_split(p_bd, q_bd) is not None:
                fnote = (note + "; " if note else "") + \
                    "in-program Redistribute (shared mesh)"
                cf = M.alg2_fused_cost(n, r, p_bd, q_bd)
                cands.append(Candidate(
                    "alg2_bound_driven_fused", cf, cf.seconds(machine, isz),
                    grid=p_bd, q_grid=q_bd, executable=True, note=fnote))
                cfp = M.alg2_fused_cost(n, r, p_bd, q_bd, backend="pallas")
                cands.append(Candidate(
                    "alg2_bound_driven_fused", cfp,
                    cfp.seconds(machine, isz), grid=p_bd, q_grid=q_bd,
                    backend="pallas", executable=allow_pallas,
                    note=fnote if allow_pallas else
                    fnote + "; needs TPU (interpret-only here)"))
        else:
            cb = M.alg2_cost(n, r, ideal.p, ideal.q)
            cands.append(Candidate(
                "alg2_bound_driven", cb, cb.seconds(machine, isz),
                grid=tuple(ideal.p), q_grid=tuple(ideal.q), executable=False,
                note=f"no (p, q) factorization pair of P={P} divides "
                     f"(n={n}, r={r})"))

    return _finish_plan("nystrom", (n, r), P, dtype, kind, machine,
                        cands, lb, regime, require=require)


# ---------------------------------------------------------------------------
# plan_stream
# ---------------------------------------------------------------------------

def plan_stream(n1: int, n2: int, r: int, P: Optional[int] = None,
                chunk_rows: Optional[int] = None, l: Optional[int] = None,
                corange: bool = False, dtype="float32",
                kind: str = "normal",
                machine: Optional[M.MachineModel] = None,
                allow_pallas: Optional[bool] = None,
                nnz: Optional[int] = None) -> Plan:
    """Plan a full streaming pass over A in row slabs of ``chunk_rows``.

    Scores the local accumulator against the mesh-sharded one; predicted
    cost is the per-update cost times the number of slabs (one full pass).
    Sharded candidates are priced per backend: the fused pallas body drops
    the per-update Omega HBM stream and halves the Y round trips.

    ``nnz`` declares the WHOLE pass stored-sparse with that many nonzeros
    total and adds the COO ingest candidate (``stream_sparse`` —
    ``update_rows_sparse``, (indices+values) payload per slab, O(nnz)
    scatter fold); same kind-substitution and honest-note contract as
    :func:`plan_sketch`.
    """
    if P is None:
        import jax
        P = len(jax.devices())
    machine = machine or M.probe_machine()
    if allow_pallas is None:
        allow_pallas = machine.supports_pallas
    dtype = _dtype_name(dtype)
    isz = _itemsize(dtype)
    chunk_rows = chunk_rows or max(1, n1 // 8)
    n_upd = math.ceil(n1 / chunk_rows)
    l_eff = l if l is not None else min(2 * r + 1, n1)
    lb = matmul_lower_bound(n1, n2, r, P)
    regime = matmul_regime(n1, n2, r, P)

    def scaled(c: M.Cost) -> M.Cost:
        return M.Cost(words=c.words * n_upd, messages=c.messages * n_upd,
                      flops=c.flops * n_upd, hbm_words=c.hbm_words * n_upd)

    cands = []
    c_loc = scaled(M.stream_update_cost(chunk_rows, n2, r, l_eff,
                                        (1, 1, 1), corange))
    cands.append(Candidate("stream_local", c_loc, c_loc.seconds(machine, isz),
                           executable=(P == 1),
                           note="" if P == 1 else "single-device only"))
    if P > 1:
        grid = _best_executable_alg1_grid(n1, n2, r, P)
        if grid is not None:
            c = scaled(M.stream_update_cost(chunk_rows, n2, r, l_eff,
                                            grid, corange))
            cands.append(Candidate("stream_sharded", c,
                                   c.seconds(machine, isz), grid=grid))
            cp = scaled(M.stream_update_cost(chunk_rows, n2, r, l_eff,
                                             grid, corange,
                                             backend="pallas"))
            cands.append(Candidate(
                "stream_sharded", cp, cp.seconds(machine, isz), grid=grid,
                backend="pallas", executable=allow_pallas,
                note="" if allow_pallas else "needs TPU (interpret-only "
                                             "here)"))

    if nnz is not None:
        skind = kind if kind in SPARSE_KINDS else "countsketch"
        nnz_u = nnz / n_upd                      # per-slab payload
        cs = scaled(M.sparse_stream_update_cost(chunk_rows, n2, r, l_eff,
                                                nnz_u, (1, 1, 1), corange,
                                                skind))
        cands.append(Candidate(
            "stream_sparse", cs, cs.seconds(machine, isz),
            executable=(P == 1),
            note="" if P == 1 else "single-device only (distributed "
                                   "sparse bodies: ROADMAP item 3)"))
        cands = _note_sparse_losses(cands, kind, skind, nnz, n1 * n2)

    plan = _finish_plan("stream", (n1, n2, r), P, dtype, kind, machine,
                        cands, lb, regime)
    if nnz is not None and plan.variant == "stream_sparse":
        plan = dataclasses.replace(plan, kind=skind)
    return dataclasses.replace(plan, chunk_rows=chunk_rows, corange=corange,
                               sketch_l=l)


# ---------------------------------------------------------------------------
# plan_train_compression — per-leaf raw-vs-sketched gradient exchange
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LeafDecision:
    """One parameter leaf's priced exchange choice.

    ``m``/``n`` are the leaf folded to a matrix (leading dims merged, the
    same folding ``parallel.grad_compress`` applies); ``r_eff`` is the
    rank clamped to ``min(rank, m, n)``.  Non-matrix leaves (ndim < 2)
    always go raw — there is nothing to sketch.
    """
    name: str
    shape: Tuple[int, ...]
    m: int
    n: int
    r_eff: int
    compress: bool
    raw_cost: M.Cost
    comp_cost: M.Cost
    raw_seconds: float
    comp_seconds: float
    note: str = ""

    @property
    def words(self) -> float:
        """Predicted exchange words for the decision actually taken."""
        return self.comp_cost.words if self.compress else self.raw_cost.words


@dataclasses.dataclass(frozen=True)
class TrainCompressionPlan:
    """Per-leaf decision map for the DP gradient exchange
    (``train.step.make_dp_compressed_step`` consumes it; ``explain.
    explain_train_compression`` renders the word table).

    ``exchange_words`` is the per-step, per-worker prediction the comm
    ledger audits (``train.dp_compressed_step`` site): compressed leaves
    contribute ``r·(m+n)``, raw leaves ``m·n``.  It is also the plan's
    ``lower_bound_words`` — the factor-exchange floor: Omega is
    regenerated (Theorem 2 regime 1, zero words), but the data-dependent
    factors P and Q must move, so a schedule that meets the prediction is
    AT the floor, not above it.
    """
    rank: int
    n_procs: int
    dtype: str
    kind: str
    machine: str
    backend: str
    objective: str
    decisions: Tuple[LeafDecision, ...]
    treedef: object

    def decision_tree(self):
        """Pytree of per-leaf bools matching the params structure."""
        import jax
        return jax.tree_util.tree_unflatten(
            self.treedef, [d.compress for d in self.decisions])

    @property
    def exchange_words(self) -> float:
        return sum(d.words for d in self.decisions)

    @property
    def raw_words(self) -> float:
        return sum(d.raw_cost.words for d in self.decisions)

    @property
    def lower_bound_words(self) -> float:
        return self.exchange_words

    @property
    def savings(self) -> float:
        """Raw-over-compressed word ratio for the whole step (>= 1 when
        any leaf compresses; exactly 1 when none do)."""
        ex = self.exchange_words
        return self.raw_words / ex if ex > 0 else 1.0

    @property
    def n_compressed(self) -> int:
        return sum(1 for d in self.decisions if d.compress)


def _leaf_name(path) -> str:
    parts = []
    for p in path:        # DictKey(.key) / SequenceKey(.idx) / GetAttrKey
        for attr in ("key", "idx", "name"):
            v = getattr(p, attr, None)
            if v is not None:
                parts.append(str(v))
                break
        else:
            parts.append(str(p))
    return ".".join(parts) or "<root>"


def plan_train_compression(params_shapes, rank: int, P: Optional[int] = None,
                           *, dtype="float32", kind: str = "normal",
                           machine: Optional[M.MachineModel] = None,
                           backend: Optional[str] = None,
                           objective: str = "words") -> TrainCompressionPlan:
    """Decide, per parameter leaf, raw all-reduce vs sketched exchange.

    ``params_shapes`` is any pytree of shaped leaves (concrete params or
    ``jax.eval_shape`` output).  For each matrix leaf the planner prices
    ``grad_allreduce_cost`` (m·n words) against ``grad_compress_cost``
    (r·(m+n) words + the rank-r GEMM/QR work) on the measured machine
    model and keeps whichever wins under ``objective``:

      * ``"words"``  (default) — compress iff the predicted exchange
        words strictly drop: ``r_eff·(m+n) < m·n``, i.e. the Theorem-2
        crossover ``r_eff < m·n/(m+n)``.  This is the paper's objective
        (communication is the scarce resource the bounds govern) and the
        contract the decision property test pins.
      * ``"seconds"`` — compress iff predicted seconds drop on
        ``machine`` (the added rank-r FLOPs can outweigh the network
        saving on compute-bound hosts; both estimates are kept on every
        row so ``explain_train_compression`` shows the disagreement).

    ``backend`` prices the local bodies (None: pallas where the machine
    supports it, else jnp).  Dispatch overhead is a per-step constant —
    the whole exchange lives inside ONE jitted step either way — so it
    cancels between the candidates and only the per-leaf resource terms
    decide.
    """
    if objective not in ("words", "seconds"):
        raise ValueError(f"unknown objective {objective!r} "
                         f"(want words|seconds)")
    if P is None:
        import jax
        P = len(jax.devices())
    import jax
    machine = machine or M.probe_machine()
    if backend is None:
        backend = "pallas" if machine.supports_pallas else "jnp"
    dtype = _dtype_name(dtype)
    isz = _itemsize(dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)

    decisions = []
    for path, leaf in flat:
        shape = tuple(leaf.shape)
        if len(shape) < 2:
            m = 1 if not shape else int(shape[0])
            n = 1
            raw = M.grad_allreduce_cost(m, n, P)
            decisions.append(LeafDecision(
                name=_leaf_name(path), shape=shape, m=m, n=n, r_eff=0,
                compress=False, raw_cost=raw, comp_cost=raw,
                raw_seconds=raw.seconds(machine, isz),
                comp_seconds=raw.seconds(machine, isz),
                note="not a matrix"))
            continue
        m = math.prod(shape[:-1])
        n = int(shape[-1])
        r_eff = min(rank, m, n)
        raw = M.grad_allreduce_cost(m, n, P)
        comp = M.grad_compress_cost(m, n, r_eff, P, backend=backend)
        raw_s = raw.seconds(machine, isz)
        comp_s = comp.seconds(machine, isz)
        if objective == "words":
            compress = comp.words < raw.words
        else:
            compress = comp_s < raw_s
        note = ""
        if not compress:
            note = ("below crossover r >= m*n/(m+n)" if objective == "words"
                    else "network saving < added rank-r compute")
        elif objective == "words" and comp_s > raw_s:
            note = "words win; seconds would not on this machine"
        decisions.append(LeafDecision(
            name=_leaf_name(path), shape=shape, m=m, n=n, r_eff=r_eff,
            compress=compress, raw_cost=raw, comp_cost=comp,
            raw_seconds=raw_s, comp_seconds=comp_s, note=note))

    return TrainCompressionPlan(
        rank=rank, n_procs=P, dtype=dtype, kind=kind, machine=machine.name,
        backend=backend, objective=objective,
        decisions=tuple(decisions), treedef=treedef)


# ---------------------------------------------------------------------------
# shared tail
# ---------------------------------------------------------------------------

def _finish_plan(task: str, dims: Tuple[int, ...], P: int, dtype: str,
                 kind: str, machine: M.MachineModel,
                 cands: Sequence[Candidate], lb: float, regime: int,
                 require: Optional[str] = None) -> Plan:
    cands = tuple(sorted(
        cands, key=lambda c: (not c.executable, c.seconds,
                              c.cost.hbm_words, c.cost.words)))
    eligible = [c for c in cands
                if require is None or c.variant == require]
    chosen = next((c for c in eligible if c.executable), None)
    if chosen is None:
        # analytic-only plan; execute() raises
        chosen = eligible[0] if eligible else cands[0]
    return Plan(
        task=task, variant=chosen.variant, dims=tuple(dims), n_procs=P,
        dtype=dtype, kind=kind, grid=chosen.grid, q_grid=chosen.q_grid,
        blocks=dict(chosen.blocks) if chosen.blocks else None,
        predicted_words=chosen.cost.words,
        predicted_flops=chosen.cost.flops,
        predicted_hbm_words=chosen.cost.hbm_words,
        predicted_seconds=chosen.seconds,
        lower_bound_words=lb, regime=regime, candidates=cands,
        machine=machine.name,
        executable=chosen.executable,
        backend=chosen.backend)
