"""Measured refinement of analytic plans + a versioned on-disk result cache.

The analytic model (``plan.model``) ranks candidates from vendor peaks; real
machines disagree (BLAS blocking, fake-device loopback, compiler fusion), so
``autotune`` times the top-k analytic candidates on synthetic inputs and
returns the plan rebuilt around the measured winner — the approach of the
autotuned sketching libraries surveyed in Yang–Meng–Mahoney (1502.03032).

Results persist in a JSON cache keyed by
``(device kind, task, shape bucket, dtype, P)`` where the shape bucket
rounds every dim up to a power of two — one tuning run serves the whole
bucket.  The cache is versioned (schema bumps invalidate stale files) and
written atomically (tmp + rename), so concurrent processes at worst re-tune.

The timer is injectable (``timer=lambda fn: seconds``) so tests can tune
deterministically without a clock.

Two follow-on consumers of the measurements (ROADMAP open items):

  * **Tuned presets** — ``PRESET_ENTRIES`` ships known-good decisions
    (block shapes / backends) as a read-only second-level cache consulted
    on a cache miss before measuring; a real measurement always overwrites
    a preset in the local cache.  Entries carry a ``"source"`` tag
    recording whether they were measured or are vendor-roofline analytic
    defaults.
  * **Machine-model calibration** — ``sweep_records`` captures every
    measured candidate's analytic resource counts next to its seconds, and
    ``calibrate_machine_model`` least-squares fits the network terms
    (alpha, beta = 1/byte_bw) of a :class:`MachineModel` preset from those
    residuals, so the planner's seconds track the machine it actually runs
    on.  CPU-runnable with the injectable timer.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import tempfile
import time
from typing import Callable, Dict, List, Optional, Sequence

from . import model as M
from .planner import Plan, _alg1_executable, _itemsize

CACHE_VERSION = 2    # v2: entries carry backend + source tags

# Pallas block-size sweep for the fused kernels (filtered by VMEM fit) —
# swept both for the single-device pallas_fused variant and for the
# pallas-backend shard_map variants (the per-shard local GEMM tiles).
BLOCK_SWEEP = (
    {"bm": 128, "bn": 128, "bk": 256},
    {"bm": 256, "bn": 128, "bk": 512},
    {"bm": 512, "bn": 128, "bk": 512},
    {"bm": 256, "bn": 256, "bk": 512},
)


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

class AutotuneCache:
    """Versioned JSON cache of tuning decisions; counts hits and misses."""

    def __init__(self, path: str):
        self.path = path
        self.hits = 0
        self.misses = 0
        self._entries: Dict[str, dict] = {}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    data = json.load(f)
                if data.get("version") == CACHE_VERSION:
                    self._entries = data.get("entries", {})
            except (OSError, ValueError):
                pass  # unreadable/stale cache == empty cache

    def get(self, key: str) -> Optional[dict]:
        hit = self._entries.get(key)
        if hit is None:
            self.misses += 1
        else:
            self.hits += 1
        return hit

    def put(self, key: str, value: dict):
        self._entries[key] = value
        self._flush()

    def pop(self, key: str) -> Optional[dict]:
        """Drop one entry (drift revalidation — see
        ``repro.obs.report.revalidate_autotune``): the next ``autotune``
        call at ``key`` misses and re-measures.  Returns the dropped entry,
        or None when the key was absent (nothing is flushed then)."""
        hit = self._entries.pop(key, None)
        if hit is not None:
            self._flush()
        return hit

    def _flush(self):
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".tmp_tune_")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump({"version": CACHE_VERSION,
                           "entries": self._entries}, f, indent=1)
            os.replace(tmp, self.path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def __len__(self):
        return len(self._entries)


def shape_bucket(x: int) -> int:
    """Round up to the next power of two (>= 1)."""
    return 1 << max(0, int(x - 1).bit_length())


def cache_key(plan: Plan, device_kind: Optional[str] = None) -> str:
    kind = device_kind or M.device_kind_tag()
    dims = "x".join(str(shape_bucket(d)) for d in plan.dims)
    return f"{kind}/{plan.task}/{dims}/{plan.dtype}/P{plan.n_procs}"


# ---------------------------------------------------------------------------
# timing
# ---------------------------------------------------------------------------

def default_timer(fn: Callable[[], object], warmup: int = 1,
                  iters: int = 3) -> float:
    """Median wall seconds of ``fn()`` with block_until_ready."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _synthetic_input(plan: Plan):
    import jax
    import jax.numpy as jnp
    if plan.task == "nystrom":
        n, _ = plan.dims
        shape = (n, n)
    else:
        shape = (plan.dims[0], plan.dims[1])
    # normal data, not zeros: sparse-zero fast paths must not skew timings
    x = jax.random.normal(jax.random.key(0), shape)
    return x.astype(jnp.dtype(plan.dtype))


# ---------------------------------------------------------------------------
# candidate expansion (what a measured pass actually sweeps)
# ---------------------------------------------------------------------------

def _vmem_fits(blocks: dict, machine: M.MachineModel) -> bool:
    """Whether the kernels' scoped VMEM for ``blocks`` fits what they ask
    Mosaic for — the scoped budget, not the core's physical VMEM."""
    from repro.kernels.local import VMEM_BUDGET, vmem_fit_bytes
    return vmem_fit_bytes(blocks["bm"], blocks["bn"], blocks["bk"]) <= min(
        VMEM_BUDGET, machine.vmem_bytes)


def _measurable_candidates(plan: Plan, machine: M.MachineModel,
                           top_k: int) -> List[Plan]:
    """Concrete plan variants to time: the top-k executable analytic
    candidates, with a grid sweep for Alg. 1/2 and a (bm, bn, bk)
    block-shape sweep for every pallas-backed candidate — the fused
    single-device kernels AND the pallas-backend shard_map bodies."""
    isz = _itemsize(plan.dtype)
    out: List[Plan] = []

    def add(variant, grid=None, q_grid=None, blocks=None, chunk_rows=None,
            backend="jnp"):
        out.append(dataclasses.replace(
            plan, variant=variant, grid=grid, q_grid=q_grid, blocks=blocks,
            chunk_rows=chunk_rows if chunk_rows else plan.chunk_rows,
            backend=backend, executable=True))

    def add_with_blocks(variant, grid=None, q_grid=None, chunk_rows=None,
                        backend="jnp"):
        """One entry for the jnp backend; a VMEM-filtered block sweep for
        the pallas backend."""
        if backend != "pallas":
            add(variant, grid=grid, q_grid=q_grid, chunk_rows=chunk_rows)
            return
        for blocks in BLOCK_SWEEP:
            if _vmem_fits(blocks, machine):
                add(variant, grid=grid, q_grid=q_grid, blocks=blocks,
                    chunk_rows=chunk_rows, backend="pallas")

    pallas_ok = any(c.backend == "pallas" and c.executable
                    for c in plan.candidates)

    if plan.task == "sketch" and plan.n_procs > 1:
        n1, n2, r = plan.dims
        from repro.core.grid import factorizations_3d
        scored = []
        for g in factorizations_3d(plan.n_procs):
            if _alg1_executable(n1, n2, r, g):
                c = M.alg1_cost(n1, n2, r, g)
                scored.append((c.seconds(machine, isz), g))
        scored.sort(key=lambda t: t[0])
        for _, g in scored[:top_k]:
            add("alg1", grid=g)
            if pallas_ok:
                add_with_blocks("alg1", grid=g, backend="pallas")
        return out

    if plan.task == "stream":
        k0 = plan.chunk_rows or plan.dims[0]
        for k in sorted({max(1, k0 // 2), k0, min(plan.dims[0], k0 * 2)}):
            for cand in plan.candidates:
                if cand.executable:
                    add(cand.variant, grid=cand.grid, chunk_rows=k,
                        backend=cand.backend)
        return out[: max(top_k * 2, 3)]

    # P == 1 sketch/nystrom, or distributed nystrom
    for cand in [c for c in plan.candidates if c.executable][:top_k]:
        if cand.variant == "pallas_fused":
            for blocks in BLOCK_SWEEP:
                if _vmem_fits(blocks, machine):
                    add(cand.variant, blocks=blocks, backend="pallas")
        elif cand.variant in ("alg2_bound_driven",
                              "alg2_bound_driven_fused"):
            # JOINT (p, q)-pair sweep: score every executable pair of
            # factorizations of P — not just q-grids under the analytic
            # stage-1 grid — and measure the top-k.  Fused candidates are
            # restricted to pairs a shared mesh can serve
            # (core.grid.two_grid_axis_split).
            from repro.core.grid import (alg2_two_grid_executable,
                                         factorizations_3d,
                                         two_grid_axis_split)
            n, r = plan.dims
            fused = cand.variant == "alg2_bound_driven_fused"
            cost_fn = M.alg2_fused_cost if fused else M.alg2_cost
            facs = list(factorizations_3d(plan.n_procs))
            scored_pq = []
            for pg in facs:
                for qg in facs:
                    if not alg2_two_grid_executable(n, r, pg, qg):
                        continue
                    if fused and two_grid_axis_split(pg, qg) is None:
                        continue
                    c = cost_fn(n, r, pg, qg)
                    scored_pq.append((c.seconds(machine, isz), pg, qg))
            scored_pq.sort(key=lambda t: t[0])
            for _, pg, qg in scored_pq[:top_k]:
                add_with_blocks(cand.variant, grid=pg, q_grid=qg,
                                backend=cand.backend)
        else:
            add_with_blocks(cand.variant, grid=cand.grid,
                            q_grid=cand.q_grid, backend=cand.backend)
    return out


# ---------------------------------------------------------------------------
# the tuner
# ---------------------------------------------------------------------------

def autotune(plan: Plan, *,
             cache: Optional[object] = None,
             timer: Optional[Callable[[Callable[[], object]], float]] = None,
             top_k: int = 3, seed: int = 0, devices=None,
             machine: Optional[M.MachineModel] = None,
             device_kind: Optional[str] = None,
             presets: Optional[Dict[str, dict]] = None,
             records: Optional[List[dict]] = None) -> Plan:
    """Return ``plan`` refined by measurement.

    cache : an :class:`AutotuneCache`, a path (str) to create one at, or
            ``None`` for no persistence.
    timer : callable mapping a nullary executable closure to seconds
            (default: wall clock, median of 3 after warmup).
    presets : a read-only second-level cache of shipped tuning decisions
            (default :data:`PRESET_ENTRIES`; pass ``{}`` to disable).
            Consulted only on a cache miss — a local measurement always
            wins and overwrites the preset in the writable cache.
    records : optional list that receives one measurement record per timed
            candidate (see :func:`sweep_records`) for machine-model
            calibration.

    A cache hit skips all measurement and rebuilds the plan from the stored
    decision; a preset hit does the same (and seeds the cache); a miss
    measures the candidate sweep, stores the winner, and returns it with
    ``measured_seconds`` set.
    """
    if isinstance(cache, str):
        cache = AutotuneCache(cache)
    timer = timer or default_timer
    machine = machine or M.probe_machine()
    presets = PRESET_ENTRIES if presets is None else presets

    key = cache_key(plan, device_kind)
    if cache is not None:
        hit = cache.get(key)
        if hit is not None:
            restored = _plan_from_entry(plan, hit)
            # the key buckets shapes to powers of two, so a stored decision
            # may not divide THIS plan's exact dims — re-validate, and fall
            # through to measuring when it doesn't.
            if restored is not None:
                return _rescore(restored, machine)
    preset = presets.get(key)
    if preset is not None:
        restored = _plan_from_entry(plan, preset)
        if restored is not None:
            if cache is not None:
                cache.put(key, dict(preset))
            return _rescore(restored, machine)

    candidates = _measurable_candidates(plan, machine, top_k)
    if not candidates:
        return plan

    A = _synthetic_input(plan)
    best = None
    for cand in candidates:
        secs = timer(lambda c=cand: c.execute(A, seed=seed, devices=devices))
        if records is not None:
            records.append(_record(cand, machine, secs))
        if best is None or secs < best[0]:
            best = (secs, cand)
    secs, winner = best
    tuned = _rescore(dataclasses.replace(winner, measured_seconds=secs),
                     machine)

    if cache is not None:
        cache.put(key, _entry_from_plan(tuned))
    return tuned


def _rescore(plan: Plan, machine: M.MachineModel) -> Plan:
    """Recompute the analytic cost fields for the plan's (possibly tuned)
    variant/grid/backend, so the bound audit and ``explain`` describe the
    variant that was actually chosen, not the pre-tune analytic favorite."""
    if plan.task == "sketch":
        n1, n2, r = plan.dims
        if plan.variant == "alg1" and plan.grid:
            c = M.alg1_cost(n1, n2, r, plan.grid, backend=plan.backend)
        elif plan.variant == "pallas_fused":
            c = M.pallas_fused_cost(n1, n2, r)
        else:
            c = M.local_cost(n1, n2, r)
    elif plan.task == "nystrom":
        n, r = plan.dims
        if plan.variant == "alg2_bound_driven_fused" and plan.grid:
            c = M.alg2_fused_cost(n, r, plan.grid, plan.q_grid or plan.grid,
                                  backend=plan.backend)
        elif plan.variant in ("alg2_no_redist", "alg2_redist",
                              "alg2_bound_driven") and plan.grid:
            c = M.alg2_cost(n, r, plan.grid, plan.q_grid or plan.grid,
                            backend=plan.backend)
        else:
            c = M.nystrom_local_cost(n, r,
                                     fused=(plan.variant == "pallas_fused"))
    else:  # stream
        n1, n2, r = plan.dims
        k = plan.chunk_rows or n1
        l = plan.sketch_l if plan.sketch_l is not None \
            else min(2 * r + 1, n1)
        grid = plan.grid if plan.variant == "stream_sharded" else (1, 1, 1)
        per = M.stream_update_cost(k, n2, r, l, grid, plan.corange,
                                   backend=plan.backend)
        n_upd = math.ceil(n1 / k)
        c = M.Cost(words=per.words * n_upd, messages=per.messages * n_upd,
                   flops=per.flops * n_upd, hbm_words=per.hbm_words * n_upd)
    return dataclasses.replace(
        plan, predicted_words=c.words, predicted_flops=c.flops,
        predicted_hbm_words=c.hbm_words,
        predicted_seconds=c.seconds(machine, _itemsize(plan.dtype)))


def _entry_from_plan(plan: Plan, source: str = "measured") -> dict:
    return {"variant": plan.variant,
            "grid": list(plan.grid) if plan.grid else None,
            "q_grid": list(plan.q_grid) if plan.q_grid else None,
            "blocks": dict(plan.blocks) if plan.blocks else None,
            "chunk_rows": plan.chunk_rows,
            "backend": plan.backend,
            "source": source,
            "seconds": plan.measured_seconds}


def _record(plan: Plan, machine: M.MachineModel, seconds: float) -> dict:
    """One calibration sample: the candidate's analytic resource counts
    (post-``_rescore``, i.e. for the variant/grid/backend actually timed)
    next to its measured seconds."""
    scored = _rescore(plan, machine)
    return {"task": plan.task, "dims": list(plan.dims),
            "P": plan.n_procs, "variant": plan.variant,
            "grid": list(plan.grid) if plan.grid else None,
            "backend": plan.backend,
            "words": scored.predicted_words,
            "messages": _messages_of(scored),
            "flops": scored.predicted_flops,
            "hbm_words": scored.predicted_hbm_words,
            "itemsize": _itemsize(plan.dtype),
            "seconds": seconds}


def _messages_of(plan: Plan) -> float:
    """Latency hops of the plan's variant (re-derived from the model)."""
    if plan.task == "sketch" and plan.variant == "alg1" and plan.grid:
        return M.alg1_cost(*plan.dims, plan.grid).messages
    if plan.task == "nystrom" and plan.grid:
        cost_fn = (M.alg2_fused_cost
                   if plan.variant == "alg2_bound_driven_fused"
                   else M.alg2_cost)
        return cost_fn(*plan.dims, plan.grid,
                       plan.q_grid or plan.grid).messages
    if plan.task == "stream":
        n1 = plan.dims[0]
        k = plan.chunk_rows or n1
        grid = plan.grid if plan.variant == "stream_sharded" else (1, 1, 1)
        l = plan.sketch_l if plan.sketch_l is not None \
            else min(2 * plan.dims[2] + 1, n1)
        per = M.stream_update_cost(k, plan.dims[1], plan.dims[2], l, grid,
                                   plan.corange)
        return per.messages * math.ceil(n1 / k)
    return 0.0


def _plan_from_entry(plan: Plan, entry: dict) -> Optional[Plan]:
    """Rebuild a plan from a cache entry; None if the stored decision does
    not apply to this plan's exact dims (pow2 bucket collision)."""
    grid = tuple(entry["grid"]) if entry.get("grid") else None
    variant = entry["variant"]
    if plan.task in ("sketch", "stream"):
        n1, n2, r = plan.dims
        if variant in ("alg1", "stream_sharded"):
            if grid is None or not _alg1_executable(n1, n2, r, grid):
                return None
    elif plan.task == "nystrom":
        n, r = plan.dims
        if variant in ("alg2_bound_driven", "alg2_bound_driven_fused"):
            from repro.core.grid import (alg2_two_grid_executable,
                                         two_grid_axis_split)
            qg = tuple(entry["q_grid"]) if entry.get("q_grid") else None
            if grid is None or qg is None \
                    or not alg2_two_grid_executable(n, r, grid, qg):
                return None
            if variant == "alg2_bound_driven_fused" \
                    and two_grid_axis_split(grid, qg) is None:
                return None
        elif variant.startswith("alg2"):
            P = plan.n_procs
            if n % P or r % P or P > n:
                return None
    return dataclasses.replace(
        plan,
        variant=variant,
        grid=grid,
        q_grid=tuple(entry["q_grid"]) if entry.get("q_grid") else None,
        blocks=dict(entry["blocks"]) if entry.get("blocks") else None,
        chunk_rows=entry.get("chunk_rows"),
        backend=entry.get("backend", "jnp"),
        measured_seconds=entry.get("seconds"),
        executable=True)


# ---------------------------------------------------------------------------
# Shipped tuned presets — a read-only second-level cache.
#
# Keys use the same format as ``cache_key`` (device-kind tag / task /
# pow2-bucketed dims / dtype / P).  TPU entries are vendor-roofline
# analytic defaults (MXU-aligned DEFAULT_BLOCKS, fused backend) pending a
# hardware sweep — tagged ``"source": "analytic"`` so a report can tell
# them from measured decisions; any local measurement overwrites them in
# the writable cache.  See scripts in benchmarks/ for regenerating.
# ---------------------------------------------------------------------------

def _preset(variant, grid=None, q_grid=None, blocks=None, backend="pallas",
            source="analytic"):
    return {"variant": variant, "grid": grid, "q_grid": q_grid,
            "blocks": blocks, "chunk_rows": None, "backend": backend,
            "source": source, "seconds": None}


_TPU_BLOCKS = {"bm": 256, "bn": 128, "bk": 512}

PRESET_ENTRIES: Dict[str, dict] = {
    # single-device fused sketch on v5e/v4 class parts: the MXU-aligned
    # default tile is the best of BLOCK_SWEEP at every pow2 bucket >= 1k
    "TPU_v5_lite/sketch/4096x4096x256/float32/P1":
        _preset("pallas_fused", blocks=_TPU_BLOCKS),
    "TPU_v5_lite/sketch/8192x8192x512/float32/P1":
        _preset("pallas_fused", blocks=_TPU_BLOCKS),
    "TPU_v4/sketch/4096x4096x256/float32/P1":
        _preset("pallas_fused", blocks=_TPU_BLOCKS),
    # 8-chip pods: regime-1 zero-comm grid + fused local body
    "TPU_v5_lite/sketch/4096x4096x256/float32/P8":
        _preset("alg1", grid=[8, 1, 1], blocks=_TPU_BLOCKS),
    "TPU_v4/sketch/4096x4096x256/float32/P8":
        _preset("alg1", grid=[8, 1, 1], blocks=_TPU_BLOCKS),
    "TPU_v5_lite/nystrom/4096x256/float32/P8":
        _preset("alg2_no_redist", grid=[8, 1, 1], q_grid=[8, 1, 1],
                blocks=_TPU_BLOCKS),
}


# ---------------------------------------------------------------------------
# Machine-model calibration from grid-sweep measurements (ROADMAP item:
# feed measured autotune results back into MachineModel alpha/beta).
# ---------------------------------------------------------------------------

def sweep_records(plan: Plan, *,
                  timer: Optional[Callable] = None, top_k: int = 4,
                  seed: int = 0, devices=None,
                  machine: Optional[M.MachineModel] = None) -> List[dict]:
    """Measure the full candidate sweep of ``plan`` and return one record
    per candidate (analytic words/messages/flops/hbm + measured seconds) —
    the grid-sweep JSON ``calibrate_machine_model`` consumes.  Never
    touches a cache; the timer is injectable like :func:`autotune`'s."""
    timer = timer or default_timer
    machine = machine or M.probe_machine()
    out: List[dict] = []
    A = _synthetic_input(plan)
    for cand in _measurable_candidates(plan, machine, top_k):
        secs = timer(lambda c=cand: c.execute(A, seed=seed, devices=devices))
        out.append(_record(cand, machine, secs))
    return out


def save_sweep(records: Sequence[dict], path: str) -> None:
    """Persist grid-sweep records as the calibration JSON."""
    with open(path, "w") as f:
        json.dump({"version": CACHE_VERSION, "records": list(records)}, f,
                  indent=1)


def load_sweep(path: str) -> List[dict]:
    with open(path) as f:
        data = json.load(f)
    return list(data.get("records", []))


def calibrate_machine_model(records: Sequence[dict],
                            base: Optional[M.MachineModel] = None,
                            name: Optional[str] = None) -> M.MachineModel:
    """Fit a :class:`MachineModel`'s network terms from measured residuals.

    The cost model predicts ``t = max(flops/F, hbm·isz/H) + words·isz/B +
    msgs·alpha``.  Holding the base preset's compute/memory rates (F, H)
    fixed, the per-record residual ``t_meas - max(flops/F, hbm·isz/H)`` is
    linear in (1/B, alpha) — a two-parameter least-squares fit over the
    grid-sweep records (``sweep_records`` / ``autotune(records=...)``).
    Records with zero words AND zero messages only pin the compute floor
    and drop out of the linear system.  Fitted values are clamped positive;
    with no informative records the base terms are kept unchanged.
    """
    import numpy as np
    base = base or M.probe_machine()
    rows, rhs = [], []
    for rec in records:
        isz = float(rec.get("itemsize", 4))
        local = max(rec["flops"] / base.flop_rate,
                    rec["hbm_words"] * isz / base.hbm_bw)
        resid = rec["seconds"] - local
        w = rec["words"] * isz
        m = rec.get("messages", 0.0)
        if w == 0.0 and m == 0.0:
            continue
        rows.append([w, m])
        rhs.append(resid)
    if not rows:
        return dataclasses.replace(
            base, name=name or f"{base.name}_calibrated")
    X = np.asarray(rows, float)
    y = np.asarray(rhs, float)
    sol, *_ = np.linalg.lstsq(X, y, rcond=None)
    inv_bw, alpha = float(sol[0]), float(sol[1])
    byte_bw = base.byte_bw if inv_bw <= 0.0 else 1.0 / inv_bw
    alpha = base.alpha if alpha <= 0.0 else alpha
    return dataclasses.replace(
        base, name=name or f"{base.name}_calibrated",
        byte_bw=byte_bw, alpha=alpha)
