"""Single-jit two-grid Nyström (core.nystrom.nystrom_two_grid_fused).

Pins the whole bitwise contract of the fused §5.3 path (ISSUE acceptance
criteria):

  (a) ``nystrom_two_grid_fused`` — stage 1, the §5.2 Redistribute expressed
      IN-PROGRAM on the shared mesh of ``core.grid.two_grid_shared_mesh``,
      and stage 2, one executable — is bitwise-identical to the cross-mesh
      ``nystrom_two_grid`` across kinds x dtypes (f32/bf16) x
      non-divisible shapes x backends, and matches ``nystrom_reference``
      to the f32 summation-order bound for p2==1 ∧ q1==1 pairs;
  (b) an HLO byte audit: the in-program Redistribute moves <= nr/P words
      per processor and the compiled program contains zero unplanned
      collectives versus the planner's prediction (stage All-Gathers /
      Reduce-Scatters + one resharding);
  (c) ``two_grid_shared_mesh`` never silently reorders devices — stage 1
      alone on the shared mesh is bitwise stage 1 on the original p-grid
      mesh — and when it returns ``None`` the dispatcher demonstrably falls
      back to the cross-mesh path (counted via monkeypatch, not timing);
  (d) the planner emits ``alg2_bound_driven_fused`` candidates that price
      at/above the Theorem 3 floor, ``Plan.execute`` dispatches them
      bitwise-equal to the direct call, and the autotuner's JOINT (p, q)
      sweep measures pairs beyond the analytic fixed-p grid and caches
      fused decisions.
"""
import math

import pytest

from _hypothesis_compat import given, settings, st
from dist_helper import run_distributed

from repro.core.grid import (
    alg2_two_grid_executable,
    factorizations_3d,
    two_grid_axis_split,
)
from repro.core.lower_bounds import nystrom_lower_bound
from repro.plan import PRESETS, explain, plan_nystrom
from repro.plan.model import (
    alg2_cost,
    alg2_fused_cost,
    fused_redistribute_words,
    redistribute_words,
)

CPU = PRESETS["cpu"]


# ---------------------------------------------------------------------------
# shared-mesh reconciliation: pure-arithmetic properties
# ---------------------------------------------------------------------------

def _pairs(P):
    facs = list(factorizations_3d(P))
    return [(p, q) for p in facs for q in facs]


@settings(max_examples=60, deadline=None)
@given(Pe=st.integers(0, 6), i=st.integers(0, 10 ** 6),
       j=st.integers(0, 10 ** 6))
def test_axis_split_refinement_property(Pe, i, j):
    """When a split exists it is a true row-major common refinement: axis
    sizes multiply to P and each grid's dims are products of CONSECUTIVE
    axis groups (so sharding over a group reproduces the standalone mesh's
    device assignment); when it doesn't, the prefix products of p and q
    genuinely fail to chain under divisibility."""
    P = 2 ** Pe * 3 ** (i % 2)          # include non-powers of two
    facs = list(factorizations_3d(P))
    p, q = facs[i % len(facs)], facs[j % len(facs)]
    split = two_grid_axis_split(p, q)
    cuts = sorted({1, P, p[0], p[0] * p[1], q[0], q[0] * q[1]})
    chains = all(b % a == 0 for a, b in zip(cuts, cuts[1:]))
    assert (split is not None) == chains or P == 1
    if split is None:
        return
    sizes, pg, qg = split
    assert math.prod(sizes) == P
    for g, groups in ((p, pg), (q, qg)):
        flat = [i for grp in groups for i in grp]
        assert flat == sorted(flat)                    # row-major order
        assert sorted(flat) == list(range(len(sizes)))  # disjoint cover
        for dim, grp in zip(g, groups):
            assert math.prod(sizes[i] for i in grp) == dim


def test_axis_split_none_cases():
    # P = 6: 2x3 vs 3x2 leading blocks cannot share one row-major order
    assert two_grid_axis_split((2, 3, 1), (3, 2, 1)) is None
    assert two_grid_axis_split((3, 2, 1), (2, 3, 1)) is None
    # but any pair where one side is 1-D always reconciles (the streamed
    # accumulator's (P,1,1) grid in particular)
    for P in (2, 4, 6, 8, 12):
        for qc in factorizations_3d(P):
            assert two_grid_axis_split((P, 1, 1), qc) is not None
    # power-of-two P: every pair chains (all cuts are powers of two)
    for p, q in _pairs(8):
        assert two_grid_axis_split(p, q) is not None
    with pytest.raises(ValueError, match="same P"):
        two_grid_axis_split((2, 1, 1), (3, 1, 1))


# ---------------------------------------------------------------------------
# fused Redistribute cost model
# ---------------------------------------------------------------------------

@settings(max_examples=80, deadline=None)
@given(ne=st.integers(4, 9), re_=st.integers(1, 6), Pe=st.integers(1, 6),
       i=st.integers(0, 10 ** 6), j=st.integers(0, 10 ** 6))
def test_fused_redistribute_min_cut_bounds(ne, re_, Pe, i, j):
    """The in-program min-cut never exceeds the cross-mesh bound nr/P, and
    the full fused cost never dips below the Theorem 3 floor."""
    n, r, P = 2 ** ne, 2 ** re_, 2 ** Pe
    if r >= n:
        return
    facs = list(factorizations_3d(P))
    p, q = facs[i % len(facs)], facs[j % len(facs)]
    if not alg2_two_grid_executable(n, r, p, q):
        return
    fw = fused_redistribute_words(n, r, p, q)
    assert 0.0 <= fw <= n * r / P + 1e-9
    cf = alg2_fused_cost(n, r, p, q)
    cx = alg2_cost(n, r, p, q)
    assert cf.words >= nystrom_lower_bound(n, r, P) - 1e-9, (p, q)
    if tuple(p) != tuple(q):
        # the min-cut replaces the nr/P all-to-all term, so the fused form
        # never prices above the cross-mesh form (and its in-program hop
        # replaces the log2(P) host-mediated hops)
        assert cf.words <= cx.words + 1e-9
        assert fw <= redistribute_words(n, r, p, q) + 1e-9
        assert cf.seconds(CPU) <= cx.seconds(CPU) + 1e-15
    # p == q: the cross-mesh model scores the in-place reuse as free while
    # the fused min-cut honestly prices the stage-1 -> stage-2 layout
    # mismatch, so no ordering is asserted there.
    assert cf.flops == cx.flops and cf.hbm_words == cx.hbm_words


def test_fused_redistribute_known_values():
    # regime-1 ideal pair: every device keeps the (n/P x r/P) intersection
    # of its row-slab and column-slab shards
    n, r, P = 64, 16, 8
    assert fused_redistribute_words(n, r, (P, 1, 1), (1, 1, P)) \
        == n * r / P - n * r / P ** 2
    assert redistribute_words(n, r, (P, 1, 1), (1, 1, P)) == n * r / P
    # identical layouts (rows over P both stages, cols unsplit): zero moved
    assert fused_redistribute_words(n, r, (P, 1, 1), (P, 1, 1)) == 0.0


# ---------------------------------------------------------------------------
# planner + autotune integration (pure: no devices needed)
# ---------------------------------------------------------------------------

def test_planner_emits_fused_candidates_and_prefers_them():
    plan = plan_nystrom(64, 4, P=8, machine=CPU)
    assert plan.variant == "alg2_bound_driven_fused" and plan.executable
    fused = [c for c in plan.candidates
             if c.variant == "alg2_bound_driven_fused"]
    cross = [c for c in plan.candidates
             if c.variant == "alg2_bound_driven"]
    assert fused and cross
    fj = next(c for c in fused if c.backend == "jnp")
    cj = next(c for c in cross if c.backend == "jnp")
    assert (fj.grid, fj.q_grid) == (cj.grid, cj.q_grid)
    assert fj.cost.words < cj.cost.words          # min-cut < nr/P here
    assert fj.seconds < cj.seconds
    assert two_grid_axis_split(fj.grid, fj.q_grid) is not None
    # forcing selects each form explicitly
    assert plan_nystrom(64, 4, P=8, machine=CPU,
                        variant="bound_driven").variant \
        == "alg2_bound_driven"
    assert plan_nystrom(64, 4, P=8, machine=CPU,
                        variant="bound_driven_fused").variant \
        == "alg2_bound_driven_fused"


def test_explain_prints_fused_vs_cross_mesh_redistribute():
    pf = plan_nystrom(64, 4, P=8, machine=CPU, variant="bound_driven_fused")
    text = explain(pf)
    assert "IN-PROGRAM" in text and "min-cut" in text
    assert "cross-mesh device_put would move" in text
    pc = plan_nystrom(64, 4, P=8, machine=CPU, variant="bound_driven")
    textc = explain(pc)
    assert "cross-mesh device_put" in textc
    assert "fused form would move" in textc


def test_autotune_joint_pq_sweep_and_fused_cache(tmp_path):
    """The (p, q) sweep is JOINT — it measures stage-1 grids beyond the
    analytic fixed p — and the winning fused decision round-trips through
    the versioned cache (entries re-validated for exact dims)."""
    from repro.plan import autotune
    from repro.plan.autotune import AutotuneCache

    plan = plan_nystrom(64, 4, P=8, machine=CPU)
    assert plan.variant == "alg2_bound_driven_fused"
    records = []
    calls = []

    def fake_timer(fn):
        calls.append(fn)
        return 1e-3 * len(calls)

    cache = AutotuneCache(str(tmp_path / "tune.json"))
    tuned = autotune(plan, cache=cache, timer=fake_timer, records=records)
    assert len(calls) >= 2
    swept = {(rec["variant"], tuple(rec["grid"])) for rec in records
             if rec["variant"].startswith("alg2_bound_driven")}
    p_grids = {g for _, g in swept}
    assert len(p_grids) > 1, f"joint sweep must vary p, saw {p_grids}"
    assert any(v == "alg2_bound_driven_fused" for v, _ in swept)
    # cache entry for the fused winner: a second autotune is a pure hit
    assert tuned.variant in ("alg2_bound_driven", "alg2_bound_driven_fused")
    assert alg2_two_grid_executable(64, 4, tuned.grid, tuned.q_grid)
    if tuned.variant == "alg2_bound_driven_fused":
        assert two_grid_axis_split(tuned.grid, tuned.q_grid) is not None

    def no_timer(fn):
        raise AssertionError("cache hit must skip measurement")

    again = autotune(plan_nystrom(64, 4, P=8, machine=CPU), cache=cache,
                     timer=no_timer)
    assert (again.variant, again.grid, again.q_grid) == \
        (tuned.variant, tuned.grid, tuned.q_grid)
    assert cache.hits >= 1


# ---------------------------------------------------------------------------
# execution: the bitwise property matrix + HLO byte audit (8 fake devices)
# ---------------------------------------------------------------------------

def test_fused_bitwise_matrix():
    """Fused == cross-mesh bitwise across (p, q) pairs x kinds x dtypes x
    backends, including a shape the ideal grids do NOT divide (the snap
    path); == nystrom_reference to the f32 summation-order bound for
    p2==1 ∧ q1==1 pairs."""
    run_distributed(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import (nystrom_reference, nystrom_two_grid,
                        nystrom_two_grid_fused, nystrom_auto,
                        nystrom_second_stage_two_grid,
                        nystrom_second_stage_two_grid_fused)
from repro.plan import plan_nystrom, PRESETS
CPU = PRESETS["cpu"]
assert len(jax.devices()) == 8

seed, n, r = 5, 64, 16
X = jax.random.normal(jax.random.key(2), (n, 8)); S = X @ X.T
Bref, Cref = nystrom_reference(S, seed, r)
# against the single-device reference the contract is the f32
# summation-order bound (tests/f32_bounds.py): a per-shard GEMM and the
# whole-matrix GEMM add the same products in different orders on XLA:CPU
from f32_bounds import (assert_orders_agree, gemm_diff_bound,
                        nystrom_diff_bounds)
from repro.core.sketch import omega_tile
om = omega_tile(seed, 0, 0, n, r)
dB, dC = nystrom_diff_bounds(S, om)

# (p, q) matrix: every pair matches the cross-mesh path bit for bit
# (grouped-axis collectives reduce in the same order); pairs that split
# no contraction (p2==1, q1==1) also match the single-device reference.
for (p, q) in [((8,1,1), (1,1,8)), ((8,1,1), (1,2,4)), ((4,1,2), (1,4,2)),
               ((2,1,4), (1,8,1)), ((8,1,1), (2,1,4)), ((2,2,2), (4,2,1)),
               ((1,2,4), (2,2,2))]:
    Bx, Cx = nystrom_two_grid(S, seed, r, p=p, q=q)
    Bf, Cf = nystrom_two_grid_fused(S, seed, r, p=p, q=q)
    assert np.array_equal(np.asarray(Bx), np.asarray(Bf)), (p, q)
    assert np.array_equal(np.asarray(Cx), np.asarray(Cf)), (p, q)
    if p[1] == 1 and q[0] == 1:
        assert_orders_agree(Bf, Bref, dB, f"B {(p, q)}")
        assert_orders_agree(Cf, Cref, dC, f"C {(p, q)}")
print("OK pair matrix")

# kinds x backends on a genuinely two-grid pair
for kind in ("normal", "uniform", "rademacher"):
    for backend in ("jnp", "pallas"):
        Bx, Cx = nystrom_two_grid(S, seed, r, p=(8,1,1), q=(1,2,4),
                                  kind=kind, backend=backend)
        Bf, Cf = nystrom_two_grid_fused(S, seed, r, p=(8,1,1), q=(1,2,4),
                                        kind=kind, backend=backend)
        assert np.array_equal(np.asarray(Bx), np.asarray(Bf)), (kind, backend)
        assert np.array_equal(np.asarray(Cx), np.asarray(Cf)), (kind, backend)
print("OK kinds x backends")

# bf16 inputs (f32 accumulation contract), both backends
Sb = S.astype(jnp.bfloat16)
for backend in ("jnp", "pallas"):
    Bx, Cx = nystrom_two_grid(Sb, seed, r, p=(8,1,1), q=(1,1,8),
                              backend=backend)
    Bf, Cf = nystrom_two_grid_fused(Sb, seed, r, p=(8,1,1), q=(1,1,8),
                                    backend=backend)
    assert Bf.dtype == jnp.bfloat16 and Cf.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(Bx, np.float32),
                          np.asarray(Bf, np.float32)), backend
    assert np.array_equal(np.asarray(Cx, np.float32),
                          np.asarray(Cf, np.float32)), backend
print("OK bf16")

# a shape the IDEAL bound-driven grids do not divide: the snapped pair
# still runs fused and bitwise (n=48, r=12 — non-power-of-two dims)
n2, r2 = 48, 12
X2 = jax.random.normal(jax.random.key(4), (n2, 6)); S2 = X2 @ X2.T
from repro.core.grid import select_two_grid_executable
p2_, q2_, exact = select_two_grid_executable(n2, r2, 8)
assert not exact    # genuinely snapped
for (p, q) in [(p2_, q2_), ((8,1,1), (2,1,4))]:
    Bx, Cx = nystrom_two_grid(S2, seed, r2, p=p, q=q)
    Bf, Cf = nystrom_two_grid_fused(S2, seed, r2, p=p, q=q)
    assert np.array_equal(np.asarray(Bx), np.asarray(Bf)), (p, q)
    assert np.array_equal(np.asarray(Cx), np.asarray(Cf)), (p, q)
print("OK non-divisible snap")

# planner-chosen fused plan: Plan.execute IS the direct call, and
# nystrom_auto prefers the fused path
pf = plan_nystrom(n, r, P=8, machine=CPU, variant="bound_driven_fused")
assert pf.variant == "alg2_bound_driven_fused" and pf.executable
B, C = pf.execute(S, seed=seed)
Bd, Cd = nystrom_two_grid_fused(S, seed, r, p=pf.grid, q=pf.q_grid)
assert np.array_equal(np.asarray(B), np.asarray(Bd))
assert np.array_equal(np.asarray(C), np.asarray(Cd))
Ba, Ca, _, v = nystrom_auto(S, seed, r, variant="bound_driven")
assert v == "bound_driven"
assert_orders_agree(Ca, Cref, dC, "auto C")
print("OK plan dispatch")

# the fused standalone second stage (streamed-Y finalize form) matches the
# cross-mesh second stage bitwise for row-sharded B
for q in [(1, 2, 4), (2, 1, 4), (1, 1, 8)]:
    Bx, Cx = nystrom_second_stage_two_grid(Bref, seed, r, q)
    Bf, Cf = nystrom_second_stage_two_grid_fused(Bref, seed, r, q)
    assert np.array_equal(np.asarray(Bx), np.asarray(Bf)), q
    assert np.array_equal(np.asarray(Cx), np.asarray(Cf)), q
print("OK fused second stage")

# error paths stay loud
try:
    nystrom_two_grid_fused(S, seed, 7, p=(8,1,1), q=(1,1,8))
    raise SystemExit("expected ValueError")
except ValueError as e:
    assert "not divisible" in str(e)
print("OK errors")
""", timeout=900)


def test_hlo_redistribute_byte_audit():
    """The compiled fused program's Redistribute moves <= nr/P words per
    processor and the collective schedule contains EXACTLY the planner's
    predicted stage collectives plus the one in-program resharding —
    nothing unplanned, and no host-mediated transfer in the hot path."""
    run_distributed(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core.grid import two_grid_shared_mesh
from repro.core.nystrom import (_nystrom_two_grid_fused_prog, _spec_entry)
from repro.core.rng import seed_keys
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.roofline.hlo import collective_bytes_of
assert len(jax.devices()) == 8

seed, n, r = 5, 64, 16
S = jax.random.normal(jax.random.key(2), (n, n)); S = S @ S.T / n
ITEM = 4   # f32

REDIST = ("all-to-all", "collective-permute")
STAGE = {"all-gather", "reduce-scatter"}

for (p, q) in [((8,1,1), (1,1,8)), ((8,1,1), (2,1,4)), ((8,1,1), (1,2,4)),
               ((2,2,2), (4,2,1))]:
    shared = two_grid_shared_mesh(p, q)
    assert shared is not None, (p, q)
    pa = shared.p_axes
    A = jax.device_put(S, NamedSharding(
        shared.mesh, P(_spec_entry(pa[0]), _spec_entry(pa[1] + pa[2]))))
    keys = jnp.stack(seed_keys(seed))
    fn = _nystrom_two_grid_fused_prog(r, shared, "normal", "jnp", None)
    cb = collective_bytes_of(fn.lower(A, keys).compile().as_text())

    # (1) every collective kind is planned: the Alg.-1 / stage-2
    # All-Gathers and Reduce-Scatters, plus the one in-program resharding
    assert set(cb.by_kind) <= STAGE | set(REDIST), (p, q, cb)
    n_ag = int(p[2] > 1) + int(q[1] > 1)
    n_rs = int(p[1] > 1) + int(q[0] > 1)
    assert cb.counts.get("all-gather", 0) == n_ag, (p, q, cb)
    assert cb.counts.get("reduce-scatter", 0) == n_rs, (p, q, cb)

    # (2) the Redistribute itself: each resharding hop carries at most the
    # §5.2 bound nr/P words per processor (B's full per-device shard)
    budget = n * r / 8 * ITEM
    for kind in REDIST:
        if kind in cb.by_kind:
            assert cb.by_kind[kind] <= budget + 1e-6, (p, q, kind, cb)
    assert sum(cb.counts.get(k, 0) for k in REDIST) <= 2, (p, q, cb)

    # (3) the §5.2 Redistribute lives inside the ONE compiled executable:
    # either as its own all-to-all / collective-permute, or absorbed into
    # the adjacent stage collectives by the partitioner (only possible
    # because it IS in-program — the whole point of the fused form)
    assert any(k in cb.by_kind for k in REDIST) or (n_ag + n_rs) >= 1, \
        (p, q, cb)
print("OK audit")

# the pure regime-1 pair: the redistribute is the ONLY collective and its
# bytes are exactly the per-device B shard
shared = two_grid_shared_mesh((8,1,1), (1,1,8))
A = jax.device_put(S, NamedSharding(
    shared.mesh, P(_spec_entry(shared.p_axes[0]), None)))
keys = jnp.stack(seed_keys(seed))
fn = _nystrom_two_grid_fused_prog(r, shared, "normal", "jnp", None)
cb = collective_bytes_of(fn.lower(A, keys).compile().as_text())
assert cb.total == n * r / 8 * ITEM, cb
print("OK exact regime-1 bytes")
""", timeout=900)


def test_shared_mesh_stage1_bitwise_and_fallback():
    """(c): the shared mesh preserves the p-grid device assignment — stage
    1 alone on it is bitwise Alg. 1 on the standalone p-grid mesh — and an
    incompatible pair demonstrably falls back to the cross-mesh dispatcher
    (counted via monkeypatch on 6 devices, where (2,3,1)/(3,2,1) cannot
    share a device order)."""
    run_distributed(r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import rand_matmul, make_grid_mesh
from repro.core.grid import two_grid_shared_mesh, two_grid_axis_split
from repro.core.sketch import input_sharding
from repro.core.compat import shard_map
from repro.core.nystrom import _axes_index, _spec_entry
from repro.kernels.local import sketch_block
from repro.core.rng import seed_keys
assert len(jax.devices()) == 8

seed, n, r = 11, 64, 16
A = jax.random.normal(jax.random.key(1), (n, n))

for (p, q) in [((8,1,1), (1,1,8)), ((2,1,4), (1,8,1)), ((2,2,2), (4,2,1))]:
    shared = two_grid_shared_mesh(p, q)
    # no silent reorder: the shared mesh holds the SAME devices in the
    # SAME flat order as both standalone grid meshes
    assert list(shared.mesh.devices.flat) \
        == list(make_grid_mesh(*p).devices.flat) \
        == list(make_grid_mesh(*q).devices.flat), (p, q)

    # stage 1 alone, on the shared mesh's p-axis groups
    mesh, (pa1, pa2, pa3) = shared.mesh, shared.p_axes
    p1, p2, p3 = p
    keys = jnp.stack(seed_keys(seed))
    blk_rows, blk_cols = n // p2, r // p3

    def stage1(a_blk):
        j = _axes_index(mesh, pa2)
        k = _axes_index(mesh, pa3)
        a_ij = a_blk if p3 == 1 else jax.lax.all_gather(
            a_blk, pa3, axis=1, tiled=True)
        b = sketch_block(a_ij, keys, blk_cols, row0=j * blk_rows,
                         col0=k * blk_cols, kind="normal")
        if p2 == 1:
            return b
        return jax.lax.psum_scatter(b, pa2, scatter_dimension=0, tiled=True)

    in_spec = P(_spec_entry(pa1), _spec_entry(pa2 + pa3))
    out_spec = P(_spec_entry(pa1 + pa2), _spec_entry(pa3))
    Ash = jax.device_put(A, NamedSharding(mesh, in_spec))
    Bshared = jax.jit(shard_map(stage1, mesh=mesh, in_specs=in_spec,
                                out_specs=out_spec))(Ash)

    mesh_p = make_grid_mesh(*p)
    Bp = rand_matmul(jax.device_put(A, input_sharding(mesh_p)), seed, r,
                     mesh_p)
    assert np.array_equal(np.asarray(Bshared), np.asarray(Bp)), (p, q)
print("OK stage-1 bitwise on shared mesh")

# fallback: an incompatible pair routes through the cross-mesh dispatcher
import repro.core.nystrom as nys
devices6 = jax.devices()[:6]
assert two_grid_axis_split((2,3,1), (3,2,1)) is None
n6, r6 = 36, 6
X6 = jax.random.normal(jax.random.key(7), (n6, 4)); S6 = X6 @ X6.T
calls = []
orig = nys.nystrom_two_grid
def counting(*a, **kw):
    calls.append((kw.get("p"), kw.get("q")))
    return orig(*a, **kw)
nys.nystrom_two_grid = counting
try:
    Bf, Cf = nys.nystrom_two_grid_fused(S6, 5, r6, p=(2,3,1), q=(3,2,1),
                                        devices=devices6)
finally:
    nys.nystrom_two_grid = orig
assert calls == [((2,3,1), (3,2,1))], calls
Bx, Cx = orig(S6, 5, r6, p=(2,3,1), q=(3,2,1), devices=devices6)
assert np.array_equal(np.asarray(Bf), np.asarray(Bx))
assert np.array_equal(np.asarray(Cf), np.asarray(Cx))
# and a compatible pair never touches the cross-mesh dispatcher
calls.clear()
nys.nystrom_two_grid = counting
try:
    nys.nystrom_two_grid_fused(S6, 5, r6, p=(6,1,1), q=(1,1,6),
                               devices=devices6)
finally:
    nys.nystrom_two_grid = orig
assert calls == [], calls
print("OK fallback counted")
""", timeout=900)
