"""§5.3 bound-driven general two-grid Nyström (core.nystrom.nystrom_two_grid).

Contract pillars (ISSUE acceptance criteria):
  (a) ``plan_nystrom`` returns an ``executable=True`` ``alg2_bound_driven``
      candidate whose ``Plan.execute`` runs on 8 fake devices and is bitwise
      ``nystrom_two_grid`` called directly — and, for a (p, q) pair whose
      contractions are never split (p2 == 1, q1 == 1), bitwise
      ``nystrom_reference`` with p != q;
  (b) predicted words for every *executable* candidate stay at or above the
      Theorem 3 lower bound across swept (n, r, P);
  (c) the snap policy mirrors Alg. 1's ``grid="auto"``: the ideal
      bound-driven pair when it divides, else the min-words executable pair
      of factorizations, else an analytic-only candidate.
"""
import math

import pytest

from _hypothesis_compat import given, settings, st
from dist_helper import run_distributed

from repro.core.grid import (
    alg2_bandwidth_words,
    alg2_two_grid_executable,
    factorizations_3d,
    select_nystrom_grids,
    select_two_grid_executable,
)
from repro.core.lower_bounds import nystrom_lower_bound
from repro.plan import PRESETS, explain, plan_nystrom

CPU = PRESETS["cpu"]


# ---------------------------------------------------------------------------
# (b) planner audit invariants across the new variant
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(ne=st.integers(4, 9), re_=st.integers(1, 6), Pe=st.integers(1, 8))
def test_bound_driven_candidate_never_below_bound(ne, re_, Pe):
    n, r, P = 2 ** ne, 2 ** re_, 2 ** Pe
    if r >= n:
        return
    plan = plan_nystrom(n, r, P=P, machine=CPU)
    lb = nystrom_lower_bound(n, r, P)
    bd = [c for c in plan.candidates if c.variant == "alg2_bound_driven"]
    assert bd, "bound_driven candidate must always be scored for P > 1"
    for c in bd:
        assert c.cost.words >= lb - 1e-9, (c.grid, c.q_grid, c.cost.words)
        # the candidate prices at the paper's closed form on its own grids
        assert math.isclose(c.cost.words,
                            alg2_bandwidth_words(n, r, c.grid, c.q_grid),
                            rel_tol=1e-12)
        if c.executable:
            assert alg2_two_grid_executable(n, r, c.grid, c.q_grid)
    # every executable candidate — not just the winner — respects the bound
    for c in plan.candidates:
        if c.executable:
            assert c.cost.words >= lb - 1e-9, c


@settings(max_examples=60, deadline=None)
@given(ne=st.integers(3, 9), re_=st.integers(1, 6), Pe=st.integers(1, 8))
def test_select_two_grid_snap_policy(ne, re_, Pe):
    """(c): exact == the §5.3 ideal pair; snapped == min-words executable."""
    n, r, P = 2 ** ne, 2 ** re_, 2 ** Pe
    if r >= n:
        return
    got = select_two_grid_executable(n, r, P)
    ideal = select_nystrom_grids(n, r, P, variant="bound_driven")
    if got is None:
        # nothing divides: no executable pair may exist among factorizations
        assert not any(
            alg2_two_grid_executable(n, r, pc, qc)
            for pc in factorizations_3d(P) for qc in factorizations_3d(P))
        return
    p, q, exact = got
    assert p[0] * p[1] * p[2] == P and q[0] * q[1] * q[2] == P
    assert alg2_two_grid_executable(n, r, p, q)
    if exact:
        assert (p, q) == (tuple(ideal.p), tuple(ideal.q))
    else:
        best = min(alg2_bandwidth_words(n, r, pc, qc)
                   for pc in factorizations_3d(P)
                   for qc in factorizations_3d(P)
                   if alg2_two_grid_executable(n, r, pc, qc))
        assert math.isclose(alg2_bandwidth_words(n, r, p, q), best,
                            rel_tol=1e-12)


def test_bound_driven_is_only_executable_variant_when_1d_cannot_run():
    """r % P != 0 rules the 1-D variants out, but the two-grid pair runs —
    the planner can now dispatch in regimes that were analytic-only.  The
    single-jit fused form wins over the cross-mesh form whenever the pair
    admits a shared mesh (fewer Redistribute words, no host hop)."""
    plan = plan_nystrom(64, 4, P=8, machine=CPU)   # r=4 < P=8
    assert plan.executable
    assert plan.variant == "alg2_bound_driven_fused"
    assert plan.grid != plan.q_grid
    cross = [c for c in plan.candidates if c.variant == "alg2_bound_driven"]
    assert cross and any(c.executable for c in cross)
    one_d = [c for c in plan.candidates
             if c.variant in ("alg2_no_redist", "alg2_redist")]
    assert one_d and not any(c.executable for c in one_d)


def test_plan_nystrom_variant_forcing():
    pn = plan_nystrom(64, 16, P=8, machine=CPU, variant="bound_driven")
    assert pn.variant == "alg2_bound_driven" and pn.executable
    assert pn.grid != pn.q_grid
    # the un-forced candidates stay in the audit trail
    assert {c.variant for c in pn.candidates} >= {
        "alg2_no_redist", "alg2_redist", "alg2_bound_driven"}
    assert plan_nystrom(64, 16, P=8, machine=CPU,
                        variant="redist").variant == "alg2_redist"
    with pytest.raises(ValueError, match="needs P > 1"):
        plan_nystrom(64, 16, P=1, machine=CPU, variant="bound_driven")
    with pytest.raises(ValueError, match="unknown variant"):
        plan_nystrom(64, 16, P=8, machine=CPU, variant="fastest")


def test_explain_reports_two_grid_redistribution():
    pn = plan_nystrom(64, 4, P=8, machine=CPU, variant="bound_driven")
    text = explain(pn)
    assert "general two-grid" in text
    assert "Redistribute" in text
    assert str(pn.q_grid) in text


def test_indivisible_two_grid_is_analytic_only():
    plan = plan_nystrom(30, 7, P=8, machine=CPU)
    bd = [c for c in plan.candidates if c.variant == "alg2_bound_driven"]
    assert bd and not bd[0].executable
    assert "no (p, q) factorization" in bd[0].note


def test_autotune_sweeps_q_grids_for_bound_driven():
    from repro.plan import autotune
    plan = plan_nystrom(64, 4, P=8, machine=CPU)    # bound_driven wins
    assert plan.variant == "alg2_bound_driven_fused"
    seen = []

    def fake_timer(fn):
        seen.append(fn)
        return 1e-3 * len(seen)

    tuned = autotune(plan, cache=None, timer=fake_timer)
    assert len(seen) >= 2, "(p, q) sweep must measure more than one option"
    assert tuned.variant in ("alg2_bound_driven", "alg2_bound_driven_fused")
    assert tuned.q_grid is not None
    assert alg2_two_grid_executable(64, 4, tuned.grid, tuned.q_grid)
    # rescoring describes the tuned pair, not the pre-tune favorite
    from repro.plan.model import alg2_fused_cost
    want = (alg2_fused_cost(64, 4, tuned.grid, tuned.q_grid).words
            if tuned.variant == "alg2_bound_driven_fused"
            else alg2_bandwidth_words(64, 4, tuned.grid, tuned.q_grid))
    assert math.isclose(tuned.predicted_words, want, rel_tol=1e-12)


# ---------------------------------------------------------------------------
# (a) execution on 8 fake devices: bitwise contracts
# ---------------------------------------------------------------------------

def test_two_grid_execution_bitwise():
    run_distributed(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import (nystrom_reference, nystrom_two_grid, nystrom_auto,
                        nystrom_second_stage_two_grid)
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

# (p, q) pairs that never split a contraction (p2 == 1, q1 == 1) match
# the single-device reference to the summation-order bound — including
# p != q pairs that nystrom_general's shared-axis mesh cannot express.
for (p, q) in [((8,1,1), (1,1,8)), ((8,1,1), (1,2,4)), ((4,1,2), (1,4,2)),
               ((8,1,1), (1,4,2)), ((2,1,4), (1,8,1))]:
    B, C = nystrom_two_grid(S, seed, r, p=p, q=q)
    assert_orders_agree(B, Bref, dB, f"B {(p, q)}")
    assert_orders_agree(C, Cref, dC, f"C {(p, q)}")
print("OK bitwise-safe pairs")

# split-contraction pairs (p2 > 1 or q1 > 1) reorder partial sums: close,
# not bitwise — same contract as the other shard_map variants.
for (p, q) in [((8,1,1), (2,1,4)), ((2,2,2), (4,2,1)), ((1,2,4), (2,2,2))]:
    B, C = nystrom_two_grid(S, seed, r, p=p, q=q)
    assert np.allclose(np.asarray(B), np.asarray(Bref), atol=1e-3), (p, q)
    assert np.allclose(np.asarray(C), np.asarray(Cref), atol=1e-2), (p, q)
print("OK split pairs close")

# acceptance: an executable=True alg2_bound_driven candidate whose
# Plan.execute matches nystrom_reference with p != q (regime-1 ideal
# grids p=(8,1,1), q=(1,1,8) keep both contractions whole)...
pn = plan_nystrom(n, r, P=8, machine=CPU, variant="bound_driven")
assert pn.variant == "alg2_bound_driven" and pn.executable
assert pn.grid != pn.q_grid, (pn.grid, pn.q_grid)
B, C = pn.execute(S, seed=seed)
assert_orders_agree(B, Bref, dB, "plan B")
assert_orders_agree(C, Cref, dC, "plan C")
# ...and Plan.execute IS the direct call
Bd, Cd = nystrom_two_grid(S, seed, r, p=pn.grid, q=pn.q_grid)
assert np.array_equal(np.asarray(B), np.asarray(Bd))
assert np.array_equal(np.asarray(C), np.asarray(Cd))
print("OK plan bound_driven vs reference, bitwise vs direct call")

# regime 2 (r < P): a genuinely two-grid pair q=(2,1,4) the 1-D variants
# cannot run at all (r % P != 0); the single-jit fused form wins in auto
# mode and execute == the cross-mesh direct call, bitwise.
pn2 = plan_nystrom(n, 4, P=8, machine=CPU)
assert pn2.variant == "alg2_bound_driven_fused" and pn2.executable
assert pn2.q_grid not in (pn2.grid, (1, 1, 8)), pn2.q_grid
B2, C2 = pn2.execute(S, seed=seed)
B2d, C2d = nystrom_two_grid(S, seed, 4, p=pn2.grid, q=pn2.q_grid)
assert np.array_equal(np.asarray(B2), np.asarray(B2d))
assert np.array_equal(np.asarray(C2), np.asarray(C2d))
B2r, C2r = nystrom_reference(S, seed, 4)
assert np.allclose(np.asarray(B2), np.asarray(B2r), atol=1e-3)
assert np.allclose(np.asarray(C2), np.asarray(C2r), atol=1e-2)
print("OK regime-2 bound_driven execute == direct")

# nystrom_auto dispatches both the explicit variant and a bound-driven plan
Ba, Ca, mesh_q, v = nystrom_auto(S, seed, r, variant="bound_driven")
assert v == "bound_driven"
assert_orders_agree(Ca, Cref, dC, "auto C")
Bp, Cp, _, vp = nystrom_auto(S, seed, r, plan=pn)
assert vp == "bound_driven"
assert_orders_agree(Cp, Cref, dC, "auto plan C")
print("OK nystrom_auto bound_driven")

# the second stage alone consumes any row-sharded B (streaming finalize)
B3, C3 = nystrom_second_stage_two_grid(Bref, seed, r, (1, 2, 4))
assert_orders_agree(C3, Cref, gemm_diff_bound(om.T, Bref), "stage 2")
print("OK standalone second stage")

# streamed Y -> bound_driven finalize, vs the one-shot reference
from repro.core.sketch import make_grid_mesh
from repro.stream import StreamConfig, SketchService
svc = SketchService(mesh=make_grid_mesh(8, 1, 1))
sid = svc.open(StreamConfig(n1=n, n2=n, r=r, seed=seed, corange=False))
for (i0, i1) in [(0, 32), (32, 64)]:
    svc.update(sid, jnp.zeros((n, n)).at[i0:i1].set(S[i0:i1]))
Bs, Cs = svc.nystrom(sid, variant="bound_driven")
assert np.allclose(np.asarray(Bs), np.asarray(Bref), atol=1e-4)
assert np.allclose(np.asarray(Cs), np.asarray(Cref), atol=1e-3)
print("OK stream bound_driven finalize")

# indivisible grids fail loudly, not with an opaque XLA error
try:
    nystrom_two_grid(S, seed, 7, p=(8,1,1), q=(1,1,8))
    raise SystemExit("expected ValueError")
except ValueError as e:
    assert "not divisible" in str(e)
print("OK error paths")
""")
