"""Planner subsystem (repro.plan).

Contract pillars (ISSUE acceptance criteria):
  (a) ``plan_sketch`` / ``plan_nystrom`` never predict below the Theorem 2/3
      lower bounds, in every regime;
  (b) when a shard_map variant wins, its analytic words equal the paper's
      closed forms ``alg1_bandwidth_words`` / ``alg2_bandwidth_words``
      exactly, and the Alg.-1 grid agrees with ``select_matmul_grid``;
  (c) below the paper's crossover (Thm. 2 regime 1, P <= n1) the planner
      picks the zero-communication local-regenerate variant;
  (d) ``Plan.execute`` is bitwise-identical to calling the underlying entry
      point directly (single-device here; multi-device in a subprocess);
  (e) the autotune cache round-trips: first call measures + persists,
      second call is a pure cache hit (the timer must not run).
"""
import json
import math
import os

import numpy as np
import pytest

import jax

from _hypothesis_compat import given, settings, st
from dist_helper import run_distributed

from repro.core import sketch_reference
from repro.core.grid import (
    alg1_bandwidth_words,
    alg2_bandwidth_words,
    select_matmul_grid,
)
from repro.core.lower_bounds import matmul_lower_bound, nystrom_lower_bound
from repro.plan import (
    AutotuneCache,
    PRESETS,
    autotune,
    explain,
    plan_nystrom,
    plan_sketch,
    plan_stream,
    regime_sweep,
    shape_bucket,
)

CPU = PRESETS["cpu"]


# ---------------------------------------------------------------------------
# (a) predictions never beat the lower bound; (b) tight where the paper is
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(n1e=st.integers(0, 6), n2e=st.integers(2, 8),
       re_=st.integers(0, 5), Pe=st.integers(0, 9))
def test_plan_sketch_never_below_bound(n1e, n2e, re_, Pe):
    n1, n2, r, P = 2 ** n1e, 2 ** n2e, 2 ** re_, 2 ** Pe
    if r >= n2 or P > n1 * n2 * r:
        return
    plan = plan_sketch(n1, n2, r, P=P, machine=CPU)
    lb = matmul_lower_bound(n1, n2, r, P)
    assert plan.lower_bound_words == lb
    assert plan.predicted_words >= lb - 1e-9, (plan.variant, plan.grid)
    # every scored candidate respects the bound too (it is a LOWER bound)
    for c in plan.candidates:
        if c.variant != "alg1_communicating":
            assert c.cost.words >= lb - 1e-9, c


@settings(max_examples=40, deadline=None)
@given(ne=st.integers(4, 9), re_=st.integers(1, 6), Pe=st.integers(0, 8))
def test_plan_nystrom_never_below_bound(ne, re_, Pe):
    n, r, P = 2 ** ne, 2 ** re_, 2 ** Pe
    if r >= n:
        return
    plan = plan_nystrom(n, r, P=P, machine=CPU)
    lb = nystrom_lower_bound(n, r, P)
    assert plan.lower_bound_words == lb
    assert plan.predicted_words >= lb - 1e-9, (plan.variant, plan.grid)
    # every executable candidate — including the §5.3 bound-driven general
    # two-grid pair — respects the Theorem 3 floor on its own grids
    for c in plan.candidates:
        if c.executable:
            assert c.cost.words >= lb - 1e-9, (c.variant, c.grid, c.q_grid)


def test_alg1_choice_equals_closed_form_and_grid_selector():
    """(b): in each Theorem-2 regime the shard_map winner's words are the
    paper's closed form on its own grid; the grid agrees with
    ``select_matmul_grid`` whenever that grid is executable, and is the
    min-words *executable* factorization otherwise.

    (The §4.3 ideal grids of regimes 2/3 put p1 = n1, so B's
    P((p1, p2), p3) layout would have to split one-row blocks p2 ways —
    analytically tight but not runnable by Alg. 1's reduce-scatter; the
    planner must snap to what the program can execute.)
    """
    from repro.core.grid import factorizations_3d
    from repro.plan.planner import _alg1_executable

    cases = [
        (64, 256, 16, 32),     # regime 1: P <= n1
        (16, 1024, 8, 64),     # regime 2: n1 < P <= n1n2/r
        (256, 64, 16, 4096),   # regime 3: P > n1n2/r
    ]
    for (n1, n2, r, P) in cases:
        plan = plan_sketch(n1, n2, r, P=P, machine=CPU)
        g = select_matmul_grid(n1, n2, r, P)
        assert plan.variant == "alg1"
        assert plan.regime == g.regime
        assert plan.executable
        assert _alg1_executable(n1, n2, r, plan.grid)
        # chosen cost IS the paper's closed form on the chosen grid
        assert plan.predicted_words == alg1_bandwidth_words(n1, n2, r,
                                                            *plan.grid)
        if _alg1_executable(n1, n2, r, g.shape):
            # selector's grid runs -> exact agreement (and tightness)
            assert plan.grid == g.shape, (plan.grid, g.shape)
            assert math.isclose(plan.predicted_words,
                                matmul_lower_bound(n1, n2, r, P),
                                abs_tol=1e-9)
        else:
            # snapped: optimal among what the program can execute
            best = min(alg1_bandwidth_words(n1, n2, r, *c)
                       for c in factorizations_3d(P)
                       if _alg1_executable(n1, n2, r, c))
            assert plan.predicted_words == best
    # regime 1's ideal grid is always executable on divisible shapes, so
    # the agreement branch above is exercised there
    assert plan_sketch(64, 256, 16, P=32, machine=CPU).grid == (32, 1, 1)


def test_alg2_choice_equals_closed_form():
    for P in (4, 8, 16):
        plan = plan_nystrom(4096, 256, P=P, machine=CPU)
        assert plan.variant in ("alg2_no_redist", "alg2_redist")
        assert plan.predicted_words == alg2_bandwidth_words(
            4096, 256, plan.grid, plan.q_grid)


def test_one_chip_nystrom_plans_the_fused_kernels():
    """On one v5e the chip-filling pair (A 13.15 GB) plans the fused
    variant with the default blocks: it reads no Omega words."""
    from repro.plan.planner import DEFAULT_BLOCKS
    plan = plan_nystrom(57344, 256, P=1, machine=PRESETS["tpu_v5e"])
    assert (plan.variant, plan.backend) == ("pallas_fused", "pallas")
    assert plan.blocks == DEFAULT_BLOCKS and plan.executable
    local = [c for c in plan.candidates if c.variant == "local_xla"]
    assert local[0].cost.hbm_words - plan.predicted_hbm_words == \
        2 * 57344 * 256


def test_zero_communication_regime_below_crossover():
    """(c): P <= n1 -> the (P, 1, 1) local-regenerate grid, zero words."""
    for P in (2, 8, 32, 64):
        plan = plan_sketch(64, 512, 16, P=P, machine=CPU)
        assert plan.regime == 1
        assert plan.grid == (P, 1, 1)
        assert plan.predicted_words == 0.0
        assert plan.lower_bound_words == 0.0


def test_nystrom_crossover_bandwidth_dominated():
    """At paper scale the redist/no_redist choice follows the Fig.-7 rule
    (at tiny sizes latency legitimately dominates; not asserted there)."""
    n, r = 49152, 4096          # n/r = 12
    below = plan_nystrom(n, r, P=4, machine=CPU)
    above = plan_nystrom(n, r, P=64, machine=CPU)
    assert below.variant == "alg2_no_redist"
    # above the crossover the planner abandons no_redist for the redist
    # all-to-all family — since PR 5 in its fused single-jit form: the
    # regime-1 bound-driven pair IS the redist layout p=(P,1,1), q=(1,1,P),
    # with the §5.2 Redistribute in-program at the layout min-cut < nr/P
    assert above.variant == "alg2_bound_driven_fused"
    assert (above.grid, above.q_grid) == ((64, 1, 1), (1, 1, 64))
    # and the words honor the closed forms on both sides
    assert below.predicted_words == alg2_bandwidth_words(n, r, (4, 1, 1),
                                                         (4, 1, 1))
    from repro.plan.model import alg2_fused_cost
    assert above.predicted_words == alg2_fused_cost(
        n, r, (64, 1, 1), (1, 1, 64)).words
    assert above.predicted_words < alg2_bandwidth_words(n, r, (64, 1, 1),
                                                        (1, 1, 64))
    # the plain redist closed form still backs the cross-mesh candidates
    redist = [c for c in above.candidates if c.variant == "alg2_redist"]
    assert redist and redist[0].cost.words == alg2_bandwidth_words(
        n, r, (64, 1, 1), (1, 1, 64))


def test_infeasible_shape_yields_analytic_only_plan():
    plan = plan_sketch(7, 7, 3, P=4, machine=CPU)   # nothing divides
    assert not plan.executable
    with pytest.raises(ValueError):
        plan.execute(np.zeros((7, 7), np.float32))


# ---------------------------------------------------------------------------
# (d) execute == direct call (single device; multi-device in subprocess)
# ---------------------------------------------------------------------------

def test_probe_machine_maps_known_devices_and_refuses_others():
    """Known device kinds and the CPU map to their presets; any other
    device raises instead of borrowing another chip's peaks."""
    from types import SimpleNamespace
    from repro.plan import PRESETS, probe_machine
    dev = lambda platform, kind: SimpleNamespace(platform=platform,
                                                 device_kind=kind)
    assert probe_machine(dev("tpu", "TPU v5 lite")) is PRESETS["tpu_v5e"]
    assert probe_machine(dev("tpu", "TPU v4")) is PRESETS["tpu_v4"]
    assert probe_machine(dev("cpu", "cpu")) is PRESETS["cpu"]
    for d in (dev("tpu", "TPU v6 lite"), dev("gpu", "NVIDIA H100")):
        with pytest.raises(ValueError, match="no machine preset"):
            probe_machine(d)
    assert probe_machine() is PRESETS["cpu"]    # this process: CPU


def test_execute_local_bitwise():
    n1, n2, r, seed = 48, 64, 8, 11
    A = jax.random.normal(jax.random.key(0), (n1, n2))
    plan = plan_sketch(n1, n2, r, P=1, machine=CPU)
    assert plan.variant == "local_xla"
    np.testing.assert_array_equal(
        np.asarray(plan.execute(A, seed=seed)),
        np.asarray(sketch_reference(A, seed, r)))


def test_execute_stream_local_bitwise():
    n1, n2, r, seed = 48, 64, 8, 3
    A = jax.random.normal(jax.random.key(2), (n1, n2))
    plan = plan_stream(n1, n2, r, P=1, chunk_rows=16, machine=CPU)
    st_acc = plan.execute(A, seed=seed)
    np.testing.assert_array_equal(
        np.asarray(st_acc.sketch),
        np.asarray(sketch_reference(A, seed, r)))


def test_execute_pallas_interpret_matches_reference():
    n1, n2, r, seed = 32, 32, 8, 2
    A = jax.random.normal(jax.random.key(4), (n1, n2))
    plan = plan_sketch(n1, n2, r, P=1, machine=CPU, allow_pallas=True)
    assert plan.variant == "pallas_fused"   # fewer HBM words than local_xla
    B = plan.execute(A, seed=seed)
    np.testing.assert_allclose(np.asarray(B),
                               np.asarray(sketch_reference(A, seed, r)),
                               rtol=2e-5, atol=2e-4)


def test_execute_distributed_bitwise():
    run_distributed(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import rand_matmul, make_grid_mesh, nystrom_reference
from repro.core.sketch import input_sharding
from repro.core.nystrom import nystrom_no_redist, nystrom_redist
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.plan import plan_sketch, plan_nystrom, PRESETS
CPU = PRESETS["cpu"]
assert len(jax.devices()) == 8

seed, n1, n2, r = 7, 16, 64, 8
A = jax.random.normal(jax.random.key(1), (n1, n2))
plan = plan_sketch(n1, n2, r, P=8, machine=CPU)
assert plan.variant == "alg1", plan.variant
B = plan.execute(A, seed=seed)
mesh = make_grid_mesh(*plan.grid)
B_direct = rand_matmul(jax.device_put(A, input_sharding(mesh)),
                       seed, r, mesh)
assert np.array_equal(np.asarray(B), np.asarray(B_direct))
print("OK alg1 execute bitwise")

n, rn = 64, 16
X = jax.random.normal(jax.random.key(4), (n, 8)); S = X @ X.T
pn = plan_nystrom(n, rn, P=8, machine=CPU)
assert pn.variant in ("alg2_no_redist", "alg2_redist"), pn.variant
B2, C2 = pn.execute(S, seed=5)
mesh1 = Mesh(np.asarray(jax.devices()), ("x",))
Sx = jax.device_put(S, NamedSharding(mesh1, P("x", None)))
fn = nystrom_no_redist if pn.variant == "alg2_no_redist" else nystrom_redist
Bd, Cd = fn(Sx, 5, rn, mesh1, axis="x")
assert np.array_equal(np.asarray(B2), np.asarray(Bd))
assert np.array_equal(np.asarray(C2), np.asarray(Cd))
print("OK alg2 execute bitwise")

# wiring: rand_matmul_auto plan path == direct
from repro.core import rand_matmul_auto
B3, g, mesh3 = rand_matmul_auto(A, seed, r, grid="plan")
assert g.shape == plan.grid
assert np.array_equal(np.asarray(B3), np.asarray(B_direct))
print("OK rand_matmul_auto plan path")

# grid="auto" snaps to an executable factorization when the ideal §4.3
# grid does not divide the shape (12 % 8 != 0 -> not (8,1,1))
A12 = jax.random.normal(jax.random.key(2), (12, 50))
B4, g4, _ = rand_matmul_auto(A12, seed, 8, grid="auto")
assert 12 % g4.p1 == 0 and 50 % (g4.p2 * g4.p3) == 0 and 8 % g4.p3 == 0
from repro.core import sketch_reference as sref
assert np.allclose(np.asarray(B4), np.asarray(sref(A12, seed, 8)),
                   atol=1e-4)
print("OK grid=auto divisibility snap")

# wiring: service + sharded stream accept a Plan
from repro.serve import make_sketch_service
from repro.stream import StreamConfig, ShardedStreamingSketch
svc = make_sketch_service(plan=plan)
assert svc.mesh is not None
sid = svc.open(StreamConfig(n1=n1, n2=n2, r=r, seed=seed, corange=False))
svc.update(sid, jnp.asarray(A))
assert np.array_equal(np.asarray(svc.sketch(sid)), np.asarray(B_direct))
st = ShardedStreamingSketch(StreamConfig(n1=n1, n2=n2, r=r, seed=seed),
                            plan)
st.update(jnp.asarray(A))
assert np.array_equal(np.asarray(st.sketch), np.asarray(B_direct))
print("OK plan-driven service + stream")
""")


# ---------------------------------------------------------------------------
# (e) autotune: measured refinement + cache round trip with a fake timer
# ---------------------------------------------------------------------------

def test_autotune_cache_round_trip(tmp_path):
    path = os.path.join(str(tmp_path), "tune.json")
    plan = plan_sketch(64, 128, 16, P=1, machine=CPU)

    calls = []

    def fake_timer(fn):
        calls.append(fn)
        return 1e-3 * len(calls)      # first measured candidate wins

    cache = AutotuneCache(path)
    tuned = autotune(plan, cache=cache, timer=fake_timer)
    assert calls, "timer must run on a cache miss"
    assert cache.misses == 1 and cache.hits == 0
    assert tuned.measured_seconds == pytest.approx(1e-3)
    assert tuned.executable

    # persisted, versioned, atomic
    from repro.plan.autotune import CACHE_VERSION
    with open(path) as f:
        data = json.load(f)
    assert data["version"] == CACHE_VERSION
    assert len(data["entries"]) == 1

    # second invocation (fresh cache object): pure hit, timer must NOT run
    def forbidden_timer(fn):
        raise AssertionError("timer ran on a cache hit")

    cache2 = AutotuneCache(path)
    tuned2 = autotune(plan, cache=cache2, timer=forbidden_timer)
    assert cache2.hits == 1 and cache2.misses == 0
    assert tuned2.variant == tuned.variant
    assert tuned2.blocks == tuned.blocks
    assert tuned2.measured_seconds == tuned.measured_seconds

    # stale-version cache files are ignored, not crashed on
    with open(path, "w") as f:
        json.dump({"version": -1, "entries": {"x": {}}}, f)
    assert len(AutotuneCache(path)) == 0


def test_autotune_measures_real_execution(tmp_path):
    """With the default wall-clock timer the tuned plan still executes
    bitwise-identically (the tuner only reorders, never rewrites math)."""
    n1, n2, r, seed = 32, 64, 8, 9
    A = jax.random.normal(jax.random.key(3), (n1, n2))
    plan = plan_sketch(n1, n2, r, P=1, machine=CPU)
    tuned = autotune(plan, cache=os.path.join(str(tmp_path), "t.json"))
    assert tuned.measured_seconds is not None and tuned.measured_seconds > 0
    np.testing.assert_array_equal(
        np.asarray(tuned.execute(A, seed=seed)),
        np.asarray(sketch_reference(A, seed, r)))


def test_autotune_cache_hit_revalidates_against_exact_dims(tmp_path):
    """(16,64,8) and (9,50,8) share one pow2 bucket key, but the cached
    (8,1,1)-style decision does not divide the second shape — the hit must
    fall back to measuring (or analytic), never execute a bad grid."""
    path = os.path.join(str(tmp_path), "tune.json")
    good = plan_sketch(16, 64, 8, P=8, machine=CPU)
    from repro.plan import cache_key
    bad = plan_sketch(9, 50, 8, P=8, machine=CPU)
    assert cache_key(good) == cache_key(bad)   # the collision under test
    assert good.executable and not bad.executable

    autotune(good, cache=path, timer=lambda fn: 1e-3)
    calls = []

    def counting_timer(fn):
        calls.append(fn)
        return 1e-3

    tuned_bad = autotune(bad, cache=path, timer=counting_timer)
    # no executable candidates exist for (9,50,8): nothing measured, and
    # crucially the cached (dividing) grid was NOT stamped onto the plan
    assert not calls
    assert not tuned_bad.executable
    with pytest.raises(ValueError):
        tuned_bad.execute(np.zeros((9, 50), np.float32))


def test_autotune_rescores_predictions_for_the_winner(tmp_path):
    """The tuned plan's predicted words must describe the tuned grid, not
    the pre-tune analytic favorite (explain/bound audit correctness)."""
    from repro.core.grid import alg1_bandwidth_words as w

    def timer_prefers_last(fn):
        timer_prefers_last.n += 1
        return 1.0 / timer_prefers_last.n      # later candidate "faster"

    timer_prefers_last.n = 0
    plan = plan_sketch(16, 64, 8, P=8, machine=CPU)
    run = {"tuned": autotune(plan, cache=None, timer=timer_prefers_last)}
    tuned = run["tuned"]
    assert tuned.predicted_words == w(16, 64, 8, *tuned.grid)
    # and a cache round-trip preserves the rescored numbers
    path = os.path.join(str(tmp_path), "t.json")
    autotune(plan, cache=path, timer=lambda fn: 1e-3)
    hit = autotune(plan, cache=path,
                   timer=lambda fn: pytest.fail("hit must not measure"))
    assert hit.predicted_words == w(16, 64, 8, *hit.grid)


def test_stream_plan_carries_corange():
    n1, n2, r = 32, 48, 8
    M_ = (jax.random.normal(jax.random.key(1), (n1, 4))
          @ jax.random.normal(jax.random.key(2), (4, n2)))
    plan = plan_stream(n1, n2, r, P=1, chunk_rows=16, corange=True,
                       machine=CPU)
    acc = plan.execute(M_, seed=3)
    assert acc.corange_sketch is not None
    acc.reconstruct(rank=4)       # must not raise (W is tracked)


def test_entry_points_reject_analytic_only_plans():
    from repro.core import nystrom_auto, rand_matmul_auto
    bad = plan_sketch(7, 7, 3, P=4, machine=CPU)
    with pytest.raises(ValueError, match="analytic-only"):
        rand_matmul_auto(np.zeros((7, 7), np.float32), 0, 3, P_procs=4,
                         plan=bad)
    bad_n = plan_nystrom(30, 7, P=8, machine=CPU)
    assert not bad_n.executable
    with pytest.raises(ValueError, match="analytic-only"):
        nystrom_auto(np.zeros((30, 30), np.float32), 0, 7, plan=bad_n)


def test_shape_bucket():
    assert [shape_bucket(x) for x in (1, 2, 3, 64, 65, 1000)] == \
        [1, 2, 4, 64, 128, 1024]


# ---------------------------------------------------------------------------
# explain / reports
# ---------------------------------------------------------------------------

def test_explain_mentions_regime_bound_and_candidates():
    plan = plan_sketch(16, 1024, 8, P=64, machine=CPU)
    text = explain(plan)
    assert "Theorem 2 regime 2" in text
    assert "alg1" in text and "lower bound" in text
    assert "alg1_communicating" in text          # the Fig.-3 contrast row
    assert str(plan.grid) in text

    pn = plan_nystrom(4096, 256, P=8, machine=CPU)
    tn = explain(pn)
    assert "Theorem 3" in tn and "crossover" in tn


def test_regime_sweep_table():
    table = regime_sweep(plan_sketch, (4096, 4096, 256),
                         [1, 8, 65536], machine=CPU)
    lines = table.splitlines()
    assert len(lines) == 5                       # header + sep + 3 rows
    assert "variant" in lines[0]


# ---------------------------------------------------------------------------
# (g) machine-model calibration from grid-sweep residuals (autotune.py)
# ---------------------------------------------------------------------------

def test_calibrate_machine_model_recovers_alpha_beta():
    """Times synthesized from a known (alpha, beta) over a communicating
    grid sweep must be fit back to those values (within lstsq noise)."""
    import dataclasses
    from repro.plan import calibrate_machine_model
    from repro.plan import model as M

    true = dataclasses.replace(CPU, alpha=3e-5, byte_bw=2e9)
    recs = []
    for grid in ((8, 1, 1), (2, 2, 2), (1, 4, 2), (4, 2, 1), (1, 1, 8)):
        c = M.alg1_cost(64, 128, 16, grid)
        recs.append({"words": c.words, "messages": c.messages,
                     "flops": c.flops, "hbm_words": c.hbm_words,
                     "itemsize": 4, "seconds": c.seconds(true, 4)})
    fit = calibrate_machine_model(recs, base=CPU)
    assert abs(fit.alpha - true.alpha) / true.alpha < 0.05
    assert abs(fit.byte_bw - true.byte_bw) / true.byte_bw < 0.05
    assert fit.name.endswith("_calibrated")
    # compute/memory rates come from the base preset, untouched
    assert fit.flop_rate == CPU.flop_rate and fit.hbm_bw == CPU.hbm_bw


def test_calibrate_machine_model_degenerate_keeps_base():
    """Zero-communication records carry no network information — the base
    terms must survive unchanged instead of fitting noise."""
    from repro.plan import calibrate_machine_model
    recs = [{"words": 0.0, "messages": 0.0, "flops": 1e6,
             "hbm_words": 1e4, "itemsize": 4, "seconds": 1e-4}]
    fit = calibrate_machine_model(recs, base=CPU)
    assert fit.alpha == CPU.alpha and fit.byte_bw == CPU.byte_bw


def test_sweep_records_round_trip(tmp_path):
    """sweep_records measures every candidate with the injected timer and
    the JSON round-trips through save_sweep/load_sweep."""
    from repro.plan import load_sweep, save_sweep, sweep_records

    plan = plan_sketch(32, 64, 8, P=1, machine=CPU)
    recs = sweep_records(plan, timer=lambda fn: 1e-3, machine=CPU)
    assert recs and all(r["seconds"] == 1e-3 for r in recs)
    assert all({"words", "flops", "hbm_words", "itemsize"} <= set(r)
               for r in recs)
    path = os.path.join(str(tmp_path), "sweep.json")
    save_sweep(recs, path)
    assert load_sweep(path) == recs


def test_autotune_records_and_presets(tmp_path):
    """autotune(records=...) captures one record per timed candidate, and
    a preset entry short-circuits measurement on a cache miss (then seeds
    the writable cache)."""
    from repro.plan import AutotuneCache, cache_key

    plan = plan_sketch(64, 128, 16, P=1, machine=CPU)
    recs = []
    tuned = autotune(plan, timer=lambda fn: 1e-3, records=recs,
                     presets={})
    assert tuned.measured_seconds == 1e-3
    assert len(recs) >= 1 and all("seconds" in r for r in recs)

    # preset hit: no measurement, decision restored, cache seeded
    key = cache_key(plan)
    preset = {key: {"variant": "local_xla", "grid": None, "q_grid": None,
                    "blocks": None, "chunk_rows": None, "backend": "jnp",
                    "source": "analytic", "seconds": None}}

    def forbidden_timer(fn):
        raise AssertionError("timer ran despite a preset hit")

    cache = AutotuneCache(os.path.join(str(tmp_path), "t.json"))
    got = autotune(plan, cache=cache, timer=forbidden_timer, presets=preset)
    assert got.variant == "local_xla"
    assert cache.get(key) is not None       # preset copied into the cache


def test_autotune_cache_entry_preserves_backend(tmp_path):
    """A tuned pallas-backend decision round-trips through the cache with
    its backend and block shape."""
    import dataclasses
    from repro.plan.autotune import _entry_from_plan, _plan_from_entry

    plan = plan_sketch(64, 128, 16, P=8, machine=CPU)
    tuned = dataclasses.replace(plan, backend="pallas", grid=(8, 1, 1),
                                blocks={"bm": 128, "bn": 128, "bk": 256},
                                measured_seconds=1e-3, executable=True)
    entry = _entry_from_plan(tuned)
    assert entry["backend"] == "pallas" and entry["source"] == "measured"
    restored = _plan_from_entry(plan, entry)
    assert restored.backend == "pallas"
    assert restored.blocks == {"bm": 128, "bn": 128, "bk": 256}
