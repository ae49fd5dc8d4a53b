"""The Nyström pair's cell, ``nystrom.dense56k``: its reference, its work,
whole small runs on the CPU, and faults planted under them.

The runs go through the planner's fused plan, as on the chip: the machine
model is the CPU's with the fused kernels allowed, which then run in
interpret mode.  Faults planted in the program:

  * C from a second stage run on another Omega seed;
  * an answer altered where it is produced (one entry of C);
  * the second stage at one bfloat16 pass (``Precision.DEFAULT`` on a
    TPU, written out here, where the CPU's DEFAULT is full float32);
  * half of the batch left out (the second half of B's rows).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, pair, peaks, work
from bench import reference as R
from bench import trace as T
from bench.tests import helpers

from repro.kernels.ops import sketch_matmul_launch, sketch_t_matmul_launch
from repro.obs import metrics as obs_metrics
from repro.plan.planner import DEFAULT_BLOCKS

CELL = "nystrom.dense56k"
SECONDS = 0.3


@pytest.fixture
def fused(monkeypatch):
    """The cell at its small size, through the planner's fused Nyström
    plan, as on a TPU."""
    from repro.plan import PRESETS, model
    monkeypatch.setitem(helpers.SMALL, "dense56k", {"n": 256, "r": 32})
    cpu = dataclasses.replace(PRESETS["cpu"], supports_pallas=True)
    monkeypatch.setattr(model, "probe_machine", lambda device=None: cpu)


@pytest.fixture
def v5e_peaks(monkeypatch):
    monkeypatch.setattr("bench.peaks.peaks", lambda kind: {
        "flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})


def test_c_reference_is_omega_t_a_omega():
    A = jax.random.normal(jax.random.key(1), (64, 64), jnp.float32)
    B, C = pair.nystrom(A, 13, 8)
    om = np.asarray(R.omega(R.key_array(13), 64, 8, 0), np.float64)
    a = np.asarray(A, np.float64)
    np.testing.assert_allclose(np.asarray(B), a @ om, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(C), om.T @ (a @ om), rtol=1e-5,
                               atol=1e-3)


def test_work_of_a_pair():
    w = pair.work(57344, 256)
    n, r = 57344, 256
    assert w["flops"] == 2 * n * n * r + 2 * n * r * r
    assert w["bytes"] == 4 * (n * n + n * r + r * r)
    assert w["stage2_flops"] == 2 * n * r * r
    assert w["stage2_bytes"] == 4 * (n * r + r * r)
    v5e = peaks.peaks("TPU v5 lite")
    whole = work.least_seconds(w, v5e, 1)
    assert whole["bound"] == "memory"
    assert whole["seconds"] == pytest.approx(16.13e-3, rel=1e-3)
    stage2 = work.least_seconds({"flops": w["stage2_flops"],
                                 "bytes": w["stage2_bytes"]}, v5e, 1)
    assert stage2["bound"] == "memory"
    assert stage2["seconds"] == pytest.approx(72.0e-6, rel=1e-3)


def _with_kernel_ops(monkeypatch):
    """Give the CPU's trace the device operations a chip's would have:
    each ``bench.call`` runs the two kernels back to back on device 0.
    Returns the list the loaded traces are appended to."""
    loaded = []
    orig = T.load

    def load(path):
        tr = orig(path)
        ops = []
        for s in tr.spans:
            if s.name == "bench.call":
                mid = s.start + 0.6 * (s.end - s.start)
                ops.append(T.Op(s.start, mid, "%sketch_a_omega.1 = "
                                "f32[256,32]{1,0} custom-call(%p0)"))
                ops.append(T.Op(mid, s.end - 0.1 * (s.end - s.start),
                                "%sketch_omega_t_b.1 = f32[32,32]{1,0} "
                                "custom-call(%p1)"))
        tr.ops[0] = ops
        loaded.append(tr)
        return tr
    monkeypatch.setattr(T, "load", load)
    return loaded


def test_sound_pair_run_is_correct(fused, v5e_peaks, monkeypatch):
    line, out = helpers.run_small(CELL, seconds=SECONDS)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"call_ms", "setup_s"}
    assert "variant pallas_fused, backend pallas" in out.notes[0]
    assert out.work == pair.work(256, 32)
    loaded = _with_kernel_ops(monkeypatch)
    prev = obs_metrics.set_metrics(None)        # a registry of its own
    try:
        line, out = helpers.run_small(CELL, seconds=SECONDS, trace=True)
    finally:
        obs_metrics.set_metrics(prev)
    assert line["correct"] is True
    got = line["metrics"]
    assert {"call_roofline", "idle_share.call", "omega_useful.call",
            "omega_t_b_roofline.nystrom", "plan_host_ms.nystrom"} <= set(got)
    assert got["plan_host_ms.nystrom"]["value"] > 0
    assert got["plan_host_ms.nystrom"]["unit"] == "ms"
    (tr,) = loaded
    win = tr.window()
    stage2 = sum(s for name, s in T.op_seconds(tr, win, [0])
                 if name.startswith("sketch_omega_t_b"))
    least = 4 * (256 * 32 + 32 * 32) / 819e9
    assert got["omega_t_b_roofline.nystrom"]["value"] == pytest.approx(
        100 * least / (stage2 / out.calls))
    assert got["omega_t_b_roofline.nystrom"]["unit"] == "%"
    a = sketch_matmul_launch(256, 256, 32, **DEFAULT_BLOCKS)
    t = sketch_t_matmul_launch(256, 32, 32)
    assert got["omega_useful.call"]["value"] == pytest.approx(
        100 * (a.needed + t.needed) / (a.generated + t.generated))


def test_omega_t_b_roofline_reads_nothing_without_the_kernel():
    from bench import spec
    read = spec.load_module(spec.metric_path("omega_t_b_roofline.nystrom"),
                            "bench_metric_omega_t_b_roofline").read
    out = harness.Outcome(setup_s=1.0, attempted=3, failed=0, end_to_end={},
                          checks={}, memory_peak_bytes=0, calls=3,
                          work=pair.work(256, 32))
    ops = {0: [T.Op(0, 10, "%sketch_a_omega.1 = f32[256,32] custom-call()")]}
    tr = T.Trace(ops, [T.Span(0, 100, "bench.window")])
    v5e = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    assert read(harness.Reading(out, tr, tr.window(), [0], v5e, 1)) is None
    assert read(harness.Reading(out, None, None, [0], v5e, 1)) is None
    out.work = work.sketch(256, 256, 32)            # the sketch cell's work
    assert read(harness.Reading(out, tr, tr.window(), [0], v5e, 1)) is None


def test_plan_host_ms_nystrom_is_the_median_of_the_nystrom_plan():
    from bench import spec
    read = spec.load_module(spec.metric_path("plan_host_ms.nystrom"),
                            "bench_metric_plan_host_ms_nystrom").read
    out = harness.Outcome(setup_s=1.0, attempted=3, failed=0, end_to_end={},
                          checks={}, memory_peak_bytes=0, calls=3)
    r = harness.Reading(out, None, None, [0], {}, 1)
    prev = obs_metrics.set_metrics(None)
    try:
        reg = obs_metrics.get_metrics()
        assert read(r) is None                          # no series
        hist = reg.histogram("plan_execute_seconds")
        hist.observe(0.9, task="sketch", variant="pallas_fused")
        assert read(r) is None                          # no Nyström series
        # two set-up calls, then the window's calls
        for s in (0.9, 0.002, 1e-4, 3e-4, 2e-4, 4e-4, 2.5e-4):
            hist.observe(s, task="nystrom", variant="pallas_fused")
        hist.observe(5e-3, task="nystrom", variant="local_xla")
        assert read(r) == pytest.approx(0.3)
    finally:
        obs_metrics.set_metrics(prev)


def _plan_fault(monkeypatch, alter):
    from repro.plan.planner import Plan
    orig = Plan.execute

    def execute(self, A, seed=0, devices=None):
        return alter(*orig(self, A, seed, devices))
    monkeypatch.setattr(Plan, "execute", execute)


def _stage2_fault(monkeypatch, stage2):
    from repro.kernels import ops
    orig = ops.sketch_t_matmul

    def sketch_t_matmul(B, *, seed, r, **kw):
        return stage2(orig, B, seed, r, kw)
    monkeypatch.setattr(ops, "sketch_t_matmul", sketch_t_matmul)


def other_seed(monkeypatch):
    _stage2_fault(monkeypatch, lambda orig, B, seed, r, kw:
                  orig(B, seed=seed + 1, r=r, **kw))


def one_bf16_pass(monkeypatch):
    def stage2(orig, B, seed, r, kw):
        om = R.omega(R.key_array(seed), B.shape[0], r, 0)
        return jnp.matmul(om.T.astype(jnp.bfloat16), B.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    _stage2_fault(monkeypatch, stage2)


def one_entry(monkeypatch):
    _plan_fault(monkeypatch, lambda B, C: (
        B, C.at[3, 5].add(1e-3 * jnp.max(jnp.abs(C)))))


def half_rows(monkeypatch):
    _plan_fault(monkeypatch, lambda B, C: (
        B.at[B.shape[0] // 2:].set(0.0), C))


@pytest.mark.parametrize("plant", [other_seed, one_entry, one_bf16_pass,
                                   half_rows],
                         ids=["other_omega_seed", "altered_answer",
                              "stage2_one_bf16_pass", "half_batch"])
def test_pair_fault_is_caught(fused, monkeypatch, plant):
    plant(monkeypatch)
    line, _ = helpers.run_small(CELL, seconds=SECONDS)
    assert line["correct"] is False


def test_pair_readings_put_the_program_below_its_control(fused):
    """The readings the limits are set from: the program's pass them, and
    every control fails them through the harness's own comparison: the
    three-pass product in both stages, and in the second stage alone.
    (Here each control is written out; on the chip ``control`` and
    ``control_stage2`` are ``Precision.HIGH``.)"""
    from bench import control_pair
    cell = helpers.small_cell(CELL)
    for seed in (1, 2, 3):
        got = control_pair.pair_readings(cell, seed, jax.devices()[:1])
        assert set(got) == {"program", "control", "control_emulated",
                            "control_stage2", "control_stage2_emulated"}
        assert harness.passes(harness.judge(got["program"], cell))
        for name in ("control", "control_emulated", "control_stage2",
                     "control_stage2_emulated"):
            assert not harness.passes(harness.judge(got[name], cell)), name
            for check, value in got[name].items():
                assert got["program"][check] < value
        assert got["control"] == got["control_emulated"]
        assert got["control_stage2"] == got["control_stage2_emulated"]
