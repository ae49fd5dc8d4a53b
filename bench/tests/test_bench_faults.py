"""A run's ``correct`` comes out false when the timed path is broken
underneath it, and when the control takes the program's place.

Each test drives a whole run at a small size on the CPU (everything after
the harness's look for the chip) with one fault planted in the program:

  * half of the batch left out (the second half of B's rows);
  * an answer altered where it is produced (one entry of B).

The one-shot cell holds no state from call to call and have no exchange
between chips, so those two faults do not arise in it.  The control is
the plain reference computed with the three-pass bfloat16 product, judged
against the cell's own limits.  A run whose window compiles gives no
result.
"""
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests import helpers

SECONDS = 0.3


@pytest.fixture
def v5e_peaks(monkeypatch):
    monkeypatch.setattr("bench.peaks.peaks", lambda kind: {
        "flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})


def test_sound_sketch_run_is_correct(v5e_peaks):
    line, _ = helpers.run_small("sketch.dense32k", seconds=SECONDS)
    assert line["correct"] is True
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "setup_steps", "checks"]
    assert set(line["metrics"]) == {"call_ms", "setup_s"}
    line, _ = helpers.run_small("sketch.dense32k", seconds=SECONDS,
                                trace=True)
    assert line["correct"] is True and "breakdown" in line
    assert list(line)[-1] == "checks"


def _plan_fault(monkeypatch, alter):
    from repro.plan.planner import Plan
    orig = Plan.execute

    def execute(self, A, seed=0, devices=None):
        return alter(orig(self, A, seed, devices))
    monkeypatch.setattr(Plan, "execute", execute)


def half_rows(B):
    return B.at[B.shape[0] // 2:].set(0.0)


def one_entry(B):
    return B.at[3, 5].add(1e-3 * jnp.max(jnp.abs(B)))


@pytest.mark.parametrize("alter", [half_rows, one_entry],
                         ids=["half_batch", "altered_answer"])
def test_sketch_fault_is_caught(monkeypatch, alter):
    _plan_fault(monkeypatch, alter)
    line, _ = helpers.run_small("sketch.dense32k", seconds=SECONDS)
    assert line["correct"] is False


@pytest.mark.parametrize("workload", ["sketch.dense32k"])
def test_dense_control_fails_the_limits(workload):
    import jax
    from bench import control
    cell = helpers.small_cell(workload)
    for seed in (1, 2, 3):
        got = control.oneshot_readings(cell, seed, jax.devices()[:1])
        assert harness.passes(harness.judge(got["program"], cell))
        assert not harness.passes(harness.judge(got["control"], cell))


def test_set_up_is_timed_step_by_step(v5e_peaks):
    line, out = helpers.run_small("sketch.dense32k", seconds=SECONDS)
    steps = line["setup_steps"]
    assert list(steps) == ["inputs_s", "first_call_s", "warm_call_s"]
    assert all(v > 0 for v in steps.values())
    assert sum(steps.values()) <= out.setup_s
    assert list(line)[-2:] == ["setup_steps", "checks"]


def test_a_window_that_compiles_gives_no_result(monkeypatch, capsys):
    """Each call compiles a program of its own: the run exits non-zero and
    prints no result line."""
    import jax
    from bench import run
    from repro.plan.planner import Plan
    orig = Plan.execute

    def execute(self, A, seed=0, devices=None):
        return jax.jit(lambda b: b + 0.0)(orig(self, A, seed, devices))
    monkeypatch.setattr(Plan, "execute", execute)
    ctx, out = helpers.drive("sketch.dense32k", seconds=SECONDS)
    assert out.window_compiles > 0
    capsys.readouterr()
    assert run.report(ctx, out) == 3
    got = capsys.readouterr()
    assert got.out == "" and "compiles inside the measured window" in got.err
