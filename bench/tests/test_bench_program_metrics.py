"""The per-layer metrics read from the program's own instruments: the
process-wide metrics registry of ``repro.obs``, filled here by hand."""
import contextlib

import pytest

from bench import harness, spec
from bench.tests import helpers  # noqa: F401  (puts the program on the path)

from repro.obs import metrics as obs_metrics


def reader(name):
    return spec.load_module(spec.metric_path(name),
                            "bench_metric_" + name.replace(".", "_"))


def reading():
    out = harness.Outcome(setup_s=1.0, attempted=45, failed=0,
                          end_to_end={"call_ms": 234.0}, checks={},
                          memory_peak_bytes=0, calls=43)
    return harness.Reading(out, None, None, [0], {}, 1)


@contextlib.contextmanager
def registry():
    prev = obs_metrics.set_metrics(None)
    try:
        yield obs_metrics.get_metrics()
    finally:
        obs_metrics.set_metrics(prev)


def test_plan_host_ms_is_the_median_of_the_sketch_plan():
    read = reader("plan_host_ms.call").read
    with registry() as reg:
        assert read(reading()) is None                  # no series
        hist = reg.histogram("plan_execute_seconds")
        hist.observe(0.9, task="nystrom", variant="pallas_fused")
        assert read(reading()) is None                  # no sketch series
        # two set-up calls, then the window's calls
        for s in (0.9, 0.002, 1e-4, 3e-4, 2e-4, 4e-4, 2.5e-4):
            hist.observe(s, task="sketch", variant="pallas_fused")
        hist.observe(5e-3, task="sketch", variant="local_xla")
        assert read(reading()) == pytest.approx(0.3)


def test_omega_useful_reads_the_dense32k_grid():
    from repro.kernels.ops import sketch_matmul_launch
    from repro.plan import plan_sketch
    read = reader("omega_useful.call").read
    cfg = spec.resolve("sketch.dense32k").config
    n, r = cfg["n"], cfg["r"]
    plan = plan_sketch(n, n, r, P=1, allow_pallas=True)
    assert plan.variant == "pallas_fused"
    with registry() as reg:
        assert read(reading()) is None
        launch = sketch_matmul_launch(n, n, r, **plan.blocks)
        for _ in range(45):
            reg.counter("omega_entries_generated_total").inc(
                launch.generated, kernel="sketch_a_omega")
            reg.counter("omega_entries_needed_total").inc(
                launch.needed, kernel="sketch_a_omega")
        assert read(reading()) == 0.78125
