"""The yardstick's arithmetic: work counts, roofline shares, peaks, and the
seeded inputs."""
import numpy as np
import pytest

from bench import gen, peaks, work
from bench.tests import helpers  # noqa: F401  (puts the program on the path)


def test_work_counts_from_shapes():
    s = work.sketch(32768, 32768, 256)
    assert s["flops"] == 2 * 32768 * 32768 * 256
    assert s["bytes"] == 4 * (32768 * 32768 + 32768 * 256)
    assert work.OPS["sketch"]({"n": 64, "r": 8}) == work.sketch(64, 64, 8)


def test_least_time_takes_the_binding_bound():
    v5e = peaks.peaks("TPU v5 lite")
    one = work.least_seconds(work.sketch(32768, 32768, 256), v5e, 1)
    assert one["bound"] == "memory"
    assert one["seconds"] == pytest.approx(4.3e9 / 819e9, rel=0.01)
    assert one["compute_s"] == pytest.approx(5.497e11 / 197e12, rel=1e-3)
    four = work.least_seconds(work.sketch(57344, 57344, 256), v5e, 4)
    assert four["seconds"] == pytest.approx(
        4 * (57344 ** 2 + 57344 * 256) / (4 * 819e9))
    compute_bound = work.least_seconds({"flops": 1e15, "bytes": 1.0}, v5e, 1)
    assert compute_bound["bound"] == "compute"


def test_roofline_share_arithmetic():
    """call_roofline reads least time over device time per call."""
    from bench import harness, spec
    from bench import trace as T
    reader = spec.load_module(spec.metric_path("call_roofline"), "roof")
    w = work.sketch(32768, 32768, 256)
    least = work.least_seconds(w, peaks.peaks("TPU v5 lite"), 1)["seconds"]
    calls, per_call_ns = 40, 250e6
    ops = {0: [T.Op(i * 300e6, i * 300e6 + per_call_ns, "k")
               for i in range(calls)]}
    tr = T.Trace(ops, [T.Span(0, calls * 300e6, "bench.window")])
    out = harness.Outcome(setup_s=1.0, attempted=calls, failed=0,
                          end_to_end={}, checks={}, memory_peak_bytes=0,
                          calls=calls, work=w)
    r = harness.Reading(out, tr, tr.window(), [0],
                        peaks.peaks("TPU v5 lite"), 1)
    assert reader.read(r) == pytest.approx(100 * least / 0.25)
    idle = spec.load_module(spec.metric_path("idle_share.call"), "idle")
    assert idle.read(r) == pytest.approx(100 * (1 - 250 / 300))
    r.trace = None
    assert reader.read(r) is None and idle.read(r) is None


def test_peaks_known_and_unknown():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert peaks.peaks("TPU v5e") == v5e
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v4")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_kernel_matrix_is_seeded_symmetric_and_positive_definite():
    a = np.asarray(gen.kernel_matrix(2 ** 31 + 5, 96), np.float64)
    assert np.array_equal(a, np.asarray(gen.kernel_matrix(2 ** 31 + 5, 96)))
    assert not np.array_equal(a, np.asarray(gen.kernel_matrix(6, 96)))
    np.testing.assert_allclose(a, a.T, rtol=1e-6, atol=0)
    np.testing.assert_allclose(np.diag(a), 1.0, rtol=1e-6)
    assert np.all((a > 0) & (a <= 1)) and np.linalg.eigvalsh(a).min() > 0
    import jax
    high, low = (np.asarray(jax.random.key_data(gen.jax_key(s)))
                 for s in (2 ** 40 + 3, 3))
    assert not np.array_equal(high, low)       # the high word counts
