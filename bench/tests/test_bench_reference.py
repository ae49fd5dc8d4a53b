"""The plain references: Omega as the paper's generator defines it, the
dense reference, and the numbers compared."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as R
from bench.tests import helpers  # noqa: F401  (puts the program on the path)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345, 2 ** 40 + 99])
@pytest.mark.parametrize("salt,row0", [(0, 0), (1, 24)])
def test_omega_is_the_programs_generator_bit_for_bit(seed, salt, row0):
    from repro.core.sketch import omega_tile
    got = R.omega(R.key_array(seed), 40, 16, salt, row0=row0)
    want = omega_tile(seed, row0, 0, 40, 16, "normal", jnp.float32,
                      salt=salt)
    assert jnp.array_equal(got, want)


def test_omega_entries_are_standardised():
    om = np.asarray(R.omega(R.key_array(3), 512, 64, 0))
    assert abs(om.mean()) < 0.02 and abs(om.std() - 1.0) < 0.02
    assert np.abs(om).max() <= 6.0


def test_dense_reference_is_a_times_omega():
    A = jax.random.normal(jax.random.key(0), (64, 64), jnp.float32)
    B = R.dense(A, 11, 8, block=16)
    om = np.asarray(R.omega(R.key_array(11), 64, 8, 0), np.float64)
    a = np.asarray(A, np.float64)
    np.testing.assert_allclose(np.asarray(B), a @ om, rtol=1e-5, atol=1e-4)


def test_three_pass_control_is_measurably_less_precise():
    a = jax.random.normal(jax.random.key(2), (128, 1024), jnp.float32)
    b = jax.random.normal(jax.random.key(3), (1024, 32), jnp.float32)
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    hi = R.worst_row(R.dot(a, b, "highest"), jnp.asarray(exact, jnp.float32))
    c3 = R.worst_row(R.dot(a, b, "high3"), jnp.asarray(exact, jnp.float32))
    assert hi < 1e-6 < 2e-6 < c3 < 1e-4
    with pytest.raises(ValueError):
        R.dot(a, b, "default")


def test_gaps_scale_by_the_larger_of_own_and_median_norm():
    ref = jnp.asarray([[3.0, 4.0], [0.0, 0.0], [6.0, 8.0]])
    x = ref.at[1, 0].set(0.5)
    gaps = np.asarray(R.row_gaps(x, ref))
    assert gaps.tolist() == pytest.approx([0.0, 0.1, 0.0])
    assert R.worst_row(x, ref) == pytest.approx(0.1)
