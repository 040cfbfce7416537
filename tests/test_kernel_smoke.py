"""Deterministic tier-1 kernel/oracle parity smoke (interpret mode).

The parametrized sweeps in test_kernels.py are nightly (`slow`) and
test_kernel_properties.py degrades to seeded replay without hypothesis —
this file is the per-PR floor: one fixed small shape per Pallas kernel
(`window_features`, `holt_winters`), seconds to run, so a kernel
regression is caught in the same CI pass that introduced it.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


def test_window_features_small_shape_parity():
    rng = np.random.default_rng(42)
    x = rng.gamma(2.0, 10.0, size=(8, 60)).astype(np.float32)
    x[0, :] = 0.0                        # all-zero window
    x[4, 30] = 1e5                       # spike outlier
    got = np.asarray(ops.window_features(jnp.asarray(x), interpret=True))
    want = np.asarray(ref.window_features_ref(jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("t", (120, 1100))
def test_holt_winters_small_shape_parity(t):
    """t=1100 spans three time blocks of the kernel grid, so the level /
    trend / season state carried in VMEM scratch across blocks is
    exercised."""
    rng = np.random.default_rng(7)
    y = rng.gamma(2.0, 5.0, size=(4, t)).astype(np.float32)
    got = np.asarray(ops.holt_winters(jnp.asarray(y), period=24,
                                      interpret=True))
    want = np.asarray(ref.holt_winters_ref(jnp.asarray(y), period=24))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
