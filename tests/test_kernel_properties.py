"""Property-based kernel/oracle parity (via tests/_hypo_compat: real
hypothesis when installed, seeded replay otherwise): interpret-mode
Pallas kernels vs their pure-jnp `core` oracles across random window
lengths, batch sizes, periods, and deliberately non-multiple-of-tile
shapes — the regimes the fixed parametrized sweeps in test_kernels.py
don't reach."""
import numpy as np
import jax.numpy as jnp
from _hypo_compat import example, given, settings, st

from repro.kernels import ops, ref


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=1, max_value=13),
       st.integers(min_value=40, max_value=96),
       st.integers(min_value=3, max_value=24),
       st.integers(min_value=3, max_value=8))
def test_holt_winters_parity_any_shape(b, t, period, tile_b):
    """Kernel == oracle for arbitrary (batch, time, period, tile) combos,
    including batches that don't divide the sublane tile."""
    rng = np.random.default_rng(b * 7919 + t * 31 + period)
    y = rng.gamma(2.0, 5.0, size=(b, t)).astype(np.float32)
    got = np.asarray(ops.holt_winters(jnp.asarray(y), period=period,
                                      tile_b=tile_b, interpret=True))
    want = np.asarray(ref.holt_winters_ref(jnp.asarray(y), period=period))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=1, max_value=37),
       st.integers(min_value=33, max_value=72),
       st.integers(min_value=5, max_value=48))
def test_window_features_parity_any_shape(n, w, tile_n):
    """Fused feature kernel == oracle for arbitrary window counts/lengths
    and tile sizes that don't divide the batch; includes all-zero and
    spike-contaminated windows."""
    rng = np.random.default_rng(n * 104729 + w)
    x = rng.gamma(2.0, 10.0, size=(n, w)).astype(np.float32)
    x[0, :] = 0.0                       # all-zero window
    x[n // 2, w // 2] = 1e5             # spike outlier
    got = np.asarray(ops.window_features(jnp.asarray(x), tile_n=tile_n,
                                         interpret=True))
    want = np.asarray(ref.window_features_ref(jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=2, max_value=9),
       st.integers(min_value=64, max_value=200))
def test_holt_winters_padding_lanes_inert(b, t):
    """Appending batch rows must not perturb the original rows: the tile
    pad region stays inert through the sequential recurrence."""
    rng = np.random.default_rng(b * 31 + t)
    y = rng.gamma(2.0, 5.0, size=(b, t)).astype(np.float32)
    solo = np.asarray(ops.holt_winters(jnp.asarray(y[:1]), period=12,
                                       interpret=True))
    packed = np.asarray(ops.holt_winters(jnp.asarray(y), period=12,
                                         interpret=True))
    np.testing.assert_allclose(packed[:1], solo, rtol=1e-5, atol=1e-5)
