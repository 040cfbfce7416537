"""Pure-jnp oracles for every Pallas kernel in this package.

The kernels implement AAPA's two compute hot-spots (DESIGN.md §2):
window feature extraction (28 stat/time-domain features over hundreds of
thousands of 60-minute windows) and batched Holt-Winters smoothing.
"""
from __future__ import annotations

import jax

from repro.core.features import stat_time_features
from repro.core.forecasting import hw_smooth


def window_features_ref(windows: jax.Array) -> jax.Array:
    """[N, W] -> [N, 28] — identical math to repro.core.features."""
    return stat_time_features(windows)


def holt_winters_ref(y: jax.Array, *, period: int = 60, alpha: float = 0.1,
                     beta: float = 0.01, gamma: float = 0.3) -> jax.Array:
    """[B, T] -> one-step-ahead forecasts [B, T]."""
    return hw_smooth(y, period=period, alpha=alpha, beta=beta, gamma=gamma)
