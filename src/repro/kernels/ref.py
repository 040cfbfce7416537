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


def episode_block_ref(rates, controller, cfg):
    """rates [B, M] -> MinuteOut of [B, M]: the CPU blocked scan, one
    lane per workload — the dispatch oracle for the fused-decide episode
    kernel (compiled-program parity is ulp-tight, not bitwise; see the
    episode_block module docstring)."""
    from repro.sim.cluster import simulate
    return jax.vmap(lambda r: simulate(r, controller, cfg,
                                       decide_kernel=False))(rates)


def gbdt_logits_ref(params, X):
    """Host node-table inference — the oracle for the GBDT kernel (the
    kernel runs the identical traversal over the identical layout, so
    parity is bit-exact in interpret mode)."""
    from repro.core.gbdt import predict_logits
    return predict_logits(params, X)
