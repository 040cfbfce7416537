"""The built-in forecasters: Holt-Winters, linear trend, seasonal naive,
and EWMA — one factory per model, all assembled through
``api.make_forecaster`` so residual tracking, native intervals, and the
scan-based backtest come for free.

Holt-Winters is the only one with a custom offline path: `smooth`
dispatches to the Pallas TPU kernel (``repro.kernels.holt_winters``) when
a TPU backend is attached and falls back to the pure-jnp oracle
(``repro.core.forecasting.hw_smooth``, the same function ``kernels/ref``
wraps) on CPU, where interpret-mode Pallas would be orders of magnitude
slower.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import forecasting as fc
from repro.forecast.api import Forecaster, make_forecaster


# ---------------------------------------------------------- Holt-Winters ----
def holt_winters_forecaster(*, period: int = 60, alpha: float = 0.1,
                            beta: float = 0.01,
                            gamma: float = 0.3) -> Forecaster:
    """Additive-seasonal triple exponential smoothing (PERIODIC strategy,
    paper Table III; the Generic-Predictive baseline, §IV.C)."""

    def smooth_fn(y):
        y = jnp.asarray(y, jnp.float32)
        flat = y.reshape((-1, y.shape[-1]))
        if jax.default_backend() == "tpu":
            from repro.kernels import ops
            out = ops.holt_winters(flat, period=period, alpha=alpha,
                                   beta=beta, gamma=gamma, interpret=False)
        else:
            out = fc.hw_smooth(flat, period=period, alpha=alpha,
                               beta=beta, gamma=gamma)
        return out.reshape(y.shape)

    return make_forecaster(
        "holt_winters",
        init_inner=lambda: fc.hw_init(period),
        update_inner=lambda st, y: fc.hw_step(st, y, alpha=alpha,
                                              beta=beta, gamma=gamma),
        point_fn=lambda st, h: jnp.maximum(fc.hw_forecast_max(st, h), 0.0),
        smooth_fn=smooth_fn)


# ----------------------------------------------------------- linear trend ----
def linear_trend_forecaster(*, window: int = 30) -> Forecaster:
    """OLS trend extrapolation over a sliding window (RAMP strategy).
    State is just the [window] ring of most recent observations."""

    def point(buf: jax.Array, h: int):
        p1 = fc.linear_trend_forecast(buf, 1)
        ph = fc.linear_trend_forecast(buf, h)
        # peak over the horizon: a line attains its max at an endpoint
        return jnp.maximum(p1, ph)

    return make_forecaster(
        "linear_trend",
        init_inner=lambda: jnp.zeros((window,), jnp.float32),
        update_inner=lambda buf, y: jnp.concatenate([buf[1:], y[None]]),
        point_fn=point)


# --------------------------------------------------------- seasonal naive ----
class SeasonalState(NamedTuple):
    season: jax.Array    # [period] last observation at each phase
    t: jax.Array         # int32 samples seen


def seasonal_naive_forecaster(*, period: int = 60) -> Forecaster:
    """Repeat the value one period ago (the classic strong baseline for
    cyclic serverless traffic; needs one full period of warm-up)."""

    def update(st: SeasonalState, y):
        hot = fc.ring_slot(st.t, period)
        return SeasonalState(season=fc.ring_write(st.season, hot, y),
                             t=st.t + 1)

    def point(st: SeasonalState, h: int):
        _, _, read = fc.ring_lookahead(st.t, period, h)
        peak = jnp.max(jnp.where(read, st.season, -jnp.inf), axis=-1)
        return jnp.maximum(peak, 0.0)

    return make_forecaster(
        "seasonal_naive",
        init_inner=lambda: SeasonalState(
            season=jnp.zeros((period,), jnp.float32), t=jnp.int32(0)),
        update_inner=update,
        point_fn=point)


# ------------------------------------------------------------------- EWMA ----
def ewma_forecaster(*, alpha: float = 0.3) -> Forecaster:
    """Exponentially weighted level; flat forecast at every horizon (the
    conservative choice for SPIKE / STATIONARY_NOISY archetypes)."""
    return make_forecaster(
        "ewma",
        init_inner=lambda: jnp.float32(0.0),
        update_inner=lambda lvl, y: lvl + alpha * (y - lvl),
        point_fn=lambda lvl, h: jnp.maximum(lvl, 0.0))
