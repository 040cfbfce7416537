"""Holt-Winters (triple exponential smoothing) in JAX.

Used by the PERIODIC archetype strategy (paper Table III) and by the
Generic Predictive baseline (paper §IV.C: uniform Holt-Winters with a
15-minute prediction horizon).

Two forms are provided:

* ``hw_step`` — one online update, usable inside the cluster simulator's
  lax.scan (state lives in the controller carry).
* ``hw_smooth`` — full-series smoothing with one-step-ahead forecasts,
  used for offline backtests. This sequential recurrence is also
  implemented as a Pallas TPU kernel (``repro.kernels.holt_winters``);
  this function is its oracle.

The seasonal ring is addressed by slot mask, not by index (``ring_slot``,
``ring_read``, ``ring_write``, ``ring_lookahead``): a read is a masked sum
over the ``period`` slots, a write a masked select, and the horizon max a
masked max over the slots with the step at which each is next read.
Under the lane vmap the phase ``t`` differs per lane, and index addressing
there lowers to per-lane gathers and scatters whose cost grows with the
number of indices; the mask is O(period) element-wise work per lane and
gives the same numbers.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp


class HWState(NamedTuple):
    level: jax.Array    # []
    trend: jax.Array    # []
    season: jax.Array   # [period]
    t: jax.Array        # [] int32, current phase


def hw_init(period: int, y0: float | jax.Array = 0.0) -> HWState:
    y0 = jnp.asarray(y0, jnp.float32)
    return HWState(level=y0, trend=jnp.float32(0.0),
                   season=jnp.zeros((period,), jnp.float32),
                   t=jnp.int32(0))


def ring_slot(t: jax.Array, period: int) -> jax.Array:
    """[..., period] bool mask of the ring slot that step `t` addresses."""
    return jnp.arange(period) == (jnp.asarray(t) % period)[..., None]


def ring_read(ring: jax.Array, hot: jax.Array) -> jax.Array:
    """The value of the masked slot: a sum in which every other slot is an
    exact zero (`where`, not a one-hot product: 0 * inf would be NaN)."""
    return jnp.sum(jnp.where(hot, ring, 0.0), axis=-1)


def ring_write(ring: jax.Array, hot: jax.Array, value: jax.Array):
    """`ring` with the masked slot set to `value`."""
    return jnp.where(hot, value[..., None], ring)


def ring_lookahead(t: jax.Array, period: int, horizon: int):
    """For each slot of the ring, the first and last of the steps
    ``1..horizon`` ahead of phase `t` at which it is read, and whether it is
    read at all. Step ``h`` reads slot ``(t + h - 1) % period``, so slot
    ``k`` is first read at ``(k - t) % period + 1`` and then every `period`
    steps; the two coincide unless ``horizon > period``.
    """
    ahead = jnp.arange(period) - (jnp.asarray(t) % period)[..., None]
    first = jnp.where(ahead < 0, ahead + period, ahead) + 1
    if horizon <= period:
        last = first
    else:
        last = first + period * ((horizon - first) // period)
    return first, last, first <= horizon


def hw_step(state: HWState, y: jax.Array, *, alpha=0.1, beta=0.01,
            gamma=0.3) -> HWState:
    """Additive-seasonal Holt-Winters online update with observation y.
    Reads and rewrites the seasonal slot of phase ``t`` by mask."""
    hot = ring_slot(state.t, state.season.shape[-1])
    s_t = ring_read(state.season, hot)
    level_new = alpha * (y - s_t) + (1.0 - alpha) * (state.level + state.trend)
    trend_new = beta * (level_new - state.level) + (1.0 - beta) * state.trend
    season_new = ring_write(state.season, hot,
                            gamma * (y - level_new) + (1.0 - gamma) * s_t)
    return HWState(level_new, trend_new, season_new, state.t + 1)


def hw_forecast(state: HWState, horizon: int) -> jax.Array:
    """h-step-ahead point forecast from the current state."""
    hot = ring_slot(state.t + horizon - 1, state.season.shape[-1])
    return state.level + horizon * state.trend \
        + ring_read(state.season, hot)


def hw_forecast_max(state: HWState, horizon: int) -> jax.Array:
    """Max forecast over the next `horizon` steps (for peak pre-scaling).

    Each slot contributes its forecast at the steps it is read; as the
    forecast is linear in the step and float32 rounding is monotone in it,
    the first and last such step hold that slot's max exactly.
    """
    first, last, read = ring_lookahead(state.t, state.season.shape[-1],
                                       horizon)

    def pred(h):
        return (state.level[..., None] + h.astype(jnp.float32)
                * state.trend[..., None]) + state.season

    preds = pred(first)
    if horizon > state.season.shape[-1]:
        preds = jnp.maximum(preds, pred(last))
    return jnp.max(jnp.where(read, preds, -jnp.inf), axis=-1)


_SMOOTH_BUCKET = 256     # series lengths round up to this compile bucket


@partial(jax.jit, static_argnames=("period",), donate_argnums=(0,))
def _hw_smooth_padded(y: jax.Array, alpha, beta, gamma, *,
                      period: int) -> jax.Array:
    def scan_one(series):
        def body(state, yt):
            pred = hw_forecast(state, 1)
            nxt = hw_step(state, yt, alpha=alpha, beta=beta, gamma=gamma)
            return nxt, pred
        init = hw_init(period, series[0])
        _, preds = jax.lax.scan(body, init, series)
        return preds

    return jax.vmap(scan_one)(y)


def hw_smooth(y: jax.Array, *, period: int = 60, alpha=0.1, beta=0.01,
              gamma=0.3) -> jax.Array:
    """One-step-ahead forecasts over a whole series.

    y [..., T] -> forecasts [..., T] where forecasts[..., t] is the
    prediction of y[..., t] made at time t-1. Vectorizes over leading axes.

    The recurrence is causal, so the series is zero-padded up to the next
    ``_SMOOTH_BUCKET`` multiple before entering the jitted scan: backtests
    over mixed-length traces inside one bucket reuse a single compilation
    (the padded scratch buffer is donated). `period` stays a static arg of
    the inner jit; alpha/beta/gamma are traced scalars.
    """
    T = y.shape[-1]
    pad_t = -(-T // _SMOOTH_BUCKET) * _SMOOTH_BUCKET
    flat = jnp.asarray(y, jnp.float32).reshape((-1, T))
    padded = jnp.pad(flat, ((0, 0), (0, pad_t - T)))
    out = _hw_smooth_padded(padded, jnp.float32(alpha), jnp.float32(beta),
                            jnp.float32(gamma), period=period)
    return out[:, :T].reshape(y.shape)


def linear_trend_forecast(history: jax.Array, horizon: int) -> jax.Array:
    """RAMP strategy: OLS trend extrapolation `horizon` steps ahead.

    history [..., T] -> scalar forecast [...]. Clipped at zero.
    """
    x = history.astype(jnp.float32)
    n = x.shape[-1]
    t = jnp.arange(n, dtype=jnp.float32)
    tbar = (n - 1) / 2.0
    tvar = jnp.mean((t - tbar) ** 2)
    mean = jnp.mean(x, axis=-1)
    slope = jnp.mean((t - tbar) * (x - mean[..., None]), axis=-1) / tvar
    return jnp.maximum(mean + slope * ((n - 1) - tbar + horizon), 0.0)
