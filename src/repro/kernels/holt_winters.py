"""Pallas TPU kernel: batched Holt-Winters triple exponential smoothing.

The Generic-Predictive baseline and AAPA's PERIODIC strategy backtest
Holt-Winters over every workload series (paper §IV.C). The recurrence is
sequential in time and independent across series, so the TPU mapping is
time on the sublane axis and series on the 128-wide lane axis:

* the input is transposed to ``[T, B]``; one grid step is a
  ``(TIME_BLOCK, TILE_B)`` block — ``TIME_BLOCK`` steps of ``TILE_B``
  series — and the time loop inside the kernel reads and writes one
  sublane row per step (a dynamic row index, which the TPU compiler
  accepts where a dynamic one-column lane slice is refused);
* the grid is (lane tiles, time blocks) with time innermost, so the
  level / trend / seasonal state persists across time blocks in VMEM
  scratch — the seasonal state is a ``(period, TILE_B)`` tile updated
  with a one-hot row mask.

Oracle: ``repro.core.forecasting.hw_smooth`` (see ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: time steps per grid block (sublane rows); shorter series use one block
TIME_BLOCK = 512


def _kernel(y_ref, o_ref, level_ref, trend_ref, season_ref, *,
            period: int, alpha: float, beta: float, gamma: float):
    """y_ref / o_ref: (t_block, TILE_B) f32 — rows are time steps, lanes
    are series; o_ref receives one-step-ahead predictions. Scratch:
    level / trend (1, TILE_B), season (period, TILE_B)."""
    t_block, tile_b = y_ref.shape
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():                                  # level = y[0]
        level_ref[...] = y_ref[0:1, :]
        trend_ref[...] = jnp.zeros((1, tile_b), jnp.float32)
        season_ref[...] = jnp.zeros((period, tile_b), jnp.float32)

    row_p = jax.lax.broadcasted_iota(jnp.int32, (period, tile_b), 0)

    def body(k, carry):
        level, trend, season = carry
        onehot = row_p == jax.lax.rem(c * t_block + k, period)
        s_t = jnp.sum(jnp.where(onehot, season, 0.0), axis=0, keepdims=True)

        o_ref[pl.ds(k, 1), :] = level + trend + s_t   # 1-step forecast

        yt = y_ref[pl.ds(k, 1), :]
        level_new = alpha * (yt - s_t) + (1.0 - alpha) * (level + trend)
        trend_new = beta * (level_new - level) + (1.0 - beta) * trend
        s_new = gamma * (yt - level_new) + (1.0 - gamma) * s_t
        season = jnp.where(onehot, s_new, season)
        return level_new, trend_new, season

    level, trend, season = jax.lax.fori_loop(
        0, t_block, body, (level_ref[...], trend_ref[...], season_ref[...]))
    level_ref[...] = level
    trend_ref[...] = trend
    season_ref[...] = season


@functools.partial(jax.jit,
                   static_argnames=("period", "alpha", "beta", "gamma",
                                    "tile_b", "interpret"))
def holt_winters_kernel(y: jax.Array, *, period: int = 60,
                        alpha: float = 0.1, beta: float = 0.01,
                        gamma: float = 0.3, tile_b: int = 128,
                        interpret: bool = True) -> jax.Array:
    """y [B, T] -> one-step-ahead forecasts [B, T] (f32).

    Matches ``hw_smooth`` semantics: prediction at t is made from state
    after observing y[:t]; the t=0 prediction is the y[0]-initialized level.
    B pads to a multiple of `tile_b` lanes and T to a whole number of time
    blocks; padded steps come after every real one, so they never feed a
    real prediction.
    """
    B, T = y.shape
    n_tiles = max(-(-B // tile_b), 1)
    pad_b = n_tiles * tile_b
    t_block = min(TIME_BLOCK, -(-T // 8) * 8)
    n_blocks = -(-T // t_block)
    pad_t = n_blocks * t_block
    x = jnp.zeros((pad_t, pad_b), jnp.float32)
    x = x.at[:T, :B].set(y.astype(jnp.float32).T)

    block = pl.BlockSpec((t_block, tile_b), lambda i, c: (c, i))
    out = pl.pallas_call(
        functools.partial(_kernel, period=period, alpha=alpha, beta=beta,
                          gamma=gamma),
        grid=(n_tiles, n_blocks),
        in_specs=[block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((pad_t, pad_b), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, tile_b), jnp.float32),
                        pltpu.VMEM((1, tile_b), jnp.float32),
                        pltpu.VMEM((period, tile_b), jnp.float32)],
        interpret=interpret,
    )(x)
    return out[:T, :B].T
