"""Backend-agnostic forecasting protocol for the scaling control plane.

Mirrors ``repro.scaling.api``: a `Forecaster` is a named bundle of pure,
jittable closures, so the same object runs compiled inside the cluster
simulator's `lax.scan` (state carried in the controller carry) and
eagerly inside the serving-engine adapter:

    init()                       -> state
    update(state, y)             -> state        # observe one sample
    forecast(state, horizon)     -> Interval(point, lo, hi)
    smooth(y [..., T])           -> [..., T]     # offline one-step backtest

`forecast` returns the *peak* point forecast over the next `horizon`
steps (what pre-scaling wants) plus an uncertainty band. The native band
comes from an EWMA of one-step absolute residuals tracked inside every
state (`FState.resid`) and widens with sqrt(horizon); split-conformal
calibration (``repro.forecast.conformal``) replaces it with a
distribution-free one. Interval width is the confidence signal the
control plane feeds into Algorithm 1 (``repro.core.uncertainty.adjust``)
via `interval_confidence`.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.obs.stages import FORECAST, stage

RESID_RHO = 0.05         # EWMA rate for the one-step residual scale
NATIVE_Z = 1.64          # ~90% band under a Gaussian residual model
EPSF = 1e-9
MIN_CONF_SCALE = 1.0     # one request/min: arrival counts resolve no finer


class Interval(NamedTuple):
    """Point forecast with an uncertainty band (lo <= point <= hi)."""
    point: jax.Array
    lo: jax.Array
    hi: jax.Array


class FState(NamedTuple):
    """Uniform forecaster carry: model state + residual-scale EWMA."""
    inner: Any
    resid: jax.Array     # f32 EWMA of |one-step-ahead error|


class Forecaster(NamedTuple):
    """Pluggable forecaster (all functions jittable)."""
    name: str
    init: Callable[[], "FState"]
    update: Callable[["FState", jax.Array], "FState"]
    forecast: Callable[["FState", int], Interval]
    smooth: Callable[[jax.Array], jax.Array]


def interval_confidence(iv: Interval, scale: jax.Array | None = None, *,
                        floor: float = MIN_CONF_SCALE):
    """Map an interval's relative width to a confidence c in [0, 1].

    c = scale / (scale + width): 1 for a zero-width band, monotonically
    decreasing as the band widens. `scale` defaults to the point forecast
    (relative-width semantics); pass the conformal band's trace scale for
    a calibration-consistent signal.

    The scale is floored at `floor` (default `MIN_CONF_SCALE`, one
    request/min — the resolution of arrival counts). Without the floor an
    idle/near-zero trace collapses the scale to ~0 and c -> width/(0 +
    width) ~ 0 however narrow the band is, so AAPA's forecast-confidence
    signal forced maximally conservative Algorithm-1 adjustments exactly
    when the trace was trivially predictable. Pass the tracked
    residual/trace scale as `floor` to tighten it further.
    """
    width = jnp.maximum(iv.hi - iv.lo, 0.0)
    s = jnp.maximum(iv.point if scale is None else scale,
                    jnp.maximum(floor, EPSF))
    return s / (s + width)


def make_forecaster(name: str, *, init_inner, update_inner, point_fn,
                    smooth_fn=None, z: float = NATIVE_Z) -> Forecaster:
    """Assemble a Forecaster from model-specific pieces.

    ``init_inner() -> inner``, ``update_inner(inner, y) -> inner``,
    ``point_fn(inner, horizon) -> peak point forecast``. Residual
    tracking, the native interval, and (unless `smooth_fn` is given) the
    scan-based offline backtest are shared here. `update` and `forecast`
    run under the ``lane.forecast`` stage.
    """

    def init() -> FState:
        return FState(inner=init_inner(), resid=jnp.float32(0.0))

    def update(state: FState, y) -> FState:
        with stage(FORECAST):
            y = jnp.asarray(y, jnp.float32)
            pred1 = point_fn(state.inner, 1)
            resid = state.resid + RESID_RHO * (jnp.abs(y - pred1)
                                               - state.resid)
            return FState(inner=update_inner(state.inner, y), resid=resid)

    def forecast(state: FState, horizon: int) -> Interval:
        with stage(FORECAST):
            point = point_fn(state.inner, horizon)
            half = z * state.resid * jnp.sqrt(jnp.float32(horizon))
            return Interval(point=point,
                            lo=jnp.maximum(point - half, 0.0),
                            hi=point + half)

    def smooth(y: jax.Array) -> jax.Array:
        """[..., T] -> one-step-ahead point forecasts [..., T]."""
        y = jnp.asarray(y, jnp.float32)     # lists/tuples have no .shape
        if smooth_fn is not None:
            return smooth_fn(y)

        def scan_one(series):
            def body(st, yt):
                return update(st, yt), point_fn(st.inner, 1)
            _, preds = jax.lax.scan(body, init(), series)
            return preds

        flat = y.reshape((-1, y.shape[-1]))
        return jax.vmap(scan_one)(flat).reshape(y.shape)

    return Forecaster(name, init, update, forecast, smooth)
