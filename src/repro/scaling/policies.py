"""Autoscaling policies, all speaking the `repro.scaling.api` protocol.

* ``hpa_controller`` — paper §IV.C baseline: reactive, 70% CPU target,
  5-minute downscale stabilization window, 5-minute scale-down cooldown,
  +-10% tolerance band (Kubernetes semantics).
* ``predictive_controller`` — paper §IV.C baseline: uniform Holt-Winters,
  15-minute prediction horizon, no workload differentiation.
* ``aapa_controller`` — the paper's system (§III.C): every 10 minutes,
  extract 38 features from the last 60 minutes, classify the archetype,
  beta-calibrate the confidence, adjust Table III parameters via
  Algorithm 1, and apply the archetype strategy.
* ``kpa_controller`` — Knative-KPA-style concurrency scaler: stable and
  panic windows over estimated in-flight concurrency, panic mode pins the
  max while active.
* ``hybrid_controller`` — AAPA with a reactive guardrail: the archetype
  strategy never drops below what live utilization requires, and each
  scale-down step is bounded to a fraction of the fleet.

Every controller is fully jittable and backend-agnostic: the same closure
runs compiled inside ``repro.sim.cluster`` and eagerly inside
``repro.scaling.adapter``. The `cfg` argument is duck-typed — anything
with the ``SimConfig`` capacity fields (`rps_per_replica`, `service_sec`,
`initial_replicas`, `control_interval_sec`) works.

Where a controller converts a rate into replicas (`predictive`, `aapa`,
`hybrid`) or into concurrency (`kpa`), it reads the lane's capacity and
service time in `decide` through ``api.lane_plant(obs, cfg)``: the
observed lane's own `LanePlant` when the plant gives each lane its own
(``repro.sim.cluster.LanePlant``, carried by ``repro.scaling.batch``),
else the scalar `cfg`. `hpa` reads utilization only. The serving adapter
observes no `LanePlant`, so it runs the scalar plant.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import features as F
from repro.core import forecasting as fc
from repro.core import uncertainty
from repro.core.archetypes import table_iii_arrays
from repro.forecast import api as fapi
from repro.forecast import conformal as fconf
from repro.forecast import registry as forecast_registry
from repro.obs.stages import RECLASSIFY, stage
from repro.obs.trace import ExplainOut
from repro.scaling.api import Controller, Obs, lane_plant

EPSF = 1e-9


def _nan() -> jax.Array:
    return jnp.float32(jnp.nan)


def _select4(idx, v0, v1, v2, v3):
    """Branch-free 4-way archetype select, bit-exact with ``table[idx]``
    (it returns exactly one of the four values) but lowered as three
    vector selects instead of a lane-dynamic gather."""
    return jnp.where(idx == 0, v0,
                     jnp.where(idx == 1, v1,
                               jnp.where(idx == 2, v2, v3)))


# ---------------------------------------------------------------- HPA ----
class HPAState(NamedTuple):
    desired_buf: jax.Array  # ring buffer of recent desired counts
    last_total: jax.Array


def hpa_controller(cfg, *, target: float = 0.70,
                   stabilization_min: float = 5.0,
                   cooldown_min: float = 5.0,
                   tolerance: float = 0.10) -> Controller:
    buf_len = max(int(stabilization_min * 60 / cfg.control_interval_sec), 1)

    def init():
        return HPAState(
            desired_buf=jnp.full((buf_len,), cfg.initial_replicas,
                                 jnp.float32),
            last_total=jnp.float32(cfg.initial_replicas))

    def on_minute(state, hist, minute_idx):
        return state

    def decide(state: HPAState, obs: Obs):
        ratio = obs.util_ema / target
        in_band = jnp.abs(ratio - 1.0) <= tolerance
        raw = jnp.ceil(obs.ready_total * ratio)
        raw = jnp.where(in_band, obs.ready_total, raw)
        # serverless scale-to-zero on sustained idle (Knative-style KPA);
        # the activator path below wakes the endpoint on traffic.
        idle = ((obs.util_ema < 0.02) & (obs.queue <= 0.0)
                & (obs.rate_rps <= 1e-6))
        raw = jnp.where(idle, 0.0, jnp.maximum(raw, 1.0))
        wake = (obs.rate_rps > 0.0) | (obs.queue > 0.0)
        raw = jnp.where(wake, jnp.maximum(raw, 1.0), raw)
        buf = jnp.concatenate([state.desired_buf[1:], raw[None]])
        # downscale stabilization: never below the window max
        stabilized = jnp.maximum(raw, jnp.max(buf))
        desired = jnp.where(raw >= obs.ready_total, raw, stabilized)
        return (HPAState(buf, desired), desired,
                jnp.float32(cooldown_min * 60.0))

    return Controller("hpa", init, on_minute, decide)


# --------------------------------------------------- Generic Predictive ----
class PredState(NamedTuple):
    fc: fapi.FState


def _resolve_forecaster(forecaster, band):
    """Name or Forecaster -> Forecaster, conformal-wrapped when a
    calibrated band is supplied. Returns (forecaster, confidence_scale)."""
    fcst = forecast_registry.make(forecaster)
    if band is not None:
        return fconf.wrap(fcst, band), band.scale
    return fcst, None


def predictive_controller(cfg, *, target: float = 0.70,
                          horizon_min: int = 15,
                          cooldown_min: float = 5.0,
                          forecaster="holt_winters",
                          band: fconf.ConformalBand | None = None,
                          conservative: bool = False) -> Controller:
    """Uniform predictive baseline over any registered forecaster.
    `conservative=True` scales to the interval's upper bound instead of
    the point forecast (pay replicas for forecast uncertainty)."""
    fcst, _ = _resolve_forecaster(forecaster, band)

    def init():
        return PredState(fc=fcst.init())

    def on_minute(state: PredState, hist, minute_idx):
        return PredState(fc=fcst.update(state.fc, hist[-1]))

    def decide(state: PredState, obs: Obs):
        iv = fcst.forecast(state.fc, horizon_min)
        pred_per_min = jnp.maximum(iv.hi if conservative else iv.point, 0.0)
        rps = lane_plant(obs, cfg).rps_per_replica
        need_pred = pred_per_min / 60.0 / (rps * target)
        need_now = obs.rate_rps / (rps * target)
        desired = jnp.ceil(jnp.maximum(need_pred, need_now))
        # scale to zero when neither live traffic nor forecast needs pods
        idle = ((desired < 1.0) & (obs.queue <= 0.0)
                & (obs.rate_rps <= 1e-6))
        desired = jnp.where(idle, 0.0, jnp.maximum(desired, 1.0))
        return state, desired, jnp.float32(cooldown_min * 60.0)

    def explain(state: PredState, obs: Obs):
        iv = fcst.forecast(state.fc, horizon_min)
        return ExplainOut(fc_point=iv.point, fc_lo=iv.lo, fc_hi=iv.hi,
                          confidence=_nan(), archetype=_nan(),
                          guard_floor=_nan())

    return Controller("predictive", init, on_minute, decide, explain)


# ------------------------------------------------------------------ AAPA ----
class AAPAState(NamedTuple):
    fc: fapi.FState         # named forecaster carry (PERIODIC strategy)
    arch: jax.Array         # int32 current archetype
    conf: jax.Array         # f32 effective confidence fed to Algorithm 1
    cpu_adj: jax.Array
    cool_adj_min: jax.Array
    minrep_adj: jax.Array


def aapa_controller(
        cfg,
        classify: Callable[[jax.Array], tuple[jax.Array, jax.Array]],
        *, stride_min: int = 10, horizon_min: int = 15,
        forecaster="holt_winters",
        band: fconf.ConformalBand | None = None,
        forecast_confidence: bool | None = None) -> Controller:
    """`classify(features [38]) -> (class id int32, confidence f32)`,
    typically GBDT + beta calibration (see ``repro.core.pipeline``).

    The predictive strategy runs any registered forecaster (by name or
    instance). When forecast confidence is on, Algorithm 1's confidence
    is the classifier's calibrated confidence *times* the forecast
    confidence — the forecaster's interval width mapped to [0, 1]
    (split-conformal when a calibrated `band` is supplied, residual-EWMA
    native band otherwise). Wide bands mean the forecast cannot be
    trusted, so the adjustment gets more conservative exactly as
    §III.C.3 prescribes. `forecast_confidence=None` (default) enables
    the signal only when a calibrated `band` is present, so an
    uncalibrated AAPA feeds the classifier signal alone."""
    tab = table_iii_arrays()
    fcst, conf_scale = _resolve_forecaster(forecaster, band)
    if forecast_confidence is None:
        forecast_confidence = band is not None

    def init():
        return AAPAState(fc=fcst.init(),
                         arch=jnp.int32(2),          # start conservative
                         conf=jnp.float32(0.5),
                         cpu_adj=jnp.float32(0.5),
                         cool_adj_min=jnp.float32(5.0),
                         minrep_adj=jnp.float32(1.0))

    def on_minute(state: AAPAState, hist, minute_idx):
        fst = fcst.update(state.fc, hist[-1])

        def reclassify(_):
            with stage(RECLASSIFY):
                feats = F.extract_features(hist)
                arch, conf = classify(feats)
                if forecast_confidence:
                    iv = fcst.forecast(fst, horizon_min)
                    conf = conf * fapi.interval_confidence(iv, conf_scale)
                adj = uncertainty.adjust(
                    conf, _select4(arch, *tab["target_cpu"]),
                    _select4(arch, *tab["cooldown_min"]),
                    _select4(arch, *tab["min_replicas"]))
                return AAPAState(fst, arch, conf, adj.target_cpu,
                                 adj.cooldown_min, adj.min_replicas)

        def keep(_):
            return state._replace(fc=fst)

        do = (minute_idx % stride_min) == 0
        return jax.lax.cond(do, reclassify, keep, None)

    def decide(state: AAPAState, obs: Obs):
        cap = (lane_plant(obs, cfg).rps_per_replica
               * jnp.maximum(state.cpu_adj, 0.05))
        # reactive component (archetype-specific utilization target)
        ratio = obs.util_ema / jnp.maximum(state.cpu_adj, 0.05)
        reactive = jnp.ceil(obs.ready_total * ratio)
        reactive = jnp.where(jnp.abs(ratio - 1.0) <= 0.1,
                             obs.ready_total, reactive)

        # strategy components (paper Table III)
        warm = _select4(state.arch, *tab["warm_pool"])
        need_now = jnp.ceil(obs.rate_rps / cap)
        spike_d = need_now + warm + state.minrep_adj

        fc_pred = jnp.maximum(fcst.forecast(state.fc, horizon_min).point,
                              0.0) / 60.0
        periodic_d = jnp.ceil(fc_pred / cap)

        trend_pred = fc.linear_trend_forecast(
            obs.rate_history[-30:], horizon_min) / 60.0
        ramp_d = jnp.ceil(jnp.maximum(trend_pred, obs.rate_rps) / cap)

        mean_rps = jnp.mean(obs.rate_history[-15:]) / 60.0
        stat_d = jnp.ceil(mean_rps / cap)

        strat = _select4(state.arch, periodic_d, spike_d, stat_d, ramp_d)
        desired = jnp.maximum(jnp.maximum(reactive, strat),
                              jnp.maximum(state.minrep_adj, 1.0))
        return state, desired, state.cool_adj_min * 60.0

    def explain(state: AAPAState, obs: Obs):
        iv = fcst.forecast(state.fc, horizon_min)
        return ExplainOut(fc_point=iv.point, fc_lo=iv.lo, fc_hi=iv.hi,
                          confidence=state.conf,
                          archetype=state.arch.astype(jnp.float32),
                          guard_floor=_nan())

    return Controller("aapa", init, on_minute, decide, explain)


# ------------------------------------------------------------------- KPA ----
class KPAState(NamedTuple):
    stable_ema: jax.Array    # concurrency, ~stable_window average
    panic_ema: jax.Array     # concurrency, ~panic_window average
    panic_left_s: jax.Array  # seconds of panic mode remaining
    panic_max: jax.Array     # max desired seen during the panic


def kpa_controller(cfg, *, target_concurrency: float | None = None,
                   panic_threshold: float = 2.0,
                   stable_window_s: float = 60.0,
                   panic_window_s: float = 6.0,
                   cooldown_min: float = 1.0) -> Controller:
    """Knative-KPA-style concurrency autoscaler.

    Estimated in-flight concurrency (Little's law: rate x service time,
    plus the standing queue) feeds two EMAs. The stable window drives
    steady-state sizing; when the panic-window estimate needs more than
    `panic_threshold` x the current fleet, the scaler enters panic mode
    for one stable window, during which desired is pinned to the maximum
    seen (never scales down mid-burst). The default target is one
    replica's concurrency at full utilization, the lane's own where the
    lane has its own plant.
    """
    lane_target = target_concurrency is None
    if lane_target:
        target_concurrency = cfg.rps_per_replica * cfg.service_sec
    dt = float(cfg.control_interval_sec)

    def init():
        return KPAState(stable_ema=jnp.float32(0.0),
                        panic_ema=jnp.float32(0.0),
                        panic_left_s=jnp.float32(0.0),
                        panic_max=jnp.float32(0.0))

    def on_minute(state, hist, minute_idx):
        return state

    def decide(state: KPAState, obs: Obs):
        lane = lane_plant(obs, cfg)
        conc = obs.queue + obs.rate_rps * lane.service_sec
        a_s = jnp.float32(min(dt / stable_window_s, 1.0))
        a_p = jnp.float32(min(dt / panic_window_s, 1.0))
        stable = state.stable_ema + a_s * (conc - state.stable_ema)
        panic = state.panic_ema + a_p * (conc - state.panic_ema)

        tgt = jnp.float32(target_concurrency)
        if lane_target and obs.plant is not None:
            tgt = lane.rps_per_replica * lane.service_sec
        want_stable = jnp.ceil(stable / tgt)
        want_panic = jnp.ceil(panic / tgt)

        fleet = jnp.maximum(obs.ready_total, 1.0)
        enter = want_panic >= panic_threshold * fleet
        panic_left = jnp.where(enter, jnp.float32(stable_window_s),
                               jnp.maximum(state.panic_left_s - dt, 0.0))
        in_panic = panic_left > 0.0
        panic_max = jnp.where(
            in_panic, jnp.maximum(jnp.where(state.panic_left_s > 0.0,
                                            state.panic_max, 0.0),
                                  jnp.maximum(want_panic, fleet)),
            jnp.float32(0.0))
        desired = jnp.where(in_panic, panic_max, want_stable)

        # scale-to-zero on a truly idle stable window; wake on traffic
        idle = ((stable <= 1e-3) & (obs.queue <= 0.0)
                & (obs.rate_rps <= 1e-6))
        desired = jnp.where(idle, 0.0, jnp.maximum(desired, 1.0))
        return (KPAState(stable, panic, panic_left, panic_max), desired,
                jnp.float32(cooldown_min * 60.0))

    return Controller("kpa", init, on_minute, decide)


# ---------------------------------------------------------------- hybrid ----
def hybrid_controller(cfg, classify, *, guard_target: float = 0.85,
                      max_down_frac: float = 0.3,
                      **aapa_kw) -> Controller:
    """AAPA plus a reactive guardrail.

    Two failure modes of a pure archetype strategy are fenced off:

    * misclassification under-provisioning — desired never drops below
      what live utilization requires at `guard_target` (an HPA-style
      floor computed from the actual load, independent of the archetype);
    * scale-down cliffs — one decision may remove at most
      `max_down_frac` of the current fleet.

    State and classification cadence are inherited from
    ``aapa_controller``; only `decide` is wrapped.
    """
    base = aapa_controller(cfg, classify, **aapa_kw)

    def guard_floor(obs: Obs):
        """Replicas that live utilization and the live rate require."""
        floor = jnp.ceil(obs.ready_total * obs.util_ema / guard_target)
        return jnp.maximum(floor,
                           jnp.ceil(obs.rate_rps
                                    / (lane_plant(obs, cfg).rps_per_replica
                                       * guard_target)))

    def decide(state, obs: Obs):
        state, desired, cool = base.decide(state, obs)
        # reactive floor from live utilization
        floor = guard_floor(obs)
        guarded = jnp.maximum(desired, floor)
        # bounded scale-down step
        step_floor = jnp.ceil(obs.ready_total * (1.0 - max_down_frac))
        guarded = jnp.where(guarded < obs.ready_total,
                            jnp.maximum(guarded, step_floor), guarded)
        return state, guarded, cool

    def explain(state, obs: Obs):
        return base.explain(state, obs)._replace(guard_floor=guard_floor(obs))

    return Controller("hybrid", base.init, base.on_minute, decide, explain)
