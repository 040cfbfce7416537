"""The `repro.forecast` subsystem: registry round-trips, forecaster
semantics, batched backtest parity, split-conformal coverage, the
confidence path into Algorithm 1, and the forecasters x policies x
workloads batched simulation (bit-exact vs the per-forecaster path)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import forecasting as fc
from repro.core import uncertainty
from repro.data.azure_synth import generate_traces
from repro.forecast import (Forecaster, backtest, conformal,
                            interval_confidence, registry)
from repro.forecast.api import FState, make_forecaster
from repro.forecast.models import SeasonalState
from repro.core.archetypes import Archetype


# ------------------------------------------------------------- registry ----
def test_registry_round_trips_every_forecaster():
    for name in registry.available():
        f = registry.make(name)
        assert isinstance(f, Forecaster) and f.name == name
        st = f.init()
        assert isinstance(st, FState)
        for v in (5.0, 9.0, 4.0, 12.0):
            st = f.update(st, jnp.float32(v))
        iv = f.forecast(st, 15)
        assert float(iv.lo) <= float(iv.point) <= float(iv.hi)
        assert float(iv.lo) >= 0.0


def test_registry_rejects_unknown_names_and_params():
    with pytest.raises(KeyError):
        registry.make("oracle")
    with pytest.raises(TypeError):
        registry.make("ewma", period=60)
    # instances pass through, but can't be re-parameterized
    f = registry.make("ewma")
    assert registry.make(f) is f
    with pytest.raises(TypeError):
        registry.make(f, alpha=0.5)


def test_archetype_defaults_cover_every_archetype():
    for arch in Archetype:
        name = registry.for_archetype(arch)
        assert name in registry.available()
    assert registry.for_archetype(Archetype.RAMP) == "linear_trend"
    assert registry.for_archetype(Archetype.PERIODIC) == "holt_winters"


# --------------------------------------------------- forecaster semantics ----
def test_linear_trend_forecaster_exact_on_line():
    f = registry.make("linear_trend", window=30)
    st = f.init()
    for v in 10.0 + 3.0 * np.arange(30):
        st = f.update(st, jnp.float32(v))
    iv = f.forecast(st, 10)
    # increasing line: peak over the horizon is the endpoint forecast
    assert float(iv.point) == pytest.approx(10.0 + 3.0 * 39, rel=1e-4)


def test_seasonal_naive_repeats_the_cycle():
    period = 12
    f = registry.make("seasonal_naive", period=period)
    st = f.init()
    cycle = 50.0 + 40.0 * np.sin(2 * np.pi * np.arange(period) / period)
    for _ in range(3):
        for v in cycle:
            st = f.update(st, jnp.float32(v))
    # peak over one full period = the cycle's max
    iv = f.forecast(st, period)
    assert float(iv.point) == pytest.approx(cycle.max(), rel=1e-5)


def test_ewma_converges_to_level_with_tight_band():
    f = registry.make("ewma", alpha=0.5)
    st = f.init()
    for _ in range(80):
        st = f.update(st, jnp.float32(42.0))
    iv = f.forecast(st, 15)
    assert float(iv.point) == pytest.approx(42.0, rel=1e-3)
    # constant input -> residual EWMA ~ 0 -> near-degenerate interval
    assert float(iv.hi - iv.lo) < 1.0
    assert float(interval_confidence(iv)) > 0.95


def test_native_interval_widens_with_noise_and_horizon():
    rng = np.random.default_rng(0)
    f = registry.make("ewma")
    st_lo, st_hi = f.init(), f.init()
    for _ in range(200):
        st_lo = f.update(st_lo, jnp.float32(100.0 + rng.normal(0, 1)))
        st_hi = f.update(st_hi, jnp.float32(100.0 + rng.normal(0, 25)))
    w = lambda iv: float(iv.hi - iv.lo)
    assert w(f.forecast(st_hi, 1)) > w(f.forecast(st_lo, 1))
    assert w(f.forecast(st_hi, 16)) > w(f.forecast(st_hi, 1))
    c_lo = float(interval_confidence(f.forecast(st_lo, 1)))
    c_hi = float(interval_confidence(f.forecast(st_hi, 1)))
    assert c_lo > c_hi  # noisier series -> lower forecast confidence


# ------------------------------------------------------ batched backtest ----
def test_batch_backtest_bit_exact_vs_per_forecaster():
    rng = np.random.default_rng(3)
    y = rng.gamma(2.0, 10.0, size=(5, 240)).astype(np.float32)
    names = registry.available()
    out = backtest.batch_smooth(names, y)              # [F, B, T]
    assert out.shape == (len(names), 5, 240)
    for i, name in enumerate(names):
        single = backtest.stream_smooth(name, y)
        np.testing.assert_array_equal(np.asarray(out[i]),
                                      np.asarray(single),
                                      err_msg=name)


def test_smooth_accepts_lists_and_1d_input():
    """`smooth` coerces before touching .shape, so Python lists and bare
    1-D traces work on every forecaster (including Holt-Winters' custom
    offline path, which had its own pre-coercion .shape read)."""
    trace = [3.0, 4.0, 5.0, 6.0, 5.0, 4.0] * 20
    for name in registry.available():
        f = registry.make(name)
        from_list = f.smooth(trace)
        from_arr = f.smooth(jnp.asarray(trace, jnp.float32))
        assert from_list.shape == (len(trace),), name
        np.testing.assert_array_equal(np.asarray(from_list),
                                      np.asarray(from_arr), err_msg=name)


def test_smooth_matches_stream_path_for_scan_forecasters():
    """Forecasters without a custom offline kernel path must have
    `smooth` == the streaming scan exactly."""
    rng = np.random.default_rng(4)
    y = rng.gamma(2.0, 10.0, size=(3, 180)).astype(np.float32)
    for name in ("ewma", "linear_trend", "seasonal_naive"):
        f = registry.make(name)
        np.testing.assert_array_equal(
            np.asarray(f.smooth(jnp.asarray(y))),
            np.asarray(backtest.stream_smooth(f, y)), err_msg=name)


def test_hw_smooth_dispatch_matches_kernel_oracle():
    """On CPU the HW forecaster's offline path is the hw_smooth oracle —
    the same function the Pallas kernel is validated against."""
    rng = np.random.default_rng(5)
    y = rng.gamma(2.0, 5.0, size=(4, 300)).astype(np.float32)
    f = registry.make("holt_winters", period=24)
    got = np.asarray(f.smooth(jnp.asarray(y)))
    want = np.asarray(fc.hw_smooth(jnp.asarray(y), period=24))
    np.testing.assert_array_equal(got, want)


def test_hw_smooth_reuses_one_compile_across_series_lengths():
    """Mixed-length backtests must not retrace per length: series pad to
    a 256 bucket, so 100/130/250 all share one compilation."""
    from repro.core.forecasting import _hw_smooth_padded
    rng = np.random.default_rng(6)
    outs = {}
    before = _hw_smooth_padded._cache_size()
    for T in (100, 130, 250):
        y = rng.gamma(2.0, 5.0, size=(2, T)).astype(np.float32)
        outs[T] = np.asarray(fc.hw_smooth(jnp.asarray(y), period=24))
        assert outs[T].shape == (2, T)
    grown = _hw_smooth_padded._cache_size() - before
    assert grown <= 1, f"retraced per length: {grown} new compilations"
    # padding must not change the causal prefix
    y = rng.gamma(2.0, 5.0, size=(2, 100)).astype(np.float32)
    direct = np.asarray(_hw_smooth_padded(
        jnp.asarray(np.pad(y, ((0, 0), (0, 156)))), jnp.float32(0.1),
        jnp.float32(0.01), jnp.float32(0.3), period=24))[:, :100]
    np.testing.assert_array_equal(
        np.asarray(fc.hw_smooth(jnp.asarray(y), period=24)), direct)


# ------------------------------------------- seasonal ring by slot mask ----
# The index forms the ring forecasters had before they addressed the ring
# by slot mask: the oracles the mask forms must equal bit for bit.
RING_PERIOD = 60
RING_LANES = 4096


def _hw_step_gather(state, y, *, alpha=0.1, beta=0.01, gamma=0.3):
    period = state.season.shape[0]
    phase = state.t % period
    s_t = state.season[phase]
    level_new = alpha * (y - s_t) + (1.0 - alpha) * (state.level + state.trend)
    trend_new = beta * (level_new - state.level) + (1.0 - beta) * state.trend
    season_new = state.season.at[phase].set(
        gamma * (y - level_new) + (1.0 - gamma) * s_t)
    return fc.HWState(level_new, trend_new, season_new, state.t + 1)


def _hw_forecast_max_gather(state, horizon):
    hs = jnp.arange(1, horizon + 1)
    period = state.season.shape[0]
    phases = (state.t + hs - 1) % period
    preds = state.level + hs.astype(jnp.float32) * state.trend \
        + state.season[phases]
    return jnp.max(preds)


def _naive_update_gather(st, y):
    return st._replace(season=st.season.at[st.t % RING_PERIOD].set(y),
                       t=st.t + 1)


def _naive_point_gather(st, h):
    phases = (st.t + jnp.arange(1, h + 1) - 1) % RING_PERIOD
    return jnp.maximum(jnp.max(st.season[phases]), 0.0)


def _gather_forecaster(name):
    if name == "holt_winters":
        return make_forecaster(
            "holt_winters_gather", init_inner=None,
            update_inner=_hw_step_gather,
            point_fn=lambda st, h: jnp.maximum(
                _hw_forecast_max_gather(st, h), 0.0))
    return make_forecaster("seasonal_naive_gather", init_inner=None,
                           update_inner=_naive_update_gather,
                           point_fn=_naive_point_gather)


def _ring_phases(case, rng):
    """Per-lane phases `t`: spread over three periods, the ring's two
    ends, or near the top of int32 (leaving room for 2 * period + 10
    steps of look-ahead in the index form)."""
    if case == "spread":
        return rng.integers(0, 3 * RING_PERIOD, RING_LANES)
    if case == "ends":
        return rng.choice([0, RING_PERIOD - 1], RING_LANES)
    return rng.integers(2**31 - 2**20, 2**31 - 4 * RING_PERIOD, RING_LANES)


def _ring_lanes(name, case, seed=0):
    """A vmapped batch of forecaster states with per-lane phases."""
    rng = np.random.default_rng(seed)
    season = rng.normal(0.0, 50.0, (RING_LANES, RING_PERIOD))
    season[::7] *= 1e-3                       # small and large magnitudes
    season = jnp.asarray(season, jnp.float32)
    t = jnp.asarray(_ring_phases(case, rng), jnp.int32)
    if name == "holt_winters":
        inner = fc.HWState(
            level=jnp.asarray(rng.gamma(2.0, 40.0, RING_LANES), jnp.float32),
            trend=jnp.asarray(rng.normal(0.0, 3.0, RING_LANES), jnp.float32),
            season=season, t=t)
    else:
        inner = SeasonalState(season=season, t=t)
    resid = jnp.asarray(rng.gamma(1.0, 5.0, RING_LANES), jnp.float32)
    ys = jnp.asarray(rng.gamma(2.0, 30.0, (3, RING_LANES)), jnp.float32)
    return FState(inner=inner, resid=resid), ys


def _assert_trees_equal(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


RING_FORECASTERS = ("holt_winters", "seasonal_naive")
RING_PHASES = ("spread", "ends", "int32_top")
RING_HORIZONS = (1, 15, RING_PERIOD, RING_PERIOD + 1, 2 * RING_PERIOD + 10)


@pytest.mark.parametrize("case", RING_PHASES)
@pytest.mark.parametrize("name", RING_FORECASTERS)
def test_ring_update_equals_the_index_form(name, case):
    """Three updates of 4096 lanes, each lane at its own phase: the slot
    mask read and write give the index form's states exactly."""
    f = registry.make(name, period=RING_PERIOD)
    oracle = _gather_forecaster(name)
    st, ys = _ring_lanes(name, case)
    got, want = st, st
    for y in ys:
        got = jax.jit(jax.vmap(f.update))(got, y)
        want = jax.jit(jax.vmap(oracle.update))(want, y)
        _assert_trees_equal(got, want)


@pytest.mark.parametrize("horizon", RING_HORIZONS)
@pytest.mark.parametrize("case", RING_PHASES)
@pytest.mark.parametrize("name", RING_FORECASTERS)
def test_ring_lookahead_equals_the_index_form(name, case, horizon):
    """The horizon max by slot mask equals the max over the gathered
    horizon, for horizons up to and past the period (where a slot is read
    at several steps), each lane at its own phase."""
    f = registry.make(name, period=RING_PERIOD)
    oracle = _gather_forecaster(name)
    st, _ = _ring_lanes(name, case, seed=horizon)
    fcast = jax.jit(jax.vmap(lambda s: f.forecast(s, horizon)))
    want = jax.jit(jax.vmap(lambda s: oracle.forecast(s, horizon)))(st)
    _assert_trees_equal(fcast(st), want)


@pytest.mark.parametrize("horizon", (1, 15, RING_PERIOD + 1))
def test_hw_forecast_reads_the_slot_of_its_horizon(horizon):
    """The single-horizon forecast (``hw_smooth``'s one-step prediction)
    by slot mask equals the index read of slot ``(t + h - 1) % period``."""
    st, _ = _ring_lanes("holt_winters", "spread", seed=7)
    hw = st.inner
    got = jax.vmap(lambda s: fc.hw_forecast(s, horizon))(hw)
    phase = (hw.t + horizon - 1) % RING_PERIOD
    want = hw.level + horizon * hw.trend \
        + jnp.take_along_axis(hw.season, phase[:, None], axis=1)[:, 0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _primitives(jaxpr):
    """Every primitive name in a jaxpr, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _primitives(inner)


@pytest.mark.parametrize("method", ("update", "forecast"))
@pytest.mark.parametrize("name", RING_FORECASTERS)
def test_ring_forecasters_lower_without_gather_or_scatter(name, method):
    """Vmapped over lanes with per-lane phases, neither ring forecaster's
    update nor its 15-minute forecast holds a gather or a scatter: what
    makes the lane scan's forecast stage cheap on every platform."""
    f = registry.make(name, period=RING_PERIOD)
    st, ys = _ring_lanes(name, "spread")
    if method == "update":
        jaxpr = jax.make_jaxpr(jax.vmap(f.update))(st, ys[0])
    else:
        jaxpr = jax.make_jaxpr(jax.vmap(lambda s: f.forecast(s, 15)))(st)
    prims = set(_primitives(jaxpr.jaxpr))
    assert not {p for p in prims if "gather" in p or "scatter" in p}, prims
    assert "select_n" in prims


# -------------------------------------------------------------- conformal ----
@pytest.fixture(scope="module")
def stationary_traces():
    traces = generate_traces(n_functions=12, n_days=1, seed=99,
                             mix={Archetype.STATIONARY_NOISY: 1.0})
    return traces.counts          # [12, 1440]


@pytest.mark.parametrize("alpha", [0.8, 0.9, 0.95])
def test_conformal_coverage_near_nominal(stationary_traces, alpha):
    """Split-conformal bands hit their nominal coverage within +-5 pts
    on held-out halves of stationary Azure-like traces."""
    f = registry.make("ewma")
    calib = stationary_traces[:, :720]
    test = stationary_traces[:, 720:]
    band = conformal.calibrate(f, calib, alpha=alpha)
    cov = conformal.coverage(f, band, test)
    assert abs(cov - alpha) <= 0.05, (cov, alpha)


def test_conformal_band_feeds_interval_and_confidence(stationary_traces):
    f = registry.make("ewma")
    lo = conformal.calibrate(f, stationary_traces, alpha=0.5)
    hi = conformal.calibrate(f, stationary_traces, alpha=0.95)
    assert float(hi.q) > float(lo.q)          # wider band at higher alpha
    # lower alpha -> narrower band -> higher confidence
    assert float(conformal.confidence(lo)) > float(conformal.confidence(hi))

    wrapped = conformal.wrap(f, hi)
    st = wrapped.init()
    for v in stationary_traces[0, :120]:
        st = wrapped.update(st, jnp.float32(v))
    iv1 = wrapped.forecast(st, 1)
    iv9 = wrapped.forecast(st, 9)
    assert float(iv1.hi - iv1.point) == pytest.approx(float(hi.q), rel=1e-5)
    # sqrt-horizon widening: 9 steps -> 3x the one-step half-width
    assert float(iv9.hi - iv9.point) == pytest.approx(3 * float(hi.q),
                                                      rel=1e-5)


def test_margin_multiplier_monotone_under_decreasing_confidence():
    cs = jnp.linspace(1.0, 0.0, 21)
    ms = np.asarray(uncertainty.margin_multiplier(cs))
    assert (np.diff(ms) >= -1e-7).all()       # conf down -> margin up
    assert ms[0] == pytest.approx(1.0) and ms[-1] == pytest.approx(1.5)


def test_interval_confidence_monotone_in_width():
    from repro.forecast.api import Interval
    point = jnp.float32(100.0)
    widths = [0.0, 10.0, 50.0, 200.0]
    cs = [float(interval_confidence(
        Interval(point, point - w / 2, point + w / 2))) for w in widths]
    assert cs[0] == pytest.approx(1.0)
    assert all(a > b for a, b in zip(cs, cs[1:]))
    assert all(0.0 <= c <= 1.0 for c in cs)


def test_interval_confidence_idle_trace_stays_high():
    """An idle/near-zero trace must not collapse confidence: with the
    scale floored at MIN_CONF_SCALE (1 req/min), a tight band around a
    ~0 point forecast reads as near-certain, not maximally uncertain."""
    from repro.forecast.api import Interval, MIN_CONF_SCALE
    f = registry.make("ewma")
    st = f.init()
    for _ in range(60):                 # a workload that is simply idle
        st = f.update(st, jnp.float32(0.0))
    iv = f.forecast(st, 15)
    assert float(iv.point) == pytest.approx(0.0, abs=1e-6)
    assert float(interval_confidence(iv)) > 0.95
    # exact floor semantics: c = floor / (floor + width)
    zero = Interval(jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.5))
    assert float(interval_confidence(zero)) == pytest.approx(
        MIN_CONF_SCALE / (MIN_CONF_SCALE + 0.5), rel=1e-6)
    # a caller-tracked scale still tightens the floor
    assert float(interval_confidence(zero, scale=jnp.float32(10.0))) \
        == pytest.approx(10.0 / 10.5, rel=1e-6)


# --------------------------------------- wired into the control plane ----
def test_aapa_scales_with_named_forecaster_and_conformal_confidence(
        stationary_traces):
    """Acceptance: registry.make("aapa") runs end-to-end with a named
    forecaster + conformal band, and the band's width actually modulates
    Algorithm 1 (conf = classifier x interval signal)."""
    from repro.scaling import registry as scaling_registry
    from repro.sim.cluster import SimConfig, simulate

    cfg = SimConfig()
    f = registry.make("ewma")
    band = conformal.calibrate(f, stationary_traces[:, :720], alpha=0.9)
    ctrl = scaling_registry.make("aapa", cfg, forecaster="ewma", band=band)
    out = simulate(jnp.asarray(stationary_traces[0]), ctrl, cfg)
    assert float(out.served.sum()) > 0

    # eager wiring check: drive on_minute to a reclassify boundary
    ctrl_plain = scaling_registry.make("aapa", cfg, forecaster="ewma",
                                       forecast_confidence=False)
    hist = jnp.asarray(stationary_traces[0, :60])
    st_band = ctrl.init()
    st_plain = ctrl_plain.init()
    for m in range(1, 21):
        st_band = ctrl.on_minute(st_band, hist, jnp.int32(m))
        st_plain = ctrl_plain.on_minute(st_plain, hist, jnp.int32(m))
    # default classifier confidence is 0.5; the conformal path multiplies
    # by the interval signal in (0, 1), the plain path does not
    assert float(st_plain.conf) == pytest.approx(0.5)
    assert 0.0 < float(st_band.conf) < 0.5
    expected = 0.5 * float(interval_confidence(
        conformal.wrap(f, band).forecast(st_band.fc, 15), band.scale))
    assert float(st_band.conf) == pytest.approx(expected, rel=1e-5)


def test_forecast_batch_simulator_bit_exact():
    """Acceptance: forecasters x policies x workloads in one jitted scan,
    bit-exact against each per-forecaster standalone simulation."""
    from repro.scaling import batch, registry as scaling_registry
    from repro.sim.cluster import SimConfig, make_simulator

    cfg = SimConfig()
    rng = np.random.default_rng(7)
    rates = jnp.asarray(rng.poisson(900, (2, 75)).astype(np.float32))
    fore = ("holt_winters", "ewma", "linear_trend")
    pols = ("predictive", "aapa")
    out = batch.make_forecast_batch_simulator(pols, fore, cfg)(rates)
    assert out.served.shape == (3, 2, 2, 75)
    for fi, f in enumerate(fore):
        for pi, p in enumerate(pols):
            single = make_simulator(
                scaling_registry.make(p, cfg, forecaster=f), cfg)(rates)
            for field in ("served", "violated", "replica_seconds",
                          "ready_mean"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(out, field)[fi, pi]),
                    np.asarray(getattr(single, field)),
                    err_msg=f"{f}/{p}.{field}")


def test_forecast_batch_simulator_rejects_forecasterless_policy():
    from repro.scaling import batch
    with pytest.raises(TypeError):
        batch.make_forecast_batch_simulator(("hpa",), ("ewma",))
