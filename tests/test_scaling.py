"""The unified scaling control plane: registry round-trips, batched
policies x workloads parity with the per-policy simulators, hyperparam
grid stacking, scenarios, shared cooldown semantics, and sim-vs-engine
adapter parity."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import gbdt
from repro.scaling import api, batch, registry, scenarios
from repro.sim import metrics as M
from repro.sim.cluster import SimConfig, make_simulator, simulate

CFG = SimConfig()


def _rates(shape, lam=1200, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).poisson(lam, shape).astype(np.float32))


def _tiny_gbdt():
    """A real (tiny) trained GBDT, so the classifier policies run actual
    node-table inference, not the constant fallback classifier."""
    rng = np.random.default_rng(11)
    X = rng.normal(size=(96, 38)).astype(np.float32)
    y = rng.integers(0, 4, 96).astype(np.int32)
    return gbdt.fit(X, y, gbdt.GBDTConfig(n_rounds=4, depth=3))


def _gbdt_classify(params):
    def classify(feats):
        logits = gbdt.predict_logits(params, feats[None, :])[0]
        p = jax.nn.softmax(logits)
        return jnp.argmax(p).astype(jnp.int32), jnp.max(p).astype(
            jnp.float32)
    return classify


# ------------------------------------------------------------- registry ----
def test_registry_round_trips_every_policy():
    rates = _rates(90, lam=900)
    for name in registry.available():
        ctrl = registry.get_controller(name, CFG)
        assert ctrl.name == name or name in ctrl.name
        out = simulate(rates, ctrl, CFG)
        assert np.isfinite(np.asarray(out.served)).all()
        assert float(out.served.sum()) > 0


def test_registry_rejects_unknown_policy_and_hyperparam():
    with pytest.raises(KeyError):
        registry.get_controller("nope", CFG)
    with pytest.raises(TypeError):
        registry.get_controller("hpa", CFG, warp_factor=9)


def test_registry_overrides_apply():
    lo = registry.get_controller("hpa", CFG, target=0.3)
    hi = registry.get_controller("hpa", CFG, target=0.95)
    rates = _rates(120, lam=6000)
    rep_lo = float(simulate(rates, lo, CFG).replica_seconds.sum())
    rep_hi = float(simulate(rates, hi, CFG).replica_seconds.sum())
    assert rep_lo > rep_hi  # lower CPU target -> more replicas


def test_backcompat_reexports():
    from repro.core.controllers import hpa_controller as old_hpa
    from repro.scaling.policies import hpa_controller as new_hpa
    from repro.sim.cluster import Controller, Obs
    assert old_hpa is new_hpa
    assert Controller is api.Controller and Obs is api.Obs


# ---------------------------------------------------------------- batch ----
def test_batch_simulate_matches_per_policy_simulators():
    """The single compiled policies x workloads scan reproduces each
    standalone make_simulator run (same seeds, allclose)."""
    rates = _rates((3, 120), lam=1500, seed=1)
    names = registry.available()
    ctrls = [registry.get_controller(n, CFG) for n in names]
    out = batch.batch_simulate(ctrls, rates, CFG)       # [P, W, M]
    assert out.served.shape == (len(ctrls), 3, 120)
    for i, ctrl in enumerate(ctrls):
        single = make_simulator(ctrl, CFG)(rates)
        for field in ("served", "violated", "cold_starts",
                      "replica_seconds", "ready_mean", "oscillations"):
            np.testing.assert_allclose(
                np.asarray(getattr(out, field)[i]),
                np.asarray(getattr(single, field)), rtol=1e-5, atol=1e-5,
                err_msg=f"{ctrl.name}.{field}")


# ci=30 keeps the unrolled-tick program small (the 29-tick remainder
# goes through lax.scan), so each policy compiles in seconds.
_CI30 = SimConfig(control_interval_sec=30)


@pytest.mark.parametrize("policy", registry.available())
def test_batch_core_matches_single_lane_core(policy):
    """The batch core (`make_batch_simulator`) equals the single-lane
    core (`make_simulator`) on every MinuteOut field, for every registry
    policy. Classifier policies run a real tiny GBDT with stride_min=2,
    so reclassification fires mid-episode."""
    rates = jnp.asarray(np.random.default_rng(5).uniform(
        0.0, 200.0, size=(5, 6)), jnp.float32)
    kw = {}
    if registry.spec(policy).needs_classifier:
        kw = dict(classify=_gbdt_classify(_tiny_gbdt()), stride_min=2)
    ctrl = registry.get_controller(policy, _CI30, **kw)
    got = batch.make_batch_simulator([ctrl], _CI30)(rates)
    want = make_simulator(ctrl, _CI30)(rates)
    for f in want._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(got, f)[0]), np.asarray(getattr(want, f)),
            rtol=1e-5, atol=1e-5, err_msg=f"{policy}.{f}")


def test_grid_simulator_matches_individual_factories():
    grid = [{"target": 0.5}, {"target": 0.7}, {"target": 0.9}]
    rates = _rates((2, 90), lam=2400, seed=2)
    out = batch.make_grid_simulator("hpa", grid, CFG)(rates)
    assert out.served.shape == (3, 2, 90)
    for i, g in enumerate(grid):
        single = make_simulator(
            registry.get_controller("hpa", CFG, **g), CFG)(rates)
        np.testing.assert_allclose(np.asarray(out.served[i]),
                                   np.asarray(single.served), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(out.ready_mean[i]),
                                   np.asarray(single.ready_mean),
                                   rtol=1e-5)


def test_grid_simulator_sweeps_static_keys():
    """Non-stackable keys (here stabilization_min) are swept via static
    grouping: one compile per distinct static value, grid-order results
    that match the per-candidate factories."""
    grid = [{"target": t, "stabilization_min": s}
            for s in (2.0, 8.0) for t in (0.5, 0.8)]
    rates = _rates((2, 90), lam=2400, seed=3)
    run = batch.make_grid_simulator("hpa", grid, CFG)
    out = run(rates)
    assert out.served.shape == (4, 2, 90)
    assert run._cache_size() == 2        # one compile per static group
    for i, g in enumerate(grid):
        single = make_simulator(
            registry.get_controller("hpa", CFG, **g), CFG)(rates)
        np.testing.assert_allclose(np.asarray(out.served[i]),
                                   np.asarray(single.served), rtol=1e-5,
                                   err_msg=f"grid[{i}]={g}")


def test_grid_simulator_validates_keys_up_front():
    """Typo'd grid keys and fixed kwargs fail eagerly with the accepted
    hyperparameter list, not at trace time inside the factory."""
    with pytest.raises(TypeError, match=r"cooldwon_min.*accepts"):
        batch.make_grid_simulator("hpa", [{"target": 0.5}], CFG,
                                  cooldwon_min=2.0)
    with pytest.raises(TypeError, match=r"grid keys.*accepts"):
        batch.make_grid_simulator("hpa", [{"tarket": 0.5}], CFG)
    with pytest.raises(TypeError, match="also passed as fixed"):
        batch.make_grid_simulator("hpa", [{"target": 0.5}], CFG,
                                  target=0.7)


# ------------------------------------------------------------ scenarios ----
def test_scenarios_shapes_and_sweeps():
    sc = scenarios.get("burst_storm", n_workloads=4, minutes=180, seed=1)
    assert sc.rates.shape == (4, 180)
    assert (sc.rates >= 0).all()

    swept = scenarios.startup_sweep(values=(10, 60), base="idle_wake",
                                    n_workloads=2, minutes=60)
    assert [s.cfg.startup_sec for s in swept] == [10, 60]
    np.testing.assert_array_equal(swept[0].rates, swept[1].rates)

    for name in scenarios.available():
        s = scenarios.get(name, n_workloads=2, minutes=60)
        assert s.rates.shape[0] == 2 and s.rates.shape[1] == 60


def test_rps_per_replica_sweep_varies_only_the_plant():
    swept = scenarios.rps_per_replica_sweep(values=(5.0, 40.0),
                                            base="archetype_mix",
                                            n_workloads=2, minutes=60)
    assert [s.cfg.rps_per_replica for s in swept] == [5.0, 40.0]
    assert [s.meta["rps_per_replica"] for s in swept] == [5.0, 40.0]
    np.testing.assert_array_equal(swept[0].rates, swept[1].rates)
    # smaller per-replica capacity must need at least as many replicas
    ctrl = lambda cfg: registry.get_controller("hpa", cfg)
    rep = [float(simulate(jnp.asarray(s.rates[0]), ctrl(s.cfg),
                          s.cfg).replica_seconds.sum()) for s in swept]
    assert rep[0] >= rep[1]


def test_startup_sweep_shifts_cold_starts():
    swept = scenarios.startup_sweep(values=(5, 120), base="idle_wake",
                                    n_workloads=1, minutes=120, seed=5)
    cold = []
    for s in swept:
        out = simulate(jnp.asarray(s.rates[0]),
                       registry.get_controller("hpa", s.cfg), s.cfg)
        cold.append(float(out.cold_starts.sum()))
    # slower pod startup can only make wake-from-zero cold starts worse
    assert cold[1] >= cold[0]


def test_archetype_pure_scenario_is_pure():
    sc = scenarios.get("archetype_pure", kind="SPIKE", n_workloads=3,
                       minutes=1440, seed=2)
    assert sc.meta["kind"] == "SPIKE"
    # spike family: heavy-tailed — the day's peak dwarfs the mean floor
    assert sc.rates.max() > 20 * max(sc.rates.mean(), 1.0)


# -------------------------------------------------- cooldown semantics ----
def test_apply_decision_cooldown_blocks_scale_down():
    lim = api.limiter_init()
    t, f = jnp.bool_(True), jnp.float32
    # scale up immediately
    lim, act = api.apply_decision(lim, f(2.0), f(5.0), f(300.0), t)
    assert float(act.add) == 3.0 and float(act.remove) == 0.0
    # scale down starts the cooldown
    lim, act = api.apply_decision(lim, f(5.0), f(2.0), f(300.0), t)
    assert float(act.remove) == 3.0 and float(lim.cooldown) == 300.0
    assert float(act.oscillation) == 1.0  # up then down
    # further scale-down blocked while cooling
    lim, act = api.apply_decision(lim, f(2.0), f(1.0), f(300.0), t)
    assert float(act.remove) == 0.0
    # ...but scale-up is never blocked
    lim, act = api.apply_decision(lim, f(2.0), f(6.0), f(300.0), t)
    assert float(act.add) == 4.0


# ------------------------------------------------------ adapter parity ----
@pytest.fixture(scope="module")
def engine_parts():
    import jax as _jax
    from repro.configs import get_config, smoke_config
    from repro.models import model as Mo
    cfg = smoke_config(get_config("internlm2_1_8b"))
    params = Mo.init(_jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.mark.slow
def test_adapter_matches_sim_steady_state(engine_parts):
    """Constant-rate trace: the engine driven through the adapter and the
    cluster sim driven by the same hpa controller + SimConfig converge to
    the same replica count."""
    from repro.scaling import adapter
    from repro.serve.engine import Request, ServingEngine

    model_cfg, params = engine_parts
    minute_s = 1.0
    steps_per_min = 20
    eng = ServingEngine(model_cfg, params, lanes_per_replica=2,
                        max_replicas=8, step_time_s=minute_s / steps_per_min,
                        startup_s=0.1, slo_s=5.0)
    # fixed gen_len=4 -> 4 steps x 0.05 s = 0.2 engine-s service time
    sim_cfg = adapter.sim_config_for_engine(eng, minute_s=minute_s,
                                            service_s=0.2)
    # short stabilization so both backends settle within the trace
    ctrl = registry.get_controller("hpa", sim_cfg, stabilization_min=2.0,
                                   cooldown_min=2.0)
    auto = adapter.EngineAutoscaler(eng, ctrl, sim_cfg, minute_s=minute_s)

    per_min = 30                      # arrivals per logical minute
    minutes = 20
    rid = 0
    rng = np.random.default_rng(0)
    for _ in range(minutes):
        for s in range(steps_per_min):
            for _ in range(per_min // steps_per_min
                           + (rng.random() < (per_min % steps_per_min)
                              / steps_per_min)):
                eng.submit(Request(rid, eng.t, prompt_len=2, gen_len=4))
                rid += 1
            eng.step()
            auto.on_tick()

    out = simulate(jnp.full((minutes,), float(per_min)), ctrl, sim_cfg)
    sim_final = float(out.ready_mean[-1])
    eng_final = float(eng.ready_replicas)
    # ceil-based HPA has adjacent stable fixed points; both backends must
    # land in the same band (within one replica)
    assert abs(sim_final - eng_final) <= 1.0 + 1e-3, (sim_final, eng_final)
    assert eng.stats.served > 0


def test_scale_to_zero_agrees_across_backends():
    """Idle trace: sim-side controllers go to zero; the shared policy
    decides 0 for the adapter-style Obs too."""
    rates = jnp.zeros(180, jnp.float32)
    out = simulate(rates, registry.get_controller("hpa", CFG), CFG)
    assert float(out.ready_mean[-1]) == pytest.approx(0.0, abs=1e-6)

    ctrl = registry.get_controller("kpa", CFG)
    state = ctrl.init()
    idle_obs = api.Obs(ready_total=jnp.float32(1.0),
                       ready=jnp.float32(1.0),
                       util_ema=jnp.float32(0.0), queue=jnp.float32(0.0),
                       rate_rps=jnp.float32(0.0),
                       rate_history=jnp.zeros(60, jnp.float32),
                       minute_idx=jnp.int32(30))
    for _ in range(40):               # drain the stable window EMA
        state, desired, _ = ctrl.decide(state, idle_obs)
    assert float(desired) == 0.0


class FakeEngine:
    """Duck-typed stand-in for ServingEngine: just the attributes the
    adapter senses and the `scale_to` actuator, with manual time."""

    def __init__(self, *, ready=2, lanes=2, startup_s=6.0, slo_s=1.0,
                 max_replicas=10):
        self.ready_replicas = ready
        self.lanes = lanes
        self.startup_s = startup_s
        self.slo_s = slo_s
        self.max_replicas = max_replicas
        self.starting, self.active, self.queue = [], [], []
        self.t = 0.0
        self.arrivals_total = 0
        self.rate = 0.0
        self.scale_calls = []

    def observed_rate(self, window_s):
        return self.rate

    def scale_to(self, n):
        self.scale_calls.append(n)
        self.ready_replicas = n


def test_sim_config_for_engine_converts_to_logical_units():
    from repro.scaling import adapter
    eng = FakeEngine(ready=3, lanes=4, startup_s=6.0, slo_s=1.0)
    # 1 logical minute = 2 engine-seconds -> 30 logical sec per engine sec
    cfg = adapter.sim_config_for_engine(eng, minute_s=2.0, service_s=0.4)
    assert cfg.startup_sec == 180            # 6 engine-s x 30
    assert cfg.service_sec == pytest.approx(0.4 * 30)
    assert cfg.slo_sec == pytest.approx(30.0)
    assert cfg.rps_per_replica == pytest.approx(4 / (0.4 * 30))
    assert cfg.initial_replicas == 3.0
    # identity mapping at minute_s=60
    cfg60 = adapter.sim_config_for_engine(eng, minute_s=60.0, service_s=0.4)
    assert cfg60.startup_sec == 6 and cfg60.service_sec == pytest.approx(0.4)


def test_adapter_cooldown_blocks_scale_down_in_logical_time():
    """A decide() cooldown is logical seconds; with minute_s=2 the
    adapter must hold a second scale-down for cooldown/30 engine-seconds."""
    import jax.numpy as jnp
    from repro.scaling import adapter

    def shrinker(cfg):
        def init():
            return jnp.float32(0.0)
        def on_minute(state, hist, minute_idx):
            return state
        def decide(state, obs):
            return state, obs.ready_total - 1.0, jnp.float32(120.0)
        return api.Controller("shrinker", init, on_minute, decide)

    eng = FakeEngine(ready=8)
    minute_s = 2.0
    cfg = adapter.sim_config_for_engine(eng, minute_s=minute_s,
                                        control_interval_sec=15)
    auto = adapter.EngineAutoscaler(eng, shrinker(cfg), cfg,
                                    minute_s=minute_s)
    # control fires every 15 logical s = 0.5 engine s
    for step in range(1, 9):
        eng.t = step * 0.25
        auto.on_tick()
    # first decision scales 8 -> 7 and starts a 120-logical-s cooldown
    # (= 4 engine s); every later decision within that window is blocked
    assert eng.scale_calls[0] == 7
    assert all(c == 7 for c in eng.scale_calls), eng.scale_calls
    assert auto.last_cooldown_s == pytest.approx(120.0)
    # past the cooldown (4 engine-s later) the next shrink goes through
    # (the clock drains on the first post-expiry decision, which unblocks
    # the one after — the same pre-decay check the simulator compiles)
    eng.t = 4.0 + 0.5
    auto.on_tick()
    eng.t = 5.0
    auto.on_tick()
    assert eng.scale_calls[-1] == 6


def test_adapter_scales_to_zero_on_idle_engine():
    from repro.scaling import adapter
    eng = FakeEngine(ready=2)
    auto = adapter.EngineAutoscaler.from_policy(
        eng, "hpa", minute_s=1.0, cooldown_min=0.0)
    # idle engine: no traffic, empty queue; util EMA decays to ~0
    for step in range(1, 80):
        eng.t = step * 0.25
        auto.on_tick()
    assert eng.scale_calls[-1] == 0
    assert eng.ready_replicas == 0


def test_adapter_from_policy_resolves_forecaster():
    from repro.scaling import adapter
    eng = FakeEngine(ready=2)
    auto = adapter.EngineAutoscaler.from_policy(eng, "predictive",
                                                forecaster="ewma",
                                                minute_s=1.0)
    eng.rate = 5.0
    eng.t = 0.25
    auto.on_tick()
    assert auto.last_desired >= 1.0


def test_metrics_on_batched_output():
    rates = _rates((2, 60), lam=600, seed=3)
    ctrls = [registry.get_controller(n, CFG) for n in ("hpa", "kpa")]
    out = batch.batch_simulate(ctrls, rates, CFG)
    agg = M.aggregate(jax.tree.map(lambda a: a[0], out),
                      workload_axis=True)
    assert 0.0 <= agg.slo_violation_rate <= 1.0
    assert agg.replica_minutes > 0
