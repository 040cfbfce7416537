"""Decision-telemetry contracts: the off path is free, the on path is
faithful, and blame is conservative.

* telemetry=False compiles to the pre-PR program — MinuteOut bit-exact
  against the telemetry run for every registry policy (single lane and
  the fused batch), so capture can never perturb scores.
* telemetry=True keeps ONE compile on the matrix runner and produces
  traces whose decisions replay the head schedule exactly.
* blame attribution is conservative by construction: per-cause violation
  counts sum to the pooled EpisodeMetrics violation total.
* the engine adapter logs the same DecisionRecord schema the sim scan
  captures — the two streams agree on a shared trace (sim-vs-engine
  telemetry parity).
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.evals import fleet, matrix
from repro.obs import artifacts as OA
from repro.obs import attribute as AT
from repro.obs import trace as T
from repro.scaling import adapter, batch, registry, scenarios
from repro.sim.cluster import SimConfig, simulate

REPO = pathlib.Path(__file__).resolve().parent.parent


def _rates(minutes=90, seed=3):
    cfg = SimConfig()
    sc = scenarios.get("burst_storm", n_workloads=2, minutes=minutes,
                       seed=seed, cfg=cfg)
    return np.asarray(sc.rates, np.float32)


def _ctrl(policy, cfg, **kw):
    if registry.spec(policy).takes_forecaster:
        kw.setdefault("forecaster", "holt_winters")
    return registry.get_controller(policy, cfg, **kw)


# ------------------------------------------------- off-path bit-exactness
@pytest.mark.parametrize("policy", registry.available())
def test_telemetry_off_is_bit_exact_per_policy(policy):
    """The telemetry=False default and the telemetry=True capture run
    the same control path: MinuteOut identical bit for bit."""
    cfg = SimConfig()
    rates = jnp.asarray(_rates()[0])
    ctrl = _ctrl(policy, cfg)
    base = simulate(rates, ctrl, cfg)
    out, ct = simulate(rates, ctrl, cfg, telemetry=True)
    for f, a, b in zip(base._fields, base, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f)
    M = rates.shape[0]
    H = len(T.head_schedule(cfg))
    assert np.asarray(ct.decisions.desired).shape == (M, H)
    assert np.asarray(ct.minutes.rate).shape == (M,)


def test_batch_telemetry_bit_exact_and_lane_sampled():
    """Fused batch path: telemetry (full and lane-sampled) leaves the
    MinuteOut stream bit-exact, and the sampled trace is a slice of the
    full one."""
    cfg = SimConfig()
    rates = _rates()
    ctrls = [_ctrl(p, cfg) for p in ("hpa", "predictive", "aapa")]
    sim0 = batch.make_batch_simulator(ctrls, cfg)
    sim1 = batch.make_batch_simulator(ctrls, cfg, telemetry=True)
    sim2 = batch.make_batch_simulator(ctrls, cfg, telemetry=True,
                                      trace_lanes=1)
    base = jax.block_until_ready(sim0(rates))
    out1, ct1 = jax.block_until_ready(sim1(rates))
    out2, ct2 = jax.block_until_ready(sim2(rates))
    for f, a, b, c in zip(base._fields, base, out1, out2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c),
                                      err_msg=f)
    idx = T.sample_lanes(rates.shape[0], 1)
    np.testing.assert_array_equal(
        np.asarray(ct2.decisions.desired),
        np.asarray(ct1.decisions.desired)[..., idx])


def test_trace_head_schedule_nondividing_interval():
    """ci=7 doesn't divide 60: the trace's sec field must replay the
    blocked scan's exact head schedule (including the tail head)."""
    cfg = SimConfig(control_interval_sec=7)
    rates = jnp.asarray(_rates(minutes=10)[0])
    _, ct = simulate(rates, _ctrl("hpa", cfg), cfg, telemetry=True)
    heads = T.head_schedule(cfg)
    assert heads == [0, 7, 14, 21, 28, 35, 42, 49, 56]
    np.testing.assert_array_equal(
        np.asarray(ct.decisions.sec)[0], np.asarray(heads, np.float32))


def test_explain_signals_per_policy():
    """hpa carries no signals (NaN), predictive carries the forecast,
    aapa adds confidence + archetype, hybrid adds the guard floor."""
    cfg = SimConfig()
    rates = jnp.asarray(_rates(minutes=30)[0])
    traces = {p: simulate(rates, _ctrl(p, cfg), cfg, telemetry=True)[1]
              for p in ("hpa", "predictive", "aapa", "hybrid")}
    d = {p: ct.decisions for p, ct in traces.items()}
    assert np.all(np.isnan(np.asarray(d["hpa"].fc_point)))
    assert np.any(np.isfinite(np.asarray(d["predictive"].fc_point)))
    assert np.all(np.isnan(np.asarray(d["predictive"].confidence)))
    assert np.any(np.isfinite(np.asarray(d["aapa"].confidence)))
    assert np.any(np.isfinite(np.asarray(d["aapa"].archetype)))
    assert np.any(np.isfinite(np.asarray(d["hybrid"].guard_floor)))


# ------------------------------------------------------- matrix + fleet
def test_matrix_runner_telemetry_one_compile_and_bit_exact():
    sp = matrix.smoke_spec()
    rates = matrix.build_rates(sp)
    pool0, perw0 = jax.block_until_ready(matrix.make_runner(sp)(rates))
    run1 = matrix.make_runner(sp, telemetry=True)
    pool1, perw1, ct = jax.block_until_ready(run1(rates))
    assert run1._cache_size() == 1
    for f, a, b in zip(pool0._fields, pool0, pool1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f)
    S, Z, F, P = sp.shape
    H = len(T.head_schedule(sp.sim_config()))
    assert np.asarray(ct.decisions.desired).shape == (
        S, Z, sp.minutes, H, F, P, sp.n_workloads)
    assert np.asarray(ct.minutes.violated).shape == (
        S, Z, sp.minutes, F, P, sp.n_workloads)


def test_fleet_trace_lanes_rides_chunk_scan():
    sp0 = fleet.spec("obs_t", policies=("hpa", "predictive"),
                     n_workloads=8, w_chunk=4, minutes=20, seed=1)
    sp1 = fleet.spec("obs_t", policies=("hpa", "predictive"),
                     n_workloads=8, w_chunk=4, minutes=20, seed=1,
                     trace_lanes=2)
    r0, r1 = fleet.run_fleet(sp0), fleet.run_fleet(sp1)
    assert r0.trace is None
    for f, a, b in zip(r0.pooled._fields, r0.pooled, r1.pooled):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f)
    H = len(T.head_schedule(sp1.sim_config()))
    assert r1.trace.decisions.desired.shape == (2, 20, H, 2, 2)
    assert r1.trace.minutes.rate.shape == (2, 20, 2, 2)
    with pytest.raises(ValueError, match="one-dispatch"):
        fleet.run_fleet(sp1, stream=True)


def test_telemetry_w_chunk_error_names_fleet_front_door():
    """The telemetry+w_chunk rejection must point at the actual recourse:
    FleetSpec(..., trace_lanes=K) via repro.evals.fleet."""
    cfg = SimConfig()
    with pytest.raises(ValueError) as ei:
        batch.make_batch_simulator([_ctrl("hpa", cfg)], cfg, telemetry=True,
                                   w_chunk=4)
    msg = str(ei.value)
    assert "trace_lanes" in msg and "evals.fleet" in msg


# ------------------------------------------------------------ attribution
def test_blame_counts_sum_to_pooled_violations():
    """The acceptance pin: per-cause blame totals over every traced lane
    sum to the pooled EpisodeMetrics violation total (violation_rate x
    arrivals), because each violated minute lands in exactly one cause."""
    sp = matrix.smoke_spec()
    cfg = sp.sim_config()
    rates = matrix.build_rates(sp)
    pool, _, ct = jax.block_until_ready(
        matrix.make_runner(sp, telemetry=True)(rates))
    ct = T.to_numpy(ct)
    blame_total = 0.0
    K = ct.minutes.rate.shape[-1]
    for label, pre, post in OA._lane_labels(sp, K):
        b = AT.attribute(T.lane(ct, pre, post), cfg)
        assert sum(b.counts.values()) == pytest.approx(b.total)
        blame_total += sum(b.counts.values())
    arrived = float(np.asarray(ct.minutes.rate, np.float64).sum())
    pooled_violated = float(
        (np.asarray(pool.slo_violation_rate, np.float64)
         * np.asarray(ct.minutes.rate, np.float64)
            .sum(axis=(2, 5))).sum())
    assert blame_total == pytest.approx(pooled_violated, rel=1e-5)
    assert blame_total > 0 and arrived > 0


def test_blame_cascade_buckets_reachable():
    """capacity_capped and cooldown_suppressed fire on scenarios built
    to trigger them; every minute's cause indexes CAUSES."""
    cfg = SimConfig(max_replicas=3.0)
    rates = jnp.asarray(np.full(20, 20000.0, np.float32))
    _, ct = simulate(rates, _ctrl("hpa", cfg), cfg, telemetry=True)
    b = AT.attribute(T.to_numpy(ct), cfg)
    assert b.counts["capacity_capped"] > 0

    cfg2 = SimConfig()
    lull = np.concatenate([np.full(20, 6000.0), np.full(10, 100.0),
                           np.full(20, 6000.0)]).astype(np.float32)
    _, ct2 = simulate(jnp.asarray(lull), _ctrl("hpa", cfg2), cfg2,
                      telemetry=True)
    b2 = AT.attribute(T.to_numpy(ct2), cfg2)
    assert b2.counts["cooldown_suppressed"] > 0
    for b_ in (b, b2):
        assert set(np.unique(b_.cause)) <= set(range(-1, len(AT.CAUSES)))


def test_blame_tables_render():
    cfg = SimConfig()
    rates = jnp.asarray(_rates(minutes=60)[0])
    _, ct = simulate(rates, _ctrl("aapa", cfg), cfg, telemetry=True)
    ct = T.to_numpy(ct)
    b = AT.attribute(ct, cfg)
    tbl = AT.blame_table({"aapa": b})
    assert "| lane |" in tbl and "aapa" in tbl
    arch = AT.archetype_table(AT.archetype_counts(ct, b))
    assert "archetype" in arch
    tl = AT.timeline(ct, b, max_rows=24)
    # bounded: blamed minutes are always kept, the rest is subsampled
    H = np.asarray(ct.decisions.minute).shape[1]
    n_blamed = int((b.cause >= 0).sum())
    assert tl.count("\n") <= 2 + H * (n_blamed + max(24 // H, 1))
    assert tl.count("\n") < AT.timeline(ct, b, max_rows=10**6).count("\n")
    for m in np.nonzero(b.cause >= 0)[0]:        # blamed minutes kept
        assert f"| {m}m00s |" in tl


# -------------------------------------------------------------- obs cards
def test_obs_card_publish_and_cache(tmp_path):
    sp = matrix.smoke_spec()
    cap = OA.capture_matrix(sp, root=tmp_path)
    assert not cap.cached
    out = OA.capture_dir(sp.name, cap.card["key"], tmp_path)
    assert (out / "card.json").exists()
    assert (out / "trace.npz").exists()
    assert (out / "timeline.md").exists()
    assert cap.card["violations_total"] == pytest.approx(
        sum(cap.card["blame_totals"].values()))
    cap2 = OA.capture_matrix(sp, root=tmp_path)
    assert cap2.cached
    np.testing.assert_array_equal(
        np.asarray(cap.trace.decisions.desired),
        np.asarray(cap2.trace.decisions.desired))
    assert list(cap.blames) == list(cap2.blames)
    with open(out / "card.json") as f:
        card = json.load(f)
    assert card["tables"]["blame"].startswith("| lane |")


# ------------------------------------------------- sim-vs-engine parity
class FakeEngine:
    """Minimal duck-typed engine (mirrors test_scaling.FakeEngine)."""

    def __init__(self, *, ready=2, lanes=20, startup_s=30.0, slo_s=0.5,
                 max_replicas=100):
        self.ready_replicas = ready
        self.lanes = lanes
        self.startup_s = startup_s
        self.slo_s = slo_s
        self.max_replicas = max_replicas
        self.starting, self.active, self.queue = [], [], []
        self.t = 0.0
        self.arrivals_total = 0.0
        self.rate = 0.0

    def observed_rate(self, window_s):
        return self.rate

    def scale_to(self, n):
        self.ready_replicas = n


def test_sim_vs_engine_decision_records_agree():
    """The same rate trace through the compiled sim scan and the eager
    engine adapter yields DecisionRecord streams that agree on the
    predictive policy's desired/cooldown/forecast fields (its decide
    reads only rate history + forecast, which both plants feed
    identically)."""
    cfg = SimConfig()
    minutes = 40
    rng = np.random.default_rng(5)
    rates = np.round(rng.gamma(2.0, 400.0, minutes)).astype(np.float32)

    ctrl = _ctrl("predictive", cfg)
    _, ct = simulate(jnp.asarray(rates), ctrl, cfg, telemetry=True)
    sim_d = T.to_numpy(ct).decisions                      # [M, H]

    eng = FakeEngine(ready=int(cfg.initial_replicas))
    auto = adapter.EngineAutoscaler(eng, _ctrl("predictive", cfg), cfg,
                                    minute_s=60.0)
    heads = T.head_schedule(cfg)
    for m in range(minutes):
        eng.rate = float(rates[m]) / 60.0
        for sec in heads:
            eng.t = m * 60.0 + sec
            auto.on_tick()
        eng.arrivals_total += float(rates[m])
        eng.t = (m + 1) * 60.0 - 1e-9
    eng_d = auto.decision_trace()                         # [N]

    H = len(heads)
    n = min(minutes * H, len(eng_d.desired))
    for field in ("desired_raw", "desired", "cooldown_req", "fc_point"):
        a = np.asarray(getattr(sim_d, field)).reshape(-1)[:n]
        b = np.asarray(getattr(eng_d, field))[:n]
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                   equal_nan=True, err_msg=field)
    np.testing.assert_array_equal(
        np.asarray(sim_d.minute).reshape(-1)[:n], eng_d.minute[:n])
    np.testing.assert_array_equal(
        np.asarray(sim_d.sec).reshape(-1)[:n], eng_d.sec[:n])


def test_run_autoscaled_returns_decision_trace():
    eng = FakeEngine()
    ctrl = _ctrl("hpa", SimConfig())
    summary_calls = {}

    class SummaryEngine(FakeEngine):
        def step(self):
            self.t += 15.0

        def summary(self):
            summary_calls["hit"] = True
            return {"served": 0}

    eng = SummaryEngine()
    summary, trace = adapter.run_autoscaled(
        eng, ctrl, submit_fn=lambda i, e: None, n_steps=8,
        cfg=SimConfig(), minute_s=60.0)
    assert summary_calls["hit"] and summary == {"served": 0}
    assert isinstance(trace, T.DecisionRecord)
    assert len(trace.desired) > 0
    assert np.all(np.isnan(trace.fc_point))     # hpa has no forecast


# ------------------------------------------------------- profile smoke
def test_bench_profile_writes_trace_dir(tmp_path):
    """benchmarks.run --profile captures a non-empty jax.profiler trace
    directory per bench (what the nightly CI job uploads)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "sim", "--smoke",
         "--json", str(tmp_path), "--profile", str(tmp_path / "prof")],
        check=True, cwd=REPO, timeout=3000, env=env)
    traced = list((tmp_path / "prof" / "sim").rglob("*"))
    assert any(p.is_file() for p in traced)
    assert (tmp_path / "BENCH_sim.json").exists()
