"""Fleet-scale evaluation: pooled in-scan reductions, W-chunked
execution, the streaming donated fold, loader chunk feeds, and the
8-virtual-device sharded-vs-unsharded parity pins (subprocess; tier-1 —
the acceptance bar for the sharded evaluation plane)."""
import json
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from _hypo_compat import given, settings, st

from repro.aapaset.loader import AAPAsetLoader
from repro.dist import sharding as shd
from repro.evals import fleet, matrix
from repro.evals import metrics as EM
from repro.forecast import backtest
from repro.scaling import batch, registry, scenarios
from repro.sim.cluster import SimConfig, make_simulator, simulate

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

Q_RTOL = 2.5 * EM.quantile_rel_bound()

FLEET_SPEC = fleet.spec("t_fleet", policies=("hpa", "predictive"),
                        scenario="burst_storm", n_workloads=8, w_chunk=4,
                        minutes=40, seed=3)


def _close(a, b, *, rtol):
    """Field-wise EpisodeMetrics comparison; quantiles get the histogram
    half-bin bound (they snap to bin representatives, so tiny weight
    shifts can move them a whole bin)."""
    for field in EM.EpisodeMetrics._fields:
        tol = max(rtol, Q_RTOL) if field.startswith(("p95", "p99")) \
            else rtol
        np.testing.assert_allclose(
            np.asarray(getattr(a, field)), np.asarray(getattr(b, field)),
            rtol=tol, atol=1e-3, err_msg=field)


# ------------------------------------------------ pooled in-scan accums ----
def test_pooled_accum_matches_per_workload_sum():
    """per_workload=False streams the W reduction inside the scan; it
    must agree with the materialize-then-pool path (same adds, different
    f32 order) within host tolerance."""
    cfg = SimConfig()
    sc = scenarios.get("burst_storm", n_workloads=6, minutes=40, seed=0)
    ctrls = [registry.get_controller(n, cfg) for n in ("hpa", "kpa")]
    pool_ref, per_w = matrix.evaluate_controllers(ctrls, sc.rates, cfg)
    pool_stream, none = matrix.evaluate_controllers(
        ctrls, sc.rates, cfg, per_workload=False)
    assert none is None
    assert np.asarray(per_w.served if hasattr(per_w, "served") else
                      per_w.total_requests).shape == (2, 6)
    _close(pool_stream, pool_ref, rtol=2e-4)


def test_accum_update_pooled_equals_summed_updates():
    """Unit pin: one pooled fold over [W] MinuteOut == W scalar folds
    summed — the streaming reduction only reorders f32 adds."""
    import jax
    from repro.sim.cluster import MinuteOut
    rng = np.random.default_rng(0)
    edges = EM.response_edges(64, 600.0)
    W = 4
    fields = {f: jnp.asarray(rng.gamma(2.0, 10.0, (W,)), jnp.float32)
              for f in MinuteOut._fields}
    pooled = EM.accum_update_pooled(EM.accum_init(64),
                                    MinuteOut(**fields), edges)
    summed = EM.accum_init(64)
    for w in range(W):
        one = EM.accum_update(EM.accum_init(64),
                              MinuteOut(**{f: fields[f][w]
                                           for f in MinuteOut._fields}),
                              edges)
        summed = jax.tree.map(jnp.add, summed, one)
    for f in EM.MetricAccum._fields:
        np.testing.assert_allclose(np.asarray(getattr(pooled, f)),
                                   np.asarray(getattr(summed, f)),
                                   rtol=1e-6, err_msg=f)


# --------------------------------------------------------- fleet runner ----
def test_fleet_one_dispatch_matches_stream():
    """The single-dispatch chunk scan and the donated streaming fold run
    the same compiled chunk body in the same order — compiled-program
    tolerance applies."""
    res = fleet.run_fleet(FLEET_SPEC)
    res_s = fleet.run_fleet(FLEET_SPEC, stream=True)
    assert res.meta["dispatches"] == 1
    assert res_s.meta["dispatches"] == FLEET_SPEC.n_chunks
    _close(res.pooled, res_s.pooled, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(res.rei.rei),
                               np.asarray(res_s.rei.rei), rtol=2e-6)


def test_fleet_matches_controller_evaluator():
    """The fleet's chunked pooled metrics agree with the unchunked
    pooled evaluator on the SAME rates (chunking only reorders the f32
    pooling adds)."""
    rates = fleet.build_rates(FLEET_SPEC)            # [C, Wc, M]
    W, M = FLEET_SPEC.n_workloads, FLEET_SPEC.minutes
    flat = rates.reshape(W, M)
    ctrls = fleet.controllers(FLEET_SPEC)
    pool_ref, _ = matrix.evaluate_controllers(
        ctrls, flat, FLEET_SPEC.sim_config(), per_workload=False)
    res = fleet.run_fleet(FLEET_SPEC)
    _close(res.pooled, pool_ref, rtol=2e-4)
    assert res.meta["workloads"] == W
    assert res.meta["lane_minutes_per_sec"] > 0


def test_fleet_spec_validates_chunking():
    with pytest.raises(ValueError, match="must divide"):
        fleet.spec("bad", policies=("hpa",), n_workloads=10, w_chunk=4)


def test_fleet_chunk_rates_deterministic():
    a = fleet.chunk_rates(FLEET_SPEC, 1)
    b = fleet.chunk_rates(FLEET_SPEC, 1)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (FLEET_SPEC.w_chunk, FLEET_SPEC.minutes)
    # distinct chunks draw distinct workloads
    assert not np.array_equal(a, fleet.chunk_rates(FLEET_SPEC, 0))


# ------------------------------------------------- chunked simulators ----
def test_batch_simulator_w_chunk_parity():
    cfg = SimConfig()
    sc = scenarios.get("idle_wake", n_workloads=8, minutes=30, seed=1)
    ctrls = [registry.get_controller(n, cfg) for n in ("hpa", "kpa")]
    full = batch.make_batch_simulator(ctrls, cfg)(jnp.asarray(sc.rates))
    chunked = batch.make_batch_simulator(ctrls, cfg, w_chunk=4)(
        jnp.asarray(sc.rates))
    for f in full._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(chunked, f)), np.asarray(getattr(full, f)),
            rtol=1e-5, atol=1e-5, err_msg=f)
    with pytest.raises(ValueError, match="must divide"):
        batch.make_batch_simulator(ctrls, cfg, w_chunk=3)(
            jnp.asarray(sc.rates))


def test_make_simulator_w_chunk_and_donate():
    cfg = SimConfig()
    sc = scenarios.get("burst_storm", n_workloads=6, minutes=30, seed=2)
    ctrl = registry.get_controller("hpa", cfg)
    full = make_simulator(ctrl, cfg)(jnp.asarray(sc.rates))
    chunked = make_simulator(ctrl, cfg, w_chunk=2, donate=True)(
        jnp.asarray(sc.rates))
    for f in full._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(chunked, f)), np.asarray(getattr(full, f)),
            rtol=1e-5, atol=1e-5, err_msg=f)


@settings(max_examples=4, deadline=None)
@given(st.integers(min_value=1, max_value=4),
       st.integers(min_value=1, max_value=3),
       st.integers(min_value=2, max_value=7))
def test_make_simulator_w_chunk_equals_per_lane_simulate(n_chunks, w_chunk,
                                                          m):
    """At any W, M and a `w_chunk` that divides W, `make_simulator`
    equals `simulate` run on each lane alone."""
    cfg = SimConfig(control_interval_sec=30)
    ctrl = registry.get_controller("hpa", cfg)
    rng = np.random.default_rng(n_chunks * 7919 + w_chunk * 31 + m)
    rates = jnp.asarray(rng.uniform(0.0, 300.0, size=(n_chunks * w_chunk,
                                                      m)), jnp.float32)
    got = make_simulator(ctrl, cfg, w_chunk=w_chunk)(rates)
    one = jax.jit(lambda r: simulate(r, ctrl, cfg))
    for w in range(rates.shape[0]):
        want = one(rates[w])
        for f in want._fields:
            np.testing.assert_allclose(
                np.asarray(getattr(got, f)[w]), np.asarray(getattr(want, f)),
                rtol=1e-5, atol=1e-5, err_msg=f"lane {w} {f}")


_SIMULATORS = {
    "make_simulator": lambda ctrl, cfg: make_simulator(ctrl, cfg),
    "make_batch_simulator": lambda ctrl, cfg: batch.make_batch_simulator(
        [ctrl], cfg),
    "make_batch_simulator_w_chunk": lambda ctrl, cfg:
        batch.make_batch_simulator([ctrl], cfg, w_chunk=2),
}


@pytest.mark.parametrize("build", sorted(_SIMULATORS))
def test_simulators_compile_once(build):
    """Two calls at one shape reuse the one compiled program."""
    cfg = SimConfig(control_interval_sec=30)
    run = _SIMULATORS[build](registry.get_controller("hpa", cfg), cfg)
    rng = np.random.default_rng(0)
    for _ in range(2):
        run(jnp.asarray(rng.uniform(0.0, 200.0, size=(4, 6)), jnp.float32))
    assert run._cache_size() == 1


# ----------------------------------------------------- loader fleet feed ----
def _fake_loader(F=5, T=50) -> AAPAsetLoader:
    series = np.arange(F * T, dtype=np.float32).reshape(F, T)
    return AAPAsetLoader(data=types.SimpleNamespace(series=series),
                         manifest={})


def test_loader_rate_chunks_deterministic_and_sharded():
    ld = _fake_loader()
    a = list(ld.rate_chunks(8, 2, minutes=20, seed=7))
    b = list(ld.rate_chunks(8, 2, minutes=20, seed=7))
    assert len(a) == 4 and all(c.shape == (2, 20) for c in a)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)

    # shards partition the chunk stream disjointly and exhaustively
    s0 = list(ld.rate_chunks(8, 2, minutes=20, seed=7, shard_index=0,
                             num_shards=2))
    s1 = list(ld.rate_chunks(8, 2, minutes=20, seed=7, shard_index=1,
                             num_shards=2))
    assert len(s0) == len(s1) == 2
    np.testing.assert_array_equal(np.stack(a),
                                  np.stack([s0[0], s1[0], s0[1], s1[1]]))

    with pytest.raises(ValueError, match="must divide"):
        next(ld.rate_chunks(7, 2))
    with pytest.raises(ValueError, match="out of range"):
        next(ld.rate_chunks(8, 2, shard_index=2, num_shards=2))


def test_loader_rate_chunks_feed_fleet_stream():
    ld = _fake_loader(F=4, T=FLEET_SPEC.minutes)
    res = fleet.run_fleet(FLEET_SPEC, stream=True,
                          chunks=ld.rate_chunks(FLEET_SPEC.n_workloads,
                                                FLEET_SPEC.w_chunk,
                                                seed=0))
    assert res.meta["workloads"] == FLEET_SPEC.n_workloads
    assert np.all(np.isfinite(np.asarray(res.pooled.slo_violation_rate)))


# --------------------------------------------------- backtest b_chunk ----
def test_backtest_b_chunk_bit_exact():
    """Chunked backtests (including a padded tail) are bit-identical to
    the unchunked [F, B, T] path — each series' lane is independent."""
    rng = np.random.default_rng(0)
    y = rng.gamma(2.0, 50.0, (8, 40)).astype(np.float32)
    fcs = ("ewma", "holt_winters")
    ref = np.asarray(backtest.batch_smooth(fcs, y))
    chunked = np.asarray(backtest.batch_smooth(fcs, y, b_chunk=3))
    np.testing.assert_array_equal(chunked, ref)
    with pytest.raises(ValueError, match="positive"):
        backtest.batch_smooth(fcs, y, b_chunk=0)


# ------------------------------------------------- sharding (1 device) ----
def test_lane_sharding_none_without_mesh():
    assert shd.active() is None
    assert shd.lane_sharding((2, 16, 30)) is None
    # constraints are no-ops too: the sharded step runs on one device
    x = jnp.ones((4, 8))
    np.testing.assert_array_equal(np.asarray(shd.constrain(x, (None, "dp"))),
                                  np.asarray(x))


# ------------------------------------- 8-virtual-device parity (tier-1) ----
def _run_in_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_matrix_matches_unsharded_8dev():
    """THE acceptance pin: the same matrix runner under an 8-device dp
    mesh is bit-close (rtol 2e-6) to the unsharded path — pooled metrics
    and REI — and still compiles exactly once. Also pins the strict=/
    warn-once spec semantics and lane_sharding, which need real multi-
    device axis sizes."""
    code = textwrap.dedent("""
        import json
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.dist import sharding as shd
        from repro.evals import matrix
        from repro.evals import rei as ER

        spec = matrix.spec(
            "t_shard", policies=("hpa", "predictive"),
            scenarios=(("burst_storm", {}),), seeds=(0,),
            n_workloads=8, minutes=60)
        rates = matrix.build_rates(spec)

        def score(pool):
            return ER.rei(pool.slo_violation_rate, pool.replica_minutes,
                          pool.scaling_actions, minutes=spec.minutes,
                          n_workloads=spec.n_workloads).rei

        # unsharded reference (no active mesh: constraints are no-ops)
        pool1, _ = matrix.make_runner(spec)(rates)
        rei1 = score(pool1)

        # 8-way dp mesh; input placed with lane_sharding (W axis = 2)
        mesh = shd.make_mesh((8,), ("data",))
        rules = shd.set_mesh(mesh)
        sh = shd.lane_sharding(rates.shape, w_axis=2, strict=True)
        assert sh.spec == P(None, None, "data", None), sh.spec
        placed = jax.device_put(jnp.asarray(rates, jnp.float32), sh)
        runner = matrix.make_runner(spec)
        with mesh:
            pool2, _ = runner(placed)
            rei2 = score(pool2)
        one_compile = runner._cache_size() == 1
        n_shards = len(pool2.slo_violation_rate.addressable_shards) >= 1

        # the compiled program really sharded: the per-lane plant state
        # is [P, W] with W=8 over 8 devices
        err = max(float(np.max(np.abs(np.asarray(getattr(pool1, f))
                                      - np.asarray(getattr(pool2, f)))
                               / np.maximum(np.abs(
                                   np.asarray(getattr(pool1, f))), 1e-9)))
                  for f in ("slo_violation_rate", "mean_response_ms",
                            "replica_minutes", "avg_cpu_util",
                            "scaling_actions", "total_requests"))
        rei_err = float(np.max(np.abs(np.asarray(rei1)
                                      - np.asarray(rei2))))

        # quantiles snap to bin representatives: equal bins, not rtol
        q_equal = bool(
            np.array_equal(np.asarray(pool1.p95_response_ms),
                           np.asarray(pool2.p95_response_ms))
            and np.array_equal(np.asarray(pool1.p99_response_ms),
                               np.asarray(pool2.p99_response_ms)))

        # strict=/warn-once semantics need a real >1 axis size
        strict_raises = False
        try:
            rules.spec(("dp",), (10,), strict=True)
        except ValueError:
            strict_raises = True
        import repro.dist.sharding as S
        n_warn0 = len(S._WARNED)
        rules.spec(("dp",), (10,))
        rules.spec(("dp",), (10,))
        warn_once = (len(S._WARNED) - n_warn0) == 1
        replicated = rules.spec(("dp",), (10,)) == P(None)

        print(json.dumps({
            "err": err, "rei_err": rei_err, "q_equal": q_equal,
            "one_compile": one_compile, "n_shards": n_shards,
            "strict_raises": strict_raises, "warn_once": warn_once,
            "replicated": replicated,
            "n_devices": jax.device_count()}))
    """)
    res = _run_in_subprocess(code)
    assert res["n_devices"] == 8, res
    assert res["err"] < 2e-6, res
    assert res["rei_err"] < 2e-6, res
    assert res["q_equal"], res
    assert res["one_compile"], res
    assert res["strict_raises"] and res["warn_once"] and res["replicated"], \
        res


def test_sharded_fleet_matches_unsharded_8dev():
    """The fleet runner's one-dispatch chunk scan under the mesh: pooled
    [P] metrics bit-close to the single-device run."""
    code = textwrap.dedent("""
        import json
        import numpy as np, jax, jax.numpy as jnp
        from repro.dist import sharding as shd
        from repro.evals import fleet

        spec = fleet.spec("t_fleet_shard", policies=("hpa",),
                          scenario="burst_storm", n_workloads=32,
                          w_chunk=16, minutes=40, seed=0)
        res1 = fleet.run_fleet(spec)

        mesh = shd.make_mesh((8,), ("data",))
        shd.set_mesh(mesh)
        with mesh:
            res2 = fleet.run_fleet(spec)

        err = max(float(np.max(np.abs(
            np.asarray(getattr(res1.pooled, f))
            - np.asarray(getattr(res2.pooled, f)))
            / np.maximum(np.abs(np.asarray(getattr(res1.pooled, f))),
                         1e-9)))
            for f in ("slo_violation_rate", "mean_response_ms",
                      "replica_minutes", "total_requests"))
        print(json.dumps({
            "err": err,
            "one_dispatch": res2.meta["dispatches"] == 1,
            "mesh": res2.meta["mesh"],
            "n_devices": jax.device_count()}))
    """)
    res = _run_in_subprocess(code)
    assert res["n_devices"] == 8, res
    assert res["one_dispatch"], res
    assert res["mesh"] == {"data": 8}, res
    assert res["err"] < 2e-6, res
