"""Control-period-blocked scan vs the retained seed tick-level scan.

The blocked fast path (`simulate`) runs `controller.decide` once per
control interval; the reference path (`simulate_reference`) keeps the
seed semantics — decide evaluated on every one-second tick and masked
off-interval. The two are BIT-EXACT by construction: the masked decides
were fully discarded and every masked action is an exact float identity
(see the sim.cluster module docstring). That claim is about the float
*semantics* — same operations in the same order — and is pinned here by
`test_bit_exact_semantics`, which compares op-for-op under
`jax.disable_jit()` for every registry policy, including a
control interval that does not divide 60 (remainder-block semantics:
the last block simply runs the leftover ``60 % ci`` ticks).

The compiled programs are additionally pinned tightly (rtol 2e-6) over
policies x scenarios x control intervals. Compiled comparisons cannot be
bitwise in general: XLA/LLVM may FMA-contract a mul+add chain inside a
policy's `decide` in one program embedding and not the other, which on
chaotic inputs (burst_storm's 1e5-scale spikes) occasionally moves a
`ceil` by one. The plant math itself is written contraction-stable (see
`_flow_tick`), so in like-for-like embeddings the compiled paths agree
bitwise too — but only the eager pin is a structural guarantee."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypo_compat import given, settings, st

from repro.scaling import registry, scenarios
from repro.scaling.api import Controller
from repro.sim import cluster as SC
from repro.sim.cluster import SimConfig, simulate, simulate_reference

W, MINUTES = 2, 45
SCENARIOS = ("burst_storm", "idle_wake", "archetype_mix")

_SIM_CACHE: dict = {}


def _batched(ci: int):
    """One jitted blocked + one jitted reference batch over every
    registry policy, cached per control interval so the scenario sweep
    reuses the compiles (all scenarios share the [W, MINUTES] shape)."""
    if ci not in _SIM_CACHE:
        cfg = SimConfig(control_interval_sec=ci)
        ctrls = [registry.get_controller(n, cfg)
                 for n in registry.available()]

        def stack(sim_fn):
            def run(rates):
                outs = [jax.vmap(lambda r, c=c: sim_fn(r, c, cfg))(rates)
                        for c in ctrls]
                return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
            return jax.jit(run)

        _SIM_CACHE[ci] = (stack(simulate), stack(simulate_reference))
    return _SIM_CACHE[ci]


def _assert_bit_exact(blocked, reference, ctx):
    for field in blocked._fields:
        b = np.asarray(getattr(blocked, field))
        r = np.asarray(getattr(reference, field))
        np.testing.assert_array_equal(b, r, err_msg=f"{ctx}.{field}")


def _assert_ulp_tight(blocked, reference, ctx):
    for field in blocked._fields:
        b = np.asarray(getattr(blocked, field))
        r = np.asarray(getattr(reference, field))
        np.testing.assert_allclose(b, r, rtol=2e-6, atol=2e-4,
                                   err_msg=f"{ctx}.{field}")


def test_bit_exact_semantics():
    """THE parity pin: op-for-op (eager) the blocked scan reproduces the
    seed tick-level scan bit-for-bit, every registry policy, at ci=7
    (8 full blocks + a 4-tick remainder block per minute)."""
    cfg = SimConfig(control_interval_sec=7)
    rng = np.random.default_rng(3)
    rates = jnp.asarray(rng.poisson(2000, 2).astype(np.float32))
    with jax.disable_jit():
        for name in registry.available():
            ctrl = registry.get_controller(name, cfg)
            _assert_bit_exact(simulate(rates, ctrl, cfg),
                              simulate_reference(rates, ctrl, cfg),
                              f"eager ci=7 {name}")


@pytest.mark.parametrize("ci", (15, 7))
@pytest.mark.parametrize("scenario", SCENARIOS)
def test_blocked_matches_reference_all_policies(ci, scenario):
    """Compiled: every registry policy at the default 15 s interval and
    at 7 s (60 % 7 != 0: exercises the remainder block)."""
    sc = scenarios.get(scenario, n_workloads=W, minutes=MINUTES, seed=3)
    rates = jnp.asarray(sc.rates, jnp.float32)
    blocked_fn, ref_fn = _batched(ci)
    _assert_ulp_tight(blocked_fn(rates), ref_fn(rates),
                      f"ci={ci} {scenario}[all-policies]")


@pytest.mark.parametrize("ci", (1, 13, 20, 45, 60, 90))
def test_blocked_matches_reference_interval_sweep(ci):
    """Interval sweep on two policies covering both plant-block regimes:
    ci=1 (every tick a head), non-divisors 13/20/45, ci=60 (one decide a
    minute, 59-tick scan block), ci=90 (> 60: clamped, still one head)."""
    cfg = SimConfig(control_interval_sec=ci)
    rng = np.random.default_rng(11)
    rates = jnp.asarray(rng.poisson(1500, 40).astype(np.float32))
    for name in ("hpa", "kpa"):
        ctrl = registry.get_controller(name, cfg)
        _assert_ulp_tight(simulate(rates, ctrl, cfg),
                          simulate_reference(rates, ctrl, cfg),
                          f"ci={ci} {name}")


def test_remainder_block_head_schedule():
    """ci=7 must place decides at sec 0,7,...,56 within each minute —
    exactly where the reference's `sec % ci == 0` mask is true. A
    controller whose every applied decide is a scaling action sees
    ceil(60/7)=9 actions per minute on both paths."""
    cfg = SimConfig(control_interval_sec=7)

    def counting(cfg):
        # desired alternates above/below total so every applied decide is
        # a scaling action; ups+downs then counts applied decides
        def init():
            return jnp.float32(0.0)

        def on_minute(state, hist, minute_idx):
            return state

        def decide(state, obs):
            desired = jnp.where(state % 2 == 0, obs.ready_total + 2.0,
                                jnp.maximum(obs.ready_total - 2.0, 1.0))
            return state + 1.0, desired, jnp.float32(0.0)

        return Controller("counting", init, on_minute, decide)

    rates = jnp.full((3,), 600.0, jnp.float32)
    out = simulate(rates, counting(cfg), cfg)
    ref = simulate_reference(rates, counting(cfg), cfg)
    np.testing.assert_array_equal(np.asarray(out.ups + out.downs),
                                  np.asarray(ref.ups + ref.downs))
    assert float((out.ups + out.downs)[1]) == pytest.approx(9.0)


def test_blocked_is_the_default_everywhere():
    """minute_step (what evals scans) IS the blocked minute; the
    reference spelling stays exported for parity work."""
    assert SC.minute_step is SC._minute_blocked
    assert SC.minute_step_reference is SC._minute_reference


def _plant_lanes(rng, b, s):
    """Random but physical lane state for `advance_plant`: non-negative,
    pipe_sum consistent with the pipeline, a zero minute accumulator."""
    pipeline = rng.gamma(1.0, 0.6, (b, s)).astype(np.float32)
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    return dict(
        ready=f32(rng.gamma(2.0, 2.0, b)), pipeline=f32(pipeline),
        pipe_sum=f32(pipeline.sum(axis=1)),
        queue=f32(rng.gamma(1.0, 25.0, b)),
        wait_sum=f32(rng.gamma(1.0, 5.0, b)),
        util_ema=f32(rng.random(b)),
        cooldown=f32(rng.uniform(0.0, 20.0, b)),
        acc=tuple(jnp.zeros((b,), jnp.float32) for _ in range(11)),
        arrivals=f32(rng.gamma(2.0, 30.0, b)))


def _advance(lanes, n_ticks, cfg=SimConfig()):
    state, acc = SC.advance_plant(cfg, *lanes.values(), n_ticks)
    return [np.asarray(x) for x in (*state, *acc)]


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=1, max_value=9),
       st.integers(min_value=3, max_value=40),
       st.integers(min_value=1, max_value=SC._UNROLL_MAX_TICKS))
def test_advance_plant_unrolled_matches_scan(b, s, n_ticks):
    """The unrolled short-block form of `advance_plant` (static pipeline
    reads, one shift at block end, one cooldown step) is bit-identical
    op-for-op to the per-tick scan it replaces, including blocks longer
    than the startup pipeline (s < n_ticks)."""
    lanes = _plant_lanes(np.random.default_rng(b * 7919 + s * 31 + n_ticks),
                         b, s)
    with jax.disable_jit():
        unrolled = _advance(lanes, n_ticks)
        limit, SC._UNROLL_MAX_TICKS = SC._UNROLL_MAX_TICKS, 0
        try:
            scanned = _advance(lanes, n_ticks)
        finally:
            SC._UNROLL_MAX_TICKS = limit
    for i, (u, c) in enumerate(zip(unrolled, scanned)):
        np.testing.assert_array_equal(u, c, err_msg=f"output[{i}]")


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=2, max_value=11),
       st.integers(min_value=1, max_value=45))
def test_advance_plant_lanes_independent(b, n_ticks):
    """`advance_plant` is shape-agnostic: on a batch of lanes (the fused
    P x W layout of scaling.batch) each lane evolves exactly as it does
    alone, in both the unrolled and the scanned regime."""
    lanes = _plant_lanes(np.random.default_rng(b * 131 + n_ticks), b, 30)
    solo = {k: (tuple(a[:1] for a in v) if k == "acc" else v[:1])
            for k, v in lanes.items()}
    with jax.disable_jit():
        many, one = _advance(lanes, n_ticks), _advance(solo, n_ticks)
    for i, (m, o) in enumerate(zip(many, one)):
        np.testing.assert_array_equal(m[:1], o, err_msg=f"output[{i}]")


@pytest.mark.slow
def test_bit_exact_semantics_default_interval():
    """Nightly: the eager bitwise pin again at the default 15 s interval
    over a longer trace."""
    cfg = SimConfig()
    rng = np.random.default_rng(9)
    rates = jnp.asarray(rng.poisson(1500, 4).astype(np.float32))
    with jax.disable_jit():
        for name in registry.available():
            ctrl = registry.get_controller(name, cfg)
            _assert_bit_exact(simulate(rates, ctrl, cfg),
                              simulate_reference(rates, ctrl, cfg),
                              f"eager ci=15 {name}")


@pytest.mark.slow
def test_blocked_matches_reference_long_trace():
    """Nightly: a day-long trace stays ulp-tight (no slow drift between
    the incremental pipe_sum bookkeeping of the two paths)."""
    cfg = SimConfig()
    rng = np.random.default_rng(7)
    rates = jnp.asarray(rng.poisson(2000, 1440).astype(np.float32))
    for name in registry.available():
        ctrl = registry.get_controller(name, cfg)
        _assert_ulp_tight(simulate(rates, ctrl, cfg),
                          simulate_reference(rates, ctrl, cfg),
                          f"long-trace {name}")
