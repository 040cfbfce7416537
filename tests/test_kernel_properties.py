"""Property-based kernel/oracle parity (via tests/_hypo_compat: real
hypothesis when installed, seeded replay otherwise): interpret-mode
Pallas kernels vs their pure-jnp `core` oracles across random window
lengths, batch sizes, periods, and deliberately non-multiple-of-tile
shapes — the regimes the fixed parametrized sweeps in test_kernels.py
don't reach."""
import numpy as np
import jax.numpy as jnp
from _hypo_compat import example, given, settings, st

from repro.kernels import ops, ref


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=1, max_value=13),
       st.integers(min_value=40, max_value=96),
       st.integers(min_value=3, max_value=24),
       st.integers(min_value=3, max_value=8))
def test_holt_winters_parity_any_shape(b, t, period, tile_b):
    """Kernel == oracle for arbitrary (batch, time, period, tile) combos,
    including batches that don't divide the sublane tile."""
    rng = np.random.default_rng(b * 7919 + t * 31 + period)
    y = rng.gamma(2.0, 5.0, size=(b, t)).astype(np.float32)
    got = np.asarray(ops.holt_winters(jnp.asarray(y), period=period,
                                      tile_b=tile_b, interpret=True))
    want = np.asarray(ref.holt_winters_ref(jnp.asarray(y), period=period))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=1, max_value=37),
       st.integers(min_value=33, max_value=72),
       st.integers(min_value=5, max_value=48))
def test_window_features_parity_any_shape(n, w, tile_n):
    """Fused feature kernel == oracle for arbitrary window counts/lengths
    and tile sizes that don't divide the batch; includes all-zero and
    spike-contaminated windows."""
    rng = np.random.default_rng(n * 104729 + w)
    x = rng.gamma(2.0, 10.0, size=(n, w)).astype(np.float32)
    x[0, :] = 0.0                       # all-zero window
    x[n // 2, w // 2] = 1e5             # spike outlier
    got = np.asarray(ops.window_features(jnp.asarray(x), tile_n=tile_n,
                                         interpret=True))
    want = np.asarray(ref.window_features_ref(jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


_GBDT_CACHE: dict = {}


def _gbdt_params(seed, rounds, depth):
    """Tiny trained GBDTs, cached per config: fitting dominates the
    example budget otherwise."""
    from repro.core import gbdt
    key = (seed, rounds, depth)
    if key not in _GBDT_CACHE:
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(64, 9)).astype(np.float32)
        y = rng.integers(0, 4, 64).astype(np.int32)
        _GBDT_CACHE[key] = gbdt.fit(
            X, y, gbdt.GBDTConfig(n_rounds=rounds, depth=depth,
                                  n_bins=16))
    return _GBDT_CACHE[key]


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=1, max_value=41),
       st.integers(min_value=4, max_value=24),
       st.integers(min_value=2, max_value=6),
       st.integers(min_value=2, max_value=3))
@example(5, 4, 4, 3)
def test_gbdt_tables_parity_any_shape(n, tile_n, rounds, depth):
    """Node-table kernel == the host table path for arbitrary row counts
    (including non-multiple-of-tile), tile sizes, and tree geometries.
    Ulp-tight, not bitwise: the kernel and the host path are two
    compiled programs, and XLA may order the per-class leaf reduction
    differently in each. The pinned example (n=5, tile_n=4, rounds=4,
    depth=3) differs in 2 of 20 logits by 1.19e-7 absolute, 5.2e-7
    relative."""
    params = _gbdt_params(rounds * 10 + depth, rounds, depth)
    rng = np.random.default_rng(n * 7919 + tile_n)
    X = jnp.asarray(rng.normal(size=(n, 9)).astype(np.float32))
    got = np.asarray(ops.gbdt_logits(params, X, tile_n=tile_n,
                                     interpret=True))
    want = np.asarray(ref.gbdt_logits_ref(params, X))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-6)


@settings(max_examples=4, deadline=None)
@given(st.integers(min_value=1, max_value=7),
       st.integers(min_value=2, max_value=5),
       st.integers(min_value=2, max_value=4))
def test_episode_block_parity_any_shape(b, m, tile_b):
    """Fused-decide episode kernel == CPU blocked-scan oracle for
    arbitrary lane counts (including non-multiple-of-tile), episode
    lengths, and tile sizes. HPA only: each distinct shape recompiles
    the whole episode kernel, so the per-policy sweep lives in the
    deterministic smoke (test_kernel_smoke)."""
    from repro.scaling import registry
    from repro.sim.cluster import SimConfig
    cfg = SimConfig(control_interval_sec=30)
    ctrl = registry.get_controller("hpa", cfg)
    rng = np.random.default_rng(b * 7919 + m * 31 + tile_b)
    rates = jnp.asarray(rng.uniform(0.0, 300.0, size=(b, m)), jnp.float32)
    got = ops.episode_block(rates, ctrl, cfg, tile_b=tile_b,
                            interpret=True)
    want = ref.episode_block_ref(rates, ctrl, cfg)
    for i, (a, e) in enumerate(zip(got, want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=3e-6, atol=1e-4,
                                   err_msg=f"MinuteOut[{i}]")


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=2, max_value=9),
       st.integers(min_value=64, max_value=200))
def test_holt_winters_padding_lanes_inert(b, t):
    """Appending batch rows must not perturb the original rows: the tile
    pad region stays inert through the sequential recurrence."""
    rng = np.random.default_rng(b * 31 + t)
    y = rng.gamma(2.0, 5.0, size=(b, t)).astype(np.float32)
    solo = np.asarray(ops.holt_winters(jnp.asarray(y[:1]), period=12,
                                       interpret=True))
    packed = np.asarray(ops.holt_winters(jnp.asarray(y), period=12,
                                         interpret=True))
    np.testing.assert_allclose(packed[:1], solo, rtol=1e-5, atol=1e-5)
