"""Deterministic tier-1 kernel/oracle parity smoke (interpret mode).

The parametrized sweeps in test_kernels.py are nightly (`slow`) and
test_kernel_properties.py degrades to seeded replay without hypothesis —
this file is the per-PR floor: one fixed small shape per Pallas kernel
(`window_features`, `holt_winters`, the fused-decide
`episode_block` for every registry policy, and the GBDT node-table
kernel), seconds to run, so a kernel regression is caught in the same CI
pass that introduced it.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import gbdt
from repro.kernels import ops, ref
from repro.scaling import registry
from repro.sim.cluster import SimConfig


def _tiny_gbdt():
    """A real (tiny) trained GBDT so the AAPA-family smoke exercises
    actual node-table inference inside the kernel, not the constant
    fallback classifier."""
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


def test_window_features_small_shape_parity():
    rng = np.random.default_rng(42)
    x = rng.gamma(2.0, 10.0, size=(8, 60)).astype(np.float32)
    x[0, :] = 0.0                        # all-zero window
    x[4, 30] = 1e5                       # spike outlier
    got = np.asarray(ops.window_features(jnp.asarray(x), interpret=True))
    want = np.asarray(ref.window_features_ref(jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("t", (120, 1100))
def test_holt_winters_small_shape_parity(t):
    """t=1100 spans three time blocks of the kernel grid, so the level /
    trend / season state carried in VMEM scratch across blocks is
    exercised."""
    rng = np.random.default_rng(7)
    y = rng.gamma(2.0, 5.0, size=(4, t)).astype(np.float32)
    got = np.asarray(ops.holt_winters(jnp.asarray(y), period=24,
                                      interpret=True))
    want = np.asarray(ref.holt_winters_ref(jnp.asarray(y), period=24))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


# ci=30 keeps the unrolled-tick jaxpr small (the 29-tick remainder goes
# through lax.scan) so each policy compiles in seconds under interpret.
_EP_CFG = SimConfig(control_interval_sec=30)


@pytest.mark.parametrize("policy", registry.available())
def test_episode_block_policy_parity(policy):
    """Fused-decide episode kernel == CPU blocked-scan oracle for every
    registry policy, on a lane count (5) that does not divide the tile
    (4). AAPA-family policies run a real tiny GBDT classifier with
    stride_min=2 so in-kernel reclassification fires mid-episode."""
    rng = np.random.default_rng(5)
    rates = jnp.asarray(rng.uniform(0.0, 200.0, size=(5, 6)), jnp.float32)
    kw = {}
    if registry.spec(policy).needs_classifier:
        kw = dict(classify=_gbdt_classify(_tiny_gbdt()), stride_min=2)
    ctrl = registry.get_controller(policy, _EP_CFG, **kw)
    got = ops.episode_block(rates, ctrl, _EP_CFG, tile_b=4,
                            interpret=True)
    want = ref.episode_block_ref(rates, ctrl, _EP_CFG)
    for i, (a, e) in enumerate(zip(got, want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=3e-6, atol=1e-4,
                                   err_msg=f"{policy} MinuteOut[{i}]")


def test_gbdt_tables_small_shape_parity():
    """Node-table kernel == the host table path (identical traversal
    over the identical layout), on a row count that does not divide the
    tile. Ulp-tight, not bitwise: the kernel and the host path are two
    compiled programs, and XLA may order the per-class leaf reduction
    differently in each (a few f32 ulp at logit magnitudes ~0.1-1)."""
    params = _tiny_gbdt()
    rng = np.random.default_rng(23)
    X = jnp.asarray(rng.normal(size=(37, 38)).astype(np.float32))
    got = np.asarray(ops.gbdt_logits(params, X, tile_n=16,
                                     interpret=True))
    want = np.asarray(ref.gbdt_logits_ref(params, X))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-6)
