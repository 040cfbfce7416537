"""JAX histogram-GBDT classifier."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import gbdt


@pytest.fixture(scope="module")
def trained():
    rng = np.random.default_rng(0)
    N = 4000
    X = rng.normal(size=(N, 6)).astype(np.float32)
    y = ((X[:, 0] > 0).astype(int) * 2 + (X[:, 1] * X[:, 2] > 0)).astype(
        np.int32)  # 4 classes, nonlinear
    cfg = gbdt.GBDTConfig(n_rounds=30, depth=4)
    params = gbdt.fit(X, y, cfg)
    return X, y, params


def test_learns_nonlinear_4class(trained):
    X, y, params = trained
    acc = float((np.asarray(gbdt.predict(params, jnp.asarray(X))) == y
                 ).mean())
    assert acc > 0.93


def test_proba_normalized(trained):
    X, _, params = trained
    proba = np.asarray(gbdt.predict_proba(params, jnp.asarray(X[:100])))
    np.testing.assert_allclose(proba.sum(1), 1.0, rtol=1e-5)
    assert proba.min() >= 0.0


def test_save_load_roundtrip(tmp_path, trained):
    X, _, params = trained
    path = str(tmp_path / "model.npz")
    gbdt.save(params, path)
    loaded = gbdt.load(path)
    a = np.asarray(gbdt.predict_logits(params, jnp.asarray(X[:50])))
    b = np.asarray(gbdt.predict_logits(loaded, jnp.asarray(X[:50])))
    np.testing.assert_array_equal(a, b)


def test_class_weights_help_rare_class():
    rng = np.random.default_rng(1)
    N = 6000
    X = rng.normal(size=(N, 4)).astype(np.float32)
    y = np.zeros(N, np.int32)
    rare = rng.choice(N, size=60, replace=False)     # 1% rare class
    y[rare] = 1
    X[rare, 0] += 3.0
    cfg = gbdt.GBDTConfig(n_classes=2, n_rounds=20, class_weighted=True)
    params = gbdt.fit(X, y, cfg)
    pred = np.asarray(gbdt.predict(params, jnp.asarray(X)))
    recall = (pred[rare] == 1).mean()
    assert recall > 0.8


def test_binning_monotonic():
    X = np.linspace(0, 1, 1000)[:, None].astype(np.float32)
    edges = gbdt.compute_bin_edges(X, 64)
    b = np.asarray(gbdt.bin_features(jnp.asarray(X), jnp.asarray(edges)))
    assert (np.diff(b[:, 0]) >= 0).all()
    assert b.min() >= 0 and b.max() <= 63


def _rows(kind: str, edges: np.ndarray, n: int = 256) -> np.ndarray:
    """Feature rows [n, F] against per-feature edges [F, B-1]: drawn
    like the edges, equal to an edge, or at the extremes (below the
    first edge, above the last, +-inf, NaN, signed zeros)."""
    rng = np.random.default_rng(7)
    F, E = edges.shape
    if kind == "spread":
        return (rng.lognormal(0.0, 2.0, (n, F)) - 1.0).astype(np.float32)
    if kind == "on_edges":
        j = rng.integers(0, E, (n, F))
        j[0], j[1] = 0, E - 1
        return edges[np.arange(F)[None, :], j]
    pool = np.stack([edges[:, 0] - 1.0, edges[:, -1] + 1.0,
                     np.full(F, np.inf), np.full(F, -np.inf),
                     np.full(F, np.nan), np.full(F, -0.0), np.zeros(F),
                     np.full(F, np.finfo(np.float32).max),
                     np.full(F, -np.finfo(np.float32).max)])
    return pool[rng.integers(0, len(pool), (n, F)),
                np.arange(F)[None, :]].astype(np.float32)


@pytest.mark.parametrize("kind", ["spread", "on_edges", "extremes"])
@pytest.mark.parametrize("rounds,depth", [(6, 3), (60, 4)])
def test_row_evaluator_matches_the_table_path(rounds, depth, kind):
    """`row_logits`, vmapped over rows, takes the same bins, leaves and
    logits as the host table path, and `make_classify` (which runs it)
    the same class and confidence as `predict_logits` + calibration."""
    import jax
    from perfbench import classifier
    from perfbench.tests import fakes
    from repro.core import calibration
    clf = fakes.random_classifier(seed=rounds + depth, rounds=rounds,
                                  depth=depth)
    params = gbdt.GBDTParams(
        feat=jnp.asarray(clf.feat), thresh=jnp.asarray(clf.thresh),
        leaf=jnp.asarray(clf.leaf), bin_edges=jnp.asarray(clf.bin_edges),
        base=jnp.asarray(clf.base))
    X = jnp.asarray(_rows(kind, np.asarray(clf.bin_edges)))

    bins = gbdt.bin_features(X, params.bin_edges)
    row_bins = jax.vmap(gbdt.bin_row, in_axes=(None, 0))(params.bin_edges, X)
    np.testing.assert_array_equal(np.asarray(row_bins), np.asarray(bins))

    vals = np.asarray(gbdt.traverse_tables(params.tables, bins))
    row_vals = jax.vmap(gbdt.traverse_row, in_axes=(None, 0))(
        params.tables, bins)
    np.testing.assert_array_equal(np.asarray(row_vals), vals)

    # the per-class sum of T / K leaf values, in float32
    K = params.base.shape[0]
    want = np.asarray(gbdt.predict_logits(params, X))
    got = np.asarray(jax.jit(jax.vmap(gbdt.row_logits, in_axes=(None, 0)))(
        params, X))
    scale = np.abs(vals).reshape(len(vals), -1, K).sum(axis=1)
    np.testing.assert_array_less(
        np.abs(got - want),
        np.finfo(np.float32).eps * (rounds * scale + np.abs(want)) + 1e-30)

    cal = calibration.BetaCalibration(jnp.asarray(clf.cal_a_raw),
                                      jnp.asarray(clf.cal_b_raw),
                                      jnp.asarray(clf.cal_c))
    calp = np.asarray(calibration.calibrate(
        cal, jax.nn.softmax(jnp.asarray(want), axis=-1)))
    arch, conf = jax.jit(jax.vmap(classifier.program_classify(clf)))(X)
    np.testing.assert_array_equal(np.asarray(arch), calp.argmax(axis=1))
    np.testing.assert_allclose(np.asarray(conf), calp.max(axis=1),
                               rtol=1e-6)
