"""The configuration's archetype classifier, fitted by the benchmark
itself and cached.

The classifier belongs to the configuration the way weights belong to a
model, and the benchmark makes it, so that nothing the program made
reaches the reference. From the configuration's ``classifier.dataset``
(a fixed seed; ``--seed`` never touches it) the ``archetype_pure``
traffic family draws traces of each of the four archetypes; random
windows of them, labelled by the archetype that drew them, are
featurized by ``reference.window_features``. A plain histogram GBDT
(softmax loss, second-order leaves, level-order trees over per-feature
quantile bins) is fitted on most traces, and a per-class beta
calibration on the rest. The arrays are saved under ``perfbench/.cache/``
in the checkout and loaded by every later run. The program gets a
``TrainedAAPA`` built from them, the reference the same arrays as
``reference.Classifier``.
"""
from __future__ import annotations

import hashlib
import json
import os
import pathlib

import numpy as np

from perfbench import generator, harness, reference

CACHE = pathlib.Path(__file__).resolve().parent / ".cache"


def _path(spec: dict) -> pathlib.Path:
    key = hashlib.sha256(json.dumps(spec, sort_keys=True)
                         .encode()).hexdigest()[:12]
    return CACHE / f"classifier-{key}.npz"


def dataset(ds: dict):
    """(windows [N, window_min] float32, labels [N], held-out mask [N]):
    ``windows_per_trace`` random windows of each of ``traces_per_kind``
    traces per archetype; the last ``calibration_share`` of each
    archetype's traces are held out for the calibration."""
    family = harness.traffic_family(ds["family"])
    n, per = int(ds["traces_per_kind"]), int(ds["windows_per_trace"])
    minutes, width = int(ds["minutes"]), int(ds["window_min"])
    held = np.arange(n) >= n - int(round(n * ds["calibration_share"]))
    xs, ys, hs = [], [], []
    for k, kind in enumerate(family.ARCHETYPES):
        counts = family.pure_counts(kind, n, minutes,
                                    generator.derived_seed(ds["seed"], k))
        rng = np.random.default_rng(generator.derived_seed(ds["seed"], k, 1))
        starts = rng.integers(0, minutes - width + 1, size=(n, per))
        idx = starts[..., None] + np.arange(width)
        xs.append(counts[np.arange(n)[:, None, None], idx]
                  .reshape(n * per, width))
        ys.append(np.full(n * per, k))
        hs.append(np.repeat(held, per))
    return np.concatenate(xs), np.concatenate(ys), np.concatenate(hs)


def features(x: np.ndarray) -> np.ndarray:
    """``reference.window_features`` in float32, on the host's CPU where
    JAX has one."""
    import jax
    try:
        dev = jax.devices("cpu")[0]
    except RuntimeError:
        dev = None
    f = jax.jit(reference.window_features)
    return np.asarray(f(jax.device_put(np.asarray(x, np.float32), dev)))


def bin_edges(X: np.ndarray, n_bins: int) -> np.ndarray:
    """Per-feature quantile edges: X [N, F] -> [F, n_bins - 1] float32."""
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    return np.quantile(X.astype(np.float64), qs, axis=0).T.astype(np.float32)


def binned(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """The bin of each feature value (a value above an edge lies right
    of it), as ``reference.classify`` bins them."""
    return np.stack([np.searchsorted(edges[f], X[:, f], side="right")
                     for f in range(X.shape[1])], 1)


def _tree(xb, g, h, *, depth, n_bins, lam, min_child):
    """One level-order tree on gradients g and hessians h: (feat
    [2^depth - 1], thresh [2^depth - 1], leaf [2^depth], leaf of each
    row). A node without a split sends every row left (threshold
    n_bins - 1)."""
    N, F = xb.shape
    inner = (1 << depth) - 1
    feat = np.zeros(inner, np.int32)
    thresh = np.full(inner, n_bins - 1, np.int32)
    node = np.zeros(N, np.int64)
    rows = np.arange(N)
    cols = np.arange(F) * n_bins
    for d in range(depth):
        width = 1 << d
        key = (node[:, None] * (F * n_bins) + cols + xb).ravel()
        size = width * F * n_bins
        G = np.bincount(key, np.repeat(g, F), size).reshape(width, F, -1)
        H = np.bincount(key, np.repeat(h, F), size).reshape(width, F, -1)
        gl, hl = G.cumsum(-1)[..., :-1], H.cumsum(-1)[..., :-1]
        gt, ht = G.sum(-1, keepdims=True), H.sum(-1, keepdims=True)
        gain = (gl ** 2 / (hl + lam) + (gt - gl) ** 2 / (ht - hl + lam)
                - gt ** 2 / (ht + lam))
        gain = np.where((hl >= min_child) & (ht - hl >= min_child), gain,
                        -np.inf).reshape(width, -1)
        best = gain.argmax(1)
        split = gain[np.arange(width), best] > 0.0
        at = (1 << d) - 1 + np.arange(width)
        feat[at] = np.where(split, best // (n_bins - 1), 0)
        thresh[at] = np.where(split, best % (n_bins - 1), n_bins - 1)
        here = (1 << d) - 1 + node
        node = 2 * node + (xb[rows, feat[here]] > thresh[here])
    L = 1 << depth
    leaf = -np.bincount(node, g, L) / (np.bincount(node, h, L) + lam)
    return feat, thresh, leaf, node


def _softmax(z):
    e = np.exp(z - z.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def fit_gbdt(xb: np.ndarray, y: np.ndarray, spec: dict):
    """Softmax gradient boosting: (feat, thresh, leaf, base) with the
    learning rate folded into the leaves."""
    K, R = int(spec["n_classes"]), int(spec["n_rounds"])
    Y = np.eye(K)[y]
    base = np.log(np.maximum(Y.mean(0), 1e-12))
    logits = np.tile(base, (len(y), 1))
    feat, thresh, leaf = [], [], []
    for _ in range(R):
        P = _softmax(logits)
        trees = [_tree(xb, P[:, k] - Y[:, k],
                       np.maximum(P[:, k] * (1.0 - P[:, k]), 1e-6),
                       depth=int(spec["depth"]), n_bins=int(spec["n_bins"]),
                       lam=float(spec["reg_lambda"]),
                       min_child=float(spec["min_child_weight"]))
                 for k in range(K)]
        for k, (_, _, lv, node) in enumerate(trees):
            logits[:, k] += spec["learning_rate"] * lv[node]
        feat.append([t[0] for t in trees])
        thresh.append([t[1] for t in trees])
        leaf.append([spec["learning_rate"] * t[2] for t in trees])
    return (np.asarray(feat, np.int32), np.asarray(thresh, np.int32),
            np.asarray(leaf, np.float32), base.astype(np.float32))


def predict_logits(xb, feat, thresh, leaf, base) -> np.ndarray:
    """The trees' logits over binned rows xb, in float64."""
    R, K, inner = feat.shape
    depth = int(round(np.log2(inner + 1)))
    rows = np.arange(len(xb))
    logits = np.tile(base.astype(np.float64), (len(xb), 1))
    for r in range(R):
        for k in range(K):
            node = np.zeros(len(xb), np.int64)
            for d in range(depth):
                at = (1 << d) - 1 + node
                node = 2 * node + (xb[rows, feat[r, k, at]]
                                   > thresh[r, k, at])
            logits[:, k] += leaf[r, k, node]
    return logits


def fit_beta(p: np.ndarray, y: np.ndarray, iters: int = 30,
             ridge: float = 1.0):
    """Per class, q = sigmoid(a log p - b log(1 - p) + c) fitted to the
    one-vs-rest labels by damped Newton steps on the log loss (a ridge
    towards the identity a = b = 1, c = 0); a and b are kept above 1e-3.
    Returns (a_raw, b_raw, c) with a = softplus(a_raw)."""
    p = np.clip(p, reference.CAL_EPS, 1.0 - reference.CAL_EPS)
    w0 = np.array([1.0, 1.0, 0.0])
    out = []
    for k in range(p.shape[1]):
        Z = np.stack([np.log(p[:, k]), -np.log1p(-p[:, k]),
                      np.ones(len(p))], 1)
        t = (y == k).astype(np.float64)

        def loss(w):
            z = Z @ w
            return (np.sum(np.logaddexp(0.0, z) - t * z)
                    + 0.5 * ridge * np.sum((w - w0) ** 2))

        w = w0.copy()
        for _ in range(iters):
            q = 0.5 * (1.0 + np.tanh(0.5 * (Z @ w)))
            grad = Z.T @ (q - t) + ridge * (w - w0)
            hess = (Z * (q * (1.0 - q))[:, None]).T @ Z + ridge * np.eye(3)
            step, now = np.linalg.solve(hess, grad), loss(w)
            while loss(w - step) > now and np.abs(step).max() > 1e-9:
                step = 0.5 * step
            w = w - step
        out.append(w)
    a, b, c = np.asarray(out).T
    raw = lambda v: np.log(np.expm1(np.maximum(v, 1e-3)))  # noqa: E731
    return (raw(a).astype(np.float32), raw(b).astype(np.float32),
            c.astype(np.float32))


def fit(spec: dict) -> tuple[reference.Classifier, float]:
    """The classifier of `spec`, and its accuracy on the held-out
    windows."""
    x, y, held = dataset(spec["dataset"])
    X = features(x)
    edges = bin_edges(X[~held], int(spec["n_bins"]))
    xb = binned(X, edges)
    feat, thresh, leaf, base = fit_gbdt(xb[~held], y[~held], spec)
    p = _softmax(predict_logits(xb[held], feat, thresh, leaf, base))
    a_raw, b_raw, c = fit_beta(p, y[held])
    acc = float(np.mean(p.argmax(-1) == y[held]))
    return reference.Classifier(edges, feat, thresh, leaf, base, a_raw,
                                b_raw, c), acc


def program_classify(clf: reference.Classifier):
    """The program's classify closure over the arrays of `clf`."""
    import jax.numpy as jnp
    from repro.core import calibration, gbdt, pipeline
    f32 = jnp.float32
    params = gbdt.GBDTParams(
        feat=jnp.asarray(clf.feat, jnp.int32),
        thresh=jnp.asarray(clf.thresh, jnp.int32),
        leaf=jnp.asarray(clf.leaf, f32),
        bin_edges=jnp.asarray(clf.bin_edges, f32),
        base=jnp.asarray(clf.base, f32))
    cal = calibration.BetaCalibration(jnp.asarray(clf.cal_a_raw, f32),
                                      jnp.asarray(clf.cal_b_raw, f32),
                                      jnp.asarray(clf.cal_c, f32))
    return pipeline.TrainedAAPA(
        params=params, cal=cal, train_acc=0.0, val_acc=0.0, test_acc=0.0,
        label_dist=np.zeros(clf.base.shape), n_windows=0,
        fit_seconds=0.0).make_classify()


def get(cfg: dict):
    """(program classify closure, reference.Classifier, fitted_now)."""
    spec = cfg["classifier"]
    path = _path(spec)
    fitted = not path.exists()
    if fitted:
        clf, acc = fit(spec)
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(f".tmp-{os.getpid()}-{path.name}")
        np.savez(tmp, held_out_accuracy=acc, **clf._asdict())
        tmp.replace(path)
    with np.load(path) as z:
        clf = reference.Classifier(*(z[k] for k in
                                     reference.Classifier._fields))
    return program_classify(clf), clf, fitted
