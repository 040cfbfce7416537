"""End-to-end AAPA pipeline: traces -> windows -> features -> weak labels
-> GBDT -> beta calibration -> deployable classifier closure.

This is the glue the paper's Figure 1 describes: the feature-extraction
pipeline feeds the weak-supervision labeler, the classifier trains on the
weak labels (days 1-9), calibrates on validation days (10-11), and the
resulting `classify` closure plugs into ``aapa_controller``.

Dataset construction lives in ``repro.aapaset`` (chunked jitted build,
content-addressed shard cache, named registry); this module trains
classifiers from those datasets — either directly from traces
(``train_aapa``) or from a named, hash-pinned artifact
(``train_from_loader`` / ``train_classifier``).
"""
from __future__ import annotations

import dataclasses
import pathlib
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import calibration, gbdt
from repro.data import windows as W
from repro.data.azure_synth import TraceSet


@dataclasses.dataclass
class TrainedAAPA:
    params: gbdt.GBDTParams
    cal: calibration.BetaCalibration
    train_acc: float
    val_acc: float
    test_acc: float
    label_dist: np.ndarray     # weak-label distribution over 4 classes
    n_windows: int
    fit_seconds: float
    dataset_id: str = ""       # "name-hash12" when trained from an artifact

    def make_classify(self) -> Callable:
        """Returns classify(features [38]) -> (class int32, confidence).
        It evaluates one row (`gbdt.row_logits`): controllers call it
        per lane inside the vmapped minute scan."""
        params, cal = self.params, self.cal

        def classify(feats: jax.Array):
            logits = gbdt.row_logits(params, feats)
            probs = jax.nn.softmax(logits, axis=-1)
            calp = calibration.calibrate(cal, probs)
            return (jnp.argmax(calp).astype(jnp.int32),
                    jnp.max(calp).astype(jnp.float32))

        return classify

    def save(self, path: str | pathlib.Path) -> None:
        """Single-file npz round-trip (classifier + calibration + card)."""
        p = self.params
        np.savez(
            path,
            feat=np.asarray(p.feat), thresh=np.asarray(p.thresh),
            leaf=np.asarray(p.leaf), bin_edges=np.asarray(p.bin_edges),
            base=np.asarray(p.base),
            cal_a_raw=np.asarray(self.cal.a_raw),
            cal_b_raw=np.asarray(self.cal.b_raw),
            cal_c=np.asarray(self.cal.c),
            label_dist=np.asarray(self.label_dist),
            scalars=np.array([self.train_acc, self.val_acc, self.test_acc,
                              float(self.n_windows), self.fit_seconds],
                             np.float64),
            dataset_id=np.array(self.dataset_id))

    @classmethod
    def load(cls, path: str | pathlib.Path) -> "TrainedAAPA":
        with np.load(path) as z:
            return cls._from_npz(z)

    @classmethod
    def _from_npz(cls, z) -> "TrainedAAPA":
        params = gbdt.GBDTParams(
            feat=jnp.asarray(z["feat"]), thresh=jnp.asarray(z["thresh"]),
            leaf=jnp.asarray(z["leaf"]),
            bin_edges=jnp.asarray(z["bin_edges"]),
            base=jnp.asarray(z["base"]))
        cal = calibration.BetaCalibration(
            a_raw=jnp.asarray(z["cal_a_raw"]),
            b_raw=jnp.asarray(z["cal_b_raw"]),
            c=jnp.asarray(z["cal_c"]))
        s = z["scalars"]
        return cls(params=params, cal=cal, train_acc=float(s[0]),
                   val_acc=float(s[1]), test_acc=float(s[2]),
                   label_dist=z["label_dist"], n_windows=int(s[3]),
                   fit_seconds=float(s[4]),
                   dataset_id=str(z["dataset_id"]))


def featurize_and_label(ds: W.WindowDataset, batch: int = 8192):
    """Extract 38 features + weak labels for every window.

    Thin wrapper over the chunked jitted AAPAset builder (one compile
    per chunk shape) — kept for callers that work from a raw
    ``WindowDataset`` rather than a named artifact. Always uses the ref
    feature math (the legacy contract: identical bytes on every
    backend); artifact builds choose their feature path explicitly via
    ``DatasetConfig.feature_path``.
    """
    from repro.aapaset.build import featurize_windows
    feats, labels, confs, _ = featurize_windows(ds.windows, chunk=batch,
                                                use_kernel=False)
    return feats, labels, confs


def _fit_classifier(X, y, split_masks, cfg: gbdt.GBDTConfig,
                    *, verbose: bool,
                    dataset_id: str = "") -> TrainedAAPA:
    """Shared trainer: fit on train mask, calibrate on val, report accs.

    `X`/`y` must already be restricted to labeled windows (y >= 0)."""
    t0 = time.time()
    params = gbdt.fit(X[split_masks["train"]], y[split_masks["train"]],
                      cfg, verbose=verbose)
    fit_s = time.time() - t0

    def acc(m):
        if m.sum() == 0:
            return float("nan")
        pred = np.asarray(gbdt.predict(params, jnp.asarray(X[m])))
        return float((pred == y[m]).mean())

    probs_val = np.asarray(gbdt.predict_proba(
        params, jnp.asarray(X[split_masks["val"]])))
    cal = calibration.fit(probs_val, y[split_masks["val"]])

    dist = np.bincount(y, minlength=4) / max(len(y), 1)
    return TrainedAAPA(params=params, cal=cal,
                       train_acc=acc(split_masks["train"]),
                       val_acc=acc(split_masks["val"]),
                       test_acc=acc(split_masks["test"]),
                       label_dist=dist, n_windows=len(y),
                       fit_seconds=fit_s, dataset_id=dataset_id)


def train_aapa(traces: TraceSet, cfg: gbdt.GBDTConfig = gbdt.GBDTConfig(),
               *, verbose: bool = False) -> TrainedAAPA:
    """Train directly from a TraceSet (no artifact cache)."""
    ds = W.make_windows(traces)
    split = W.default_day_split(ds, traces.n_days)
    X, y, conf = featurize_and_label(ds)

    labeled = y >= 0  # drop windows where every LF abstained
    masks = {k: m[labeled] for k, m in split.items()}
    return _fit_classifier(X[labeled], y[labeled], masks, cfg,
                           verbose=verbose)


def train_from_loader(loader, cfg: gbdt.GBDTConfig = gbdt.GBDTConfig(),
                      *, verbose: bool = False) -> TrainedAAPA:
    """Train from a built AAPAset artifact via its loader: the classifier
    the `aapa`/`hybrid` policies consume names the exact dataset it was
    trained on (``trained.dataset_id``)."""
    idx = loader.split_indices(None)                 # all labeled rows
    X = loader.data.features[idx]
    y = loader.data.labels[idx]
    split = loader.data.split[idx]
    from repro.aapaset.build import SPLIT_NAMES
    masks = {name: split == code
             for code, name in enumerate(SPLIT_NAMES)}
    return _fit_classifier(X, y, masks, cfg, verbose=verbose,
                           dataset_id=loader.dataset_id)


# Bump whenever gbdt.fit / calibration.fit / _fit_classifier change in a
# way that alters trained outputs: it keys the classifier npz cache the
# same way aapaset's SCHEMA_VERSION keys dataset artifacts.
CLASSIFIER_VERSION = 1


def train_classifier(dataset: str = "aapaset_ci",
                     cfg: gbdt.GBDTConfig = gbdt.GBDTConfig(),
                     *, root=None, cache: bool = True,
                     verbose: bool = False,
                     loader_factory=None) -> TrainedAAPA:
    """Build-or-load a named dataset, then train-or-load the classifier.

    The trained model is cached as npz inside the dataset artifact dir,
    keyed by (CLASSIFIER_VERSION, GBDT config), so examples and
    benchmarks reuse one fit. On a classifier-cache hit no dataset shard
    is touched; on a miss the dataset comes from `loader_factory()` when
    given (lets callers share one loaded artifact) else is loaded fresh.
    """
    import os

    from repro.aapaset import manifest as MF
    from repro.aapaset import registry
    from repro.aapaset.loader import AAPAsetLoader

    root = MF.DEFAULT_ROOT if root is None else root
    key = MF.hash_json({"v": CLASSIFIER_VERSION,
                        "gbdt": dataclasses.asdict(cfg)}, n=8)
    path = MF.artifact_dir(registry.get(dataset), root) \
        / f"classifier-{key}.npz"
    if cache and path.exists():       # skip loading the dataset shards
        return TrainedAAPA.load(path)
    loader = loader_factory() if loader_factory is not None \
        else AAPAsetLoader.from_name(dataset, root)
    trained = train_from_loader(loader, cfg, verbose=verbose)
    # a dataset too small for a test split (n_days <= 2) yields
    # test_acc = NaN by design — return it, but never cache it
    if cache and np.isfinite(trained.test_acc):
        path.parent.mkdir(parents=True, exist_ok=True)
        MF.sweep_stale_tmp(path.parent, f".tmp-*-{path.name}")
        tmp = path.with_name(f".tmp-{os.getpid()}-{path.name}")
        trained.save(tmp)
        tmp.replace(path)             # atomic: never a half-written cache
    return trained
