"""Small stand-ins for the CPU tests: a random classifier in place of the
configuration's fitted one, and tiny traffic in place of the cells'."""
from __future__ import annotations

import numpy as np

from perfbench import classifier, reference

#: per cell, traffic small enough for a CPU test
TINY = {"fleet_burst_1e5": {"n_workloads": 24, "w_chunk": 8, "minutes": 30},
        "fleet_stream_1e5": {"n_workloads": 24, "w_chunk": 8,
                             "minutes": 30},
        "fleet_burst_4e5_4chip": {"n_workloads": 32, "w_chunk": 16,
                                  "minutes": 30},
        "matrix_fig2": {"kinds": ["SPIKE", "RAMP"], "seeds_per_kind": 2,
                        "n_workloads": 4, "minutes": 40}}


def random_classifier(seed: int = 0, rounds: int = 6,
                      depth: int = 3) -> reference.Classifier:
    """Random trees over 38 features and a random beta calibration (its
    predictions are arbitrary but deterministic)."""
    rng = np.random.default_rng(seed)
    K, inner = 4, 2 ** depth - 1
    edges = np.sort(rng.lognormal(0.0, 2.0, (38, 63)), -1) - 1.0
    return reference.Classifier(
        edges.astype(np.float32),
        rng.integers(0, 38, (rounds, K, inner)).astype(np.int32),
        rng.integers(0, 63, (rounds, K, inner)).astype(np.int32),
        rng.normal(0, 1, (rounds, K, inner + 1)).astype(np.float32),
        np.zeros(K, np.float32),
        rng.normal(0.5, 0.1, K).astype(np.float32),
        rng.normal(0.5, 0.1, K).astype(np.float32),
        rng.normal(0.0, 0.1, K).astype(np.float32))


def fake_get(cfg):
    clf = random_classifier()
    return classifier.program_classify(clf), clf, False


def use_fake_classifier(monkeypatch):
    monkeypatch.setattr(classifier, "get", fake_get)
