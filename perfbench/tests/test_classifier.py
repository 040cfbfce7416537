"""The configuration's classifier is made by the benchmark from its own
traffic family and fit, at a small size on the CPU: the program and the
reference classify alike from the same arrays, the fit beats chance, and
nothing of the program takes part in the fit."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp

from perfbench import classifier, harness, reference

SMALL = {"dataset": {"family": "archetype_pure", "seed": 7,
                     "traces_per_kind": 24, "minutes": 240,
                     "windows_per_trace": 4, "window_min": 60,
                     "calibration_share": 0.25},
         "n_classes": 4, "n_rounds": 8, "depth": 3, "learning_rate": 0.25,
         "reg_lambda": 1.0, "n_bins": 64, "min_child_weight": 0.001,
         "calibration": "beta"}


def test_the_fit_has_the_configured_shape_and_beats_chance():
    clf, acc = classifier.fit(SMALL)
    assert clf.bin_edges.shape == (38, 63)
    assert clf.feat.shape == clf.thresh.shape == (8, 4, 7)
    assert clf.leaf.shape == (8, 4, 8)
    assert clf.feat.max() < 38 and clf.thresh.max() <= 63
    assert np.all(np.isfinite(clf.leaf)) and np.all(np.isfinite(clf.cal_c))
    assert acc > 0.5
    again, _ = classifier.fit(SMALL)
    for a, b in zip(clf, again):
        np.testing.assert_array_equal(a, b)


def test_the_program_and_the_reference_classify_alike():
    clf, _ = classifier.fit(SMALL)
    x, y, held = classifier.dataset(SMALL["dataset"])
    X = jnp.asarray(classifier.features(x))
    k_ref, q_ref = reference.classify(clf, X)
    k_prog, q_prog = jax.vmap(classifier.program_classify(clf))(X)
    assert float(jnp.mean(k_prog == k_ref)) > 0.99
    np.testing.assert_allclose(q_prog, q_ref, atol=1e-5)
    # the reference's tree walk agrees with the fit's own
    p = classifier.predict_logits(classifier.binned(np.asarray(X),
                                                    clf.bin_edges),
                                  clf.feat, clf.thresh, clf.leaf, clf.base)
    assert np.mean(p.argmax(-1) == np.asarray(k_ref)) > 0.9


def test_get_fits_once_and_then_loads(tmp_path, monkeypatch):
    monkeypatch.setattr(classifier, "CACHE", tmp_path)
    cfg = {"name": "c", "classifier": SMALL}
    _, first, fitted = classifier.get(cfg)
    assert fitted and len(list(tmp_path.glob("classifier-*.npz"))) == 1
    _, second, fitted = classifier.get(cfg)
    assert not fitted
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a, b)


def test_the_fit_uses_nothing_of_the_program():
    code = textwrap.dedent(f"""
        import sys
        sys.modules["repro"] = None      # any import of the program fails
        from perfbench import classifier
        clf, acc = classifier.fit({json.dumps(SMALL)})
        print(acc)
        """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(harness.REPO)}
    r = subprocess.run([sys.executable, "-c", code], cwd=harness.REPO,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert float(r.stdout.strip().splitlines()[-1]) > 0.5
