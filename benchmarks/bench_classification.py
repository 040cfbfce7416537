"""Paper §V.A + Table IV: classifier accuracy and confusion matrix on the
held-out test days, plus the weak-label distribution (paper: PERIODIC
70.2%, SPIKE 17.6%, STATIONARY 12.0%, RAMP 0.2%; accuracy 99.8%)."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from benchmarks import common
from repro.core import gbdt
from repro.core.archetypes import ARCHETYPE_NAMES


def main():
    trained = common.get_trained()
    loader = common.get_loader()
    X, y, _ = loader.arrays("test")

    us = common.timeit(
        lambda: np.asarray(gbdt.predict(trained.params,
                                        jnp.asarray(X[:4096]))),
        warmup=1, iters=3)

    pred = np.asarray(gbdt.predict(trained.params, jnp.asarray(X)))
    acc = float((pred == y).mean())
    conf = np.zeros((4, 4), np.int64)
    for t, p in zip(y, pred):
        conf[t, p] += 1

    dist = np.asarray([loader.manifest["card"]["class_balance"][n]
                       for n in ARCHETYPE_NAMES])
    payload = {
        "dataset": loader.dataset_id,
        "test_accuracy": acc,
        "paper_accuracy": 0.998,
        "confusion_matrix": conf.tolist(),
        "confusion_labels": ARCHETYPE_NAMES,
        "label_distribution": {n: float(d) for n, d in
                               zip(ARCHETYPE_NAMES, dist)},
        "paper_label_distribution": {"PERIODIC": 0.702, "SPIKE": 0.176,
                                     "STATIONARY_NOISY": 0.120,
                                     "RAMP": 0.002},
        "n_test_windows": int(len(y)),
        "train_acc": trained.train_acc, "val_acc": trained.val_acc,
    }
    common.emit("classification_tableIV", us,
                f"test_acc={acc:.4f}_paper=0.998", payload)
    print("# confusion matrix (rows=true PERI/SPIKE/STAT/RAMP):")
    for name, row in zip(ARCHETYPE_NAMES, conf):
        print(f"#   {name:17s} {row}")

    # ---- host inference: flattened node tables vs per-round scan -------
    # Measured on the FULL test split: the table path's cache-blocked
    # lockstep traversal wins at paper-scale batches (the pipeline
    # scores whole splits); at toy batch sizes the two are at parity.
    import jax
    Xq = jnp.asarray(X)
    tables = jax.jit(gbdt.predict_logits)
    scan = jax.jit(gbdt.predict_logits_scan)
    tt = common.timeit(
        lambda: jax.block_until_ready(tables(trained.params, Xq)),
        warmup=1, iters=5)
    ts = common.timeit(
        lambda: jax.block_until_ready(scan(trained.params, Xq)),
        warmup=1, iters=5)
    gp = {"rows": int(Xq.shape[0]),
          "rounds": int(trained.params.feat.shape[0]),
          "depth": int(trained.params.depth),
          "tables_us": tt, "scan_us": ts, "tables_speedup": ts / tt}
    common.emit("classification_node_tables", tt,
                f"tables_vs_scan={ts / tt:.2f}x", gp)


if __name__ == "__main__":
    main()
