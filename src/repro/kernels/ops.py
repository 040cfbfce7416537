"""Public jit'd wrappers for the Pallas kernels.

`interpret=None` (the default) compiles the kernel with Mosaic on a TPU
backend and runs the Pallas interpreter everywhere else — the CPU
correctness vehicle. ``tests/test_tpu_compile.py`` compiles every kernel
a TPU run can take for a described TPU v5e; `gbdt_logits` does not lower
there (its node-table gathers) and runs only under the interpreter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.features import freq_features
from repro.kernels.episode_block import episode_minutes
from repro.kernels.gbdt_tables import gbdt_logits_kernel
from repro.kernels.holt_winters import holt_winters_kernel
from repro.kernels.window_features import window_features_kernel


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def window_features(windows: jax.Array, *, tile_n: int = 256,
                    interpret: bool | None = None) -> jax.Array:
    """[N, W] -> 28 stat/time features [N, 28] via the fused kernel."""
    if interpret is None:
        interpret = _default_interpret()
    return window_features_kernel(windows, tile_n=tile_n,
                                  interpret=interpret)


def extract_features_fused(windows: jax.Array, *, tile_n: int = 256,
                           interpret: bool | None = None) -> jax.Array:
    """All 38 AAPA features: fused Pallas kernel (28) + XLA rFFT (10)."""
    st = window_features(windows, tile_n=tile_n, interpret=interpret)
    fq = freq_features(windows)
    return jnp.concatenate([st, fq], axis=-1)


def holt_winters(y: jax.Array, *, period: int = 60, alpha: float = 0.1,
                 beta: float = 0.01, gamma: float = 0.3, tile_b: int = 128,
                 interpret: bool | None = None) -> jax.Array:
    """[B, T] -> one-step-ahead Holt-Winters forecasts [B, T]."""
    if interpret is None:
        interpret = _default_interpret()
    return holt_winters_kernel(y, period=period, alpha=alpha, beta=beta,
                               gamma=gamma, tile_b=tile_b,
                               interpret=interpret)


def episode_block(rates, controller, cfg, *, tile_b: int = 8,
                  interpret: bool | None = None):
    """Whole episodes fused on-chip: rates [B, M] -> MinuteOut of [B, M]
    with plant ticks AND `controller.decide` inside one Pallas kernel
    (``repro.kernels.episode_block``). Oracle: the CPU blocked scan
    ``repro.sim.cluster.simulate`` per lane."""
    if interpret is None:
        interpret = _default_interpret()
    return episode_minutes(controller, cfg, rates, tile_b=tile_b,
                           interpret=interpret)


def gbdt_logits(params, X, *, tile_n: int = 128,
                interpret: bool | None = None):
    """GBDT logits [N, K] from raw features X [N, F] via the node-table
    kernel (``repro.kernels.gbdt_tables``); `params` is a trained
    ``repro.core.gbdt.GBDTParams``. Oracle: ``gbdt.predict_logits``
    (the host path over the same flattened tables)."""
    if interpret is None:
        interpret = _default_interpret()
    t = params.tables
    return gbdt_logits_kernel(X, params.bin_edges, t.feat, t.thresh,
                              t.leaf, params.base, tile_n=tile_n,
                              interpret=interpret)
