"""Public jit'd wrappers for the Pallas kernels.

`interpret=None` (the default) compiles the kernel with Mosaic on a TPU
backend and runs the Pallas interpreter everywhere else — the CPU
correctness vehicle. ``tests/test_tpu_compile.py`` compiles every kernel
a TPU run can take for a described TPU v5e.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.features import freq_features
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
