"""Traffic family ``burst_storm``: a copy of
``repro.scaling.scenarios.burst_storm`` (its distributions, not its
bits). Parameters: ``n_workloads``, ``w_chunk``, ``minutes``, ``floor``,
``height``, ``n_storms``."""
from __future__ import annotations

import numpy as np

from perfbench.generator import derived_seed


def storm_chunk(rng, n: int, minutes: int, floor: float, height: float,
                n_storms: int) -> np.ndarray:
    """Synchronized bursts over `n` workloads: every workload spikes in
    the same windows (correlated incident traffic); Poisson counts."""
    rates = np.full((n, minutes), floor, np.float64)
    lo = max(minutes // 6, 1)
    hi = max(minutes - max(minutes // 6, 15), lo + 1)
    for start in rng.integers(lo, hi, size=n_storms):
        dur = int(rng.integers(3, 10))
        decay = np.exp(-np.arange(dur) / max(dur / 3.0, 1.0))
        amp = height * rng.uniform(0.5, 1.5, size=(n, 1))
        end = min(start + dur, minutes)
        rates[:, start:end] += amp * decay[None, :end - start]
    return rng.poisson(rates).astype(np.float32)


def generate(mix: dict, seed: int) -> np.ndarray:
    """[chunks, w_chunk, minutes]: chunk c draws its storm timing, heights
    and counts from the seed derived from (seed, c)."""
    n, wc = int(mix["n_workloads"]), int(mix["w_chunk"])
    if n % wc:
        raise ValueError(f"w_chunk {wc} must divide n_workloads {n}")
    return np.stack([
        storm_chunk(np.random.default_rng(derived_seed(seed, c)), wc,
                    int(mix["minutes"]), float(mix["floor"]),
                    float(mix["height"]), int(mix["n_storms"]))
        for c in range(n // wc)])
