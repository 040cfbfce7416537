"""Traffic family ``azure_functions``: an Azure Functions production
fleet after Shahrad et al., "Serverless in the Wild" (USENIX ATC 2020),
section 3. Each workload is one function:

* its base rate, log10(invocations per minute) ~ N(`log10_rate_mean`,
  `log10_rate_sd`): with the mix's values, 45 % of the functions are
  invoked once an hour or less and 81 % once a minute or less, the
  paper's two published points of its popularity skew;
* its temporal shape, one of ``archetype_pure``'s four shape functions
  at that base, in equal shares;
* its counts, Poisson (means capped at 1e7 a minute, as in
  ``archetype_pure``);
* its average execution time, ln(seconds) ~ N(`ln_exec_mean`,
  `ln_exec_sd`) (the paper's log-normal fit), clipped to
  [`exec_min_sec`, `exec_max_sec`], drawn independently of the rate.

Parameters: those above and ``n_workloads``, ``w_chunk``, ``minutes``.
Chunk c draws from the seed derived from (seed, c).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from perfbench.generator import derived_seed
from perfbench.harness import traffic_family


class FunctionFleet(NamedTuple):
    """What the family gives a cell: the invocation tensor and each
    function's average execution time."""
    rates: np.ndarray       # [chunks, w_chunk, minutes] float32
    exec_sec: np.ndarray    # [chunks, w_chunk] float32


def base_rates(rng, n: int, mix: dict) -> np.ndarray:
    """n functions' base invocations per minute (the popularity law)."""
    return 10.0 ** rng.normal(float(mix["log10_rate_mean"]),
                              float(mix["log10_rate_sd"]), size=n)


def exec_seconds(rng, n: int, mix: dict) -> np.ndarray:
    """n functions' average execution times in seconds."""
    return np.clip(np.exp(rng.normal(float(mix["ln_exec_mean"]),
                                     float(mix["ln_exec_sd"]), size=n)),
                   float(mix["exec_min_sec"]), float(mix["exec_max_sec"]))


def function_chunk(rng, n: int, minutes: int, mix: dict):
    """(counts [n, minutes] float32, exec_sec [n] float32) of n functions."""
    shapes = traffic_family("archetype_pure")
    base = base_rates(rng, n, mix)
    kinds = rng.integers(0, len(shapes.ARCHETYPES), size=n)
    rates = np.stack([
        shapes._GENERATORS[shapes.ARCHETYPES[k]](rng, minutes, b)
        for k, b in zip(kinds, base)])
    counts = rng.poisson(np.minimum(rates, 1e7)).astype(np.float32)
    return counts, exec_seconds(rng, n, mix).astype(np.float32)


def generate(mix: dict, seed: int) -> FunctionFleet:
    """The fleet: chunk c of `w_chunk` functions from the seed derived
    from (seed, c)."""
    n, wc = int(mix["n_workloads"]), int(mix["w_chunk"])
    if n % wc:
        raise ValueError(f"w_chunk {wc} must divide n_workloads {n}")
    chunks = [function_chunk(np.random.default_rng(derived_seed(seed, c)),
                             wc, int(mix["minutes"]), mix)
              for c in range(n // wc)]
    return FunctionFleet(np.stack([r for r, _ in chunks]),
                         np.stack([e for _, e in chunks]))
