"""Traffic family ``archetype_pure``: copies of the four archetype
generators of ``repro.data.azure_synth`` and their base-rate law (their
distributions, not their bits). Parameters: ``kinds``,
``seeds_per_kind``, ``n_workloads``, ``minutes``."""
from __future__ import annotations

import numpy as np

from perfbench.generator import derived_seed

MINUTES_PER_DAY = 1440
#: in the order of the classifier's class ids
ARCHETYPES = ("PERIODIC", "SPIKE", "STATIONARY_NOISY", "RAMP")


def _periodic(rng, T, base):
    period = rng.choice([5, 10, 15, 20, 30, 60, 240],
                        p=[0.22, 0.24, 0.2, 0.14, 0.1, 0.05, 0.05])
    amp = rng.uniform(0.4, 0.95)
    phase = rng.uniform(0, 2 * np.pi)
    wave = np.sin(2 * np.pi * np.arange(T) / period + phase)
    sharp = rng.uniform(1.0, 3.0)
    wave = np.sign(wave) * np.abs(wave) ** (1.0 / sharp)
    return np.maximum(base * (1.0 + amp * wave), 0.0)


def _spike(rng, T, base):
    rate = np.full(T, base * rng.uniform(0.02, 0.15))
    n_spikes = rng.poisson(6.0 * (T / MINUTES_PER_DAY)) + 1
    for s in rng.integers(0, T, size=n_spikes):
        height = base * rng.uniform(20.0, 300.0)
        dur = int(rng.integers(2, 12))
        decay = np.exp(-np.arange(dur) / max(dur / 3.0, 1.0))
        end = min(s + dur, T)
        rate[s:end] += height * decay[:end - s]
    return rate


def _ramp(rng, T, base):
    rate = np.empty(T)
    t0, level = 0, base * rng.uniform(0.3, 0.8)
    while t0 < T:
        seg = int(rng.integers(90, 360))
        direction = rng.choice([1.0, 1.0, 1.0, -0.7])
        target = np.clip(level * rng.uniform(3.0, 8.0) ** direction,
                         0.1 * base, 100.0 * base)
        end = min(t0 + seg, T)
        rate[t0:end] = np.linspace(level, target, end - t0)
        level, t0 = target, end
    return rate


def _stationary(rng, T, base):
    cv = rng.uniform(0.05, 0.25)
    ar = rng.uniform(0.3, 0.8)
    eps = rng.normal(0, 1, T)
    noise = np.zeros(T)
    for t in range(1, T):
        noise[t] = ar * noise[t - 1] + eps[t]
    noise /= max(noise.std(), 1e-9)
    return np.maximum(base * (1.0 + cv * noise), 0.0)


_GENERATORS = {"PERIODIC": _periodic, "SPIKE": _spike, "RAMP": _ramp,
               "STATIONARY_NOISY": _stationary}


def pure_counts(kind: str, n: int, minutes: int, seed: int) -> np.ndarray:
    """n traces of one archetype: log-uniform base rates over ~3.7
    decades, the archetype's rate curve, Poisson counts."""
    rng = np.random.default_rng(seed)
    days = max(-(-minutes // MINUTES_PER_DAY), 1)
    T = days * MINUTES_PER_DAY
    base = 10.0 ** rng.uniform(-0.5, 3.2, size=n)
    gen = _GENERATORS[kind]
    rates = np.stack([gen(rng, T, base[i]) for i in range(n)])
    counts = rng.poisson(np.minimum(rates, 1e7)).astype(np.float32)
    return counts[:, :minutes]


def generate(mix: dict, seed: int) -> np.ndarray:
    """[kinds, seeds_per_kind, n_workloads, minutes]: for every archetype
    in `kinds`, `seeds_per_kind` trials of archetype-pure traces, trial z
    drawn from the seed derived from (seed, z)."""
    trials = [derived_seed(seed, z)
              for z in range(int(mix["seeds_per_kind"]))]
    return np.stack([
        np.stack([pure_counts(kind, int(mix["n_workloads"]),
                              int(mix["minutes"]), s) for s in trials])
        for kind in mix["kinds"]])
