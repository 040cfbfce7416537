"""The benchmark's one traffic generator.

A traffic mix is a data file, ``perfbench/traffic/<mix>.json``, whose
``"generator"`` key names a family and whose other keys are that
family's parameters. A family is a file of its own,
``perfbench/traffic/<family>.py`` with ``generate(mix, seed)``, found by
name as entries and per-layer readers are; a later mix of a new
distribution adds its family as a new file. ``generate(mix, seed)``
returns the per-minute invocation tensor the cell's entry feeds to the
program.

The families are copies of the repository's own generators, kept here
so that a change to the program cannot move the benchmark's traffic.
They keep the source distributions, not the source's bits: the
reference runs on the same generated inputs as the program. Nothing here
imports the program.
"""
from __future__ import annotations

import numpy as np

from perfbench import harness


def load_mix(name: str) -> dict:
    """The parameters of traffic mix `name`, from its data file."""
    return harness.load_json("traffic", name)


def derived_seed(seed: int, *path: int) -> int:
    """A 32-bit seed derived from (seed, *path); any whole seed works."""
    ss = np.random.SeedSequence([seed % (1 << 64), *path])
    return int(ss.generate_state(1)[0])


def families() -> list[str]:
    """The names of the traffic families there are."""
    return sorted(p.stem for p in (harness.ROOT / "traffic").glob("*.py"))


def generate(mix: dict, seed: int) -> np.ndarray:
    """The invocation tensor of traffic mix `mix` (float32) for `seed`."""
    name = mix.get("generator")
    if name not in families():
        raise KeyError(f"unknown traffic generator {name!r}; available: "
                       f"{families()}")
    return harness.traffic_family(name).generate(mix, seed)
