"""The benchmark's traffic copies keep the distributions of the program's
generators (summary statistics at fixed seeds, not bits), and import
nothing of the program."""
import ast
import json
import pathlib

import numpy as np
import pytest

from perfbench import generator, harness

PERFBENCH = pathlib.Path(generator.__file__).resolve().parent
ARCHETYPES = harness.traffic_family("archetype_pure").ARCHETYPES


def _imports(path: pathlib.Path) -> set[str]:
    mods = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            mods.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            mods.add(node.module or "")
    return mods


@pytest.mark.parametrize("name", ["generator.py", "reference.py",
                                  "traffic/burst_storm.py",
                                  "traffic/archetype_pure.py"])
def test_yardstick_imports_nothing_of_the_program(name):
    mods = _imports(PERFBENCH / name)
    assert not any(m == "repro" or m.startswith("repro.") for m in mods), mods


def test_every_traffic_file_names_a_generator_family():
    for path in sorted((PERFBENCH / "traffic").glob("*.json")):
        mix = json.loads(path.read_text())
        assert mix["generator"] in generator.families(), path.name


def test_burst_storm_keeps_floor_storms_and_heights():
    from repro.scaling import scenarios
    mix = {"generator": "burst_storm", "n_workloads": 4000,
           "w_chunk": 1000, "minutes": 60, "floor": 30.0,
           "height": 6000.0, "n_storms": 3}
    ours = generator.generate(mix, 7)
    assert ours.shape == (4, 1000, 60) and ours.dtype == np.float32
    for c in range(4):
        theirs = scenarios.get("burst_storm", n_workloads=1000, minutes=60,
                               seed=generator.derived_seed(7, c)).rates
        a, b = ours[c], np.asarray(theirs)
        # the floor: Poisson(30) minutes outside the storms
        assert abs(np.median(a) - np.median(b)) <= 2.0
        assert 25.0 < np.median(a) < 35.0
        # storm minutes: every workload bursts in the same minutes
        storm = a.mean(0) > 300.0
        assert storm.sum() == (b.mean(0) > 300.0).sum()
        assert 3 <= storm.sum() <= 27
        np.testing.assert_allclose(a.mean(), b.mean(), rtol=0.05)
        np.testing.assert_allclose(a.max(), b.max(), rtol=0.25)


def test_burst_storm_chunks_differ_and_repeat():
    mix = json.loads((PERFBENCH / "traffic/burst_storm_1e5.json")
                     .read_text())
    mix = {**mix, "n_workloads": 3000}
    a = generator.generate(mix, 2 ** 31 + 11)
    np.testing.assert_array_equal(a, generator.generate(mix, 2 ** 31 + 11))
    assert not np.array_equal(a[0], a[1])
    assert not np.array_equal(a, generator.generate(mix, 12))


@pytest.mark.parametrize("kind", ARCHETYPES)
def test_archetype_marginals_match_the_program(kind):
    from repro.core.archetypes import Archetype
    from repro.data.azure_synth import generate_traces
    n, minutes = 160, 1440
    ours = harness.traffic_family("archetype_pure").pure_counts(
        kind, n, minutes, 5)
    theirs = generate_traces(n_functions=n, n_days=1, seed=5,
                             mix={Archetype[kind]: 1.0}).counts
    assert ours.shape == theirs.shape == (n, minutes)

    def stats(x):
        mean = x.mean(1)
        return {"log_mean": np.log10(mean + 1).mean(),
                "zero_share": (x == 0).mean(),
                "peak_ratio": np.median(x.max(1) / (mean + 1)),
                "cv": np.median(x.std(1) / (mean + 1))}

    s, t = stats(ours), stats(theirs)
    assert abs(s["log_mean"] - t["log_mean"]) < 0.35, (s, t)
    assert abs(s["zero_share"] - t["zero_share"]) < 0.08, (s, t)
    np.testing.assert_allclose(s["peak_ratio"], t["peak_ratio"], rtol=0.35)
    np.testing.assert_allclose(s["cv"], t["cv"], rtol=0.35)


def test_archetype_pure_shape_and_seed():
    mix = json.loads((PERFBENCH / "traffic/archetype_pure_fig2.json")
                     .read_text())
    mix = {**mix, "n_workloads": 3, "minutes": 120}
    a = generator.generate(mix, 2 ** 33)
    assert a.shape == (4, 5, 3, 120) and a.dtype == np.float32
    np.testing.assert_array_equal(a, generator.generate(mix, 2 ** 33))
    assert not np.array_equal(a[:, 0], a[:, 1])
