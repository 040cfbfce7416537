"""The Azure Functions fleet cell on the CPU: its traffic family keeps
the published popularity and execution-time laws and repeats for a seed,
the lane-plant reference is ``reference.py``'s equations with each
lane's own plant, and the entry agrees with it where the bfloat16
control does not."""
import ast

import numpy as np
import pytest

from perfbench import (calibrate, compare, generator, harness,
                       lane_plant_reference, reference)
from perfbench.tests import fakes

CELL = "azure_fleet_1e5"
MIX = generator.load_mix("azure_functions_1e5")
FAMILY = harness.traffic_family("azure_functions")


def _imports(path) -> set[str]:
    mods = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            mods.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            mods.add(node.module or "")
    return mods


@pytest.mark.parametrize("name", ["traffic/azure_functions.py",
                                  "lane_plant_reference.py"])
def test_family_and_reference_import_nothing_of_the_program(name):
    mods = _imports(harness.ROOT / name)
    assert not any(m == "repro" or m.startswith("repro.") for m in mods), mods


def test_popularity_and_execution_time_keep_the_published_laws():
    """At 10^5 draws: 45 % of the functions at most once an hour and 81 %
    at most once a minute (the base rate), a median execution time of
    0.68 s, clipped to [1 ms, 600 s]."""
    rng = np.random.default_rng(generator.derived_seed(2 ** 33 + 1, 0))
    base = FAMILY.base_rates(rng, 100_000, MIX)
    assert abs((base <= 1 / 60).mean() - 0.45) <= 0.02
    assert abs((base <= 1.0).mean() - 0.81) <= 0.02
    fleet = FAMILY.generate(dict(MIX, minutes=2), 2 ** 33 + 2)
    exec_sec = fleet.exec_sec.reshape(-1)
    assert exec_sec.size == 100_000 and exec_sec.dtype == np.float32
    assert abs(np.median(exec_sec) / 0.68 - 1.0) <= 0.05
    assert exec_sec.min() >= 1e-3 and exec_sec.max() <= 600.0
    # about half the functions run under a second
    assert 0.5 <= (exec_sec < 1.0).mean() <= 0.6


def test_chunks_repeat_for_a_seed_and_differ_across_seeds():
    mix = dict(MIX, n_workloads=64, w_chunk=16, minutes=30)
    seed = 2 ** 31 + 77
    a, b = FAMILY.generate(mix, seed), FAMILY.generate(mix, seed)
    c = FAMILY.generate(mix, seed + 1)
    assert a.rates.shape == (4, 16, 30) and a.rates.dtype == np.float32
    assert a.exec_sec.shape == (4, 16)
    np.testing.assert_array_equal(a.rates, b.rates)
    np.testing.assert_array_equal(a.exec_sec, b.exec_sec)
    assert not np.array_equal(a.exec_sec, c.exec_sec)
    assert not np.array_equal(a.rates, c.rates)
    # chunk c is drawn from the seed derived from (seed, c) alone
    rates, exec_sec = FAMILY.function_chunk(
        np.random.default_rng(generator.derived_seed(seed, 2)), 16, 30, mix)
    np.testing.assert_array_equal(a.rates[2], rates)
    np.testing.assert_array_equal(a.exec_sec[2], exec_sec)
    assert len({a.rates[i].tobytes() for i in range(4)}) == 4


def _cfg():
    return harness.load_json("configs", "azure_fn_hpa_aapa")


def test_lane_plant_follows_the_configured_law():
    svc = np.array([0.001, 0.1, 2.0, 600.0], np.float32)
    plant = lane_plant_reference.lane_plant(svc, _cfg()["lane_plant"])
    np.testing.assert_allclose(plant["rps_per_replica"], 2.0 / svc,
                               rtol=1e-7)
    np.testing.assert_array_equal(plant["service_sec"], svc)
    np.testing.assert_allclose(plant["slo_sec"], 5.0 * svc, rtol=1e-7)


def _lanes(n, seed):
    rng = np.random.default_rng(seed)
    return rng.poisson(10.0 ** rng.uniform(0, 4, (n, 1))
                       * np.ones((1, 30))).astype(np.float32)


def test_uniform_lanes_equal_the_reference():
    """With every lane on the configuration's scalar plant, the
    lane-plant reference is ``reference.Reference``, lane for lane,
    blocks and padding included."""
    cfg = harness.load_json("configs", "k8s_fig2_3pol")
    clf = fakes.random_classifier()
    rates = _lanes(40, 3)
    plant = {k: np.full(40, cfg["plant"][k], np.float32)
             for k in lane_plant_reference.PLANT_KEYS}
    ref = reference.Reference(cfg, clf, block=16)
    lanes = lane_plant_reference.LanePlantReference(cfg, clf, plant,
                                                    block=16)
    for policy in cfg["controllers"]:
        want = ref.lanes(policy, rates, per_lane_hist=True)
        got = lanes.lanes(policy, rates, per_lane_hist=True)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_each_lane_runs_its_own_plant():
    """A lane of the lane-plant reference equals ``reference.Reference``
    on that lane alone, configured with the lane's plant."""
    cfg = harness.load_json("configs", "k8s_fig2_3pol")
    clf = fakes.random_classifier()
    rates = _lanes(3, 4)
    plant = lane_plant_reference.lane_plant(
        np.array([0.005, 0.4, 7.0], np.float32), _cfg()["lane_plant"])
    lanes = lane_plant_reference.LanePlantReference(cfg, clf, plant)
    for policy in ("predictive", "aapa"):
        got = lanes.lanes(policy, rates, per_lane_hist=True)
        for w in range(3):
            own = dict(cfg, plant={**cfg["plant"], **{
                k: float(v[w]) for k, v in plant.items()}})
            want = reference.Reference(own, clf).lanes(
                policy, rates[w:w + 1], per_lane_hist=True)
            for k in want:
                np.testing.assert_allclose(got[k][w], want[k][0],
                                           rtol=1e-6, atol=1e-6,
                                           err_msg=f"{policy} {w} {k}")


def test_the_entry_agrees_and_the_control_does_not(monkeypatch):
    """At a small size on the CPU the program's pooled sums sit within
    the cell's limits of the lane-plant reference, and the reference in
    bfloat16 in the program's place fails at least one of them."""
    fakes.use_fake_classifier(monkeypatch)
    limits = harness.load_json("cells", CELL)["limits"]
    small = {"n_workloads": 64, "w_chunk": 32, "minutes": 60}
    for r in calibrate.readings(CELL, [2 ** 32 + 9, 11], platform="cpu",
                                mix_overrides=small):
        assert compare.judge(r["program"], limits), r
        assert not compare.judge(r["control"], limits), r
