"""`correct` on the CPU at tiny sizes: the control (the reference in
bfloat16, put in the program's place) fails each cell's limits while the
program passes them, and a run whose timed path is broken underneath
reports ``correct: false``."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from perfbench import calibrate, compare, harness
from perfbench.tests import fakes

BENCH = harness.benchmark()
ONE_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_the_control_is_not_correct(cell, monkeypatch):
    fakes.use_fake_classifier(monkeypatch)
    limits = harness.load_json("cells", cell)["limits"]
    for r in calibrate.readings(cell, [2 ** 31 + 22], platform="cpu",
                                mix_overrides=fakes.TINY[cell]):
        assert compare.judge(r["program"], limits), r
        assert not compare.judge(r["control"], limits), r


def _double_first_half(rates, axis):
    """Half of the batch left out, its place taken by the other half
    (so a sum over the batch is twice the mean over the rest)."""
    half = np.take(rates, np.arange(rates.shape[axis] // 2), axis=axis)
    return np.concatenate([half, half], axis=axis)


def _patch_call(attr, make):
    def patch(driver):
        setattr(driver, attr, make(getattr(driver, attr)))
    return patch


def _zeros(fn):
    return lambda *a: jax.tree.map(jnp.zeros_like, fn(*a))


def _off_by_a_percent(o, field, where):
    """One number of the answer altered where it is produced: 1% off."""
    return o._replace(**{field: getattr(o, field).at[where].multiply(1.01)})


FAULTS = {
    "fleet_burst_1e5": {
        "state_unchanged": _patch_call("run", _zeros),
        "half_batch": _patch_call(
            "run", lambda f: lambda r: f(_double_first_half(r, 1))),
        "answer_altered": _patch_call(
            "run", lambda f: lambda r: _off_by_a_percent(f(r), "ups", 0)),
    },
    "fleet_stream_1e5": {
        "state_unchanged": _patch_call("fold", lambda f: lambda a, c: a),
        "half_batch": _patch_call(
            "fold", lambda f: lambda a, c: f(a, _double_first_half(c, 0))),
        "answer_altered": _patch_call(
            "fold", lambda f: lambda a, c: _off_by_a_percent(
                f(a, c), "served", 1)),
    },
    "matrix_fig2": {
        "state_unchanged": _patch_call("run", _zeros),
        "half_batch": _patch_call(
            "run", lambda f: lambda r: f(_double_first_half(r, 2))),
        "answer_altered": _patch_call(
            "run", lambda f: lambda r: (lambda o: (o[0], _off_by_a_percent(
                o[1], "total_requests", (0, 0, 0, 0, 0))))(f(r))),
    },
}


@pytest.mark.parametrize("cell,fault", [(c, f) for c in FAULTS
                                        for f in FAULTS[c]])
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    fakes.use_fake_classifier(monkeypatch)
    line = harness.run_cell(cell, 31, 0.1, False, t_start=0.0,
                            platform="cpu", mix_overrides=fakes.TINY[cell],
                            patch=FAULTS[cell][fault])
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0


def test_the_exchange_between_chips_left_out_is_not_correct():
    """On four devices, each device's pooled sums without the cross-chip
    reduction: the answer holds one chip's share of the lanes."""
    cell = next(p.stem for p in sorted((harness.ROOT / "cells").glob("*.json"))
                if harness.load_json("cells", p.stem)["chips"] == 4)
    code = textwrap.dedent(f"""
        import json
        from perfbench import classifier, harness
        from perfbench.tests import fakes
        classifier.get = fakes.fake_get

        def patch(driver):
            run = driver.run
            # device 0's lanes of every chunk, summed alone
            driver.run = lambda rates: run(rates[:, :rates.shape[1] // 4])

        line = harness.run_cell({cell!r}, 5, 0.1, False, t_start=0.0,
                                platform="cpu",
                                mix_overrides=fakes.TINY[{cell!r}],
                                patch=patch)
        print(json.dumps(line))
        """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([str(harness.REPO),
                                          str(harness.REPO / "src")])}
    r = subprocess.run([sys.executable, "-c", code], cwd=harness.REPO,
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
