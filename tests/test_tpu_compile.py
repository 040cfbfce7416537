"""Every Pallas kernel a TPU run can take, compiled for a described TPU v5e.

No chip is attached: `jax.experimental.topologies` describes a v5e and
the TPU compiler (Mosaic) compiles each kernel for it at the shapes
``chip_smoke.py`` runs, so a kernel the chip would refuse fails here.
Each kernel's test asserts the compiled program holds the kernel
(`tpu_custom_call`). Covered: `window_features` (dataset build) and
`holt_winters` (every Holt-Winters `smooth` on TPU). The lane scan
(`make_simulator`) compiles for the v5e with no kernel in it. Besides,
the fleet runner's AAPA reclassification is compiled for the CPU and for
the v5e and checked to hold no loop and no gather, and its forecast
stage to hold no gather and no scatter.

The topology is described inside a module fixture — never at import —
so under pytest-xdist only the worker that runs this file loads the TPU
compiler library; that worker keeps it until it exits.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.scaling import registry
from repro.sim.cluster import SimConfig, make_simulator


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described device cannot be read back from the
    persistent cache without the chip: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile_text(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_window_features_compiles(one_chip, no_compile_cache):
    txt = _compile_text(
        lambda x: ops.window_features(x, interpret=False), (8192, 60),
        sharding=one_chip)
    assert "tpu_custom_call" in txt


def test_holt_winters_compiles(one_chip, no_compile_cache):
    txt = _compile_text(
        lambda y: ops.holt_winters(y, interpret=False), (16, 2880),
        sharding=one_chip)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("policy", ("hpa", "kpa"))
@pytest.mark.parametrize("lanes", (1, 1000))
def test_lane_scan_compiles(policy, lanes, one_chip, no_compile_cache):
    """`make_simulator` at the episode length ``chip_smoke.py`` runs:
    one XLA scan on every platform, with no Pallas kernel in it."""
    cfg = SimConfig()
    run = make_simulator(registry.get_controller(policy, cfg), cfg)
    rates = jax.ShapeDtypeStruct((lanes, 240), jnp.float32,
                                 sharding=one_chip)
    txt = run.lower(rates).compile().as_text()
    assert "tpu_custom_call" not in txt


@pytest.mark.parametrize("platform", ["cpu", "v5e"])
def test_reclassify_has_no_loop_and_no_gather(platform, request):
    """The fleet runner at test size, with a classifier of the published
    shape (60 rounds x 4 classes of depth-4 trees): the optimized
    program's ops under the `lane.reclassify` scope hold no `while` and
    no `gather` (the bins' binary search, the tree-chunk loop and the
    leaf lookup of the host table path)."""
    from perfbench import classifier
    from perfbench.tests import fakes
    from repro.evals import fleet
    from repro.obs import stages
    classify = classifier.program_classify(
        fakes.random_classifier(rounds=60, depth=4))
    fs = fleet.spec("t", policies=("hpa", "aapa"), n_workloads=16,
                    w_chunk=8, minutes=30)
    run = fleet.make_fleet_runner(fs, classify, donate=False)
    if platform == "cpu":
        rates = np.zeros((2, 8, 30), np.float32)
    else:
        request.getfixturevalue("no_compile_cache")
        rates = jax.ShapeDtypeStruct((2, 8, 30), jnp.float32,
                                     sharding=request.getfixturevalue(
                                         "one_chip"))
    hlo = run.lower(rates).compile().as_text()
    ops = [m.group(1) for line in hlo.splitlines()
           if stages.RECLASSIFY in line
           for m in [re.search(r"= [^=]*? ([a-z][\w-]*)\(", line)] if m]
    assert "compare" in ops
    assert "while" not in ops
    assert "gather" not in ops


@pytest.mark.parametrize("platform", ["cpu", "v5e"])
def test_forecast_stage_has_no_gather_or_scatter(platform, request):
    """The fleet runner at test size (``hpa`` + ``aapa``, Holt-Winters
    with per-lane phases): the optimized program's ops under the
    `lane.forecast` scope address the seasonal ring by slot mask, so
    they hold no `gather` and no `scatter`."""
    from perfbench import classifier
    from perfbench.tests import fakes
    from repro.evals import fleet
    from repro.obs import stages
    classify = classifier.program_classify(fakes.random_classifier())
    fs = fleet.spec("t", policies=("hpa", "aapa"), n_workloads=16,
                    w_chunk=8, minutes=30)
    run = fleet.make_fleet_runner(fs, classify, donate=False)
    if platform == "cpu":
        rates = np.zeros((2, 8, 30), np.float32)
    else:
        request.getfixturevalue("no_compile_cache")
        rates = jax.ShapeDtypeStruct((2, 8, 30), jnp.float32,
                                     sharding=request.getfixturevalue(
                                         "one_chip"))
    hlo = run.lower(rates).compile().as_text()
    ops = [m.group(1) for line in hlo.splitlines()
           if stages.FORECAST in line
           for m in [re.search(r"= [^=]*? ([a-z][\w-]*)\(", line)] if m]
    assert "select" in ops
    assert not [op for op in ops if "gather" in op or "scatter" in op]
