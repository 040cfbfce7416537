"""Every Pallas kernel a TPU run can take, compiled for a described TPU v5e.

No chip is attached: `jax.experimental.topologies` describes a v5e and
the TPU compiler (Mosaic) compiles each kernel for it at the shapes
``chip_smoke.py`` runs, so a kernel the chip would refuse fails here.
Each test asserts the compiled program holds the kernel
(`tpu_custom_call`). Covered: `window_features` (dataset build),
`holt_winters` (every Holt-Winters `smooth` on TPU), and `episode_block`
for every policy the `decide_kernel` auto rule sends to it
(`Controller.tpu_kernel`).

The topology is described inside a module fixture — never at import —
so under pytest-xdist only the worker that runs this file loads the TPU
compiler library; that worker keeps it until it exits.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.scaling import registry
from repro.sim.cluster import SimConfig

#: the policies whose decide lowers inside the episode kernel on TPU
KERNEL_POLICIES = ("hpa", "kpa")


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


@pytest.mark.parametrize("policy", KERNEL_POLICIES)
@pytest.mark.parametrize("lanes", (1, 1000))
def test_episode_block_compiles(policy, lanes, one_chip, no_compile_cache):
    cfg = SimConfig()
    ctrl = registry.get_controller(policy, cfg)
    txt = _compile_text(
        lambda r: ops.episode_block(r, ctrl, cfg, interpret=False),
        (lanes, 240), sharding=one_chip)
    assert "tpu_custom_call" in txt


def test_kernel_policies_are_the_auto_rule_set():
    """The parametrization above covers exactly the policies that
    declare `tpu_kernel` — a policy added to the auto rule must be
    compiled here too."""
    cfg = SimConfig()
    declared = tuple(n for n in registry.available()
                     if registry.get_controller(n, cfg).tpu_kernel)
    assert declared == KERNEL_POLICIES
