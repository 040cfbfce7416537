"""The `decide_kernel=` dispatch contract (ISSUE: whole-episode-on-chip).

Three guarantees:

* **off path is bit-exact** — `decide_kernel=False` and the CPU default
  (auto-off on non-TPU backends) produce byte-identical MinuteOut: the
  flag cannot perturb the published eval numbers.
* **on path is one compile** — the fused episode kernel replaces the
  whole episode loop, so `make_simulator(decide_kernel=True)` still
  shows `_cache_size() == 1` after running, and composes with
  `w_chunk` in the batch front door.
* **telemetry is loudly incompatible** — decisions never leave the chip
  on the fused path, so `telemetry=True` raises at build time (both
  `cluster.make_simulator` and `scaling.batch.make_batch_simulator`)
  instead of silently returning empty traces.

The interpret-mode fused-vs-oracle parity itself is pinned per policy in
test_kernel_smoke.py; the `tpu_backend`-gated test at the bottom re-pins
it with `interpret=False` on real hardware.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.scaling import batch, registry
from repro.sim.cluster import SimConfig, make_simulator, simulate

# ci=30: small unrolled-tick jaxpr -> seconds-scale interpret compiles.
CFG = SimConfig(control_interval_sec=30)


def _rates(w=5, m=6, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(0.0, 200.0, size=(w, m)), jnp.float32)


def _ctrl(name="hpa"):
    return registry.get_controller(name, CFG)


def test_off_path_bit_exact_vs_default():
    rates = _rates()
    explicit = make_simulator(_ctrl(), CFG, decide_kernel=False)(rates)
    default = make_simulator(_ctrl(), CFG)(rates)  # CPU -> auto off
    for i, (a, e) in enumerate(zip(explicit, default)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(e),
                                      err_msg=f"MinuteOut[{i}]")


def test_fused_simulator_one_compile_and_parity():
    rates = _rates()
    fused = make_simulator(_ctrl(), CFG, decide_kernel=True)
    got = fused(rates)
    assert fused._cache_size() == 1
    want = make_simulator(_ctrl(), CFG, decide_kernel=False)(rates)
    for i, (a, e) in enumerate(zip(got, want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=3e-6, atol=1e-4,
                                   err_msg=f"MinuteOut[{i}]")


def test_fused_single_episode_simulate():
    r = _rates(w=1)[0]
    got = simulate(r, _ctrl(), CFG, decide_kernel=True)
    want = simulate(r, _ctrl(), CFG, decide_kernel=False)
    for i, (a, e) in enumerate(zip(got, want)):
        assert a.shape == e.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=3e-6, atol=1e-4,
                                   err_msg=f"MinuteOut[{i}]")


def test_batch_fused_parity_and_w_chunk():
    rates = _rates(w=10)
    ctrls = [_ctrl("hpa"), _ctrl("kpa")]
    on = batch.make_batch_simulator(ctrls, CFG, decide_kernel=True)
    off = batch.make_batch_simulator(ctrls, CFG, decide_kernel=False)
    got, want = on(rates), off(rates)
    assert on._cache_size() == 1
    for i, (a, e) in enumerate(zip(got, want)):
        assert a.shape == (2, 10, rates.shape[1])
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=3e-6, atol=1e-4,
                                   err_msg=f"MinuteOut[{i}]")
    chunked = batch.make_batch_simulator(ctrls, CFG, decide_kernel=True,
                                         w_chunk=5)(rates)
    for i, (a, e) in enumerate(zip(chunked, got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=3e-6, atol=1e-4,
                                   err_msg=f"w_chunk MinuteOut[{i}]")


def test_telemetry_rejected_on_fused_path():
    with pytest.raises(ValueError, match="decide_kernel"):
        make_simulator(_ctrl(), CFG, decide_kernel=True, telemetry=True)
    with pytest.raises(ValueError, match="decide_kernel"):
        simulate(_rates(w=1)[0], _ctrl(), CFG, decide_kernel=True,
                 telemetry=True)
    with pytest.raises(ValueError, match="decide_kernel"):
        batch.make_batch_simulator([_ctrl()], CFG, decide_kernel=True,
                                   telemetry=True)


def test_telemetry_w_chunk_error_names_fleet_front_door():
    """The telemetry+w_chunk rejection must point at the actual recourse:
    FleetSpec(..., trace_lanes=K) via repro.evals.fleet."""
    with pytest.raises(ValueError) as ei:
        batch.make_batch_simulator([_ctrl()], CFG, telemetry=True,
                                   w_chunk=4)
    msg = str(ei.value)
    assert "trace_lanes" in msg and "evals.fleet" in msg


@pytest.fixture
def tpu_backend():
    """Skip unless this process runs on a TPU. Asked inside the test, so
    collecting the file never starts a backend."""
    if jax.default_backend() != "tpu":
        pytest.skip("requires a TPU backend (interpret=False kernel path)")


def test_fused_compiled_parity_on_tpu(tpu_backend):
    """interpret=False (Mosaic-compiled) fused episode vs the blocked
    scan, for every policy the auto rule sends to the kernel on TPU
    (`tpu_kernel`; see the episode_block docstring for the rest)."""
    from repro.kernels import ops, ref
    rates = _rates()
    names = [n for n in registry.available() if _ctrl(n).tpu_kernel]
    assert names
    for name in names:
        ctrl = registry.get_controller(name, CFG)
        got = ops.episode_block(rates, ctrl, CFG, interpret=False)
        want = ref.episode_block_ref(rates, ctrl, CFG)
        for i, (a, e) in enumerate(zip(got, want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       rtol=1e-4, atol=1e-3,
                                       err_msg=f"{name} MinuteOut[{i}]")
