"""`chip_smoke.py`'s phases at a tiny size on the CPU.

The script itself refuses any platform but a TPU; here each phase
function runs on the CPU backend, where the auto rules take the XLA
paths and the kernels the phases check (window_features, holt_winters,
the fused episode for hpa / kpa) run in interpret mode. Each phase's own
on-device comparisons are the assertions."""
import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load()

TINY_FLEET = dict(n_workloads=8, w_chunk=4, minutes=20)


@pytest.fixture(scope="module")
def trained_and_built():
    trained, built, info = cs.phase_dataset(n_rounds=4)
    assert info["windows"] == len(built) > 0
    assert info["feature_path"] == "ref"         # the CPU auto rule
    return trained, built


def test_main_refuses_cpu(capsys):
    assert cs.main([]) == 1
    out, err = capsys.readouterr()
    assert out == "" and "needs a TPU" in err


def test_script_alone_fails(tmp_path):
    """Outside a checkout the script cannot import the system: it exits
    non-zero and prints no result line."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


def test_phase_dataset(trained_and_built):
    trained, _ = trained_and_built
    assert trained.test_acc > 0.9


def test_phase_calibration(trained_and_built):
    _, built = trained_and_built
    band, info = cs.phase_calibration(built.series, n_series=3,
                                      minutes=600)
    assert info["half_width"] == float(band.q) > 0
    assert info["hw_max_abs_err"] <= 1e-3


def test_phase_fleet(trained_and_built):
    trained, _ = trained_and_built
    info = cs.phase_fleet(cs.fleet_spec(**TINY_FLEET),
                          trained.make_classify(), stream_chunks=2)
    assert info["workloads"] == 8 and info["stream_workloads"] == 8
    assert info["stream_vs_dispatch_worst_rel_err"] <= cs.DP_RTOL
    assert info["oracle_worst_rel_err"] < cs.METRIC_RTOL


def _forgetful_fold(orig):
    """The streaming fold with a fault that drops every earlier chunk."""
    def make(spec_, classify=None):
        fold = orig(spec_, classify)
        return lambda acc, chunk: fold(
            jax.tree.map(jnp.zeros_like, acc), chunk)
    return make


def _last_chunk_runner(orig):
    """The one-dispatch runner with a fault that pools the last chunk
    only."""
    def make(spec_, classify=None, **kw):
        run = orig(spec_, classify, **kw)
        return lambda rates: run(rates[-1:])
    return make


@pytest.mark.parametrize("target, fault", [
    ("make_chunk_folder", _forgetful_fold),
    ("make_fleet_runner", _last_chunk_runner)])
def test_phase_fleet_catches_cross_chunk_faults(target, fault,
                                                trained_and_built,
                                                monkeypatch):
    """A fault that pools across chunks, in the streaming fold or in the
    chunk scan of the one-dispatch runner, fails the fleet phase."""
    trained, _ = trained_and_built
    monkeypatch.setattr(cs.fleet, target, fault(getattr(cs.fleet, target)))
    with pytest.raises(AssertionError):
        cs.phase_fleet(cs.fleet_spec(**TINY_FLEET),
                       trained.make_classify(), stream_chunks=2)


def test_phase_matrix(trained_and_built):
    trained, _ = trained_and_built
    info = cs.phase_matrix(trained.make_classify(), "t", n_workloads=2,
                           minutes=60)
    assert info["cells"] == 5 and set(info["rei"]) == {
        "aapa", "hpa", "hybrid", "kpa", "predictive"}


def test_phase_episodes(trained_and_built):
    trained, _ = trained_and_built
    info = cs.phase_episodes(trained.make_classify(), n_workloads=3,
                             minutes=12)
    assert {p["path"] for p in info.values()} == {"xla_scan"}   # CPU
    # the policies that take the kernel on TPU were checked against the
    # scan here, in interpret mode
    checked = {n for n, p in info.items()
               if p["kernel_vs_scan_max_abs_err"] is not None}
    assert checked == {"hpa", "kpa"}


def test_phase_fleet_dp_one_device():
    info = cs.phase_fleet_dp(cs.fleet_spec(**TINY_FLEET), 1)
    assert info["mesh"] == {"data": 1}
    assert info["worst_rel_err"] <= cs.DP_RTOL


_CLOCK_PROBE = """
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
import jax, jax.numpy as jnp
cs.compile_cache.enable()
hits = []
jax.monitoring.register_event_duration_secs_listener(
    lambda e, d, **_: hits.append(d)
    if e == "/jax/compilation_cache/cache_retrieval_time_sec" else None)
clock = cs.CompileClock()
jax.jit(lambda x: jnp.cos(x) * 3.0 - 1.0)(jnp.ones(8)).block_until_ready()
print(json.dumps({"clock": clock.seconds, "retrieved": sum(hits),
                  "hits": len(hits)}))
"""


def test_compile_clock_counts_cache_hits(tmp_path):
    """`CompileClock` counts a cold compile, and in a second process a
    persistent-cache hit with at least its retrieval time."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")

    def probe():
        res = subprocess.run(
            [sys.executable, "-c", _CLOCK_PROBE, str(ROOT / "chip_smoke.py")],
            env=env, capture_output=True, text=True, timeout=300)
        assert res.returncode == 0, res.stderr[-2000:]
        return json.loads(res.stdout.strip().splitlines()[-1])

    cold, warm = probe(), probe()
    assert cold["hits"] == 0 and cold["clock"] > 0
    assert warm["hits"] >= 1
    assert warm["clock"] >= warm["retrieved"] > 0


def test_phase_lines_are_json(capsys):
    clock = cs.CompileClock()
    with cs.reported("demo", clock) as info:
        info["x"] = 1
    line = json.loads(capsys.readouterr().out.strip())
    assert line["phase"] == "demo" and line["x"] == 1
    assert line["wall_s"] >= 0 and line["compile_s"] >= 0
