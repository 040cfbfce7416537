"""The bench_sim perf-trajectory contract: the smoke tier proves the
records and BENCH_sim.json schema (what CI uploads as an artifact); the
nightly slow tier runs the full sweep."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _run(tmp_path, *args):
    cmd = [sys.executable, "-m", "benchmarks.run", "sim",
           "--json", str(tmp_path), *args]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    subprocess.run(cmd, check=True, cwd=REPO, timeout=3000, env=env)
    with open(tmp_path / "BENCH_sim.json") as f:
        return json.load(f)


def _check_doc(doc, *, smoke):
    assert doc["bench"] == "sim" and doc["smoke"] is smoke
    assert not doc["failed"]
    names = [r["name"] for r in doc["records"]]
    assert names == ["sim_blocked", "sim_batch", "sim_workloads",
                     "sim_fused_decide"]
    for r in doc["records"]:
        assert set(r) == {"name", "us_per_call", "derived"}
        assert r["us_per_call"] > 0
    blocked = doc["records"][0]
    assert blocked["derived"].startswith("aapa_blocked_speedup=")
    fused = doc["records"][3]
    assert fused["derived"].startswith("hpa_fused_vs_blocked=")


@pytest.mark.slow
def test_bench_sim_smoke_json_schema(tmp_path):
    """The CI smoke invocation end-to-end: stable record names, stable
    schema, machine-readable speedups."""
    _check_doc(_run(tmp_path, "--smoke"), smoke=True)


@pytest.mark.slow
def test_bench_sim_full_sweep(tmp_path):
    """Nightly: the full sweep (policy counts, workload counts,
    blocked-vs-seed, fused-decide kernel) completes and reports sane numbers."""
    _check_doc(_run(tmp_path), smoke=False)
