"""The bench_fleet decade-sweep contract: the smoke tier proves the
records and BENCH_fleet.json schema (what CI uploads as an artifact);
the nightly slow tier runs the full W sweep and asserts the memory
acceptance bar (W=1e5 in one dispatch, peak RSS < 2x the W=1e4 run)."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _run(tmp_path, *args):
    cmd = [sys.executable, "-m", "benchmarks.run", "fleet",
           "--json", str(tmp_path), *args]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    subprocess.run(cmd, check=True, cwd=REPO, timeout=3000, env=env)
    with open(tmp_path / "BENCH_fleet.json") as f:
        return json.load(f)


def _check_doc(doc, *, smoke):
    assert doc["bench"] == "fleet" and doc["smoke"] is smoke
    assert not doc["failed"]
    names = [r["name"] for r in doc["records"]]
    assert names == ["fleet_decades", "fleet_stream"]
    for r in doc["records"]:
        assert set(r) == {"name", "us_per_call", "derived"}
        assert r["us_per_call"] > 0
    assert doc["records"][0]["derived"].startswith("w")


@pytest.mark.slow
def test_bench_fleet_smoke_json_schema(tmp_path):
    """The CI smoke invocation end-to-end: stable record names, stable
    schema."""
    _check_doc(_run(tmp_path, "--smoke"), smoke=True)


@pytest.mark.slow
def test_bench_fleet_full_decades(tmp_path):
    """Nightly: the full W in {64, 1e2, 1e3, 1e4, 1e5} sweep, pinning
    the acceptance criteria — W=1e5 completes in ONE dispatch and its
    peak RSS stays under 2x the W=1e4 run (the streamed O(bins)
    reductions keep accumulator memory W-independent)."""
    _check_doc(_run(tmp_path), smoke=False)
    with open(REPO / "experiments/bench/fleet_decades.json") as f:
        payload = json.load(f)
    per_w = {int(k): v for k, v in payload["per_w"].items()}
    assert set(per_w) == {64, 100, 1_000, 10_000, 100_000}
    assert per_w[100_000]["dispatches"] == 1
    assert (per_w[100_000]["peak_rss_mb"]
            < 2.0 * per_w[10_000]["peak_rss_mb"]), per_w
    assert payload["rss_ratio_1e5_vs_1e4"] < 2.0
