"""The harness on the CPU at tiny sizes: the refusal off a TPU, the
layout that finds every part by name, the throughput arithmetic, and one
tiny run of every cell through its entry driver."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from perfbench import generator, harness
from perfbench.tests import fakes

PERFBENCH = harness.ROOT
REPO = harness.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def _run(args, cwd, **env):
    return subprocess.run([sys.executable, "perfbench/run.py", *args],
                          cwd=cwd, capture_output=True, text=True,
                          timeout=300, env={**os.environ, **env})


def test_run_refuses_a_cpu_and_prints_no_result():
    r = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0"], REPO, JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert '"correct"' not in r.stdout and "metrics" not in r.stdout
    assert "needs a TPU" in r.stderr


def test_run_fails_with_the_benchmark_alone(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: no program."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns(".cache",
                                                      "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "perfbench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=300,
                       env={**env, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0 and '"correct"' not in r.stdout


@pytest.mark.parametrize("cell", sorted(p.stem for p in
                                  (PERFBENCH / "cells").glob("*.json")))
def test_every_cell_names_existing_parts(cell):
    """Every cell file, registered in BENCHMARK.json or kept for a later
    PR to register."""
    c = harness.load_json("cells", cell)
    w = next((w for w in BENCH["workloads"] if w["name"] == cell), None)
    if w is not None:
        assert (c["config"], c["traffic"], c["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        cfg = next(x for x in BENCH["configs"] if x["name"] == c["config"])
        assert (REPO / cfg["file"]).is_file()
    assert harness.load_json("configs", c["config"])["name"] == c["config"]
    assert (PERFBENCH / "entries" / f"{c['entry']}.py").is_file()
    assert generator.load_mix(c["traffic"])["generator"] in \
        generator.families()
    assert c["limits"] and all(v > 0 for v in c["limits"].values())
    for m in harness.layer_metrics(BENCH, cell):
        assert (PERFBENCH / "layers" / f"{m['name']}.py").is_file()


def test_every_metric_and_config_is_used():
    names = {m["name"] for m in BENCH["per_layer"]}
    assert names <= {p.stem for p in (PERFBENCH / "layers").glob("*.py")}
    for m in BENCH["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS), m["name"]
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_a_new_cell_and_metric_are_found_by_name(tmp_path, monkeypatch):
    """A later PR adds a cell, a configuration, a traffic mix, a traffic
    family, an entry and a per-layer metric as new files; no file that is
    there is edited."""
    root = tmp_path / "perfbench"
    shutil.copytree(PERFBENCH, root,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    (root / "traffic" / "tiny_storm.json").write_text(json.dumps(
        {"generator": "burst_storm", "n_workloads": 8, "w_chunk": 4,
         "minutes": 5, "floor": 1.0, "height": 10.0, "n_storms": 1}))
    (root / "traffic" / "tiny_ramp.py").write_text(textwrap.dedent("""
        import numpy as np
        def generate(mix, seed):
            n, m = mix["n_workloads"], mix["minutes"]
            return np.tile(np.arange(m, dtype=np.float32) * seed, (n, 1))
        """))
    (root / "traffic" / "tiny_ramp_mix.json").write_text(json.dumps(
        {"generator": "tiny_ramp", "n_workloads": 3, "minutes": 4}))
    cfg = dict(harness.load_json("configs", "k8s_hpa_aapa"), name="tiny_cfg")
    (root / "configs" / "tiny_cfg.json").write_text(json.dumps(cfg))
    (root / "cells" / "tiny_cell.json").write_text(json.dumps(
        {"config": "tiny_cfg", "entry": "count_entry",
         "traffic": "tiny_storm", "chips": 1, "limits": {"gap": 0.1},
         "why": "a cell added as files only"}))
    (root / "entries" / "count_entry.py").write_text(textwrap.dedent("""
        class Driver:
            module, units, iterations, lane_minutes = "jit_x", 1, 5, 40
            def __init__(self, ctx):
                self.shape = ctx.rates.shape
        def prepare(ctx):
            return Driver(ctx)
        """))
    (root / "layers" / "tiny_metric.py").write_text(
        "def read(run):\n    return 2.0 * run.dispatches\n")
    bench = dict(BENCH, per_layer=BENCH["per_layer"] + [
        {"name": "tiny_metric", "unit": "1", "better": "lower",
         "source": "host_clock", "layer": "test", "moves": "setup_s",
         "workloads": ["tiny_cell"]}])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "REPO", tmp_path)

    cell = harness.load_json("cells", "tiny_cell")
    assert harness.load_json("configs", cell["config"]) == cfg
    mix = generator.load_mix(cell["traffic"])
    rates = generator.generate(mix, 3)
    assert rates.shape == (2, 4, 5)
    assert "tiny_ramp" in generator.families()
    ramp = generator.generate(generator.load_mix("tiny_ramp_mix"), 2)
    assert ramp.shape == (3, 4) and ramp[0, -1] == 6.0
    ctx = harness.Context("tiny_cell", cell, {}, mix, rates, None, None,
                          harness.Spans(), 1)
    assert harness.entry(cell["entry"]).prepare(ctx).shape == (2, 4, 5)
    metrics = harness.layer_metrics(harness.benchmark(), "tiny_cell")
    assert [m["name"] for m in metrics][-1] == "tiny_metric"
    assert all(m["name"] not in ("host_per_chunk_ms", "collective_share")
               for m in metrics)
    run = harness.Run("tiny_cell", None, harness.Spans(), None, 3, 0.0)
    assert harness.layer("tiny_metric").read(run) == 6.0
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_lane_min_per_s_over_a_fake_window():
    # 2 policies x 1e5 workloads x 60 minutes per dispatch, 5 dispatches
    # in a 21.5 s window
    assert harness.lane_min_per_s(2 * 100_000 * 60, 5, 21.5) == \
        pytest.approx(6e7 / 21.5, rel=1e-12)


def test_setup_compile_reader_and_host_spans():
    spans = harness.Spans()
    for _ in range(4):
        with spans("feed"):
            pass
    run = harness.Run("c", None, spans, None, 1, 12.5)
    assert harness.layer("setup_compile_s").read(run) == 12.5
    assert harness.layer("host_per_chunk_ms").read(run) >= 0.0
    assert harness.layer("host_per_chunk_ms").read(
        harness.Run("c", None, harness.Spans(), None, 1, 0.0)) is None


@pytest.mark.parametrize("cell", [c for c in CELLS
                                  if harness.load_json("cells", c)["chips"]
                                  == 1])
def test_each_entry_runs_a_tiny_dispatch(cell, monkeypatch):
    fakes.use_fake_classifier(monkeypatch)
    line = harness.run_cell(cell, 2 ** 31 + 5, 0.2, False, t_start=0.0,
                            platform="cpu",
                            mix_overrides=fakes.TINY[cell])
    assert line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"lane_min_per_s", "setup_s"}
    assert line["metrics"]["lane_min_per_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checked"
    for c in line["checked"].values():
        assert c["value"] <= c["limit"]


def test_a_traced_run_on_the_cpu_reads_what_it_can(monkeypatch):
    """The CPU trace has no device plane: the trace readers find nothing
    and are left out; the host-side readers still report."""
    fakes.use_fake_classifier(monkeypatch)
    cell = "fleet_stream_1e5"
    line = harness.run_cell(cell, 4, 1.0, True, t_start=0.0,
                            platform="cpu", mix_overrides=fakes.TINY[cell])
    assert line["correct"] is True and line["attempted"] == 3
    assert set(line["metrics"]) == {"host_per_chunk_ms", "setup_compile_s"}
    assert line["device"]["busy_s"] == 0.0 and line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_four_chip_cell_on_four_virtual_devices():
    cell = next(p.stem for p in sorted((PERFBENCH / "cells").glob("*.json"))
                if harness.load_json("cells", p.stem)["chips"] == 4)
    code = textwrap.dedent(f"""
        import json
        from perfbench import classifier, harness
        from perfbench.tests import fakes
        classifier.get = fakes.fake_get
        line = harness.run_cell({cell!r}, 9, 0.2, False, t_start=0.0,
                                platform="cpu",
                                mix_overrides=fakes.TINY[{cell!r}])
        print(json.dumps(line))
        """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([str(REPO), str(REPO / "src")])}
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["count"] == 4
