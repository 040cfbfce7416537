"""Bring-up smoke: the AAPA simulator's main path, once, on a TPU.

    python chip_smoke.py             # every phase on one chip
    python chip_smoke.py --chips 4   # only the fleet: 4-device dp mesh
                                     # vs the same fleet on one device

Phases (one process, no CPU fallback):

1. device — the first JAX device must be a TPU, else exit 1 before any
   phase runs;
2. dataset + classifier — build ``aapaset_ci`` from its seed (the
   ``window_features`` kernel on TPU) and fit the GBDT archetype
   classifier, from committed files only (nothing read from or written
   to ``experiments/``);
3. conformal calibration of Holt-Winters (the ``holt_winters`` kernel);
4. fleet — ``evals.fleet.run_fleet`` of hpa + aapa over a 10^5-lane
   ``burst_storm`` fleet in one dispatch, then its first chunks both
   streamed and in one dispatch;
5. the Table IV matrix of the quickstart (five policies,
   ``archetype_mix``, 1440 minutes, 16 workloads);
6. single episodes (``make_simulator`` / ``simulate``) per registry
   policy.

Every phase checks its result on the device against the repository's
own references at the tier-1 tests' tolerances and raises on a
mismatch; no phase catches its own failure. Earlier lines report wall
and compile seconds per phase (informational, not a benchmark). The
last line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import pathlib
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.aapaset import manifest as MF  # noqa: E402
from repro.aapaset import registry as datasets  # noqa: E402
from repro.aapaset.build import build  # noqa: E402
from repro.aapaset.loader import AAPAsetLoader  # noqa: E402
from repro.core import features as F  # noqa: E402
from repro.core import forecasting as fc  # noqa: E402
from repro.core import gbdt, pipeline  # noqa: E402
from repro.dist import sharding as shd  # noqa: E402
from repro.evals import fleet, matrix  # noqa: E402
from repro.evals import metrics as EM  # noqa: E402
from repro.forecast import conformal  # noqa: E402
from repro.forecast import registry as forecasters  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.scaling import batch, registry, scenarios  # noqa: E402
from repro.sim import cluster  # noqa: E402
from repro.sim import metrics as M  # noqa: E402

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# the tolerances of the tier-1 tests that pin the same comparisons
FEATURE_TOL = dict(rtol=5e-4, atol=5e-4)       # test_kernel_smoke
HW_TOL = dict(rtol=1e-4, atol=1e-3)            # test_kernel_smoke
EPISODE_TOL = dict(rtol=1e-4, atol=1e-3)       # test_fleet, TPU
METRIC_RTOL = 2e-4                             # test_evals
DP_RTOL = 2e-6                                 # test_fleet, 8-device pin


# ------------------------------------------------------------- checks ----
def check_metrics(what: str, dev, host, *, rtol: float = METRIC_RTOL):
    """EpisodeMetrics on the device vs the NumPy oracle
    (``sim.metrics.aggregate``), field by field; quantiles get the
    histogram bound of ``tests/test_evals.py``. Returns the worst
    relative error over the non-quantile fields."""
    q_rtol = 2.5 * EM.quantile_rel_bound()
    worst, bad = 0.0, []
    for field in EM.EpisodeMetrics._fields:
        d = float(np.asarray(getattr(dev, field)))
        h = float(getattr(host, field))
        tol = q_rtol if field.startswith(("p95", "p99")) else rtol
        err = abs(d - h)
        if err > max(tol * abs(h), 1e-3):
            bad.append(f"{field}: device {d!r} vs oracle {h!r}")
        if not field.startswith(("p95", "p99")):
            worst = max(worst, err / max(abs(h), 1e-9))
    if bad:
        raise AssertionError(f"{what}: " + "; ".join(bad))
    return worst


def check_same_fleet(what: str, got, want) -> float:
    """Two FleetResults over the same lanes: pooled metrics and REI
    agree at DP_RTOL (the repository's rule for two compiled programs).
    Returns the worst relative difference of the pooled metrics."""
    worst = 0.0
    for field in EM.EpisodeMetrics._fields:
        a = np.asarray(getattr(want.pooled, field))
        b = np.asarray(getattr(got.pooled, field))
        np.testing.assert_allclose(b, a, rtol=DP_RTOL,
                                   err_msg=f"{what}: {field}")
        worst = max(worst, float(np.max(np.abs(a - b)
                                        / np.maximum(np.abs(a), 1e-9))))
    np.testing.assert_allclose(got.rei.rei, want.rei.rei, rtol=DP_RTOL,
                               err_msg=f"{what}: REI")
    return worst


def check_close(what: str, got, want, *, rtol: float, atol: float):
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        raise AssertionError(f"{what}: shape {got.shape} vs {want.shape} "
                             "or non-finite values")
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol,
                               err_msg=what)
    return float(np.max(np.abs(got - want)))


def oracle_pooled(ctrls, cfg, rates):
    """Per-lane runs of every controller through the batch simulator,
    pooled by the NumPy oracle: one EpisodeMetrics per controller."""
    out = batch.make_batch_simulator(ctrls, cfg)(jnp.asarray(rates))
    out = jax.tree.map(np.asarray, out)
    return [M.aggregate(jax.tree.map(lambda a: a[p], out),
                        workload_axis=True) for p in range(len(ctrls))]


# ------------------------------------------------------------- phases ----
def phase_dataset(*, n_rounds: int = 20, depth: int = 3,
                  min_test_acc: float = 0.9, **dataset_overrides):
    """Build ``aapaset_ci`` from its seed and fit the classifier. The
    build's feature path is the auto rule's (the kernel on TPU); the
    kernel is also checked against the reference feature math."""
    cfg = datasets.get("aapaset_ci", **dataset_overrides)
    built = build(cfg)
    manifest = {"config": dataclasses.asdict(cfg),
                "hash": MF.config_hash(cfg)}
    loader = AAPAsetLoader(built, manifest)
    trained = pipeline.train_classifier(
        cfg.name, gbdt.GBDTConfig(n_rounds=n_rounds, depth=depth),
        cache=False, loader_factory=lambda: loader)
    if not trained.test_acc > min_test_acc:
        raise AssertionError(f"classifier test accuracy "
                             f"{trained.test_acc} <= {min_test_acc}")
    win = jnp.asarray(built.windows[:512])
    err = check_close("window_features kernel vs reference",
                      ops.window_features(win), F.stat_time_features(win),
                      **FEATURE_TOL)
    info = {"windows": len(built), "feature_path":
            cfg.resolved_feature_path(), "test_acc": trained.test_acc,
            "feature_max_abs_err": err}
    return trained, built, info


def phase_calibration(series: np.ndarray, *, n_series: int = 16,
                      minutes: int = 2880):
    """Split-conformal calibration of Holt-Winters through the kernel,
    and the kernel's one-step forecasts against the reference scan."""
    y = np.asarray(series[:n_series, :minutes], np.float32)
    band = conformal.calibrate(forecasters.make("holt_winters"), y,
                               alpha=0.9)
    q = float(band.q)
    if not (np.isfinite(q) and q > 0):
        raise AssertionError(f"conformal half-width {q}")
    err = check_close("holt_winters kernel vs reference",
                      ops.holt_winters(jnp.asarray(y)),
                      fc.hw_smooth(jnp.asarray(y)), **HW_TOL)
    return band, {"series": y.shape[0], "minutes": y.shape[1],
                  "half_width": q, "hw_max_abs_err": err}


def fleet_spec(*, n_workloads: int = 100_000, w_chunk: int = 1000,
               minutes: int = 60) -> fleet.FleetSpec:
    return fleet.spec("chip_smoke_fleet", policies=("hpa", "aapa"),
                      scenario="burst_storm", n_workloads=n_workloads,
                      w_chunk=w_chunk, minutes=minutes)


def phase_fleet(spec: fleet.FleetSpec, classify, *, stream_chunks: int = 10):
    """The fleet in one dispatch (warm), then its first `stream_chunks`
    chunks twice: through the streaming fold, and in one dispatch. The
    two must agree at DP_RTOL (the chunk scan's carry against the
    streaming fold), and the one-dispatch run must match the NumPy
    oracle over per-lane runs of the same lanes."""
    res = fleet.run_fleet(spec, classify=classify, warmup=True)
    for i, p in enumerate(spec.policies):
        v = float(res.pooled.slo_violation_rate[i])
        if not np.isfinite(v):
            raise AssertionError(f"fleet {p}: violation rate {v}")
    head = dataclasses.replace(spec,
                               n_workloads=stream_chunks * spec.w_chunk)
    stream = fleet.run_fleet(head, classify=classify, stream=True)
    once = fleet.run_fleet(head, classify=classify)
    stream_err = check_same_fleet("fleet streamed vs one dispatch",
                                  stream, once)
    want = oracle_pooled(fleet.controllers(spec, classify),
                         spec.sim_config(),
                         fleet.build_rates(head).reshape(-1, spec.minutes))
    worst = max(check_metrics(f"fleet first {stream_chunks} chunks {p}",
                              jax.tree.map(lambda a: a[i], once.pooled),
                              want[i])
                for i, p in enumerate(spec.policies))
    return {"workloads": res.meta["workloads"],
            "lane_minutes_per_sec": res.meta["lane_minutes_per_sec"],
            "dispatch_s": res.meta["wall_s"], "build_s": res.meta["build_s"],
            "stream_workloads": stream.meta["workloads"],
            "stream_lane_minutes_per_sec":
                stream.meta["lane_minutes_per_sec"],
            "stream_vs_dispatch_worst_rel_err": stream_err,
            "oracle_worst_rel_err": worst, "path": "xla_scan"}


def phase_matrix(classify, classifier_id: str, *, n_workloads: int = 16,
                 minutes: int = 1440):
    """The quickstart's Table IV matrix; every policy's cell is checked
    against the NumPy oracle over per-lane runs."""
    spec = matrix.spec("chip_smoke_matrix",
                       policies=tuple(registry.available()),
                       scenarios=(("archetype_mix", {}),), seeds=(11,),
                       n_workloads=n_workloads, minutes=minutes)
    with tempfile.TemporaryDirectory() as root:
        run = matrix.run(spec, classify=classify,
                         classifier_id=classifier_id, root=root, force=True)
    want = oracle_pooled(matrix.controllers(spec, classify),
                         spec.sim_config(), matrix.build_rates(spec)[0, 0])
    worst = max(check_metrics(f"matrix cell {p}", jax.tree.map(
        lambda a: a[0, 0, 0, i], run.result.pooled), want[i])
        for i, p in enumerate(spec.policies))
    rei = {p: float(run.result.rei.rei[0, 0, 0, i])
           for i, p in enumerate(spec.policies)}
    return {"cells": len(spec.policies), "worst_rel_err": worst,
            "rei": rei, "path": "xla_scan"}


def phase_episodes(classify, *, n_workloads: int = 1000,
                   minutes: int = 240):
    """Every registry policy through `make_simulator` and `simulate`:
    finite outputs, and lane 0 of the fleet equals the lone episode."""
    cfg = cluster.SimConfig()
    rates = jnp.asarray(scenarios.get(
        "burst_storm", n_workloads=n_workloads, minutes=minutes,
        seed=0).rates, jnp.float32)
    info = {}
    for name in registry.available():
        ctrl = registry.get_controller(name, cfg, classify=classify)
        out = cluster.make_simulator(ctrl, cfg)(rates)
        one = cluster.simulate(rates[0], ctrl, cfg)
        for f, a in zip(cluster.MinuteOut._fields, out):
            if not np.all(np.isfinite(np.asarray(a))):
                raise AssertionError(f"{name}: non-finite {f}")
        info[name] = {"lane0_max_abs_err": check_close(
            f"{name} simulate vs make_simulator lane 0",
            one.served, out.served[0], **EPISODE_TOL)}
    return info


def phase_fleet_dp(spec: fleet.FleetSpec, n_devices: int):
    """The fleet on one device, then under an `n_devices` dp mesh in the
    same process; pooled metrics and REI must agree at rtol 2e-6."""
    one = fleet.run_fleet(spec, warmup=True)
    mesh = shd.make_mesh((n_devices,), ("data",))
    shd.set_mesh(mesh)
    try:
        with mesh:
            many = fleet.run_fleet(spec, warmup=True)
    finally:
        shd.set_mesh(None)
    worst = check_same_fleet(f"fleet on {n_devices} devices vs one",
                             many, one)
    return {"devices": n_devices, "mesh": many.meta["mesh"],
            "worst_rel_err": worst,
            "lane_minutes_per_sec_1dev": one.meta["lane_minutes_per_sec"],
            "lane_minutes_per_sec_dp": many.meta["lane_minutes_per_sec"]}


# --------------------------------------------------------------- main ----
class CompileClock:
    """Sums the seconds JAX reports under its backend-compile event. That
    event times ``compile_or_get_cached``, so a persistent-cache hit
    counts with the time it took to look up and load the executable
    (pinned in tests/test_chip_smoke.py)."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration


@contextlib.contextmanager
def reported(name: str, clock: CompileClock):
    """Times the block and prints one JSON line with what the block put
    into the yielded dict."""
    t0, c0 = time.perf_counter(), clock.seconds
    info = {}
    yield info
    line = {"phase": name, "wall_s": time.perf_counter() - t0,
            "compile_s": clock.seconds - c0, **info}
    print(json.dumps(line, default=float), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the fleet on a 4-device dp mesh, "
                         "compared with the same fleet on one device")
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devs[0].platform!r}; "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 1
    cache_dir = compile_cache.enable()
    clock = CompileClock()
    t_start = time.perf_counter()
    print(json.dumps({"phase": "device", "kind": devs[0].device_kind,
                      "count": len(devs), "jax": jax.__version__,
                      "compile_cache": cache_dir}), flush=True)

    if args.chips == 4:
        with reported("fleet_dp", clock) as info:
            info.update(phase_fleet_dp(fleet_spec(), args.chips))
    else:
        with reported("dataset", clock) as info:
            trained, built, more = phase_dataset()
            info.update(more)
        classify = trained.make_classify()
        with reported("calibration", clock) as info:
            info.update(phase_calibration(built.series)[1])
        with reported("fleet", clock) as info:
            info.update(phase_fleet(fleet_spec(), classify))
        with reported("matrix", clock) as info:
            info.update(phase_matrix(classify, "chip-smoke-aapaset-ci"))
        with reported("episodes", clock) as info:
            info.update(phase_episodes(classify))

    cache = pathlib.Path(cache_dir)
    entries = len(list(cache.iterdir())) if cache.is_dir() else 0
    print(json.dumps({"phase": "total",
                      "wall_s": time.perf_counter() - t_start,
                      "compile_s": clock.seconds,
                      "compile_cache_entries": entries}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
