"""Fleet-scale evaluation: 10^5-10^6 fused workload lanes per run.

The matrix runner materializes per-workload accumulators ([..., W, bins]
histograms) — fine for a grid cell, fatal for a region. This module is
the fleet front door over the same compiled core
(``matrix._lane_runner``): W-chunked episodes with the workload axis
reduced *inside* the scan (``metrics.accum_update_pooled``), so live
state is [P, w_chunk] plant lanes plus an O(P * bins) accumulator no
matter how large the fleet grows.

Two execution modes, one compiled chunk body:

* ``make_fleet_runner`` — ONE dispatch: rates [C, Wc, M] scanned over
  chunks inside jit, chunk accumulators tree-summed in the carry. The
  W=1e5 decade of BENCH_fleet.json runs this way (acceptance: peak host
  memory < 2x the W=1e4 run, because only the rates tensor grows).
* ``make_chunk_folder`` — streaming: a host function (accum, chunk) ->
  accum that puts each chunk on the device and calls the jitted fold
  with the accumulator donated, driven by a host generator
  (``rate_chunks`` here or ``aapaset.AAPAsetLoader.rate_chunks``). Rates
  never materialize beyond one chunk — this is the 1e6-lane mode. The
  transfer and the fold call are the ``fleet.put`` and ``fleet.fold``
  host spans of a profiler trace (``repro.obs.stages``).

Under an active ``repro.dist.sharding`` mesh the chunk's workload axis
shards over "dp" (each device advances its slice of every policy's
lanes); without a mesh everything is a no-op. ``run_fleet`` wraps either
mode with throughput + peak-RSS accounting and the pooled REI.
"""
from __future__ import annotations

import dataclasses
import resource
import time
from typing import Any, Iterator, NamedTuple, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.dist import sharding as shd
from repro.evals import metrics as EM
from repro.evals import rei as ER
from repro.evals.matrix import _lane_runner
from repro.obs.stages import FLEET_FOLD, FLEET_PUT
from repro.scaling import registry, scenarios
from repro.sim.cluster import LanePlant, SimConfig


@dataclasses.dataclass(frozen=True)
class FleetSpec:
    """One fleet run: P policies x W workloads of one scenario family.

    `n_workloads` is the fleet size W; `w_chunk` lanes are live at a
    time (must divide W). Chunk c's workloads are drawn with a seed
    derived from (seed, c), so the fleet is deterministic and any chunk
    can be regenerated independently — the streaming mode depends on
    exactly that."""
    name: str
    policies: tuple[str, ...]
    forecaster: str = "holt_winters"
    scenario: str = "burst_storm"
    scenario_kw: tuple[tuple[str, Any], ...] = ()
    n_workloads: int = 1024
    w_chunk: int = 256
    minutes: int = 60
    seed: int = 0
    sim: tuple[tuple[str, Any], ...] = ()
    bins: int = EM.DEFAULT_BINS
    #: capture the decision trace for this many deterministically
    #: sampled lanes PER CHUNK (0 = telemetry off). The trace rides the
    #: chunk scan as ys, so the carry stays O(P * bins) at any W.
    trace_lanes: int = 0

    def __post_init__(self):
        if self.n_workloads % self.w_chunk:
            raise ValueError(f"w_chunk {self.w_chunk} must divide "
                             f"n_workloads {self.n_workloads}")

    @property
    def n_chunks(self) -> int:
        return self.n_workloads // self.w_chunk

    def sim_config(self) -> SimConfig:
        return SimConfig(**dict(self.sim))


def spec(name: str, *, policies: Sequence[str], **kw) -> FleetSpec:
    """Normalizing constructor (dict kwargs become sorted tuples)."""
    for key in ("scenario_kw", "sim"):
        if isinstance(kw.get(key), dict):
            kw[key] = tuple(sorted(kw[key].items()))
    return FleetSpec(name=name, policies=tuple(policies), **kw)


def controllers(spec_: FleetSpec, classify=None) -> list:
    cfg = spec_.sim_config()
    out = []
    for p in spec_.policies:
        fkw = ({"forecaster": spec_.forecaster}
               if registry.spec(p).takes_forecaster else {})
        out.append(registry.get_controller(p, cfg, classify=classify,
                                           **fkw))
    return out


def chunk_seed(seed: int, chunk: int) -> int:
    """Derived per-chunk scenario seed, stable across runs/processes."""
    return int(np.random.SeedSequence([seed, chunk]).generate_state(1)[0])


def chunk_rates(spec_: FleetSpec, chunk: int) -> np.ndarray:
    """Chunk `chunk`'s workloads: [w_chunk, minutes] float32."""
    sc = scenarios.get(spec_.scenario, n_workloads=spec_.w_chunk,
                       minutes=spec_.minutes,
                       seed=chunk_seed(spec_.seed, chunk),
                       cfg=spec_.sim_config(), **dict(spec_.scenario_kw))
    return np.asarray(sc.rates, np.float32)


def rate_chunks(spec_: FleetSpec) -> Iterator[np.ndarray]:
    """All C chunks in order — the streaming mode's default feed."""
    for c in range(spec_.n_chunks):
        yield chunk_rates(spec_, c)


def build_rates(spec_: FleetSpec) -> np.ndarray:
    """Materialize the whole fleet [C, w_chunk, minutes] for the
    one-dispatch mode. At W=1e5 x 60 min this is ~24 MB — the rates are
    the ONLY thing that grows with W; accumulators stay O(P * bins)."""
    return np.stack([chunk_rates(spec_, c) for c in range(spec_.n_chunks)])


def _lane_plant(plant: LanePlant | None, axes) -> LanePlant | None:
    """A per-lane plant as float32 arrays constrained like the rates'
    lane axes (None stays None: every lane runs the spec's plant)."""
    if plant is None:
        return None
    return LanePlant(*(shd.constrain(jnp.asarray(a, jnp.float32), axes)
                       for a in plant))


def _pooled_acc0(n_lanes: int, bins: int):
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (n_lanes,) + a.shape),
                        EM.accum_init(bins))


def make_fleet_runner(spec_: FleetSpec, classify=None, *,
                      donate: bool = True):
    """jit: rates [C, Wc, M] -> pooled MetricAccum of [P] leaves, ONE
    dispatch. A lax.scan over chunks runs each [P, Wc] episode with the
    workload axis pooled in-scan, tree-summing chunk accumulators in the
    carry; the rates buffer is donated (it is dead after the scan reads
    it). The chunk's lane axis is constrained over "dp".

    ``run(rates, plant)`` gives every workload its own plant parameters:
    `plant` is a ``LanePlant`` of [C, Wc] arrays, scanned beside the
    rates chunk by chunk and sharded like them.

    With ``spec_.trace_lanes > 0`` the runner returns ``(accum,
    ControlTrace)`` — the trace of K sampled lanes per chunk rides the
    chunk scan as ys (decisions leaves [C, M, H, P, K], minutes
    [C, M, P, K]); the carry is unchanged."""
    cfg = spec_.sim_config()
    ctrls = controllers(spec_, classify)
    edges = EM.response_edges(spec_.bins, cfg.resp_cap_sec)
    telemetry = spec_.trace_lanes > 0
    lanes = _lane_runner(ctrls, cfg, edges, per_workload=False,
                         telemetry=telemetry,
                         trace_lanes=spec_.trace_lanes or None)

    def run(rates, plant: LanePlant | None = None):
        rates = shd.constrain(jnp.asarray(rates, jnp.float32),
                              (None, "dp", None))
        plant = _lane_plant(plant, (None, "dp"))

        def body(acc, xs):
            chunk, plant_c = xs
            if telemetry:
                acc_c, ct = lanes(chunk, plant_c)
                return jax.tree.map(jnp.add, acc, acc_c), ct
            return jax.tree.map(jnp.add, acc, lanes(chunk, plant_c)), None

        acc, ct = jax.lax.scan(body,
                               _pooled_acc0(len(ctrls), spec_.bins),
                               (rates, plant))
        return (acc, ct) if telemetry else acc

    return jax.jit(run, donate_argnums=(0,) if donate else ())


def _jit_fold(spec_: FleetSpec, classify=None):
    """jit with a DONATED accumulator: (MetricAccum [P], rates [Wc, M],
    plant=None) -> MetricAccum [P], the device program of
    `make_chunk_folder`."""
    cfg = spec_.sim_config()
    ctrls = controllers(spec_, classify)
    edges = EM.response_edges(spec_.bins, cfg.resp_cap_sec)
    lanes = _lane_runner(ctrls, cfg, edges, per_workload=False)

    def fold(acc, chunk, plant: LanePlant | None = None):
        chunk = shd.constrain(jnp.asarray(chunk, jnp.float32), ("dp", None))
        return jax.tree.map(jnp.add, acc,
                            lanes(chunk, _lane_plant(plant, ("dp",))))

    return jax.jit(fold, donate_argnums=(0,))


def make_chunk_folder(spec_: FleetSpec, classify=None):
    """(MetricAccum [P], rates [Wc, M], plant=None) -> MetricAccum [P],
    the streaming fold for generator-fed fleets: host memory is one
    chunk of rates + one O(P * bins) accumulator, so W is bounded by
    wall clock, not memory. Each call puts the chunk (and the chunk's
    per-lane plant, a ``LanePlant`` of [Wc] arrays, when given) on the
    device with the lanes' sharding (the ``fleet.put`` host span) and
    calls the jitted fold, which DONATES the accumulator (the
    ``fleet.fold`` span)."""
    jfold = _jit_fold(spec_, classify)

    def folder(acc, chunk, plant: LanePlant | None = None):
        with jax.profiler.TraceAnnotation(FLEET_PUT):
            chunk = jax.device_put(
                chunk, shd.lane_sharding(np.shape(chunk), w_axis=0))
            if plant is not None:
                plant = LanePlant(*(jax.device_put(
                    a, shd.lane_sharding(np.shape(a), w_axis=0))
                    for a in plant))
        with jax.profiler.TraceAnnotation(FLEET_FOLD):
            return jfold(acc, chunk, plant)

    return folder


class FleetResult(NamedTuple):
    spec: FleetSpec
    pooled: EM.EpisodeMetrics    # [P] numpy, pooled over the whole fleet
    rei: ER.REIBreakdown         # [P] numpy
    meta: dict                   # wall_s, lane_minutes_per_sec, rss ...
    trace: Any = None            # ControlTrace (numpy) if trace_lanes > 0


def _peak_rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def run_fleet(spec_: FleetSpec, *, classify=None, stream: bool = False,
              chunks: Iterator[np.ndarray] | None = None,
              warmup: bool = False) -> FleetResult:
    """Evaluate the fleet; returns pooled metrics + REI + throughput.

    `stream=False`: one sharded dispatch over the materialized
    [C, Wc, M] tensor. `stream=True`: python loop over `chunks` (default
    `rate_chunks(spec_)`) through the donated-accumulator fold — pass a
    loader-backed generator (`AAPAsetLoader.rate_chunks`) to run real
    traces instead of synthetic scenarios. `warmup=True` (one-dispatch
    mode) runs the compiled call once before timing, so `wall_s` is the
    steady-state dispatch — the benchmark trajectory uses it; a cold
    call folds XLA compile time into the smallest decades."""
    cfg = spec_.sim_config()
    edges = EM.response_edges(spec_.bins, cfg.resp_cap_sec)
    P = len(spec_.policies)
    telemetry = spec_.trace_lanes > 0
    if telemetry and stream:
        raise ValueError("trace_lanes requires the one-dispatch mode; "
                         "the streaming fold keeps only the donated "
                         "accumulator (set stream=False)")
    t_build = time.perf_counter()
    ct = None
    if stream:
        fold = make_chunk_folder(spec_, classify)
        acc = _pooled_acc0(P, spec_.bins)
        t0 = time.perf_counter()
        n_chunks = 0
        for chunk in (rate_chunks(spec_) if chunks is None else chunks):
            acc = fold(acc, chunk)
            n_chunks += 1
        acc = jax.block_until_ready(acc)
        W = n_chunks * spec_.w_chunk
        dispatches = n_chunks
    else:
        rates = build_rates(spec_)
        run = make_fleet_runner(spec_, classify)
        if warmup:          # np input: each call transfers a fresh copy
            jax.block_until_ready(run(rates))
        t0 = time.perf_counter()
        out = jax.block_until_ready(run(rates))
        acc, ct = out if telemetry else (out, None)
        W, dispatches = spec_.n_workloads, 1
    wall = time.perf_counter() - t0
    pooled = jax.tree.map(np.asarray, EM.finalize(acc, edges))
    rei_b = jax.tree.map(np.asarray, ER.rei(
        pooled.slo_violation_rate, pooled.replica_minutes,
        pooled.scaling_actions, minutes=spec_.minutes, n_workloads=W))
    meta = {
        "workloads": W, "minutes": spec_.minutes, "policies": P,
        "w_chunk": spec_.w_chunk, "dispatches": dispatches,
        "stream": stream, "wall_s": wall, "warm": bool(warmup),
        "build_s": t0 - t_build,
        "lane_minutes_per_sec": P * W * spec_.minutes / max(wall, 1e-9),
        "minutes_per_sec": W * spec_.minutes / max(wall, 1e-9),
        "peak_rss_mb": _peak_rss_mb(),
        "n_devices": jax.device_count(),
        "mesh": (dict(shd.active().mesh.shape)
                 if shd.active() is not None else None)}
    if ct is not None:
        ct = jax.tree.map(np.asarray, ct)
    return FleetResult(spec_, pooled, rei_b, meta, ct)
