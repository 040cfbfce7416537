"""Policies x forecasters x scenarios x seeds in ONE compiled call.

``spec(...)`` names an evaluation matrix (which policies, which
forecasters, which scenarios at which seeds, on which plant);
``make_runner(spec)`` compiles the whole grid into a single jitted
function — one control-period-blocked scan per controller lane (exactly
one `decide` per control step, the same O(P) layout as
``repro.scaling.batch.make_batch_simulator``) fused with the in-scan
metrics of ``repro.evals.metrics`` — per-minute outputs never
materialize, each cell returns EpisodeMetrics directly; and
``run(spec)`` is the front door: content-addressed against
``experiments/evals`` (same hashing scheme as ``aapaset.manifest``), so
re-running an identical spec is a cache hit on the result card.

    from repro.evals import matrix
    run = matrix.run(matrix.spec(
        "sweep", policies=("hpa", "aapa"), forecasters=("holt_winters",),
        scenarios=(("burst_storm", {}), ("idle_wake", {})), seeds=(0, 1)))
    run.result.pooled.slo_violation_rate        # [S, Z, F, P]
    run.card["hash"]                            # names the exact run

Policies that are not forecaster-aware (no `takes_forecaster` in their
registry spec) simply ignore the forecaster axis — lane (f, p) repeats
the same controller for every f, which keeps the result tensor dense.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.dist import sharding as shd
from repro.evals import metrics as EM
from repro.evals import rei as ER
from repro.obs.stages import METRIC_FOLD, stage
from repro.scaling import batch, registry, scenarios
from repro.sim.cluster import SimConfig

SCHEMA_VERSION = 1


@dataclasses.dataclass(frozen=True)
class MatrixSpec:
    """One named evaluation matrix. Every field is part of the content
    key (including `bins`, which changes the reported quantiles)."""
    name: str
    policies: tuple[str, ...]
    forecasters: tuple[str, ...]
    scenarios: tuple[tuple[str, tuple[tuple[str, Any], ...]], ...]
    seeds: tuple[int, ...]
    n_workloads: int
    minutes: int
    sim: tuple[tuple[str, Any], ...] = ()
    bins: int = EM.DEFAULT_BINS

    def sim_config(self) -> SimConfig:
        return SimConfig(**dict(self.sim))

    def content_key(self) -> dict:
        return {"schema": SCHEMA_VERSION, "name": self.name,
                "policies": list(self.policies),
                "forecasters": list(self.forecasters),
                "scenarios": [[n, dict(kw)] for n, kw in self.scenarios],
                "seeds": list(self.seeds),
                "n_workloads": self.n_workloads, "minutes": self.minutes,
                "sim": dict(self.sim), "bins": self.bins}

    @property
    def shape(self) -> tuple[int, int, int, int]:
        return (len(self.scenarios), len(self.seeds),
                len(self.forecasters), len(self.policies))

    def scenario_names(self) -> list[str]:
        return [n if not kw else f"{n}:{dict(kw)}"
                for n, kw in self.scenarios]


def spec(name: str, *, policies: Sequence[str],
         forecasters: Sequence[str] = ("holt_winters",),
         scenarios: Sequence = (("archetype_mix", {}),),
         seeds: Sequence[int] = (0,), n_workloads: int = 8,
         minutes: int = 720, sim: dict | None = None,
         bins: int = EM.DEFAULT_BINS) -> MatrixSpec:
    """Normalizing constructor: scenario entries may be bare names or
    (name, kwargs) pairs; kwargs/sim dicts become sorted tuples so the
    spec is hashable and its content key canonical."""
    norm = []
    for entry in scenarios:
        if isinstance(entry, str):
            entry = (entry, {})
        sc_name, kw = entry
        norm.append((sc_name, tuple(sorted(dict(kw).items()))))
    return MatrixSpec(name=name, policies=tuple(policies),
                      forecasters=tuple(forecasters),
                      scenarios=tuple(norm), seeds=tuple(seeds),
                      n_workloads=int(n_workloads), minutes=int(minutes),
                      sim=tuple(sorted((sim or {}).items())), bins=bins)


def smoke_spec() -> MatrixSpec:
    """The CI tier-1 smoke matrix: 2 policies x 2 scenarios x 1 seed."""
    return spec("ci_smoke", policies=("hpa", "predictive"),
                scenarios=(("burst_storm", {}), ("idle_wake", {})),
                seeds=(0,), n_workloads=2, minutes=120)


class EvalResult(NamedTuple):
    """Structured result pytree of an evaluation matrix."""
    pooled: EM.EpisodeMetrics        # fields [S, Z, F, P]
    per_workload: EM.EpisodeMetrics  # fields [S, Z, F, P, W]
    rei: ER.REIBreakdown             # fields [S, Z, F, P]


class MatrixRun(NamedTuple):
    spec: MatrixSpec
    result: EvalResult               # numpy arrays
    card: dict
    cached: bool


def controllers(spec_: MatrixSpec, classify=None) -> list:
    """The F*P controller lanes, forecaster-major (lane = f * P + p)."""
    cfg = spec_.sim_config()
    ctrls = []
    for f in spec_.forecasters:
        for p in spec_.policies:
            kw = ({"forecaster": f}
                  if registry.spec(p).takes_forecaster else {})
            ctrls.append(registry.get_controller(p, cfg, classify=classify,
                                                 **kw))
    return ctrls


def build_rates(spec_: MatrixSpec) -> np.ndarray:
    """Materialize the scenario x seed workload tensor [S, Z, W, M]."""
    cfg = spec_.sim_config()
    rows = []
    for sc_name, kw in spec_.scenarios:
        per_seed = [scenarios.get(sc_name, n_workloads=spec_.n_workloads,
                                  minutes=spec_.minutes, seed=seed,
                                  cfg=cfg, **dict(kw)).rates
                    for seed in spec_.seeds]
        rows.append(np.stack(per_seed))
    rates = np.stack(rows).astype(np.float32)
    expect = spec_.shape[:2] + (spec_.n_workloads, spec_.minutes)
    if rates.shape != expect:
        raise ValueError(f"scenario tensor is {rates.shape}, expected "
                         f"{expect}; every scenario must honor "
                         "n_workloads/minutes")
    return rates


def _lane_runner(ctrls, cfg, edges, *, per_workload: bool = True,
                 shard: bool = True, telemetry: bool = False,
                 trace_lanes: int | None = None):
    """(rates [W, M], plant=None) -> MetricAccums of [P, W, ...] leaves:
    ONE blocked scan advances all P x W fused plant lanes with exactly
    one `decide` per controller per control step
    (`scaling.batch.make_batch_minute_step`), folding each minute into
    per-lane MetricAccums in the scan carry — the shared core of the
    matrix runner, the ad-hoc controller evaluator, and the fleet runner.
    Memory stays O(bins) per lane. The fold runs under the
    ``lane.metric_fold`` stage (``repro.obs.stages``).
    `plant` (optional) is a ``sim.cluster.LanePlant`` of [W] arrays, each
    workload's own capacity, service time and SLO; without it every lane
    runs the `cfg` plant.

    With ``per_workload=False`` the workload axis reduces *inside* the
    scan (`EM.accum_update_pooled`) and the leaves are [P, ...]: the
    carry is O(P * bins) however large W grows — the fleet-scale mode.
    Under an active mesh the lane state and the per-workload accums are
    constrained over "dp"; the pooled accums are tiny and replicate (the
    cross-shard reduction happens in the scatter/sum ops themselves).

    ``telemetry=True`` rides the in-scan decision trace out as scan ys
    (NOT carry — the O(bins) carry bound holds at any fleet size) and
    returns ``(accums, ControlTrace)``: decisions leaves [M, H, P, K],
    minutes leaves [M, P, K], K = `trace_lanes` sampled lanes."""
    n_lanes = len(ctrls)
    step = batch.make_batch_minute_step(ctrls, cfg, shard=shard,
                                        telemetry=telemetry,
                                        trace_lanes=trace_lanes)
    if per_workload:
        fold = jax.vmap(jax.vmap(lambda a, m: EM.accum_update(a, m,
                                                              edges)))
    else:
        fold = lambda a, m: EM.accum_update_pooled(a, m, edges)  # noqa: E731

    def lanes(rates_w, plant=None):
        W, _ = rates_w.shape
        lead = (n_lanes, W) if per_workload else (n_lanes,)
        acc0 = jax.tree.map(
            lambda a: jnp.broadcast_to(a, lead + a.shape),
            EM.accum_init(edges.shape[0]))

        def body(carry, rate_w):
            st, idx, acc = carry
            if telemetry:
                st, (m, ct) = step(st, idx, rate_w)
            else:
                st, m = step(st, idx, rate_w)
                ct = None
            with stage(METRIC_FOLD):
                acc = fold(acc, m)
                if shard and per_workload:
                    acc = jax.tree.map(
                        lambda a: shd.constrain(a, (None, "dp")), acc)
            return (st, idx + 1, acc), ct

        (_, _, acc), ct = jax.lax.scan(
            body,
            (batch.batch_initial_state(ctrls, W, cfg, plant),
             jnp.int32(0), acc0),
            rates_w.T)
        return (acc, ct) if telemetry else acc
    return lanes


def make_runner(spec_: MatrixSpec, classify=None, *,
                per_workload: bool = True, shard: bool = True,
                donate: bool = False, telemetry: bool = False,
                trace_lanes: int | None = None):
    """jit: rates [S, Z, W, M] -> (pooled EpisodeMetrics [S, Z, F, P],
    per-workload EpisodeMetrics [S, Z, F, P, W]). One compile, one
    dispatch for the whole matrix. Under an active `repro.dist.sharding`
    mesh the workload axis shards over "dp" (constrained on the input
    tensor and on every lane carry inside the scan).

    ``per_workload=False`` streams the workload reduction inside the
    scan (accum memory O(bins) per cell, independent of W) and returns
    ``(pooled, None)`` — the fleet-scale mode. ``donate=True`` donates
    the rates buffer to the call (fleet-sized inputs are not needed
    again after dispatch).

    ``telemetry=True`` also captures the in-scan decision trace (still
    ONE compile — the `_cache_size()==1` pin holds) and returns a
    3-tuple ``(pooled, per_workload, ControlTrace)`` with decisions
    leaves [S, Z, M, H, F, P, K] and minutes leaves [S, Z, M, F, P, K]
    (K = `trace_lanes` sampled workloads, all when None)."""
    cfg = spec_.sim_config()
    ctrls = controllers(spec_, classify)
    edges = EM.response_edges(spec_.bins, cfg.resp_cap_sec)
    _, _, f_axis, p_axis = spec_.shape

    over_seeds = jax.vmap(_lane_runner(ctrls, cfg, edges,
                                       per_workload=per_workload,
                                       shard=shard, telemetry=telemetry,
                                       trace_lanes=trace_lanes))
    over_scenarios = jax.vmap(over_seeds)        # [S, Z, L(, W), ...]

    def split_lanes(a, axis):
        return a.reshape(a.shape[:axis] + (f_axis, p_axis)
                         + a.shape[axis + 1:])

    def run_fn(rates):
        rates = jnp.asarray(rates, jnp.float32)
        if shard:
            rates = shd.constrain(rates, (None, None, "dp", None))
        out = over_scenarios(rates)
        accs, ct = out if telemetry else (out, None)
        accs = jax.tree.map(lambda a: split_lanes(a, 2), accs)
        if telemetry:
            # lane axis L -> (F, P): decisions [S, Z, M, H, L, K],
            # minutes [S, Z, M, L, K]
            ct = ct._replace(
                decisions=jax.tree.map(lambda a: split_lanes(a, 4),
                                       ct.decisions),
                minutes=jax.tree.map(lambda a: split_lanes(a, 3),
                                     ct.minutes))
        if not per_workload:
            pool = EM.finalize(accs, edges)
            return (pool, None, ct) if telemetry else (pool, None)
        per_w = EM.finalize(accs, edges)
        pool = EM.finalize(jax.tree.map(lambda a: a.sum(4), accs), edges)
        return (pool, per_w, ct) if telemetry else (pool, per_w)

    return jax.jit(run_fn, donate_argnums=(0,) if donate else ())


def make_controller_evaluator(ctrls: Sequence,
                              cfg: SimConfig = SimConfig(), *,
                              bins: int = EM.DEFAULT_BINS,
                              per_workload: bool = True,
                              shard: bool = True,
                              telemetry: bool = False,
                              trace_lanes: int | None = None):
    """Reusable jitted single-scenario evaluator for ad-hoc controllers
    (ablation variants, custom bands): rates [W, M] -> (pooled
    EpisodeMetrics [P], per-workload [P, W]). Keep the returned fn when
    sweeping many rate tensors — each call reuses the one compile.

    ``per_workload=False`` never materializes the [P, W, bins] accum
    tensor — the W reduction streams inside the scan and the result is
    ``(pooled [P], None)``. Use it for fleet-sized W (the host-parity
    tests at W >= 1e4 do).

    ``telemetry=True`` appends the in-scan ControlTrace (decisions
    leaves [M, H, P, K], minutes [M, P, K]) as a third element."""
    ctrls = list(ctrls)
    edges = EM.response_edges(bins, cfg.resp_cap_sec)
    lanes = _lane_runner(ctrls, cfg, edges, per_workload=per_workload,
                         shard=shard, telemetry=telemetry,
                         trace_lanes=trace_lanes)

    def run_fn(rates_w):
        out = lanes(rates_w)
        accs, ct = out if telemetry else (out, None)
        if not per_workload:
            pool = EM.finalize(accs, edges)
            return (pool, None, ct) if telemetry else (pool, None)
        pool = EM.finalize(jax.tree.map(lambda a: a.sum(1), accs), edges)
        per_w = EM.finalize(accs, edges)
        return (pool, per_w, ct) if telemetry else (pool, per_w)

    return jax.jit(run_fn)


def evaluate_controllers(ctrls: Sequence, rates,
                         cfg: SimConfig = SimConfig(), *,
                         bins: int = EM.DEFAULT_BINS,
                         per_workload: bool = True):
    """One-shot convenience wrapper over `make_controller_evaluator`."""
    return make_controller_evaluator(ctrls, cfg, bins=bins,
                                     per_workload=per_workload)(
        jnp.asarray(rates, jnp.float32))


def _execute(spec_: MatrixSpec, classify) -> EvalResult:
    pool, per_w = make_runner(spec_, classify)(build_rates(spec_))
    rei_b = ER.rei(pool.slo_violation_rate, pool.replica_minutes,
                   pool.scaling_actions, minutes=spec_.minutes,
                   n_workloads=spec_.n_workloads)
    to_np = lambda t: jax.tree.map(np.asarray, t)  # noqa: E731
    return EvalResult(to_np(pool), to_np(per_w), to_np(rei_b))


def run(spec_: MatrixSpec, *, classify=None, classifier_id: str = "",
        root=None, force: bool = False) -> MatrixRun:
    """The front door: evaluate the matrix, content-addressed.

    `classifier_id` must name the classifier whenever `classify` is
    passed (e.g. `trained.dataset_id`) — the callable itself cannot be
    hashed, so the id is what keys the artifact."""
    from repro.evals import artifacts
    if classify is not None and not classifier_id:
        raise ValueError("pass classifier_id= to content-address a run "
                         "with a custom classifier")
    key = dict(spec_.content_key(),
               classifier=classifier_id or "default_classify")
    root = artifacts.DEFAULT_ROOT if root is None else root
    if not force and artifacts.is_cached(spec_.name, key, root):
        result, card = artifacts.load_result(spec_.name, key, root)
        return MatrixRun(spec_, result, card, True)
    result = _execute(spec_, classify)
    card = artifacts.save_result(spec_, key, result, root, replace=force)
    return MatrixRun(spec_, result, card, False)
