"""Batched multi-policy simulation: policies x workloads in ONE compile.

``make_simulator`` (one policy, vmapped workloads) compiles one scan per
policy — benchmarks that sweep policies pay the XLA compile N times and
dispatch N times. This module folds the policy axis into the same
compiled call:

* `make_batch_simulator(controllers, cfg)` — arbitrary (heterogeneous)
  controllers. ONE control-period-blocked scan advances all P x W plant
  lanes as fused [P, W] vectors, and at each block head every controller
  runs its `decide` exactly once on its own [W] row of the lanes: one
  compile, one dispatch, exactly P (not P^2) decide evaluations per
  control step, with the plant dynamics amortized across the whole
  P x W batch. This replaced a design that carried every controller's
  state in every lane and selected by index — O(P^2) duplicated
  `decide` FLOPs per control step (benchmarks/bench_sim.py keeps that
  shape as its measured baseline). Lane (p, w) reproduces
  `simulate(rates[w], controllers[p])` (pinned to tolerance by
  tests/test_scaling.py — compiled embeddings differ, so last-ulp
  equality is not guaranteed, see tests/test_sim_blocked.py).

  The W axis is the fleet axis: every lane field keeps W as its second
  dimension and is constrained over the ``repro.dist.sharding`` "dp"
  axis each minute, so activating a mesh (`shd.set_mesh`) shards the
  whole episode scan across devices with no code change — each device
  advances its W-shard of every policy's lanes and only the episode-end
  reductions communicate. With no active mesh the constraints are
  no-ops. `w_chunk=` additionally scans over W-chunks of the workload
  axis inside one dispatch so the live plant state is [P, w_chunk]
  regardless of W (the fleet-scale front door over this is
  ``repro.evals.fleet``).

* `make_grid_simulator(name, grid, cfg)` — same-structured controllers
  (one registry family). Hyperparameters split two ways: `stackable`
  keys are stacked into arrays and the *factory itself* is traced with
  per-lane scalars (the policy axis is a true vmap with no per-slot
  duplication); the remaining *static* keys (`horizon_min`,
  `stride_min`, `stabilization_min`, ...) change compiled structure, so
  the grid groups by static values and compiles once per group. This is
  the cheap path for hyperparameter sweeps (target CPU, panic
  thresholds, guardrail fractions...).

* `make_grid_evaluator(name, cfg)` — the same fused grid lanes with
  `repro.evals.metrics` accumulators carried inside the scan: candidates
  come back as pooled EpisodeMetrics + REI without ever materializing a
  [G, W, M] MinuteOut tensor. ``repro.tuning`` drives its searches
  through this.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.dist import sharding as shd
from repro.obs import trace as obs_trace
from repro.obs.stages import DECIDE, PLANT, stage
from repro.scaling import registry
from repro.scaling.api import (Controller, LimiterState, Obs,
                               apply_decision)
from repro.sim.cluster import (LanePlant, MinuteOut, SimConfig,
                               advance_plant, minute_step, simulate,
                               _acc_fold, _acc_init, _apply_scaling,
                               _flow_tick, _pop_pipeline, initial_state)


class BatchState(NamedTuple):
    """Plant state for P x W fused lanes plus the per-controller control
    states (leaves lead with [W]). W is the fleet/sharding axis: every
    lane field keeps it second so `constrain_lanes` can pin it to the
    "dp" mesh axis. `plant` is each workload's own plant parameters
    (a `LanePlant` of [W] arrays, shared across policies, read by the
    plant ticks and by every controller's `decide`), or None where all
    lanes run the `SimConfig` plant."""
    ready: jax.Array         # [P, W]
    pipeline: jax.Array      # [P, W, startup_sec]
    pipe_sum: jax.Array      # [P, W]
    queue: jax.Array         # [P, W]
    wait_sum: jax.Array      # [P, W]
    util_ema: jax.Array      # [P, W]
    cooldown: jax.Array      # [P, W]
    last_dir: jax.Array      # [P, W]
    rate_history: jax.Array  # [W, history_len] (shared across policies)
    ctrl: tuple              # per-controller state pytrees, leaves [W, ...]
    plant: Any = None        # LanePlant of [W] arrays, or None


def batch_initial_state(ctrls, W: int, cfg: SimConfig,
                        plant: LanePlant | None = None) -> BatchState:
    P = len(ctrls)
    st = initial_state(ctrls[0], cfg)

    def rep(x):
        return jnp.broadcast_to(x, (P, W) + jnp.shape(x))

    return BatchState(
        ready=rep(st.ready), pipeline=rep(st.pipeline),
        pipe_sum=rep(st.pipe_sum), queue=rep(st.queue),
        wait_sum=rep(st.wait_sum), util_ema=rep(st.util_ema),
        cooldown=jnp.zeros((P, W), jnp.float32),
        last_dir=jnp.zeros((P, W), jnp.float32),
        rate_history=jnp.zeros((W, cfg.history_len), jnp.float32),
        ctrl=tuple(jax.vmap(lambda _, c=c: c.init())(jnp.arange(W))
                   for c in ctrls),
        plant=plant)


#: vmap axes of a batch's Obs over its W lanes: every lane field (the
#: per-lane plant's too) maps over W, the minute index is shared
OBS_AXES = Obs(0, 0, 0, 0, 0, 0, None, 0)


def constrain_lanes(state: BatchState) -> BatchState:
    """Constrain every lane field's workload axis over the "dp" mesh
    axis (no-op without an active mesh): [P, W, ...] fields shard dim 1,
    rate_history, the per-controller [W, ...] states and the per-lane
    plant's [W] arrays shard dim 0."""
    lanes = {f: shd.constrain(getattr(state, f), (None, "dp"))
             for f in ("ready", "pipeline", "pipe_sum", "queue",
                       "wait_sum", "util_ema", "cooldown", "last_dir")}
    return state._replace(
        rate_history=shd.constrain(state.rate_history, ("dp",)),
        ctrl=jax.tree.map(lambda x: shd.constrain(x, ("dp",)), state.ctrl),
        plant=jax.tree.map(lambda x: shd.constrain(x, ("dp",)),
                           state.plant),
        **lanes)


def _batch_ctrl_tick(cfg, ctrls, state: BatchState, acc, arr_w,
                     minute_idx, telemetry: bool = False, head_sec=0.0):
    """Block-head tick for all lanes: fused plant flow on [P, W], then
    each controller's decide vmapped over ITS [W] row (P decide
    subgraphs total), then the shared scaling semantics back on [P, W].
    The plant pieces are cluster.py's own shape-agnostic helpers, so the
    batched and single-lane dynamics cannot drift apart. `telemetry`
    (static) additionally returns a [P, W] DecisionRecord; the False
    path is op-for-op the pre-telemetry program. The plant pieces run
    under the ``lane.plant`` stage, the decides under ``lane.decide``;
    both read the lanes' own plant (``state.plant``) where there is one."""
    with stage(PLANT):
        ready, pipeline, pipe_sum = _pop_pipeline(
            state.ready, state.pipeline, state.pipe_sum)

        arr_pw = jnp.broadcast_to(arr_w, ready.shape)
        (queue, wait_sum, util_ema, served, violated, cold, resp,
         util) = _flow_tick(cfg, ready, state.queue, state.wait_sum,
                            state.util_ema, arr_pw, state.plant)
        total = ready + pipe_sum

    W = arr_w.shape[0]
    new_ctrl, desired, cool_req, exps = [], [], [], []
    with stage(DECIDE):
        for p, c in enumerate(ctrls):
            obs = Obs(ready_total=total[p], ready=ready[p],
                      util_ema=util_ema[p], queue=queue[p], rate_rps=arr_w,
                      rate_history=state.rate_history,
                      minute_idx=minute_idx, plant=state.plant)
            cs, des, coo = jax.vmap(c.decide, in_axes=(0, OBS_AXES))(
                state.ctrl[p], obs)
            new_ctrl.append(cs)
            desired.append(jnp.asarray(des, jnp.float32))
            cool_req.append(jnp.broadcast_to(
                jnp.asarray(coo, jnp.float32), (W,)))
            if telemetry:
                exps.append(jax.vmap(c.explain, in_axes=(0, OBS_AXES))(
                    state.ctrl[p], obs)
                    if getattr(c, "explain", None) is not None
                    else obs_trace.explain_nan((W,)))
    with stage(PLANT):
        desired_raw = jnp.stack(desired)
        desired = jnp.clip(desired_raw, 0.0, cfg.max_replicas)
        cool_req = jnp.stack(cool_req)

        cooldown_before = state.cooldown
        lim, act = apply_decision(
            LimiterState(cooldown=state.cooldown, last_dir=state.last_dir),
            total, desired, cool_req, jnp.bool_(True), dt=1.0)
        ready_at_decision = ready
        ready, pipeline, pipe_sum = _apply_scaling(ready, pipeline,
                                                   pipe_sum, act)

        state = BatchState(ready=ready, pipeline=pipeline,
                           pipe_sum=pipe_sum, queue=queue,
                           wait_sum=wait_sum, util_ema=util_ema,
                           cooldown=lim.cooldown, last_dir=lim.last_dir,
                           rate_history=state.rate_history,
                           ctrl=tuple(new_ctrl), plant=state.plant)
        acc = _acc_fold(acc, (served, violated, cold, ready + pipe_sum,
                              resp, util, act.scale_up.astype(jnp.float32),
                              act.scale_down.astype(jnp.float32),
                              act.oscillation, ready))
    if not telemetry:
        return state, acc
    exp = jax.tree.map(lambda *xs: jnp.stack(xs), *exps)      # [P, W]
    rec = obs_trace.record(
        cfg, minute_idx=minute_idx, sec=head_sec,
        ready=ready_at_decision, total=total, queue=queue,
        util_ema=util_ema, rate_rps=arr_pw, exp=exp,
        desired_raw=desired_raw, desired=desired, cooldown_req=cool_req,
        cooldown_before=cooldown_before, act=act)
    return state, acc, rec


def _batch_plant_block(cfg, state: BatchState, acc, arr_pw, n_ticks: int):
    """`n_ticks` decision-free ticks for all [P, W] lanes — exactly
    cluster.advance_plant on the batched fields."""
    with stage(PLANT):
        (ready, pipeline, pipe_sum, queue, wait_sum, util_ema,
         cool), acc = advance_plant(
            cfg, state.ready, state.pipeline, state.pipe_sum, state.queue,
            state.wait_sum, state.util_ema, state.cooldown, acc, arr_pw,
            n_ticks, state.plant)
    state = state._replace(
        ready=ready, pipeline=pipeline, pipe_sum=pipe_sum, queue=queue,
        wait_sum=wait_sum, util_ema=util_ema, cooldown=cool)
    return state, acc


def make_batch_minute_step(controllers: Sequence[Controller],
                           cfg: SimConfig = SimConfig(), *,
                           shard: bool = True, telemetry: bool = False,
                           trace_lanes: int | None = None):
    """(BatchState carry, minute_idx, rate_w [W]) stepping function for
    the fused P x W batch: returns per-minute MinuteOut of [P, W]
    arrays. `repro.evals.matrix` scans this directly with its metric
    accumulator in the carry; `make_batch_simulator` wraps it for
    materialized [P, W, M] outputs. `decide` runs exactly once per
    controller per control step (O(P), not O(P^2)). With `shard` (the
    default) every carry field is constrained over the "dp" mesh axis
    once per minute — a no-op without an active mesh.

    With `telemetry` (static) each step returns ``(state, (MinuteOut
    [P, W], ControlTrace))`` — decisions leaves [H, P, K], minutes
    leaves [P, K], where H is the block-head count and K the traced
    lane count: `trace_lanes` bounds capture to K deterministically
    sampled lanes (``repro.obs.trace.sample_lanes``) so fleet-scale
    scans stay O(P * bins) in the carry and O(K) in the trace ys. The
    default path is untouched."""
    ctrls = list(controllers)
    P = len(ctrls)
    ci = max(min(int(cfg.control_interval_sec), 60), 1)
    n_full = 60 // ci
    tail = 60 - n_full * ci

    def step(state: BatchState, minute_idx, rate_w):
        if shard:
            state = constrain_lanes(state)
            rate_w = shd.constrain(rate_w, ("dp",))
        W = rate_w.shape[0]
        with stage(PLANT):
            arr_w = rate_w / 60.0
            arr_pw = jnp.broadcast_to(arr_w, (P, W))
            acc = tuple(jnp.zeros((P, W), jnp.float32)
                        for _ in _acc_init())

        if telemetry:
            return _step_telemetry(state, minute_idx, rate_w, arr_w,
                                   arr_pw, acc, W)

        def block(st, a, n_ticks):
            st, a = _batch_ctrl_tick(cfg, ctrls, st, a, arr_w, minute_idx)
            if n_ticks > 1:
                st, a = _batch_plant_block(cfg, st, a, arr_pw, n_ticks - 1)
            return st, a

        if n_full == 1:
            state, acc = block(state, acc, ci)
        elif n_full:
            def body(carry, _):
                return block(*carry, ci), None
            (state, acc), _ = jax.lax.scan(body, (state, acc), None,
                                           length=n_full)
        if tail:
            state, acc = block(state, acc, tail)

        return _finish(state, minute_idx, rate_w, acc)

    def _step_telemetry(state, minute_idx, rate_w, arr_w, arr_pw, acc, W):
        idx = obs_trace.sample_lanes(W, trace_lanes)   # None keeps all

        def block(st, a, n_ticks, head_sec):
            st, a, rec = _batch_ctrl_tick(cfg, ctrls, st, a, arr_w,
                                          minute_idx, telemetry=True,
                                          head_sec=head_sec)
            if n_ticks > 1:
                st, a = _batch_plant_block(cfg, st, a, arr_pw, n_ticks - 1)
            if idx is not None:
                rec = jax.tree.map(lambda x: x[..., idx], rec)
            return st, a, rec

        recs = []
        if n_full == 1:
            state, acc, rec = block(state, acc, ci, jnp.float32(0.0))
            recs.append(jax.tree.map(lambda x: x[None], rec))
        elif n_full:
            def body(carry, head_sec):
                st, a, rec = block(*carry, ci, head_sec)
                return (st, a), rec
            (state, acc), rec = jax.lax.scan(
                body, (state, acc),
                jnp.arange(n_full, dtype=jnp.float32) * ci)
            recs.append(rec)
        if tail:
            state, acc, rec = block(state, acc, tail,
                                    jnp.float32(n_full * ci))
            recs.append(jax.tree.map(lambda x: x[None], rec))
        decisions = (recs[0] if len(recs) == 1 else jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *recs))  # [H, P, K]

        state, m = _finish(state, minute_idx, rate_w, acc)
        sel = (lambda a: a) if idx is None else (lambda a: a[..., idx])
        mt = obs_trace.MinuteTrace(
            rate=jnp.broadcast_to(sel(rate_w), sel(m.served).shape),
            served=sel(m.served), violated=sel(m.violated),
            queue_end=sel(m.queue_end), ready_mean=sel(m.ready_mean))
        return state, (m, obs_trace.ControlTrace(decisions=decisions,
                                                 minutes=mt))

    def _finish(state, minute_idx, rate_w, acc):
        with stage(PLANT):
            m = MinuteOut(
                served=acc[0], violated=acc[1], cold_starts=acc[2],
                replica_seconds=acc[3], queue_end=state.queue,
                resp_sum=acc[4], resp_max=acc[5], ups=acc[6],
                downs=acc[7], oscillations=acc[8],
                util_mean=acc[9] / 60.0, ready_mean=acc[10] / 60.0)
            hist = jnp.concatenate(
                [state.rate_history[:, 1:], rate_w[:, None]], axis=1)
        with stage(DECIDE):
            ctrl = tuple(
                jax.vmap(c.on_minute, in_axes=(0, 0, None))(
                    s, hist, minute_idx + 1)
                for c, s in zip(ctrls, state.ctrl))
        state = state._replace(rate_history=hist, ctrl=ctrl)
        return state, m

    return step


def make_batch_simulator(controllers: Sequence[Controller],
                         cfg: SimConfig = SimConfig(), *,
                         shard: bool = True, w_chunk: int | None = None,
                         donate: bool = False, telemetry: bool = False,
                         trace_lanes: int | None = None):
    """jit: rates [W, M] -> MinuteOut [P, W, M]. One compile, one
    dispatch: a single blocked scan over fused P x W plant lanes with
    exactly P (not P^2) decide evaluations per control step.

    `w_chunk` scans over chunks of the workload axis inside the same
    dispatch, so the live plant state is [P, w_chunk] however large W
    grows (the chunks are independent episodes; requires
    W % w_chunk == 0). `donate` donates the rates buffer to the call.

    `telemetry` returns ``(MinuteOut [P, W, M], ControlTrace)`` with the
    trace time-major: decisions leaves [M, H, P, K], minutes leaves
    [M, P, K] (K = `trace_lanes` sampled lanes, all W when None);
    incompatible with `w_chunk` — chunked capture is what
    ``repro.evals.fleet`` is for: pass `trace_lanes` on its `FleetSpec`
    to stream sampled-lane traces per chunk.
    """
    if telemetry and w_chunk is not None:
        raise ValueError(
            "telemetry does not compose with w_chunk here; for chunked "
            "capture use repro.evals.fleet with trace_lanes "
            "(FleetSpec(..., trace_lanes=K) samples K lanes per chunk)")
    ctrls = list(controllers)
    step = make_batch_minute_step(ctrls, cfg, shard=shard,
                                  telemetry=telemetry,
                                  trace_lanes=trace_lanes)

    def episode(rates):                       # [Wc, M] -> [P, Wc, M]
        W, M = rates.shape

        def minute(carry, rate_w):
            state, idx = carry
            state, m = step(state, idx, rate_w)
            return (state, idx + 1), m

        (_, _), out = jax.lax.scan(
            minute, (batch_initial_state(ctrls, W, cfg), jnp.int32(0)),
            rates.T)
        if telemetry:
            m, ct = out       # the trace stays time-major ([M, ...])
            return jax.tree.map(lambda a: jnp.moveaxis(a, 0, -1), m), ct
        return jax.tree.map(lambda a: jnp.moveaxis(a, 0, -1), out)

    def run(rates):
        rates = rates.astype(jnp.float32)
        W, M = rates.shape
        if w_chunk is None or w_chunk >= W:
            return episode(rates)
        if W % w_chunk:
            raise ValueError(f"w_chunk {w_chunk} must divide W {W}")
        chunked = rates.reshape(W // w_chunk, w_chunk, M)
        _, out = jax.lax.scan(lambda c, r: (c, episode(r)), 0, chunked)
        # [C, P, Wc, M] -> [P, W, M]
        return jax.tree.map(
            lambda a: jnp.moveaxis(a, 0, 1).reshape(
                a.shape[1], W, a.shape[3]), out)

    return jax.jit(run, donate_argnums=(0,) if donate else ())


def batch_simulate(controllers: Sequence[Controller], rates,
                   cfg: SimConfig = SimConfig()) -> MinuteOut:
    """Convenience wrapper: rates [W, M] -> MinuteOut of [P, W, M]."""
    return make_batch_simulator(controllers, cfg)(jnp.asarray(rates))


def make_forecast_batch_simulator(policies: Sequence[str],
                                  forecasters: Sequence,
                                  cfg: SimConfig = SimConfig(), *,
                                  classify=None, **overrides):
    """Forecasters x policies x workloads in ONE compiled call.

    Every policy must be forecaster-aware (`takes_forecaster` in its
    registry spec: `predictive`, `aapa`, `hybrid`); `forecasters` are
    ``repro.forecast.registry`` names or Forecaster instances. Returns a
    fn rates [W, M] -> MinuteOut [F, P, W, M]; lane (f, p) is bit-for-bit
    the standalone simulation of policy p using forecaster f (pinned by
    tests/test_forecast.py)."""
    aware = [n for n in registry.available()
             if registry.spec(n).takes_forecaster]
    for p in policies:
        if not registry.spec(p).takes_forecaster:
            raise TypeError(f"policy {p!r} takes no forecaster; "
                            f"forecaster-aware policies: {aware}")
    ctrls = [registry.get_controller(p, cfg, classify=classify,
                                     forecaster=f, **overrides)
             for f in forecasters for p in policies]
    sim = make_batch_simulator(ctrls, cfg)
    shape = (len(forecasters), len(policies))

    def run(rates):
        out = sim(jnp.asarray(rates))                 # [F*P, W, M]
        return jax.tree.map(
            lambda a: a.reshape(shape + a.shape[1:]), out)

    return run


def _canon_static(v):
    """Canonical hashable form of a static hyperparameter value: jit
    static-arg cache keys and artifact JSON must agree on it. Ints stay
    ints — factories index/`arange` with keys like `horizon_min`."""
    if isinstance(v, (bool, str)):
        return v
    if isinstance(v, (int, np.integer)):
        return int(v)
    if isinstance(v, (float, np.floating)):
        return float(v)
    return v


def _validate_hyper(sp, keys, what: str) -> None:
    bad = set(keys) - set(sp.defaults)
    if bad:
        raise TypeError(f"policy {sp.name!r} has no hyperparameters "
                        f"{sorted(bad)} ({what}); "
                        f"accepts {sorted(sp.defaults)}")


def grid_split(name: str, grid: Sequence[dict], fixed: dict):
    """Validate a hyperparameter grid and split it into traced
    stackables vs static keys.

    Every grid point must set the same keys, all drawn from the policy's
    accepted hyperparameters (a typo'd key raises the same clean
    TypeError `registry.get_controller` gives, not an opaque factory
    error deep inside vmap tracing). Keys in the family's `stackable`
    tuple are *traced* — stacked into f32 arrays and vmapped as fused
    lanes; everything else is *static* — it changes compiled structure
    (buffer lengths, reclassify cadence), so points are grouped by their
    static values and each group compiles once.

    Returns (spec, traced_keys, groups) with groups an ordered list of
    (static_items, grid_indices) preserving first-appearance order.
    """
    sp = registry.spec(name)
    if not grid:
        raise ValueError("empty hyperparameter grid")
    _validate_hyper(sp, fixed, "fixed kwargs")
    keys = sorted(grid[0])
    _validate_hyper(sp, keys, "grid keys")
    overlap = set(keys) & set(fixed)
    if overlap:
        raise TypeError(f"grid key(s) {sorted(overlap)} for policy "
                        f"{name!r} are also passed as fixed kwargs")
    for g in grid:
        if sorted(g) != keys:
            raise ValueError("every grid point must set the same keys")
    traced = tuple(k for k in keys if k in sp.stackable)
    static = tuple(k for k in keys if k not in sp.stackable)
    groups: dict[tuple, list[int]] = {}
    order: list[tuple] = []
    for i, g in enumerate(grid):
        skey = tuple((k, _canon_static(g[k])) for k in static)
        if skey not in groups:
            groups[skey] = []
            order.append(skey)
        groups[skey].append(i)
    return sp, traced, [(skey, tuple(groups[skey])) for skey in order]


def _grid_factory(sp, cfg, classify, fixed):
    """(traced hyper dict, static hyper dict) -> Controller, with the
    registry defaults + `fixed` underneath — the one place grid lanes
    build controllers, shared by the MinuteOut and metrics paths."""
    def build(hyper, static_kw):
        kw = dict(sp.defaults)
        kw.update(fixed)
        kw.update(static_kw)
        kw.update(hyper)       # traced per-lane scalars
        if sp.needs_classifier:
            return sp.factory(cfg, classify or registry.default_classify,
                              **kw)
        return sp.factory(cfg, **kw)
    return build


def _stack_traced(grid: Sequence[dict], idxs, traced) -> dict:
    return {k: jnp.asarray([float(grid[i][k]) for i in idxs], jnp.float32)
            for k in traced}


def _stitch(parts, order):
    """Concatenate per-group [Gk, ...] pytrees back into grid order."""
    cat = (parts[0] if len(parts) == 1
           else jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *parts))
    perm = np.argsort(np.asarray(order, np.int64), kind="stable")
    if (perm == np.arange(perm.size)).all():
        return cat
    return jax.tree.map(lambda a: a[perm], cat)


def make_grid_simulator(name: str, grid: Sequence[dict],
                        cfg: SimConfig = SimConfig(), *,
                        classify=None, **fixed):
    """One policy family, a grid of hyperparameter points, few compiles.

    `grid` is a list of dicts over the family's accepted hyperparameters;
    every point must set the same keys (`fixed` pins the rest). Stackable
    keys are traced f32 lanes under one vmap; static keys
    (`horizon_min`, `stride_min`, `stabilization_min`, ...) group the
    grid and compile once per static group. Returns a fn
    rates [W, M] -> MinuteOut [len(grid), W, M] (grid order preserved);
    its `_cache_size()` reports the compile count for the one-compile-
    per-static-group pin.
    """
    _, traced, groups = grid_split(name, grid, fixed)
    sp = registry.spec(name)
    build = _grid_factory(sp, cfg, classify, fixed)
    grid = [dict(g) for g in grid]

    def run_group(lane_ids, stacked, rates, static_kw):
        def sim_one(_, hyper, r):
            return simulate(r, build(hyper, dict(static_kw)), cfg)
        over_w = jax.vmap(sim_one, in_axes=(None, None, 0))
        return jax.vmap(over_w, in_axes=(0, 0, None))(
            lane_ids, stacked, rates)

    run_group = jax.jit(run_group, static_argnums=(3,))

    def run(rates):
        rates = jnp.asarray(rates, jnp.float32)
        parts, order = [], []
        for skey, idxs in groups:
            parts.append(run_group(jnp.arange(len(idxs)),
                                   _stack_traced(grid, idxs, traced),
                                   rates, skey))
            order.extend(idxs)
        return _stitch(parts, order)

    run._cache_size = run_group._cache_size
    return run


def make_grid_evaluator(name: str, cfg: SimConfig = SimConfig(), *,
                        classify=None, bins: int | None = None,
                        rei_kw: dict | None = None, **fixed):
    """Fused candidate scoring: grid lanes carry `repro.evals.metrics`
    accumulators *inside* the scan and come back as pooled
    EpisodeMetrics + REI per candidate — a [G, W, M] MinuteOut tensor
    never materializes, so scoring 10^3+ candidates is O(G * bins)
    memory. This is the evaluation core of ``repro.tuning``.

    Returns ``evaluate(grid, rates [W, M]) -> (EpisodeMetrics [G],
    REIBreakdown [G])``. The grid is passed per call (search strategies
    re-propose candidates every round); the compiled group body is
    shared across calls, so a search whose rounds keep candidate counts
    constant compiles once per static group total (`_cache_size()` pins
    it). REI baselines default from the episode shape; `rei_kw`
    overrides (e.g. paper-constant baselines).
    """
    # lazy: repro.evals.matrix imports this module at package init
    from repro.evals import metrics as EM
    from repro.evals import rei as ER
    sp = registry.spec(name)
    _validate_hyper(sp, fixed, "fixed kwargs")
    build = _grid_factory(sp, cfg, classify, fixed)
    bins = EM.DEFAULT_BINS if bins is None else bins
    edges = EM.response_edges(bins, cfg.resp_cap_sec)
    rei_kw = dict(rei_kw or {})

    def eval_group(lane_ids, stacked, rates, static_kw):
        W, _ = rates.shape

        def eval_one(_, hyper):
            ctrl = build(hyper, dict(static_kw))
            st0 = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (W,) + jnp.shape(a)),
                initial_state(ctrl, cfg))
            idx0 = jnp.zeros((W,), jnp.int32)

            def one_lane(s, i, r):
                (s2, i2), m = minute_step(cfg, ctrl, (s, i), r)
                return s2, i2, m

            def body(carry, rate_w):
                st, idx, acc = carry
                st, idx, m = jax.vmap(one_lane)(st, idx, rate_w)
                return (st, idx,
                        EM.accum_update_pooled(acc, m, edges)), None

            (_, _, acc), _ = jax.lax.scan(
                body, (st0, idx0, EM.accum_init(bins)), rates.T)
            return acc

        return jax.vmap(eval_one)(lane_ids, stacked)

    eval_group = jax.jit(eval_group, static_argnums=(3,))

    def evaluate(grid, rates):
        _, traced, groups = grid_split(name, grid, fixed)
        grid = [dict(g) for g in grid]
        rates = jnp.asarray(rates, jnp.float32)
        W, M = rates.shape
        parts, order = [], []
        for skey, idxs in groups:
            parts.append(eval_group(jnp.arange(len(idxs)),
                                    _stack_traced(grid, idxs, traced),
                                    rates, skey))
            order.extend(idxs)
        met = EM.finalize(_stitch(parts, order), edges)
        rb = ER.rei(met.slo_violation_rate, met.replica_minutes,
                    met.scaling_actions,
                    **{"minutes": M, "n_workloads": W, **rei_kw})
        return met, rb

    evaluate._cache_size = eval_group._cache_size
    return evaluate
