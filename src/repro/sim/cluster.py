"""Discrete-time Kubernetes cluster simulator as a jittable lax.scan.

Replaces the paper's SimPy simulator (§IV.B) with the same dynamics:

* 30-second pod startup (start pipeline),
* CPU-based scaling with 1-minute metric aggregation (EMA, tau = 60 s),
* FIFO request queue with a fluid M/D/c-style service model,
* 500 ms SLO; cold start = arrivals when zero pods are ready,
* requests uniform within each trace minute (paper's stated simplification).

Structure: outer `lax.scan` over minutes; inside each minute the 60 one-
second ticks are *control-period blocked*: `controller.decide` runs once
at each block head (the ticks where ``sec % control_interval_sec == 0``)
and the remaining ticks advance pure plant dynamics (pipeline pop, fluid
queue, EMA, limiter cooldown decay) in an unrolled loop that touches the
startup pipeline array only once per block. This is bit-exact with the
retained tick-level reference scan (``simulate_reference``) — which
keeps the seed's decide-every-tick-and-mask SEMANTICS — because the
masked decides were fully discarded and every masked action is an exact
float identity; pinned by the parity suite in tests/test_sim_blocked.py.
(The plant float ops themselves were reordered for speed and
FMA-stability in BOTH paths — div-form response terms, fold-based minute
aggregation, incremental pipe_sum — so absolute outputs drift at the
~1e-6-relative level vs the literal pre-blocking implementation, which
benchmarks/bench_sim.py reconstructs as its measured seed baseline.)
Remainder-block semantics for `control_interval_sec` values that don't
divide 60 (e.g. 7): the last block simply runs the leftover ``60 % ci``
ticks after its head, so the head schedule is identical to the
reference (`sec % ci == 0`).

Two plant-cost levers keep the blocked path hot-loop cheap:

* the minute aggregates fold tick-by-tick in the scan carry (strictly
  left-to-right, shared with the reference path — a post-hoc `jnp.sum`
  over materialized [60] outputs would fuse differently per path and
  break bitwise parity), so per-tick outputs never materialize;
* `SimState.pipe_sum` carries the startup-pipeline total incrementally
  (pop subtracts, scale-up adds, scale-down rescales — the identical
  update sequence in both paths), so plant ticks do O(1) work instead of
  an O(startup_sec) shift + reduction per tick.

This module is the *plant*; the control plane lives in `repro.scaling`:
the Controller/Obs protocol and the cooldown semantics come from
`repro.scaling.api` (re-exported here for back-compat), the policies from
`repro.scaling.policies`, and batched policies-x-workloads evaluation
from `repro.scaling.batch`. `vmap` over workloads gives thousands of
simulated workload-days per minute of wall clock (vs the paper's 7 min
per workload-day).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.obs import trace as obs_trace
from repro.scaling.api import (Controller, LimiterState, Obs,
                               apply_decision, limiter_init)

__all__ = ["Controller", "Obs", "SimConfig", "LanePlant", "SimState",
           "MinuteOut", "advance_plant", "initial_state", "minute_step",
           "minute_step_reference", "simulate",
           "simulate_reference", "make_simulator"]

EPSF = 1e-9


@dataclasses.dataclass(frozen=True)
class SimConfig:
    startup_sec: int = 30          # pod startup time (paper §IV.B)
    control_interval_sec: int = 15 # controller sync period (K8s default)
    # 1000 mCPU per replica (paper §IV.E), ~500 mCPU-seconds per request
    # -> 2 concurrent requests at 100 ms service time = 20 req/s. Chosen so
    # median functions need 1-3 replicas and peaks exercise scaling.
    rps_per_replica: float = 20.0
    service_sec: float = 0.1       # per-request service time
    slo_sec: float = 0.5           # SLO threshold (paper: 500 ms)
    max_replicas: float = 100.0
    initial_replicas: float = 2.0
    metric_tau_sec: float = 60.0   # 1-minute metric aggregation
    history_len: int = 60          # minutes of rate history kept for ctrl
    resp_cap_sec: float = 600.0    # cap reported response times (metrics)


class LanePlant(NamedTuple):
    """The plant parameters that may differ from lane to lane: each a
    float32 array that broadcasts against the lane state (a scalar for
    one lane, [W] for a batch whose state is [W] or [P, W]). Where none
    is given, every path reads `SimConfig`'s fields of the same names,
    Python floats that compile as constants, as they always did."""
    rps_per_replica: Any     # requests per second one ready replica serves
    service_sec: Any         # per-request service time
    slo_sec: Any             # response-time SLO threshold


class SimState(NamedTuple):
    ready: jax.Array         # f32 ready replicas
    pipeline: jax.Array      # [startup_sec] replicas starting (FIFO)
    pipe_sum: jax.Array      # f32 running total of `pipeline` (see module
    #                          docstring: updated incrementally, clamped
    #                          at 0, so plant ticks never reduce the array)
    queue: jax.Array         # f32 queued requests
    wait_sum: jax.Array      # f32 total request-seconds waited by the queue
    util_ema: jax.Array
    lim: LimiterState        # scale-down cooldown / direction tracking
    rate_history: jax.Array  # [history_len] per-minute arrival counts
    ctrl_state: Any


class MinuteOut(NamedTuple):
    served: jax.Array
    violated: jax.Array
    cold_starts: jax.Array
    replica_seconds: jax.Array
    queue_end: jax.Array
    resp_sum: jax.Array      # served-weighted response-time sum
    resp_max: jax.Array
    ups: jax.Array
    downs: jax.Array
    oscillations: jax.Array
    util_mean: jax.Array
    ready_mean: jax.Array


def _flow_tick(cfg: SimConfig, ready, queue, wait_sum, util_ema, arrivals,
               plant: LanePlant | None = None):
    """The queue/response/EMA dynamics of one 1-second tick, after the
    startup-pipeline pop: shared by the control tick, the plant-only
    tick and the reference tick. Capacity, service time and SLO are the
    lane's (`plant`), else `cfg`'s."""
    lane = cfg if plant is None else plant
    # serve FIFO queue (fluid model with queue-age tracking)
    throughput = ready * lane.rps_per_replica         # req/s
    work = queue + arrivals
    served = jnp.minimum(work, throughput)            # dt = 1 s
    new_queue = work - served
    # the standing queue ages 1 s; fresh arrivals have ~0 accumulated wait
    wait_aged = wait_sum + queue
    mean_age = wait_aged / jnp.maximum(work, EPSF)
    # served requests carry their accumulated wait; remaining queue keeps
    # a proportional share (uniform-age fluid approximation)
    wait_sum = wait_aged * new_queue / jnp.maximum(work, EPSF)
    # response = congestion-inflated service time (M/D/1-style 1/(1-u):
    # running hot costs latency) + accumulated wait + residual drain time
    util = served / jnp.maximum(throughput, EPSF)
    # every resp term is a division result (service/capped-headroom is the
    # M/D/1-style congestion inflation, capped at 20x service time): a
    # product feeding an add here would be an FMA-contraction candidate,
    # which LLVM applies per compiled program — the blocked and reference
    # paths compile to different programs, and a contracted-vs-plain resp
    # would break their bitwise parity (div-fed adds cannot contract)
    resp = (lane.service_sec / jnp.maximum(1.0 - util, 0.05)
            + mean_age
            + (0.5 * new_queue) / jnp.maximum(throughput, EPSF))
    resp = jnp.minimum(resp, cfg.resp_cap_sec)
    resp = jnp.where(served > 0, resp, 0.0)
    violated = jnp.where(resp > lane.slo_sec, served, 0.0)
    cold = jnp.where(ready < 0.5, arrivals, 0.0)      # zero ready pods
    # metrics (util is both the congestion input and the EMA input);
    # div-fed add for the same FMA-stability reason as resp
    util_ema = util_ema + (util - util_ema) / cfg.metric_tau_sec
    return new_queue, wait_sum, util_ema, served, violated, cold, resp, util


def _pop_pipeline(ready, pipeline, pipe_sum):
    """Pods finishing startup: pop slot 0, shift, keep the incremental
    pipeline total non-negative. Shape-agnostic: works on one lane
    (pipeline [S]) or a batch of lanes (pipeline [..., S])."""
    popped = pipeline[..., 0]
    ready = ready + popped
    pipeline = jnp.concatenate(
        [pipeline[..., 1:],
         jnp.zeros(pipeline.shape[:-1] + (1,), jnp.float32)], axis=-1)
    pipe_sum = jnp.maximum(pipe_sum - popped, 0.0)
    return ready, pipeline, pipe_sum


def _apply_scaling(ready, pipeline, pipe_sum, act):
    """Turn a ScaleAction into pipeline/ready updates: starts enter the
    pipeline tail; removals cancel starting pods first (proportional
    rescale), then ready pods. Shape-agnostic like `_pop_pipeline`."""
    # a static-slot add written as a concatenate, not `.at[..., -1].add`:
    # the same float op, without a scatter
    pipeline = jnp.concatenate(
        [pipeline[..., :-1], pipeline[..., -1:] + act.add[..., None]],
        axis=-1)
    pipe_sum = pipe_sum + act.add
    n_start = pipe_sum
    from_pipe = jnp.minimum(act.remove, n_start)
    factor = 1.0 - from_pipe / jnp.maximum(n_start, EPSF)
    pipeline = pipeline * factor[..., None]
    pipe_sum = pipe_sum * factor
    ready = jnp.maximum(ready - (act.remove - from_pipe), 0.0)
    return ready, pipeline, pipe_sum


def _ctrl_tick(cfg: SimConfig, controller: Controller, state: SimState,
               arrivals: jax.Array, minute_idx: jax.Array, do_ctrl,
               telemetry: bool = False, head_sec=0.0,
               plant: LanePlant | None = None):
    """One 1-second step with a controller decision. `do_ctrl` is the
    Python literal True on block heads (the blocked path — the masking
    folds away) or a traced mask (the reference path, which evaluates
    `decide` on every tick and discards the off-interval results).
    `telemetry` (static) additionally returns a DecisionRecord of this
    decision — the True branch only ADDS read-only ops, so the False
    path compiles to exactly the pre-telemetry program."""
    # 1. pods finishing startup
    ready, pipeline, pipe_sum = _pop_pipeline(
        state.ready, state.pipeline, state.pipe_sum)

    # 2./3. queue + metrics
    (queue, wait_sum, util_ema, served, violated, cold, resp,
     util) = _flow_tick(cfg, ready, state.queue, state.wait_sum,
                        state.util_ema, arrivals, plant)

    # 4. control every control_interval_sec
    total = ready + pipe_sum
    obs = Obs(ready_total=total, ready=ready, util_ema=util_ema,
              queue=queue, rate_rps=arrivals,
              rate_history=state.rate_history, minute_idx=minute_idx,
              plant=plant)
    ctrl_state, desired, cool_req = controller.decide(state.ctrl_state, obs)
    if do_ctrl is not True:
        ctrl_state = jax.tree.map(
            lambda new, old: jnp.where(do_ctrl, new, old),
            ctrl_state, state.ctrl_state)
    desired_raw = desired
    desired = jnp.clip(desired, 0.0, cfg.max_replicas)

    lim, act = apply_decision(state.lim, total, desired, cool_req,
                              jnp.bool_(True) if do_ctrl is True else
                              do_ctrl, dt=1.0)
    ready_at_decision = ready
    ready, pipeline, pipe_sum = _apply_scaling(ready, pipeline, pipe_sum,
                                               act)

    new_state = SimState(ready=ready, pipeline=pipeline, pipe_sum=pipe_sum,
                         queue=queue, wait_sum=wait_sum, util_ema=util_ema,
                         lim=lim, rate_history=state.rate_history,
                         ctrl_state=ctrl_state)
    out = (served, violated, cold, ready + pipe_sum, resp,
           util, act.scale_up.astype(jnp.float32),
           act.scale_down.astype(jnp.float32), act.oscillation, ready)
    if not telemetry:
        return new_state, out
    exp = (controller.explain(state.ctrl_state, obs)
           if getattr(controller, "explain", None) is not None
           else obs_trace.explain_nan())
    rec = obs_trace.record(
        cfg, minute_idx=minute_idx, sec=head_sec, ready=ready_at_decision,
        total=total, queue=queue, util_ema=util_ema, rate_rps=arrivals,
        exp=exp, desired_raw=desired_raw, desired=desired,
        cooldown_req=cool_req, cooldown_before=state.lim.cooldown, act=act)
    return new_state, out, rec


# ------------------------------------------------- minute accumulation ----
#: Per-minute aggregates folded tick-by-tick in the scan carry (strictly
#: left-to-right over the 60 ticks) instead of reduced over materialized
#: [60] outputs — the blocked and reference paths share this fold, which
#: is what makes them bitwise identical: a post-hoc `jnp.sum` would fuse
#: differently over the two paths' output layouts.
def _resp_weight(resp, served):
    """`resp * served`, routed through a select so the accumulating add
    cannot FMA-contract with the product (contraction decisions differ
    between the blocked and reference compiled programs and would break
    their bitwise parity; a select operand is not a fusable product).
    Bit-identical to the bare product: resp is already 0 when served is."""
    return jnp.where(served > 0, resp * served, 0.0)


def _acc_init():
    z = jnp.float32(0.0)
    return (z,) * 11


def _acc_fold(acc, out):
    """Fold a control tick's 10-tuple (ups/downs/osc included)."""
    (served, violated, cold, total, resp, util, ups, downs, osc,
     ready) = out
    return (acc[0] + served, acc[1] + violated, acc[2] + cold,
            acc[3] + total, acc[4] + _resp_weight(resp, served),
            jnp.maximum(acc[5], resp), acc[6] + ups, acc[7] + downs,
            acc[8] + osc, acc[9] + util, acc[10] + ready)


def _acc_fold_plant(acc, served, violated, cold, total, resp, util, ready):
    """Fold a plant-only tick: ups/downs/oscillations are exactly 0.0 on
    non-control ticks, so skipping those adds is bit-exact."""
    return (acc[0] + served, acc[1] + violated, acc[2] + cold,
            acc[3] + total, acc[4] + _resp_weight(resp, served),
            jnp.maximum(acc[5], resp), acc[6], acc[7], acc[8],
            acc[9] + util, acc[10] + ready)


def _minute_out(acc, state: SimState) -> MinuteOut:
    return MinuteOut(
        served=acc[0], violated=acc[1], cold_starts=acc[2],
        replica_seconds=acc[3], queue_end=state.queue, resp_sum=acc[4],
        resp_max=acc[5], ups=acc[6], downs=acc[7], oscillations=acc[8],
        util_mean=acc[9] / 60.0, ready_mean=acc[10] / 60.0)


# --------------------------------------------------- plant-block advance ----
#: Unroll plant blocks up to this many ticks (covers control intervals
#: through ~17 s, in particular the 15 s default); longer blocks scan
#: (see the advance_plant docstring).
_UNROLL_MAX_TICKS = 16


def advance_plant(cfg: SimConfig, ready, pipeline, pipe_sum, queue,
                  wait_sum, util_ema, cooldown, acc, arrivals,
                  n_ticks: int, plant: LanePlant | None = None):
    """`n_ticks` decision-free plant ticks with the minute accumulator
    folded along, on one lane or any batch of lanes (shape-agnostic like
    `_pop_pipeline`; the fused P x W batch in ``repro.scaling.batch``
    calls this on [L] fields). `plant` is the lanes' own parameters
    (`_flow_tick`). Returns (updated 7-field tuple, acc).

    Short blocks (the default 15 s control interval): an unrolled loop
    that reads `pipeline[..., k]` by static index and materializes the
    shifted pipeline array ONCE at block end — bit-identical to per-tick
    shifting, since the popped values and the incremental `pipe_sum`
    updates are the same floats; the n per-tick max(c-1, 0) cooldown
    decays likewise collapse to one exact step (nothing reads the
    limiter inside a block; c-1 is exact in the f32 range cooldowns live
    in, and both forms clamp to 0). Long blocks fall back to a per-tick
    lax.scan (same floats again; unrolling 40+ tick bodies was observed
    to perturb LLVM's scheduling of the resp math enough to cost
    last-ulp parity with the reference — and the decide savings already
    dominate at such long control intervals)."""
    S = pipeline.shape[-1]
    if n_ticks > _UNROLL_MAX_TICKS:
        def body(carry, _):
            ready, pipeline, pipe_sum, queue, wait_sum, util_ema, a = carry
            ready, pipeline, pipe_sum = _pop_pipeline(ready, pipeline,
                                                      pipe_sum)
            (queue, wait_sum, util_ema, served, violated, cold, resp,
             util) = _flow_tick(cfg, ready, queue, wait_sum, util_ema,
                                arrivals, plant)
            a = _acc_fold_plant(a, served, violated, cold,
                                ready + pipe_sum, resp, util, ready)
            return (ready, pipeline, pipe_sum, queue, wait_sum, util_ema,
                    a), None
        carry0 = (ready, pipeline, pipe_sum, queue, wait_sum, util_ema,
                  acc)
        (ready, pipeline, pipe_sum, queue, wait_sum, util_ema,
         acc), _ = jax.lax.scan(body, carry0, None, length=n_ticks)
    else:
        pipe0 = pipeline
        for k in range(n_ticks):
            if k < S:
                popped = pipe0[..., k]
                ready = ready + popped
                # the shift-based form pops 0.0 once the pipeline has
                # fully drained (k >= S); max(ps - 0, 0) == ps for
                # ps >= 0, so the skip is exact
                pipe_sum = jnp.maximum(pipe_sum - popped, 0.0)
            (queue, wait_sum, util_ema, served, violated, cold, resp,
             util) = _flow_tick(cfg, ready, queue, wait_sum, util_ema,
                                arrivals, plant)
            acc = _acc_fold_plant(acc, served, violated, cold,
                                  ready + pipe_sum, resp, util, ready)
        if n_ticks < S:
            pipeline = jnp.concatenate(
                [pipe0[..., n_ticks:],
                 jnp.zeros(pipe0.shape[:-1] + (n_ticks,), jnp.float32)],
                axis=-1)
        else:
            pipeline = jnp.zeros_like(pipe0)
    cooldown = jnp.maximum(cooldown - float(n_ticks), 0.0)
    return (ready, pipeline, pipe_sum, queue, wait_sum, util_ema,
            cooldown), acc


def _plant_block(cfg: SimConfig, state: SimState, acc,
                 arrivals: jax.Array, n_ticks: int,
                 plant: LanePlant | None = None):
    """`n_ticks` plant-only ticks folded into the minute accumulator
    (`advance_plant` on the SimState fields)."""
    (ready, pipeline, pipe_sum, queue, wait_sum, util_ema,
     cool), acc = advance_plant(
        cfg, state.ready, state.pipeline, state.pipe_sum, state.queue,
        state.wait_sum, state.util_ema, state.lim.cooldown, acc,
        arrivals, n_ticks, plant)
    state = state._replace(
        ready=ready, pipeline=pipeline, pipe_sum=pipe_sum,
        queue=queue, wait_sum=wait_sum, util_ema=util_ema,
        lim=LimiterState(cooldown=cool, last_dir=state.lim.last_dir))
    return state, acc


def _block(cfg: SimConfig, controller: Controller, state: SimState, acc,
           arrivals, minute_idx, n_ticks: int, telemetry: bool = False,
           head_sec=0.0, plant: LanePlant | None = None):
    """One control period: decide at the head tick, then `n_ticks - 1`
    plant-only ticks, all folded into the minute accumulator."""
    if telemetry:
        state, head, rec = _ctrl_tick(cfg, controller, state, arrivals,
                                      minute_idx, True, telemetry=True,
                                      head_sec=head_sec, plant=plant)
        acc = _acc_fold(acc, head)
        if n_ticks > 1:
            state, acc = _plant_block(cfg, state, acc, arrivals,
                                      n_ticks - 1, plant)
        return state, acc, rec
    state, head = _ctrl_tick(cfg, controller, state, arrivals, minute_idx,
                             True, plant=plant)
    acc = _acc_fold(acc, head)
    if n_ticks == 1:
        return state, acc
    return _plant_block(cfg, state, acc, arrivals, n_ticks - 1, plant)


def _minute_blocked(cfg: SimConfig, controller: Controller, carry,
                    rate_this_min: jax.Array, telemetry: bool = False,
                    plant: LanePlant | None = None):
    """One minute = ceil(60/ci) control-period blocks + the minute-
    boundary controller hook. `decide` runs exactly once per block.
    `plant` is the lane's own parameters (`LanePlant`), else `cfg`'s.

    With `telemetry` (static flag) the per-minute output becomes
    ``(MinuteOut, ControlTrace)`` where the trace's decisions stack the
    minute's H block-head DecisionRecords (H = #blocks, see
    ``repro.obs.trace.head_schedule``); the default path is untouched
    and compiles to the identical program."""
    state, minute_idx = carry
    arrivals_per_sec = rate_this_min / 60.0
    ci = max(min(int(cfg.control_interval_sec), 60), 1)
    n_full = 60 // ci                  # full-length blocks
    tail = 60 - n_full * ci            # remainder block (0 if ci | 60)

    acc = _acc_init()

    if telemetry:
        recs = []

        def block_body(carry, head_sec):
            st, a = carry
            st, a, rec = _block(cfg, controller, st, a, arrivals_per_sec,
                                minute_idx, ci, telemetry=True,
                                head_sec=head_sec, plant=plant)
            return (st, a), rec

        if n_full == 1:
            (state, acc), rec = block_body((state, acc), jnp.float32(0.0))
            recs.append(jax.tree.map(lambda x: x[None], rec))
        elif n_full:
            (state, acc), rec = jax.lax.scan(
                block_body, (state, acc),
                jnp.arange(n_full, dtype=jnp.float32) * ci)
            recs.append(rec)
        if tail:
            state, acc, rec = _block(cfg, controller, state, acc,
                                     arrivals_per_sec, minute_idx, tail,
                                     telemetry=True,
                                     head_sec=jnp.float32(n_full * ci),
                                     plant=plant)
            recs.append(jax.tree.map(lambda x: x[None], rec))
        decisions = (recs[0] if len(recs) == 1 else jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *recs))  # [H, ...]
        carry2, m = _finish_minute(cfg, controller, state, minute_idx,
                                   rate_this_min, acc)
        mt = obs_trace.MinuteTrace(
            rate=jnp.broadcast_to(rate_this_min, m.served.shape),
            served=m.served, violated=m.violated, queue_end=m.queue_end,
            ready_mean=m.ready_mean)
        return carry2, (m, obs_trace.ControlTrace(decisions=decisions,
                                                  minutes=mt))

    def block_body(carry, _):
        st, a = carry
        return _block(cfg, controller, st, a, arrivals_per_sec,
                      minute_idx, ci, plant=plant), None

    if n_full == 1:      # a length-1 scan only obscures the block body
        state, acc = _block(cfg, controller, state, acc, arrivals_per_sec,
                            minute_idx, ci, plant=plant)
    elif n_full:
        (state, acc), _ = jax.lax.scan(block_body, (state, acc), None,
                                       length=n_full)
    if tail:
        state, acc = _block(cfg, controller, state, acc, arrivals_per_sec,
                            minute_idx, tail, plant=plant)
    return _finish_minute(cfg, controller, state, minute_idx,
                          rate_this_min, acc)


def _finish_minute(cfg, controller, state, minute_idx, rate_this_min, acc):
    """Turn the tick-folded accumulator into MinuteOut and run the minute
    hook — shared verbatim by the blocked and reference paths so their
    aggregates stay bitwise identical."""
    m = _minute_out(acc, state)

    # minute boundary: push this minute's arrivals into history, run hook
    hist = jnp.concatenate(
        [state.rate_history[1:], rate_this_min[None]])
    ctrl_state = controller.on_minute(state.ctrl_state, hist,
                                      minute_idx + 1)
    state = state._replace(rate_history=hist, ctrl_state=ctrl_state)
    return (state, minute_idx + 1), m


# ----------------------------------------------------- reference path ----
def _minute_reference(cfg: SimConfig, controller: Controller, carry,
                      rate_this_min: jax.Array,
                      plant: LanePlant | None = None):
    """One minute = 60 ticks (decide evaluated on EVERY tick and masked
    by `do_ctrl` — the historical semantics the blocked scan is pinned
    bit-exact against) + the minute hook."""
    state, minute_idx = carry
    arrivals_per_sec = rate_this_min / 60.0

    def tick_body(carry, sec):
        st, a = carry
        do_ctrl = (sec % cfg.control_interval_sec) == 0
        st, out = _ctrl_tick(cfg, controller, st, arrivals_per_sec,
                             minute_idx, do_ctrl, plant=plant)
        return (st, _acc_fold(a, out)), None

    (state, acc), _ = jax.lax.scan(tick_body, (state, _acc_init()),
                                   jnp.arange(60, dtype=jnp.int32))
    return _finish_minute(cfg, controller, state, minute_idx,
                          rate_this_min, acc)


def initial_state(controller: Controller,
                  cfg: SimConfig = SimConfig()) -> SimState:
    """The t=0 plant state every simulation path starts from (the scan in
    `simulate` and the fused metrics scan in `repro.evals.metrics`)."""
    return SimState(
        ready=jnp.float32(cfg.initial_replicas),
        pipeline=jnp.zeros((cfg.startup_sec,), jnp.float32),
        pipe_sum=jnp.float32(0.0),
        queue=jnp.float32(0.0),
        wait_sum=jnp.float32(0.0),
        util_ema=jnp.float32(0.5),
        lim=limiter_init(),
        rate_history=jnp.zeros((cfg.history_len,), jnp.float32),
        ctrl_state=controller.init())


#: Public minute-granularity step: carry=(SimState, minute_idx) -> per-
#: minute MinuteOut scalars. `repro.evals.metrics` scans this directly to
#: accumulate metrics in-carry without materializing [M] outputs. This is
#: the control-period-blocked fast path; `minute_step_reference` keeps
#: the historical decide-every-tick semantics for parity pins.
minute_step = _minute_blocked
minute_step_reference = _minute_reference


def simulate(rates_per_min: jax.Array, controller: Controller,
             cfg: SimConfig = SimConfig(), *,
             telemetry: bool = False,
             plant: LanePlant | None = None) -> MinuteOut:
    """Simulate one workload. rates_per_min [M] -> MinuteOut of [M] arrays.

    Control-period-blocked: `decide` runs once per control interval
    (bit-exact with `simulate_reference`, which evaluates it every tick).

    `telemetry=True` (static) additionally captures the in-scan decision
    trace and returns ``(MinuteOut, ControlTrace)`` with decisions
    leaves [M, H] (H block heads per minute) and minutes leaves [M];
    the default path compiles to the identical pre-telemetry program.

    `plant` gives the lane its own capacity, service time and SLO
    (`LanePlant` of scalars).
    """
    (state, _), out = jax.lax.scan(
        partial(_minute_blocked, cfg, controller, telemetry=telemetry,
                plant=plant),
        (initial_state(controller, cfg), jnp.int32(0)),
        rates_per_min.astype(jnp.float32))
    return out


def simulate_reference(rates_per_min: jax.Array, controller: Controller,
                       cfg: SimConfig = SimConfig(), *,
                       plant: LanePlant | None = None) -> MinuteOut:
    """The retained seed-semantics scan (decide evaluated on all 60 ticks
    per minute, masked off-interval). Slow; exists as the parity oracle
    for `simulate` and the blocked-vs-seed benchmark baseline. `plant`
    gives the lane its own parameters, as in `simulate`."""
    (state, _), out = jax.lax.scan(
        partial(_minute_reference, cfg, controller, plant=plant),
        (initial_state(controller, cfg), jnp.int32(0)),
        rates_per_min.astype(jnp.float32))
    return out


def make_simulator(controller: Controller, cfg: SimConfig = SimConfig(), *,
                   w_chunk: int | None = None, donate: bool = False,
                   telemetry: bool = False):
    """jit(vmap(simulate)): rates [W, M] (and optionally a `LanePlant`
    of [W] arrays, each lane's own parameters) -> MinuteOut of [W, M]
    arrays.

    Fleet knobs (mirroring `repro.scaling.batch.make_batch_simulator`):
    `w_chunk` scans over chunks of the workload axis inside the one
    dispatch so live plant state is [w_chunk] however large W grows
    (chunks are independent episodes; requires W % w_chunk == 0);
    `donate` donates the rates buffer to the call, so a fleet-sized
    input tensor never double-buffers against the outputs. `telemetry`
    returns ``(MinuteOut [W, M], ControlTrace)`` with decisions leaves
    [W, M, H] and minutes leaves [W, M]."""
    fn = jax.vmap(lambda r, pl: simulate(r, controller, cfg,
                                         telemetry=telemetry, plant=pl))

    def run(rates, plant: LanePlant | None = None):
        if plant is not None:
            plant = LanePlant(*(jnp.asarray(a, jnp.float32)
                                for a in plant))
        W, M = rates.shape
        if w_chunk is None or w_chunk >= W:
            return fn(rates, plant)
        if W % w_chunk:
            raise ValueError(f"w_chunk {w_chunk} must divide W {W}")
        chunked = rates.reshape(W // w_chunk, w_chunk, M)
        plant = jax.tree.map(lambda a: a.reshape(W // w_chunk, w_chunk),
                             plant)
        _, out = jax.lax.scan(lambda c, xs: (c, fn(*xs)), 0,
                              (chunked, plant))
        return jax.tree.map(lambda a: a.reshape((W,) + a.shape[2:]), out)

    return jax.jit(run, donate_argnums=(0,) if donate else ())
