"""Simulation-plant throughput (the perf trajectory for the ROADMAP's
"fast as the hardware allows" north star).

Three measurements, all in simulated workload-minutes per wall-second:

* `sim_blocked`  — the control-period-blocked scan vs the SEED tick-level
  scan (decide evaluated on all 60 ticks/minute, per-tick pipeline
  shift + reduction) for the AAPA policy and the HPA baseline. The seed
  implementation is reconstructed inline below so the baseline stays
  measurable after the refactor; `simulate_reference` (tick-level
  decides on the optimized plant) isolates the blocking win alone.
* `sim_batch`    — the O(P) per-controller-lane batch vs the seed's
  stacked O(P^2) design (every lane evaluates all P decides) at P = 1..5.
* `sim_workloads`— blocked-scan scaling in the workload axis.

`python -m benchmarks.run sim --json .` writes the records to
BENCH_sim.json (stable schema) so perf regressions diff across PRs.
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks import common
from repro.scaling import batch, registry
from repro.scaling.api import Obs, apply_decision
from repro.sim.cluster import (SimConfig, initial_state, simulate,
                               simulate_reference)

EPSF = 1e-9


# ------------------------------------------------ seed implementation ----
# The pre-blocking simulator exactly as shipped by PR 4: decide evaluated
# and masked on every one-second tick, pipeline shifted and re-reduced per
# tick, per-tick outputs materialized and jnp.sum'd per minute.
def _seed_tick(cfg, controller, state, arrivals, sec_in_min, minute_idx):
    ready = state.ready + state.pipeline[0]
    pipeline = jnp.concatenate(
        [state.pipeline[1:], jnp.zeros((1,), jnp.float32)])

    throughput = ready * cfg.rps_per_replica
    work = state.queue + arrivals
    served = jnp.minimum(work, throughput)
    queue = work - served
    wait_aged = state.wait_sum + state.queue
    mean_age = wait_aged / jnp.maximum(work, EPSF)
    wait_sum = wait_aged * queue / jnp.maximum(work, EPSF)
    util_now = served / jnp.maximum(throughput, EPSF)
    congest = 1.0 / jnp.maximum(1.0 - util_now, 0.05)
    resp = (cfg.service_sec * congest + mean_age
            + 0.5 * queue / jnp.maximum(throughput, EPSF))
    resp = jnp.minimum(resp, cfg.resp_cap_sec)
    resp = jnp.where(served > 0, resp, 0.0)
    violated = served * (resp > cfg.slo_sec)
    cold = arrivals * (ready < 0.5)

    util_inst = served / jnp.maximum(throughput, EPSF)
    util_ema = state.util_ema + (1.0 / cfg.metric_tau_sec) * (
        util_inst - state.util_ema)

    total = ready + jnp.sum(pipeline)
    do_ctrl = (sec_in_min % cfg.control_interval_sec) == 0
    obs = Obs(ready_total=total, ready=ready, util_ema=util_ema,
              queue=queue, rate_rps=arrivals,
              rate_history=state.rate_history, minute_idx=minute_idx)
    ctrl_state_new, desired, cool_req = controller.decide(
        state.ctrl_state, obs)
    ctrl_state = jax.tree.map(
        lambda new, old: jnp.where(do_ctrl, new, old),
        ctrl_state_new, state.ctrl_state)
    desired = jnp.clip(desired, 0.0, cfg.max_replicas)

    lim, act = apply_decision(state.lim, total, desired, cool_req,
                              do_ctrl, dt=1.0)
    pipeline = pipeline.at[-1].add(act.add)
    n_start = jnp.sum(pipeline)
    from_pipe = jnp.minimum(act.remove, n_start)
    pipeline = pipeline * (1.0 - from_pipe / jnp.maximum(n_start, EPSF))
    ready = jnp.maximum(ready - (act.remove - from_pipe), 0.0)

    new_state = state._replace(ready=ready, pipeline=pipeline, queue=queue,
                               wait_sum=wait_sum, util_ema=util_ema,
                               lim=lim, ctrl_state=ctrl_state)
    out = (served, violated, cold, ready + jnp.sum(pipeline), resp,
           util_inst, act.scale_up.astype(jnp.float32),
           act.scale_down.astype(jnp.float32), act.oscillation, ready)
    return new_state, out


def _seed_minute(cfg, controller, carry, rate_this_min):
    from repro.sim.cluster import MinuteOut
    state, minute_idx = carry
    arrivals = rate_this_min / 60.0

    def body(st, sec):
        return _seed_tick(cfg, controller, st, arrivals, sec, minute_idx)

    state, outs = jax.lax.scan(body, state, jnp.arange(60, dtype=jnp.int32))
    (served, violated, cold, total_reps, resp, util, ups, downs, osc,
     ready) = outs
    m = MinuteOut(
        served=jnp.sum(served), violated=jnp.sum(violated),
        cold_starts=jnp.sum(cold), replica_seconds=jnp.sum(total_reps),
        queue_end=state.queue, resp_sum=jnp.sum(resp * served),
        resp_max=jnp.max(resp), ups=jnp.sum(ups), downs=jnp.sum(downs),
        oscillations=jnp.sum(osc), util_mean=jnp.mean(util),
        ready_mean=jnp.mean(ready))
    hist = jnp.concatenate([state.rate_history[1:], rate_this_min[None]])
    ctrl_state = controller.on_minute(state.ctrl_state, hist,
                                      minute_idx + 1)
    state = state._replace(rate_history=hist, ctrl_state=ctrl_state)
    return (state, minute_idx + 1), m


def seed_simulate(rates_per_min, controller, cfg):
    """The seed tick-level scan, full MinuteOut contract (pipe_sum rides
    along untouched)."""
    from functools import partial
    (state, _), out = jax.lax.scan(
        partial(_seed_minute, cfg, controller),
        (initial_state(controller, cfg), jnp.int32(0)),
        rates_per_min.astype(jnp.float32))
    return out


def seed_stacked_batch(controllers, cfg):
    """The seed O(P^2) batch: one Controller carrying every component's
    state; every lane evaluates ALL P decides and selects by index."""
    ctrls = list(controllers)

    def stacked(policy_idx):
        def init():
            return tuple(c.init() for c in ctrls)

        def on_minute(state, hist, minute_idx):
            return tuple(c.on_minute(s, hist, minute_idx)
                         for c, s in zip(ctrls, state))

        def decide(state, obs):
            outs = [c.decide(s, obs) for c, s in zip(ctrls, state)]
            new_state = tuple(o[0] for o in outs)
            desired = jnp.stack(
                [jnp.asarray(o[1], jnp.float32) for o in outs])[policy_idx]
            cool = jnp.stack(
                [jnp.asarray(o[2], jnp.float32) for o in outs])[policy_idx]
            return new_state, desired, cool

        from repro.scaling.api import Controller
        return Controller("stacked", init, on_minute, decide)

    def sim_one(idx, rates):
        return seed_simulate(rates, stacked(idx), cfg)

    over_w = jax.vmap(sim_one, in_axes=(None, 0))
    over_p = jax.vmap(over_w, in_axes=(0, None))
    idxs = jnp.arange(len(ctrls), dtype=jnp.int32)
    return jax.jit(lambda rates: over_p(idxs, rates.astype(jnp.float32)))


# ------------------------------------------------------------- timing ----
def _interleaved(fns: dict, args, iters: int) -> dict:
    """min-of-N wall seconds per fn, interleaved so machine noise hits
    every candidate equally."""
    for f in fns.values():
        jax.block_until_ready(f(args))
    times = {k: [] for k in fns}
    for _ in range(iters):
        for k, f in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(f(args))
            times[k].append(time.perf_counter() - t0)
    return {k: min(v) for k, v in times.items()}


def main(smoke: bool = False):
    cfg = SimConfig()
    rng = np.random.default_rng(0)
    W, M = (2, 60) if smoke else (8, 240)
    iters = 2 if smoke else 8
    rates = jnp.asarray(rng.poisson(1200, (W, M)).astype(np.float32))

    # ---- blocked vs seed vs reference, per policy -----------------------
    n_blocks = -(-60 // cfg.control_interval_sec)
    payload = {"workloads": W, "minutes": M,
               "control_interval_sec": cfg.control_interval_sec,
               "decide_evals_per_min": {"seed": 60, "blocked": n_blocks},
               "policies": {}}
    aapa_speedup = 0.0
    for name in ("aapa", "hpa"):
        ctrl = registry.get_controller(name, cfg)
        # full MinuteOut outputs on every candidate: benching a single
        # field would let XLA dead-code the other metrics and flatter
        # whichever path folds them more cheaply
        t = _interleaved({
            "blocked": jax.jit(jax.vmap(
                lambda r, c=ctrl: simulate(r, c, cfg))),
            "seed": jax.jit(jax.vmap(
                lambda r, c=ctrl: seed_simulate(r, c, cfg))),
            "reference": jax.jit(jax.vmap(
                lambda r, c=ctrl: simulate_reference(r, c, cfg))),
        }, rates, iters)
        mps = {k: W * M / v for k, v in t.items()}
        payload["policies"][name] = {
            "minutes_per_sec": mps,
            "speedup_vs_seed": mps["blocked"] / mps["seed"],
            "speedup_vs_reference": mps["blocked"] / mps["reference"]}
        if name == "aapa":
            aapa_speedup = mps["blocked"] / mps["seed"]
    aapa_mps = payload["policies"]["aapa"]["minutes_per_sec"]["blocked"]
    common.emit("sim_blocked", 1e6 / aapa_mps,
                f"aapa_blocked_speedup={aapa_speedup:.1f}x", payload)

    # ---- O(P) vs O(P^2) batching ---------------------------------------
    names = registry.available()
    bp = {"workloads": W, "minutes": M, "per_p": {}}
    ratio_p5 = 0.0
    for P in ((len(names),) if smoke else (1, 3, len(names))):
        ctrls = [registry.get_controller(n, cfg) for n in names[:P]]
        t = _interleaved({
            "o_p": batch.make_batch_simulator(ctrls, cfg),
            "o_p2_seed": seed_stacked_batch(ctrls, cfg),
        }, rates, iters)
        lane_minutes = P * W * M
        bp["per_p"][P] = {
            "lane_minutes_per_sec_o_p": lane_minutes / t["o_p"],
            "lane_minutes_per_sec_o_p2_seed": lane_minutes / t["o_p2_seed"],
            "speedup": t["o_p2_seed"] / t["o_p"],
            "decide_evals_per_ctrl_step": {"o_p": P, "o_p2_seed": P * P}}
        ratio_p5 = t["o_p2_seed"] / t["o_p"]
    P = max(bp["per_p"])
    common.emit("sim_batch",
                1e6 / bp["per_p"][P]["lane_minutes_per_sec_o_p"],
                f"p{P}_opn_vs_op2={ratio_p5:.1f}x", bp)

    # ---- workload-axis scaling -----------------------------------------
    ctrl = registry.get_controller("aapa", cfg)
    ws = {"minutes": M, "per_w": {}}
    for Wn in ((4,) if smoke else (4, 16, 64)):
        r = jnp.asarray(rng.poisson(1200, (Wn, M)).astype(np.float32))
        f = jax.jit(jax.vmap(lambda x: simulate(x, ctrl, cfg)))
        t = _interleaved({"blocked": f}, r, iters)["blocked"]
        ws["per_w"][Wn] = {"minutes_per_sec": Wn * M / t}
    top = max(ws["per_w"])
    common.emit("sim_workloads",
                1e6 / ws["per_w"][top]["minutes_per_sec"],
                f"w{top}_mps={ws['per_w'][top]['minutes_per_sec']:,.0f}", ws)


if __name__ == "__main__":
    main()
