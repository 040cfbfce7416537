"""Backend-agnostic autoscaling control-plane protocol.

One Controller API serves every plant that can produce an `Obs`: the
jittable cluster simulator (`repro.sim.cluster`, lax.scan over ticks) and
the Python-loop serving engine (`repro.serve.engine` via
`repro.scaling.adapter`). A controller is three pure functions:

    init()                               -> ctrl_state
    on_minute(ctrl_state, rate_history, minute_idx) -> ctrl_state
    decide(ctrl_state, obs) -> (ctrl_state, desired_replicas, cooldown_sec)

All functions must be jittable: the simulator traces them inside nested
scans, the serving adapter calls the very same closures eagerly. Policies
therefore never branch in Python on observation values.

Scale-down stabilization (cooldown) is plant-independent semantics and
lives here too: `apply_decision` turns a raw `decide` output into an
add/remove action under the cooldown rules every backend shares —
scale-ups apply immediately, scale-downs only once the cooldown requested
by the *previous* scale-down has expired.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp


class Obs(NamedTuple):
    """What a controller sees at a control step."""
    ready_total: jax.Array   # ready + starting replicas
    ready: jax.Array         # ready replicas only
    util_ema: jax.Array      # 1-min aggregated CPU utilization
    queue: jax.Array         # queued requests
    rate_rps: jax.Array      # current arrival rate (req/s)
    rate_history: jax.Array  # [history_len] per-minute counts (old->new)
    minute_idx: jax.Array    # int32 global minute
    plant: Any = None        # the lane's own sim.cluster.LanePlant, or
    #                          None where every lane has the cfg plant


def lane_plant(obs: Obs, cfg):
    """The observed lane's capacity and service time: ``obs.plant`` (its
    `rps_per_replica`, `service_sec`, `slo_sec`) where each lane has its
    own, else `cfg`, whose fields carry the same names. A controller
    converts a rate into replicas with this, never with `cfg` alone."""
    return cfg if obs.plant is None else obs.plant


class Controller(NamedTuple):
    """Pluggable autoscaling policy (all functions jittable)."""
    name: str
    init: Callable[[], Any]                      # -> ctrl_state
    on_minute: Callable[[Any, jax.Array, jax.Array], Any]
    # (ctrl_state, rate_history, minute_idx) -> ctrl_state
    decide: Callable[[Any, "Obs"], tuple[Any, jax.Array, jax.Array]]
    # (ctrl_state, obs) -> (ctrl_state, desired_replicas, cooldown_sec)
    explain: Callable[[Any, "Obs"], Any] | None = None
    # optional telemetry hook: (PRE-decide ctrl_state, obs) ->
    # repro.obs.trace.ExplainOut — the forecast/confidence/guardrail
    # signals behind the decision `decide` is about to make. Pure and
    # jittable like decide; None means "no signals" (NaN-filled record).


# ----------------------------------------------- cooldown / stabilization ----
class LimiterState(NamedTuple):
    """Scale-down rate-limiter state shared by every backend."""
    cooldown: jax.Array      # seconds until the next scale-down is allowed
    last_dir: jax.Array      # +1 / -1 / 0 last scaling direction


class ScaleAction(NamedTuple):
    add: jax.Array           # replicas to start now
    remove: jax.Array        # replicas to remove now
    scale_up: jax.Array      # bool
    scale_down: jax.Array    # bool
    oscillation: jax.Array   # f32 1.0 when direction flipped


def limiter_init() -> LimiterState:
    return LimiterState(cooldown=jnp.float32(0.0),
                        last_dir=jnp.float32(0.0))


def apply_decision(lim: LimiterState, total: jax.Array,
                   desired: jax.Array, cooldown_req: jax.Array,
                   do_ctrl: jax.Array,
                   dt: float | jax.Array = 1.0
                   ) -> tuple[LimiterState, ScaleAction]:
    """Shared scaling semantics: compare `desired` against the current
    `total` (ready + starting), honor the scale-down cooldown, and track
    direction flips (the oscillation metric). `do_ctrl` masks off-interval
    ticks; `dt` is the wall seconds since the last call."""
    scale_up = do_ctrl & (desired > total + 0.5)
    can_down = lim.cooldown <= 0.0
    scale_down = do_ctrl & (desired < total - 0.5) & can_down

    add = jnp.where(scale_up, desired - total, 0.0)
    remove = jnp.where(scale_down, total - desired, 0.0)

    dir_now = jnp.where(scale_up, 1.0, jnp.where(scale_down, -1.0, 0.0))
    osc = ((dir_now != 0.0) & (lim.last_dir != 0.0)
           & (dir_now != lim.last_dir)).astype(jnp.float32)
    last_dir = jnp.where(dir_now != 0.0, dir_now, lim.last_dir)
    cooldown = jnp.where(scale_down, cooldown_req,
                         jnp.maximum(lim.cooldown - dt, 0.0))

    return (LimiterState(cooldown=cooldown, last_dir=last_dir),
            ScaleAction(add=add, remove=remove, scale_up=scale_up,
                        scale_down=scale_down, oscillation=osc))
