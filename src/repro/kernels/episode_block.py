"""Pallas TPU kernel: whole-episode fused plant + controller advance.

A kernel that advanced only the decision-free ticks of one control
period would return to XLA at every block head for ``controller.decide``
— an M-minute episode would pay M x ceil(60/ci) kernel-boundary round
trips. This kernel fuses the entire episode:

* grid = (lane tiles, minutes); the minute axis is sequential per tile,
  so the plant lanes, the startup pipeline, the rate history ring and
  every controller-state leaf live in VMEM **scratch that persists
  across grid steps** — the whole episode advances without touching HBM
  except for the streams below;
* the rate trace streams in as (tile, 128-minute) blocks and the 12
  per-minute aggregates stream out the same way (BlockSpec index maps
  give the automatic double-buffered DMA pipeline); each grid step
  reads and writes its own minute's column under a lane mask;
* at each control-period head the controller update runs *inside* the
  kernel: ``controller.decide`` vmapped over the lane tile (hpa / kpa /
  predictive are a handful of vector ops; AAPA's archetype strategy
  table is a select chain, and its reclassification descends the GBDT
  node tables — see kernels/gbdt_tables.py), with the cooldown /
  limiter state carried in the plant scratch columns.

Controllers are arbitrary closures over trained arrays (Table III,
forecaster seasonals, GBDT node tables), and Pallas kernels cannot
capture array constants — so the whole one-minute step is traced once
with ``jax.make_jaxpr`` and its captured constants are hoisted into
explicit kernel inputs that ride VMEM as full blocks shared by every
grid step (``jax.closure_convert`` is no help here: it hoists traced
values and deliberately leaves concrete arrays in the closure). Any
registry controller works unmodified.

The tick math is ``repro.sim.cluster``'s own shape-agnostic helpers
(`_pop_pipeline`, `_flow_tick`, `_apply_scaling`, `advance_plant`) and
the shared `apply_decision` limiter — the identical contraction-stable
float ops as the blocked scan, in the identical order, with the minute
accumulator folded tick-by-tick left-to-right. The CPU blocked scan
(``cluster.simulate``) is therefore the dispatch oracle this kernel is
pinned against: tests/test_kernel_smoke.py (deterministic, tier-1, all
five registry policies incl. AAPA-with-GBDT) and
tests/test_kernel_properties.py (random shapes, non-multiple-of-tile
lane counts). Compiled-program parity is ulp-tight, not bitwise — the
two paths are different XLA programs, so FMA contraction may differ
(see the `_flow_tick` stability note for why the drift stays ~1e-6).

On a TPU the body must lower through Mosaic, which takes elementwise
math, selects and static slices, but no gather, scatter, sort or FFT.
hpa and kpa qualify and declare it (`Controller.tpu_kernel`, compiled
for a v5e in tests/test_tpu_compile.py). predictive (Holt-Winters
seasonal slot by index), aapa and hybrid (sorted and rFFT features,
GBDT table gathers) do not, so the auto rule sends them to the XLA
scan; interpret mode runs every policy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.scaling.api import (Controller, LimiterState, Obs,
                               apply_decision)
from repro.sim.cluster import (MinuteOut, SimConfig, _acc_fold, _acc_init,
                               _apply_scaling, _flow_tick, _pop_pipeline,
                               advance_plant)

#: plant scratch column order (the limiter direction rides along because
#: the decide fused here is what reads/writes it)
PLANT_COLS = ("ready", "queue", "wait_sum", "util_ema", "cooldown",
              "pipe_sum", "last_dir")

#: minutes per streamed rate/output block: one full 128-lane vreg row
COL_BLOCK = 128


def _make_minute_body(controller: Controller, cfg: SimConfig, tile_b: int,
                      init_leaves, blocks):
    """One minute for one lane tile as a pure function of the VMEM
    carry — the unit `jax.closure_convert` hoists the controller's
    closed-over arrays out of. `m == 0` selects the initial state
    (cluster.initial_state semantics), so episode start needs no
    separate init pass over the scratch."""
    decide_v = jax.vmap(controller.decide,
                        in_axes=(0, Obs(0, 0, 0, 0, 0, 0, None)))
    on_minute_v = jax.vmap(controller.on_minute, in_axes=(0, 0, None))
    treedef = jax.tree_util.tree_structure(controller.init())

    def minute_body(plant, pipe, hist, leaves, rate, m):
        first = m == 0
        z = jnp.zeros((tile_b,), jnp.float32)
        init_plant = jnp.stack(
            [jnp.full((tile_b,), float(cfg.initial_replicas), jnp.float32),
             z, z, jnp.full((tile_b,), 0.5, jnp.float32), z, z, z], axis=1)
        plant = jnp.where(first, init_plant, plant)
        pipe = jnp.where(first, 0.0, pipe)
        hist = jnp.where(first, 0.0, hist)
        leaves = tuple(
            jnp.where(first, jnp.broadcast_to(il, l.shape).astype(l.dtype),
                      l) for il, l in zip(init_leaves, leaves))

        arr = rate / 60.0
        ready, queue, wait_sum, util_ema, cool, pipe_sum, last_dir = (
            plant[:, k] for k in range(7))
        pipeline = pipe
        ctrl = jax.tree_util.tree_unflatten(treedef, leaves)
        acc = _acc_init()

        for n_ticks in blocks:
            # block head: decide once — the blocked scan's _ctrl_tick
            ready, pipeline, pipe_sum = _pop_pipeline(ready, pipeline,
                                                      pipe_sum)
            (queue, wait_sum, util_ema, served, violated, cold, resp,
             util) = _flow_tick(cfg, ready, queue, wait_sum, util_ema,
                                arr)
            total = ready + pipe_sum
            obs = Obs(ready_total=total, ready=ready, util_ema=util_ema,
                      queue=queue, rate_rps=arr, rate_history=hist,
                      minute_idx=m)
            ctrl, desired, cool_req = decide_v(ctrl, obs)
            desired = jnp.clip(jnp.asarray(desired, jnp.float32), 0.0,
                               cfg.max_replicas)
            cool_req = jnp.broadcast_to(
                jnp.asarray(cool_req, jnp.float32), desired.shape)
            lim, act = apply_decision(
                LimiterState(cooldown=cool, last_dir=last_dir), total,
                desired, cool_req, jnp.bool_(True), dt=1.0)
            cool, last_dir = lim.cooldown, lim.last_dir
            ready, pipeline, pipe_sum = _apply_scaling(
                ready, pipeline, pipe_sum, act)
            acc = _acc_fold(acc, (served, violated, cold,
                                  ready + pipe_sum, resp, util,
                                  act.scale_up.astype(jnp.float32),
                                  act.scale_down.astype(jnp.float32),
                                  act.oscillation, ready))
            # the rest of the block is pure plant dynamics
            if n_ticks > 1:
                (ready, pipeline, pipe_sum, queue, wait_sum, util_ema,
                 cool), acc = advance_plant(
                    cfg, ready, pipeline, pipe_sum, queue, wait_sum,
                    util_ema, cool, acc, arr, n_ticks - 1)

        # minute boundary: history push + hook (cluster._finish_minute)
        hist = jnp.concatenate([hist[:, 1:], rate[:, None]], axis=1)
        ctrl = on_minute_v(ctrl, hist, m + 1)

        plant = jnp.stack([ready, queue, wait_sum, util_ema, cool,
                           pipe_sum, last_dir], axis=1)
        leaves_out = tuple(
            o.astype(l.dtype) for o, l in
            zip(jax.tree_util.tree_leaves(ctrl), leaves))
        outs = (acc[0], acc[1], acc[2], acc[3], queue, acc[4], acc[5],
                acc[6], acc[7], acc[8], acc[9] / 60.0, acc[10] / 60.0)
        return plant, pipeline, hist, leaves_out, outs

    return minute_body


def _hoist(fun, example_args):
    """Trace `fun` once over `example_args` (avals) and return
    ``(call, consts)`` where `call(args, consts)` evaluates the traced
    jaxpr with the captured array constants passed explicitly — the
    closure conversion Pallas needs (`jax.closure_convert` keeps
    concrete arrays in the closure, which pallas_call rejects)."""
    flat_ex, in_tree = jax.tree_util.tree_flatten(tuple(example_args))
    out_tree_box = []

    def flat_fun(*flat_args):
        args = jax.tree_util.tree_unflatten(in_tree, flat_args)
        flat_out, out_tree = jax.tree_util.tree_flatten(fun(*args))
        out_tree_box.append(out_tree)
        return flat_out

    closed = jax.make_jaxpr(flat_fun)(*flat_ex)
    out_tree = out_tree_box[0]

    def call(args, consts):
        flat_args, _ = jax.tree_util.tree_flatten(tuple(args))
        out_flat = jax.core.eval_jaxpr(closed.jaxpr, list(consts),
                                       *flat_args)
        return jax.tree_util.tree_unflatten(out_tree, out_flat)

    return call, closed.consts


def _episode_kernel(rate_ref, *refs, minute_conv, const_shapes,
                    n_leaves):
    """One grid step = one minute for one lane tile. refs order: hoisted
    closure constants, 12 MinuteOut outputs, then scratch (plant
    (TILE_B, 7) in PLANT_COLS order, pipeline (TILE_B, S), history ring
    (TILE_B, H), one buffer per controller-state leaf).

    The rate input and the outputs move in (TILE_B, COL_BLOCK) blocks:
    minute m is column m % COL_BLOCK of block m // COL_BLOCK, read and
    written with a lane mask. The TPU compiler accepts neither a
    one-column block nor a dynamic one-column lane slice, and the
    output block stays resident until its 128 minutes are written."""
    n_consts = len(const_shapes)
    const_refs = refs[:n_consts]
    out_refs = refs[n_consts:n_consts + 12]
    plant_ref, pipe_ref, hist_ref = refs[n_consts + 12:n_consts + 15]
    ctrl_refs = refs[n_consts + 15:]
    m = pl.program_id(1)

    consts = [r[:].reshape(s) for r, s in zip(const_refs, const_shapes)]
    leaves = tuple(r[:] for r in ctrl_refs)
    col = (jax.lax.broadcasted_iota(jnp.int32, rate_ref.shape, 1)
           == m % COL_BLOCK)
    rate = jnp.sum(jnp.where(col, rate_ref[...], 0.0), axis=1)
    plant, pipe, hist, leaves, outs = minute_conv(
        (plant_ref[:], pipe_ref[:], hist_ref[:], leaves, rate, m), consts)

    plant_ref[:] = plant
    pipe_ref[:] = pipe
    hist_ref[:] = hist
    for r, leaf in zip(ctrl_refs, leaves):
        r[:] = leaf
    for r, v in zip(out_refs, outs):
        r[...] = jnp.where(col, v[:, None], r[...])


def episode_minutes(controller: Controller, cfg: SimConfig,
                    rates: jax.Array, *, tile_b: int = 8,
                    interpret: bool = True) -> MinuteOut:
    """Run whole episodes on-chip: rates [B, M] -> MinuteOut of [B, M].

    Lane b reproduces ``cluster.simulate(rates[b], controller, cfg)`` to
    compiled-program (ulp) tolerance. B pads to a multiple of `tile_b`
    (padding lanes simulate a zero-rate workload and are sliced off)."""
    rates = jnp.asarray(rates, jnp.float32)
    B, M = rates.shape
    S = int(cfg.startup_sec)
    H = int(cfg.history_len)
    ci = max(min(int(cfg.control_interval_sec), 60), 1)
    n_full = 60 // ci
    tail = 60 - n_full * ci
    blocks = tuple([ci] * n_full + ([tail] if tail else []))

    init_leaves, _ = jax.tree_util.tree_flatten(controller.init())
    init_leaves = [jnp.asarray(leaf) for leaf in init_leaves]

    n_tiles = max((B + tile_b - 1) // tile_b, 1)
    pad_b = n_tiles * tile_b
    pad_m = -(-M // COL_BLOCK) * COL_BLOCK
    rp = jnp.zeros((pad_b, pad_m), jnp.float32).at[:B, :M].set(rates)

    # hoist every array the controller closes over (Table III, GBDT node
    # tables, forecaster seasonals, init buffers) into explicit inputs
    minute_body = _make_minute_body(controller, cfg, tile_b, init_leaves,
                                    blocks)
    lv = lambda leaf: jax.ShapeDtypeStruct((tile_b,) + leaf.shape,  # noqa: E731
                                           leaf.dtype)
    examples = (jax.ShapeDtypeStruct((tile_b, 7), jnp.float32),
                jax.ShapeDtypeStruct((tile_b, S), jnp.float32),
                jax.ShapeDtypeStruct((tile_b, H), jnp.float32),
                tuple(lv(leaf) for leaf in init_leaves),
                jax.ShapeDtypeStruct((tile_b,), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.int32))
    minute_conv, consts = _hoist(minute_body, examples)
    const_shapes = tuple(jnp.shape(c) for c in consts)
    # every const becomes a leading-1 "tile" broadcast to all grid steps
    const_in = [jnp.reshape(c, (1,) + (jnp.shape(c) or (1,)))
                for c in consts]
    const_specs = [
        pl.BlockSpec(a.shape, functools.partial(
            lambda nd, i, m: (0,) * nd, a.ndim)) for a in const_in]

    col = pl.BlockSpec((tile_b, COL_BLOCK),
                       lambda i, m: (i, m // COL_BLOCK))
    scratch = [pltpu.VMEM((tile_b, 7), jnp.float32),
               pltpu.VMEM((tile_b, S), jnp.float32),
               pltpu.VMEM((tile_b, H), jnp.float32)]
    scratch += [pltpu.VMEM((tile_b,) + leaf.shape, leaf.dtype)
                for leaf in init_leaves]

    outs = pl.pallas_call(
        functools.partial(_episode_kernel, minute_conv=minute_conv,
                          const_shapes=const_shapes,
                          n_leaves=len(init_leaves)),
        grid=(n_tiles, M),
        in_specs=[col] + const_specs,
        out_specs=[col] * 12,
        out_shape=[jax.ShapeDtypeStruct((pad_b, pad_m), jnp.float32)] * 12,
        scratch_shapes=scratch,
        interpret=interpret,
    )(rp, *const_in)
    return MinuteOut(*(o[:B, :M] for o in outs))
