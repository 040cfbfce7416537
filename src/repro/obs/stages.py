"""Names of the lane core's stages and of the streaming feed's host spans.

A stage is a ``jax.named_scope`` around one part of the lane core's
minute step. It changes only the op-name metadata of the ops traced
inside it, so the compiled program is the same with or without it; a
profiler trace of a TPU run carries that op name on every op (its
``tf_op`` stat), which is how device time is split by stage. Scopes
nest and an op belongs to the innermost stage around it: a forecast
inside a controller's ``decide`` counts as ``lane.forecast``.

The host spans are ``jax.profiler.TraceAnnotation`` names that
``repro.evals.fleet.make_chunk_folder`` opens around each chunk's
transfer and fold call; they cost nothing while no trace is taken.
"""
from __future__ import annotations

import jax

PLANT = "lane.plant"                # plant ticks, limiter, scaling apply
DECIDE = "lane.decide"              # every controller's decide, on_minute
FORECAST = "lane.forecast"          # forecaster update / forecast
RECLASSIFY = "lane.reclassify"      # AAPA features + GBDT + Algorithm 1
METRIC_FOLD = "lane.metric_fold"    # evals.metrics accumulator update
STAGES = (PLANT, DECIDE, FORECAST, RECLASSIFY, METRIC_FOLD)

FLEET_PUT = "fleet.put"             # host-to-device transfer of a chunk
FLEET_FOLD = "fleet.fold"           # the donated fold call on the chunk
HOST_SPANS = (FLEET_PUT, FLEET_FOLD)


def stage(name: str):
    """``jax.named_scope(name)`` for one of `STAGES`; any other name
    raises, so a stage cannot be misspelt into a scope nothing reads."""
    if name not in STAGES:
        raise ValueError(f"unknown lane stage {name!r}; stages: {STAGES}")
    return jax.named_scope(name)
