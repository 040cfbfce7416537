"""The comparison that decides ``correct``: what a cell's timed path
produced against the plain reference, as named numbers, each judged
against the limit the cell file gives it (``"limits"``).

Two shapes of answer occur:

* pooled metric accumulators (the fleet entries): per policy the sums
  of the minute aggregates over every lane, and the served-weighted
  response histogram. ``field_gap`` is the largest relative gap of a
  sum, ``hist_gap`` the largest share of histogram mass that lies in
  another bin than the reference's;
* episode metrics per cell and per workload (the matrix entry), judged
  field by field with a per-field floor under the denominator:
  ``cell_gap`` over the pooled cells, ``lane_gap`` the 90th percentile
  over (policy, workload) of each workload's largest field gap, and
  ``served_gap`` the largest gap of a workload's served requests (a
  count that a flipped scaling decision barely moves, so that one
  workload's answer altered shows).
"""
from __future__ import annotations

import types

import numpy as np

ACCUM_FIELDS = ("served", "violated", "cold", "replica_sec", "resp_sum",
                "util_sum", "over_cnt", "ups", "downs", "osc", "minutes")
# the denominator floor of each episode metric: rates in [0, 1] at 1e-2,
# milliseconds at 1 ms, counts and minutes at 1
METRIC_FLOOR = {"slo_violation_rate": 1e-2, "cold_start_rate": 1e-2,
                "mean_response_ms": 1.0, "p95_response_ms": 1.0,
                "p99_response_ms": 1.0, "replica_minutes": 1.0,
                "avg_cpu_util": 1e-2, "overprovision_rate": 1e-2,
                "scaling_actions": 1.0, "oscillations": 1.0,
                "mean_action_interval_min": 1.0, "total_requests": 1.0}


def pooled_reference(ref, policies, rates_w: np.ndarray):
    """The answer of a fleet entry as the reference `ref` gives it: per
    policy the accumulators summed over all lanes of rates [W, M], as
    [P] arrays (hist [P, bins]) under MetricAccum's field names."""
    per = []
    for p in policies:
        acc = ref.lanes(p, rates_w, per_lane_hist=False)
        per.append({k: (v if k == "hist" else v.sum())
                    for k, v in acc.items()})
    return types.SimpleNamespace(**{k: np.stack([a[k] for a in per])
                                    for k in per[0]})


def pooled_gaps(got, want) -> dict:
    """got, want: pooled accumulators of [P] leaves (hist [P, bins])."""
    field = 0.0
    for f in ACCUM_FIELDS:
        g = np.asarray(getattr(got, f), np.float64)
        w = np.asarray(getattr(want, f), np.float64)
        field = max(field, float(np.max(np.abs(g - w)
                                        / np.maximum(np.abs(w), 1.0))))
    g = np.asarray(got.hist, np.float64)
    w = np.asarray(want.hist, np.float64)
    hist = float(np.max(np.abs(g - w).sum(-1)
                        / np.maximum(w.sum(-1), 1.0)))
    return {"field_gap": field, "hist_gap": hist}


def metric_gap(got, want, field: str) -> np.ndarray:
    g = np.asarray(got, np.float64)
    return np.abs(g - want) / np.maximum(np.abs(want), METRIC_FLOOR[field])


def episode_gaps(got, want) -> dict:
    """got, want: (pooled, per-workload) episode metrics with leaves
    [S, Z, F, P] / [S, Z, F, P, W] under EpisodeMetrics' field names."""
    cell, lanes = 0.0, None
    for f in METRIC_FLOOR:
        cell = max(cell, float(metric_gap(getattr(got[0], f),
                                          np.asarray(getattr(want[0], f)),
                                          f).max()))
        g = metric_gap(getattr(got[1], f), np.asarray(getattr(want[1], f)),
                       f)
        lanes = g if lanes is None else np.maximum(lanes, g)
    served = metric_gap(got[1].total_requests,
                        np.asarray(want[1].total_requests),
                        "total_requests")
    return {"cell_gap": cell,
            "lane_gap": float(np.quantile(lanes.reshape(-1), 0.9)),
            "served_gap": float(served.max())}


def judge(numbers: dict, limits: dict) -> bool:
    """True when every number is finite and at or under its limit."""
    return all(np.isfinite(v) and v <= limits[k] for k, v in numbers.items())


def worst(many: list[dict]) -> dict:
    """Per name, the largest reading over a list of readings."""
    return {k: max(m[k] for m in many) for k in many[0]}
