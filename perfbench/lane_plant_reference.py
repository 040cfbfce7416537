"""Plain reference of a fleet whose functions each have their own plant:
capacity, service time and SLO per lane. Nothing here imports
``repro``.

The equations are ``reference.py``'s, unchanged: its plant ticks,
controllers, features, GBDT walk, Holt-Winters, metric fold and
finalization. Where ``reference.make_lanes`` reads the configuration's
``rps_per_replica``, ``service_sec`` and ``slo_sec`` (the fluid queue's
capacity and service time, the SLO test, and the controllers' capacity
terms), this module hands it each lane's own values as arrays: the
lanes' plant goes into the compiled program as an input beside the
rates, in the program's dtype (float32 the reference, bfloat16 the
control), so one program serves every block of lanes.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from perfbench import reference

PLANT_KEYS = ("rps_per_replica", "service_sec", "slo_sec")


def lane_plant(exec_sec: np.ndarray, law: dict) -> dict:
    """Each lane's plant from its average execution time and the
    configuration's ``lane_plant`` law: {key: float32 array}."""
    svc = np.asarray(exec_sec, np.float32)
    return {"rps_per_replica": np.float32(law["concurrency"]) / svc,
            "service_sec": svc,
            "slo_sec": np.float32(law["slo_over_service"]) * svc}


def make_lanes(policy: str, cfg: dict, clf, n: int, minutes: int,
               dtype=jnp.float32):
    """jit: (rates [n, minutes], rps [n], svc [n], slo [n]) -> per-minute
    aggregates {field: [minutes, n]}, ``reference.make_lanes`` with the
    lanes' own plant in place of the configuration's scalars."""
    dt = jnp.dtype(dtype)

    def run(rates, *values):
        plant = {**cfg["plant"], **{k: v.astype(dt) for k, v in
                                    zip(PLANT_KEYS, values)}}
        return reference.make_lanes(policy, {**cfg, "plant": plant}, clf,
                                    n, minutes, dtype)(rates)

    return jax.jit(run)


_PROGRAMS: dict = {}


class LanePlantReference(reference.Reference):
    """``reference.Reference`` over W lanes that each carry their own
    plant, `plant` {key: [W]} (`lane_plant`): blocked and spread over
    devices in the same way, and answering ``lanes`` alike."""

    def __init__(self, cfg: dict, clf, plant: dict, **kw):
        super().__init__(cfg, clf, **kw)
        self.plant = plant

    def lanes(self, policy: str, rates: np.ndarray, *,
              per_lane_hist: bool) -> dict:
        """`fold` of `policy` over rates [W, M], lane w with the plant's
        values at w."""
        W, M = rates.shape
        block = min(self.block, W)
        key = self._key + (policy, block, M)
        if key not in _PROGRAMS:
            _PROGRAMS[key] = make_lanes(policy, self.cfg, self.clf, block,
                                        M, self.dtype)
        pending = []
        for i, lo in enumerate(range(0, W, block)):
            r = np.asarray(rates[lo:lo + block], np.float32)
            pl = [np.asarray(self.plant[k][lo:lo + block], np.float32)
                  for k in PLANT_KEYS]
            live = r.shape[0]
            if live < block:      # padding lanes: no traffic, unit plant
                r = np.concatenate([r, np.zeros((block - live, M),
                                                np.float32)])
                pl = [np.concatenate([a, np.ones(block - live, np.float32)])
                      for a in pl]
            dev = self.devices[i % len(self.devices)]
            pending.append((live, _PROGRAMS[key](
                *(jax.device_put(a, dev) for a in [r, *pl]))))
        parts = [reference.fold({k: v[:, :live] for k, v in out.items()},
                                self.edges, per_lane_hist=per_lane_hist)
                 for live, out in pending]
        acc = {k: np.concatenate([pt[k] for pt in parts])
               for k in parts[0] if k != "hist"}
        acc["hist"] = (np.concatenate([pt["hist"] for pt in parts])
                       if per_lane_hist else sum(pt["hist"] for pt in parts))
        return acc
