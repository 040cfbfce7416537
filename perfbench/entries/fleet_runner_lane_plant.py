"""Entry ``repro.evals.fleet.make_fleet_runner`` with a per-lane plant:
the whole fleet in one dispatch, as ``fleet_runner`` runs it, with each
workload's own capacity, service time and SLO beside its rates. The
traffic gives each function's average execution time (``ctx.rates`` is
the family's ``FunctionFleet``); the configuration's ``lane_plant`` law
turns it into the plant, which goes in as a ``LanePlant`` of [chunks,
w_chunk] arrays. The runner pools the accumulators inside the scan; the
reference is ``lane_plant_reference``, in the reference's dtype on the
reference's devices.
"""
from __future__ import annotations

import jax

from perfbench import compare, lane_plant_reference
from perfbench.entries.fleet_runner import FleetCheck, fleet_spec


class Driver(FleetCheck):
    module = "jit_run"

    def __init__(self, ctx):
        from repro.evals import fleet
        self.use(ctx)
        spec = fleet_spec(ctx)
        self.policies = spec.policies
        self.run = fleet.make_fleet_runner(spec, ctx.classify)
        C, Wc, M = self.rates.shape
        self.units = 1
        self.iterations = C * M
        self.lane_minutes = len(spec.policies) * C * Wc * M

    def use(self, ctx):
        """Take another seed's traffic of the same shape."""
        # a program without per-lane plants has no LanePlant: it stops
        # here, before anything could run the scalar plant in its place
        from repro.sim.cluster import LanePlant
        self.ctx, self.rates = ctx, ctx.rates.rates
        self.plant = lane_plant_reference.lane_plant(
            ctx.rates.exec_sec, ctx.cfg["lane_plant"])
        self.lanes = LanePlant(*(self.plant[k] for k in
                                 lane_plant_reference.PLANT_KEYS))

    def dispatch(self):
        with self.ctx.span("dispatch"):
            out = self.run(self.rates, self.lanes)
        with self.ctx.span("block"):
            return jax.block_until_ready(out)

    def release(self):
        self.run = None

    def expected(self, ref):
        """The answer as `ref` gives it, from the lane-plant reference in
        `ref`'s dtype on its devices."""
        C, Wc, M = self.rates.shape
        lanes = lane_plant_reference.LanePlantReference(
            ref.cfg, ref.clf,
            {k: v.reshape(C * Wc) for k, v in self.plant.items()},
            dtype=ref.dtype, block=ref.block, devices=ref.devices)
        return compare.pooled_reference(lanes, self.policies,
                                        self.rates.reshape(C * Wc, M))


def prepare(ctx):
    return Driver(ctx)
