"""Entry ``repro.evals.fleet.make_fleet_runner``: the whole fleet in
one dispatch. Rates [chunks, w_chunk, minutes] go in as a NumPy array,
as ``run_fleet`` passes them (the runner donates its copy); a lax.scan
over chunks runs every policy's lanes of a chunk through the lane core
and pools the metrics inside the scan. On several chips the chunk's
lanes shard over the dp mesh and the pooled sums reduce across chips.
"""
from __future__ import annotations

import jax

from perfbench import compare


def fleet_spec(ctx):
    from repro.evals import fleet
    return fleet.spec(ctx.cell_name, policies=tuple(ctx.cfg["controllers"]),
                      forecaster=ctx.cfg["forecaster"]["name"],
                      n_workloads=int(ctx.mix["n_workloads"]),
                      w_chunk=int(ctx.mix["w_chunk"]),
                      minutes=int(ctx.mix["minutes"]),
                      sim=dict(ctx.cfg["plant"]),
                      bins=int(ctx.cfg["metric_bins"]))


class FleetCheck:
    """Every answer is the fleet's pooled accumulators; compared with the
    reference pooled over the same lanes."""

    def use(self, ctx):
        """Take another seed's traffic of the same shape."""
        self.ctx, self.rates = ctx, ctx.rates

    def expected(self, ref):
        C, Wc, M = self.rates.shape
        return compare.pooled_reference(ref, self.policies,
                                        self.rates.reshape(C * Wc, M))

    def verify(self, outputs):
        want = self.expected(self.ctx.reference())
        return [compare.pooled_gaps(o, want) for o in outputs]


class Driver(FleetCheck):
    module = "jit_run"

    def __init__(self, ctx):
        from repro.evals import fleet
        spec = fleet_spec(ctx)
        self.ctx, self.rates = ctx, ctx.rates
        self.policies = spec.policies
        self.run = fleet.make_fleet_runner(spec, ctx.classify)
        C, Wc, M = self.rates.shape
        self.units = 1
        self.iterations = C * M
        self.lane_minutes = len(spec.policies) * C * Wc * M

    def dispatch(self):
        with self.ctx.span("dispatch"):
            out = self.run(self.rates)
        with self.ctx.span("block"):
            return jax.block_until_ready(out)

    def release(self):
        self.run = None


def prepare(ctx):
    return Driver(ctx)
