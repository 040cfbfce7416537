"""Entry ``repro.evals.fleet.make_chunk_folder``: the streaming fold.
One dispatch is one pass over the fleet as ``run_fleet(stream=True)``
makes it: a fresh pooled accumulator, then every chunk of rates handed
from the host (NumPy) to the donated-accumulator fold in order, then
``block_until_ready``. Each fold call pays its own host-to-device
transfer and dispatch, which the one-dispatch runner does not.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.entries.fleet_runner import FleetCheck, fleet_spec


class Driver(FleetCheck):
    module = "jit_fold"

    def __init__(self, ctx):
        from repro.evals import fleet
        from repro.evals import metrics as EM
        spec = fleet_spec(ctx)
        self.ctx, self.rates = ctx, ctx.rates
        self.policies = spec.policies
        self.fold = fleet.make_chunk_folder(spec, ctx.classify)
        P = len(spec.policies)
        self.acc0 = lambda: jax.tree.map(  # noqa: E731
            lambda a: jnp.broadcast_to(a, (P,) + a.shape),
            EM.accum_init(spec.bins))
        C, Wc, M = self.rates.shape
        self.chunks = list(self.rates)
        self.units = C
        self.iterations = C * M
        self.lane_minutes = P * C * Wc * M

    def use(self, ctx):
        super().use(ctx)
        self.chunks = list(self.rates)

    def dispatch(self):
        acc = self.acc0()
        for chunk in self.chunks:
            with self.ctx.span("feed"):
                acc = self.fold(acc, chunk)
        with self.ctx.span("block"):
            return jax.block_until_ready(acc)

    def release(self):
        self.fold = None


def prepare(ctx):
    return Driver(ctx)
