"""Entry ``repro.evals.matrix.make_runner``: the paper's evaluation
matrix (scenarios x seeds x forecasters x policies) in one dispatch,
with per-workload accumulators in the scan carry. ``matrix.run`` is not
used: its content-addressed result cache would skip the work.
"""
from __future__ import annotations

import types

import numpy as np
import jax

from perfbench import compare, reference


class Driver:
    module = "jit_run_fn"

    def __init__(self, ctx):
        from repro.evals import matrix
        mix, cfg = ctx.mix, ctx.cfg
        self.ctx, self.rates = ctx, ctx.rates       # [S, Z, W, M]
        S, Z, W, M = self.rates.shape
        self.policies = tuple(cfg["controllers"])
        spec = matrix.spec(
            ctx.cell_name, policies=self.policies,
            forecasters=(cfg["forecaster"]["name"],),
            scenarios=tuple(("archetype_pure", {"kind": k})
                            for k in mix["kinds"]),
            seeds=tuple(range(Z)), n_workloads=W, minutes=M,
            sim=dict(cfg["plant"]), bins=int(cfg["metric_bins"]))
        self.run = matrix.make_runner(spec, ctx.classify)
        self.units = 1
        self.iterations = M
        self.lane_minutes = len(self.policies) * S * Z * W * M

    def dispatch(self):
        with self.ctx.span("dispatch"):
            out = self.run(self.rates)
        with self.ctx.span("block"):
            return jax.block_until_ready(out)

    def release(self):
        self.run = None

    def use(self, ctx):
        """Take another seed's traffic of the same shape."""
        self.ctx, self.rates = ctx, ctx.rates

    def expected(self, ref):
        """(pooled, per-workload) episode metrics as the reference `ref`
        gives them, shaped like the program's [S, Z, F=1, P(, W)]."""
        S, Z, W, M = self.rates.shape
        pool, per_w = [], []
        for p in self.policies:
            acc = ref.lanes(p, self.rates.reshape(S * Z * W, M),
                            per_lane_hist=True)
            acc = {k: v.reshape((S, Z, W) + v.shape[1:])
                   for k, v in acc.items()}
            per_w.append(reference.finalize(acc, ref.edges))
            pool.append(reference.finalize(
                {k: v.sum(2) for k, v in acc.items()}, ref.edges))

        def stack(per, axis):
            return types.SimpleNamespace(**{
                f: np.expand_dims(np.stack([d[f] for d in per], axis), 2)
                for f in per[0]})
        return stack(pool, -1), stack(per_w, 2)

    def verify(self, outputs):
        want = self.expected(self.ctx.reference())
        return [compare.episode_gaps(o, want) for o in outputs]


def prepare(ctx):
    return Driver(ctx)
