"""Readings that the limits of ``correct`` are set from (not run by the
benchmark's own runs).

    python3 perfbench/calibrate.py --workload <cell> --seeds 1 2 3 ...

In one process on the cell's chips, for every seed: one dispatch of the
program at the cell's own size, compared with the float32 reference (the
lower readings), and the control, the reference computed in bfloat16 and
put in the program's place, compared with the same float32 reference
(the upper readings). One JSON line per seed on standard output.
"""
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]


def readings(cell_name: str, seeds, *, platform: str = "tpu",
             mix_overrides: dict | None = None):
    """Yields one dict per seed: the program's and the control's
    readings of every compared number, and the dispatch seconds."""
    import jax.numpy as jnp
    from perfbench import classifier, generator, harness

    cell = harness.load_json("cells", cell_name)
    harness.require_chips(cell["chips"], platform)
    cfg = harness.load_json("configs", cell["config"])
    harness.check_program(cfg)
    mix = {**generator.load_mix(cell["traffic"]), **(mix_overrides or {})}
    classify, clf, _ = classifier.get(cfg)
    spans = harness.Spans()
    with harness.placement(cell["chips"]):
        driver = None
        for seed in seeds:
            rates = generator.generate(mix, seed)
            ctx = harness.Context(cell_name, cell, cfg, mix, rates,
                                  classify, clf, spans, cell["chips"])
            if driver is None:
                driver = harness.entry(cell["entry"]).prepare(ctx)
                driver.dispatch()
            else:
                driver.use(ctx)
            t0 = time.perf_counter()
            out = driver.dispatch()
            dispatch_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            program = driver.verify([out])[0]
            reference_s = time.perf_counter() - t0
            control = driver.verify(
                [driver.expected(ctx.reference(jnp.bfloat16))])[0]
            yield {"cell": cell_name, "seed": seed, "program": program,
                   "control": control, "dispatch_s": dispatch_s,
                   "reference_s": reference_s}


def main(argv=None) -> int:
    import argparse
    import jax
    from repro import compile_cache
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for r in readings(args.workload, args.seeds):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(HERE / ".cache" / "jax")
    sys.exit(main())
