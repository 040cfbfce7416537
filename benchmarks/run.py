# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
#   python -m benchmarks.run [bench] [--smoke] [--json DIR] [--profile DIR]
#
# --json DIR writes each bench's emitted records to DIR/BENCH_<bench>.json
# (stable schema, sorted keys) so perf numbers diff across PRs; --smoke
# asks benches that support it (bench_sim, bench_fleet, bench_tuning) for
# a seconds-scale variant — the CI tier-1 smoke uploads BENCH_sim.json,
# BENCH_fleet.json and BENCH_tuning.json as workflow artifacts. --profile DIR wraps each bench
# in jax.profiler.trace (one trace subdir per bench, viewable in
# TensorBoard/Perfetto) so a fleet-scale regression is attributed to a
# dispatch, not guessed at.
#
# JAX's persistent compilation cache is placed by repro.compile_cache
# (JAX_COMPILATION_CACHE_DIR if set, else .jax_cache/ in the checkout), so
# a number that includes compile time (bench_aapaset's cold build, a
# streaming run's first chunk) depends on whether an earlier run filled
# that cache; each BENCH_<bench>.json records who placed the cache and
# how many entries it held when the run started.
from __future__ import annotations

import inspect
import json
import os
import pathlib
import sys
import time
import traceback

BENCH_SCHEMA_VERSION = 1


def _write_json(out_dir: pathlib.Path, bench: str, records: list,
                elapsed_s: float, failed: bool, smoke: bool,
                compile_cache: dict) -> None:
    out_dir.mkdir(parents=True, exist_ok=True)
    doc = {"schema": BENCH_SCHEMA_VERSION, "bench": bench,
           "smoke": smoke, "elapsed_s": round(elapsed_s, 3),
           "failed": failed, "compile_cache": compile_cache,
           "records": records}
    path = out_dir / f"BENCH_{bench}.json"
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True, default=float)
        f.write("\n")
    print(f"# wrote {path}", flush=True)


def main() -> None:
    from benchmarks import (bench_aapaset, bench_autoscaling,
                            bench_classification, bench_fleet,
                            bench_labeling, bench_latency,
                            bench_pipeline_perf, bench_rei,
                            bench_roofline, bench_sim, bench_tuning,
                            bench_uncertainty)
    from benchmarks import common
    from repro import compile_cache
    cache_dir = pathlib.Path(compile_cache.enable())
    cache = {"placed_by": ("env" if os.environ.get(compile_cache.ENV)
                           else "checkout"),
             "entries_at_start": (len(list(cache_dir.iterdir()))
                                  if cache_dir.is_dir() else 0)}
    benches = [
        ("sim", bench_sim),
        ("fleet", bench_fleet),
        ("tuning", bench_tuning),
        ("aapaset", bench_aapaset),
        ("labeling", bench_labeling),
        ("classification", bench_classification),
        ("latency", bench_latency),
        ("autoscaling", bench_autoscaling),
        ("rei", bench_rei),
        ("uncertainty", bench_uncertainty),
        ("pipeline_perf", bench_pipeline_perf),
        ("roofline", bench_roofline),
    ]
    argv = sys.argv[1:]
    smoke = "--smoke" in argv
    json_dir: pathlib.Path | None = None
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv):
            sys.exit("--json needs a directory argument")
        json_dir = pathlib.Path(argv[i + 1])
        del argv[i:i + 2]
    profile_dir: pathlib.Path | None = None
    if "--profile" in argv:
        i = argv.index("--profile")
        if i + 1 >= len(argv):
            sys.exit("--profile needs a directory argument")
        profile_dir = pathlib.Path(argv[i + 1])
        del argv[i:i + 2]
    argv = [a for a in argv if a != "--smoke"]
    only = argv[0] if argv else None

    print(f"# compile cache: {cache_dir} "
          f"({cache['entries_at_start']} entries at start)")
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in benches:
        if only and only != name:
            continue
        kwargs = {}
        if smoke and "smoke" in inspect.signature(mod.main).parameters:
            kwargs["smoke"] = True
        t0 = time.time()
        failed = False
        common.start_capture()
        trace = None
        if profile_dir is not None:
            import contextlib
            import jax
            trace = contextlib.ExitStack()
            trace.enter_context(
                jax.profiler.trace(str(profile_dir / name)))
        try:
            mod.main(**kwargs)
        except Exception:
            failures += 1
            failed = True
            traceback.print_exc()
            print(f"{name},0.0,FAILED")
        finally:
            if trace is not None:
                trace.close()
                print(f"# [{name}] profile -> {profile_dir / name}",
                      flush=True)
        records = common.drain_capture()
        if json_dir is not None:
            # a bench without a smoke variant ran its full workload even
            # under --smoke; label its records accordingly
            _write_json(json_dir, name, records, time.time() - t0, failed,
                        bool(kwargs.get("smoke", False)), cache)
        print(f"# [{name}] {time.time()-t0:.0f}s", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
