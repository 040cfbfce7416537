"""The benchmark harness: one run of one cell.

Everything that belongs to one cell, configuration, traffic mix, entry
point or per-layer metric sits in a file of its own, found by name:

* ``BENCHMARK.json`` (checkout root): the cells, their metrics, and
  which per-layer metric each cell reports;
* ``perfbench/cells/<cell>.json``: configuration, entry, traffic mix,
  chips, and the limits of the numbers that decide ``correct``;
* ``perfbench/configs/<config>.json``: plant, controllers, forecaster,
  classifier;
* ``perfbench/traffic/<mix>.json``: a traffic family and its parameters;
* ``perfbench/traffic/<family>.py``: ``generate(mix, seed) -> rates``;
* ``perfbench/entries/<entry>.py``: ``prepare(ctx) -> driver``, the
  driver of one program entry point;
* ``perfbench/layers/<metric>.py``: ``read(run) -> float | None``.

A run: build the configuration's classifier (cached) and the traffic
from the seed, let the entry compile and warm its shapes with one whole
dispatch (set-up), run whole dispatches back to back until `seconds`
have passed (the window), then read the device's peak memory, free the
program's state, and compare every dispatch's answer with the plain
reference.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
REPO = ROOT.parent
CACHE = ROOT / ".cache"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """The platform or the device count does not fit the cell."""


def load_json(kind: str, name: str) -> dict:
    return json.loads((ROOT / kind / f"{name}.json").read_text())


def benchmark() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())


def _module(kind: str, name: str):
    """``perfbench/<kind>/<name>.py``, imported from its file."""
    qual = f"perfbench.{kind}.{name}"
    path = ROOT / kind / f"{name}.py"
    mod = sys.modules.get(qual)
    if mod is not None and pathlib.Path(mod.__file__) == path:
        return mod
    spec = importlib.util.spec_from_file_location(qual, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[qual] = mod
    spec.loader.exec_module(mod)
    return mod


def entry(name: str):
    """The driver module of entry point `name`."""
    return _module("entries", name)


def layer(name: str):
    """The reader module of per-layer metric `name`."""
    return _module("layers", name)


def traffic_family(name: str):
    """The generator module of traffic family `name`."""
    return _module("traffic", name)


def layer_metrics(bench: dict, cell: str) -> list[dict]:
    """The per-layer metrics that `cell` reports: those that list it
    under ``workloads``, or list no workloads at all."""
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])]


class CompileClock:
    """Seconds under JAX's backend-compile event, which times
    ``compile_or_get_cached``: a persistent-cache hit counts the time to
    load the executable."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration


class Spans:
    """The benchmark's own host spans: (name, start_s, end_s) on the
    host clock, also written into the profiler's trace when tracing."""

    def __init__(self):
        self.records: list = []
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        ann = (jax.profiler.TraceAnnotation(name) if self.tracing
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def durations(self, name: str) -> list[float]:
        return [b - a for n, a, b in self.records if n == name]


@dataclasses.dataclass
class Context:
    """What an entry's `prepare` gets."""
    cell_name: str
    cell: dict
    cfg: dict
    mix: dict
    rates: object           # numpy invocation tensor from the generator
    classify: object        # the program's classify closure
    clf: object             # reference.Classifier
    span: Spans
    chips: int

    def reference(self, dtype=None):
        """The plain reference, its blocks spread over the cell's chips."""
        import jax
        import jax.numpy as jnp
        from perfbench import reference
        return reference.Reference(self.cfg, self.clf,
                                   dtype=dtype or jnp.float32,
                                   devices=jax.devices()[:self.chips])


@dataclasses.dataclass
class Run:
    """What a per-layer reader gets."""
    cell_name: str
    driver: object
    spans: Spans
    summary: object          # trace.Summary of the traced window
    dispatches: int          # whole dispatches in the traced window
    setup_compile_s: float


def check_program(cfg: dict) -> None:
    """The program must run what the configuration states: its policy
    and forecaster defaults have to equal the configuration's numbers
    (the plant, the bins and the classifier are passed explicitly)."""
    from repro.forecast import registry as forecasters
    from repro.scaling import registry
    fc = dict(cfg["forecaster"])
    have = forecasters.spec(fc.pop("name")).defaults
    bad = [f"forecaster.{k}" for k, v in fc.items() if have.get(k) != v]
    for policy, params in cfg["controllers"].items():
        have = registry.spec(policy).defaults
        bad += [f"{policy}.{k}" for k, v in params.items()
                if have.get(k, v) != v or k not in have]
    if bad:
        raise RuntimeError("the program's defaults differ from the "
                           f"configuration {cfg['name']!r}: {bad}")


@contextlib.contextmanager
def placement(chips: int):
    """A dp mesh over the cell's chips (none for one chip)."""
    if chips == 1:
        yield
        return
    from repro.dist import sharding as shd
    mesh = shd.make_mesh((chips,), ("data",))
    shd.set_mesh(mesh)
    try:
        with mesh:
            yield
    finally:
        shd.set_mesh(None)


def require_chips(chips: int, platform: str = "tpu") -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"needs a {platform.upper()}, found "
                     f"{devs[0].platform!r}; nothing was run")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devs)}")
    return devs


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def lane_min_per_s(lane_minutes: float, dispatches: int,
                   window_s: float) -> float:
    """Policy lanes x workloads x simulated minutes completed in the
    window, per second of the window."""
    return lane_minutes * dispatches / window_s


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, platform: str = "tpu",
             mix_overrides: dict | None = None, patch=None) -> dict:
    """One run of `cell_name`; returns the result line as a dict. The
    `mix_overrides` (smaller traffic) and `patch` (a function applied to
    the prepared driver) hooks exist for the CPU tests."""
    import jax
    from perfbench import classifier, compare, generator

    bench = benchmark()
    cell = load_json("cells", cell_name)
    devs = require_chips(cell["chips"], platform)
    devs = devs[:cell["chips"]]
    clock = CompileClock()
    cfg = load_json("configs", cell["config"])
    check_program(cfg)
    mix = {**generator.load_mix(cell["traffic"]), **(mix_overrides or {})}
    spans = Spans()
    t_clf = time.perf_counter()
    classify, clf, _ = classifier.get(cfg)
    t_traffic = time.perf_counter()
    rates = generator.generate(mix, seed)
    t_prepare = time.perf_counter()
    ctx = Context(cell_name, cell, cfg, mix, rates, classify, clf, spans,
                  cell["chips"])
    outputs, trace_dir = [], None
    with placement(cell["chips"]):
        driver = entry(cell["entry"]).prepare(ctx)
        if patch is not None:
            patch(driver)
        driver.dispatch()                       # compile + warm-up
        setup_s = time.perf_counter() - t_start
        setup_compile_s = clock.seconds
        spans.records.clear()
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
            spans.tracing = True
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t0 = time.perf_counter()
        with (jax.profiler.TraceAnnotation("window") if trace
              else contextlib.nullcontext()):
            while True:
                outputs.append(driver.dispatch())
                if trace or time.perf_counter() - t0 >= seconds:
                    break               # a traced run traces one dispatch
        window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        window_compile_s = clock.seconds - setup_compile_s
    peak = memory_peak(devs)
    outputs = jax.device_get(outputs)
    driver.release()
    gc.collect()

    summary = None
    if trace:
        from perfbench import trace as tr
        pb = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        summary = tr.load(pb[-1])
        shutil.rmtree(trace_dir, ignore_errors=True)

    t_ref = time.perf_counter()
    readings = driver.verify(outputs)
    reference_s = time.perf_counter() - t_ref
    limits = cell["limits"]
    failed_each = [not compare.judge(r, limits) for r in readings]
    numbers = compare.worst(readings)
    correct = (not any(failed_each) and compare.judge(numbers, limits)
               and window_compile_s == 0.0)
    attempted = driver.units * len(outputs)
    failed = driver.units * sum(failed_each)
    checked = {k: {"value": v, "limit": limits[k]}
               for k, v in numbers.items()}
    checked["compile_s_in_window"] = {"value": window_compile_s,
                                      "limit": 0.0}

    if trace:
        run = Run(cell_name, driver, spans, summary, len(outputs),
                  setup_compile_s)
        metrics = {}
        for m in layer_metrics(bench, cell_name):
            v = layer(m["name"]).read(run)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {
            "lane_min_per_s": {"value": lane_min_per_s(
                driver.lane_minutes, len(outputs), window_s),
                "unit": "lane-min/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = {"device_ops": summary.top_ops(10),
                             "idle_gaps": summary.idle_gaps(10)}
    line["timing"] = {"setup_s": setup_s,
                      "to_classifier_s": t_clf - t_start,
                      "classifier_s": t_traffic - t_clf,
                      "traffic_s": t_prepare - t_traffic,
                      "prepare_and_warm_s": t_start + setup_s - t_prepare,
                      "window_s": window_s, "reference_s": reference_s}
    if trace:
        line["timing"]["trace_offset_s"] = summary.offset_s
    line["checked"] = checked
    return line


def main(argv=None, t_start: float | None = None) -> int:
    """The command line of ``run.py``; `t_start` is the host clock at
    process start, taken before JAX was imported."""
    import argparse
    if t_start is None:
        t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="one run of one benchmark "
                                             "cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    timing = line.pop("timing")
    print("timing " + json.dumps(timing), file=sys.stderr)
    for name, c in line["checked"].items():
        print(f"checked {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0
