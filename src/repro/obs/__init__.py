"""Decision telemetry: see what every control decision saw, attribute
every violated minute to the decision stage that caused it.

* ``trace``     — the `DecisionRecord` / `ControlTrace` schema captured
                  in-scan by ``repro.sim.cluster`` /
                  ``repro.scaling.batch`` under `telemetry=True` and
                  logged eagerly by the serving adapter (same fields, so
                  sim and engine traces diff directly).
* ``attribute`` — host-side SLO blame: walk violated minutes back
                  through the startup_sec cold-start window to the
                  responsible decision and classify the cause
                  (under-forecast / confidence-downscale /
                  cooldown-suppressed / limiter-clamped /
                  capacity-capped); blame + per-archetype tables.
* ``artifacts`` — content-addressed obs cards (trace npz + blame
                  summary + decision timeline markdown) on the
                  ``aapaset.manifest`` staged-publish scheme, rendered
                  into EXPERIMENTS.md by ``tools/render_experiments``.
* ``stages``    — the lane core's stage names (``jax.named_scope``
                  around plant, decide, forecast, reclassification and
                  the metric fold) and the streaming feed's host span
                  names, which ``perfbench`` splits a profiler trace by.

Only ``trace`` loads eagerly (it is dependency-free and imported by the
sim core); ``attribute`` / ``artifacts`` resolve lazily because they
import the evals plane, which itself imports the scaling layer.
"""
from repro.obs import trace  # noqa: F401
from repro.obs.trace import (ControlTrace, DecisionRecord,  # noqa: F401
                             ExplainOut, MinuteTrace)

_LAZY = ("attribute", "artifacts")


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(f"repro.obs.{name}")
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_LAZY))
