"""Use hypothesis when available; otherwise a deterministic fallback that
replays a fixed number of seeded examples (the container image may not
ship hypothesis — property tests still run, just without shrinking)."""
from __future__ import annotations

try:
    from hypothesis import example, given, settings  # noqa: F401
    from hypothesis import strategies as st  # noqa: F401
except ImportError:
    import numpy as np

    class _Strategy:
        def __init__(self, sampler):
            self.sample = sampler

    class st:  # noqa: N801 - mimics hypothesis.strategies
        @staticmethod
        def floats(min_value=0.0, max_value=1.0, **_):
            return _Strategy(
                lambda rng: float(rng.uniform(min_value, max_value)))

        @staticmethod
        def integers(min_value=0, max_value=1 << 30, **_):
            return _Strategy(
                lambda rng: int(rng.integers(min_value, max_value,
                                             endpoint=True)))

    def given(*strategies):
        def deco(fn):
            # no functools.wraps: pytest must see a zero-arg signature,
            # not the wrapped function's strategy parameters
            def wrapper():
                for args in getattr(fn, "_examples", ()):
                    fn(*args)
                n = getattr(wrapper, "_max_examples", 10)
                rng = np.random.default_rng(0)
                for _ in range(n):
                    fn(*(s.sample(rng) for s in strategies))
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            return wrapper
        return deco

    def example(*args):
        """An explicit case, replayed before the seeded ones; place it
        between `@given` and the function."""
        def deco(fn):
            fn._examples = getattr(fn, "_examples", ()) + (args,)
            return fn
        return deco

    def settings(max_examples=10, **_):
        def deco(fn):
            fn._max_examples = min(max_examples, 10)
            return fn
        return deco
