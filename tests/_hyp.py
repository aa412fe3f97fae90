"""Optional-import shim for hypothesis.

Uses the real library when installed; otherwise provides a tiny deterministic
fallback (seeded uniform sampling, capped example count) so property tests
still collect and run green without the dependency.
"""
try:
    from hypothesis import example, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

    import functools
    import inspect
    import random

    _FALLBACK_EXAMPLES = 5

    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def draw(self, rng):
            return self._draw(rng)

    class st:  # noqa: N801 — mirrors `hypothesis.strategies as st`
        @staticmethod
        def floats(min_value, max_value):
            return _Strategy(lambda rng: rng.uniform(min_value, max_value))

        @staticmethod
        def integers(min_value, max_value):
            return _Strategy(lambda rng: rng.randint(min_value, max_value))

        @staticmethod
        def booleans():
            return _Strategy(lambda rng: bool(rng.getrandbits(1)))

        @staticmethod
        def sampled_from(elements):
            elements = list(elements)
            return _Strategy(lambda rng: rng.choice(elements))

    def given(**strategies):
        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                n = min(
                    getattr(wrapper, "_max_examples", _FALLBACK_EXAMPLES),
                    _FALLBACK_EXAMPLES,
                )
                for pinned in getattr(fn, "_examples", ()):
                    fn(*args, **kwargs, **pinned)
                rng = random.Random(0)
                for _ in range(n):
                    drawn = {k: s.draw(rng) for k, s in strategies.items()}
                    fn(*args, **kwargs, **drawn)

            wrapper.hypothesis_fallback = True
            # pytest must not mistake the drawn arguments for fixtures
            del wrapper.__wrapped__
            wrapper.__signature__ = inspect.Signature()
            return wrapper

        return deco

    def example(**pinned):
        def deco(fn):
            fn._examples = [pinned, *getattr(fn, "_examples", ())]
            return fn

        return deco

    def settings(max_examples=None, **_ignored):
        def deco(fn):
            if max_examples is not None:
                fn._max_examples = max_examples
            return fn

        return deco
