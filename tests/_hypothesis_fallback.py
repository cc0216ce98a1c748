"""Minimal deterministic stand-in for `hypothesis`, used only when the
real package is not installed (tests/conftest.py wires it into
``sys.modules``).  It implements exactly the subset this suite uses —
``given``, ``settings``, and the ``integers / floats / lists /
sampled_from / composite / .map`` strategies — by drawing a fixed number
of pseudo-random examples from a seeded RNG, so the property tests stay
collected, running, and reproducible without the dependency.  Install
the real thing (requirements-dev.txt) for actual input-space search.
"""
from __future__ import annotations

import functools
import random
from typing import Any, Callable, List

DEFAULT_MAX_EXAMPLES = 25
_SEED = 0xC0FFEE


class Strategy:
    def __init__(self, sample: Callable[[random.Random], Any]):
        self._sample = sample

    def map(self, f: Callable[[Any], Any]) -> "Strategy":
        return Strategy(lambda rng: f(self._sample(rng)))


def integers(min_value: int, max_value: int) -> Strategy:
    return Strategy(lambda rng: rng.randint(min_value, max_value))


def floats(min_value: float, max_value: float, *, width: int = 64,
           allow_subnormal: bool = True) -> Strategy:
    # uniform draws in the tests' ranges are never subnormal at either
    # width, so neither flag needs handling
    return Strategy(lambda rng: rng.uniform(min_value, max_value))


def sampled_from(elements) -> Strategy:
    pool = list(elements)
    return Strategy(lambda rng: pool[rng.randrange(len(pool))])


def lists(elements: Strategy, *, min_size: int = 0,
          max_size: int = 10) -> Strategy:
    def sample(rng: random.Random) -> List[Any]:
        n = rng.randint(min_size, max_size)
        return [elements._sample(rng) for _ in range(n)]
    return Strategy(sample)


def composite(f: Callable) -> Callable[..., Strategy]:
    @functools.wraps(f)
    def build(*args, **kwargs) -> Strategy:
        return Strategy(lambda rng: f(
            lambda strat: strat._sample(rng), *args, **kwargs))
    return build


def settings(max_examples: int = DEFAULT_MAX_EXAMPLES, **_ignored):
    """Decorator: records the example budget on the (given-wrapped)
    test; extra knobs like ``deadline`` are accepted and ignored."""
    def deco(fn):
        fn._fallback_max_examples = max_examples
        return fn
    return deco


def given(*strategies: Strategy):
    def deco(fn):
        # NOT functools.wraps: pytest must see a zero-arg signature, or
        # it would treat the property's parameters as fixtures
        def wrapper():
            rng = random.Random(_SEED)
            n = getattr(wrapper, "_fallback_max_examples",
                        DEFAULT_MAX_EXAMPLES)
            for _ in range(n):
                drawn = [s._sample(rng) for s in strategies]
                fn(*drawn)
        wrapper.__name__ = fn.__name__
        wrapper.__doc__ = fn.__doc__
        wrapper.__module__ = fn.__module__
        return wrapper
    return deco
