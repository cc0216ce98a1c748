"""The one traffic generator: reads a mix from ``traffic/<name>.json``.

A mix names its arrival process and its length distributions.  The
lengths and inter-arrival gaps are the distributions' quantiles at
(i + 0.5) / n, in a stratified order drawn from the mix's own
``order_seed``: each run of ``block`` consecutive requests holds one
value from each of ``block`` strata (the sorted values cut into equal
parts), shuffled within the block.  So every seed offers the same
requests at the same times; the run's seed draws only the token ids.

Keys of a mix (all lengths in tokens):

* ``arrival``: ``"poisson"`` (open loop at ``rate_per_s``; enough
  requests for ``overfill`` times the window) or ``"backlog"``
  (``requests`` requests, all due at t = 0).
* ``prompt`` / ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``.
* ``block``: requests per stratified block (1: a plain shuffle).
* ``order_seed``: the seed of that order.
* ``temperature``: 0 for greedy decoding.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new_tokens: int
    arrival_s: float
    temperature: float


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's quantiles, clipped."""
    u = _quantiles(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(q)) for q in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = lo + u * (hi - lo + 1)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def stratified(values: np.ndarray, block: int,
               rng: np.random.Generator) -> np.ndarray:
    """``values`` reordered so that each run of ``block`` holds one value
    from each stratum of the sorted values, in an order drawn from
    ``rng``."""
    v = np.sort(values)
    n = len(v)
    if block <= 1:
        return rng.permutation(v)
    m = -(-n // block)                           # blocks
    strata = [rng.permutation(v[k * m:(k + 1) * m]) for k in range(block)]
    out = []
    for j in range(m):
        members = [s[j] for s in strata if j < len(s)]
        out.extend(rng.permutation(members))
    return np.asarray(out, dtype=values.dtype)


def count(mix: dict, seconds: float) -> int:
    if mix["arrival"] == "backlog":
        return int(mix["requests"])
    return int(math.ceil(mix["rate_per_s"] * seconds
                         * mix.get("overfill", 1.25)))


def arrivals(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if mix["arrival"] == "backlog":
        return np.zeros(n)
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    gaps = -np.log1p(-_quantiles(n)) / mix["rate_per_s"]
    return np.cumsum(stratified(gaps, mix.get("block", 1), rng))


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> List[Req]:
    """The requests of one run, in arrival order."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(int(mix["order_seed"]))
    n = count(mix, seconds)
    block = mix.get("block", 1)
    prompt = stratified(lengths(mix["prompt"], n), block, order)
    out = stratified(lengths(mix["output"], n), block, order)
    due = arrivals(mix, n, order)
    temp = float(mix.get("temperature", 0.0))
    return [Req(i, rng.integers(0, vocab, size=int(p), dtype=np.int32),
                int(o), float(t), temp)
            for i, (p, o, t) in enumerate(zip(prompt, out, due))]
