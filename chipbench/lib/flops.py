"""Counts that hold for every architecture, computed from shapes.

These count what the algorithm requires, whatever implements it: a copy
the program makes on top is not counted, so it shows as a lower share of
the roofline.  What one architecture's step needs (its FLOPs per tick,
its bytes per decode tick) is counted by its configuration's reference
module (``references/dense_decoder.py`` states the contract), from
these helpers.

Exchanges (``op`` on ``n`` ranks, ``size`` bytes per rank): the bytes
one chip must move for the operation's semantics, never what a
transport happens to send.
"""
from __future__ import annotations

BF16 = 2


def context(start: int, length: int, window: int) -> int:
    """Keys attended by tokens at positions start .. start+length-1,
    each seeing min(p + 1, window) keys."""
    lo, hi = start + 1, start + length              # keys of first, last
    full = max(0, hi - max(lo, window) + 1) if hi >= window else 0
    below_hi = min(hi, window - 1)
    part = 0
    if below_hi >= lo:
        part = (lo + below_hi) * (below_hi - lo + 1) // 2
    return part + full * window


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def exchange_bytes(op: str, size: int, n: int) -> float:
    """Bytes one chip must send (or the root receive) for ``op``."""
    if op in ("send", "recv"):
        return float(size)
    if op in ("bcast", "scatter", "alltoall"):
        return (n - 1) / n * size
    if op == "agg":
        return float((n - 1) * size)
    if op == "allreduce":
        return 2 * (n - 1) / n * size
    raise ValueError(f"no byte count for {op!r}")
