"""Operations and bytes that a step needs, computed from shapes.

These count what the algorithm requires, whatever implements it: a copy
the program makes on top is not counted, so it shows as a lower share of
the roofline.

Serving (dense decoder, ``model`` is a configuration's ``model`` dict):

* a token costs 2 FLOPs per matmul weight of the layers, plus attention
  over its live context: 4 * heads * head_dim FLOPs per key per layer
  (scores and the weighted sum);
* a row that samples costs the LM head, 2 * d_model * vocab;
* a decode tick reads every weight once (layers, final norm and LM
  head; the embedding only for its rows) and the K/V of every live
  token, and writes the new token's K/V.

Exchanges (``op`` on ``n`` ranks, ``size`` bytes per rank): the bytes
one chip must move for the operation's semantics, never what a
transport happens to send.
"""
from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2


def layer_weights(model: dict) -> int:
    """Matmul weights of one decoder layer."""
    D, H, K, dh, F = (model["d_model"], model["num_heads"],
                      model["num_kv_heads"], model["head_dim"], model["d_ff"])
    return D * H * dh + 2 * D * K * dh + H * dh * D + 3 * D * F


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


def tick_flops(model: dict, rows: Iterable[Tuple[int, int]]) -> float:
    """Model FLOPs of one serving tick; ``rows`` holds (start, length)
    of every row that carries tokens (each samples one token)."""
    L, H, dh, D, V = (model["num_layers"], model["num_heads"],
                      model["head_dim"], model["d_model"],
                      model["vocab_size"])
    W = model.get("sliding_window") or (1 << 30)
    per_token = 2 * L * layer_weights(model)
    flops = 0
    for start, n in rows:
        flops += n * per_token + 2 * D * V
        flops += 4 * L * H * dh * context(start, n, W)
    return float(flops)


def decode_bytes(model: dict, rows: Iterable[Tuple[int, int]]) -> float:
    """HBM bytes one decode tick needs: weights once, the K/V of each
    row's live context read, and its new K/V written."""
    L, K, dh, D, V = (model["num_layers"], model["num_kv_heads"],
                      model["head_dim"], model["d_model"],
                      model["vocab_size"])
    W = model.get("sliding_window") or (1 << 30)
    weights = (L * (layer_weights(model) + 2 * D) + D + D * V) * BF16
    kv_token = L * 2 * K * dh * BF16
    total = weights
    for start, n in rows:
        total += D * BF16 * n                           # embedding rows
        total += kv_token * (context(start, n, W) + n)
    return float(total)


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
