"""Plain float32 reference of a decoder whose windowed and global layers
alternate, for the benchmark's tests.

Every ``global_every``-th layer attends over the whole context with the
RoPE base ``rope_theta_global``; the others attend within
``sliding_window`` with ``rope_theta``.  The LM head is the token
embedding, transposed (tied).  The program serves this pattern as its
``gsuper`` group of sub-blocks (gemma3-4b's layout, here at test size).

It follows the contract that ``references/dense_decoder.py`` states, and
exists only as a test's file.  Its weights are laid out by position in
the repeating block: ``w["sub"][j]`` holds sub-block ``j``'s leaves, each
stacked over the blocks; the dense decoder stacks one layer's leaves
over all layers.  It shares the dense decoder's layer arithmetic on
purpose, through that module's public ``rms``, ``rope``, ``mm`` and
``attention``: the two differ in layout and layer pattern only.
"""
from __future__ import annotations

import functools
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp

from chipbench.lib.flops import BF16, context
from chipbench.lib.harness import BENCH, load_module

dd = load_module(BENCH / "references" / "dense_decoder.py")

FULL = 1 << 30


def blocks(m: dict) -> int:
    """Repeats of the (windowed x (global_every - 1), global) block."""
    n, rem = divmod(m["num_layers"], m["global_every"])
    if rem:
        raise ValueError("num_layers must be whole blocks of global_every")
    return n


def sub_shapes(m: dict) -> Dict[str, tuple]:
    n, D, H, K, dh, F = (blocks(m), m["d_model"], m["num_heads"],
                         m["num_kv_heads"], m["head_dim"], m["d_ff"])
    return {"attn_norm": (n, D), "wq": (n, D, H * dh), "wk": (n, D, K * dh),
            "wv": (n, D, K * dh), "wo": (n, H * dh, D), "ffn_norm": (n, D),
            "w1": (n, D, F), "w3": (n, D, F), "w2": (n, F, D)}


def init_weights(m: dict, key) -> dict:
    """Normal weights scaled by fan_in ** -0.5, norm gains 1; bfloat16."""
    def normal(k, shape, fan_in):
        w = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
        return w.astype(jnp.bfloat16)

    D = m["d_model"]
    subs = []
    for j in range(m["global_every"]):
        sub = {}
        for i, (name, shape) in enumerate(sorted(sub_shapes(m).items())):
            if name.endswith("norm"):
                sub[name] = jnp.ones(shape, jnp.bfloat16)
            else:
                sub[name] = normal(jax.random.fold_in(key, 100 * j + i),
                                   shape, shape[-2])
        subs.append(sub)
    return {"embed": normal(jax.random.fold_in(key, 1 << 20),
                            (m["vocab_size"], D), D),
            "final_norm": jnp.ones((D,), jnp.bfloat16), "sub": tuple(subs)}


def program_params(w: dict, model: dict) -> dict:
    """The program's ``gsuper`` tree for these weights (the same arrays);
    tied, so no ``unemb``."""
    subs = tuple({"norm": {"scale": p["attn_norm"]}, "wq": p["wq"],
                  "wk": p["wk"], "wv": p["wv"], "wo": p["wo"],
                  "fnorm": {"scale": p["ffn_norm"]},
                  "ffn": {"w1": p["w1"], "w3": p["w3"], "w2": p["w2"]}}
                 for p in w["sub"])
    return {"emb": w["embed"], "final_norm": {"scale": w["final_norm"]},
            "dec_gsuper": subs}


@functools.partial(jax.jit, static_argnames=("m", "glob", "quant"))
def _layer(h, sub, b, *, m, glob, quant):
    m = dict(m)
    B, T, D = h.shape
    H, K, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps = m["rms_norm_eps"]
    theta = m["rope_theta_global"] if glob else m["rope_theta"]
    window = T if glob else m["sliding_window"]
    lw = {n: v[b] for n, v in sub.items()}
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    x = dd.rms(h, lw["attn_norm"], eps)
    q = dd.rope(dd.mm(x, lw["wq"], quant).reshape(B, T, H, dh), pos, theta)
    k = dd.rope(dd.mm(x, lw["wk"], quant).reshape(B, T, K, dh), pos, theta)
    v = dd.mm(x, lw["wv"], quant).reshape(B, T, K, dh)
    a = dd.attention(q, k, v, window, quant).reshape(B, T, H * dh)
    h = h + dd.mm(a, lw["wo"], quant)
    x = dd.rms(h, lw["ffn_norm"], eps)
    return h + dd.mm(jax.nn.silu(dd.mm(x, lw["w1"], quant))
                     * dd.mm(x, lw["w3"], quant), lw["w2"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(h, at, w, *, eps, quant):
    hs = jnp.take_along_axis(h, at[..., None], axis=1)           # (B, n, D)
    return dd.mm(dd.rms(hs, w["final_norm"], eps), w["embed"].T, quant)


def logits_at(m: dict, w, tokens, at, quant: bool = False):
    """Float32 logits (B, n, V) at positions ``at`` (B, n) of the
    sequences ``tokens`` (B, T), computed layer by layer."""
    h = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(
        jnp.float32)
    frozen = tuple(sorted(m.items()))          # hashable, for jit
    every = m["global_every"]
    for i in range(m["num_layers"]):
        b, j = divmod(i, every)
        h = _layer(h, w["sub"][j], b, m=frozen, glob=j == every - 1,
                   quant=quant)
    return _head(h, jnp.asarray(at), w, eps=m["rms_norm_eps"], quant=quant)


# ------------------------------------------------------------------ counts


def _keys(m: dict, start: int, n: int) -> int:
    """Keys a row's tokens attend, summed over the layers."""
    n_global = blocks(m)
    return ((m["num_layers"] - n_global)
            * context(start, n, m["sliding_window"])
            + n_global * context(start, n, FULL))


def tick_flops(m: dict, rows: Iterable[Tuple[int, int]]) -> float:
    """Model FLOPs of one serving tick."""
    L, H, dh, D, V = (m["num_layers"], m["num_heads"], m["head_dim"],
                      m["d_model"], m["vocab_size"])
    per_token = 2 * L * dd.layer_weights(m)
    return float(sum(n * per_token + 2 * D * V + 4 * H * dh * _keys(m, s, n)
                     for s, n in rows))


def decode_bytes(m: dict, rows: Iterable[Tuple[int, int]]) -> float:
    """HBM bytes one decode tick needs: weights once (the tied embedding
    once, as the LM head), the embedding rows, the K/V of each row's
    live keys read and its new K/V written."""
    L, K, dh, D, V = (m["num_layers"], m["num_kv_heads"], m["head_dim"],
                      m["d_model"], m["vocab_size"])
    kv = 2 * K * dh * BF16                  # one token's K/V in one layer
    total = (L * (dd.layer_weights(m) + 2 * D) + D + D * V) * BF16
    for s, n in rows:
        total += D * BF16 * n + kv * (_keys(m, s, n) + L * n)
    return float(total)
