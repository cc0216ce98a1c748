"""Composable blocks: self-attention (+dense/MoE FFN), cross-attention,
encoder, and the Hymba parallel attention+SSM block.

Every ``apply_*`` runs in one of three modes:
  * ``train``  — no cache, full-sequence causal attention;
  * ``chunk``  — chunked prefill: attend over [cache ++ chunk], then write
                 the chunk into the ring;
  * ``decode`` — single token: write first, attend over the ring only.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import GLOBAL_WINDOW
from repro.models import cache as cache_lib
from repro.models.layers import (attention, dense_init, rmsnorm,
                                 rmsnorm_init, rope, swiglu, swiglu_init)
from repro.models.moe import moe_ffn, moe_init
from repro.models.ssm import ssm_forward, ssm_init

Array = jax.Array


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def attn_init(key, d_model, heads, kv_heads, dh, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    return {
        "norm": rmsnorm_init(d_model, dtype),
        "wq": dense_init(ks[0], (d_model, heads * dh), dtype),
        "wk": dense_init(ks[1], (d_model, kv_heads * dh), dtype),
        "wv": dense_init(ks[2], (d_model, kv_heads * dh), dtype),
        "wo": dense_init(ks[3], (heads * dh, d_model), dtype),
    }


def ffn_init(key, d_model, d_ff, kind, num_experts=0, dtype=jnp.bfloat16):
    p = {"fnorm": rmsnorm_init(d_model, dtype)}
    if kind == "dense":
        p["ffn"] = swiglu_init(key, d_model, d_ff, dtype)
    elif kind == "moe":
        k1, k2 = jax.random.split(key)
        p["moe"] = moe_init(k1, d_model, d_ff, num_experts, dtype)
    return p


def xattn_init(key, d_model, heads, kv_heads, dh, gated, dtype=jnp.bfloat16):
    p = attn_init(key, d_model, heads, kv_heads, dh, dtype)
    if gated:
        p["gate_attn"] = jnp.zeros((), jnp.float32)
        p["gate_ffn"] = jnp.zeros((), jnp.float32)
    return p


def hymba_init(key, d_model, heads, kv_heads, dh, d_inner, state,
               dtype=jnp.bfloat16):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = attn_init(k1, d_model, heads, kv_heads, dh, dtype)
    p["ssm"] = ssm_init(k2, d_model, d_inner, state, dtype)
    p["anorm"] = rmsnorm_init(d_model, dtype)
    p["snorm"] = rmsnorm_init(d_model, dtype)
    return p


# ---------------------------------------------------------------------------
# attention core shared by self/cross blocks
# ---------------------------------------------------------------------------

def _qkv(p, xq: Array, xkv: Array, heads, kv_heads, dh):
    B, C, _ = xq.shape
    N = xkv.shape[1]
    q = (xq @ p["wq"]).reshape(B, C, heads, dh)
    k = (xkv @ p["wk"]).reshape(B, N, kv_heads, dh)
    v = (xkv @ p["wv"]).reshape(B, N, kv_heads, dh)
    return q, k, v


def self_attention(p, x, pos, kv, *, heads, kv_heads, dh, window, theta,
                   mode, q_chunk, logits_dtype=jnp.float32
                   ) -> Tuple[Array, Optional[dict]]:
    """x: (B, C, D); pos: (B, C); kv: {'k','v'} (B,W,...) + group-level pos
    handled by the caller (passed as kv['pos']).  A paged entry carries a
    block table in kv['btab'] and the layer's index in kv['layer'], and
    its k/v are the whole stacked physical pool (count, num_blocks, bs,
    *row; see ``cache.pool_row``) instead of per-slot rings; the returned
    k/v are that pool with this layer's tokens written in place.  Same
    update discipline; reads and writes go through the table at the
    layer's index."""
    xn = rmsnorm(p["norm"], x)
    q, k, v = _qkv(p, xn, xn, heads, kv_heads, dh)
    q = rope(q, pos, theta)
    k = rope(k, pos, theta)
    new_kv = None
    paged = kv is not None and "btab" in kv
    if paged:
        btab, layer = kv["btab"], kv["layer"]

        def gather(pool):
            rows = cache_lib.paged_gather(pool, btab, layer)
            return rows.reshape(rows.shape[:2] + (kv_heads, dh))

        def scatter(pool, new):
            return cache_lib.paged_scatter(pool, btab, layer, new, pos)
    if mode == "train":
        out = attention(q, k, v, pos, pos, window=window, causal=True,
                        q_chunk=q_chunk, logits_dtype=logits_dtype)
    elif mode == "chunk":
        old_k = gather(kv["k"]) if paged else kv["k"]
        old_v = gather(kv["v"]) if paged else kv["v"]
        keys = jnp.concatenate([old_k, k], axis=1)
        vals = jnp.concatenate([old_v, v], axis=1)
        k_pos = jnp.concatenate([kv["pos"], pos], axis=1)
        out = attention(q, keys, vals, pos, k_pos, window=window,
                        causal=True, q_chunk=q_chunk,
                        logits_dtype=logits_dtype)
        if paged:
            k2, v2 = scatter(kv["k"], k), scatter(kv["v"], v)
        else:
            k2, v2, _ = cache_lib.update_kv(kv["k"], kv["v"], kv["pos"],
                                            k, v, pos)
        new_kv = {"k": k2, "v": v2}
    else:  # decode: update-then-attend
        pos2 = cache_lib.scatter_ring(kv["pos"], pos, pos)
        if paged:
            k2, v2 = scatter(kv["k"], k), scatter(kv["v"], v)
            gk, gv = gather(k2), gather(v2)
        else:
            k2 = cache_lib.scatter_ring(kv["k"], k, pos)
            v2 = cache_lib.scatter_ring(kv["v"], v, pos)
            gk, gv = k2, v2
        out = attention(q, gk, gv, pos, pos2, window=window, causal=True)
        new_kv = {"k": k2, "v": v2}
    B, C = x.shape[:2]
    return out.reshape(B, C, heads * dh) @ p["wo"], new_kv


def cross_attention(p, x, media_kv, *, heads, kv_heads, dh
                    ) -> Array:
    """media_kv: {'k','v'} (B, N, kv_heads, dh) precomputed/cached."""
    B, C, _ = x.shape
    xn = rmsnorm(p["norm"], x)
    q = (xn @ p["wq"]).reshape(B, C, heads, dh)
    N = media_kv["k"].shape[1]
    zeros = jnp.zeros((B, N), jnp.int32)
    qp = jnp.zeros((B, C), jnp.int32)
    out = attention(q, media_kv["k"], media_kv["v"], qp, zeros,
                    causal=False)
    return out.reshape(B, C, heads * dh) @ p["wo"]


def media_kv_of(p, media: Array, kv_heads, dh) -> Dict[str, Array]:
    B, N, _ = media.shape
    return {"k": (media @ p["wk"]).reshape(B, N, kv_heads, dh),
            "v": (media @ p["wv"]).reshape(B, N, kv_heads, dh)}


# ---------------------------------------------------------------------------
# FFN application
# ---------------------------------------------------------------------------

def apply_ffn(p, x, *, kind, moe_kwargs, mode) -> Tuple[Array, Array]:
    if kind == "none":
        return x, jnp.zeros((), jnp.float32)
    xn = rmsnorm(p["fnorm"], x)
    if kind == "dense":
        return x + swiglu(p["ffn"], xn), jnp.zeros((), jnp.float32)
    moe_mode = "replicated" if mode == "decode" else "scatter"
    y, aux = moe_ffn(p["moe"], xn, mode=moe_mode, **moe_kwargs)
    return x + y, aux
