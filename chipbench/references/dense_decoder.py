"""Plain float32 reference of a dense decoder (Llama/Mistral layout).

Per layer: RMSNorm -> attention (RoPE on q and k, grouped KV heads,
causal, within ``sliding_window``) -> residual -> RMSNorm -> SwiGLU ->
residual; then a final RMSNorm and the LM head.  Written from the
published descriptions (Llama, arXiv:2302.13971; Mistral's window,
arXiv:2310.06825), in ``jax.numpy`` at float32 with every matmul at
``Precision.HIGHEST``; it imports nothing of the system under test.

The weights are the benchmark's: ``init_weights`` makes them on the
device from a key, in bfloat16 (the served type), and the same arrays
feed the program and this reference.

``quant=True`` is the control: every matmul operand is rounded to
float8 e4m3 with an absmax scale per row of the activation and per
output column of the weight, then multiplied at float32 -- the step
below the bfloat16 that the configuration serves in.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
Q_BLOCK = 512                      # query rows per attention block


def weight_shapes(m: dict) -> Dict[str, tuple]:
    L, D, H, K, dh, F, V = (m["num_layers"], m["d_model"], m["num_heads"],
                            m["num_kv_heads"], m["head_dim"], m["d_ff"],
                            m["vocab_size"])
    return {"embed": (V, D), "lm_head": (D, V), "final_norm": (D,),
            "attn_norm": (L, D), "wq": (L, D, H * dh), "wk": (L, D, K * dh),
            "wv": (L, D, K * dh), "wo": (L, H * dh, D), "ffn_norm": (L, D),
            "w1": (L, D, F), "w3": (L, D, F), "w2": (L, F, D)}


def init_weights(m: dict, key) -> Dict[str, jax.Array]:
    """Normal weights scaled by fan_in ** -0.5, norm gains 1; bfloat16."""
    out = {}
    shapes = weight_shapes(m)
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.bfloat16)
            continue
        fan_in = shape[-1] if name == "embed" else shape[-2]
        w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = (w * fan_in ** -0.5).astype(jnp.bfloat16)
    return out


def _q8(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, quant: bool):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if quant:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, g, eps):
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """Rotate-half RoPE; x (B, T, H, dh), pos (B, T)."""
    half = x.shape[-1] // 2
    inv = theta ** -(jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _attention(q, k, v, window, quant):
    """Causal attention within ``window``; q (B,T,H,dh), k/v (B,T,K,dh).
    Query blocks of ``Q_BLOCK`` bound the score buffer."""
    B, T, H, dh = q.shape
    g = H // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    kpos = jnp.arange(T)
    nb = -(-T // Q_BLOCK)
    qb = jnp.pad(q, ((0, 0), (0, nb * Q_BLOCK - T), (0, 0), (0, 0)))
    qb = qb.reshape(B, nb, Q_BLOCK, H, dh).transpose(1, 0, 2, 3, 4)

    def block(args):
        i, qi = args
        if quant:
            qi, kk, vv = _q8(qi, -1), _q8(k, -1), _q8(v, 1)
        else:
            kk, vv = k, v
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, kk,
                       precision=HIGHEST) * dh ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        rel = qpos[:, None] - kpos[None, :]
        s = jnp.where((rel >= 0) & (rel < window), s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vv, precision=HIGHEST)

    out = lax.map(block, (jnp.arange(nb), qb))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, nb * Q_BLOCK, H, dh)
    return out[:, :T]


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(h, w, i, *, m, quant):
    m = dict(m)
    B, T, D = h.shape
    H, K, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    window = m.get("sliding_window") or T
    lw = {n: w[n][i] for n in ("attn_norm", "wq", "wk", "wv", "wo",
                                "ffn_norm", "w1", "w3", "w2")}
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    x = _rms(h, lw["attn_norm"], eps)
    q = _rope(_mm(x, lw["wq"], quant).reshape(B, T, H, dh), pos, theta)
    k = _rope(_mm(x, lw["wk"], quant).reshape(B, T, K, dh), pos, theta)
    v = _mm(x, lw["wv"], quant).reshape(B, T, K, dh)
    a = _attention(q, k, v, window, quant).reshape(B, T, H * dh)
    h = h + _mm(a, lw["wo"], quant)
    x = _rms(h, lw["ffn_norm"], eps)
    return h + _mm(jax.nn.silu(_mm(x, lw["w1"], quant))
                   * _mm(x, lw["w3"], quant), lw["w2"], quant)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(h, at, w, *, m, quant):
    m = dict(m)
    hs = jnp.take_along_axis(h, at[..., None], axis=1)           # (B, n, D)
    return _mm(_rms(hs, w["final_norm"], m["rms_norm_eps"]), w["lm_head"],
               quant)


def logits_at(m: dict, w, tokens, at, quant: bool = False):
    """Float32 logits (B, n, V) at positions ``at`` (B, n) of the
    sequences ``tokens`` (B, T), computed layer by layer."""
    h = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(
        jnp.float32)
    frozen = tuple(sorted(m.items()))          # hashable, for jit
    for i in range(m["num_layers"]):
        h = _layer(h, w, i, m=frozen, quant=quant)
    return _head(h, jnp.asarray(at), w, m=frozen, quant=quant)
