"""Pallas TPU flash attention (causal / sliding-window / GQA).

Why a kernel here: the XLA attention path materializes (Tq, Tk) logits in
fp32 — the dominant memory-roofline term for every train/prefill cell
(see EXPERIMENTS.md §Roofline) — and cannot skip fully-masked key blocks,
so sliding-window archs (danube, gemma locals, hymba) pay full quadratic
traffic.  The kernel keeps the online-softmax state in VMEM, streams KV
blocks through VMEM tiles, and skips key blocks that the causal/window
mask kills entirely: O(S*W) instead of O(S^2) for windowed layers.

TPU mapping: grid = (batch, q_heads, q_blocks, kv_blocks) with the
kv_blocks dimension 'arbitrary' (sequential) so the (m, l, acc) online
state lives in VMEM scratch across kv iterations; MXU-aligned tiles
(block sizes multiples of 128 on the lane dim); fp32 accumulation.

Validated against ref.py (pure jnp) in interpret mode on CPU — the
container has no TPU; `interpret=True` executes the same kernel body.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq: int, bk: int, n_kv: int, causal: bool, window: int,
            scale: float):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qb * bq
    k_start = kb * bk
    # block-level skip: any (q, k) pair alive in this tile?
    # causal: need k_start <= q_end;  window: need k_end >= q_start-window+1
    q_end = q_start + bq - 1
    k_end = k_start + bk - 1
    alive = jnp.asarray(True)
    if causal:
        alive = k_start <= q_end
        if window > 0:
            alive = jnp.logical_and(alive, k_end >= q_start - window + 1)

    @pl.when(alive)
    def _body():
        dh = q_ref.shape[-1]
        q = q_ref[...].reshape(bq, dh).astype(jnp.float32)
        k = k_ref[...].reshape(bk, dh).astype(jnp.float32)
        v = v_ref[...].reshape(bk, dh).astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        q_idx = q_start + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_idx = k_start + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            rel = q_idx - k_idx
            mask = rel >= 0
            if window > 0:
                mask = jnp.logical_and(mask, rel < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                          # (bq, 1)
        m_cur = jnp.maximum(m_prev[:, 0], jnp.max(s, axis=1))[:, None]
        alpha = jnp.exp(m_prev - m_cur)              # (bq, 1)
        p = jnp.exp(s - m_cur)                       # (bq, bk)
        l_cur = l_scr[...] * alpha + jnp.sum(p, axis=1)[:, None]
        acc = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_cur
        l_scr[...] = l_cur
        acc_scr[...] = acc

    @pl.when(kb == n_kv - 1)
    def _finish():
        l = l_scr[...]
        o = acc_scr[...] / jnp.maximum(l, 1e-30)
        o_ref[...] = o.reshape(o_ref.shape).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Sq, Hq, dh); k/v: (B, Sk, Hkv, dh); GQA via Hq % Hkv == 0.
    window=0 means unbounded (full causal); window=w keeps k in
    (q-w, q].  Returns (B, Sq, Hq, dh) in q.dtype."""
    B, Sq, Hq, dh = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    g = Hq // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    n_q, n_kv = Sq // bq, Sk // bk
    scale = dh ** -0.5

    # (B, H, S, dh) layout for clean 2-D tiles
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_kernel, bq=bq, bk=bk, n_kv=n_kv,
                               causal=causal, window=window, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b, h, qb, kb: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, qb, kb, g=g: (b, h // g, kb, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, qb, kb, g=g: (b, h // g, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda b, h, qb, kb: (b, h, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
