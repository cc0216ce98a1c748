"""Pallas TPU chunkwise-parallel mLSTM.

The xLSTM matrix-memory recurrence has a chunkwise form: an intra-chunk
attention-like term (L x L matmuls — MXU work) plus an inter-chunk state
(C: dh x dh, n: dh, m: scalar) carried sequentially.  The XLA path (see
repro/models/ssm.py) scans chunks at HLO level, re-loading state from HBM
each step; this kernel keeps the carry in VMEM scratch across the
sequential grid dimension and fuses the decay/gate elementwise math into
the two MXU matmuls per chunk.

Grid: (B, H, n_chunks) with n_chunks 'arbitrary' (sequential).  The
chunk-local cumulative log-forget ``bc`` is precomputed outside (cheap,
XLA) so the kernel body is pure matmul + elementwise.

Outputs: hidden states (B, H, S, dh) and the final (C, n, m) state for
decode continuation.  Oracle: repro/kernels/ref.py::mlstm_chunk_ref via
the model-layer chunk function (itself tested against the sequential
recurrence).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, bc_ref, li_ref, h_ref, c_out_ref,
            n_out_ref, m_out_ref, c_scr, n_scr, m_scr, *,
            L: int, dh: int, n_chunks: int, scale: float):
    cb = pl.program_id(2)

    @pl.when(cb == 0)
    def _init():
        c_scr[...] = jnp.zeros_like(c_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG)

    q = q_ref[...].reshape(L, dh).astype(jnp.float32)
    k = k_ref[...].reshape(L, dh).astype(jnp.float32)
    v = v_ref[...].reshape(L, dh).astype(jnp.float32)
    b = bc_ref[...].reshape(L, 1)                  # chunk-local cum log f
    li = li_ref[...].reshape(L, 1)
    C_in = c_scr[...]
    n_in = n_scr[...]                              # (1, dh)
    m_in = m_scr[0, 0]

    # intra-chunk decay scores g[t,s] = b_t - b_s + li_s, s <= t
    g = b - b.reshape(1, L) + li.reshape(1, L)
    ti = lax.broadcasted_iota(jnp.int32, (L, L), 0)
    si = lax.broadcasted_iota(jnp.int32, (L, L), 1)
    g = jnp.where(ti >= si, g, NEG)
    m_intra = jnp.max(g, axis=1, keepdims=True)    # (L,1)
    m_t = jnp.maximum(m_in + b, m_intra)
    s = jnp.exp(g - m_t)
    qk = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32) * scale
    w = qk * s
    inter = jnp.exp(m_in + b - m_t) * scale        # (L,1)
    num = lax.dot_general(w, v, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32) \
        + lax.dot_general(q * inter, C_in, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    den = jnp.sum(w, axis=1, keepdims=True) \
        + lax.dot_general(q * inter, n_in.reshape(dh, 1),
                          (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))
    h_ref[...] = h.reshape(h_ref.shape).astype(h_ref.dtype)

    # state update
    bL = b[L - 1, 0]
    dec = bL - b + li                               # (L,1)
    m_out = jnp.maximum(m_in + bL, jnp.max(dec))
    carry = jnp.exp(m_in + bL - m_out)
    kvc = jnp.exp(dec - m_out)                      # (L,1)
    C_out = C_in * carry + lax.dot_general(
        k * kvc, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    n_out = n_in * carry + jnp.sum(k * kvc, axis=0, keepdims=True)
    c_scr[...] = C_out
    n_scr[...] = n_out
    m_scr[...] = jnp.full_like(m_scr, m_out)

    @pl.when(cb == n_chunks - 1)
    def _emit_state():
        c_out_ref[...] = C_out.reshape(c_out_ref.shape)
        n_out_ref[...] = n_out.reshape(n_out_ref.shape)
        m_out_ref[...] = jnp.full(m_out_ref.shape, m_out, jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunk(q, k, v, li, lf, *, chunk: int = 128,
                interpret: bool = False):
    """q/k/v: (B, H, S, dh) ; li/lf: (B, H, S) log gates.
    Returns (h (B,H,S,dh) f32, (C (B,H,dh,dh), n (B,H,dh), m (B,H)))."""
    B, H, S, dh = q.shape
    L = min(chunk, S)
    if S % L:
        L = S
    n_chunks = S // L
    # chunk-local cumulative log-forget, precomputed in XLA
    bc = jnp.cumsum(lf.reshape(B, H, n_chunks, L), axis=-1) \
        .reshape(B, H, S, 1)
    li4 = li.reshape(B, H, S, 1)
    kernel = functools.partial(_kernel, L=L, dh=dh, n_chunks=n_chunks,
                               scale=dh ** -0.5)
    h, C, n, m = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, L, dh), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, dh), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, dh), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda b, h, c: (b, h, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, dh), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, dh, dh), lambda b, h, c: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, dh), lambda b, h, c: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, H, dh, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, bc, li4)
    return h, (C, n.reshape(B, H, dh), m.reshape(B, H))
