"""jit'd public wrappers around the Pallas kernels.

``attention`` dispatches to the flash kernel on TPU (or when forced via
``use_kernel=True``) and to the pure-jnp reference otherwise.  The
kernel runs in Pallas interpret mode only when the caller passes
``interpret=True`` (the CPU kernel tests); off the TPU a forced kernel
without it fails to lower instead of silently running interpreted.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm_chunk import mlstm_chunk


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              use_kernel: bool = False, interpret: bool = False,
              block_q: int = 128, block_k: int = 128):
    if use_kernel or on_tpu():
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    return ref.attention_ref(q, k, v, causal=causal, window=window)
