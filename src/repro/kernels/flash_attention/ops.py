"""Jit'd public wrapper for the flash-attention kernel (pass
``interpret=True`` to run it off the TPU)."""
from __future__ import annotations

import functools

import jax

from .kernel import flash_attention
from .ref import attention_ref  # noqa: F401  (the oracle, next to the op)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv", "interpret"))
def flash_attention_op(q, k, v, *, causal: bool = True, window: int = 0,
                       block_q: int = 128, block_kv: int = 128,
                       interpret: bool = False):
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_kv=block_kv,
                           interpret=interpret)

