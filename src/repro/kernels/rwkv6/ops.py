"""Jit'd wrapper for the RWKV6 wkv kernel (pass ``interpret=True`` to run
it off the TPU)."""
from __future__ import annotations

import functools

import jax

from .kernel import wkv_kernel
from .ref import wkv_ref  # noqa: F401  (the oracle, next to the op)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def wkv_op(r, k, v, w, u, *, block_t: int = 64, interpret: bool = False):
    return wkv_kernel(r, k, v, w, u, block_t=block_t, interpret=interpret)

