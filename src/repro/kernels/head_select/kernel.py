"""FACADE head-selection kernel: cross-entropy of ALL k candidate heads in
one pass, vocab-blocked, without ever materializing [T, V] logits (let alone
k of them).

This is the paper's hot spot on TPU: step 2c evaluates k losses per node per
round; for LM heads the k x (T x D x V) logit matmuls dominate. The kernel
streams vocab blocks through VMEM with an online log-sum-exp (flash-style),
accumulating per-token running (m, l, gold) in scratch, and emits one
partial NLL sum per (head, token-block).

Grid: (K, T/bt, V/bv) with the vocab axis sequential ("arbitrary").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(f_ref, w_ref, lab_ref, out_ref, m_ref, l_ref, g_ref, *,
            block_v: int, n_v: int):
    vi = pl.program_id(2)

    @pl.when(vi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        g_ref[...] = jnp.zeros_like(g_ref)

    f = f_ref[...].astype(jnp.float32)                   # [bt, d]
    w = w_ref[0].astype(jnp.float32)                     # [d, bv]
    logits = jax.lax.dot_general(f, w, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    labs = lab_ref[...]                                  # [bt, 1]
    cols = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    gold_hit = labs == cols
    g_ref[...] += jnp.where(gold_hit, logits, 0.0).sum(
        axis=1, keepdims=True)

    m_prev = m_ref[...]                                  # [bt, 1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.exp(logits - m_new).sum(
        axis=1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(vi == n_v - 1)
    def _done():
        lse = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        nll = jnp.where(labs >= 0, lse - g_ref[...], 0.0)
        out_ref[...] = jnp.full(out_ref.shape, nll.sum(), out_ref.dtype)


def head_select_losses(features, heads, labels, *, block_t: int = 128,
                       block_v: int = 512, interpret: bool = False):
    """features [T,D], heads [K,D,V], labels [T] (−1 = padding)
    -> summed NLL per head [K] (divide by valid count outside)."""
    t, d = features.shape
    k, _, v = heads.shape
    block_t = min(block_t, t)
    block_v = min(block_v, v)
    assert t % block_t == 0 and v % block_v == 0
    n_t, n_v = t // block_t, v // block_v

    kernel = functools.partial(_kernel, block_v=block_v, n_v=n_v)
    partial = pl.pallas_call(
        kernel,
        grid=(k, n_t, n_v),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda ki, ti, vi: (ti, 0)),
            pl.BlockSpec((1, d, block_v), lambda ki, ti, vi: (ki, 0, vi)),
            pl.BlockSpec((block_t, 1), lambda ki, ti, vi: (ti, 0)),
        ],
        # one lane-dense (1, 128) row per (head, token-block), the partial
        # sum broadcast along it: a (1, 1) block breaks the (8, 128) tiling
        out_specs=pl.BlockSpec((1, 1, 1, 128),
                               lambda ki, ti, vi: (ki, ti, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, n_t, 1, 128), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(features, heads, labels[:, None].astype(jnp.int32))
    return partial[:, :, 0, 0].sum(axis=1)
