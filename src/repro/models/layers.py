"""Common neural-net layers as pure functions (init + apply).

Convention: params are nested dicts of jnp arrays; every ``init_*`` takes a
PRNG key and returns the param subtree; every ``apply``-style function takes
(params, inputs). Matmuls run in the param dtype (bf16 on TPU); norms,
softmax and losses accumulate in fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# init helpers
def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else (1.0 / jnp.sqrt(d_in))
    w = jax.random.normal(key, (d_in, d_out), dtype=jnp.float32) * scale
    return w.astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    w = jax.random.normal(key, (vocab, d), dtype=jnp.float32) * 0.02
    return w.astype(dtype)


# --------------------------------------------------------------------------
# norms
def rms_norm(x, gamma, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return out.astype(x.dtype)


def group_norm(x, gamma, beta, groups: int, eps: float = 1e-5):
    """GroupNorm over the channel (last) axis of NHWC activations."""
    xf = x.astype(jnp.float32)
    c = x.shape[-1]
    g = xf.reshape(x.shape[:-1] + (groups, c // groups))
    # NHWC: normalize over (H, W, channels-in-group)
    if x.ndim == 4:
        mu = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
        var = jnp.var(g, axis=(1, 2, 4), keepdims=True)
    else:
        mu = jnp.mean(g, axis=-1, keepdims=True)
        var = jnp.var(g, axis=-1, keepdims=True)
    g = (g - mu) * jax.lax.rsqrt(var + eps)
    out = g.reshape(x.shape)
    out = out * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# rotary embeddings
def rope_freqs(positions, dim: int, theta: float):
    """cos/sin tables for given integer positions. positions [...,S]."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv  # [..., S, dim/2]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x [..., S, H, D]; cos/sin [..., S, D/2] broadcast over heads."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    c = cos[..., None, :]
    s = sin[..., None, :]
    xf1 = x1.astype(jnp.float32)
    xf2 = x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * c - xf2 * s, xf2 * c + xf1 * s], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# MLPs
def init_swiglu(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff, dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype),
    }


def swiglu(params, x):
    g = x @ params["w_gate"]
    u = x @ params["w_up"]
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return h @ params["w_down"]


def init_gelu_mlp(key, d_model: int, d_ff: int, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "w_in": dense_init(k1, d_model, d_ff, dtype),
        "b_in": jnp.zeros((d_ff,), dtype),
        "w_out": dense_init(k2, d_ff, d_model, dtype),
        "b_out": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp(params, x):
    h = x @ params["w_in"] + params["b_in"]
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return h @ params["w_out"] + params["b_out"]


# --------------------------------------------------------------------------
# losses
def chunked_softmax_xent(logits_fn, features, w_head, labels, mask,
                         chunk: int = 2048):
    """Cross-entropy over a huge vocab without materializing all logits twice.

    features [B,S,D] (fp any), w_head [D,V]; labels [B,S]; mask [B,S] float.
    Computes logits in fp32 via one matmul but reduces immediately; for
    memory-constrained cases the Pallas head_select kernel does true
    vocab-chunked CE. Returns mean loss over masked tokens.
    """
    del logits_fn, chunk
    logits = (features.astype(jnp.float32) @ w_head.astype(jnp.float32))
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


def softmax_xent(logits, labels, mask=None):
    """Standard CE; logits [..., V] fp-any, labels int, mask float."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return nll.mean()
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
