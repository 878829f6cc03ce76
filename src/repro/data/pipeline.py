"""Deterministic per-node batch sampling for the DL training loop.

``sample_round_batches`` draws, for every node, H local-step batches of size
B (paper: H=tau local steps on batches of B=8) — returned stacked
[n, H, B, ...] so one jit'd round consumes the whole round's data.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import scope


@scope("sample_batches")
def sample_round_batches(key, train_x, train_y, h: int, b: int):
    """train_x [n, N, ...], train_y [n, N] -> batches pytree [n, H, B, ...]."""
    n, per_node = train_x.shape[0], train_x.shape[1]
    idx = jax.random.randint(key, (n, h, b), 0, per_node)
    gx = jax.vmap(lambda x, i: x[i])(train_x, idx.reshape(n, h * b))
    gy = jax.vmap(lambda y, i: y[i])(train_y, idx.reshape(n, h * b))
    return {
        "x": gx.reshape((n, h, b) + train_x.shape[2:]),
        "y": gy.reshape(n, h, b),
    }


def sample_round_token_batches(key, train_tokens, h: int, b: int):
    """train_tokens [n, N, S] -> {tokens, labels, mask} with [n,H,B,S-1]."""
    n, per_node, s = train_tokens.shape
    idx = jax.random.randint(key, (n, h, b), 0, per_node)
    g = jax.vmap(lambda x, i: x[i])(train_tokens, idx.reshape(n, h * b))
    g = g.reshape(n, h, b, s)
    return {
        "tokens": g[..., :-1],
        "labels": g[..., 1:],
        "mask": jnp.ones((n, h, b, s - 1), jnp.float32),
    }


def padded_eval_batches(x: np.ndarray, batch: int):
    """[N, ...] -> (batches [nb, B, ...], mask [nb, B] float32).

    Shape-stable eval batching: the trailing partial batch is zero-padded
    and masked out instead of yielded ragged, so the evaluator can jit/vmap
    over a fixed [nb, B, ...] block (one compile per test-set shape).
    """
    x = np.asarray(x)
    n = x.shape[0]
    nb = max(1, -(-n // batch))
    pad = nb * batch - n
    mask = np.ones((n,), np.float32)
    if pad:
        x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
        mask = np.concatenate([mask, np.zeros((pad,), np.float32)])
    return (x.reshape((nb, batch) + x.shape[1:]), mask.reshape(nb, batch))
