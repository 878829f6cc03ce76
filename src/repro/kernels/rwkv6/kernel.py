"""RWKV6 wkv recurrence — Pallas TPU kernel.

The HBM-resident lax.scan implementation rereads and rewrites the
[hd x hd] per-head state every timestep. This kernel keeps the state in
VMEM scratch for an entire time block (the roofline win: state traffic
drops from O(T * hd^2) HBM bytes to O(T/block * hd^2)), iterating time
blocks sequentially in the grid.

    y_t = r_t @ (S + diag(u) k_t^T v_t);  S <- diag(w_t) S + k_t^T v_t

Grid: (B*H, T/block_t) with time the sequential axis. The final state is
emitted for chaining into decode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_out_ref, s_ref, *,
            block_t: int, n_t: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0]                                         # [1, hd]

    # every per-step operand is a [1, hd] row: Mosaic has no matmul with a
    # 1-D operand, and the row's transpose gives the column the outer
    # products need
    def step(t, _):
        r = r_ref[0, pl.ds(t, 1), :]                     # [1, hd]
        k = k_ref[0, pl.ds(t, 1), :]
        v = v_ref[0, pl.ds(t, 1), :]
        w = w_ref[0, pl.ds(t, 1), :]
        s = s_ref[...]                                   # [hd, hd]
        bonus = jnp.sum(r * u * k)                       # scalar
        y = jnp.dot(r, s, preferred_element_type=jnp.float32) + bonus * v
        y_ref[0, pl.ds(t, 1), :] = y.astype(y_ref.dtype)
        s_ref[...] = w.T * s + k.T * v
        return 0

    jax.lax.fori_loop(0, block_t, step, 0)

    @pl.when(ti == n_t - 1)
    def _done():
        s_out_ref[0] = s_ref[...]


def wkv_kernel(r, k, v, w, u, *, block_t: int = 64, interpret: bool = False):
    """r,k,v,w [B,S,H,hd] fp32; u [H,hd] -> (y [B,S,H,hd], S_f [B,H,hd,hd])."""
    b, s, h, hd = r.shape
    block_t = min(block_t, s)
    assert s % block_t == 0
    n_t = s // block_t

    def flat(x):  # [B,S,H,hd] -> [B*H, S, hd]
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, hd)

    rf, kf, vf, wf = map(flat, (r, k, v, w))
    # u rides as [B*H, 1, hd]: a (1, hd) block of a 2-D array breaks the
    # (8, 128) tiling, a (1, 1, hd) block spans the last two dims whole
    uf = jnp.broadcast_to(u[None], (b, h, hd)).reshape(b * h, 1, hd)

    kernel = functools.partial(_kernel, block_t=block_t, n_t=n_t)
    y, s_f = pl.pallas_call(
        kernel,
        grid=(b * h, n_t),
        in_specs=[
            pl.BlockSpec((1, block_t, hd), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, block_t, hd), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, block_t, hd), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, block_t, hd), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, 1, hd), lambda bh, ti: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, hd), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, hd, hd), lambda bh, ti: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, hd), jnp.float32),
            jax.ShapeDtypeStruct((b * h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, wf, uf)
    y = y.reshape(b, h, s, hd).transpose(0, 2, 1, 3)
    return y, s_f.reshape(b, h, hd, hd)
