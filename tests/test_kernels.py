"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode).

Assignment: "For each Pallas kernel, sweep shapes/dtypes and
assert_allclose against the ref.py pure-jnp oracle." That the kernels
compile for the chip is ``tests/test_tpu_compile.py``'s business.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa
from repro.kernels.head_select import ops as hs
from repro.kernels.head_select.ref import head_losses_ref
from repro.kernels.rwkv6 import ops as rw


# --------------------------------------------------------------------------
FA_SHAPES = [
    # (B, Hq, Hkv, S, D)
    (1, 4, 4, 128, 64),      # MHA
    (2, 8, 2, 256, 64),      # GQA group 4
    (1, 4, 1, 128, 128),     # MQA, wide head
    (2, 2, 2, 512, 64),      # longer seq
]


@pytest.mark.parametrize("b,hq,hkv,s,d", FA_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, hq, hkv, s, d, dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = (0.3 * jax.random.normal(ks[0], (b, hq, s, d))).astype(dtype)
    k = (0.3 * jax.random.normal(ks[1], (b, hkv, s, d))).astype(dtype)
    v = (0.3 * jax.random.normal(ks[2], (b, hkv, s, d))).astype(dtype)
    out = fa.flash_attention_op(q, k, v, interpret=True)
    ref = fa.attention_ref(q, k, v)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    b, hq, hkv, s, d = 1, 2, 2, 256, 64
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    q = 0.3 * jax.random.normal(ks[0], (b, hq, s, d))
    k = 0.3 * jax.random.normal(ks[1], (b, hkv, s, d))
    v = 0.3 * jax.random.normal(ks[2], (b, hkv, s, d))
    out = fa.flash_attention_op(q, k, v, window=window, interpret=True,
                                block_q=64, block_kv=64)
    ref = fa.attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("block_q,block_kv", [(64, 64), (128, 256)])
def test_flash_attention_block_shape_invariance(block_q, block_kv):
    b, hq, hkv, s, d = 1, 2, 1, 512, 64
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 3)
    q = 0.3 * jax.random.normal(ks[0], (b, hq, s, d))
    k = 0.3 * jax.random.normal(ks[1], (b, hkv, s, d))
    v = 0.3 * jax.random.normal(ks[2], (b, hkv, s, d))
    out = fa.flash_attention_op(q, k, v, block_q=block_q, block_kv=block_kv,
                                interpret=True)
    ref = fa.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


# --------------------------------------------------------------------------
HS_SHAPES = [
    # (K, T, D, V)
    (2, 128, 64, 256),
    (3, 256, 64, 512),
    (5, 128, 128, 1024),
]


@pytest.mark.parametrize("k,t,d,v", HS_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_head_select_matches_ref(k, t, d, v, dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    feats = (0.5 * jax.random.normal(ks[0], (t, d))).astype(dtype)
    heads = (0.05 * jax.random.normal(ks[1], (k, d, v))).astype(dtype)
    labels = jax.random.randint(ks[2], (t,), 0, v, dtype=jnp.int32)
    mask = (jax.random.uniform(ks[2], (t,)) > 0.1).astype(jnp.float32)
    got = hs.facade_head_losses(feats, heads, labels, mask, interpret=True)
    want = head_losses_ref(feats, heads, labels, mask)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)
    # argmin (the FACADE selection decision) must agree exactly
    assert int(jnp.argmin(got)) == int(jnp.argmin(want))


def test_head_select_negative_labels_excluded():
    k, t, d, v = 2, 64, 32, 128
    key = jax.random.PRNGKey(3)
    feats = 0.5 * jax.random.normal(key, (t, d))
    heads = 0.05 * jax.random.normal(key, (k, d, v))
    labels = jax.random.randint(key, (t,), 0, v, dtype=jnp.int32)
    labels = labels.at[:10].set(-1)
    got = hs.facade_head_losses(feats, heads, labels, None, interpret=True)
    want = head_losses_ref(feats, heads, labels, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


# --------------------------------------------------------------------------
RW_SHAPES = [
    # (B, T, H, hd)
    (1, 64, 1, 32),
    (2, 128, 2, 32),
    (1, 256, 4, 64),
]


@pytest.mark.parametrize("b,t,h,hd", RW_SHAPES)
def test_rwkv6_wkv_matches_ref(b, t, h, hd):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    r = 0.3 * jax.random.normal(ks[0], (b, t, h, hd))
    k = 0.3 * jax.random.normal(ks[1], (b, t, h, hd))
    v = 0.3 * jax.random.normal(ks[2], (b, t, h, hd))
    w = jnp.exp(-jnp.exp(0.3 * jax.random.normal(ks[3], (b, t, h, hd))))
    u = 0.3 * jax.random.normal(ks[4], (h, hd))
    y1, s1 = rw.wkv_op(r, k, v, w, u, interpret=True)
    y2, s2 = rw.wkv_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("block_t", [16, 64])
def test_rwkv6_block_invariance(block_t):
    b, t, h, hd = 1, 128, 2, 32
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 5)
    r = 0.3 * jax.random.normal(ks[0], (b, t, h, hd))
    k = 0.3 * jax.random.normal(ks[1], (b, t, h, hd))
    v = 0.3 * jax.random.normal(ks[2], (b, t, h, hd))
    w = jnp.exp(-jnp.exp(0.3 * jax.random.normal(ks[3], (b, t, h, hd))))
    u = 0.3 * jax.random.normal(ks[4], (h, hd))
    y1, _ = rw.wkv_op(r, k, v, w, u, block_t=block_t, interpret=True)
    y2, _ = rw.wkv_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)
