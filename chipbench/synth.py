"""The cell's data, made on the device from the seed.

A port of the program's ``data/synthetic.py`` to ``jax.random``: class
prototypes are smooth random patterns (a coarse normal grid upsampled 4x
and box-blurred), each image is its class prototype circularly shifted
by up to ``jitter`` pixels plus Gaussian noise, clipped to [-2, 2], and
each cluster sees its images through its own transform (rotations, the
paper's feature skew, Sec. V-A). Labels are uniform per node and shuffled
per node; test sets are per cluster. Images are made in fixed-size blocks
so that generation never holds more than a few of them on the device, and
returned as the program's ``ClusteredDataset`` of host arrays, the form a
user hands to ``run_experiment``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 2048          # images made per device call


def _blur(x, axis: int):
    """5-tap box blur along ``axis`` with zero padding ('same' length)."""
    pad = [(0, 0)] * x.ndim
    pad[axis] = (2, 2)
    xp = jnp.pad(x, pad)
    length = x.shape[axis]
    return sum(jax.lax.slice_in_dim(xp, i, i + length, axis=axis)
               for i in range(5)) / 5.0


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def prototypes(key, n_classes: int, size: int, channels: int):
    coarse = size // 4
    protos = jax.random.normal(key, (n_classes, coarse, coarse, channels))
    up = jnp.repeat(jnp.repeat(protos, 4, axis=1), 4, axis=2)
    for ax in (1, 2):
        up = _blur(up, ax)
    return up / (jnp.abs(up).max(axis=(1, 2, 3), keepdims=True) + 1e-9)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def node_labels(key, n_nodes: int, n_classes: int, per_class: int):
    """[n_nodes, n_classes * per_class]: every class ``per_class`` times,
    in a shuffled order of each node's own."""
    base = jnp.repeat(jnp.arange(n_classes, dtype=jnp.int32), per_class)
    keys = jax.random.split(key, n_nodes)
    return jax.vmap(lambda k: jax.random.permutation(k, base))(keys)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def images(key, protos, labels, quarter_turns: int, noise: float,
           jitter: int):
    """Images for ``labels`` [M]: prototype, shift, noise, clip, rotate."""
    k_shift, k_noise = jax.random.split(key)
    x = protos[labels]
    shifts = jax.random.randint(k_shift, (labels.shape[0], 2), -jitter,
                                jitter + 1)
    x = jax.vmap(lambda img, s: jnp.roll(img, (s[0], s[1]),
                                         axis=(0, 1)))(x, shifts)
    x = x + noise * jax.random.normal(k_noise, x.shape)
    x = jnp.clip(x, -2.0, 2.0)
    return jnp.rot90(x, k=quarter_turns, axes=(1, 2))


def quarter_turns(transform: str) -> int:
    if transform in ("rot0", "none"):
        return 0
    if transform.startswith("rot") and int(transform[3:]) % 90 == 0:
        return (int(transform[3:]) // 90) % 4
    raise ValueError(f"unsupported transform {transform!r}")


def _fill(out, key, protos, labels, turns, noise, jitter):
    """Write images for the flat ``labels`` into ``out`` [M, ...], one
    fixed-size block per device call (the last block padded)."""
    m = labels.shape[0]
    for i, lo in enumerate(range(0, m, BLOCK)):
        blk = labels[lo:lo + BLOCK]
        pad = BLOCK - blk.shape[0]
        if pad:
            blk = np.concatenate([blk, np.zeros(pad, np.int32)])
        x = images(jax.random.fold_in(key, i), protos, jnp.asarray(blk),
                   turns, noise, jitter)
        out[lo:lo + BLOCK - pad] = np.asarray(x)[:BLOCK - pad]


def make_dataset(cell: dict, seed: int):
    """The cell's ``ClusteredDataset`` from a 31-bit ``seed``."""
    from repro.data.synthetic import ClusteredDataset, SynthSpec

    model = cell["model"]
    size, ch, n_cls = model["image_size"], model["channels"], \
        model["n_classes"]
    sizes, transforms = cell["clusters"], cell["transforms"]
    noise, jitter = float(cell["noise"]), int(cell["jitter"])
    k_proto, k_train, k_test = jax.random.split(jax.random.PRNGKey(seed), 3)
    protos = prototypes(k_proto, n_cls, size, ch)

    per_node = n_cls * cell["train_per_class"]
    n = sum(sizes)
    train_y = np.asarray(node_labels(k_train, n, n_cls,
                                     cell["train_per_class"]))
    train_x = np.empty((n, per_node, size, size, ch), np.float32)
    flat = train_x.reshape(n * per_node, size, size, ch)
    test_x, test_y = [], []
    start = 0
    for c, (count, tf) in enumerate(zip(sizes, transforms)):
        turns = quarter_turns(tf)
        rows = slice(start * per_node, (start + count) * per_node)
        _fill(flat[rows], jax.random.fold_in(k_train, c), protos,
              train_y[start:start + count].reshape(-1), turns, noise, jitter)
        start += count
        y = np.repeat(np.arange(n_cls, dtype=np.int32),
                      cell["test_per_class"])
        x = np.empty((y.shape[0], size, size, ch), np.float32)
        _fill(x, jax.random.fold_in(k_test, c), protos, y, turns, noise,
              jitter)
        test_x.append(x)
        test_y.append(y)
    node_cluster = np.repeat(np.arange(len(sizes), dtype=np.int32), sizes)
    spec = SynthSpec(n_classes=n_cls, image_size=size, channels=ch,
                     samples_per_class=cell["train_per_class"],
                     test_per_class=cell["test_per_class"], noise=noise,
                     jitter=jitter, seed=seed)
    return ClusteredDataset(train_x=train_x, train_y=train_y.astype(np.int32),
                            test_x=test_x, test_y=test_y,
                            node_cluster=node_cluster, spec=spec,
                            transforms=tuple(transforms))
