"""The paper's experimental models: GN-LeNet (CIFAR-10/Imagenette runs) and
ResNet8 (Flickr-Mammals runs), both with GroupNorm as in Hsieh et al. [41].

GN-LeNet is the published network (Hsieh et al., arXiv:1910.00189; the
FACADE authors' decentralizepy ``GN_LeNet``): three 5x5 "same"
convolutions of widths (w, w, 2w), each followed by GroupNorm, ReLU and a
2x2 max-pool, then one FC; at w = 32 on 32x32 images, 32/32/64 channels
and an FC of 1,024 -> 10.

FACADE head split (paper Sec. V-A "Models"):
  * GN-LeNet  — head = final fully-connected layer.
  * ResNet8   — head = last two basic blocks + final FC.

Each network is written once over a :class:`Layers` set. ``NODE`` runs one
node's model on ``[B, H, W, C]`` activations. ``PACKED`` runs ``n``
node-stacked models at once on ``[B, H, W, n*C]`` activations, node-major in
the channels, so that a TPU keeps whole 128-lane vectors of channels in HBM
where one node's 16 to 64 channels would each be padded to 128.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from . import layers
from .base import CNNConfig


def conv_init(key, kh, kw, cin, cout, dtype):
    fan_in = kh * kw * cin
    w = jax.random.normal(key, (kh, kw, cin, cout)) * jnp.sqrt(2.0 / fan_in)
    return w.astype(dtype)


def conv2d(x, w, stride: int = 1):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def maxpool2(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


LANES = 128    # a TPU vector register's lanes, and its HBM tiles' minor width
PAD_FLOOR = 8  # group nodes only where one node alone pads its lanes 8x


def pack_nodes(x):
    """``[n, ..., C]`` -> ``[..., n*C]``: the node axis moved into the
    channels, node-major."""
    n, c = x.shape[0], x.shape[-1]
    return jnp.moveaxis(x, 0, -2).reshape(x.shape[1:-1] + (n * c,))


def pack_group(n: int, cout: int) -> int:
    """Nodes to a group of :func:`_conv2d_nodes` for ``n`` nodes of
    ``cout`` output channels."""
    return math.gcd(n, LANES // cout) if cout * PAD_FLOOR <= LANES else 1


def _conv2d_nodes(x, w, stride: int = 1):
    """``x [B,H,W,n*Cin]`` by node-stacked ``w [n,kh,kw,Cin,Cout]``: one
    grouped convolution of ``g`` nodes to a group, over block-diagonal
    weights (each node's outputs sum its own inputs and exact zeros).

    XLA lays a grouped convolution out with each group's channels minor,
    so one 16-channel node to a group pads them 8x in HBM; ``g = gcd(n,
    LANES // Cout)`` such nodes fill the lanes, for ``g`` times the MXU's
    multiply-adds. Wider convolutions keep one node to a group: grouping
    them as well ran faster still, but its 10 MB more TPU code, resident
    in HBM, raised peak device memory by 2% (PERF.md, section 6)."""
    n, kh, kw, ci, co = w.shape
    g = pack_group(n, co)
    w = jnp.moveaxis(w.reshape(n // g, g, kh, kw, ci, co), (0, 1), (3, 4))
    eye = jnp.eye(g, dtype=bool)[:, None, None, :, None]
    w = jnp.where(eye, w[:, :, None], 0).reshape(kh, kw, g * ci, n * co)
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=n // g)


def _group_norm_nodes(x, gamma, beta, groups: int, eps: float = 1e-5):
    """:func:`layers.group_norm` per node on ``x [B,H,W,n*C]``, with
    ``gamma``/``beta`` ``[n, C]``. The statistics sum over (H, W) first,
    per channel, so every full-size operand keeps ``n*C`` as its minor
    dimension; only the ``[B, n*C]`` sums are split into groups."""
    n, c = gamma.shape
    xf = x.astype(jnp.float32)
    count = x.shape[1] * x.shape[2] * (c // groups)

    def per_group(s):           # [B, n*C] -> group mean on each channel
        g = s.reshape(-1, n, groups, c // groups)
        g = jnp.broadcast_to(g.sum(-1, keepdims=True) / count, g.shape)
        return g.reshape(-1, 1, 1, n * c)

    d = xf - per_group(xf.sum(axis=(1, 2)))
    var = per_group((d * d).sum(axis=(1, 2)))
    out = d * jax.lax.rsqrt(var + eps)
    out = (out * gamma.reshape(-1).astype(jnp.float32)
           + beta.reshape(-1).astype(jnp.float32))
    return out.astype(x.dtype)


def _dense_nodes(x, w, b):
    """Per-node FC: ``x [B, ..., n*C]`` (channels last, node-major) by
    ``w [n, F, O]`` -> logits ``[n, B, O]``; each node's features are
    flattened in ``NODE``'s order, (..., C)."""
    n = w.shape[0]
    x = x.reshape(x.shape[:-1] + (n, x.shape[-1] // n))
    x = jnp.moveaxis(x, -2, 1).reshape(x.shape[0], n, -1)
    return jnp.einsum("bnf,nfo->nbo", x, w) + b[:, None, :]


class Layers(NamedTuple):
    """The layer operations a network is written over."""
    conv: Callable      # (x, w, stride) -> x
    norm: Callable      # (x, gamma, beta, groups) -> x
    flat: Callable      # conv features -> what ``dense`` takes
    dense: Callable     # (x, w, b) -> logits


NODE = Layers(conv2d, layers.group_norm,
              lambda x: x.reshape(x.shape[0], -1),
              lambda x, w, b: x @ w + b)
PACKED = Layers(_conv2d_nodes, _group_norm_nodes, lambda x: x, _dense_nodes)


def _gn_params(c, dtype):
    return {"g": jnp.ones((c,), dtype), "b": jnp.zeros((c,), dtype)}


# ==========================================================================
# GN-LeNet
def init_lenet(cfg: CNNConfig, key):
    w, k = cfg.width, 5
    ks = jax.random.split(key, 4)
    feat = (cfg.image_size // 8) ** 2 * 2 * w
    return {
        "conv1": {"w": conv_init(ks[0], k, k, cfg.channels, w, cfg.dt),
                  "gn": _gn_params(w, cfg.dt)},
        "conv2": {"w": conv_init(ks[1], k, k, w, w, cfg.dt),
                  "gn": _gn_params(w, cfg.dt)},
        "conv3": {"w": conv_init(ks[2], k, k, w, 2 * w, cfg.dt),
                  "gn": _gn_params(2 * w, cfg.dt)},
        "fc": {"w": layers.dense_init(ks[3], feat, cfg.n_classes, cfg.dt),
               "b": jnp.zeros((cfg.n_classes,), cfg.dt)},
    }


def lenet_features(cfg: CNNConfig, params, x, nn: Layers = NODE):
    """x [B,H,W,C] -> flattened conv features (the FACADE *core*)."""
    for name in ("conv1", "conv2", "conv3"):
        p = params[name]
        x = nn.conv(x, p["w"])
        x = nn.norm(x, p["gn"]["g"], p["gn"]["b"], cfg.groups)
        x = jax.nn.relu(x)
        x = maxpool2(x)
    return nn.flat(x)


def lenet_head(cfg: CNNConfig, head_params, feats, nn: Layers = NODE):
    return nn.dense(feats, head_params["fc"]["w"], head_params["fc"]["b"])


def lenet_forward(cfg: CNNConfig, params, x, nn: Layers = NODE):
    return lenet_head(cfg, {"fc": params["fc"]},
                      lenet_features(cfg, params, x, nn), nn)


LENET_HEAD_KEYS = ("fc",)


# ==========================================================================
# ResNet8 (GN): stem + 3 basic blocks (16,32,64) + FC
def _init_block(key, cin, cout, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"conv1": conv_init(k1, 3, 3, cin, cout, dtype),
         "gn1": _gn_params(cout, dtype),
         "conv2": conv_init(k2, 3, 3, cout, cout, dtype),
         "gn2": _gn_params(cout, dtype)}
    if cin != cout:
        p["proj"] = conv_init(k3, 1, 1, cin, cout, dtype)
    return p


def _block(cfg: CNNConfig, p, x, stride: int, nn: Layers):
    h = nn.conv(x, p["conv1"], stride)
    h = jax.nn.relu(nn.norm(h, p["gn1"]["g"], p["gn1"]["b"], cfg.groups))
    h = nn.conv(h, p["conv2"])
    h = nn.norm(h, p["gn2"]["g"], p["gn2"]["b"], cfg.groups)
    if "proj" in p:
        x = nn.conv(x, p["proj"], stride)
    elif stride != 1:
        x = x[:, ::stride, ::stride]
    return jax.nn.relu(h + x)


def init_resnet8(cfg: CNNConfig, key):
    w = cfg.width // 2  # stem width 16 for width=32
    ks = jax.random.split(key, 5)
    return {
        "stem": {"w": conv_init(ks[0], 3, 3, cfg.channels, w, cfg.dt),
                 "gn": _gn_params(w, cfg.dt)},
        "block1": _init_block(ks[1], w, w, cfg.dt),
        "block2": _init_block(ks[2], w, 2 * w, cfg.dt),
        "block3": _init_block(ks[3], 2 * w, 4 * w, cfg.dt),
        "fc": {"w": layers.dense_init(ks[4], 4 * w, cfg.n_classes, cfg.dt),
               "b": jnp.zeros((cfg.n_classes,), cfg.dt)},
    }


def resnet8_features(cfg: CNNConfig, params, x, nn: Layers = NODE):
    """Core: stem + block1 (head owns block2, block3, fc)."""
    p = params["stem"]
    x = jax.nn.relu(nn.norm(nn.conv(x, p["w"]), p["gn"]["g"], p["gn"]["b"],
                            cfg.groups))
    return _block(cfg, params["block1"], x, 1, nn)


def resnet8_head(cfg: CNNConfig, head_params, feats, nn: Layers = NODE):
    h = _block(cfg, head_params["block2"], feats, 2, nn)
    h = _block(cfg, head_params["block3"], h, 2, nn)
    h = h.mean(axis=(1, 2))
    return nn.dense(h, head_params["fc"]["w"], head_params["fc"]["b"])


def resnet8_forward(cfg: CNNConfig, params, x, nn: Layers = NODE):
    head = {k: params[k] for k in RESNET8_HEAD_KEYS}
    return resnet8_head(cfg, head, resnet8_features(cfg, params, x, nn), nn)


RESNET8_HEAD_KEYS = ("block2", "block3", "fc")


# ==========================================================================
# uniform API used by the FACADE trainer
def init_params(cfg: CNNConfig, key):
    return init_lenet(cfg, key) if cfg.kind == "lenet" else init_resnet8(cfg, key)


def features(cfg: CNNConfig, params, x):
    return (lenet_features(cfg, params, x) if cfg.kind == "lenet"
            else resnet8_features(cfg, params, x))


def head_apply(cfg: CNNConfig, head_params, feats):
    return (lenet_head(cfg, head_params, feats) if cfg.kind == "lenet"
            else resnet8_head(cfg, head_params, feats))


def head_keys(cfg: CNNConfig):
    return LENET_HEAD_KEYS if cfg.kind == "lenet" else RESNET8_HEAD_KEYS


def forward(cfg: CNNConfig, params, x, nn: Layers = NODE):
    return (lenet_forward(cfg, params, x, nn) if cfg.kind == "lenet"
            else resnet8_forward(cfg, params, x, nn))


def _conv_weights(params):
    """The convolution kernels of ``params`` in forward order (the order
    the init functions build them in)."""
    for v in params.values():
        if isinstance(v, dict):
            yield from _conv_weights(v)
        elif v.ndim == 4:
            yield v


def pack_groups(cfg: CNNConfig, n: int) -> tuple[int, ...]:
    """Each convolution's nodes to a group on the packed path, in forward
    order, for ``n`` nodes on a device: ``(1, 1, 1)`` for GN-LeNet at
    width 32."""
    shapes = jax.eval_shape(
        lambda k: list(_conv_weights(init_params(cfg, k))),
        jax.random.PRNGKey(0))
    return tuple(pack_group(n, s.shape[-1]) for s in shapes)


def loss_fn(cfg: CNNConfig, params, batch):
    """Mean cross-entropy of one node's model on ``batch`` (``x [B,H,W,C]``,
    ``y [B]``). On a packed batch (``x [B,H,W,n*C]``, ``y [B, n]``) of
    node-stacked ``params``: the sum over nodes of each node's mean
    cross-entropy, whose gradient is each node's own gradient, stacked."""
    if batch["y"].ndim == 2:
        logits = forward(cfg, params, batch["x"], PACKED)      # [n, B, O]
        y = batch["y"].T
        loss = jax.vmap(layers.softmax_xent)(logits, y).sum()
    else:
        logits, y = forward(cfg, params, batch["x"]), batch["y"]
        loss = layers.softmax_xent(logits, y)
    acc = (jnp.argmax(logits, -1) == y).mean()
    return loss, {"ce": loss, "acc": acc}
