"""Model bindings: the uniform interface the DL algorithms train against.

A binding exposes:
    init(key)                  -> full param pytree (head keys included)
    head_keys                  -> which top-level groups form the FACADE head
    loss(params, batch)        -> scalar training loss (grads flow here)
    features(core, batch)      -> core activations shared by the k heads
    head_loss(head, feats, b)  -> candidate-head loss on cached core features

The features/head_loss pair implements the paper's III-E optimization
("store the output tokens of the model core and input these to each model
head") — the core runs ONCE per round per node, not k times.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.models import api, cnn, layers, transformer, whisper
from repro.models.base import CNNConfig, ModelConfig
from repro.obs.trace import scope

from . import meshctx


def node_matmul(a, x):
    """THE cross-node contraction: ``out[i, ...] = sum_j a[i, j] x[j, ...]``
    (``einsum("ij,j...->i...")``). Outside a node-mesh trace context this
    IS that einsum, bit for bit. Under :func:`repro.core.meshctx.activate`
    it lowers as a shard_map row block: each device holds a row shard of
    ``a`` and a node shard of ``x``, all-gathers the senders, and runs the
    einsum on its rows — per-row arithmetic (and therefore the result) is
    identical to the unsharded form; only cross-row REDUCTIONS downstream
    of this op can see a different summation order."""
    mesh = meshctx.current()
    if mesh is None:
        return jnp.einsum("ij,j...->i...", a, x)

    def blk(a_blk, x_blk):
        xg = jax.lax.all_gather(x_blk, meshctx.NODE_AXIS, tiled=True)
        return jnp.einsum("ij,j...->i...", a_blk, xg)

    return jax.shard_map(blk, mesh=mesh,
                         in_specs=(P(meshctx.NODE_AXIS, None),
                                   P(meshctx.NODE_AXIS)),
                         out_specs=P(meshctx.NODE_AXIS))(a, x)


def node_head_matmul(a, onehot, h):
    """FACADE's Eq. 4 receive contraction
    ``recv[i, c, ...] = sum_j a[i, j] onehot[j, c] h[j, ...]``
    (``einsum("ij,jc,j...->ic...")``) — same sharding story as
    :func:`node_matmul`: row-sharded ``a``, all-gathered senders."""
    mesh = meshctx.current()
    if mesh is None:
        return jnp.einsum("ij,jc,j...->ic...", a, onehot, h)

    def blk(a_blk, o_blk, h_blk):
        og = jax.lax.all_gather(o_blk, meshctx.NODE_AXIS, tiled=True)
        hg = jax.lax.all_gather(h_blk, meshctx.NODE_AXIS, tiled=True)
        return jnp.einsum("ij,jc,j...->ic...", a_blk, og, hg)

    return jax.shard_map(blk, mesh=mesh,
                         in_specs=(P(meshctx.NODE_AXIS, None),
                                   P(meshctx.NODE_AXIS),
                                   P(meshctx.NODE_AXIS)),
                         out_specs=P(meshctx.NODE_AXIS))(a, onehot, h)


def node_blocks(fn):
    """``fn`` over node-stacked arguments and results (leading dim n),
    partitioned over the active node mesh. Outside a mesh trace context
    this IS ``fn``. Under :func:`repro.core.meshctx.activate` ``fn`` runs
    inside ``shard_map``, on each device's own node block."""
    mesh = meshctx.current()
    if mesh is None:
        return fn

    def call(*args):
        def row(l):
            return P(meshctx.NODE_AXIS, *([None] * (l.ndim - 1)))

        in_specs = jax.tree.map(row, args)
        out_sds = jax.eval_shape(fn, *args)
        out_specs = jax.tree.map(
            lambda s: P(meshctx.NODE_AXIS,
                        *([None] * (len(s.shape) - 1))), out_sds)
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(*args)

    return call


def node_vmap(fn):
    """``jax.vmap`` over the node axis, partitioned over the active node
    mesh (:func:`node_blocks`). Outside a mesh trace context this IS
    ``jax.vmap(fn)`` — same jaxpr, bit for bit. Load-bearing for the
    sharded engine's scaling: XLA lowers a vmapped convolution to a
    grouped conv whose node axis lands in the FEATURE dimension, which
    GSPMD replicates (all-gathering every activation) rather than shards —
    so without the shard_map the whole local-training phase runs in full
    on every device. Per-node arithmetic is untouched either way; every
    argument and result must be node-stacked (leading dim n)."""
    return node_blocks(jax.vmap(fn))


class Binding(NamedTuple):
    cfg: Any
    init: Callable
    head_keys: tuple
    loss: Callable          # (params, batch) -> scalar
    features: Callable      # (core, batch) -> feats
    head_loss: Callable     # (head, feats, batch) -> scalar
    # batches [n, H, ...] -> [H, ...] packed, on which ``loss`` of
    # node-stacked params is the sum of the nodes' losses (CNNs; None
    # elsewhere):
    pack: Callable | None = None


@scope("local_sgd")
def local_sgd(binding: "Binding", params, batches_h, lr):
    """H plain-SGD steps (paper step 2d) on one node's params.

    ``batches_h``: pytree with leading [H, ...]. Shared by FACADE and every
    baseline round function — one arithmetic definition keeps the scan
    engine's parity guarantees algorithm-independent.
    """
    def step(p, batch):
        g = jax.grad(binding.loss)(p, batch)
        p = jax.tree.map(lambda w, gg: (w - lr * gg).astype(w.dtype), p, g)
        return p, None

    params, _ = jax.lax.scan(step, params, batches_h)
    return params


def sgd_path(binding: "Binding") -> str:
    """Which local-SGD program :func:`local_sgd_nodes` builds for
    ``binding``: ``"packed"`` or ``"vmap"``."""
    return "vmap" if binding.pack is None else "packed"


def compile_stats(binding: "Binding", n: int) -> dict:
    """What a ``compile`` span states of the local-SGD program built for
    ``binding`` with ``n`` nodes on each device: ``sgd_path``, ``model``
    (the config's name) and, on the packed path, ``pack_groups``: each
    packed convolution's nodes to a group, in forward order (``"1,1,1"``
    for GN-LeNet at width 32)."""
    stats = {"sgd_path": sgd_path(binding), "model": binding.cfg.name}
    if stats["sgd_path"] == "packed":
        stats["pack_groups"] = ",".join(
            str(g) for g in cnn.pack_groups(binding.cfg, n))
    return stats


def local_sgd_nodes(binding: "Binding", params_n, batches_nh, lr):
    """:func:`local_sgd` on every node: node-stacked ``params_n`` and
    ``batches_nh`` (leading ``[n, H, ...]``). A binding that can ``pack``
    trains all nodes of a device in one packed program
    (``[B, H, W, n*C]`` activations); any other runs
    ``node_vmap(local_sgd)``."""
    if sgd_path(binding) == "vmap":
        return node_vmap(lambda p, b: local_sgd(binding, p, b, lr))(
            params_n, batches_nh)
    return node_blocks(lambda p, b: _packed_sgd(binding, lr, p, b))(
        params_n, batches_nh)


@scope("local_sgd")
def _packed_sgd(binding: "Binding", lr, params_n, batches_nh):
    """H SGD steps on all nodes at once: the gradient of the stacked loss
    is each node's own gradient, so every node takes :func:`local_sgd`'s
    step. The batches are packed once, before the scan.

    Packed nodes share contractions at exact-zero weight, where a value
    that is not finite would spread to its neighbours. So a node whose
    state is not finite trains on zeros and comes out NaN, poisoned as
    node-by-node training leaves it (there, leaves its loss never reaches
    may stay finite); the other nodes' results are their own."""
    from repro import resil   # local import: resil must stay core-free
    ok = resil.node_finite(params_n) > 0

    def keep(fill):
        return lambda l: jnp.where(
            ok.reshape((-1,) + (1,) * (l.ndim - 1)), l, fill)

    batches_h = binding.pack(batches_nh)

    def step(p, batch):
        g = jax.grad(binding.loss)(p, batch)
        p = jax.tree.map(lambda w, gg: (w - lr * gg).astype(w.dtype), p, g)
        return p, None

    params_n, _ = jax.lax.scan(step, jax.tree.map(keep(0), params_n),
                               batches_h)
    return jax.tree.map(keep(jnp.nan), params_n)


@scope("gossip")
def gossip_mix(w, tree, visible=None, guard=None):
    """Row-stochastic gossip mixing (Eq. 3): ``out_i = sum_j W_ij x_j``
    over node-stacked pytrees — THE one mixing definition shared by FACADE
    and every baseline, so the engine's parity guarantees stay
    algorithm-independent (like :func:`local_sgd` for the local phase).

    ``visible`` (async stale gossip, ``netwire.stale_view`` /
    ``netwire.sent_view``): an optional same-structure tree of the
    per-node snapshots *neighbors observe* — stale nodes expose their
    last published state there. Neighbor terms then read ``visible``
    while each node's self-term always uses its own fresh leaf:
    ``out_i = sum_j W_ij v_j + W_ii (x_i - v_i)``. With no stale node
    (``visible == tree``) the correction is exactly zero.

    ``guard`` (robust aggregation, :func:`repro.resil.guard_of`): when a
    :class:`repro.resil.FaultConfig` is supplied, the mix degrades
    gracefully under poisoned payloads instead of NaN'ing every receiver:

    * **quarantine** — senders with ANY non-finite float leaf lose their
      off-diagonal weight entirely and each row of ``W`` is renormalized
      over its surviving neighbors (self weight always kept), so one
      NaN'd node costs its neighbors one contribution, not their state;
    * **norm clip** — every surviving neighbor's contribution is scaled
      by ``min(1, clip * ||self|| / ||sender||)``: a blown-up payload
      contributes at most ``clip`` times the receiver's own norm in the
      sender's direction. Honest payloads (comparable norms) are scaled
      by exactly 1.0's neighborhood, so degradation is smooth.

    ``guard=None`` (every zero-rate off-switch) is bit-for-bit the
    historical arithmetic — the guard's renormalization must never touch
    honest runs (``mixing_matrix`` rows are only float-tolerance
    stochastic, so renormalizing would perturb bits).
    """
    if guard is None:
        if visible is None:
            return jax.tree.map(
                lambda p: node_matmul(w.astype(p.dtype), p), tree)
        diag = jnp.diagonal(w)

        def mix(p, v):
            out = node_matmul(w.astype(p.dtype), v.astype(p.dtype))
            d = diag.reshape((diag.shape[0],) + (1,) * (p.ndim - 1))
            return (out + d.astype(p.dtype)
                    * (p - v.astype(p.dtype))).astype(p.dtype)

        return jax.tree.map(mix, tree, visible)

    from repro import resil   # local import: resil must stay core-free
    v_tree = tree if visible is None else visible
    n = w.shape[0]
    finite = resil.node_finite(v_tree)                         # [n]
    vnorm = jnp.where(finite > 0, resil.node_norm(v_tree), 1.0)
    pnorm = resil.node_norm(tree)                              # own, fresh
    eye = jnp.eye(n, dtype=w.dtype)
    off = 1.0 - eye
    # quarantine: drop poisoned senders' off-diagonal mass, renormalize
    # each row over the survivors (the self weight is always kept)
    wq = w * off * finite[None, :] + w * eye
    wr = wq / jnp.maximum(wq.sum(axis=1, keepdims=True), 1e-12)
    # norm clip: cap each neighbor's contribution at `clip` x own norm
    scale = jnp.minimum(1.0, guard.clip * jnp.maximum(pnorm, 1e-12)[:, None]
                        / jnp.maximum(vnorm, 1e-12)[None, :])
    scale = scale * off + eye          # never clip the self term
    ws = wr * scale
    diag = jnp.diagonal(wr)

    def mix(p, v):
        m = finite.reshape((n,) + (1,) * (p.ndim - 1))
        # zero quarantined leaves BEFORE the einsum: 0-weight x NaN = NaN
        vs = jnp.where(m > 0, v.astype(p.dtype), 0).astype(p.dtype)
        out = node_matmul(ws.astype(p.dtype), vs)
        d = diag.reshape((n,) + (1,) * (p.ndim - 1))
        return (out + d.astype(p.dtype) * (p - vs)).astype(p.dtype)

    return jax.tree.map(mix, tree, v_tree)


def _untie_lm_head(cfg, params, key):
    if "lm_head" not in params:
        params = dict(params)
        params["lm_head"] = layers.dense_init(
            key, cfg.d_model, cfg.vocab_size, cfg.dt, scale=0.02)
    return params


def make_binding(cfg) -> Binding:
    if isinstance(cfg, CNNConfig):
        return _cnn_binding(cfg)
    if cfg.encoder_layers > 0:
        return _whisper_binding(cfg)
    return _lm_binding(cfg)


# --------------------------------------------------------------------------
def _cnn_binding(cfg: CNNConfig) -> Binding:
    hk = cnn.head_keys(cfg)

    def loss(params, batch):
        return cnn.loss_fn(cfg, params, batch)[0]

    def features(core, batch):
        return cnn.features(cfg, core, batch["x"])

    def head_loss(head, feats, batch):
        logits = cnn.head_apply(cfg, head, feats)
        return layers.softmax_xent(logits, batch["y"])

    def pack(batches_nh):       # x [n, H, B, ..., C] -> [H, B, ..., n*C]
        return {"x": cnn.pack_nodes(batches_nh["x"]),    # y -> [H, B, n]
                "y": jnp.moveaxis(batches_nh["y"], 0, -1)}

    return Binding(cfg, lambda k: cnn.init_params(cfg, k), hk, loss,
                   features, head_loss, pack)


# --------------------------------------------------------------------------
def _lm_binding(cfg: ModelConfig) -> Binding:
    hk = ("final_norm", "lm_head")

    def init(key):
        k1, k2 = jax.random.split(key)
        return _untie_lm_head(cfg, transformer.init_params(cfg, k1), k2)

    def loss(params, batch):
        return transformer.loss_fn(cfg, params, batch)[0]

    def features(core, batch):
        feats, _ = transformer.forward(cfg, core, batch["tokens"],
                                       img_embeds=batch.get("img_embeds"),
                                       apply_final_norm=False)
        n_img = (0 if batch.get("img_embeds") is None
                 else batch["img_embeds"].shape[1])
        return feats[:, n_img:]

    def head_loss(head, feats, batch):
        h = layers.rms_norm(feats, head["final_norm"], cfg.norm_eps)
        l, _ = transformer.chunked_ce(h, head["lm_head"], batch["labels"],
                                      batch["mask"].astype(jnp.float32))
        return l

    return Binding(cfg, init, hk, loss, features, head_loss)


# --------------------------------------------------------------------------
def _whisper_binding(cfg: ModelConfig) -> Binding:
    hk = ("final_norm", "lm_head")

    def init(key):
        k1, k2 = jax.random.split(key)
        return _untie_lm_head(cfg, whisper.init_params(cfg, k1), k2)

    def loss(params, batch):
        return whisper.loss_fn(cfg, params, batch)[0]

    def features(core, batch):
        feats, _ = whisper.forward(cfg, core, batch["tokens"],
                                   batch["frames"], apply_final_norm=False)
        return feats

    def head_loss(head, feats, batch):
        h = layers.layer_norm(feats, head["final_norm"]["g"],
                              head["final_norm"]["b"], cfg.norm_eps)
        l, _ = transformer.chunked_ce(h, head["lm_head"], batch["labels"],
                                      batch["mask"].astype(jnp.float32))
        return l

    return Binding(cfg, init, hk, loss, features, head_loss)
