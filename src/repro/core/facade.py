"""The FACADE algorithm (paper Sec. III-D), fully jit-compiled.

One call to ``facade_round`` executes, for ALL nodes at once:

    1. randomized r-regular topology                      (step 1)
    2. core aggregation (Eq. 3) + cluster-wise head aggregation (Eq. 4)
    3. cluster identification: argmin_j loss(core ∘ head_j)  (step 2c)
    4. H local SGD steps on (core, selected head)            (step 2d)
    5. write trained head into the selected slot; report cluster ID

Node states are stacked (leading ``n`` axis); gossip is an einsum with the
round's mixing matrix. In simulation mode the node axis lives on one device;
in production mode it is sharded over the ``pod`` mesh axis and GSPMD turns
the einsums into cross-pod collectives (see launch/shardings.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro import resil
from repro import topo as topo_mod
from repro.obs.trace import scope

from . import split, topology
from .bindings import (Binding, gossip_mix, node_head_matmul, node_matmul,
                       node_vmap)
# the round's local step: every node's H SGD steps at once
from .bindings import local_sgd_nodes as local_sgd
from .netwire import comm_info, masked_topology, sent_view
from .state import FacadeState, freeze_inactive


@dataclasses.dataclass(frozen=True)
class FacadeConfig:
    n_nodes: int
    k: int                    # number of cluster heads (paper hyperparam)
    degree: int = 4           # topology degree r (paper: 4)
    local_steps: int = 10     # H / tau (paper: 10; Flickr-Mammals 40)
    lr: float = 0.01
    warmup_rounds: int = 0    # App. F: initial EL-style shared-head rounds
    head_jitter: float = 0.0


# --------------------------------------------------------------------------
@scope("gossip")
def _aggregate_heads(adj, cluster_id, heads, k, sent_heads=None,
                     guard=None):
    """Eq. 4: for each node i and cluster j, average the heads *sent* by
    neighbors claiming cluster j together with i's own stored head j.

    heads [n, k, ...]; sent head of node j' = sent_heads[j', cid_j'].
    ``cluster_id``/``sent_heads`` describe what each node PUBLISHES this
    round (under async gossip a stale node publishes its old snapshot;
    under payload corruption it may be mangled); ``heads`` is always the
    receiver's own fresh stored bank.

    ``guard`` (:func:`repro.resil.guard_of`): the head-bank analogue of
    ``gossip_mix``'s robust guard — a sender whose published head is
    non-finite is quarantined (dropped from both the sum AND the count),
    and finite senders are norm-clipped against the receiver's own
    per-slot RMS head norm. ``None`` is the bit-exact legacy arithmetic.
    """
    n = adj.shape[0]
    if sent_heads is None:
        sent_heads = heads
    sent = jax.tree.map(
        lambda h: h[jnp.arange(n), cluster_id], sent_heads)  # [n, ...]
    onehot = jax.nn.one_hot(cluster_id, k, dtype=jnp.float32)  # [n, k]
    adj_w = adj
    if guard is not None:
        finite = resil.node_finite(sent)                     # [n]
        snorm = jnp.where(finite > 0, resil.node_norm(sent), 1.0)
        own = resil.node_norm(heads) / jnp.sqrt(float(k))    # per-slot RMS
        clip = jnp.minimum(
            1.0, guard.clip * jnp.maximum(own, 1e-12)[:, None]
            / jnp.maximum(snorm, 1e-12)[None, :])            # [n, n]
        # quarantined senders leave both the weighted sum and the count;
        # their (possibly NaN) head leaves are zeroed before the einsum
        adj = adj * finite[None, :]
        adj_w = adj * clip
        sent = resil_tree_zero(sent, finite)
    # cnt[i, c] = number of neighbors of i claiming cluster c
    cnt = node_matmul(adj, onehot)                          # [n, k]
    denom = 1.0 + cnt                                        # + own stored head

    def agg(h_all, h_sent):
        recv = node_head_matmul(adj_w.astype(h_sent.dtype),
                                onehot.astype(h_sent.dtype), h_sent)
        d = denom.reshape(denom.shape + (1,) * (h_all.ndim - 2))
        return ((h_all + recv) / d.astype(h_all.dtype)).astype(h_all.dtype)

    return jax.tree.map(agg, heads, sent)


def resil_tree_zero(tree, keep):
    """Zero float leaves of nodes with ``keep == 0`` along the leading
    axis (quarantine hygiene: 0-weight x NaN is still NaN in an einsum)."""
    def z(l):
        if not jnp.issubdtype(l.dtype, jnp.floating):
            return l
        m = keep.reshape((keep.shape[0],) + (1,) * (l.ndim - 1))
        return jnp.where(m > 0, l, 0).astype(l.dtype)

    return jax.tree.map(z, tree)


@scope("select_heads")
def _select_heads(binding: Binding, cores, heads, batches):
    """losses [n, k] via shared core features (paper III-E optimization)."""
    def per_node(core, heads_k, batch):
        feats = binding.features(core, batch)
        return jax.vmap(lambda h: binding.head_loss(h, feats, batch))(heads_k)

    return node_vmap(per_node)(cores, heads, batches)       # [n, k]


# --------------------------------------------------------------------------
def facade_round(fcfg: FacadeConfig, binding: Binding, state: FacadeState,
                 batches, warmup: bool = False, net=None, gossip=None,
                 topo=None, topo_cfg=None, fault_cfg=None):
    """One synchronous FACADE round for all nodes.

    batches: pytree with leading [n, H, B, ...] — per-node, per-local-step.
    net: optional ``netsim.RoundConditions`` (edge_mask/active/straggler
    masks). ``None`` is the exact ideal-medium code path; with masks, the
    drawn topology is filtered through :func:`topology.effective_adjacency`,
    churned-out nodes neither mix nor train (state frozen), and comm bytes
    count the directed edges that actually carried a message.
    gossip: optional async-gossip published-snapshot dict (``cores`` /
    ``heads`` / ``cluster_id``): stale nodes (``net.stale``) expose those
    to their neighbors instead of this round's fresh state.
    topo/topo_cfg: optional adaptive-topology state + static policy
    (:mod:`repro.topo`) — an adaptive policy replaces the uniform
    r-regular draw (same PRNG split, so the uniform policy stays
    bit-for-bit the legacy path).
    fault_cfg: optional static :class:`repro.resil.FaultConfig` — payload
    corruption mangles what a flagged node delivers (``netwire.sent_view``)
    and, when robust, the aggregation guard quarantines/clips poisoned
    senders in BOTH the core mix and the head aggregation.
    Returns (new_state, info dict with losses/selection/comm bytes).
    """
    n, k = fcfg.n_nodes, fcfg.k
    key, subkey = jax.random.split(state.rng)
    if topo_mod.adaptive(topo_cfg):
        adj = topo_mod.sample(topo_cfg, topo, subkey, n, fcfg.degree)
    else:
        adj = topology.random_regular(subkey, n, fcfg.degree)
    adj = masked_topology(net, adj)
    w = topology.mixing_matrix(adj)

    # --- what each node's neighbors receive this round (== its fresh
    # --- state unless it stays stale under async gossip or ships a
    # --- corrupted payload under fault injection) ---
    fresh = {"cores": state.cores, "heads": state.heads,
             "cluster_id": state.cluster_id}
    sent = sent_view(net, gossip, fresh, fault_cfg)
    if sent is None:
        vis_cores, sent_heads, sent_cid = None, None, state.cluster_id
    else:
        vis_cores, sent_heads = sent["cores"], sent["heads"]
        sent_cid = sent["cluster_id"]

    # --- aggregation (steps 2a/2b) ---
    guard = resil.guard_of(fault_cfg)
    cores = gossip_mix(w, state.cores, vis_cores, guard=guard)
    heads = _aggregate_heads(adj, sent_cid, state.heads, k,
                             sent_heads=sent_heads, guard=guard)

    # --- cluster identification (step 2c) on the first local batch ---
    first = jax.tree.map(lambda b: b[:, 0], batches)
    losses = _select_heads(binding, cores, heads, first)     # [n, k]
    new_cid = jnp.argmin(losses, axis=1).astype(jnp.int32)
    if warmup:  # App. F: shared-head warmup trains head 0 everywhere
        new_cid = jnp.zeros((n,), jnp.int32)

    # --- local training (step 2d) on (core, selected head) ---
    def pick(core, heads_k, cid):
        return split.merge_params(core, split.select_head(heads_k, cid))

    def put(params, heads_k, cid):
        new_core, new_head = split.split_params(params, binding.head_keys)
        if warmup:  # broadcast the trained head to every slot
            heads_k = split.stack_heads(new_head, k)
        else:
            heads_k = split.set_head(heads_k, cid, new_head)
        return new_core, heads_k

    params = local_sgd(binding, node_vmap(pick)(cores, heads, new_cid),
                       batches, fcfg.lr)
    new_cores, new_heads = node_vmap(put)(params, heads, new_cid)

    # --- communication accounting: each node pushes (core, head, cid) ---
    core_bytes = split.tree_size_bytes(
        jax.tree.map(lambda l: l[0], state.cores))
    head_bytes = split.tree_size_bytes(
        jax.tree.map(lambda l: l[0, 0], state.heads))
    payload = core_bytes + head_bytes + 4
    if net is not None:
        new_cid = jnp.where(net.active > 0, new_cid, state.cluster_id)
        new_cores = freeze_inactive(net.active, new_cores, state.cores)
        new_heads = freeze_inactive(net.active, new_heads, state.heads)

    new_state = FacadeState(cores=new_cores, heads=new_heads,
                            cluster_id=new_cid, round=state.round + 1,
                            rng=key)
    info = {
        "selection_losses": losses,
        "cluster_id": new_cid,
        "quarantined": resil.quarantined_count(guard, sent),
        **comm_info(net, adj, payload, n * fcfg.degree,
                    actual=topo_mod.adaptive(topo_cfg)),
    }
    return new_state, info


# --------------------------------------------------------------------------
def final_allreduce(fcfg: FacadeConfig, state: FacadeState) -> FacadeState:
    """Paper Sec. V-A: a final all-reduce where every node shares its model
    with everyone and aggregates cluster-wise."""
    n, k = fcfg.n_nodes, fcfg.k
    adj = topology.fully_connected(n)
    w = topology.mixing_matrix(adj)
    cores = gossip_mix(w, state.cores)
    heads = _aggregate_heads(adj, state.cluster_id, state.heads, k)
    return state._replace(cores=cores, heads=heads)


def node_models(state: FacadeState, binding: Binding):
    """Merged per-node deployable models, stacked [n, ...]."""
    def pick(core, heads_k, cid):
        return split.merge_params(core, split.select_head(heads_k, cid))

    return jax.vmap(pick)(state.cores, state.heads, state.cluster_id)
