"""DAC baseline [Zec et al., 2022]: decentralized adaptive clustering —
communication partners are sampled with probability derived from the
(inverse) loss of each peer's model on the local data; mixing weights adapt
to data similarity. Dynamic topology, full-model exchange."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import resil
from repro import topo as topo_mod

from .. import split, topology
from ..bindings import Binding, gossip_mix, local_sgd_nodes
from ..state import BaselineState, freeze_inactive
from ..netwire import comm_info, masked_topology, sent_view


@dataclasses.dataclass(frozen=True)
class DACConfig:
    n_nodes: int
    degree: int = 4
    local_steps: int = 10
    lr: float = 0.005
    tau: float = 30.0  # similarity temperature (DAC paper's tau)


def init_dac_extra(n: int):
    """Pairwise similarity scores, updated every round."""
    return {"sim": jnp.zeros((n, n), jnp.float32)}


def dac_round(cfg: DACConfig, binding: Binding, state: BaselineState,
              batches, net=None, gossip=None, topo=None, topo_cfg=None,
              fault_cfg=None):
    n = cfg.n_nodes
    key, k_top = jax.random.split(state.rng)
    sim = state.extra["sim"]

    # --- sample neighbors: Gumbel-top-k over similarity logits ---
    # DAC keeps its own data-similarity sampler; an adaptive topology
    # policy composes with it via the shared participation-gated pipeline
    # (topo.gumbel_graph) — link-quality logits add to the similarity
    # logits and the fairness floor gates the round — so partners are
    # chosen by similarity AND link quality, at the policy's degree budget
    logits = cfg.tau * sim - 1e9 * jnp.eye(n)
    part = None
    if topo_mod.adaptive(topo_cfg):
        adj, nbr, part = topo_mod.gumbel_graph(
            topo_cfg, topo, k_top, n,
            topo_mod.budget(topo_cfg, cfg.degree), extra_logits=logits)
    else:
        gumbel = jax.random.gumbel(k_top, (n, n))
        _, nbr = jax.lax.top_k(logits + gumbel, cfg.degree)  # [n, r]
        adj = jnp.zeros((n, n)).at[jnp.arange(n)[:, None], nbr].set(1.0)
        adj = jnp.maximum(adj, adj.T)  # symmetrize (push-pull exchange)
    adj = masked_topology(net, adj)

    # what each peer DELIVERS this round: its published snapshot when it
    # is stale (async gossip), its live params otherwise — possibly
    # corrupted in transit (fault injection)
    vis = sent_view(net, gossip, state.params, fault_cfg)
    guard = resil.guard_of(fault_cfg)
    delivered_params = state.params if vis is None else vis

    # --- similarity update: inverse loss of peer's model on local batch ---
    first = jax.tree.map(lambda b: b[:, 0], batches)

    def peer_losses(i):
        my_batch = jax.tree.map(lambda b: b[i], first)

        def loss_of(j):
            pj = jax.tree.map(lambda p: p[j], delivered_params)
            return binding.loss(pj, my_batch)

        return jax.vmap(loss_of)(nbr[i])                     # [r]

    l_peer = jax.vmap(peer_losses)(jnp.arange(n))            # [n, r]
    if guard is not None:
        # a NaN'd peer model scores NaN loss, which would poison the
        # similarity table forever — under the robust guard it scores as
        # maximally dissimilar instead
        l_peer = jnp.where(jnp.isfinite(l_peer), l_peer, 1e9)
    rows = jnp.arange(n)[:, None]
    inv_loss = 1.0 / jnp.maximum(l_peer, 1e-6)
    if net is not None or part is not None:
        # a lost/offline/non-participating exchange brings no model to
        # score — keep the old entry
        delivered = adj[rows, nbr] > 0                       # [n, r]
        inv_loss = jnp.where(delivered, inv_loss, sim[rows, nbr])
    new_sim = sim.at[rows, nbr].set(inv_loss)

    # --- aggregate with similarity weights, then local train ---
    w = topology.weighted_mixing(adj, jnp.maximum(new_sim, 1e-6))
    params = gossip_mix(w, state.params, vis, guard=guard)

    params = local_sgd_nodes(binding, params, batches, cfg.lr)
    if net is not None:
        params = freeze_inactive(net.active, params, state.params)
        new_sim = jnp.where(net.active[:, None] > 0, new_sim, sim)

    model_bytes = split.tree_size_bytes(
        jax.tree.map(lambda l: l[0], state.params))
    info = comm_info(net, adj, model_bytes, n * cfg.degree,
                     actual=part is not None)
    info["quarantined"] = resil.quarantined_count(guard, vis)
    return BaselineState(params=params, extra={"sim": new_sim},
                         round=state.round + 1, rng=key), info
