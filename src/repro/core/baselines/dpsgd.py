"""D-PSGD baseline [Lian et al., NeurIPS'17]: static-topology decentralized
SGD (paper Alg. 1 / Appendix B). Used for the Fig. 1 motivation experiment."""
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
class DpsgdConfig:
    n_nodes: int
    degree: int = 4
    local_steps: int = 10
    lr: float = 0.05


def dpsgd_round(cfg: DpsgdConfig, binding: Binding, state: BaselineState,
                batches, net=None, gossip=None, topo=None, topo_cfg=None,
                fault_cfg=None):
    # legacy topology is a static ring (no per-round PRNG to reuse), so an
    # adaptive policy samples from repro.topo's own seeded round stream
    if topo_mod.adaptive(topo_cfg):
        adj = topo_mod.sample(topo_cfg, topo,
                              topo_mod.static_key(topo_cfg, state.round),
                              cfg.n_nodes, cfg.degree)
    else:
        adj = topology.ring(cfg.n_nodes, cfg.degree)
    adj = masked_topology(net, adj)
    w = topology.mixing_matrix(adj)

    # D-PSGD order: local train, then exchange+aggregate (stale neighbors
    # contribute their last published model instead of today's)
    params = local_sgd_nodes(binding, state.params, batches, cfg.lr)
    vis = sent_view(net, gossip, params, fault_cfg)
    guard = resil.guard_of(fault_cfg)
    params = gossip_mix(w, params, vis, guard=guard)
    if net is not None:
        params = freeze_inactive(net.active, params, state.params)

    model_bytes = split.tree_size_bytes(
        jax.tree.map(lambda l: l[0], state.params))
    info = comm_info(net, adj, model_bytes, cfg.n_nodes * cfg.degree,
                     actual=topo_mod.adaptive(topo_cfg))
    info["quarantined"] = resil.quarantined_count(guard, vis)
    return BaselineState(params=params, extra=state.extra,
                         round=state.round + 1, rng=state.rng), info
