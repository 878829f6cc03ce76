"""DEPRL baseline [Xiong et al., AAAI'24]: personalized DL with shared
representations — the core is gossiped over a STATIC topology, the head is
trained locally and NEVER shared (the paper observes this overfits and
plateaus, Sec. V-B/V-D)."""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Any

import jax
import jax.numpy as jnp

from repro import resil
from repro import topo as topo_mod

from .. import split, topology
from ..bindings import Binding, gossip_mix, local_sgd_nodes
from ..state import BaselineState, freeze_inactive
from ..netwire import comm_info, masked_topology, sent_view


@dataclasses.dataclass(frozen=True)
class DeprlConfig:
    n_nodes: int
    degree: int = 4
    local_steps: int = 10
    lr: float = 0.01


def deprl_round(cfg: DeprlConfig, binding: Binding, state: BaselineState,
                batches, net=None, gossip=None, topo=None, topo_cfg=None,
                fault_cfg=None):
    """state.params [n, ...] full models; only cores are mixed."""
    # static-ring legacy topology: adaptive sampling uses repro.topo's own
    # seeded round stream (see dpsgd_round)
    if topo_mod.adaptive(topo_cfg):
        adj = topo_mod.sample(topo_cfg, topo,
                              topo_mod.static_key(topo_cfg, state.round),
                              cfg.n_nodes, cfg.degree)
    else:
        adj = topology.ring(cfg.n_nodes, cfg.degree)
    adj = masked_topology(net, adj)
    w = topology.mixing_matrix(adj)

    def split_n(params):
        return split.split_params(params, binding.head_keys)

    cores, heads = jax.vmap(split_n)(state.params)
    pub_cores = None
    if gossip is not None:
        pub_cores, _ = jax.vmap(split_n)(gossip)
    vis = sent_view(net, pub_cores, cores, fault_cfg)
    guard = resil.guard_of(fault_cfg)
    cores = gossip_mix(w, cores, vis, guard=guard)

    params = local_sgd_nodes(binding, split.merge_params(cores, heads),
                             batches, cfg.lr)
    if net is not None:
        params = freeze_inactive(net.active, params, state.params)

    core_bytes = split.tree_size_bytes(jax.tree.map(lambda l: l[0], cores))
    info = comm_info(net, adj, core_bytes, cfg.n_nodes * cfg.degree,
                     actual=topo_mod.adaptive(topo_cfg))
    info["quarantined"] = resil.quarantined_count(guard, vis)
    return BaselineState(params=params, extra=state.extra,
                         round=state.round + 1, rng=state.rng), info
