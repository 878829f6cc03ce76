"""Epidemic Learning (EL) baseline [NeurIPS'23, de Vos et al.]:
D-PSGD over a fresh random r-regular topology each round. This is the
paper's primary baseline and the communication-cost reference point."""
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
class ELConfig:
    n_nodes: int
    degree: int = 4
    local_steps: int = 10
    lr: float = 0.05


def el_round(cfg: ELConfig, binding: Binding, state: BaselineState, batches,
             net=None, gossip=None, topo=None, topo_cfg=None,
             fault_cfg=None):
    """batches: pytree leading [n, H, B, ...]; net: optional
    ``netsim.RoundConditions`` masks (see ``facade_round``); gossip:
    optional published-snapshot tree (async stale gossip); topo/topo_cfg:
    optional adaptive topology policy (:mod:`repro.topo` — uniform stays
    the legacy draw bit-for-bit, same PRNG split); fault_cfg: optional
    :class:`repro.resil.FaultConfig` (payload corruption + robust mix
    guard, see ``facade_round``)."""
    key, sub = jax.random.split(state.rng)
    if topo_mod.adaptive(topo_cfg):
        adj = topo_mod.sample(topo_cfg, topo, sub, cfg.n_nodes, cfg.degree)
    else:
        adj = topology.random_regular(sub, cfg.n_nodes, cfg.degree)
    adj = masked_topology(net, adj)
    w = topology.mixing_matrix(adj)

    vis = sent_view(net, gossip, state.params, fault_cfg)
    guard = resil.guard_of(fault_cfg)
    params = gossip_mix(w, state.params, vis, guard=guard)
    params = local_sgd_nodes(binding, params, batches, cfg.lr)
    if net is not None:
        params = freeze_inactive(net.active, params, state.params)

    model_bytes = split.tree_size_bytes(
        jax.tree.map(lambda l: l[0], state.params))
    info = comm_info(net, adj, model_bytes, cfg.n_nodes * cfg.degree,
                     actual=topo_mod.adaptive(topo_cfg))
    info["quarantined"] = resil.quarantined_count(guard, vis)
    return BaselineState(params=params, extra=state.extra,
                         round=state.round + 1, rng=key), info
