"""On-device per-round telemetry: the ``MetricsFrame`` scan leaf.

Since the scan-fused segment engine landed (PR 2), everything between two
evals — gossip mixing, cluster re-assignment, netsim conditions, the
adaptive topology policy — compiles away inside one opaque
``lax.scan`` dispatch. The paper's claims live on exactly those
internals (cluster-assignment settlement, per-tier bytes, staleness,
fairness dynamics), so this module recovers them WITHOUT reopening the
scan: a :class:`MetricsFrame` is a fixed pytree of per-round scalars
computed inside the scan step and stacked ``[length, ...]`` like every
other per-round output, then drained to the host in the segment's
existing single ``device_get`` — telemetry costs zero extra dispatches
and zero extra host syncs.

Schema contract (ROADMAP "obs"):

* every field is a fixed-shape float32 array whose shape depends only on
  the static :class:`ObsConfig` (``stale_hist`` is ``[staleness_bins]``,
  everything else a scalar), so the frame can ride ``lax.scan`` outputs;
* fields that don't apply to a run are ZEROS, never absent — the pytree
  structure is identical for FACADE and every baseline, with and without
  netsim, so one compiled segment program per config serves all;
* :func:`compute_frame` is the single definition both drivers share
  (the engine scans over it, the legacy loop jits it), the same
  discipline that keeps ``netsim.advance_conditions`` / ``topo.advance``
  engine/legacy bit-identical;
* adding a metric = add a ``MetricsFrame`` field + compute it here.
  Device-side knobs that change the compiled frame (an :class:`ObsConfig`
  field) fork the ``EngineSpec`` cache key; host-side sink/tracer
  settings (:class:`repro.obs.Obs`) never do — so adding a sink or a
  profile dir recompiles nothing.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro import netsim

from .trace import scope


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Static, device-side telemetry description — an ``EngineSpec``
    cache-key component (every field here changes the compiled segment
    program's outputs, so every field forks the key; the every-field-
    forks + coverage contract is pinned in ``tests/test_obs.py`` /
    ``tests/test_property.py``, same pattern as ``TopoConfig``).

    ``norms``/``comm``/``switches`` gate the corresponding frame fields
    (gated-off fields are computed as zeros, keeping the pytree fixed);
    ``staleness_bins`` is the staleness histogram width — ages are
    clipped into the last bin.
    """
    norms: bool = True           # update/param L2 norms
    comm: bool = True            # delivered edges, inclusion, tier bytes
    switches: bool = True        # FACADE cluster-assignment switches
    staleness_bins: int = 4      # gossip-age histogram width
    faults: bool = True          # crashed/corrupted/quarantined counters
    #                              (repro.resil; zeros when faults are off)

    def __post_init__(self):
        if self.staleness_bins < 1:
            raise ValueError(
                f"staleness_bins must be >= 1, got {self.staleness_bins}")


class MetricsFrame(NamedTuple):
    """One round's telemetry. All leaves float32; shapes fixed per
    :class:`ObsConfig` (scalars except ``stale_hist`` ``[bins]``)."""
    update_norm: Any       # global L2 of the round's mixable-state delta
    param_norm: Any        # global L2 of the new mixable state
    cluster_switches: Any  # nodes whose cluster_id changed (0 off-FACADE)
    delivered_edges: Any   # directed edges that carried a message
    inclusion: Any         # fraction of nodes with >= 1 incident edge
    bytes_core: Any        # fresh bytes sent by core-tier nodes
    bytes_edge: Any        # fresh bytes sent by edge-tier nodes
    stale_hist: Any        # [bins] node count per gossip-staleness age
    crashed: Any           # nodes down this round (repro.resil crash chain)
    corrupted: Any         # nodes shipping a corrupted payload this round
    quarantined: Any       # senders the robust guard quarantined


FRAME_FIELDS = MetricsFrame._fields


def tiers_of(net, n: int):
    """Static per-node tier vector (1.0 = edge) for the byte split —
    all-core when the run has no tiered link classes."""
    if net is not None and net.classes is not None:
        return jnp.asarray(netsim.node_tiers(net, n), jnp.float32)
    return jnp.zeros((n,), jnp.float32)


def _sq_norms(prev_tree, new_tree):
    """(sum (new-prev)^2, sum new^2) over float leaves only — int leaves
    (cluster ids, round counters, PRNG keys) carry no norm."""
    usq = psq = jnp.zeros((), jnp.float32)
    for a, b in zip(jax.tree.leaves(prev_tree), jax.tree.leaves(new_tree)):
        if not jnp.issubdtype(jnp.asarray(b).dtype, jnp.floating):
            continue
        a32 = jnp.asarray(a, jnp.float32)
        b32 = jnp.asarray(b, jnp.float32)
        usq = usq + jnp.sum(jnp.square(b32 - a32))
        psq = psq + jnp.sum(jnp.square(b32))
    return usq, psq


@scope("obs_frame")
def compute_frame(cfg: ObsConfig, n: int, tiers, prev_mix, new_mix,
                  prev_cid, new_cid, info, conds, gossip) -> MetricsFrame:
    """Build one round's :class:`MetricsFrame`. Pure observation: reads
    the round's states/info, never feeds anything back — enabling
    telemetry cannot perturb a trajectory (pinned by ``test_obs.py``).

    ``prev_mix``/``new_mix``: the algorithm's mixable trees before/after
    the round; ``prev_cid``/``new_cid``: cluster ids (``None``
    off-FACADE); ``info``: the round function's info dict (``adj_eff`` /
    ``payload_bytes`` from :func:`repro.core.netwire.comm_info`);
    ``conds``: the round's ``RoundConditions`` (``None`` without
    netsim); ``gossip``: the post-round :class:`netsim.GossipState`
    (``None`` means every node is fresh -> all mass in age bin 0).
    """
    zero = jnp.zeros((), jnp.float32)
    update_norm = param_norm = zero
    if cfg.norms:
        usq, psq = _sq_norms(prev_mix, new_mix)
        update_norm, param_norm = jnp.sqrt(usq), jnp.sqrt(psq)

    switches = zero
    if cfg.switches and prev_cid is not None and new_cid is not None:
        switches = jnp.sum((prev_cid != new_cid).astype(jnp.float32))

    delivered = inclusion = bytes_core = bytes_edge = zero
    if cfg.comm and "adj_eff" in info:
        adj = jnp.asarray(info["adj_eff"], jnp.float32)
        payload = jnp.asarray(info["payload_bytes"], jnp.float32)
        delivered = adj.sum()
        inclusion = jnp.mean((adj.sum(1) > 0).astype(jnp.float32))
        sends = adj
        if conds is not None and conds.stale is not None:
            # match the byte-honesty contract: a stale sender's
            # neighbors reuse its cached snapshot — no fresh bytes
            sends = adj * (1.0 - conds.stale)[:, None]
        node_bytes = sends.sum(1) * payload
        bytes_edge = (node_bytes * tiers).sum()
        bytes_core = node_bytes.sum() - bytes_edge

    bins = cfg.staleness_bins
    if gossip is not None:
        age = jnp.clip(gossip.age, 0, bins - 1)
        stale_hist = jnp.sum(jax.nn.one_hot(age, bins, dtype=jnp.float32),
                             axis=0)
    else:
        stale_hist = jnp.zeros((bins,), jnp.float32).at[0].set(float(n))

    crashed = corrupted = quarantined = zero
    if cfg.faults and conds is not None:
        if conds.crashed is not None:
            crashed = jnp.sum(jnp.asarray(conds.crashed, jnp.float32))
        if conds.corrupt is not None:
            corrupted = jnp.sum(jnp.asarray(conds.corrupt, jnp.float32))
        if "quarantined" in info:
            quarantined = jnp.asarray(info["quarantined"], jnp.float32)

    return MetricsFrame(update_norm=update_norm, param_norm=param_norm,
                        cluster_switches=switches,
                        delivered_edges=delivered, inclusion=inclusion,
                        bytes_core=bytes_core, bytes_edge=bytes_edge,
                        stale_hist=stale_hist, crashed=crashed,
                        corrupted=corrupted, quarantined=quarantined)
