"""Adaptive, netsim-aware topology policies with a fairness floor.

``core/topology.py`` draws every round's graph blind: a uniform
r-regular sample happily spends its degree budget on links netsim knows
are bursty, slow, or churned out. This module turns graph sampling into
a carried, learned, on-device policy:

* :class:`TopoConfig` — frozen, hashable policy description (a component
  of the ``EngineSpec`` cache key). ``policy="uniform"`` is the
  contract-preserving default: the algorithm's legacy sampler runs
  bit-for-bit (the round functions never call into this module's
  sampler), and no state rides in the carry.
* :class:`TopoState` — per-link EWMAs of observed *delivery* (from the
  round's edge/churn masks, which fold in the Gilbert–Elliott channel
  and event schedules) and observed *link seconds* (straggler-stretched
  transfer time of a reference payload). A pytree that rides in the
  donated ``EngineCarry`` next to ``chan``/``gossip`` and advances once
  per scanned round (:func:`advance`) — both drivers share the exact
  same entry points, the way ``netsim.advance_conditions`` is shared.
* :func:`sample` — the next round's graph via Gumbel-top-k over link
  scores. Each *participating* node picks ``max(1, r//2)`` peers by
  score (union-symmetrized, the DAC idiom), so the drawn graph never
  spends more than the legacy edge budget (``<= n * max(1, r//2)``
  undirected edges). Participation is where adaptation bites AND where the
  fairness floor lives: a node's participation probability scales with
  its link quality but is clamped to ``>= min_inclusion``, so edge-tier
  nodes are throttled, never starved — the failure mode naive
  reliability-weighted selection is known for (arXiv:2012.10069).

Observation model: the EWMAs observe the round's *conditions* (masks
are defined for every pair in simulation), not just the drawn links —
a deliberate simulation-side simplification that keeps ``advance``
independent of the sampled graph and therefore identical across
drivers. Scores:

* ``reliability``: ``delivery / link_s`` — expected delivered payload
  per simulated second ("goodput"); dropped-out AND slow links both
  score low, so it learns Gilbert–Elliott burst state (bursts persist
  ``~1/p_recover`` rounds — within an EWMA's memory) and static
  core/edge tiers alike;
* ``bandwidth``: ``1 / link_s`` — pure speed, ignores loss.

This module never imports ``repro.core`` (the round functions import
it), only jax + ``repro.netsim``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro import netsim
from repro.obs.trace import scope

POLICIES = ("uniform", "reliability", "bandwidth")

_EPS = 1e-6
_NEG = -1e9
_TOPO_STREAM = 7     # fold_in tag for static-topology algorithms (ring
#                      baselines have no per-round PRNG to reuse)


@dataclasses.dataclass(frozen=True)
class TopoConfig:
    """Static topology-policy description (an ``EngineSpec`` component:
    every field here forks the sweep cache key).

    ``degree`` overrides the run's degree budget when set (``None``
    inherits ``run_experiment(degree=...)``); ``min_inclusion`` is the
    fairness floor — a per-round, per-node participation probability
    guaranteed regardless of how hostile the learned scores are;
    ``ref_payload_bytes`` is the reference message size the link-time
    EWMA observes (ordering between links can depend on it when latency
    and bandwidth trade off); ``seed`` drives the sampling stream of
    algorithms whose legacy topology is static (ring baselines).
    """
    policy: str = "uniform"
    decay: float = 0.8               # EWMA weight on history
    degree: "int | None" = None      # degree budget (None -> run degree)
    min_inclusion: float = 0.1       # fairness floor on participation
    ref_payload_bytes: float = 1e6   # payload for link-time observations
    seed: int = 0                    # stream for static-topology algos

    def __post_init__(self):
        if self.policy not in POLICIES:
            raise ValueError(
                f"unknown topology policy {self.policy!r}; know {POLICIES}")
        if not 0.0 <= self.min_inclusion <= 1.0:
            raise ValueError(
                f"min_inclusion must be in [0, 1], got {self.min_inclusion}")
        if not 0.0 <= self.decay < 1.0:
            raise ValueError(
                f"decay must be in [0, 1), got {self.decay}")


class TopoState(NamedTuple):
    """On-device policy state (symmetric ``[n, n]`` float32, zero diag),
    carried in the engine's donated scan carry / threaded through the
    legacy loop."""
    delivery: Any    # EWMA of observed per-link delivery in [0, 1]
    link_s: Any      # EWMA of observed per-link seconds (ref payload)


def adaptive(cfg: "TopoConfig | None") -> bool:
    """True iff the policy actually overrides the legacy sampler."""
    return cfg is not None and cfg.policy != "uniform"


def budget(cfg: "TopoConfig | None", degree: int) -> int:
    return degree if cfg is None or cfg.degree is None else cfg.degree


# --------------------------------------------------------------------------
def _offdiag(n: int):
    return 1.0 - jnp.eye(n)


def _base_link_s(net, n: int, payload: float):
    """Per-link base transfer seconds for the reference payload: the
    tiered matrices when ``net.classes`` is set, the uniform scalar
    otherwise, ones without netsim (nothing to observe)."""
    if net is None:
        return jnp.ones((n, n), jnp.float32)
    if net.classes is None:
        return jnp.full((n, n), netsim.link_seconds(net, payload),
                        jnp.float32)
    lat, bw = netsim.link_matrices(net, n)
    return (lat + 8.0 * payload / bw).astype(jnp.float32)


def init_state(cfg: "TopoConfig | None", net, n: int):
    """Fresh neutral state (``None`` for uniform/off — the carry then
    costs nothing). Neutral means *learned from scratch*: all links
    start equally deliverable and equally fast; the policy discovers
    tiers and bursts from observations, it is not seeded with the
    simulator's ground truth."""
    if not adaptive(cfg):
        return None
    off = _offdiag(n).astype(jnp.float32)
    # distinct buffers: the carry is donated, and two leaves aliasing one
    # array would be donated twice
    return TopoState(delivery=off, link_s=jnp.copy(off))


def advance(cfg: "TopoConfig | None", net, state, conds):
    """Fold one round's observed conditions into the EWMAs.

    THE shared per-round entry point for both drivers (the scan engine
    calls it inside ``lax.scan`` with the state in the donated carry;
    the legacy loop threads the same object through Python) — called
    AFTER the round, so round ``t`` is always sampled from conditions
    observed up to ``t-1``. A no-op without netsim conditions (nothing
    was observed) or without an adaptive policy.
    """
    if state is None or conds is None or net is None:
        return state
    n = conds.active.shape[0]
    off = _offdiag(n)
    obs_d = (conds.edge_mask * conds.active[:, None]
             * conds.active[None, :]) * off
    slow = 1.0 + (net.straggler_slowdown - 1.0) * conds.straggler
    pair_slow = jnp.maximum(slow[:, None], slow[None, :])
    obs_t = pair_slow * _base_link_s(net, n, cfg.ref_payload_bytes) * off
    d = cfg.decay
    return TopoState(
        delivery=(d * state.delivery + (1.0 - d) * obs_d).astype(jnp.float32),
        link_s=(d * state.link_s + (1.0 - d) * obs_t).astype(jnp.float32))


# --------------------------------------------------------------------------
def link_scores(cfg: TopoConfig, state: TopoState):
    """Nonnegative per-link preference ``[n, n]`` (symmetric; diagonal
    meaningless — mask it before use)."""
    if cfg.policy == "reliability":
        return state.delivery / (state.link_s + _EPS)
    if cfg.policy == "bandwidth":
        return 1.0 / (state.link_s + _EPS)
    raise ValueError(f"policy {cfg.policy!r} has no link scores")


def link_logits(cfg: TopoConfig, state: TopoState, n: int):
    """log-scores with the diagonal masked, ready for Gumbel-top-k —
    also the additive term DAC folds into its similarity logits."""
    return jnp.log(link_scores(cfg, state) + 1e-9) + _NEG * jnp.eye(n)


def participation_probs(cfg: TopoConfig, state: TopoState):
    """Per-node participation probability ``[n]``.

    ``p_i = min_inclusion + (1 - min_inclusion) * q_i / max(q)`` where
    ``q_i`` is the node's mean off-diagonal link score. The best-connected
    node always participates; the floor is EXACT — ``p_i >=
    min_inclusion`` for every node under ANY score matrix (including the
    all-zero hostile one, where ``q/max(q)`` is defined as 0) — which is
    the deterministic guarantee the fairness tests pin.
    """
    s = link_scores(cfg, state)
    n = s.shape[0]
    q = (s * _offdiag(n)).sum(axis=1) / max(n - 1, 1)
    qhat = q / jnp.maximum(q.max(), _EPS)
    p = cfg.min_inclusion + (1.0 - cfg.min_inclusion) * qhat
    return jnp.clip(p, cfg.min_inclusion, 1.0)


def participants(cfg: TopoConfig, state: TopoState, key, n: int):
    """{0,1} [n]: the round's participation draw (fairness floor
    applied)."""
    del n  # shape comes from the state
    p = participation_probs(cfg, state)
    return (jax.random.uniform(key, p.shape) < p).astype(jnp.float32)


def gumbel_graph(cfg: TopoConfig, state: TopoState, key, n: int,
                 kpick: int, extra_logits=None):
    """Participation-gated Gumbel-top-k graph — the one sampling pipeline
    shared by :func:`sample` and DAC's similarity sampler.

    Each participating node picks ``kpick`` peers by link score (plus
    optional caller logits, e.g. DAC's data-similarity term); the picks
    are union-symmetrized (push-pull exchange) and gated so edges only
    join participants. Returns ``(adj, nbr, part)`` — the adjacency, the
    raw per-row pick indices ``[n, kpick]`` (DAC scores peer losses at
    them), and the participation mask.
    """
    k_part, k_gum = jax.random.split(key)
    part = participants(cfg, state, k_part, n)
    logits = link_logits(cfg, state, n) + _NEG * (1.0 - part)[None, :]
    if extra_logits is not None:
        logits = logits + extra_logits
    gumbel = jax.random.gumbel(k_gum, (n, n))
    _, nbr = jax.lax.top_k(logits + gumbel, kpick)            # [n, kpick]
    adj = jnp.zeros((n, n), jnp.float32)
    adj = adj.at[jnp.arange(n)[:, None], nbr].set(1.0)
    adj = jnp.maximum(adj, adj.T)
    return adj * part[:, None] * part[None, :] * _offdiag(n), nbr, part


@scope("topology")
def sample(cfg: TopoConfig, state: TopoState, key, n: int, degree: int):
    """Draw one adaptive round graph (adjacency ``[n, n]``, float 0/1).

    Guarantees (pinned by ``tests/test_topo.py`` / ``test_property.py``):
    symmetric, zero diagonal, edges only between participants, at most
    ``n * max(1, r//2)`` undirected edges — never more than the legacy
    r-regular draw spends at ANY degree (legacy builds ``r//2`` cycles
    of ``n`` edges, plus an ``n/2`` matching for odd ``r``), so
    adaptive-vs-uniform byte comparisons are never budget-inflated —
    and every participant with a participating peer has degree >= 1.
    Inclusion (participation) probability >= ``min_inclusion`` per node
    per round regardless of the learned scores.
    """
    r = budget(cfg, degree)
    adj, _, _ = gumbel_graph(cfg, state, key, n, max(1, r // 2))
    return adj


def static_key(cfg: TopoConfig, rnd):
    """Sampling key for algorithms whose legacy topology is static (the
    ring baselines): a seeded stream folded on the round counter, so the
    schedule replays and never touches the algorithm's own PRNG."""
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(cfg.seed), _TOPO_STREAM), rnd)
