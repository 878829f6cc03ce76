"""Randomized communication topologies (paper Sec. III-D step 1).

Each round FACADE (and the EL baseline) draws a fresh random r-regular
undirected graph. We build it jit-compatibly as the union of ``r/2`` random
cyclic permutations (plus their inverses), which yields an r-regular
multigraph whose union over rounds mixes well — the property the paper's
convergence analysis (Remark 1) relies on. DAC uses similarity-weighted
sampling instead; D-PSGD uses a fixed ring/torus.

All functions return a dense adjacency matrix ``A [n, n]`` (float, 0/1,
zero diagonal). The mixing matrix helpers turn A into the row-stochastic
W used for aggregation (uniform weights over neighbors + self, Eq. 3/4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs.trace import scope


def _check_degree(n: int, r: int):
    """Degrees at or above ``n`` used to silently collapse into
    multi-edges (a nominally r-regular draw quietly delivering degree
    <= n - 1); fail loudly instead. ``n`` and ``r`` are static Python
    ints, so this runs at trace time and costs nothing jitted."""
    if not 1 <= r < n:
        raise ValueError(
            f"degree={r} out of range for n={n} nodes: a simple graph "
            f"supports 1 <= degree <= n - 1 (multi-edges collapse)")


@scope("topology")
def random_regular(key, n: int, r: int):
    """Random r-regular-ish undirected graph via r/2 random cycles.

    For odd r the last 'half-edge' round adds one extra random matching.
    Guaranteed: symmetric, zero diagonal, every node degree >= r//2*2 and
    <= r (multi-edges collapse). Matches EL's 'sample s out-neighbors'
    spirit while staying jit-friendly (no rejection sampling).
    Raises ``ValueError`` when ``r`` is outside ``[1, n - 1]``.
    """
    _check_degree(n, r)
    a = jnp.zeros((n, n), jnp.float32)
    n_cycles = max(1, r // 2)
    keys = jax.random.split(key, n_cycles + 1)
    for i in range(n_cycles):
        perm = jax.random.permutation(keys[i], n)
        src = perm
        dst = jnp.roll(perm, 1)
        a = a.at[src, dst].set(1.0)
        a = a.at[dst, src].set(1.0)
    if r % 2 == 1:
        # one extra matching: pair consecutive nodes of a random permutation
        perm = jax.random.permutation(keys[-1], n)
        half = n // 2
        u, v = perm[:half], perm[half:2 * half]
        a = a.at[u, v].set(1.0)
        a = a.at[v, u].set(1.0)
    a = a * (1.0 - jnp.eye(n))
    return a


def ring(n: int, r: int = 2):
    """Static ring (D-PSGD default) with r//2 hops each side.
    Raises ``ValueError`` when ``r`` is outside ``[1, n - 1]``."""
    _check_degree(n, r)
    a = jnp.zeros((n, n), jnp.float32)
    idx = jnp.arange(n)
    for hop in range(1, max(1, r // 2) + 1):
        a = a.at[idx, (idx + hop) % n].set(1.0)
        a = a.at[(idx + hop) % n, idx].set(1.0)
    return a * (1.0 - jnp.eye(n))


def fully_connected(n: int):
    return jnp.ones((n, n), jnp.float32) - jnp.eye(n)


def effective_adjacency(adj, edge_mask, active):
    """The adjacency that actually carried messages this round: drawn edges
    masked by per-edge delivery (netsim drop model / partitions) and by both
    endpoints being online. Stays symmetric when ``edge_mask`` is symmetric;
    churned-out nodes end up with degree 0 (``mixing_matrix`` then gives
    them the self-weight-1 row, i.e. they keep their own model)."""
    return adj * edge_mask * active[:, None] * active[None, :]


@scope("topology")
def mixing_matrix(adj):
    """Row-stochastic W with uniform weights over {neighbors} ∪ {self}:
    W[i, j] = 1/(deg_i + 1) for j ∈ N(i) ∪ {i} (Eq. 3 aggregation).
    Row-stochastic for ANY 0/1 adjacency, including zero-degree nodes
    (the self edge keeps every denominator >= 1)."""
    n = adj.shape[0]
    a_hat = adj + jnp.eye(n)
    deg = a_hat.sum(axis=1, keepdims=True)
    return a_hat / deg


def weighted_mixing(adj, weights):
    """DAC-style: row-normalize arbitrary nonnegative weights masked by
    adjacency (+ self edge with weight = max of the row's weights)."""
    n = adj.shape[0]
    w = weights * adj
    self_w = jnp.maximum(w.max(axis=1), 1e-6)
    w = w + jnp.diag(self_w)
    return w / w.sum(axis=1, keepdims=True)


def degrees(adj):
    return adj.sum(axis=1)
