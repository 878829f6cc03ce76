"""Node-axis mesh plumbing for the sharded segment engine.

The engine's ``mesh=`` path lays the donated :class:`EngineCarry` out over
a 1-D ``node`` device mesh (leading-``n`` leaves row-sharded, everything
else replicated) and routes the cross-node contractions in
:mod:`repro.core.bindings` through ``shard_map`` row blocks. This module
owns the three pieces everything shares:

* the canonical mesh description — a SHAPE tuple like ``(8,)``, which is
  what :class:`repro.core.cache.EngineSpec` keys on (device objects never
  enter cache keys or checkpoint fingerprints) — plus :func:`build`, which
  turns it into a live ``jax.sharding.Mesh`` over host devices;
* the carry layout rule (:func:`node_spec` / :func:`carry_shardings`):
  a leaf whose leading dim equals ``n`` is ``P('node', None, ...)`` —
  so ``[n, n]`` mixing weights, ``ChannelState.bad``, link matrices and
  topo/fault masks all shard along ROWS — and every other leaf (scalars,
  PRNG keys) is replicated;
* the TRACE-TIME context (:func:`activate` / :func:`current`): the engine
  traces its segment program inside ``activate(mesh)``, and the bindings'
  contraction helpers consult :func:`current` to decide between the plain
  einsum and the shard_map row-block form. ``mesh=None`` never activates
  a context, so that path stays bit-for-bit the historical single-device
  arithmetic — same jaxpr, same program.

Forced host devices (``XLA_FLAGS=--xla_force_host_platform_device_count``)
must be set BEFORE the first jax import — the ``launch/dryrun.py`` /
``benchmarks/scale_curve.py`` subprocess pattern.
"""
from __future__ import annotations

import contextlib

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NODE_AXIS = "node"

_ACTIVE: list = []   # trace-time stack; [-1] is the mesh being traced under


def normalize(mesh):
    """Canonicalize a user-facing ``mesh=`` argument to the shape tuple the
    cache keys on: ``None`` | int | 1-tuple | ``Mesh`` -> ``None`` or
    ``(n_devices,)``. Multi-axis meshes are rejected — the engine shards
    exactly one axis (the node axis)."""
    if mesh is None:
        return None
    if isinstance(mesh, Mesh):
        shape = tuple(int(s) for s in mesh.devices.shape)
    elif isinstance(mesh, int):
        shape = (int(mesh),)
    else:
        shape = tuple(int(s) for s in mesh)
    if len(shape) != 1:
        raise ValueError(
            f"mesh shape {shape} has {len(shape)} axes; the segment engine "
            "shards exactly one axis (the node axis) — pass an int, a "
            "1-tuple like (8,), or a 1-D Mesh")
    if shape[0] < 1:
        raise ValueError(f"mesh needs at least 1 device, got {shape[0]}")
    return shape


def build(shape) -> "Mesh | None":
    """Shape tuple -> live 1-D node mesh over the first ``shape[0]`` host
    devices (``None`` passes through)."""
    if shape is None:
        return None
    (size,) = normalize(shape)
    devices = jax.devices()
    if size > len(devices):
        platform = devices[0].platform
        hint = ""
        if platform == "cpu":
            hint = (f"; set XLA_FLAGS=--xla_force_host_platform_device_"
                    f"count={size} BEFORE importing jax (the "
                    "launch/dryrun.py subprocess pattern)")
        raise RuntimeError(
            f"node mesh ({size},) needs {size} devices, but {platform} "
            f"shows {len(devices)}{hint}")
    return Mesh(np.asarray(devices[:size]), (NODE_AXIS,))


@contextlib.contextmanager
def activate(mesh: "Mesh | None"):
    """Trace-time marker: while active, the cross-node contractions in
    :mod:`repro.core.bindings` lower as shard_map row blocks over ``mesh``.
    ``None`` is a true no-op so un-meshed callers never pay anything."""
    if mesh is None:
        yield
        return
    _ACTIVE.append(mesh)
    try:
        yield
    finally:
        _ACTIVE.pop()


def current() -> "Mesh | None":
    """The mesh being traced under, or ``None`` outside any context."""
    return _ACTIVE[-1] if _ACTIVE else None


def node_spec(leaf, n: int) -> P:
    """The carry layout rule: leading dim == ``n`` -> rows on the node
    axis, anything else (scalars, PRNG keys, odd shapes) replicated."""
    shape = getattr(leaf, "shape", ())
    if len(shape) >= 1 and shape[0] == n:
        return P(NODE_AXIS, *([None] * (len(shape) - 1)))
    return P()


def carry_shardings(mesh: Mesh, tree, n: int):
    """Pytree of :class:`NamedSharding` mirroring ``tree`` under the
    :func:`node_spec` rule — the layout ``device_put`` commits the carry
    to and ``with_sharding_constraint`` pins at segment boundaries."""
    return jax.tree.map(
        lambda l: NamedSharding(mesh, node_spec(l, n)), tree)


def constrain_tree(tree, n: int):
    """Pin a node-stacked pytree to the active node-mesh layout under the
    :func:`node_spec` rule (identity when no mesh context is active).
    Load-bearing on the per-round batch tree: its gather indices come off
    a REPLICATED PRNG key, so without this pin GSPMD replicates the
    gathered batches — and the whole local-training phase downstream of
    them — onto every device instead of partitioning over nodes."""
    mesh = current()
    if mesh is None:
        return tree
    return jax.lax.with_sharding_constraint(
        tree, carry_shardings(mesh, tree, n))


def constrain_rows(a):
    """Pin a node-leading array's rows to the active node mesh (identity
    when no mesh context is active) — keeps GSPMD from replicating the
    per-round ``[n, n]`` adjacency/mask intermediates across devices."""
    mesh = current()
    if mesh is None:
        return a
    return jax.lax.with_sharding_constraint(
        a, NamedSharding(mesh, P(NODE_AXIS, *([None] * (a.ndim - 1)))))
