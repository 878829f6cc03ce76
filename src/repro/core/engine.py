"""Scan-fused segment engine: whole eval-to-eval spans in one XLA dispatch.

The legacy driver pays, per round: an eager ``sample_round_batches``, a
jitted conditions call, a jitted round call, a jitted timing call, and a
forced device->host sync (``float(round_bytes)``). At paper scale (5
algorithms x seeds x hundreds of rounds x netsim presets) that per-round
overhead dominates the tiny per-round compute.

This module folds everything between two evals into one ``lax.scan``:

* per-round batch sampling runs on device, keyed off a split of the
  carried PRNG (bit-identical to the legacy eager sampling);
* ``netsim.round_conditions`` is computed inside the scan from the scanned
  round counter (``start + arange(length)``);
* the algorithm round function — FACADE or any baseline, all sharing the
  ``fn(state, batches, net=conds) -> (state, info)`` stepper signature —
  advances the node-stacked state, which ``donate_argnums`` updates in
  place instead of copying every round;
* per-round scalars (``round_bytes``, simulated ``round_s``, FACADE's
  cluster ids) come back stacked ``[length, ...]`` and are drained to the
  host in ONE transfer per segment (``CommLog.record_bulk``).

FACADE's warmup/main phase split is two compiled segment variants (the
``warmup`` flag is static), so a run with warmup compiles at most
``{lengths} x {warmup, main}`` segment programs; ``segment_plan`` cuts the
round range at eval boundaries AND at the warmup->main boundary, never
inside a phase. ``target_acc`` early exit therefore happens at segment
granularity — exactly the rounds where the legacy driver evaluated.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import netsim
from repro import resil as resil_mod
from repro import topo as topo_mod
from repro.data import pipeline
from repro.obs import frame as obs_frame
from repro.obs.trace import span

from . import meshctx
from .netwire import round_seconds
from .state import EngineCarry


class Segment(NamedTuple):
    start: int           # first round of the span (0-based)
    length: int          # number of rounds fused into one dispatch
    warmup: bool         # FACADE warmup phase? (static at compile time)
    eval_at_end: bool    # the span's last round is an eval round


def segment_plan(rounds: int, eval_every: int,
                 warmup_rounds: int = 0) -> list[Segment]:
    """Cut ``range(rounds)`` into scan segments.

    Boundaries: every eval round (``(rnd+1) % eval_every == 0`` plus the
    final round — the legacy driver's eval schedule) and the warmup->main
    phase switch (a cut without an eval). Segments never straddle the
    warmup boundary, so the per-segment ``warmup`` flag can stay static.
    """
    evals = set(range(eval_every, rounds + 1, eval_every))
    if rounds > 0:
        evals.add(rounds)
    cuts = {0, rounds} | evals
    if 0 < warmup_rounds < rounds:
        cuts.add(warmup_rounds)
    cuts = sorted(cuts)
    return [Segment(a, b - a, a < warmup_rounds, b in evals)
            for a, b in zip(cuts[:-1], cuts[1:])]


class SegmentEngine:
    """Compiles and runs eval-to-eval spans for one (algorithm, net) pair.

    ``round_fn`` / ``warmup_fn``: the shared stepper signature
    ``fn(state, batches, net=conds, gossip=published, topo=tstate) ->
    (state, info)`` where ``info`` carries ``round_bytes``
    (+ ``adj_eff``/``payload_bytes`` under netsim, + ``cluster_id`` for
    FACADE). ``topo`` is the static :class:`repro.topo.TopoConfig` whose
    per-link EWMA state rides in the carry (``None`` => the legacy
    sampling path). Compiled segment programs are cached per
    ``(length, warmup)``; carries are donated, so the caller must treat the
    passed-in ``EngineCarry`` as consumed.

    ``mesh``: optional 1-D node mesh (``jax.sharding.Mesh`` or anything
    :func:`repro.core.meshctx.normalize` accepts). When set, the carry's
    node axis is laid out over the mesh devices (:meth:`place_carry`),
    the segment program is traced under the mesh context — so the
    cross-node contractions in :mod:`repro.core.bindings` lower as
    shard_map row blocks — and segment boundaries pin the carry layout
    with sharding constraints, keeping donation buffer-compatible across
    dispatches. ``mesh=None`` is bit-for-bit the historical single-device
    path: no context is activated and the traced program is unchanged.
    Per-row arithmetic is identical either way; only reductions ACROSS
    rows (``round_bytes``/``round_s``/obs-frame scalars) may sum in a
    different order on a multi-device mesh.

    ``compile_stats``: given the nodes each device holds, what the
    ``compile`` span states of the program besides ``nodes``
    (:func:`repro.core.bindings.compile_stats`: ``sgd_path``, ``model``,
    ``pack_groups``).
    """

    def __init__(self, round_fn: Callable, *, n: int, local_steps: int,
                 batch_size: int, net=None, warmup_fn: Callable | None = None,
                 track_cluster: bool = False, mixable_of: Callable | None = None,
                 topo=None, obs=None, mesh=None,
                 compile_stats: Callable | None = None):
        self._round = round_fn
        self._warm = warmup_fn if warmup_fn is not None else round_fn
        self._net = net
        self._topo = topo           # repro.topo.TopoConfig | None (static)
        self._obs = obs             # repro.obs.ObsConfig | None (static):
        #                             when set, every scanned round also
        #                             emits a MetricsFrame — an extra out
        #                             leaf stacked [length, ...], drained
        #                             in the segment's one device_get
        self._tiers = obs_frame.tiers_of(net, n) if obs is not None else None
        self._n = n
        self._h = local_steps
        self._b = batch_size
        self._track = track_cluster
        self._mixable_of = mixable_of
        self._mesh = meshctx.build(mesh) if not hasattr(mesh, "devices") \
            else mesh
        if self._mesh is not None and n % self._mesh.size != 0:
            raise ValueError(
                f"mesh of {self._mesh.size} devices must divide n={n} "
                "nodes evenly: the carry's node axis is row-sharded in "
                "equal blocks (pad the node count or shrink the mesh)")
        per_device = n if self._mesh is None else n // self._mesh.size
        self._stats = None if compile_stats is None else {
            **compile_stats(per_device), "nodes": n}
        self._compiled: dict[tuple[int, bool], Callable] = {}
        # compile_count tracks XLA compiles, not just fresh (length, warmup)
        # builds: a cached jitted segment RETRACES when the train arrays
        # change shape/dtype (the only traced args whose shapes aren't
        # pinned by the engine's config), so the counter is keyed on those
        # too — sweep drivers assert it plateaus once a cell is warm.
        self._traced: set[tuple] = set()
        self.compile_count = 0

    # -- run-level carry ----------------------------------------------------
    def init_carry(self, state, k_data) -> EngineCarry:
        """Mint the run's :class:`EngineCarry`: algorithm state, data PRNG,
        plus the netsim-v2 on-device state — the Gilbert–Elliott channel
        (``net.burst``) and the async staleness buffer (``net.async_gossip``;
        a leaf-for-leaf COPY of the initial mixable state so the buffer
        never aliases the donated training buffers) — plus the adaptive
        topology policy's link EWMAs (``None`` for uniform/off) and the
        node-crash chain (``net.faults``, :mod:`repro.resil`)."""
        net, n = self._net, self._n
        chan = netsim.init_channel(net, n) if net is not None else None
        gossip = None
        if net is not None and net.async_gossip:
            if self._mixable_of is None:
                raise ValueError(
                    "async_gossip needs mixable_of: construct the "
                    "SegmentEngine with mixable_of=<state -> gossip tree> "
                    "(runner.algo_program provides it)")
            gossip = netsim.init_gossip(net, n, self._mixable_of(state))
        topo = topo_mod.init_state(self._topo, net, n)
        fault = resil_mod.init_state(net, n, state)
        return self.place_carry(
            EngineCarry(state, k_data, chan, gossip, topo, fault))

    def place_carry(self, carry: EngineCarry) -> EngineCarry:
        """Commit the carry to the node-mesh layout (leading-``n`` leaves
        row-sharded, scalars/PRNG keys replicated) — identity when
        ``mesh=None``. Also the checkpoint-resume hook: a carry rebuilt
        from host arrays must be re-placed before dispatch so donation
        reuses correctly laid-out buffers."""
        if self._mesh is None:
            return carry
        return jax.device_put(
            carry, meshctx.carry_shardings(self._mesh, carry, self._n))

    def place_data(self, train_x, train_y):
        """Commit the node-stacked train arrays (leading ``[n, ...]``) to
        the node mesh — identity when ``mesh=None``. One placement per
        run; every segment dispatch then reads its node shard locally."""
        if self._mesh is None:
            return train_x, train_y
        sh = jax.sharding.NamedSharding(
            self._mesh, jax.sharding.PartitionSpec(meshctx.NODE_AXIS))
        return jax.device_put(train_x, sh), jax.device_put(train_y, sh)

    # -- one segment = one jitted scan --------------------------------------
    def _build(self, length: int, warmup: bool) -> Callable:
        round_fn = self._warm if warmup else self._round
        net, n, h, b, track = self._net, self._n, self._h, self._b, self._track
        mixable_of, tcfg = self._mixable_of, self._topo
        ocfg, tiers = self._obs, self._tiers
        mesh = self._mesh
        mix_of = mixable_of if mixable_of is not None else (lambda s: s)

        def segment(carry, start, train_x, train_y):
            # the mesh context is consulted at TRACE time (this body runs
            # under jit tracing): with a mesh, the carry layout is pinned
            # at entry/exit — donation then reuses identically-sharded
            # buffers — and the bindings' contractions see the context;
            # with mesh=None nothing here runs and the jaxpr is unchanged
            with meshctx.activate(mesh):
                if mesh is not None:
                    carry = jax.lax.with_sharding_constraint(
                        carry, meshctx.carry_shardings(mesh, carry, n))
                carry, outs = _scan(carry, start, train_x, train_y)
                if mesh is not None:
                    carry = jax.lax.with_sharding_constraint(
                        carry, meshctx.carry_shardings(mesh, carry, n))
                return carry, outs

        def _scan(carry, start, train_x, train_y):
            def step(carry, rnd):
                prev_state, k_data, chan, gossip, topo, fault = carry
                k_data, k_b = jax.random.split(k_data)
                batches = meshctx.constrain_tree(
                    pipeline.sample_round_batches(k_b, train_x, train_y,
                                                  h, b), n)
                conds = published = None
                if net is not None:
                    with jax.named_scope("netsim"):
                        conds, chan = netsim.advance_conditions(net, n, rnd,
                                                                chan)
                        conds, fault, restarted = resil_mod.advance(
                            net, n, rnd, conds, fault)
                        if restarted is not None:
                            prev_state = resil_mod.reset_nodes(
                                n, restarted, fault.init, prev_state)
                        conds, published = netsim.apply_async(net, conds,
                                                              gossip)
                state, info = round_fn(prev_state, batches, net=conds,
                                       gossip=published, topo=topo)
                with jax.named_scope("netsim"):
                    if published is not None:
                        gossip = netsim.fold_gossip(net, gossip, conds,
                                                    mixable_of(state))
                    # fold this round's observed conditions into the policy
                    # EWMAs AFTER the round: round t samples from what was
                    # seen up to t-1 (no-op when topo is off / net is None)
                    topo = topo_mod.advance(tcfg, net, topo, conds)
                out = {"round_bytes": info["round_bytes"],
                       "round_s": round_seconds(net, info, conds, h)}
                if track:
                    out["cluster_id"] = info["cluster_id"]
                if ocfg is not None:
                    out["frame"] = obs_frame.compute_frame(
                        ocfg, n, tiers, mix_of(prev_state), mix_of(state),
                        getattr(prev_state, "cluster_id", None),
                        getattr(state, "cluster_id", None), info, conds,
                        gossip)
                return EngineCarry(state, k_data, chan, gossip, topo,
                                   fault), out

            rnds = start + jnp.arange(length, dtype=jnp.int32)
            return jax.lax.scan(step, carry, rnds)

        return jax.jit(segment, donate_argnums=(0,))

    def dispatch_segment(self, carry: EngineCarry, start: int, length: int,
                         train_x, train_y, warmup: bool = False,
                         tracer=None):
        """Enqueue ``length`` rounds in one async dispatch — no host sync.

        Returns ``(new_carry, outs)`` where both are DEVICE values (the
        stacked per-round outs still live on device); pair with
        :meth:`drain` to pull ``outs`` to the host. The engine loop
        dispatches segment ``t+1`` off the fresh carry before draining
        segment ``t``'s scalars, so host-side bookkeeping overlaps device
        compute. The input ``carry`` is donated — consumed either way.

        The call is a ``compile`` span (first trace of this program in
        this process; it states ``compile_stats`` and ``nodes``) or a
        ``dispatch`` span (async: trace + enqueue only): a ``repro.*``
        profiler annotation, and a ``tracer`` span when a tracer is given
        (:func:`repro.obs.trace.span`).
        """
        key = (length, warmup)
        fn = self._compiled.get(key)
        if fn is None:
            fn = self._compiled[key] = self._build(length, warmup)
        trace_key = key + tuple((a.shape, str(a.dtype))
                                for a in (train_x, train_y))
        fresh = trace_key not in self._traced
        if fresh:
            self._traced.add(trace_key)
            self.compile_count += 1
        attrs = self._stats if fresh and self._stats is not None else {}
        with span(tracer, "compile" if fresh else "dispatch",
                  length=length, warmup=warmup, **attrs):
            return fn(carry, jnp.asarray(start, jnp.int32),
                      train_x, train_y)

    def drain(self, outs, tracer=None, length: int | None = None):
        """Pull a dispatched segment's stacked outs to the host (the
        segment's only device->host transfer). The engine loop drains
        segment ``t`` once ``t+1`` is dispatched, so the ``drain`` span is
        the residual wait for ``t``'s device compute + transfer, not the
        segment's device time."""
        with span(tracer, "drain",
                  **({} if length is None else {"length": length})):
            return jax.device_get(outs)
