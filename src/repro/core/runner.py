"""Experiment runner: drives any DL algorithm (FACADE / EL / D-PSGD / DEPRL
/ DAC) over a clustered dataset, evaluating per-cluster accuracy, fairness
metrics and communication volume — the harness behind every paper table.

Two interchangeable drivers share all setup and evaluation code:

* ``engine=True`` (default): the scan-fused segment engine
  (:mod:`repro.core.engine`) — one XLA dispatch and one device->host
  transfer per eval-to-eval span, donated state buffers, and segment
  ``t+1`` dispatched before segment ``t`` is drained;
* ``engine=False``: the legacy per-round Python loop, kept as the parity
  reference.

Both produce bit-identical trajectories for the same seed.

All seed-independent machinery (bindings, round closures, compiled segment
programs, evaluators) is resolved through a
:class:`repro.core.cache.EngineCache`; pass ``cache=`` to share compiles
across calls — that is how ``repro.sweep.run_sweep`` makes many-seed grids
pay XLA compilation once per cell. The default (``cache=None``) builds a
private fresh cache, i.e. exactly the historical per-call behavior.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.comm import CommLog
from repro.data import pipeline
from repro.models import cnn as cnn_mod
from repro import netsim
from repro import obs as obs_mod
from repro.obs.trace import span
from repro import resil as resil_mod
from repro import topo as topo_mod

from . import facade as facade_mod
from . import meshctx
from . import netwire
from .baselines import (DACConfig, DeprlConfig, DpsgdConfig, ELConfig,
                        dac_round, deprl_round, dpsgd_round, el_round,
                        init_dac_extra)
from .bindings import Binding
from .cache import EngineCache, EngineSpec, host_bytes  # noqa: F401 (re-export)
from .engine import segment_plan
from .state import EngineCarry, init_baseline_state, init_facade_state


@dataclasses.dataclass
class RunResult:
    algo: str
    acc_per_cluster: list      # history: [(round, [acc_c0, acc_c1, ...])]
    fair_acc: list             # [(round, fair_acc)]
    dp: float                  # final demographic parity
    eo: float                  # final equalized odds
    comm: CommLog
    cluster_history: list      # FACADE: [(round, cluster_id array)]
    final_acc: list            # per-cluster accuracy at the end
    node_acc: Any = None       # final per-NODE accuracy [n] (per-tier /
    #                            fairness-floor tables; repro.topo)
    eval_frames: list = dataclasses.field(default_factory=list)
    #                            per-eval EvalFrame fairness trajectory
    #                            (repro.obs.evalframe) — recorded for every
    #                            run, obs attached or not: pure host
    #                            bookkeeping over the arrays the evaluator
    #                            already drains

    def best_fair_acc(self) -> float:
        return max(v for _, v in self.fair_acc) if self.fair_acc else 0.0


# --------------------------------------------------------------------------
class AlgoSetup(NamedTuple):
    """Everything the drivers need, behind one stepper signature:
    ``round_fn(state, batches, net=conds, gossip=published, topo=tstate)
    -> (state, info)``."""
    state: Any                 # initial stacked state
    round_fn: Callable         # main-phase round
    warmup_fn: Callable        # warmup-phase round (== round_fn off-FACADE)
    models_of: Callable        # state -> deployable models, stacked [n, ...]
    finalize: Callable         # applied to the state after the last round
    track_cluster: bool        # info carries a per-round cluster_id [n]
    mixable_of: Callable       # state -> what gossip exchanges (async
    #                            staleness buffers snapshot this tree)


class AlgoProgram(NamedTuple):
    """The seed-INDEPENDENT part of an algorithm: round closures and state
    constructor. ``EngineCache`` memoizes programs per static config, so a
    sweep builds one and mints per-seed setups via :meth:`setup`."""
    init_state: Callable       # PRNG key -> initial stacked state
    round_fn: Callable
    warmup_fn: Callable
    models_of: Callable
    finalize: Callable
    track_cluster: bool
    mixable_of: Callable

    def setup(self, key) -> AlgoSetup:
        return AlgoSetup(self.init_state(key), self.round_fn, self.warmup_fn,
                         self.models_of, self.finalize, self.track_cluster,
                         self.mixable_of)


def algo_program(algo: str, binding: Binding, n: int, k: int, *,
                 degree: int, local_steps: int, lr: float,
                 warmup_rounds: int = 0, head_jitter: float = 0.0,
                 topo=None, faults=None) -> AlgoProgram:
    """``topo``: optional frozen :class:`repro.topo.TopoConfig`, closed
    over the round closures like the algorithm config (static at trace
    time); its per-link EWMA state is passed per round via the stepper's
    ``topo=`` kwarg. ``faults``: optional frozen
    :class:`repro.resil.FaultConfig` (== ``net.faults``), closed over the
    same way — payload corruption + the robust aggregation guard."""
    if algo == "facade":
        fcfg = facade_mod.FacadeConfig(
            n_nodes=n, k=k, degree=degree, local_steps=local_steps, lr=lr,
            warmup_rounds=warmup_rounds, head_jitter=head_jitter)
        return AlgoProgram(
            init_state=lambda key: init_facade_state(
                binding, key, n, k, head_jitter=head_jitter),
            round_fn=functools.partial(facade_mod.facade_round, fcfg,
                                       binding, warmup=False,
                                       topo_cfg=topo, fault_cfg=faults),
            warmup_fn=functools.partial(facade_mod.facade_round, fcfg,
                                        binding, warmup=True,
                                        topo_cfg=topo, fault_cfg=faults),
            models_of=lambda s: facade_mod.node_models(s, binding),
            finalize=functools.partial(facade_mod.final_allreduce, fcfg),
            track_cluster=True,
            mixable_of=lambda s: {"cores": s.cores, "heads": s.heads,
                                  "cluster_id": s.cluster_id})
    if algo in ("el", "dpsgd", "deprl", "dac"):
        cfg_cls = {"el": ELConfig, "dpsgd": DpsgdConfig,
                   "deprl": DeprlConfig, "dac": DACConfig}[algo]
        acfg = cfg_cls(n_nodes=n, degree=degree, local_steps=local_steps,
                       lr=lr)
        round_fn = {"el": el_round, "dpsgd": dpsgd_round,
                    "deprl": deprl_round, "dac": dac_round}[algo]
        fn = functools.partial(round_fn, acfg, binding, topo_cfg=topo,
                               fault_cfg=faults)
        return AlgoProgram(
            init_state=lambda key: init_baseline_state(
                binding, key, n,
                extra=init_dac_extra(n) if algo == "dac" else None),
            round_fn=fn, warmup_fn=fn,
            models_of=lambda s: s.params,
            finalize=lambda s: s, track_cluster=False,
            mixable_of=lambda s: s.params)
    raise ValueError(f"unknown algorithm {algo!r}")


def algo_setup(algo: str, binding: Binding, key, n: int, k: int, *,
               degree: int, local_steps: int, lr: float,
               warmup_rounds: int = 0, head_jitter: float = 0.0,
               topo=None, faults=None) -> AlgoSetup:
    return algo_program(algo, binding, n, k, degree=degree,
                        local_steps=local_steps, lr=lr,
                        warmup_rounds=warmup_rounds,
                        head_jitter=head_jitter, topo=topo,
                        faults=faults).setup(key)


# --------------------------------------------------------------------------
def make_evaluator(binding: Binding, node_cluster, test_x, test_y,
                   batch: int = 256) -> Callable:
    """Vmapped, padded per-cluster evaluator.

    Replaces the legacy Python node-loop: every node of a cluster runs the
    whole (zero-padded, masked) test set in ONE jit dispatch per cluster —
    a ``lax.map`` over fixed-shape eval batches with the node axis vmapped
    inside. Built once per experiment so compiles are reused across evals.

    Returns ``evaluate(models) -> (acc_per_cluster, preds_c, labels_c,
    node_acc)`` — per-cluster mean node accuracy and the first node's
    predictions per cluster for DP/EO (the legacy contract), plus the
    per-NODE accuracy vector ``[n]`` the per-tier fairness tables
    (adaptive topology, :mod:`repro.topo`) consume.

    Empty clusters — the imbalanced-cluster grids can assign a cluster
    zero nodes — are SKIPPED, not crashed on: they contribute no entry to
    ``acc_per_cluster``/``preds_c``/``labels_c`` (and therefore drop out
    of fair-accuracy and DP/EO, which compare the clusters that exist).
    ``evaluate.cluster_ids`` records which cluster each returned entry
    belongs to; with no empty clusters it is exactly ``range(k)``.

    ``evaluate.begin(models)`` / ``evaluate.finish(pending)`` split the
    call at the dispatch boundary: ``begin`` enqueues every per-cluster
    prediction asynchronously (no host sync), ``finish`` drains and
    reduces. The engine loop uses the split to overlap the eval's host
    reduction with the next segment's device compute;
    ``evaluate(models)`` == ``finish(begin(models))``. ``begin`` is
    ``evaluate.predict`` over ``evaluate.inputs(models)``, the per-cluster
    (gathered models, eval batches) pairs — exposed so their device
    placement and the program's memory can be inspected.
    """
    cfg = binding.cfg
    node_cluster = np.asarray(node_cluster)
    clusters = []
    for c in range(len(test_x)):
        idx = np.where(node_cluster == c)[0]
        if idx.size == 0:
            continue        # empty cluster: nothing to evaluate
        x = np.asarray(test_x[c])
        # cap the batch at the test-set size: padding waste stays < one row
        xb, mask = pipeline.padded_eval_batches(
            x, min(batch, max(1, x.shape[0])))
        clusters.append((idx, xb, mask.reshape(-1) > 0,
                         np.asarray(test_y[c])))
    # the padded test batches go to the device once, when the evaluator
    # is built (no tracer here: the cache builds evaluators)
    with span(None, "upload", bytes=sum(xb.nbytes for _, xb, _, _ in
                                        clusters)):
        clusters = [(idx, jnp.asarray(xb), valid, y)
                    for idx, xb, valid, y in clusters]

    @jax.jit
    def predict(models_c, xb):                       # xb [nb, B, ...]
        def per_batch(x):
            logits = jax.vmap(
                lambda p: cnn_mod.forward(cfg, p, x))(models_c)
            return jnp.argmax(logits, -1)            # [m, B]

        with jax.named_scope("predict"):
            return jax.lax.map(per_batch, xb)        # [nb, m, B]

    def inputs(models):
        return [(jax.tree.map(lambda l: l[idx], models), xb)
                for idx, xb, _, _ in clusters]

    def begin(models):
        return [predict(models_c, xb) for models_c, xb in inputs(models)]

    def finish(pending):
        accs, preds_c, labels_c = [], [], []
        node_acc = np.zeros(node_cluster.shape[0], np.float64)
        for (idx, _, valid, y), pred in zip(clusters, pending):
            p = np.asarray(pred)                     # [nb, m, B]
            p = np.moveaxis(p, 1, 0).reshape(len(idx), -1)[:, valid]
            eq = p == y[None, :]
            accs.append(float(eq.mean()))
            node_acc[idx] = eq.mean(axis=1)
            preds_c.append(p[0])
            labels_c.append(y)
        return accs, preds_c, labels_c, node_acc

    def evaluate(models):
        return finish(begin(models))

    evaluate.begin = begin
    evaluate.finish = finish
    evaluate.inputs = inputs        # per cluster: (gathered models, batches)
    evaluate.predict = predict      # the jitted per-cluster program
    evaluate.cluster_ids = tuple(int(node_cluster[idx[0]])
                                 for idx, _, _, _ in clusters)
    return evaluate


# --------------------------------------------------------------------------
class _History:
    """Shared bookkeeping for both drivers: comm log, eval histories,
    weighted mean accuracy and the target-accuracy stop condition."""

    def __init__(self, node_cluster, n: int, evaluator, models_of,
                 target_acc, verbose: bool, algo: str, n_classes: int,
                 tiers=None, obs=None):
        self.comm = CommLog()
        self.acc_hist, self.fair_hist, self.cluster_hist = [], [], []
        self.dp = self.eo = 0.0
        self.accs = []
        self.node_acc = None
        self.eval_frames = []           # per-eval EvalFrame trajectory
        self._prev_eval_cid = None      # cluster ids at the previous eval
        #                                 (the churn baseline)
        self._weights = np.asarray(node_cluster)
        self._n = n
        self._evaluator = evaluator
        self._models_of = models_of
        self._target = target_acc
        self._verbose = verbose
        self._algo = algo
        self._n_classes = n_classes
        self._tiers = None if tiers is None else np.asarray(tiers)
        self._obs = obs

    def eval_begin(self, state):
        """Enqueue the eval's per-cluster predictions asynchronously (no
        host sync) — the engine loop calls this BEFORE dispatching the
        next segment (which donates the state buffers), then settles with
        :meth:`eval_finish` while that segment computes.

        Alongside the prediction dispatches, an async device COPY of the
        state's cluster assignment is enqueued (FACADE only) for the
        EvalFrame's churn column — ``jnp.copy``, not a host read, so the
        buffer survives the next segment's donation without a sync."""
        cid = getattr(state, "cluster_id", None)
        return (self._evaluator.begin(self._models_of(state)),
                None if cid is None else jnp.copy(cid))

    def eval_round(self, state, rnd: int, round_bytes: float,
                   round_s: float) -> bool:
        """Evaluate at round ``rnd`` (1-based), record, and report whether
        ``target_acc`` is reached (the driver then stops)."""
        return self.eval_finish(self.eval_begin(state), rnd, round_bytes,
                                round_s)

    def eval_finish(self, pending, rnd: int, round_bytes: float,
                    round_s: float) -> bool:
        pending, eval_cid = pending
        accs, preds_c, labels_c, node_acc = self._evaluator.finish(pending)
        cids = getattr(self._evaluator, "cluster_ids",
                       tuple(range(len(accs))))
        self.accs = accs
        self.node_acc = node_acc
        self.acc_hist.append((rnd, accs))
        # node-weighted mean over the clusters that exist; with no empty
        # clusters ``cids == range(len(accs))`` and this is bit-for-bit
        # the historical enumerate() formula
        mean_acc = float(np.mean(
            [a * (self._weights == c).sum()
             for c, a in zip(cids, accs)]) * len(accs) / self._n)
        # ONE shared hook (the eval twin of compute_frame): DP/EO/fair-acc
        # are computed inside the frame with the same repro.fairness calls
        # this method historically made, and the run's final scalars are
        # read OFF the frame — the series' last entry IS the final scalar,
        # bit-for-bit, on both drivers
        eval_cid = None if eval_cid is None else np.asarray(eval_cid)
        frame = obs_mod.compute_eval_frame(
            rnd, accs, cids, preds_c, labels_c, node_acc,
            self._n_classes, mean_acc=mean_acc, tiers=self._tiers,
            prev_cid=self._prev_eval_cid, cid=eval_cid)
        self._prev_eval_cid = eval_cid
        self.eval_frames.append(frame)
        if self._obs is not None:
            self._obs.record_eval(frame)
        self.fair_hist.append((rnd, frame.fair_acc))
        self.dp = frame.dp
        self.eo = frame.eo
        self.comm.record(rnd, round_bytes, mean_acc, round_s=round_s)
        if self._verbose:
            print(f"  [{self._algo}] round {rnd}: acc={accs} "
                  f"fair={frame.fair_acc:.3f}")
        return self._target is not None and mean_acc >= self._target

    def result(self, algo: str) -> RunResult:
        return RunResult(algo=algo, acc_per_cluster=self.acc_hist,
                         fair_acc=self.fair_hist, dp=self.dp, eo=self.eo,
                         comm=self.comm, cluster_history=self.cluster_hist,
                         final_acc=self.accs, node_acc=self.node_acc,
                         eval_frames=self.eval_frames)


# --------------------------------------------------------------------------
def run_experiment(algo: str, cfg, dataset, *, rounds: int, k: int | None = None,
                   degree: int = 4, local_steps: int = 10, batch_size: int = 8,
                   lr: float = 0.05, eval_every: int = 20, seed: int = 0,
                   warmup_rounds: int = 0, head_jitter: float = 0.0,
                   target_acc: float | None = None,
                   net: "netsim.NetworkConfig | None" = None,
                   topo: "topo_mod.TopoConfig | None" = None,
                   engine: bool = True,
                   mesh=None,
                   cache: EngineCache | None = None,
                   eval_batch: int = 256,
                   obs: "obs_mod.Obs | None" = None,
                   ckpt: "str | None" = None,
                   verbose: bool = False) -> RunResult:
    """Run one (algorithm, dataset) experiment end to end (CNN models).

    ``net``: optional :class:`repro.netsim.NetworkConfig` — simulate churn,
    message loss, stragglers and link latency/bandwidth for ANY algorithm
    (e.g. ``net=NetworkConfig.preset("edge-churn")``). The returned
    ``CommLog`` then carries simulated wall-clock seconds next to bytes.
    ``None`` keeps the historical ideal-medium path untouched.

    ``topo``: optional :class:`repro.topo.TopoConfig` — an adaptive,
    netsim-aware topology policy (per-link delivery/time EWMAs carried
    on device, Gumbel-top-k sampling, ``min_inclusion`` fairness floor).
    ``None`` and ``TopoConfig(policy="uniform")`` are bit-for-bit the
    legacy sampling path for every algorithm and both drivers.

    ``engine``: ``True`` compiles whole eval-to-eval spans into one XLA
    dispatch (scan-fused segment engine, the fast path); ``False`` runs the
    legacy per-round loop. Same seed => bit-identical trajectories.

    ``mesh`` (engine driver only): shard the node axis across devices —
    an int / 1-tuple device count or a 1-D ``jax.sharding.Mesh`` (see
    :mod:`repro.core.meshctx`; ``launch.mesh.make_node_mesh`` builds one).
    The donated carry is row-sharded over the mesh, gossip mixing becomes
    a shard_map row-block matmul, and everything else (vmapped local
    training, netsim/topo/resil row ops) partitions via GSPMD. The node
    count must divide evenly by the mesh size. ``mesh=None`` (default) is
    bit-for-bit the historical single-device path; on a mesh, per-row
    state is identical but cross-node scalar REDUCTIONS (round bytes /
    seconds, obs frames) can sum in a different order — compare those
    with a tolerance. The mesh shape is part of the cache key, so
    sharded and unsharded programs never collide in an ``EngineCache``.

    ``cache``: optional :class:`repro.core.cache.EngineCache` shared across
    calls — a sweep of seeds over one config then pays the XLA compiles
    once (see :mod:`repro.sweep`), and every call after the first over the
    same dataset object reuses its train split already on the device
    (:meth:`~repro.core.cache.EngineCache.train_data`). So a dataset's
    arrays, train and eval split alike, must not be changed in place
    after first use: build a new dataset, or assign a new array. ``None``
    (the default) uses a fresh private cache, which is bit-identical to
    the historical build-everything-per-call behavior.

    ``obs``: optional :class:`repro.obs.Obs` — in-scan per-round metric
    frames (when ``obs.config`` is set), nested tracer spans around
    compile / dispatch / drain / eval, cache hit/miss events, and a
    :class:`repro.obs.RunManifest` at the end of the run. ``None`` is
    bit-for-bit the untelemetered path; an attached ``Obs`` never
    perturbs the trajectory either (telemetry is pure observation).

    ``ckpt``: optional checkpoint path (engine driver only). After every
    segment the full :class:`EngineCarry`, the ``CommLog``/eval histories
    and the drained obs frames are snapshotted atomically
    (write-temp-then-rename, :mod:`repro.checkpoint`); rerunning the SAME
    call with the same path resumes from the last completed segment and
    finishes bit-for-bit identical to an uninterrupted run — segment
    boundaries are exactly the eval boundaries, and everything that
    crosses them (data PRNG, netsim channel, async gossip, topo EWMAs,
    crash chain) lives in the carry. A checkpoint written by a DIFFERENT
    run configuration is refused (fingerprint mismatch), never silently
    reused. The cost: a checkpointed run holds one extra device copy of
    the carry between dispatches, because segment ``t+1`` is dispatched
    (donating the carry) before segment ``t``'s checkpoint is written.
    """
    if ckpt is not None and not engine:
        raise ValueError(
            "ckpt= needs the segment engine (engine=True): the legacy "
            "per-round loop has no segment boundaries to snapshot at")
    mesh = meshctx.normalize(mesh)
    if mesh is not None and not engine:
        raise ValueError(
            "mesh= needs the segment engine (engine=True): the legacy "
            "per-round loop is the single-device parity reference and "
            "never shards")
    if eval_every <= 0:
        raise ValueError(
            f"eval_every={eval_every} must be a positive round count: the "
            "drivers schedule an eval every eval_every-th round, so 0 "
            "divides by zero and negative values silently degrade to a "
            "single final-round eval")
    if target_acc is not None and eval_every > rounds:
        raise ValueError(
            f"target_acc={target_acc} can never trigger an early exit with "
            f"eval_every={eval_every} > rounds={rounds}: no eval is "
            "scheduled before the run's final round. Lower eval_every (or "
            "raise rounds, or drop target_acc).")
    if algo != "facade":
        warmup_rounds = 0   # only FACADE has a warmup phase; normalizing
                            # here keeps baseline cache keys from forking
    n = dataset.n_nodes
    k = k if k is not None else dataset.k
    if mesh is not None and n % mesh[0] != 0:
        raise ValueError(
            f"mesh={mesh} must divide n={n} nodes evenly: the engine "
            "row-shards the node axis in equal blocks per device")
    for r in {degree, topo_mod.budget(topo, degree)}:
        if not 1 <= r < n:
            raise ValueError(
                f"degree={r} out of range for n={n} nodes: the topology "
                "builders silently collapse multi-edges at degree >= n; "
                "pick 1 <= degree <= n - 1")
    key = jax.random.PRNGKey(seed)
    k_init, k_data = jax.random.split(key)

    cache = cache if cache is not None else EngineCache()
    tracer = obs.tracer if obs is not None else None
    spec = EngineSpec(
        algo=algo, cfg=cfg, n=n, k=k, degree=degree,
        local_steps=local_steps, batch_size=batch_size, lr=lr,
        warmup_rounds=warmup_rounds, head_jitter=head_jitter, net=net,
        eval_batch=eval_batch, topo=topo,
        obs=obs.config if obs is not None else None, mesh=mesh)
    if obs is not None:
        obs.begin_run(algo=algo, seed=seed, rounds=rounds, engine=engine)
    misses0 = cache.misses
    with span(tracer, "cache.entry", algo=algo):
        entry = cache.entry(spec, tracer=tracer)
    if tracer is not None:
        tracer.event("cache.miss" if cache.misses > misses0
                     else "cache.hit", algo=algo, seed=seed)
    prof = obs.profile() if obs is not None else contextlib.nullcontext()
    # pin the entry while the run is live: an LRU-bounded cache must never
    # evict the engine whose donated carry/segment programs are in flight
    with prof, cache.pin(spec), \
            span(tracer, "run", algo=algo, seed=seed, engine=engine):
        # the node-stacked train arrays on the device, on the entry's node
        # mesh when there is one so every segment reads its shard locally;
        # the cache keeps them there for its next run over this dataset
        train_x, train_y = cache.train_data(entry, dataset, tracer=tracer)
        with span(tracer, "setup"):
            setup = entry.setup(k_init)
        builds0 = cache.evaluator_builds
        evaluator = cache.evaluator(entry.binding, dataset,
                                    batch=spec.eval_batch)
        if tracer is not None and cache.evaluator_builds > builds0:
            tracer.event("evaluator.build", batch=spec.eval_batch)
        hist = _History(dataset.node_cluster, n, evaluator, setup.models_of,
                        target_acc, verbose, algo,
                        entry.binding.cfg.n_classes,
                        tiers=(np.asarray(obs_mod.tiers_of(net, n))
                               if net is not None else None),
                        obs=obs)
        ckpt_fp = None
        if ckpt is not None:
            # everything that shapes the trajectory or the resume schedule;
            # a stale checkpoint from any other configuration is refused
            ckpt_fp = obs_mod.fingerprint({
                "spec": repr(spec), "seed": seed, "rounds": rounds,
                "eval_every": eval_every, "warmup_rounds": warmup_rounds,
                "target": repr(target_acc)})
        if engine:
            _drive_engine(entry.engine, setup, hist, k_data, train_x,
                          train_y, rounds=rounds, eval_every=eval_every,
                          warmup_rounds=warmup_rounds, obs=obs,
                          ckpt=ckpt, ckpt_fp=ckpt_fp)
        else:
            _drive_legacy(setup, hist, k_data, train_x, train_y,
                          rounds=rounds, eval_every=eval_every,
                          warmup_rounds=warmup_rounds,
                          local_steps=local_steps, batch_size=batch_size,
                          net=net, n=n, topo=topo, obs=obs)
    if obs is not None:
        health = None
        if obs.health_config is not None:
            ctx = obs_mod.HealthContext(
                n=n, warmup_rounds=warmup_rounds,
                inclusion_floor=(topo.min_inclusion
                                 if topo_mod.adaptive(topo) else None),
                faults=net is not None and net.faults is not None)
            health = obs_mod.evaluate_health(
                obs.health_config, ctx, obs.run_frames_table(),
                obs.run_eval_table(), tracer=obs.tracer).to_json()
        sink_path = getattr(obs.sink, "path", None)
        obs.end_run(obs_mod.RunManifest.build(
            kind="run", name=f"{algo}-seed{seed}", spec=spec,
            settings={"rounds": rounds, "eval_every": eval_every,
                      "engine": engine, "seed": seed, "net": repr(net),
                      "topo": repr(topo), "obs": repr(obs.config),
                      "jsonl": (None if sink_path is None
                                else str(sink_path))},
            timing=obs.tracer.rollup(), cache=cache.stats(),
            health=health))
    return hist.result(algo)


# --------------------------------------------------------------------------
def _hist_snapshot(hist: _History) -> dict:
    """The :class:`_History` as a checkpoint-able pytree (plain arrays);
    inverse of :func:`_hist_restore`. float64/int64 round-trip exactly, so
    a restored history is bit-for-bit the live one."""
    c = hist.comm
    return {
        "comm": {"rounds": np.asarray(c.rounds, np.int64),
                 "bytes": np.asarray(c.bytes, np.float64),
                 "seconds": np.asarray(c.seconds, np.float64),
                 "acc": np.asarray(c.acc, np.float64),
                 "evaled": np.asarray(c.evaled, np.bool_)},
        "acc_hist": [{"round": np.asarray(r, np.int64),
                      "accs": np.asarray(a, np.float64)}
                     for r, a in hist.acc_hist],
        "fair_hist": {
            "rounds": np.asarray([r for r, _ in hist.fair_hist], np.int64),
            "vals": np.asarray([v for _, v in hist.fair_hist], np.float64)},
        "cluster_hist": [{"round": np.asarray(r, np.int64),
                          "cid": np.asarray(cid)}
                         for r, cid in hist.cluster_hist],
        "dp": np.asarray(hist.dp, np.float64),
        "eo": np.asarray(hist.eo, np.float64),
        "accs": np.asarray(hist.accs, np.float64),
        "node_acc": (None if hist.node_acc is None
                     else np.asarray(hist.node_acc)),
        # the per-eval fairness trajectory: one dict of float64/int64
        # arrays per EvalFrame (plain floats round-trip exactly, so the
        # resumed trajectory is bit-for-bit the live one)
        "eval_frames": [
            {name: np.asarray(getattr(f, name),
                              np.int64 if name in ("round", "cluster_ids")
                              else np.float64)
             for name in obs_mod.EVAL_FIELDS}
            for f in hist.eval_frames],
        "prev_eval_cid": (None if hist._prev_eval_cid is None
                          else np.asarray(hist._prev_eval_cid)),
    }


def _hist_restore(hist: _History, snap: dict):
    """Rehydrate ``hist`` from a :func:`_hist_snapshot` pytree, restoring
    the exact Python container types the drivers append (lists of ints /
    floats / tuples) so downstream consumers can't tell a resumed run
    from an uninterrupted one."""
    c = hist.comm
    c.rounds = [int(v) for v in snap["comm"]["rounds"]]
    c.bytes = [float(v) for v in snap["comm"]["bytes"]]
    c.seconds = [float(v) for v in snap["comm"]["seconds"]]
    c.acc = [float(v) for v in snap["comm"]["acc"]]
    c.evaled = [bool(v) for v in snap["comm"]["evaled"]]
    hist.acc_hist = [(int(e["round"]), [float(a) for a in e["accs"]])
                     for e in snap["acc_hist"]]
    hist.fair_hist = [(int(r), float(v))
                      for r, v in zip(snap["fair_hist"]["rounds"],
                                      snap["fair_hist"]["vals"])]
    hist.cluster_hist = [(int(e["round"]), np.asarray(e["cid"]))
                         for e in snap["cluster_hist"]]
    hist.dp = float(snap["dp"])
    hist.eo = float(snap["eo"])
    hist.accs = [float(a) for a in snap["accs"]]
    hist.node_acc = (None if snap["node_acc"] is None
                     else np.asarray(snap["node_acc"]))
    # defensive .get: checkpoints written before the eval-frame series
    # existed restore to an empty trajectory instead of KeyError-ing
    hist.eval_frames = []
    for e in snap.get("eval_frames", []):
        frame = obs_mod.EvalFrame(
            round=int(e["round"]),
            acc=tuple(float(a) for a in np.atleast_1d(e["acc"])),
            cluster_ids=tuple(int(c)
                              for c in np.atleast_1d(e["cluster_ids"])),
            **{name: float(e[name]) for name in obs_mod.EVAL_SCALAR_FIELDS
               if name != "round"})
        hist.eval_frames.append(frame)
        if hist._obs is not None:
            # replay into the live Obs, like the metrics-frame sidecars:
            # eval_table / health / JSONL see the pre-crash evals too
            hist._obs.record_eval(frame)
    prev = snap.get("prev_eval_cid")
    hist._prev_eval_cid = None if prev is None else np.asarray(prev)


def _frame_path(ckpt: str, index: int) -> str:
    return f"{ckpt}.frames-{index}.npz"


def _ckpt_save(path: str, fp: str, carry: EngineCarry, hist: _History,
               new_frames, n_frame_files: int, next_segment: int,
               finished: bool) -> int:
    """Snapshot the whole resumable run state at a segment boundary:
    the drained :class:`EngineCarry` (algorithm state + data PRNG + netsim
    channel + async gossip + topo EWMAs + crash chain), the eval/comm
    histories, and — when obs frames are enabled — THIS segment's drained
    frames (``new_frames = (rounds, MetricsFrame)`` or ``None``).

    Frames are append-only sidecar files (``<path>.frames-<i>.npz``), one
    per frame-bearing segment, so the per-segment write cost stays ~flat:
    the main archive rewrites only the carry + the (scalar-sized)
    histories, never the accumulated frame payloads — checkpoint I/O is
    O(segments), not the O(segments^2) a rewrite-everything layout costs
    on long obs-enabled runs. The sidecar is written BEFORE the main
    archive, whose meta records how many sidecars are valid
    (``frame_files``); a crash in between leaves an orphan the next run
    deterministically overwrites. Each write is atomic via
    :func:`repro.checkpoint.save`. Returns the updated sidecar count."""
    if new_frames is not None:
        rnds, fr = new_frames
        checkpoint.save(
            _frame_path(path, n_frame_files),
            {"rounds": np.asarray(rnds, np.int64),
             "frame": tuple(None if l is None else np.asarray(l)
                            for l in fr)},
            meta={"fingerprint": fp, "index": int(n_frame_files)})
        n_frame_files += 1
    checkpoint.save(path, {"carry": jax.device_get(carry),
                           "hist": _hist_snapshot(hist)},
                    meta={"fingerprint": fp,
                          "next_segment": int(next_segment),
                          "finished": bool(finished),
                          "frame_files": int(n_frame_files)})
    return n_frame_files


def _ckpt_resume(ckpt, ckpt_fp, carry, hist, obs, tracer):
    """Fast-forward a checkpointed run: rebuild the carry leaf-for-leaf on
    the freshly minted template (the checkpoint stores plain tuples/dicts,
    the template restores the NamedTuple treedef and None placement the
    engine donates), rehydrate the histories, and replay every frame
    sidecar into the new ``Obs``. Returns ``(carry, start_idx,
    n_frame_files, finished)``."""
    payload, meta = checkpoint.load(ckpt)
    if meta.get("fingerprint") != ckpt_fp:
        raise ValueError(
            f"checkpoint {ckpt!r} was written by a different run "
            "configuration (fingerprint mismatch) — refusing to "
            "resume from it; delete the file or pick a fresh path")
    carry = jax.tree.unflatten(
        jax.tree.structure(carry),
        [jnp.asarray(l) for l in jax.tree.leaves(payload["carry"])])
    _hist_restore(hist, payload["hist"])
    n_frame_files = int(meta.get("frame_files", 0))
    for j in range(n_frame_files):
        rec, fmeta = checkpoint.load(_frame_path(ckpt, j))
        if fmeta.get("fingerprint") != ckpt_fp:
            raise ValueError(
                f"frame sidecar {_frame_path(ckpt, j)!r} does not match "
                f"checkpoint {ckpt!r} (fingerprint mismatch) — refusing "
                "to resume; delete the checkpoint files to restart")
        if obs is not None:
            obs.record_frames(np.asarray(rec["rounds"]),
                              obs_mod.MetricsFrame(*rec["frame"]))
    if tracer is not None:
        tracer.event("ckpt.resume", segment=int(meta["next_segment"]),
                     finished=bool(meta.get("finished")))
    return (carry, int(meta["next_segment"]), n_frame_files,
            bool(meta.get("finished")))


def _drive_engine(eng, setup: AlgoSetup, hist: _History, k_data,
                  train_x, train_y, *, rounds, eval_every, warmup_rounds,
                  obs=None, ckpt=None, ckpt_fp=None):
    """The segment-engine loop: one dispatch + one host transfer per span,
    and while the host drains and processes segment ``t``, the device
    already computes segment ``t+1``. ``eng`` comes from the run's
    :class:`EngineCache` entry, so repeated runs of one config reuse its
    compiled segment programs.

    Order per segment ``t`` — the ordering is what makes donation safe:

    1. enqueue ``t``'s finalize (last segment) and eval (async
       ``predict`` dispatches reading ``carry.state``) and, under
       ``ckpt``, an async device COPY of the carry — all capture the
       buffers BEFORE they are donated;
    2. dispatch ``t+1`` off the fresh carry (donates it);
    3. drain ``t``'s stacked scalars and do its host bookkeeping
       (``record_bulk``, eval reduction, cluster history, checkpoint
       write), overlapping ``t+1``'s device compute.

    Segments are processed on the host strictly in order with the values
    the legacy loop computes, so the trajectory is its bit for bit. A
    ``target_acc`` hit abandons the one dispatched segment after it (its
    carry was consumed, its outs are never drained). ``obs``: the run's
    :class:`repro.obs.Obs` — its tracer instruments every segment
    (compile/dispatch/drain spans) and eval (both halves under ``eval``),
    and the segment's stacked ``MetricsFrame`` (drained in the one bulk
    ``device_get``) is handed over whole — on a ``target_acc`` hit the
    full segment is recorded (frames are pure observation; the early exit
    only truncates the comm/cluster histories, matching the legacy loop's
    break).

    ``ckpt``/``ckpt_fp``: crash-safe resume. After every segment the carry
    + histories + frames are checkpointed (atomically); on entry, an
    existing checkpoint with a matching fingerprint fast-forwards the run
    to its ``next_segment``. Segments are deterministic functions of the
    carry, so the resumed trajectory is bit-for-bit the uninterrupted one.
    """
    tracer = obs.tracer if obs is not None else None
    plan = segment_plan(rounds, eval_every, warmup_rounds)
    with span(tracer, "setup"):
        carry = eng.init_carry(setup.state, k_data)
    start_idx = 0
    n_frames = 0        # frame sidecar files already on disk
    if ckpt is not None and os.path.exists(ckpt):
        carry, start_idx, n_frames, finished = _ckpt_resume(
            ckpt, ckpt_fp, carry, hist, obs, tracer)
        # re-commit the rebuilt carry to the engine's node-mesh layout
        # (identity off-mesh): donation needs correctly sharded buffers
        carry = eng.place_carry(carry)
        if finished:
            return
    if start_idx == len(plan):
        return                              # rounds=0: nothing to run

    def dispatch(i, c):
        s = plan[i]
        return eng.dispatch_segment(c, s.start, s.length, train_x,
                                    train_y, warmup=s.warmup,
                                    tracer=tracer)

    carry, pending = dispatch(start_idx, carry)
    for idx in range(start_idx, len(plan)):
        seg = plan[idx]
        last = idx + 1 == len(plan)
        rnd = seg.start + seg.length
        ev = None
        if seg.eval_at_end:
            state = carry.state
            if rnd == rounds:
                with span(tracer, "finalize"):
                    state = setup.finalize(state)
                carry = carry._replace(state=state)
            with span(tracer, "eval", round=rnd):
                ev = hist.eval_begin(state)
        saved, nxt = carry, None
        if not last:
            if ckpt is not None:
                saved = _snapshot(carry)
            carry, nxt = dispatch(idx + 1, carry)
        outs = eng.drain(pending, tracer=tracer, length=seg.length)
        pending = nxt
        rnds = np.arange(seg.start + 1, rnd + 1)
        if obs is not None and "frame" in outs:
            obs.record_frames(rnds, outs["frame"])
        _record_comm(hist, rnds, outs, seg.eval_at_end, tracer)
        hit = False
        if seg.eval_at_end:
            with span(tracer, "eval", round=rnd):
                hit = hist.eval_finish(ev, rnd,
                                       float(outs["round_bytes"][-1]),
                                       float(outs["round_s"][-1]))
        _record_clusters(hist, setup, rnds, outs, hit, tracer)
        if ckpt is not None:
            new_fr = (rnds, outs["frame"]) if "frame" in outs else None
            finished = hit or last
            with span(tracer, "ckpt.save", segment=idx, finished=finished):
                n_frames = _ckpt_save(ckpt, ckpt_fp, saved, hist, new_fr,
                                      n_frames, idx + 1, finished)
        if hit:
            break


def _snapshot(carry: EngineCarry) -> EngineCarry:
    """An async device copy of ``carry``: the checkpoint reads its values
    after the next dispatch has donated the carry's buffers."""
    return jax.tree.map(jnp.copy, carry)


def _record_comm(hist: _History, rnds, outs, eval_at_end: bool, tracer):
    """Log a drained segment's per-round bytes and seconds, all but an
    eval round's (the eval records that one)."""
    m = len(rnds) - 1 if eval_at_end else len(rnds)
    with span(tracer, "record"):
        hist.comm.record_bulk(rnds[:m], outs["round_bytes"][:m],
                              outs["round_s"][:m])


def _record_clusters(hist: _History, setup: AlgoSetup, rnds, outs,
                     hit: bool, tracer):
    """Append a drained segment's per-round cluster ids (FACADE)."""
    if not setup.track_cluster:
        return
    # legacy parity: on a target_acc hit the loop broke BEFORE appending
    # the eval round's cluster ids
    with span(tracer, "record"):
        for i in range(len(rnds) - 1 if hit else len(rnds)):
            hist.cluster_hist.append(
                (int(rnds[i]), np.asarray(outs["cluster_id"][i])))


def _drive_legacy(setup: AlgoSetup, hist: _History, k_data, train_x, train_y,
                  *, rounds, eval_every, warmup_rounds, local_steps,
                  batch_size, net, n, topo=None, obs=None):
    """Legacy per-round driver: eager sampling, one jitted dispatch per
    round, per-round host syncs. Kept as the engine's parity reference and
    the benchmark baseline. ``topo`` is the static TopoConfig; its EWMA
    state is threaded through Python and advanced by the SAME
    ``repro.topo.advance`` the engine scans over. ``obs``: frames come
    from the SAME :func:`repro.obs.compute_frame` the engine scans over,
    at the same point in the round (after ``fold_gossip`` and the topo
    advance, before ``finalize``), so engine and legacy frames agree
    bit-for-bit like the trajectories do."""
    tracer = obs.tracer if obs is not None else None
    ocfg = obs.config if obs is not None else None
    round_main = jax.jit(setup.round_fn)
    round_warm = jax.jit(setup.warmup_fn)
    chan = gossip = None
    tstate = topo_mod.init_state(topo, net, n)
    topo_fn = None
    if tstate is not None and net is not None:
        topo_fn = jax.jit(functools.partial(topo_mod.advance, topo, net))
    fstate = fault_fn = reset_fn = None
    if net is not None:
        conds_fn = jax.jit(
            lambda rnd, chan: netsim.advance_conditions(net, n, rnd, chan))
        time_fn = jax.jit(functools.partial(
            netwire.round_seconds, net, local_steps=local_steps))
        chan = netsim.init_channel(net, n)
        gossip = netsim.init_gossip(net, n, setup.mixable_of(setup.state))
        if net.faults is not None:
            # the SAME per-round hook the engine scans over (resil.advance /
            # resil.reset_nodes), threaded through Python like chan/tstate
            fstate = resil_mod.init_state(net, n, setup.state)
            fault_fn = jax.jit(functools.partial(resil_mod.advance, net, n))
            reset_fn = jax.jit(functools.partial(resil_mod.reset_nodes, n))
    frame_fn = None
    if ocfg is not None:
        tiers = obs_mod.tiers_of(net, n)
        mix_of = setup.mixable_of

        @jax.jit
        def frame_fn(prev, state, info, conds, gossip):
            return obs_mod.compute_frame(
                ocfg, n, tiers, mix_of(prev), mix_of(state),
                getattr(prev, "cluster_id", None),
                getattr(state, "cluster_id", None), info, conds, gossip)

    state = setup.state
    for rnd in range(rounds):
        k_data, k_b = jax.random.split(k_data)
        batches = pipeline.sample_round_batches(
            k_b, train_x, train_y, local_steps, batch_size)
        conds = published = None
        if net is not None:
            conds, chan = conds_fn(rnd, chan)
            if fault_fn is not None:
                conds, fstate, restarted = fault_fn(rnd, conds, fstate)
                if restarted is not None:
                    # engine parity: factory-reset BEFORE the round, so the
                    # round (and the obs frame's prev mix) sees fresh state
                    state = reset_fn(restarted, fstate.init, state)
            conds, published = netsim.apply_async(net, conds, gossip)
        prev = state
        fn = round_warm if rnd < warmup_rounds else round_main
        state, info = fn(prev, batches, net=conds, gossip=published,
                         topo=tstate)
        if published is not None:
            gossip = netsim.fold_gossip(net, gossip, conds,
                                        setup.mixable_of(state))
        if topo_fn is not None:
            tstate = topo_fn(tstate, conds)
        if frame_fn is not None:
            fr = jax.device_get(frame_fn(prev, state, info, conds, gossip))
            obs.record_frames(
                np.asarray([rnd + 1]),
                jax.tree.map(lambda l: np.asarray(l)[None], fr))
        round_s = 0.0
        if net is not None:
            round_s = float(time_fn(info, conds))

        last_round = rnd == rounds - 1
        if last_round:
            with span(tracer, "finalize"):
                state = setup.finalize(state)
        if (rnd + 1) % eval_every == 0 or last_round:
            with span(tracer, "eval", round=rnd + 1):
                hit = hist.eval_round(state, rnd + 1,
                                      float(info["round_bytes"]), round_s)
            if hit:
                break
        else:
            hist.comm.record(rnd + 1, float(info["round_bytes"]),
                             round_s=round_s)
        if setup.track_cluster:
            hist.cluster_hist.append(
                (rnd + 1, np.asarray(state.cluster_id)))
