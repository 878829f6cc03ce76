"""Cross-run compile cache: the seed-independent machinery behind a sweep.

``run_experiment`` historically rebuilt everything per call — the model
binding, the algorithm round closures, the scan engine's jitted segment
programs and the jitted evaluator — so a sweep of S seeds over ONE config
paid S identical XLA compiles. At paper scale (5 algorithms x netsim
presets x cluster-imbalance grids x many seeds, tiny per-round compute)
those compiles dominate wall-clock.

:class:`EngineCache` memoizes on a static :class:`EngineSpec` key:

* the :class:`~repro.core.bindings.Binding` and the algorithm *program*
  (round/warmup closures, ``models_of``, ``finalize`` — everything
  ``runner.algo_setup`` builds except the seed-dependent initial state);
* one :class:`~repro.core.engine.SegmentEngine` per entry, whose compiled
  segment programs (keyed per ``(length, warmup)`` inside the engine) are
  therefore shared by every run of the cell;
* evaluators, cached cache-wide on ``(model cfg, eval batch, content
  fingerprint of the eval split)`` — independent of algorithm and netsim
  preset, so a grid of presets over one dataset compiles ONE evaluator;
* the train split on the device (:meth:`EngineCache.train_data`): ONE
  slot, keyed on the dataset object (held weakly), the identity of its
  ``train_x`` / ``train_y`` arrays and the node-mesh shape, so every run
  over one dataset after the first moves no bytes. A run over another
  dataset or placement releases the old arrays before it uploads, so
  never more than one train set is resident.

Cache-key contract: every knob that changes a compiled program or the
round/eval arithmetic MUST be a field of :class:`EngineSpec`; only the
experiment seed (PRNG) and the data may vary within an entry. A changed
eval split changes the fingerprint, never silently reuses a stale
evaluator; a reassigned ``train_x`` / ``train_y`` is a new upload. A
dataset's arrays are never changed IN PLACE after first use (neither
split): build a new dataset, or assign a new array. ``rounds`` and
``eval_every`` are deliberately NOT key fields — segment programs are
keyed per ``(length, warmup)`` inside the engine, so different eval
schedules share an entry safely. The netsim-v2 knobs (``burst`` /
``classes`` / ``async_gossip`` / ``max_staleness``) need no extra key
field: they live on the frozen ``NetworkConfig``, which is already the
``net`` component of the key — ``tests/test_property.py`` pins that
perturbing ANY ``NetworkConfig`` field forks the key. The adaptive
topology policy is the ``topo`` component (a frozen
``repro.topo.TopoConfig`` or ``None``) with the same every-field-forks
contract, pinned the same way. In-scan telemetry is the ``obs``
component (a frozen ``repro.obs.ObsConfig`` or ``None``): its fields
change the compiled segment program's OUTPUTS (the MetricsFrame scan
leaf), so they fork the key too — while host-side sinks/tracers never
do (``tests/test_obs.py`` pins both directions).

Donation caveat: segment programs donate their input :class:`EngineCarry`
buffers. Reusing a cached engine across runs is safe precisely because
each run builds a FRESH carry from its own seed; never feed a consumed
carry back into ``dispatch_segment``. Only the carry is donated: the segment
programs, the legacy per-round loop and checkpoint resume only READ the
train arrays, which is why the kept train split can be reused as is.

Always-warm extensions (ROADMAP Open Item 5a):

* :func:`use_compile_cache` turns JAX's persistent compilation cache on
  at ONE resolved place, so the serialized XLA executables behind every
  entry survive the PROCESS — a second sweep, a CI shard or a resumed
  grid reaches its first dispatch without recompiling. Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own reading of it stands and
  no code here names another directory; otherwise the cache lives at the
  fixed ``<checkout>/.jax_cache`` (:data:`CHECKOUT_CACHE_DIR`), found from
  this package's location. The path never varies between runs, so a
  later process finds what an earlier one wrote.
  ``EngineCache(persist_dir=...)`` attaches an explicit directory
  instead (tests that need their own). The
  in-process :class:`EngineCache` keys stay the source of truth; the
  persistent layer only short-circuits XLA compilation underneath them.
* ``EngineCache(max_entries=...)`` bounds the in-process entry count with
  LRU eviction, so giant grids don't grow program memory without limit.
  Entries pinned via :meth:`EngineCache.pin` (``run_experiment`` pins its
  entry for the duration of the run) are never evicted — donation and
  segment-program reuse stay safe mid-run; when everything live is
  pinned the bound is allowed to overshoot rather than break a run.
  Evictions are counted in :meth:`stats` and emitted as ``cache.evict``
  tracer events next to the existing ``cache.hit``/``cache.miss``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import os
import pathlib
import weakref
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import span

from .bindings import compile_stats, make_binding
from .engine import SegmentEngine


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """Static cache key for one sweep cell.

    All fields are hashable statics: ``cfg`` is a frozen model config
    dataclass and ``net`` a frozen :class:`repro.netsim.NetworkConfig`
    (or ``None``). Two specs compare equal iff every compiled program and
    every round closure they imply is interchangeable.
    """
    algo: str                    # facade | el | dpsgd | deprl | dac
    cfg: Any                     # CNNConfig / ModelConfig (frozen)
    n: int                       # number of nodes
    k: int                       # number of clusters / FACADE heads
    degree: int
    local_steps: int
    batch_size: int
    lr: float
    warmup_rounds: int = 0
    head_jitter: float = 0.0
    net: Any = None              # NetworkConfig | None
    eval_batch: int = 256        # make_evaluator batch size
    topo: Any = None             # repro.topo.TopoConfig | None
    obs: Any = None              # repro.obs.ObsConfig | None — the
    #                              DEVICE-side telemetry spec: an enabled
    #                              MetricsFrame adds scan outputs, i.e. a
    #                              different compiled segment program, so
    #                              it must fork the key. Host-side sink /
    #                              tracer / profiler settings (repro.obs.
    #                              Obs) deliberately never appear here.
    mesh: Any = None             # node-mesh SHAPE tuple (e.g. ``(8,)``)
    #                              or None — repro.core.meshctx.normalize's
    #                              canonical form. A sharded segment
    #                              program has different layouts and
    #                              collectives than the single-device one,
    #                              so sharded and unsharded runs must
    #                              never collide on an entry. Device
    #                              OBJECTS never enter the key (shape
    #                              only): specs stay repr-stable for
    #                              checkpoint fingerprints.


# src/repro/core/cache.py -> parents[3] is the checkout root
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names when it is set, else the fixed
    :data:`CHECKOUT_CACHE_DIR`. Never a temporary name, a process id or a
    time: the next process must find what this one wrote."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT_CACHE_DIR))


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on at
    :func:`compile_cache_dir` and return that directory. With
    ``JAX_COMPILATION_CACHE_DIR`` set, JAX read it at import and that
    reading stands: only the persistence floors are lowered. Otherwise
    the checkout's ``.jax_cache`` is attached (:func:`attach_persist_dir`)."""
    path = compile_cache_dir()
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _lower_persist_floors()
        _reset_jax_cache()
        return path
    return attach_persist_dir(path)


def attach_persist_dir(path) -> str:
    """Point JAX's persistent compilation cache at ``path`` (created if
    missing) and drop the persistence floors so the sweeps' many small
    segment programs — each well under the default 1s-compile-time /
    min-entry-size thresholds — are persisted too.

    The JAX compilation-cache directory is PROCESS-GLOBAL state: the last
    attach wins for every compile in the process, not just this cache's.
    That is the behavior we want (one warm disk cache per sweep process)
    but it means two live ``EngineCache(persist_dir=...)`` instances with
    different directories cannot both be honored — the newer one is.
    """
    import jax

    path = str(path)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    _lower_persist_floors()
    _reset_jax_cache()
    return path


def _lower_persist_floors() -> None:
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def detach_persist_dir() -> None:
    """Undo :func:`attach_persist_dir`: stop persisting compiles to disk.
    Call this before a temporary persist dir is deleted — the attached
    cache object is process-global and would otherwise keep writing into
    the removed directory."""
    import jax

    jax.config.update("jax_compilation_cache_dir", None)
    _reset_jax_cache()


def _reset_jax_cache() -> None:
    """Drop JAX's lazily-initialized persistent-cache singleton so the
    next compile re-reads ``jax_compilation_cache_dir``. Without this,
    attaching after the process's first compile is silently a no-op (the
    singleton latched the old — usually absent — directory)."""
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


def host_bytes(*arrays) -> int:
    """Bytes a host-to-device copy of ``arrays`` moves: each host array's
    ``nbytes``; an array already on the device counts 0."""
    return sum(0 if isinstance(a, jax.Array) else np.asarray(a).nbytes
               for a in arrays)


_FP_MEMO: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def data_fingerprint(dataset) -> str:
    """Content hash of everything an evaluator closes over: the node ->
    cluster map and the per-cluster eval split (shapes, dtypes, bytes).

    Memoized per dataset OBJECT (weakly, so the memo never pins data):
    sweeps look the same dataset up once per run, and re-hashing the eval
    split every time would be pure overhead. The memo needs a hashable
    dataset: ``ClusteredDataset`` hashes by identity for this, as the
    train slot of :meth:`EngineCache.train_data` does. The flip side:
    mutating a dataset's arrays IN PLACE after first use — the eval split
    here, the train split there — is not detected: build a new dataset,
    or assign a new array, instead (the synthetic pipeline always builds).
    """
    try:
        return _FP_MEMO[dataset]
    except (KeyError, TypeError):   # TypeError: non-weakrefable dataset
        pass
    h = hashlib.sha1()

    def feed(a):
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())

    feed(dataset.node_cluster)
    for x, y in zip(dataset.test_x, dataset.test_y):
        feed(x)
        feed(y)
    fp = h.hexdigest()
    try:
        _FP_MEMO[dataset] = fp
    except TypeError:
        pass
    return fp


class CacheEntry:
    """Seed-independent machinery for one :class:`EngineSpec`: binding,
    algorithm program and segment engine. ``setup(key)`` mints a fresh
    per-seed :class:`~repro.core.runner.AlgoSetup` over the shared
    closures — state is the ONLY per-seed piece."""

    def __init__(self, spec: EngineSpec):
        from . import runner     # runner imports this module; bind lazily
        self.spec = spec
        self.binding = make_binding(spec.cfg)
        self.program = runner.algo_program(
            spec.algo, self.binding, spec.n, spec.k, degree=spec.degree,
            local_steps=spec.local_steps, lr=spec.lr,
            warmup_rounds=spec.warmup_rounds, head_jitter=spec.head_jitter,
            topo=spec.topo,
            faults=spec.net.faults if spec.net is not None else None)
        self.engine = SegmentEngine(
            self.program.round_fn, warmup_fn=self.program.warmup_fn,
            net=spec.net, n=spec.n, local_steps=spec.local_steps,
            batch_size=spec.batch_size,
            track_cluster=self.program.track_cluster,
            mixable_of=self.program.mixable_of, topo=spec.topo,
            obs=spec.obs, mesh=spec.mesh,
            compile_stats=lambda n: compile_stats(self.binding, n))

    def setup(self, key):
        return self.program.setup(key)

    @property
    def compile_count(self) -> int:
        return self.engine.compile_count


class EngineCache:
    """Config-keyed store of :class:`CacheEntry` + evaluators.

    ``entry(spec)`` returns the cell's entry, building it on first use;
    ``evaluator(binding, dataset, batch)`` returns the (cfg, batch,
    data-fingerprint)-keyed evaluator; ``train_data(entry, dataset)`` the
    dataset's train split on the device, kept in one slot across runs
    (``data_hits`` / ``data_uploads`` count how it was served).
    ``compile_count`` totals every
    compiled program the cache EVER built — segment builds plus evaluator
    builds, monotone across LRU evictions — which is what sweep smokes
    assert stays flat after each cell's first run.

    ``persist_dir``: attach JAX's persistent compilation cache at this
    explicit directory (see :func:`attach_persist_dir`) so compiled
    executables survive the process — for tests that need a directory of
    their own; everything else uses :func:`use_compile_cache`.
    ``max_entries``: LRU bound on live entries; ``None`` (the
    default) keeps the historical unbounded behavior.

    The attached directory is PROCESS-GLOBAL jax state, so a cache built
    over a temporary directory must detach before that directory is
    deleted — otherwise every later compile in the process tries to
    persist into the void and fails. :meth:`close` (or using the cache as
    a context manager) does exactly that, and only if this cache's
    directory is still the attached one — it never stomps a newer attach
    by another cache. In-process entries stay usable after ``close``;
    only disk persistence stops.
    """

    def __init__(self, *, persist_dir=None, max_entries: int | None = None):
        if max_entries is not None and max_entries < 1:
            raise ValueError(
                f"max_entries={max_entries} must be >= 1 (or None for "
                "an unbounded cache): a run always needs its own entry")
        self._entries: dict[EngineSpec, CacheEntry] = {}  # insertion = LRU
        self._evaluators: dict[tuple, Any] = {}
        self._pins: dict[EngineSpec, int] = {}
        self.hits = 0            # entry() served from cache
        self.misses = 0          # entry() had to build
        self.evictions = 0       # entries dropped by the LRU bound
        self.evaluator_builds = 0
        # one train split on the device: dataset -> (weakrefs to its
        # train_x / train_y, mesh shape, placed arrays); at most one item
        self._train: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()
        self.data_hits = 0       # train_data() served the kept arrays
        self.data_uploads = 0    # train_data() copied the train split
        self.max_entries = max_entries
        self._evicted_compiles = 0   # keeps compile_count monotone
        self.persist_dir = (attach_persist_dir(persist_dir)
                            if persist_dir is not None else None)

    def close(self) -> None:
        """Detach the persistent compile directory this cache attached
        (no-op without ``persist_dir``, idempotent). Call before deleting
        a temporary persist dir — the attach is process-global, so a
        deleted-but-still-attached directory would poison every later
        compile in the process. If ANOTHER cache attached a different
        directory since (last-attach-wins), that newer attach is left
        alone."""
        if self.persist_dir is None:
            return
        import jax

        if jax.config.jax_compilation_cache_dir == self.persist_dir:
            detach_persist_dir()
        self.persist_dir = None

    def __enter__(self) -> "EngineCache":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def entry(self, spec: EngineSpec, tracer=None) -> CacheEntry:
        e = self._entries.get(spec)
        if e is None:
            self.misses += 1
            e = self._entries[spec] = CacheEntry(spec)
        else:
            self.hits += 1
            self._entries[spec] = self._entries.pop(spec)  # -> MRU slot
        self._evict(keep=spec, tracer=tracer)
        return e

    def _evict(self, keep: EngineSpec, tracer=None):
        if self.max_entries is None:
            return
        while len(self._entries) > self.max_entries:
            victim = next(
                (s for s in self._entries       # oldest-first = LRU order
                 if s != keep and self._pins.get(s, 0) == 0), None)
            if victim is None:
                return   # every live entry is pinned by a running
                #          experiment: overshoot rather than break one
            dead = self._entries.pop(victim)
            self._evicted_compiles += dead.compile_count
            self.evictions += 1
            if tracer is not None:
                tracer.event("cache.evict", algo=victim.algo,
                             entries=len(self._entries))

    @contextlib.contextmanager
    def pin(self, spec: EngineSpec):
        """Hold ``spec``'s entry out of LRU eviction for the duration —
        ``run_experiment`` wraps each run in this so the entry (and its
        compiled segment programs) can't be dropped mid-run."""
        self._pins[spec] = self._pins.get(spec, 0) + 1
        try:
            yield
        finally:
            n = self._pins[spec] - 1
            if n:
                self._pins[spec] = n
            else:
                del self._pins[spec]

    def pinned(self, spec: EngineSpec) -> bool:
        return self._pins.get(spec, 0) > 0

    def evaluator(self, binding, dataset, batch: int = 256):
        key = (binding.cfg, batch, data_fingerprint(dataset))
        ev = self._evaluators.get(key)
        if ev is None:
            from . import runner
            ev = self._evaluators[key] = runner.make_evaluator(
                binding, dataset.node_cluster, dataset.test_x,
                dataset.test_y, batch=batch)
            self.evaluator_builds += 1
        return ev

    def train_data(self, entry: CacheEntry, dataset, tracer=None):
        """``dataset``'s node-stacked train arrays placed for ``entry``'s
        engine (on its node mesh when it has one), inside the ``upload``
        span, whose ``bytes`` stat is what was moved: 0 when the slot
        already holds this dataset object, its current ``train_x`` /
        ``train_y`` objects (compared with ``is``: a reassigned array is a
        miss) and the same mesh shape. A miss releases the slot's arrays
        BEFORE it uploads, so at most one train set is resident. Emits a
        ``data.hit`` / ``data.miss`` tracer event. A dataset that cannot be
        held weakly (unhashable, or arrays without weak references) is
        uploaded on every call, as before."""
        x, y, mesh = dataset.train_x, dataset.train_y, entry.spec.mesh
        try:
            kept = self._train.get(dataset)
        except TypeError:                  # unhashable: never kept
            kept = None
        if kept is not None and kept[0]() is x and kept[1]() is y \
                and kept[2] == mesh:
            self.data_hits += 1
            if tracer is not None:
                tracer.event("data.hit")
            with span(tracer, "upload", bytes=0):
                return kept[3]
        self._train.clear()
        self.data_uploads += 1
        if tracer is not None:
            tracer.event("data.miss")
        with span(tracer, "upload", bytes=host_bytes(x, y)):
            placed = entry.engine.place_data(jnp.asarray(x), jnp.asarray(y))
        try:
            self._train[dataset] = (weakref.ref(x), weakref.ref(y), mesh,
                                    placed)
        except TypeError:
            pass
        return placed

    @property
    def compile_count(self) -> int:
        return (sum(e.compile_count for e in self._entries.values())
                + self._evicted_compiles + self.evaluator_builds)

    def stats(self) -> dict:
        return {"entries": len(self._entries), "hits": self.hits,
                "misses": self.misses, "evictions": self.evictions,
                "compiles": self.compile_count,
                "evaluator_builds": self.evaluator_builds,
                "data_hits": self.data_hits,
                "data_uploads": self.data_uploads,
                "max_entries": self.max_entries,
                "persist_dir": self.persist_dir}

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, spec) -> bool:
        return spec in self._entries
