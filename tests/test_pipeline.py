"""Always-warm engine (ROADMAP Open Item 5a): the persistent/LRU-bounded
``EngineCache``, and the validation fixes that rode along.

* kill + resume while a segment is in flight lands on the uninterrupted
  trajectory: the engine loop dispatches segment ``t+1`` before it
  drains ``t``, and its checkpoint snapshots the carry before that
  dispatch donates it.
* ``EngineCache(persist_dir=...)`` persists XLA executables on disk
  without perturbing results; ``max_entries`` LRU-evicts, but never an
  entry pinned by a live run.
* ``eval_every <= 0`` is refused up front on BOTH drivers (it used to
  divide by zero in the engine plan and silently degrade in the legacy
  loop); zero-node clusters are skipped by the evaluator instead of
  raising IndexError; checkpoint frame writes are per-segment sidecars,
  O(segments) total instead of O(segments^2).
"""
import pathlib

import jax
import numpy as np
import pytest

from repro import checkpoint
from repro.configs.facade_paper import lenet
from repro.core import engine as engine_mod
from repro.core.bindings import make_binding
from repro.core.cache import (EngineCache, EngineSpec, attach_persist_dir,
                              detach_persist_dir)
from repro.core.runner import algo_setup, make_evaluator, run_experiment
from repro.data.synthetic import SynthSpec, make_clustered_data
from repro.netsim import NetworkConfig
from repro.obs import Obs, ObsConfig

pytestmark = pytest.mark.tier0

CFG = lenet(smoke=True).replace(n_classes=4)
KW = dict(rounds=6, k=2, degree=2, local_steps=2, batch_size=4, lr=0.05,
          eval_every=2, seed=0)


@pytest.fixture(scope="module")
def tiny_ds():
    spec = SynthSpec(n_classes=4, image_size=16, samples_per_class=8,
                     test_per_class=8, seed=3)
    return make_clustered_data(spec, cluster_sizes=(3, 1),
                               transforms=("rot0", "rot180"))


def _assert_runs_identical(ref, got):
    assert ref.acc_per_cluster == got.acc_per_cluster
    assert ref.fair_acc == got.fair_acc
    assert ref.dp == got.dp and ref.eo == got.eo
    assert ref.final_acc == got.final_acc
    assert ref.comm.rounds == got.comm.rounds
    assert ref.comm.bytes == got.comm.bytes          # exact float equality
    assert ref.comm.seconds == got.comm.seconds
    assert ref.comm.evaled == got.comm.evaled
    np.testing.assert_array_equal(np.asarray(ref.node_acc),
                                  np.asarray(got.node_acc))
    assert len(ref.cluster_history) == len(got.cluster_history)
    for (r1, c1), (r2, c2) in zip(ref.cluster_history, got.cluster_history):
        assert r1 == r2
        np.testing.assert_array_equal(c1, c2)


def _assert_frames_identical(obs_a: Obs, obs_b: Obs):
    fa, fb = obs_a.frames_table(), obs_b.frames_table()
    assert set(fa) == set(fb)
    for k in fa:
        np.testing.assert_array_equal(np.asarray(fa[k]),
                                      np.asarray(fb[k]))


# ------------------------------------------------ in-flight kill+resume ---
class _Killed(Exception):
    pass


def test_pipeline_kill_and_resume_bit_parity(tiny_ds, tmp_path):
    """Kill the engine loop mid-flight (on the dispatch of segment 2,
    after segment 0's checkpoint landed) and resume with the same call:
    indistinguishable from an uninterrupted run — metrics, frames, and
    the final checkpointed carry, which the snapshot before each dispatch
    keeps whole."""
    net = NetworkConfig.preset("edge-churn")
    ocfg = ObsConfig()
    obs_ref = Obs(config=ocfg)
    ck_ref = str(tmp_path / "ref.npz")
    ref = run_experiment("facade", CFG, tiny_ds, net=net, ckpt=ck_ref,
                         obs=obs_ref, **KW)

    orig = engine_mod.SegmentEngine.dispatch_segment
    calls = {"n": 0}

    def killer(self, *a, **k):
        if calls["n"] >= 2:
            raise _Killed()
        calls["n"] += 1
        return orig(self, *a, **k)

    ck = str(tmp_path / "killed.npz")
    obs_dead = Obs(config=ocfg)
    engine_mod.SegmentEngine.dispatch_segment = killer
    try:
        with pytest.raises(_Killed):
            run_experiment("facade", CFG, tiny_ds, net=net, ckpt=ck,
                           obs=obs_dead, **KW)
    finally:
        engine_mod.SegmentEngine.dispatch_segment = orig
    assert pathlib.Path(ck).exists()     # segment 0 landed before the kill

    obs_got = Obs(config=ocfg)
    got = run_experiment("facade", CFG, tiny_ds, net=net, ckpt=ck,
                         obs=obs_got, **KW)
    _assert_runs_identical(ref, got)
    _assert_frames_identical(obs_ref, obs_got)
    pr, _ = checkpoint.load(ck_ref)
    pg, _ = checkpoint.load(ck)
    for a, b in zip(jax.tree.leaves(pr["carry"]),
                    jax.tree.leaves(pg["carry"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------- persist_dir --------
def test_persist_dir_populates_disk_and_stays_bit_identical(tiny_ds,
                                                            tmp_path):
    """EngineCache(persist_dir=...) must (a) leave serialized executables
    on disk, (b) not perturb results, and (c) let a FRESH EngineCache
    over the same dir reproduce the run bit-for-bit (the cross-process
    warm-start story, in-process: benchmarks/warm_start.py measures the
    actual second-process speedup)."""
    ref = run_experiment("el", CFG, tiny_ds, **KW)
    pdir = tmp_path / "xla-cache"
    try:
        cache = EngineCache(persist_dir=str(pdir))
        assert cache.persist_dir == str(pdir)
        assert cache.stats()["persist_dir"] == str(pdir)
        got = run_experiment("el", CFG, tiny_ds, cache=cache, **KW)
        n_files = len(list(pdir.iterdir()))
        assert n_files > 0
        # a fresh cache over the same dir: XLA deserializes instead of
        # compiling, and the trajectory is still bit-identical
        cache2 = EngineCache(persist_dir=str(pdir))
        again = run_experiment("el", CFG, tiny_ds, cache=cache2, **KW)
    finally:
        # the persist dir is process-global jax config: detach so later
        # tests don't keep writing executables into this tmp_path
        detach_persist_dir()
    _assert_runs_identical(ref, got)
    _assert_runs_identical(ref, again)


def test_attach_persist_dir_creates_and_returns(tmp_path):
    target = tmp_path / "nested" / "cache"
    try:
        got = attach_persist_dir(target)
    finally:
        detach_persist_dir()
    assert got == str(target)
    assert target.is_dir()


def test_cache_close_detaches_before_dir_deletion(tiny_ds, tmp_path):
    """Regression: a temp persist dir deleted while still attached
    poisons every later compile in the process (XLA persists into the
    void). ``close()`` detaches, is idempotent, and a post-close compile
    in the same process works with the directory gone."""
    import shutil

    pdir = tmp_path / "xla-tmp"
    cache = EngineCache(persist_dir=str(pdir))
    run_experiment("el", CFG, tiny_ds, cache=cache, **KW)
    cache.close()
    assert cache.persist_dir is None
    cache.close()                                    # idempotent
    shutil.rmtree(pdir)
    # dir is gone AND detached: a fresh compile must still succeed
    fresh = EngineCache()
    got = run_experiment("el", CFG, tiny_ds, cache=fresh,
                         **{**KW, "local_steps": 3})
    assert np.isfinite(got.final_acc).all()


def test_cache_context_manager_detaches(tmp_path):
    import jax

    pdir = str(tmp_path / "xla-cm")
    try:
        with EngineCache(persist_dir=pdir) as cache:
            assert cache.persist_dir == pdir
            assert jax.config.jax_compilation_cache_dir == pdir
        assert cache.persist_dir is None
        assert jax.config.jax_compilation_cache_dir is None
    finally:
        detach_persist_dir()


def test_cache_close_never_stomps_a_newer_attach(tmp_path):
    """Attach is process-global, last-attach-wins: closing an OLDER cache
    must leave a newer cache's directory attached."""
    import jax

    old = EngineCache(persist_dir=str(tmp_path / "old"))
    new = EngineCache(persist_dir=str(tmp_path / "new"))
    try:
        old.close()                   # old's dir is no longer attached:
        assert jax.config.jax_compilation_cache_dir == new.persist_dir
        new.close()
        assert jax.config.jax_compilation_cache_dir is None
    finally:
        detach_persist_dir()


def test_cache_close_without_persist_dir_is_noop():
    cache = EngineCache()
    cache.close()                                    # nothing to detach
    assert cache.persist_dir is None
    with EngineCache() as cm:                        # context form too
        assert cm.persist_dir is None


# ------------------------------------------------------- LRU bound --------
def _spec(lr: float) -> EngineSpec:
    return EngineSpec(algo="el", cfg=CFG, n=4, k=2, degree=2,
                      local_steps=2, batch_size=4, lr=lr)


def test_lru_bound_evicts_oldest_and_counts():
    cache = EngineCache(max_entries=2)
    s1, s2, s3 = _spec(0.01), _spec(0.02), _spec(0.03)
    cache.entry(s1)
    cache.entry(s2)
    cache.entry(s1)                       # s1 -> MRU; s2 is now oldest
    assert cache.entry(s3) is not None    # evicts s2, not s1
    assert len(cache) == 2
    assert s1 in cache and s3 in cache and s2 not in cache
    st = cache.stats()
    assert st["evictions"] == 1 and st["max_entries"] == 2
    # compile_count stays monotone across evictions (sweep smokes assert
    # it plateaus; an eviction must never make it drop)
    before = cache.compile_count
    cache.entry(s2)                       # evicts s1, rebuilds s2
    assert cache.compile_count >= before


def test_pinned_entry_is_never_evicted():
    cache = EngineCache(max_entries=1)
    s1, s2 = _spec(0.01), _spec(0.02)
    cache.entry(s1)
    with cache.pin(s1):
        assert cache.pinned(s1)
        cache.entry(s2)                   # bound=1 but s1 is pinned:
        assert s1 in cache                # overshoot instead of breaking
        assert s2 in cache and len(cache) == 2
        assert cache.evictions == 0
    assert not cache.pinned(s1)
    cache.entry(s2)                       # unpinned now: bound enforced
    assert s1 not in cache and len(cache) == 1
    assert cache.evictions == 1


def test_max_entries_validation():
    with pytest.raises(ValueError, match="max_entries"):
        EngineCache(max_entries=0)


def test_lru_bounded_run_stays_bit_identical(tiny_ds):
    """An LRU-bounded cache thrashing across algorithms still reproduces
    the unbounded runs exactly — eviction only drops compiled programs,
    never affects a trajectory (the run's own entry is pinned)."""
    refs = {a: run_experiment(a, CFG, tiny_ds, **KW)
            for a in ("el", "dac")}
    cache = EngineCache(max_entries=1)
    for algo in ("el", "dac", "el"):      # second el rebuilds after evict
        got = run_experiment(algo, CFG, tiny_ds, cache=cache, **KW)
        _assert_runs_identical(refs[algo], got)
    assert cache.evictions >= 2
    assert len(cache) == 1


# ------------------------------------------- eval_every validation --------
@pytest.mark.parametrize("engine", [True, False], ids=["engine", "legacy"])
@pytest.mark.parametrize("bad", [0, -3])
def test_eval_every_must_be_positive_on_both_drivers(bad, engine, tiny_ds):
    """eval_every=0 used to die in segment_plan's range() step (engine)
    and silently degrade to a single final eval (legacy); both now refuse
    up front with the same error."""
    with pytest.raises(ValueError, match="eval_every"):
        run_experiment("el", CFG, tiny_ds, engine=engine,
                       **{**KW, "eval_every": bad})


# ------------------------------------------------ empty clusters ----------
@pytest.fixture(scope="module")
def lopsided_ds():
    """k=2 splits/test sets but every node in cluster 0 — the shape a
    skewed node_cluster map (or a down-scaled sweep) produces."""
    spec = SynthSpec(n_classes=4, image_size=16, samples_per_class=8,
                     test_per_class=8, seed=3)
    return make_clustered_data(spec, cluster_sizes=(4, 0),
                               transforms=("rot0", "rot180"))


def test_evaluator_skips_zero_node_clusters(lopsided_ds):
    """make_evaluator used to index p[0] of an empty gather and raise
    IndexError; empty clusters are now skipped and cluster_ids names the
    survivors."""
    binding = make_binding(CFG)
    setup = algo_setup("el", binding, jax.random.PRNGKey(0),
                       lopsided_ds.n_nodes, 2, degree=2, local_steps=2,
                       lr=0.05)
    evaluate = make_evaluator(binding, lopsided_ds.node_cluster,
                              lopsided_ds.test_x, lopsided_ds.test_y,
                              batch=5)
    assert evaluate.cluster_ids == (0,)
    accs, preds_c, labels_c, node_acc = evaluate(
        setup.models_of(setup.state))
    assert len(accs) == 1 and len(preds_c) == 1 and len(labels_c) == 1
    assert np.asarray(node_acc).shape == (4,)
    assert np.isfinite(accs[0])


@pytest.mark.parametrize("engine", [True, False], ids=["engine", "legacy"])
def test_run_with_empty_cluster_end_to_end(engine, lopsided_ds):
    res = run_experiment("el", CFG, lopsided_ds, engine=engine, **KW)
    assert len(res.final_acc) == 1
    assert all(np.isfinite(a) for a in res.final_acc)
    assert np.isfinite(res.dp) and np.isfinite(res.eo)
    assert all(len(accs) == 1 for _, accs in res.acc_per_cluster)


# ------------------------------------------- flat checkpoint writes -------
def test_ckpt_frame_writes_are_per_segment_sidecars(tiny_ds, tmp_path):
    """Obs frames go to append-only per-segment sidecar files: each holds
    exactly its segment's rounds (never the accumulated history, the old
    O(segments^2) layout), sizes stay flat, and the main archive carries
    only carry+hist."""
    ck = str(tmp_path / "run.npz")
    kw = {**KW, "rounds": 8, "eval_every": 1}     # 8 segments, 8 sidecars
    obs = Obs(config=ObsConfig())
    run_experiment("el", CFG, tiny_ds, ckpt=ck, obs=obs, **kw)

    payload, meta = checkpoint.load(ck)
    assert set(payload) == {"carry", "hist"}      # frames never in main
    assert meta["frame_files"] == 8
    sizes = []
    for j in range(8):
        fpath = pathlib.Path(f"{ck}.frames-{j}.npz")
        assert fpath.exists()
        rec, fmeta = checkpoint.load(str(fpath))
        assert fmeta["index"] == j
        # one segment's rounds only — the flat-write contract
        np.testing.assert_array_equal(np.asarray(rec["rounds"]), [j + 1])
        sizes.append(fpath.stat().st_size)
    # per-segment bytes ~flat: the last sidecar is the same size as the
    # first (a cumulative rewrite would make it ~8x)
    assert sizes[-1] <= 2 * sizes[0]

    # the resume guarantee survives the layout: a fresh Obs replays every
    # sidecar and matches the live frame stream exactly
    obs2 = Obs(config=ObsConfig())
    run_experiment("el", CFG, tiny_ds, ckpt=ck, obs=obs2, **kw)
    _assert_frames_identical(obs, obs2)
