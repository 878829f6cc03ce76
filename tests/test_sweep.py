"""repro.sweep + EngineCache: warm-cache sweep runs are bit-identical to
fresh ``run_experiment(engine=True)`` calls (all 5 algorithms, with and
without netsim, including donated-carry reuse across runs); cache keys
never collide across configs; cross-seed aggregation; and the
``target_acc``/``eval_every`` validation regression."""
import dataclasses

import numpy as np
import pytest

from repro.configs.facade_paper import lenet
from repro.core.cache import EngineCache, EngineSpec, data_fingerprint
from repro.core.runner import run_experiment
from repro.data.synthetic import SynthSpec, make_clustered_data
from repro.netsim import NetworkConfig
from repro.sweep import SweepCell, aggregate_cell, run_sweep

CFG = lenet(smoke=True).replace(n_classes=4)
ALGOS = ("facade", "el", "dpsgd", "deprl", "dac")
SEEDS = (0, 1, 2)
KW = dict(k=2, degree=2, local_steps=2, batch_size=4, lr=0.05, eval_every=2)


@pytest.fixture(scope="module")
def tiny_ds():
    spec = SynthSpec(n_classes=4, image_size=16, samples_per_class=8,
                     test_per_class=8, seed=3)
    return make_clustered_data(spec, cluster_sizes=(3, 1),
                               transforms=("rot0", "rot180"))


def _cell(algo, ds, net=None, rounds=4, **overrides):
    kw = dict(KW)
    kw.update(overrides)
    return SweepCell(name=algo, algo=algo, cfg=CFG, dataset=ds,
                     rounds=rounds, net=net, kwargs=kw)


def _assert_runs_identical(ref, got):
    assert ref.acc_per_cluster == got.acc_per_cluster
    assert ref.fair_acc == got.fair_acc
    assert ref.dp == got.dp and ref.eo == got.eo
    assert ref.final_acc == got.final_acc
    assert ref.comm.rounds == got.comm.rounds
    assert ref.comm.bytes == got.comm.bytes          # exact float equality
    assert ref.comm.seconds == got.comm.seconds
    assert ref.comm.evaled == got.comm.evaled
    assert len(ref.cluster_history) == len(got.cluster_history)
    for (r1, c1), (r2, c2) in zip(ref.cluster_history, got.cluster_history):
        assert r1 == r2
        np.testing.assert_array_equal(c1, c2)


# ----------------------------------------------------- cache-hit parity ----
@pytest.mark.parametrize("netname", [None, "edge-churn"],
                         ids=["ideal", "edge-churn"])
@pytest.mark.parametrize("algo", ALGOS)
def test_sweep_parity_bitforbit(algo, netname, tiny_ds):
    """A 3-seed warm-cache sweep cell (seeds 1 and 2 reuse seed 0's
    compiled, donated-carry segment programs) must equal three fresh
    ``run_experiment(engine=True)`` calls bit for bit — trajectories,
    stop rounds, and full CommLog contents."""
    cache = EngineCache()
    sweep = run_sweep([_cell(algo, tiny_ds, net=netname)], SEEDS,
                      cache=cache)
    assert cache.misses == 1                     # one entry for the cell
    assert cache.hits == len(SEEDS) - 1          # warm for seeds 1, 2
    net = NetworkConfig.preset(netname) if netname else None
    for seed, got in zip(SEEDS, sweep.cells[0].results):
        ref = run_experiment(algo, CFG, tiny_ds, rounds=4, seed=seed,
                             net=net, engine=True, **KW)
        _assert_runs_identical(ref, got)


def test_sweep_warmup_boundary_parity(tiny_ds):
    """FACADE's two-variant warmup/main compile split survives caching."""
    cache = EngineCache()
    cell = _cell("facade", tiny_ds, rounds=6, eval_every=4, warmup_rounds=3)
    sweep = run_sweep([cell], SEEDS, cache=cache)
    for seed, got in zip(SEEDS, sweep.cells[0].results):
        ref = run_experiment("facade", CFG, tiny_ds, rounds=6, seed=seed,
                             warmup_rounds=3,
                             **{**KW, "eval_every": 4})
        _assert_runs_identical(ref, got)


def test_sweep_target_acc_stop_parity(tiny_ds):
    """target_acc early exit fires at the same eval round warm as fresh."""
    cache = EngineCache()
    cell = _cell("el", tiny_ds, rounds=8, target_acc=0.0)
    sweep = run_sweep([cell], SEEDS, cache=cache)
    for seed, got in zip(SEEDS, sweep.cells[0].results):
        ref = run_experiment("el", CFG, tiny_ds, rounds=8, seed=seed,
                             target_acc=0.0, **KW)
        _assert_runs_identical(ref, got)
        assert got.comm.rounds[-1] == 2          # stopped at the first eval


def test_sweep_zero_recompiles_after_first_run(tiny_ds):
    cache = EngineCache()
    cells = [_cell("el", tiny_ds), _cell("dac", tiny_ds)]
    run_sweep(cells, SEEDS[:1], cache=cache)     # first run of each cell
    compiled = cache.compile_count
    assert compiled > 0
    run_sweep(cells, SEEDS, cache=cache)
    assert cache.compile_count == compiled


def test_sweep_v2_presets_zero_recompile_and_warm_parity(tiny_ds):
    """netsim-v2 knobs keep both sweep invariants: a warm cell never
    recompiles (the carried channel/gossip state is per-run, not
    per-compile), and warm-cache runs stay bit-identical to fresh
    ``run_experiment`` calls — including the async staleness buffers and
    the donated carry they ride in."""
    cache = EngineCache()
    cells = [_cell("el", tiny_ds, net="edge-v2"),
             _cell("facade", tiny_ds, net="bursty-wan"),
             _cell("dac", tiny_ds, net="async-edge")]
    run_sweep(cells, SEEDS[:1], cache=cache)     # first run of each cell
    compiled = cache.compile_count
    assert compiled > 0
    sweep = run_sweep(cells, SEEDS, cache=cache)
    assert cache.compile_count == compiled       # warm: zero recompiles
    for cell, cres in zip(cells, sweep.cells):
        for seed, got in zip(SEEDS, cres.results):
            ref = run_experiment(cell.algo, CFG, tiny_ds, rounds=4,
                                 seed=seed,
                                 net=NetworkConfig.preset(cell.net),
                                 engine=True, **KW)
            _assert_runs_identical(ref, got)


def test_sweep_topo_zero_recompile_and_warm_parity(tiny_ds):
    """Adaptive topology keeps both sweep invariants: the TopoState EWMAs
    are per-run carry state (minted fresh each run, donated through the
    scan), so a warm cell never recompiles, and warm-cache runs stay
    bit-identical to fresh ``run_experiment(topo=...)`` calls. A cell
    with ``topo`` set and one without fork into separate entries."""
    from repro.topo import TopoConfig

    topo = TopoConfig(policy="reliability", min_inclusion=0.25)
    cache = EngineCache()
    cells = [_cell("el", tiny_ds, net="core-edge", topo=topo),
             _cell("facade", tiny_ds, net="edge-v2", topo=topo)]
    run_sweep(cells, SEEDS[:1], cache=cache)     # first run of each cell
    compiled = cache.compile_count
    assert compiled > 0
    sweep = run_sweep(cells, SEEDS, cache=cache)
    assert cache.compile_count == compiled       # warm: zero recompiles
    for cell, cres in zip(cells, sweep.cells):
        for seed, got in zip(SEEDS, cres.results):
            ref = run_experiment(cell.algo, CFG, tiny_ds, rounds=4,
                                 seed=seed, topo=topo,
                                 net=NetworkConfig.preset(cell.net),
                                 engine=True, **KW)
            _assert_runs_identical(ref, got)
    # topo on/off is a key axis: the same cell without topo is a miss
    before = cache.misses
    run_experiment("el", CFG, tiny_ds, rounds=4, cache=cache,
                   net=NetworkConfig.preset("core-edge"), **KW)
    assert cache.misses == before + 1


# ------------------------------------------------- cache-key collisions ----
def test_cache_key_no_collision_on_local_steps_or_preset(tiny_ds):
    """Two configs differing ONLY in local_steps (or only in netsim
    preset) must not share entries — a collision would silently train
    with the wrong compiled program."""
    base = EngineSpec(algo="el", cfg=CFG, n=4, k=2, degree=2,
                      local_steps=2, batch_size=4, lr=0.05)
    cache = EngineCache()
    e_base = cache.entry(base)
    e_steps = cache.entry(dataclasses.replace(base, local_steps=3))
    e_net = cache.entry(
        dataclasses.replace(base, net=NetworkConfig.preset("edge-churn")))
    assert cache.misses == 3 and cache.hits == 0
    assert e_base is not e_steps and e_base is not e_net
    assert len({id(e_base.engine), id(e_steps.engine),
                id(e_net.engine)}) == 3
    # and the run-level path sees the same distinction
    cache2 = EngineCache()
    run_experiment("el", CFG, tiny_ds, rounds=2, cache=cache2, **KW)
    run_experiment("el", CFG, tiny_ds, rounds=2, cache=cache2,
                   **{**KW, "local_steps": 3})
    run_experiment("el", CFG, tiny_ds, rounds=2, cache=cache2, **KW)
    assert cache2.misses == 2 and cache2.hits == 1


def test_cache_key_equal_configs_share_entry():
    cache = EngineCache()
    a = EngineSpec(algo="facade", cfg=CFG, n=4, k=2, degree=2,
                   local_steps=2, batch_size=4, lr=0.05,
                   net=NetworkConfig.preset("wan"))
    b = EngineSpec(algo="facade", cfg=CFG, n=4, k=2, degree=2,
                   local_steps=2, batch_size=4, lr=0.05,
                   net=NetworkConfig.preset("wan"))
    assert a == b and hash(a) == hash(b)
    assert cache.entry(a) is cache.entry(b)
    assert cache.stats()["entries"] == 1


def test_evaluator_cache_keyed_on_data_content(tiny_ds):
    """Same shapes, different eval content => different fingerprint, so a
    changed dataset can never reuse a stale evaluator."""
    spec = dataclasses.replace(tiny_ds.spec, seed=tiny_ds.spec.seed + 1)
    other = make_clustered_data(spec, cluster_sizes=(3, 1),
                                transforms=("rot0", "rot180"))
    assert data_fingerprint(tiny_ds) != data_fingerprint(other)
    assert data_fingerprint(tiny_ds) == data_fingerprint(tiny_ds)
    cache = EngineCache()
    run_experiment("el", CFG, tiny_ds, rounds=2, cache=cache, **KW)
    run_experiment("el", CFG, other, rounds=2, cache=cache, **KW)
    assert cache.evaluator_builds == 2
    run_experiment("el", CFG, tiny_ds, rounds=2, cache=cache, **KW)
    assert cache.evaluator_builds == 2           # warm again


def test_data_fingerprint_hashes_a_dataset_once(tiny_ds, monkeypatch):
    """A ``ClusteredDataset`` hashes by identity, so the fingerprint's weak
    per-object memo holds: its content is hashed on first use only, and
    a rebuilt dataset object (same content) is hashed afresh."""
    import hashlib
    import types

    from repro.core import cache as cache_mod

    spec = dataclasses.replace(tiny_ds.spec, seed=tiny_ds.spec.seed + 2)
    ds = make_clustered_data(spec, cluster_sizes=(3, 1),
                             transforms=("rot0", "rot180"))
    hashed = []
    monkeypatch.setattr(cache_mod, "hashlib", types.SimpleNamespace(
        sha1=lambda: hashed.append(1) or hashlib.sha1()))
    assert hash(ds) == object.__hash__(ds) and ds != dataclasses.replace(ds)
    fp = data_fingerprint(ds)
    for _ in range(3):
        assert data_fingerprint(ds) == fp
    assert len(hashed) == 1
    assert data_fingerprint(dataclasses.replace(ds)) == fp
    assert len(hashed) == 2


def test_compile_count_counts_retraces_on_new_train_shapes(tiny_ds):
    """A same-spec cell fed a different train shape RETRACES the cached
    jitted segment program; the compile counter must count that, or
    zero-recompile assertions would falsely pass while XLA recompiles."""
    spec2 = dataclasses.replace(tiny_ds.spec, samples_per_class=12)
    bigger = make_clustered_data(spec2, cluster_sizes=(3, 1),
                                 transforms=("rot0", "rot180"))
    cache = EngineCache()
    run_experiment("el", CFG, tiny_ds, rounds=2, cache=cache, **KW)
    c1 = cache.compile_count
    run_experiment("el", CFG, bigger, rounds=2, cache=cache, **KW)
    assert cache.misses == 1 and cache.hits == 1       # one shared entry
    assert cache.compile_count == c1 + 2               # retrace + evaluator
    c2 = cache.compile_count
    run_experiment("el", CFG, bigger, rounds=2, cache=cache, **KW)
    assert cache.compile_count == c2                   # warm for both shapes


# ------------------------------------------------------------ aggregation --
def test_aggregate_matches_manual(tiny_ds):
    sweep = run_sweep([_cell("el", tiny_ds)], SEEDS)
    cres = sweep.cells[0]
    s = cres.summary
    assert s["n_seeds"] == len(SEEDS)
    assert s["eval_rounds"] == [2, 4]
    for row in s["trajectory"]:
        fas = [dict(r.fair_acc)[row["round"]] for r in cres.results]
        assert row["n"] == len(SEEDS)
        assert row["fair_acc_mean"] == pytest.approx(np.mean(fas))
        assert row["fair_acc_std"] == pytest.approx(np.std(fas))
    assert s["total_bytes"]["mean"] == pytest.approx(
        np.mean([r.comm.bytes[-1] for r in cres.results]))
    assert s["dp"]["mean"] == pytest.approx(
        np.mean([r.dp for r in cres.results]))
    np.testing.assert_allclose(
        s["final_acc_mean"],
        np.mean([r.final_acc for r in cres.results], axis=0))


def test_sweep_to_target_table_and_json(tiny_ds, tmp_path):
    path = tmp_path / "sweep.json"
    sweep = run_sweep([_cell("el", tiny_ds)], SEEDS, targets=(0.0, 2.0),
                      json_path=path)
    tt = sweep.cells[0].summary["to_target"]
    assert tt["0"]["reached_frac"] == 1.0        # acc >= 0 at the first eval
    assert tt["0"]["bytes"]["mean"] > 0
    assert tt["2"]["reached_frac"] == 0.0        # acc can never reach 2.0
    # the CommLog never-reached sentinel propagates as an EXPLICIT None —
    # consumers key on `is None`, not on a missing key
    assert tt["2"]["bytes"] is None and tt["2"]["seconds"] is None
    import json
    blob = json.loads(path.read_text())
    assert blob["seeds"] == list(SEEDS)
    assert blob["cells"]["el"]["summary"]["n_seeds"] == len(SEEDS)
    assert blob["cache"]["entries"] == 1


def test_sweep_rejects_degenerate_grids(tiny_ds):
    """Regression: an empty seeds sequence used to make EVERY cell 'fail'
    on an empty aggregation and surface as a misleading every-cell-failed
    RuntimeError; an empty cell grid returned a useless empty SweepResult.
    Both now raise a clear ValueError up front."""
    with pytest.raises(ValueError, match="empty cell grid"):
        run_sweep([], SEEDS)
    with pytest.raises(ValueError, match="no seeds"):
        run_sweep([_cell("el", tiny_ds)], [])
    with pytest.raises(ValueError, match="no seeds"):
        run_sweep([_cell("el", tiny_ds)], iter(()))   # exhausted iterator


def test_sweep_all_cells_skipped_returns_cleanly(tiny_ds, tmp_path):
    """A rerun whose every cell is fingerprint-skipped must return the
    reloaded summaries, not trip the every-cell-failed guard (skipped
    cells carry no error)."""
    cells = lambda: [_cell("el", tiny_ds), _cell("dac", tiny_ds)]  # noqa: E731
    first = run_sweep(cells(), SEEDS[:2], ckpt_dir=tmp_path)
    assert not any(c.skipped for c in first.cells)
    again = run_sweep(cells(), SEEDS[:2], ckpt_dir=tmp_path)
    assert all(c.skipped for c in again.cells)
    assert all(c.error is None for c in again.cells)
    for a, b in zip(first.cells, again.cells):
        assert b.results == []                       # summary-only reload
        assert b.summary["n_seeds"] == a.summary["n_seeds"]
        assert b.summary["final_acc_mean"] == pytest.approx(
            a.summary["final_acc_mean"])


def test_sweep_rejects_seed_kwarg_and_dup_names(tiny_ds):
    cell = _cell("el", tiny_ds)
    cell.kwargs["seed"] = 7
    with pytest.raises(ValueError, match="seed"):
        run_sweep([cell], SEEDS)
    with pytest.raises(ValueError, match="duplicate"):
        run_sweep([_cell("el", tiny_ds), _cell("el", tiny_ds)], SEEDS)


# ------------------------------------------------------------- regression --
def test_target_acc_with_unreachable_eval_raises(tiny_ds):
    """Regression: target_acc + eval_every > rounds used to yield a run
    that could never early-exit; now it raises up front."""
    with pytest.raises(ValueError, match="eval_every"):
        run_experiment("el", CFG, tiny_ds, rounds=4, target_acc=0.5,
                       **{**KW, "eval_every": 8})
    with pytest.raises(ValueError, match="eval_every"):
        run_experiment("el", CFG, tiny_ds, rounds=0, target_acc=0.5, **KW)
    # without target_acc the same schedule stays legal (final-round eval)
    res = run_experiment("el", CFG, tiny_ds, rounds=2,
                         **{**KW, "eval_every": 8})
    assert res.comm.rounds[-1] == 2
