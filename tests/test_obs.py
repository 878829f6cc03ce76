"""repro.obs: in-scan telemetry, span tracing and run manifests.

Pins the subsystem's contracts: ``obs=None`` (the default) is bit-for-bit
the untelemetered path AND attaching a full ``Obs`` never perturbs a
trajectory, for FACADE + all four baselines on BOTH drivers; the engine
and the legacy loop produce identical ``MetricsFrame`` streams (one
shared ``compute_frame``, same point in the round); every ``ObsConfig``
field forks the ``EngineSpec`` cache key (with a fields-coverage
completeness check, the ``TopoConfig`` pattern) while host-side
sink/tracer settings never do; frame semantics (staleness histogram mass,
inclusion bounds, baseline switch counts, byte split); JSONL events
round-trip through the sink; tracer span nesting and rollup; manifest
save/load; and ``run_sweep`` writing its manifest + per-cell cache stats.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro import netsim
from repro.core.cache import EngineCache, EngineSpec
from repro.core.runner import run_experiment
from repro.configs.facade_paper import lenet
from repro.data.synthetic import SynthSpec, make_clustered_data
from repro.obs import (FRAME_FIELDS, JsonlSink, MetricsFrame, Obs,
                       ObsConfig, RunManifest, Tracer, bench_stamp,
                       fingerprint, read_jsonl)
from repro.sweep import SweepCell, run_sweep

pytestmark = pytest.mark.tier0

CFG = lenet(smoke=True).replace(n_classes=4)
ALL_ALGOS = ("facade", "el", "dpsgd", "deprl", "dac")
KW = dict(rounds=3, k=2, degree=2, local_steps=2, batch_size=4, lr=0.05,
          eval_every=1, seed=0)


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
    np.testing.assert_array_equal(np.asarray(ref.node_acc),
                                  np.asarray(got.node_acc))
    # the per-eval fairness trajectory (plain-scalar NamedTuples) must be
    # value-identical too — eval telemetry is pure observation
    assert ref.eval_frames == got.eval_frames
    for (r1, c1), (r2, c2) in zip(ref.cluster_history, got.cluster_history):
        assert r1 == r2
        np.testing.assert_array_equal(c1, c2)


# ------------------------------------------------- telemetry is pure ------
@pytest.mark.parametrize("engine", [True, False],
                         ids=["engine", "legacy"])
@pytest.mark.parametrize("algo", ALL_ALGOS)
def test_obs_never_perturbs_trajectory(algo, engine, tiny_ds, tmp_path):
    """The central off-switch contract, both directions at once:
    ``obs=None`` is the historical path, and a fully enabled ``Obs``
    (frames + tracer + JSONL sink) observes the SAME trajectory."""
    ref = run_experiment(algo, CFG, tiny_ds, engine=engine, **KW)
    obs = Obs(ObsConfig(), jsonl=tmp_path / f"{algo}.jsonl",
              out_dir=tmp_path)
    got = run_experiment(algo, CFG, tiny_ds, engine=engine, obs=obs, **KW)
    _assert_runs_identical(ref, got)
    # and telemetry actually observed every round
    assert obs.frames_table()["round"].tolist() == [1, 2, 3]
    assert len(obs.manifests) == 1
    # eval-side telemetry observed every eval, and the series' FINAL
    # entry is bit-for-bit the run's final DP/EO scalars (they are read
    # off the frame, never recomputed) — for all 5 algorithms on both
    # drivers via this parametrization
    et = obs.eval_table()
    assert et["round"].tolist() == [1, 2, 3]
    last = got.eval_frames[-1]
    assert last.dp == got.dp and last.eo == got.eo
    assert et["dp"][-1] == got.dp and et["eo"][-1] == got.eo
    assert last.fair_acc == got.fair_acc[-1][1]
    # churn only exists where a cluster assignment does
    if algo != "facade":
        assert et["cluster_churn"].tolist() == [0.0, 0.0, 0.0]


def test_obs_parity_under_netsim(tiny_ds):
    """Same contract on the hardest preset (bursty + tiers + async stale
    gossip), where the frame reads conds/gossip state."""
    net = netsim.NetworkConfig.preset("edge-v2")
    for engine in (True, False):
        ref = run_experiment("facade", CFG, tiny_ds, engine=engine,
                             net=net, **KW)
        got = run_experiment("facade", CFG, tiny_ds, engine=engine,
                             net=net, obs=Obs(ObsConfig()), **KW)
        _assert_runs_identical(ref, got)


# ------------------------------------------- engine/legacy frame parity --
@pytest.mark.parametrize("algo,preset", [
    (algo, preset) for algo in ("facade", "el")
    for preset in (None, "async-edge", "edge-v2")] + [
    ("dpsgd", "edge-v2"), ("deprl", "edge-v2"), ("dac", "edge-v2")])
def test_engine_and_legacy_frames_identical(algo, preset, tiny_ds):
    """Both drivers run the one shared ``compute_frame`` at the same
    point in the round — frames must agree like trajectories do."""
    net = netsim.NetworkConfig.preset(preset) if preset else None
    obs_e, obs_l = Obs(ObsConfig()), Obs(ObsConfig())
    run_experiment(algo, CFG, tiny_ds, engine=True, net=net, obs=obs_e,
                   **KW)
    run_experiment(algo, CFG, tiny_ds, engine=False, net=net, obs=obs_l,
                   **KW)
    te, tl = obs_e.frames_table(), obs_l.frames_table()
    for field in te:
        np.testing.assert_allclose(te[field], tl[field], rtol=1e-6,
                                   atol=1e-6, err_msg=field)


# ------------------------------------------------------ frame semantics --
def test_frame_semantics(tiny_ds):
    n = tiny_ds.n_nodes
    net = netsim.NetworkConfig.preset("edge-v2")
    obs = Obs(ObsConfig())
    run_experiment("facade", CFG, tiny_ds, net=net, obs=obs, **KW)
    t = obs.frames_table()
    assert set(t) == {"round"} | set(FRAME_FIELDS)
    # staleness histogram: one bin per node, every round
    np.testing.assert_allclose(t["stale_hist"].sum(axis=1), float(n))
    assert np.all(t["inclusion"] >= 0.0) and np.all(t["inclusion"] <= 1.0)
    assert np.all(t["delivered_edges"] <= n * (n - 1))
    assert np.all(t["update_norm"] >= 0) and np.all(t["param_norm"] > 0)
    assert np.all(t["bytes_core"] >= 0) and np.all(t["bytes_edge"] >= 0)


def test_baselines_report_zero_switches(tiny_ds):
    """Off-FACADE there is no cluster assignment — the field must be an
    all-zeros constant, never absent (fixed pytree contract)."""
    obs = Obs(ObsConfig())
    run_experiment("el", CFG, tiny_ds, obs=obs, **KW)
    t = obs.frames_table()
    np.testing.assert_array_equal(t["cluster_switches"], 0.0)
    # all-fresh run: staleness mass sits entirely in age bin 0
    np.testing.assert_allclose(t["stale_hist"][:, 0], tiny_ds.n_nodes)
    np.testing.assert_allclose(t["stale_hist"][:, 1:], 0.0)


def test_gated_off_fields_are_zero_not_absent(tiny_ds):
    cfg = ObsConfig(norms=False, comm=False, switches=False,
                    staleness_bins=2)
    obs = Obs(cfg)
    run_experiment("facade", CFG, tiny_ds, obs=obs, **KW)
    t = obs.frames_table()
    assert set(t) == {"round"} | set(FRAME_FIELDS)   # schema fixed
    for f in ("update_norm", "param_norm", "cluster_switches",
              "delivered_edges", "inclusion", "bytes_core", "bytes_edge"):
        np.testing.assert_array_equal(t[f], 0.0, err_msg=f)
    assert t["stale_hist"].shape[1] == 2


def test_obsconfig_validation():
    with pytest.raises(ValueError, match="staleness_bins"):
        ObsConfig(staleness_bins=0)


# ------------------------------------------------------- cache-key fork --
# Every ObsConfig field changes the compiled segment program's outputs
# (the MetricsFrame leaf), so every field must fork the EngineSpec key.
# Fields-coverage completeness check + perturbation, the _TOPO_PERTURB
# pattern; tests/test_property.py imports this table so the hypothesis
# twin can never drift.
_OBS_PERTURB = {
    "norms": lambda v: not v,
    "comm": lambda v: not v,
    "switches": lambda v: not v,
    "staleness_bins": lambda v: v + 1,
    "faults": lambda v: not v,
}


def test_obs_perturb_covers_every_obsconfig_field():
    fields = {f.name for f in dataclasses.fields(ObsConfig)}
    assert fields == set(_OBS_PERTURB)


def _spec(obs):
    return EngineSpec(algo="facade", cfg=CFG, n=4, k=2, degree=2,
                      local_steps=2, batch_size=4, lr=0.05, obs=obs)


def test_every_obsconfig_field_forks_the_cache_key():
    base = _spec(ObsConfig())
    assert base != _spec(None)                       # enabling forks
    assert base == _spec(ObsConfig())                # equal configs share
    for name, fn in _OBS_PERTURB.items():
        mutated = _spec(dataclasses.replace(
            ObsConfig(), **{name: fn(getattr(ObsConfig(), name))}))
        assert mutated != base, name
        table = {base: "b", mutated: "m"}
        assert table[base] == "b" and table[mutated] == "m"


def test_host_side_obs_settings_never_fork_the_key(tiny_ds, tmp_path):
    """Attaching different sinks / out dirs / no Obs config at all must
    reuse one cache entry: only the device-side ObsConfig is keyed."""
    cache = EngineCache()
    run_experiment("el", CFG, tiny_ds, cache=cache,
                   obs=Obs(ObsConfig(), jsonl=tmp_path / "a.jsonl"), **KW)
    run_experiment("el", CFG, tiny_ds, cache=cache,
                   obs=Obs(ObsConfig(), out_dir=tmp_path), **KW)
    run_experiment("el", CFG, tiny_ds, cache=cache, obs=Obs(ObsConfig()),
                   **KW)
    st = cache.stats()
    assert st["entries"] == 1 and st["hits"] == 2
    # and an Obs with config=None (spans only) shares the obs=None entry
    run_experiment("el", CFG, tiny_ds, cache=cache, **KW)
    run_experiment("el", CFG, tiny_ds, cache=cache, obs=Obs(config=None),
                   **KW)
    assert cache.stats()["entries"] == 2


# ------------------------------------------------------------ sink/trace --
def test_jsonl_round_trip(tmp_path):
    path = tmp_path / "events.jsonl"
    records = [{"type": "event", "name": "a", "x": 1},
               {"type": "span", "name": "b", "dur_s": 0.25,
                "attrs": {"nested": [1, 2, 3]}}]
    with JsonlSink(path) as sink:
        for r in records:
            sink.emit(r)
    assert sink.n_emitted == len(records)
    assert read_jsonl(path) == records
    assert read_jsonl(tmp_path / "never_written.jsonl") == []


def test_tracer_nesting_and_rollup(tmp_path):
    sink = JsonlSink(tmp_path / "t.jsonl")
    tr = Tracer(sink=sink)
    with tr.span("outer"):
        with tr.span("inner"):
            tr.event("tick", k=1)
        with tr.span("inner"):
            pass
    sink.close()
    by_name = {}
    for s in tr.spans:
        by_name.setdefault(s["name"], []).append(s)
    assert [s["parent"] for s in by_name["inner"]] == ["outer", "outer"]
    assert all(s["depth"] == 1 for s in by_name["inner"])
    assert by_name["outer"][0]["parent"] is None
    # inner spans closed before outer: durations nest
    assert by_name["outer"][0]["dur_s"] >= max(
        s["dur_s"] for s in by_name["inner"])
    roll = tr.rollup()
    assert roll["spans"]["inner"]["count"] == 2
    assert roll["events"] == {"tick": 1}
    # the sink saw every record (2 inner + 1 outer spans + 1 event)
    assert len(read_jsonl(sink.path)) == 4


def test_run_emits_expected_spans_and_events(tiny_ds, tmp_path):
    obs = Obs(ObsConfig(), jsonl=tmp_path / "run.jsonl")
    run_experiment("facade", CFG, tiny_ds, obs=obs, **KW)
    roll = obs.tracer.rollup()
    for name in ("cache.entry", "compile", "drain", "eval", "run"):
        assert name in roll["spans"], name
    assert roll["events"]["run.begin"] == roll["events"]["run.end"] == 1
    assert roll["events"]["cache.miss"] == 1        # private fresh cache
    recs = read_jsonl(tmp_path / "run.jsonl")
    assert {"span", "event", "metrics"} <= {r["type"] for r in recs}


def test_manifest_round_trip(tmp_path):
    m = RunManifest.build(kind="run", name="el-seed0",
                          spec=_spec(ObsConfig()),
                          settings={"rounds": 3},
                          timing={"spans": {}},
                          cache={"entries": 1})
    path = m.save(tmp_path / "manifest.json")
    back = RunManifest.load(path)
    assert back == m
    assert m.fingerprint == fingerprint(repr(_spec(ObsConfig())))
    # fingerprints are content hashes: same spec -> same print
    m2 = RunManifest.build(kind="run", name="other",
                           spec=_spec(ObsConfig()), settings={})
    assert m2.fingerprint == m.fingerprint
    assert RunManifest.build(
        kind="run", name="x", spec=_spec(None),
        settings={}).fingerprint != m.fingerprint


def test_bench_stamp_fingerprints_payload():
    stamp = bench_stamp("demo", {"a": 1})
    assert stamp["name"] == "demo"
    assert stamp["fingerprint"] == fingerprint({"a": 1})
    assert stamp["fingerprint"] != bench_stamp("demo", {"a": 2})["fingerprint"]


# ------------------------------------------------------------- run_sweep --
def test_run_sweep_manifest_and_cache_stats(tiny_ds, tmp_path):
    cells = [SweepCell(name=a, algo=a, cfg=CFG, dataset=tiny_ds, rounds=2,
                       kwargs=dict(k=2, degree=2, local_steps=2,
                                   batch_size=4, lr=0.05, eval_every=2))
             for a in ("facade", "el")]
    json_path = tmp_path / "sweep.json"
    obs = Obs(ObsConfig(), jsonl=tmp_path / "sweep.jsonl")
    sweep = run_sweep(cells, (0, 1), json_path=json_path, obs=obs)

    out = json.loads(json_path.read_text())
    assert out["cache"] == sweep.cache.stats()       # top-level stats
    for name in ("facade", "el"):
        cell = out["cells"][name]
        assert cell["cache"]["entries"] >= 1         # per-cell snapshot
    # snapshots are cumulative: the last cell's equals the final stats
    assert sweep.cells[-1].cache_stats == sweep.cache.stats()

    manifest = RunManifest.load(
        json_path.with_suffix(json_path.suffix + ".manifest.json"))
    assert manifest.kind == "sweep"
    assert manifest.cache == sweep.cache.stats()
    assert manifest.settings["cells"] == ["facade", "el"]
    assert "sweep.cell" in manifest.timing["spans"]
    # per-run manifests accumulated on the shared Obs: 2 cells x 2 seeds
    assert len(obs.manifests) == 4


def test_frames_table_concats_across_runs(tiny_ds):
    obs = Obs(ObsConfig())
    run_experiment("el", CFG, tiny_ds, obs=obs, **KW)
    run_experiment("el", CFG, tiny_ds, obs=obs, **{**KW, "seed": 1})
    t = obs.frames_table()
    assert t["round"].tolist() == [1, 2, 3, 1, 2, 3]
    for f in FRAME_FIELDS:
        assert t[f].shape[0] == 6


def test_empty_obs_frames_table():
    t = Obs(config=None).frames_table()
    assert t["round"].shape == (0,)
    assert all(t[f].shape[0] == 0 for f in FRAME_FIELDS)
    assert isinstance(MetricsFrame._fields, tuple)
