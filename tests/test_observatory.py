"""The fairness observatory: the health rule engine, manifest schema
tolerance, crash-tolerant JSONL replay, run/sweep reports, and the
benchmark regression gate.

Unit tests drive :func:`repro.obs.evaluate_health` over hand-built
tables (every rule fires and stays quiet on demand); the acceptance
pins run real experiments — an unguarded NaN-corruption run must come
back ``fail`` while a fault-free run stays a quiet ``ok``, and a
kill+resume run must preserve the per-eval fairness trajectory
bit-for-bit.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.configs.facade_paper import lenet
from repro.core.runner import run_experiment
from repro.data.synthetic import SynthSpec, make_clustered_data
from repro.obs import (HealthConfig, HealthContext, HealthReport, Obs,
                       ObsConfig, RunManifest, evaluate_health, read_jsonl,
                       worst_verdict)
from repro.obs.report import build_report, settlement_round
from repro.obs.report import main as report_main

pytestmark = pytest.mark.tier0

CFG = lenet(smoke=True).replace(n_classes=4)
KW = dict(rounds=4, k=2, degree=2, local_steps=2, batch_size=4,
          lr=0.05, eval_every=2, seed=0)


@pytest.fixture(scope="module")
def tiny_ds():
    spec = SynthSpec(n_classes=4, image_size=16, samples_per_class=8,
                     test_per_class=8, seed=3)
    return make_clustered_data(spec, (3, 1), ("rot0", "rot180"))


# ---------------------------------------------------- health: helpers ----
def _frames(rounds, **cols):
    """A healthy frames_table() dict over ``rounds``, columns overridable
    per test (only the columns the rules read)."""
    n = len(rounds)
    table = {"round": np.asarray(rounds, np.int64),
             "update_norm": np.full(n, 0.5),
             "param_norm": np.full(n, 1.0),
             "crashed": np.zeros(n),
             "quarantined": np.zeros(n),
             "inclusion": np.ones(n),
             "cluster_switches": np.zeros(n)}
    for k, v in cols.items():
        table[k] = np.asarray(v, np.float64)
    return table


def _evals(rounds, mean_acc):
    return {"round": np.asarray(rounds, np.int64),
            "mean_acc": np.asarray(mean_acc, np.float64)}


CTX = HealthContext(n=4)


def _judge(frames=None, evals=None, ctx=CTX, cfg=HealthConfig(),
           tracer=None):
    return evaluate_health(
        cfg, ctx,
        _frames([]) if frames is None else frames,
        _evals([], []) if evals is None else evals, tracer=tracer)


class _FakeTracer:
    def __init__(self):
        self.events = []

    def event(self, name, **kw):
        self.events.append({"name": name, **kw})


# ------------------------------------------------------- health: rules ---
def test_clean_tables_verdict_ok():
    rep = _judge(_frames(range(1, 7)), _evals([2, 4, 6], [0.3, 0.5, 0.7]))
    assert rep.verdict == "ok" and rep.issues == []
    assert rep.rounds_seen == 6 and rep.evals_seen == 3


def test_empty_tables_verdict_ok():
    # a run without a device ObsConfig has no metrics frames; a
    # target_acc run may stop after one eval — rules must stay silent
    rep = _judge()
    assert rep.verdict == "ok" and rep.issues == []
    assert rep.rounds_seen == 0 and rep.evals_seen == 0


def test_nonfinite_fires_per_contiguous_range():
    un = [0.5, np.nan, np.inf, 0.5, 0.5, np.nan]
    rep = _judge(_frames([1, 2, 3, 4, 5, 6], update_norm=un))
    assert rep.verdict == "fail"
    assert [(i.rule, i.round_start, i.round_end) for i in rep.issues] == [
        ("nonfinite", 2, 3), ("nonfinite", 6, 6)]


def test_divergence_finite_but_runaway():
    pn = [1.0, 1.0, 2e6, 1.0]
    rep = _judge(_frames([1, 2, 3, 4], param_norm=pn))
    assert [i.rule for i in rep.issues] == ["divergence"]
    assert rep.verdict == "fail"
    assert rep.issues[0].value == pytest.approx(2e6)


def test_quarantine_spike():
    rep = _judge(_frames([1, 2, 3, 4], crashed=[0, 3, 3, 0]))
    (issue,) = rep.issues
    assert issue.rule == "quarantine_spike" and issue.severity == "warn"
    assert (issue.round_start, issue.round_end) == (2, 3)
    assert issue.value == pytest.approx(0.75)
    assert rep.verdict == "warn"


def test_inclusion_floor_needs_context():
    frames = _frames(range(1, 9), inclusion=np.full(8, 0.5))
    # no adaptive-topo floor in context: the rule has nothing to check
    assert _judge(frames).issues == []
    ctx = HealthContext(n=4, warmup_rounds=2, inclusion_floor=0.9)
    rep = _judge(frames, ctx=ctx)
    (issue,) = rep.issues
    assert issue.rule == "inclusion_floor" and issue.severity == "warn"
    assert issue.round_start == 3        # first post-warmup round
    # within inclusion_slack of the floor: delivered as promised
    ok = _frames(range(1, 9), inclusion=np.full(8, 0.88))
    assert _judge(ok, ctx=ctx).issues == []


def test_cluster_flapping_past_warmup_grace():
    switches = np.full(16, 4.0)          # every node flips, every round
    rep = _judge(_frames(range(1, 17), cluster_switches=switches))
    (issue,) = rep.issues
    assert issue.rule == "cluster_flapping"
    assert issue.round_start == 9        # default flap_grace=8, warmup=0
    assert issue.value == pytest.approx(1.0)
    # settled assignment: quiet
    assert _judge(_frames(range(1, 17))).issues == []


def test_accuracy_stall_low_and_flat_only():
    rounds = list(range(2, 22, 2))
    (issue,) = _judge(evals=_evals(rounds, [0.3] * 10)).issues
    assert issue.rule == "accuracy_stall" and issue.severity == "warn"
    # improving: quiet
    assert _judge(evals=_evals(rounds, np.linspace(0.1, 0.8, 10))).issues == []
    # flat but already accurate: quiet
    assert _judge(evals=_evals(rounds, [0.8] * 10)).issues == []
    # too few evals for the window: quiet
    assert _judge(evals=_evals([2, 4], [0.3, 0.3])).issues == []


def test_accuracy_collapse_from_peak():
    rep = _judge(evals=_evals([2, 4, 6, 8], [0.1, 0.5, 0.6, 0.2]))
    (issue,) = rep.issues
    assert issue.rule == "accuracy_collapse" and rep.verdict == "fail"
    assert (issue.round_start, issue.round_end) == (8, 8)
    assert issue.value == pytest.approx(0.4)
    # peak never cleared collapse_min_peak: a bad run, not a collapse
    assert _judge(evals=_evals([2, 4, 6], [0.1, 0.35, 0.05])).issues == []


def test_disable_and_unknown_rule_names():
    frames = _frames([1, 2], update_norm=[np.nan, np.nan])
    assert _judge(frames).verdict == "fail"
    quiet = _judge(frames, cfg=HealthConfig(disable=("nonfinite",)))
    assert quiet.verdict == "ok"
    with pytest.raises(ValueError, match="unknown health rules"):
        HealthConfig(disable=("no_such_rule",))


def test_worst_verdict_ordering():
    assert worst_verdict([]) == "ok"
    assert worst_verdict(["ok", "ok"]) == "ok"
    assert worst_verdict(["ok", "warn", "ok"]) == "warn"
    assert worst_verdict(["warn", "fail", "warn"]) == "fail"
    # a garbled verdict is not a clean one
    assert worst_verdict(["ok", "borked"]) == "fail"


def test_health_events_fired_on_tracer():
    tracer = _FakeTracer()
    _judge(_frames([1, 2], update_norm=[np.nan, 0.5]),
           _evals([2, 4, 6, 8], [0.1, 0.5, 0.6, 0.2]), tracer=tracer)
    names = [e["name"] for e in tracer.events]
    assert names == ["health.nonfinite", "health.accuracy_collapse"]
    assert all({"severity", "round_start", "round_end", "value",
                "detail"} <= set(e) for e in tracer.events)


def test_health_report_json_roundtrip():
    rep = _judge(_frames([1, 2], update_norm=[np.nan, 0.5]))
    back = HealthReport.from_json(json.loads(json.dumps(rep.to_json())))
    assert back == rep


# ----------------------------------------- acceptance: real-run verdicts --
def test_nan_storm_flagged_clean_run_quiet(tiny_ds, tmp_path):
    from repro.netsim import NetworkConfig
    from repro.resil import FaultConfig

    ideal = NetworkConfig.preset("ideal")
    clean_obs = Obs(ObsConfig(), out_dir=tmp_path)
    run_experiment("facade", CFG, tiny_ds, net=ideal, obs=clean_obs, **KW)
    clean = clean_obs.manifests[-1].health
    assert clean["verdict"] == "ok" and clean["issues"] == []
    assert not [e for e in clean_obs.tracer.events
                if e["name"].startswith("health.")]

    storm = dataclasses.replace(ideal, faults=FaultConfig(
        corrupt_rate=0.6, corrupt_mode="nan", robust=False))
    storm_obs = Obs(ObsConfig(), out_dir=tmp_path)
    run_experiment("facade", CFG, tiny_ds, net=storm, obs=storm_obs, **KW)
    health = storm_obs.manifests[-1].health
    assert health["verdict"] == "fail"
    assert "nonfinite" in {i["rule"] for i in health["issues"]}
    fired = {e["name"] for e in storm_obs.tracer.events
             if e["name"].startswith("health.")}
    assert "health.nonfinite" in fired
    # the verdict survives the manifest round-trip on disk
    back = RunManifest.load(tmp_path / "manifest_facade-seed0.json")
    assert back.health["verdict"] == "fail"


def test_resume_preserves_eval_frames(tiny_ds, tmp_path):
    from repro.core import engine as engine_mod

    ref = run_experiment("facade", CFG, tiny_ds,
                         ckpt=str(tmp_path / "ref.npz"), **KW)
    assert len(ref.eval_frames) == 2     # rounds 2 and 4

    class _Killed(Exception):
        pass

    # die draining the second segment: the first one's checkpoint has
    # landed by then
    ck = str(tmp_path / "killed.npz")
    orig = engine_mod.SegmentEngine.drain
    calls = {"n": 0}

    def killer(self, *a, **k):
        if calls["n"] >= 1:
            raise _Killed()
        calls["n"] += 1
        return orig(self, *a, **k)

    engine_mod.SegmentEngine.drain = killer
    try:
        with pytest.raises(_Killed):
            run_experiment("facade", CFG, tiny_ds, obs=Obs(ObsConfig()),
                           ckpt=ck, **KW)
    finally:
        engine_mod.SegmentEngine.drain = orig

    obs = Obs(ObsConfig())
    got = run_experiment("facade", CFG, tiny_ds, obs=obs, ckpt=ck, **KW)
    # the restored half was replayed, the finished half recorded live —
    # the stitched trajectory is bit-for-bit the uninterrupted one
    assert got.eval_frames == ref.eval_frames
    table = obs.eval_table()
    assert table["round"].tolist() == [f.round for f in ref.eval_frames]
    assert table["dp"].tolist() == [f.dp for f in ref.eval_frames]
    assert table["eo"].tolist() == [f.eo for f in ref.eval_frames]


# ------------------------------------------------ manifest & jsonl I/O ---
def test_manifest_schema_growth_both_directions(tmp_path):
    m = RunManifest.build(kind="run", name="x", spec="spec",
                          settings={"preset": "ideal"},
                          health={"verdict": "warn", "issues": []})
    p = m.save(tmp_path / "m.json")
    data = json.loads(p.read_text())
    data["from_the_future"] = {"new": True}   # a newer writer's extra key
    del data["jax_version"]                   # an older writer's missing key
    p.write_text(json.dumps(data))
    back = RunManifest.load(p)
    assert back.name == "x" and back.settings == {"preset": "ideal"}
    assert back.health == {"verdict": "warn", "issues": []}
    assert back.jax_version == ""             # defaulted, no TypeError
    assert not hasattr(back, "from_the_future")


def test_read_jsonl_skips_truncated_final_line(tmp_path):
    p = tmp_path / "t.jsonl"
    p.write_text('{"a": 1}\n{"b": 2}\n{"c": 3')   # killed mid-write
    with pytest.warns(RuntimeWarning, match="truncated final line 3"):
        assert read_jsonl(p) == [{"a": 1}, {"b": 2}]


def test_read_jsonl_midfile_corruption_raises(tmp_path):
    p = tmp_path / "t.jsonl"
    p.write_text('{"a": 1}\nnot json\n{"c": 3}\n')
    with pytest.raises(json.JSONDecodeError):
        read_jsonl(p)
    assert read_jsonl(tmp_path / "never_written.jsonl") == []


# -------------------------------------------------------------- reports --
def _fake_run_artifacts(tmp_path, churn_last=0.0):
    """A manifest + JSONL trace shaped like run_experiment's output."""
    def ev(rnd, dp, churn):
        return {"type": "eval", "round": rnd, "mean_acc": 0.5, "fair_acc":
                0.6, "dp": dp, "eo": dp, "worst_cluster_acc": 0.4,
                "cluster_churn": churn}
    events = [
        {"type": "event", "name": "run.begin", "run": "facade-seed0"},
        ev(2, 0.4, 1.0), ev(4, 0.2, churn_last),
        {"type": "event", "name": "health.nonfinite", "severity": "fail",
         "round_start": 3, "round_end": 4, "value": 2.0, "detail": "x"},
        {"type": "event", "name": "run.end", "run": "facade-seed0"},
    ]
    trace = tmp_path / "trace.jsonl"
    trace.write_text("".join(json.dumps(e) + "\n" for e in events))
    manifest = RunManifest.build(
        kind="run", name="facade-seed0", spec="spec",
        settings={"jsonl": str(trace)},
        timing={"spans": {"engine.segment": {"count": 2, "total_s": 1.5}}},
        cache={"compiles": 3},
        health={"verdict": "fail", "rounds_seen": 4, "evals_seen": 2,
                "issues": [{"rule": "nonfinite", "severity": "fail",
                            "round_start": 3, "round_end": 4,
                            "value": 2.0, "detail": "poisoned"}]})
    return manifest.save(tmp_path / "manifest.json"), trace


def test_run_report_build_and_render(tmp_path):
    path, _ = _fake_run_artifacts(tmp_path)
    report, md = build_report(path)
    assert report["n_evals"] == 2
    assert report["trajectory"]["dp"] == [0.4, 0.2]
    assert report["settlement_round"] == 4    # churn at 2, settled by 4
    assert [e["name"] for e in report["health_events"]] == [
        "health.nonfinite"]
    for section in ("# Run report: facade-seed0", "**verdict: fail**",
                    "## Health", "## Fairness trajectory",
                    "settlement round: 4", "## Timing", "## Compile cache"):
        assert section in md


def test_report_settlement_and_missing_trace(tmp_path):
    # still churning at the last eval: settlement is honestly n/a
    path, trace = _fake_run_artifacts(tmp_path, churn_last=2.0)
    report, md = build_report(path)
    assert report["settlement_round"] is None
    assert "still churning" in md
    assert settlement_round([]) is None
    # a lost trace degrades to a manifest-only report, never raises
    trace.unlink()
    report, md = build_report(path)
    assert report["n_evals"] == 0 and "no eval records" in md


def test_report_cli_out_and_json(tmp_path, capsys):
    path, _ = _fake_run_artifacts(tmp_path)
    out = tmp_path / "report.md"
    assert report_main([str(path), "--out", str(out)]) == 0
    assert "# Run report: facade-seed0" in out.read_text()
    assert report_main([str(path), "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["name"] == "facade-seed0" and payload["n_evals"] == 2


def test_sweep_report_render(tmp_path):
    sweep = {"seeds": [0, 1], "wall_s": 1.0, "cells": {
        "facade/ideal": {"algo": "facade", "net": "ideal", "error": None,
                         "skipped": False,
                         "health": {"verdict": "warn"},
                         "summary": {"best_fair_acc": {"mean": 0.8},
                                     "dp": {"mean": 0.1},
                                     "eo": {"mean": 0.2}}},
        "el/ideal": {"algo": "el", "net": "ideal", "error": "boom",
                     "skipped": False, "health": None, "summary": {}},
    }}
    path = tmp_path / "sweep.json"
    path.write_text(json.dumps(sweep))
    report, md = build_report(path)
    assert report["kind"] == "sweep" and len(report["cells"]) == 2
    assert "# Sweep report" in md and "warn" in md and "ERROR" in md


# ------------------------------------------- sweep health + trajectory ---
def test_sweep_cell_health_and_fairness_trajectory(tiny_ds, tmp_path):
    from repro.sweep import SweepCell, run_sweep

    obs = Obs(ObsConfig())
    kw = {k: v for k, v in KW.items() if k not in ("rounds", "seed")}
    cell = SweepCell(name="facade-ideal", algo="facade", cfg=CFG,
                     dataset=tiny_ds, rounds=KW["rounds"], kwargs=kw)
    sweep = run_sweep([cell], seeds=(0, 1), obs=obs,
                      json_path=tmp_path / "sweep.json")
    c = sweep.to_json()["cells"]["facade-ideal"]
    assert c["health"]["verdict"] == "ok"
    assert set(c["health"]["runs"]) == {"facade-seed0", "facade-seed1"}
    traj = c["summary"]["fairness_trajectory"]
    assert [row["round"] for row in traj] == [2, 4]
    assert all(row["n"] == 2 for row in traj)
    assert {"dp_mean", "dp_std", "eo_mean", "worst_cluster_acc_mean",
            "cluster_churn_mean"} <= set(traj[0])
    # the sweep manifest rolls the per-cell verdicts up...
    man = RunManifest.load(tmp_path / "sweep.json.manifest.json")
    assert man.health == {"verdict": "ok",
                          "cells": {"facade-ideal": "ok"}}
    # ...and the sweep JSON renders through the same report CLI path
    _, md = build_report(tmp_path / "sweep.json")
    assert "# Sweep report" in md and "facade-ideal" in md


# ------------------------------------------------- the regression gate ---
def _traj_rec(name, payload):
    return {"name": name, "payload": payload}


def test_write_bench_appends_trajectory(tmp_path, monkeypatch):
    from benchmarks import common as bcommon

    monkeypatch.setattr(bcommon, "RESULTS_DIR", tmp_path)
    bcommon.write_bench("demo", {"metric": 1.0})
    bcommon.write_bench("demo", {"metric": 2.0})
    recs = read_jsonl(bcommon.trajectory_path())
    assert [r["name"] for r in recs] == ["demo", "demo"]
    assert [r["payload"]["metric"] for r in recs] == [1.0, 2.0]
    assert all("manifest" in r["payload"] for r in recs)  # bench_stamp'd
    assert (tmp_path / "BENCH_demo.json").exists()


def test_check_regress_semantics():
    from benchmarks import check_regress

    gates = {"demo": (check_regress.Gate("results.*.rps", "higher",
                                         rel_tol=0.1),)}
    good = {"results": {"a": {"rps": 100.0}, "b": {"rps": 50.0}}}
    # one record: baseline, nothing to diff, never fails
    v = check_regress.check([_traj_rec("demo", good)], gates)
    assert v["baselines"] == ["demo"] and not v["rows"]
    # identical back-to-back records pass every gate
    v = check_regress.check([_traj_rec("demo", good),
                             _traj_rec("demo", dict(good))], gates)
    assert len(v["rows"]) == 2 and not v["failures"]
    # a doctored regression on one leaf fails exactly that leaf
    bad = {"results": {"a": {"rps": 50.0}, "b": {"rps": 50.0}}}
    v = check_regress.check([_traj_rec("demo", good),
                             _traj_rec("demo", bad)], gates)
    assert [f["metric"] for f in v["failures"]] == ["results.a.rps"]
    # schema growth (a leaf absent on either side) is not a regression
    grown = {"results": {"a": {"rps": 100.0}, "c": {"rps": 1.0}}}
    v = check_regress.check([_traj_rec("demo", good),
                             _traj_rec("demo", grown)], gates)
    assert not v["failures"]
    with pytest.raises(ValueError, match="higher|lower"):
        check_regress.Gate("x", "sideways")


def test_check_regress_run_gates_the_trajectory(tmp_path, monkeypatch):
    from benchmarks import check_regress
    from benchmarks import common as bcommon

    monkeypatch.setattr(bcommon, "RESULTS_DIR", tmp_path)
    traj = bcommon.trajectory_path()
    traj.parent.mkdir(parents=True, exist_ok=True)
    good = json.dumps(_traj_rec("throughput", {"min_speedup": 2.0}))
    traj.write_text(good + "\n" + good + "\n")
    payload = check_regress.run()
    assert payload["n_failed"] == 0 and payload["n_checked"] == 1
    with traj.open("a") as fh:
        fh.write(json.dumps(_traj_rec("throughput",
                                      {"min_speedup": 0.5})) + "\n")
    with pytest.raises(RuntimeError, match="regression gate failed"):
        check_regress.run()
