"""repro.resil: node-fault injection, robust gossip, crash-safe resume.

Pins the subsystem's contracts:

* **off-switches are bit-for-bit**: ``FaultConfig()`` (all rates zero) and
  ``FaultConfig(robust=False)`` run the EXACT legacy trajectory for FACADE
  + all four baselines on BOTH drivers — injecting the fault machinery
  costs nothing until a rate is turned on;
* **engine/legacy parity under faults**: crashes, corruption and
  factory-reset restarts follow the shared ``resil.advance`` /
  ``resil.reset_nodes`` entry points, so the scan engine and the legacy
  loop stay bit-identical with faults ON, for every algorithm;
* **byte/time honesty**: a crashed node sends nothing (0 bytes) and never
  gates the round clock;
* **the robust guard**: non-finite senders are quarantined, honest mass
  renormalized, oversized payloads norm-clipped — and the guard is
  statically off at zero corruption;
* **crash-safe checkpoint/resume**: ``run_experiment(ckpt=...)`` resumes a
  killed run bit-for-bit (final carry, CommLog, eval histories, obs
  frames) for all five algorithms; stale checkpoints from another config
  are refused; ``repro.checkpoint.save`` is atomic and its loader turns
  garbage files into a clear ``CheckpointError``;
* **preemption-safe sweeps**: a failing cell is recorded and the grid
  continues (``RuntimeError`` only when ALL cells fail); with
  ``ckpt_dir=`` completed cells are skipped on rerun via their manifest
  fingerprint;
* **cache-key coverage**: every ``FaultConfig`` field forks the
  ``EngineSpec`` key through ``net.faults`` (perturbation table
  ``_FAULT_PERTURB`` + fields-coverage check; ``tests/test_property.py``
  imports the table for its hypothesis twin).
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint, netsim, resil
from repro.configs.facade_paper import lenet
from repro.core import engine as engine_mod
from repro.core.bindings import gossip_mix
from repro.core.cache import EngineSpec
from repro.core.runner import run_experiment
from repro.data.synthetic import SynthSpec, make_clustered_data
from repro.netsim import NetworkConfig
from repro.obs import Obs, ObsConfig
from repro.resil import FaultConfig, FaultState
from repro.sweep import SweepCell, run_sweep

pytestmark = pytest.mark.tier0

CFG = lenet(smoke=True).replace(n_classes=4)
ALL_ALGOS = ("facade", "el", "dpsgd", "deprl", "dac")
KW = dict(rounds=3, k=2, degree=2, local_steps=2, batch_size=4, lr=0.05,
          eval_every=3, seed=0)
NET = NetworkConfig.preset("edge-churn")


def _faulted(fcfg, net=NET):
    return dataclasses.replace(net, faults=fcfg)


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
    for (r1, c1), (r2, c2) in zip(ref.cluster_history, got.cluster_history):
        assert r1 == r2
        np.testing.assert_array_equal(c1, c2)


# ------------------------------------------------- config validation ------
def test_fault_config_validates():
    with pytest.raises(ValueError):
        FaultConfig(restart_mode="reboot")
    with pytest.raises(ValueError):
        FaultConfig(corrupt_mode="bitflip")
    with pytest.raises(ValueError):
        FaultConfig(crash_rate=1.5)
    with pytest.raises(ValueError):
        FaultConfig(corrupt_rate=-0.1)
    with pytest.raises(ValueError):
        FaultConfig(clip=0.0)


# ------------------------------------------------- cache-key contract -----
# Every FaultConfig field forks the EngineSpec key (through net.faults).
# tests/test_property.py imports this table for its hypothesis twin, so
# the two suites can never drift.
_FAULT_PERTURB = {
    "crash_rate": lambda v: (v + 0.1) % 1.0,
    "restart_rate": lambda v: (v + 0.25) % 1.0,
    "restart_mode": lambda v: ("reset" if v == "rejoin-stale"
                               else "rejoin-stale"),
    "corrupt_rate": lambda v: (v + 0.1) % 1.0,
    "corrupt_mode": lambda v: "scale" if v == "noise" else "noise",
    "corrupt_scale": lambda v: v + 1.0,
    "robust": lambda v: not v,
    "clip": lambda v: v + 0.5,
}


def test_fault_perturb_covers_every_faultconfig_field():
    fields = {f.name for f in dataclasses.fields(FaultConfig)}
    assert fields == set(_FAULT_PERTURB)


def _spec(net):
    return EngineSpec(algo="facade", cfg=CFG, n=4, k=2, degree=2,
                      local_steps=2, batch_size=4, lr=0.05, net=net)


def test_every_faultconfig_field_forks_the_cache_key():
    faults = FaultConfig()
    base = _spec(_faulted(faults))
    assert base != _spec(NET)                        # attaching forks
    assert base == _spec(_faulted(FaultConfig()))    # equal configs share
    for name, fn in _FAULT_PERTURB.items():
        mutated = _spec(_faulted(dataclasses.replace(
            faults, **{name: fn(getattr(faults, name))})))
        assert mutated != base, name
        table = {base: "b", mutated: "m"}
        assert table[base] == "b" and table[mutated] == "m"


# ------------------------------------------------- off-switches -----------
@pytest.mark.parametrize("algo", ALL_ALGOS)
def test_zero_rate_faults_bit_identical(algo, tiny_ds):
    """The central off-switch contract: a FaultConfig with all rates zero
    (robust on OR off) runs the exact legacy trajectory, both drivers."""
    for engine in (True, False):
        ref = run_experiment(algo, CFG, tiny_ds, net=NET, engine=engine,
                             **KW)
        for fcfg in (FaultConfig(), FaultConfig(robust=False)):
            got = run_experiment(algo, CFG, tiny_ds, net=_faulted(fcfg),
                                 engine=engine, **KW)
            _assert_runs_identical(ref, got)


# ------------------------------------------------- engine/legacy parity ---
@pytest.mark.parametrize("algo", ALL_ALGOS)
def test_engine_legacy_parity_under_faults(algo, tiny_ds):
    """Crashes + corruption active: scan engine == legacy loop, and the
    trajectory actually differs from the fault-free one."""
    net = _faulted(FaultConfig(crash_rate=0.3, restart_rate=0.5,
                               corrupt_rate=0.3))
    eng = run_experiment(algo, CFG, tiny_ds, net=net, engine=True, **KW)
    leg = run_experiment(algo, CFG, tiny_ds, net=net, engine=False, **KW)
    _assert_runs_identical(eng, leg)
    ref = run_experiment(algo, CFG, tiny_ds, net=NET, engine=True, **KW)
    assert (eng.comm.bytes != ref.comm.bytes
            or eng.fair_acc != ref.fair_acc)


@pytest.mark.parametrize("algo", ["facade", "dac"])
def test_engine_legacy_parity_reset_restarts(algo, tiny_ds):
    """restart_mode="reset" factory-resets a rejoining node BEFORE the
    round, identically in both drivers (the stateful-extra algorithms are
    the hard cases: FACADE's cluster ids, DAC's similarity table)."""
    net = _faulted(FaultConfig(crash_rate=0.4, restart_rate=0.6,
                               restart_mode="reset"))
    eng = run_experiment(algo, CFG, tiny_ds, net=net, engine=True, **KW)
    leg = run_experiment(algo, CFG, tiny_ds, net=net, engine=False, **KW)
    _assert_runs_identical(eng, leg)


# ------------------------------------------------- byte/time honesty ------
def test_crashed_nodes_cost_zero_bytes_and_seconds(tiny_ds):
    """crash_rate=1, restart_rate=0: after round 1 every node is down —
    no bytes move and the round clock never waits on a corpse."""
    net = _faulted(FaultConfig(crash_rate=1.0, restart_rate=0.0))
    r = run_experiment("el", CFG, tiny_ds, net=net, **KW)
    per_round = np.diff(np.asarray([0.0] + list(r.comm.bytes)))
    assert (per_round == 0).all()
    per_s = np.diff(np.asarray([0.0] + list(r.comm.seconds)))
    assert (per_s == 0).all()


# ------------------------------------------------- guard unit tests -------
def _ring_w(n):
    from repro.core import topology
    return topology.mixing_matrix(topology.ring(n, 2))


def test_gossip_mix_guard_quarantines_nan_sender():
    n = 4
    w = _ring_w(n)
    key = jax.random.PRNGKey(0)
    tree = {"p": jax.random.normal(key, (n, 3))}
    poisoned = {"p": tree["p"].at[1].set(jnp.nan)}
    guard = FaultConfig(corrupt_rate=0.5, corrupt_mode="nan")
    out = gossip_mix(w, tree, poisoned, guard=resil.guard_of(guard))
    # receivers stay finite; the poisoned sender's row mixes only its own
    # (finite, local) state with honest neighbors
    assert bool(jnp.isfinite(out["p"]).all())
    # unguarded: NaN spreads to every neighbor of node 1
    bad = gossip_mix(w, tree, poisoned)
    assert not bool(jnp.isfinite(bad["p"]).all())


def test_gossip_mix_guard_clips_oversized_sender():
    n = 4
    w = _ring_w(n)
    tree = {"p": jnp.ones((n, 3))}
    blown = {"p": tree["p"].at[2].mul(1e6)}
    guard = resil.guard_of(FaultConfig(corrupt_rate=0.5, clip=3.0))
    out = gossip_mix(w, tree, blown, guard=guard)
    # the 1e6-norm payload is clipped to ~clip x receiver norm, so no
    # receiver can be dragged more than a few x its own scale
    assert float(jnp.abs(out["p"]).max()) < 1e3
    bad = gossip_mix(w, tree, blown)
    assert float(jnp.abs(bad["p"]).max()) > 1e4


def test_guard_of_statically_gates():
    assert resil.guard_of(None) is None
    assert resil.guard_of(FaultConfig()) is None                # rate 0
    assert resil.guard_of(FaultConfig(corrupt_rate=0.5,
                                      robust=False)) is None    # robust off
    g = resil.guard_of(FaultConfig(corrupt_rate=0.5))
    assert g is not None and g.clip == 3.0


# ------------------------------------------------- fault primitives -------
def test_corrupt_view_modes_and_masking():
    n = 3
    conds = netsim.RoundConditions(
        edge_mask=jnp.ones((n, n)), active=jnp.ones((n,)),
        straggler=jnp.zeros((n,)), stale=None,
        corrupt=jnp.asarray([0.0, 1.0, 0.0]),
        fault_key=jax.random.PRNGKey(7))
    tree = {"f": jnp.ones((n, 2)), "i": jnp.arange(n, dtype=jnp.int32)}
    for mode, check in [
        ("nan", lambda v: bool(jnp.isnan(v).all())),
        ("scale", lambda v: bool((v == 100.0).all())),
        ("noise", lambda v: bool((jnp.abs(v - 1.0) > 1.0).all())),
    ]:
        out = resil.corrupt_view(
            FaultConfig(corrupt_rate=0.5, corrupt_mode=mode), conds, tree)
        assert check(out["f"][1]), mode               # masked row mangled
        np.testing.assert_array_equal(out["f"][0], tree["f"][0])
        np.testing.assert_array_equal(out["f"][2], tree["f"][2])
        np.testing.assert_array_equal(out["i"], tree["i"])  # ints shielded


def test_reset_nodes_restores_only_restarted_rows():
    n = 2
    init = {"p": jnp.zeros((n, 3)), "rng": jnp.zeros((2,), jnp.uint32),
            "round": jnp.asarray(0)}
    live = {"p": jnp.ones((n, 3)), "rng": jnp.ones((2,), jnp.uint32),
            "round": jnp.asarray(9)}
    out = resil.reset_nodes(n, jnp.asarray([1.0, 0.0]), init, live)
    np.testing.assert_array_equal(out["p"][0], np.zeros(3))   # reset
    np.testing.assert_array_equal(out["p"][1], np.ones(3))    # untouched
    # PRNG keys (uint32, shape (2,) == n here!) and scalars pass through
    np.testing.assert_array_equal(out["rng"], live["rng"])
    assert int(out["round"]) == 9


def test_init_state_gating():
    assert resil.init_state(None, 4) is None
    assert resil.init_state(NET, 4) is None
    assert resil.init_state(_faulted(FaultConfig(corrupt_rate=0.5)),
                            4) is None                # corruption: stateless
    st = resil.init_state(_faulted(FaultConfig(crash_rate=0.5)), 4)
    assert isinstance(st, FaultState) and st.init is None
    with pytest.raises(ValueError):
        resil.init_state(_faulted(FaultConfig(crash_rate=0.5,
                                              restart_mode="reset")), 4)
    st = resil.init_state(
        _faulted(FaultConfig(crash_rate=0.5, restart_mode="reset")), 4,
        state={"p": jnp.ones((4, 2))})
    assert st.init is not None


# ------------------------------------------------- checkpoint io ----------
def test_checkpoint_roundtrip_bf16_none_namedtuple(tmp_path):
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "bf": jnp.ones((3,), jnp.bfloat16) * 1.5,
            "none": None,
            "nt": FaultState(down=np.zeros(4, np.float32), init=None),
            "nested": [np.asarray(2), (np.asarray(3.0), None)]}
    p = tmp_path / "ck.npz"
    checkpoint.save(str(p), tree, meta={"k": 1})
    got, meta = checkpoint.load(str(p))
    assert meta == {"k": 1}
    np.testing.assert_array_equal(got["a"], tree["a"])
    assert got["bf"].dtype == np.dtype("bfloat16")
    np.testing.assert_array_equal(np.asarray(got["bf"], np.float32),
                                  np.asarray(tree["bf"], np.float32))
    assert got["none"] is None
    # NamedTuples come back as plain tuples (container survives, class
    # doesn't) — resume unflattens onto a typed template treedef
    assert got["nt"] == (pytest.approx(np.zeros(4)), None)
    assert got["nested"][1] == (pytest.approx(3.0), None)
    assert not p.with_name(p.name + ".tmp").exists()  # atomic: no tmp left


def test_checkpoint_save_is_atomic_over_existing(tmp_path):
    p = tmp_path / "ck.npz"
    checkpoint.save(str(p), {"v": np.asarray(1)})
    checkpoint.save(str(p), {"v": np.asarray(2)})    # overwrite, atomically
    got, _ = checkpoint.load(str(p))
    assert int(got["v"]) == 2


def test_checkpoint_load_errors_name_the_path(tmp_path):
    with pytest.raises(FileNotFoundError, match="no checkpoint"):
        checkpoint.load(str(tmp_path / "missing.npz"))
    bad = tmp_path / "garbage.npz"
    bad.write_bytes(b"this is not a zip archive")
    with pytest.raises(checkpoint.CheckpointError, match="garbage.npz"):
        checkpoint.load(str(bad))
    # truncated: a real checkpoint cut in half
    p = tmp_path / "trunc.npz"
    checkpoint.save(str(p), {"v": np.arange(1000)})
    p.write_bytes(p.read_bytes()[: p.stat().st_size // 2])
    with pytest.raises(checkpoint.CheckpointError, match="trunc.npz"):
        checkpoint.load(str(p))


# ------------------------------------------------- kill + resume ----------
class _Killed(Exception):
    pass


def _run_killed_then_resume(algo, ds, net, ck, kw, obs_cfg=None):
    """Run with ckpt, kill while draining the second segment (the first
    one's checkpoint has landed), then resume. Returns the resumed result
    and its Obs."""
    orig = engine_mod.SegmentEngine.drain
    calls = {"n": 0}

    def killer(self, *a, **k):
        if calls["n"] >= 1:
            raise _Killed()
        calls["n"] += 1
        return orig(self, *a, **k)

    obs = Obs(config=obs_cfg) if obs_cfg is not None else None
    engine_mod.SegmentEngine.drain = killer
    try:
        with pytest.raises(_Killed):
            run_experiment(algo, CFG, ds, net=net, ckpt=ck, obs=obs, **kw)
    finally:
        engine_mod.SegmentEngine.drain = orig
    assert checkpoint.load(ck)[1]["next_segment"] == 1
    obs2 = Obs(config=obs_cfg) if obs_cfg is not None else None
    got = run_experiment(algo, CFG, ds, net=net, ckpt=ck, obs=obs2, **kw)
    return got, obs2


@pytest.mark.parametrize("algo", ALL_ALGOS)
def test_kill_and_resume_bit_parity(algo, tiny_ds, tmp_path):
    """The headline resume contract: kill after segment 1, resume with the
    same call, and the run is indistinguishable from an uninterrupted one
    — metrics, CommLog, cluster history, per-node accuracy, obs frames,
    and the FINAL CARRY (params and all) down to the last bit."""
    kw = {**KW, "rounds": 4, "eval_every": 2}
    net = _faulted(FaultConfig(crash_rate=0.3, corrupt_rate=0.3))
    ocfg = ObsConfig()
    obs_ref = Obs(config=ocfg)
    ref_ck = str(tmp_path / f"{algo}-ref.npz")
    ref = run_experiment(algo, CFG, tiny_ds, net=net, ckpt=ref_ck,
                         obs=obs_ref, **kw)
    ck = str(tmp_path / f"{algo}.npz")
    got, obs_got = _run_killed_then_resume(algo, tiny_ds, net, ck, kw,
                                           obs_cfg=ocfg)
    _assert_runs_identical(ref, got)
    # final carries (params, PRNG, channel, gossip, crash chain) match
    # leaf-for-leaf across the interrupted and uninterrupted runs
    pr, _ = checkpoint.load(ref_ck)
    pg, _ = checkpoint.load(ck)
    for a, b in zip(jax.tree.leaves(pr["carry"]),
                    jax.tree.leaves(pg["carry"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # obs frame streams match
    fr, fg = obs_ref.frames_table(), obs_got.frames_table()
    assert set(fr) == set(fg)
    for k in fr:
        np.testing.assert_array_equal(np.asarray(fr[k]), np.asarray(fg[k]))


def test_resume_of_finished_run_is_a_noop_replay(tiny_ds, tmp_path):
    kw = {**KW, "rounds": 4, "eval_every": 2}
    ck = str(tmp_path / "done.npz")
    ref = run_experiment("el", CFG, tiny_ds, net=NET, ckpt=ck, **kw)
    again = run_experiment("el", CFG, tiny_ds, net=NET, ckpt=ck, **kw)
    _assert_runs_identical(ref, again)


def test_resume_refuses_foreign_checkpoint(tiny_ds, tmp_path):
    kw = {**KW, "rounds": 4, "eval_every": 2}
    ck = str(tmp_path / "ck.npz")
    run_experiment("el", CFG, tiny_ds, net=NET, ckpt=ck, **kw)
    with pytest.raises(ValueError, match="fingerprint"):
        run_experiment("el", CFG, tiny_ds, net=NET, ckpt=ck,
                       **{**kw, "seed": 1})


def test_ckpt_requires_engine(tiny_ds, tmp_path):
    with pytest.raises(ValueError, match="engine"):
        run_experiment("el", CFG, tiny_ds, net=NET, engine=False,
                       ckpt=str(tmp_path / "x.npz"), **KW)


# ------------------------------------------------- obs integration --------
def test_frames_carry_fault_counters(tiny_ds):
    obs = Obs(config=ObsConfig())
    # corrupt_rate=1.0: with only 4 nodes x 3 rounds, and churn + crashes
    # already benching half the fleet, a 0.5 coin can miss every live
    # sender for the whole run — rate 1 makes "some corrupted sender
    # existed" deterministic (any round with a live node)
    net = _faulted(FaultConfig(crash_rate=0.5, corrupt_rate=1.0,
                               corrupt_mode="nan"))
    run_experiment("el", CFG, tiny_ds, net=net, obs=obs, **KW)
    t = obs.frames_table()
    for f in ("crashed", "corrupted", "quarantined"):
        assert f in t
    assert np.asarray(t["crashed"]).sum() > 0
    assert np.asarray(t["corrupted"]).sum() > 0
    assert np.asarray(t["quarantined"]).sum() > 0
    # gated off: the fields exist but stay zero
    obs0 = Obs(config=ObsConfig(faults=False))
    run_experiment("el", CFG, tiny_ds, net=net, obs=obs0, **KW)
    t0 = obs0.frames_table()
    assert np.asarray(t0["crashed"]).sum() == 0
    assert np.asarray(t0["quarantined"]).sum() == 0


def test_robust_mix_keeps_params_finite_under_nan_storm(tiny_ds):
    """Run-level guard story: at 20% NaN corruption the unguarded mix
    poisons the model; the robust mix never lets a non-finite parameter
    through (the benchmark's headline, pinned at smoke scale)."""
    obs_r, obs_u = Obs(config=ObsConfig()), Obs(config=ObsConfig())
    base = FaultConfig(corrupt_rate=0.2, corrupt_mode="nan")
    run_experiment("dpsgd", CFG, tiny_ds, obs=obs_r,
                   net=_faulted(base), **KW)
    run_experiment("dpsgd", CFG, tiny_ds, obs=obs_u,
                   net=_faulted(dataclasses.replace(base, robust=False)),
                   **KW)
    assert np.isfinite(np.asarray(obs_r.frames_table()["param_norm"])).all()
    assert not np.isfinite(
        np.asarray(obs_u.frames_table()["param_norm"])).all()


# ------------------------------------------------- sweep resilience -------
def test_sweep_survives_failing_cell(tiny_ds):
    kw = dict(k=2, degree=2, local_steps=2, batch_size=4, lr=0.05,
              eval_every=2)
    cells = [
        SweepCell("ok", "el", CFG, tiny_ds, rounds=2, kwargs=dict(kw)),
        SweepCell("bad", "el", CFG, tiny_ds, rounds=2,
                  kwargs={**kw, "degree": 99}),
        SweepCell("ok2", "dpsgd", CFG, tiny_ds, rounds=2, kwargs=dict(kw)),
    ]
    obs = Obs()
    res = run_sweep(cells, seeds=[0], obs=obs)
    assert res.cell("bad").error is not None
    assert res.cell("ok").error is None and res.cell("ok2").error is None
    assert [e for e in obs.tracer.events
            if e.get("name") == "sweep.cell_failed"]
    j = res.to_json()
    assert j["cells"]["bad"]["error"] is not None
    assert j["cells"]["ok"]["error"] is None


def test_sweep_raises_only_when_all_cells_fail(tiny_ds):
    kw = dict(k=2, degree=99, local_steps=2, batch_size=4, lr=0.05,
              eval_every=2)
    cells = [SweepCell("bad1", "el", CFG, tiny_ds, rounds=2,
                       kwargs=dict(kw)),
             SweepCell("bad2", "dpsgd", CFG, tiny_ds, rounds=2,
                       kwargs=dict(kw))]
    with pytest.raises(RuntimeError, match="every sweep cell failed"):
        run_sweep(cells, seeds=[0])


def test_sweep_ckpt_dir_skips_completed_cells(tiny_ds, tmp_path):
    kw = dict(k=2, degree=2, local_steps=2, batch_size=4, lr=0.05,
              eval_every=2)
    cells = [SweepCell("c1", "el", CFG, tiny_ds, rounds=2,
                       kwargs=dict(kw))]
    ckd = tmp_path / "grid"
    res1 = run_sweep(cells, seeds=[0, 1], ckpt_dir=ckd)
    assert not res1.cell("c1").skipped
    assert (ckd / "c1.summary.json").exists()
    assert (ckd / "c1.manifest.json").exists()
    assert (ckd / "c1-s0.npz").exists()              # per-run checkpoints
    obs = Obs()
    res2 = run_sweep(cells, seeds=[0, 1], ckpt_dir=ckd, obs=obs)
    assert res2.cell("c1").skipped
    assert [e for e in obs.tracer.events
            if e.get("name") == "sweep.cell_skipped"]
    assert (json.loads(json.dumps(res1.cell("c1").summary, default=float))
            == json.loads(json.dumps(res2.cell("c1").summary,
                                     default=float)))
    # a different sweep axis (seeds) forks the fingerprint: no false skip
    res3 = run_sweep(cells, seeds=[5], ckpt_dir=ckd)
    assert not res3.cell("c1").skipped


def test_sweep_owns_ckpt_kwarg(tiny_ds, tmp_path):
    cells = [SweepCell("c", "el", CFG, tiny_ds, rounds=2,
                       kwargs={"ckpt": "x.npz"})]
    with pytest.raises(ValueError, match="ckpt"):
        run_sweep(cells, seeds=[0], ckpt_dir=tmp_path)
