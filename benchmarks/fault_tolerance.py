"""Fault-tolerance benchmark (repro.resil): the robust-aggregation payoff
table and the crash-churn byte/accuracy ledger, written to
``results/bench/BENCH_resil.json``.

Headline table: FACADE under on-device NaN corruption (a fraction of
senders publish poisoned models each round, ``corrupt_mode="nan"``) at
increasing rates, with the robust gossip guard (non-finite quarantine +
norm clipping, shared by every algorithm's ``gossip_mix``) switched on vs
off. The contract the resilience tests pin qualitatively, measured
quantitatively here: with the guard, fair accuracy stays near the
fault-free run even at 5-10% corruption; without it, one NaN sender
poisons the whole mixture within a round or two and the run collapses
(non-finite parameters or a >20% accuracy drop).

Second table: crash churn (``crash_rate`` Markov chain, rejoin-stale
restarts). Crashed nodes publish nothing and never gate the simulated
round clock, so total traffic drops roughly with the stationary downtime
while accuracy degrades gracefully — the byte-honesty contract.

The module also hosts the resilience smokes for the dry-run matrix:
:func:`smoke` (fault off-switch bit-parity + a guarded NaN-storm run) and
:func:`smoke_resume` (save -> kill mid-run -> resume bit-parity via the
crash-safe checkpoint path).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.netsim import NetworkConfig
from repro.resil import FaultConfig

from . import common

# corruption rates for the headline table; 0.0 is the fault-free anchor
RATES = (0.05, 0.1)


def _fair(res) -> float:
    return res.best_fair_acc()


def _finite(res) -> bool:
    return bool(np.all(np.isfinite(np.asarray(res.final_acc, float)))
                and np.isfinite(_fair(res)))


def run(quick: bool = True) -> dict:
    cluster_cfgs, rounds, spec, cfg = common.scaled(quick)
    sizes = cluster_cfgs[1]                      # the imbalanced 6:2 config
    ds = common.make_ds(spec, sizes, ("rot0", "rot180"))
    rounds = min(rounds, 32) if quick else rounds
    base = NetworkConfig.preset("ideal")         # isolate faults from churn

    def go(fcfg):
        net = dataclasses.replace(base, faults=fcfg)
        return common.run_algo("facade", cfg, ds, rounds, quick, net=net)

    clean = go(None)
    rows = [["0.00", "-", f"{_fair(clean):.3f}",
             f"{min(clean.final_acc):.3f}", "yes"]]
    payload = {"clean": {"fair_acc": _fair(clean),
                         "worst_cluster": float(min(clean.final_acc)),
                         "total_bytes": clean.comm.bytes[-1]}}

    # --- NaN corruption x robust guard on/off -----------------------------
    collapse_ok = within_ok = True
    for rate in RATES:
        for robust in (True, False):
            res = go(FaultConfig(corrupt_rate=rate, corrupt_mode="nan",
                                 robust=robust))
            fair, finite = _fair(res), _finite(res)
            rows.append([f"{rate:.2f}", "on" if robust else "off",
                         f"{fair:.3f}" if finite else "nan",
                         f"{min(res.final_acc):.3f}" if finite else "nan",
                         "yes" if finite else "NO"])
            payload[f"corrupt{rate}-{'robust' if robust else 'unguarded'}"] = {
                "fair_acc": fair, "finite": finite,
                "worst_cluster": float(min(res.final_acc)),
                "total_bytes": res.comm.bytes[-1]}
            if robust:
                # guard keeps the run within a few points of fault-free
                within_ok &= finite and fair >= _fair(clean) - 0.05
            else:
                # unguarded: non-finite params or a >20% fair-acc drop
                collapse_ok &= ((not finite)
                                or fair <= _fair(clean) - 0.20)
    print(common.table(
        ["corrupt", "guard", "fair_acc", "worst_cluster", "finite"], rows))

    # --- crash churn: bytes drop with downtime, accuracy degrades
    # --- gracefully (crashed senders cost 0 bytes, never gate the clock)
    crash_rows = [["0.00", f"{_fair(clean):.3f}",
                   f"{clean.comm.bytes[-1]/1e6:.1f} MB"]]
    crash_ok = True
    for crate in ((0.25,) if quick else (0.1, 0.25)):
        res = go(FaultConfig(crash_rate=crate, restart_rate=0.5,
                             restart_mode="rejoin-stale"))
        crash_rows.append([f"{crate:.2f}", f"{_fair(res):.3f}",
                           f"{res.comm.bytes[-1]/1e6:.1f} MB"])
        payload[f"crash{crate}"] = {
            "fair_acc": _fair(res), "finite": _finite(res),
            "total_bytes": res.comm.bytes[-1]}
        crash_ok &= (_finite(res)
                     and res.comm.bytes[-1] < clean.comm.bytes[-1])
    print("\ncrash churn (rejoin-stale restarts):")
    print(common.table(["crash_rate", "fair_acc", "traffic"], crash_rows))

    payload["headline"] = {"robust_within_5pts": within_ok,
                           "unguarded_collapsed": collapse_ok,
                           "crash_bytes_drop": crash_ok}
    verdict = "PASS" if (within_ok and collapse_ok and crash_ok) else "FAIL"
    print(f"\nresilience contract: robust-within-5pts={within_ok} "
          f"unguarded-collapsed={collapse_ok} crash-bytes-drop={crash_ok} "
          f"-> {verdict}")
    common.write_bench("resil", payload)
    return payload


def _tiny():
    from repro.configs.facade_paper import lenet
    from repro.data.synthetic import SynthSpec

    spec = SynthSpec(n_classes=4, image_size=16, samples_per_class=8,
                     test_per_class=8, seed=3)
    ds = common.make_ds(spec, (3, 1), ("rot0", "rot180"))
    cfg = lenet(smoke=True).replace(n_classes=4)
    return cfg, ds


def smoke() -> dict:
    """Resilience smoke for the dry-run matrix: (a) a zero-rate
    ``FaultConfig`` is bit-for-bit the no-faults run (the off-switch
    contract), (b) a guarded crash+NaN storm stays finite and sheds bytes
    (crashed senders cost 0). Cheap enough to run on every invocation."""
    cfg, ds = _tiny()
    net = NetworkConfig.preset("edge-churn")
    kw = dict(local_steps=2, batch_size=4, eval_every=1)
    plain = common.run_algo("facade", cfg, ds, 2, True, net=net, **kw)
    off = common.run_algo(
        "facade", cfg, ds, 2, True,
        net=dataclasses.replace(net, faults=FaultConfig()), **kw)
    parity = (list(plain.final_acc) == list(off.final_acc)
              and np.array_equal(plain.comm.bytes, off.comm.bytes)
              and np.array_equal(plain.comm.seconds, off.comm.seconds))
    # the storm runs on "ideal" so the byte comparison has signal — on
    # edge-churn a 2-round window can legitimately deliver 0 edges
    ideal = NetworkConfig.preset("ideal")
    clean = common.run_algo("facade", cfg, ds, 2, True, net=ideal, **kw)
    storm = common.run_algo(
        "facade", cfg, ds, 2, True,
        net=dataclasses.replace(ideal, faults=FaultConfig(
            crash_rate=0.5, restart_rate=0.5,
            corrupt_rate=0.5, corrupt_mode="nan")), **kw)
    finite = bool(np.all(np.isfinite(np.asarray(storm.final_acc, float))))
    shed = 0 < storm.comm.bytes[-1] < clean.comm.bytes[-1]
    ok = parity and finite and shed
    return {"status": "ok" if ok else "fail",
            "off_switch_parity": bool(parity),
            "storm_finite": finite,
            "storm_bytes": float(storm.comm.bytes[-1]),
            "plain_bytes": float(clean.comm.bytes[-1])}


def smoke_resume() -> dict:
    """Checkpoint/resume smoke for the dry-run matrix: run with
    ``ckpt=``, kill the driver after the first fused segment, resume from
    the on-disk checkpoint, and demand bit-parity with an uninterrupted
    reference — metrics AND the final saved carry, leaf for leaf."""
    import tempfile

    import jax

    from repro import checkpoint
    from repro.core import engine as engine_mod
    from repro.core.runner import run_experiment

    cfg, ds = _tiny()
    net = dataclasses.replace(
        NetworkConfig.preset("edge-churn"),
        faults=FaultConfig(crash_rate=0.3, corrupt_rate=0.3))
    kw = dict(rounds=4, k=2, degree=2, local_steps=2, batch_size=4,
              lr=0.05, eval_every=2, seed=0, net=net)
    tmp = tempfile.mkdtemp(prefix="resil-smoke-")
    ref_ck, ck = f"{tmp}/ref.npz", f"{tmp}/killed.npz"
    ref = run_experiment("facade", cfg, ds, ckpt=ref_ck, **kw)

    class _Killed(Exception):
        pass

    # die draining the second segment: the first one's checkpoint has
    # landed by then
    orig = engine_mod.SegmentEngine.drain
    calls = {"n": 0}

    def killer(self, *a, **k):
        if calls["n"] >= 1:
            raise _Killed()
        calls["n"] += 1
        return orig(self, *a, **k)

    engine_mod.SegmentEngine.drain = killer
    try:
        run_experiment("facade", cfg, ds, ckpt=ck, **kw)
        killed = False                      # killer never fired: bad plan
    except _Killed:
        killed = True
    finally:
        engine_mod.SegmentEngine.drain = orig
    got = run_experiment("facade", cfg, ds, ckpt=ck, **kw)

    metrics = (list(ref.final_acc) == list(got.final_acc)
               and np.array_equal(ref.comm.bytes, got.comm.bytes)
               and np.array_equal(ref.comm.seconds, got.comm.seconds)
               and ref.fair_acc == got.fair_acc)
    pr, _ = checkpoint.load(ref_ck)
    pg, _ = checkpoint.load(ck)
    carry = all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(jax.tree.leaves(pr["carry"]),
                                jax.tree.leaves(pg["carry"])))
    ok = killed and metrics and carry
    return {"status": "ok" if ok else "fail",
            "killed_mid_run": killed,
            "metrics_parity": bool(metrics),
            "carry_parity": bool(carry),
            "fair_acc": float(got.best_fair_acc())}


if __name__ == "__main__":
    run()
