"""Systems benchmark: render the §Dry-run / §Roofline tables from the
records produced by ``python -m repro.launch.dryrun`` (results/dryrun/).

Does not recompute anything — the 512-device lowering runs in its own
process (device-count pinning); this module aggregates and validates.
"""
from __future__ import annotations

import json
import pathlib

from . import common

DRYRUN_DIR = pathlib.Path(__file__).resolve().parent.parent / "results" / "dryrun"

HBM_PER_CHIP_GB = 16.0  # TPU v5e


def load(pattern: str):
    recs = []
    for f in sorted(DRYRUN_DIR.glob(pattern)):
        with f.open() as fh:
            recs += [json.loads(l) for l in fh if l.strip()]
    # newest record wins per (arch, shape, mesh, tag)
    dedup = {}
    for r in recs:
        dedup[(r["arch"], r["shape"], r["mesh"], r.get("tag", ""))] = r
    return list(dedup.values())


def run(quick: bool = True) -> dict:
    # tiny netsim config exercised on every invocation (churn_resilience
    # smoke: 4-node FACADE under edge-churn) so the netsim path can't rot;
    # a smoke failure is reported in the payload, never aborts the table
    from . import churn_resilience
    try:
        net_rec = churn_resilience.smoke()
    except Exception as e:
        net_rec = {"status": "fail", "preset": "edge-churn", "error": repr(e)}
        print(f"netsim smoke [edge-churn]: FAIL ({e!r})")
    else:
        s2t = net_rec["seconds_to_target"]
        print(f"netsim smoke [{net_rec['preset']}]: {net_rec['status']} "
              f"({net_rec['sim_seconds']:.2f} sim-s, "
              f"{net_rec['total_bytes']/1e3:.1f} KB); SLO: "
              + (f"{s2t:.2f} sim-s to acc 0.1" if s2t is not None
                 else "target acc 0.1 not reached"))

    # netsim-v2 smoke: bursty + core/edge tiers + async stale gossip in one
    # preset, plus channel statistics; reported, never aborts the table
    try:
        v2_rec = churn_resilience.smoke_v2()
    except Exception as e:
        v2_rec = {"status": "fail", "preset": "edge-v2", "error": repr(e)}
        print(f"netsim-v2 smoke [edge-v2]: FAIL ({e!r})")
    else:
        print(f"netsim-v2 smoke [{v2_rec['preset']}]: {v2_rec['status']} "
              f"({v2_rec['total_bytes']/1e3:.1f} KB async vs "
              f"{v2_rec['sync_bytes']/1e3:.1f} KB sync, "
              f"bad-rate {v2_rec['channel_bad_rate']:.2f})")

    # segment-engine smoke: one fused span, parity-checked vs the legacy
    # driver (keeps the scan path from rotting); reported, never aborts
    try:
        from . import round_throughput
        eng_rec = round_throughput.smoke()
    except Exception as e:
        eng_rec = {"status": "fail", "error": repr(e)}
        print(f"engine smoke: FAIL ({e!r})")
    else:
        print(f"engine smoke: {eng_rec['status']} "
              f"({eng_rec['total_bytes']/1e3:.1f} KB)")

    # sweep smoke: 2-seed x 2-algorithm grid on one shared EngineCache —
    # asserts zero recompiles after the first run of each cell
    try:
        from . import seed_sweep
        sweep_rec = seed_sweep.smoke()
    except Exception as e:
        sweep_rec = {"status": "fail", "error": repr(e)}
        print(f"sweep smoke: FAIL ({e!r})")
    else:
        print(f"sweep smoke: {sweep_rec['status']} "
              f"({sweep_rec['compiles_after_first']} compiles, "
              f"{sweep_rec['recompiles']} recompiles after first run)")

    # adaptive-topology smoke: uniform-policy bit-parity + one adaptive
    # run + the sampler's fairness floor (repro.topo); reported, never
    # aborts the table
    try:
        from . import topo_adapt
        topo_rec = topo_adapt.smoke()
    except Exception as e:
        topo_rec = {"status": "fail", "error": repr(e)}
        print(f"topo smoke: FAIL ({e!r})")
    else:
        print(f"topo smoke [{topo_rec['preset']}]: {topo_rec['status']} "
              f"(uniform parity {topo_rec['uniform_parity']}, adaptive "
              f"{topo_rec['adaptive_bytes']/1e3:.1f} KB vs uniform "
              f"{topo_rec['uniform_bytes']/1e3:.1f} KB, min inclusion "
              f"{topo_rec['min_inclusion_freq']:.2f})")

    # obs smoke: full telemetry attached to a tiny run — trajectory
    # parity, finite round-complete frames, JSONL round-trip; reported,
    # never aborts the table
    try:
        from . import obs_overhead
        obs_rec = obs_overhead.smoke()
    except Exception as e:
        obs_rec = {"status": "fail", "error": repr(e)}
        print(f"obs smoke: FAIL ({e!r})")
    else:
        print(f"obs smoke: {obs_rec['status']} "
              f"({obs_rec['frames']} frames, "
              f"{obs_rec['jsonl_records']} JSONL records, spans "
              f"{obs_rec['spans']})")

    # health+report smoke: an unguarded NaN-corruption run must be
    # flagged (fail verdict + health.* events) while a clean run stays
    # quiet, and the report CLI must render from the real manifest +
    # JSONL; reported, never aborts the table
    try:
        from . import obs_overhead as obs_bench
        health_rec = obs_bench.smoke_health()
    except Exception as e:
        health_rec = {"status": "fail", "error": repr(e)}
        print(f"health smoke: FAIL ({e!r})")
    else:
        print(f"health smoke: {health_rec['status']} "
              f"(clean={health_rec['clean_verdict']}, "
              f"storm={health_rec['storm_verdict']} via "
              f"{health_rec['storm_events']}, report rendered "
              f"{health_rec['report_rendered']})")

    # resil smoke: fault off-switch bit-parity + a guarded crash/NaN
    # storm staying finite while shedding bytes; reported, never aborts
    try:
        from . import fault_tolerance
        resil_rec = fault_tolerance.smoke()
    except Exception as e:
        resil_rec = {"status": "fail", "error": repr(e)}
        print(f"resil smoke: FAIL ({e!r})")
    else:
        print(f"resil smoke: {resil_rec['status']} "
              f"(off-switch parity {resil_rec['off_switch_parity']}, "
              f"storm finite {resil_rec['storm_finite']}, "
              f"{resil_rec['storm_bytes']/1e3:.1f} KB under faults vs "
              f"{resil_rec['plain_bytes']/1e3:.1f} KB clean)")

    # checkpoint smoke: save -> kill mid-run -> resume, bit-parity with an
    # uninterrupted run (metrics and final carry); reported, never aborts
    try:
        from . import fault_tolerance
        ckpt_rec = fault_tolerance.smoke_resume()
    except Exception as e:
        ckpt_rec = {"status": "fail", "error": repr(e)}
        print(f"ckpt smoke: FAIL ({e!r})")
    else:
        print(f"ckpt smoke: {ckpt_rec['status']} "
              f"(killed {ckpt_rec['killed_mid_run']}, metrics parity "
              f"{ckpt_rec['metrics_parity']}, carry parity "
              f"{ckpt_rec['carry_parity']})")

    # persist-dir smoke: a run over EngineCache(persist_dir=...) must stay
    # bit-for-bit a plain run AND leave serialized executables on disk
    try:
        from . import warm_start
        warm_rec = warm_start.smoke()
    except Exception as e:
        warm_rec = {"status": "fail", "error": repr(e)}
        print(f"persist smoke: FAIL ({e!r})")
    else:
        print(f"persist smoke: {warm_rec['status']} "
              f"({warm_rec['persisted_files']} files persisted)")

    # sharded-engine smoke: 8-node FACADE on a forced 8-device node mesh
    # (own subprocess: the device-count flag must precede jax init) —
    # bytes bit-parity + tolerance-pinned accuracy vs the unsharded run
    try:
        from . import scale_curve
        shard_rec = scale_curve.smoke()
    except Exception as e:
        shard_rec = {"status": "fail", "error": repr(e)}
        print(f"shard smoke: FAIL ({e!r})")
    else:
        print(f"shard smoke: {shard_rec['status']} "
              f"({shard_rec['n_devices']} devices, bytes parity "
              f"{shard_rec['bytes_parity']}, acc maxdiff "
              f"{shard_rec['acc_maxdiff']:.4f})")

    recs = [r for r in load("dryrun_*.jsonl") if r.get("tag", "") == ""]
    if not recs:
        print("no dry-run records; run `python -m repro.launch.dryrun --all` "
              "(and --multi-pod) first")
        return {"netsim_smoke": net_rec, "netsim_v2_smoke": v2_rec,
                "engine_smoke": eng_rec, "sweep_smoke": sweep_rec,
                "topo_smoke": topo_rec, "obs_smoke": obs_rec,
                "health_smoke": health_rec,
                "resil_smoke": resil_rec, "ckpt_smoke": ckpt_rec,
                "persist_smoke": warm_rec, "shard_smoke": shard_rec}
    rows = []
    ok = fail = skip = 0
    for r in sorted(recs, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        if r["status"] == "skipped":
            skip += 1
            continue
        if r["status"] == "fail":
            fail += 1
            rows.append([r["arch"], r["shape"], r["mesh"], "FAIL",
                         "", "", "", ""])
            continue
        ok += 1
        fits = "Y" if r["peak_gbytes_per_dev"] <= HBM_PER_CHIP_GB else "over"
        rows.append([
            r["arch"], r["shape"], r["mesh"],
            f"{r['peak_gbytes_per_dev']:.1f}GB/{fits}",
            f"{r['t_compute_s']:.3f}", f"{r['t_memory_s']:.3f}",
            f"{r['t_collective_s']:.3f}", r["dominant"]])
    print(common.table(
        ["arch", "shape", "mesh", "peak/fits", "t_comp", "t_mem",
         "t_coll", "dominant"], rows))
    print(f"\n{ok} compiled, {fail} failed, {skip} skipped "
          f"(full-attention long_500k carve-outs)")
    payload = {"n_ok": ok, "n_fail": fail, "n_skip": skip, "records": recs,
               "netsim_smoke": net_rec, "netsim_v2_smoke": v2_rec,
               "engine_smoke": eng_rec, "sweep_smoke": sweep_rec,
               "topo_smoke": topo_rec, "obs_smoke": obs_rec,
               "health_smoke": health_rec,
               "resil_smoke": resil_rec, "ckpt_smoke": ckpt_rec,
               "persist_smoke": warm_rec, "shard_smoke": shard_rec}
    common.save("dryrun_matrix", payload)
    return payload


if __name__ == "__main__":
    run()
