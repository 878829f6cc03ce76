"""Chip smoke: FACADE's main path, end to end, on a TPU.

    python chip_smoke.py              # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4    # four chips: the sharded phase only

Drives :func:`repro.core.runner.run_experiment` exactly as a user does
(the scan-fused segment engine, defaults otherwise) at the paper's full
model widths, in ONE process (a chip belongs to one process at a time):

(a) FACADE on GN-LeNet (32x32, width 32, 10 classes), 32 nodes split
    30:2, 80 rounds in two segments — twice, through one ``EngineCache``;
(b) Epidemic Learning in the same shape (the baseline path);
(c) FACADE on ResNet8 (64x64, width 32, 41 classes), 32 nodes, 40 rounds.

``--chips 4`` runs FACADE on GN-LeNet with 1,024 nodes split 960:64 on a
``mesh=(4,)`` node mesh and the same seed unsharded on one chip of the
four, and compares them by the sharded engine's contract: bytes and
simulated seconds exactly, accuracies within 0.1.

Every phase fails the script (non-zero exit, nothing caught) on a
non-finite or out-of-range accuracy, a non-finite per-round byte count,
cumulative bytes off ``rounds x`` the nominal per-round count (computed
here from parameter shapes), a compile after the first segment of each
segment-program variant or on a warm pass, or passes that disagree.
Timings are host clock around ``run_experiment``, whose results are host
values drained from the device, so they include all device work.

The last line of stdout is one JSON object naming the device; there is
no CPU fallback: without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

import jax
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
DEGREE = 4          # run_experiment's default gossip degree
ACC_TOL = 0.1       # multi-device accuracy tolerance (tests/test_mesh.py)
EVAL_BATCH_1024 = 8     # 960 vmapped nodes x 8 images keep the
#                         evaluator's activations within one chip


def expect(cond, msg: str) -> None:
    """A smoke check: raise (never caught here) when ``cond`` is false."""
    if not cond:
        raise AssertionError(msg)


def nominal_round_bytes(algo: str, cfg, n: int, degree: int) -> float:
    """Bytes one round sends on an ideal medium (``net=None``): ``n *
    degree`` pushes of the payload — the whole model, plus FACADE's int32
    cluster id (core + the node's own head + id). Computed from parameter
    SHAPES only, then rounded to float32 as the device reports it."""
    from repro.models import cnn

    shapes = jax.eval_shape(lambda key: cnn.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    payload = sum(math.prod(l.shape) * l.dtype.itemsize
                  for l in jax.tree.leaves(shapes))
    if algo == "facade":
        payload += 4
    return float(np.float32(n * degree * payload))


def check_run(res, *, rounds: int, per_round: float, where: str) -> None:
    """Finite accuracies in [0, 1], finite per-round bytes, and cumulative
    bytes at every eval == eval round x the nominal per-round count."""
    for rnd, accs in res.acc_per_cluster:
        a = np.asarray(accs, np.float64)
        expect(np.isfinite(a).all() and (a >= 0).all() and (a <= 1).all(),
               f"{where}: accuracy {accs} at round {rnd}")
    for rnd, fair in res.fair_acc:
        expect(np.isfinite(fair) and 0 <= fair <= 1,
               f"{where}: fair accuracy {fair} at round {rnd}")
    cum = np.asarray(res.comm.bytes, np.float64)
    expect(np.isfinite(np.diff(cum, prepend=0.0)).all(),
           f"{where}: non-finite per-round bytes")
    expect(res.comm.rounds[-1] == rounds,
           f"{where}: ran {res.comm.rounds[-1]} of {rounds} rounds")
    for i, (rnd, evaled) in enumerate(zip(res.comm.rounds,
                                          res.comm.evaled)):
        if evaled:
            expect(cum[i] == rnd * per_round,
                   f"{where}: {cum[i]} bytes after round {rnd}, expected "
                   f"{rnd} x {per_round}")


def same_run(a, b, where: str) -> None:
    """Two runs of one seed agree: accuracies, bytes, seconds, heads."""
    expect(a.acc_per_cluster == b.acc_per_cluster,
           f"{where}: accuracies differ")
    expect(a.comm.bytes == b.comm.bytes, f"{where}: bytes differ")
    expect(a.comm.seconds == b.comm.seconds, f"{where}: seconds differ")
    expect(len(a.cluster_history) == len(b.cluster_history)
           and all(np.array_equal(x, y) for (_, x), (_, y)
                   in zip(a.cluster_history, b.cluster_history)),
           f"{where}: head choices differ")


def peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def run_phase(name: str, algo: str, cfg, ds, *, rounds: int,
              eval_every: int) -> dict:
    """Two passes of one run through one ``EngineCache``: a cold pass
    (compiles) and a warm pass that must compile nothing and agree with
    the cold pass exactly. Raises on any failed check; returns the
    readings."""
    from repro.core.cache import EngineCache
    from repro.core.engine import segment_plan
    from repro.core.runner import run_experiment

    per_round = nominal_round_bytes(algo, cfg, ds.n_nodes, DEGREE)
    variants = {(s.length, s.warmup)
                for s in segment_plan(rounds, eval_every)}
    cache = EngineCache()

    c0, e0 = cache.compile_count, cache.evaluator_builds
    t0 = time.perf_counter()
    cold = run_experiment(algo, cfg, ds, rounds=rounds,
                          eval_every=eval_every, cache=cache)
    cold_s = time.perf_counter() - t0
    segs = (cache.compile_count - c0) - (cache.evaluator_builds - e0)
    expect(segs == len(variants),
           f"{name}: {segs} segment compiles for {len(variants)} "
           "(length, warmup) variants")
    check_run(cold, rounds=rounds, per_round=per_round, where=f"{name} cold")

    c1 = cache.compile_count
    t0 = time.perf_counter()
    warm = run_experiment(algo, cfg, ds, rounds=rounds,
                          eval_every=eval_every, cache=cache)
    steady_s = time.perf_counter() - t0
    expect(cache.compile_count == c1,
           f"{name}: warm pass compiled {cache.compile_count - c1} programs")
    check_run(warm, rounds=rounds, per_round=per_round, where=f"{name} warm")
    same_run(cold, warm, f"{name} cold vs warm")

    return {"phase": name, "algo": algo, "model": cfg.name,
            "nodes": ds.n_nodes, "rounds": rounds,
            "cold_s": cold_s, "steady_s": steady_s,
            "compile_s": cold_s - steady_s,
            "final_acc": [float(a) for a in warm.final_acc],
            "fair_acc": float(warm.fair_acc[-1][1]),
            "round_bytes": per_round,
            "peak_bytes_in_use": peak_bytes()}


def one_chip_phases():
    """Phases (a), (b), (c), yielded as each one passes."""
    from repro.configs.facade_paper import resnet8
    from repro.data.synthetic import SynthSpec, make_clustered_data

    from benchmarks import common

    _, _, spec, cfg = common.scaled(quick=False)
    ds = make_clustered_data(spec, (30, 2), ("rot0", "rot180"))
    yield run_phase("a", "facade", cfg, ds, rounds=80, eval_every=40)
    yield run_phase("b", "el", cfg, ds, rounds=80, eval_every=40)
    cfg8 = resnet8()
    spec8 = SynthSpec(n_classes=cfg8.n_classes, image_size=cfg8.image_size,
                      samples_per_class=4, test_per_class=16, seed=3)
    ds8 = make_clustered_data(spec8, (30, 2), ("rot0", "rot180"))
    yield run_phase("c", "facade", cfg8, ds8, rounds=40, eval_every=20)


def mesh_phases(chips: int):
    """The sharded phase at full width: GN-LeNet, 1,024 nodes split
    960:64, one 40-round segment."""
    from repro.configs.facade_paper import lenet
    from repro.data.synthetic import SynthSpec, make_clustered_data

    # 40 training images per node: the one-chip reference's segment
    # program then fits one chip's memory (scripts/rehearse_chip.py)
    spec = SynthSpec(n_classes=10, image_size=32, samples_per_class=4,
                     test_per_class=64, seed=3)
    ds = make_clustered_data(spec, (960, 64), ("rot0", "rot180"))
    yield mesh_phase(chips, lenet(), ds, rounds=40,
                     eval_batch=EVAL_BATCH_1024)


def placement(tree) -> list[str]:
    """One line per distinct (shape, sharding) among a pytree's leaves."""
    seen = {}
    for leaf in jax.tree.leaves(tree):
        sh = leaf.sharding
        key = (tuple(leaf.shape), str(getattr(sh, "spec", sh)),
               len(sh.device_set))
        seen[key] = seen.get(key, 0) + 1
    return [f"{n}x {shape} {spec} on {d} device(s)"
            for (shape, spec, d), n in sorted(seen.items())]


def mesh_phase(chips: int, cfg, ds, *, rounds: int, eval_batch: int) -> dict:
    """FACADE for one segment of ``rounds``: ``mesh=(chips,)`` vs the same
    seed unsharded on one chip, compared by the sharded engine's contract
    (bytes and simulated seconds exactly, accuracies within ``ACC_TOL``);
    then where the carry, the evaluator's inputs and its output live."""
    from repro.core.cache import EngineCache, EngineSpec
    from repro.core.runner import run_experiment

    kw = dict(rounds=rounds, eval_every=rounds, eval_batch=eval_batch)
    per_round = nominal_round_bytes("facade", cfg, ds.n_nodes, DEGREE)

    cache = EngineCache()
    t0 = time.perf_counter()
    got = run_experiment("facade", cfg, ds, mesh=(chips,), cache=cache, **kw)
    sharded_s = time.perf_counter() - t0
    check_run(got, rounds=rounds, per_round=per_round, where="sharded")
    t0 = time.perf_counter()
    ref = run_experiment("facade", cfg, ds, cache=cache, **kw)
    single_s = time.perf_counter() - t0
    check_run(ref, rounds=rounds, per_round=per_round, where="one chip")

    expect(ref.comm.bytes == got.comm.bytes, "sharded: bytes differ")
    expect(ref.comm.seconds == got.comm.seconds, "sharded: seconds differ")
    ra = np.array([a for _, accs in ref.acc_per_cluster for a in accs])
    ga = np.array([a for _, accs in got.acc_per_cluster for a in accs])
    diff = float(np.abs(ra - ga).max())
    expect(diff <= ACC_TOL, f"sharded: accuracy off by {diff} > {ACC_TOL}")

    # where the run's state and its evaluation live: the carry as the
    # engine places it, the evaluator's inputs and its output
    espec = EngineSpec(algo="facade", cfg=cfg, n=ds.n_nodes, k=ds.k,
                       degree=DEGREE, local_steps=10, batch_size=8,
                       lr=0.05, eval_batch=eval_batch, mesh=(chips,))
    expect(espec in cache, "sharded: EngineSpec mirror missed the entry")
    entry = cache.entry(espec)
    k_init, k_data = jax.random.split(jax.random.PRNGKey(0))
    setup = entry.setup(k_init)
    carry = entry.engine.init_carry(setup.state, k_data)
    builds = cache.evaluator_builds
    ev = cache.evaluator(entry.binding, ds, batch=eval_batch)
    expect(cache.evaluator_builds == builds, "sharded: evaluator rebuilt")
    pairs = ev.inputs(setup.models_of(carry.state))
    evals = []
    for cid, (models_c, xb) in zip(ev.cluster_ids, pairs):
        pred = ev.predict(models_c, xb)
        evals.append({"cluster": cid, "models": placement(models_c),
                      "batches": placement(xb),
                      "predictions": placement(pred)})
    return {"phase": "mesh", "chips": chips, "nodes": ds.n_nodes,
            "rounds": rounds, "sharded_s": sharded_s, "one_chip_s": single_s,
            "acc_maxdiff": diff, "bytes_exact": True, "seconds_exact": True,
            "final_acc_sharded": [float(a) for a in got.final_acc],
            "final_acc_one_chip": [float(a) for a in ref.final_acc],
            "carry": placement(carry), "evaluator": evals,
            "peak_bytes_in_use": peak_bytes()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase on four chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke needs a TPU; JAX sees {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips; "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.core.cache import use_compile_cache

    cache_dir = pathlib.Path(use_compile_cache())
    entries = len(list(cache_dir.iterdir())) if cache_dir.is_dir() else 0
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    print(f"compile cache: {cache_dir} ({entries} entries before this run)")
    phases = (one_chip_phases() if args.chips == 1
              else mesh_phases(args.chips))
    for rec in phases:
        print(f"phase {rec['phase']}: {json.dumps(rec)}", flush=True)
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
