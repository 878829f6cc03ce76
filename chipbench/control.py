"""Readings that the correctness limits are set from, on the chip.

    python chipbench/control.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--faults half_batch:7,8,9]

For each of ``--seeds`` it makes what a benchmark run with that seed makes
(the data, and the experiment the run would check), runs that experiment
through ``run_experiment`` as the window does, and compares its first
segment with the reference: the program's readings, whose largest is a
limit's lower reading. For each of ``--control-seeds`` it puts the
reference computed in bfloat16 in the program's place (the control), and
for each ``--faults name:seeds`` the reference with that fault planted;
both are compared the same way, and their least readings are the upper
ones. Every reading is one JSON line on stdout; the benchmark's own runs
never run this. It needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def program_reading(cell, seed: int, cache=None) -> dict:
    """What a run with ``seed`` would compare, without the window."""
    import jax

    from chipbench import correct, reference, run, spec, synth
    from repro.core.runner import run_experiment

    seeds = run.Seeds(seed)
    t0 = time.perf_counter()
    ds = synth.make_dataset(cell, seeds.data)
    cache = cache if cache is not None else run.tapped_cache()
    kw = run.experiment_kwargs(cell, cache)
    t1 = time.perf_counter()
    res = run_experiment(cell["algo"], spec.cnn_config(cell["model"]), ds,
                         rounds=cell["rounds_per_run"],
                         seed=seeds.experiment(seeds.checked), **kw)
    seg = run.checked_segment(cell, res, cache.tap)
    cache.tap.armed = True
    t2 = time.perf_counter()
    numbers = correct.compare(reference.setup(cell), ds,
                              seed=seeds.experiment(seeds.checked),
                              select_margin=cell["select_margin"], **seg)
    jax.effects_barrier()
    return {"kind": "program", "seed": seed, **numbers,
            "data_s": t1 - t0, "experiment_s": t2 - t1,
            "reference_s": time.perf_counter() - t2}


def stand_in_reading(cell, seed: int, *, dtype: str = "float32",
                     fault: str | None = None) -> dict:
    """The reference in the program's place, in ``dtype`` and with
    ``fault``, compared with the clean reference as a run is."""
    from chipbench import correct, reference, run, synth

    seeds = run.Seeds(seed)
    ds = synth.make_dataset(cell, seeds.data)
    exp = seeds.experiment(seeds.checked)
    r, final = cell["eval_every"], cell["eval_every"] == cell["rounds_per_run"]
    t0 = time.perf_counter()
    s = reference.setup(cell, dtype=dtype, fault=fault)
    got = reference.run(s, exp, ds.train_x, ds.train_y, r, final=final)
    preds = reference.predictions(s, got.models, ds.node_cluster, ds.test_x)
    accs = [float((p == y[None]).mean()) for p, y in zip(preds, ds.test_y)]
    t1 = time.perf_counter()
    numbers = correct.compare(
        reference.setup(cell), ds, seed=exp, rounds=r, final=final,
        cids=got.cids, models=_f32(run.host_tree(got.models)), preds=preds,
        accs=accs,
        cum_bytes=r * reference.round_bytes(cell["model"], s.n, s.degree),
        select_margin=cell["select_margin"])
    return {"kind": fault or f"control-{dtype}", "seed": seed, **numbers,
            "stand_in_s": t1 - t0,
            "reference_s": time.perf_counter() - t1}


def _summary(rec: dict) -> dict:
    """A reading as one JSON line: the per-choice excess losses become
    their quantiles and the share of them above a few margins."""
    import numpy as np

    ex = np.asarray(rec.pop("excess"), np.float64)
    rec["excess_q50_q90_q99"] = [float(np.quantile(ex, q))
                                 for q in (0.5, 0.9, 0.99)]
    rec["miss_at"] = {m: float((ex > m).mean())
                      for m in (0.003, 0.01, 0.03, 0.1, 0.3)}
    return rec


def _f32(tree):
    import jax
    import numpy as np

    return jax.tree.map(lambda l: np.asarray(l, np.float32), tree)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--faults", action="append", default=[],
                    help="name:seed,seed,...")
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    from chipbench import run, spec
    from repro.core.cache import use_compile_cache

    cell = spec.workload(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("control readings need the cell's chips", file=sys.stderr)
        return run.NO_DEVICE
    use_compile_cache()
    def emit(rec):
        print(json.dumps({**_summary(rec), "workload": args.workload}),
              flush=True)

    cache = run.tapped_cache()
    for seed in args.seeds:
        emit(program_reading(cell, seed, cache))
    for seed in args.control_seeds:
        emit(stand_in_reading(cell, seed, dtype="bfloat16"))
    for spec_ in args.faults:
        name, seeds = spec_.split(":")
        for seed in _seeds(seeds):
            emit(stand_in_reading(cell, seed, fault=name))
    return 0


if __name__ == "__main__":
    sys.exit(main())
