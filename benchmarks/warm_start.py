"""Warm start: the persistent XLA compile cache across PROCESSES.

Every sweep process historically started cold — the in-process
``EngineCache`` shares compiled programs across runs, but the XLA
executables behind them died with the process, so a rerun grid, a CI
shard or a preemption-resumed sweep paid the full compile bill again.
:func:`repro.core.cache.use_compile_cache` turns JAX's persistent cache
on at ONE resolved place (``JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache``), so serialized executables survive on disk.

This benchmark launches the SAME tiny run in three fresh child
processes: ``cold`` with the persistent cache switched off (a true cold
compile, whatever the cache already holds), ``fill`` with it on (makes
sure the run's executables are on disk), and ``warm`` with it on, which
deserializes them and reaches its first segment dispatch measurably
faster. Each child reports ``first_dispatch_s`` (cache-entry build +
the run's one segment, i.e. time to first useful device work) and its
tracer ``compile`` span total.

The children each need the accelerator, and a chip belongs to one
process at a time: :func:`run` refuses to start from a parent that has
already initialised a JAX backend (``python -m benchmarks.run`` runs this
suite before any in-process one).

Writes ``results/bench/BENCH_warmstart.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

from . import common

N_NODES = 8
ROUNDS = 8
EVAL_EVERY = 8


def _child_payload() -> dict:
    """One fresh-process measurement: attach the resolved compile cache
    and time cache-entry build + the first segment."""
    import jax  # noqa: F401  (imported before timing starts, like a real run)

    from repro.core.cache import EngineCache, use_compile_cache
    from repro.core.runner import run_experiment
    from repro.obs import Obs

    cfg, ds = common.micro_config(N_NODES)
    cache_dir = use_compile_cache()
    cache = EngineCache()
    obs = Obs(config=None)           # spans only: no device-side frames
    t0 = time.perf_counter()
    run_experiment("facade", cfg, ds, rounds=ROUNDS, k=2, degree=2,
                   local_steps=1, batch_size=2, lr=0.05,
                   eval_every=EVAL_EVERY, seed=0, cache=cache, obs=obs)
    first = time.perf_counter() - t0
    roll = obs.tracer.rollup()["spans"]
    return {"first_dispatch_s": first,
            "compile_s": roll.get("compile", {}).get("total_s", 0.0),
            "eval_s": roll.get("eval", {}).get("total_s", 0.0),
            "cache_dir": cache_dir}


def _require_fresh_parent() -> None:
    """Refuse to spawn accelerator-bound children from a process that
    already holds a JAX backend: on a TPU host the parent then owns the
    chip and every child fails or hangs on it."""
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "warm_start starts child processes that each need the "
            "accelerator, but this process has already initialised a JAX "
            "backend (and holds the chip): run it in a fresh process, e.g. "
            "python -m benchmarks.run --only warm_start")


def _spawn(cache_on: bool) -> dict:
    """Run ``_child_payload`` in a FRESH interpreter (the whole point:
    in-process jit caches don't survive it; only the disk cache does)."""
    env = dict(os.environ)
    src = str(pathlib.Path(__file__).resolve().parent.parent / "src")
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true" if cache_on else "false"
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.warm_start", "--child"],
        cwd=str(pathlib.Path(__file__).resolve().parent.parent),
        env=env, capture_output=True, text=True, check=False)
    if proc.returncode != 0:
        raise RuntimeError(
            f"warm_start child failed:\n{proc.stdout}\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run(quick: bool = True) -> dict:
    _require_fresh_parent()
    cold = _spawn(cache_on=False)
    fill = _spawn(cache_on=True)
    n_files = len(list(pathlib.Path(fill["cache_dir"]).iterdir()))
    warm = _spawn(cache_on=True)
    speedup = cold["first_dispatch_s"] / max(warm["first_dispatch_s"], 1e-9)
    rows = [[name, f"{r['first_dispatch_s']:.2f}", f"{r['compile_s']:.2f}"]
            for name, r in (("cold", cold), ("fill", fill), ("warm", warm))]
    print(common.table(["process", "first_dispatch_s", "compile_s"], rows))
    payload = {"n_nodes": N_NODES, "rounds": ROUNDS,
               "cold": cold, "fill": fill, "warm": warm,
               "speedup_first_dispatch": speedup,
               "persisted_files": n_files,
               "warm_faster": warm["first_dispatch_s"]
               < cold["first_dispatch_s"]}
    out = common.write_bench("warmstart", payload)
    print(f"wrote {out} (with the cache warm a process reaches first "
          f"dispatch {speedup:.2f}x faster than with it off)")
    return payload


def smoke() -> dict:
    """In-process persistent-cache exercise for the dry-run matrix: a run
    with the resolved compile cache attached must leave executables there
    and stay bit-for-bit a plain run."""
    import numpy as np

    from repro.core.cache import EngineCache, use_compile_cache
    from repro.core.runner import run_experiment

    cfg, ds = common.micro_config(4)
    kw = dict(rounds=4, k=2, degree=2, local_steps=1, batch_size=2,
              lr=0.05, eval_every=2, seed=0)
    ref = run_experiment("facade", cfg, ds, **kw)
    cache_dir = pathlib.Path(use_compile_cache())
    cache = EngineCache()
    got = run_experiment("facade", cfg, ds, cache=cache, **kw)
    n_files = len(list(cache_dir.iterdir()))
    ok = (ref.acc_per_cluster == got.acc_per_cluster
          and ref.comm.bytes == got.comm.bytes and n_files > 0
          and np.isfinite(got.comm.bytes[-1]))
    return {"status": "ok" if ok else "fail", "persisted_files": n_files,
            "cache_dir": str(cache_dir), "cache_stats": cache.stats()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)
    if args.child:
        print(json.dumps(_child_payload()))
        return 0
    run(quick=not args.full)
    return 0


if __name__ == "__main__":
    sys.exit(main())
