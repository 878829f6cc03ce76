"""Shared harness for the paper-table benchmarks.

Every benchmark module exposes ``run(quick: bool) -> dict`` and registers
itself in ``REGISTRY``. ``quick`` (the default for ``-m benchmarks.run``)
scales the paper's 16-32-node/1200-round experiments down to CPU size
(8 nodes / tens of rounds) while keeping cluster-ratio structure; ``--full``
uses the paper-shaped configuration (slow on CPU).
"""
from __future__ import annotations

import json
import pathlib
import time

from repro.configs.facade_paper import lenet
from repro.core.runner import run_experiment
from repro.data.synthetic import SynthSpec, make_clustered_data

RESULTS_DIR = pathlib.Path(__file__).resolve().parent.parent / "results" / "bench"

ALGOS = ("facade", "el", "dac", "deprl")


def scaled(quick: bool):
    """(cluster configs, rounds, spec, cnn cfg) at CPU scale."""
    if quick:
        # noise=0.8 calibrated so EL shows the paper's minority-cluster gap
        # at CPU scale (EL ~0.32 vs FACADE ~0.87 on the 7:1 minority)
        spec = SynthSpec(n_classes=6, image_size=16, samples_per_class=12,
                         test_per_class=32, noise=0.8, seed=3)
        cfg = lenet(smoke=True).replace(n_classes=6)
        cluster_cfgs = [(4, 4), (6, 2), (7, 1)]   # 16:16 / 24:8 / 30:2 scaled
        rounds = 48
    else:
        spec = SynthSpec(n_classes=10, image_size=32, samples_per_class=32,
                         test_per_class=64, seed=3)
        cfg = lenet(smoke=False)
        cluster_cfgs = [(16, 16), (24, 8), (30, 2)]
        rounds = 400
    return cluster_cfgs, rounds, spec, cfg


def std_kwargs(quick: bool):
    return dict(degree=2 if quick else 4, local_steps=4 if quick else 10,
                batch_size=8, lr=0.05, eval_every=8 if quick else 40,
                seed=0)


def run_algo(algo, cfg, ds, rounds, quick, **overrides):
    kw = std_kwargs(quick)
    kw.update(overrides)
    k = kw.pop("k", ds.k)
    t0 = time.time()
    res = run_experiment(algo, cfg, ds, rounds=rounds, k=k, **kw)
    res.wall_s = time.time() - t0
    return res


def save(name: str, payload: dict) -> pathlib.Path:
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    out = RESULTS_DIR / f"{name}.json"
    out.write_text(json.dumps(payload, indent=2, default=float))
    return out


def trajectory_path() -> pathlib.Path:
    """Where :func:`write_bench` appends its history. Module-level
    ``RESULTS_DIR`` lookup at call time so tests can monkeypatch it."""
    return RESULTS_DIR / "TRAJECTORY.jsonl"


def write_bench(name: str, payload: dict) -> pathlib.Path:
    """The one way a benchmark writes its ``BENCH_<name>.json``: stamps a
    ``manifest`` block (payload content fingerprint + jax version +
    timestamp, :func:`repro.obs.bench_stamp`) so every benchmark artifact
    records what exactly produced it, then routes through :func:`save`.

    Every payload is ALSO appended to ``results/bench/TRAJECTORY.jsonl``
    (one record per write, never truncated) — the across-runs history
    ``benchmarks/check_regress.py`` diffs latest-vs-previous against.
    """
    from repro.obs import bench_stamp

    payload = dict(payload)
    payload["manifest"] = bench_stamp(name, payload)
    traj = trajectory_path()
    traj.parent.mkdir(parents=True, exist_ok=True)
    with traj.open("a") as fh:
        fh.write(json.dumps({"name": name, "payload": payload},
                            default=repr) + "\n")
        fh.flush()
    return save(f"BENCH_{name}", payload)


def engine_cache(max_entries: int | None = None):
    """Build the benchmark-suite :class:`repro.core.cache.EngineCache` over
    the resolved persistent compile cache
    (:func:`repro.core.cache.use_compile_cache`: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache``), so compiled XLA executables
    survive across benchmark PROCESSES and a re-run of
    ``-m benchmarks.run`` or a CI shard starts warm."""
    from repro.core.cache import EngineCache, use_compile_cache

    use_compile_cache()
    return EngineCache(max_entries=max_entries)


def fmt_to_target(v, fmt: str = "{:.1f} s"):
    """Render a ``CommLog`` bytes/seconds-to-target value for a table.
    ``None`` is the log's never-reached sentinel (see
    :mod:`repro.comm.accounting`) — formatted as ``"not reached"``
    instead of crashing an f-string's float format."""
    return "not reached" if v is None else fmt.format(v)


def to_target_ratio(base, new):
    """Speedup ``base / new`` for a pair of to-target values, propagating
    the never-reached sentinel: ``None`` when either side never crossed
    the target (a run that never got there has no finite speedup)."""
    if base is None or new is None or new == 0:
        return None
    return base / new


def table(headers, rows) -> str:
    w = [max(len(str(h)), *(len(str(r[i])) for r in rows)) if rows
         else len(str(h)) for i, h in enumerate(headers)]
    line = " | ".join(str(h).ljust(w[i]) for i, h in enumerate(headers))
    sep = "-+-".join("-" * x for x in w)
    body = "\n".join(" | ".join(str(c).ljust(w[i])
                                for i, c in enumerate(r)) for r in rows)
    return f"{line}\n{sep}\n{body}"


def make_ds(spec, sizes, transforms=None, label_split=None):
    return make_clustered_data(spec, sizes, transforms,
                               label_split=label_split)


def micro_config(n_nodes: int = 32, seed: int = 3):
    """Deliberately tiny 32-node GN-LeNet setup (8x8 images, width 2) where
    per-round compute is a few ms — the regime where driver overhead and
    XLA compiles, not model FLOPs, bound sweep throughput. Shared by the
    ``round_throughput`` and ``seed_sweep`` benchmarks."""
    from repro.models.base import CNNConfig

    cfg = CNNConfig(name="lenet-micro", kind="lenet", image_size=8,
                    width=2, n_classes=4)
    spec = SynthSpec(n_classes=4, image_size=8, samples_per_class=8,
                     test_per_class=16, seed=seed)
    half = n_nodes // 2
    ds = make_clustered_data(spec, (half, n_nodes - half),
                             ("rot0", "rot180"))
    return cfg, ds
