"""Run one benchmark cell once, in one process, on the chips it asks for.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's data from the seed, then runs one experiment of
``eval_every`` rounds through the ``EngineCache`` the window uses, which
compiles (or loads from the persistent cache) the segment program and the
evaluator. The window then drives ``run_experiment`` with the cell's
arguments through that cache, one whole experiment after another, each
with a new seed drawn from ``--seed``, until ``--seconds`` have passed;
the experiment running at the deadline finishes and counts.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
profiles the window's first experiment and reports the per-layer metrics
that ``BENCHMARK.json`` lists for the cell, read by
``chipbench/metrics/<name>.py``. After the window, one experiment drawn
from the seed is checked against the plain reference (``correct.py``).
The last line of stdout is the result as JSON; the numbers compared, each
beside its limit, are also the last lines of stderr. Without a TPU, or
with fewer chips than the cell asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
MIN_EXPERIMENTS = 3      # the window holds at least this many; the checked
#                          experiment is drawn from among them
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
ANNOTATION = "chipbench.experiment"
NO_DEVICE = 3


def _paths():
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


class Seeds:
    """Every seed of a run, drawn from ``--seed`` (any non-negative
    integer): the data's, the warm-up's, one per experiment, and which of
    the first experiments is checked. All are below 2**31, where
    ``PRNGKey`` takes them whole."""

    def __init__(self, seed: int):
        import numpy as np

        rng = np.random.default_rng(seed)
        self.data, self.warm = (int(v) for v in rng.integers(0, 2**31, 2))
        self._rng = rng
        self._exp: list[int] = []
        self.checked = int(rng.integers(0, MIN_EXPERIMENTS))

    def experiment(self, i: int) -> int:
        while len(self._exp) <= i:
            self._exp.append(int(self._rng.integers(0, 2**31)))
        return self._exp[i]


class Tap:
    """The evaluator the program built, passed through unchanged; when
    armed, it keeps the next evaluation's node models (a copy: the next
    segment donates the state they alias) and its per-cluster predictions
    as the program made them."""

    def __init__(self, evaluator):
        self._ev = evaluator
        self.armed = True
        self.models = self.preds = None

    def begin(self, models):
        if not self.armed:
            return self._ev.begin(models)
        import jax
        import jax.numpy as jnp

        self.models = jax.tree.map(jnp.copy, models)
        self.preds = self._ev.begin(models)
        self.armed = False
        return self.preds

    def __getattr__(self, name):
        return getattr(self._ev, name)


def tapped_cache():
    """An ``EngineCache`` whose evaluators are :class:`Tap` s."""
    from repro.core.cache import EngineCache

    class TappedCache(EngineCache):
        tap = None

        def evaluator(self, binding, dataset, batch=256):
            ev = super().evaluator(binding, dataset, batch)
            if self.tap is None or self.tap._ev is not ev:
                self.tap = Tap(ev)
            return self.tap

    return TappedCache()


class CompileCounter:
    """Counts XLA compilations (or loads from the persistent cache)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1


def experiment_kwargs(cell: dict, cache) -> dict:
    mesh = cell.get("mesh")
    return dict(degree=cell["degree"], local_steps=cell["local_steps"],
                batch_size=cell["batch_size"], lr=cell["lr"],
                eval_every=cell["eval_every"], eval_batch=cell["eval_batch"],
                mesh=tuple(mesh) if mesh else None, cache=cache)


def checked_segment(cell: dict, res, tap) -> dict:
    """What the check compares of an experiment: its first segment, and
    the evaluation at its end (``tap`` holds that evaluation's models and
    predictions)."""
    import numpy as np

    r = cell["eval_every"]
    return {"rounds": r, "final": r == cell["rounds_per_run"],
            "cids": np.stack([c for _, c in res.cluster_history[:r]]),
            "models": host_tree(tap.models),
            "preds": [np.asarray(p) for p in tap.preds],
            "accs": list(res.acc_per_cluster[0][1]),
            "cum_bytes": res.comm.bytes[r - 1]}


def host_tree(tree):
    import jax
    import numpy as np

    return jax.tree.map(np.asarray, tree)


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def per_layer(cell: dict, bench: dict, ctx) -> dict:
    from chipbench import spec

    out = {}
    for m in spec.per_layer_metrics(cell["name"], bench):
        reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def breakdown(ctx) -> dict:
    """The device operations that took most time, and the longest idle
    gaps by the host event that was open over them."""
    from chipbench import tracing

    ops: dict[str, int] = {}
    per = tracing.device_events(ctx.trace, tracing.OPS, ctx.lo, ctx.hi)
    for evs in per.values():
        for n, s, e in evs:
            ops[n] = ops.get(n, 0) + (e - s)
    chips = max(1, len(per))
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    host = [(n, s, s + d) for evs in ctx.trace["host"].values()
            for n, s, d in evs if n != ANNOTATION]
    idle = []
    for evs in per.values():
        idle += tracing.gaps([(s, e) for _, s, e in evs], ctx.lo, ctx.hi)
    idle = sorted(idle, key=lambda g: g[0] - g[1])[:10]
    named = []
    for s, e in idle:
        # the shortest host event that covers the gap's middle
        mid = (s + e) // 2
        over = [(he - hs, n) for n, hs, he in host if hs <= mid < he]
        named.append([min(over)[1] if over else "(no host event)",
                      (e - s) / 1e9])
    return {"device_ops": [[n, t / chips / 1e9] for n, t in top],
            "idle_gaps": named}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=pathlib.Path,
                    help="with --trace 1, also write the traced experiment's "
                         "reduced trace (gzipped JSON, tracing.py's form) to "
                         "this file: how the readers' test trace is recorded")
    args = ap.parse_args(argv)
    _paths()
    from chipbench import spec

    cell = spec.workload(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench needs a TPU; JAX sees {devices[0].platform}",
              file=sys.stderr)
        return NO_DEVICE
    if len(devices) < cell["chips"]:
        print(f"{args.workload} needs {cell['chips']} chips; JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return NO_DEVICE
    from repro.core.cache import use_compile_cache

    use_compile_cache()
    result, checks = run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices[:cell["chips"]],
                              spec.load_json(spec.BENCHMARK),
                              keep_trace=args.keep_trace)
    for name, c in checks.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result))
    return 0


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices,
             bench: dict, t0: float = T0, keep_trace=None):
    """One run of the cell; returns the result line and the checks."""
    import jax

    from chipbench import checks, correct, reference, spec, synth, window
    from repro.core.runner import run_experiment

    seeds = Seeds(seed)
    n = sum(cell["clusters"])
    rounds = cell["rounds_per_run"]
    per_round = reference.round_bytes(cell["model"], n, cell["degree"])
    ds = synth.make_dataset(cell, seeds.data)
    cfg = spec.cnn_config(cell["model"])
    cache = tapped_cache()
    kw = experiment_kwargs(cell, cache)
    # set-up: one experiment of one segment warms every program and every
    # eager operation the window uses, the tap's copy included
    run_experiment(cell["algo"], cfg, ds, rounds=cell["eval_every"],
                   seed=seeds.warm, **kw)
    compiles = CompileCounter()
    setup_s = time.perf_counter() - t0

    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    kept = {}

    def run_one(i: int):
        c0, e0, k0 = cache.compile_count, cache.evaluator_builds, compiles.n
        cache.tap.armed = i == seeds.checked
        profiling = trace and i == 0
        if profiling:
            jax.profiler.start_trace(log_dir)
        try:
            with jax.profiler.TraceAnnotation(ANNOTATION):
                res = run_experiment(cell["algo"], cfg, ds, rounds=rounds,
                                     seed=seeds.experiment(i), **kw)
            bad = checks.check_run(res, rounds=rounds, per_round=per_round)
        except Exception:   # a failed experiment counts; the window goes on
            traceback.print_exc()
            res, bad = None, ["raised"]
        finally:
            if profiling:
                jax.profiler.stop_trace()
        if (cache.compile_count, cache.evaluator_builds, compiles.n) != \
                (c0, e0, k0):
            bad.append("compiled inside the window")
        if bad:
            print(f"experiment {i}: {bad}", file=sys.stderr)
        if i == seeds.checked:
            kept["res"] = res
        return n * rounds, not bad

    w0, done = window.drive(run_one, seconds, time.perf_counter,
                            MIN_EXPERIMENTS)
    rate = window.rate(w0, done)
    peak = memory_peak(devices)
    info = {**device_info(devices), "memory_peak_bytes": peak}

    # the reference runs once the program's state is freed
    res, seg = kept.get("res"), None
    if res is not None and cache.tap.models is not None:
        seg = checked_segment(cell, res, cache.tap)
    del cache, kw, res
    gc.collect()
    numbers, r0 = {}, time.perf_counter()
    if seg is not None:
        numbers = correct.compare(reference.setup(cell), ds,
                                  seed=seeds.experiment(seeds.checked),
                                  select_margin=cell["select_margin"], **seg)
    print(f"reference check {time.perf_counter() - r0:.1f} s, window "
          f"{done[-1].end - w0:.1f} s, {len(done)} experiments",
          file=sys.stderr)
    ok, verdicts = correct.verdict(numbers, cell["limits"])

    out = {"correct": ok, "attempted": len(done),
           "failed": sum(not e.ok for e in done)}
    if trace:
        metrics, extra, bd = traced(cell, bench, log_dir, devices,
                                    keep_trace)
        info.update(extra)
        out.update(metrics=metrics, device=info, breakdown=bd)
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        if rate is not None:
            metrics["node_rounds_per_s"] = {"value": rate,
                                            "unit": "node-rounds/s"}
        if peak is not None:
            metrics["peak_hbm_gb"] = {"value": peak / 1e9, "unit": "GB"}
        out.update(metrics=metrics, device=info)
    out["checks"] = verdicts
    return out, verdicts


def traced(cell: dict, bench: dict, log_dir: str, devices, keep=None):
    """Per-layer metrics, busy and window seconds, and the breakdown, from
    the trace of the window's first experiment; the trace is deleted."""
    from chipbench import tracing

    try:
        trace = tracing.load_xspace(tracing.find_xspace(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    ctx = context(cell, trace, len(devices), devices[0].device_kind)
    if keep is not None:
        tracing.dump(tracing.clip(trace, ctx.lo, ctx.hi), keep)
    busy = tracing.busy(ctx)
    extra = {"busy_s": sum(busy) / len(busy) / 1e9 if busy else 0.0,
             "window_s": (ctx.hi - ctx.lo) / 1e9}
    return per_layer(cell, bench, ctx), extra, breakdown(ctx)


def context(cell: dict, trace: dict, chips: int, device_kind: str):
    """What the readers are handed: the traced experiment's annotation
    and the cell's shape."""
    from chipbench import flops, tracing

    lo, hi = tracing.host_spans(trace, ANNOTATION)[0]
    rounds = cell["rounds_per_run"]
    return tracing.Context(
        trace, lo, hi, rounds=rounds, evals=rounds // cell["eval_every"],
        nodes=sum(cell["clusters"]), chips=chips,
        flops_per_round=float(flops.round_flops(cell)),
        peak_flops=flops.peak(device_kind)["bf16_flops"])


if __name__ == "__main__":
    sys.exit(main())
