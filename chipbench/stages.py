"""Where a traced experiment's time goes, by the program's own names: the
device time of each stage scope inside the segment program, the device
idle under each ``repro.*`` span, the bytes the ``repro.upload`` spans
copied, and what a profiler session costs.

    python chipbench/stages.py --workload <cell> --seed <n> \
        [--experiments 3] [--keep FILE]

Set-up as ``run.py``'s (the cell's data from the seed, one warm-up
experiment), then three phases of whole experiments, each in a
``chipbench.experiment`` annotation: ``--experiments`` with no profiler
session, as many inside one session opened as ``run.py --trace 1`` opens
it (JAX's Python function tracer on), and one inside a session with that
tracer off. The first experiment of each session is read twice: by the
cell's per-layer readers, and here, from what ``tracing.load_xspace``
leaves out: each ``XLA Ops`` event's scope path and the stats of the
program's ``repro.*`` host events. On the chip an op event names its HLO
instruction but carries no ``op_name`` (its stats are offsets and
durations), so the paths come from the compiled HLO text of the cell's
segment and evaluator programs, which are compiled again for it (a load
from the persistent compile cache). The result is one JSON line on
stdout. ``--keep`` writes a cut of the first traced experiment (programs,
busy intervals, ``repro.*`` spans with their stats, per-stage intervals)
from which every number of it is read again; the tests keep one recorded
on a chip. Without a TPU it exits 3.
"""
from __future__ import annotations

import argparse
import bisect
import gzip
import json
import pathlib
import re
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent

# the stage scopes the program puts on its operations (``jax.named_scope``)
STAGES = ("sample_batches", "topology", "gossip", "select_heads",
          "local_sgd", "netsim", "obs_frame", "predict")
OTHER = "(no stage)"
PREFIX = "repro."
UPLOAD = "repro.upload"
# the per-stage metrics, by the stage each reads
STAGE_METRICS = {"sample_ms_per_round": "sample_batches",
                 "select_ms_per_round": "select_heads",
                 "sgd_ms_per_round": "local_sgd",
                 "gossip_ms_per_round": "gossip"}
_WRAPPER = re.compile(r"^(?:[\w.\-]+\()*")
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?'
                     r'metadata=\{[^}\n]*op_name="([^"]*)"', re.M)


def stages_of(path: str) -> tuple[str, ...]:
    """The stage scopes an ``op_name`` path holds, outermost first; a scope
    under a transformation (``vmap(local_sgd)``) counts as itself."""
    out = []
    for part in path.split("/"):
        core = _WRAPPER.sub("", part).rstrip(")")
        if core in STAGES and core not in out:
            out.append(core)
    return tuple(out)


def load_scoped(path, op_paths: dict) -> dict:
    """From a ``.xplane.pb``: each device's ``XLA Ops`` events with the
    ``op_name`` path of their instruction, and the program's ``repro.*``
    host events with their stats. ``op_paths`` is ``{program: {instruction:
    op_name}}`` (:func:`hlo_op_paths` of each program's compiled text); an
    op takes the map of the program whose ``XLA Modules`` event it starts
    in, and an empty path where that has none."""
    from jax.profiler import ProfileData

    from chipbench import tracing

    def events(line):
        return [(e.name, int(e.start_ns), int(e.duration_ns))
                for e in getattr(line, "events", ())]

    data = ProfileData.from_file(str(path))
    out = {"ops": {}, "spans": []}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {line.name: line for line in plane.lines}
            out["ops"][plane.name] = name_ops(
                events(lines.get(tracing.OPS)),
                events(lines.get(tracing.MODULES)), op_paths)
        elif plane.name == tracing.HOST_PLANE:
            for line in plane.lines:
                out["spans"].extend(
                    [e.name, int(e.start_ns), int(e.duration_ns),
                     {k: v for k, v in e.stats}]
                    for e in line.events if e.name.startswith(PREFIX))
    return out


def name_ops(ops, modules, op_paths: dict) -> list:
    """``[path, start, dur]`` for each ``(instruction, start, dur)`` op
    event: its path in the map of the program (``XLA Modules`` event, name
    up to ``(``) it starts in, ``""`` where there is none."""
    mods = sorted((s, s + d, n.split("(")[0]) for n, s, d in modules)
    starts = [m[0] for m in mods]
    out = []
    for name, t, d in ops:
        i = bisect.bisect_right(starts, t) - 1
        prog = mods[i][2] if i >= 0 and t < mods[i][1] else None
        inst = name.lstrip("%").split(" ")[0]
        out.append([op_paths.get(prog, {}).get(inst, ""), t, d])
    return out


def hlo_op_paths(texts) -> dict:
    """Instruction name -> ``op_name`` over the compiled HLO texts of one
    program; a name to which two texts give different paths is left out."""
    out, clash = {}, set()
    for text in texts:
        for name, op in _HLO_OP.findall(text):
            if out.setdefault(name, op) != op:
                clash.add(name)
    return {k: v for k, v in out.items() if k not in clash}


def by_stage(ops: dict, lo: int, hi: int) -> dict:
    """Per device: the merged intervals of the ops that start in [lo, hi),
    by each stage their path holds (ops with a path and no stage under
    ``OTHER``; ops with no path are left out)."""
    out = {}
    for plane, evs in sorted(ops.items()):
        per: dict[str, list] = {}
        for op, s, d in evs:
            if not (lo <= s < hi) or not op:
                continue
            for st in stages_of(op) or (OTHER,):
                per.setdefault(st, []).append((s, s + d))
        out[plane] = {st: _merge(iv) for st, iv in sorted(per.items())}
    return out


def _merge(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def stage_ms_per_round(stages: dict, stage: str, rounds: int) -> float | None:
    """Device ms a round in which an op of ``stage`` ran, mean over the
    chips; ``None`` when no op in the window carries a scope path. Where
    ops carry paths but none holds ``stage``, the name is stale and this
    raises, naming the stages there are."""
    per = [sum(e - s for s, e in st[stage]) for st in stages.values()
           if stage in st]
    if per:
        return sum(per) / len(per) / rounds / 1e6
    seen = sorted({s for st in stages.values() for s in st})
    if seen:
        raise LookupError(f"no operation under the scope {stage!r} in the "
                          f"traced window; scopes there: {seen}")
    return None


def h2d_mb(spans, lo: int, hi: int) -> float | None:
    """MB the ``repro.upload`` spans that start in [lo, hi) copied."""
    ups = [st.get("bytes", 0) for n, s, _, st in spans
           if n == UPLOAD and lo <= s < hi]
    return sum(ups) / 1e6 if ups else None


def span_ms(spans, lo: int, hi: int) -> dict:
    """Wall ms of each ``repro.*`` span name, over the spans in [lo, hi)."""
    out: dict[str, float] = {}
    for n, s, d, _ in spans:
        if lo <= s < hi:
            out[n] = out.get(n, 0.0) + d / 1e6
    return dict(sorted(out.items()))


def idle_by_span(ctx, spans) -> dict:
    """Device idle ms inside the window, mean over the chips, put down to
    the innermost ``repro.*`` span open over each stretch of it."""
    from chipbench import tracing

    open_ = [(s, s + d, n) for n, s, d, _ in spans
             if s < ctx.hi and s + d > ctx.lo]
    per = tracing.device_events(ctx.trace, tracing.OPS, ctx.lo, ctx.hi)
    out: dict[str, float] = {}
    chips = [evs for evs in per.values() if evs]
    for evs in chips:
        for gs, ge in tracing.gaps([(s, e) for _, s, e in evs],
                                   ctx.lo, ctx.hi):
            cuts = sorted({gs, ge} | {t for s, e, _ in open_
                                      for t in (s, e) if gs < t < ge})
            for a, b in zip(cuts[:-1], cuts[1:]):
                mid = (a + b) / 2
                over = [(e - s, n) for s, e, n in open_ if s <= mid < e]
                name = min(over)[1] if over else "(no repro span)"
                out[name] = out.get(name, 0.0) + (b - a) / 1e6
    return {n: v / max(1, len(chips)) for n, v in sorted(out.items())}


def read(ctx, scoped_stages: dict, spans) -> dict:
    """Every number this module reports of one traced experiment."""
    out = {m: stage_ms_per_round(scoped_stages, st, ctx.rounds)
           for m, st in STAGE_METRICS.items()}
    out["h2d_mb_per_run"] = h2d_mb(spans, ctx.lo, ctx.hi)
    out["stage_ms_per_round"] = {
        st: stage_ms_per_round(scoped_stages, st, ctx.rounds)
        for st in sorted({s for p in scoped_stages.values() for s in p})}
    out["idle_ms_by_span"] = idle_by_span(ctx, spans)
    out["span_ms"] = span_ms(spans, ctx.lo, ctx.hi)
    return out


def cut(trace: dict, scoped: dict, lo: int, hi: int) -> dict:
    """What the readers and :func:`read` need of the window [lo, hi):
    the programs whole, the operations merged into busy intervals, the
    host's annotation and ``repro.*`` events, the ``repro.*`` spans with
    their stats, and per-stage intervals."""
    from chipbench import tracing

    devices = {}
    for plane, lines in trace["devices"].items():
        mods = [e for e in lines.get(tracing.MODULES, [])
                if lo <= e[1] < hi]
        busy = _merge((s, s + d) for _, s, d in lines.get(tracing.OPS, [])
                      if lo <= s < hi)
        devices[plane] = {tracing.MODULES: mods,
                          tracing.OPS: [["busy", s, e - s] for s, e in busy]}
    host = {ln: [e for e in evs if e[1] < hi and e[1] + e[2] > lo and
                 (e[0].startswith(PREFIX) or e[0] == "chipbench.experiment")]
            for ln, evs in trace["host"].items()}
    return {"devices": devices, "host": {k: v for k, v in host.items() if v},
            "stages": by_stage(scoped["ops"], lo, hi),
            "spans": [sp for sp in scoped["spans"]
                      if sp[1] < hi and sp[1] + sp[2] > lo]}


def dump(obj, path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(obj, f)


def load(path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------- on chip
def program_texts(cache, ds) -> dict:
    """The compiled HLO text of every segment and evaluator program the
    cache's entries run, by program name."""
    import jax
    import jax.numpy as jnp

    texts = {"jit_segment": [], "jit_predict": []}
    key = jax.random.PRNGKey(0)
    for entry in cache._entries.values():
        eng, setup = entry.engine, entry.setup(key)
        carry = eng.init_carry(setup.state, key)
        tx, ty = eng.place_data(jnp.asarray(ds.train_x),
                                jnp.asarray(ds.train_y))
        for fn in eng._compiled.values():
            texts["jit_segment"].append(fn.lower(
                carry, jnp.asarray(0, jnp.int32), tx, ty).compile().as_text())
        ev = cache.evaluator(entry.binding, ds, batch=entry.spec.eval_batch)
        for models_c, xb in ev.inputs(setup.models_of(setup.state)):
            texts["jit_predict"].append(
                ev.predict.lower(models_c, xb).compile().as_text())
    return texts


def analyse(cell, bench, path, op_paths, devices):
    """The readers' numbers and :func:`read`'s of the first experiment of
    the trace at ``path``, and its cut."""
    from chipbench import run, tracing

    trace = tracing.load_xspace(path)
    scoped = load_scoped(path, op_paths)
    ctx = run.context(cell, trace, len(devices), devices[0].device_kind)
    kept = cut(trace, scoped, ctx.lo, ctx.hi)
    metrics = {k: v["value"]
               for k, v in run.per_layer(cell, bench, ctx).items()}
    return {"metrics": metrics, **read(ctx, kept["stages"], kept["spans"]),
            "window_ms": (ctx.hi - ctx.lo) / 1e6}, kept


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--experiments", type=int, default=3)
    ap.add_argument("--keep", type=pathlib.Path,
                    help="write the cut of the traced experiment here "
                         "(gzipped JSON) and the numbers beside it, "
                         "as <FILE>.expected.json")
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    from chipbench import run, spec, synth, tracing
    from repro.core.cache import EngineCache, use_compile_cache
    from repro.core.runner import run_experiment

    cell = spec.workload(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"{args.workload} needs {cell['chips']} TPU chips; JAX sees "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return run.NO_DEVICE
    devices = devices[:cell["chips"]]
    use_compile_cache()
    seeds = run.Seeds(args.seed)
    ds = synth.make_dataset(cell, seeds.data)
    cfg = spec.cnn_config(cell["model"])
    cache = EngineCache()
    kw = run.experiment_kwargs(cell, cache)
    n, rounds = sum(cell["clusters"]), cell["rounds_per_run"]
    run_experiment(cell["algo"], cfg, ds, rounds=cell["eval_every"],
                   seed=seeds.warm, **kw)
    i = 0

    def phase(count: int, log_dir=None, options=None) -> float:
        """node-rounds/s over ``count`` experiments, in a profiler session
        when ``log_dir`` is given (opening and closing it not timed)."""
        nonlocal i
        if log_dir is not None:
            jax.profiler.start_trace(log_dir, profiler_options=options)
        t0 = time.perf_counter()
        for _ in range(count):
            with jax.profiler.TraceAnnotation(run.ANNOTATION):
                run_experiment(cell["algo"], cfg, ds, rounds=rounds,
                               seed=seeds.experiment(i), **kw)
            i += 1
        t = time.perf_counter() - t0
        if log_dir is not None:
            jax.profiler.stop_trace()
        return n * rounds * count / t

    dirs = [tempfile.mkdtemp(prefix="chipbench-stages-") for _ in range(2)]
    no_python = jax.profiler.ProfileOptions()
    no_python.python_tracer_level = 0
    rates = {"closed": phase(args.experiments),
             "open": phase(args.experiments, dirs[0]),
             "open_no_python_tracer": phase(1, dirs[1], no_python)}
    op_paths = {prog: hlo_op_paths(ts)
                for prog, ts in program_texts(cache, ds).items()}
    bench = spec.load_json(spec.BENCHMARK)
    try:
        (out, kept), (quiet, _) = (
            analyse(cell, bench, tracing.find_xspace(d), op_paths, devices)
            for d in dirs)
    finally:
        for d in dirs:
            shutil.rmtree(d, ignore_errors=True)
    out = {"workload": args.workload, "seed": args.seed,
           "device": run.device_info(devices), "node_rounds_per_s": rates,
           **out, "no_python_tracer": quiet}
    if args.keep is not None:
        dump(kept, args.keep)
        expected = args.keep.with_name(
            args.keep.name.replace(".json.gz", "") + ".expected.json")
        expected.write_text(json.dumps(out, indent=1) + "\n")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
