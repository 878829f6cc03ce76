"""Runner layer: device idle from the start of the experiment's
``repro.run`` span to the start of its first segment program
(``jit_segment``), mean over the chips — the wait for the train set's
upload and the run's set-up, as the device sees it. A program without
``repro.*`` spans gives nothing to read."""
from chipbench import tracing

SPAN = "repro.run"
MODULE = "jit_segment"


def read(ctx: tracing.Context):
    runs = [s for s, _ in tracing.host_spans(ctx.trace, SPAN)
            if ctx.lo <= s < ctx.hi]
    if not runs:
        return None
    t0 = runs[0]
    mods = tracing.device_events(ctx.trace, tracing.MODULES, t0, ctx.hi)
    ops = tracing.device_events(ctx.trace, tracing.OPS, ctx.lo, ctx.hi)
    idle = []
    for plane, evs in mods.items():
        starts = [s for n, s, _ in evs if n.startswith(MODULE)]
        if starts:
            t1 = min(starts)
            busy = tracing.union([(s, e) for _, s, e in ops[plane]], t0, t1)
            idle.append((t1 - t0) - busy)
    if idle:
        return sum(idle) / len(idle) / 1e6
    seen = sorted({n.split("(")[0] for evs in mods.values()
                   for n, _, _ in evs})
    if seen:
        raise LookupError(f"no device program named {MODULE}* after "
                          f"{SPAN}; programs there: {seen}")
    return None
