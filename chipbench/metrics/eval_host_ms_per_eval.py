"""Evaluator layer, host side: wall time of the program's ``repro.eval``
spans per evaluation — the whole evaluation as the host sees it (model
gathering, dispatch, drain and the fairness reduction), beside
``eval_ms_per_eval``'s device time. A program without ``repro.*`` spans
gives nothing to read."""
from chipbench import tracing

SPAN = "repro.eval"


def read(ctx: tracing.Context):
    spans = [(s, e) for s, e in tracing.host_spans(ctx.trace, SPAN)
             if ctx.lo <= s < ctx.hi]
    if not spans or ctx.evals == 0:
        return None
    return sum(e - s for s, e in spans) / ctx.evals / 1e6
