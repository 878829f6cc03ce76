"""Evaluator layer: device time of the per-cluster prediction program
(``jit_predict``) per evaluation."""
from chipbench import tracing

MODULE = "jit_predict"


def read(ctx: tracing.Context):
    t = tracing.module_seconds(ctx, MODULE)
    return None if t is None or ctx.evals == 0 else t / ctx.evals * 1e3
