"""Engine layer, whole round: FLOPs the algorithm needs per round
(``chipbench/flops.py``) over the segment program's device time times the
chips' peak, in percent."""
from chipbench import tracing

MODULE = "jit_segment"


def read(ctx: tracing.Context):
    t = tracing.module_seconds(ctx, MODULE)
    if t is None:
        return None
    return 100.0 * ctx.flops_per_round * ctx.rounds / (
        t * ctx.chips * ctx.peak_flops)
