"""Engine layer: device time of the segment program (``jit_segment``, the
engine's ``lax.scan`` over a span of rounds) per round."""
from chipbench import tracing

MODULE = "jit_segment"


def read(ctx: tracing.Context):
    t = tracing.module_seconds(ctx, MODULE)
    return None if t is None else t / ctx.rounds * 1e3
