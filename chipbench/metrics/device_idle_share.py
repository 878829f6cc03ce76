"""Device layer: the share of the traced window in which no operation ran,
in percent, averaged over the chips."""
from chipbench import tracing


def read(ctx: tracing.Context):
    busy = tracing.busy(ctx)
    if not busy:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / (ctx.hi - ctx.lo))
