"""Runner layer: time inside the traced experiment in which no operation
ran on the device, per experiment (mean over the chips)."""
from chipbench import tracing


def read(ctx: tracing.Context):
    busy = tracing.busy(ctx)
    if not busy:
        return None
    return ((ctx.hi - ctx.lo) - sum(busy) / len(busy)) / 1e6
