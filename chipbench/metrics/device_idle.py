"""device_idle: share of the traced window in which no operation ran on the
device, averaged over the cell's chips: 100 · (1 − busy / window)."""

from chipbench import xtrace


def read(ctx):
    if not ctx.trace.devices or ctx.hi <= ctx.lo:
        return None
    busy = sum(xtrace.busy_ns(d, ctx.lo, ctx.hi) for d in ctx.trace.devices)
    busy /= len(ctx.trace.devices)
    return 100.0 * (1.0 - busy / (ctx.hi - ctx.lo))
