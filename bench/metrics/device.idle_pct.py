"""The device's idle share of the traced window: 1 less the union of its
ops' intervals over the window, the mean over the chips."""

from bench import trace


def read(ctx):
    if not ctx.trace.ops or ctx.hi <= ctx.lo:
        return None
    busy = trace.busy_s(ctx.trace, ctx.lo, ctx.hi)
    return 100.0 * (1.0 - busy / ((ctx.hi - ctx.lo) / 1e6))
