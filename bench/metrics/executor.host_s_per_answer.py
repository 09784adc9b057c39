"""Host seconds per answer: the window's ``bench.answer`` spans less the
device-busy time inside them, over the answers."""

from bench import trace


def read(ctx):
    if not ctx.trace.ops or not ctx.answers:
        return None
    return trace.host_s(ctx.trace, "bench.answer", ctx.lo, ctx.hi) / ctx.answers
