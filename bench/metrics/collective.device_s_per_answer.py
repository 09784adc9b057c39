"""Device seconds per answer of the exchanges between chips: all-reduce
(``psum``) and all-gather ops, the mean over the chips."""

from bench import trace


def read(ctx):
    t = trace.op_time_s(ctx.trace, trace.is_collective, ctx.lo, ctx.hi)
    return None if t is None or not ctx.answers else t / ctx.answers
