"""Device seconds per answer of the walks: the ops under
``jit(residual_walks)`` in the trace's name stack."""

from bench import trace


def read(ctx):
    t = trace.op_time_s(ctx.trace, trace.in_scope("jit(residual_walks)"),
                        ctx.lo, ctx.hi)
    return None if t is None or not ctx.answers else t / ctx.answers
