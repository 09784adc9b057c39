"""Device seconds per answer of the push: the ops under ``jit(forward_push)``
in the trace's name stack."""

from bench import trace


def read(ctx):
    t = trace.op_time_s(ctx.trace, trace.in_scope("jit(forward_push)"),
                        ctx.lo, ctx.hi)
    return None if t is None or not ctx.answers else t / ctx.answers
