"""Device seconds per answer of placing the walkers: the ops under the
program's ``fora.walk_starts`` scope (the residual's cumsum, the uniform
draw and the ``searchsorted`` over n)."""

from bench import trace


def read(ctx):
    t = trace.op_time_s(ctx.trace, trace.in_scope("fora.walk_starts"),
                        ctx.lo, ctx.hi)
    return None if t is None or not ctx.answers else t / ctx.answers
