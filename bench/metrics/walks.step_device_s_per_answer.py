"""Device seconds per answer of stepping the walkers: the ops under the
program's ``fora.walk_steps`` scope (the step draws, the lockstep scan, the
endpoint sum and, on several chips, its exchange)."""

from bench import trace


def read(ctx):
    t = trace.op_time_s(ctx.trace, trace.in_scope("fora.walk_steps"),
                        ctx.lo, ctx.hi)
    return None if t is None or not ctx.answers else t / ctx.answers
