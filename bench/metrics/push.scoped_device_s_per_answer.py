"""Device seconds per answer of the push: the ops under the program's
``fora.push`` scope (the whole ``forward_push`` call, the exchange between
chips of each sweep included)."""

from bench import trace


def read(ctx):
    t = trace.op_time_s(ctx.trace, trace.in_scope("fora.push"),
                        ctx.lo, ctx.hi)
    return None if t is None or not ctx.answers else t / ctx.answers
