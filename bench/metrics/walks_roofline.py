"""The walks' share of their HBM roofline: the bytes FORA's walk count
needs (``roofline.walk_bytes``) over their device time, against the chips'
HBM bandwidth."""

from bench import roofline, trace


def read(ctx):
    t = trace.op_time_s(ctx.trace, trace.in_scope("jit(residual_walks)"),
                        ctx.lo, ctx.hi)
    if not t:
        return None
    need = sum(roofline.walk_bytes(r, ctx.omega, ctx.alpha)
               for c in ctx.calls for r in c["r_sum"])
    return 100.0 * need / (t * ctx.chips * ctx.peaks["hbm_bytes_per_s"])
