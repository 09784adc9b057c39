"""The push's share of its HBM roofline: the bytes its counted sweeps need
(``roofline.push_bytes``) over its device time, against the chips' HBM
bandwidth."""

from bench import roofline, trace


def read(ctx):
    t = trace.op_time_s(ctx.trace, trace.in_scope("jit(forward_push)"),
                        ctx.lo, ctx.hi)
    if not t:
        return None
    need = sum(roofline.push_bytes(c["sweeps"], ctx.n, ctx.m, c["batch"])
               for c in ctx.calls)
    return 100.0 * need / (t * ctx.chips * ctx.peaks["hbm_bytes_per_s"])
