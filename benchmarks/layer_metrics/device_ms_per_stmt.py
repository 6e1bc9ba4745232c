"""Device-op time (mean over the chips) per statement completed in the
traced span; a statement partly inside the span counts by the part of
its time that is inside. Source: profiler trace."""


def read(ctx):
    t = ctx.trace
    if not t or not t["devices"] or ctx.traced_statements <= 0:
        return None
    return t["op_ns_mean"] / 1e6 / ctx.traced_statements
