"""Share of the traced span in which no op ran on the device (the
busiest device where there are several). Source: profiler trace."""


def read(ctx):
    t = ctx.trace
    if not t or not t["devices"] or t["span_ns"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_ns_max"] / t["span_ns"])
