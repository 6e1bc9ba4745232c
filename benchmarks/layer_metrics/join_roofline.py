"""Join statements' share of their roofline: the least time for the key
and payload columns of both sides to be read once (and, across chips,
to cross the interconnect once; the run names which bound holds) over
the device-op time the span spent, all ops counted. Source: trace."""

ROOFLINE = "join_roofline"


def read(ctx):
    return ctx.roofline_pct(ROOFLINE)
