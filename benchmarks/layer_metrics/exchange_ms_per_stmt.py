"""Device time of the collective ops (all-to-all, all-gather,
all-reduce ...), mean over the chips, per statement of the traced span.
Nothing to read on one chip, where XLA emits none. Source: trace."""


def read(ctx):
    t = ctx.trace
    if not t or ctx.traced_statements <= 0 or t["collective_ns_mean"] <= 0:
        return None
    return t["collective_ns_mean"] / 1e6 / ctx.traced_statements
