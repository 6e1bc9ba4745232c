"""Device dispatches (the program's DISPATCH_TOTAL, all sites) in the
window per statement completed in it: a count, which repeats exactly.
Source: program counter."""


def read(ctx):
    if ctx.window_statements <= 0:
        return None
    return ctx.window_counters.get("dispatches", 0) / ctx.window_statements
