"""Launches of mesh-tier fragment programs (the program's
FRAGMENT_DISPATCH, every kind) in the window per statement completed in
it: 1.0 where each statement is one fragment and no capacity blows; a
statement whose group table, exchange or compaction buffer overflows
launches its fragment again with the capacity grown. Every kind is
counted because a program before PR 28 labels a re-launch by the kind of
the knob that blew, not by the fragment's. Source: program counter."""


def read(ctx):
    n = [v for k, v in ctx.window_counters.items() if k.startswith("fragment:")]
    if not n or ctx.window_statements <= 0:
        return None
    return sum(n) / ctx.window_statements
