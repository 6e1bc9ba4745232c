"""Slots of the compactions a statement's fragment launches took, per
statement: the count ``compact_slots``, which the program adds to each
general fragment's launch span (``fragment.<kind>[parts=N]``), the sum of
the static capacities its compactions move the live rows into (twice the
planner's estimate of the rows a join side, a join's output or an
aggregate's input holds; more after a relaunch grew one). Every later
sort, gather and exchange of the program pays for these slots, so an
estimate that is too high shows here; the process-wide form is the
counter FRAGMENT_COMPACT_SLOTS. A count: it repeats exactly. Mean over the
statements of the window (``program_parts.py``). Nothing to read from a
program whose launches carry no such count. Source: program span."""

from benchmarks import program_parts


def read(ctx):
    parts = program_parts._window(ctx)
    if parts is None or not any(k.endswith("/compact_slots") for k in parts[1]):
        return None
    return program_parts.count_mean(ctx, "compact_slots")
