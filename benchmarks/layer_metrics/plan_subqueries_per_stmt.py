"""Subqueries that planning ran as statements of their own, per
statement: the count ``subqueries_run``, which the program adds to the
innermost open span (``session.plan``, or a span under it) each time the
planner hands a subquery to the session to answer before the statement's
own plan is built (an uncorrelated scalar subquery folded to a literal,
an uncorrelated EXISTS, a CTE body made a table). A count: it repeats
exactly. 0.0 where the window's traces carry phases and none carries
the count (no subquery ran at plan time). Mean over the statements of
the window (``program_parts.py``). Nothing to read from a program
without phases or counts. Source: program span."""

from benchmarks import program_parts


def read(ctx):
    return program_parts.count_mean(ctx, "subqueries_run")
