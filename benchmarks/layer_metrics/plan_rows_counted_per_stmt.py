"""Rows of table state the host read to COUNT a table's live rows
(``Table.live_rows`` adds the table's physical rows to the count
``rows_counted`` of the span it was called under, at every call), per
statement, over every span of the trace. A count: it repeats exactly, and
is the calls times the tables' rows. 0 where a statement's planning asks no
table for its count. Mean over the statements of the window
(``program_parts.py``). Nothing to read from a program without counts.
Source: program span."""

from benchmarks import program_parts


def read(ctx):
    return program_parts.count_mean(ctx, "rows_counted")
