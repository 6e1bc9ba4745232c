"""Uploads of a table's columns to the device (the program's
DISPATCH_TOTAL at site ``stage``: one a column, validity mask and
selection mask that ``shard_table`` puts on the mesh) in the window, per
statement the query streams completed in it. 0 where nothing is written:
a connection shards a table once, in set-up. Above 0, a statement found
the table at another version than its connection's copy and staged the
whole table again. Source: program counter."""


def read(ctx):
    if ctx.window_statements <= 0:
        return None
    return ctx.window_counters.get("dispatch:stage", 0) / ctx.window_statements
