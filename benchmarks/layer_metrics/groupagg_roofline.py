"""Grouped-aggregate statements' share of their roofline: the least time
the chip's memory could take to read the grouping key and the summed
column once, with their validity masks and the table's selection mask
(benchmarks/work.py; HBM-bound), over the device-op time the span spent,
all ops counted. The bytes are the statement's, whatever implements it:
sorts, exchanges and group tables are the implementation's own traffic.
Source: profiler trace."""

ROOFLINE = "groupagg_roofline"


def read(ctx):
    return ctx.roofline_pct(ROOFLINE)
