"""Subquery-join-and-group statements' share of their roofline (TPC-H
Q18 whole): the least time the chip's memory could take to read, once,
every column the statement names in each of its tables, with their
validity masks and each table's selection mask (benchmarks/work.py;
HBM-bound on one chip; lineitem once, though the statement names it
twice), over the device-op time the span spent, all ops counted. The
bytes are the statement's, whatever implements it: the subquery's group
table, its HAVING and compaction, the semi-join's rank, the joins'
gathers and the outer group table are the implementation's own traffic.
Source: profiler trace."""

ROOFLINE = "subqjoin_roofline"


def read(ctx):
    return ctx.roofline_pct(ROOFLINE)
