"""Statements of several joins' share of their roofline (TPC-H Q9
whole): the least time the chip's memory could take to read, once, every
column the statement names in each of its six tables, with their
validity masks and each table's selection mask (benchmarks/work.py;
HBM-bound on one chip), over the device-op time the span spent, all ops
counted. The bytes are the statement's, whatever implements it: the
joins' ranks, expansions and gathers and the group table are the
implementation's own traffic. Source: profiler trace."""

ROOFLINE = "multijoin_roofline"


def read(ctx):
    return ctx.roofline_pct(ROOFLINE)
