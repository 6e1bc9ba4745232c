"""Scan-and-aggregate statements' share of their roofline: the least
time the chip's memory could take for the bytes each statement has to
read (benchmarks/work.py; HBM-bound) over the device-op time the span
spent, all ops counted. Source: profiler trace."""

ROOFLINE = "scan_agg_roofline"


def read(ctx):
    return ctx.roofline_pct(ROOFLINE)
