"""Megabytes (1e6 bytes) the host fetched from the device: ``device.wait``'s
count ``bytes``, the bytes of the arrays every ``device_get`` returned (what
the program books under ``XFER_BYTES{d2h}``), per statement. Mean over the
statements of the window (``program_parts.py``). Nothing to read from a
program without counts. Source: program span."""

from benchmarks import program_parts


def read(ctx):
    n = program_parts.count_mean(ctx, "bytes", span="device.wait")
    return None if n is None else n / 1e6
