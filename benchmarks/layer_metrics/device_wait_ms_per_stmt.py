"""Time the host was blocked on the device (``device.wait``: every
``device_get``), per statement.
Mean over the statements of the window (``program_spans.py``).
Source: program span."""

from benchmarks import program_spans


def read(ctx):
    return program_spans.mean_ms(ctx, "device_wait")
