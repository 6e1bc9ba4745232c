"""Time on the connection thread, per statement: ``wire.stmt``'s self time
(hand-over to the scheduler and back) and ``wire.write`` (the result's
encoding and socket writes).
Mean over the statements of the window (``program_spans.py``).
Source: program span."""

from benchmarks import program_spans


def read(ctx):
    return program_spans.mean_ms(ctx, "wire")
