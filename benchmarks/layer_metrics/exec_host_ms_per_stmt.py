"""Host work under the catalog lock that is neither planning nor waiting
for the device, per statement: ``stmt.*`` and ``session.execute`` self time,
the ``dispatch.*`` and ``fragment.*`` launches, result decode.
Mean over the statements of the window (``program_spans.py``).
Source: program span."""

from benchmarks import program_spans


def read(ctx):
    return program_spans.mean_ms(ctx, "exec_host")
