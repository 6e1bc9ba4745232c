"""Time a statement waited for a scheduler worker (``sched.queue``) and
for the catalog statement lock (``sched.lock_wait``), per statement.
Mean over the statements of the window (``program_spans.py``).
Source: program span."""

from benchmarks import program_spans


def read(ctx):
    return program_spans.mean_ms(ctx, "queue")
