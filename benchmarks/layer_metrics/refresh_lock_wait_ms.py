"""Time the writer's statements (BEGIN, the INSERTs, COMMIT) waited for
a scheduler worker (``sched.queue``) and for the catalog statement lock
(``sched.lock_wait``), summed per transaction. Mean over the window's
acknowledged transactions (``program_spans.py``). Source: program span."""

from benchmarks import program_spans


def read(ctx):
    return program_spans.writer_mean_ms(ctx, "queue")
