"""The optimizer's part of ``session.plan``: its phases ``bind`` (the
statement bound to the catalog: ``build_select``), ``rules`` (the logical
rewrites and the join order: ``optimize_logical``) and ``lower`` (the
physical plan), per statement. A part of ``plan_ms_per_stmt``, not beside
it: a phase is kept on its span and takes nothing out of the span's time
(``program_parts.py``). Mean over the statements of the window. Nothing to
read from a program without phases. Source: program span."""

from benchmarks import program_parts


def read(ctx):
    return program_parts.phases_mean_ms(
        ctx, "session.plan", ("bind", "rules", "lower"))
