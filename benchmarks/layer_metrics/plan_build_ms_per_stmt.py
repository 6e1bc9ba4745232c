"""What ``session.plan`` holds besides the optimizer: its phases ``cache``
(the plan cache's eligibility, analysis, digest and lookup), ``privs`` (the
privilege check of the plan's tables) and ``build`` (the executor tree and
its routing to an engine), per statement. A part of ``plan_ms_per_stmt``
(``program_parts.py``). Mean over the statements of the window. Nothing to
read from a program without phases. Source: program span."""

from benchmarks import program_parts


def read(ctx):
    return program_parts.phases_mean_ms(
        ctx, "session.plan", ("cache", "privs", "build"))
