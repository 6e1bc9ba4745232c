"""What a layer's own time is made of, read from inside the program: the
phases and counts the program keeps ON a span (``tidb_tpu.utils.tracing``
``phase`` / ``add``, PR 37), as per-statement means over the window's
request traces, and the spans of set-up's traces. Beside ``system.py`` and
``program_spans.py`` the third file of the benchmark that imports the
program, and of it ``tidb_tpu.utils.tracing`` only.

A phase is no span: it takes nothing out of its span's self time, so the
five groups of ``program_spans.py`` read as without it. The keys are
``"<span name>/<phase>"`` -> [microseconds, calls] (``Trace.phases_us``)
and ``"<span name>/<count>"`` -> n (``Trace.counts``).

A program whose traces have no ``phases_us`` (the parent of the PR that
brought it) gives ``None``: the readers then report nothing.
"""

from __future__ import annotations

from benchmarks import program_spans


def _window(ctx):
    """(phase microseconds by key, counts by key, statements), summed
    over the window's traces; None where there is nothing to read."""
    memo = getattr(ctx, "_program_parts", None)
    if memo is None:
        traces = program_spans.window_traces(ctx)
        if not traces or not hasattr(traces[0], "phases_us"):
            return None
        phases, counts = {}, {}
        for tr in traces:
            for key, (us, _calls) in tr.phases_us().items():
                phases[key] = phases.get(key, 0) + us
            for key, n in tr.counts().items():
                counts[key] = counts.get(key, 0) + n
        memo = ctx._program_parts = (phases, counts, len(traces))
    return memo


def phases_mean_ms(ctx, span: str, phases: tuple):
    """Mean ms per statement of the window that `span`'s named phases
    took together; 0.0 where the window's traces hold none of them."""
    parts = _window(ctx)
    if parts is None:
        return None
    by_key, _counts, n = parts
    return sum(by_key.get(f"{span}/{p}", 0) for p in phases) / 1e3 / n


def count_mean(ctx, count: str, span: str = None):
    """Mean per statement of the window of the count `count`, on the
    spans named `span` or, without it, on every span of the trace."""
    parts = _window(ctx)
    if parts is None:
        return None
    _phases, by_key, n = parts
    if span is not None:
        return by_key.get(f"{span}/{count}", 0) / n
    return sum(v for k, v in by_key.items()
               if k.rpartition("/")[2] == count) / n


def setup_traces(ctx):
    """The request traces of set-up's warm statements: the roots that
    opened inside ``ctx.warm``'s intervals (one statement at a time, so
    the clock tells them). None without the ring or without phases."""
    from tidb_tpu.utils import tracing

    finished = getattr(tracing.STORE, "finished", None)
    if finished is None or not ctx.warm \
            or not hasattr(tracing.Trace, "phases_us"):
        return None
    out = []
    for tr in finished():
        root = tr.root()
        if root is None or root.name != "wire.stmt":
            continue
        t0 = tr.interval_perf()[0]
        if any(a / 1e9 <= t0 <= b / 1e9 for a, b in ctx.warm):
            out.append(tr)
    return out
