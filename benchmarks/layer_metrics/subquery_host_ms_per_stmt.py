"""Host time a statement spends answering a subquery as a statement of
its own, to hand its rows to the fragment that asked: the self time of
``fragment.broadcast`` and of every span beneath it (the subtree's own
launches and fetches, its ``device.wait``, the HAVING and projection on
the host, the upload of the padded build side), per statement. Mean over
the statements of the window (``program_spans.py``). 0.0 where the
window has traces and none holds the span: the subquery ran inside the
fragment's own program, or the statement has none. Nothing to read from
a program without the ring of traces. A program that sends the subquery
through the host and has no such span reads 0.0 here and shows it in
``dispatches_per_stmt``. Source: program span."""

from benchmarks import program_spans

SPAN = "fragment.broadcast"


def subtree_self_us(trace) -> int:
    """Self time of every span named SPAN and of all their descendants."""
    by_id = {s.span_id: s for s in list(trace.spans)}
    if not any(s.name == SPAN for s in by_id.values()):
        return 0

    def beneath(s) -> bool:
        while s is not None and s.name != SPAN:
            s = by_id.get(s.parent_id)
        return s is not None

    self_us = trace.self_us()
    return sum(self_us.get(i, 0) for i, s in by_id.items() if beneath(s))


def read(ctx):
    traces = program_spans.window_traces(ctx)
    if not traces:
        return None
    return sum(subtree_self_us(tr) for tr in traces) / 1e3 / len(traces)
