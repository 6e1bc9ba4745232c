"""Host time between the general fragment's launch and the first chunk
its operator hands on (``fragment.finalize``'s self time: the decode of
the fetched group tables, their concatenation and the cut into chunks;
the fetch itself is its ``device.wait`` child and counts there), per
statement. Mean over the statements of the window (``program_spans.py``).
Nothing to read from a program that has no such span. Source: program
span."""

from benchmarks import program_spans

SPAN = "fragment.finalize"


def read(ctx):
    traces = program_spans.window_traces(ctx)
    if not traces:
        return None
    us = [tr.self_us_by_name()[SPAN] for tr in traces
          if SPAN in tr.self_us_by_name()]
    if not us:
        return None
    return sum(us) / 1e3 / len(traces)
