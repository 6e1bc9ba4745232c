"""Seconds of set-up the host spent putting tables on the device: the
durations of the spans ``stage.upload`` (``shard_table``: a table's columns
encoded, padded to the mesh's parts and handed to ``device_put``; one a
table a connection) summed over set-up's request traces
(``program_parts.setup_traces``). The span ends when ``device_put`` has
returned: what the transfers still owe is in the same statement's
``device.wait``. 0.0 where set-up's traces hold no such span. Nothing to
read from a program without it. Source: program span."""

from benchmarks import program_parts

SPAN = "stage.upload"


def read(ctx):
    traces = program_parts.setup_traces(ctx)
    if traces is None:
        return None
    return sum(max(s.dur_us, 0) for tr in traces for s in list(tr.spans)
               if s.name == SPAN) / 1e6
