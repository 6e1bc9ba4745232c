"""The program's own spans of the window's statements, as per-statement
means by layer. Beside ``system.py`` the second file of the benchmark
that imports the program, and of it ``tidb_tpu.utils.tracing`` only: the
ring of every finished request trace (``STORE.finished()``), each trace
placed on ``time.perf_counter``'s clock — the clock ``run.py`` stamps
``t_send``/``t_done`` with — and its self time by span name.

A request's trace (root ``wire.stmt``) runs from the decoded command
packet to the last result packet written, so the five groups below
partition what the client measured, less the loopback's two hops:

    queue        sched.queue + sched.lock_wait (and a batched member's
                 sched.batch_pass)      waiting for a worker, for the
                                        catalog lock
    wire         wire.stmt's self time + wire.write
    plan         session.parse + session.plan
    device_wait  device.wait            the host blocked on the device
    exec_host    every other span: stmt.*, session.execute, dispatch.*,
                 fragment.* ...         host work under the lock

A program without the ring (the parent of the PR that brought it) gives
``None``: the readers then report nothing.
"""

from __future__ import annotations

GROUPS = ("queue", "wire", "plan", "exec_host", "device_wait")
_BY_NAME = {"sched.queue": "queue", "sched.lock_wait": "queue",
            "sched.batch_pass": "queue",
            "session.parse": "plan", "session.plan": "plan",
            "device.wait": "device_wait"}


def group_of(span_name: str) -> str:
    if span_name.startswith("wire."):
        return "wire"
    return _BY_NAME.get(span_name, "exec_host")


def window_traces(ctx):
    """The request traces of the window's statements: those whose root
    opened between the window's first send and its last completion. (By
    the root's start, not its end: the connection thread closes the
    root a few microseconds after the client has its last packet.)"""
    from tidb_tpu.utils import tracing

    finished = getattr(tracing.STORE, "finished", None)
    if finished is None or not ctx.records:
        return None
    lo = min(r["t_send"] for r in ctx.records) / 1e9
    hi = max(r["t_done"] for r in ctx.records) / 1e9
    out = []
    for tr in finished():
        root = tr.root()
        if root is not None and root.name == "wire.stmt" \
                and lo <= tr.interval_perf()[0] <= hi:
            out.append(tr)
    return out


def means_ms(ctx):
    """{group: mean ms per statement of the window}, or None."""
    memo = getattr(ctx, "_program_span_means", None)
    if memo is None:
        traces = window_traces(ctx)
        if not traces:
            return None
        total = dict.fromkeys(GROUPS, 0)
        for tr in traces:
            for name, us in tr.self_us_by_name().items():
                total[group_of(name)] += us
        memo = ctx._program_span_means = {
            g: us / 1e3 / len(traces) for g, us in total.items()}
    return memo


def mean_ms(ctx, group: str):
    means = means_ms(ctx)
    return None if means is None else means[group]
