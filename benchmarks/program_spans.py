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

In a cell with a writer these five stay the query streams' (they
partition what those clients measured); the writer's statements have
``writer_mean_ms``, per transaction.

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


def _split(ctx):
    """(the query streams' request traces, the writer's): the traces
    whose root opened between the stream's first send and its last
    completion in the window. (By the root's start, not its end: the
    connection thread closes the root a few microseconds after the client
    has its last packet.) None without the ring.

    A trace names no connection, and under the catalog lock a writer's
    statement and a query can share their interval to the millisecond,
    so in a cell with a writer the clock cannot tell them apart. The
    statement digest a ``trace_id`` begins with can: the menu's digests
    are those of the traces set-up's warm passes left (``ctx.warm``: one
    statement at a time), and the writer's traces are the others."""
    from tidb_tpu.utils import tracing

    finished = getattr(tracing.STORE, "finished", None)
    if finished is None or not ctx.records:
        return None
    roots = [(tr, tr.interval_perf()[0]) for tr in finished()
             if tr.root() is not None and tr.root().name == "wire.stmt"]
    lo = min(r["t_send"] for r in ctx.records) / 1e9
    hi = max(r["t_done"] for r in ctx.records) / 1e9
    writes = getattr(ctx, "writes", ())
    if not writes:
        return [tr for tr, t0 in roots if lo <= t0 <= hi], []
    digest = lambda tr: tr.trace_id.rpartition("-")[0]  # noqa: E731
    menu = {digest(tr) for tr, t0 in roots
            if any(a / 1e9 <= t0 <= b / 1e9 for a, b in ctx.warm)}
    if not menu:
        return None
    w_lo = min(w["t_send"] for w in writes) / 1e9
    w_hi = max((w["stmts"][-1][1] for w in writes if w["stmts"]),
               default=0) / 1e9
    return ([tr for tr, t0 in roots if lo <= t0 <= hi and digest(tr) in menu],
            [tr for tr, t0 in roots if w_lo <= t0 <= w_hi and digest(tr) not in menu])


def window_traces(ctx):
    """The request traces of the window's query statements."""
    split = _split(ctx)
    return None if split is None else split[0]


def writer_mean_ms(ctx, group: str):
    """Mean ms of `group` per acknowledged transaction of the window,
    summed over the transaction's statements; None without a writer."""
    acked = sum(1 for w in getattr(ctx, "writes", ()) if w["t_ack"] is not None)
    split = _split(ctx) if acked else None
    if not split or not split[1]:
        return None
    return sum(us for tr in split[1] for name, us in tr.self_us_by_name().items()
               if group_of(name) == group) / 1e3 / acked


def by_name_ms(ctx) -> dict:
    """Self time by span name, for the window's stdout line: mean ms per
    query statement and, in a cell with a writer, per acknowledged
    transaction. Information, not a metric."""
    split = _split(ctx)
    if not split:
        return {}
    acked = sum(1 for w in getattr(ctx, "writes", ()) if w["t_ack"] is not None)
    out = {}
    for key, traces, n in (("query", split[0], len(split[0])),
                           ("writer", split[1], acked)):
        total = {}
        for tr in traces:
            for name, us in tr.self_us_by_name().items():
                total[name] = total.get(name, 0) + us
        if n:
            out[key] = {name: round(us / 1e3 / n, 3) for name, us in sorted(
                total.items(), key=lambda kv: -kv[1])}
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
