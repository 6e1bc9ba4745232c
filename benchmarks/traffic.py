"""The one general traffic generator: a mix is a data file
(``traffic/<name>.json``) and this reads it.

    loop          "closed": a stream sends its next statement when the
                  last one returned (TPC-H's power and throughput streams)
    streams       connections, one thread each
    menu          the statements by name, each with its substitution
                  parameters; every seed runs the same menu
    warm_passes   times every menu entry runs on every connection in set-up
    trace_seconds length of the profiler's span in a --trace 1 run
    writers       optional: the streams that write, each
                  ``{"statement": name, "params": {...},
                  "warm_transactions": n}`` — a closed loop of its own on
                  its own connection, whose transaction k goes out when
                  transaction k-1 was acknowledged (no think time); `n`
                  transactions run in set-up, before the warm passes, so
                  that the queries are warm at the version the window
                  starts from. `streams` and `menu` are the query streams.

Every seed gets the same menu (the same work); the seed decides the
order: each stream cycles through its own shuffle of the menu. A
writer's transactions are a function of the seed and k
(``statements/<name>.py source``).
"""

from __future__ import annotations

import numpy as np


def stream_orders(traffic: dict, seed: int) -> list:
    """Per stream, a permutation of the menu's indices."""
    if traffic.get("loop") != "closed":
        raise ValueError(f"loop {traffic.get('loop')!r}: only 'closed' exists")
    n = len(traffic["menu"])
    return [[int(i) for i in np.random.default_rng(
        [int(seed), 0x7F1C, s]).permutation(n)]
        for s in range(int(traffic["streams"]))]


def writers(traffic: dict) -> list:
    """The writer streams of a mix; none in a mix that only reads. The
    reference follows ONE sequence of commits (``reference.Versions``),
    so a second writer is refused rather than compared wrongly."""
    out = list(traffic.get("writers", ()))
    if len(out) > 1:
        raise ValueError(f"{len(out)} writers: the comparison follows one")
    return out
