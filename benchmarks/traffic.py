"""The one general traffic generator: a mix is a data file
(``traffic/<name>.json``) and this reads it.

    loop          "closed": a stream sends its next statement when the
                  last one returned (TPC-H's power and throughput streams)
    streams       connections, one thread each
    menu          the statements by name, each with its substitution
                  parameters; every seed runs the same menu
    warm_passes   times every menu entry runs on every connection in set-up
    trace_seconds length of the profiler's span in a --trace 1 run

Every seed gets the same menu (the same work); the seed decides the
order: each stream cycles through its own shuffle of the menu.
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
