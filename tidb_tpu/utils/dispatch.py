"""Device-dispatch accounting.

Every dispatch is a program launch or a transfer the host waits on, and
a statement should pay O(1) of them, not O(ops). This module keeps a
process-global counter incremented at the engine's device choke points:

  - every invocation of a ``cached_jit`` kernel (the local executor
    engine's compiled expression/sort/join/agg programs)
  - every mesh fragment dispatch (``ShardCache.get_fragment``)
  - every host->device staging transfer (``parallel.partition``)

``execdetails`` snapshots the counter around each operator's open/next
so EXPLAIN ANALYZE shows per-operator dispatch counts — the visibility
knob the reference gets from its coprocessor request counters
(ref: util/execdetails CopRuntimeStats' distsql request counts).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable

import jax

from tidb_tpu.utils import tracing

__all__ = ["record", "launch", "count", "counted_jit", "record_xfer",
           "xfer_bytes", "record_fetch", "device_get", "record_spill", "spill_bytes",
           "compile_seconds"]

# thread-local: the server runs each connection's queries on its own
# thread, so per-operator EXPLAIN ANALYZE deltas must not absorb a
# concurrent session's kernel launches
_tls = threading.local()


def record(n: int = 1, site: str = "other") -> None:
    """Count n device round trips (program launches or transfers)."""
    _tls.count = getattr(_tls, "count", 0) + n
    by = getattr(_tls, "by_site", None)
    if by is None:
        by = _tls.by_site = {}
    by[site] = by.get(site, 0) + n
    # process-wide mirror: /metrics exposes dispatch totals so external
    # drivers (benchmarks/program_counters.py: dispatches_per_stmt) read
    # the engine's own figure instead of re-deriving it — the
    # thread-local stays the per-query source for EXPLAIN ANALYZE deltas
    from tidb_tpu.utils.metrics import DISPATCH_TOTAL

    DISPATCH_TOTAL.inc(n, site=site)


class launch(tracing.span):
    """``with launch(site)``: one device round trip, counted, and timed
    as the span ``dispatch.<site>`` of the statement's trace — the
    count and the span are made by the same line, so a statement's
    ``dispatch.*`` spans number its dispatches. The span covers the
    host's part (the launch is asynchronous); the wait for the device
    is ``device.wait`` (``device_get``)."""

    __slots__ = ("_site",)

    def __init__(self, site: str):
        super().__init__("dispatch." + site)
        self._site = site

    def __enter__(self):
        record(site=self._site)
        return super().__enter__()


def event(site: str) -> None:
    """Count a per-site EVENT without touching the device round-trip
    totals: by_site() observers (tests, profiling) see it, but EXPLAIN
    ANALYZE dispatch deltas, stmt-summary dispatch counts, and the
    /metrics dispatch totals stay honest. Used for engine milestones
    that are observable like dispatches but aren't one (e.g. one CTE
    materialization per WITH body)."""
    by = getattr(_tls, "by_site", None)
    if by is None:
        by = _tls.by_site = {}
    by[site] = by.get(site, 0) + 1


def count() -> int:
    return getattr(_tls, "count", 0)


def record_xfer(nbytes: int, direction: str = "h2d") -> None:
    """Count host↔device transfer BYTES on this thread (ISSUE 16
    resource profiles). Called at the existing staging/fetch choke
    points AFTER the transfer completes — never a new device sync. The
    thread-local feeds the per-statement profile; the process-wide
    mirror feeds /metrics."""
    n = int(nbytes)
    if n <= 0:
        return
    _tls.xfer = getattr(_tls, "xfer", 0) + n
    from tidb_tpu.utils.metrics import XFER_BYTES

    XFER_BYTES.inc(n, dir=direction)


def xfer_bytes() -> int:
    return getattr(_tls, "xfer", 0)


def record_fetch(tree):
    """Record a COMPLETED device→host fetch's bytes (d2h, and the count
    ``bytes`` of the span it is called under: ``device.wait``) and
    return the tree unchanged (the arrays are host-resident by the time
    this sums nbytes, so the accounting itself never blocks)."""
    n = sum(getattr(leaf, "nbytes", 0)
            for leaf in jax.tree_util.tree_leaves(tree))
    record_xfer(n, "d2h")
    tracing.add("bytes", n)
    return tree


def device_get(tree, counted: bool = True):
    """The one place where the host waits for the device:
    ``jax.device_get`` (a whole pytree in one transfer) inside the span
    ``device.wait``, the fetched bytes booked (``XFER_BYTES{d2h}`` and
    the span's count ``bytes``), and — `counted` — the
    round trip as a ``fetch`` dispatch. Not `counted`: the per-chunk
    fetches of the host tiers and the exchange-overflow scalar, which
    the dispatch budget (O(1) a statement) has never held. The lint
    passes know the fetch by this name, as they know jax's."""
    with launch("fetch") if counted else contextlib.nullcontext():
        with tracing.span("device.wait"):
            # the span's two parts: until the device has the arrays
            # ready, then what is left of their copy to the host and
            # the conversion to numpy. The copies are asked for FIRST,
            # as jax.device_get itself does, so that each follows its
            # program on the device with no host round trip between:
            # asked for after the wait, every fetch that had to wait
            # paid one more (PERF.md section 6, PR 37: +0.6-1.2% on
            # q18agg's statement of 25 fetches)
            leaves = jax.tree_util.tree_leaves(tree)
            for leaf in leaves:
                if isinstance(leaf, jax.Array):
                    leaf.copy_to_host_async()
            with tracing.phase("ready"):
                jax.block_until_ready(leaves)
            with tracing.phase("copy"):
                host = jax.device_get(tree)
            return record_fetch(host)


def record_spill(nbytes: int) -> None:
    """Count bytes this thread's statement spilled to disk (the
    process-wide SPILL_BYTES/SPILL_SEGMENT_BYTES metrics move at the
    spill sites themselves)."""
    _tls.spill = getattr(_tls, "spill", 0) + int(nbytes)


def spill_bytes() -> int:
    return getattr(_tls, "spill", 0)


def record_compile(kernel: str = "join") -> None:
    """Count one kernel (re)trace on this thread. Called from inside
    traced jit bodies (they only execute at trace time), so the counter
    moves on real XLA compilations — EXPLAIN ANALYZE diffs it around
    each operator to surface per-operator recompiles, and the statement
    trace (if one is active) gets the event as a span annotation."""
    _tls.compiles = getattr(_tls, "compiles", 0) + 1
    tracing.annotate(f"recompile:{kernel}")


def compile_count() -> int:
    return getattr(_tls, "compiles", 0)


def _record_compile_seconds(s: float) -> None:
    _tls.compile_s = getattr(_tls, "compile_s", 0.0) + float(s)
    from tidb_tpu.utils.metrics import COMPILE_SECONDS

    COMPILE_SECONDS.inc(float(s))


def compile_seconds() -> float:
    """Wall seconds this thread spent tracing+compiling fragments
    (first invocation per jit entry per shape — where XLA compiles
    synchronously), attributed to the statement that triggered them."""
    return getattr(_tls, "compile_s", 0.0)


def by_site() -> dict:
    """Cumulative per-site breakdown (for profiling, not EXPLAIN)."""
    return dict(getattr(_tls, "by_site", {}))


def counted_jit(fn: Callable, site: str = "jit", **jit_kwargs) -> Callable:
    """jax.jit with dispatch accounting on every invocation."""
    # lint: disable=jit-hygiene -- this IS the counting wrapper the
    # pass audits call sites of; identity discipline is the caller's
    jitted = jax.jit(fn, **jit_kwargs)
    sizer = getattr(jitted, "_cache_size", None)

    def counted(*args, **kwargs):
        with launch(site):
            if sizer is None:
                return jitted(*args, **kwargs)
            # compile-seconds attribution (ISSUE 16): a growing
            # executable cache means THIS invocation paid a
            # trace+compile — charge its wall time to the triggering
            # statement's thread. Warm calls pay two perf_counter reads
            # and one C++ cache-size probe.
            n0 = sizer()
            t0 = time.perf_counter()
            out = jitted(*args, **kwargs)
            if sizer() > n0:
                _record_compile_seconds(time.perf_counter() - t0)
            return out

    return counted
