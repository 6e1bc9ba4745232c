"""Pipelined device-resident fragment execution (ISSUE 9 / ROADMAP 3).

Three pieces collapse the per-chunk host ping-pong of the single-chip
executor spine into a push-based, device-resident pipeline:

  * ``FusedScanAggExec`` — scan→filter→project→partial-agg as ONE
    module-level jitted program per chunk. The scan's staged inputs
    (encoded segment payloads or raw slices) and the running agg state
    are the only things that cross the jit boundary; the [G]-shaped
    (segment strategy) or group-table (generic strategy) state
    accumulates ON DEVICE across chunks and is fetched exactly once at
    finalize. Columnar segments pack SEVERAL per batch at a fixed
    ``seg_cap`` stride inside one capacity-sized buffer, so a fragment
    over a 64k-row segment store still issues ~n/chunk_capacity
    dispatches, not one per segment.

  * ``ChunkPrefetcher`` — double-buffered host→device staging: while
    chunk *k* computes, a staging thread builds chunk *k+1*'s host
    buffers and ``jax.device_put``s them, with the in-flight window
    bounded by ``tidb_tpu_pipeline_prefetch_depth`` and charged to the
    statement MemTracker. KILL/deadline is polled inside the thread
    (``raise_if_cancelled``) so a cancelled statement stops staging,
    not just computing.

  * ``DeviceBufferCache`` — staged scan inputs kept device-resident
    ACROSS statements, keyed and invalidated exactly like the plan
    cache: any ``catalog.schema_version`` bump clears it eagerly (the
    same hook that clears the plan cache), and per-entry identity pins
    ``Table.version`` / ``Table.data_epoch`` / the stats object / the
    segment store generation, so DML, DDL, ANALYZE and TRUNCATE all
    invalidate. A warm TPC-H Q1/Q6 re-run stages nothing.

ISSUE 10 extends fusion past aggregation roots: ``FusedScanProbeExec``
runs an inner hash join's probe side — decode + filter + project + key
pack + probe + first-tile expansion — as ONE jitted program per staged
chunk against a device-resident build table, with the build side itself
parked in the ``DeviceBufferCache`` so a warm repeated join stages and
sorts nothing. See the class docstring for the overflow/deferral
contract.

Placement: on a non-CPU backend everything above lives and runs on the
accelerator — staged scan buffers, ``DeviceBufferCache`` entries, build
tables, carried state and the fused programs. The fused execs enter
``utils.device.device_tier()`` around their staging/build/dispatch
loops (the tree walk around them is pinned to the host by
``host_eager``), and the prefetch thread — which does not inherit jax's
thread-local default device — stages to ``accelerator_device()``
captured in the constructor. Glue (finalize over a few groups, result
decode) stays on the host.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tidb_tpu.chunk.chunk import Chunk
from tidb_tpu.chunk.column import Column
from tidb_tpu.executor.aggregate import HashAggExec, make_segment_kernel
from tidb_tpu.executor.base import ExecContext, Executor, raise_if_cancelled
from tidb_tpu.executor.join import HashJoinExec
from tidb_tpu.ops import join_kernels as jk
from tidb_tpu.ops import prefix
from tidb_tpu.utils.device import device_tier, note_placement
from tidb_tpu.utils.jitcache import cached_jit
from tidb_tpu.utils.memory import QueryOOMError

__all__ = ["DEVICE_CACHE", "DeviceBufferCache", "ChunkPrefetcher",
           "FusedScanAggExec", "FusedScanProbeExec", "FusedScanTopNExec",
           "table_ident"]


def table_ident(table) -> tuple:
    """Everything a cached staged buffer's validity depends on — the
    plan cache's invalidation axes applied to data instead of plans:
    ``version`` moves on every DML (and TRUNCATE), ``data_epoch`` on
    in-place rewrites (column DDL, gc compaction, dict re-encode), the
    stats identity on ANALYZE, and the segment-store generation/coverage
    on columnar rebuilds. Schema-version bumps clear the whole cache
    eagerly via the catalog hook instead."""
    base = getattr(table, "_base", table)
    stats = getattr(base, "stats", None)
    store = getattr(base, "_segment_store", None)
    return (
        getattr(base, "version", None),
        getattr(base, "data_epoch", None),
        None if stats is None else (id(stats), stats.version),
        None if store is None else (store.generation, store.covered),
        getattr(table, "n", None),
    )


def _pytree_nbytes(tree) -> int:
    return int(sum(getattr(leaf, "nbytes", 0)
                   for leaf in jax.tree_util.tree_leaves(tree)))


class DeviceBufferCache:
    """Process-global LRU of staged device scan inputs.

    One entry = one (table, staging layout) pair holding the full list
    of staged per-chunk pytrees a fused fragment consumed, plus the
    identity tuple that proves them current. The entry pins the table
    object (like plan-cache entries) so a recycled ``id()`` can never
    alias a different table; the byte budget
    (``tidb_tpu_device_buffer_cache_bytes``) bounds resident bytes with
    LRU eviction."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, dict]" = OrderedDict()
        self._bytes = 0

    def _count(self, kind: str, n: int = 1) -> None:
        from tidb_tpu.utils.metrics import DEVICE_CACHE_TOTAL

        DEVICE_CACHE_TOTAL.inc(n, kind=kind)

    def get(self, table, tag, ident) -> Optional[List]:
        key = (id(table), tag)
        with self._lock:
            e = self._entries.get(key)
            if e is not None and e["table"] is table and e["ident"] == ident:
                self._entries.move_to_end(key)
                self._count("hit")
                return e["chunks"]
            if e is not None:
                # same statement shape, stale data: the plan cache's
                # stats/DML invalidation analogue
                self._bytes -= e["nbytes"]
                del self._entries[key]
                self._count("invalidate")
        self._count("miss")
        return None

    def put(self, table, tag, ident, chunks: List, nbytes: int,
            budget: int) -> None:
        if budget <= 0 or nbytes > budget:
            return
        key = (id(table), tag)
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old["nbytes"]
            self._entries[key] = {"table": table, "ident": ident,
                                  "chunks": chunks, "nbytes": int(nbytes)}
            self._bytes += int(nbytes)
            while self._bytes > budget and len(self._entries) > 1:
                _k, ev = self._entries.popitem(last=False)
                self._bytes -= ev["nbytes"]
                self._count("evict")

    def resident(self) -> list:
        """Snapshot of the cached device buffers:
        [(table, tag, chunks, nbytes)]."""
        with self._lock:
            return [(e["table"], key[1], e["chunks"], e["nbytes"])
                    for key, e in self._entries.items()]

    def on_schema_change(self) -> None:
        """Eager clear on any catalog.schema_version bump (DDL) — the
        exact hook the plan cache invalidates through."""
        with self._lock:
            n = len(self._entries)
            self._entries.clear()
            self._bytes = 0
        if n:
            self._count("invalidate", n)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0

    def resident_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


DEVICE_CACHE = DeviceBufferCache()


class ChunkPrefetcher:
    """Double-buffered host→device staging ahead of the compute loop.

    ``jobs`` is an ordered list of zero-arg callables, each returning
    one chunk's HOST pytree (numpy buffers). A daemon thread runs them
    in order, ``jax.device_put``s the result onto the staging device
    captured in the constructor (thread-locals like ``device_tier`` do
    not cross threads), and parks when ``depth`` buffers sit staged but
    unconsumed. In-flight staged bytes are charged to the statement
    MemTracker — a tight ``tidb_mem_quota_query`` surfaces as the same
    typed OOM/spill behavior as any other operator state. KILL and
    statement deadlines are polled before every job AND while parked,
    so a cancelled statement never keeps staging in the background."""

    POLL_S = 0.05

    def __init__(self, jobs: List[Callable], ctx: ExecContext, stats=None):
        from tidb_tpu.utils.device import accelerator_device

        self.jobs = jobs
        self.ctx = ctx
        self.stats = stats
        self.depth = max(int(getattr(ctx, "prefetch_depth", 0) or 0), 0)
        self.tracker = ctx.mem_tracker.child("pipeline.prefetch")
        self._device = accelerator_device()  # None = the backend is CPU
        self._staged: Dict[int, Tuple[object, int]] = {}
        self._err: Optional[BaseException] = None
        self._next_get = 0
        self._cv = threading.Condition()
        self._stop = False
        self._thread = None
        if self.depth > 0 and len(jobs) > 1:
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="tidb-tpu-prefetch")
            self._thread.start()

    # -- staging -----------------------------------------------------------

    def _stage(self, host) -> Tuple[object, int]:
        from tidb_tpu.utils import dispatch as dsp
        from tidb_tpu.utils.metrics import PIPELINE_PREFETCH_BYTES

        nbytes = _pytree_nbytes(host)
        if self._device is not None:
            staged = jax.device_put(host, self._device)
        else:
            staged = jax.device_put(host)
        dsp.record(site="stage")
        note_placement("stage", staged)
        PIPELINE_PREFETCH_BYTES.inc(nbytes)
        return staged, nbytes

    def _run(self) -> None:
        from tidb_tpu.utils.metrics import PIPELINE_PREFETCH_TOTAL

        try:
            for i, job in enumerate(self.jobs):
                with self._cv:
                    while (not self._stop
                           and i - self._next_get >= self.depth):
                        # parked on a full window: keep honoring
                        # KILL/deadline while the consumer computes
                        raise_if_cancelled(self.ctx)
                        self._cv.wait(self.POLL_S)
                    if self._stop:
                        return
                raise_if_cancelled(self.ctx)
                staged, nbytes = self._stage(job())
                self.tracker.consume(nbytes)  # typed OOM propagates below
                with self._cv:
                    if self._stop:
                        self.tracker.release(nbytes)
                        return
                    self._staged[i] = (staged, nbytes)
                    self._cv.notify_all()
        except BaseException as e:  # noqa: BLE001 — relayed to the
            # consumer thread verbatim via get(); the typed
            # kill/deadline/OOM classification must survive the hop
            from tidb_tpu.errors import QueryKilledError, QueryTimeoutError

            # keep the counter honest: "cancelled" means KILL/deadline
            # stopped staging; quota OOM or a staging bug is "error"
            cancelled = isinstance(e, (QueryKilledError, QueryTimeoutError))
            PIPELINE_PREFETCH_TOTAL.inc(
                outcome="cancelled" if cancelled else "error")
            with self._cv:
                self._err = e
                self._cv.notify_all()

    # -- consumption -------------------------------------------------------

    def get(self, i: int):
        """Chunk i's staged device pytree, blocking on in-flight staging."""
        from tidb_tpu.utils import dispatch as dsp
        from tidb_tpu.utils.metrics import PIPELINE_PREFETCH_TOTAL

        if self._thread is None:
            staged, nbytes = self._stage(self.jobs[i]())
            dsp.record_xfer(nbytes, "h2d")
            PIPELINE_PREFETCH_TOTAL.inc(outcome="inline")
            return staged
        with self._cv:
            self._next_get = max(self._next_get, i + 1)
            self._cv.notify_all()
            ready = i in self._staged
            while i not in self._staged and self._err is None:
                raise_if_cancelled(self.ctx)
                self._cv.wait(self.POLL_S)
            if i not in self._staged:
                raise self._err
            staged, nbytes = self._staged.pop(i)
            self._cv.notify_all()
        self.tracker.release(nbytes)
        # h2d accounting lands HERE (the consuming statement thread),
        # not in _stage on the daemon thread — the thread-local profile
        # must attribute the staged bytes to the statement that asked
        dsp.record_xfer(nbytes, "h2d")
        PIPELINE_PREFETCH_TOTAL.inc(outcome="hit" if ready else "wait")
        if ready and self.stats is not None:
            self.stats.staged += 1
        return staged

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
            leftover = sum(n for _v, n in self._staged.values())
            self._staged.clear()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        if leftover:
            self.tracker.release(leftover)


# ---------------------------------------------------------------------------
# fused scan→filter→project→partial-agg programs
# ---------------------------------------------------------------------------


def _barrier_chunk(chunk):
    """Materialization boundary between the scan pipeline and the agg
    update INSIDE the fused program. Without it XLA fuses the
    decode+filter+projection chain into every aggregate consumer and
    recomputes it once per state array — a fused Q1 measured ~1.5x
    SLOWER than the two-dispatch tree it replaced. The barrier keeps
    one kernel launch while pinning the scan's outputs to be computed
    once, exactly like the unfused path's intermediate chunk."""
    return jax.tree_util.tree_map(jax.lax.optimization_barrier, chunk)


def _make_fused_segment_fn(stages, col_types, group_exprs, aggs, domains,
                           seg_cap: Optional[int]):
    """(state, data, valid, refs, sel) -> state: decode + pipeline +
    segment-agg update as ONE program.

    Batches whose length is a multiple of ``seg_cap`` stream through an
    INTERNAL ``lax.scan`` over seg_cap-sized blocks: one device
    dispatch covers the whole packed batch (the single-digit dispatch
    budget) while each scan step touches only a cache-sized block —
    running the update over a monolithic 1M-row batch measurably lost
    to the chunk-synced path on XLA:CPU purely on locality (its 64k
    chunks stayed L2-resident). Per-step FoR refs arrive as scan-sliced
    scalars, so the decode is a scalar add, not a gather."""
    from tidb_tpu.ops.segment_scan import make_segment_scan_fn

    scan_fn = make_segment_scan_fn(stages, col_types, seg_stride=seg_cap)
    _init, update, _g = make_segment_kernel(group_exprs, aggs, domains)

    def run(state, data, valid, refs, sel):
        n = sel.shape[0]
        if not seg_cap or n <= seg_cap or n % seg_cap:
            return update(state, _barrier_chunk(scan_fn(data, valid, refs,
                                                        sel)))
        k = n // seg_cap
        bdata = {u: d.reshape((k, seg_cap) + d.shape[1:])
                 for u, d in data.items()}
        bvalid = {u: v.reshape(k, seg_cap) for u, v in valid.items()}
        bsel = sel.reshape(k, seg_cap)

        def step(st, xs):
            d, v, r, sl = xs
            return update(st, _barrier_chunk(scan_fn(d, v, r, sl))), None

        state, _ = jax.lax.scan(step, state, (bdata, bvalid, refs, bsel))
        return state

    return run


def _make_fused_generic_fn(stages, col_types, group_exprs, aggs,
                           seg_cap: Optional[int]):
    """(data, valid, refs, sel) -> group table: decode + pipeline +
    sort-based partial grouping as ONE program. No internal blocking
    here: the partial is a whole-batch sort (one big lax.sort beats
    per-block sorts + extra merge levels), and its output shape is the
    input capacity — per-block tables would just re-create the stack's
    merge work inside the program."""
    from tidb_tpu.executor.agg_device import make_partial_kernel
    from tidb_tpu.ops.segment_scan import make_segment_scan_fn

    scan_fn = make_segment_scan_fn(stages, col_types, seg_stride=seg_cap)
    partial = make_partial_kernel(group_exprs, aggs)

    def run(data, valid, refs, sel):
        return partial(_barrier_chunk(scan_fn(data, valid, refs, sel)))

    return run


class _StagedScanMixin:
    """The scan side of a fused fragment, shared by ``FusedScanAggExec``
    and ``FusedScanProbeExec``: plan the ordered chunk staging schedule
    (packed columnar segments with zone-map pruning, raw slices for the
    uncovered tail), stream the staged device pytrees through the
    prefetcher, and ride the cross-statement ``DeviceBufferCache``.
    Requires ``table``, ``scan_schema``, ``prune_bounds``, ``ctx``,
    ``stats``, and the ``_pin``/``_prefetcher``/``_seg_cap`` slots."""

    def _release_staging(self) -> None:
        it = getattr(self, "_staged_iter", None)
        if it is not None:
            it.close()  # runs the generator's finally (fill release)
            self._staged_iter = None
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None
        if self._pin is not None:
            self._pin.close()
            self._pin = None
        if getattr(self, "_staged_scan_counted", False):
            self._staged_scan_counted = False
            self.table.txn_guard.scan_exit()

    # -- staging plan ------------------------------------------------------

    def _plan_staging(self, ctx: ExecContext):
        """The ordered chunk staging schedule (a list of zero-arg host
        staging jobs). Columnar
        segments pack ``k = capacity // seg_cap`` per batch at a fixed
        stride; the uncovered delta tail stages as raw capacity-sized
        slices. Zone maps prune segments BEFORE anything is staged,
        exactly like the unfused scan."""
        cap = ctx.chunk_capacity
        table = self.table
        # count as an open scan for the staging window: raw-tail slices
        # and live_mask reads hit the table's live arrays lock-free, so
        # a CLUSTER BY permute must refuse until _release_staging runs
        guard = getattr(table, "txn_guard", None)
        if guard is not None and not getattr(
                self, "_staged_scan_counted", False):
            guard.scan_enter()
            self._staged_scan_counted = True
        jobs = []
        tail_start = 0
        self._seg_cap = None
        if ctx.columnar_enable:
            from tidb_tpu.columnar.store import ScanPin, store_for

            store = store_for(
                table, segment_rows=ctx.segment_rows,
                delta_rows=ctx.segment_delta_rows,
                spill_dir=ctx.columnar_spill_dir or None,
                compaction=ctx.compaction_enable)
            if store is not None:
                self._pin = ScanPin(store, ctx.mem_tracker)
                segs, pruned, covered = store.plan_scan(
                    self.prune_bounds, pin=self._pin)
                self.stats.segs_scanned += len(segs)
                self.stats.segs_pruned += pruned
                tail_start = covered
                seg_cap = 1
                while seg_cap < min(store.segment_rows, cap):
                    seg_cap *= 2
                self._seg_cap = seg_cap
                k = max(cap // seg_cap, 1)
                slots = []
                for seg in segs:
                    for s in range(0, seg.rows, seg_cap):
                        slots.append((seg, s, min(s + seg_cap, seg.rows)))
                for i in range(0, len(slots), k):
                    batch = slots[i:i + k]
                    # the tail batch sizes to ITS slot count: padding it
                    # to k segments would run the internal scan over
                    # dead all-zero blocks (13/16 of a 1M buffer for a
                    # 3-segment tail — measured ~0.8s of pure waste)
                    jobs.append(self._seg_batch_job(batch, len(batch),
                                                    seg_cap))
                if not slots:
                    self._pin.close()  # nothing to stage: drop refs now
                    self._pin = None
        n = table.n
        for s in range(tail_start, n, cap):
            e = min(s + cap, n)
            jobs.append(self._raw_slice_job(s, e, cap))
        return jobs

    def _seg_batch_job(self, batch, k: int, seg_cap: int):
        """Stage up to k encoded segments into ONE [k * seg_cap] buffer
        set. Payloads keep their narrow encoded dtypes (promoted to the
        widest within the batch); per-segment FoR bases travel as [k]
        vectors, decoded on device against an iota-derived segment id.
        MVCC visibility is read fresh from the table's arrays."""
        table, pin, schema, ctx = self.table, self._pin, self.scan_schema, \
            self.ctx

        def job():
            bcap = k * seg_cap
            sel = np.zeros(bcap, dtype=np.bool_)
            per_col: Dict[str, list] = {c.uid: [] for c in schema}
            for j, (seg, s, e) in enumerate(batch):
                pin.touch(seg)
                off = j * seg_cap
                n = e - s
                for c in schema:
                    if c.name == "__rowid__":
                        per_col[c.uid].append(("rowid", seg.start + s, n))
                    else:
                        enc, sd, sv = seg.col(c.name)
                        # slices VIEW the (immutable) payload arrays;
                        # the views keep them alive past an eviction
                        per_col[c.uid].append((enc, sd[s:e], sv[s:e]))
                sel[off:off + n] = table.live_mask(
                    seg.start + s, seg.start + e,
                    read_ts=ctx.read_ts, marker=ctx.txn_marker)
            data, valid, refs = {}, {}, {}
            for c in schema:
                uid = c.uid
                entries = per_col[uid]
                if c.name == "__rowid__":
                    d = np.zeros(bcap, dtype=np.int64)
                    v = np.zeros(bcap, dtype=np.bool_)
                    for j, (_tag, start0, n) in enumerate(entries):
                        off = j * seg_cap
                        d[off:off + n] = np.arange(start0, start0 + n,
                                                   dtype=np.int64)
                        v[off:off + n] = True
                    data[uid], valid[uid] = d, v
                    continue
                dt = entries[0][1].dtype
                for _enc, sd, _sv in entries[1:]:
                    dt = np.promote_types(dt, sd.dtype)
                any_for = any(enc.kind == "for" for enc, _d, _v in entries)
                d = np.zeros(bcap, dtype=dt)
                v = np.zeros(bcap, dtype=np.bool_)
                rv = np.zeros(k, dtype=np.int64)
                for j, (enc, sd, sv) in enumerate(entries):
                    off = j * seg_cap
                    n = len(sd)
                    d[off:off + n] = sd
                    v[off:off + n] = sv
                    if enc.kind == "for":
                        rv[j] = enc.ref
                data[uid], valid[uid] = d, v
                if any_for:
                    refs[uid] = rv
            return data, valid, refs, sel

        return job

    def _raw_slice_job(self, s: int, e: int, cap: int):
        table, schema, ctx = self.table, self.scan_schema, self.ctx

        def job():
            n = e - s
            data, valid = {}, {}
            for c in schema:
                if c.name == "__rowid__":
                    d = np.zeros(cap, dtype=np.int64)
                    d[:n] = np.arange(s, e, dtype=np.int64)
                    v = np.zeros(cap, dtype=np.bool_)
                    v[:n] = True
                else:
                    cd, cv = table.column_slice(c.name, s, e)
                    d = np.zeros(cap, dtype=cd.dtype)
                    d[:n] = cd
                    v = np.zeros(cap, dtype=np.bool_)
                    v[:n] = cv
                data[c.uid], valid[c.uid] = d, v
            sel = np.zeros(cap, dtype=np.bool_)
            sel[:n] = table.live_mask(
                s, e, read_ts=ctx.read_ts, marker=ctx.txn_marker)
            return data, valid, {}, sel

        return job

    # -- staged chunk stream (prefetch + device buffer cache) --------------

    def _staged_chunks(self, jobs):
        """Yield staged device pytrees in chunk order: from the device
        buffer cache when a warm identical statement already staged
        them, else through the double-buffered prefetcher — filling the
        cache on the way out when everything fits the budget."""
        ctx = self.ctx
        budget = int(getattr(ctx, "device_buffer_cache_bytes", 0) or 0)
        cacheable = (budget > 0 and jobs
                     and ctx.read_ts is None and ctx.txn_marker == 0)
        tag = ident = None
        if cacheable:
            # the chunk-set descriptor (descs) is deliberately NOT part
            # of the tag: it is a deterministic function of (table
            # identity, bounds, capacities), so folding it into the
            # key would turn every DML into a silent key change (stale
            # entry leaks until LRU) instead of a counted invalidation
            tag = ("scanstage",
                   tuple((c.uid, c.name) for c in self.scan_schema),
                   ctx.chunk_capacity, self._seg_cap,
                   repr(self.prune_bounds))
            ident = table_ident(self.table)
            hit = DEVICE_CACHE.get(self.table, tag, ident)
            if hit is not None:
                self.stats.staged += len(hit)
                for staged in hit:
                    yield staged
                return
        pf = ChunkPrefetcher(jobs, ctx, stats=self.stats)
        self._prefetcher = pf
        collect: Optional[list] = [] if cacheable else None
        # the fill holds every staged buffer alive until put(): that
        # working set is charged to the STATEMENT tracker while the
        # fragment runs (ownership transfers to the process-level cache
        # at put). Quota pressure must abandon the fill, never fail the
        # query — and the fill must not even APPROACH the budget, or
        # the other consumers (prefetch window, segment pins) would OOM
        # against consumption the fill inflated: stop filling past half
        # the statement's remaining headroom.
        fill_tracker = ctx.mem_tracker.child("pipeline.cache_fill")
        stmt_budget = getattr(ctx.mem_tracker, "budget", None)
        nbytes = 0

        def abandon():
            nonlocal collect, nbytes
            collect = None
            fill_tracker.release(nbytes)
            nbytes = 0

        try:
            for i in range(len(jobs)):
                staged = pf.get(i)
                if collect is not None:
                    b = _pytree_nbytes(staged)
                    if nbytes + b > budget:
                        abandon()  # too big to pin: stream through
                    elif stmt_budget and (ctx.mem_tracker.consumed + b
                                          > stmt_budget // 2):
                        abandon()  # leave the quota to the real work
                    else:
                        try:
                            fill_tracker.consume(b)
                        except QueryOOMError:
                            abandon()
                        else:
                            nbytes += b
                            collect.append(staged)
                yield staged
            if collect is not None:
                DEVICE_CACHE.put(self.table, tag, ident, collect, nbytes,
                                 budget)
        finally:
            fill_tracker.release(nbytes)


def _collect_feedback_pairs(root) -> list:
    """(plan_node, actual_out_rows) pairs of every annotated exec in a
    transient subtree whose actual is host-known — taken BEFORE the
    subtree is dropped, so plan feedback still sees e.g. the build-side
    join a fused probe drained inside its own open()."""
    out = []
    stack = [root]
    while stack:
        e = stack.pop()
        if e is None:
            continue
        p = getattr(e, "_feedback_plan", None)
        rows = getattr(getattr(e, "stats", None), "out_rows", -1)
        if p is not None and rows >= 0:
            out.append((p, int(rows)))
        out.extend(getattr(e, "_fb_build_pairs", ()))
        stack.extend(getattr(e, "children", ()))
        stack.append(getattr(e, "_delegate", None))
    return out


def _close_delegate(outer) -> None:
    """Close a fused exec's open()-time fallback delegate and preserve
    the feedback truth its subtree learned: the delegate's own
    host-known output count folds onto the OUTER exec's stats (they
    answer for the same plan node), and its annotated children's pairs
    park on _fb_build_pairs — plan feedback harvests after the tree is
    closed, when only the outer exec remains."""
    d, outer._delegate = outer._delegate, None
    if d is None:
        return
    d.close()  # first: nested fused execs fold their own delegates
    # EXPLAIN ANALYZE renders AFTER the tree is closed, so keep the
    # closed delegate reachable — analyze_text walks _fallback_taken to
    # show the classic subtree that actually ran under a [classic] node
    outer._fallback_taken = d
    st = getattr(d, "stats", None)
    if st is not None and st.out_rows >= 0:
        outer.stats.add_out_rows(st.out_rows)
    outer._fb_build_pairs = (tuple(outer._fb_build_pairs)
                             + tuple(_collect_feedback_pairs(d)))


class FusedScanAggExec(_StagedScanMixin, HashAggExec):
    """HashAgg whose child is a fusible scan pipeline, executed as a
    push-based device-resident fragment: staged inputs stream through
    ONE jitted program per chunk and the aggregation state never visits
    the host until finalize. Falls back to the classic pull-based tree
    (``fallback_build``) when the context disables fusion or the
    aggregate shape needs the host paths (DISTINCT, non-core funcs,
    ``tidb_enable_tpu_exec`` off for generic strategy)."""

    def __init__(self, schema, scan_schema, table, stages, prune_bounds,
                 group_exprs, group_uids, aggs, strategy,
                 segment_sizes=None, fallback_build=None):
        super().__init__(schema, None, group_exprs, group_uids, aggs,
                         strategy, segment_sizes=segment_sizes)
        self.children = []
        self.scan_schema = scan_schema
        self.table = table
        self.scan_stages = stages
        self.prune_bounds = prune_bounds
        self._fallback_build = fallback_build
        self._delegate = None
        self._ran_fused = False
        self._fb_build_pairs = ()
        self._pin = None
        self._prefetcher = None
        self._seg_cap = None

    # -- lifecycle ---------------------------------------------------------

    def open(self, ctx: ExecContext) -> None:
        self.ctx = ctx
        self._out = []
        self._emitted = False
        self._delegate = None
        if not self._fuse_eligible(ctx):
            self._ran_fused = False
            d = self._fallback_build()
            d.open(ctx)
            self._delegate = d
            return
        self._ran_fused = True
        try:
            if self.strategy == "segment":
                self._run_segment_fused()
            else:
                self._run_generic_fused()
        finally:
            self._release_staging()

    def next(self):
        if self._delegate is not None:
            return self._delegate.next()
        return super().next()

    def close(self) -> None:
        _close_delegate(self)
        self._release_staging()
        super().close()

    def _fuse_eligible(self, ctx: ExecContext) -> bool:
        if not getattr(ctx, "pipeline_fuse", True) or self.table is None:
            return False
        if self.strategy == "segment":
            return True
        from tidb_tpu.planner.logical import core_generic_agg

        return ctx.device_agg and core_generic_agg(self.group_exprs,
                                                   self.aggs)

    # -- fused execution ---------------------------------------------------

    def _run_segment_fused(self):
        from tidb_tpu.ops.segment_scan import segment_scan_key

        ctx = self.ctx
        domains = [s + 1 for s in (self.segment_sizes or [])]
        jobs = self._plan_staging(ctx)
        col_types = [(c.uid, c.type_) for c in self.scan_schema]
        stages, seg_cap = self.scan_stages, self._seg_cap
        key = ("seg|" + segment_scan_key(stages, col_types, seg_cap)
               + "|" + repr((self.group_exprs, self.aggs, domains)))
        fused = cached_jit(
            "fusedagg", key,
            lambda: _make_fused_segment_fn(stages, col_types,
                                           self.group_exprs, self.aggs,
                                           domains, seg_cap),
            donate_argnums=0)
        init_state, _u, _g = make_segment_kernel(
            self.group_exprs, self.aggs, domains)
        with device_tier():
            state = init_state()
            for staged in self._staged_chunks(jobs):
                # KILL/deadline polls BETWEEN device steps: the fusion
                # must not turn a chunked fragment into an
                # uninterruptible run
                raise_if_cancelled(ctx)
                state = fused(state, *staged)
            note_placement("fused", state)
        self._finalize_segment_state(state, domains)

    def _run_generic_fused(self):
        from tidb_tpu.executor.agg_device import GroupTableStack
        from tidb_tpu.ops.segment_scan import segment_scan_key

        ctx = self.ctx
        jobs = self._plan_staging(ctx)
        col_types = [(c.uid, c.type_) for c in self.scan_schema]
        stages, seg_cap = self.scan_stages, self._seg_cap
        sig = repr((self.group_exprs, self.aggs))
        key = ("gen|" + segment_scan_key(stages, col_types, seg_cap)
               + "|" + sig)
        fused = cached_jit(
            "fusedagg", key,
            lambda: _make_fused_generic_fn(stages, col_types,
                                           self.group_exprs, self.aggs,
                                           seg_cap))
        stack = GroupTableStack(len(self.group_exprs), self.aggs, sig)
        with device_tier():
            for staged in self._staged_chunks(jobs):
                raise_if_cancelled(ctx)  # see _run_segment_fused
                stack.push(fused(*staged))
            tables = stack.tables()
            note_placement("fused", tables)
        self._finalize_group_tables(tables)


# ---------------------------------------------------------------------------
# fused scan→probe programs (ISSUE 10: fusion past aggregation roots)
# ---------------------------------------------------------------------------


def _make_fused_probe_fn(stages, col_types, key_irs, modes, probe_uids,
                         direct: bool, probe: str, left: bool,
                         seg_cap: Optional[int]):
    """(staged scan inputs, build arrays) -> (first output tile, totals,
    probe state): decode + filter + project + key pack + probe range
    lookup + count + prefix sum + first-tile expansion as ONE program.

    The expansion emits a single FIXED-capacity tile (the chunk's own
    capacity) inside the same dispatch — for the workhorse PK-FK shape
    (Q18's lineitem→orders) every probe row matches at most once, so the
    whole chunk's output fits and the chunk completes in ONE device
    round trip. The on-device ``total`` doubles as the overflow flag:
    the caller's batched window fetch reads it, and only chunks whose
    expansion overflowed the in-program tile pay classic ``expand_tiles``
    dispatches for the remainder. The probe's range lookup runs through
    ``probe_ranges_any`` — the SAME traced step as the standalone
    probe kernel (direct-address index / open-addressing table /
    searchsorted), so the fused and classic paths cannot drift.

    ISSUE 18 widens the shape: composite keys pack through the SAME
    ``jk.pack_keys`` range packer as the standalone probe (the traced
    pack ranges arrive as args), and LEFT OUTER pads every live
    unmatched probe row with one NULL-build-payload slot in-program —
    ``real_count`` rides the deferral token so the overflow
    re-expansion masks the pad slots identically."""
    from tidb_tpu.expression.compiler import eval_expr
    from tidb_tpu.ops.segment_scan import make_segment_scan_fn

    scan_fn = make_segment_scan_fn(stages, col_types, seg_stride=seg_cap)

    def run(data, valid, refs, sel, sorted_keys, n_build, firsts,
            lo_packed, rng_packed, tkeys, tlos, this, tok,
            los, strides, rngs, b_datas, b_valids):
        ch = _barrier_chunk(scan_fn(data, valid, refs, sel))
        kds, kvs = [], []
        for ir in key_irs:
            kd, kv = eval_expr(ir, ch)
            kds.append(kd)
            kvs.append(kv)
        packed, kvalid, pack_ok = jk.pack_keys(
            kds, kvs, los, strides, rngs, ch.sel, modes, False)
        ok = kvalid & ch.sel
        start, end, range_ok = jk.probe_ranges_any(
            sorted_keys, n_build, packed, firsts, lo_packed, rng_packed,
            tkeys, tlos, this, tok, direct, probe)
        in_range = pack_ok & range_ok
        count = jnp.where(ok & in_range, end - start, 0)
        real_count = count
        if left:
            # unfiltered LEFT JOIN: every live probe row emits >= 1
            # slot; the pad slot carries NULL build payload (the
            # classic probe's left_pad arithmetic, traced here)
            count = jnp.where(ch.sel, jnp.maximum(count, 1), 0)
        cum = prefix.cumsum(count)
        total = cum[-1]
        R = packed.shape[0]
        B = sorted_keys.shape[0]
        valid_out, probe_row, build_pos, k = jk.tile_positions(
            start, count, cum, 0, R, R, B)
        p_cols = tuple((ch.columns[u].data, ch.columns[u].valid)
                       for u in probe_uids)
        out_p = tuple((jnp.take(d, probe_row, mode="clip"),
                       jnp.take(v, probe_row, mode="clip") & valid_out)
                      for d, v in p_cols)
        bmask = valid_out
        if left:
            bmask = bmask & (k < jnp.take(real_count, probe_row,
                                          mode="clip"))
        out_b = tuple((jnp.take(d, build_pos, mode="clip"),
                       jnp.take(v, build_pos, mode="clip") & bmask)
                      for d, v in zip(b_datas, b_valids))
        return (out_p, out_b, valid_out, total, start, count, real_count,
                cum, p_cols)

    return run


class FusedScanProbeExec(_StagedScanMixin, HashJoinExec):
    """Inner or LEFT OUTER hash join (single- or composite-key, ISSUE
    18) whose probe side is a plain scan pipeline, run
    as a push-based device fragment (ISSUE 10): each staged probe chunk
    streams through ONE jitted scan→probe→expand program against a
    device-resident build table, cutting the classic tree's per-chunk
    scan dispatch + probe dispatch + expand dispatch(es) to a single
    round trip for the PK-FK shape. Per-chunk match totals stay on
    device and resolve in one batched fetch per deferral window
    (PROBE_SYNC_CHUNKS), exactly like the classic probe's deferral —
    the fused path adds no per-chunk host syncs.

    The build side runs the classic ``HashJoinExec`` build (drain +
    pack + sort + direct/hash index) and — when the build child is
    itself a plain scan over a stored table — parks the finished device
    arrays in the cross-statement ``DeviceBufferCache`` keyed by the
    build plan's shape and proven current by ``table_ident``, so a warm
    repeated join stages and sorts NOTHING. Ineligible contexts
    (fusion/device engine off) fall back to the classic tree through
    the open()-time ``fallback_build`` delegate, like
    ``FusedScanAggExec``."""

    def __init__(self, schema, scan_schema, table, stages, prune_bounds,
                 probe_schema, probe_keys, build_keys, build_schema,
                 build_child_build, build_table=None, build_tag=None,
                 kind="inner", fallback_build=None):
        Executor.__init__(self, schema, [])
        self.kind = kind
        self.probe_keys = probe_keys
        self.build_keys = build_keys
        self.other_cond = None
        self.probe_schema = probe_schema
        self.build_schema = build_schema
        self.exists_sem = False
        self.scan_schema = scan_schema
        self.table = table
        self.scan_stages = stages
        self.prune_bounds = prune_bounds
        self._build_child_build = build_child_build
        self._build_cache_table = build_table
        self._build_cache_tag = build_tag
        self._fallback_build = fallback_build
        self._delegate = None
        self._ran_fused = False
        self._fb_build_pairs = ()
        self._pin = None
        self._prefetcher = None
        self._staged_iter = None
        self._seg_cap = None

    # -- lifecycle ---------------------------------------------------------

    def open(self, ctx: ExecContext) -> None:
        self.ctx = ctx
        self._delegate = None
        self._pending: List[Chunk] = []
        self._drained = False
        if not self._fuse_eligible(ctx):
            self._ran_fused = False
            d = self._fallback_build()
            d.open(ctx)
            self._delegate = d
            return
        self._ran_fused = True
        try:
            with device_tier():
                self._open_build(ctx)
            if self._hash_mode:
                # composite-key ranges overflowed int64 range packing
                # (data-dependent, known only after the build drain):
                # hash candidates need the classic probe's exact per-key
                # re-verification after expansion, so keep the classic
                # tree — its feedback pairs were parked by _open_build
                self._ran_fused = False
                d = self._fallback_build()
                d.open(ctx)
                self._delegate = d
                return
            jobs = self._plan_staging(ctx)
            with device_tier():
                self._fused_fn = self._make_fused()
            self._staged_iter = self._staged_chunks(jobs)
        except BaseException:
            self._release_staging()
            raise

    def next(self) -> Optional[Chunk]:
        if self._delegate is not None:
            return self._delegate.next()
        while True:
            if self._pending:
                return self._pending.pop(0)
            if self._drained:
                return None
            with device_tier():
                self._fill_pending_fused()

    def close(self) -> None:
        _close_delegate(self)
        self._release_staging()
        super().close()  # releases the build side's tracked bytes

    def _fuse_eligible(self, ctx: ExecContext) -> bool:
        if not getattr(ctx, "pipeline_fuse", True) or self.table is None:
            return False
        # the fused program is a device fragment: host-engine routing
        # (device_agg off) keeps the classic tree and its numpy probe
        return bool(getattr(ctx, "device_agg", True))

    # -- build side (classic build + cross-statement device cache) ---------

    # everything a warm statement needs to probe without re-draining the
    # build child: the staged device arrays AND the host-side pack/index
    # decisions derived from the drained build data
    _BUILD_STATE_FIELDS = (
        "_sorted_keys", "_n_build_dev", "_firsts", "_build_payload",
        "_build_keyvals_dev", "_payload_uids", "_pack_info", "_hash_mode",
        "_modes", "_los", "_strides", "_rngs", "_direct", "_direct_lo",
        "_direct_rng", "_n_build", "_build_had_null", "_has_filter",
        "_probe_mode", "_probe_table", "_build_bytes")

    def _open_build(self, ctx: ExecContext) -> None:
        from tidb_tpu.utils.metrics import JOIN_BUILD_SECONDS

        from tidb_tpu.ops import hash_probe as hp

        budget = int(getattr(ctx, "device_buffer_cache_bytes", 0) or 0)
        bt = self._build_cache_table
        cacheable = (budget > 0 and bt is not None
                     and ctx.read_ts is None and ctx.txn_marker == 0)
        tag = ident = None
        if cacheable:
            # the RESOLVED probe mode joins the tag: the parked state
            # bakes in the mode's table/index decision, and a knob
            # change must mint a fresh build, not serve a stale one
            tag = ("joinbuild", self._build_cache_tag,
                   hp.resolve_mode(getattr(ctx, "join_probe_mode", "off")))
            ident = table_ident(bt)
            hit = DEVICE_CACHE.get(bt, tag, ident)
            if hit is not None:
                t0 = time.perf_counter()
                self._restore_build(hit[0])
                self.stats.staged += 1
                JOIN_BUILD_SECONDS.observe(time.perf_counter() - t0,
                                           tier="cached")
                return
        child = self._build_child_build()
        child.open(ctx)
        self.children = [None, child]
        try:
            self._build()  # HashJoinExec._build: drains children[1]
        finally:
            child.close()
            self.children = []
            # the transient build subtree is gone after this open();
            # park its host-known actuals for the feedback harvest
            self._fb_build_pairs = _collect_feedback_pairs(child)
        if cacheable:
            # ownership of the resident arrays transfers to the process
            # cache; the statement keeps its charge until close() like
            # any other build (the _staged_chunks fill pattern)
            DEVICE_CACHE.put(bt, tag, ident, [self._snapshot_build()],
                             self._build_bytes, budget)

    def _snapshot_build(self) -> dict:
        return {f: getattr(self, f) for f in self._BUILD_STATE_FIELDS}

    def _restore_build(self, state: dict) -> None:
        for f, v in state.items():
            setattr(self, f, v)
        self._sorted_keys_np = None
        self._build_payload_np = {}
        self._build_schema_by_uid = {c.uid: c
                                     for c in (self.build_schema or [])}
        # the resident bytes are owned (and budgeted) by the process
        # cache on a hit — close() must not release them
        self._build_bytes = 0

    # -- fused probe loop --------------------------------------------------

    def _make_fused(self):
        from tidb_tpu.ops.segment_scan import segment_scan_key

        col_types = [(c.uid, c.type_) for c in self.scan_schema]
        probe_uids = tuple(c.uid for c in self.probe_schema)
        stages, seg_cap = self.scan_stages, self._seg_cap
        probe = "sorted" if self._probe_table is None else self._probe_mode
        self._fused_probe_label = "direct" if self._direct else probe
        # per-statement invariants, hoisted off the per-chunk hot loop:
        # the direct-domain device scalars and the payload arg tuples
        # are fixed once the build completes
        self._direct_lo_dev = jnp.asarray(self._direct_lo, dtype=jnp.int64)
        self._direct_rng_dev = jnp.asarray(self._direct_rng,
                                           dtype=jnp.int64)
        self._table_args = (self._probe_table
                            if self._probe_table is not None
                            else jk.no_table())
        self._b_datas = tuple(self._build_payload[u][0]
                              for u in self._payload_uids)
        self._b_valids = tuple(self._build_payload[u][1]
                               for u in self._payload_uids)
        key = ("probe|" + segment_scan_key(stages, col_types, seg_cap)
               + "|" + repr((self.probe_keys, self._modes, self._direct,
                             probe, probe_uids, self.kind,
                             tuple(self._payload_uids))))
        return cached_jit(
            "fusedprobe", key,
            lambda: _make_fused_probe_fn(
                stages, col_types, tuple(self.probe_keys),
                tuple(self._modes), probe_uids, self._direct, probe,
                self.kind == "left", seg_cap))

    def _fill_pending_fused(self) -> None:
        """Pull staged probe chunks until output lands in _pending or
        the scan drains. Every chunk's match total stays a device scalar
        inside its deferral token; ONE batched device_get per window
        resolves the whole window — the fused fragment syncs
        O(chunks / window), the same budget as the classic probe."""
        deferred: List[dict] = []
        dbytes = 0
        while not self._pending and not self._drained:
            raise_if_cancelled(self.ctx)
            staged = next(self._staged_iter, None)
            if staged is None:
                self._drained = True
                break
            tok = self._probe_chunk_fused(staged)
            deferred.append(tok)
            dbytes += tok["nbytes"]
            if (len(deferred) >= self.PROBE_SYNC_CHUNKS
                    or dbytes >= self.PROBE_DEFER_BYTES):
                self._finish_fused_batch(deferred)
                deferred = []
                dbytes = 0
        if deferred:
            self._finish_fused_batch(deferred)

    def _probe_chunk_fused(self, staged) -> dict:
        """Launch the fused scan→probe→expand program for one staged
        chunk; returns the deferral token pinning its device results."""
        from tidb_tpu.utils.metrics import JOIN_PROBE_MODE_TOTAL

        t0 = time.perf_counter()
        JOIN_PROBE_MODE_TOTAL.inc(mode="fused_" + self._fused_probe_label)
        data, valid, refs, sel = staged
        (out_p, out_b, sel_tile, total_dev, start, count, real_count,
         cum, p_cols) = \
            self._fused_fn(data, valid, refs, sel, self._sorted_keys,
                           self._n_build_dev, self._firsts,
                           self._direct_lo_dev, self._direct_rng_dev,
                           *self._table_args, self._los, self._strides,
                           self._rngs, self._b_datas, self._b_valids)
        tok = {"out_p": out_p, "out_b": out_b, "sel_tile": sel_tile,
               "total_dev": total_dev, "start": start, "count": count,
               "real_count": real_count, "cum": cum, "p_cols": p_cols,
               "cap": int(sel_tile.shape[0]), "t0": t0}
        note_placement("fused", (total_dev, sel_tile))
        # the window pins the chunk's expanded tile AND the probe state
        # needed for a potential overflow re-expansion
        tok["nbytes"] = _pytree_nbytes(
            (out_p, out_b, sel_tile, start, count, real_count, cum,
             p_cols))
        return tok

    def _finish_fused_batch(self, tokens: List[dict]) -> None:
        from tidb_tpu.utils import dispatch as dsp
        from tidb_tpu.utils.metrics import JOIN_PROBE_SECONDS

        # THE intentional probe sync, batched: one fetch of the
        # accumulated per-chunk match totals per deferred window — the
        # totals double as overflow flags, and fused chunks whose
        # expansion fit their in-program tile need nothing further
        # (sanctioned device_get outside any loop — the chunk-loop
        # sync-budget pass watches the loop form)
        totals = dsp.device_get([t["total_dev"] for t in tokens])
        # plan feedback: the fused inner PK-FK shape's summed totals are
        # its exact output cardinality, and total vs tile capacity is
        # the overflow telemetry that sizes join_tiles next time —
        # all host-known from the fetch this window already pays
        self.stats.add_out_rows(int(sum(int(t) for t in totals)))
        for tok, total in zip(tokens, totals):
            self.stats.tile_chunks += 1
            if int(total) > tok["cap"]:
                self.stats.tile_overflows += 1
                need = -(-(int(total) - tok["cap"]) // tok["cap"])
                self.stats.tile_max_need = max(self.stats.tile_max_need,
                                               need)
        for tok, total in zip(tokens, totals):
            try:
                self._emit_fused(tok, int(total))
            finally:
                JOIN_PROBE_SECONDS.observe(time.perf_counter() - tok["t0"],
                                           kind="fused")

    def _emit_fused(self, tok: dict, total: int) -> None:
        """Complete one fused chunk with its host-known total: emit the
        in-program tile, then expand any overflow past the tile through
        the classic fixed-capacity tile dispatches."""
        if total == 0:
            return
        cap = tok["cap"]
        cols = {}
        for c, (d, v) in zip(self.probe_schema, tok["out_p"]):
            cols[c.uid] = Column(d, v, c.type_)
        for uid, (d, v) in zip(self._payload_uids, tok["out_b"]):
            cols[uid] = Column(d, v, self._build_schema_by_uid[uid].type_)
        self._pending.append(Chunk(cols, tok["sel_tile"]))
        self.stats.chunks += 1
        if total <= cap:
            return
        # dup-heavy overflow: slots [cap, total) expand through
        # expand_tiles against the SAME device arrays (start/count/cum
        # and the scan-produced probe columns are already resident)
        p_datas = tuple(d for d, _v in tok["p_cols"])
        p_valids = tuple(v for _d, v in tok["p_cols"])
        b_datas, b_valids = self._b_datas, self._b_valids
        max_tiles = max(1, getattr(self.ctx, "join_tiles", 8))
        w0 = cap
        while w0 < total:
            rem = -(-(total - w0) // cap)  # ceil-div: tiles still needed
            T = min(jk.shape_bucket(rem, floor=1), max_tiles)
            out_p, out_b, sel_t, _pr, _bp = jk.expand_tiles(
                tok["start"], tok["count"], tok["real_count"],
                tok["cum"], w0, p_datas, p_valids, b_datas, b_valids,
                n_tiles=T, tile_cap=cap,
                build_cap=self._sorted_keys.shape[0],
                left=self.kind == "left")
            for i in range(min(T, rem)):
                cols = {}
                for c, (d2, v2) in zip(self.probe_schema, out_p):
                    cols[c.uid] = Column(d2[i], v2[i], c.type_)
                for uid, (d2, v2) in zip(self._payload_uids, out_b):
                    cols[uid] = Column(d2[i], v2[i],
                                       self._build_schema_by_uid[uid].type_)
                self._pending.append(Chunk(cols, sel_t[i]))
                self.stats.chunks += 1
            w0 += T * cap


# ---------------------------------------------------------------------------
# fused scan→top-k programs (ISSUE 18: fusing the operator long tail)
# ---------------------------------------------------------------------------


def _make_fused_topn_fn(stages, col_types, sort_irs, descs, out_uids,
                        seg_cap: Optional[int]):
    """(state, staged scan inputs) -> state: decode + filter + project +
    per-chunk top-k merge as ONE program. The bounded top-k state (the
    C = shape_bucket(offset + count) current winners, ops/topk.py
    layout) is the only thing carried between chunks — exactly the
    fused aggregate's state contract, so the scan never materializes to
    host and the winners are fetched once at finalize."""
    from tidb_tpu.expression.compiler import eval_expr
    from tidb_tpu.ops import topk as tk
    from tidb_tpu.ops.segment_scan import make_segment_scan_fn

    scan_fn = make_segment_scan_fn(stages, col_types, seg_stride=seg_cap)

    def run(state, data, valid, refs, sel):
        ch = _barrier_chunk(scan_fn(data, valid, refs, sel))
        pairs = tuple(tk.rank_operands(*eval_expr(ir, ch), desc)
                      for ir, desc in zip(sort_irs, descs))
        payload = tuple((ch.columns[u].data, ch.columns[u].valid)
                        for u in out_uids)
        return tk.topk_merge(state, pairs, payload, ch.sel, descs)

    return run


class FusedScanTopNExec(_StagedScanMixin, Executor):
    """ORDER BY [+ LIMIT] root whose child is a plain scan pipeline,
    run as a push-based device fragment (ISSUE 18): each staged chunk
    streams through ONE jitted scan→top-k program that folds the
    chunk's rows into a bounded device state of the current
    ``offset + count`` winners; the host fetches the winners exactly
    once at finalize. The classic ``TopNExec`` pays one device_get per
    chunk (it materializes EVERY child row to host runs before
    ``np.lexsort`` keeps k of them) — here the full-table host round
    trip disappears and the sort work per chunk is one cheap
    single-array cut to C candidates (single sort key; ops/topk.py
    ``_cut_single_key``) or one ``lax.sort`` over C + chunk_capacity
    rows (multi-key).

    A full ORDER BY (no LIMIT) takes the same path under a capacity
    gate — when every live row fits the state (``table.n <= capacity``)
    the "top n" IS the complete sort; larger inputs keep the classic
    materializing sort via the open()-time ``fallback_build`` delegate.
    A LIMIT whose ``offset + count`` exceeds the gate falls back the
    same way and records the k-overflow on the exec (plan feedback
    harvests it, so the digest's SECOND execution routes to the classic
    plan up front instead of re-paying the fallback probe).

    Ordering is bit-exact with the classic path: ops/topk.py replicates
    ``_sort_order``'s null-rank/negation semantics and ties resolve by
    global drain position, the device analogue of np.lexsort stability.
    """

    def __init__(self, schema, scan_schema, table, stages, prune_bounds,
                 items, count, offset, full_sort=False,
                 fallback_build=None):
        Executor.__init__(self, schema, [])
        self.scan_schema = scan_schema
        self.table = table
        self.scan_stages = stages
        self.prune_bounds = prune_bounds
        self.items = items
        self.count = count
        self.offset = offset
        self.full_sort = full_sort
        self._fallback_build = fallback_build
        self._delegate = None
        self._ran_fused = False
        self._topn_overflow = 0
        self._fb_build_pairs = ()
        self._pin = None
        self._prefetcher = None
        self._seg_cap = None

    # -- lifecycle ---------------------------------------------------------

    def open(self, ctx: ExecContext) -> None:
        self.ctx = ctx
        self._chunks: List[Chunk] = []
        self._delegate = None
        self._topn_overflow = 0
        k, eligible = self._state_rows(ctx)
        if not eligible:
            self._ran_fused = False
            d = self._fallback_build()
            d.open(ctx)
            self._delegate = d
            return
        self._ran_fused = True
        try:
            self._run_fused(ctx, k)
        finally:
            self._release_staging()

    def next(self) -> Optional[Chunk]:
        if self._delegate is not None:
            return self._delegate.next()
        if self._chunks:
            return self._chunks.pop(0)
        return None

    def close(self) -> None:
        _close_delegate(self)
        self._release_staging()
        super().close()

    def _state_rows(self, ctx: ExecContext):
        """(k, fuse?) — k the live-row bound the device state must hold
        (offset + count, or the whole table for a full sort). The gate
        is the chunk capacity: the per-chunk merge sorts C + capacity
        rows, so a state larger than one chunk loses the asymptotic
        win over the classic path anyway. Overflow is recorded on the
        exec for the feedback harvest (satellite: a digest whose
        LIMIT + offset proved too big plans classic next time)."""
        # no device_agg gate: like the segment-strategy fused agg, the
        # top-k state program wins on every backend (it removes the
        # classic path's per-chunk host materialization), so host-engine
        # routing does not demote it
        if not getattr(ctx, "pipeline_fuse", True) or self.table is None:
            return 0, False
        if not getattr(ctx, "fused_topn", True):
            return 0, False  # plan feedback routed this digest classic
        if not self.items:
            return 0, False
        gate = int(ctx.chunk_capacity)
        if self.full_sort:
            k = int(self.table.n)
        else:
            k = int(self.count) + int(self.offset)
        if k > gate:
            self._topn_overflow = k
            return k, False
        return k, True

    # -- fused execution ---------------------------------------------------

    def _run_fused(self, ctx: ExecContext, k: int) -> None:
        from tidb_tpu.ops import topk as tk
        from tidb_tpu.ops.segment_scan import segment_scan_key
        from tidb_tpu.utils import dispatch as dsp

        jobs = self._plan_staging(ctx)
        col_types = [(c.uid, c.type_) for c in self.scan_schema]
        stages, seg_cap = self.scan_stages, self._seg_cap
        cap_state = jk.shape_bucket(k, floor=64)
        sort_irs = tuple(e for e, _ in self.items)
        descs = tuple(bool(d) for _, d in self.items)
        out_uids = tuple(c.uid for c in self.schema)
        key = ("topn|" + segment_scan_key(stages, col_types, seg_cap)
               + "|" + repr((self.items, out_uids, cap_state)))
        fused = cached_jit(
            "fusedtopk", key,
            lambda: _make_fused_topn_fn(stages, col_types, sort_irs,
                                        descs, out_uids, seg_cap),
            donate_argnums=0)
        key_floats = tuple(tk.key_spec(e.type_) for e in sort_irs)
        dtypes = tuple(c.type_.np_dtype for c in self.schema)
        with device_tier():
            state = tk.topk_init(cap_state, key_floats, dtypes)
            for staged in self._staged_chunks(jobs):
                # KILL/deadline polls BETWEEN device steps: the fusion
                # must not turn a chunked fragment into an
                # uninterruptible run
                raise_if_cancelled(ctx)
                state = fused(state, *staged)
            note_placement("fused", state[0])
        # THE intentional top-k sync: ONE fetch of the C winners at
        # finalize, however many chunks streamed through (sanctioned
        # device_get outside any loop — the chunk-loop sync-budget pass
        # watches the loop form)
        dead, _ranks, _pos, _next, payload = state
        host = dsp.device_get((dead, payload))
        self._emit_winners(*host)

    def _emit_winners(self, dead, payload) -> None:
        """Slice [offset, offset + count) of the live winners (the
        state is already in final sort order — dead slots sort last)
        into capacity-sized output chunks."""
        n_live = int((np.asarray(dead) == 0).sum())
        lo = 0 if self.full_sort else min(int(self.offset), n_live)
        hi = n_live if self.full_sort else min(
            int(self.offset) + int(self.count), n_live)
        self.stats.add_out_rows(hi - lo)
        cap = self.ctx.chunk_capacity
        for s in range(lo, hi, cap):
            e = min(s + cap, hi)
            cols = {}
            for c, (d, v) in zip(self.schema, payload):
                cols[c.uid] = Column.from_numpy(
                    np.asarray(d)[s:e], c.type_,
                    valid=np.asarray(v)[s:e], capacity=cap)
            sel = np.zeros(cap, dtype=np.bool_)
            sel[:e - s] = True
            self._chunks.append(Chunk(cols, sel))
            self.stats.chunks += 1
