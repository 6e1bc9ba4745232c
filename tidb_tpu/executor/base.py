"""Executor protocol and execution context."""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional

import numpy as np

from tidb_tpu.chunk.chunk import Chunk
from tidb_tpu.planner.binder import PlanCol

__all__ = ["ExecContext", "Executor", "ResultSet", "RuntimeStats",
           "run_plan", "raise_if_cancelled"]


def raise_if_cancelled(ctx: "ExecContext") -> None:
    """Poll the context's cancel hook (KILL flags + statement deadline).

    The hook may return a bool (legacy callers) or an exception instance
    carrying the cancellation REASON — a deadline expiry must surface as
    the MySQL "maximum statement execution time exceeded" error, not as
    a generic KILL. Every long executor loop (the chunk loop here, the
    streamed fragment loops on the dist tier) polls through this one
    function so the classification can never diverge."""
    if ctx.cancel_check is None:
        return
    r = ctx.cancel_check()
    if not r:
        return
    if isinstance(r, BaseException):
        raise r
    from tidb_tpu.errors import QueryKilledError

    raise QueryKilledError("Query execution was interrupted (KILL)")


@dataclass
class RuntimeStats:
    """Per-operator stats surfaced by EXPLAIN ANALYZE
    (ref: util/execdetails RuntimeStats)."""

    rows: int = 0
    chunks: int = 0
    open_wall: float = 0.0
    next_wall: float = 0.0
    # device round trips (kernel launches + transfers) issued while this
    # operator (incl. its children) ran — utils.dispatch deltas; EXPLAIN
    # ANALYZE shows own = cumulative - children's
    dispatches: int = 0
    # kernel (re)traces while this operator ran (dispatch.compile_count
    # deltas): nonzero on a warm re-execution means a shape key leaked
    # into traced code
    recompiles: int = 0
    # perf_counter of this operator's FIRST open/next activity — async
    # fragment dispatches overlap, and without a start offset EXPLAIN
    # ANALYZE / TRACE render them as if sequential
    first_ts: Optional[float] = None
    # columnar segment store (ISSUE 8): segments this scan skipped via
    # zone-map pruning vs segments it actually staged; zero/zero on
    # operators (or tables) without a segment store
    segs_pruned: int = 0
    segs_scanned: int = 0
    # pipelined execution (ISSUE 9): chunks whose staged device buffers
    # were already in place when the compute loop asked — prefetch hits
    # plus device-buffer-cache hits. EXPLAIN ANALYZE's `staged` column
    staged: int = 0
    # plan feedback (ISSUE 15): the planner's row estimate for the plan
    # node this executor answers for (-1 = unannotated), and the actual
    # output rows the operator learned HOST-SIDE FOR FREE (-1 = never
    # known without instrumentation): joins fill it from their already-
    # batched match-total fetches, aggregates from the group count at
    # finalize — no new per-chunk device syncs. `measured` marks rows as
    # exact (the instrument() wrapper counted every emitted chunk);
    # feedback harvest prefers `rows` then, else `out_rows`.
    est_rows: float = -1.0
    out_rows: int = -1
    measured: bool = False
    # fused scan→probe tile telemetry (feedback consumer: tile-capacity
    # sizing): chunks probed / chunks whose expansion overflowed the
    # in-program tile / the worst ceil(overflow/cap) tile need seen
    tile_chunks: int = 0
    tile_overflows: int = 0
    tile_max_need: int = 0

    def add_out_rows(self, n: int) -> None:
        """Fold a host-known output count into out_rows, owning the
        -1 = unknown sentinel so call sites don't each re-implement
        the set-vs-accumulate split."""
        self.out_rows = n if self.out_rows < 0 else self.out_rows + n


@dataclass
class ExecContext:
    chunk_capacity: int = 1 << 16
    collect_stats: bool = False
    # MVCC snapshot: None reads committed-latest; a txn's reads carry its
    # start ts and marker so it sees its own provisional writes
    read_ts: Optional[int] = None
    txn_marker: int = 0
    # KILL support: polled between chunks; return True to cancel
    cancel_check: Optional[object] = None
    # host-side memory accounting root (budget + spill/OOM actions live
    # here; ref: the per-query memory.Tracker in sessionctx)
    mem_tracker: "object" = None
    # generic (high-cardinality) aggregation via the jitted sort-based
    # grouping kernels; off falls back to the numpy oracle path
    # (tidb_enable_tpu_exec sysvar)
    device_agg: bool = True
    # tables above this stream through staged batches on the dist scan
    # path instead of full device residency (tidb_device_cache_bytes)
    device_cache_bytes: int = 8 << 30
    # GROUP_CONCAT result truncation (group_concat_max_len sysvar)
    group_concat_max_len: int = 1024
    # device-resident hash-join build: pack+sort on device instead of a
    # host np.argsort round trip (tidb_tpu_join_device_build sysvar)
    join_device_build: bool = True
    # output tiles one fused join-expand dispatch may emit; bounds the
    # [T, C] buffer a many-many join materializes per dispatch
    # (tidb_tpu_join_tiles_per_dispatch sysvar)
    join_tiles: int = 8
    # probe strategy for the device join: off = searchsorted, auto =
    # hash table on TPU / searchsorted on CPU, xla forces the
    # open-addressing table (tidb_tpu_join_probe_mode sysvar)
    join_probe_mode: str = "auto"
    # rows above which a fragment build side refuses to replicate and
    # the query falls back single-chip (tidb_broadcast_join_threshold_count)
    broadcast_rows_limit: int = 1 << 21
    # columnar segment store (ISSUE 8): scans over stored tables go
    # through encoded, zone-mapped segments (tidb_tpu_columnar_enable)
    columnar_enable: bool = True
    # fixed segment capacity in rows (tidb_tpu_segment_rows); the first
    # store built for a table pins its value
    segment_rows: int = 1 << 16
    # appended delta rows that trigger a coverage extension + zone-map
    # refresh at the next scan (tidb_tpu_segment_delta_rows)
    segment_delta_rows: int = 1 << 16
    # directory for spilled segment files (tidb_tpu_columnar_spill_dir;
    # empty = system tmp)
    columnar_spill_dir: str = ""
    # background delta->segment compaction (ISSUE 17): delta-depth
    # rebuilds run on a worker thread off the statement path instead of
    # inline at the next scan (tidb_tpu_compaction)
    compaction_enable: bool = True
    # pipelined device-resident execution (ISSUE 9): fuse eligible
    # scan->filter->project->partial-agg fragments into one jitted
    # program per chunk (tidb_tpu_pipeline_fuse)
    pipeline_fuse: bool = True
    # fused ORDER BY [+ LIMIT] roots (ISSUE 18): False routes the
    # statement to the classic materializing sort up front — plan
    # feedback flips it for digests whose observed LIMIT + offset
    # overflowed the device top-k capacity gate
    fused_topn: bool = True
    # staging chunks kept in flight ahead of compute by the prefetch
    # thread; 0 = stage inline (tidb_tpu_pipeline_prefetch_depth)
    prefetch_depth: int = 2
    # byte budget of the cross-statement device buffer cache; 0 = off
    # (tidb_tpu_device_buffer_cache_bytes)
    device_buffer_cache_bytes: int = 256 << 20

    def __post_init__(self):
        if self.mem_tracker is None:
            from tidb_tpu.utils.memory import MemTracker

            self.mem_tracker = MemTracker("query")


class Executor:
    """Open/Next/Close — the same operator boundary as the reference's
    executor.Executor, pulling device Chunks instead of CPU chunks."""

    schema: List[PlanCol]

    def __init__(self, schema: List[PlanCol], children: List["Executor"]):
        self.schema = schema
        self.children = children
        self.stats = RuntimeStats()

    def open(self, ctx: ExecContext) -> None:
        for c in self.children:
            c.open(ctx)

    def next(self) -> Optional[Chunk]:
        raise NotImplementedError

    def close(self) -> None:
        for c in self.children:
            c.close()

    def chunks(self) -> Iterator[Chunk]:
        while True:
            ch = self.next()
            if ch is None:
                return
            yield ch


@dataclass
class ResultSet:
    names: List[str]
    rows: List[tuple]
    # column type kinds (tidb_tpu.types.TypeKind) for wire-protocol column
    # metadata; None for synthetic result sets (SHOW/EXPLAIN)
    types: Optional[list] = None
    # full SQLTypes (precision/scale preserved) when produced by a real
    # plan — CTAS derives its schema from these
    sql_types: Optional[list] = None
    # per-column string collation (from the plan column's dictionary)
    # so CTAS keeps the source's collation; None entries = non-string
    collations: Optional[list] = None

    def __len__(self):
        return len(self.rows)


def run_plan(root: Executor, ctx: ExecContext, n_visible: Optional[int] = None) -> ResultSet:
    """Drive an executor tree to completion and materialize host rows.

    Runs under host_eager(): the tree's glue ops (finalize, sort of a
    few groups, result decode) stay on the host CPU backend; only the
    compiled mesh fragments — whose inputs are committed device arrays —
    execute on the accelerator. Keeps device round-trips per query O(1)."""
    from tidb_tpu.utils.device import host_eager

    with host_eager():
        return _run_plan(root, ctx, n_visible)


def _run_plan(root: Executor, ctx: ExecContext, n_visible: Optional[int] = None) -> ResultSet:
    opened = False
    try:
        root.open(ctx)  # inside try: open() can raise after acquiring
        opened = True   # spill files / device buffers that close() frees
        visible = root.schema if n_visible is None else root.schema[:n_visible]
        uids = [c.uid for c in visible]
        dicts = {c.uid: c.dict_ for c in visible if c.dict_ is not None}
        rows: List[tuple] = []
        for ch in root.chunks():
            raise_if_cancelled(ctx)
            rows.extend(ch.to_pylist(dicts=dicts, names=uids))
        return ResultSet(
            names=[c.name for c in visible],
            rows=rows,
            types=[c.type_.kind for c in visible],
            sql_types=[c.type_ for c in visible],
            collations=[getattr(c.dict_, "collation", None)
                        for c in visible],
        )
    finally:
        try:
            root.close()
        except Exception:
            if opened:
                raise
