"""Distributed executors: plug mesh fragments into the Volcano tree.

build_dist_executor mirrors executor/builder.py but intercepts plan
shapes that can run as one collective fragment across the mesh:

  * HashAgg(segment) over fused Selection/Projection stages on one scan
    -> dist_agg_fragment (scan+filter+partial agg per shard, psum merge)
  * HashAgg(segment) over Join(scan-side, scan-side) with int equi-keys
    -> dist_join_agg_fragment (all_to_all repartition + local join)

Anything else falls back to the single-chip executors — exactly how the
reference falls back from coprocessor pushdown to root-task execution
when a subtree isn't pushable (ref: planner "cop task" vs "root task").
"""

from __future__ import annotations

import contextlib
import time
from collections import OrderedDict
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from tidb_tpu.utils import dispatch as dsp
from tidb_tpu.utils.lru import get_or_build, touch


from tidb_tpu.errors import ExecutionError
from tidb_tpu.executor.aggregate import HashAggExec
from tidb_tpu.executor.builder import build_executor, peel_stages, scan_stages_for
from tidb_tpu.executor.base import Executor, raise_if_cancelled
from tidb_tpu.executor.scan import ProjectionExec, SelectionExec
from tidb_tpu.executor.sort import LimitExec, SortExec, TopNExec
from tidb_tpu.parallel.distsql import (
    exchange_steps,
    make_agg_fragment,
    make_join_agg_fragment,
)
from tidb_tpu.parallel.fragment import BROADCAST_LIMIT, compile_fragment
from tidb_tpu.parallel.mesh import dcn_axis, shard_axis
from tidb_tpu.parallel.partition import ShardedTable, shard_table
from tidb_tpu.planner.physical import (
    PHashAgg,
    PHashJoin,
    PLimit,
    PProjection,
    PScan,
    PSelection,
    PSort,
    PTopN,
    PhysicalPlan,
)

__all__ = ["ShardCache", "build_dist_executor", "DistAggExec", "DistJoinAggExec"]


@contextlib.contextmanager
def _fragment_launch(kind: str, n_parts: int, exchanges: int = 0,
                     reduced: Tuple[int, int] = (0, 0), joins=(),
                     subqueries: Tuple[int, int] = (0, 0), compactions=()):
    """One fragment launch: the span ``fragment.<kind>[parts=N]`` on the
    statement's trace and the FRAGMENT_SECONDS collector for /metrics
    (with a trace_id exemplar). Wall time covers the launch plus any
    synchronous trace/compile, never the device's work (jax dispatch is
    async): whoever needs the result waits in ``device.wait``
    (utils/dispatch.py). One launch is one fragment execution, so the
    dispatch counter lives here too — the count and the histogram can
    never desynchronize — and beside it the `exchanges` the launched
    program holds (FRAGMENT_EXCHANGE_STEPS; 0 on a mesh of one part) and
    the payloads its sort-reduces take, `reduced` = (summed in row order,
    by a segment op) (FRAGMENT_REDUCE_PAYLOADS; a generic aggregate's),
    and the joins a general fragment's program holds, `joins` = the probe
    path of each (FRAGMENT_JOINS; read after the launch: a program's
    first call fills the list as it traces), and the subtrees it takes as
    build sides that are no scans, `subqueries` = (compiled into the
    program, answered through the host and broadcast)
    (FRAGMENT_SUBQUERIES), and the compactions its trace took,
    `compactions` = the knob of each (FRAGMENT_COMPACTIONS; read after
    the launch, as `joins`)."""
    from tidb_tpu.utils import tracing
    from tidb_tpu.utils.metrics import (
        FRAGMENT_COMPACTIONS,
        FRAGMENT_DISPATCH,
        FRAGMENT_EXCHANGE_STEPS,
        FRAGMENT_JOINS,
        FRAGMENT_REDUCE_PAYLOADS,
        FRAGMENT_SECONDS,
        FRAGMENT_SUBQUERIES,
    )

    t0 = time.perf_counter()
    with tracing.span(f"fragment.{kind}[parts={n_parts}]"):
        yield
    FRAGMENT_DISPATCH.inc(kind=kind)
    FRAGMENT_EXCHANGE_STEPS.inc(exchanges, kind=kind)
    for path, n in zip(("runs", "scatter"), reduced):
        FRAGMENT_REDUCE_PAYLOADS.inc(n, kind=kind, path=path)
    for probe in joins:
        FRAGMENT_JOINS.inc(kind=kind, probe=probe)
    for path, n in zip(("inline", "broadcast"), subqueries):
        FRAGMENT_SUBQUERIES.inc(n, kind=kind, path=path)
    FRAGMENT_COMPACTIONS.inc(len(compactions), kind=kind)
    FRAGMENT_SECONDS.observe(time.perf_counter() - t0, kind=kind)


def _timed_combine(sig, state, part):
    """Merge two per-shard collective states, timing the host-driven
    merge into COLLECTIVE_MERGE_SECONDS."""
    from tidb_tpu.utils.metrics import COLLECTIVE_MERGE_SECONDS

    t0 = time.perf_counter()
    out = _segment_state_combine(sig)(state, part)
    COLLECTIVE_MERGE_SECONDS.observe(time.perf_counter() - t0)
    return out


class ShardCache:
    """(table identity, version) -> ShardedTable. The region-cache analogue:
    invalidated by table mutation (version bump), not by epoch.

    The entry pins the host table object so a recycled id() can never alias
    a different table; a small LRU bounds how many dead tables' [P,R]
    device copies can stay resident after drops/replacements. Also caches
    compiled collective fragments (keyed by plan signature) — shard_map
    closures recompile per jit identity, and a repeated query must not pay
    XLA compilation twice — and the proven exchange growth per join
    signature so skewed joins don't re-run known-overflowing fragments."""

    MAX_TABLES = 16
    MAX_FRAGMENTS = 128

    def __init__(self, mesh):
        self.mesh = mesh
        self._cache: "OrderedDict[int, Tuple[object, int, ShardedTable]]" = OrderedDict()
        self.fragments: "OrderedDict[object, object]" = OrderedDict()
        # bounded with fragments' LRU discipline: one entry per join
        # signature+data version, pruned opportunistically
        self.growth: "OrderedDict[object, float]" = OrderedDict()

    def get(self, table, encode: bool = False) -> ShardedTable:
        hit = self._cache.get(id(table))
        if hit is not None:
            held, version, enc0, st = hit
            if held is table and version == table.version \
                    and enc0 == encode:
                self._cache.move_to_end(id(table))
                return st
        st = shard_table(table, self.mesh, encode=encode)
        self._cache[id(table)] = (table, table.version, encode, st)
        self._cache.move_to_end(id(table))
        while len(self._cache) > self.MAX_TABLES:
            self._cache.popitem(last=False)
        return st

    def resident(self) -> list:
        """Snapshot of what is held on the devices: [(table, ShardedTable)]."""
        return [(held, st) for held, _ver, _enc, st in
                list(self._cache.values())]

    def evict(self, table) -> None:
        """Drop a table's resident sharding (e.g. it grew past the
        device-cache budget and the streaming path takes over)."""
        self._cache.pop(id(table), None)

    def get_fragment(self, key, build):
        fn = get_or_build(self.fragments, key, build, self.MAX_FRAGMENTS)
        # fragments trace lazily on first call, under the glue's
        # host-CPU default-device pin — every dispatch enters the
        # device tier on the mesh's real platform so kernel choice
        # follows the arrays (utils/device.py)
        from tidb_tpu.utils.device import device_tier, note_placement

        platform = self.mesh.devices.flat[0].platform

        def dispatch(*args):
            with dsp.launch("fragment"):
                with device_tier(platform):
                    out = fn(*args)
                note_placement("fragment", out)
            return out

        # a general fragment's joins by probe path and the compactions
        # its trace took (fragment.py build_fn)
        dispatch.join_probes = getattr(fn, "join_probes", ())
        dispatch.compactions = getattr(fn, "compactions", ())
        return dispatch

    def get_growth(self, gkey) -> float:
        g = self.growth.get(gkey)
        if g is None:
            return 2.0
        self.growth.move_to_end(gkey)
        return g

    def put_growth(self, gkey, growth: float) -> None:
        touch(self.growth, gkey, growth, self.MAX_FRAGMENTS)


def _segment_state_combine(sig):
    """Jitted elementwise merge of two segment-state dicts (sum/min/max
    per key via merge_op_for) — shared by every streaming path."""
    from tidb_tpu.executor.aggregate import merge_op_for
    from tidb_tpu.utils.jitcache import cached_jit

    def build():
        def combine(s1, s2):
            out = {}
            for k, v in s1.items():
                op = merge_op_for(k)
                if op == "sum":
                    out[k] = v + s2[k]
                elif op == "min":
                    out[k] = jnp.minimum(v, s2[k])
                else:
                    out[k] = jnp.maximum(v, s2[k])
            return out

        return combine

    return cached_jit("aggcombine", repr(sig), build)


def _types_sig(st: ShardedTable) -> str:
    """Schema signature of a sharding: the compiled fragments close over
    st.types (column name -> SQLType), so the cache key must distinguish
    shardings by it — but nothing else."""
    return repr(sorted((n, t) for n, t in st.types.items()))


def _collapse_to_scan(plan: PhysicalPlan):
    """Fuse Selection/Projection chain onto a single scan; return
    (scan, stages) or None if the subtree isn't a pushable pipeline."""
    stages, base = peel_stages(plan)
    if not isinstance(base, PScan) or base.table is None:
        return None
    return base, scan_stages_for(base, stages)


def _uid_map(scan: PScan) -> Dict[str, str]:
    return {c.name: c.uid for c in scan.schema}


class DistAggExec(HashAggExec):
    """Segment agg whose input is a sharded scan fragment on the mesh."""

    def __init__(self, plan: PHashAgg, scan: PScan, stages, cache: ShardCache):
        super().__init__(plan.schema, None, plan.group_exprs, plan.group_uids,
                         plan.aggs, "segment",
                         segment_sizes=getattr(plan, "segment_sizes", None))
        self.children = []
        self._scan = scan
        self._stages = stages
        self._cache = cache

    # per-shard staging batch for the >HBM streaming path (rows; the
    # batch buffer is P * this many rows of the scanned columns)
    STREAM_ROWS_PER_PART = 1 << 20

    def _run_segment(self):
        from tidb_tpu.parallel.partition import table_bytes

        sizes = self.segment_sizes or []
        domains = [s + 1 for s in sizes]
        table = self._scan.table
        scan_cols = [c.name for c in self._scan.schema]
        # gate on the FULL table size: the resident path shards every
        # column; streaming then stages only the scanned columns
        if table_bytes(table) > self.ctx.device_cache_bytes:
            self._cache.evict(table)  # drop any stale resident sharding
            self._run_segment_streaming(domains, scan_cols)
            return
        # resident sharding stages ONCE and is dispatched many times:
        # FoR-encoding it would charge the in-program decode to every
        # warm execution (measured 3.5x on warm Q1) for a one-time
        # transfer saving. Encoded staging pays on the STREAMING paths,
        # where the bytes move on every batch.
        st = self._cache.get(table)
        # keyed on schema signature, NOT data identity: the compiled fragment
        # is a pure function of plan + shapes + column types (arrays are
        # arguments), so version bumps with unchanged schema reuse it
        key = ("agg", repr((self._stages, self.group_exprs, self.aggs, domains)),
               st.n_parts, st.rows_per_part, _types_sig(st))
        fn = self._cache.get_fragment(
            key,
            lambda: make_agg_fragment(st, self._stages, self.group_exprs,
                                      self.aggs, domains, uid_map=_uid_map(self._scan)),
        )
        with _fragment_launch("scan_agg", st.n_parts):
            state = fn(st.data, st.valid, st.sel, st.refs)
        self._finalize_segment_state(state, domains)

    def _run_segment_streaming(self, domains, scan_cols):
        """>HBM tables: stream fixed [P, R] staging batches through the
        (once-compiled) partial-agg fragment, combining the replicated
        [G] states on device; one fetch at the end. jax's async dispatch
        overlaps batch k's compute with batch k+1's host staging (the
        IndexLookUp double-pipeline analogue)."""
        from tidb_tpu.parallel.partition import stream_batches

        table = self._scan.table
        mesh = self._cache.mesh
        sig = repr((self._stages, self.group_exprs, self.aggs, domains))
        state = None
        fn = None
        for st in stream_batches(table, mesh, scan_cols,
                                 self.STREAM_ROWS_PER_PART, encode=True):
            raise_if_cancelled(self.ctx)  # see _run_fragment_streaming
            if fn is None:
                key = ("agg", sig, st.n_parts, st.rows_per_part,
                       _types_sig(st), "stream")
                fn = self._cache.get_fragment(
                    key,
                    lambda st=st: make_agg_fragment(
                        st, self._stages, self.group_exprs, self.aggs,
                        domains, uid_map=_uid_map(self._scan)),
                )
            with _fragment_launch("scan_agg_stream", st.n_parts):
                part = fn(st.data, st.valid, st.sel, st.refs)
            state = part if state is None else _timed_combine(
                sig, state, part)
        self._finalize_segment_state(state, domains)


class DistJoinAggExec(HashAggExec):
    """Segment agg over a repartition join of two sharded scans."""

    def __init__(self, plan: PHashAgg, join: PHashJoin,
                 probe_scan, probe_stages, build_scan, build_stages,
                 post_stages, cache: ShardCache):
        super().__init__(plan.schema, None, plan.group_exprs, plan.group_uids,
                         plan.aggs, "segment",
                         segment_sizes=getattr(plan, "segment_sizes", None))
        self.children = []
        self._plan = plan
        self._delegate = None
        self._join = join
        self._probe_scan, self._probe_stages = probe_scan, probe_stages
        self._build_scan, self._build_stages = build_scan, build_stages
        self._post_stages = post_stages
        self._cache = cache

    def next(self):
        if self._delegate is not None:
            return self._delegate.next()
        return super().next()

    def close(self):
        if self._delegate is not None:
            self._delegate.close()
            self._delegate = None
        super().close()

    def _run_segment(self):
        from tidb_tpu.parallel.partition import table_bytes

        sizes = self.segment_sizes or []
        domains = [s + 1 for s in sizes]
        join = self._join
        if max(table_bytes(self._probe_scan.table),
               table_bytes(self._build_scan.table)) > self.ctx.device_cache_bytes:
            # >HBM side: the general fragment path streams it in fixed
            # [P, R] batches; this resident fast path cannot
            mesh = self._cache.mesh
            prog = compile_fragment(
                self._plan, mesh, int(np.prod(list(mesh.shape.values()))))
            if prog is not None:
                d = DistFragmentExec(self._plan, prog, self._cache)
            else:
                # never shard an over-budget table resident: the host
                # executors stream chunk-wise within the budget
                d = build_executor(self._plan)
            d.open(self.ctx)
            self._delegate = d
            return
        probe_idx = 1 - join.build_side
        probe_keys = join.eq_left if probe_idx == 0 else join.eq_right
        build_keys = join.eq_right if join.build_side == 1 else join.eq_left
        probe_st = self._cache.get(self._probe_scan.table)
        build_st = self._cache.get(self._build_scan.table)
        sig = repr((self._probe_stages, self._build_stages, probe_keys[0],
                    build_keys[0], self._post_stages, self.group_exprs,
                    self.aggs, domains))
        # start from the growth that last worked for this signature on this
        # data version so a skewed join doesn't replay its known-overflowing
        # fragments; keyed on serials so it resets when the data changes
        gkey = (sig, probe_st.serial, build_st.serial)
        growth = self._cache.get_growth(gkey)
        while growth <= 16.0:
            key = ("joinagg", sig, growth, probe_st.n_parts,
                   probe_st.rows_per_part, build_st.rows_per_part,
                   _types_sig(probe_st), _types_sig(build_st))
            fn = self._cache.get_fragment(
                key,
                lambda: make_join_agg_fragment(
                    probe_st, build_st,
                    self._probe_stages, self._build_stages,
                    probe_keys[0], build_keys[0],
                    _uid_map(self._probe_scan), _uid_map(self._build_scan),
                    self._post_stages, self.group_exprs, self.aggs, domains,
                    growth=growth,
                ),
            )
            with _fragment_launch("join_agg", probe_st.n_parts,
                                  exchange_steps(probe_st.n_parts, 2)):
                state, ovf = fn(probe_st.data, probe_st.valid,
                                probe_st.sel, probe_st.refs,
                                build_st.data, build_st.valid,
                                build_st.sel, build_st.refs)
            # host-sync: one scalar per dispatch — the exchange
            # overflow count decides the grow-and-retry loop; the wait
            # for it is the wait for the whole join program
            if dsp.device_get(ovf, counted=False) == 0:
                self._cache.put_growth(gkey, growth)
                break
            growth *= 2  # skewed exchange: retry with bigger buckets
        else:
            raise ExecutionError("join exchange overflow persisted at growth=16x")
        self._finalize_segment_state(state, domains)


class _BroadcastTooLarge(Exception):
    def __init__(self, rows):
        super().__init__(f"broadcast side too large ({rows} rows)")


class DistFragmentExec(HashAggExec):
    """Agg root over a general compiled fragment (parallel/fragment.py):
    join trees, broadcast build sides, segment or generic aggregation —
    one shard_map dispatch per execution, with per-knob capacity retry."""

    # "compact" knobs have no ceiling: their cap is min'd against the
    # static capacity inside the fragment, so growth converges to a no-op
    # in O(log) retries even from a wildly wrong estimate. "expand" jumps
    # to the exact reported factor (never speculative), and a compacted
    # probe side legitimately inflates the factor — the ceiling only
    # guards against compiling absurd buffers for pathological skew.
    MAX_GROWTH = {"exch": 64.0, "expand": 65536.0, "compact": float("inf")}

    def __init__(self, plan: PHashAgg, prog, cache: ShardCache):
        super().__init__(plan.schema, None, plan.group_exprs, plan.group_uids,
                         plan.aggs, plan.strategy,
                         segment_sizes=getattr(plan, "segment_sizes", None))
        self.children = []
        self._plan = plan
        self._prog = prog
        self._cache = cache
        self._delegate = None

    def _run_segment(self):
        self._run_fragment()

    def _run_generic(self):
        self._run_fragment()



    def next(self):
        if self._delegate is not None:
            return self._delegate.next()
        return super().next()

    def close(self):
        if self._delegate is not None:
            self._delegate.close()
            self._delegate = None
        super().close()

    def _fall_back_single_chip(self):
        """Pathological skew blew every capacity retry: run the plan on
        the single-chip executors instead of failing the query (the
        reference's root-task fallback)."""
        root = build_executor(self._plan)
        root.open(self.ctx)
        self._delegate = root

    # ------------------------------------------------------------------

    def _gather_broadcasts(self, prog):
        """Materialize every broadcast subtree; returns (args, shapes).
        A subtree too large to replicate raises _BroadcastTooLarge; the
        fragment runners catch it and fall back to single-chip execution
        like every other unsupported shape (round-2 review weak #6 — it
        used to be a hard error telling the user to flip a sysvar)."""
        args, shapes = [], []
        limit = getattr(self.ctx, "broadcast_rows_limit", BROADCAST_LIMIT)
        from tidb_tpu.utils import tracing

        for bc in prog.broadcasts:
            # the host's statement-within-a-statement: the subtree's own
            # launches, fetches and device.wait are this span's children
            with tracing.span("fragment.broadcast"):
                data, valid, sel, n = self._materialize_broadcast(bc)
            if n > limit:
                raise _BroadcastTooLarge(n)
            args += [data, valid, sel]
            shapes.append(len(sel))
        return args, shapes

    @staticmethod
    def _iter_host_parts(host):
        """Split a fetched [n_parts * S] group-table dict into per-part
        tables; yields (part_index, table_dict) for non-empty parts."""
        n_per = np.asarray(host["n"]).reshape(-1)
        n_parts = len(n_per)
        for p in range(n_parts):
            if n_per[p] == 0:
                continue
            t = {"n": n_per[p]}
            for name, arr in host.items():
                if name == "n":
                    continue
                S = len(arr) // n_parts
                t[name] = arr[p * S:(p + 1) * S]
            yield p, t

    def _host_partial(self, t, nk: int):
        """One part's fetched group table as a host partial; merged by
        exact key where the program counted groups that a collision of
        two keys' hashes split (a table in hash order: `_sort_reduce`)."""
        from tidb_tpu.executor.agg_device import table_to_host_partial

        part = table_to_host_partial(t, nk, self.aggs)
        if "split" in t and int(np.sum(t["split"])):
            part = self._merge_partials([part])
        return part

    def _materialize_broadcast(self, bc):
        """Run a non-scan subtree and return replicated (data, valid, sel)
        arrays — the broadcast exchange input. The subtree itself runs
        through the distributed builder, so an agg-rooted build side (a
        HAVING subquery, say) executes as a mesh fragment instead of a
        single-chip pass over the whole table."""
        root = build_dist_executor(bc.plan, self._cache)
        datas = {c.uid: [] for c in bc.schema}
        valids = {c.uid: [] for c in bc.schema}
        n = 0
        try:
            root.open(self.ctx)
            for ch in root.chunks():
                sel = np.asarray(ch.sel)
                live = np.nonzero(sel)[0]
                n += len(live)
                for c in bc.schema:
                    col = ch.columns[c.uid]
                    datas[c.uid].append(np.asarray(col.data)[live])
                    valids[c.uid].append(np.asarray(col.valid)[live])
        finally:
            root.close()
        # pad to pow2 so repeated executions reuse compiled shapes
        cap = 1
        while cap < max(n, 1):
            cap *= 2
        data, valid = {}, {}
        for c in bc.schema:
            d = (np.concatenate(datas[c.uid]) if datas[c.uid]
                 else np.zeros(0, dtype=c.type_.np_dtype))
            v = (np.concatenate(valids[c.uid]) if valids[c.uid]
                 else np.zeros(0, dtype=np.bool_))
            db = np.zeros(cap, dtype=d.dtype)
            vb = np.zeros(cap, dtype=np.bool_)
            db[:n], vb[:n] = d, v
            data[c.uid], valid[c.uid] = db, vb
        sel = np.zeros(cap, dtype=np.bool_)
        sel[:n] = True
        return data, valid, sel, n

    def _pick_stream_source(self, prog):
        """Index of the source to stream, or None. A table above the
        device-cache budget streams in fixed [P, R] batches IF the
        statement reads it once — a self-join of a streamed table would
        pair only same-batch rows (the compiler pins a source that two
        scans share: `stream_unsafe`). Running
        the fragment per batch is otherwise sound: probe rows partition
        across batches (each contributes once), build/broadcast sides
        are identical every batch, and the agg outputs merge (segment:
        state merge; generic: per-part table merge)."""
        from tidb_tpu.parallel.partition import table_bytes

        best, best_bytes = None, 0
        for i, src in enumerate(prog.sources):
            if i in prog.stream_unsafe:
                continue
            b = table_bytes(src.scan.table)
            if b > self.ctx.device_cache_bytes and b > best_bytes:
                best, best_bytes = i, b
        return best

    def _run_fragment(self):
        prog = self._prog
        stream_idx = self._pick_stream_source(prog)
        if stream_idx is not None:
            self._run_fragment_streaming(prog, stream_idx)
            return
        args, sts = [], []
        for src in prog.sources:
            # resident shardings stage raw (see DistAggExec._run_segment)
            st = self._cache.get(src.scan.table)
            args += [st.data, st.valid, st.sel, st.refs]
            sts.append(st)
        try:
            bcast_args, bcast_shapes = self._gather_broadcasts(prog)
        except _BroadcastTooLarge:
            self._fall_back_single_chip()
            return
        args += bcast_args

        gkey = (prog.sig,) + tuple(st.serial for st in sts)
        growths = self._cache.growth.get(gkey) or prog.growth_defaults
        shapes_sig = (tuple((st.n_parts, st.rows_per_part) for st in sts),
                      tuple(bcast_shapes))
        types_sig = tuple(_types_sig(st) for st in sts)
        out, growths = self._dispatch_retry(
            prog, args, shapes_sig, types_sig, growths,
            f"general_{prog.out_kind}", sts[0].n_parts if sts else 0)
        if out is None:
            self._fall_back_single_chip()
            return
        touch(self._cache.growth, gkey, growths, ShardCache.MAX_FRAGMENTS)

        if prog.out_kind == "segment":
            self._finalize_segment_state(out, prog.domains)
        else:
            self._finalize_generic_tables(out)

    def _dispatch_retry(self, prog, args, shapes_sig, types_sig, growths,
                        kind: str, n_parts: int):
        """Run the fragment, growing only blown capacity knobs: "exch"
        knobs double; "expand"/"compact" jump to the reported required
        factor in one recompile (skewed joins can demand 100x+ at once).
        Returns (out, growths) or (None, growths) past the ceilings.
        Every attempt is one launch (``fragment.<kind>[parts=N]``); what
        sends it round again is counted by knob (FRAGMENT_RETRY_TOTAL)."""
        from tidb_tpu.utils.metrics import FRAGMENT_RETRY_TOTAL

        # the statement's resolved probe mode becomes a trace-time
        # static of the fragment program: it joins the cache key (a
        # knob flip must not serve a program traced for the other
        # strategy) and rides build_fn instead of the process global
        # that concurrent sessions used to race (ISSUE 12)
        probe_mode = getattr(self.ctx, "join_probe_mode", None)
        while True:
            # each retry pays a recompile: bail between attempts if the
            # statement was killed or ran out of its deadline
            raise_if_cancelled(self.ctx)
            key = ("frag", prog.sig, growths, shapes_sig, types_sig,
                   probe_mode)
            fn = self._cache.get_fragment(
                key, lambda: prog.build_fn(growths, probe_mode=probe_mode))
            with _fragment_launch(kind, n_parts, prog.n_exchange,
                                  prog.n_reduce, fn.join_probes,
                                  (prog.n_subquery, len(prog.broadcasts)),
                                  fn.compactions):
                out, ovf = fn(*args)
            # host-sync: the per-knob overflow vector (a few int64s)
            # gates the capacity-retry loop — one fetch per dispatch
            ovf = dsp.device_get(ovf, counted=False)
            if not (ovf > 0).any():
                return out, growths
            # once per re-launch and kind of knob that blew in this one
            for knob in {k for o, k in zip(ovf, prog.growth_kinds) if o > 0}:
                FRAGMENT_RETRY_TOTAL.inc(kind=kind, knob=knob)
            new = []
            for g, o, knob in zip(growths, ovf, prog.growth_kinds):
                if o <= 0:
                    new.append(g)
                elif knob in ("expand", "compact"):
                    factor = int(o) + 1
                    mult = 1
                    while mult < factor:
                        mult *= 2
                    new.append(g * max(mult, 2))
                else:
                    new.append(g * 2)
            growths = tuple(new)
            if any(g > self.MAX_GROWTH[k]
                   for g, k in zip(growths, prog.growth_kinds)):
                return None, growths

    def _run_fragment_streaming(self, prog, stream_idx):
        """>HBM sources: stream the oversized table through the compiled
        fragment in fixed [P, R] batches against resident build sides
        (ref: SURVEY.md:315 hard-part 6 generalized beyond scan-agg;
        VERDICT round-2 item 4). Segment states merge on device across
        batches; generic group tables merge per-part on host (parts stay
        disjoint — the exchange routing is identical every batch)."""
        from tidb_tpu.executor.aggregate import merge_op_for
        from tidb_tpu.parallel.partition import stream_batches

        mesh = self._cache.mesh
        if prog.topn is not None:
            # a group's partials span batches: a per-batch top-k would
            # drop state a later batch needed — recompile without it
            # (the root TopNExec still bounds what the user sees)
            prog = compile_fragment(
                prog.agg, mesh,
                mesh.shape[dcn_axis] * mesh.shape[shard_axis])
            if prog is None:
                self._fall_back_single_chip()
                return
        src = prog.sources[stream_idx]
        table = src.scan.table
        self._cache.evict(table)  # its full sharding must not stay resident
        scan_cols = [c.name for c in src.scan.schema]
        n_parts = int(np.prod(list(mesh.shape.values())))
        bytes_per_row = sum(
            table.data[n].dtype.itemsize + 1 for n in scan_cols) + 1
        rows_per_part = max(4096, int(
            self.ctx.device_cache_bytes // (4 * n_parts * bytes_per_row)))

        # the STREAMED source stages encoded (its bytes move every
        # batch); resident co-sources stay raw like every other
        # resident sharding
        sts = {}
        for i, s2 in enumerate(prog.sources):
            if i != stream_idx:
                sts[i] = self._cache.get(s2.scan.table)
        try:
            bcast_args, bcast_shapes = self._gather_broadcasts(prog)
        except _BroadcastTooLarge:
            self._fall_back_single_chip()
            return

        gkey = ((prog.sig, "stream", rows_per_part)
                + tuple(sts[i].serial for i in sorted(sts)))
        growths = self._cache.growth.get(gkey) or prog.growth_defaults
        types_fixed = tuple(_types_sig(sts[i]) for i in sorted(sts))

        seg_state = None
        gen_parts = None  # part index -> [host partial dicts]
        nk = len(self.group_exprs)
        for batch in stream_batches(table, mesh, scan_cols, rows_per_part,
                                    encode=True):
            # a KILL or deadline expiry must interrupt a >HBM streamed
            # fragment between batches, not only at the root chunk loop
            # (which never runs until every batch has been merged)
            raise_if_cancelled(self.ctx)
            args = []
            shapes = []
            for i in range(len(prog.sources)):
                st = batch if i == stream_idx else sts[i]
                args += [st.data, st.valid, st.sel, st.refs]
                shapes.append((st.n_parts, st.rows_per_part))
            args += bcast_args
            shapes_sig = (tuple(shapes), tuple(bcast_shapes))
            types_sig = types_fixed + (_types_sig(batch), "stream")
            out, growths = self._dispatch_retry(
                prog, args, shapes_sig, types_sig, growths,
                f"general_{prog.out_kind}_stream", batch.n_parts)
            if out is None:
                self._fall_back_single_chip()
                return
            if prog.out_kind == "segment":
                if seg_state is None:
                    seg_state = out
                else:
                    seg_state = _timed_combine(prog.sig, seg_state, out)
            else:
                # host-sync: >HBM generic streaming — per-part group
                # tables must merge on host across batches (parts stay
                # disjoint), one batched fetch per streamed batch
                host = dsp.device_get(out)
                if gen_parts is None:
                    n_parts_out = len(np.asarray(host["n"]).reshape(-1))
                    gen_parts = [[] for _ in range(n_parts_out)]
                for pi, t in self._iter_host_parts(host):
                    gen_parts[pi].append(self._host_partial(t, nk))
        touch(self._cache.growth, gkey, growths, ShardCache.MAX_FRAGMENTS)

        if prog.out_kind == "segment":
            self._finalize_segment_state(seg_state, prog.domains)
            return
        cap = self.ctx.chunk_capacity
        emitted = False
        merged_parts = []
        for partials in (gen_parts or []):
            if not partials:
                continue
            # same key appears across batches of one part: exact merge
            merged_parts.append(partials[0] if len(partials) == 1
                                else self._merge_partials(partials))
        if merged_parts:
            # parts are disjoint across the exchange: concat, emit once
            if self.group_exprs:
                self._emit_merged(self._concat_partials(merged_parts), cap)
            else:
                self._emit_merged(self._merge_partials(merged_parts), cap)
            emitted = True
        if not emitted:
            self._out = []

    @staticmethod
    def _concat_partials(partials):
        """Concatenate DISJOINT host partials (exchange-routed parts of
        one group space) into a single partial so the root emits ONE
        chunk. Per-part emission made every downstream operator pay a
        device dispatch per part."""
        if len(partials) == 1:
            return partials[0]
        out = {
            "mat": np.concatenate([p["mat"] for p in partials], axis=0),
            "keys": [np.concatenate(ks)
                     for ks in zip(*(p["keys"] for p in partials))],
            "kvalids": [np.concatenate(ks)
                        for ks in zip(*(p["kvalids"] for p in partials))],
        }
        states = []
        for j in range(len(partials[0]["states"])):
            states.append({
                k: np.concatenate([p["states"][j][k] for p in partials])
                for k in partials[0]["states"][j]
            })
        out["states"] = states
        return out

    def _finalize_generic_tables(self, out):
        """Fetch the sharded per-part group tables (one device_get),
        concatenate the disjoint parts, and emit once. The exchange
        routes every key to exactly one shard and the final on-device
        reduce is EXACT (sorts by hash + full key bits), so parts are
        disjoint and duplicate-free — no cross-part host merge exists at
        any cardinality (the 10^7-group host-merge hotspot the round-2
        review flagged). A mesh of one part exchanges nothing: its one
        reduce is the exact one, and its table is `capT` slots, not
        `n_parts * cap` received ones. Of more than TIE_BREAK_KEYS group
        keys that table comes in hash order with "split", its count of
        runs that a collision of two keys' hashes split: where it is not
        0 the part is merged by exact key here."""
        from tidb_tpu.utils import tracing

        # the fetch is this span's device.wait child; its self time is
        # the host's decode, concatenation and cut into chunks
        with tracing.span("fragment.finalize"):
            host = dsp.device_get(out)
            nk = len(self.group_exprs)
            cap = self.ctx.chunk_capacity
            partials = [self._host_partial(t, nk)
                        for _p, t in self._iter_host_parts(host)]
            if not partials:
                self._out = []  # no groups anywhere
            elif nk == 0:
                # keyless partials are not disjoint — exact merge instead
                self._emit_merged(self._merge_partials(partials), cap)
            else:
                self._emit_merged(self._concat_partials(partials), cap)


def _try_dist_agg(plan: PHashAgg, cache: ShardCache) -> Optional[Executor]:
    if plan.strategy != "segment":
        return None
    scan_frag = _collapse_to_scan(plan.child)
    if scan_frag is not None:
        scan, stages = scan_frag
        return DistAggExec(plan, scan, stages, cache)
    # join underneath?
    post_stages, node = peel_stages(plan.child)
    if not isinstance(node, PHashJoin) or node.kind != "inner":
        return None
    if len(node.eq_left) != 1 or node.other_cond is not None:
        return None
    probe_idx = 1 - node.build_side
    probe_frag = _collapse_to_scan(node.children[probe_idx])
    build_frag = _collapse_to_scan(node.children[node.build_side])
    if probe_frag is None or build_frag is None:
        return None
    # unique-build-key requirement: trust the planner only when the build
    # key is the build table's primary key
    build_scan = build_frag[0]
    build_keys = node.eq_right if node.build_side == 1 else node.eq_left
    from tidb_tpu.expression.expr import ColumnRef

    pk = getattr(build_scan.table.schema, "primary_key", None)
    key_ir = build_keys[0]
    key_col = key_ir.name if isinstance(key_ir, ColumnRef) else None
    pk_uids = []
    if pk:
        by_name = {c.name: c.uid for c in build_scan.schema}
        pk_uids = [by_name.get(n) for n in pk]
    if not (len(pk_uids) == 1 and key_col == pk_uids[0]):
        return None
    return DistJoinAggExec(plan, node, probe_frag[0], probe_frag[1],
                           build_frag[0], build_frag[1], post_stages, cache)


def _all_scans_pointy(plan: PhysicalPlan) -> bool:
    """True when every base-table access is a point get (or tiny): the
    whole plan touches a handful of rows, so the O(log n) host path wins.
    A point-get LEAF inside a big join must NOT drag the rest of the
    plan off the mesh — the fragment treats it as a filtered scan."""
    from tidb_tpu.planner.physical import PIndexRangeScan, PPointGet

    found = False
    stack = [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, PPointGet):
            found = True
        elif isinstance(node, PIndexRangeScan):
            # a selective range behaves like a point get (compact
            # row-id set via the sorted cache); a wide one must stay
            # eligible for the mesh like any big scan
            if node.est_rows <= 4096:
                found = True
            else:
                return False
        elif isinstance(node, PScan) and node.table is not None:
            if node.table.n > 4096:
                return False
        stack.extend(getattr(node, "children", ()))
    return found


def _try_dist_topn(plan, cache) -> Optional[Executor]:
    """TopN whose sort keys resolved onto a generic dist agg below
    (planner's resolve_topn_pushdown): compile the fragment with a
    per-shard partial top-k, so only n_parts * k candidate groups ever
    reach the host; the root TopNExec applies the exact ordering over
    that superset (SURVEY.md:93 — the reference pushes TopN into
    coprocessors the same way)."""
    from tidb_tpu.planner.physical import PProjection, PTopN

    if getattr(plan, "pushdown", None) is None:
        return None
    agg, items = plan.pushdown
    k = plan.count + plan.offset  # bounds pre-checked by the resolver
    prog = compile_fragment(
        agg, cache.mesh,
        cache.mesh.shape[dcn_axis] * cache.mesh.shape[shard_axis],
        topn=(tuple(items), k))
    if prog is None:
        return None
    ex: Executor = DistFragmentExec(agg, prog, cache)
    chain = []
    node = plan.child
    while isinstance(node, PProjection):
        chain.append(node)
        node = node.child
    if node is not agg:
        return None  # resolver and builder walked different chains
    for p in reversed(chain):
        ex = ProjectionExec(p.schema, ex, p.exprs)
    return TopNExec(plan.schema, ex, plan.items, plan.count, plan.offset)


def build_dist_executor(plan: PhysicalPlan, cache: ShardCache,
                        full: bool = True) -> Executor:
    """Build an executor tree, running distributable fragments on the mesh.

    full=False (the degenerate single-CPU backend) distributes only
    segment scan-agg fragments — joins and generic aggregation run on
    the vectorized host engine, which beats XLA:CPU's sorts there."""
    if _all_scans_pointy(plan):
        # the whole plan touches a handful of rows; the O(log n) host
        # path beats staging tables onto the mesh
        return build_executor(plan)
    if isinstance(plan, PHashAgg):
        if not full:
            # single-CPU backend: keep segment scan-aggs on device
            # (linear scatter-adds win) but run joins and generic
            # aggregation on the vectorized host engine at EVERY size:
            # XLA:CPU's sort-based fragments lose to the numpy engine
            # there, which is why `full=False` exists.
            if plan.strategy == "segment":
                frag = _collapse_to_scan(plan.child)
                if frag is not None:
                    return DistAggExec(plan, frag[0], frag[1], cache)
            return build_executor(plan)
        ex = _try_dist_agg(plan, cache)  # proven fast paths first
        if ex is not None:
            return ex
        prog = compile_fragment(
            plan, cache.mesh,
            cache.mesh.shape[dcn_axis] * cache.mesh.shape[shard_axis])
        if prog is not None:
            return DistFragmentExec(plan, prog, cache)
        if _collapse_to_scan(plan.child) is None:
            # the agg itself isn't distributable (agg-over-agg, DISTINCT,
            # ...) but its subtree may contain fragmentable aggs/joins —
            # run the root agg on the host over a distributed child
            return HashAggExec(
                plan.schema, build_dist_executor(plan.child, cache, full),
                plan.group_exprs, plan.group_uids, plan.aggs, plan.strategy,
                segment_sizes=getattr(plan, "segment_sizes", None))
        return build_executor(plan)
    if isinstance(plan, (PProjection, PSelection)):
        # a fusible chain over a plain scan has no collective fragment —
        # hand the whole thing to the single-chip builder so it fuses into
        # one scan pipeline instead of per-node executors
        _, base = peel_stages(plan)
        if isinstance(base, PScan):
            return build_executor(plan)
        if isinstance(plan, PProjection):
            return ProjectionExec(plan.schema, build_dist_executor(plan.child, cache, full), plan.exprs)
        return SelectionExec(plan.schema, build_dist_executor(plan.child, cache, full), plan.cond)
    if isinstance(plan, PSort):
        return SortExec(plan.schema, build_dist_executor(plan.child, cache, full), plan.items)
    if isinstance(plan, PTopN):
        if full:
            ex = _try_dist_topn(plan, cache)
            if ex is not None:
                return ex
        return TopNExec(plan.schema, build_dist_executor(plan.child, cache, full), plan.items,
                        plan.count, plan.offset)
    if isinstance(plan, PLimit):
        return LimitExec(plan.schema, build_dist_executor(plan.child, cache, full), plan.count, plan.offset)
    return build_executor(plan)
