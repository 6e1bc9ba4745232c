"""General distributed fragments: an agg-rooted plan subtree compiled
into ONE shard_map program over the mesh.

This generalizes distsql.py's two fixed shapes (ref: the MPP exchange +
coprocessor tiers, SURVEY.md §2 parallelism table) to:

  * join trees of any depth/width — each equi-join hash-repartitions
    both sides over lax.all_to_all and joins locally by sorted-key
    ranges with duplicate expansion (many-many joins), all inside the
    same per-shard program
  * all join kinds: inner, left (NULL-padded unmatched probe rows),
    semi, anti (incl. NOT IN null semantics via a psum'd build-NULL
    count), with other_cond filters and multi-key equi joins (routed by
    a combined key hash, verified by exact per-key equality)
  * build sides that aren't scans (subquery results, small dimension
    pipelines) materialize on the host and enter the fragment as
    REPLICATED broadcast inputs — the broadcast-join exchange — which
    also skips repartitioning the probe side entirely. On a mesh of ONE
    part an aggregate subquery (filters and projections — the HAVING —
    over a generic GROUP BY over a scan or a join tree: TPC-H Q18's IN)
    is no broadcast but a producer INSIDE the program
    (_subquery_agg_producer): shard-local groups are the groups, so the
    rows are reduced, filtered and compacted (`_compact`: one 32-bit
    scatter of row numbers, one stacked gather) where the join reads
    them, and nothing of the subquery's answer sizes anything. A table
    that several scans of the statement read is ONE source of the program
  * both aggregation strategies at the root: segment (dense [G] states,
    psum/pmin/pmax merge) and generic (per-shard sort-based partial
    tables from executor/agg_device.py, hash-repartitioned by group key
    and locally merged — the two-phase MPP shuffle agg), so
    high-cardinality GROUP BY runs on the mesh too

Every fixed-capacity buffer (exchange buckets, join expansion slots)
counts its overflow instead of dropping rows; the driving executor
doubles the blown growth factor and re-runs — the static-shape analogue
of the reference's spill/split retry.

On a mesh of ONE part nothing is exchanged (`n_parts` is a static int
when the fragment is compiled): a join's sides go to the local join as
they are, the generic aggregate's partial table — reduced exactly — is
its final table (of more than TIE_BREAK_KEYS group keys: in hash order
with its count of groups that a hash collision split, which the host
finalize merges where it is not 0), and neither adds an "exch" knob. FragmentProgram's
`n_exchange` says how many repartitions a program holds, `n_reduce` how
many payloads its sort-reduces sum in row order and how many by a
segment op (agg_device._sort_reduce), `n_join` how many joins.

A join's device ops carry its number and stage in their `op_name`:
``join<j>/join.{compact,build,probe,expand,gather}``, j counting the
joins in the order their ops run (a join's inputs before the join; the
order of the growth knobs); an eager partial aggregate under a join
sorts and reduces under ``agg.eager``; an aggregate subquery compiled
into the program under ``subq<k>/{agg.partial,having,compact}``, k
counting them likewise (`n_subquery`).

Every ``join.compact``, ``compact`` and ``agg.compact`` scope is one
`_compact` per chunk it shrinks: a 32-bit prefix sum, ONE 32-bit scatter
of row numbers and ONE gather of all the chunk's arrays as an int64
stack — never a scatter per column, which for every 64-bit column is a
sort to this compiler (Q3's dearest, two int64 columns and their masks
from 6.0M slots into 3.1M: 1,076-1,116 ms a scatter per array, 79 ms so;
PERF.md section 6, PR 36). A built program's `compactions` lists the
ones its trace took (a capacity at or over its chunk's compiles
nothing): FRAGMENT_COMPACTIONS.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from tidb_tpu.chunk.chunk import Chunk
from tidb_tpu.chunk.column import Column
from tidb_tpu.executor.agg_device import (
    TIE_BREAK_KEYS,
    _bits64,
    _sort_reduce,
    _state_layout,
    make_partial_kernel,
    reduce_paths,
)
from tidb_tpu.executor.aggregate import make_segment_kernel
from tidb_tpu.executor.builder import peel_stages, scan_stages_for
from tidb_tpu.executor.scan import make_pipeline_fn
from tidb_tpu.expression.compiler import compile_predicate, eval_expr
from tidb_tpu.ops import prefix
from tidb_tpu.parallel.distsql import merge_state, pmax, repartition_by_key
from tidb_tpu.parallel.mesh import dcn_axis, shard_axis
from tidb_tpu.planner.physical import PHashAgg, PHashJoin, PScan
from tidb_tpu.types import TypeKind

__all__ = ["compile_fragment", "FragmentProgram"]

_AXES = (dcn_axis, shard_axis)
_SPEC = P(_AXES, None)

# rows above this don't broadcast — the subtree is too big to replicate
BROADCAST_LIMIT = 1 << 21


# group/join key identity bits: same rule as the local sort-reduce
# (NULL -> 0 + validity flag; floats by bit pattern) so exchange routing
# and local grouping can never disagree
_key_bits = _bits64


# boolean arrays (every validity mask) a stack row carries as its bits
_PACK = 63


def _compact(arrays: Dict[str, jax.Array], sel: jax.Array, cap: int):
    """Move live rows to the prefix of [cap] buffers (linear — no sort).

    Static capacities cascade: every stage inherits the worst case of the
    stage before, while selective joins/filters collapse the LIVE count.
    Sorts and exchanges pay for capacity, so compacting to an
    estimate-sized buffer (with the usual overflow-retry knob) is the
    static-shape analogue of a dynamic repartition.

    The move is a 32-bit scatter of ROW NUMBERS and ONE gather of a
    stack (PR 36), whatever the chunk carries: a live row's place is the
    int32 prefix sum of `sel` (dead rows and rows past `cap` go to the
    drop lane), each slot learns its source row by one scatter of the
    iota, and every array of the chunk rides as a row of one int64 stack
    (narrower integers widened and narrowed back; the boolean arrays —
    every validity mask — packed `_PACK` to a row) that `jnp.take`
    fetches by those row numbers once. Float columns ride in a float64
    stack of their own, one gather more where a chunk has any (the
    chip's compiler has no bitcast between 64-bit floats and integers).
    The scatter is told nothing of its indices (PR 31: neither hint
    bought a millisecond and `indices_are_sorted` gave wrong rows).

    What the chip charges (PERF.md section 6, PRs 31 and 36): an array
    scattered by itself is a 64-bit scatter wherever it is an int64, a
    float64 or a decimal, which this compiler lowers through a sort —
    two int64 columns and their masks from 6,001,215 slots into
    3,145,728 cost 1,076-1,116 ms so (508 a column in Q3's trace), and
    79 ms this way: the int32 prefix sum 2.2, the scatter of the row
    numbers 36.2, the gather of the [3, R] stack by 3.1M indices 40.0
    (in the compiled text its two u32 halves); six int64 columns 4.8 s
    against 0.17-0.19. A gather is paid by the index more than by what
    an index fetches (PR 31: three int64 arrays apart 331 ms, one stack
    65).

    Slots at and past the live count hold zero / False. Returns
    (arrays', sel', required_factor_minus_one)."""
    (R,) = sel.shape
    if R >= 1 << 31:
        raise ValueError(f"a compaction of {R} slots on one shard; use "
                         "more shards")
    pos = prefix.cumsum(sel.astype(jnp.int32)) - 1
    total = pos[-1].astype(jnp.int64) + 1
    tgt = jnp.where(sel & (pos < cap), pos, cap)  # dead rows -> drop lane
    src = jnp.zeros(cap + 1, dtype=jnp.int32).at[tgt].set(
        jnp.arange(R, dtype=jnp.int32), mode="drop")[:cap]
    nsel = jnp.arange(cap) < jnp.minimum(total, cap)

    # name -> (its stack, its row there); the flags' bits after the words
    stacks = {jnp.int64: [], jnp.float64: []}
    flags, at = [], {}
    for name, a in arrays.items():
        if a.dtype == jnp.bool_:
            flags.append(name)
            continue
        wide = (jnp.float64 if jnp.issubdtype(a.dtype, jnp.floating)
                else jnp.int64)
        at[name] = wide, len(stacks[wide])
        stacks[wide].append(a.astype(wide))
    packed = len(stacks[jnp.int64])
    for i in range(0, len(flags), _PACK):
        stacks[jnp.int64].append(functools.reduce(
            jnp.bitwise_or,
            [arrays[n].astype(jnp.int64) << b
             for b, n in enumerate(flags[i:i + _PACK])]))
    moved = {wide: jnp.where(nsel, jnp.take(jnp.stack(rows), src, axis=1,
                                            mode="clip"), 0)
             for wide, rows in stacks.items() if rows}
    out = {name: moved[wide][row].astype(arrays[name].dtype)
           for name, (wide, row) in at.items()}
    for i, name in enumerate(flags):
        out[name] = (moved[jnp.int64][packed + i // _PACK]
                     >> (i % _PACK)) & 1 != 0
    factor = (total + cap - 1) // cap
    return out, nsel, jnp.maximum(factor - 1, 0)


def _compact_chunk(env, chunk: Chunk, cap: int, knob: int, ovfs) -> Chunk:
    """`chunk`'s live rows in a Chunk of capacity `cap`, where that is
    below its own (static: the chunk itself otherwise); the overflow
    reported under `knob`, the compaction noted in `env` (what
    FRAGMENT_COMPACTIONS counts)."""
    if cap >= chunk.capacity:
        return chunk
    arrays = {}
    for uid, col in chunk.columns.items():
        arrays[uid + ".d"] = col.data
        arrays[uid + ".v"] = col.valid
    out, nsel, ovf = _compact(arrays, chunk.sel, cap)
    ovfs.append((knob, pmax(ovf, _AXES)))
    env["compactions"].append(knob)
    cols = {
        uid: Column(data=out[uid + ".d"], valid=out[uid + ".v"],
                    type_=col.type_)
        for uid, col in chunk.columns.items()
    }
    return Chunk(cols, nsel)


def _mix_hash(bits: List[jax.Array]) -> jax.Array:
    """Combine per-key bit patterns into one routing/sort hash."""
    if len(bits) == 1:
        return bits[0]  # exact value: collision-free fast path
    h = jnp.zeros_like(bits[0])
    for b in bits:
        h = (h ^ b) * np.int64(-7046029254386353131) + np.int64(0x165667B19E3779F9)
    return h


def _normalize_red_limbs(red, layout, aggs):
    """Carry-normalize (lo, hi) decimal-sum limb pairs in a reduced
    payload list (the post-exchange reduce's; on one part the partial
    reduce's, which is the last), keeping lo in [0, 2^32) for the TopN
    limb sort keys and the host finalize."""
    from tidb_tpu.executor.aggregate import normalize_limbs

    idx_of = {name: i for i, (name, _) in enumerate(layout)}
    red = list(red)
    for j, _a in enumerate(aggs):
        hi_i = idx_of.get(f"a{j}.sumhi")
        if hi_i is not None:
            lo_i = idx_of[f"a{j}.sum"]
            lo, hi = normalize_limbs(red[lo_i], red[hi_i])
            red[lo_i], red[hi_i] = lo, hi
    return red


@dataclass
class _Source:
    """A sharded scan input (4 fragment args: data, valid, sel, refs —
    refs carries the FoR bases of encoded staged columns, {} raw); the
    first of the scans that read the table (`_scan_producer`)."""
    scan: PScan


@dataclass
class _Broadcast:
    """A host-materialized subtree entering replicated (2 args + sel)."""
    plan: object  # physical subtree to materialize
    schema: list


@dataclass
class FragmentProgram:
    """Compiled description of a distributable agg subtree."""
    agg: PHashAgg
    sources: List[_Source]
    broadcasts: List[_Broadcast]
    n_growth: int                      # number of growth knobs
    n_exchange: int                    # repartitions compiled in (0 on one part)
    # payloads of the generic aggregate's sort-reduces: ("runs" summed in
    # row order, "scatter" by a segment op) — FRAGMENT_REDUCE_PAYLOADS
    n_reduce: Tuple[int, int]
    n_join: int                        # joins compiled in (_join_producer)
    # aggregate subqueries compiled in (_subquery_agg_producer; the
    # others are `broadcasts`) — FRAGMENT_SUBQUERIES
    n_subquery: int
    sig: str
    # (growths tuple) -> per-shard program; its `join_probes` lists, once
    # traced, the probe path of each join ("table" | "search"), join0 first,
    # and its `compactions` the "compact" knob of each `_compact` the trace
    # took (static per growths: a capacity under its chunk's)
    build_fn: Callable
    out_kind: str                      # "segment" | "generic"
    domains: List[int] = field(default_factory=list)
    growth_defaults: Tuple[float, ...] = ()
    growth_kinds: Tuple[str, ...] = ()
    # source indexes that must NOT be streamed in batches: they sit on
    # the build side of a non-inner join, where partitioning the build
    # set changes per-probe-row match decisions (semi/anti/left)
    stream_unsafe: frozenset = frozenset()
    # (resolved items, k) when a per-shard partial top-k is compiled in;
    # streaming executions must recompile without it (a group's partials
    # span batches — dropping it in one batch would corrupt its state)
    topn: object = None


class _Unsupported(Exception):
    pass


class _Compiler:
    def __init__(self, n_parts: int):
        self.n_parts = n_parts
        self.sources: List[_Source] = []
        self.broadcasts: List[_Broadcast] = []
        self.n_growth = 0
        # default capacity factor per knob, in assignment order: exchanges
        # start at 2x (skew headroom), join expansion at 1x (covers <=1
        # match per probe row — the PK-FK common case). "exch" knobs
        # report an overflow row count (executor doubles); "expand" knobs
        # report required-factor-minus-one (executor jumps in one step —
        # a skewed many-many join can demand 100x+ slots at once)
        self.growth_defaults: List[float] = []
        self.growth_kinds: List[str] = []
        self.sig: List[str] = []
        self.stream_unsafe: set = set()
        # repartitions compiled into the program (0 on one part)
        self.n_exchange = 0
        # how each payload of the program's sort-reduces is reduced
        # (agg_device.reduce_paths: "runs" | "scatter")
        self.reduce_paths: List[str] = []
        # joins compiled into the program, numbered as their ops run
        self.n_join = 0
        # aggregate subqueries compiled into the program, likewise
        self.n_subquery = 0

    def _add_growth(self, default: float, kind: str) -> int:
        idx = self.n_growth
        self.n_growth += 1
        self.growth_defaults.append(default)
        self.growth_kinds.append(kind)
        return idx

    # headroom over an estimate: twice a guess; a quarter over a distinct
    # count read from the data (three sigma of the NDV sketch are 9.4%)
    HEADROOM, NDV_HEADROOM = 2.0, 1.25

    def _compact_knob(self, est_rows: float, headroom: float = HEADROOM,
                      rounded: bool = True) -> Tuple[int, int]:
        """Estimate-sized compaction target: a "compact" knob plus its
        base capacity (`headroom` times the per-shard cardinality
        estimate, floor 64). The base is part of the fragment signature —
        a stats change that moves an estimate must not hit a cached
        fragment compiled with the old capacities."""
        base = max(64, int(np.ceil(
            headroom * max(est_rows, 1.0) / self.n_parts)))
        if rounded:
            # a capacity is a shape, and a shape is a compile: a guess's
            # capacity keeps four leading bits (at most an eighth more
            # slots), so that two loads of one deployment whose counts
            # differ by a percent share one program. A group table sized
            # from a distinct count stays to the slot (`rounded=False`):
            # every slot of it is fetched
            step = 1 << max(base.bit_length() - 4, 0)
            base = -(-base // step) * step
        idx = self._add_growth(1.0, "compact")
        self.sig.append(f"cap{idx}:{base}")
        return idx, base

    # -- producers ---------------------------------------------------------

    def producer(self, plan) -> Callable:
        """Compile a subtree into emit(env, growths) -> (Chunk, [ovf])."""
        stages, base = peel_stages(plan)
        if isinstance(base, PScan) and base.table is not None:
            return self._scan_producer(base, scan_stages_for(base, stages))
        if isinstance(base, PHashJoin):
            join_emit = self._join_producer(base)
            if stages:
                pipe = make_pipeline_fn(stages)

                def emit(env, growths, _j=join_emit, _p=pipe):
                    ch, ovf = _j(env, growths)
                    return _p(ch), ovf

                self.sig.append(f"stages{stages!r}")
                return emit
            return join_emit
        if isinstance(base, PHashAgg) and not stages:
            p = self._try_partial_agg_producer(base)
            if p is not None:
                return p
        if self._subquery_agg_ok(plan):
            return self._subquery_agg_producer(plan)
        # anything else (agg subtree, union, limit...) becomes a broadcast
        return self._broadcast_producer(plan)

    @staticmethod
    def _emits_as_rows(agg) -> bool:
        """Is `agg` a GROUP BY whose group table `_group_rows` can hand
        on as rows: the generic strategy, counts, sums and extremes."""
        from tidb_tpu.planner.logical import CORE_AGGS

        return (isinstance(agg, PHashAgg) and agg.strategy == "generic"
                and bool(agg.group_exprs)
                and not any(a.distinct or a.func not in CORE_AGGS
                            or a.func == "avg" for a in agg.aggs))

    def _partial_agg_ok(self, plan) -> bool:
        """Can `plan` run as a per-shard partial aggregate (a SHARDED
        join input, not a broadcast)?"""
        stages, agg = peel_stages(plan)
        if stages or not self._emits_as_rows(agg):
            return False
        # ONLY eager-agg partials (rule-derived 'eagg.' uids): per-shard
        # emission is sound because THAT rule's upper aggregate re-sums
        # partial rows; a user-written derived-table aggregate has plain
        # uids and must broadcast (shard-local groups would duplicate),
        # except on one part (`_subquery_agg_ok`)
        if not all(a.uid.startswith("eagg.") for a in agg.aggs):
            return False
        _, base = peel_stages(agg.child)
        return isinstance(base, PScan) and base.table is not None

    def _try_partial_agg_producer(self, agg: PHashAgg):
        """A partial aggregate as a JOIN INPUT (the device side of eager
        aggregation): each shard reduces its local rows into a group
        table and emits the groups as ordinary rows. No cross-shard
        merge is needed — the rewrite's upper aggregate re-sums partial
        rows, so shard-local groups with duplicate keys are exactly what
        the row-level semantics produced. Returns None for shapes the
        kernel can't take (falls back to the broadcast producer)."""
        if not self._partial_agg_ok(agg):
            return None
        child_emit = self.producer(agg.child)
        partial = make_partial_kernel(agg.group_exprs, agg.aggs)
        self.sig.append(
            f"eagg:{agg.group_exprs!r}:{agg.aggs!r}:{agg.group_uids!r}")
        self.reduce_paths += reduce_paths(agg.aggs)

        def emit(env, growths):
            chunk, ovfs = child_emit(env, growths)
            with jax.named_scope("agg.eager"):
                t = partial(chunk)
            return self._group_rows(agg, t), ovfs

        return emit

    def _subquery_agg_ok(self, plan) -> bool:
        """Can `plan` — stages over a user-written GROUP BY — run INSIDE
        the program, its groups emitted as rows where they are reduced?
        Only on a mesh of ONE part (a static int when the fragment is
        compiled): there the shard-local groups ARE the groups, which is
        what `_partial_agg_ok` must refuse a derived-table aggregate on
        several (a HAVING could fail each part's share of a group that
        their sum passes; ROADMAP: exchange by group key, then the
        same). AVG stays a broadcast: its state is a sum and a count and
        the division is the host finalize's."""
        _, agg = peel_stages(plan)
        if self.n_parts != 1 or not self._emits_as_rows(agg):
            return False
        # over what compile_agg takes at the root: a sharded scan or a
        # join tree
        _, base = peel_stages(agg.child)
        return isinstance(base, PHashJoin) or (
            isinstance(base, PScan) and base.table is not None)

    @staticmethod
    def _group_rows(agg: PHashAgg, t) -> Chunk:
        """A partial kernel's group table as rows: the keys and each
        aggregate's value under the aggregate's uid, slots past `n`
        dead. DECIMAL sums recombine their two limbs on device
        (hi*2^32+lo): exact while a group's sum stays inside int64 — the
        same representability bound as the final DECIMAL result."""
        types = {c.uid: c.type_ for c in agg.schema}
        live = jnp.arange(t["k0.d"].shape[0]) < t["n"]
        cols = {}
        for i, uid in enumerate(agg.group_uids):
            cols[uid] = Column(data=t[f"k{i}.d"], valid=t[f"k{i}.v"] & live,
                               type_=types[uid])
        for j, a in enumerate(agg.aggs):
            cnt = t[f"a{j}.cnt"]
            if a.func == "count":
                data, valid = cnt, live
            elif a.func == "sum":
                data = t[f"a{j}.sum"]
                if f"a{j}.sumhi" in t:
                    data = data + (t[f"a{j}.sumhi"] << 32)
                valid = live & (cnt > 0)
            else:  # min / max
                data = t[f"a{j}.{a.func}"]
                valid = live & (cnt > 0)
            cols[a.uid] = Column(data=data.astype(types[a.uid].np_dtype),
                                 valid=valid, type_=types[a.uid])
        return Chunk(cols, live)

    def _subquery_agg_producer(self, plan) -> Callable:
        """An aggregate subquery as a producer of its own (one part; see
        `_subquery_agg_ok`): the child's rows reduced exactly into a
        group table sized as `compile_agg` sizes the root's
        (`_table_reducer`), the groups emitted as rows, the
        stages — the HAVING, the select list — run over them, the
        survivors compacted to twice the planner's estimate of them.
        Every capacity comes from estimates and the load's record: a
        statement's program is the same whatever the subquery answers."""
        stages, agg = peel_stages(plan)
        n_before = len(self.sources)
        child_emit = self.producer(agg.child)
        # a group's rows span the batches of a streamed source: every
        # source under the subquery is pinned resident
        self.stream_unsafe.update(range(n_before, len(self.sources)))
        reduce_to_table = self._table_reducer(agg, exact=True)
        pipe = make_pipeline_fn(stages) if stages else (lambda c: c)
        self.reduce_paths += reduce_paths(agg.aggs)
        g_out, out_base = self._compact_knob(plan.est_rows)
        # numbered after its input, as the knobs are: subq0's ops run first
        scope = f"subq{self.n_subquery}"
        self.n_subquery += 1
        self.sig.append(f"subq:{agg.group_exprs!r}:{agg.aggs!r}"
                        f":{agg.group_uids!r}:{stages!r}")

        def emit(env, growths):
            chunk, ovfs = child_emit(env, growths)
            with jax.named_scope(scope):
                rows = reduce_to_table(
                    env, chunk, growths, ovfs,
                    lambda table: self._group_rows(agg, table))
                with jax.named_scope("having"):
                    rows = pipe(rows)
                capO = int(np.ceil(growths[g_out] * out_base))
                with jax.named_scope("compact"):
                    rows = _compact_chunk(env, rows, capO, g_out, ovfs)
            return rows, ovfs

        return emit

    def _scan_producer(self, scan: PScan, stages) -> Callable:
        if any(c.name == "__rowid__" for c in scan.schema):
            # physical rowids are a host-engine concept (shardings
            # re-partition rows); DML selects fall back to the host path
            raise _Unsupported("__rowid__ pseudo-column in a fragment")
        # a table the statement names twice (Q18: lineitem under the
        # joins and under the IN-subquery) enters the program ONCE: equal
        # work over equal arguments is then the compiler's to share (both
        # sort-reduces of lineitem by order key are one sort), and a
        # sort is minutes of compile. Such a source is never streamed in
        # batches: a self-join would pair only same-batch rows
        idx = next((i for i, src in enumerate(self.sources)
                    if src.scan.table is scan.table), None)
        if idx is None:
            idx = len(self.sources)
            self.sources.append(_Source(scan))
        else:
            self.stream_unsafe.add(idx)
        uid_of = {c.name: c.uid for c in scan.schema}
        type_of = {c.name: c.type_ for c in scan.schema}
        pipe = make_pipeline_fn(stages) if stages else (lambda c: c)
        self.sig.append(f"scan{idx}:{scan.table_name}:{stages!r}")

        def emit(env, growths):
            from tidb_tpu.ops.segment_scan import decode_for

            data, valid, sel, refs = env["scan"][idx]
            # the sharding carries every table column; take only the
            # (pruned) scan schema. Encoded columns decode here, inside
            # the compiled program (stored + ref, widened to the device
            # repr), so only the narrow payload crossed the host boundary
            with jax.named_scope("scan"):
                cols = {}
                for name in uid_of:
                    t = type_of[name]
                    d = decode_for(data[name][0], refs.get(name), t.np_dtype)
                    cols[uid_of[name]] = Column(
                        data=d, valid=valid[name][0], type_=t)
                return pipe(Chunk(cols, sel[0])), []

        return emit

    def _broadcast_producer(self, plan) -> Callable:
        idx = len(self.broadcasts)
        self.broadcasts.append(_Broadcast(plan, list(plan.schema)))
        types = {c.uid: c.type_ for c in plan.schema}
        self.sig.append(f"bcast{idx}:{[(c.uid, c.type_) for c in plan.schema]!r}")

        def emit(env, growths):
            data, valid, sel = env["bcast"][idx]
            cols = {uid: Column(data=data[uid], valid=valid[uid], type_=types[uid])
                    for uid in data}
            return Chunk(cols, sel), []

        return emit

    # -- joins -------------------------------------------------------------

    def _join_producer(self, join: PHashJoin) -> Callable:
        if not join.eq_left:
            raise _Unsupported("keyless (cross) join")
        if join.kind not in ("inner", "left", "semi", "anti"):
            raise _Unsupported(f"join kind {join.kind}")

        probe_idx = 1 - join.build_side
        probe_plan = join.children[probe_idx]
        build_plan = join.children[join.build_side]
        probe_keys = join.eq_left if probe_idx == 0 else join.eq_right
        build_keys = join.eq_right if join.build_side == 1 else join.eq_left

        # decide build mode BEFORE compiling children: a broadcast build
        # skips both exchanges
        def _is_bcast(plan) -> bool:
            _, base = peel_stages(plan)
            if isinstance(base, PScan) and base.table is not None:
                return False
            if isinstance(base, PHashJoin):
                return False
            if self._partial_agg_ok(plan):
                # eager-agg partial over a sharded scan: each shard emits
                # its local groups exactly once — sharded, not replicated
                return False
            if self._subquery_agg_ok(plan):
                # one part: the subquery's groups are rows of the program
                return False
            return True

        build_is_bcast = _is_bcast(build_plan)
        if _is_bcast(probe_plan):
            # a replicated probe side would be joined (and aggregated)
            # once PER SHARD, inflating every result by n_parts
            raise _Unsupported("broadcast probe side")

        probe_emit = self.producer(probe_plan)
        n_before_build = len(self.sources)
        build_emit = self.producer(build_plan)
        if join.kind != "inner":
            # a batched build side would re-decide semi/anti/left matches
            # per batch: every source under it is pinned resident
            self.stream_unsafe.update(
                range(n_before_build, len(self.sources)))

        # one part owns every key: nothing to exchange, no knob for it
        exchange = not build_is_bcast and self.n_parts > 1
        if exchange:
            self.n_exchange += 2
        g_exch = self._add_growth(2.0, "exch") if exchange else None
        g_expand = self._add_growth(1.0, "expand")
        # numbered after its inputs, as the knobs are: join0's ops run first
        j_scope = f"join{self.n_join}"
        self.n_join += 1
        # estimate-sized compaction targets (overflow-retried): selective
        # filters/joins collapse live counts, and every sort/exchange
        # downstream pays for capacity — so shrink to ~2x the planner's
        # cardinality estimate wherever that is below the static capacity
        g_pcomp, p_base = self._compact_knob(probe_plan.est_rows)
        g_bcomp, b_base = self._compact_knob(build_plan.est_rows)
        g_ocomp, o_base = self._compact_knob(join.est_rows)

        kind = join.kind
        exists_sem = join.exists_sem
        other_cond = join.other_cond
        other_pred = compile_predicate(other_cond) if other_cond is not None else None
        n_parts = self.n_parts
        nk = len(probe_keys)
        need_verify = nk > 1
        self.sig.append(
            f"join:{kind}:{exists_sem}:{probe_keys!r}:{build_keys!r}:{other_cond!r}"
            f":exch{exchange}"
        )
        # probe columns survive the join; build columns only feed inner/left
        # output and other_cond evaluation
        build_cols_out = kind in ("inner", "left")

        def emit(env, growths):
            pch, p_ovf = probe_emit(env, growths)
            bch, b_ovf = build_emit(env, growths)
            ovfs = list(p_ovf) + list(b_ovf)
            # the inputs' ops carry their own scopes; this join's, from here
            with jax.named_scope(j_scope):
                return join_local(env, growths, pch, bch, ovfs)

        def join_local(env, growths, pch, bch, ovfs):
            with jax.named_scope("join.compact"):
                capP = int(np.ceil(growths[g_pcomp] * p_base))
                pch = _compact_chunk(env, pch, capP, g_pcomp, ovfs)
                capB = int(np.ceil(growths[g_bcomp] * b_base))
                bch = _compact_chunk(env, bch, capB, g_bcomp, ovfs)

            p_outs = [eval_expr(k, pch) for k in probe_keys]
            b_outs = [eval_expr(k, bch) for k in build_keys]
            p_bits = [_key_bits(d, v) for d, v in p_outs]
            b_bits = [_key_bits(d, v) for d, v in b_outs]
            p_kvalid = p_outs[0][1]
            b_kvalid = b_outs[0][1]
            for _, v in p_outs[1:]:
                p_kvalid = p_kvalid & v
            for _, v in b_outs[1:]:
                b_kvalid = b_kvalid & v
            p_hash = _mix_hash(p_bits)
            b_hash = _mix_hash(b_bits)

            # NOT IN null semantics: any live build row with a NULL key
            # empties the anti result — counted across the whole mesh
            b_null = None
            if kind == "anti" and not exists_sem:
                b_null = jax.lax.psum(
                    jnp.sum((bch.sel & ~b_kvalid).astype(jnp.int64)), _AXES)

            def flat(ch: Chunk, bits, kvalid):
                arrs = {}
                for uid, col in ch.columns.items():
                    arrs[uid + ".d"] = col.data
                    arrs[uid + ".v"] = col.valid
                for i, b in enumerate(bits):
                    arrs[f"__kb{i}"] = b
                arrs["__kv"] = kvalid
                return arrs

            def unflat(arrs, ref: Chunk, sel):
                cols = {
                    uid: Column(data=arrs[uid + ".d"], valid=arrs[uid + ".v"],
                                type_=col.type_)
                    for uid, col in ref.columns.items()
                }
                bits = [arrs[f"__kb{i}"] for i in range(nk)]
                return Chunk(cols, sel), bits, arrs["__kv"]

            if exchange:
                growth = growths[g_exch]
                pr, pr_sel, pr_hash, povf = repartition_by_key(
                    flat(pch, p_bits, p_kvalid), pch.sel, p_hash,
                    jnp.ones_like(p_kvalid), n_parts, growth)
                br, br_sel, br_hash, bovf = repartition_by_key(
                    flat(bch, b_bits, b_kvalid), bch.sel, b_hash,
                    jnp.ones_like(b_kvalid), n_parts, growth)
                ovfs.append((g_exch, jax.lax.psum(povf + bovf, _AXES)))
                pch2, p_bits2, p_kvalid2 = unflat(pr, pch, pr_sel)
                bch2, b_bits2, b_kvalid2 = unflat(br, bch, br_sel)
                p_hash2, b_hash2 = pr_hash, br_hash
            else:
                pch2, p_bits2, p_kvalid2, p_hash2 = pch, p_bits, p_kvalid, p_hash
                bch2, b_bits2, b_kvalid2, b_hash2 = bch, b_bits, b_kvalid, b_hash

            Rp = pch2.capacity
            Rb = bch2.capacity

            # local join on the hash, through the SAME expansion
            # arithmetic the single-chip executor runs (ops/join_kernels
            # tile_positions). How a probe slot learns its run of equal
            # live build hashes is the statement's
            # tidb_tpu_join_probe_mode: by default ONE sort of both
            # sides together (merged_hash_ranges: no search, no table,
            # on every platform and at every build size); "off" and
            # "xla" keep the build's own sort with a binary search or
            # the open-addressing table over it, the references the
            # merged rank is tested against
            from tidb_tpu.ops import hash_probe
            from tidb_tpu.ops.join_kernels import (
                merged_hash_ranges,
                probe_hash_ranges,
                sort_build_hashes,
                tile_positions,
            )

            b_live = bch2.sel & b_kvalid2
            p_ok = pch2.sel & p_kvalid2
            # trace-time static, threaded per statement via build_fn (the
            # module-global read raced concurrent sessions, ISSUE 12)
            mode = env.get("probe_mode") or hash_probe._mode
            if mode == "auto":
                # the one sort is the build's too: `order` is the merged
                # order itself and `lo` a place in it (the primitive
                # scopes its sort join.build and the rest join.probe)
                lo, cnt, order = merged_hash_ranges(
                    b_hash2, b_live, p_hash2, p_ok)
                path = "merge"
            else:
                with jax.named_scope("join.build"):
                    sh, cvi, order = sort_build_hashes(b_hash2, b_live)
                with jax.named_scope("join.probe"):
                    lo, cnt, path = probe_hash_ranges(
                        sh, cvi, p_hash2, p_ok, mode=mode)
            env["joins"].append(path)

            with jax.named_scope("join.expand"):
                # 64 bits from here (the merged rank hands back 32): a
                # many-to-many join's total may pass 2^31
                lo, cnt = lo.astype(jnp.int64), cnt.astype(jnp.int64)
                cum = prefix.cumsum(cnt)
                total = cum[-1]
                growth_j = growths[g_expand]
                capJ = int(np.ceil(growth_j * Rp))
                # required-factor-minus-one, maxed over shards (0 = fits)
                factor = (total + capJ - 1) // capJ
                ovfs.append(
                    (g_expand, pmax(jnp.maximum(factor - 1, 0), _AXES)))

                valid_out, p_row, b_sorted_pos, k = tile_positions(
                    lo, cnt, cum, 0, capJ, Rp, order.shape[0])
                # (a slot past the output's end may read a probe slot's
                # number off the merged order: kept inside the build side)
                b_row = jnp.minimum(order[b_sorted_pos], Rb - 1)

            with jax.named_scope("join.gather"):
                sel_out = valid_out
                if need_verify:  # hash routing can collide; verify exact keys
                    for pb, bb, in zip(p_bits2, b_bits2):
                        sel_out = sel_out & (pb[p_row] == bb[b_row])
                    sel_out = sel_out & p_kvalid2[p_row] & b_kvalid2[b_row]

                cols = {}
                for uid, col in pch2.columns.items():
                    cols[uid] = col.gather(p_row, valid_out)
                for uid, col in bch2.columns.items():
                    bc = col.gather(b_row, valid_out)
                    cols[uid] = Column(bc.data, bc.valid & sel_out, col.type_)
                joined = Chunk(cols, sel_out & pch2.sel[p_row])

            if other_pred is not None:
                joined = joined.filter(other_pred(joined))

            if kind == "inner":
                result = joined
            else:
                # per-probe-row match flags (post-cond): scatter-or by p_row
                m = jnp.zeros(Rp, dtype=jnp.int32).at[p_row].add(
                    joined.sel.astype(jnp.int32)) > 0
                if kind == "semi":
                    result = pch2.with_sel(p_ok & m)
                elif kind == "anti":
                    if exists_sem:
                        keep = pch2.sel & ~(p_kvalid2 & m)
                    else:
                        keep = pch2.sel & p_kvalid2 & ~m & (b_null == 0)
                    result = pch2.with_sel(keep)
                else:
                    # left join: expanded matches + one NULL-build row for
                    # each unmatched live probe row, concatenated
                    pad_sel = pch2.sel & ~m
                    out_cols = {}
                    for uid, col in pch2.columns.items():
                        jc = joined.columns[uid]
                        out_cols[uid] = Column(
                            jnp.concatenate([jc.data, col.data]),
                            jnp.concatenate([jc.valid, col.valid]),
                            col.type_,
                        )
                    for uid, col in bch2.columns.items():
                        jc = joined.columns[uid]
                        out_cols[uid] = Column(
                            jnp.concatenate([jc.data, jnp.zeros(Rp, dtype=col.data.dtype)]),
                            jnp.concatenate([jc.valid, jnp.zeros(Rp, dtype=jnp.bool_)]),
                            col.type_,
                        )
                    result = Chunk(out_cols, jnp.concatenate([joined.sel, pad_sel]))

            capO = int(np.ceil(growths[g_ocomp] * o_base))
            with jax.named_scope("join.compact"):
                result = _compact_chunk(env, result, capO, g_ocomp, ovfs)
            return result, ovfs

        return emit

    # -- per-shard partial top-k ------------------------------------------

    def _topn_select(self, items, nk, layout, kmax, aggs):
        """Build fn(n, fk, fkv, red) -> (n', fk', fkv', red') keeping
        each shard's top `kmax` groups under the resolved sort items —
        the exchange routes every group to exactly one shard, so the
        union of per-shard top-k sets contains the global top-k; the
        root TopNExec applies the exact host ordering over that superset
        (the mesh analogue of the reference's TopN-into-coprocessor
        pushdown, SURVEY.md:93). Encodings mirror sort.py's _sort_order:
        NULLs first ASC / last DESC, dead lanes always last; desc ints
        invert via ~x (order-exact), floats negate."""
        self.sig.append(f"topn:{items!r}:{kmax}")

        def select(n, fk, fkv, red):
            state = {name: arr for (name, _), arr in zip(layout, red)}
            S = (fk[0] if nk else red[0]).shape[0]
            kcap = min(kmax, S)
            live = jnp.arange(S, dtype=jnp.int64) < n
            ops = []
            for kind, idx, desc in items:
                limbs = None
                if kind == "key":
                    data, valid = fk[idx], fkv[idx]
                elif kind == "cnt":
                    data = state[f"a{idx}.cnt"]
                    valid = jnp.ones(S, dtype=jnp.bool_)
                elif kind == "avg":
                    c = state[f"a{idx}.cnt"]
                    s = state[f"a{idx}.sum"]
                    hi = state.get(f"a{idx}.sumhi")
                    # jnp-native limb->float (limbs_to_float is numpy);
                    # divide in the SAME order as the host finalize
                    # (scale first, then count) so rounding can never
                    # rank two groups differently than the final TopN
                    sf = (hi.astype(jnp.float64) * float(1 << 32)
                          + s.astype(jnp.float64)
                          if hi is not None else s.astype(jnp.float64))
                    a = aggs[idx]
                    if a.arg is not None and a.arg.type_.kind == TypeKind.DECIMAL:
                        sf = sf / (10 ** a.arg.type_.scale)
                    data = sf / jnp.maximum(c, 1).astype(jnp.float64)
                    valid = c > 0
                else:  # sum | min | max: NULL when no non-null input
                    data = state[f"a{idx}.{kind}"]
                    valid = state[f"a{idx}.cnt"] > 0
                    if kind == "sum" and f"a{idx}.sumhi" in state:
                        # two-limb decimal sum: carry-normalize, then
                        # (hi, lo) lexicographic IS the numeric order
                        # (lo in [0, 2^32) after the carry)
                        from tidb_tpu.executor.aggregate import (
                            normalize_limbs,
                        )

                        lo, hi = normalize_limbs(data, state[f"a{idx}.sumhi"])
                        limbs = (hi, lo)
                rank = jnp.where(
                    ~live, jnp.int32(2),
                    jnp.where(valid, jnp.int32(0) if desc else jnp.int32(1),
                              jnp.int32(1) if desc else jnp.int32(0)))
                if limbs is not None:
                    dead = ~(valid & live)
                    khi = jnp.where(dead, 0, limbs[0])
                    klo = jnp.where(dead, 0, limbs[1])
                    if desc:
                        khi, klo = ~khi, ~klo
                    ops += [rank, khi, klo]
                    continue
                if data.dtype == jnp.bool_:
                    data = data.astype(jnp.int64)
                if jnp.issubdtype(data.dtype, jnp.floating):
                    key = jnp.where(valid & live, data.astype(jnp.float64), 0.0)
                    if desc:
                        key = -key
                else:
                    key = jnp.where(valid & live, data.astype(jnp.int64), 0)
                    if desc:
                        key = ~key
                ops += [rank, key]
            perm = jax.lax.sort(
                tuple(ops) + (jnp.arange(S, dtype=jnp.int64),),
                num_keys=len(ops))[-1][:kcap]
            return (jnp.minimum(n, kcap),
                    [a[perm] for a in fk], [a[perm] for a in fkv],
                    [a[perm] for a in red])

        return select

    # -- aggregation root --------------------------------------------------

    def _table_reducer(self, agg: PHashAgg, exact) -> Callable:
        """A generic aggregate's first pass with its two capacity knobs:
        reduce(env, chunk, growths, ovfs, then=identity) -> then(group table),
        `then` running inside the ``agg.partial`` scope. Estimate-sized
        shrink targets (see _compact): the partial sort pays for input
        capacity; every slot of the table is exchanged and sorted again
        (fetched and decoded, or read by a join, on one part) by every
        statement, so where the groups are bounded by the keys' distinct
        count the table takes the smaller headroom, to the slot."""
        partial = make_partial_kernel(agg.group_exprs, agg.aggs, exact=exact)
        g_in, in_base = self._compact_knob(agg.child.est_rows)
        g_tab, tab_base = self._compact_knob(
            agg.est_rows,
            self.NDV_HEADROOM if agg.est_from_ndv else self.HEADROOM,
            rounded=not agg.est_from_ndv)

        def reduce(env, chunk, growths, ovfs, then=lambda table: table):
            capI = int(np.ceil(growths[g_in] * in_base))
            with jax.named_scope("agg.compact"):
                chunk = _compact_chunk(env, chunk, capI, g_in, ovfs)
            with jax.named_scope("agg.partial"):
                capT = int(np.ceil(growths[g_tab] * tab_base))
                # local dedup before the exchange. Groups are dense in
                # [0, n): the table keeps `capT` slots, which shrinks
                # what the reduction gathers and everything the exchange
                # must carry (on one part: everything the host fetches)
                table = partial(chunk, slots=capT)
                if capT < chunk.capacity:
                    factor = (table["n"] + capT - 1) // capT
                    ovfs.append(
                        (g_tab, pmax(jnp.maximum(factor - 1, 0), _AXES)))
                return then(table)

        return reduce

    def compile_agg(self, agg: PHashAgg,
                    topn=None) -> Tuple[Callable, str, List[int]]:
        # the agg child must peel to a real sharded scan or a join tree;
        # anything else would make the whole input a replicated broadcast
        _, base = peel_stages(agg.child)
        if not (isinstance(base, PHashJoin)
                or (isinstance(base, PScan) and base.table is not None)):
            raise _Unsupported("agg over non-scan/join subtree")
        child_emit = self.producer(agg.child)

        if any(a.distinct for a in agg.aggs):
            raise _Unsupported("DISTINCT aggregates")
        from tidb_tpu.planner.logical import CORE_AGGS

        for a in agg.aggs:
            if a.func not in CORE_AGGS:
                raise _Unsupported(f"aggregate {a.func} on the fragment tier")

        if agg.strategy == "segment":
            sizes = agg.segment_sizes or []
            domains = [s + 1 for s in sizes]
            init_state, update, _ = make_segment_kernel(
                agg.group_exprs, agg.aggs, domains)
            self.sig.append(f"segagg:{agg.group_exprs!r}:{agg.aggs!r}:{domains!r}")

            def emit(env, growths):
                chunk, ovfs = child_emit(env, growths)
                state = merge_state(update(init_state(), chunk))
                return state, ovfs

            return emit, "segment", domains

        if not agg.group_exprs:
            raise _Unsupported("generic global agg")  # planner uses segment
        n_parts = self.n_parts
        # one part owns every key, so its partial table is the final
        # table: no exchange of the groups, no second sort-reduce, no
        # knob for either. What the second pass guaranteed is asked of
        # the first: a duplicate-free table (`exact`: several keys order
        # by their mixed hash, and a collision may split a group) with
        # carry-normalised limbs. Past TIE_BREAK_KEYS keys the table
        # comes in hash order with its count of split groups instead
        # ("count": `_sort_reduce`), and the host finalize merges by
        # exact key where that count is not 0; a per-shard top-k ranks
        # whole groups, so it keeps the tie-break sort
        one_part = n_parts == 1
        layout = _state_layout(agg.aggs)
        nk = len(agg.group_exprs)
        exact = one_part and (
            True if nk <= TIE_BREAK_KEYS or topn is not None else "count")
        topn_fn = (self._topn_select(topn[0], nk, layout, topn[1], agg.aggs)
                   if topn is not None else None)
        if one_part:
            g_agg = None
        else:
            g_agg = self._add_growth(2.0, "exch")
            self.n_exchange += 1
        # one sort-reduce on one part, a second after the exchange
        self.reduce_paths += reduce_paths(agg.aggs) * (1 if one_part else 2)
        reduce_to_table = self._table_reducer(agg, exact=exact)
        self.sig.append(
            f"genagg:{agg.group_exprs!r}:{agg.aggs!r}:exch{not one_part}")

        def exchange_and_reduce(table, growths, ovfs):
            """The partial groups to the parts that own their keys, and
            there reduced again: (n, keys, key validity, states)."""
            with jax.named_scope("exchange.agg"):
                live = jnp.arange(table["k0.d"].shape[0]) < table["n"]
                kd = [table[f"k{i}.d"] for i in range(nk)]
                kv = [table[f"k{i}.v"] for i in range(nk)]
                khash = _mix_hash([_key_bits(d, v) for d, v in zip(kd, kv)])

                arrays = {}
                for i in range(nk):
                    arrays[f"k{i}.d"] = kd[i]
                    arrays[f"k{i}.v"] = kv[i]
                for name, _ in layout:
                    arrays[name] = table[name]
                recv, recv_sel, _, ovf = repartition_by_key(
                    arrays, live, khash, jnp.ones_like(live), n_parts,
                    growths[g_agg])
                ovfs.append((g_agg, jax.lax.psum(ovf, _AXES)))

            with jax.named_scope("agg.final"):
                rkd = [recv[f"k{i}.d"] for i in range(nk)]
                rkv = [recv[f"k{i}.v"] for i in range(nk)]
                payload = [recv[name] for name, _ in layout]
                ops = [op for _, op in layout]
                # exact mode: the emitted tables are duplicate-free, so
                # the host finalize is a straight per-part conversion —
                # no merge
                n, fk, fkv, red, _ = _sort_reduce(rkd, rkv, recv_sel, payload,
                                                  ops, exact=True)
                return n, fk, fkv, _normalize_red_limbs(red, layout, agg.aggs)

        def emit(env, growths):
            chunk, ovfs = child_emit(env, growths)

            def final_of_one_part(table):
                return (table["n"], [table[f"k{i}.d"] for i in range(nk)],
                        [table[f"k{i}.v"] for i in range(nk)],
                        _normalize_red_limbs(
                            [table[name] for name, _ in layout], layout,
                            agg.aggs), table.get("split"))

            split = None
            if one_part:
                n, fk, fkv, red, split = reduce_to_table(
                    env, chunk, growths, ovfs, final_of_one_part)
            else:
                table = reduce_to_table(env, chunk, growths, ovfs)
                n, fk, fkv, red = exchange_and_reduce(table, growths, ovfs)
            if topn_fn is not None:
                with jax.named_scope("agg.topn"):
                    n, fk, fkv, red = topn_fn(n, fk, fkv, red)
            out = {"n": n[None]}
            if split is not None:
                out["split"] = split[None]
            for i in range(nk):
                out[f"k{i}.d"] = fk[i]
                out[f"k{i}.v"] = fkv[i]
            for (name, _), arr in zip(layout, red):
                out[name] = arr
            return out, ovfs

        return emit, "generic", []


def compile_fragment(agg: PHashAgg, mesh, n_parts: int,
                     topn=None) -> Optional[FragmentProgram]:
    """Try to compile an agg-rooted subtree; None if not distributable.
    `topn` = (resolved items, k) applies a per-shard partial top-k to
    the generic group tables before they leave the device (SURVEY.md:93
    TopN pushdown); ignored for segment aggs, whose bounded states are
    already cheap to rank on the host."""
    from tidb_tpu.utils.failpoint import inject

    # chaos hook: fail fragment compilation itself (the coordinator
    # must surface a clean error, not a half-built program)
    inject("fragment.compile")
    c = _Compiler(n_parts)
    try:
        emit, out_kind, domains = c.compile_agg(agg, topn=topn)
    except _Unsupported:
        return None
    if not c.sources:
        return None  # nothing sharded: run single-chip
    from tidb_tpu.utils import tracing
    from tidb_tpu.utils.metrics import FRAGMENT_COMPILE

    FRAGMENT_COMPILE.inc(kind=out_kind)
    # compile events become annotations on the statement's trace span
    tracing.annotate(f"compile:fragment:{out_kind}")

    n_src = len(c.sources)
    n_bc = len(c.broadcasts)
    n_knobs = c.n_growth

    def build_fn(growths: Tuple[float, ...], probe_mode: str = None):
        # probe_mode: the statement's resolved tidb_tpu_join_probe_mode
        # (trace-time STATIC — callers key their fragment cache on it so
        # a knob flip can never serve a program traced for the other
        # strategy); None = the hash_probe process default
        join_probes: List[str] = []
        compactions: List[int] = []

        def frag_general(*args):
            env = {"scan": [], "bcast": [], "probe_mode": probe_mode,
                   "joins": [], "compactions": []}
            i = 0
            for _ in range(n_src):
                env["scan"].append((args[i], args[i + 1], args[i + 2],
                                    args[i + 3]))
                i += 4
            for _ in range(n_bc):
                env["bcast"].append((args[i], args[i + 1], args[i + 2]))
                i += 3
            out, reports = emit(env, growths)
            # trace time: static per program
            join_probes[:] = env["joins"]
            compactions[:] = env["compactions"]
            # per-knob overflow vector, slot-indexed by knob id so the
            # executor always grows exactly the blown capacity (emission
            # order differs from knob-assignment order)
            slots = [jnp.zeros((), dtype=jnp.int64)] * n_knobs
            for idx, v in reports:
                slots[idx] = slots[idx] + v.astype(jnp.int64)
            ovf = (jnp.stack(slots) if slots
                   else jnp.zeros((0,), dtype=jnp.int64))
            return out, ovf

        out_spec = P() if out_kind == "segment" else P(_AXES)
        in_specs = tuple([_SPEC, _SPEC, _SPEC, P()] * n_src
                         + [P(), P(), P()] * n_bc)
        # lint: disable=jit-hygiene -- signature-keyed: DistFragmentExec
        # caches build_fn(growths) under (sig, growths, shapes, types)
        # via ShardCache.get_fragment; the closure carries the compiled
        # plan description only — every array arrives as an argument
        fn = jax.jit(jax.shard_map(
            frag_general, mesh=mesh, in_specs=in_specs, out_specs=(out_spec, P()),
            # pallas_call outputs carry no vma metadata; the fragment's
            # out_specs are the authority here
            check_vma=False,
        ))
        # what FRAGMENT_JOINS and FRAGMENT_COMPACTIONS count at every
        # launch: filled by the first call's trace, kept with the function
        # in the fragment cache
        fn.join_probes = join_probes
        fn.compactions = compactions
        return fn

    return FragmentProgram(
        agg=agg, sources=c.sources, broadcasts=c.broadcasts,
        n_growth=c.n_growth, n_exchange=c.n_exchange,
        n_reduce=(c.reduce_paths.count("runs"),
                  c.reduce_paths.count("scatter")),
        n_join=c.n_join, n_subquery=c.n_subquery,
        sig="|".join(c.sig),
        build_fn=build_fn,
        out_kind=out_kind, domains=domains,
        growth_defaults=tuple(c.growth_defaults),
        growth_kinds=tuple(c.growth_kinds),
        stream_unsafe=frozenset(c.stream_unsafe),
        topn=topn if out_kind == "generic" else None,
    )
