"""Distributed plan fragments: scan/agg/join over the mesh.

This is the coprocessor pushdown tier (ref: distsql.Select fan-out +
mocktikv coprocessor + MPP exchange) rebuilt as XLA collectives:

  * scan+filter+partial-agg fragments run per shard under jax.shard_map;
    partial [G]-shaped agg states merge with psum/pmin/pmax over the mesh
    (merge ops declared next to the kernel in executor/aggregate.py)
  * join repartitioning is a fixed-capacity bucket exchange over
    lax.all_to_all — rows hash to a destination shard, take a slot in a
    [P, cap] send buffer (cap = growth * R / P), and overflow is counted
    and surfaced rather than silently dropped (static shapes: capacity
    overflow is the TPU analogue of the reference's spill trigger).
    A mesh of ONE part exchanges nothing: every row is already on the
    part that owns its key, so the rows are handed on as they are — no
    sort, no send buffer, no collective, no `exchange.*` scope in the
    program (`exchange_steps` says how many a program holds)
  * local join per shard is a sort-merge: one sort of both sides' keys
    together, a running maximum down each run of equal keys, a sort
    back to slot order (no hash table, no binary search: on a TPU a
    sort of all slots costs less than one gather round of them). Build
    side must be unique-key (PK-FK joins — the reference's common
    HashJoinExec shape); many-many joins stay on the host executor.
"""

from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from tidb_tpu.chunk.chunk import Chunk
from tidb_tpu.chunk.column import Column
from tidb_tpu.errors import ExecutionError
from tidb_tpu.executor.aggregate import make_segment_kernel, merge_op_for
from tidb_tpu.executor.scan import make_pipeline_fn
from tidb_tpu.expression.compiler import eval_expr
from tidb_tpu.ops.prefix import cummax
from tidb_tpu.parallel.mesh import dcn_axis, shard_axis
from tidb_tpu.parallel.partition import ShardedTable

__all__ = [
    "merge_state",
    "make_agg_fragment",
    "make_join_agg_fragment",
    "dist_agg_fragment",
    "dist_join_agg_fragment",
    "repartition_by_key",
    "exchange_steps",
]

_HASH_MULT = np.int64(-7046029254386353131)  # 0x9E3779B97F4A7C15 as int64

_AXES = (dcn_axis, shard_axis)
_SPEC = P(_AXES, None)


def _all_reduce_minmax(v: jax.Array, axes, op: str) -> jax.Array:
    """lax.pmax / lax.pmin, except for 64-bit values: XLA:TPU emulates
    64-bit types as 32-bit pairs and that rewriting lowers only Sum
    all-reduces (a v5e refuses an s64 pmax: "UNIMPLEMENTED: Supported
    lowering only of Sum all reduce" — PR 22 chip run, pinned in
    tests/test_chip_compile.py). Those gather and reduce locally; the
    merged states are small ([G] or scalars), so the gather is noise."""
    if v.dtype.itemsize < 8:
        return (jax.lax.pmax if op == "max" else jax.lax.pmin)(v, axes)
    gathered = jax.lax.all_gather(v, axes)
    return (jnp.max if op == "max" else jnp.min)(gathered, axis=0)


def pmax(v: jax.Array, axes=_AXES) -> jax.Array:
    return _all_reduce_minmax(v, axes, "max")


def pmin(v: jax.Array, axes=_AXES) -> jax.Array:
    return _all_reduce_minmax(v, axes, "min")


def merge_state(state: Dict[str, jax.Array], axes=_AXES) -> Dict[str, jax.Array]:
    """Merge per-shard partial agg states across mesh axes (final-agg step)."""
    out = {}
    for k, v in state.items():
        op = merge_op_for(k)
        if op == "sum":
            out[k] = jax.lax.psum(v, axes)
        elif op == "min":
            out[k] = pmin(v, axes)
        elif op == "max":
            out[k] = pmax(v, axes)
        else:
            raise ValueError(f"unknown merge op {op}")
    return out


def _shard_chunk(types: Dict, data, valid, sel, uid_map,
                 refs: Optional[Dict] = None) -> Chunk:
    from tidb_tpu.ops.segment_scan import decode_for

    cols = {}
    for name in data:
        uid = uid_map.get(name, name) if uid_map else name
        t = types[name]
        # fused FoR decode: the narrow staged payload widens to the
        # column's device repr INSIDE the program (ISSUE 9)
        d = decode_for(data[name][0], (refs or {}).get(name), t.np_dtype)
        cols[uid] = Column(data=d, valid=valid[name][0], type_=t)
    return Chunk(cols, sel[0])


def make_agg_fragment(st: ShardedTable, stages: List, group_exprs, aggs,
                      domains: List[int], uid_map: Optional[Dict[str, str]] = None):
    """Compile scan->filter->partial-agg->merge over the mesh.

    Returns a jitted fn(data, valid, sel, refs) -> merged [G]-state dict
    (replicated; fetched once); refs carries the FoR bases of encoded
    staged columns ({} for raw staging). Cache the returned fn — jit
    keys on function identity, so rebuilding it means recompiling. The
    closure deliberately captures only st's metadata (types/mesh), never
    the ShardedTable itself, so a cached fragment cannot pin retired
    [P,R] device arrays."""
    pipeline = make_pipeline_fn(stages) if stages else (lambda c: c)
    init_state, update, _ = make_segment_kernel(group_exprs, aggs, domains)
    types, mesh = dict(st.types), st.mesh

    # the function's name is the device program's (`jit_frag_scan_agg`
    # in a profiler trace); the scopes name its stages in every op's
    # metadata — no fusion changes, nothing at run time
    def frag_scan_agg(data, valid, sel, refs):
        with jax.named_scope("scan"):
            chunk = pipeline(_shard_chunk(types, data, valid, sel, uid_map,
                                          refs))
        with jax.named_scope("agg.update"):
            state = update(init_state(), chunk)
        with jax.named_scope("agg.merge"):
            return merge_state(state)

    # lint: disable=jit-hygiene -- signature-keyed: callers cache the
    # returned fn via ShardCache.get_fragment (plan/shape/type key);
    # the closure carries only schema metadata, never table arrays
    return jax.jit(jax.shard_map(
        frag_scan_agg, mesh=mesh,
        in_specs=(_SPEC, _SPEC, _SPEC, P()), out_specs=P(),
        check_vma=False,
    ))


def dist_agg_fragment(st: ShardedTable, stages: List, group_exprs, aggs,
                      domains: List[int], uid_map: Optional[Dict[str, str]] = None):
    """Compile + run (convenience; see make_agg_fragment for the cached path)."""
    fn = make_agg_fragment(st, stages, group_exprs, aggs, domains, uid_map)
    return fn(st.data, st.valid, st.sel, st.refs)


# ---------------------------------------------------------------------------
# repartition exchange
# ---------------------------------------------------------------------------


def _hash_dest(key: jax.Array, n_parts: int) -> jax.Array:
    h = key * _HASH_MULT
    return ((h % n_parts) + n_parts) % n_parts


def exchange_steps(n_parts: int, repartitions: int) -> int:
    """How many exchange steps a program that calls `repartition_by_key`
    `repartitions` times over `n_parts` parts really holds: on one part
    none (what FRAGMENT_EXCHANGE_STEPS counts at every launch)."""
    return repartitions if n_parts > 1 else 0


def _exchange_scope(name: str, n_parts: int):
    """`jax.named_scope(name)` around a repartition, where there is one
    to name: on one part the rows are handed on and no op is the
    exchange's."""
    return jax.named_scope(name) if n_parts > 1 else contextlib.nullcontext()


def repartition_by_key(arrays: Dict[str, jax.Array], sel: jax.Array,
                       key: jax.Array, key_valid: jax.Array, n_parts: int,
                       growth: float = 2.0,
                       axes=_AXES) -> Tuple[Dict[str, jax.Array], jax.Array, jax.Array, jax.Array]:
    """Exchange rows so equal keys land on the same shard (call in shard_map).

    arrays: name -> [R]; returns (arrays', sel', key', overflow_count) with
    [n_parts * cap] shapes where cap = ceil(growth * R / n_parts).
    NULL keys never join, so such rows are dropped here (sel'=False).

    On ONE part (`n_parts` is a static Python int, the mesh's shape when
    the fragment is traced) the exchange is the identity: the arrays and
    the key come back as they were given, at [R] slots, sel' is
    sel & key_valid and the overflow a constant 0. Dead slots keep
    whatever key they held; `_local_join` and `_sort_reduce` take
    validity as a sort key, so they need no sentinel.
    """
    live = sel & key_valid
    if n_parts == 1:
        return arrays, live, key, jnp.zeros((), dtype=jnp.int64)
    R = sel.shape[0]
    cap = int(np.ceil(growth * R / n_parts))
    dest = jnp.where(live, _hash_dest(key, n_parts), n_parts)  # P = drop lane

    with jax.named_scope("exchange.sort"):
        order = jnp.argsort(dest, stable=True)
        sorted_dest = dest[order]
        seg_start = jnp.searchsorted(sorted_dest, jnp.arange(n_parts + 1, dtype=sorted_dest.dtype))
        pos = jnp.arange(R) - seg_start[jnp.clip(sorted_dest, 0, n_parts)]
        in_cap = (pos < cap) & (sorted_dest < n_parts)
        overflow = jnp.sum((pos >= cap) & (sorted_dest < n_parts))

    # scatter row `order[i]` into send slot [sorted_dest[i], pos[i]];
    # dead/overflow rows land in a trash lane (row n_parts) that is sliced
    # off before the exchange — slot (0,0) must never see collisions
    with jax.named_scope("exchange.scatter"):
        slot_d = jnp.where(in_cap, sorted_dest, n_parts)
        slot_p = jnp.where(in_cap, pos, 0)

        def scatter(a):
            buf = jnp.zeros((n_parts + 1, cap), dtype=a.dtype)
            return buf.at[slot_d, slot_p].set(a[order])[:n_parts]

        sent_sel = (jnp.zeros((n_parts + 1, cap), dtype=jnp.bool_)
                    .at[slot_d, slot_p].set(True))[:n_parts]
        sent_key = scatter(key)
        sent = {name: scatter(a) for name, a in arrays.items()}

    with jax.named_scope("exchange.all_to_all"):
        recv_sel = jax.lax.all_to_all(sent_sel, axes, 0, 0).reshape(-1)
        recv_key = jax.lax.all_to_all(sent_key, axes, 0, 0).reshape(-1)
        recv = {name: jax.lax.all_to_all(a, axes, 0, 0).reshape(-1)
                for name, a in sent.items()}
    return recv, recv_sel, recv_key, overflow


_IDX_BITS = 29  # slot index bits under the 2-bit tag in the sort's int32 second key


def _local_join(build_key, build_sel, probe_key, probe_sel):
    """Sort-merge join of a unique-key build side. Returns (build_idx,
    hit), one per probe slot: where hit[j], probe slot j joins build
    slot build_idx[j]; elsewhere build_idx is 0.

    ONE sort of both sides' keys together ranks every probe slot against
    the build rows; a running maximum carries each run's build row to
    the run's probe rows; a second sort, on the 32-bit slot index, puts
    the answers back in slot order. No searching and no gather: on a v5e
    a sort of the 15.0M slots of TPC-H SF1's lineitem-orders join costs
    74 ms and the sort back 48 ms, where ONE gather of as many 64-bit
    elements costs 226 ms and a binary search 22-24 such rounds (PERF.md
    section 6, PR 26).

    Validity is a sort key (tag 0 live build row, 1 live probe row, 2
    dead slot of either side), never an in-band sentinel: the exchange's
    dead slots carry key 0 beside a legitimate key 0, and INT64_MIN/MAX
    still join. Keys compare at full width. Within a run of equal keys
    the live build row, if any, sorts first (there is at most one: the
    build key is unique).

    Limit: build plus probe slots of one shard stay under 2**29
    (536,870,912), so that the 2-bit tag sits clear of the slot index in
    the sort's int32 second key; more raises ExecutionError when the
    fragment is traced, before anything runs. (One v5e holds no such
    shard: the keys alone would be 4.3 GB, the sort several times
    that.)"""
    nb, n = build_key.shape[0], build_key.shape[0] + probe_key.shape[0]
    if n >= 1 << _IDX_BITS:
        raise ExecutionError(
            f"mesh join: {n} build+probe slots on one shard, "
            f"limit {(1 << _IDX_BITS) - 1}; use more shards")
    idx_mask = (1 << _IDX_BITS) - 1
    with jax.named_scope("join.sort"):
        key = jnp.concatenate([build_key, probe_key])
        tag = jnp.concatenate([jnp.where(build_sel, 0, 2),
                               jnp.where(probe_sel, 1, 2)]).astype(jnp.int32)
        tagged = (tag << _IDX_BITS) | jnp.arange(n, dtype=jnp.int32)
        skey, stagged = jax.lax.sort((key, tagged), num_keys=2)
    with jax.named_scope("join.probe"):
        pos = jnp.arange(n, dtype=jnp.int64)
        head = (pos == 0) | (skey != jnp.roll(skey, 1))
        # a run's head, under its position so that the maximum is the
        # nearest head at or before each row
        carried = cummax(jnp.where(head, (pos << 32) | stagged, -1))
        head_tagged = carried.astype(jnp.int32)  # the low 32 bits
        hit = ((stagged >> _IDX_BITS) == 1) & ((head_tagged >> _IDX_BITS) == 0)
        found = jnp.where(hit, head_tagged & idx_mask, -1)
    with jax.named_scope("join.unsort"):
        _, found = jax.lax.sort((stagged & idx_mask, found), num_keys=1)
        found = found[nb:]  # the build side's own slots come first
        return jnp.maximum(found, 0), found >= 0


def make_join_agg_fragment(
    probe: ShardedTable, build: ShardedTable,
    probe_stages: List, build_stages: List,
    probe_key_ir, build_key_ir,
    probe_uids: Dict[str, str], build_uids: Dict[str, str],
    post_stages: List, group_exprs, aggs, domains: List[int],
    growth: float = 2.0,
):
    """Compile hash-repartition join + partial agg, all on device.

    Pipeline per shard: scan probe/build -> fused FoR decode -> pushed
    filters -> eval join keys -> all_to_all exchange both sides (none on
    a mesh of one part) -> local unique-build-key join -> post-join
    filter/project -> partial segment agg -> collective merge.

    Returns a jitted fn(p_data, p_valid, p_sel, p_refs, b_data, b_valid,
    b_sel, b_refs) -> (state, overflow) — state is the merged [G] dict;
    overflow is the total row count dropped by exchange capacity (must
    be 0; caller re-runs with higher growth otherwise).
    """
    p_pipe = make_pipeline_fn(probe_stages) if probe_stages else (lambda c: c)
    b_pipe = make_pipeline_fn(build_stages) if build_stages else (lambda c: c)
    post_pipe = make_pipeline_fn(post_stages) if post_stages else (lambda c: c)
    init_state, update, _ = make_segment_kernel(group_exprs, aggs, domains)
    mesh = probe.mesh
    n_parts = probe.n_parts
    # capture metadata only — never the ShardedTables (see make_agg_fragment)
    probe_types, build_types = dict(probe.types), dict(build.types)

    # named like make_agg_fragment's program, and staged like it
    def frag_join_agg(p_data, p_valid, p_sel, p_refs,
                      b_data, b_valid, b_sel, b_refs):
        with jax.named_scope("scan"):
            pch = p_pipe(_shard_chunk(probe_types, p_data, p_valid, p_sel,
                                      probe_uids, p_refs))
            bch = b_pipe(_shard_chunk(build_types, b_data, b_valid, b_sel,
                                      build_uids, b_refs))

            pk, pkv = eval_expr(probe_key_ir, pch)
            bk, bkv = eval_expr(build_key_ir, bch)
            pk = pk.astype(jnp.int64)
            bk = bk.astype(jnp.int64)

        def flat(ch: Chunk):
            arrs = {}
            for uid, col in ch.columns.items():
                arrs[uid + ".d"] = col.data
                arrs[uid + ".v"] = col.valid
            return arrs

        def unflat(arrs, ref: Chunk, sel):
            cols = {}
            for uid, col in ref.columns.items():
                cols[uid] = Column(data=arrs[uid + ".d"], valid=arrs[uid + ".v"],
                                   type_=col.type_)
            return Chunk(cols, sel)

        with _exchange_scope("exchange.probe", n_parts):
            pr, pr_sel, pr_key, p_ovf = repartition_by_key(
                flat(pch), pch.sel, pk, pkv, n_parts, growth)
        with _exchange_scope("exchange.build", n_parts):
            br, br_sel, br_key, b_ovf = repartition_by_key(
                flat(bch), bch.sel, bk, bkv, n_parts, growth)

        bidx, hit = _local_join(br_key, br_sel, pr_key, pr_sel)
        with jax.named_scope("join.gather"):
            joined_cols = dict(pr)
            for uid, col in bch.columns.items():
                joined_cols[uid + ".d"] = br[uid + ".d"][bidx]
                joined_cols[uid + ".v"] = br[uid + ".v"][bidx] & hit
            ref_cols = dict(pch.columns)
            ref_cols.update(bch.columns)
            ref = Chunk(ref_cols, pch.sel)  # types template only
            joined = unflat(joined_cols, ref, hit)

        with jax.named_scope("agg.update"):
            state = update(init_state(), post_pipe(joined))
        with jax.named_scope("agg.merge"):
            state = merge_state(state)
            ovf = jax.lax.psum(p_ovf + b_ovf, _AXES)
        return state, ovf

    # lint: disable=jit-hygiene -- signature-keyed via
    # ShardCache.get_fragment like make_agg_fragment; closure carries
    # plan metadata only (types/mesh/keys), never the ShardedTables
    return jax.jit(jax.shard_map(
        frag_join_agg, mesh=mesh,
        in_specs=(_SPEC, _SPEC, _SPEC, P(), _SPEC, _SPEC, _SPEC, P()),
        out_specs=(P(), P()), check_vma=False,
    ))


def dist_join_agg_fragment(probe: ShardedTable, build: ShardedTable, *args, **kwargs):
    """Compile + run (convenience; see make_join_agg_fragment)."""
    fn = make_join_agg_fragment(probe, build, *args, **kwargs)
    return fn(probe.data, probe.valid, probe.sel, probe.refs,
              build.data, build.valid, build.sel, build.refs)
