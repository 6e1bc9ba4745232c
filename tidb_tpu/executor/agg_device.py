"""Device-native generic hash aggregation: sort-based grouping.

The segment strategy (aggregate.py) needs a small dense key domain; this
module handles arbitrary / high-cardinality keys ON DEVICE (ref:
executor/aggregate.go HashAggExec's partial/final worker pipeline; the
TPU redesign is SURVEY.md §7.4's sort-based grouping). Hash tables
scatter poorly on TPU; `lax.sort` tiles well, so grouping is:

  per chunk:  multi-key sort (key bits + validity, dead rows last)
              -> segment boundaries (adjacent inequality) -> segment ids
              -> segment_sum / segment_min / segment_max partial states
              -> a dense "group table": slot i < n holds group i's key
              values and mergeable agg states, all [capacity]-shaped.

  across chunks: group tables merge pairwise on device (concat -> same
              sort-reduce over the state arrays) in a binary-counter
              schedule, so compile count is O(log chunks) and slot waste
              is bounded; all state stays device-resident until ONE
              batched fetch at finalize.

  finalize:   remaining level tables fetch in one device_get; the host
              converts them to the partial-state format aggregate.py
              already merges/emits (numpy path kept as oracle).

NULL-key semantics: a key is (bits, valid); valid participates in the
sort and in boundary detection, so NULL forms its own group. Float keys
group by bit pattern (same as the host path's int64 view — -0.0 and
NaN payloads are distinct groups, matching np.unique on bits).
"""

from __future__ import annotations

import warnings
from typing import Dict, List, Tuple

import jax

# merge kernels donate their input tables (halves peak HBM on device);
# the CPU backend can't honor donation and warns once per compile
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")
import jax.numpy as jnp
import numpy as np

from tidb_tpu.chunk.chunk import Chunk
from tidb_tpu.expression.compiler import eval_expr
from tidb_tpu.ops import prefix
from tidb_tpu.planner.logical import AggSpec
from tidb_tpu.types import TypeKind
from tidb_tpu.utils.jitcache import cached_jit

__all__ = ["make_partial_kernel", "make_merge_kernel", "GroupTableStack",
           "table_to_host_partial"]


def _bits64(data: jax.Array, valid: jax.Array) -> jax.Array:
    """Group-identity bits: NULLs unify to 0, floats group by bit pattern."""
    if jnp.issubdtype(data.dtype, jnp.floating):
        b = jax.lax.bitcast_convert_type(data.astype(jnp.float64), jnp.int64)
    else:
        b = data.astype(jnp.int64)
    return jnp.where(valid, b, 0)


def _group_hash(kbits: List[jax.Array], kvalids: List[jax.Array]) -> jax.Array:
    """One i64 ordering hash over all key components (validity folded in
    so a NULL key and a live 0 key land in different runs)."""
    h = jnp.zeros_like(kbits[0])
    for b, v in zip(kbits, kvalids):
        hb = b * np.int64(2) + v.astype(jnp.int64)
        h = (h ^ hb) * np.int64(-7046029254386353131) + np.int64(0x165667B19E3779F9)
    return h


def _sort_reduce(kbits: List[jax.Array], kvalids: List[jax.Array],
                 kdatas: List[jax.Array], live: jax.Array,
                 payload: List[jax.Array], reduce_ops: List[str],
                 exact: bool = False):
    """Shared core: sort rows by (dead, key identity), find segment
    boundaries, reduce payload arrays into dense per-group slots.

    Only (dead, order-key, iota) go through the sorting network; key
    values and payloads are gathered by the resulting permutation —
    lax.sort carries every operand through its whole comparison network,
    so this is ~(2+nk*3+npayload)/4 less data movement than sorting the
    carried arrays directly. Single-key inputs order by the exact key
    bits; multi-key inputs order by a mixed 64-bit hash with exact-key
    boundary detection, so a hash collision can only SPLIT a group into
    two partial slots (never merge two groups) — consumers dedup by
    exact key at finalize (host _merge_partials), keeping results exact.

    Returns (ngroups, rep_kdatas, rep_kvalids, reduced_payloads) — all
    slot arrays with groups dense in [0, ngroups)."""
    R = live.shape[0]
    dead = (~live).astype(jnp.int32)
    iota = jnp.arange(R, dtype=jnp.int32)
    if len(kbits) == 1:
        # exact: equal bits tie-break on validity (NULL run != live-0 run)
        out = jax.lax.sort(
            (dead, kbits[0], kvalids[0].astype(jnp.int32), iota), num_keys=3)
    elif exact:
        # hash first (cheap comparisons), exact bits as tie-breaks: equal
        # keys are guaranteed contiguous, so the output table can never
        # hold a collision-split duplicate — consumers may emit it
        # directly without a dedup pass. kvalids must join the tie-break:
        # _bits64 zeroes NULL bits, so a NULL key and a live 0 share bits
        # and differ only in validity — without it a hash collision could
        # interleave the two groups
        keys = ((dead, _group_hash(kbits, kvalids)) + tuple(kbits)
                + tuple(v.astype(jnp.int32) for v in kvalids) + (iota,))
        out = jax.lax.sort(keys, num_keys=len(keys) - 1)
    else:
        out = jax.lax.sort(
            (dead, _group_hash(kbits, kvalids), iota), num_keys=2)
    perm = out[-1]

    def take(a):
        return jnp.take(a, perm, axis=0)

    s_kbits = [take(b) for b in kbits]
    s_kdatas = [take(d) for d in kdatas]
    s_kvalids = [take(v) for v in kvalids]
    s_payload = [take(p) for p in payload]
    s_live = take(live)

    # live rows are a prefix (dead sorts last); a new segment starts at
    # row 0 or where any exact key component differs from the previous row
    idx = jnp.arange(R)
    diff = jnp.zeros(R, dtype=jnp.bool_)
    for b, v in zip(s_kbits, s_kvalids):
        diff = diff | (b != jnp.roll(b, 1)) | (v != jnp.roll(v, 1))
    newseg = s_live & ((idx == 0) | diff)
    seg = jnp.clip(prefix.cumsum(newseg.astype(jnp.int64)) - 1, 0, R - 1)
    ngroups = jnp.sum(newseg.astype(jnp.int64))

    # representative key values per group, scattered from boundary rows
    # only — dead rows share the last group's clipped seg id, and letting
    # them race the scatter would clobber that group's key with zeros
    tgt = jnp.where(newseg, seg, R)  # non-boundary rows drop out of bounds
    rep_kdatas = [jnp.zeros(R, dtype=d.dtype).at[tgt].set(d, mode="drop")
                  for d in s_kdatas]
    rep_kvalids = [jnp.zeros(R, dtype=jnp.bool_).at[tgt].set(v, mode="drop")
                   for v in s_kvalids]

    reduced = []
    for arr, op in zip(s_payload, reduce_ops):
        if op == "sum":
            contrib = jnp.where(s_live, arr, jnp.zeros((), dtype=arr.dtype))
            reduced.append(jax.ops.segment_sum(contrib, seg, num_segments=R))
        elif op == "min":
            reduced.append(jax.ops.segment_min(
                jnp.where(s_live, arr, jnp.full((), _ident_min(arr.dtype), arr.dtype)),
                seg, num_segments=R))
        elif op == "max":
            reduced.append(jax.ops.segment_max(
                jnp.where(s_live, arr, jnp.full((), _ident_max(arr.dtype), arr.dtype)),
                seg, num_segments=R))
        else:  # pragma: no cover
            raise ValueError(op)
    return ngroups, rep_kdatas, rep_kvalids, reduced


def _ident_min(dtype):
    dt = np.dtype(dtype)
    return np.inf if np.issubdtype(dt, np.floating) else np.iinfo(dt).max


def _ident_max(dtype):
    dt = np.dtype(dtype)
    return -np.inf if np.issubdtype(dt, np.floating) else np.iinfo(dt).min


def _state_layout(aggs: List[AggSpec]) -> List[Tuple[str, str]]:
    """Per-agg mergeable state arrays: [(name, merge op)]. Mirrors
    aggregate.py's partial-state dict keys (cnt/sum/min/max)."""
    from tidb_tpu.executor.aggregate import needs_sum_limbs

    layout = []
    for j, a in enumerate(aggs):
        layout.append((f"a{j}.cnt", "sum"))
        if a.func in ("sum", "avg"):
            layout.append((f"a{j}.sum", "sum"))
            if needs_sum_limbs(a):
                # two-limb exact decimal states: .sum = low 32-bit limb
                layout.append((f"a{j}.sumhi", "sum"))
        elif a.func == "min":
            layout.append((f"a{j}.min", "min"))
        elif a.func == "max":
            layout.append((f"a{j}.max", "max"))
    return layout


def make_partial_kernel(group_exprs, aggs: List[AggSpec],
                        exact: bool = False):
    """fn(chunk) -> group table dict {"n", "k{i}.d", "k{i}.v", state...}.

    `exact` (see _sort_reduce): the table holds every group once, also
    where several keys' mixed hashes collide — for a consumer that emits
    the table as it is (the fragment tier on a mesh of one part). The
    host executor merges its tables by exact key and leaves it off."""
    layout = _state_layout(aggs)

    def partial(chunk: Chunk):
        R = chunk.capacity
        sel = chunk.sel
        kdatas, kvalids, kbits = [], [], []
        for g in group_exprs:
            d, v = eval_expr(g, chunk)
            kdatas.append(d)
            kvalids.append(v)
            kbits.append(_bits64(d, v))

        payload, ops = [], []
        for j, a in enumerate(aggs):
            if a.arg is not None:
                d, v = eval_expr(a.arg, chunk)
                ok = sel & v
            else:  # count(*)
                d, ok = None, sel
            payload.append(ok.astype(jnp.int64))
            ops.append("sum")  # the .cnt slot
            if a.func in ("sum", "avg"):
                from tidb_tpu.executor.aggregate import (
                    needs_sum_limbs,
                    split_limbs,
                )

                dt = jnp.float64 if a.arg.type_.kind == TypeKind.FLOAT else jnp.int64
                contrib = jnp.where(ok, d, 0).astype(dt)
                if needs_sum_limbs(a):
                    clo, chi = split_limbs(contrib)
                    payload.append(clo)
                    ops.append("sum")
                    payload.append(chi)
                    ops.append("sum")
                else:
                    payload.append(contrib)
                    ops.append("sum")
            elif a.func == "min":
                dt = a.arg.type_.np_dtype
                payload.append(jnp.where(ok, d, _ident_min(dt)).astype(dt))
                ops.append("min")
            elif a.func == "max":
                dt = a.arg.type_.np_dtype
                payload.append(jnp.where(ok, d, _ident_max(dt)).astype(dt))
                ops.append("max")

        n, rk, rkv, red = _sort_reduce(kbits, kvalids, kdatas, sel, payload,
                                       ops, exact=exact)
        table = {"n": n}
        for i in range(len(group_exprs)):
            table[f"k{i}.d"] = rk[i]
            table[f"k{i}.v"] = rkv[i]
        for (name, _), arr in zip(layout, red):
            table[name] = arr
        return table

    return partial


def make_merge_kernel(nkeys: int, aggs: List[AggSpec]):
    """fn(tableA, tableB) -> merged table with len(A)+len(B) slots."""
    layout = _state_layout(aggs)

    def merge(ta, tb):
        def cat(name):
            return jnp.concatenate([ta[name], tb[name]])

        la = jnp.arange(ta[f"k0.d"].shape[0]) < ta["n"]
        lb = jnp.arange(tb[f"k0.d"].shape[0]) < tb["n"]
        live = jnp.concatenate([la, lb])
        kdatas = [cat(f"k{i}.d") for i in range(nkeys)]
        kvalids = [cat(f"k{i}.v") for i in range(nkeys)]
        kbits = [_bits64(d, v) for d, v in zip(kdatas, kvalids)]
        payload = [cat(name) for name, _ in layout]
        ops = [op for _, op in layout]
        n, rk, rkv, red = _sort_reduce(kbits, kvalids, kdatas, live, payload, ops)
        table = {"n": n}
        for i in range(nkeys):
            table[f"k{i}.d"] = rk[i]
            table[f"k{i}.v"] = rkv[i]
        for (name, _), arr in zip(layout, red):
            table[name] = arr
        _normalize_table_limbs(table, aggs)
        return table

    return merge


def _normalize_table_limbs(table, aggs: List[AggSpec]) -> None:
    """Carry-normalize every (lo, hi) limb pair in a group table, so lo
    stays in [0, 2^32) no matter how many merges stack (a group fed by
    2^31+ rows would otherwise wrap the lo accumulator — the segment
    kernel normalizes per chunk; merge trees must do it per level)."""
    from tidb_tpu.executor.aggregate import normalize_limbs

    for j, a in enumerate(aggs):
        if f"a{j}.sumhi" in table:
            lo, hi = normalize_limbs(table[f"a{j}.sum"],
                                     table[f"a{j}.sumhi"])
            table[f"a{j}.sum"] = lo
            table[f"a{j}.sumhi"] = hi


class GroupTableStack:
    """Binary-counter accumulation of device group tables.

    push() merges equal-sized tables immediately (level L holds one table
    of chunk_capacity * 2^L slots), so at most log2(chunks) tables are
    live and each merge kernel shape compiles once (the cached jit is
    shape-polymorphic; one cache entry retraces per level)."""

    def __init__(self, nkeys: int, aggs: List[AggSpec], cache_key: str):
        self._levels: List[object] = []
        # lint: disable=cache-key-completeness -- nkeys/aggs arrive
        # WITH their key: every caller passes cache_key =
        # repr((group_exprs, aggs)) — the repr of exactly the values
        # nkeys and aggs derive from — so the key names them even
        # though this scope cannot prove it
        self._merge = cached_jit(
            "aggmerge", cache_key, lambda: make_merge_kernel(nkeys, aggs),
            donate_argnums=(0, 1),
        )

    def push(self, table) -> None:
        level = 0
        while level < len(self._levels) and self._levels[level] is not None:
            table = self._merge(self._levels[level], table)
            self._levels[level] = None
            level += 1
        if level == len(self._levels):
            self._levels.append(None)
        self._levels[level] = table

    def tables(self) -> List[object]:
        return [t for t in self._levels if t is not None]


def table_to_host_partial(host_table: Dict[str, np.ndarray], nkeys: int,
                          aggs: List[AggSpec]) -> dict:
    """Convert a fetched group table into aggregate.py's partial-state
    format ({"mat", "keys", "kvalids", "states"}) so the existing host
    merge/emit path finalizes it."""
    n = int(host_table["n"])
    keys = [np.asarray(host_table[f"k{i}.d"][:n]) for i in range(nkeys)]
    kvalids = [np.asarray(host_table[f"k{i}.v"][:n]).astype(np.bool_)
               for i in range(nkeys)]

    def bits(k, kv):
        a = np.where(kv, k, 0)
        if np.issubdtype(a.dtype, np.floating):
            return a.astype(np.float64).view(np.int64)
        return a.astype(np.int64)

    mat = (np.stack([bits(k, kv) for k, kv in zip(keys, kvalids)]
                    + [kv.astype(np.int64) for kv in kvalids], axis=1)
           if nkeys else np.zeros((1, 0), dtype=np.int64))
    states = []
    for j, a in enumerate(aggs):
        st = {"cnt": np.asarray(host_table[f"a{j}.cnt"][:n])}
        if a.func in ("sum", "avg"):
            st["sum"] = np.asarray(host_table[f"a{j}.sum"][:n])
            if f"a{j}.sumhi" in host_table:
                st["sumhi"] = np.asarray(host_table[f"a{j}.sumhi"][:n])
        elif a.func in ("min", "max"):
            st[a.func] = np.asarray(host_table[f"a{j}.{a.func}"][:n])
        states.append(st)
    return {"mat": mat, "keys": keys, "kvalids": kvalids, "states": states}
