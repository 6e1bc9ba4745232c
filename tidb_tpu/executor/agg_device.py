"""Device-native generic hash aggregation: sort-based grouping.

The segment strategy (aggregate.py) needs a small dense key domain; this
module handles arbitrary / high-cardinality keys ON DEVICE (ref:
executor/aggregate.go HashAggExec's partial/final worker pipeline; the
TPU redesign is SURVEY.md §7.4's sort-based grouping). Hash tables
scatter poorly on TPU; `lax.sort` tiles well, so grouping is:

  per chunk:  multi-key sort (key bits + validity, dead rows last)
              -> run boundaries (adjacent inequality) -> run ids, and
              the row at which each run ends
              -> partial states: an integer sum (count, integer and
              decimal sums, every limb) is one running total over the
              sorted rows, read at each run's end and differenced — the
              rows of a group are contiguous, so no addition needs an
              address (PR 31: on the chip a 64-bit scatter-add of 6.0M
              rows costs 535-743 ms, a blocked prefix sum and a gather
              of the run ends a fraction of it; PERF.md section 6);
              float sums and min / max stay segment_sum / segment_min /
              segment_max
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

import functools
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


# A duplicate-free table of up to this many group keys comes from the
# tie-break sort (`exact=True`: TPC-H Q3's three); of more, from the hash
# order with its count of split groups (`exact="count"`: Q18's five) —
# see `_sort_reduce`. Where the table's consumer can merge by exact key
# (the fragment tier's root on one part: the host finalize).
TIE_BREAK_KEYS = 3


def _bits64(data: jax.Array, valid: jax.Array) -> jax.Array:
    """Group-identity bits: NULLs unify to 0, floats group by bit pattern."""
    if jnp.issubdtype(data.dtype, jnp.floating):
        b = jax.lax.bitcast_convert_type(data.astype(jnp.float64), jnp.int64)
    else:
        b = data.astype(jnp.int64)
    return jnp.where(valid, b, 0)


def _from_bits64(bits: jax.Array, dtype) -> jax.Array:
    """A key's data from its `_bits64` (NULL reads as the bits' 0)."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jax.lax.bitcast_convert_type(bits, jnp.float64).astype(dtype)
    return bits.astype(dtype)


def _group_hash(kbits: List[jax.Array], kvalids: List[jax.Array]) -> jax.Array:
    """One i64 ordering hash over all key components (validity folded in
    so a NULL key and a live 0 key land in different runs)."""
    h = jnp.zeros_like(kbits[0])
    for b, v in zip(kbits, kvalids):
        hb = b * np.int64(2) + v.astype(jnp.int64)
        h = (h ^ hb) * np.int64(-7046029254386353131) + np.int64(0x165667B19E3779F9)
    return h


def _reduced_in_row_order(op: str, dtype) -> bool:
    """Whether `_sort_reduce` reads this payload's group sums off one
    running total: exact only for an integer sum (two's-complement
    addition wraps, so the difference of two readings is the run's sum
    modulo 2^64 — what an int64 scatter-add returns — whatever the total
    over all rows does). A difference of running FLOAT totals is not the
    group's sum, and a running extreme is its own scan: those keep
    `segment_sum` / `segment_min` / `segment_max`."""
    return op == "sum" and jnp.issubdtype(dtype, jnp.integer)


def _sort_reduce(kdatas: List[jax.Array], kvalids: List[jax.Array],
                 live: jax.Array, payload: List[jax.Array],
                 reduce_ops: List[str], exact=False,
                 slots: int = None):
    """Shared core: sort rows by (dead, key identity: `_bits64`), find
    the runs of equal keys, reduce payload arrays into dense per-group
    slots.

    Single-key inputs order by the exact key bits; multi-key inputs
    order by a mixed 64-bit hash with exact-key boundary detection, so a
    hash collision can only SPLIT a group into two partial slots (never
    merge two groups) — consumers dedup by exact key at finalize (host
    _merge_partials), keeping results exact; `exact` adds the key bits
    as tie-breaks, and no group is split; `exact="count"` keeps the hash
    order and also returns how many runs start INSIDE a block of equal
    hashes (`splits`): at 0, which is what 64 bits make of millions of
    groups, every block holds one key and the table is duplicate-free;
    past 0 the consumer merges by exact key. The tie-break sort carries
    two 32-bit operands a key through the sorting network, and its
    compile grows with every one of them (the chip, PRs 32-35: a 3-key
    aggregate's program 384 s, a 5-key one's past 1,000; counted, that
    program takes a quarter of it): `TIE_BREAK_KEYS`.

    After the sort a group's rows are CONTIGUOUS, and the reduction uses
    that (PR 31). An integer sum is the difference of one running total
    (`prefix.cumsum`) read at the end of the group's run and at the end
    of the run before; the group's key is the key of that same row,
    turned back from its bits into the key's type (`_from_bits64`: a
    NULL key's data reads 0). The only scatter left is 32 bits wide: the
    run-end rows' numbers into their groups' slots (`end_row`). Float
    sums and min / max keep their segment ops (`_reduced_in_row_order`).

    Only (dead, order-key, iota) go through the sorting network. What
    follows the sort goes by two gathers, each of a STACK of int64 rows:
    the summed payloads (and, ordered by the hash alone, the key bits)
    by the sort's permutation; then the key bits, their validity and
    the running totals by `end_row`, `slots` columns of them.

    What the chip measured at 6.0M rows into 1.5M groups, 1.87M slots
    (PERF.md section 6, PRs 26, 28 and 31): `segment_sum` as a 64-bit
    scatter-add 543 ms a payload, whatever it is told of its indices,
    and a 64-bit scatter of the keys 514; against a blocked prefix sum
    11 ms, the 32-bit scatter 36, a gather of 1.87M int64 30. A gather
    is paid by the index and hardly by what an index fetches: 6.0M
    int64 by a permutation 86 ms, three such arrays in one program 331,
    a [3, R] stack 65 — hence the stacks. Carrying the payloads through
    the sort as operands instead costs the sort 37 ms more and its
    COMPILE 160 s more (100 -> 260 s with three operands added; the
    cell's cold set-up 143 -> 248 s): not taken.

    `slots` (static; default: one per row) is how many group slots the
    caller keeps: the gathers run over no more. Groups past it are
    dropped, and `ngroups` still counts them (the caller's overflow
    test). Slots at and past `ngroups` hold zero / False (min / max:
    the identity).

    Returns (ngroups, rep_kdatas, rep_kvalids, reduced_payloads, splits)
    — all slot arrays with groups dense in [0, ngroups); `splits` is None
    unless `exact == "count"`."""
    R = live.shape[0]
    S = R if slots is None else min(int(slots), R)
    nk = len(kdatas)
    kbits = [_bits64(d, v) for d, v in zip(kdatas, kvalids)]
    dead = (~live).astype(jnp.int32)
    iota = jnp.arange(R, dtype=jnp.int32)
    with jax.named_scope("sort"):
        if nk == 1:
            # exact: equal bits tie-break on validity (NULL run != live-0 run)
            out = jax.lax.sort(
                (dead, kbits[0], kvalids[0].astype(jnp.int32), iota), num_keys=3)
            s_kbits, s_kvalids = [out[1]], [out[2] != 0]
        elif exact is True:
            # hash first (cheap comparisons), exact bits as tie-breaks: equal
            # keys are guaranteed contiguous, so the output table can never
            # hold a collision-split duplicate — consumers may emit it
            # directly without a dedup pass. kvalids must join the tie-break:
            # _bits64 zeroes NULL bits, so a NULL key and a live 0 share bits
            # and differ only in validity — without it a hash collision could
            # interleave the two groups. The validity bits go as ONE packed
            # operand (first key's bit highest: the order of the tuple), and
            # the row number is the last KEY, a total order, so the sort
            # need not be stable: the same permutation, and a program the
            # chip's compiler takes a fraction of the time over (PERF.md
            # section 6, PR 32: a stable sort's time grows with every
            # operand it carries, 64-bit ones most)
            if nk > 31:
                raise ValueError(f"{nk} group keys")
            vpack = functools.reduce(
                lambda acc, v: acc * 2 + v.astype(jnp.int32), kvalids,
                jnp.zeros(R, dtype=jnp.int32))
            keys = ((dead, _group_hash(kbits, kvalids)) + tuple(kbits)
                    + (vpack, iota))
            out = jax.lax.sort(keys, num_keys=len(keys), is_stable=False)
            s_kbits = list(out[2:2 + nk])
            s_kvalids = [(out[2 + nk] >> (nk - 1 - i)) & 1 != 0
                         for i in range(nk)]
        else:
            # by the hash alone; the row number is the last KEY, so the
            # sort need not be stable (the same permutation; see above)
            out = jax.lax.sort(
                (dead, _group_hash(kbits, kvalids), iota), num_keys=3,
                is_stable=False)
            s_kbits = s_kvalids = None
        perm = out[-1]

    in_order = [_reduced_in_row_order(op, p.dtype)
                for p, op in zip(payload, reduce_ops)]
    with jax.named_scope("gather"):
        # what follows the sort goes by ONE gather of a stack of rows
        rows = [] if s_kbits is not None else list(kbits)
        rows += [p.astype(jnp.int64) for p, c in zip(payload, in_order) if c]
        # (no row: a GROUP BY without aggregates, ordered by its bits)
        moved = (jnp.take(jnp.stack(rows), perm, axis=1) if rows
                 else jnp.zeros((0, R), dtype=jnp.int64))
        if s_kbits is None:  # ordered by the hash alone: the bits follow
            s_kbits = list(moved[:nk])
            s_kvalids = [jnp.take(v, perm, axis=0) for v in kvalids]
        s_sums = moved[len(rows) - sum(in_order):]

    with jax.named_scope("runs"):
        # live rows are the prefix [0, L) (dead sorts last); a run starts
        # at row 0 or where any exact key component differs from the row
        # before, and ends where the next begins or the live rows end
        L = jnp.sum(live.astype(jnp.int32))
        s_live = iota < L
        diff = jnp.zeros(R, dtype=jnp.bool_)
        for b, v in zip(s_kbits, s_kvalids):
            diff = diff | (b != jnp.roll(b, 1)) | (v != jnp.roll(v, 1))
        newseg = s_live & ((iota == 0) | diff)
        runend = s_live & (jnp.roll(newseg, -1) | (iota == L - 1))
        seg = prefix.cumsum(newseg.astype(jnp.int32)) - 1
        ngroups = (seg[-1] + 1).astype(jnp.int64)
        # the row at which each group's run ends, dense by group: the
        # one scatter, 32 bits wide. Every other row (and a run past the
        # table's last slot) drops out of bounds, so the targets neither
        # ascend nor are unique, and the scatter is told neither: on the
        # chip neither hint bought a millisecond (36 ms at 6.0M rows into
        # 1.87M slots), and `indices_are_sorted` gave wrong rows
        end_row = jnp.zeros(S, dtype=jnp.int32).at[
            jnp.where(runend, seg, S)].set(iota, mode="drop")
        occupied = jnp.arange(S, dtype=jnp.int32) < seg[-1] + 1
        splits = None
        if exact == "count":
            # one key sorts by its bits and the tie-break sort by all of
            # them: neither splits. In hash order a run that starts where
            # the hash does not change is two keys' collision
            splits = jnp.zeros((), dtype=jnp.int64)
            if nk > 1:
                same_hash = out[1] == jnp.roll(out[1], 1)
                splits = jnp.sum((newseg & (iota != 0) & same_hash)
                                 .astype(jnp.int64))

    with jax.named_scope("reduce"):
        # one running total a payload; at a run's end it holds no dead
        # row (they all lie after the last run). Every row of a run holds
        # the same key, so the key is read at the run's end too: again
        # one gather for all of it
        totals = jax.vmap(prefix.cumsum)(s_sums) if any(in_order) else s_sums
        ends = jnp.take(
            jnp.concatenate([jnp.stack(
                s_kbits + [v.astype(jnp.int64) for v in s_kvalids]), totals]),
            end_row, axis=1, mode="clip")
        tot = ends[2 * nk:]
        before = jnp.concatenate(
            [jnp.zeros((tot.shape[0], 1), tot.dtype), tot[:, :-1]], axis=1)
        sums = iter(jnp.where(occupied, tot - before, 0))

        # the others follow the sort on their own and keep their segment
        # ops; dead rows share the last run's id and offer the identity
        seg_all = jnp.maximum(seg, 0)
        reduced = []
        for arr, op, summed in zip(payload, reduce_ops, in_order):
            if summed:
                reduced.append(next(sums).astype(arr.dtype))
                continue
            arr = jnp.take(arr, perm, axis=0)
            if op == "sum":
                contrib = jnp.where(s_live, arr, jnp.zeros((), dtype=arr.dtype))
                reduced.append(jax.ops.segment_sum(contrib, seg_all, num_segments=S))
            elif op == "min":
                reduced.append(jax.ops.segment_min(
                    jnp.where(s_live, arr, jnp.full((), _ident_min(arr.dtype), arr.dtype)),
                    seg_all, num_segments=S))
            elif op == "max":
                reduced.append(jax.ops.segment_max(
                    jnp.where(s_live, arr, jnp.full((), _ident_max(arr.dtype), arr.dtype)),
                    seg_all, num_segments=S))
            else:  # pragma: no cover
                raise ValueError(op)

    with jax.named_scope("keys"):
        rep_kdatas = [_from_bits64(jnp.where(occupied, b, 0), d.dtype)
                      for b, d in zip(ends[:nk], kdatas)]
        rep_kvalids = [occupied & (v != 0) for v in ends[nk:2 * nk]]
    return ngroups, rep_kdatas, rep_kvalids, reduced, splits


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


def _sum_dtype(a: AggSpec):
    """The accumulator of a SUM / AVG state: float64 for a FLOAT
    argument, else int64 (integers, and decimals as scaled integers)."""
    return jnp.float64 if a.arg.type_.kind == TypeKind.FLOAT else jnp.int64


def reduce_paths(aggs: List[AggSpec]) -> List[str]:
    """Per state array of `_state_layout(aggs)`, how `_sort_reduce`
    reduces it: "runs" (a running total read at the run ends) or
    "scatter" (a segment op). What FRAGMENT_REDUCE_PAYLOADS counts."""
    paths = []
    for name, op in _state_layout(aggs):
        j, state = name[1:].split(".")
        dtype = _sum_dtype(aggs[int(j)]) if state == "sum" else jnp.int64
        paths.append("runs" if _reduced_in_row_order(op, dtype) else "scatter")
    return paths


def make_partial_kernel(group_exprs, aggs: List[AggSpec],
                        exact=False):
    """fn(chunk, slots=None) -> group table dict {"n", "k{i}.d",
    "k{i}.v", state...} of `slots` slots (static; default: the chunk's
    capacity; see _sort_reduce).

    `exact` (see _sort_reduce): the table holds every group once, also
    where several keys' mixed hashes collide — for a consumer that emits
    the table as it is (the fragment tier on a mesh of one part); with
    `exact="count"` the table is in hash order and says under "split"
    how many runs a collision split (0: every group once). The host
    executor merges its tables by exact key and leaves it off."""
    layout = _state_layout(aggs)

    def partial(chunk: Chunk, slots: int = None):
        sel = chunk.sel
        kdatas, kvalids = [], []
        for g in group_exprs:
            d, v = eval_expr(g, chunk)
            kdatas.append(d)
            kvalids.append(v)

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

                contrib = jnp.where(ok, d, 0).astype(_sum_dtype(a))
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

        n, rk, rkv, red, splits = _sort_reduce(
            kdatas, kvalids, sel, payload, ops, exact=exact, slots=slots)
        table = {"n": n}
        if splits is not None:
            table["split"] = splits
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
        payload = [cat(name) for name, _ in layout]
        ops = [op for _, op in layout]
        n, rk, rkv, red, _ = _sort_reduce(kdatas, kvalids, live, payload, ops)
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
