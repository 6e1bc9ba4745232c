"""Fused, shape-stable kernels for the partitioned device hash join.

The pre-PR join paid two structural costs (BENCH_tpu.json:
join_build_probe_gbps = 0.009 while scans sustained ~10M rows/s):

  * the build side round-tripped through a host ``np.argsort`` (and
    fancy-indexed the sorted keys twice, once per tier);
  * every probe chunk ran through ``counted_jit`` closures minted per
    executor instance, so a repeated join re-traced and re-compiled its
    probe + expand programs on EVERY execution (~hundreds of ms of XLA
    work per query on the CPU backend, far more for the TPU compiler).

This module is the fix: the join's device programs live HERE, at module
level, and take everything query-specific — key arrays, pack ranges,
payload columns — as *arguments*, never as closure state. jax.jit then
keys executables purely on (shapes, dtypes, static flags):

  * build sides are padded to power-of-two buckets (``shape_bucket``),
    so two queries whose build sides land in the same bucket share one
    compiled program, and a steady-state repeated join re-traces nothing;
  * the probe is ONE fused kernel — key pack → range lookup → per-row
    match count → prefix sum — and expansion is one fused kernel
    emitting ``[T, C]`` fixed-capacity output tiles (the same layout
    ``parallel/partition.py`` streams), T output tiles per dispatch
    instead of one dispatch per output window. The range lookup
    (``probe_ranges_any``) is strategy-parameterized (ISSUE 10):
    dense packed domains take the O(1) direct-address index, the
    TPU-shaped path probes the prebuilt open-addressing table
    (``build_hash_table`` / ops/hash_probe, MAX_PROBES vectorized
    window rounds instead of O(log B) dependent gathers), and
    searchsorted remains the CPU default and in-jit fallback;
  * the build sort runs on device: NULL/dead keys are sent to
    ``INT64_MAX`` and sorted to the tail with a stable secondary flag,
    so ``n_build`` (a traced scalar) bounds every probe range exactly
    and the padding can never produce a phantom match — even for a
    legitimate INT64_MAX key, whose valid run sits before the sentinels.

Every kernel body calls ``_note_trace`` as its first statement: the
Python body only runs while jax traces, so ``JOIN_COMPILE_TOTAL`` counts
real XLA (re)compilations, not dispatches. The retrace-guard test and
EXPLAIN ANALYZE's per-operator ``recompiles:`` field both read it.

``parallel/fragment.py``'s general fragment join traces the fragment-tier
primitives at the end of this file inside its shard_map program and
expands through the same ``tile_positions`` as the local executor, so
the two tiers share one definition of the expand arithmetic. How a
probe slot learns its run of equal build hashes there: by default
``merged_hash_ranges``, ONE sort of both sides together and two running
maxima (PR 33: no search, no table; on the chip 80 ms of a Q3 statement
where the searched and table probes took 6.7 s); under
tidb_tpu_join_probe_mode ``off`` / ``xla`` the pair it replaced,
``sort_build_hashes`` + ``probe_hash_ranges`` (the build's own sort,
then the binary search or the open-addressing table), kept as the
in-program references its tests compare it with.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tidb_tpu.ops import prefix
from tidb_tpu.utils import dispatch
from tidb_tpu.utils.hashutil import SM_ADD, SM_MUL1, SM_MUL2

__all__ = [
    "shape_bucket", "as_int64_key", "hash_combine_device", "pack_keys",
    "build_sort", "build_hash_table", "no_table", "probe_count",
    "probe_ranges_any", "expand_tiles",
    "merged_hash_ranges", "sort_build_hashes", "probe_hash_ranges",
    "tile_positions",
]

I64_MAX = np.iinfo(np.int64).max


def shape_bucket(n: int, floor: int = 64) -> int:
    """Next power of two >= max(n, floor): the padding target that makes
    jit signatures stable across nearby build/probe sizes."""
    b = max(int(floor), 1)
    while b < n:
        b *= 2
    return b


def _note_trace(kernel: str) -> None:
    """Trace-time side effect: the enclosing jitted body executes only
    while XLA traces it, so this counts compilations (cache misses),
    never steady-state dispatches."""
    from tidb_tpu.utils.metrics import JOIN_COMPILE_TOTAL

    JOIN_COMPILE_TOTAL.inc(kernel=kernel)
    dispatch.record_compile(kernel)


# -- key packing (device) ---------------------------------------------------

def as_int64_key(d: jax.Array, mode: str) -> jax.Array:
    """Comparable int64 key; floats by bit pattern ('bits' mode)."""
    if mode == "bits":
        return jax.lax.bitcast_convert_type(d.astype(jnp.float64), jnp.int64)
    return d.astype(jnp.int64)


def hash_combine_device(keys_i64) -> jax.Array:
    """uint64 mixing hash of composite int64 keys (splitmix64 finalizer,
    identical to the host combiner in executor/join.py)."""
    h = jnp.zeros_like(keys_i64[0], dtype=jnp.uint64)
    for k in keys_i64:
        z = jax.lax.bitcast_convert_type(k, jnp.uint64) + jnp.uint64(SM_ADD)
        z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(SM_MUL1)
        z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(SM_MUL2)
        z = z ^ (z >> jnp.uint64(31))
        h = h * jnp.uint64(SM_ADD) ^ z
    return jax.lax.bitcast_convert_type(h, jnp.int64)


def _pack_device(key_datas, key_valids, los, strides, rngs, sel,
                 modes: Tuple[str, ...], hash_mode: bool):
    """(packed int64, key-valid, in-range) from per-key arrays + traced
    pack ranges. Mirrors the host packer exactly: range packing with an
    out-of-range mask (a definite non-match, NOT a NULL — anti joins keep
    the row), or the mixing hash when ranges overflowed int64."""
    ones = jnp.ones_like(sel)
    if not key_datas:  # keyless (cross) join: constant key matches all
        return jnp.zeros(sel.shape[0], dtype=jnp.int64), ones, ones
    if hash_mode:
        keys = [as_int64_key(d, m) for d, m in zip(key_datas, modes)]
        valid = key_valids[0]
        for v in key_valids[1:]:
            valid = valid & v
        # every hash is "in range"; exact per-key verification removes
        # false candidates after expansion
        return hash_combine_device(keys), valid, ones
    if len(key_datas) == 1:
        return as_int64_key(key_datas[0], modes[0]), key_valids[0], ones
    packed = jnp.zeros(sel.shape[0], dtype=jnp.int64)
    valid = ones
    in_range = ones
    for i, (d, v) in enumerate(zip(key_datas, key_valids)):
        d = as_int64_key(d, modes[i])
        lo, stride, rng = los[i], strides[i], rngs[i]
        valid = valid & v
        in_range = in_range & (d >= lo) & (d < lo + rng)
        packed = packed + jnp.clip(d - lo, 0, jnp.maximum(rng - 1, 0)) * stride
    return packed, valid, in_range


# the fused scan→probe program (executor/pipeline.py) traces the SAME
# packing step as the standalone probe kernel, so the two cannot drift
# on multi-key range packing or the out-of-range mask
pack_keys = _pack_device


# -- build: pack + sort + payload gather, all on device ---------------------

@functools.partial(jax.jit, static_argnames=("modes", "hash_mode"))
def _build_sort(key_datas, key_valids, ok, payload_datas, payload_valids,
                los, strides, rngs, modes, hash_mode):
    _note_trace("build_sort")
    B = ok.shape[0]
    packed, kvalid, in_range = _pack_device(
        key_datas, key_valids, los, strides, rngs, ok, modes, hash_mode)
    live = ok & kvalid & in_range
    # dead keys -> INT64_MAX; a stable secondary flag sorts them AFTER
    # any legitimate INT64_MAX keys, so [0, n_build) is exactly the live
    # sorted prefix and searchsorted ranges clamp against it losslessly
    skey = jnp.where(live, packed, I64_MAX)
    flag = (~live).astype(jnp.int32)
    sorted_keys, _, order = jax.lax.sort(
        (skey, flag, jnp.arange(B, dtype=jnp.int64)), num_keys=2)
    n_build = jnp.sum(live.astype(jnp.int64))
    out_d = tuple(jnp.take(d, order, mode="clip") for d in payload_datas)
    out_v = tuple(jnp.take(v, order, mode="clip") for v in payload_valids)
    # raw key values build-sorted — only hash-mode exact verification
    # reads them; hash_mode is static, so non-hash builds pay nothing
    out_k = (tuple(jnp.take(as_int64_key(d, m), order, mode="clip")
                   for d, m in zip(key_datas, modes))
             if hash_mode else ())
    return sorted_keys, n_build, out_d, out_v, out_k


def build_sort(key_datas, key_valids, ok, payload_datas, payload_valids,
               los, strides, rngs, modes, hash_mode):
    """Device-resident build: returns (sorted_keys [B], n_build scalar,
    sorted payload datas/valids, sorted raw key values). Inputs must be
    padded to a ``shape_bucket`` capacity with ok=False padding."""
    with dispatch.launch("jit:join.build"):
        return _build_sort(key_datas, key_valids, ok, payload_datas,
                           payload_valids, los, strides, rngs,
                           modes=tuple(modes), hash_mode=bool(hash_mode))


# -- direct-address (radix-histogram) index over the packed-key domain ------

@functools.partial(jax.jit, static_argnames=("rng_bucket",))
def _build_direct_index(sorted_keys, n_build, lo, rng_bucket):
    _note_trace("direct_index")
    B = sorted_keys.shape[0]
    live = jnp.arange(B, dtype=jnp.int64) < n_build
    idx = jnp.clip(sorted_keys - lo, 0, rng_bucket - 1)
    # radix histogram by scatter-add: counts[k] = live keys equal to lo+k
    # (every live key is in [lo, lo+rng) by construction of the index)
    counts = jnp.zeros(rng_bucket + 1, dtype=jnp.int64).at[
        jnp.where(live, idx, rng_bucket)].add(1, mode="drop")
    # firsts[i] = first sorted position with key >= lo + i; probes then
    # resolve in O(1) gathers instead of O(log B) dependent rounds
    return jnp.concatenate([jnp.zeros(1, dtype=jnp.int64),
                            prefix.cumsum(counts[:rng_bucket])])


def build_direct_index(sorted_keys, n_build, lo, rng_bucket: int):
    """[rng_bucket + 1] run-start positions over the dense packed-key
    domain [lo, lo + rng_bucket): the partition-then-probe structure.
    Built once per join build; XLA:CPU measures the O(1) gather probe
    ~30x faster than its searchsorted lowering (and on TPU it replaces
    log(B) dependent gather rounds with two vector gathers)."""
    with dispatch.launch("jit:join.build"):
        return _build_direct_index(sorted_keys, n_build,
                                   jnp.asarray(lo, dtype=jnp.int64),
                                   rng_bucket=int(rng_bucket))


# -- open-addressing hash table over the sorted build keys ------------------

@functools.partial(jax.jit, static_argnames=("cap",))
def _build_hash_table(sorted_keys, cap):
    _note_trace("hash_table")
    from tidb_tpu.ops.hash_probe import _build_table

    return _build_table(sorted_keys, cap)


def build_hash_table(sorted_keys):
    """(keys32, lo32, hi32, all_placed) open-addressing table over the
    sorted build keys — built ONCE per join build (like the
    direct-address index) and passed to every probe_count as args, so
    the per-chunk probe is MAX_PROBES vectorized window rounds instead
    of O(log B) dependent searchsorted gathers (ISSUE 10: the TPU-shaped
    main-join probe). Returns None when the build side exceeds the VMEM
    capacity envelope — the caller stays on searchsorted. The sentinel
    tail (NULL/dead keys at INT64_MAX) forms ordinary runs whose ranges
    the probe's n_build clamp truncates exactly like searchsorted's."""
    from tidb_tpu.ops.hash_probe import table_capacity

    cap = table_capacity(sorted_keys.shape[0])
    if cap is None:
        return None
    with dispatch.launch("jit:join.build"):
        return _build_hash_table(sorted_keys, cap=cap)


_NO_TABLE = None


def no_table():
    """Placeholder table args for the searchsorted path: tiny constant-
    shape arrays the kernel's static 'sorted' branch never reads (XLA
    dead-code-eliminates them), keeping one probe_count signature.
    Memoized — probe_count runs once per probe chunk, and minting four
    device constants per chunk would tax the very hot path this module
    exists to thin (first call is lazy so no arrays materialize at
    import, before backend selection)."""
    global _NO_TABLE
    if _NO_TABLE is None:
        _NO_TABLE = (jnp.full(2, 0x7FFFFFFF, dtype=jnp.int32),
                     jnp.zeros(2, dtype=jnp.int32),
                     jnp.zeros(2, dtype=jnp.int32), jnp.asarray(False))
    return _NO_TABLE


def probe_ranges_any(sorted_keys, n_build, packed, firsts, lo_packed,
                     rng_packed, tkeys, tlos, this, tok,
                     direct: bool, probe: str):
    """(start, end, in_range) match ranges per packed probe key — THE
    range-lookup step, traced inside both the standalone probe kernel
    and the fused scan→probe program so the two cannot drift. Strategy
    is static: 'direct' wins when the dense-domain index exists (two
    O(1) gathers beat any hash walk), else the open-addressing table
    ('xla': the window scan) with the in-jit lax.cond
    searchsorted fallback when the build overflowed its displacement
    bound, else plain searchsorted. Ranges clamp to n_build so the
    NULL/dead/padding sentinel tail can never produce a match."""
    from tidb_tpu.ops import hash_probe as hp

    ones = jnp.ones(packed.shape[0], dtype=jnp.bool_)
    if direct:
        # dense domain: two gathers into the radix histogram's prefix sums
        idx = packed - lo_packed
        in_range = (idx >= 0) & (idx < rng_packed)
        idxc = jnp.clip(idx, 0, firsts.shape[0] - 2)
        return jnp.take(firsts, idxc), jnp.take(firsts, idxc + 1), in_range
    if probe != "sorted":
        def fast(_):
            return hp._probe_xla(tkeys, tlos, this, sorted_keys, packed,
                                 tkeys.shape[0])

        def slow(_):
            lo = jnp.searchsorted(sorted_keys, packed, side="left")
            hi = jnp.searchsorted(sorted_keys, packed, side="right")
            return lo.astype(jnp.int64), hi.astype(jnp.int64)

        start, end = jax.lax.cond(tok, fast, slow, None)
    else:
        start = jnp.searchsorted(sorted_keys, packed, side="left")
        end = jnp.searchsorted(sorted_keys, packed, side="right")
    # the region past n_build holds NULL/dead/padding sentinels: clamp
    # so a probe of INT64_MAX counts only the genuine run
    return (jnp.minimum(start, n_build), jnp.minimum(end, n_build), ones)


# -- probe: pack + range lookup + count + prefix sum, one kernel ------------

@functools.partial(jax.jit, static_argnames=("modes", "hash_mode",
                                             "left_pad", "direct", "probe"))
def _probe_count(sorted_keys, n_build, key_datas, key_valids, sel,
                 los, strides, rngs, firsts, lo_packed, rng_packed,
                 tkeys, tlos, this, tok,
                 modes, hash_mode, left_pad, direct, probe):
    _note_trace("probe")
    packed, kvalid, in_range = _pack_device(
        key_datas, key_valids, los, strides, rngs, sel, modes, hash_mode)
    ok = kvalid & sel
    start, end, range_ok = probe_ranges_any(
        sorted_keys, n_build, packed, firsts, lo_packed, rng_packed,
        tkeys, tlos, this, tok, direct, probe)
    in_range = in_range & range_ok
    count = jnp.where(ok & in_range, end - start, 0)
    matched = count > 0
    real_count = count
    if left_pad:
        # unfiltered LEFT JOIN: every live probe row emits >= 1 slot; the
        # slot beyond real_count carries NULL build payload
        count = jnp.where(sel, jnp.maximum(count, 1), 0)
    cum = prefix.cumsum(count)
    return start, count, real_count, cum, cum[-1], ok, matched


def probe_count(sorted_keys, n_build, key_datas, key_valids, sel,
                los, strides, rngs, firsts, lo_packed, rng_packed,
                modes, hash_mode, left_pad, direct,
                table=None, probe="sorted"):
    """Fused probe over one chunk: (start, count, real_count, cum, total,
    ok, matched). ``total`` is the only value a caller syncs to the
    host (to size the expansion). ``table`` is the prebuilt
    open-addressing table (build_hash_table) consulted when ``probe``
    is 'xla'; 'sorted' takes placeholder args and the
    searchsorted branch."""
    from tidb_tpu.utils.metrics import JOIN_PROBE_MODE_TOTAL

    probe = "sorted" if table is None else str(probe)
    JOIN_PROBE_MODE_TOTAL.inc(mode="direct" if direct else probe)
    tkeys, tlos, this, tok = table if table is not None else no_table()
    with dispatch.launch("jit:join.probe"):
        return _probe_count(sorted_keys, n_build, key_datas, key_valids, sel,
                            los, strides, rngs, firsts,
                            jnp.asarray(lo_packed, dtype=jnp.int64),
                            jnp.asarray(rng_packed, dtype=jnp.int64),
                            tkeys, tlos, this, tok,
                            modes=tuple(modes), hash_mode=bool(hash_mode),
                            left_pad=bool(left_pad), direct=bool(direct),
                            probe=probe)


# -- shared expand-position arithmetic --------------------------------------

def tile_positions(start, count, cum, w0, n_slots: int,
                   n_probe_cap: int, n_build_cap: int):
    """Map output slots [w0, w0 + n_slots) to (valid_out, probe_row,
    build_pos, k).

    The single source of truth for windowed join expansion — traced both
    inside ``expand_tiles`` (local executor) and inside the fragment
    tier's shard_map program, so the two tiers cannot drift.

    probe_row is recovered with a scatter + prefix sum over the window
    (probe_row(j) = #{r : cum[r] <= w0 + j} = a scalar searchsorted at
    the window base plus the running count of row boundaries inside the
    window) instead of an elementwise searchsorted — O(R + n_slots)
    vector work where XLA:CPU's searchsorted lowering paid ~20 ms per
    2^17-slot window."""
    w0 = jnp.asarray(w0, dtype=jnp.int64)
    total = cum[-1]
    j = w0 + jnp.arange(n_slots, dtype=jnp.int64)
    valid_out = j < total
    row0 = jnp.searchsorted(cum, w0, side="right")  # scalar: window base
    bound = cum - w0  # row r's matches end at window-relative slot bound[r]
    in_win = (bound >= 1) & (bound <= n_slots - 1)
    marks = jnp.zeros(n_slots + 1, dtype=jnp.int64).at[
        jnp.where(in_win, bound, n_slots)].add(1, mode="drop")
    probe_row = jnp.clip(row0 + prefix.cumsum(marks[:n_slots]),
                         0, n_probe_cap - 1)
    k = j - (cum[probe_row] - count[probe_row])
    build_pos = jnp.clip(start[probe_row] + k, 0, max(n_build_cap - 1, 0))
    return valid_out, probe_row, build_pos, k


# -- expand: gather probe + build payload into [T, C] tiles -----------------

@functools.partial(jax.jit, static_argnames=(
    "n_tiles", "tile_cap", "build_cap", "left",
    "with_probe_row", "with_build_pos"))
def _expand_tiles(start, count, real_count, cum, w0,
                  probe_datas, probe_valids, build_datas, build_valids,
                  n_tiles, tile_cap, build_cap, left,
                  with_probe_row, with_build_pos):
    _note_trace("expand")
    R = start.shape[0]
    # build_cap is explicit, NOT inferred from the payload: semi/anti
    # joins carry no payload columns but still need exact __build_pos__
    # for hash-mode key verification
    B = build_cap
    valid_out, probe_row, build_pos, k = tile_positions(
        start, count, cum, w0, n_tiles * tile_cap, R, B)
    real = k < real_count[probe_row]

    def shape(a):
        return a.reshape(n_tiles, tile_cap)

    out_p = tuple((shape(jnp.take(d, probe_row, mode="clip")),
                   shape(jnp.take(v, probe_row, mode="clip") & valid_out))
                  for d, v in zip(probe_datas, probe_valids))
    out_b = []
    for d, v in zip(build_datas, build_valids):
        bv = jnp.take(v, build_pos, mode="clip") & valid_out
        if left:
            # the left-join pad slot (k beyond the real match count)
            # carries NULL build payload
            bv = bv & real
        out_b.append((shape(jnp.take(d, build_pos, mode="clip")), shape(bv)))
    prow = shape(probe_row) if with_probe_row else None
    bpos = shape(build_pos) if with_build_pos else None
    return out_p, tuple(out_b), shape(valid_out), prow, bpos


def expand_tiles(start, count, real_count, cum, w0,
                 probe_datas, probe_valids, build_datas, build_valids,
                 n_tiles, tile_cap, build_cap, left=False,
                 with_probe_row=False, with_build_pos=False):
    """One fused dispatch emitting ``n_tiles`` output tiles of capacity
    ``tile_cap`` ([T, C] arrays — the partition.py streaming layout)
    starting at flat output offset ``w0``."""
    with dispatch.launch("jit:join.expand"):
        return _expand_tiles(
            start, count, real_count, cum, jnp.asarray(w0, dtype=jnp.int64),
            tuple(probe_datas), tuple(probe_valids),
            tuple(build_datas), tuple(build_valids),
            n_tiles=int(n_tiles), tile_cap=int(tile_cap),
            build_cap=int(build_cap), left=bool(left),
            with_probe_row=bool(with_probe_row),
            with_build_pos=bool(with_build_pos))


# -- fragment-tier primitives (traced inside shard_map) ---------------------

_SLOT_BITS = 30  # slot number bits under the 2-bit tag in the sort's uint32 second key


def merged_hash_ranges(b_hash, b_live, p_hash, p_ok):
    """The fragment join's default rank: ONE sort of both sides' hashes
    together, and every probe slot's run of equal live build hashes read
    off the merged order by two running maxima. Returns (start, cnt,
    slot), `start` and `cnt` int32 a probe slot, `slot` int32 a merged
    place: the slot numbers in merged order, a build row's its row
    number, probe slot j's Rb + j. Probe slot j matches the build rows
    ``slot[start[j] : start[j] + cnt[j]]``, the live rows of its hash in
    the order of their row numbers; `cnt` is 0 for a dead probe slot and
    where nothing matches, and `start` then means nothing. The build
    rows of `slot`, in their sequence, are `sort_build_hashes`'
    permutation (by hash, live before dead, then by row number), so the
    matches and their order are the searched and the table paths' to the
    bit.

    No search, no table, no gather: on a v5e a sort of N slots costs less
    than one round of a binary search over N slots, and a search is 21
    rounds left and 21 right (PERF.md section 6, PRs 26, 32, 33).

    Validity is a sort key and never an in-band sentinel (`_local_join`'s
    rule): a 2-bit tag above the slot number, 0 a live build row, 1 a
    dead one, 2 a live probe slot, 3 a dead one. Within a run of equal
    hashes the live build rows therefore come first and are contiguous
    from the run's head; every operand is a key and the order is total,
    so the sort need not be stable (what that saves the chip's compiler:
    `sort_build_hashes`). What goes back to probe-slot order is 32 bits
    wide and written once a slot (a 64-bit scatter costs ten times a
    32-bit one here, PERF.md section 5).

    Limit: build plus probe slots of one shard stay under 2**30; more
    raises when the fragment is traced, before anything runs."""
    Rb, Rp = b_hash.shape[0], p_hash.shape[0]
    n = Rb + Rp
    if n >= 1 << _SLOT_BITS:
        raise ValueError(
            f"fragment join: {n} build+probe slots on one shard, "
            f"limit {(1 << _SLOT_BITS) - 1}; use more shards")
    # scoped as the searched path's two steps are (parallel/fragment.py):
    # the sort is this join's build, the rest its probe
    with jax.named_scope("join.build"):
        tag = jnp.concatenate([jnp.where(b_live, 0, 1),
                               jnp.where(p_ok, 2, 3)]).astype(jnp.uint32)
        sh, st = jax.lax.sort(
            (jnp.concatenate([b_hash, p_hash]),
             (tag << _SLOT_BITS) | jnp.arange(n, dtype=jnp.uint32)),
            num_keys=2, is_stable=False)
    with jax.named_scope("join.probe"):
        tag = st >> _SLOT_BITS
        slot = (st & jnp.uint32((1 << _SLOT_BITS) - 1)).astype(jnp.int32)
        pos = jnp.arange(n, dtype=jnp.int32)
        head = jnp.concatenate(
            [jnp.ones(1, dtype=jnp.bool_), sh[1:] != sh[:-1]])
        # places only grow, so a running maximum carries the nearest one
        # at or before each row: its run's head, and the last live build
        # row
        start = prefix.cummax(jnp.where(head, pos, 0))
        last_live = prefix.cummax(jnp.where(tag == 0, pos, -1))
        cnt = jnp.where(tag == 2, jnp.maximum(last_live - start + 1, 0), 0)
        # back to probe-slot order: the build side's own slots come first
        # in the numbering and fall outside, dropped
        at = jnp.where(tag >= 2, slot - Rb, Rp)
        start, cnt = (jnp.zeros(Rp, dtype=jnp.int32).at[at].set(v, mode="drop")
                      for v in (start, cnt))
    return start, cnt, slot


def sort_build_hashes(b_hash, b_live):
    """Sorted-run build for the fragment join's searched and table
    probes (tidb_tpu_join_probe_mode off / xla; the default ranks by
    `merged_hash_ranges`): (sorted hashes, cvi, order) where dead rows
    sort after live rows of the same hash and ``cvi[i]`` counts live
    rows in the sorted prefix [0, i) — so (cvi[hi] - cvi[lo]) is an
    exact live-match count per range."""
    Rb = b_hash.shape[0]
    if Rb >= 1 << 31:
        raise ValueError(f"a build side of {Rb} slots a shard")
    # ONE 32-bit operand beside the hash: the row number with the dead
    # flag as its top bit. As the second key it orders live before dead
    # and rows by number, a total order, so the sort need not be stable:
    # the same permutation as a stable sort by (hash, dead) carrying an
    # int64 row number, which the chip's compiler took five times as
    # long over (257 s against 50 s for 1.5M rows, 115 against 40 for
    # 61,440: a described v5e, PERF.md)
    tag = (jnp.arange(Rb, dtype=jnp.uint32)
           | ((~b_live).astype(jnp.uint32) << 31))
    sh, tag = jax.lax.sort((b_hash, tag), num_keys=2, is_stable=False)
    cvi = jnp.concatenate([
        jnp.zeros(1, dtype=jnp.int64),
        prefix.cumsum((tag >> 31 == 0).astype(jnp.int64)),
    ])
    return sh, cvi, (tag & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)


def probe_hash_ranges(sh, cvi, p_hash, p_ok, mode=None):
    """(lo, cnt, path) per probe row over a sorted build-hash array,
    through the configured probe strategy (ops/hash_probe: open-addressing
    table on TPU, searchsorted elsewhere — identical range semantics;
    `path` says which was traced, "table" or "search").
    ``mode`` threads the per-statement tidb_tpu_join_probe_mode from
    the fragment args (ISSUE 12); None = process default."""
    from tidb_tpu.ops.hash_probe import probe_for_join

    lo, hi, path = probe_for_join(sh, p_hash, mode=mode)
    cnt = jnp.where(p_ok, cvi[hi] - cvi[lo], 0)
    return lo, cnt, path
