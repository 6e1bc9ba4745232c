"""Open-addressing hash probe for the device joins.

The host-tier join (executor/join.py) sorts its build side and,
pre-ISSUE 10, probed with two `jnp.searchsorted` calls — O(log Rb)
dependent gather rounds per probe element, hostile to TPU (each round is
an HBM gather the next round depends on). The reference's hash join
probes an O(1)-expected hash table instead (ref: executor/'s HashJoinExec build+probe workers;
SURVEY.md:294-296 names this kernel as the planned fast path). This
module supplies that table: the main single-chip join (ISSUE 10) builds
it ONCE per join build (ops/join_kernels.build_hash_table) and probes
it per chunk with the table arrays as kernel args. Strategy selection
there: `tidb_tpu_join_probe_mode` (off/auto/xla) through `resolve_mode`
— auto picks the table exactly when the computation targets TPU.

The mesh tier does not come through here by default. Its unique-key
join under a segment aggregate (parallel/distsql.py `_local_join`) ranks
both sides by one sort (PR 26), with no table and no mode; the general
fragment's join (parallel/fragment.py) does the same under `auto` since
PR 33 (ops/join_kernels.merged_hash_ranges: the chip put the table probe
at 1.6 s for 1.5M probes and the search at 5.1 s for 3.1M, the merged
rank at 0.08 s for both) and builds + probes this table inside its
shard_map program via `probe_for_join` only where the statement forces
`xla` (`off`: the search) — the references its tests compare with.

  * BUILD (XLA, inside the same jit): runs of equal values in the sorted
    hash array become (lo, hi) ranges; each run's FIRST row inserts
    (hash, lo, hi) into an open-addressing table of power-of-two
    capacity ~2x the run count via bounded scatter rounds (linear
    probing; round r claims slot (home + r) & mask with scatter-min
    arbitration). `placed` tracks success — if any run needs more than
    MAX_PROBES displacements the whole probe falls back to searchsorted
    THROUGH lax.cond, so results never depend on table luck.
  * PROBE (XLA): each probe element scans its MAX_PROBES window with
    vectorized selects over gathers of the table — no data-dependent
    loop, no divergence. Not a Pallas kernel over a VMEM-resident
    table: the v5e compiler refuses a gather by a vector of positions
    inside a kernel ("Cannot do int indexing on TPU").

Correctness envelope: every inserted run sits within MAX_PROBES slots
of its home (else the searchsorted branch runs), so a probe that scans
the full window and finds no match has PROVEN absence. Duplicate probe
hashes, absent keys, and invalid rows all resolve exactly like
searchsorted — pinned by tests against it (tests/test_ops_probe.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tidb_tpu.ops.segment_sum import pallas_enabled

__all__ = ["probe_ranges", "xla_probe_ranges", "probe_for_join",
           "set_mode", "resolve_mode", "table_capacity", "MAX_CAPACITY"]

# "off": always searchsorted; "auto" (default): hash table when the
# computation targets TPU (trace-time force_platform aware, like
# segment_sum), searchsorted elsewhere; "xla": hash table everywhere.
# (What "auto" means in the general fragment's join, which does not come
# here under it: the comment above tidb_tpu_join_probe_mode in
# session/sysvars.py.) Which of table and search is faster in the host
# tier's join is not measured on the chip (no cell reaches it: ROADMAP D3).
# Sessions thread tidb_tpu_join_probe_mode PER STATEMENT through
# ExecContext/fragment args (ISSUE 12 — the old per-statement set_mode
# write raced concurrent sessions); this global is only the default
# for bare fragments.
_mode = "auto"


def set_mode(m: str) -> None:
    """Seed the PROCESS-WIDE default probe mode. Bare fragments only:
    engine statements thread the session's resolved mode per-statement
    (ExecContext.join_probe_mode -> fragment args, ISSUE 12), so
    concurrent sessions never race this global. The sanitizer's
    shared-mutable-global witness flags any write that lands while a
    statement is in flight."""
    global _mode
    from tidb_tpu.analysis import sanitizer as _san

    if _san.enabled():
        _san.note_global_write("ops.hash_probe._mode", m)
    _mode = m


def resolve_mode(mode: str = None) -> str:
    """Concrete probe strategy — 'sorted' | 'xla' — for the platform
    the CURRENT computation targets (trace-time, so mesh fragments
    under force_platform resolve against the mesh's devices). `mode`
    defaults to the module global the session sysvar wires."""
    m = _mode if mode is None else mode
    if m == "off":
        return "sorted"
    if m == "auto":
        return "xla" if pallas_enabled() else "sorted"
    return m


def probe_for_join(sorted_hashes: jax.Array, probes: jax.Array,
                   mode: str = None):
    """The fragment join's forced probes (`off`, `xla`; `auto` ranks by
    the merged sort and never calls this): (lo, hi, path) — the
    ranges over the sorted build hashes via the configured strategy, and
    which of the two paths was traced for them (static): "table", the
    open-addressing table (which still falls back inside the program if
    a run finds no slot in MAX_PROBES), or "search", the binary search
    by gathers — the sorted strategy, or a build past the table's half
    load within MAX_CAPACITY. ``mode`` is the per-statement value
    threaded from ExecContext through the fragment builder (ISSUE 12 —
    the trace-time global read raced concurrent sessions); None falls
    back to the process default for bare fragments."""
    if resolve_mode(mode) == "sorted":
        lo, hi = xla_probe_ranges(sorted_hashes, probes)
        return lo.astype(jnp.int64), hi.astype(jnp.int64), "search"
    return _table_or_search(sorted_hashes, probes)


MAX_PROBES = 32
# three int32 tables of this capacity ~= 6 MiB: dimension-sized build
# sides (the star-join case) qualify; big fact-fact joins keep the
# searchsorted path
MAX_CAPACITY = 1 << 19

# a numpy scalar, not a jax array: this module is first imported lazily,
# possibly inside a shard_map trace, and a jax array made there would be
# typed with that trace's mesh and refused under any other mesh
_EMPTY = np.int32(0x7FFFFFFF)


def _mix32(h: jax.Array, salt: int = 0) -> jax.Array:
    """int64 hash -> well-spread int32 (splitmix tail); `salt` derives
    the independent fingerprint stream."""
    h = h.astype(jnp.uint64) ^ jnp.uint64(salt)
    h = (h ^ (h >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    h = (h ^ (h >> 27)) * jnp.uint64(0x94D049BB133111EB)
    out = (h ^ (h >> 31)).astype(jnp.uint32).astype(jnp.int32)
    # the table's EMPTY sentinel must be unreachable as a fingerprint:
    # a run stored as 0x7FFFFFFF would look like a free slot (silent
    # match loss); remap it consistently on build AND probe sides
    return jnp.where(out == _EMPTY, jnp.int32(0), out)


_FP_SALT = 0x9E3779B97F4A7C15


def xla_probe_ranges(sorted_hashes: jax.Array, probes: jax.Array):
    """Reference path: (lo, hi) = searchsorted left/right."""
    lo = jnp.searchsorted(sorted_hashes, probes, side="left")
    hi = jnp.searchsorted(sorted_hashes, probes, side="right")
    return lo, hi


def _next_pow2(n: int) -> int:
    c = 1
    while c < n:
        c *= 2
    return c


def table_capacity(n_build: int):
    """Open-addressing table capacity for an `n_build`-row build side,
    or None when the table is ineligible (load factor would exceed 1/2
    within MAX_CAPACITY, or the build is empty). One definition shared
    by probe_ranges (fragment tier, in-jit) and the main join's
    build-time table construction (ops/join_kernels.build_hash_table)."""
    if n_build == 0:
        return None
    cap = min(_next_pow2(max(2 * n_build, 16)), MAX_CAPACITY)
    if cap < 2 * n_build:
        return None
    return cap


def _build_table(sh: jax.Array, cap: int):
    """(keys32[cap], lo32[cap], hi32[cap], all_placed) from the sorted
    hash array. keys32 stores the mixed 32-bit fingerprint of the run's
    hash; collisions between DIFFERENT 64-bit hashes on both slot AND
    fingerprint are resolved by verifying via the (lo) range's actual
    hash at probe time."""
    Rb = sh.shape[0]
    idx = jnp.arange(Rb, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones(1, dtype=jnp.bool_), sh[1:] != sh[:-1]])
    # hi of the run starting at i = index of the NEXT start (suffix min)
    start_pos = jnp.where(is_start, idx, Rb)
    next_start = jnp.flip(jax.lax.cummin(jnp.flip(
        jnp.concatenate([start_pos[1:], jnp.array([Rb], jnp.int32)]))))
    mask = cap - 1
    home = _mix32(sh) & mask
    fp = _mix32(sh, salt=_FP_SALT)

    # one PARKING slot at index cap: losers scatter there, never into a
    # live slot (a parked .set at a shared fixed index could clobber a
    # genuine win landing on that same slot in the same scatter)
    keys = jnp.full(cap + 1, _EMPTY, dtype=jnp.int32)
    los = jnp.zeros(cap + 1, dtype=jnp.int32)
    his = jnp.zeros(cap + 1, dtype=jnp.int32)
    placed = ~is_start  # non-starts have nothing to insert

    def round_(r, state):
        keys, los, his, placed = state
        pos = (home + r) & mask
        want = ~placed
        # scatter-min arbitration: the lowest claiming row wins the slot
        claim = jnp.full(cap + 1, jnp.iinfo(jnp.int32).max, dtype=jnp.int32)
        claim = claim.at[jnp.where(want, pos, cap)].min(
            jnp.where(want, idx, jnp.iinfo(jnp.int32).max))
        free = keys[pos] == _EMPTY
        won = want & free & (claim[pos] == idx)
        park = jnp.where(won, pos, cap)
        keys = keys.at[park].set(jnp.where(won, fp, _EMPTY))
        los = los.at[park].set(jnp.where(won, idx, 0))
        his = his.at[park].set(jnp.where(won, next_start, 0))
        return keys, los, his, placed | won

    keys, los, his, placed = jax.lax.fori_loop(
        0, MAX_PROBES, round_, (keys, los, his, placed))
    return keys[:cap], los[:cap], his[:cap], placed.all()


def _probe_xla(keys, los, his, sh, probes, cap):
    """Window-scan probe: MAX_PROBES rounds of vectorized selects over
    gathers of the table."""
    mask = cap - 1
    home = _mix32(probes) & mask
    fp = _mix32(probes, salt=_FP_SALT)
    lo = jnp.zeros(probes.shape[0], dtype=jnp.int32)
    hi = jnp.zeros(probes.shape[0], dtype=jnp.int32)
    found = jnp.zeros(probes.shape[0], dtype=jnp.bool_)

    def round_(r, state):
        lo, hi, found = state
        pos = (home + r) & mask
        k = keys[pos]
        cand_lo = los[pos]
        # fingerprint match is only a CANDIDATE: verify via the run's
        # actual 64-bit hash (two different hashes can share slot + fp)
        hit = (~found) & (k == fp) & (sh[jnp.clip(cand_lo, 0, sh.shape[0] - 1)]
                                      == probes)
        lo = jnp.where(hit, cand_lo, lo)
        hi = jnp.where(hit, his[pos], hi)
        found = found | hit
        return lo, hi, found

    lo, hi, found = jax.lax.fori_loop(
        0, MAX_PROBES, round_, (lo, hi, found))
    # miss => empty range (searchsorted yields lo == hi there; the join
    # only consumes hi - lo and lo + k under cnt, so any equal pair works)
    lo = jnp.where(found, lo, 0)
    hi = jnp.where(found, hi, 0)
    return lo.astype(jnp.int64), hi.astype(jnp.int64)


@jax.jit
def probe_ranges(sorted_hashes: jax.Array, probes: jax.Array):
    """(lo, hi) per probe element over the sorted build hashes —
    numerically identical to searchsorted left/right wherever the join
    consumes them (hi - lo counts and lo + k positions). Falls back to
    searchsorted inside the SAME jit when the table build overflows its
    displacement bound, so callers never see a behavioral difference."""
    return _table_or_search(sorted_hashes, probes)[:2]


def _table_or_search(sorted_hashes: jax.Array, probes: jax.Array):
    """`probe_ranges`' (lo, hi) and the path traced for them: "table" or
    "search" (`probe_for_join`)."""
    from tidb_tpu.ops.join_kernels import _note_trace

    _note_trace("hash_probe")  # trace-time only: joins the retrace guard
    cap = table_capacity(sorted_hashes.shape[0])
    if cap is None:
        # load factor would exceed 1/2 within MAX_CAPACITY: stay on
        # searchsorted
        return (*xla_probe_ranges(sorted_hashes, probes), "search")
    keys, los, his, ok = _build_table(sorted_hashes, cap)

    def fast(_):
        return _probe_xla(keys, los, his, sorted_hashes, probes, cap)

    def slow(_):
        lo, hi = xla_probe_ranges(sorted_hashes, probes)
        return lo.astype(jnp.int64), hi.astype(jnp.int64)

    return (*jax.lax.cond(ok, fast, slow, None), "table")
