"""Open-addressing hash probe for the device joins (Pallas).

The host-tier join (executor/join.py) and the general mesh fragment's
join (parallel/fragment.py) sort their build side and, pre-ISSUE 10,
probed with two `jnp.searchsorted` calls — O(log Rb) dependent gather
rounds per probe element, hostile to TPU (each round is an HBM gather
the next round depends on). The reference's hash join probes an
O(1)-expected hash table instead (ref: executor/'s HashJoinExec build+probe workers;
SURVEY.md:294-296 names this kernel as the planned fast path). This
module supplies that table, consumed two ways: the fragment join
(parallel/fragment.py) builds + probes it inside one shard_map program
via `probe_for_join`, and the main single-chip join (ISSUE 10) builds
it ONCE per join build (ops/join_kernels.build_hash_table) and probes
it per chunk with the table arrays as kernel args. Strategy selection:
`tidb_tpu_join_probe_mode` (off/auto/xla/pallas) through
`resolve_mode` — auto picks the table exactly when the computation
targets TPU. The mesh tier's unique-key join under a segment
aggregate (parallel/distsql.py `_local_join`) never came through here:
it ranks both sides by one sort (PR 26), with no table and no mode.

  * BUILD (XLA, inside the same jit): runs of equal values in the sorted
    hash array become (lo, hi) ranges; each run's FIRST row inserts
    (hash, lo, hi) into an open-addressing table of power-of-two
    capacity ~2x the run count via bounded scatter rounds (linear
    probing; round r claims slot (home + r) & mask with scatter-min
    arbitration). `placed` tracks success — if any run needs more than
    MAX_PROBES displacements the whole probe falls back to searchsorted
    THROUGH lax.cond, so results never depend on table luck.
  * PROBE (Pallas): the table lives in VMEM (the kernel targets
    dimension-sized build sides; capacity is capped so three i32 tables
    fit comfortably), each probe element scans its MAX_PROBES window
    with vectorized selects — no data-dependent loop, no divergence.

Correctness envelope: every inserted run sits within MAX_PROBES slots
of its home (else the searchsorted branch runs), so a probe that scans
the full window and finds no match has PROVEN absence. Duplicate probe
hashes, absent keys, and invalid rows all resolve exactly like
searchsorted — pinned by tests against it (tests/test_ops_probe.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from tidb_tpu.errors import UnsupportedError
from tidb_tpu.ops.segment_sum import pallas_enabled, pallas_interpret
from tidb_tpu.utils.device import target_platform

__all__ = ["probe_ranges", "xla_probe_ranges", "probe_for_join",
           "set_mode", "resolve_mode", "table_capacity", "MAX_CAPACITY"]

import os

# "off": always searchsorted; "auto" (default): hash table when the
# computation targets TPU (trace-time force_platform aware, like
# segment_sum); "xla": hash table everywhere (window-scan probe);
# "pallas": hash table with the Pallas VMEM kernel — CPU interpret only:
# the TPU compiler refuses the kernel (_refuse_pallas_on_tpu), so no
# automatic choice ever picks it.
#
# Auto keeps CPU on searchsorted because it measures faster there
# (bench.py bench_probe — 32 fixed window rounds vs ~2*log2(Rb)
# cache-friendly binary rounds) while TPU gets the VMEM-resident table
# instead of O(log Rb) dependent HBM gather rounds per element.
# Sessions thread tidb_tpu_join_probe_mode PER STATEMENT through
# ExecContext/fragment args (ISSUE 12 — the old per-statement set_mode
# write raced concurrent sessions); this global is only the default
# for offline tools and bare fragments, seeded by the env var.
_mode = os.environ.get("TIDB_HASH_PROBE", "auto")


def set_mode(m: str) -> None:
    """Seed the PROCESS-WIDE default probe mode. Offline tools and bare
    fragments only: engine statements thread the session's resolved
    mode per-statement (ExecContext.join_probe_mode -> fragment args,
    ISSUE 12), so concurrent sessions never race this global. The
    sanitizer's shared-mutable-global witness flags any write that
    lands while a statement is in flight."""
    global _mode
    from tidb_tpu.analysis import sanitizer as _san

    if _san.enabled():
        _san.note_global_write("ops.hash_probe._mode", m)
    _mode = m


PALLAS_PROBE_REFUSAL = "Cannot do int indexing on TPU"


def _refuse_pallas_on_tpu() -> None:
    """'pallas' asked for on a TPU: raise the compiler's refusal typed,
    at plan time, instead of interpreting the kernel or quietly probing
    another way. _probe_pallas gathers table slots by a vector of
    positions (keys_ref[pos]), which Mosaic does not lower;
    tests/test_chip_compile.py pins the compiler's own error."""
    if target_platform() == "tpu":
        raise UnsupportedError(
            "tidb_tpu_join_probe_mode=pallas cannot run on a TPU: the "
            "chip's compiler refuses hash_probe._probe_pallas "
            f"(ValueError: {PALLAS_PROBE_REFUSAL}); use auto, xla or off")


def resolve_mode(mode: str = None) -> str:
    """Concrete probe strategy — 'sorted' | 'xla' | 'pallas' — for the
    platform the CURRENT computation targets (trace-time, so mesh
    fragments under force_platform resolve against the mesh's devices).
    `mode` defaults to the module global the session sysvar wires."""
    m = _mode if mode is None else mode
    if m == "off":
        return "sorted"
    if m == "auto":
        return "xla" if pallas_enabled() else "sorted"
    if m == "pallas":
        _refuse_pallas_on_tpu()
    return m


def probe_for_join(sorted_hashes: jax.Array, probes: jax.Array,
                   mode: str = None):
    """The fragment join's probe entry point: (lo, hi) ranges over the
    sorted build hashes via the configured strategy. ``mode`` is the
    per-statement value threaded from ExecContext through the fragment
    builder (ISSUE 12 — the trace-time global read raced concurrent
    sessions); None falls back to the process default for offline
    tools and bare fragments."""
    m = _mode if mode is None else mode
    if m == "off" or (m == "auto" and not pallas_enabled()):
        lo, hi = xla_probe_ranges(sorted_hashes, probes)
        return lo.astype(jnp.int64), hi.astype(jnp.int64)
    if m == "pallas":
        _refuse_pallas_on_tpu()
    return probe_ranges(sorted_hashes, probes,
                        use_pallas=(m == "pallas"))

MAX_PROBES = 32
# three int32 tables of this capacity ~= 6 MiB of VMEM: dimension-sized
# build sides (the star-join case) qualify; big fact-fact joins keep the
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
    within the VMEM cap, or the build is empty). One definition shared
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
    """Window-scan probe expressed in plain XLA (the same arithmetic the
    Pallas kernel runs; also the interpret-mode/CPU executable path)."""
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


def _probe_pallas(keys, los, his, sh, probes, cap):
    """VMEM-resident table scan: one grid step per probe tile, the three
    [cap] tables mapped whole into VMEM, MAX_PROBES vectorized rounds."""
    from jax.experimental import pallas as pl

    T = 2048
    Rp = probes.shape[0]
    n_tiles = (Rp + T - 1) // T
    pad = n_tiles * T - Rp
    probes_p = jnp.concatenate(
        [probes, jnp.full(pad, -1, dtype=probes.dtype)]) if pad else probes
    mask = cap - 1
    home = (_mix32(probes_p) & mask).astype(jnp.int32)
    fp = _mix32(probes_p, salt=_FP_SALT)
    # probe-side hash identity check runs on the table's lo -> sh lookup;
    # precompute sh as int32 pair to keep the kernel i32-only
    sh_hi = (sh >> 32).astype(jnp.int32)
    sh_lo = sh.astype(jnp.int32)
    pr_hi = (probes_p >> 32).astype(jnp.int32)
    pr_lo = probes_p.astype(jnp.int32)

    def kernel(home_ref, fp_ref, prhi_ref, prlo_ref, keys_ref, los_ref,
               his_ref, shhi_ref, shlo_ref, lo_ref, hi_ref):
        h = home_ref[...]
        f = fp_ref[...]
        phi = prhi_ref[...]
        plo = prlo_ref[...]
        lo = jnp.zeros_like(h)
        hi = jnp.zeros_like(h)
        found = jnp.zeros(h.shape, dtype=jnp.bool_)
        Rb = shhi_ref.shape[0]
        for r in range(MAX_PROBES):
            pos = (h + r) & mask
            k = keys_ref[pos]
            cand = los_ref[pos]
            ci = jnp.clip(cand, 0, Rb - 1)
            hit = ((~found) & (k == f)
                   & (shhi_ref[ci] == phi) & (shlo_ref[ci] == plo))
            lo = jnp.where(hit, cand, lo)
            hi = jnp.where(hit, his_ref[pos], hi)
            found = found | hit
        lo_ref[...] = lo
        hi_ref[...] = hi

    grid = (n_tiles,)
    tile = pl.BlockSpec((T,), lambda i: (i,))
    whole_cap = pl.BlockSpec((cap,), lambda i: (0,))
    whole_rb = pl.BlockSpec((sh.shape[0],), lambda i: (0,))
    lo32, hi32 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[tile, tile, tile, tile, whole_cap, whole_cap, whole_cap,
                  whole_rb, whole_rb],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((n_tiles * T,), jnp.int32)] * 2,
        interpret=pallas_interpret(),
    )(home, fp, pr_hi, pr_lo, keys, los, his, sh_hi, sh_lo)
    return lo32[:Rp].astype(jnp.int64), hi32[:Rp].astype(jnp.int64)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def probe_ranges(sorted_hashes: jax.Array, probes: jax.Array,
                 use_pallas: bool = False):
    """(lo, hi) per probe element over the sorted build hashes —
    numerically identical to searchsorted left/right wherever the join
    consumes them (hi - lo counts and lo + k positions). Falls back to
    searchsorted inside the SAME jit when the table build overflows its
    displacement bound, so callers never see a behavioral difference."""
    from tidb_tpu.ops.join_kernels import _note_trace

    _note_trace("hash_probe")  # trace-time only: joins the retrace guard
    cap = table_capacity(sorted_hashes.shape[0])
    if cap is None:
        # load factor would exceed 1/2 (or VMEM): stay on searchsorted
        return xla_probe_ranges(sorted_hashes, probes)
    keys, los, his, ok = _build_table(sorted_hashes, cap)

    def fast(_):
        if use_pallas:
            return _probe_pallas(keys, los, his, sorted_hashes, probes, cap)
        return _probe_xla(keys, los, his, sorted_hashes, probes, cap)

    def slow(_):
        lo, hi = xla_probe_ranges(sorted_hashes, probes)
        return lo.astype(jnp.int64), hi.astype(jnp.int64)

    return jax.lax.cond(ok, fast, slow, None)
