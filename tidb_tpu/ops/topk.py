"""Fused, shape-stable device top-k kernels (ISSUE 18).

``executor/sort.py`` materializes EVERY child row to host runs (one
``device_get`` per chunk) before a single ``np.lexsort`` picks the
``LIMIT k`` survivors — for an ORDER BY+LIMIT root over a fact table
that is a full-table host round trip to keep ~10 rows. This module is
the device side of ``FusedScanTopNExec``: a bounded top-k state of
capacity C (``shape_bucket(offset + count)``) rides ACROSS staged scan
chunks exactly like the fused aggregate state, merged per chunk by one
``jax.lax.sort`` over the concatenated [C + N] key operands, and the
host fetches the C winners exactly once at finalize.

The sort semantics replicate ``executor/sort.py::_sort_order`` EXACTLY
(MySQL NULL ordering — NULLs first ASC / last DESC — via a null-rank
operand that dominates the value within each key, DESC by negation,
bools widened to int64, floats compared as float64) plus a trailing
global drain-position operand, so ties resolve in drain order just like
``np.lexsort``'s stability and fused == classic row-for-row.

Like ``join_kernels``, everything query-specific arrives as arguments;
the per-key DESC flags and value dtypes are static trace parameters.
The helpers here are traced INSIDE the fused scan→topk program minted
through ``cached_jit`` (the ``probe_ranges_any`` pattern), so they
carry no ``_note_trace`` of their own; the standalone ``merge_topk``
entry point exists for kernel-level tests and non-fused callers.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tidb_tpu.ops import prefix
from tidb_tpu.utils import dispatch

__all__ = ["rank_operands", "topk_init", "topk_merge", "merge_topk",
           "key_spec"]


def key_spec(type_) -> bool:
    """Static per-key value-dtype flag: True when the key compares as
    float64 (else int64). Derived from the column/expression SQLType so
    the state arrays minted by ``topk_init`` and the merged operands
    produced by ``rank_operands`` can never disagree on dtype."""
    return bool(np.issubdtype(type_.np_dtype, np.floating))


def rank_operands(data, valid, desc: bool):
    """One sort key -> its (null-rank, value) operand pair, mirroring
    ``_sort_order`` exactly: ASC ranks NULLs (0) before values (1),
    DESC ranks NULLs (1) after values (0) and negates the value; NULL
    slots carry 0 so the rank operand alone decides them."""
    d = data
    if d.dtype == jnp.bool_:
        d = d.astype(jnp.int64)
    if jnp.issubdtype(d.dtype, jnp.floating):
        d = d.astype(jnp.float64)
    else:
        d = d.astype(jnp.int64)
    if desc:
        d = -d
        nullrank = (~valid).astype(jnp.int32)
    else:
        nullrank = valid.astype(jnp.int32)
    d = jnp.where(valid, d, jnp.zeros_like(d))
    return nullrank, d


def topk_init(cap: int, key_floats: Sequence[bool],
              payload_dtypes: Sequence[np.dtype]):
    """The empty device top-k state: every slot dead (dead=1 sorts
    after any live row), zeroed key operands and payload, and the
    global drain-position counter at 0. Layout:

        (dead [C] i32,
         ((nullrank [C] i32, value [C] i64|f64), ...) per sort key,
         pos [C] i64, next_pos scalar i64,
         ((data [C], valid [C] bool), ...) per output column)
    """
    dead = jnp.ones(cap, dtype=jnp.int32)
    ranks = tuple(
        (jnp.zeros(cap, dtype=jnp.int32),
         jnp.zeros(cap, dtype=jnp.float64 if f else jnp.int64))
        for f in key_floats)
    pos = jnp.zeros(cap, dtype=jnp.int64)
    next_pos = jnp.zeros((), dtype=jnp.int64)
    payload = tuple(
        (jnp.zeros(cap, dtype=dt), jnp.zeros(cap, dtype=jnp.bool_))
        for dt in payload_dtypes)
    return (dead, ranks, pos, next_pos, payload)


_SAMPLE = 8192  # strided-sample size for the threshold estimate
_CAND = 8192    # fixed candidate buffer the fast selection sorts


def _kth_smallest(masked, k):
    """Exact k-th smallest (1-based, k pre-clamped to [1, n]) of a
    sentinel-masked value array. Large arrays avoid the full
    single-array sort in the common case: a strided sample estimates a
    conservative threshold, the rows at-or-under it compact into a
    fixed ``_CAND`` buffer whose sort yields the exact k-th value, and
    a ``lax.cond`` falls back to the full sort whenever the estimate
    kept too few (< k) or too many (> buffer) rows — heavy duplicate
    classes land there. Exact either way; only the cost differs."""
    n = masked.shape[0]
    if jnp.issubdtype(masked.dtype, jnp.floating):
        fill = jnp.asarray(jnp.inf, masked.dtype)
    else:
        fill = jnp.asarray(jnp.iinfo(masked.dtype).max, masked.dtype)
    if n <= 4 * _CAND:
        return jax.lax.sort(masked)[jnp.clip(k - 1, 0, n - 1)]
    stride = max(1, n // _SAMPLE)
    sample = jax.lax.sort(masked[::stride])
    n_s = sample.shape[0]
    # 4x-oversampled rank + slack: expected survivors ~4k + 16·(n/n_s),
    # comfortably >= k and << _CAND for value-rich keys
    ks = jnp.clip((k * n_s) // n * 4 + 16, 0, n_s - 1)
    t_est = sample[ks]
    cand = masked <= t_est
    count = jnp.sum(cand.astype(jnp.int64))

    def fast(operands):
        # compact survivors by gather (searchsorted over the running
        # count), not scatter -- XLA CPU scatter is a serial loop over
        # all n updates and would cost more than the sort it replaces
        vals, kk = operands
        ccum = prefix.cumsum(cand.astype(jnp.int32))
        pos = jnp.searchsorted(
            ccum, jnp.arange(1, _CAND + 1, dtype=jnp.int32), side="left")
        buf = jnp.where(jnp.arange(_CAND) < count,
                        vals[jnp.clip(pos, 0, n - 1)], fill)
        return jax.lax.sort(buf)[jnp.clip(kk - 1, 0, _CAND - 1)]

    def slow(operands):
        vals, kk = operands
        return jax.lax.sort(vals)[jnp.clip(kk - 1, 0, n - 1)]

    ok = (count >= jnp.maximum(k, 1)) & (count <= _CAND)
    return jax.lax.cond(ok, fast, slow, (masked, k))


def _cut_single_key(nullrank, value, sel, cap: int, desc: bool):
    """Exact top-``cap`` candidate cut of one chunk for a SINGLE sort
    key, using only a single-array ``lax.sort`` plus prefix sums. XLA's
    variadic comparator sort (the general merge below) runs ~7x slower
    than its vectorized single-array sort on CPU, so cutting the chunk
    to ``cap`` candidates first and merging 2·cap rows is the
    difference between the fused path winning and losing against the
    classic host ``np.lexsort``.

    Exactness: the key's null-rank classes select in rank order
    (ASC: NULLs then values; DESC: values then NULLs — the
    ``rank_operands`` convention). The all-NULL class ties completely,
    so its winners are the first ``k`` in drain (array) order — one
    cumsum. The value class takes every row strictly better than the
    k-th best value (one single-array sort over the class, non-class
    rows masked to the dtype maximum) plus boundary ties in drain
    order — a second cumsum. A real value colliding with the mask
    sentinel merely joins the boundary class, where the explicit class
    mask keeps the selection exact. Ties therefore resolve identically
    to the full merge's drain-position operand.

    NaN (float keys only) is its own third class: ``< thresh`` and
    ``== thresh`` are both false for NaN, so leaving NaN rows in the
    value class would silently DROP them (and poison the threshold
    sort). Both orderings the engine must match — host ``np.lexsort``
    and the XLA total-order merge sort — place NaN after every real
    value in either direction (DESC negates, and NumPy/XLA rank any
    NaN as maximal), i.e. ASC: NULLs, values, NaN; DESC: values, NaN,
    NULLs. NaNs tie completely, so like the NULL class their winners
    are the first ``k`` in drain order.

    Returns ``(idx [cap] i32, live [cap] bool)`` — source-row gathers
    for the candidate buffer (winner order is irrelevant: the variadic
    merge re-sorts)."""
    n = sel.shape[0]
    null_nr = jnp.int32(1 if desc else 0)
    is_null = (nullrank == null_nr) & sel
    is_val = sel & ~is_null
    floating = jnp.issubdtype(value.dtype, jnp.floating)
    if floating:
        is_nan = is_val & jnp.isnan(value)
        is_val = is_val & ~is_nan
        n_nan = jnp.sum(is_nan.astype(jnp.int64))
    else:  # trace-time skip: int keys have no NaN class
        is_nan = None
        n_nan = jnp.int64(0)
    n_null = jnp.sum(is_null.astype(jnp.int64))
    n_val = jnp.sum(is_val.astype(jnp.int64))
    c = jnp.int64(cap)
    if desc:  # values, NaN, NULLs
        k_val = jnp.minimum(c, n_val)
        k_nan = jnp.minimum(c - k_val, n_nan)
        k_null = jnp.minimum(c - k_val - k_nan, n_null)
    else:  # NULLs, values, NaN
        k_null = jnp.minimum(c, n_null)
        k_val = jnp.minimum(c - k_null, n_val)
        k_nan = jnp.minimum(c - k_null - k_val, n_nan)
    ncum = prefix.cumsum(is_null.astype(jnp.int64))
    win_null = is_null & (ncum <= k_null)
    if floating:
        sentinel = jnp.asarray(jnp.inf, value.dtype)
    else:
        sentinel = jnp.asarray(jnp.iinfo(value.dtype).max, value.dtype)
    masked = jnp.where(is_val, value, sentinel)
    thresh = _kth_smallest(masked, jnp.maximum(k_val, 1))
    strict = is_val & (masked < thresh)
    boundary = is_val & (masked == thresh)
    bcum = prefix.cumsum(boundary.astype(jnp.int64))
    n_strict = jnp.sum(strict.astype(jnp.int64))
    win_val = (strict | (boundary & (bcum <= k_val - n_strict))) \
        & (k_val > 0)
    win = win_null | win_val
    if is_nan is not None:
        nancum = prefix.cumsum(is_nan.astype(jnp.int64))
        win = win | (is_nan & (nancum <= k_nan))
    # compact the <= cap winners by gather, not scatter: the j-th winner
    # sits at the first index whose running win-count reaches j+1, and
    # cap binary searches beat an n-update serial XLA CPU scatter
    wcum = prefix.cumsum(win.astype(jnp.int32))
    idx = jnp.searchsorted(
        wcum, jnp.arange(1, cap + 1, dtype=jnp.int32), side="left")
    live = jnp.arange(cap, dtype=jnp.int32) < wcum[n - 1]
    return jnp.clip(idx, 0, n - 1).astype(jnp.int32), live


def topk_merge(state, key_pairs: Tuple, payload_cols: Tuple, sel,
               descs: Tuple = None):
    """One chunk folded into the state — traced inside the fused
    scan→topk program. Concatenates the state's C entries with the
    chunk's N rows per operand, sorts ONCE over (dead, per-key
    null-rank/value pairs, drain position, source index) and keeps the
    first C of every operand; the trailing index operand routes the
    two-source payload gather (slot < C = carried state row, else chunk
    row). Filtered-out chunk rows (sel False) enter dead and can never
    displace a live entry.

    With ``descs`` given and a SINGLE sort key, the chunk is first cut
    to C exact candidates by ``_cut_single_key`` (cheap single-array
    sort) so the variadic merge sorts 2·C rows instead of C + N —
    without the cut the comparator sort over the whole chunk costs
    MORE than the classic host path it replaces. Multi-key chunks keep
    the full merge (a key-boundary tie class is unbounded, so no fixed
    candidate buffer can cut them exactly)."""
    dead, ranks, pos, next_pos, payload = state
    C = dead.shape[0]
    N = sel.shape[0]
    cpos = next_pos + jnp.arange(N, dtype=jnp.int64)
    new_next = next_pos + N
    if descs is not None and len(key_pairs) == 1 and N > C:
        (cnr, cv), = key_pairs
        idx, live = _cut_single_key(cnr, cv, sel, C, bool(descs[0]))
        key_pairs = ((jnp.take(cnr, idx, mode="clip"),
                      jnp.take(cv, idx, mode="clip")),)
        payload_cols = tuple(
            (jnp.take(d, idx, mode="clip"),
             jnp.take(v, idx, mode="clip"))
            for d, v in payload_cols)
        cpos = jnp.take(cpos, idx, mode="clip")
        sel = live
        N = C
    cdead = (~sel).astype(jnp.int32)
    ops = [jnp.concatenate([dead, cdead])]
    for (snr, sv), (cnr, cv) in zip(ranks, key_pairs):
        ops.append(jnp.concatenate([snr, cnr]))
        ops.append(jnp.concatenate([sv, cv]))
    ops.append(jnp.concatenate([pos, cpos]))
    src = jnp.arange(C + N, dtype=jnp.int64)
    sorted_ops = jax.lax.sort(tuple(ops) + (src,), num_keys=len(ops))
    top = tuple(o[:C] for o in sorted_ops)
    perm = top[-1]
    from_state = perm < C
    si = jnp.clip(perm, 0, C - 1)
    ci = jnp.clip(perm - C, 0, max(N - 1, 0))
    new_ranks = tuple((top[1 + 2 * i], top[2 + 2 * i])
                      for i in range(len(ranks)))
    new_payload = tuple(
        (jnp.where(from_state, jnp.take(sd, si, mode="clip"),
                   jnp.take(cd, ci, mode="clip")),
         jnp.where(from_state, jnp.take(sv, si, mode="clip"),
                   jnp.take(cv, ci, mode="clip")))
        for (sd, sv), (cd, cv) in zip(payload, payload_cols))
    return (top[0], new_ranks, top[-2], new_next, new_payload)


@functools.partial(jax.jit, donate_argnums=0, static_argnums=4)
def _merge_topk(state, key_pairs, payload_cols, sel, descs):
    from tidb_tpu.ops.join_kernels import _note_trace

    _note_trace("topk_merge")
    return topk_merge(state, key_pairs, payload_cols, sel, descs)


def merge_topk(state, key_pairs, payload_cols, sel, descs=None):
    """Standalone jitted merge (kernel tests / non-fused callers): the
    fused pipeline instead traces ``topk_merge`` inside its own
    ``cached_jit`` program, which counts its dispatches there."""
    with dispatch.launch("jit:topk.merge"):
        return _merge_topk(state, tuple(key_pairs), tuple(payload_cols), sel,
                           None if descs is None else tuple(descs))
