"""Compile the typed expression IR to pure jnp functions over chunks.

Each node evaluates to (data, valid) — dense arrays of the chunk capacity.
`data` is unspecified where ~valid; consumers must never branch on invalid
lanes (WHERE masks are `data & valid`). Everything composes into whatever
jitted fragment calls it, and XLA fuses the arithmetic into neighboring
kernels — this is the whole of the reference's generated VecEval* layer.

Decimal discipline: the IR carries scales in types; the compiler inserts
power-of-ten rescales so that
    add/sub  operate at the result scale,
    mul      naturally lands on scale_a + scale_b == result scale,
    div      leaves fixed point and produces float64 (MySQL widens scale
             instead; we document the deviation — exactness is kept for
             +,-,* which is what aggregation pipelines need).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tidb_tpu.errors import PlanError
from tidb_tpu.expression import dates
from tidb_tpu.expression.expr import (
    AggRef,
    Call,
    Case,
    Cast,
    ColumnRef,
    Expr,
    InList,
    Literal,
    Lookup,
)
from tidb_tpu.chunk.column import Column
from tidb_tpu.types import SQLType, TypeKind

__all__ = ["compile_expr", "compile_predicate", "eval_expr"]

Pair = Tuple[jax.Array, jax.Array]  # (data, valid)


def _rescale(data: jax.Array, from_scale: int, to_scale: int) -> jax.Array:
    if from_scale == to_scale:
        return data
    if to_scale > from_scale:
        return data * (10 ** (to_scale - from_scale))
    # scale-down rounds half away from zero like MySQL
    f = 10 ** (from_scale - to_scale)
    return jnp.where(data >= 0, (data + f // 2) // f, -((-data + f // 2) // f))


def _to_kind(data: jax.Array, frm: SQLType, to: SQLType) -> jax.Array:
    """Numeric representation change frm -> to (validity unchanged)."""
    if frm.kind == to.kind:
        if frm.kind == TypeKind.DECIMAL:
            return _rescale(data, frm.scale, to.scale)
        return data.astype(to.np_dtype)
    k_from, k_to = frm.kind, to.kind
    if k_to == TypeKind.FLOAT:
        if k_from == TypeKind.DECIMAL:
            return data.astype(jnp.float64) / (10**frm.scale)
        return data.astype(jnp.float64)
    if k_to == TypeKind.DECIMAL:
        if k_from == TypeKind.FLOAT:
            scaled = data * (10**to.scale)
            return jnp.where(scaled >= 0, scaled + 0.5, scaled - 0.5).astype(jnp.int64)
        return data.astype(jnp.int64) * (10**to.scale)
    if k_to == TypeKind.INT:
        if k_from == TypeKind.DECIMAL:
            return _rescale(data, frm.scale, 0)
        if k_from == TypeKind.FLOAT:
            return jnp.where(data >= 0, data + 0.5, data - 0.5).astype(jnp.int64)
        return data.astype(jnp.int64)
    if k_to == TypeKind.BOOL:
        return data != 0
    if k_to == TypeKind.DATETIME and k_from == TypeKind.DATE:
        return data.astype(jnp.int64) * 86_400_000_000
    if k_to == TypeKind.DATE and k_from == TypeKind.DATETIME:
        return jnp.floor_divide(data, 86_400_000_000).astype(jnp.int32)
    raise PlanError(f"unsupported cast {frm} -> {to}")


def _days(data: jax.Array, t: SQLType) -> jax.Array:
    """Temporal value -> days-since-epoch."""
    if t.kind == TypeKind.DATETIME:
        return jnp.floor_divide(data, 86_400_000_000)
    return data.astype(jnp.int64)


# ---------------------------------------------------------------------------
# evaluation
# ---------------------------------------------------------------------------


def eval_expr(e: Expr, chunk) -> Pair:
    """Evaluate IR node `e` over `chunk` -> (data, valid). Pure; call under
    jit."""
    cap = chunk.capacity

    if isinstance(e, ColumnRef):
        col = chunk.columns[e.name]
        return col.data, col.valid

    if isinstance(e, AggRef):
        col = chunk.columns[e.name]
        return col.data, col.valid

    if isinstance(e, Literal):
        if e.value is None:
            return (
                jnp.zeros(cap, dtype=e.type_.np_dtype),
                jnp.zeros(cap, dtype=jnp.bool_),
            )
        return (
            jnp.full(cap, e.value, dtype=e.type_.np_dtype),
            jnp.ones(cap, dtype=jnp.bool_),
        )

    if isinstance(e, Cast):
        data, valid = eval_expr(e.arg, chunk)
        return _to_kind(data, e.arg.type_, e.type_), valid

    if isinstance(e, Lookup):
        data, valid = eval_expr(e.arg, chunk)
        table = jnp.asarray(np.asarray(e.table, dtype=e.type_.np_dtype))
        idx = jnp.clip(data.astype(jnp.int32), 0, len(e.table) - 1)
        out = jnp.take(table, idx)
        if e.table_valid is not None:
            tv = jnp.asarray(np.asarray(e.table_valid, dtype=np.bool_))
            valid = valid & jnp.take(tv, idx)
        # codes outside the table (e.g. -1 absent sentinel) are invalid
        valid = valid & (data >= 0) & (data < len(e.table))
        return out, valid

    if isinstance(e, InList):
        data, valid = eval_expr(e.arg, chunk)
        vals = np.asarray(e.values, dtype=e.arg.type_.np_dtype)
        hit = jnp.zeros(cap, dtype=jnp.bool_)
        for v in vals:  # static unroll; planner uses Lookup for long lists
            hit = hit | (data == v)
        return (~hit if e.negated else hit), valid

    if isinstance(e, Case):
        if e.else_ is not None:
            out, ov = eval_expr(e.else_, chunk)
            out = _to_kind(out, e.else_.type_, e.type_)
        else:
            out = jnp.zeros(cap, dtype=e.type_.np_dtype)
            ov = jnp.zeros(cap, dtype=jnp.bool_)
        taken = jnp.zeros(cap, dtype=jnp.bool_)
        for cond, res in e.whens:
            cd, cv = eval_expr(cond, chunk)
            rd, rv = eval_expr(res, chunk)
            rd = _to_kind(rd, res.type_, e.type_)
            fire = cd & cv & ~taken
            out = jnp.where(fire, rd, out)
            ov = jnp.where(fire, rv, ov)
            taken = taken | fire
        return out, ov

    if isinstance(e, Call):
        fn = FUNCS.get(e.op)
        if fn is None:
            raise PlanError(f"unknown scalar function {e.op!r}")
        return fn(e, chunk)

    raise PlanError(f"cannot evaluate node {type(e).__name__}")


def compile_expr(e: Expr) -> Callable:
    """IR -> (chunk -> Column)."""

    def run(chunk) -> Column:
        data, valid = eval_expr(e, chunk)
        return Column(data, valid, e.type_)

    return run


def compile_predicate(e: Expr) -> Callable:
    """IR -> (chunk -> bool mask); NULL predicate rows are excluded."""

    def run(chunk) -> jax.Array:
        data, valid = eval_expr(e, chunk)
        return data & valid
    return run


# ---------------------------------------------------------------------------
# scalar function registry
# ---------------------------------------------------------------------------


def _strict2(op):
    """Binary strict function: valid = va & vb."""

    def fn(e: Call, chunk) -> Pair:
        a, b = e.args
        (da, va), (db, vb) = eval_expr(a, chunk), eval_expr(b, chunk)
        if e.type_.kind == TypeKind.DECIMAL:
            da = _rescale(da, a.type_.scale, e.type_.scale) if e.op in ("add", "sub") else da
            db = _rescale(db, b.type_.scale, e.type_.scale) if e.op in ("add", "sub") else db
        elif e.type_.kind == TypeKind.FLOAT:
            da = _to_kind(da, a.type_, e.type_)
            db = _to_kind(db, b.type_, e.type_)
        return op(da, db), va & vb

    return fn


def _cmp(op):
    """Comparison: builder guarantees comparable kinds; align decimal scales."""

    def fn(e: Call, chunk) -> Pair:
        a, b = e.args
        (da, va), (db, vb) = eval_expr(a, chunk), eval_expr(b, chunk)
        if a.type_.kind == TypeKind.DECIMAL or b.type_.kind == TypeKind.DECIMAL:
            s = max(a.type_.scale, b.type_.scale)
            da = _rescale(da, a.type_.scale, s) if a.type_.kind == TypeKind.DECIMAL else da * 10**s
            db = _rescale(db, b.type_.scale, s) if b.type_.kind == TypeKind.DECIMAL else db * 10**s
        return op(da, db), va & vb

    return fn


def _nseq(e: Call, chunk) -> Pair:
    """Null-safe equal <=> : NULL<=>NULL is TRUE, never returns NULL."""
    a, b = e.args
    (da, va), (db, vb) = eval_expr(a, chunk), eval_expr(b, chunk)
    if a.type_.kind == TypeKind.DECIMAL or b.type_.kind == TypeKind.DECIMAL:
        s = max(a.type_.scale, b.type_.scale)
        da = _rescale(da, a.type_.scale, s) if a.type_.kind == TypeKind.DECIMAL else da * 10**s
        db = _rescale(db, b.type_.scale, s) if b.type_.kind == TypeKind.DECIMAL else db * 10**s
    both_null = ~va & ~vb
    eq = va & vb & (da == db)
    return both_null | eq, jnp.ones_like(va)


def _truncate(e: Call, chunk) -> Pair:
    """TRUNCATE(x, d): toward zero, unlike ROUND."""
    a = e.args[0]
    nd = 0
    if len(e.args) > 1:
        lit = e.args[1]
        if not isinstance(lit, Literal):
            raise PlanError("TRUNCATE digits must be a constant")
        nd = int(lit.value)
    d, v = eval_expr(a, chunk)
    if a.type_.kind == TypeKind.DECIMAL:
        f = 10 ** max(a.type_.scale - nd, 0)
        out = jax.lax.div(d, jnp.int64(f)) * f if f > 1 else d
        return _rescale(out, a.type_.scale, e.type_.scale), v
    f = 10.0**nd
    return jnp.trunc(d.astype(jnp.float64) * f) / f, v


def _and(e: Call, chunk) -> Pair:
    a, b = e.args
    (da, va), (db, vb) = eval_expr(a, chunk), eval_expr(b, chunk)
    ta, tb = da & va, db & vb  # definitely-true lanes
    fa, fb = ~da & va, ~db & vb  # definitely-false lanes
    return ta & tb, (va & vb) | fa | fb


def _or(e: Call, chunk) -> Pair:
    a, b = e.args
    (da, va), (db, vb) = eval_expr(a, chunk), eval_expr(b, chunk)
    ta, tb = da & va, db & vb
    return ta | tb, (va & vb) | ta | tb


def _not(e: Call, chunk) -> Pair:
    d, v = eval_expr(e.args[0], chunk)
    return ~d, v


def _is_null(e: Call, chunk) -> Pair:
    _, v = eval_expr(e.args[0], chunk)
    return ~v, jnp.ones_like(v)


def _is_not_null(e: Call, chunk) -> Pair:
    _, v = eval_expr(e.args[0], chunk)
    return v, jnp.ones_like(v)


def _div(e: Call, chunk) -> Pair:
    a, b = e.args
    (da, va), (db, vb) = eval_expr(a, chunk), eval_expr(b, chunk)
    da = _to_kind(da, a.type_, e.type_)
    db = _to_kind(db, b.type_, e.type_)
    zero = db == 0
    safe = jnp.where(zero, 1, db)
    return da / safe, va & vb & ~zero  # x/0 -> NULL (MySQL)


def _intdiv(e: Call, chunk) -> Pair:
    a, b = e.args
    (da, va), (db, vb) = eval_expr(a, chunk), eval_expr(b, chunk)
    zero = db == 0
    safe = jnp.where(zero, 1, db)
    q = jnp.trunc(da.astype(jnp.float64) / safe.astype(jnp.float64)) if e.type_.kind == TypeKind.FLOAT else jax.lax.div(da.astype(jnp.int64), safe.astype(jnp.int64))
    return q, va & vb & ~zero


def _mod(e: Call, chunk) -> Pair:
    a, b = e.args
    (da, va), (db, vb) = eval_expr(a, chunk), eval_expr(b, chunk)
    # align operands on the result representation (decimal scale / float)
    da = _to_kind(da, a.type_, e.type_)
    db = _to_kind(db, b.type_, e.type_)
    zero = db == 0
    safe = jnp.where(zero, 1, db)
    # MySQL MOD takes the sign of the dividend (C semantics), not python's
    if e.type_.kind == TypeKind.FLOAT:
        r = da - jnp.trunc(da / safe) * safe
    else:
        r = da - jax.lax.div(da, safe) * safe
    return r, va & vb & ~zero


def _avg_bound(up: bool):
    """``avg_floor(sum, count)`` / ``avg_ceil(sum, count)``: the AVG of
    an exact column as an integer at the sum's own scale, rounded down /
    up, NULL where the count is 0. An exact value x at that scale lies
    over the AVG iff x > avg_floor, under it iff x < avg_ceil (the
    comparison ``x * count cmp sum`` without the product, which could
    leave int64): planner/logical.py _scalar_subquery_join."""

    def fn(e: Call, chunk) -> Pair:
        (s, vs), (c, vc) = (eval_expr(a, chunk) for a in e.args)
        s, c = s.astype(jnp.int64), c.astype(jnp.int64)
        ok = vs & vc & (c > 0)
        c = jnp.where(ok, c, 1)
        q = jax.lax.div(s, c)  # toward zero
        off = (s - q * c != 0) & ((s > 0) if up else (s < 0))
        return q + off if up else q - off, ok

    return fn


def _neg(e: Call, chunk) -> Pair:
    d, v = eval_expr(e.args[0], chunk)
    return -d, v


def _strict1(op, cast_float=False):
    def fn(e: Call, chunk) -> Pair:
        a = e.args[0]
        d, v = eval_expr(a, chunk)
        if cast_float:
            d = _to_kind(d, a.type_, e.type_)
        return op(d), v

    return fn


def _coalesce(e: Call, chunk) -> Pair:
    out = None
    for a in e.args:
        d, v = eval_expr(a, chunk)
        d = _to_kind(d, a.type_, e.type_)
        if out is None:
            out, ov = d, v
        else:
            out = jnp.where(ov, out, d)
            ov = ov | v
    return out, ov


def _if(e: Call, chunk) -> Pair:
    c, t, f = e.args
    cd, cv = eval_expr(c, chunk)
    (td, tv), (fd, fv) = eval_expr(t, chunk), eval_expr(f, chunk)
    td = _to_kind(td, t.type_, e.type_)
    fd = _to_kind(fd, f.type_, e.type_)
    cond = cd & cv
    return jnp.where(cond, td, fd), jnp.where(cond, tv, fv)


def _ifnull(e: Call, chunk) -> Pair:
    a, b = e.args
    (da, va), (db, vb) = eval_expr(a, chunk), eval_expr(b, chunk)
    da = _to_kind(da, a.type_, e.type_)
    db = _to_kind(db, b.type_, e.type_)
    return jnp.where(va, da, db), va | vb


def _nullif(e: Call, chunk) -> Pair:
    a, b = e.args
    (da, va), (db, vb) = eval_expr(a, chunk), eval_expr(b, chunk)
    eq = (da == db) & va & vb
    return da, va & ~eq


def _temporal_extract(which):
    def fn(e: Call, chunk) -> Pair:
        a = e.args[0]
        d, v = eval_expr(a, chunk)
        days = _days(d, a.type_)
        y, m, dd = dates.civil_from_days(days)
        if which == "quarter":
            out = (m - 1) // 3 + 1
        elif which == "dayofweek":
            # 1970-01-01 was a Thursday; MySQL: 1=Sunday .. 7=Saturday
            out = (days + 4) % 7 + 1
        elif which == "weekday":
            # MySQL WEEKDAY(): 0=Monday .. 6=Sunday
            out = (days + 3) % 7
        elif which == "dayofyear":
            out = days - dates.days_from_civil(y, jnp.ones_like(m), jnp.ones_like(dd)) + 1
        else:
            out = {"year": y, "month": m, "day": dd}[which]
        return out.astype(jnp.int64), v

    return fn


def _time_extract(which):
    """HOUR/MINUTE/SECOND/MICROSECOND over DATETIME micros (0 for DATE)."""

    def fn(e: Call, chunk) -> Pair:
        a = e.args[0]
        d, v = eval_expr(a, chunk)
        if a.type_.kind == TypeKind.TIME:
            # durations: HOUR('-120:30:00') = 120 (magnitude, unbounded)
            mag = jnp.abs(d.astype(jnp.int64))
            div, mod_ = {
                "hour": (3_600_000_000, None),
                "minute": (60_000_000, 60),
                "second": (1_000_000, 60),
                "microsecond": (1, 1_000_000),
            }[which]
            out = jnp.floor_divide(mag, div)
            return (out if mod_ is None else out % mod_), v
        if a.type_.kind != TypeKind.DATETIME:
            return jnp.zeros_like(d, dtype=jnp.int64), v
        micros = d.astype(jnp.int64)
        div, mod_ = {
            "hour": (3_600_000_000, 24),
            "minute": (60_000_000, 60),
            "second": (1_000_000, 60),
            "microsecond": (1, 1_000_000),
        }[which]
        out = jnp.floor_divide(micros, div) % mod_
        return out, v

    return fn


def _week(e: Call, chunk) -> Pair:
    """WEEK(d) mode 0 (MySQL default): Sunday-first; week 1 starts at the
    first Sunday of the year, earlier days are week 0."""
    a = e.args[0]
    d, v = eval_expr(a, chunk)
    days = _days(d, a.type_)
    y, _, _ = dates.civil_from_days(days)
    jan1 = dates.days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
    dow_jan1 = (jan1 + 4) % 7  # 0=Sunday
    first_sunday = jan1 + (7 - dow_jan1) % 7
    wk = jnp.where(days < first_sunday, 0, (days - first_sunday) // 7 + 1)
    return wk.astype(jnp.int64), v


def _iso_week(e: Call, chunk) -> Pair:
    """WEEKOFYEAR(d) = ISO-8601 week number (MySQL WEEK(d, 3)): Monday
    first, week 1 contains Jan 4. Handles the year-boundary weeks."""
    a = e.args[0]
    d, v = eval_expr(a, chunk)
    days = _days(d, a.type_)
    y, _, _ = dates.civil_from_days(days)

    def week1_monday(year):
        jan4 = dates.days_from_civil(year, jnp.full_like(year, 1),
                                     jnp.full_like(year, 4))
        return jan4 - (jan4 + 3) % 7  # Monday on/before Jan 4

    w_this, w_next, w_prev = week1_monday(y), week1_monday(y + 1), week1_monday(y - 1)
    wk = jnp.where(
        days >= w_next, 1,
        jnp.where(days < w_this,
                  (days - w_prev) // 7 + 1,
                  (days - w_this) // 7 + 1))
    return wk.astype(jnp.int64), v


_DAYS_0000 = 719_528  # days from year 0 ("0000-01-01") to 1970-01-01


def _to_days(e: Call, chunk) -> Pair:
    a = e.args[0]
    d, v = eval_expr(a, chunk)
    return _days(d, a.type_) + _DAYS_0000, v


def _from_days(e: Call, chunk) -> Pair:
    d, v = eval_expr(e.args[0], chunk)
    return (d.astype(jnp.int64) - _DAYS_0000).astype(jnp.int32), v


def _last_day(e: Call, chunk) -> Pair:
    """LAST_DAY(d): the final day of d's month, as a DATE."""
    a = e.args[0]
    d, v = eval_expr(a, chunk)
    days = _days(d, a.type_)
    y, m, _ = dates.civil_from_days(days)
    one = jnp.ones_like(m)
    next_start = dates.days_from_civil(
        jnp.where(m == 12, y + 1, y), jnp.where(m == 12, one, m + 1), one)
    return (next_start - 1).astype(jnp.int32), v


def _unix_timestamp(e: Call, chunk) -> Pair:
    a = e.args[0]
    d, v = eval_expr(a, chunk)
    if a.type_.kind == TypeKind.DATE:
        return d.astype(jnp.int64) * 86_400, v
    return jnp.floor_divide(d.astype(jnp.int64), 1_000_000), v


def _from_unixtime(e: Call, chunk) -> Pair:
    d, v = eval_expr(e.args[0], chunk)
    return d.astype(jnp.int64) * 1_000_000, v


def _tsdiff_months(e: Call, chunk) -> Pair:
    """TIMESTAMPDIFF(MONTH, a, b): whole months from a to b, boundary-
    aware the MySQL way — the raw (y,m) delta, minus one when b's
    (day, time-of-day) hasn't reached a's yet (symmetrically for
    negative spans)."""
    a, b = e.args

    def decompose(x):
        d, v = eval_expr(x, chunk)
        if x.type_.kind == TypeKind.DATETIME:
            micros = d.astype(jnp.int64)
            days = jnp.floor_divide(micros, 86_400_000_000)
            tod = micros - days * 86_400_000_000
        else:
            days = d.astype(jnp.int64)
            tod = jnp.zeros_like(days)
        y, m, dd = dates.civil_from_days(days)
        return y, m, dd, tod, v

    ya, ma, da, ta, va = decompose(a)
    yb, mb, db, tb, vb = decompose(b)
    months = (yb - ya) * 12 + (mb - ma)
    # fractional-month adjustment toward zero
    frac_b = db * 86_400_000_000 + tb
    frac_a = da * 86_400_000_000 + ta
    months = jnp.where((months > 0) & (frac_b < frac_a), months - 1, months)
    months = jnp.where((months < 0) & (frac_b > frac_a), months + 1, months)
    return months.astype(jnp.int64), va & vb


def _time_to_sec(e: Call, chunk) -> Pair:
    a = e.args[0]
    d, v = eval_expr(a, chunk)
    micros = d.astype(jnp.int64)
    if a.type_.kind == TypeKind.DATETIME:
        # seconds OF DAY, not epoch seconds
        micros = micros % 86_400_000_000
    # truncate toward zero (MySQL drops fractional seconds)
    q = jnp.where(micros >= 0, micros // 1_000_000,
                  -((-micros) // 1_000_000))
    return q, v


# MySQL TIME range: +-838:59:59
_TIME_MAX_SECS = 838 * 3600 + 59 * 60 + 59


def _sec_to_time(e: Call, chunk) -> Pair:
    d, v = eval_expr(e.args[0], chunk)
    secs = jnp.clip(d.astype(jnp.int64), -_TIME_MAX_SECS, _TIME_MAX_SECS)
    return secs * 1_000_000, v


def _makedate(e: Call, chunk) -> Pair:
    """MAKEDATE(year, dayofyear): day 0 or negative -> NULL (MySQL)."""
    y, vy = eval_expr(e.args[0], chunk)
    dn, vd = eval_expr(e.args[1], chunk)
    y = y.astype(jnp.int64)
    dn = dn.astype(jnp.int64)
    jan1 = dates.days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
    out = (jan1 + dn - 1).astype(jnp.int32)
    return out, vy & vd & (dn >= 1)


def _addtime(sign: int):
    def fn(e: Call, chunk) -> Pair:
        a, b = e.args
        (da, va), (db, vb) = eval_expr(a, chunk), eval_expr(b, chunk)
        out = da.astype(jnp.int64) + sign * db.astype(jnp.int64)
        return out, va & vb

    return fn


def _add_months(e: Call, chunk) -> Pair:
    """date/datetime + N months with end-of-month clamping (the device
    path for +/- INTERVAL MONTH/QUARTER/YEAR on column dates)."""
    a, n_lit = e.args
    d, v = eval_expr(a, chunk)
    n = jnp.int64(int(n_lit.value))
    if a.type_.kind == TypeKind.DATETIME:
        micros = d.astype(jnp.int64)
        days = jnp.floor_divide(micros, 86_400_000_000)
        tod = micros - days * 86_400_000_000
    else:
        days = d.astype(jnp.int64)
        tod = None
    y, m, dd = dates.civil_from_days(days)
    total = y * 12 + (m - 1) + n
    ny = jnp.floor_divide(total, 12)
    nm = total - ny * 12 + 1
    month_start = dates.days_from_civil(ny, nm, jnp.ones_like(dd))
    next_start = dates.days_from_civil(
        jnp.where(nm == 12, ny + 1, ny), jnp.where(nm == 12, 1, nm + 1), jnp.ones_like(dd))
    dd = jnp.minimum(dd, next_start - month_start)
    out_days = month_start + dd - 1
    if tod is not None:
        return out_days * 86_400_000_000 + tod, v
    return out_days, v


def _nary_extreme(pick):
    """GREATEST/LEAST: strict (NULL if any arg NULL), over the common
    type the binder computed for the Call."""

    def fn(e: Call, chunk) -> Pair:
        rt = e.type_
        acc_d = acc_v = None
        for a in e.args:
            d, v = eval_expr(a, chunk)
            if rt.kind == TypeKind.DECIMAL and a.type_.kind == TypeKind.DECIMAL:
                d = _rescale(d, a.type_.scale, rt.scale)
            elif rt.kind == TypeKind.DECIMAL:
                d = d.astype(jnp.int64) * 10**rt.scale
            elif rt.kind == TypeKind.FLOAT:
                d = _to_kind(d, a.type_, rt)
            if acc_d is None:
                acc_d, acc_v = d, v
            else:
                acc_d, acc_v = pick(acc_d, d), acc_v & v
        return acc_d, acc_v

    return fn


def _ushift(op):
    """MySQL shifts are on BIGINT UNSIGNED: logical (zero-fill) via the
    uint64 bit pattern, and counts >= 64 are defined to give 0 (XLA
    leaves oversize shifts undefined)."""
    def f(a, b):
        ua = jax.lax.bitcast_convert_type(a.astype(jnp.int64), jnp.uint64)
        bi = b.astype(jnp.int64)
        # the count is BIGINT UNSIGNED too: a negative count wraps to
        # >= 2^63, which is >= 64 -> zero
        cnt = jnp.where(bi < 0, jnp.int64(64), jnp.clip(bi, 0, 64))
        out = op(ua, jnp.minimum(cnt, 63).astype(jnp.uint64))
        out = jnp.where(cnt >= 64, jnp.uint64(0), out)
        return jax.lax.bitcast_convert_type(out, jnp.int64)

    return f


def _ubitnot(a):
    return jax.lax.bitcast_convert_type(
        ~jax.lax.bitcast_convert_type(a.astype(jnp.int64), jnp.uint64),
        jnp.int64)


def _sign(e: Call, chunk) -> Pair:
    d, v = eval_expr(e.args[0], chunk)
    return jnp.sign(d).astype(jnp.int64), v


def _round(e: Call, chunk) -> Pair:
    a = e.args[0]
    nd = 0
    if len(e.args) > 1:
        lit = e.args[1]
        if not isinstance(lit, Literal):
            raise PlanError("ROUND digits must be a constant")
        nd = int(lit.value)
    d, v = eval_expr(a, chunk)
    if a.type_.kind == TypeKind.DECIMAL:
        out = _rescale(d, a.type_.scale, nd)
        out = _rescale(out, nd, e.type_.scale)
        return out, v
    f = 10.0**nd
    scaled = d.astype(jnp.float64) * f
    return jnp.where(scaled >= 0, jnp.floor(scaled + 0.5), jnp.ceil(scaled - 0.5)) / f, v


FUNCS = {
    "add": _strict2(jnp.add),
    "sub": _strict2(jnp.subtract),
    "mul": _strict2(jnp.multiply),
    "div": _div,
    "intdiv": _intdiv,
    "mod": _mod,
    "avg_floor": _avg_bound(False),
    "avg_ceil": _avg_bound(True),
    "neg": _neg,
    "eq": _cmp(lambda a, b: a == b),
    "ne": _cmp(lambda a, b: a != b),
    "lt": _cmp(lambda a, b: a < b),
    "le": _cmp(lambda a, b: a <= b),
    "gt": _cmp(lambda a, b: a > b),
    "ge": _cmp(lambda a, b: a >= b),
    "and": _and,
    "or": _or,
    "not": _not,
    "nseq": _nseq,
    "is_null": _is_null,
    "is_not_null": _is_not_null,
    "truncate": _truncate,
    "coalesce": _coalesce,
    "if": _if,
    "ifnull": _ifnull,
    "nullif": _nullif,
    "abs": _strict1(jnp.abs),
    "ceil": _strict1(jnp.ceil, cast_float=True),
    "floor": _strict1(jnp.floor, cast_float=True),
    "sqrt": _strict1(jnp.sqrt, cast_float=True),
    "exp": _strict1(jnp.exp, cast_float=True),
    "ln": _strict1(jnp.log, cast_float=True),
    "log2": _strict1(jnp.log2, cast_float=True),
    "log10": _strict1(jnp.log10, cast_float=True),
    "sin": _strict1(jnp.sin, cast_float=True),
    "cos": _strict1(jnp.cos, cast_float=True),
    "pow": _strict2(jnp.power),
    "round": _round,
    "year": _temporal_extract("year"),
    "month": _temporal_extract("month"),
    "day": _temporal_extract("day"),
    "quarter": _temporal_extract("quarter"),
    "dayofweek": _temporal_extract("dayofweek"),
    "weekday": _temporal_extract("weekday"),
    "dayofyear": _temporal_extract("dayofyear"),
    "hour": _time_extract("hour"),
    "minute": _time_extract("minute"),
    "second": _time_extract("second"),
    "microsecond": _time_extract("microsecond"),
    "add_months": _add_months,
    "greatest": _nary_extreme(jnp.maximum),
    "least": _nary_extreme(jnp.minimum),
    "sign": _sign,
    "tan": _strict1(jnp.tan, cast_float=True),
    "atan": _strict1(jnp.arctan, cast_float=True),
    "asin": _strict1(jnp.arcsin, cast_float=True),
    "acos": _strict1(jnp.arccos, cast_float=True),
    "atan2": _strict2(jnp.arctan2),
    "radians": _strict1(jnp.radians, cast_float=True),
    "degrees": _strict1(jnp.degrees, cast_float=True),
    # MySQL bit ops are BIGINT UNSIGNED: ~ and >> operate on the uint64
    # bit pattern (logical shift, not arithmetic), and shift counts >= 64
    # are defined to produce 0 (XLA leaves them undefined)
    "week": _week,
    "weekofyear": _iso_week,
    "to_days": _to_days,
    "from_days": _from_days,
    "last_day": _last_day,
    "unix_timestamp": _unix_timestamp,
    "from_unixtime": _from_unixtime,
    "tsdiff_months": _tsdiff_months,
    "time_to_sec": _time_to_sec,
    "sec_to_time": _sec_to_time,
    "makedate": _makedate,
    "addtime": _addtime(1),
    "subtime": _addtime(-1),
    "cot": _strict1(lambda x: 1.0 / jnp.tan(x), cast_float=True),
    "sinh": _strict1(jnp.sinh, cast_float=True),
    "cosh": _strict1(jnp.cosh, cast_float=True),
    "tanh": _strict1(jnp.tanh, cast_float=True),
    "bitand": _strict2(jnp.bitwise_and),
    "bitor": _strict2(jnp.bitwise_or),
    "bitxor": _strict2(jnp.bitwise_xor),
    "shl": _strict2(_ushift(jnp.left_shift)),
    "shr": _strict2(_ushift(jnp.right_shift)),
    "bitnot": _strict1(_ubitnot),
}
