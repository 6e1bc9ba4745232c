"""TPC-H Q22 whole through the general fragment compiler: the
uncorrelated scalar AVG subquery is a join with its one row that the
program computes itself (`parallel/fragment.py _scalar_producer`: a
masked reduction, psum / pmin / pmax across parts), so a statement is
ONE `general_*` launch, planning runs no statement of its own (the count
`subqueries_run` stays 0, no `scan_agg` launch) and no literal made from
the data enters the program's key (two datasets with different averages
share one compiled program); and the NOT EXISTS decides each customer
from the merged rank's count (`join<j>/join.exists`), with no expansion,
no knob and no retry. The answers are the benchmark's own numpy
reference's (`benchmarks/statements/q22.py`) and the sqlite oracle's, on
1 and on 4 parts; the scalar producer's and the existence path's cases
each against a plain Python reference."""

from decimal import Decimal
from fractions import Fraction

import pytest

from benchmarks import reference, spec, tpch_datagen
from tidb_tpu.parallel import make_mesh
from tidb_tpu.planner import feedback
from tidb_tpu.session import Session
from tidb_tpu.storage.catalog import Catalog
from tidb_tpu.storage.table import ColumnInfo, TableSchema
from tidb_tpu.storage.tpch import TPCH_SCHEMAS
from tidb_tpu.testutil import mirror_to_sqlite, rows_equal
from tidb_tpu.utils import tracing
from tidb_tpu.utils.metrics import (
    FRAGMENT_DISPATCH,
    FRAGMENT_EXISTENCE_JOINS,
    FRAGMENT_RETRY_TOTAL,
    FRAGMENT_SUBQUERIES,
)

SF = 0.02  # 3,000 customers, 30,000 orders
SEEDS = [5, 2**31 + 17, 12]
Q22 = spec.Cell("tpch_sf1_power.q22").statements["q22"]
PARAMS = {"codes": [13, 31, 23, 29, 30, 18, 17]}


def catalog_of(tables: dict) -> Catalog:
    """The benchmark's generated tables through the bulk-load entry, as
    `benchmarks/system.py start_server` ingests them."""
    catalog = Catalog()
    for name, (arrays, pools) in tables.items():
        cols = [ColumnInfo(n, t, not_null=nn) for n, t, nn in TPCH_SCHEMAS[name]]
        table = catalog.create_table("test", TableSchema(
            name, cols, primary_key=tpch_datagen.PRIMARY_KEYS[name]))
        table.ingest_encoded(dict(arrays), pools)
    return catalog


@pytest.fixture(scope="module")
def loads():
    """Per seed: (the catalog of customer and orders, the reference's
    view of the same arrays, the sqlite mirror)."""
    out = {}
    for seed in SEEDS:
        tables = tpch_datagen.generate(SF, seed)
        tables = {t: tables[t] for t in ("customer", "orders")}
        catalog = catalog_of(tables)
        out[seed] = catalog, reference.Data(tables), mirror_to_sqlite(catalog)
    return out


def session(catalog, devices, n_parts: int) -> Session:
    s = Session(catalog=catalog, mesh=make_mesh(devices=devices[:n_parts]))
    s.execute("use test")
    # a CPU mesh routes joins and generic aggregation to the host engine unless asked
    s.execute("set tidb_device_engine_mode = 'force'")
    feedback.STORE.clear()
    return s


def samples(counter) -> dict:
    """{(kind, path or knob or ''): n} of a fragment counter."""
    return {(labels.get("kind"), labels.get("path", labels.get("knob", ""))): n
            for labels, n in counter.samples()}


def delta(counter, before: dict) -> dict:
    return {k: n - before.get(k, 0) for k, n in samples(counter).items()
            if n - before.get(k, 0)}


COUNTERS = (FRAGMENT_DISPATCH, FRAGMENT_RETRY_TOTAL, FRAGMENT_SUBQUERIES,
            FRAGMENT_EXISTENCE_JOINS)


def run_spied(s: Session, sql: str) -> tuple:
    """(rows, [(program, shapes, types, growths in, growths out)] of
    every `_dispatch_retry` the statement made, the counters' deltas, the
    statement's trace)."""
    from tidb_tpu.parallel import executor as pe

    real, seen = pe.DistFragmentExec._dispatch_retry, []

    def spy(self, prog, args, shapes_sig, types_sig, growths, *span):
        out, grown = real(self, prog, args, shapes_sig, types_sig, growths, *span)
        seen.append((prog, shapes_sig, types_sig, growths, grown))
        return out, grown

    before = [samples(c) for c in COUNTERS]
    pe.DistFragmentExec._dispatch_retry = spy
    try:
        rows = s.query(sql)
    finally:
        pe.DistFragmentExec._dispatch_retry = real
    deltas = [delta(c, b) for c, b in zip(COUNTERS, before)]
    return rows, seen, deltas, tracing.STORE.finished()[-1]


def subqueries_run(trace) -> int:
    return sum(n for k, n in trace.counts().items()
               if k.endswith("/subqueries_run"))


def as_wire(rows) -> list:
    return [tuple(str(v) for v in row) for row in rows]


@pytest.mark.parametrize("n_parts", [1, 4], ids=["1part", "4parts"])
@pytest.mark.parametrize("seed", SEEDS)
def test_q22_is_one_program_and_one_launch(devices8, loads, seed, n_parts):
    catalog, data, oracle = loads[seed]
    s = session(catalog, devices8, n_parts)
    sql = Q22.sql(PARAMS)
    rows, seen, (launches, retries, subqs, exists), trace = run_spied(s, sql)
    want = Q22.reference(data, PARAMS)
    cmp = reference.compare_rows(as_wire(rows), want)
    assert reference.answer_ok(cmp) and cmp["cells"] == 3 * len(want) == 21, cmp
    ok, msg = rows_equal(rows, oracle.execute(sql.replace("substring(", "substr(")
                                              ).fetchall(), ordered=True)
    assert ok, msg
    (prog, _shapes, _types, growths, grown), = seen
    assert prog.n_scalar == 1 and prog.n_exists == 1 and prog.n_join == 1
    assert not prog.broadcasts and "expand" not in prog.growth_kinds
    assert growths == grown == prog.growth_defaults
    # customer, which the statement names twice, is one source, pinned
    assert sorted(src.scan.table_name for src in prog.sources) == ["customer", "orders"]
    assert launches == {(f"general_{prog.out_kind}", ""): 1}  # and no scan_agg
    assert retries == {}
    assert subqs == {(f"general_{prog.out_kind}", "scalar"): 1}
    assert exists == {(f"general_{prog.out_kind}", ""): 1}
    assert subqueries_run(trace) == 0
    assert "fragment.broadcast" not in trace.self_us_by_name()


def test_the_folded_plan_runs_the_subquery_first_where_no_mesh_takes_it(loads):
    """Without the mesh tier the AVG folds to a literal as before: one
    statement within the statement's planning, counted."""
    catalog, data, _oracle = loads[SEEDS[0]]
    s = Session(catalog=catalog)
    s.execute("use test")
    rows = s.query(Q22.sql(PARAMS))
    assert reference.answer_ok(reference.compare_rows(
        as_wire(rows), Q22.reference(data, PARAMS)))
    assert subqueries_run(tracing.STORE.finished()[-1]) == 1


def test_two_datasets_with_different_averages_share_one_program(devices8, loads):
    keys, avgs = [], []
    for seed in SEEDS[:2]:
        catalog, data, _oracle = loads[seed]
        s = session(catalog, devices8, 1)
        _rows, seen, _d, _t = run_spied(s, Q22.sql(PARAMS))
        (prog, shapes, types, growths, _grown), = seen
        keys.append((prog.sig, growths, shapes, types))
        (cached,) = [k for k in s._shard_cache.fragments if k[0] == "frag"]
        assert cached[1:5] == keys[-1]
        bal = data.col("customer", "c_acctbal")
        avgs.append(Fraction(int(bal[bal > 0].sum()), int((bal > 0).sum())))
    assert avgs[0] != avgs[1] and keys[0] == keys[1]


# -- the scalar producer ---------------------------------------------------

X = [-250, -101, -100, -99, 0, 99, 100, 101, 333, 334, 10_000, None]


def catalog_of_sql(*stmts) -> Catalog:
    catalog = Catalog()
    s = Session(catalog=catalog)
    s.execute("use test")
    for sql in stmts:
        s.execute(sql)
    return catalog


def _values(rows) -> str:
    return ", ".join("(" + ", ".join("null" if v is None else str(v) for v in r) + ")"
                     for r in rows)


def _dec(units):
    return None if units is None else Decimal(units).scaleb(-2)


@pytest.fixture(scope="module")
def small():
    """t: 48 rows in 4 groups, x from X; u: y in units of 0.01 whose
    positive values sum to 1,000 over 3 rows (an AVG of 3.3333...) and
    whose negatives average exactly -1.00."""
    rows = [(i % 4, _dec(X[i % len(X)])) for i in range(48)]
    ys = [_dec(v) for v in (100, 400, 500, -50, -150, None)]
    catalog = catalog_of_sql(
        "create table t (g bigint, x decimal(15,2))",
        f"insert into t values {_values(rows)}",
        "create table u (y decimal(15,2))",
        f"insert into u values {_values([(y,) for y in ys])}")
    return (catalog, [(g, None if x is None else Fraction(x)) for g, x in rows],
            [None if y is None else Fraction(y) for y in ys])


def _agg(func, ys):
    vals = [y for y in ys if y is not None]
    if func == "count":
        return Fraction(len(vals))
    if not vals:
        return None
    return {"sum": sum(vals), "min": min(vals), "max": max(vals),
            "avg": sum(vals) / len(vals) if vals else None}[func]


_OPS = {">": lambda a, b: a > b, ">=": lambda a, b: a >= b,
        "<": lambda a, b: a < b, "<=": lambda a, b: a <= b,
        "=": lambda a, b: a == b, "<>": lambda a, b: a != b}


def _want(rows, keep):
    groups = {}
    for g, x in rows:
        if x is not None and keep(x):
            n, sm = groups.get(g, (0, Fraction(0)))
            groups[g] = (n + 1, sm + x)
    return [(g, n, f"{float(sm):.2f}") for g, (n, sm) in sorted(groups.items())]


@pytest.mark.parametrize("n_parts", [1, 4], ids=["1part", "4parts"])
@pytest.mark.parametrize("func,where", [
    ("avg", "y > 0"),             # 3.3333...: x = 3.33 is under, 3.34 over
    ("avg", "y < 0"),             # exactly -1.00: the comparison's edge
    ("avg", "y > 1000"),          # over no row: NULL, no row passes
    ("sum", "y > 0"), ("count", "y > 0"), ("count", "y > 1000"),
    ("min", "y is not null"), ("max", "y < 0"),
])
@pytest.mark.parametrize("op", sorted(_OPS))
def test_a_scalar_aggregate_is_compiled_in_and_compared_exactly(
        devices8, small, n_parts, func, where, op):
    catalog, rows, ys = small
    s = session(catalog, devices8, n_parts)
    sql = (f"select g, count(*), sum(x) from t where x {op} "
           f"(select {func}(y) from u where {where}) group by g order by g")
    got, seen, (launches, retries, subqs, _e), trace = run_spied(s, sql)
    filt = {"y > 0": lambda y: y > 0, "y < 0": lambda y: y < 0,
            "y > 1000": lambda y: y > 1000,
            "y is not null": lambda y: True}[where]
    v = _agg(func, [y for y in ys if y is not None and filt(y)])
    want = [] if v is None else _want(rows, lambda x: _OPS[op](x, v))
    assert [(g, n, f"{float(sm):.2f}") for g, n, sm in got] == want, (v, got)
    (prog, *_rest), = seen
    assert prog.n_scalar == 1 and retries == {}
    assert subqs == {(f"general_{prog.out_kind}", "scalar"): 1}
    assert sum(launches.values()) == 1 and subqueries_run(trace) == 0


def test_a_scalar_subquery_the_fragment_cannot_take_folds_as_before(devices8, small):
    """A float AVG (the chip's compiler takes no f64 bitcast) stays the
    plan-time statement and its literal: one subquery run, the answer
    the same."""
    catalog, rows, ys = small
    s = session(catalog, devices8, 1)
    got, seen, (_l, _r, subqs, _e), trace = run_spied(
        s, "select g, count(*) from t where x > "
           "(select avg(y * 1.5e0) from u where y > 0) group by g order by g")
    v = _agg("avg", [y * Fraction(3, 2) for y in ys if y is not None and y > 0])
    assert [(g, n) for g, n in got] == [(g, n) for g, n, _ in _want(rows, lambda x: x > v)]
    assert subqueries_run(trace) == 1 and not any(p == "scalar" for _k, p in subqs)


# -- existence joins ---------------------------------------------------------


PROW = [(None if i % 9 == 0 else 1 + i % 40, i % 3, i) for i in range(64)]
BROW = ([(k, k % 3, k) for k in range(2, 30, 3)] * 3 + [(1, 1, 5)] * 2000
        + [(35, 2, 0)])


@pytest.fixture(scope="module")
def keyed():
    """null_b -> (catalog, p's rows, b's rows). p: 64 probe rows (key 1-40
    repeating, every 9th NULL, x 0-63); b: build rows with duplicate keys,
    key 1 repeated 2,000 times — more matches for one probe row than the
    probe side has slots — and, under True, a NULL key."""
    out = {}
    for null_b in (False, True):
        brow = BROW + ([(None, 0, 7)] if null_b else [])
        out[null_b] = (catalog_of_sql(
            "create table p (k bigint, j bigint, x bigint)",
            f"insert into p values {_values(PROW)}",
            "create table b (k bigint, j bigint, y bigint)",
            f"insert into b values {_values(brow)}"), PROW, brow)
    return out


def _exists_want(prow, brow, keep_p, keep_b, kind):
    bkeys = {k for k, _j, y in brow if keep_b(y) and k is not None}
    null_b = any(k is None for k, _j, y in brow if keep_b(y))
    out = []
    for k, _j, x in prow:
        if not keep_p(x):
            continue
        hit = k is not None and k in bkeys
        if kind == "semi":
            ok = hit
        elif kind == "exists_anti":
            ok = not hit
        else:  # NOT IN: unknown on a NULL probe key or any NULL build key
            ok = k is not None and not hit and not null_b
        if ok:
            out.append(x)
    return [(len(out), sum(out) if out else None)]


@pytest.mark.parametrize("n_parts", [1, 4], ids=["1part", "4parts"])
@pytest.mark.parametrize("kind,null_b", [
    ("semi", False), ("exists_anti", False), ("not_in", False), ("not_in", True)])
def test_an_existence_join_reads_the_ranks_count(devices8, keyed, n_parts, kind, null_b):
    catalog, prow, brow = keyed[null_b]
    s = session(catalog, devices8, n_parts)
    # dead slots on both sides: the filters on x and y
    cond = {"semi": "p.k in (select k from b where y <> 11)",
            "exists_anti": "not exists (select * from b where b.k = p.k and y <> 11)",
            "not_in": "p.k not in (select k from b where y <> 11)"}[kind]
    got, seen, (launches, retries, _s, exists), _trace = run_spied(
        s, f"select count(*), sum(x) from p where x % 5 <> 0 and {cond}")
    want = _exists_want(prow, brow, lambda x: x % 5 != 0, lambda y: y != 11, kind)
    assert [tuple(r) for r in got] == want
    (prog, *_rest), = seen
    assert prog.n_exists == 1 and "expand" not in prog.growth_kinds
    # on several parts key 1's 2,000 build rows all hash to one part: the
    # exchange's buckets may grow ("exch"), never an expansion
    assert {knob for _k, knob in retries} <= ({"exch"} if n_parts > 1 else set())
    assert n_parts > 1 or sum(launches.values()) == 1
    assert exists == {(f"general_{prog.out_kind}", ""): sum(launches.values())}


@pytest.mark.parametrize("cond", [
    "exists (select * from b where b.k = p.k and b.j = p.j)",
    "exists (select * from b where b.k = p.k and b.y < p.x)"],
    ids=["two_keys", "other_cond"])
def test_several_keys_or_an_other_condition_still_expand(devices8, keyed, cond):
    catalog, prow, brow = keyed[False]
    s = session(catalog, devices8, 1)
    got, seen, (_l, _r, _s, exists), _trace = run_spied(
        s, f"select count(*), sum(x) from p where {cond}")
    if "b.j" in cond:
        pairs = {(k, j) for k, j, _y in brow if k is not None}
        keep = [x for k, j, x in prow if (k, j) in pairs]
    else:
        keep = [x for k, _j, x in prow
                if any(bk == k and y < x for bk, _bj, y in brow if k is not None)]
    assert [tuple(r) for r in got] == [(len(keep), sum(keep) if keep else None)]
    assert seen[0][0].n_exists == 0 and "expand" in seen[0][0].growth_kinds
    assert exists == {}
