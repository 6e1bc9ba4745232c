"""TPC-H Q9 whole through the general fragment compiler: six tables and
five joins, one of them on partsupp's whole primary key, as ONE
`general_generic` launch for the first statement on a fresh plan-feedback
store (no capacity relaunch), because the planner estimates a join whose
keys cover a unique key as the foreign-key join it is
(`planner/physical.py eq_join_rows`) and a LIKE over a dictionary from
the bulk load's code counts (`statistics._code_map_share`). The two-key
join ranks by ONE exact sort key packed from the keys' recorded bounds
(`parallel/fragment.py _key_packing`); a key outside them relaunches the
program with the keys hashed and verified. The answers are the
benchmark's own numpy reference's (`benchmarks/statements/q9.py`) and the
sqlite oracle's, on 1 and on 4 parts; the estimator's cases against plain
Python."""

import numpy as np
import pytest

from benchmarks import reference, spec, tpch_datagen
from tidb_tpu.parallel import make_mesh
from tidb_tpu.planner import feedback
from tidb_tpu.session import Session
from tidb_tpu.storage.catalog import Catalog
from tidb_tpu.storage.table import ColumnInfo, TableSchema
from tidb_tpu.storage.tpch import TPCH_SCHEMAS
from tidb_tpu.storage.tpch_queries import Q
from tidb_tpu.testutil import mirror_to_sqlite, rows_equal
from tidb_tpu.utils import tracing
from tidb_tpu.utils.metrics import (
    FRAGMENT_COMPACT_SLOTS,
    FRAGMENT_DISPATCH,
    FRAGMENT_MULTIKEY_JOINS,
    FRAGMENT_RETRY_TOTAL,
)

SF = 0.01  # 2,000 parts, 8,000 partsupp rows, 60,012 lines, 15,000 orders
SEEDS = [5, 2**31 + 17, 12]
Q9 = spec.Cell("tpch_sf1_power.q9").statements["q9"]
TABLES = ("part", "supplier", "lineitem", "partsupp", "orders", "nation")


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
    """Per seed: (the catalog of Q9's six tables, the reference's view of
    the same arrays, the sqlite mirror)."""
    out = {}
    for seed in SEEDS:
        tables = tpch_datagen.generate(SF, seed)
        tables = {t: tables[t] for t in TABLES}
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
    """{(kind, knob or ''): n} of a fragment counter."""
    return {(labels.get("kind"), labels.get("knob", "")): n
            for labels, n in counter.samples()}


def delta(counter, before: dict) -> dict:
    return {k: n - before.get(k, 0) for k, n in samples(counter).items()
            if n - before.get(k, 0)}


COUNTERS = (FRAGMENT_DISPATCH, FRAGMENT_RETRY_TOTAL, FRAGMENT_MULTIKEY_JOINS,
            FRAGMENT_COMPACT_SLOTS)


def run_spied(s: Session, sql: str) -> tuple:
    """(rows, [(program, built function, growths in, growths out)] of
    every `_dispatch_retry` the statement made, the counters' deltas, the
    statement's trace)."""
    from tidb_tpu.parallel import executor as pe

    real, seen = pe.DistFragmentExec._dispatch_retry, []

    def spy(self, prog, args, shapes_sig, types_sig, growths, *span):
        out, grown = real(self, prog, args, shapes_sig, types_sig, growths, *span)
        probe_mode = getattr(self.ctx, "join_probe_mode", None)
        fn = self._cache.fragments[("frag", prog.sig, grown, shapes_sig, types_sig,
                                    probe_mode)]
        seen.append((prog, fn, growths, grown))
        return out, grown

    before = [samples(c) for c in COUNTERS]
    pe.DistFragmentExec._dispatch_retry = spy
    try:
        rows = s.query(sql)
    finally:
        pe.DistFragmentExec._dispatch_retry = real
    deltas = [delta(c, b) for c, b in zip(COUNTERS, before)]
    return rows, seen, deltas, tracing.STORE.finished()[-1]


def as_wire(rows) -> list:
    return [tuple(str(v) for v in row) for row in rows]


def span_counts(trace, name: str) -> int:
    return sum(n for k, n in trace.counts().items() if k.endswith("/" + name))


@pytest.mark.parametrize("color", ["blue", "green"])
@pytest.mark.parametrize("n_parts", [1, 4], ids=["1part", "4parts"])
@pytest.mark.parametrize("seed", SEEDS)
def test_q9_is_one_program_and_one_launch_cold(devices8, loads, seed, n_parts, color):
    catalog, data, oracle = loads[seed]
    s = session(catalog, devices8, n_parts)
    sql = Q9.sql({"color": color})
    rows, seen, (launches, retries, multikey, slots), trace = run_spied(s, sql)
    want = Q9.reference(data, {"color": color})
    cmp = reference.compare_rows(as_wire(rows), want)
    assert reference.answer_ok(cmp) and cmp["cells"] == 3 * len(want), cmp
    assert (len(want) > 150) == (color == "blue")  # the generator's names hold no green
    ok, msg = rows_equal(rows, oracle.execute(Q["q9"][1].replace("green", color)
                                              ).fetchall(), ordered=True)
    assert ok, msg
    (prog, fn, growths, grown), = seen
    assert prog.n_join == 5 and prog.n_multikey == 1 and not prog.broadcasts
    assert sorted(src.scan.table_name for src in prog.sources) == sorted(TABLES)
    # the keys packed into one exact sort key, and no key outside its bounds
    (pack,) = [g for g, k in zip(grown, prog.growth_kinds) if k == "pack"]
    assert pack == 1.0 and growths == grown == prog.growth_defaults
    assert launches == {("general_generic", ""): 1}
    assert retries == {}
    assert multikey == {("general_generic", ""): 1}
    assert slots == ({("general_generic", ""): sum(fn.compact_slots)}
                     if sum(fn.compact_slots) else {})
    assert span_counts(trace, "multikey_joins") == 1
    assert span_counts(trace, "compact_slots") == sum(fn.compact_slots)
    assert span_counts(trace, "unique_key_estimates") >= 1
    assert span_counts(trace, "dict_pred_estimates") >= 1


# -- the estimator ------------------------------------------------------------

def explain_rows(s: Session, sql: str) -> list:
    """[(operator, estimated rows)] of the statement's plan, top down (the
    rows of EXPLAIN's table after its header)."""
    lines = [str(r[0]).split() for r in s.query("explain " + sql)]
    return [(w[0].lstrip("└─├ "), float(w[1])) for w in lines[1:]]


def plan_counts(s: Session, sql: str) -> dict:
    s.query(sql)
    trace = tracing.STORE.finished()[-1]
    return {k: span_counts(trace, k) for k in ("unique_key_estimates",
                                               "dict_pred_estimates")}


def test_a_join_covering_partsupps_primary_key_is_estimated_as_a_foreign_key_join(loads):
    """lineitem with partsupp on (l_partkey, l_suppkey): every line has
    exactly one partsupp row, so the join emits lineitem's rows times the
    share of partsupp that survives (all of it here), within 2x of the
    true count, not 1 in 2,500 of it."""
    catalog, data, _oracle = loads[SEEDS[0]]
    s = Session(catalog=catalog)
    s.execute("use test")
    feedback.STORE.clear()
    sql = ("select count(*) from lineitem, partsupp "
           "where ps_partkey = l_partkey and ps_suppkey = l_suppkey")
    pairs = set(zip(data.col("partsupp", "ps_partkey").tolist(),
                    data.col("partsupp", "ps_suppkey").tolist()))
    true = sum(1 for k in zip(data.col("lineitem", "l_partkey").tolist(),
                              data.col("lineitem", "l_suppkey").tolist()) if k in pairs)
    assert true == data.rows("lineitem") == 60_012
    (est,) = [r for op, r in explain_rows(s, sql) if op.startswith("HashJoin")]
    assert true / 2 <= est <= true * 2, est
    assert plan_counts(s, sql)["unique_key_estimates"] >= 1
    # a filter on the covered side scales the estimate by what survives
    filtered = sql + " and ps_supplycost < 250"  # 25,000 units at scale 2
    keep = int((data.col("partsupp", "ps_supplycost") < 25000).sum())
    (est,) = [r for op, r in explain_rows(s, filtered) if op.startswith("HashJoin")]
    share = keep / data.rows("partsupp")
    assert true * share / 2 <= est <= true * share * 2, (est, true * share)


def test_a_two_key_join_covering_no_unique_key_keeps_todays_rule(loads):
    """partsupp with lineitem on (ps_partkey, ps_availqty) = (l_partkey,
    l_quantity): neither side's primary key is covered, so the estimate is
    |L| x |R| over the largest distinct count per key pair that one is
    known for (ps_partkey's sketch; l_partkey, ps_availqty and l_quantity
    have none), as before."""
    from tidb_tpu.statistics import column_ndv

    catalog, data, _oracle = loads[SEEDS[0]]
    s = Session(catalog=catalog)
    s.execute("use test")
    feedback.STORE.clear()
    sql = ("select count(*) from lineitem, partsupp "
           "where ps_partkey = l_partkey and ps_availqty = l_quantity")
    ndv = column_ndv(catalog.table("test", "partsupp"), "ps_partkey")
    want = data.rows("lineitem") * data.rows("partsupp") / max(ndv, 1.0)
    (est,) = [r for op, r in explain_rows(s, sql) if op.startswith("HashJoin")]
    assert est == pytest.approx(want, rel=1e-9)
    assert plan_counts(s, sql)["unique_key_estimates"] == 0


@pytest.mark.parametrize("pattern,negated", [("%blue%", False), ("%blue%", True),
                                             ("%green%", False), ("bl%", False)])
def test_a_like_over_a_dictionary_is_estimated_from_the_loads_code_counts(
        loads, pattern, negated):
    """Exact: the rows whose name matches, counted in plain Python; '%blue%'
    is 25 of the generator's 169 names (within 1.25x of 25/169 of the
    rows), '%green%' none (the estimate floors at one row)."""
    import re

    catalog, data, _oracle = loads[SEEDS[1]]
    s = Session(catalog=catalog)
    s.execute("use test")
    feedback.STORE.clear()
    names = data.tables["part"][1]["p_name"]
    rx = re.compile(re.escape(pattern).replace("%", ".*") + r"\Z")
    matching = [bool(rx.match(n)) != negated for n in names]
    count = sum(matching[c] for c in data.col("part", "p_name").tolist())
    op = "not like" if negated else "like"
    sql = f"select count(*) from part where p_name {op} '{pattern}'"
    (est,) = [r for o, r in explain_rows(s, sql) if o.startswith("TableFullScan")]
    assert est == pytest.approx(max(count, 1))
    if pattern == "%blue%" and not negated:
        assert sum(matching) == 25 and len(names) == 169
        share = 25 / 169 * data.rows("part")
        assert share / 1.25 <= est <= share * 1.25
    assert plan_counts(s, sql)["dict_pred_estimates"] >= 1


# -- the packed key ----------------------------------------------------------

def _tables(catalog, rows_t, rows_u):
    s = Session(catalog=catalog)
    s.execute("use test")
    for name, rows in (("t", rows_t), ("u", rows_u)):
        table = catalog.create_table("test", TableSchema(name, [
            ColumnInfo("a", TPCH_SCHEMAS["partsupp"][0][1]),
            ColumnInfo("b", TPCH_SCHEMAS["partsupp"][0][1]),
            ColumnInfo("x", TPCH_SCHEMAS["partsupp"][0][1])],
            primary_key=["a", "b"] if name == "u" else None))
        table.ingest_encoded({c: np.array([r[i] for r in rows], dtype=np.int64)
                              for i, c in enumerate("abx")}, {})
    return s


T_ROWS = [(i % 7, (i * 5) % 11, i) for i in range(300)]
U_ROWS = [(a, b, 100 * a + b) for a in range(7) for b in range(11) if (a + b) % 3]


def _want(t_rows, u_rows):
    u = {(a, b): x for a, b, x in u_rows}
    hit = [(x, u[(a, b)]) for a, b, x in t_rows if (a, b) in u]
    return [(len(hit), sum(x for x, _ in hit), sum(y for _, y in hit))]


SQL = "select count(*), sum(t.x), sum(u.x) from t, u where t.a = u.a and t.b = u.b"


@pytest.mark.parametrize("n_parts", [1, 4], ids=["1part", "4parts"])
def test_two_keys_pack_into_one_exact_sort_key(devices8, n_parts):
    catalog = Catalog()
    _tables(catalog, T_ROWS, U_ROWS)
    s = session(catalog, devices8, n_parts)
    rows, seen, (launches, retries, multikey, _slots), _trace = run_spied(s, SQL)
    assert [tuple(r) for r in rows] == _want(T_ROWS, U_ROWS)
    (prog, _fn, _g, grown), = seen
    assert "pack" in prog.growth_kinds and retries == {}
    assert [g for g, k in zip(grown, prog.growth_kinds) if k == "pack"] == [1.0]
    assert multikey == {("general_generic" if prog.out_kind == "generic"
                         else "general_segment", ""): 1}


def test_a_key_outside_the_recorded_bounds_relaunches_hashed(devices8, monkeypatch):
    """Bounds narrower than the data (as a plan made before a write would
    hold): the program counts the live keys outside them, relaunches with
    the keys hashed and verified, and the answer is right."""
    from tidb_tpu import statistics

    catalog = Catalog()
    _tables(catalog, T_ROWS, U_ROWS)
    real = statistics.column_bounds
    monkeypatch.setattr(statistics, "column_bounds",
                        lambda t, c: (0, 3) if c == "a" else real(t, c))
    s = session(catalog, devices8, 1)
    rows, seen, (launches, retries, _m, _s), _trace = run_spied(s, SQL)
    assert [tuple(r) for r in rows] == _want(T_ROWS, U_ROWS)
    (prog, _fn, _g, grown), = seen
    kind = f"general_{prog.out_kind}"
    assert retries == {(kind, "pack"): 1} and launches == {(kind, ""): 2}
    assert [g for g, k in zip(grown, prog.growth_kinds) if k == "pack"] == [2.0]


def test_keys_without_bounds_are_hashed_and_verified(devices8):
    """Tables written by INSERT record no bounds: no "pack" knob, the
    combined hash and a per-key check after the gather."""
    catalog = Catalog()
    s = Session(catalog=catalog)
    s.execute("use test")
    s.execute("create table t (a bigint, b bigint, x bigint)")
    s.execute("create table u (a bigint, b bigint, x bigint, primary key (a, b))")
    for name, rows in (("t", T_ROWS), ("u", U_ROWS)):
        s.execute(f"insert into {name} values "
                  + ", ".join(f"({a}, {b}, {x})" for a, b, x in rows))
    s = session(catalog, devices8, 1)
    rows, seen, (_l, retries, multikey, _s), _trace = run_spied(s, SQL)
    assert [tuple(r) for r in rows] == _want(T_ROWS, U_ROWS)
    (prog, *_rest), = seen
    assert "pack" not in prog.growth_kinds and retries == {}
    assert sum(multikey.values()) == 1


def test_the_packing_of_bounds():
    from tidb_tpu.parallel.fragment import _key_packing

    # TPC-H SF1: ps_partkey 1..200,000 in 18 bits, ps_suppkey 1..10,000 in 14
    assert _key_packing(((1, 200_000), (1, 10_000))) == [(1, 18, 14), (1, 14, 0)]
    assert _key_packing(((5, 5), (-3, 4))) == [(5, 1, 3), (-3, 3, 0)]
    assert _key_packing(((0, 2**40), (0, 2**21))) is None  # 41 + 22 bits
    assert _key_packing(((0, 2**40), (0, 2**20))) == [(0, 41, 21), (0, 21, 0)]
    assert _key_packing(None) is None
