"""ANALYZE statistics and cost-based join reordering.

Ref counterpart: statistics/ + planner/core's join-reorder rule. The
golden checks pin the property that matters — selective-first join
orders and no cross joins in the reordered TPC-H plans — not exact plan
text."""

import numpy as np
import pytest

from tidb_tpu.parser import parse
from tidb_tpu.planner.physical import PHashJoin, PScan, explain_text
from tidb_tpu.session import Session
from tidb_tpu.statistics import analyze_table, scan_selectivity, table_stats
from tidb_tpu.storage.tpch import load_tpch
from tidb_tpu.storage.tpch_queries import Q


@pytest.fixture(scope="module")
def tpch():
    s = Session(chunk_capacity=4096)
    load_tpch(s.catalog, sf=0.01)
    s.execute("ANALYZE TABLE lineitem, orders, customer, supplier, part, "
              "partsupp, nation, region")
    return s


def test_analyze_collects(tpch):
    t = tpch.catalog.table("test", "orders")
    s = table_stats(t)
    assert s is not None and s.n_rows == t.live_rows
    ok = s.cols["o_orderkey"]
    assert ok.ndv == s.n_rows  # primary key: all distinct
    assert ok.null_count == 0
    assert ok.min == 1.0 and ok.max == float(s.n_rows)
    st = s.cols["o_orderstatus"]
    assert 1 <= st.ndv <= 3


def test_stats_go_stale_on_mutation(tpch):
    t = tpch.catalog.table("test", "region")
    assert table_stats(t) is not None
    tpch.execute("INSERT INTO region VALUES (99, 'NOWHERE', 'x')")
    assert table_stats(t) is None  # version bumped -> stale
    tpch.execute("ANALYZE TABLE region")
    assert table_stats(t).n_rows == 6
    tpch.execute("DELETE FROM region WHERE r_regionkey = 99")
    tpch.execute("ANALYZE TABLE region")


def test_range_selectivity(tpch):
    t = tpch.catalog.table("test", "lineitem")
    # build the scan IR through the planner for a real predicate
    phys = tpch._plan_select(parse(
        "select count(*) from lineitem where l_quantity < 10")[0])
    # l_quantity is uniform over 1..50, held as scale-2 ints 100..5000;
    # the IR carries the INT literal 10 and rescales it when the
    # comparison is evaluated, so the estimate has to rescale it too
    # (until PR 32 it read the literal against the scaled values: this
    # test asked for `< 1000`, which every row passes, and wanted 18%)
    scan = phys
    while not isinstance(scan, PScan):
        scan = scan.children[0]
    uid_to_col = {c.uid: c.name for c in scan.schema}
    sel = scan_selectivity(t, scan.pushed_cond, uid_to_col)
    assert 0.1 < sel < 0.3
    everything = tpch._plan_select(parse(
        "select count(*) from lineitem where l_quantity < 1000")[0])
    while not isinstance(everything, PScan):
        everything = everything.children[0]
    assert scan_selectivity(t, everything.pushed_cond, {
        c.uid: c.name for c in everything.schema}) == 1.0


def _join_order(phys):
    """Leaf table names in execution order (left-deep walk)."""
    out = []

    def visit(p):
        for c in p.children:
            visit(c)
        if isinstance(p, PScan):
            out.append(p.table_name)

    visit(phys)
    return out


def _has_cross_join(phys):
    if isinstance(phys, PHashJoin) and not phys.eq_left:
        return True
    return any(_has_cross_join(c) for c in phys.children)


def test_q5_selective_first_order(tpch):
    phys = tpch._plan_select(parse(Q["q5"][0])[0])
    order = _join_order(phys)
    # region (1 row after filter) must come first; lineitem (biggest) last
    assert order[0] == "region", order
    assert order[-1] == "lineitem", order
    assert not _has_cross_join(phys), explain_text(phys)


@pytest.mark.parametrize("name", ["q5", "q7", "q8", "q9"])
def test_no_cross_joins_after_reorder(tpch, name):
    phys = tpch._plan_select(parse(Q[name][0])[0])
    assert not _has_cross_join(phys), explain_text(phys)


def test_q8_q9_results_with_reorder(tpch):
    from tidb_tpu.testutil import mirror_to_sqlite, rows_equal

    conn = mirror_to_sqlite(tpch.catalog)
    for name in ("q8", "q9"):
        sql, lite = Q[name]
        got = tpch.query(sql)
        want = conn.execute(lite or sql).fetchall()
        ok, msg = rows_equal(got, want, ordered=True)
        assert ok, f"{name}: {msg}"
