"""Index range access (ref: planner/core IndexRangeScan feeding
executor's IndexLookUpExecutor; SURVEY.md:91, :130). A selective range
or non-unique-index equality predicate must binary-search the sorted
index cache into a compact row-id set — visible in EXPLAIN as
IndexRangeScan — instead of scanning the table."""

import re

import numpy as np
import pytest

from tidb_tpu.session import Session


@pytest.fixture
def sess():
    s = Session()
    s.execute("create table r (id bigint primary key, grp bigint, v bigint)")
    s.execute("insert into r values " + ",".join(
        f"({i}, {i % 50}, {i * 3})" for i in range(1, 5001)))
    s.execute("create index ig on r (grp)")
    s.execute("analyze table r")
    return s


def _explain(sess, sql):
    return [r[0] for r in sess.query("explain " + sql)]


def test_explain_shows_range_on_pk_between(sess):
    rows = _explain(sess, "select v from r where id between 100 and 120")
    assert any("IndexRangeScan" in r for r in rows), rows
    assert any("index:PRIMARY" in r for r in rows), rows
    assert any("range:[100,120]" in r for r in rows), rows


def test_range_results_match_full_scan(sess):
    got = sess.query(
        "select id, v from r where id between 100 and 120 order by id")
    assert got == [(i, i * 3) for i in range(100, 121)]
    # open / exclusive bounds
    assert sess.query("select count(*) from r where id > 4990") == [(10,)]
    assert sess.query("select count(*) from r where id >= 4990") == [(11,)]
    assert sess.query("select count(*) from r where id < 11") == [(10,)]
    # empty range
    assert sess.query("select v from r where id > 100 and id < 100") == []
    assert sess.query("select v from r where id > 99999") == []


def test_nonunique_index_equality_uses_range(sess):
    rows = _explain(sess, "select count(*) from r where grp = 7")
    assert any("IndexRangeScan" in r and "index:ig" in r for r in rows), rows
    assert sess.query("select count(*) from r where grp = 7") == [(100,)]


def test_residual_conjuncts_still_apply(sess):
    got = sess.query(
        "select id from r where id between 10 and 40 and v > 60 "
        "and grp = 11 order by id")
    # grp = id % 50, v = 3*id > 60 -> id > 20; id in [10,40] -> id = 11 fails
    # v, id = 61..? ids with id%50==11 in [21,40]: none except 11 (v=33<60)
    assert got == []
    got = sess.query(
        "select id from r where id between 10 and 120 and grp = 11 order by id")
    assert got == [(11,), (61,), (111,)]


def test_unselective_range_stays_scan(sess):
    # half the table: gather cost can't win; planner must keep the scan
    rows = _explain(sess, "select count(*) from r where id > 2500")
    assert not any("IndexRangeScan" in r for r in rows), rows
    assert sess.query("select count(*) from r where id > 2500") == [(2500,)]


def test_range_sees_txn_snapshot(sess):
    sess.execute("begin")
    sess.execute("update r set v = -1 where id = 105")
    assert (105, -1) in sess.query(
        "select id, v from r where id between 100 and 110")
    sess.execute("rollback")
    assert (105, 315) in sess.query(
        "select id, v from r where id between 100 and 110")
    sess.execute("delete from r where id = 106")
    got = sess.query("select id from r where id between 104 and 108 order by id")
    assert got == [(104,), (105,), (107,), (108,)]


def test_range_lookup_storage_api(sess):
    t = sess.catalog.table("test", "r")
    rows = t.index_range_lookup("PRIMARY", (), 10, 20)
    ids = sorted(int(x) for x in np.asarray(t.data["id"][rows]))
    assert ids == list(range(10, 21))
    # eq-prefix + open bounds on a non-unique index
    rows = t.index_range_lookup("ig", (7,))
    assert len(rows) == 100
    # exclusive bounds
    rows = t.index_range_lookup("PRIMARY", (), 10, 20, lo_incl=False,
                                hi_incl=False)
    ids = sorted(int(x) for x in np.asarray(t.data["id"][rows]))
    assert ids == list(range(11, 20))


def _act_rows(rows, op):
    """actRows of the first EXPLAIN ANALYZE row of operator `op`."""
    for r in rows:
        m = re.search(re.escape(op) + r"\s+\S+\s+(\d+)\s", r)
        if m:
            return int(m.group(1))
    raise AssertionError((op, rows))


def test_range_beats_full_scan(sess):
    """The point of the exercise: a selective range over a big table
    touches the rows of the range and no others, where the scan touches
    all of them. What the engine counts is asserted, not a wall-time
    ratio: on a shared CPU the ratio of two statements of a few
    milliseconds is noise (this test's own history: "measured flaky on
    a clean tree"), and a CPU timing says nothing about the chip."""
    s = Session()
    s.execute("create table big (id bigint primary key, v bigint)")
    n = 200_000
    step = 5000
    for lo in range(1, n + 1, step):
        s.execute("insert into big values " + ",".join(
            f"({i}, {i % 997})" for i in range(lo, min(lo + step, n + 1))))
    s.execute("analyze table big")
    q_range = "select sum(v) from big where id between 1000 and 1100"
    rows = _explain(s, q_range)
    assert any("IndexRangeScan" in r for r in rows), rows
    oracle = sum(i % 997 for i in range(1000, 1101))
    assert s.query(q_range) == [(oracle,)]
    ran = [r[0] for r in s.query("explain analyze " + q_range)]
    assert _act_rows(ran, "IndexRangeScan") == 101, ran
    # the scan arm: no index serves `v >= 0`, every row is read (the
    # fused scan counts segments, and all of the table's are scanned)
    q_scan = ("select count(*), sum(v), min(v), max(v), avg(v) "
              "from big where v >= 0")
    rows = _explain(s, q_scan)
    assert any("TableFullScan" in r for r in rows), rows
    assert not any("IndexRangeScan" in r for r in rows), rows
    vs = [i % 997 for i in range(1, n + 1)]
    (cnt, total, lo_v, hi_v, avg), = s.query(q_scan)
    assert (cnt, total, lo_v, hi_v) == (n, sum(vs), 0, 996)
    assert float(avg) == pytest.approx(sum(vs) / n)
    n_segs = -(-n // int(s.sysvars.get("tidb_tpu_segment_rows")))
    ran = "\n".join(r[0] for r in s.query("explain analyze " + q_scan))
    assert f"segs_scanned:{n_segs} segs_pruned:0" in ran, ran


def test_composite_index_prefix_plus_range():
    s = Session()
    s.execute("create table c (a bigint, b bigint, v bigint)")
    s.execute("insert into c values " + ",".join(
        f"({i % 10}, {i}, {i * 2})" for i in range(2000)))
    s.execute("create index iab on c (a, b)")
    s.execute("analyze table c")
    rows = [r[0] for r in s.query(
        "explain select v from c where a = 3 and b between 100 and 200")]
    assert any("IndexRangeScan" in r and "index:iab" in r for r in rows), rows
    got = s.query(
        "select v from c where a = 3 and b between 100 and 200 order by b")
    assert got == [(i * 2,) for i in range(100, 201) if i % 10 == 3]
