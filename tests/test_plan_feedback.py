"""Plan feedback (ISSUE 15): per-digest est-vs-actual capture, drift
surfaces, and the runtime-truth planner decisions.

Pinned properties:
  * store roundtrip, LRU bound, DDL/ANALYZE invalidation, concurrent
    writer safety (also under the runtime sanitizer);
  * the crafted skewed-NDV join where the heuristics pick the wrong
    order and the SECOND execution flips it — sqlite-oracle-exact both
    times (feedback changes plans, never results);
  * the eager-agg push-down exploration protocol (default plan first,
    no-push explored next, warm-measured winner sticks);
  * fused-probe tile sizing from observed overflow;
  * every surface: information_schema.plan_feedback, EXPLAIN (ANALYZE)
    est/drift columns, PLAN_EST_DRIFT, slow log + statements_summary
    drift columns, kept-trace annotations, /plan_feedback;
  * tidb_tpu_plan_feedback = 0 leaves plans byte-identical to the
    heuristic planner and records nothing.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from tidb_tpu.parser import parse
from tidb_tpu.planner import feedback as fb
from tidb_tpu.session import Session
from tidb_tpu.storage.catalog import Catalog
from tidb_tpu.testutil import mirror_to_sqlite, rows_equal


def _obs(ops=(), latency=0.01, warm=False, eager=False, fused=False,
         join_rows=None, scan_rows=None, tiles=(0, 0, 0)):
    o = fb.Observation()
    o.ops = list(ops)
    o.latency_s = latency
    o.warm = warm
    o.eager_partial = eager
    o.fused_probe = fused
    o.join_rows = dict(join_rows or {})
    o.scan_rows = dict(scan_rows or {})
    o.tile_chunks, o.tile_overflows, o.tile_max_need = tiles
    return o


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------


class TestStore:
    def test_roundtrip(self):
        st = fb.PlanFeedbackStore(capacity=8)
        st.record("d1", "p1", True,
                  _obs(ops=[("Scan", 100.0, 400.0)], latency=0.02))
        rows = st.rows()
        assert len(rows) == 1
        digest, plan, variant, execs = rows[0][:4]
        assert (digest, plan, variant, execs) == ("d1", "p1", "push", 1)
        op, est, actual, drift = rows[0][8:12]
        assert (op, est, actual, drift) == ("Scan", 100.0, 400.0, 4.0)
        d = st.stats_dict()
        assert d["recorded"] == 1 and d["digests"][0]["digest"] == "d1"

    def test_latest_actual_wins_and_execs_fold(self):
        st = fb.PlanFeedbackStore()
        st.record("d", "p", True, _obs(ops=[("Join", 10.0, 100.0)]))
        st.record("d", "p", True, _obs(ops=[("Join", 10.0, 80.0)]))
        row = st.rows()[0]
        assert row[10] == 80.0 and row[12] == 2  # actual, op execs

    def test_lru_bound(self):
        st = fb.PlanFeedbackStore(capacity=4)
        for i in range(10):
            st.record(f"d{i}", "p", True, _obs())
        assert len(st.rows()) == 4
        assert st.evicted == 6
        kept = {r[0] for r in st.rows()}
        assert kept == {"d6", "d7", "d8", "d9"}

    def test_capacity_follows_sysvar_argument(self):
        st = fb.PlanFeedbackStore(capacity=100)
        for i in range(8):
            st.record(f"d{i}", "p", True, _obs(), capacity=2)
        assert len(st.rows()) == 2

    def test_invalidation_clears_everything(self):
        st = fb.PlanFeedbackStore()
        st.record("d", "p", True, _obs(
            join_rows={frozenset({("a", "k"), ("b", "k")}): 500.0},
            scan_rows={("a", "c:x"): (10.0, 100.0)}))
        st.on_schema_change()
        assert not st.rows()
        assert st.join_hint(frozenset({("a", "k"), ("b", "k")})) is None
        assert st.scan_hint("a", "c:x") is None
        assert st.invalidations == 1

    def test_ddl_and_analyze_invalidate_the_global_store(self):
        s = Session(catalog=Catalog())
        s.execute("create table inv (a bigint)")
        fb.STORE.record("d-inv", "p", True, _obs())
        assert any(r[0] == "d-inv" for r in fb.STORE.rows())
        s.execute("create table inv2 (a bigint)")  # DDL: schema_version
        assert not any(r[0] == "d-inv" for r in fb.STORE.rows())
        fb.STORE.record("d-inv", "p", True, _obs())
        s.execute("analyze table inv")  # stats reset the baseline too
        assert not any(r[0] == "d-inv" for r in fb.STORE.rows())

    def test_concurrent_writers(self):
        st = fb.PlanFeedbackStore(capacity=64)
        errs = []

        def worker(i):
            try:
                for j in range(200):
                    st.record(f"d{j % 32}", f"p{i}", True,
                              _obs(ops=[("Scan", 10.0, 20.0 + i)]))
                    st.scan_hint("a", "fp")
                    st.rows()
            except Exception as e:  # noqa: BLE001 — collected for assert
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        assert len(st.rows()) <= 64 * 8  # per-digest variants bounded
        assert st.recorded == 800

    def test_shuffle_hint_roundtrip(self):
        st = fb.PlanFeedbackStore()
        st.record_shuffle("dg", {"t1": 1024, "t2": 9999},
                          {"t1": 3, "t2": 1})
        assert st.shuffle_hint("dg") == {"t1": 1024, "t2": 9999}
        st.record_shuffle("dg", {"t1": 2048}, {"t1": 3})
        assert st.shuffle_hint("dg")["t1"] == 2048
        assert st.shuffle_hint("other") == {}
        # schema churn (every dcn query's staging DDL) does NOT erase
        # exchange observations...
        st.on_schema_change()
        assert st.shuffle_hint("dg", {"t1": 3, "t2": 1})["t1"] == 2048
        # ...but a placement-version move (reshard/reload) does
        assert st.shuffle_hint("dg", {"t1": 4, "t2": 1}) == {}
        assert st.shuffle_hint("dg") == {}  # dropped, not just hidden


class TestApdDecision:
    """The measured push-vs-no-push protocol, driven synthetically so
    the choice is deterministic (at real scale: not measured, no cell
    reaches the fused tier)."""

    def test_protocol(self):
        st = fb.PlanFeedbackStore()
        assert st.apd_decision("d") is None  # nothing recorded
        st.record("d", "on", True, _obs(eager=True, latency=0.1))
        # default variant carried an eager partial -> explore no-push
        assert st.apd_decision("d") is False
        st.record("d", "off", False, _obs(latency=0.09))  # cold explore
        assert st.apd_decision("d") is False  # no warm measurement yet
        st.record("d", "off", False, _obs(latency=0.02, warm=True))
        # off is warm; on has no warm run -> re-measure the default
        assert st.apd_decision("d") is None
        st.record("d", "on", True, _obs(eager=True, latency=0.08,
                                        warm=True))
        # both warm: off (20ms) beats on (80ms) by the margin
        assert st.apd_decision("d") is False

    def test_faster_default_sticks(self):
        st = fb.PlanFeedbackStore()
        st.record("d", "on", True, _obs(eager=True, latency=0.02,
                                        warm=True))
        st.record("d", "off", False, _obs(latency=0.05, warm=True))
        assert st.apd_decision("d") is None  # push-down measured faster

    def test_no_eager_partial_means_no_opinion(self):
        st = fb.PlanFeedbackStore()
        st.record("d", "on", True, _obs(eager=False, latency=0.1))
        assert st.apd_decision("d") is None  # the knob changed nothing

    def test_explore_budget_gives_up_on_warm(self):
        st = fb.PlanFeedbackStore()
        st.record("d", "on", True, _obs(eager=True, latency=0.1,
                                        warm=True))
        for _ in range(fb.EXPLORE_BUDGET):
            st.record("d", "off", False, _obs(latency=0.01))  # never warm
        # budget exhausted: the off variant scores by its best cold run
        assert st.apd_decision("d") is False

    def test_tile_hint(self):
        st = fb.PlanFeedbackStore()
        st.record("d", "p", True, _obs(tiles=(10, 0, 0)))
        assert st.tile_hint("d") == 0  # no overflow, no opinion
        st.record("d", "p", True, _obs(tiles=(10, 3, 23)))
        assert st.tile_hint("d") == 23
        st.record("d", "p", True, _obs(tiles=(10, 1, 700)))
        assert st.tile_hint("d") == 64  # clamped to the sysvar ceiling


# ---------------------------------------------------------------------------
# the skewed-NDV join: heuristics pick the wrong order, the second
# execution flips it, oracle-exact both times
# ---------------------------------------------------------------------------


def _skew_session():
    s = Session(catalog=Catalog())
    s.execute("set tidb_enable_auto_analyze = 0")
    s.execute("set tidb_slow_log_threshold = 0")  # every stmt slow-logs
    rng = np.random.default_rng(7)
    s.execute("create table a (k bigint, g bigint, flag bigint)")
    s.execute("create table b (k bigint, v bigint)")
    s.execute("create table c (g bigint, lbl bigint)")
    n = 8000
    k = rng.integers(1000, 9000, n).astype(np.int64)
    flag = rng.integers(0, 80, n).astype(np.int64)
    k[flag == 77] = 5  # correlation: every flag=77 row carries the hot
    # key, which no per-column statistic can see — the estimator's
    # MCV math underestimates the filtered join ~80x
    s.catalog.table("test", "a").insert_columns({
        "k": k, "g": rng.integers(0, 200, n).astype(np.int64),
        "flag": flag})
    s.catalog.table("test", "b").insert_columns({
        "k": np.full(100, 5, dtype=np.int64),
        "v": np.arange(100, dtype=np.int64)})
    s.catalog.table("test", "c").insert_columns({
        "g": (np.arange(800) % 200).astype(np.int64),
        "lbl": np.arange(800, dtype=np.int64)})
    s.execute("analyze table a, b, c")
    return s


_SKEW_SQL = ("select count(*) as n, sum(b.v) as sv from a "
             "join b on a.k = b.k join c on a.g = c.g "
             "where a.flag = 77")


def _op_depth(line):
    """Column where the operator name starts (tree glyphs + spaces
    before it) — deeper operators start further right."""
    return len(line) - len(line.lstrip(" │├└─·"))


def _first_join_tables(explain_rows):
    """Table names that are DIRECT children of the deepest HashJoin —
    the pair the orderer chose to join first."""
    lines = [r[0] for r in explain_rows]
    joins = [(i, _op_depth(line))
             for i, line in enumerate(lines) if "HashJoin" in line]
    deepest, depth = max(joins, key=lambda t: t[1])
    tables = []
    for line in lines[deepest + 1:]:
        if _op_depth(line) <= depth:
            break
        if "table:" in line:
            tables.append(line.split("table:")[1].split(",")[0].strip())
    return set(tables)


class TestSkewedJoinOrderFlip:
    @pytest.fixture(scope="class")
    def sess(self):
        return _skew_session()

    def test_flip_is_oracle_exact_both_times(self, sess):
        conn = mirror_to_sqlite(sess.catalog, tables=["a", "b", "c"])
        want = conn.execute(_SKEW_SQL).fetchall()
        conn.close()
        ex1 = sess.execute("explain " + _SKEW_SQL).rows
        assert _first_join_tables(ex1) == {"a", "b"}, ex1  # the trap:
        # the MCV-blind estimate makes the hot pair look cheap
        r1 = sess.query(_SKEW_SQL)
        d1 = sess._last_plan_digest
        ok, msg = rows_equal(r1, want, ordered=True)
        assert ok, msg
        # the harvest recorded the base-pair truth (keyed by the
        # column pairs PLUS each side's filter fingerprint, so other
        # filter contexts of the same tables never share it)
        hints = {k: v for k, v in fb.STORE._join_rows.items()
                 if k[0] == frozenset({("a", "k"), ("b", "k")})}
        assert len(hints) == 1, fb.STORE._join_rows
        (key, got), = hints.items()
        assert got == pytest.approx(10400.0)
        sides = dict(key[1])
        assert sides["b"] == "" and "77" in sides["a"], key  # a's
        # flag=77 filter is part of the identity; b is unfiltered
        # second execution: the recorded actual flips the order
        r2 = sess.query(_SKEW_SQL)
        d2 = sess._last_plan_digest
        ok, msg = rows_equal(r2, want, ordered=True)
        assert ok, msg
        assert d1 != d2, "plan did not change on the second execution"
        ex2 = sess.execute("explain " + _SKEW_SQL).rows
        assert _first_join_tables(ex2) == {"a", "c"}, ex2  # hot pair
        # deferred to last; the cheap dimension join runs first
        # and it STAYS flipped
        r3 = sess.query(_SKEW_SQL)
        assert sess._last_plan_digest == d2
        ok, _ = rows_equal(r3, want, ordered=True)
        assert ok

    def test_feedback_off_reverts_to_heuristic_plan(self, sess):
        """With the sysvar off the polluted store is ignored: the plan
        is byte-identical to the heuristic planner's."""
        sess.execute("set tidb_tpu_plan_feedback = 0")
        try:
            ex = sess.execute("explain " + _SKEW_SQL).rows
            assert _first_join_tables(ex) == {"a", "b"}, ex
            rec0 = fb.STORE.recorded
            sess.query(_SKEW_SQL)
            assert fb.STORE.recorded == rec0  # nothing recorded either
        finally:
            sess.execute("set tidb_tpu_plan_feedback = 1")

    def test_drift_surfaces(self, sess):
        """The misestimate is findable on every surface without
        tracing: slow log, statements summary, I_S plan_feedback."""
        rows = sess.query(
            "select worst_drift_op, worst_drift from "
            "information_schema.slow_query where worst_drift > 1")
        assert rows, "no slow-log row carries drift"
        assert any(op.startswith("HashJoin") for op, _d in rows)
        summ = sess.query(
            "select max_drift, mean_drift, worst_drift_op from "
            "information_schema.statements_summary where max_drift > 4")
        assert summ, "statements_summary lost the drift aggregates"
        isrows = sess.query(
            "select op, est_rows, actual_rows, drift from "
            "information_schema.plan_feedback where drift > 4")
        assert isrows, "plan_feedback I_S table shows no drifted op"

    def test_plan_est_drift_metric_moved(self, sess):
        from tidb_tpu.utils.metrics import PLAN_EST_DRIFT

        assert PLAN_EST_DRIFT.count() > 0


# ---------------------------------------------------------------------------
# eager-agg exploration: integration (protocol + correctness)
# ---------------------------------------------------------------------------


class TestApdExplorationIntegration:
    def test_q18_shape_explores_and_stays_correct(self):
        s = Session(catalog=Catalog(), chunk_capacity=1 << 16)
        s.execute("SET tidb_device_engine_mode = 'force'")
        s.execute("SET tidb_enable_non_prepared_plan_cache = 1")
        s.execute("set tidb_enable_auto_analyze = 0")
        rng = np.random.default_rng(3)
        s.execute("create table li (ok bigint, qty bigint)")
        s.execute("create table ords (ok bigint, pri bigint)")
        n_o, n_l = 1500, 6000
        s.catalog.table("test", "ords").insert_columns({
            "ok": np.arange(n_o, dtype=np.int64),
            "pri": (np.arange(n_o) % 5).astype(np.int64)})
        s.catalog.table("test", "li").insert_columns({
            "ok": rng.integers(0, n_o, n_l).astype(np.int64),
            "qty": rng.integers(1, 50, n_l).astype(np.int64)})
        s.execute("analyze table li, ords")
        sql = ("select pri, count(*) as n, sum(qty) as q from li "
               "join ords on li.ok = ords.ok group by pri order by pri")
        conn = mirror_to_sqlite(s.catalog, tables=["li", "ords"])
        want = conn.execute(sql).fetchall()
        conn.close()
        apds = []
        for _ in range(6):
            got = s.query(sql)
            apds.append(s._fb_last_apd)
            ok, msg = rows_equal(got, want, ordered=True)
            assert ok, msg  # every explored variant is oracle-exact
        # run 0 executes the DEFAULT (push) plan; run 1 explores the
        # no-push alternative — the ISSUE's "warm second execution
        # selects the fused shape" protocol
        assert apds[0] is True and apds[1] is False, apds
        # the default sysvar never moved: the flip is feedback, not pin
        assert bool(s.sysvars.get("tidb_opt_agg_push_down"))
        from tidb_tpu.bindinfo import normalize_sql, sql_digest

        dg = sql_digest(normalize_sql(sql))
        variants = {}
        for d in fb.STORE.stats_dict(50)["digests"]:
            if d["digest"] == dg:
                variants = {v["agg_push_down"]: v for v in d["variants"]}
        assert set(variants) == {True, False}, variants
        assert variants[True]["eager_partial"]
        assert not variants[False]["eager_partial"]
        # after warm measurements exist for both, the store's choice
        # matches the measured winner (min warm latency with margin)
        if variants[True]["warm_execs"] and variants[False]["warm_execs"]:
            faster_off = (variants[False]["best_warm_ms"]
                          < variants[True]["best_warm_ms"] * fb.WIN_MARGIN)
            assert (fb.STORE.apd_decision(dg) is False) == faster_off

    def test_user_pin_is_authoritative(self):
        s = Session(catalog=Catalog())
        s.execute("create table pin_t (a bigint)")
        s.execute("set tidb_opt_agg_push_down = 0")
        # decision machinery would say False; with the sysvar pinned
        # off the override path is never consulted (apd stays False
        # because the USER said so, not feedback)
        s.query("select count(*) from pin_t")
        assert s._fb_last_apd is False


# ---------------------------------------------------------------------------
# tile-capacity consumer
# ---------------------------------------------------------------------------


class TestTileHintConsumer:
    def test_exec_ctx_raises_join_tiles(self):
        s = Session(catalog=Catalog())
        s.execute("create table tt (a bigint)")
        src = "select a from tt"
        norm_digest = s._stmt_digest(parse(src)[0], src)
        digest = norm_digest[1]
        s._stmt_digest_memo = (src, norm_digest[0], digest)
        assert s._exec_ctx().join_tiles == 8  # sysvar default
        fb.STORE.record(digest, "p", True, _obs(tiles=(100, 40, 23)))
        s._stmt_digest_memo = (src, norm_digest[0], digest)
        assert s._exec_ctx().join_tiles == 23
        s.execute("set tidb_tpu_plan_feedback = 0")
        s._stmt_digest_memo = (src, norm_digest[0], digest)
        assert s._exec_ctx().join_tiles == 8  # off: no override


# ---------------------------------------------------------------------------
# EXPLAIN / EXPLAIN ANALYZE columns
# ---------------------------------------------------------------------------


class TestExplainSurfaces:
    @pytest.fixture(scope="class")
    def sess(self):
        s = Session(catalog=Catalog())
        s.execute("create table e (a bigint, b bigint)")
        s.execute("insert into e values (1,1),(2,2),(3,3),(4,4)")
        return s

    def test_explain_renders_est_rows(self, sess):
        rs = sess.execute("explain select a from e where b > 1")
        header = rs.rows[0][0]
        assert "estRows" in header
        # every operator row carries a numeric estimate
        for (line,) in rs.rows[1:]:
            assert any(ch.isdigit() for ch in line), line

    def test_explain_analyze_est_and_drift(self, sess):
        rs = sess.execute("explain analyze select a from e where b > 1")
        header = rs.rows[0][0]
        for col in ("estRows", "actRows", "drift"):
            assert col in header, header
        body = "\n".join(r[0] for r in rs.rows[1:])
        # est 4*0.25=1 (no stats sel fallback) or histogram — either
        # way actRows=3 renders a drift ratio somewhere in the tree
        assert "3" in body


# ---------------------------------------------------------------------------
# endpoint + trace annotation + sanitizer interplay
# ---------------------------------------------------------------------------


class TestEndToEndSurfaces:
    def test_plan_feedback_endpoint(self):
        from tidb_tpu.server.server import Server

        cat = Catalog()
        s = Session(catalog=cat)
        s.execute("create table ep (a bigint)")
        s.execute("insert into ep values (1), (2)")
        s.query("select count(*) from ep")
        srv = Server(catalog=cat, port=0, status_port=0)
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.status_port}"
            doc = json.loads(urllib.request.urlopen(
                base + "/plan_feedback?top=10").read())
            assert "digests" in doc and doc["capacity"] >= 1
            assert doc["recorded"] >= 1
        finally:
            srv.stop()

    def test_worst_drift_annotation_on_kept_trace(self):
        from tidb_tpu.utils import tracing

        s = _skew_session()
        s.execute("set tidb_trace_sample_rate = 1")  # keep everything
        s.query(_SKEW_SQL)
        notes = []
        for t in tracing.STORE.traces():
            for sp in list(t.spans):
                notes.extend(getattr(sp, "notes", ()))
        assert any(str(n).startswith("worst_drift:") for n in notes), \
            "no kept trace carries the worst-drift annotation"

    def test_concurrent_statements_under_sanitizer(self):
        cat = Catalog()
        setup = Session(catalog=cat)
        setup.execute("create table cw (a bigint, b bigint)")
        setup.execute("insert into cw values " + ",".join(
            f"({i},{i * 2})" for i in range(64)))
        errs = []

        def run():
            try:
                s = Session(catalog=cat)
                s.execute("set tidb_tpu_sanitize = 1")
                for _ in range(10):
                    assert s.query(
                        "select sum(b) from cw where a < 32"
                    ) == [(992,)]
            except Exception as e:  # noqa: BLE001 — collected
                errs.append(e)

        ts = [threading.Thread(target=run) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs, errs  # no SanitizerError, no store corruption


# ---------------------------------------------------------------------------
# dcn consumer: broadcast-vs-shuffle from observed exchange bytes
# ---------------------------------------------------------------------------


class TestShuffleBytesFeedback:
    def test_observed_bytes_flip_shuffle_to_broadcast(self):
        """Neither side is placed on the join key, so both shuffle on
        the first run (raw placement sizes say replicating the smaller
        side is not worth it: y's six int64 columns weigh about as much
        raw as wide x). The FoR-encoded wire batches the scatter acks
        report are far smaller for y than for x, so the SECOND planning
        broadcasts y instead of hashing both. Results sqlite-exact both
        times: feedback picks among correct exchange plans, never
        answers."""
        from tidb_tpu.parallel.dcn import Cluster, Worker

        n = 3000
        pad = ["p" * 60 for _ in range(n)]  # x's raw bytes are DOMINATED
        # by a column the query never touches
        workers = [Worker() for _ in range(3)]
        for w in workers:
            threading.Thread(target=w.serve_forever, daemon=True).start()
        cl = Cluster([("127.0.0.1", w.port) for w in workers],
                     rpc_timeout_s=30.0, connect_timeout_s=5.0)
        oracle = Session(catalog=Catalog())
        ddl_x = ("create table x (k bigint, g bigint, pad varchar(64)) "
                 "shard by hash(g) shards 6")
        ddl_y = ("create table y (k bigint, w bigint, v bigint, "
                 "v2 bigint, v3 bigint, v4 bigint) "
                 "shard by hash(w) shards 6")
        sql = ("select count(*) as n, sum(y.v) as sv "
               "from x join y on x.k = y.k")
        try:
            cl.ddl(ddl_x)
            cl.ddl(ddl_y)
            xk = np.arange(n, dtype=np.int64)
            cl.load_sharded("x", arrays={
                "k": xk, "g": xk % 7}, strings={"pad": pad})
            yk = (np.arange(n, dtype=np.int64) * 3) % n
            ycols = {"k": yk, "w": yk % 13,
                     "v": np.arange(n, dtype=np.int64),
                     "v2": yk + 1, "v3": yk + 2, "v4": yk + 3}
            cl.load_sharded("y", arrays=ycols)
            for st, cols in (("x", {"k": xk, "g": xk % 7}),
                             ("y", ycols)):
                oracle.execute(
                    (ddl_x if st == "x" else ddl_y).split(" shard by")[0])
                t = oracle.catalog.table("test", st)
                t.insert_columns(dict(cols))
            conn = mirror_to_sqlite(oracle.catalog, tables=["x", "y"])
            want = conn.execute(sql).fetchall()
            conn.close()

            def modes_of(plan):
                out = {}
                for _w, msg in plan["shuffle"]["scatter"]:
                    out[msg["table"]] = msg.get("mode")
                return out

            plan1 = cl._plan_query(sql)
            assert modes_of(plan1) == {"x": "hash", "y": "hash"}, plan1
            got1 = cl.query(sql)
            ok, msg = rows_equal(got1, want)
            assert ok, msg
            # the scatter acks recorded each side's actual wire bytes
            from tidb_tpu.bindinfo import normalize_sql, sql_digest

            hint = fb.STORE.shuffle_hint(sql_digest(normalize_sql(sql)))
            assert set(hint) == {"x", "y"} and hint["y"] < hint["x"], hint
            plan2 = cl._plan_query(sql)
            # observed bytes say replicating y is cheap; x stays put
            # (the anchored side: gather runs at its owners)
            assert modes_of(plan2) == {"y": "broadcast"}, plan2
            got2 = cl.query(sql)
            ok, msg = rows_equal(got2, want)
            assert ok, msg
        finally:
            cl.shutdown()


# ---------------------------------------------------------------------------
# static surface count (the check_invariants --json satellite)
# ---------------------------------------------------------------------------


def test_plan_feedback_surface_count_pinned():
    import os

    from tidb_tpu.analysis.core import Project
    from tidb_tpu.analysis.registry import (_PLAN_FEEDBACK_SURFACES,
                                            plan_feedback_surfaces)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    got = plan_feedback_surfaces(Project(root))
    assert len(got) == len(_PLAN_FEEDBACK_SURFACES) == 6, got
