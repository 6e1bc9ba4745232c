"""End-to-end distributed tracing (ISSUE 5): trace-context propagation
over DCN, worker span shipping, the tail-sampled trace store, metric
exemplars, and the satellites that ride along (errored statements in
the slow log / statements_summary, information_schema.dcn_worker_stats,
EXPLAIN ANALYZE start offsets).

Workers run IN-PROCESS (threads) so failpoints and the process-global
trace store reach both sides of the wire."""

import json
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

from tidb_tpu.errors import QueryTimeoutError
from tidb_tpu.parallel.dcn import Cluster, Worker
from tidb_tpu.session import Session
from tidb_tpu.utils import metrics as M
from tidb_tpu.utils import tracing
from tidb_tpu.utils.failpoint import failpoint


# -- unit: Trace / Span / store ---------------------------------------------


class TestTraceUnit:
    def test_trace_id_format(self):
        tid = tracing.make_trace_id("a" * 32)
        assert re.fullmatch(r"a{16}-\d+", tid)
        assert tracing.make_trace_id("").startswith("anon-")

    def test_head_sampling_edges(self):
        assert tracing.head_sampled(0.0) is False
        assert tracing.head_sampled(-1) is False
        assert tracing.head_sampled(1.0) is True

    def test_span_bound_counts_drops(self):
        tr = tracing.Trace("t-1", max_spans=4)
        spans = [tr.begin(f"s{i}") for i in range(10)]
        assert len(tr.spans) == 4
        assert tr.dropped == 6
        for s in spans:  # ending a dropped span must not blow up
            tr.end(s)

    def test_graft_remaps_ids_and_offsets(self):
        tr = tracing.Trace("t-2")
        rpc = tr.begin("dcn.rpc")
        time.sleep(0.001)
        tr.end(rpc)
        # a worker-local tree: root (id 1) with a child (id 2); ids
        # collide with coordinator-side ids on purpose
        remote = [
            {"i": 1, "p": 0, "n": "worker.partial", "s": 100, "d": 500,
             "a": ["partial:rows=3"]},
            {"i": 2, "p": 1, "n": "stmt.select", "s": 150, "d": 400,
             "a": []},
        ]
        tr.graft(remote, rpc, proc="10.0.0.1:9999")
        by_name = {s.name: s for s in tr.spans}
        wroot, wchild = by_name["worker.partial"], by_name["stmt.select"]
        assert wroot.parent_id == rpc.span_id
        assert wchild.parent_id == wroot.span_id
        assert wroot.span_id != 1 and wchild.span_id != 2  # remapped
        assert wroot.start_us == rpc.start_us + 100  # re-anchored
        assert wroot.proc == wchild.proc == "10.0.0.1:9999"
        assert "partial:rows=3" in wroot.notes
        # malformed remote spans are skipped, not fatal
        tr.graft([{"n": "missing keys"}], rpc, proc="x")

    def test_to_dict_builds_tree(self):
        tr = tracing.Trace("t-3")
        a = tr.begin("a")
        b = tr.begin("b", parent_id=a.span_id)
        tr.end(b)
        tr.end(a)
        d = tr.to_dict()
        json.dumps(d)  # JSON-clean
        assert d["tree"][0]["name"] == "a"
        assert d["tree"][0]["children"][0]["name"] == "b"

    def test_store_capacity_and_lookup(self):
        st = tracing.TraceStore(capacity=2)
        ts = [tracing.Trace(f"cap-{i}") for i in range(3)]
        for t in ts:
            t.keep("slow")
            st.add(t)
        assert len(st) == 2
        assert st.get("cap-0") is None  # trimmed
        assert st.get("cap-2") is ts[2]
        assert [s["trace_id"] for s in st.list(10)] == ["cap-2", "cap-1"]

    def test_tls_span_nesting(self):
        tr = tracing.Trace("t-4")
        tracing.push(tr)
        try:
            with tracing.span("outer") as o:
                with tracing.span("inner") as i:
                    tracing.annotate("note")
                assert i.parent_id == o.span_id
                assert "note" in i.notes
        finally:
            assert tracing.pop() is tr
        assert tracing.current() is None


# -- statement-level: head/tail sampling, slow log, summary -----------------


def _quiet(s):
    """No head sampling, no slow-threshold keeps: only explicit tail
    rules can retain a trace from this session."""
    s.execute("set tidb_trace_sample_rate = 0")
    s.execute("set tidb_slow_log_threshold = 300000")
    return s


class TestStatementTracing:
    def test_head_sampled_statement_is_kept(self):
        # compare by id set, not len(): a store at ring capacity evicts
        # one trace per add, so its length never grows
        s = Session()
        s.execute("set tidb_trace_sample_rate = 1")
        s.execute("set tidb_slow_log_threshold = 300000")
        before = {t.trace_id for t in tracing.STORE.traces()}
        s.query("select 1")
        new = [t for t in tracing.STORE.traces()
               if t.trace_id not in before]
        assert new
        tr = new[-1]
        assert tr.keep_reasons == ["sampled"]
        assert tr.spans[0].name == "stmt.select"

    def test_uneventful_statement_is_discarded(self):
        s = _quiet(Session())
        s.query("select 1")  # warm
        before = {t.trace_id for t in tracing.STORE.traces()}
        s.query("select 1")
        after = {t.trace_id for t in tracing.STORE.traces()}
        assert after <= before  # nothing new kept
        assert tracing.current() is None  # nothing leaked onto the thread

    def test_slow_statement_tail_kept_with_trace_id_in_slow_log(self):
        s = _quiet(Session())
        s.query("select 1")  # jit/warm out of band
        s.execute("set tidb_slow_log_threshold = 0")  # everything is slow
        s.query("select 41 + 1")
        s.execute("set tidb_slow_log_threshold = 300000")
        rows = s.query("select query, trace_id, disposition from"
                       " information_schema.slow_query")
        hit = [r for r in rows if r[0] == "select 41 + 1"]
        assert hit, rows
        _q, trace_id, dispo = hit[-1]
        assert dispo == ""
        tr = tracing.STORE.get(trace_id)
        assert tr is not None and "slow" in tr.keep_reasons

    def test_error_statement_tail_kept_and_logged(self):
        """Satellite: statements that die mid-execution reach the slow
        log with an error disposition (they used to be invisible) and
        count an error in statements_summary."""
        s = _quiet(Session())
        s.execute("set tidb_slow_log_threshold = 0")
        with pytest.raises(Exception):
            s.query("select * from missing_tbl_for_tracing")
        s.execute("set tidb_slow_log_threshold = 300000")
        rows = s.query("select query, trace_id, disposition from"
                       " information_schema.slow_query")
        hit = [r for r in rows if "missing_tbl_for_tracing" in r[0]]
        assert hit, rows
        _q, trace_id, dispo = hit[-1]
        assert dispo == "error:SchemaError"
        tr = tracing.STORE.get(trace_id)
        assert tr is not None
        assert "error:SchemaError" in tr.keep_reasons

    def test_deadline_killed_statement_recorded_everywhere(self):
        """A QueryTimeoutError mid-chunk-loop lands in the slow log
        (error disposition), statements_summary (errors=1), and keeps
        its trace — the exact blind spot the satellite names."""
        s = _quiet(Session(chunk_capacity=1024))
        s.execute("create table big_to (a bigint)")
        s.catalog.table("test", "big_to").insert_columns(
            {"a": np.arange(120_000, dtype=np.int64)})
        s.execute("set tidb_slow_log_threshold = 0")
        s.execute("set max_execution_time = 1")  # 1 ms: must expire
        q = ("select count(*) from big_to b1 join big_to b2"
             " on b1.a = b2.a where b1.a > 10")
        with pytest.raises(QueryTimeoutError):
            s.query(q)
        s.execute("set max_execution_time = 0")
        s.execute("set tidb_slow_log_threshold = 300000")
        rows = s.query("select query, trace_id, disposition from"
                       " information_schema.slow_query")
        hit = [r for r in rows if "big_to b1" in r[0]]
        assert hit, rows
        assert hit[-1][2] == "error:QueryTimeoutError"
        tr = tracing.STORE.get(hit[-1][1])
        assert tr is not None
        assert "error:QueryTimeoutError" in tr.keep_reasons
        summ = s.query(
            "select exec_count, errors from"
            " information_schema.statements_summary where digest_text like"
            " '%big_to b1%'")
        assert summ and summ[0][1] >= 1

    def test_trace_statement_start_offsets(self):
        """TRACE rows come from the tracer: real start_ms offsets,
        monotone nondecreasing across the session phases."""
        s = _quiet(Session())
        s.execute("create table tso (a bigint)")
        s.execute("insert into tso values (1), (2)")
        rs = s.execute("TRACE select count(*) from tso")
        assert rs.names == ["span", "start_ms", "duration_ms"]
        by_name = {r[0]: r for r in rs.rows}
        plan, execute = by_name["session.plan"], by_name["session.execute"]
        assert execute[1] >= plan[1] >= 0.0
        assert any(r[0].strip().startswith("executor.") for r in rs.rows)
        # TRACE always keeps its trace, regardless of sampling
        tr = tracing.STORE.traces()[-1]
        assert "trace" in tr.keep_reasons

    def test_cluster_trace_table_rows(self):
        s = Session()
        s.execute("set tidb_trace_sample_rate = 1")
        s.execute("set tidb_slow_log_threshold = 300000")
        s.query("select 7")
        tid = tracing.STORE.traces()[-1].trace_id
        rows = s.query(
            "select trace_id, name, proc, start_us, duration_us from"
            f" information_schema.cluster_trace where trace_id = '{tid}'")
        assert rows
        assert any(r[1] == "stmt.select" for r in rows)


# -- EXPLAIN ANALYZE start offsets (satellite) -------------------------------


def test_explain_analyze_start_offset_column():
    s = Session()
    s.execute("create table ea (a bigint, b bigint)")
    s.execute("insert into ea values (1, 2), (3, 4), (5, 6)")
    rows = s.query("explain analyze select b, count(*) from ea"
                   " group by b order by b")
    text = "\n".join(r[0] for r in rows)
    header = rows[0][0]
    assert "start" in header and "execution info" in header
    # proportional gutter + numeric offset on every operator row
    assert re.search(r"\| \+\d+us", text), text


# -- distributed: the acceptance scenario ------------------------------------


def _mk_cluster(n_rows=600):
    workers = [Worker() for _ in range(2)]
    for w in workers:
        threading.Thread(target=w.serve_forever, daemon=True).start()
    cl = Cluster([("127.0.0.1", w.port) for w in workers],
                 replicas={0: 1, 1: 0}, rpc_timeout_s=15.0,
                 connect_timeout_s=5.0)
    cl.broadcast_exec("create table ct (k bigint, grp bigint, v bigint)")
    half = n_rows // 2
    ks = np.arange(n_rows, dtype=np.int64)
    cl.load_partition(0, "ct", arrays={
        "k": ks[:half], "grp": ks[:half] % 7, "v": ks[:half] * 3}, db="test")
    cl.load_partition(1, "ct", arrays={
        "k": ks[half:], "grp": ks[half:] % 7, "v": ks[half:] * 3}, db="test")
    return workers, cl


QUERY = "select grp, count(*) as n, sum(v) as s from ct group by grp order by grp"


def _last_dcn_trace():
    """Newest kept trace rooted at dcn.query — head sampling on some
    other session's statement must not misdirect the assertions."""
    for tr in reversed(tracing.STORE.traces()):
        if tr.spans and tr.spans[0].name == "dcn.query":
            return tr
    raise AssertionError(
        f"no dcn.query trace kept; store: {tracing.STORE.list(10)}")


class TestDistributedTracing:
    def test_stalled_worker_trace_assembles_end_to_end(self):
        """The acceptance scenario: sampling at 0%, one worker's partial
        deliberately stalled then failed -> the query is slow AND takes
        the failover path -> the kept trace's assembled tree holds
        coordinator dispatch spans, the stalled worker's server-side
        spans, and the retry/failover span — asserted through /trace
        and information_schema.cluster_trace."""
        from tidb_tpu.server.status import StatusServer

        workers, cl = _mk_cluster()
        session = Session()
        session.execute("set tidb_trace_sample_rate = 0")

        def stall_then_fail():
            time.sleep(0.35)
            raise ConnectionError("injected stall")

        try:
            with failpoint("dcn.worker.partial", action=stall_then_fail,
                           nth=1):
                got = cl.query(QUERY, session=session)
            assert len(got) == 7
            tr = _last_dcn_trace()
            assert tr.sampled is False
            assert "failover" in tr.keep_reasons
            names = [s.name for s in tr.spans]
            assert "dcn.dispatch[w0]" in names and "dcn.dispatch[w1]" in names
            # nth=1 fires on whichever worker's partial lands first, so
            # the failover direction varies run to run
            assert any(n.startswith("dcn.failover[") for n in names), names
            worker_spans = [s for s in tr.spans
                            if s.name.startswith("worker.") and s.proc]
            assert worker_spans, names
            # the stalled attempt's server-side span shows the stall
            stalled = [s for s in worker_spans if s.dur_us >= 300_000]
            assert stalled, [(s.name, s.dur_us) for s in worker_spans]
            # rpc spans carry per-call byte counts
            rpc_notes = [n for s in tr.spans if s.name.startswith("dcn.rpc")
                         for n in s.notes]
            assert any(n.startswith("recv_bytes=") for n in rpc_notes)

            # surface 1: /trace endpoint
            srv = StatusServer(session.catalog.base, port=0)
            srv.start()
            try:
                base = f"http://127.0.0.1:{srv.port}"
                listing = json.loads(
                    urllib.request.urlopen(base + "/trace").read())
                ids = [t["trace_id"] for t in listing["traces"]]
                assert tr.trace_id in ids
                full = json.loads(urllib.request.urlopen(
                    base + f"/trace?id={tr.trace_id}").read())
                assert full["keep"] and "failover" in full["keep"]

                def walk(nodes):
                    for n in nodes:
                        yield n
                        yield from walk(n["children"])

                flat = list(walk(full["tree"]))
                assert any(n["name"].startswith("dcn.dispatch")
                           for n in flat)
                assert any(n["name"].startswith("worker.") and n["proc"]
                           for n in flat)
                assert any("failover" in n["name"] for n in flat)
            finally:
                srv.stop()

            # surface 2: information_schema.cluster_trace
            rows = session.query(
                "select name, proc from information_schema.cluster_trace"
                f" where trace_id = '{tr.trace_id}'")
            names_sql = [r[0] for r in rows]
            assert any(n.startswith("dcn.dispatch") for n in names_sql)
            assert any(n.startswith("worker.") for n in names_sql)
            assert any("failover" in n for n in names_sql)
            assert any(r[1] not in ("", "local") for r in rows)  # remote proc

            # surface 3: exemplars — the worst recent DCN rpc links to a
            # trace id in the Prometheus exposition
            ex = M.DCN_RPC_SECONDS.exemplar(cmd="partial_paged")
            assert ex is not None and "-" in ex[1]
            text = M.render_prometheus()
            assert re.search(
                r'tidb_tpu_dcn_rpc_seconds_bucket\{.*le="\+Inf"\} \d+ '
                r'# \{trace_id="[^"]+",kept="[01]"\}', text)
        finally:
            cl.shutdown()

    def test_uneventful_query_discarded_and_worker_stats_table(self):
        """An uneventful distributed query's trace is recorded but NOT
        kept (sampling 0, no tail rule), and the dcn_worker_stats I_S
        table exposes the fleet counters from SQL (satellite)."""
        workers, cl = _mk_cluster(n_rows=100)
        session = _quiet(Session())
        try:
            before = {t.trace_id for t in tracing.STORE.traces()}
            got = cl.query(QUERY, session=session)
            assert len(got) == 7
            after = {t.trace_id for t in tracing.STORE.traces()}
            assert after <= before  # nothing new kept
            rows = session.query(
                "select worker, endpoint, state, executed, error from"
                " information_schema.dcn_worker_stats")
            ours = [r for r in rows if r[1] in
                    {f"127.0.0.1:{w.port}" for w in workers}]
            assert len(ours) == 2
            for _w, _ep, state, executed, err in ours:
                assert state == "up" and err == "" and executed >= 1
        finally:
            cl.shutdown()

    def test_cancel_observation_spans(self):
        """A deadline expiry fans cancels out; the workers' cancel
        observations come back as grafted spans under dcn.cancel."""
        workers, cl = _mk_cluster(n_rows=100)
        session = Session()
        session.execute("set tidb_trace_sample_rate = 0")
        try:
            with failpoint("dcn.worker.partial",
                           action=lambda: time.sleep(0.6)):
                with pytest.raises(QueryTimeoutError):
                    cl.query(QUERY, session=session, timeout_s=0.15)
            tr = _last_dcn_trace()
            assert "error:QueryTimeoutError" in tr.keep_reasons
            names = [s.name for s in tr.spans]
            assert "dcn.cancel" in names
            cancel_obs = [n for s in tr.spans if s.proc
                          for n in s.notes if n.startswith("cancel:")]
            assert cancel_obs, names
        finally:
            cl.shutdown()


# -- the served path: one request, one trace (ISSUE 25) ----------------------


@pytest.fixture
def served():
    """A wire server over a small table, two warmed clients; no head
    sampling, so what the kept store holds is kept by a tail rule."""
    from tidb_tpu.server.client import Client
    from tidb_tpu.server.server import Server
    from tidb_tpu.storage.catalog import Catalog

    cat = Catalog()
    cat.global_vars["tidb_trace_sample_rate"] = 0.0
    boot = Session(catalog=cat)
    boot.execute("create table rt (a bigint primary key, b bigint)")
    boot.execute("insert into rt values (1, 2), (3, 4), (5, 6)")
    srv = Server(catalog=cat, port=0)
    srv.start()
    clients = [Client(srv.host, srv.port, db="test") for _ in range(2)]
    try:
        for c in clients:
            c.query(SERVED_SQL)
        yield srv, clients
    finally:
        for c in clients:
            c.close()
        srv.stop()


SERVED_SQL = "select a, sum(b) from rt group by a order by a"


def _dispatches():
    return sum(v for _labels, v in M.DISPATCH_TOTAL.samples())


def _served_trace(fn, fails=None):
    """Run `fn` (one statement of one client; `fails`: the error it has
    to relay); its request's trace, and the client's own clock around it."""
    t0 = time.perf_counter()
    if fails is None:
        fn()
    else:
        with pytest.raises(Exception, match=fails):
            fn()
    t1 = time.perf_counter()
    # the client has its last packet before the connection thread has
    # closed the root: give that thread its microseconds
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        for tr in reversed(tracing.STORE.finished()):
            start, _end = tr.interval_perf()
            if tr.root().name == "wire.stmt" and t0 <= start <= t1:
                return tr, t0, t1
        time.sleep(0.001)
    raise AssertionError("no wire.stmt trace finished in the interval")


def _children(tr, span):
    return [s for s in tr.spans if s.parent_id == span.span_id]


class TestRequestTrace:
    def test_one_trace_from_the_packet_to_the_last_write(self, served):
        _srv, (c, _) = served
        d0 = _dispatches()
        tr, t0, t1 = _served_trace(lambda: c.query(SERVED_SQL))
        root = tr.root()
        assert root.name == "wire.stmt"
        assert [s.name for s in _children(tr, root)] == [
            "sched.queue", "sched.lock_wait", "session.parse",
            "stmt.select", "wire.write"]
        stmt = next(s for s in tr.spans if s.name == "stmt.select")
        assert [s.name for s in _children(tr, stmt)] == [
            "session.plan", "session.execute"]
        execute = _children(tr, stmt)[1]
        under = {s.span_id for s in tr.spans if s.span_id == execute.span_id}
        for s in tr.spans:  # spans are recorded parents first
            if s.parent_id in under:
                under.add(s.span_id)
        waits = [s for s in tr.spans if s.name == "device.wait"]
        assert waits and all(s.span_id in under for s in waits)
        # the trace covers the request: the client's clock around the
        # statement reads what the root span reads, to the loopback's
        # hops (the root opens after the read, and closes after the
        # client has its last packet)
        assert all(s.dur_us >= 0 for s in tr.spans)
        assert abs((t1 - t0) * 1e3 - root.dur_us / 1e3) < 10.0
        # every round trip is counted and spanned by the same line
        launches = [s for s in tr.spans if s.name.startswith("dispatch.")]
        assert len(launches) == _dispatches() - d0 > 0
        # nothing of the request is in two spans or in none
        self_us = tr.self_us()
        assert abs(sum(self_us.values()) - root.dur_us) <= len(tr.spans)
        assert sum(tr.self_us_by_name().values()) == sum(self_us.values())
        assert tracing.STORE.get(tr.trace_id) is None  # uneventful: not kept

    def test_the_lock_wait_is_the_other_statements_remaining_run(self, served):
        """Two connections: the first holds the catalog lock inside its
        commit, the second's statement is claimed by a free worker at
        once (no queue) and parks on the lock until the first is let go."""
        _srv, (c1, c2) = served
        reached, gate = threading.Event(), threading.Event()

        def hold():
            reached.set()
            assert gate.wait(10)

        with failpoint("2pc.before_prewrite", action=hold, times=1):
            first = threading.Thread(
                target=c1.query, args=("insert into rt values (7, 8)",))
            first.start()
            assert reached.wait(10)
            out = {}
            second = threading.Thread(target=lambda: out.update(
                zip(("tr", "t0", "t1"),
                    _served_trace(lambda: c2.query(SERVED_SQL)))))
            second.start()
            time.sleep(0.25)  # the second is parked; the first still holds
            released = time.perf_counter()
            gate.set()
            first.join(10)
            second.join(10)
        assert not first.is_alive() and not second.is_alive()
        tr = out["tr"]
        by_name = {s.name: s for s in tr.spans}
        held_ms = (released - out["t0"]) * 1e3
        wait_ms = by_name["sched.lock_wait"].dur_us / 1e3
        assert held_ms >= 250
        assert by_name["sched.queue"].dur_us / 1e3 < wait_ms / 4
        # the wait is in the request's time, as the client felt it, and
        # before the statement's own span
        assert held_ms - 100 < wait_ms < tr.root().dur_us / 1e3
        assert (by_name["stmt.select"].start_us
                >= by_name["sched.lock_wait"].start_us
                + by_name["sched.lock_wait"].dur_us)

    def test_an_errored_statement_closes_its_trace_on_both_threads(self, served):
        _srv, (c, _) = served
        tr, _t0, _t1 = _served_trace(
            lambda: c.query("select * from missing_rt"), fails="missing_rt")
        assert all(s.dur_us >= 0 and s.ann is None for s in tr.spans)
        assert tr.keep_reasons == ["error:SchemaError"]
        assert tracing.STORE.get(tr.trace_id) is tr  # the error tail rule
        assert "wire.write" in [s.name for s in tr.spans]  # the error packet
        # both threads are clean: the next statement's trace is its own
        nxt, _t0, _t1 = _served_trace(lambda: c.query(SERVED_SQL))
        assert nxt is not tr and nxt.root().name == "wire.stmt"
        assert not nxt.keep_reasons

    def test_a_killed_statement_closes_its_trace(self, served):
        srv, (c1, c2) = served
        victim = min(srv.sessions)  # c1's connection: the first opened
        reached, gate = threading.Event(), threading.Event()

        def hold():
            reached.set()
            assert gate.wait(10)

        out = []

        def run():  # killed inside its commit or not: the kill's business
            try:
                out.append(_served_trace(
                    lambda: c1.query("insert into rt values (9, 10)")))
            except Exception:  # noqa: BLE001 — relayed to the client
                pass

        with failpoint("2pc.before_prewrite", action=hold, times=1):
            t = threading.Thread(target=run)
            t.start()
            assert reached.wait(10)
            srv.sessions[victim]._killed = True  # KILL CONNECTION's flag
            gate.set()
            t.join(10)
        assert not t.is_alive()
        for tr, _t0, _t1 in out:  # whatever its end, finished and closed
            assert all(s.dur_us >= 0 and s.ann is None for s in tr.spans)
        tr, _t0, _t1 = _served_trace(lambda: c1.query(SERVED_SQL),
                                     fails="killed")
        assert "error:QueryKilledError" in tr.keep_reasons
        assert all(s.dur_us >= 0 and s.ann is None for s in tr.spans)
        nxt, _t0, _t1 = _served_trace(lambda: c2.query(SERVED_SQL))
        assert not nxt.keep_reasons

    def test_slow_is_judged_on_the_requests_whole_time(self, served):
        """The tail rules run where the root closes, with the session's
        threshold: a statement quick under the lock but long in the
        request (here: everything is slow) is kept as the client felt it."""
        _srv, (c, _) = served
        c.query("set tidb_slow_log_threshold = 0")
        tr, _t0, _t1 = _served_trace(lambda: c.query(SERVED_SQL))
        c.query("set tidb_slow_log_threshold = 300")
        assert tr.keep_reasons == ["slow"]
        assert tracing.STORE.get(tr.trace_id) is tr

    def test_session_without_a_server_still_owns_its_trace(self):
        s = _quiet(Session())
        s.execute("create table own (a bigint)")
        before = {id(t) for t in tracing.STORE.finished()}
        s.query("select count(*) from own")
        tr = tracing.STORE.finished()[-1]  # finished, though not kept
        assert id(tr) not in before and not tr.kept
        assert tr.root().name == "stmt.select"
        assert "session.parse" not in [s.name for s in tr.spans]
        assert tracing.current() is None

    def test_spans_are_on_the_profilers_clock(self, served, tmp_path):
        """With a profiler session open, the host plane of the xplane
        holds the request's spans as ``tidb.*`` events, the root with
        its trace_id, nested as the program recorded them."""
        import jax
        from jax.profiler import ProfileData

        _srv, (c, _) = served
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            tr, _t0, _t1 = _served_trace(lambda: c.query(SERVED_SQL))
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        events = {}
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("tidb."):
                        events.setdefault(e.name, []).append(
                            (e.start_ns, e.duration_ns, {k: v for k, v in e.stats}))
        for name in ("tidb.wire.stmt", "tidb.sched.lock_wait",
                     "tidb.session.parse", "tidb.session.plan",
                     "tidb.session.execute", "tidb.device.wait",
                     "tidb.wire.write"):
            assert name in events, sorted(events)
        assert "tidb.sched.queue" not in events  # crosses threads: host clock only
        (r0, rd, stats), = events["tidb.wire.stmt"]
        assert stats.get("trace_id") == tr.trace_id
        assert abs(rd / 1e3 - tr.root().dur_us) < 2_000
        for start, dur, _ in events["tidb.device.wait"]:
            assert r0 <= start and start + dur <= r0 + rd


# -- inside one span: phases and counts (ISSUE 37) ---------------------------


class _Clock:
    """``time`` for the tracer, moved by the test alone: what a phase
    reads of the clock cannot shift what a span reads. In ticks of 1/64
    s, T microseconds, exact in binary: no reading is rounded."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now

    def tick(self, n):
        self.now += n / 64

    time, strftime, localtime = (staticmethod(time.time), staticmethod(time.strftime),
                                 staticmethod(time.localtime))


def _scripted_tree(parts: bool):
    """One tree on the test's clock, in ticks: root [0, 40) with a child
    [4, 16) and, in the root's own time, two phases and a count —
    recorded only with `parts`."""
    import contextlib

    phase = tracing.phase if parts else (lambda _n: contextlib.nullcontext())
    add = tracing.add if parts else (lambda _n, _v: None)
    clock = _Clock()
    real, tracing.time = tracing.time, clock
    try:
        tr = tracing.Trace("t-parts")
        tracing.push(tr)
        with tracing.span("root"):
            clock.tick(4)
            with tracing.span("child"):
                with phase("inner"):
                    clock.tick(12)
                add("n", 7)
            with phase("a"):
                clock.tick(6)
                add("rows", 5)
            with phase("b"):
                clock.tick(2)
            with phase("a"):
                clock.tick(1)
                add("rows", 6)
            clock.tick(15)
        tracing.pop()
    finally:
        tracing.time = real
    return tr


T = 15625  # a tick of _Clock, microseconds


class TestSpanParts:
    def test_a_phase_or_a_count_is_no_span_and_moves_no_self_time(self):
        bare, parted = _scripted_tree(False), _scripted_tree(True)
        assert [(s.span_id, s.parent_id, s.name, s.start_us, s.dur_us)
                for s in parted.spans] == [
            (s.span_id, s.parent_id, s.name, s.start_us, s.dur_us)
            for s in bare.spans] == [(1, None, "root", 0, 40 * T),
                                     (2, 1, "child", 4 * T, 12 * T)]
        assert parted.self_us() == bare.self_us() == {1: 28 * T, 2: 12 * T}
        assert parted.self_us_by_name() == bare.self_us_by_name()
        assert parted.duration_ms() == bare.duration_ms() == 625.0
        assert bare.phases_us() == {} and bare.counts() == {}
        assert all(s.phases is None and s.counts is None for s in bare.spans)

    def test_phases_and_counts_accumulate_on_the_innermost_open_span(self):
        tr = _scripted_tree(True)
        root, child = tr.spans
        assert root.phases == {"a": [7 * T, 2], "b": [2 * T, 1]}
        assert root.counts == {"rows": 11}
        assert child.phases == {"inner": [12 * T, 1]} and child.counts == {"n": 7}
        assert tr.phases_us() == {"root/a": [7 * T, 2], "root/b": [2 * T, 1],
                                  "child/inner": [12 * T, 1]}
        assert tr.counts() == {"root/rows": 11, "child/n": 7}
        for s in tr.spans:  # the parts of a span lie inside it
            assert sum(us for us, _ in s.phases.values()) <= s.dur_us

    def test_same_named_spans_sum_in_the_read_side(self):
        tr = tracing.Trace("t-sum")
        tracing.push(tr)
        for n in (3, 4):
            with tracing.span("device.wait"):
                with tracing.phase("copy"):
                    pass
                tracing.add("bytes", n)
        tracing.pop()
        assert tr.counts() == {"device.wait/bytes": 7}
        assert tr.phases_us()["device.wait/copy"][1] == 2
        assert len(tr.spans) == 2

    def test_to_dict_and_the_rows_of_a_span_show_them(self):
        d = _scripted_tree(True).to_dict()
        (root,) = d["tree"]
        assert root["phases"] == {"a": [7 * T, 2], "b": [2 * T, 1]}
        assert root["counts"] == {"rows": 11}
        (child,) = root["children"]
        assert child["phases"] == {"inner": [12 * T, 1]} and child["counts"] == {"n": 7}
        bare = _scripted_tree(False).to_dict()["tree"][0]
        assert bare["phases"] == {} and bare["counts"] == {}
        assert bare["self_us"] == root["self_us"] == 28 * T
        json.dumps(d)  # /trace?id= serves it as it is
        tr = _scripted_tree(True)
        assert tr.spans[0].parts() == [f"phase:a={7 * T}us/2", f"phase:b={2 * T}us/1",
                                       "count:rows=11"]
        assert _scripted_tree(False).spans[0].parts() == []

    def test_they_do_not_cross_processes(self):
        """``export`` ships a worker's spans without their parts (PERF.md
        says so): the wire form is what it was."""
        tr = _scripted_tree(True)
        assert [sorted(r) for r in tr.export()] == [["a", "d", "i", "n", "p", "s"]] * 2
        coord = tracing.Trace("t-coord")
        rpc = coord.begin("dcn.rpc")
        coord.graft(tr.export(), rpc, proc="w")
        assert coord.phases_us() == {} and coord.counts() == {}

    def test_the_off_paths_do_nothing_and_raise_nothing(self):
        assert tracing.current() is None
        with tracing.phase("no-trace"):
            tracing.add("n", 1)
        tr = tracing.Trace("t-off", max_spans=1)
        tracing.push(tr)
        try:
            with tracing.phase("no-open-span"):
                tracing.add("n", 1)
            assert tr.spans == [] and tr.phases_us() == {} and tr.counts() == {}
            with tracing.span("kept"):
                with tracing.span("over-budget") as dropped:
                    with tracing.phase("on-the-sentinel"):
                        tracing.add("n", 1)
                    assert dropped is tracing._DROPPED
                tracing.add("n", 2)  # the innermost open span again
        finally:
            tracing.pop()
        assert tracing._DROPPED.phases is None and tracing._DROPPED.counts is None
        assert tr.dropped == 1 and tr.counts() == {"kept/n": 2}
        assert tr.phases_us() == {}

    def test_a_phase_left_by_an_exception_is_booked_and_does_not_swallow_it(self):
        tr = tracing.Trace("t-exc")
        tracing.push(tr)
        try:
            with pytest.raises(ValueError):
                with tracing.span("s"):
                    with tracing.phase("p"):
                        raise ValueError("x")
        finally:
            tracing.pop()
        assert tr.spans[0].phases["p"][1] == 1 and tr.spans[0].dur_us >= 0


PLAN_PHASES = ["cache", "bind", "rules", "lower", "privs", "build"]


def _plan_phases(tr):
    return {k.partition("/")[2]: v for k, v in tr.phases_us().items()
            if k.startswith("session.plan/")}


class TestProgramPhases:
    def test_session_plan_names_its_six_phases_inside_its_duration(self, served):
        _srv, (c, _) = served
        tr, _t0, _t1 = _served_trace(lambda: c.query(SERVED_SQL))
        plan = next(s for s in tr.spans if s.name == "session.plan")
        assert list(plan.phases) == PLAN_PHASES
        assert all(calls == 1 for _us, calls in plan.phases.values())
        assert sum(us for us, _ in plan.phases.values()) <= plan.dur_us
        # phases are no children: session.plan has none, its self time is
        # its duration, and the statement's spans are what they were
        assert _children(tr, plan) == []
        assert tr.self_us()[plan.span_id] == plan.dur_us
        stmt = next(s for s in tr.spans if s.name == "stmt.select")
        assert [s.name for s in _children(tr, stmt)] == [
            "session.plan", "session.execute"]

    def test_live_rows_books_the_rows_it_read_on_the_callers_span(self, served):
        srv, (c, _) = served
        table = srv.catalog.table("test", "rt")
        calls = []
        real = type(table).live_rows

        def spy(self):
            calls.append(self.n)
            return real.fget(self)

        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(type(table), "live_rows", property(spy))
            tr, _t0, _t1 = _served_trace(lambda: c.query(SERVED_SQL))
        assert calls and set(calls) == {table.n}
        assert tr.counts() == {"session.plan/rows_counted": sum(calls),
                               "device.wait/bytes": tr.counts()["device.wait/bytes"]}
        # outside any trace the count is the off path
        assert tracing.current() is None
        assert table.live_rows == 3

    def test_device_wait_is_ready_then_copy_and_its_bytes(self, served):
        _srv, (c, _) = served

        def d2h():
            return sum(v for lbl, v in M.XFER_BYTES.samples() if lbl.get("dir") == "d2h")

        b0 = d2h()
        tr, _t0, _t1 = _served_trace(lambda: c.query(SERVED_SQL))
        waits = [s for s in tr.spans if s.name == "device.wait"]
        assert waits
        for s in waits:
            assert list(s.phases) == ["ready", "copy"]
            assert [calls for _us, calls in s.phases.values()] == [1, 1]
            assert s.phases["ready"][0] + s.phases["copy"][0] <= s.dur_us
            assert s.counts["bytes"] > 0
            assert _children(tr, s) == []
        assert tr.counts()["device.wait/bytes"] == d2h() - b0 > 0

    def test_trace_records_what_a_select_records_and_prints_the_phases(self, served):
        _srv, (c, _) = served
        sel, _t0, _t1 = _served_trace(lambda: c.query(SERVED_SQL))
        out = {}
        tr, _t0, _t1 = _served_trace(
            lambda: out.update(rows=c.query("trace " + SERVED_SQL)[1]))
        stmt = next(s for s in tr.spans if s.name == "stmt.trace")
        live = [s.name for s in _children(tr, stmt) if not s.name.startswith("executor.")]
        assert live == ["session.plan", "session.execute"]
        assert "session.build_executor" not in [s.name for s in tr.spans]
        assert list(_plan_phases(tr)) == list(_plan_phases(sel)) == PLAN_PHASES
        assert tr.counts()["session.plan/rows_counted"] \
            == sel.counts()["session.plan/rows_counted"]
        names = [str(r[0]) for r in out["rows"]]
        at = names.index("session.plan")
        assert names[at + 1:at + 7] == ["  session.plan/" + p for p in PLAN_PHASES]
        assert any(n.strip() == "device.wait/copy" for n in names)
        by_name = {n.strip(): r for n, r in zip(names, out["rows"])}
        plan_ms = float(by_name["session.plan"][2])
        assert sum(float(by_name["session.plan/" + p][2]) for p in PLAN_PHASES) \
            <= plan_ms + 0.006  # six roundings to the microsecond

    def test_a_replan_shows_as_two_calls(self, monkeypatch):
        from tidb_tpu.session import session as session_mod

        s = _quiet(Session())
        s.execute("create table rp (a bigint, b bigint)")
        s.execute("insert into rp values (1, 2), (3, 4)")
        once = iter([True])
        monkeypatch.setattr(s, "_dist_expected", lambda: True)
        monkeypatch.setattr(session_mod, "_has_eager_partial", lambda _p: True)
        monkeypatch.setattr(session_mod, "_dist_engaged",
                            lambda _r: not next(once, False))
        assert s.query("select a, sum(b) from rp group by a order by a") == [(1, 2), (3, 4)]
        got = _plan_phases(tracing.STORE.finished()[-1])
        assert {p: calls for p, (_us, calls) in got.items()} == {
            "cache": 2, "bind": 2, "rules": 2, "lower": 2, "privs": 1, "build": 2}

    def test_cluster_trace_shows_a_spans_parts(self):
        s = Session()
        s.execute("set tidb_trace_sample_rate = 1")
        s.execute("set tidb_slow_log_threshold = 300000")
        s.execute("create table ctp (a bigint)")
        s.execute("insert into ctp values (1), (2)")
        s.query("select count(*) from ctp")
        tid = tracing.STORE.traces()[-1].trace_id
        (notes,) = [r[0] for r in s.query(
            "select annotations from information_schema.cluster_trace"
            f" where trace_id = '{tid}' and name = 'session.plan'")]
        assert re.search(r"phase:bind=\d+us/1", notes)
        assert "count:rows_counted=" in notes

    def test_phases_are_on_the_profilers_clock_inside_their_span(self, served, tmp_path):
        import jax
        from jax.profiler import ProfileData

        _srv, (c, _) = served
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _served_trace(lambda: c.query(SERVED_SQL))
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        events = {}
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("tidb."):
                        events.setdefault(e.name, []).append((e.start_ns, e.duration_ns))
        (p0, pd), = events["tidb.session.plan"]
        for phase in PLAN_PHASES:
            (start, dur), = events["tidb.session.plan/" + phase]
            assert p0 <= start and start + dur <= p0 + pd
        assert len(events["tidb.device.wait/ready"]) == len(events["tidb.device.wait"])
        assert len(events["tidb.device.wait/copy"]) == len(events["tidb.device.wait"])


def test_a_table_upload_is_a_span_with_its_bytes(devices8):
    """``shard_table`` is the span ``stage.upload``; its count ``bytes``
    is what went to ``device_put`` and what ``XFER_BYTES{h2d}`` gained;
    the site counts stay as they were: two a column and one."""
    from tidb_tpu.parallel import make_mesh

    def h2d():
        return sum(v for lbl, v in M.XFER_BYTES.samples() if lbl.get("dir") == "h2d")

    def staged():
        return sum(v for lbl, v in M.DISPATCH_TOTAL.samples()
                   if lbl.get("site") == "stage")

    s = _quiet(Session(mesh=make_mesh(devices=devices8[:4])))
    s.execute("set tidb_device_engine_mode = 'force'")
    s.execute("create table up (a bigint, b bigint)")
    s.execute("insert into up values (1, 2), (3, 4), (5, 6)")
    b0, n0 = h2d(), staged()
    assert s.query("select sum(b) from up") == [(12,)]
    first = tracing.STORE.finished()[-1]
    (up,) = [sp for sp in first.spans if sp.name == "stage.upload"]
    st = next(iter(s._shard_cache.resident()))[1]
    arrays = list(st.data.values()) + list(st.valid.values()) + [st.sel]
    assert up.counts == {"bytes": sum(a.nbytes for a in arrays)}
    assert h2d() - b0 >= up.counts["bytes"] > 0
    assert staged() - n0 == len(arrays) == 5
    assert up.dur_us >= 0 and up.phases is None
    assert s.query("select sum(b) from up") == [(12,)]  # resident: no upload
    assert "stage.upload" not in [sp.name for sp in tracing.STORE.finished()[-1].spans]
