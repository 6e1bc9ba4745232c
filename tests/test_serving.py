"""Serving tier (ISSUE 7): admission-controlled statement scheduler +
cross-session micro-batched device dispatch.

Covers the ISSUE's test checklist: N-client correctness under
coalescing (interleaved params vs a serial oracle, per-statement
warnings reset, rowcounts), typed admission rejection / queue-timeout
errors, KILL/deadline of one batch member leaving the batch intact, a
quota-exceeded member not poisoning its batch, deterministic drain on
shutdown, the stmt-summary / trace-store / scheduler_stats / /scheduler
surfaces, and the wire-level tidb_max_connections cap.
"""

import json
import threading
import time
import urllib.request

import pytest

from tidb_tpu.errors import (
    AdmissionRejectedError,
    QueryKilledError,
    QueryTimeoutError,
    SchedulerQueueTimeoutError,
)
from tidb_tpu.serving import StatementScheduler
from tidb_tpu.session import Session
from tidb_tpu.storage.catalog import Catalog
from tidb_tpu.utils import metrics as M
from tidb_tpu.utils.memory import QueryOOMError

POINT = "select c, k from t where id = ?"
N_ROWS = 200


def make_cat(**globals_):
    cat = Catalog()
    boot = Session(catalog=cat)
    boot.execute("set global tidb_slow_log_threshold = 300000")
    boot.execute("set global tidb_trace_sample_rate = 0")
    for k, v in globals_.items():
        boot.execute(f"set global {k} = {v}")
    boot.execute(
        "create table t (id bigint primary key, k bigint, c varchar(32))")
    boot.execute("insert into t values " + ",".join(
        f"({i},{i % 7},'c-{i:05d}')" for i in range(N_ROWS)))
    boot.execute("analyze table t")
    return cat, boot


def run_clients(sched, cat, n_clients, keys_of, submit=None):
    """N client threads each submitting its key list through the
    scheduler; returns (sessions, per-client results, per-client errors)."""
    sessions = [Session(catalog=cat) for _ in range(n_clients)]
    sids = [s.prepare(POINT)[0] for s in sessions]
    sched.submit_prepared(sessions[0], sids[0], [0])  # plan-cache fill
    results = [[] for _ in range(n_clients)]
    errors = [[] for _ in range(n_clients)]
    barrier = threading.Barrier(n_clients)

    def client(ci):
        sess, sid = sessions[ci], sids[ci]
        barrier.wait()
        for key in keys_of(ci):
            try:
                if submit is not None:
                    rs = submit(sess, sid, key)
                else:
                    rs = sched.submit_prepared(sess, sid, [key])
                results[ci].append(rs.rows)
            except Exception as e:  # noqa: BLE001 — asserted by callers
                errors[ci].append(e)

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sessions, results, errors


class TestCoalescingCorrectness:
    def test_n_client_interleaved_exact_vs_serial(self):
        """8 clients x 40 interleaved keys (hits, misses, duplicates)
        through a wide-open gather window: every result byte-identical
        to serial execution, coalescing actually engaged, and
        @@last_plan_from_cache set on every member session."""
        cat, boot = make_cat(tidb_tpu_batch_window_us=100_000,
                             tidb_tpu_max_batch_size=8)
        sched = StatementScheduler(cat, workers=4)
        c0 = M.BATCH_COALESCE_TOTAL.value()

        def keys_of(ci):
            # hits, shared hot keys (dup params in one batch) and misses
            return [(ci * 37 + i * 11) % N_ROWS if i % 5 else 7
                    for i in range(30)] + [N_ROWS + 123, N_ROWS + 456]

        sessions, results, errors = run_clients(sched, cat, 8, keys_of)
        sched.shutdown()
        assert not [e for errs in errors for e in errs]
        oracle = Session(catalog=cat)
        osid, _ = oracle.prepare(POINT)
        for ci in range(8):
            for i, key in enumerate(keys_of(ci)):
                want = oracle.execute_prepared(osid, [key]).rows
                assert repr(results[ci][i]) == repr(want), (ci, i, key)
        # the miss keys really exercised the 0-row member path
        assert results[0][-1] == []
        assert M.BATCH_COALESCE_TOTAL.value() - c0 >= 16
        for s in sessions:
            assert s.query("select @@last_plan_from_cache")[0][0] == 1

    def test_member_statement_resets_warning_area(self):
        """A coalesced member still passes through _execute_timed, so
        the MySQL per-statement warning reset happens exactly as it
        would singleton (stale warnings don't survive the statement)."""
        cat, boot = make_cat(tidb_tpu_batch_window_us=100_000,
                             tidb_tpu_max_batch_size=4)
        sched = StatementScheduler(cat, workers=2)
        sessions = [Session(catalog=cat) for _ in range(4)]
        sids = [s.prepare(POINT)[0] for s in sessions]
        sched.submit_prepared(sessions[0], sids[0], [0])
        for s in sessions:
            s._warnings.append(("Warning", 1235, "stale pre-batch warning"))
        errors = []
        barrier = threading.Barrier(4)

        def client(ci):
            barrier.wait()
            try:
                sched.submit_prepared(sessions[ci], sids[ci], [ci + 1])
            except Exception as e:  # noqa: BLE001 — asserted below
                errors.append(e)

        ts = [threading.Thread(target=client, args=(ci,)) for ci in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(15)
        sched.shutdown()
        assert not errors
        for s in sessions:
            assert s.query("show warnings") == []

    def test_unbatchable_statements_fall_back_singleton(self):
        """Correctness gate: a session in an explicit txn and a
        non-point statement never coalesce — they run full-fidelity
        singleton through the same scheduler and stay correct."""
        cat, boot = make_cat(tidb_tpu_batch_window_us=100_000)
        sched = StatementScheduler(cat, workers=2)
        txn_sess = Session(catalog=cat)
        tsid, _ = txn_sess.prepare(POINT)
        sched.submit_query(txn_sess, "begin")
        assert txn_sess.batch_probe(tsid, [5]) is None
        rs = sched.submit_prepared(txn_sess, tsid, [5])
        assert rs.rows == [("c-00005", 5)]
        sched.submit_query(txn_sess, "commit")
        scan = sched.submit_query(
            txn_sess, "select count(*) from t where k = 3")
        assert scan.rows[0][0] >= 1
        sched.shutdown()


class TestAdmission:
    def _blocked_sched(self, cat, **kw):
        """One worker, parked on the catalog lock the caller holds."""
        return StatementScheduler(cat, workers=1, **kw)

    def test_queue_full_rejected_typed(self):
        cat, boot = make_cat(tidb_tpu_sched_max_queue=1,
                             tidb_tpu_batch_window_us=0)
        sched = self._blocked_sched(cat)
        s1, s2, s3 = (Session(catalog=cat) for _ in range(3))
        box = {}
        with cat.lock:  # the single worker blocks mid-statement
            t1 = threading.Thread(target=lambda: box.update(
                a=sched.submit_query(s1, "select 1")))
            t1.start()
            deadline = time.time() + 5
            while time.time() < deadline:  # wait until s1 is CLAIMED
                if sched.stats_dict()["queue_depth"] == 0:
                    break
                time.sleep(0.002)
            t2 = threading.Thread(target=lambda: box.update(
                b=sched.submit_query(s2, "select 2")))
            t2.start()
            while time.time() < deadline:  # s2 queued (unclaimed)
                if sched.stats_dict()["queue_depth"] == 1:
                    break
                time.sleep(0.002)
            with pytest.raises(AdmissionRejectedError,
                               match="queue is full"):
                sched.submit_query(s3, "select 3")
        t1.join(10)
        t2.join(10)
        assert box["a"].rows == [(1,)] and box["b"].rows == [(2,)]
        assert sched.stats_dict()["rejected"] == 1
        sched.shutdown()

    def test_queue_timeout_typed(self):
        cat, boot = make_cat(tidb_tpu_sched_queue_timeout_ms=120,
                             tidb_tpu_batch_window_us=0)
        sched = self._blocked_sched(cat)
        s1, s2 = Session(catalog=cat), Session(catalog=cat)
        box = {}

        def second():
            try:
                box["b"] = sched.submit_query(s2, "select 2")
            except Exception as e:  # noqa: BLE001 — asserted below
                box["err"] = e

        with cat.lock:
            t1 = threading.Thread(target=lambda: box.update(
                a=sched.submit_query(s1, "select 1")))
            t1.start()
            deadline = time.time() + 5
            while time.time() < deadline:
                if sched.stats_dict()["queue_depth"] == 0:
                    break
                time.sleep(0.002)
            t2 = threading.Thread(target=second)
            t2.start()
            t2.join(10)  # the eviction fires while the worker is stuck
        t1.join(10)
        assert isinstance(box.get("err"), SchedulerQueueTimeoutError)
        assert "safe to retry" in str(box["err"])
        assert box["a"].rows == [(1,)]
        assert sched.stats_dict()["timed_out"] == 1
        sched.shutdown()

    def test_shutdown_drains_then_rejects(self):
        cat, boot = make_cat(tidb_tpu_batch_window_us=0)
        sched = StatementScheduler(cat, workers=2)
        sessions = [Session(catalog=cat) for _ in range(6)]
        results, errors = [], []

        def client(s, i):
            try:
                results.append(sched.submit_query(s, f"select {i}").rows)
            except Exception as e:  # noqa: BLE001 — asserted below
                errors.append(e)

        threads = [threading.Thread(target=client, args=(s, i))
                   for i, s in enumerate(sessions)]
        for t in threads:
            t.start()
        sched.shutdown(drain=True)
        for t in threads:
            t.join(10)
        # drain=True: everything admitted before the drain finished;
        # anything that arrived after it raises typed (never hangs)
        assert len(results) + len(errors) == 6
        for e in errors:
            assert isinstance(e, AdmissionRejectedError)
        for w in sched._workers:
            assert not w.is_alive()
        with pytest.raises(AdmissionRejectedError, match="draining"):
            sched.submit_query(sessions[0], "select 99")

    def test_shutdown_no_drain_rejects_queued_typed(self):
        cat, boot = make_cat(tidb_tpu_batch_window_us=0)
        sched = self._blocked_sched(cat)
        s1, s2 = Session(catalog=cat), Session(catalog=cat)
        box = {}

        def second():
            try:
                box["b"] = sched.submit_query(s2, "select 2")
            except Exception as e:  # noqa: BLE001 — asserted below
                box["err"] = e

        with cat.lock:
            t1 = threading.Thread(target=lambda: box.update(
                a=sched.submit_query(s1, "select 1")))
            t1.start()
            deadline = time.time() + 5
            while time.time() < deadline:
                if sched.stats_dict()["queue_depth"] == 0:
                    break
                time.sleep(0.002)
            t2 = threading.Thread(target=second)
            t2.start()
            while time.time() < deadline:
                if sched.stats_dict()["queue_depth"] == 1:
                    break
                time.sleep(0.002)
            sched.shutdown(drain=False, timeout=0.2)
            t2.join(10)
        t1.join(10)
        assert isinstance(box.get("err"), AdmissionRejectedError)
        assert box["a"].rows == [(1,)]  # claimed work still finishes


class TestMemberIsolation:
    def _gathering_group(self, cat, n_sessions, window_us=400_000,
                         max_size=8):
        boot = Session(catalog=cat)
        boot.execute(f"set global tidb_tpu_batch_window_us = {window_us}")
        boot.execute(f"set global tidb_tpu_max_batch_size = {max_size}")
        sched = StatementScheduler(cat, workers=2)
        sessions = [Session(catalog=cat) for _ in range(n_sessions)]
        sids = [s.prepare(POINT)[0] for s in sessions]
        sched.submit_prepared(sessions[0], sids[0], [0])
        return sched, sessions, sids

    def test_killed_member_leaves_batch_not_aborts_it(self):
        """KILL QUERY lands on a member while its group gathers: that
        member alone raises the typed kill error; its batchmates'
        results are exact."""
        cat, boot = make_cat()
        sched, sessions, sids = self._gathering_group(cat, 3, max_size=3)
        sa, sb, sc = sessions
        # deterministic sequencing: join A and B directly (non-blocking),
        # kill A, then C's join fills the group and seals it
        ma = sched.batcher.try_join(sa, sids[0], [10], None)
        mb = sched.batcher.try_join(sb, sids[1], [11], None)
        assert ma is not None and mb is not None
        boot.execute(f"kill query {sa.conn_id}")
        mc = sched.batcher.try_join(sc, sids[2], [12], None)
        assert mc is not None
        for m in (ma, mb, mc):
            assert m.done.wait(10)
        assert isinstance(ma.exc, QueryKilledError)
        assert mb.exc is None and mb.result.rows == [("c-00011", 4)]
        assert mc.exc is None and mc.result.rows == [("c-00012", 5)]
        # one-shot: the killed session keeps working
        assert sched.submit_prepared(sa, sids[0], [10]).rows == \
            [("c-00010", 3)]
        sched.shutdown()

    def test_deadline_expired_member_leaves_batch(self):
        cat, boot = make_cat()
        sched, sessions, sids = self._gathering_group(cat, 2, max_size=2)
        sa, sb = sessions
        expired = time.monotonic() - 0.01
        ma = sched.batcher.try_join(sa, sids[0], [20], expired)
        mb = sched.batcher.try_join(sb, sids[1], [21], None)
        assert ma is not None and mb is not None
        for m in (ma, mb):
            assert m.done.wait(10)
        assert isinstance(ma.exc, QueryTimeoutError)
        assert "execution time exceeded" in str(ma.exc)
        assert mb.exc is None and mb.result.rows == [("c-00021", 0)]
        sched.shutdown()

    def test_quota_exceeded_member_does_not_poison_batch(self):
        """A member whose session memory quota is absurdly small gets
        the typed OOM; the batch itself and its other member survive."""
        cat, boot = make_cat(tidb_tpu_batch_window_us=200_000,
                             tidb_tpu_max_batch_size=2)
        sched = StatementScheduler(cat, workers=2)
        se, sf = Session(catalog=cat), Session(catalog=cat)
        se.execute("set tidb_tpu_mem_quota_session = 1")
        sids = {id(se): se.prepare(POINT)[0], id(sf): sf.prepare(POINT)[0]}
        warm = Session(catalog=cat)
        wsid, _ = warm.prepare(POINT)
        sched.submit_prepared(warm, wsid, [0])
        box, barrier = {}, threading.Barrier(2)

        def client(sess, tag, key):
            barrier.wait()
            try:
                box[tag] = sched.submit_prepared(
                    sess, sids[id(sess)], [key]).rows
            except Exception as e:  # noqa: BLE001 — asserted below
                box[tag + "_err"] = e

        ts = [threading.Thread(target=client, args=(se, "e", 30)),
              threading.Thread(target=client, args=(sf, "f", 31))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(15)
        sched.shutdown()
        assert isinstance(box.get("e_err"), QueryOOMError)
        assert box.get("f") == [("c-00031", 3)]


class TestObservability:
    def test_summary_traces_info_table_and_endpoint(self):
        """Every admitted statement lands in statements_summary; kept
        traces carry sched.batch[n=N] (and sched.queue) spans; the
        scheduler_stats info table and /scheduler endpoint both render;
        SHOW TABLES never touches a live scheduler."""
        from tidb_tpu.server.status import StatusServer
        from tidb_tpu.utils.tracing import STORE

        cat, boot = make_cat(tidb_tpu_batch_window_us=100_000,
                             tidb_tpu_max_batch_size=4,
                             tidb_trace_sample_rate=1)
        sched = StatementScheduler(cat, workers=2)
        n_before = sum(
            r[2] for r in boot.query(
                "select digest, digest_text, exec_count from"
                " information_schema.statements_summary")
            if "where id = ?" in r[1])
        sessions, results, errors = run_clients(
            sched, cat, 4, lambda ci: [ci + 40, ci + 44])
        assert not [e for errs in errors for e in errs]

        rows = boot.query("select digest, digest_text, exec_count from"
                          " information_schema.statements_summary")
        n_point = sum(r[2] for r in rows if "where id = ?" in r[1])
        assert n_point - n_before == 4 * 2 + 1  # every member + the fill
        batch_spans = [sp for tr in STORE.traces() for sp in tr.spans
                       if sp.name.startswith("sched.batch[n=")]
        assert batch_spans, "no sched.batch span reached the trace store"
        assert any(sp.name != "sched.batch[n=1]" for sp in batch_spans)
        assert any(sp.name == "sched.queue" for tr in STORE.traces()
                   for sp in tr.spans)

        srows = boot.query("select * from information_schema.scheduler_stats")
        summary = [r for r in srows if r[1] == ""]
        assert summary and any(r[5] >= 8 for r in summary)  # admitted
        assert any(r[1] != "" and r[9] >= 2 for r in srows)  # digest rows
        boot.execute("use information_schema")
        try:
            assert ("scheduler_stats",) in boot.query("show tables")
        finally:
            boot.execute("use test")

        srv = StatusServer(cat, port=0)
        srv.start()
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/scheduler", timeout=10).read()
            doc = json.loads(body)
            assert any(d["admitted"] >= 8 for d in doc["schedulers"])
        finally:
            srv.stop()
        sched.shutdown()
        assert sched.stats_dict()["draining"] is True

    def test_admission_metrics_cover_every_outcome(self):
        cat, boot = make_cat(tidb_tpu_batch_window_us=0)
        a0 = M.SCHED_ADMISSION_TOTAL.value(outcome="admitted")
        sched = StatementScheduler(cat, workers=1)
        s = Session(catalog=cat)
        sched.submit_query(s, "select 1")
        assert M.SCHED_ADMISSION_TOTAL.value(outcome="admitted") == a0 + 1
        sched.shutdown()
        r0 = M.SCHED_ADMISSION_TOTAL.value(outcome="rejected")
        with pytest.raises(AdmissionRejectedError):
            sched.submit_query(s, "select 2")
        assert M.SCHED_ADMISSION_TOTAL.value(outcome="rejected") == r0 + 1


class TestWireLevel:
    def test_max_connections_1040_at_handshake(self):
        from tidb_tpu.server import Server
        from tidb_tpu.server.client import Client, ServerError

        srv = Server(port=0)
        srv.start()
        try:
            c1 = Client(port=srv.port)
            c1.execute("set global tidb_max_connections = 1")
            with pytest.raises(ServerError) as ei:
                Client(port=srv.port)
            assert ei.value.code == 1040
            assert "Too many connections" in ei.value.message
            c1.execute("set global tidb_max_connections = 0")
            c2 = Client(port=srv.port)  # uncapped again
            assert c2.ping()
            c2.close()
            c1.close()
        finally:
            srv.shutdown()

    def test_server_shutdown_drains_pool(self):
        from tidb_tpu.server import Server
        from tidb_tpu.server.client import Client

        srv = Server(port=0)
        srv.start()
        c = Client(port=srv.port)
        c.execute("create table wt (a bigint)")
        c.execute("insert into wt values (1), (2)")
        names, rows = c.query("select count(*) from wt")
        assert rows == [("2",)]
        sched = srv.scheduler
        srv.shutdown(drain=True)
        assert sched.stats_dict()["draining"] is True
        for w in sched._workers:
            assert not w.is_alive()
        c.close()

    def test_wire_prepared_coalesces_across_connections(self):
        """Binary-protocol executions from separate TCP connections ride
        the batcher: results stay exact and the coalesce counter moves."""
        from tidb_tpu.server import Server
        from tidb_tpu.server.client import Client

        srv = Server(port=0)
        srv.start()
        try:
            boot = Client(port=srv.port)
            boot.execute("set global tidb_tpu_batch_window_us = 100000")
            boot.execute("set global tidb_tpu_max_batch_size = 4")
            boot.execute("create table wt2 (id bigint primary key,"
                         " v varchar(16))")
            boot.execute("insert into wt2 values " + ",".join(
                f"({i},'v-{i:03d}')" for i in range(50)))
            boot.execute("analyze table wt2")
            clients = [Client(port=srv.port) for _ in range(4)]
            psids = [c.prepare("select v from wt2 where id = ?")[0]
                     for c in clients]
            c0 = M.BATCH_COALESCE_TOTAL.value()
            outs = [[] for _ in clients]
            barrier = threading.Barrier(len(clients))

            def run(ci):
                barrier.wait()
                for i in range(10):
                    outs[ci].append(clients[ci].execute_prepared(
                        psids[ci], [(ci * 13 + i * 7) % 50]))

            ts = [threading.Thread(target=run, args=(ci,))
                  for ci in range(len(clients))]
            for t in ts:
                t.start()
            for t in ts:
                t.join(30)
            for ci in range(len(clients)):
                for i in range(10):
                    key = (ci * 13 + i * 7) % 50
                    assert outs[ci][i][1] == [(f"v-{key:03d}",)]
            assert M.BATCH_COALESCE_TOTAL.value() > c0
            for c in clients:
                c.close()
            boot.close()
        finally:
            srv.shutdown()


class TestBatchedMemberTraces:
    def test_a_coalesced_member_carries_its_share_of_the_groups_waits(self):
        """ISSUE 25: a batched statement's request trace (opened by the
        submitter, carried by the Member to the worker) holds the wait
        for the seal, the group's wait for the catalog lock and the
        group's pass before the member's own turn as spans with true
        starts — nothing of the request is unaccounted."""
        from tidb_tpu.utils.tracing import STORE

        cat, _boot = make_cat(tidb_tpu_batch_window_us=100_000,
                              tidb_tpu_max_batch_size=4)
        sched = StatementScheduler(cat, workers=2)
        t0 = time.perf_counter()
        _sessions, _results, errors = run_clients(
            sched, cat, 4, lambda ci: [ci + 60, ci + 64])
        sched.shutdown()
        assert not [e for errs in errors for e in errs]
        members = [
            tr for tr in STORE.finished()
            if tr.interval_perf()[0] >= t0 and any(
                s.name.startswith("sched.batch[n=")
                and s.name != "sched.batch[n=1]" for s in tr.spans)]
        assert members, "no coalesced member's trace finished"
        for tr in members:
            root = tr.root()
            assert root.name == "sched.stmt"  # no wire server: the scheduler's
            names = [s.name for s in tr.spans if s.parent_id == root.span_id]
            assert names[:3] == ["sched.queue", "sched.lock_wait",
                                 "sched.batch_pass"], names
            assert names[3].startswith("stmt.")
            spans = {s.name: s for s in tr.spans}
            # true starts: each begins where the one before it ended
            q, w, p = (spans[n] for n in names[:3])
            assert abs(q.start_us + q.dur_us - w.start_us) <= 2
            assert abs(w.start_us + w.dur_us - p.start_us) <= 2
            assert abs(sum(tr.self_us().values()) - root.dur_us) \
                <= len(tr.spans)
