#!/usr/bin/env python3
"""One cell of the benchmark, one process:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Assembles the server in this process on the cell's chips, generates
TPC-H from ``--seed``, warms every statement of the cell's traffic on
every connection (all of it ``setup_s``), drives the closed-loop window
over the MySQL wire — the query streams and, where the traffic names one,
a writer committing beside them — then, the window closed, memory read,
the server stopped, computes the numpy reference and compares every
answer the clients received with what the commits it could have seen
leave. The last line of stdout is the result; see README.md.
Without a TPU, or with another number of chips than the cell asks for,
it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.time()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import bisect  # noqa: E402
import gc  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmarks import peaks as peaks_table  # noqa: E402
from benchmarks import (program_spans, reference, spec, system,  # noqa: E402
                        tpch_datagen, trace_reduce, traffic, work)


class NoChip(Exception):
    """The measuring entry found no TPU, or not the cell's chips."""


def info(**kw) -> None:
    kw.setdefault("t", round(time.time() - T0, 2))
    print(json.dumps(kw, default=str), flush=True)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def _stream(client, order, menu_sql, deadline_ns, records, stream_id):
    """Closed loop: the next statement goes out when the last returned,
    until the deadline has passed."""
    i = 0
    while time.perf_counter_ns() < deadline_ns:
        item = order[i % len(order)]
        i += 1
        rec = {"stream": stream_id, "item": item, "rows": None, "error": None}
        rec["t_send"] = time.perf_counter_ns()
        try:
            _names, rec["rows"] = client.query(menu_sql[item])
        except Exception as e:  # noqa: BLE001 — a failed statement is a result
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        rec["t_done"] = time.perf_counter_ns()
        records.append(rec)
        if rec["error"]:
            return


def run_transaction(client, sqls: list, k: int) -> dict:
    """Transaction `k`, statement by statement; the record holds when
    its first packet went out, when its last one (the COMMIT) did, and
    when that one's OK came back: the acknowledgement."""
    rec = {"k": k, "t_send": None, "t_commit_send": None, "t_ack": None,
           "error": None, "stmts": []}
    try:
        for i, sql in enumerate(sqls):
            t = time.perf_counter_ns()
            if i == 0:
                rec["t_send"] = t
            if i == len(sqls) - 1:
                rec["t_commit_send"] = t
            client.query(sql)
            rec["stmts"].append((t, time.perf_counter_ns()))
        rec["t_ack"] = rec["stmts"][-1][1]
    except Exception as e:  # noqa: BLE001 — a failed write is a result
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    return rec


def _writer(client, transaction, first_k, deadline_ns, writes):
    """Closed loop of its own: transaction k goes out when k-1 was
    acknowledged, until the deadline has passed (the one in flight is
    finished). An error ends the stream."""
    k = first_k
    while time.perf_counter_ns() < deadline_ns:
        sqls = transaction(k)
        writes.append(run_transaction(client, sqls, k))
        if writes[-1]["error"]:
            return
        k += 1


def _trace_span(trace_dir: str, lead_s: float, span_s: float, out: dict) -> None:
    """One profiler trace over a steady part of the window, with two
    marks that tie the host's clock to the trace's."""
    import jax

    time.sleep(lead_s)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.sync_begin"):
            out["h0"] = time.perf_counter_ns()
        time.sleep(span_s)
        with jax.profiler.TraceAnnotation("bench.sync_end"):
            out["h1"] = time.perf_counter_ns()
    finally:
        jax.profiler.stop_trace()


class GcWatch:
    """Times the interpreter's garbage collections (a pause of every
    thread of this process, the server's included): information for the
    window's stdout line, so that a stalled run can be told from a slow one."""

    def __init__(self):
        self.pauses, self._t = [], 0

    def __call__(self, phase, details):
        if phase == "start":
            self._t = time.perf_counter_ns()
        else:
            self.pauses.append((details.get("generation"), self._t,
                                time.perf_counter_ns() - self._t))

    def summary(self, t0_ns) -> dict:
        return {"collections": len(self.pauses),
                "pause_s": round(sum(p[2] for p in self.pauses) / 1e9, 4),
                "longest": [[g, round((t - t0_ns) / 1e9, 2), round(d / 1e6, 1)]
                            for g, t, d in sorted(
                                self.pauses, key=lambda p: -p[2])[:3]]}


class Heartbeat(threading.Thread):
    """Sleeps 100 ms at a time and notes how late it woke: a pause of
    the whole process (the interpreter lock held, the host's cores taken
    away) shows here, a wait for the device does not. Information for
    the window's stdout line, as GcWatch."""

    PERIOD_S = 0.1

    def __init__(self):
        super().__init__(name="bench-heartbeat", daemon=True)
        self.late, self._halt = [], threading.Event()

    def run(self):
        t = time.perf_counter_ns()
        while not self._halt.wait(self.PERIOD_S):
            now = time.perf_counter_ns()
            over = (now - t) / 1e9 - self.PERIOD_S
            if over > self.PERIOD_S:
                self.late.append((t, over))
            t = now

    def summary(self, t0_ns) -> dict:
        self._halt.set()
        self.join()
        return {"late_wakes": len(self.late),
                "late_s": round(sum(o for _t, o in self.late), 3),
                "longest": [[round((t - t0_ns) / 1e9, 2), round(o, 3)]
                            for t, o in sorted(self.late, key=lambda x: -x[1])[:3]]}


def process_cpu_s() -> float:
    """CPU seconds this process has used: a window in which the host took
    the cores away reads fewer of them (the chip machine's /proc/stat
    stands still, so the host's own counters say nothing)."""
    t = os.times()
    return t.user + t.system


def drive_window(clients, orders, menu_sql, seconds, trace_dir=None,
                 trace_seconds=0.0, writer=None) -> tuple:
    """Runs every stream to the deadline; returns (records, writes,
    marks). `writer`: (client, k -> the transaction's SQL, first k)."""
    records, writes, marks = [], [], {}
    watch = GcWatch()
    gc.callbacks.append(watch)
    beat = Heartbeat()
    beat.start()
    cpu0 = process_cpu_s()
    start = time.perf_counter_ns()
    deadline = start + int(seconds * 1e9)
    threads = [threading.Thread(
        target=_stream, name=f"bench-stream-{s}",
        args=(c, orders[s], menu_sql, deadline, records, s))
        for s, c in enumerate(clients)]
    if writer:
        threads.append(threading.Thread(
            target=_writer, name="bench-writer",
            args=(*writer, deadline, writes)))
    marks["first_send"] = time.time()
    for t in threads:
        t.start()
    if trace_dir:
        span = min(float(trace_seconds), max(1.0, seconds * 0.6))
        _trace_span(trace_dir, min(2.0, seconds * 0.1), span, marks)
    for t in threads:
        t.join()
    gc.callbacks.remove(watch)
    marks["gc"] = watch.summary(start)
    marks["heartbeat"] = beat.summary(start)
    marks["process_cpu_s"] = round(process_cpu_s() - cpu0, 2)
    marks["start_ns"] = start
    return sorted(records, key=lambda r: r["t_send"]), writes, marks


# ---------------------------------------------------------------------------
# after the window: reference, comparison, metrics
# ---------------------------------------------------------------------------

def versions(cell, data, scale, seed, lowp=None) -> tuple:
    """(the reference at every k, the mix's writer or None): with a
    writer, the rows its transactions carry come from its statement's
    ``source``, for the SQL and for the reference alike."""
    w = next(iter(traffic.writers(cell.traffic)), None)
    write = cell.statements[w["statement"]] if w else None
    return reference.Versions(
        data, cell.traffic["menu"], cell.statements, write,
        write and write.source(scale, seed, w["params"]), lowp), w


def check_answers(cell, ver, records, written=None) -> dict:
    """Every statement of the window against the reference `ver`. Marks
    each record ``ok``; returns the numbers compared, each beside its limit.

    `written`, in a cell with a writer: ``{"first_k", "writes",
    "read_back"}``. A statement sent after `k_lo` transactions were
    acknowledged (set-up's included) and answered after `k_hi` COMMITs
    had been sent is right if it equals the reference after some k of
    them, k_lo <= k <= k_hi (``r["k"]``); one that equals the reference
    at a k below k_lo has not read an acknowledged commit (stale); one
    that equals none has read a transaction in part, or something else."""
    first_k = written["first_k"] if written else 0
    writes = written["writes"] if written else []
    acks = sorted(w["t_ack"] for w in writes if w["t_ack"] is not None)
    commits = sorted(w["t_commit_send"] for w in writes
                     if w["t_commit_send"] is not None)
    worst_gap, bad_cells, cells, missing, wrong, stale = 0.0, 0, 0, 0, 0, 0
    for r in records:
        if r["error"] is not None:
            r["ok"] = False
            missing += 1
            continue
        k_lo = k_hi = first_k
        if writes:
            k_lo += bisect.bisect_left(acks, r["t_send"])
            k_hi += bisect.bisect_left(commits, r["t_done"])
        for k in range(k_hi, k_lo - 1, -1):  # ends at k_lo: the one counted
            c = reference.compare_rows(r["rows"], ver.answer(r["item"], k))
            if reference.answer_ok(c):
                break
        r["ok"], r["k"] = reference.answer_ok(c), k
        wrong += not r["ok"]
        cells += c["cells"]
        if not r["ok"] and any(reference.answer_ok(reference.compare_rows(
                r["rows"], ver.answer(r["item"], j))) for j in range(k_lo)):
            stale += 1
            continue
        bad_cells += c["exact_mismatches"]
        worst_gap = max(worst_gap, c["float_rel_gap"])
    checks = {
        "exact_mismatches": {"value": bad_cells, "limit": 0},
        "float_rel_gap": {"value": worst_gap,
                          "limit": reference.FLOAT_REL_LIMIT},
        "missing_answers": {"value": missing, "limit": 0},
        "wrong_statements": {"value": wrong, "limit": 0},
    }
    compared = {"statements": len(records) - missing, "cells": cells}
    if written:
        by_k = {}
        for r in records:
            if r["ok"]:
                by_k[r["k"]] = by_k.get(r["k"], 0) + 1
        k_end = first_k + len(acks)
        checks.update({
            "stale_answers": {"value": stale, "limit": 0},
            "failed_writes": {"value": sum(
                1 for w in writes if w["error"]), "limit": 0},
            "unread_acknowledged_rows": {"value": unread_rows(
                ver, written["read_back"], k_end), "limit": 0},
            # a window the writer never got into, or whose answers all
            # predate its first commit, measures nothing
            "unwritten_window": {"value": int(not acks), "limit": 0},
            "unseen_writes": {"value": int(not any(k > first_k for k in by_k)),
                              "limit": 0},
        })
        compared.update(transactions=len(acks), first_k=first_k,
                        answers_by_k={k: by_k[k] for k in sorted(by_k)})
    checks["compared"] = compared
    return checks


def unread_rows(ver, read_back: dict, k: int) -> int:
    """What a new connection read after the window — COUNT(*) and one
    column's SUM of every table written — against what the first `k`
    transactions, all acknowledged, leave: the rows short or over, and
    at least 1 where the count agrees and the sum does not."""
    unread = 0
    for table, (column, scale) in ver.write.READ_BACK.items():
        n_want = ver.rows(table, k)
        want = [(n_want, reference.Exact(ver.total(table, column, k), scale))]
        got = read_back.get(table)
        try:
            unread += max(abs(n_want - int(got[0][0])), reference.compare_rows(
                got, want)["exact_mismatches"] > 0)
        except (TypeError, ValueError, IndexError):  # nothing readable came back
            unread += n_want
    return unread


class Context:
    """What a per-layer metric's reader is given."""

    def __init__(self, cell, device, peaks, shapes, records, window_s,
                 setup_counters, window_counters, trace, marks, writes=()):
        self.cell, self.device, self.peaks, self.shapes = cell, device, peaks, shapes
        # the query streams' statements, and the writer's transactions
        self.records, self.writes, self.window_s = records, writes, window_s
        self.warm = marks.get("warm", ())  # set-up's query statements: (t0, t1)
        self.window_statements = sum(1 for r in records if r.get("ok"))
        self.setup_counters, self.window_counters = setup_counters, window_counters
        self.trace = trace
        self._fractions = self._traced_fractions(marks) if trace else {}
        self.traced_statements = sum(self._fractions.values())
        self.roofline_bounds = {}

    def _traced_fractions(self, marks) -> dict:
        """Per menu item, statements inside the traced span; one partly
        inside counts by the share of its time that is inside."""
        out = {}
        for r in self.records:
            inside = min(r["t_done"], marks["h1"]) - max(r["t_send"], marks["h0"])
            if inside > 0 and r["t_done"] > r["t_send"]:
                out[r["item"]] = (out.get(r["item"], 0.0)
                                  + inside / (r["t_done"] - r["t_send"]))
        return out

    def roofline_pct(self, roofline: str):
        """Least seconds for the traced statements that count under
        `roofline`, over the device-op seconds of the span (all ops)."""
        if not self.trace or self.trace["op_ns_mean"] <= 0:
            return None
        least = 0.0
        for item, frac in self._fractions.items():
            mod = self.cell.statements[self.cell.traffic["menu"][item]["statement"]]
            if getattr(mod, "ROOFLINE", None) != roofline:
                continue
            s, bound = work.least_seconds(
                mod.COLUMNS, self.shapes, self.peaks, self.device["count"],
                exchanged=len(mod.TABLES) > 1)
            self.roofline_bounds[roofline] = bound
            least += frac * s
        if least <= 0:
            return None
        return 100.0 * least / (self.trace["op_ns_mean"] / 1e9)


def end_to_end_values(cell, ver, records, writes, window_s, setup_s) -> dict:
    """Rates and latencies are the query streams'; the writer has its own:
    the median time from a transaction's BEGIN to its COMMIT's OK."""
    ok = [r for r in records if r.get("ok")]
    lat = [(r["t_done"] - r["t_send"]) / 1e6 for r in ok]
    rows = 0
    for r in ok:  # the rows the statement addressed: the table as it read it
        mod = cell.statements[cell.traffic["menu"][r["item"]]["statement"]]
        rows += sum(ver.rows(t, r["k"]) for t in mod.TABLES)
    out = {"setup_s": setup_s}
    if lat:
        out["rows_per_s"] = rows / window_s
        out["stmt_p50_ms"] = float(np.percentile(lat, 50))
        out["stmt_p95_ms"] = float(np.percentile(lat, 95))
    acked = [(w["t_ack"] - w["t_send"]) / 1e6 for w in writes
             if w["t_ack"] is not None]
    if acked:
        out["refresh_p50_ms"] = float(np.percentile(acked, 50))
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def read_back(server, timeout, pre_sql, columns: dict, clients: list) -> dict:
    """On a new connection: COUNT(*) and the SUM of one column of every
    table written; ``{table: rows}``, the error's text where one fails."""
    out = {}
    clients.append(system.connect(server, timeout, pre_sql))
    for table, (column, _scale) in columns.items():
        try:
            _names, out[table] = clients[-1].query(
                f"select count(*), sum({column}) from {table}")
        except Exception as e:  # noqa: BLE001 — a failed read-back is a result
            out[table] = f"{type(e).__name__}: {e}"[:300]
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, sf=None, pre_sql=(),
             keep_trace=None) -> dict:
    """The whole run; returns the result line's object. `require_chip`,
    `sf` and `pre_sql` exist for the CPU rehearsal in tests/bench: the
    command line cannot set them."""
    device, devs = system.device()
    if require_chip:
        if device["platform"] != "tpu":
            raise NoChip(f"jax found no TPU (platform {device['platform']!r})")
        if device["count"] != cell.chips:
            raise NoChip(f"{cell.name} asks for {cell.chips} chip(s), "
                         f"jax has {device['count']}")
        peaks = peaks_table.peaks(device["kind"])
    else:
        peaks = next(iter(peaks_table.PEAKS.values()))  # counts only
    counters = system.Counters()
    c_start = counters.read()

    t = time.time()
    scale = float(cell.config["scale_factor"] if sf is None else sf)
    tables = tpch_datagen.generate(scale, seed)
    data = reference.Data(tables)
    info(phase="generate", seconds=round(time.time() - t, 2), scale_factor=scale,
         rows={n: data.rows(n) for n in tables})
    t = time.time()
    server = system.start_server(tables, tpch_datagen.PRIMARY_KEYS,
                                 cell.config.get("cluster_by", {}))
    info(phase="load", seconds=round(time.time() - t, 2), device=device,
         mesh=str(dict(server.mesh.shape)))

    menu = cell.traffic["menu"]
    menu_sql = [cell.statements[m["statement"]].sql(m["params"]) for m in menu]
    orders = traffic.stream_orders(cell.traffic, seed)
    ver, w = versions(cell, data, scale, seed)
    writer = None
    written = {"first_k": int(w.get("warm_transactions", 0))} if w else None
    clients = []
    trace_dir = os.path.join(cell.root, ".bench_trace", cell.name) if trace else None
    try:
        timeout = float(cell.traffic.get("statement_timeout_s", 300))
        for s in range(len(orders)):
            clients.append(system.connect(server, timeout, pre_sql))
        if written:
            # the writer first, so that the queries are warmed at the
            # version the window starts from
            clients.append(system.connect(server, timeout, pre_sql))

            def transaction(k):
                return ver.write.transaction(ver.refresh, k, w["params"])

            writer = (clients[-1], transaction, written["first_k"])
            for k in range(written["first_k"]):
                rec = run_transaction(clients[-1], transaction(k), k)
                if rec["error"]:
                    raise RuntimeError(f"warm transaction {k}: {rec['error']}")
                info(phase="warm_write", k=k,
                     seconds=round((rec["t_ack"] - rec["t_send"]) / 1e9, 3),
                     rows={tab: ver.rows(tab, k + 1) - ver.rows(tab, k)
                           for tab in ver.write.TABLES})
        warm = []  # when each warm statement ran, alone: program_spans
        for p in range(int(cell.traffic.get("warm_passes", 1))):
            for s, c in enumerate(clients[:len(orders)]):
                for i, sql in enumerate(menu_sql):
                    t = time.perf_counter_ns()
                    c.query(sql)
                    warm.append((t, time.perf_counter_ns()))
                    info(phase="warm", stream=s, item=i, warm_pass=p,
                         seconds=round((warm[-1][1] - t) / 1e9, 3))
        shapes = {tab: next(iter(by_conn.values()))
                  for tab, by_conn in system.table_shapes(server).items()}
        c_setup = counters.read()
        info(phase="warmed", memory=system.memory(devs),
             counters={k: round(v - c_start.get(k, 0), 3)
                       for k, v in c_setup.items()},
             resident={tab: {"bytes": s["bytes"], "rows_per_part":
                             s["rows_per_part"], "n_parts": s["n_parts"]}
                       for tab, s in shapes.items()})
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        records, writes, marks = drive_window(
            clients[:len(orders)], orders, menu_sql, seconds, trace_dir,
            float(cell.traffic.get("trace_seconds", 4)), writer)
        marks["warm"] = warm
        c_window = counters.read()
        mem = system.memory(devs)
        if written:
            # what the acknowledged transactions left, read on a new
            # connection while the server still stands
            written.update(writes=writes, read_back=read_back(
                server, timeout, pre_sql, ver.write.READ_BACK, clients))
    finally:
        for c in clients:
            try:
                c.close()
            except OSError:
                pass
        server.stop()
    setup_s = marks["first_send"] - T0
    window_s = (max(r["t_done"] for r in records)
                - min(r["t_send"] for r in records)) / 1e9

    # the window is closed, memory is read, the server is stopped: now
    # the reference (host numpy; none of it is in setup_s)
    t = time.time()
    checks = check_answers(cell, ver, records, written)
    reference_s = time.time() - t
    failed = (sum(1 for r in records if not r["ok"])
              + sum(1 for w in writes if w["error"]))
    correct = bool(records) and failed == 0 and all(
        v["value"] <= v["limit"] for k, v in checks.items() if "limit" in v)
    for r in records:
        if not r["ok"]:
            info(phase="failed_statement", item=r["item"], error=r["error"],
                 got=str(r["rows"])[:300])
            break

    peaks_b = [m["peak_bytes_in_use"] for m in mem if m["peak_bytes_in_use"]]
    dev_out = {"platform": device["platform"], "kind": device["kind"],
               "count": device["count"],
               "memory_peak_bytes": max(peaks_b) if peaks_b else 0}
    breakdown = None
    info(phase="window", seconds=window_s, reference_s=round(reference_s, 2),
         statements=len(records), memory=mem, gc=marks["gc"],
         heartbeat=marks["heartbeat"], process_cpu_s=marks["process_cpu_s"],
         # [stream, item, seconds into the window, ms]: where a stall sits
         slowest=[[r["stream"], r["item"],
                   round((r["t_send"] - marks["start_ns"]) / 1e9, 2),
                   round((r["t_done"] - r["t_send"]) / 1e6, 1)]
                  for r in sorted(records, key=lambda r: r["t_send"] - r["t_done"])[:6]],
         # every statement's ms, in the order sent: a slow start or a slow
         # stretch shows here, a slow machine shows in all of them
         latencies_ms=[round((r["t_done"] - r["t_send"]) / 1e6, 1) for r in records],
         by_item={i: sum(1 for r in records if r["item"] == i)
                  for i in range(len(menu))},
         # [k, ms from BEGIN to the COMMIT's OK, ms of that in the COMMIT]
         writes=[[w["k"], round((w["t_ack"] - w["t_send"]) / 1e6, 1),
                  round((w["t_ack"] - w["t_commit_send"]) / 1e6, 1)]
                 for w in writes if w["t_ack"] is not None],
         counters={k: round(v - c_setup.get(k, 0), 3)
                   for k, v in c_window.items() if v - c_setup.get(k, 0)})
    units = {m["name"]: m["unit"]
             for m in cell.bench["end_to_end"] + cell.bench["per_layer"]}
    if not trace:
        values = end_to_end_values(cell, ver, records, writes, window_s, setup_s)
        names = [m["name"] for m in cell.end_to_end()]
    else:
        reduced = None
        t = time.time()
        plain = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
        sync = {e[0]: e[1] for e in trace_reduce.host_spans(plain, "bench.sync")}
        if "bench.sync_begin" in sync and "bench.sync_end" in sync:
            # the statements on the trace's clock, through the two marks
            # (an annotation begun before the trace started is not in it)
            shift = sync["bench.sync_begin"] - marks["h0"]
            spans = [[f"bench.query:{menu[r['item']]['statement']}#{r['item']}",
                      r["t_send"] + shift, r["t_done"] - r["t_send"]]
                     for r in records]
            spans += [[f"bench.write:{ver.write.__name__.rpartition('.')[2]}#{w['k']}",
                       w["t_send"] + shift, w["stmts"][-1][1] - w["t_send"]]
                      for w in writes if w["stmts"]]
            reduced = trace_reduce.reduce_trace(
                plain, sync["bench.sync_begin"], sync["bench.sync_end"], spans)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            with gzip.open(os.path.join(
                    keep_trace, f"{cell.name}.{seed}.trace.json.gz"), "wt") as f:
                json.dump(plain, f)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = Context(cell, device, peaks, shapes, records, window_s,
                      {k: v - c_start.get(k, 0) for k, v in c_setup.items()},
                      {k: v - c_setup.get(k, 0) for k, v in c_window.items()},
                      reduced, marks, writes)
        values, names = {}, []
        for m in cell.per_layer():
            v = cell.reader(m["name"])(ctx)
            if v is not None:
                values[m["name"]] = float(v)
                names.append(m["name"])
        if reduced:
            if reduced["devices"]:  # a CPU rehearsal's trace has no device plane
                dev_out["busy_s"] = reduced["busy_ns_mean"] / 1e9
                dev_out["window_s"] = reduced["span_ns"] / 1e9
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
            info(phase="trace", read_s=round(time.time() - t, 2),
                 traced_statements=ctx.traced_statements,
                 spans_ms=program_spans.by_name_ms(ctx),
                 roofline_bounds=ctx.roofline_bounds,
                 idle_by_host_s=reduced["idle_by_host_s"],
                 devices=reduced["devices"])
    result = {"correct": correct, "attempted": len(records) + len(writes),
              "failed": failed,
              "metrics": {n: {"value": values[n], "unit": units[n]}
                          for n in names if n in values},
              "device": dev_out}
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = checks  # the numbers compared come last
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="with --trace 1: also write the trace's plain "
                         "form (json.gz) into DIR, for a look by hand")
    args = ap.parse_args(argv)
    cell = spec.Cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      keep_trace=args.keep_trace)
    line = json.dumps(result)
    print("checks " + json.dumps(result["checks"]), file=sys.stderr, flush=True)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
