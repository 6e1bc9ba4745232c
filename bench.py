#!/usr/bin/env python
"""Benchmark driver: TPC-H throughput on the current JAX backend.

Prints ONE json line. Headline metric is the BASELINE.json Q1 config:
  {"metric": "tpch_q1_rows_per_sec", "value": N, "unit": "rows/sec",
   "vs_baseline": R, "extra": {...}}

`extra` carries the remaining BASELINE.md configs measured this run
(Q6 range-filter, Q18 3-way join+agg, hash-join build+probe GB/s), the
platform used, and per-query sqlite cross-check status.

vs_baseline is measured against an in-process CPU SQL executor (stdlib
sqlite3) running the identical query over the identical data — the
stand-in for the reference's CPU executor, which is unavailable in this
environment (BASELINE.json ships "published": {}; see BASELINE.md).
The north-star target is >=5x the CPU executor on Q1/Q18.

A run that cannot reach its backend, or in which any phase failed
(an ``*_error`` key in `extra`), exits non-zero: the JSON line is still
printed so the failure is diagnosable, but it is never a passing
artifact. BENCH_PLATFORM=cpu is the explicit CPU choice; there is no
fall-back to it.

`extra` also carries the SSB Q3.2 (4-way star join) and TPC-DS Q95
(semi-join) BASELINE configs, plus (ISSUE 18) the fused TopN two-arm
microbench and the full TPC-H 22-query grid with per-query dispatch
counts and fused/classic attribution.

Env knobs: BENCH_SF (default 1.0), BENCH_SF_Q18 (default min(SF, 0.2) —
Q18's group-by cardinality is ~#orders; see extra.q18_sf for the value
used), BENCH_SF_SSB (default min(SF, 0.1)), BENCH_SF_DS (default
min(SF, 0.5)), BENCH_REPS (default 3), BENCH_CHUNK (default 2^20 rows),
BENCH_ORACLE=0 to skip sqlite baselines, BENCH_PLATFORM to name the jax
platform explicitly (e.g. cpu).
"""

import json
import os
import subprocess
import sys
import time

SF = float(os.environ.get("BENCH_SF", "1.0"))
IDLE_WAIT = float(os.environ.get("BENCH_IDLE_WAIT", "300"))
REPS = int(os.environ.get("BENCH_REPS", "3"))
CAP = int(os.environ.get("BENCH_CHUNK", str(1 << 20)))
ORACLE = os.environ.get("BENCH_ORACLE", "1") != "0"
SF_Q18 = float(os.environ.get("BENCH_SF_Q18", str(min(SF, 0.2))))
SF_SSB = float(os.environ.get("BENCH_SF_SSB", str(min(SF, 0.1))))
SF_DS = float(os.environ.get("BENCH_SF_DS", str(min(SF, 0.5))))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def machine_load(sample_s=0.25):
    """Snapshot of everything that could invalidate a measurement:
    1/5/15-min load averages plus any OTHER python/compile process
    CURRENTLY burning >50% of a core — measured as a CPU-time rate over
    a short two-sample window, not cumulative seconds (a long-lived but
    idle daemon must not read as busy). Recorded into the artifact
    before and after each config so a perturbed number is visibly
    perturbed (round-3 lesson: the headline moved -38% with no load
    evidence either way)."""
    snap = {"loadavg": [round(x, 2) for x in os.getloadavg()]}

    def cpu_sample():
        out = {}
        me = os.getpid()
        tck = os.sysconf("SC_CLK_TCK")
        for pid in os.listdir("/proc"):
            if not pid.isdigit() or int(pid) == me:
                continue
            try:
                with open(f"/proc/{pid}/stat") as f:
                    parts = f.read().split()
                cpu_s = (int(parts[13]) + int(parts[14])) / tck
                with open(f"/proc/{pid}/cmdline") as f:
                    cmd = f.read().replace("\x00", " ").strip()
            except (OSError, IndexError, ValueError):
                continue
            if any(k in cmd for k in ("python", "pytest", "cc1plus",
                                      "clang", "ninja", "node")):
                out[pid] = (cpu_s, cmd)
        return out

    try:
        first = cpu_sample()
        time.sleep(sample_s)
        busy = []
        for pid, (c1, cmd) in cpu_sample().items():
            c0 = first.get(pid)
            if c0 is None:
                continue
            rate = (c1 - c0[0]) / sample_s
            if rate > 0.5:
                busy.append(f"pid{pid}:{rate:.1f}cores:{cmd[:60]}")
        snap["busy_procs"] = busy[:8]
    except OSError:
        pass
    return snap


def wait_for_idle(tag=None, extra=None, max_wait=IDLE_WAIT):
    """Block until the machine is measurably idle before a config runs
    (VERDICT r4 weak #1: never record a headline while contended).

    Primary criterion: 1-min loadavg < 0.3. Shortcut: after 90 s, three
    consecutive samples with no OTHER busy process and loadavg < 0.6
    also count as idle (our own just-finished work keeps the decaying
    loadavg above 0.3 for ~a minute with nothing actually running).
    Records what it saw either way; returns True if idle was reached."""
    t0 = time.time()
    calm = 0
    how = "gave_up"
    while True:
        snap = machine_load()
        la1 = snap["loadavg"][0]
        busy = snap.get("busy_procs", [])
        calm = calm + 1 if (not busy and la1 < 0.6) else 0
        waited = time.time() - t0
        if la1 < 0.3:
            how = "loadavg"
            break
        if calm >= 3 and waited >= 90:
            how = "calm"
            break
        if waited > max_wait:
            log(f"# idle-wait gave up after {max_wait}s: loadavg={la1} "
                f"busy={busy[:2]}")
            break
        time.sleep(5)
    idle = how != "gave_up"
    if extra is not None and tag:
        extra[f"{tag}_idle_wait"] = {
            "waited_s": round(time.time() - t0, 1), "idle": idle,
            "criterion": how, "loadavg": snap["loadavg"],
            "busy_procs": busy[:4]}
    return idle


def bench_provenance():
    """Provenance stamped into every bench JSON artifact (ISSUE 16):
    the git revision the numbers were measured at plus the engaged
    feature flags (their default values in this tree — every bench
    session runs with defaults). perf_check warns when a committed
    floor's revision differs from the tree being checked, so a stale
    capture can't silently gate a changed engine."""
    rev = ""
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=5).stdout.strip()
    except Exception:  # noqa: BLE001 — provenance is best-effort
        pass
    flags = {}
    try:
        from tidb_tpu.session.sysvars import SysVarStore

        sv = SysVarStore({})  # defaults only — bench sessions run stock
        for name in ("tidb_enable_tpu_exec", "tidb_device_engine_mode",
                     "tidb_tpu_pipeline_fuse", "tidb_tpu_columnar_enable",
                     "tidb_tpu_plan_feedback", "tidb_tpu_join_probe_mode",
                     "tidb_tpu_stage_encoded",
                     "tidb_tpu_device_buffer_cache_bytes"):
            try:
                flags[name] = sv.get(name)
            except Exception:  # noqa: BLE001 — a renamed flag drops out
                pass
    except Exception:  # noqa: BLE001
        pass
    return {"git_rev": rev, "flags": flags}


def bench_query(s, engine_sql, sqlite_conn, sqlite_sql, rows, reps=REPS,
                ordered=True, extra=None, tag=None):
    """Run engine_sql reps times; cross-check once vs sqlite. Returns
    (rows_per_sec, vs_sqlite, best_s, check). With extra/tag, waits for
    machine idleness and records load snapshots around the measurement
    into the artifact."""
    from tidb_tpu.testutil import rows_equal

    from tidb_tpu.utils import dispatch as _dsp
    from tidb_tpu.utils import metrics as _M

    def engine_dispatches():
        # the ENGINE-reported figure: the process-global dispatch
        # counter the engine itself maintains (rendered on /metrics)
        return int(sum(v for _lbl, v in _M.DISPATCH_TOTAL.samples()))

    if extra is not None and tag:
        wait_for_idle(tag, extra)
        extra[f"{tag}_load_before"] = machine_load()
    t0 = time.perf_counter()
    got = s.query(engine_sql)  # compile + warmup
    warm = time.perf_counter() - t0
    best = float("inf")
    d0 = engine_dispatches()
    d0_local = _dsp.count()
    for _ in range(reps):
        d0 = engine_dispatches()
        d0_local = _dsp.count()
        t0 = time.perf_counter()
        got = s.query(engine_sql)
        best = min(best, time.perf_counter() - t0)
    if extra is not None and tag:
        # device round trips of the last exec (a count, not a speed).
        # Headline figure comes from the engine metric; the bench's own
        # thread-local count stays as a cross-check that fails loudly
        # (the bench is the only engine thread, so they must agree)
        eng = engine_dispatches() - d0
        local = _dsp.count() - d0_local
        extra[f"{tag}_dispatches"] = eng
        if eng != local:
            extra[f"{tag}_dispatch_crosscheck"] = (
                f"MISMATCH: engine metric says {eng}, bench-local "
                f"dispatch count says {local}")
            log(f"# DISPATCH CROSS-CHECK MISMATCH ({tag}): "
                f"engine={eng} local={local}")
    vs, check, cpu_s = 0.0, "skipped", None
    if sqlite_conn is not None:
        cpu_s = float("inf")
        for _ in range(max(1, reps - 1)):
            t0 = time.perf_counter()
            want = sqlite_conn.execute(sqlite_sql).fetchall()
            cpu_s = min(cpu_s, time.perf_counter() - t0)
        ok, msg = rows_equal(got, want, ordered=ordered)
        check = "ok" if ok else f"MISMATCH: {msg}"
        vs = cpu_s / best
    if extra is not None and tag:
        extra[f"{tag}_load_after"] = machine_load()
    log(f"#   warm={warm:.2f}s best={best * 1e3:.1f}ms"
        + (f" sqlite={cpu_s * 1e3:.1f}ms" if cpu_s else "") + f" check={check}")
    return rows / best, vs, best, check


# --- pre-PR3 join baseline block (CPU backend, local engine) ---------------
# Measured on the seed engine immediately before the partitioned device
# join overhaul (ISSUE 3): local session, 50k-row build x 400k-row probe,
# count+sum probe query, best-of-3 warm on an idle machine:
#   warm_best = 0.500 s  ->  join_build_probe_gbps = 0.014
# (the per-query XLA retrace of the probe/expand closures plus the
# host np.argsort build round trip dominated). The ISSUE 3 acceptance
# gate is >= 5x this number with 0 warm recompiles.
JOIN_MICRO_BASELINE_GBPS_CPU = 0.014
# largest (the baseline-block config) FIRST: a prior config's freed
# working set measurably perturbs whoever runs after it, and the
# headline number must not absorb that
JOIN_MICRO_GRID = [(50_000, 400_000), (10_000, 100_000)]


def bench_join_micro(extra=None):
    """Join microbench (ISSUE 3): build-rows x probe-rows grid, cold vs
    warm, on the LOCAL engine (the HashJoinExec the partitioned-join
    overhaul rebuilt). Loud cross-checks: every config's rows must match
    the sqlite oracle exactly (count AND a content hash), and the
    engine-reported JOIN_COMPILE_TOTAL must not move across warm runs —
    a shape key leaking into traced code fails here before it regresses
    a real workload."""
    import numpy as np

    from tidb_tpu.session import Session
    from tidb_tpu.storage.catalog import Catalog
    from tidb_tpu.testutil import mirror_to_sqlite, rows_equal
    from tidb_tpu.utils import metrics as _M

    def compiles():
        return int(sum(v for _, v in _M.JOIN_COMPILE_TOTAL.samples()))

    out = {"configs": [], "baseline_gbps": JOIN_MICRO_BASELINE_GBPS_CPU}
    rng = np.random.default_rng(11)
    for nb, npr in JOIN_MICRO_GRID:
        s = Session(catalog=Catalog(), chunk_capacity=1 << 17)
        s.execute("SET tidb_slow_log_threshold = 300000")
        s.execute("create table b (k bigint, v bigint)")
        s.execute("create table p (k bigint, w bigint)")
        s.catalog.table("test", "b").insert_columns(
            {"k": rng.integers(0, nb, nb), "v": np.arange(nb)})
        s.catalog.table("test", "p").insert_columns(
            {"k": rng.integers(0, nb, npr), "w": np.arange(npr)})
        oracle = mirror_to_sqlite(s.catalog, tables=["b", "p"])
        # timed config: IDENTICAL query to the pre-PR baseline block
        q = ("select count(*) as n, sum(p.w) as sw "
             "from p join b on p.k = b.k")
        # oracle config: adds the build payload so the cross-check also
        # covers build-side gather content, not just match cardinality
        q_check = ("select count(*) as n, sum(p.w) as sw, sum(b.v) as sv "
                   "from p join b on p.k = b.k")
        t0 = time.perf_counter()
        got = s.query(q)
        cold = time.perf_counter() - t0
        s.query(q)  # steady the plan (auto-analyze may land stats once)
        best = float("inf")
        c0 = compiles()
        for _ in range(3):
            t0 = time.perf_counter()
            got = s.query(q)
            best = min(best, time.perf_counter() - t0)
        recompiles = compiles() - c0
        ok, msg = rows_equal(got, oracle.execute(q).fetchall(),
                             ordered=False)
        if ok:
            got = s.query(q_check)
            want = oracle.execute(q_check).fetchall()
            ok, msg = rows_equal(got, want, ordered=False)
        else:
            want = []
        check = "ok" if ok else f"MISMATCH: {msg}"
        # result-hash equality: the whole aggregate tuple, not just the
        # row count, must agree with the oracle
        import hashlib

        def rhash(rows):
            return hashlib.sha256(repr(sorted(map(tuple, rows)))
                                  .encode()).hexdigest()[:16]
        hash_equal = rhash(got) == rhash(want)
        jbytes = npr * 2 * 8 + nb * 2 * 8
        cfg = {
            "build_rows": nb, "probe_rows": npr,
            "cold_s": round(cold, 4), "warm_best_s": round(best, 4),
            "warm_over_cold": round(cold / max(best, 1e-9), 2),
            "gbps": round(jbytes / best / 1e9, 4),
            "warm_recompiles": recompiles,
            "check": check, "hash_equal": hash_equal,
        }
        if recompiles != 0:
            cfg["recompile_crosscheck"] = (
                f"MISMATCH: JOIN_COMPILE_TOTAL moved by {recompiles} "
                "across warm runs (shape key leaked into traced code)")
            log(f"# JOIN RETRACE ({nb}x{npr}): {recompiles} warm recompiles")
        if not ok or not hash_equal:
            log(f"# JOIN ORACLE MISMATCH ({nb}x{npr}): {check}")
        out["configs"].append(cfg)
        log(f"# join {nb}x{npr}: cold={cold:.3f}s warm={best:.3f}s "
            f"gbps={cfg['gbps']} recompiles={recompiles} check={check}")
        # drop this config's working set before the next one measures:
        # a lingering session + sqlite mirror measurably perturbs the
        # following config's numpy paths (page-cache pressure)
        import gc

        oracle.close()
        s = oracle = got = want = None
        gc.collect()
    head = out["configs"][0]  # the baseline-block config (50k x 400k)
    out["gbps"] = head["gbps"]
    out["improvement_vs_baseline"] = round(
        head["gbps"] / JOIN_MICRO_BASELINE_GBPS_CPU, 2)
    return out


def bench_plan_cache(extra):
    """Plan-cache microbench: repeated point-SELECT and prepared-execute
    loops, statements/sec cold (cache off / first-touch) vs warm
    (cache-hit), plus the ENGINE-reported hit rate cross-checked loudly
    against the loop's own accounting (the PR-1 dispatch-cross-check
    pattern: the engine metric is the headline, the bench's local figure
    must agree or the artifact says so)."""
    from tidb_tpu.session import Session
    from tidb_tpu.storage.catalog import Catalog
    from tidb_tpu.utils import metrics as _M

    n_rows, n_iter = 1000, 400
    s = Session(catalog=Catalog())
    s.execute("SET tidb_slow_log_threshold = 300000")
    # an OLTP-realistic row: wide schema, secondary indexes, fresh
    # stats — planning cost reflects real access-path selection, not a
    # two-column toy
    s.execute("CREATE TABLE pcb (id bigint, k bigint,"
              " a bigint, b bigint, c bigint, d bigint, e bigint,"
              " f bigint, primary key (id, k))")
    s.execute("CREATE INDEX pcb_k ON pcb (k)")
    s.execute("CREATE INDEX pcb_ab ON pcb (a, b)")
    s.execute("INSERT INTO pcb VALUES "
              + ",".join(f"({i},{i % 97},{i % 11},{i % 13},{i * 2},"
                         f"{i * 3},{i * 5},{i * 7})" for i in range(n_rows)))
    s.execute("ANALYZE TABLE pcb")
    # sysbench-style composite-key point read: access-path selection
    # works over three indexes, the probe pins both key columns
    point = "select c, d, e, f from pcb where id = %d and k = %d"
    out = {"iters": n_iter}

    def args(i):
        return i % n_rows, (i % n_rows) % 97

    def loop_text(n):
        t0 = time.perf_counter()
        for i in range(n):
            s.query(point % args(i))
        return n / (time.perf_counter() - t0)

    def loop_prepared(sid, n):
        t0 = time.perf_counter()
        for i in range(n):
            s.execute_prepared(sid, list(args(i)))
        return n / (time.perf_counter() - t0)

    # cold: full parse+plan per statement (non-prepared cache is off by
    # default, so this is the engine's pre-cache statement path)
    s.query(point % args(0))  # jit warmup out of band
    out["cold_stmts_per_sec"] = round(loop_text(n_iter), 1)

    # warm prepared: one fill execution, then the loop runs on cache hits
    sid, _ = s.prepare(
        "select c, d, e, f from pcb where id = ? and k = ?")
    s.execute_prepared(sid, list(args(0)))  # fill (miss pays the verify)
    h0 = s.catalog.plan_cache.hits
    m0 = _M.PLAN_CACHE_TOTAL.value(event="hit")
    out["warm_prepared_stmts_per_sec"] = round(loop_prepared(sid, n_iter), 1)
    eng_hits = _M.PLAN_CACHE_TOTAL.value(event="hit") - m0
    local_hits = s.catalog.plan_cache.hits - h0
    out["hit_rate"] = round(eng_hits / n_iter, 4)
    if eng_hits != local_hits:
        out["hit_crosscheck"] = (
            f"MISMATCH: engine metric says {eng_hits}, cache-object "
            f"accounting says {local_hits}")
        log(f"# PLAN-CACHE CROSS-CHECK MISMATCH: metric={eng_hits} "
            f"cache={local_hits}")
    # the summary table must tell the same story per digest
    rows = s.query(
        "select exec_count, plan_cache_hits from"
        " information_schema.statements_summary where digest_text ="
        " 'select c , d , e , f from pcb where id = ? and k = ?'")
    summ_hits = rows[0][1] if rows else -1
    if rows and summ_hits != local_hits:
        out["summary_crosscheck"] = (
            f"MISMATCH: statements_summary says {summ_hits}, cache "
            f"says {local_hits}")
        log(f"# PLAN-CACHE SUMMARY CROSS-CHECK MISMATCH: "
            f"summary={summ_hits} cache={local_hits}")

    # warm non-prepared: text statements through the opt-in cache
    s.execute("SET tidb_enable_non_prepared_plan_cache = 1")
    s.query(point % args(0))  # fill
    out["warm_text_stmts_per_sec"] = round(loop_text(n_iter), 1)
    s.execute("SET tidb_enable_non_prepared_plan_cache = 0")

    out["warm_over_cold"] = round(
        out["warm_prepared_stmts_per_sec"]
        / max(out["cold_stmts_per_sec"], 1e-9), 3)
    log(f"# plan cache: cold={out['cold_stmts_per_sec']}/s warm_prep="
        f"{out['warm_prepared_stmts_per_sec']}/s warm_text="
        f"{out['warm_text_stmts_per_sec']}/s hit_rate={out['hit_rate']}")
    return out


def bench_multichip(extra=None, n_rows=None, reps=None,
                    write_path="MULTICHIP_r06.json"):
    """Sharded scale-out capture (ISSUE 13): the SAME scan-agg query at
    1 -> 2 -> 4 workers over SHARD BY placement, interleaved arms,
    serial-oracle hash equality on every arm.

    Metric semantics on a single-core harness (this box has 1 CPU):
    workers are in-process, so raw wall clock CANNOT scale — what a
    multi-host fleet achieves is the distributed CRITICAL PATH, which
    IS measurable here: each owner's partial is timed individually
    (sequentially, so measurements don't contend), and

        scaleout_s = max(partial_i) + (wall - sum(partial_i))

    i.e. the slowest owner's partial plus the measured coordinator
    overhead (rewrite + drain + final merge) from the real end-to-end
    run. At W=1 that degenerates to the measured wall clock, so
    speedups are self-relative. On a >=4-core box the raw wall-clock
    speedup is reported alongside and should approach the modeled one.
    Every arm's full result must hash-equal the serial oracle's."""
    import hashlib
    import threading as _threading

    import numpy as np

    from tidb_tpu.parallel.dcn import Cluster, Worker, partial_rewrite
    from tidb_tpu.session import Session

    n_rows = n_rows or int(os.environ.get("BENCH_MULTICHIP_ROWS",
                                          str(1 << 20)))
    reps = reps or max(REPS, 3)
    rng = np.random.default_rng(13)
    k = rng.permutation(n_rows).astype(np.int64)
    g = (k % 97).astype(np.int64)
    v = (k * 7 - 3).astype(np.int64)
    ddl = ("create table t (k bigint, g bigint, v bigint) "
           "shard by hash(k) shards 8")
    sql = ("select g, count(*) as n, sum(v) as sv, min(v) as mv, "
           "max(v) as xv from t group by g order by g")

    def rows_hash(rows):
        return hashlib.sha256(
            repr([tuple(int(x) for x in r) for r in rows]).encode()
        ).hexdigest()[:16]

    oracle = Session(chunk_capacity=CAP)
    oracle.execute(ddl)
    oracle.catalog.table("test", "t").insert_columns(
        {"k": k, "g": g, "v": v})
    want_hash = rows_hash(oracle.query(sql))

    fleets = {}
    for W in (1, 2, 4):
        ws = [Worker() for _ in range(W)]
        for w in ws:
            _threading.Thread(target=w.serve_forever, daemon=True).start()
        cl = Cluster([("127.0.0.1", w.port) for w in ws],
                     rpc_timeout_s=600.0)
        cl.ddl(ddl)
        cl.load_sharded("t", arrays={"k": k, "g": g, "v": v})
        fleets[W] = (ws, cl)

    partial_sql, _final, _names = partial_rewrite(
        sql, partitioned=frozenset({"t"}))
    out = {"n_rows": n_rows, "reps": reps, "host_cpus": os.cpu_count(),
           "oracle_hash": want_hash, "arms": {}}
    best = {}  # W -> (scaleout_s, wall_s, max_partial_s)
    try:
        # warm every arm (compile + plan caches) and pin hash equality
        for W, (ws, cl) in fleets.items():
            h = rows_hash(cl.query(sql))
            out["arms"][W] = {"workers": W, "hash_equal": h == want_hash,
                              "hash": h}
        # interleaved measurement: rep-major, arm-minor, so machine
        # drift perturbs every arm equally instead of biasing one
        for _rep in range(reps):
            for W, (ws, cl) in fleets.items():
                t0 = time.perf_counter()
                cl.query(sql)
                wall = time.perf_counter() - t0
                pt = []
                for i in range(W):
                    t0 = time.perf_counter()
                    first = cl._call(i, {"cmd": "partial_paged",
                                         "sql": partial_sql,
                                         "page_rows": 1 << 16})
                    cl._drain_pages(i, first)
                    pt.append(time.perf_counter() - t0)
                scaleout = max(pt) + max(wall - sum(pt), 0.0)
                cur = best.get(W)
                if cur is None or scaleout < cur[0]:
                    best[W] = (scaleout, wall, max(pt))
        for W, (scaleout, wall, mp) in best.items():
            out["arms"][W].update(
                scaleout_s=round(scaleout, 4), wall_s=round(wall, 4),
                max_partial_s=round(mp, 4),
                rows_per_sec_scaleout=round(n_rows / scaleout, 1))
        base = best[1][0]
        out["speedup_2w"] = round(base / best[2][0], 3)
        out["speedup_4w"] = round(base / best[4][0], 3)
        out["wall_speedup_4w"] = round(best[1][1] / best[4][1], 3)
        out["hash_equal"] = all(a["hash_equal"]
                                for a in out["arms"].values())
        out["arms"] = {str(W): a for W, a in out["arms"].items()}
    finally:
        for _W, (_ws, cl) in fleets.items():
            try:
                cl.shutdown()
            except Exception:  # noqa: BLE001 — bench cleanup
                pass
    out["provenance"] = bench_provenance()
    if write_path:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            write_path)
        json.dump(out, open(path, "w"), indent=1)
    if extra is not None:
        extra["multichip"] = {kk: out[kk] for kk in
                              ("speedup_2w", "speedup_4w",
                               "wall_speedup_4w", "hash_equal",
                               "host_cpus")}
    log(f"# multichip: speedup_2w={out.get('speedup_2w')} "
        f"speedup_4w={out.get('speedup_4w')} "
        f"wall_4w={out.get('wall_speedup_4w')} "
        f"hash_equal={out.get('hash_equal')}")
    return out


def bench_elastic(extra=None, n_rows=None, before_s=1.5, after_s=1.5,
                  n_readers=2, n_writers=2):
    """Elastic-topology SLO bench (ISSUE 19): p99 latency + throughput
    dip DURING a live online reshard under sustained mixed traffic.
    Readers (group-agg over the stable keyspace, sqlite-oracle-checked
    on EVERY result) and 2PC point-insert writers run continuously
    against a 3-worker fleet; mid-run the table reshards 12 -> 24
    shards (shard-function change: every shard moves — the worst
    case). Captured: per-phase read p50/p99 (before/during/after the
    reshard), statements served per 1-second window, and the
    throughput dip (served rate during / before). The serving SLO —
    every 1s window serves at least one successful statement, and
    every acked writer row survives the cutover — is what perf_check
    floors; the latency numbers are the operator-facing artifact."""
    import threading as _threading

    import numpy as np

    from tidb_tpu.errors import TiDBTPUError
    from tidb_tpu.parallel.dcn import Cluster, Worker
    from tidb_tpu.session import Session
    from tidb_tpu.testutil import mirror_to_sqlite, rows_equal

    n_rows = n_rows or int(os.environ.get("BENCH_ELASTIC_ROWS",
                                          str(1 << 16)))
    rng = np.random.default_rng(19)
    k = rng.permutation(n_rows).astype(np.int64)
    g = (k % 23).astype(np.int64)
    v = (k * 5 - 7).astype(np.int64)
    ddl = ("create table e (k bigint, g bigint, v bigint) "
           "shard by hash(k) shards 12")
    read_sql = (f"select g, count(*) as n, sum(v) as sv from e "
                f"where k < {n_rows} group by g order by g")

    oracle = Session(chunk_capacity=CAP)
    oracle.execute(ddl)
    oracle.catalog.table("test", "e").insert_columns(
        {"k": k, "g": g, "v": v})
    conn = mirror_to_sqlite(oracle.catalog)
    want = conn.execute(read_sql).fetchall()

    workers = [Worker() for _ in range(3)]
    for w in workers:
        _threading.Thread(target=w.serve_forever, daemon=True).start()
    cl = Cluster([("127.0.0.1", w.port) for w in workers],
                 rpc_timeout_s=600.0)
    cl.ddl(ddl)
    cl.load_sharded("e", arrays={"k": k, "g": g, "v": v})

    stop = _threading.Event()
    lock = _threading.Lock()
    reads = []       # (t_done, dur_s) of oracle-exact reads
    writes = []      # (t_done, dur_s) of acked inserts
    mismatches = []  # correctness violations — must stay empty
    errors = []      # non-transient typed errors — must stay empty
    applied = []     # acked writer sql, replayed into the oracle

    def transient(e):
        # a statement landing inside a 2PC prepare->commit window is
        # refused typed and retried by the client — the documented
        # guard, topology change or not
        return "pending" in str(e)

    def reader():
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                got = cl.query(read_sql)
            except TiDBTPUError as e:
                if not transient(e):
                    with lock:
                        errors.append(repr(e))
                continue
            t1 = time.perf_counter()
            ok, msg = rows_equal(got, want, ordered=True)
            with lock:
                (reads.append((t1, t1 - t0)) if ok
                 else mismatches.append(msg))

    def writer(wid):
        nn = 0
        while not stop.is_set():
            kk = n_rows + wid * 10_000_000 + nn
            nn += 1
            sql = (f"insert into e (k, g, v) values "
                   f"({kk}, {kk % 23}, {kk * 5})")
            t0 = time.perf_counter()
            try:
                cl.execute_dml(sql)
            except TiDBTPUError as e:
                if not transient(e):
                    with lock:
                        errors.append(repr(e))
                continue
            t1 = time.perf_counter()
            with lock:
                writes.append((t1, t1 - t0))
                applied.append(sql)
            time.sleep(0.002)

    threads = ([_threading.Thread(target=reader)
                for _ in range(n_readers)]
               + [_threading.Thread(target=writer, args=(w,))
                  for w in range(n_writers)])
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    try:
        time.sleep(before_s)
        t_r0 = time.perf_counter()
        cl.reshard("alter table e shard by hash(k) shards 24")
        t_r1 = time.perf_counter()
        time.sleep(after_s)
    finally:
        stop.set()
        for t in threads:
            t.join(120)
    t_end = time.perf_counter()
    try:
        # every acked writer row must have survived the cutover: replay
        # the acked multiset into the oracle, compare the WHOLE table
        for sql in applied:
            conn.execute(sql)
        full = "select count(*) as n, sum(v) as sv from e"
        okf, msgf = rows_equal(cl.query(full),
                               conn.execute(full).fetchall())
        new_shards = cl.placement("e").shards
    finally:
        try:
            cl.shutdown()
        except Exception:  # noqa: BLE001 — bench cleanup
            pass
        conn.close()
    check = "ok"
    if errors:
        check = f"TYPED ERRORS ({len(errors)}): {errors[0]}"[:300]
    if mismatches:
        check = f"READ MISMATCH: {mismatches[0]}"[:300]
    if not okf:
        check = f"WRITER ROWS LOST: {msgf}"[:300]
    if new_shards != 24:
        check = f"RESHARD DID NOT LAND: shards={new_shards}"

    stamps = sorted(t for t, _d in reads + writes)
    windows = []
    w0 = t_start
    while w0 < t_end:
        windows.append(sum(1 for t in stamps if w0 <= t < w0 + 1.0))
        w0 += 1.0

    def pctl(durs, q):
        if not durs:
            return None
        ds = sorted(durs)
        return round(ds[min(len(ds) - 1, int(q * len(ds)))] * 1e3, 2)

    phases = {"before": [d for t, d in reads if t < t_r0],
              "during": [d for t, d in reads if t_r0 <= t < t_r1],
              "after": [d for t, d in reads if t >= t_r1]}
    n_before = sum(1 for t in stamps if t < t_r0)
    n_during = sum(1 for t in stamps if t_r0 <= t < t_r1)
    rate_before = n_before / max(t_r0 - t_start, 1e-9)
    rate_during = n_during / max(t_r1 - t_r0, 1e-9)
    out = {
        "n_rows": n_rows, "workers": 3, "shards": "12 -> 24",
        "reshard_s": round(t_r1 - t_r0, 3),
        "wall_s": round(t_end - t_start, 3),
        "stmts_served": len(stamps),
        "reads_ok": len(reads), "writes_acked": len(writes),
        "windows_1s": windows,
        "served_every_window": all(c > 0 for c in windows),
        "read_p50_ms": {p: pctl(d, 0.50) for p, d in phases.items()},
        "read_p99_ms": {p: pctl(d, 0.99) for p, d in phases.items()},
        "rate_before_sps": round(rate_before, 1),
        "rate_during_sps": round(rate_during, 1),
        "throughput_dip": round(rate_during / max(rate_before, 1e-9), 3),
        "check": check,
        "provenance": bench_provenance(),
    }
    log(f"# elastic: reshard={out['reshard_s']}s of {out['wall_s']}s, "
        f"{out['stmts_served']} stmts, dip={out['throughput_dip']} "
        f"p99 before/during/after="
        f"{out['read_p99_ms']['before']}/{out['read_p99_ms']['during']}/"
        f"{out['read_p99_ms']['after']}ms "
        f"served_every_window={out['served_every_window']} "
        f"check={check}")
    if extra is not None:
        extra["elastic"] = {kk: out[kk] for kk in (
            "reshard_s", "served_every_window", "throughput_dip",
            "read_p99_ms", "stmts_served", "check")}
    return out


def bench_oltp(extra, clients_list=(8, 16), iters=150):
    """Multi-client OLTP benchmark (ISSUE 7): sysbench-style point-get
    workload at N client threads through the serving tier, coalesced
    (gather window on) vs unbatched (window=0 — every statement runs
    singleton through the same scheduler), reporting stmts/s, p99,
    engine batch/admission counters, the plan-cache hit rate, and a
    serial-oracle byte-identical cross-check of every statement's
    result. A small update mix rides along (reported, not floored)."""
    import threading

    from tidb_tpu.serving import StatementScheduler
    from tidb_tpu.session import Session
    from tidb_tpu.storage.catalog import Catalog
    from tidb_tpu.utils import metrics as _M

    n_rows = 5000
    cat = Catalog()
    boot = Session(catalog=cat)
    boot.execute("SET GLOBAL tidb_slow_log_threshold = 300000")
    boot.execute("SET GLOBAL tidb_trace_sample_rate = 0")
    boot.execute("CREATE TABLE sbtest (id bigint primary key, k bigint,"
                 " c varchar(64), pad varchar(32))")
    boot.execute("INSERT INTO sbtest VALUES " + ",".join(
        f"({i},{i % 499},'c-{i:010d}-{i * 7 % 997:04d}','pad-{i % 83}')"
        for i in range(n_rows)))
    boot.execute("ANALYZE TABLE sbtest")
    point_tmpl = "select c, pad, k from sbtest where id = ?"

    def key_of(client, i):
        return (client * 7919 + i * 97) % n_rows

    def run_config(n_clients, window_us, collect=None):
        """One (clients, window) config; returns (stmts/s, p99_ms)."""
        boot.execute(f"SET GLOBAL tidb_tpu_batch_window_us = {window_us}")
        sched = StatementScheduler(cat, workers=4)
        sessions = [Session(catalog=cat) for _ in range(n_clients)]
        sids = [s.prepare(point_tmpl)[0] for s in sessions]
        # fill + per-session warm (the miss pays sentinel verification)
        sched.submit_prepared(sessions[0], sids[0], [0])
        lats = [[] for _ in range(n_clients)]
        barrier = threading.Barrier(n_clients + 1)

        def client(ci):
            sess, sid = sessions[ci], sids[ci]
            barrier.wait()
            for i in range(iters):
                t0 = time.perf_counter()
                rs = sched.submit_prepared(sess, sid, [key_of(ci, i)])
                lats[ci].append(time.perf_counter() - t0)
                if collect is not None:
                    collect[ci].append(rs.rows)

        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(n_clients)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        sched.shutdown()
        flat = sorted(x for l in lats for x in l)
        p99 = flat[int(len(flat) * 0.99) - 1] if flat else 0.0
        return n_clients * iters / wall, p99 * 1e3

    out = {"iters": iters, "rows": n_rows, "configs": []}
    for n_clients in clients_list:
        h0 = _M.PLAN_CACHE_TOTAL.value(event="hit")
        cold_rps, cold_p99 = run_config(n_clients, 0)
        bat_collect = [[] for _ in range(n_clients)]
        c0 = _M.BATCH_COALESCE_TOTAL.value()
        hist0 = list(next(
            (c for _l, c, _s, _e in _M.BATCH_SIZE.samples()), [])) or None
        warm_rps, warm_p99 = run_config(n_clients, 1500, collect=bat_collect)
        hits = _M.PLAN_CACHE_TOTAL.value(event="hit") - h0
        total_stmts = 2 * n_clients * iters + 2  # + the two fills
        hist1 = list(next(
            (c for _l, c, _s, _e in _M.BATCH_SIZE.samples()), []))
        hist = (hist1 if hist0 is None
                else [a - b for a, b in zip(hist1, hist0)])
        # oracle: the same statements serially, compared byte-identical
        oracle = Session(catalog=cat)
        osid, _ = oracle.prepare(point_tmpl)
        mismatches = 0
        for ci in range(n_clients):
            for i, got in enumerate(bat_collect[ci]):
                want = oracle.execute_prepared(osid, [key_of(ci, i)]).rows
                if repr(got) != repr(want):
                    mismatches += 1
        cfg = {
            "clients": n_clients,
            "unbatched_stmts_per_sec": round(cold_rps, 1),
            "batched_stmts_per_sec": round(warm_rps, 1),
            "speedup": round(warm_rps / max(cold_rps, 1e-9), 3),
            "p99_ms_unbatched": round(cold_p99, 2),
            "p99_ms_batched": round(warm_p99, 2),
            "coalesced_stmts": _M.BATCH_COALESCE_TOTAL.value() - c0,
            "batch_size_hist": {
                str(b): int(c) for b, c in
                zip(list(_M.BATCH_SIZE.buckets) + ["+Inf"], hist) if c},
            "hit_rate": round(hits / total_stmts, 4),
            "oracle": "ok" if mismatches == 0 else f"{mismatches} MISMATCHES",
        }
        out["configs"].append(cfg)
        log(f"# oltp {n_clients} clients: unbatched={cfg['unbatched_stmts_per_sec']}/s "
            f"batched={cfg['batched_stmts_per_sec']}/s ({cfg['speedup']}x) "
            f"p99 {cfg['p99_ms_unbatched']}->{cfg['p99_ms_batched']}ms "
            f"hit_rate={cfg['hit_rate']} oracle={cfg['oracle']}")
        if mismatches:
            log(f"# OLTP ORACLE MISMATCH at {n_clients} clients")
    # the 90/10 point-get/update mix moved to bench_mixed (ISSUE 17):
    # it is floored now (group-commit DML), so it runs two-armed on a
    # fresh catalog per arm with a serial-oracle state-hash cross-check
    return out


def _mixed_sbtest(n_rows=5000):
    """Fresh sbtest catalog for one mixed-workload arm (identical
    initial state across arms and the serial oracle)."""
    from tidb_tpu.session import Session
    from tidb_tpu.storage.catalog import Catalog

    cat = Catalog()
    boot = Session(catalog=cat)
    boot.execute("SET GLOBAL tidb_slow_log_threshold = 300000")
    boot.execute("SET GLOBAL tidb_trace_sample_rate = 0")
    boot.execute("CREATE TABLE sbtest (id bigint primary key, k bigint,"
                 " c varchar(64), pad varchar(32))")
    boot.execute("INSERT INTO sbtest VALUES " + ",".join(
        f"({i},{i % 499},'c-{i:010d}-{i * 7 % 997:04d}','pad-{i % 83}')"
        for i in range(n_rows)))
    boot.execute("ANALYZE TABLE sbtest")
    return cat, boot


def _sbtest_state_hash(cat):
    """Content hash of sbtest's committed state (order-independent of
    execution interleaving: rows sorted by primary key)."""
    import hashlib

    from tidb_tpu.session import Session

    rows = Session(catalog=cat).query(
        "select id, k, c, pad from sbtest order by id")
    return hashlib.sha256(repr(rows).encode()).hexdigest()[:16]


def bench_mixed(extra=None, n_clients=16, iters=150):
    """Mixed 90/10 point-get/point-update OLTP (ISSUE 17): the write
    path catching the read path. Two arms, each on a FRESH catalog with
    identical initial state: window=0 (every statement singleton
    through the scheduler — the pre-group-commit shape) vs the gather
    window ON (reads coalesce as before; the 10% autocommit updates now
    group-commit through the SAME window into one merged engine pass).
    Every run cross-checks the final table content hash against a
    serial one-session execution of the same statement multiset — the
    per-key updates commute (k = k + 1), so the final state is
    interleaving-invariant and the hash must match exactly."""
    import threading

    from tidb_tpu.serving import StatementScheduler
    from tidb_tpu.session import Session
    from tidb_tpu.utils import metrics as _M

    n_rows = 5000
    point_tmpl = "select c, pad, k from sbtest where id = ?"

    def key_of(client, i):
        return (client * 7919 + i * 97) % n_rows

    def run_arm(window_us):
        cat, boot = _mixed_sbtest(n_rows)
        boot.execute(f"SET GLOBAL tidb_tpu_batch_window_us = {window_us}")
        sched = StatementScheduler(cat, workers=4)
        sessions = [Session(catalog=cat) for _ in range(n_clients)]
        sids = [s.prepare(point_tmpl)[0] for s in sessions]
        sched.submit_prepared(sessions[0], sids[0], [0])
        barrier = threading.Barrier(n_clients + 1)

        def mixed(ci):
            sess, sid = sessions[ci], sids[ci]
            barrier.wait()
            for i in range(iters):
                k = key_of(ci, i)
                if i % 10 == 9:
                    sched.submit_query(
                        sess, f"update sbtest set k = k + 1 where id = {k}")
                else:
                    sched.submit_prepared(sess, sid, [k])

        threads = [threading.Thread(target=mixed, args=(ci,))
                   for ci in range(n_clients)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        adm = sched.stats_dict()
        sched.shutdown()
        return (n_clients * iters / wall, _sbtest_state_hash(cat),
                {k: adm[k] for k in ("admitted", "rejected", "timed_out")})

    hist0 = list(next(
        (c for _l, c, _s, _e in _M.DML_BATCH_SIZE.samples()), [])) or None
    c0 = _M.BATCH_COALESCE_TOTAL.value()
    cold_rps, cold_hash, _ = run_arm(0)
    warm_rps, warm_hash, adm = run_arm(1500)
    hist1 = list(next(
        (c for _l, c, _s, _e in _M.DML_BATCH_SIZE.samples()), []))
    hist = (hist1 if hist0 is None
            else [a - b for a, b in zip(hist1, hist0)])
    # serial oracle: the same statement multiset through ONE session,
    # no scheduler — the state every interleaving must reach
    cat, _boot = _mixed_sbtest(n_rows)
    oracle = Session(catalog=cat)
    for ci in range(n_clients):
        for i in range(iters):
            if i % 10 == 9:
                oracle.execute("update sbtest set k = k + 1 "
                               f"where id = {key_of(ci, i)}")
    want_hash = _sbtest_state_hash(cat)
    ok = cold_hash == want_hash and warm_hash == want_hash
    out = {
        "clients": n_clients,
        "iters": iters,
        "unbatched_stmts_per_sec": round(cold_rps, 1),
        "mixed_90_10_stmts_per_sec": round(warm_rps, 1),
        "group_commit_speedup": round(warm_rps / max(cold_rps, 1e-9), 3),
        "coalesced_stmts": _M.BATCH_COALESCE_TOTAL.value() - c0,
        "dml_batch_hist": {
            str(b): int(c) for b, c in
            zip(list(_M.DML_BATCH_SIZE.buckets) + ["+Inf"], hist) if c},
        "admission": adm,
        "oracle": "ok" if ok else (
            f"STATE HASH MISMATCH want={want_hash} "
            f"unbatched={cold_hash} batched={warm_hash}"),
    }
    log(f"# mixed 90/10 at {n_clients} clients: "
        f"unbatched={out['unbatched_stmts_per_sec']}/s "
        f"group-commit={out['mixed_90_10_stmts_per_sec']}/s "
        f"({out['group_commit_speedup']}x) oracle={out['oracle']}")
    if extra is not None:
        extra["mixed"] = out
    return out


def bench_htap(extra=None, n_clients=8, ingest_iters=160,
               analytics_iters=10, sf=0.05):
    """HTAP bench (ISSUE 17, tentpole c): analytics (TPC-H Q6 + a
    Q18-shape big-join aggregate) running DURING sustained multi-client
    ingest into the same lineitem — group-commit coalesces the insert
    stream, background compaction keeps the scan path from inheriting
    an ever-growing delta inline. Reports OLTP insert throughput,
    analytics p50/p99 under ingest, observed staleness (committed rows
    an analytics snapshot had not yet seen), and the compaction outcome
    counters. Ends with a flag-off equality check: the final Q6 with
    tidb_tpu_compaction=0 must be byte-identical to compaction ON."""
    import threading

    from tidb_tpu.serving import StatementScheduler
    from tidb_tpu.session import Session
    from tidb_tpu.storage.catalog import Catalog
    from tidb_tpu.storage.tpch import load_tpch
    from tidb_tpu.storage.tpch_queries import Q
    from tidb_tpu.utils import metrics as _M

    cat = Catalog()
    boot = Session(catalog=cat)
    boot.execute("SET GLOBAL tidb_slow_log_threshold = 300000")
    boot.execute("SET GLOBAL tidb_trace_sample_rate = 0")
    boot.execute("SET GLOBAL tidb_tpu_batch_window_us = 1500")
    # delta threshold at its floor so the ingest stream actually crosses
    # it mid-run: the fold then happens on the background worker while
    # analytics keeps scanning (the initial segmentation stays inline)
    boot.execute("SET GLOBAL tidb_tpu_segment_delta_rows = 1024")
    counts = load_tpch(cat, sf=sf, native=False)
    base_rows = counts["lineitem"]
    li = cat.table("test", "lineitem")
    ins_cols = list(li.insertable_names())
    q18_shape = (
        "select o_orderkey, sum(l_quantity) as q from lineitem "
        "join orders on l_orderkey = o_orderkey "
        "group by o_orderkey order by q desc, o_orderkey limit 10")

    sched = StatementScheduler(cat, workers=4)
    sessions = [Session(catalog=cat) for _ in range(n_clients)]
    committed = [0]          # rows committed (monotone, under lock)
    commit_lock = threading.Lock()
    barrier = threading.Barrier(n_clients + 2)
    stop = threading.Event()
    key_base = 10_000_000    # ingested l_orderkey = key_base + seq
    seq_src = iter(range(1, 1 << 30))
    seq_lock = threading.Lock()

    def ingest_row(seq):
        vals = []
        for cname in ins_cols:
            if cname == "l_orderkey":
                vals.append(str(key_base + seq))
            elif cname == "l_quantity":
                vals.append(str(1 + seq % 50))
            elif cname == "l_extendedprice":
                vals.append(str(900 + seq % 1000))
            elif cname == "l_discount":
                vals.append(f"0.0{seq % 10}")
            elif cname == "l_shipdate":
                vals.append(f"'1994-0{1 + seq % 6}-15'")
            else:
                from tidb_tpu.types import TypeKind as _TK

                c = li.schema.col(cname)
                if c.type_.is_dict_encoded:
                    vals.append("'x'")
                elif c.type_.kind in (_TK.DATE, _TK.DATETIME):
                    vals.append("'1995-01-01'")
                else:
                    vals.append("0")
        return ("insert into lineitem (" + ", ".join(ins_cols)
                + ") values (" + ", ".join(vals) + ")")

    errs = []

    def oltp(ci):
        sess = sessions[ci]
        barrier.wait()
        for _ in range(ingest_iters):
            with seq_lock:
                seq = next(seq_src)
            try:
                sched.submit_query(sess, ingest_row(seq))
                with commit_lock:
                    committed[0] += 1
            except Exception as e:  # noqa: BLE001 — reported below
                errs.append(f"{type(e).__name__}: {e}"[:200])
        stop.set()  # first finisher ends the analytics loop's tail

    lat, staleness_rows = [], []
    ana_sess = Session(catalog=cat)

    def analytics():
        barrier.wait()
        i = 0
        while True:
            with commit_lock:
                c_before = committed[0]
            sql = Q["q6"][0] if i % 2 == 0 else q18_shape
            t0 = time.perf_counter()
            sched.submit_query(ana_sess, sql)
            lat.append(time.perf_counter() - t0)
            seen = ana_sess.query(
                "select count(*) as n from lineitem "
                f"where l_orderkey >= {key_base}")[0][0]
            staleness_rows.append(max(0, c_before - seen))
            i += 1
            if i >= analytics_iters and stop.is_set():
                break

    cmp0 = {o: _M.COMPACTION_TOTAL.value(outcome=o)
            for o in ("background", "inline", "inline_fallback",
                      "discarded", "failed")}
    dml_hist0 = list(next(
        (c for _l, c, _s, _e in _M.DML_BATCH_SIZE.samples()), [])) or None
    threads = [threading.Thread(target=oltp, args=(ci,))
               for ci in range(n_clients)]
    ana = threading.Thread(target=analytics)
    for t in threads:
        t.start()
    ana.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    oltp_wall = time.perf_counter() - t0
    ana.join()
    ana_wall = time.perf_counter() - t0
    sched.shutdown()
    compaction = {o: _M.COMPACTION_TOTAL.value(outcome=o) - v
                  for o, v in cmp0.items()}
    dml_hist1 = list(next(
        (c for _l, c, _s, _e in _M.DML_BATCH_SIZE.samples()), []))
    dml_hist = (dml_hist1 if dml_hist0 is None
                else [a - b for a, b in zip(dml_hist1, dml_hist0)])
    # flag-off byte-identical: the compaction worker must never have
    # changed WHAT a scan returns, only where the rebuild ran
    chk = Session(catalog=cat)
    chk.execute("SET tidb_tpu_compaction = 0")
    off_rows = chk.query(Q["q6"][0])
    chk.execute("SET tidb_tpu_compaction = 1")
    on_rows = chk.query(Q["q6"][0])
    lats = sorted(lat)
    out = {
        "sf": sf,
        "base_rows": base_rows,
        "ingest_clients": n_clients,
        "ingested_rows": committed[0],
        "ingest_errors": errs[:3],
        "htap_oltp_stmts_per_sec": round(committed[0] / oltp_wall, 1),
        "analytics_queries": len(lat),
        "htap_analytics_qps": round(len(lat) / max(ana_wall, 1e-9), 2),
        "analytics_p50_ms": round(lats[len(lats) // 2] * 1e3, 1),
        "analytics_p99_ms": round(
            lats[max(0, int(len(lats) * 0.99) - 1)] * 1e3, 1),
        "staleness_rows_max": max(staleness_rows) if staleness_rows else 0,
        "compaction": compaction,
        "dml_batch_hist": {
            str(b): int(c) for b, c in
            zip(list(_M.DML_BATCH_SIZE.buckets) + ["+Inf"], dml_hist)
            if c},
        "flag_off_equal": repr(off_rows) == repr(on_rows),
    }
    log(f"# htap: ingest={out['htap_oltp_stmts_per_sec']}/s "
        f"analytics={out['htap_analytics_qps']}/s "
        f"p99={out['analytics_p99_ms']}ms "
        f"staleness<={out['staleness_rows_max']} rows "
        f"compaction={compaction} flag_off_equal={out['flag_off_equal']}")
    if extra is not None:
        extra["htap"] = out
    return out


def bench_pipeline(extra=None, sf=None, reps=None):
    """Fused-pipeline microbench (ISSUE 9): TPC-H Q1 + Q6 on the LOCAL
    single-chip engine — the executor spine the fused
    scan→filter→project→partial-agg path rebuilt. Two arms through the
    SAME session: the pre-PR chunk-synced tree (pipeline_fuse=0: one
    scan dispatch + one agg update + per-chunk staging every chunk) vs
    the fused pipeline (one device program per chunk, double-buffered
    prefetch, device buffer cache — a warm re-run stages nothing).
    Loud cross-checks: arms byte-identical to each other AND to the
    sqlite oracle, warm dispatch counts from the ENGINE counter
    (single-digit per fragment is the acceptance floor)."""
    from tidb_tpu.executor.pipeline import DEVICE_CACHE
    from tidb_tpu.session import Session
    from tidb_tpu.storage.catalog import Catalog
    from tidb_tpu.storage.tpch import load_tpch
    from tidb_tpu.storage.tpch_queries import Q
    from tidb_tpu.testutil import mirror_to_sqlite, rows_equal
    from tidb_tpu.utils import dispatch as _dsp

    sf = min(SF, 0.2) if sf is None else sf
    reps = REPS if reps is None else reps
    # production chunk capacity: the fragment is still genuinely
    # chunked (the 64k-row segment store feeds the unfused arm one
    # chunk per segment — the per-chunk ping-pong being measured —
    # while the fused arm packs k segments per capacity-sized batch,
    # which is where the single-digit dispatch budget comes from)
    s = Session(catalog=Catalog(), chunk_capacity=CAP)
    s.execute("SET tidb_slow_log_threshold = 300000")
    # plan reuse ON: both arms must measure EXECUTION, not re-planning
    s.execute("SET tidb_enable_non_prepared_plan_cache = 1")
    # cluster=False: this bench measures the fusion/overlap win on the
    # staging-bound Q6, so the load stays unsorted — a CLUSTER BY'd
    # lineitem lets zone maps prune ~80% of the staging in BOTH arms
    # and the ratio collapses toward compute parity (the pruning win
    # itself is bench_zone_pruning's floor, via the engine DDL path)
    counts = load_tpch(s.catalog, sf=sf, native=False, cluster=False)
    rows = counts["lineitem"]
    conn = mirror_to_sqlite(s.catalog, tables=["lineitem"])
    out = {"sf": sf, "lineitem_rows": rows, "queries": {}}

    def one(sql, fuse: bool):
        s.execute(f"SET tidb_tpu_pipeline_fuse = {int(fuse)}")
        d0 = _dsp.count()
        t0 = time.perf_counter()
        got = s.query(sql)
        return got, time.perf_counter() - t0, _dsp.count() - d0

    for name in ("q1", "q6"):
        sql, lite = Q[name]
        DEVICE_CACHE.clear()
        # warm BOTH arms (compiles, device cache fill), then interleave
        # the measured reps A/B — machine drift between back-to-back
        # blocks would otherwise bias whichever arm runs first (the
        # test_partitions lesson)
        one(sql, True)
        one(sql, False)
        fused_best = unf_best = float("inf")
        fused_disp = unf_disp = 0
        fused_rows = unf_rows = None
        # report the dispatch count of the BEST rep, not the last one:
        # a stray recompile on the final rep would otherwise misreport
        # the steady-state dispatch budget the timing reflects
        for _ in range(max(reps, 2)):
            fused_rows, dt, disp = one(sql, True)
            if dt < fused_best:
                fused_best, fused_disp = dt, disp
            unf_rows, dt, disp = one(sql, False)
            if dt < unf_best:
                unf_best, unf_disp = dt, disp
        s.execute("SET tidb_tpu_pipeline_fuse = 1")
        ok_arms, msg = rows_equal(fused_rows, unf_rows, ordered=True)
        want = conn.execute(lite or sql).fetchall()
        ok_oracle, msg2 = rows_equal(fused_rows, want, ordered=True)
        q = {
            "fused_warm_s": round(fused_best, 4),
            "unfused_warm_s": round(unf_best, 4),
            "fused_over_unfused": round(unf_best / fused_best, 3),
            "fused_warm_dispatches": fused_disp,
            "unfused_warm_dispatches": unf_disp,
            "rows_per_sec_fused": round(rows / fused_best, 1),
            "hash_equal": bool(ok_arms),
            "check": "ok" if ok_oracle else f"MISMATCH: {msg2}"[:300],
        }
        if not ok_arms:
            q["arm_mismatch"] = str(msg)[:300]
        out["queries"][name] = q
        log(f"#   {name}: fused={fused_best * 1e3:.1f}ms "
            f"({fused_disp} disp) unfused={unf_best * 1e3:.1f}ms "
            f"({unf_disp} disp) speedup={q['fused_over_unfused']}x "
            f"check={q['check']}")
    if extra is not None:
        extra["pipeline"] = out
    return out


def bench_probe(extra=None):
    """Probe-kernel microbench (ISSUE 10): searchsorted vs the
    open-addressing hash table over the (lo, hi) range contract the
    joins consume, per build/probe size, on whatever backend is live
    (the Pallas kernel rides along on TPU). CPU-runnable: the table
    path is the TPU-shaped kernel exercised with XLA window scans, so
    the regression is visible without a chip. Loud cross-check: the
    table's match counts (and lo wherever the count is non-zero) must
    equal searchsorted on every size — the chip-free half of the
    probe-mode equivalence oracle. Folded here from the orphaned
    ops/bench_probe.py so it runs (and is load-snapshotted) under the
    same protocol as every other config."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tidb_tpu.ops import hash_probe as hp
    from tidb_tpu.ops.segment_sum import pallas_enabled

    if extra is not None:
        wait_for_idle("probe_micro", extra)
        extra["probe_micro_load"] = machine_load()
    plat = __import__("jax").devices()[0].platform
    out = {"platform": plat, "max_probes": hp.MAX_PROBES,
           "counts_match": True, "sizes": []}
    rng = np.random.default_rng(7)
    for nb, npr in [(1 << 12, 1 << 20), (1 << 16, 1 << 20),
                    (1 << 18, 1 << 21)]:
        build = np.sort(rng.integers(0, 1 << 40, nb))
        probes = rng.integers(0, 1 << 41, npr)
        sh = jnp.asarray(build)
        pr = jnp.asarray(probes)
        row = {"build": nb, "probes": npr}

        def timed(fn):
            r = fn()
            jax.block_until_ready(r)
            best = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                best = min(best, time.perf_counter() - t0)
            return best, r

        t_ss, r_ss = timed(lambda: jax.jit(hp.xla_probe_ranges)(sh, pr))
        row["searchsorted_s"] = round(t_ss, 5)
        t_tab, r_tab = timed(
            lambda: hp.probe_ranges(sh, pr, use_pallas=False))
        row["table_xla_s"] = round(t_tab, 5)

        def counts_ok(r):
            c_ss = np.asarray(r_ss[1]) - np.asarray(r_ss[0])
            c = np.asarray(r[1]) - np.asarray(r[0])
            nz = c_ss > 0
            return bool((c_ss == c).all()
                        and (np.asarray(r[0])[nz]
                             == np.asarray(r_ss[0])[nz]).all())

        row["counts_match"] = counts_ok(r_tab)
        if pallas_enabled():
            t_pl, r_pl = timed(
                lambda: hp.probe_ranges(sh, pr, use_pallas=True))
            row["table_pallas_s"] = round(t_pl, 5)
            row["pallas_counts_match"] = counts_ok(r_pl)
            out["counts_match"] &= row["pallas_counts_match"]
        out["counts_match"] &= row["counts_match"]
        row["table_over_searchsorted"] = round(
            t_ss / min(t_tab, row.get("table_pallas_s", t_tab)), 3)
        out["sizes"].append(row)
        log(f"# probe {nb}x{npr}: ss={t_ss * 1e3:.1f}ms "
            f"table={t_tab * 1e3:.1f}ms "
            f"({row['table_over_searchsorted']}x) "
            f"match={row['counts_match']}")
    if extra is not None:
        extra["probe_micro"] = out
    return out


def bench_join_fused(extra=None, sf=None, reps=None):
    """Fused scan→probe microbench (ISSUE 10): the Q18 fragment shape —
    lineitem (probe, plain scan) joining orders (build) under a group
    aggregate — on the LOCAL single-chip engine, fused
    (one scan+probe+expand program per chunk, build side device-cached)
    vs the chunk-synced classic tree (pipeline_fuse=0: scan dispatch +
    probe dispatch + expand dispatch per chunk, build re-drained every
    execution). Arms INTERLEAVED through the SAME session (machine
    drift must not bias one arm); plan cache on so planning noise
    cancels. Eager-agg push-down stays at its DEFAULT (on): plan
    feedback (ISSUE 15) must LEARN that the pushed plan's join cannot
    device-cache its build and select the no-push fused shape by
    measurement — the bench asserts the flip instead of pinning
    tidb_opt_agg_push_down=0 like it used to. Loud cross-checks: arms
    byte-identical to each other AND the sqlite oracle, warm fused
    dispatches from the engine counter (the <= 12 acceptance budget),
    probe-mode equivalence (searchsorted vs hash table) result-hash
    equal on the SAME fused query, and the feedback-chosen variant."""
    from tidb_tpu.executor.pipeline import DEVICE_CACHE
    from tidb_tpu.session import Session
    from tidb_tpu.storage.catalog import Catalog
    from tidb_tpu.storage.tpch import load_tpch
    from tidb_tpu.testutil import mirror_to_sqlite, rows_equal
    from tidb_tpu.utils import dispatch as _dsp

    sf = min(SF, 0.2) if sf is None else sf
    reps = REPS if reps is None else reps
    s = Session(catalog=Catalog(), chunk_capacity=CAP)
    s.execute("SET tidb_slow_log_threshold = 300000")
    s.execute("SET tidb_device_engine_mode = 'force'")
    s.execute("SET tidb_enable_non_prepared_plan_cache = 1")
    # NO tidb_opt_agg_push_down pin (ISSUE 15): with fresh stats the
    # heuristic planner pushes a partial agg below this join (the
    # eager-agg shrink gate fires on NDV evidence), which blocks the
    # fused scan→probe shape; plan feedback explores the no-push
    # alternative and keeps whichever measures faster warm — asserted
    # below. ANALYZE is the realistic production state AND what arms
    # the eager-agg decision this bench must learn through.
    # cluster=False: this bench measures the fused probe machinery on
    # the Q18 shape, where probe keys arrive in insert (orderkey) order
    # — neighboring probes then share searchsorted paths and the CPU
    # cache carries the binary rounds. A CLUSTER BY (l_shipdate)
    # lineitem randomizes probe-key order and the same join measures
    # ~6x slower on CPU (a locality artifact, not a fusion property);
    # the clustered default's end-to-end cost is guarded separately by
    # the q18_rows_per_sec flagship floor in perf_check.py.
    counts = load_tpch(s.catalog, sf=sf, native=False, cluster=False)
    s.execute("ANALYZE TABLE lineitem, orders")
    rows = counts["lineitem"]
    conn = mirror_to_sqlite(s.catalog, tables=["lineitem", "orders"])
    sql = ("select o_orderpriority, count(*) as n, sum(l_quantity) as q "
           "from lineitem join orders on l_orderkey = o_orderkey "
           "group by o_orderpriority order by o_orderpriority")

    def one(fuse: bool):
        s.execute(f"SET tidb_tpu_pipeline_fuse = {int(fuse)}")
        d0 = _dsp.count()
        t0 = time.perf_counter()
        got = s.query(sql)
        return got, time.perf_counter() - t0, _dsp.count() - d0

    DEVICE_CACHE.clear()
    from tidb_tpu.planner.feedback import STORE as FB

    FB.clear()  # a prior bench call's learning must not pre-warm this one
    # warmup doubles as feedback convergence: run 1 executes the default
    # (eager-push) plan and records it, runs 2-3 explore the no-push
    # variant cold then warm, runs 4-5 re-measure the default warm —
    # after this both variants have WARM measurements and the store
    # picks the fused no-push shape for every measured run below
    one(True)
    one(True)  # jits traced, build + scan caches parked (no-push plan)
    one(False)
    one(True)
    one(False)
    fused_best = classic_best = float("inf")
    fused_disp = classic_disp = 0
    fused_rows = classic_rows = None
    # dispatch counts track the BEST rep (the steady state the timing
    # reports), not whichever rep happened to run last
    for _ in range(max(reps, 2)):
        fused_rows, dt, disp = one(True)
        if dt < fused_best:
            fused_best, fused_disp = dt, disp
        classic_rows, dt, disp = one(False)
        if dt < classic_best:
            classic_best, classic_disp = dt, disp
    s.execute("SET tidb_tpu_pipeline_fuse = 1")
    ok_arms, msg = rows_equal(fused_rows, classic_rows, ordered=True)
    want = conn.execute(sql).fetchall()
    ok_oracle, msg2 = rows_equal(fused_rows, want, ordered=True)

    # feedback acceptance: a warm execution must select the no-push
    # (fused) plan BECAUSE the store chose it (sysvar still default-on),
    # not because of a pin — _fb_last_apd False = the override engaged
    # on the statement we just ran
    from tidb_tpu.bindinfo import normalize_sql, sql_digest

    digest = sql_digest(normalize_sql(sql))
    s.query(sql)
    last_apd = s._fb_last_apd  # before any further statement clobbers it
    chosen_by_feedback = bool(
        last_apd is False
        and FB.apd_decision(digest) is False
        and s.query("select @@tidb_opt_agg_push_down")[0][0])

    # probe-mode equivalence on the SAME fused fragment: the hash-table
    # path (the TPU-shaped kernel, runnable via XLA window scans on
    # CPU) must hash-equal the searchsorted default on every run
    s.execute("SET tidb_tpu_join_probe_mode = 'off'")
    rows_off = s.query(sql)
    s.execute("SET tidb_tpu_join_probe_mode = 'xla'")
    rows_xla = s.query(sql)
    s.execute("SET tidb_tpu_join_probe_mode = 'auto'")
    modes_equal, mode_msg = rows_equal(rows_off, rows_xla, ordered=True)

    out = {
        "sf": sf, "lineitem_rows": rows,
        "fused_warm_s": round(fused_best, 4),
        "classic_warm_s": round(classic_best, 4),
        "fused_over_classic": round(classic_best / fused_best, 3),
        "fused_warm_dispatches": fused_disp,
        "classic_warm_dispatches": classic_disp,
        "rows_per_sec_fused": round(rows / fused_best, 1),
        "hash_equal": bool(ok_arms),
        "probe_modes_equal": bool(modes_equal),
        "chosen_by_feedback": chosen_by_feedback,
        "check": "ok" if ok_oracle else f"MISMATCH: {msg2}"[:300],
    }
    if not ok_arms:
        out["arm_mismatch"] = str(msg)[:300]
    if not modes_equal:
        out["mode_mismatch"] = str(mode_msg)[:300]
    log(f"# join fused: fused={fused_best * 1e3:.1f}ms "
        f"({fused_disp} disp) classic={classic_best * 1e3:.1f}ms "
        f"({classic_disp} disp) speedup={out['fused_over_classic']}x "
        f"modes_equal={modes_equal} feedback={chosen_by_feedback} "
        f"check={out['check']}")
    conn.close()
    if extra is not None:
        extra["join_fused"] = out
    return out


def _fused_op_counts(s, sql):
    """Fused/classic attribution for one statement: run it once under
    EXPLAIN ANALYZE (which executes the REAL exec tree, open()-time
    fallback gates included) and count the FusedScan* operators in the
    rendered plan. Nodes marked ``[classic]`` delegated to the classic
    fallback at open() and count as classic, not fused. Returns
    (fused_op_count, {op_name: count})."""
    rows = s.query("explain analyze " + sql)
    ops = {}
    for row in rows:
        for tok in str(row[0]).split():
            name = tok.lstrip("└├─│ ")
            if name.startswith("FusedScan") and "[classic]" not in name:
                ops[name] = ops.get(name, 0) + 1
    return sum(ops.values()), ops


def bench_tpch_grid(extra=None, sf=None, reps=None):
    """Full TPC-H 22-query grid (ISSUE 18): every query at SF 0.1 on
    the LOCAL single-chip engine with per-query warm wall time, warm
    device-dispatch counts (engine counter), fused/classic operator
    attribution (EXPLAIN ANALYZE exec tree: FusedScanAgg/Probe/TopN
    vs the chunk-synced classics), a result hash, and an exact
    indexed-sqlite oracle check. This is the bench-side half of the
    tentpole's (d): the tier-1 grid proves 22/22 correctness at SF0.1;
    this capture records WHICH queries the fused pipeline carries and
    what each costs, so the long-tail fusion work (TopN/sort,
    multi-key/outer probes) is measured across the whole workload
    instead of hand-picked shapes.

    Attribution runs with `tidb_device_engine_mode=force`: on a
    single-CPU backend the cost-based router sends joins and generic
    aggregation to the host engine, so under `auto` the fused probes
    legitimately delegate ([classic]) and attribution would measure
    the ROUTER, not fusion coverage. Forcing the device tier answers
    the intended question — which plans run fused device operators
    when the device engine is engaged — and the forced run must stay
    row-identical to the measured auto run (`device_arm_equal`), so
    the attribution pass doubles as an extra correctness arm. Timed
    reps keep `auto`: the wall times reflect the default routing."""
    import hashlib

    from tidb_tpu.session import Session
    from tidb_tpu.storage.catalog import Catalog
    from tidb_tpu.storage.tpch import load_tpch
    from tidb_tpu.storage.tpch_queries import Q
    from tidb_tpu.testutil import (index_tpch_oracle, mirror_to_sqlite,
                                   normalize_row, rows_equal)
    from tidb_tpu.utils import dispatch as _dsp

    sf = 0.1 if sf is None else sf
    reps = REPS if reps is None else reps
    s = Session(catalog=Catalog(), chunk_capacity=CAP)
    s.execute("SET tidb_slow_log_threshold = 300000")
    s.execute("SET tidb_enable_non_prepared_plan_cache = 1")
    t0 = time.perf_counter()
    counts = load_tpch(s.catalog, sf=sf, native=False)
    conn = None
    if ORACLE:
        # indexed oracle: above toy scale the unindexed sqlite side
        # dominates grid wall time (Q4's correlated EXISTS goes
        # nested-loop); indexes keep the oracle O(probes)
        conn = index_tpch_oracle(mirror_to_sqlite(s.catalog))
    log(f"# tpch grid sf={sf} load+mirror={time.perf_counter() - t0:.1f}s")
    out = {"sf": sf, "lineitem_rows": counts["lineitem"],
           "all_exact": True, "fused_queries": 0, "queries": {}}
    vs_list = []
    for name in Q:
        sql, osql = Q[name]
        q = {}
        try:
            got = s.query(sql)  # warm: compiles, store builds, caches
            best = float("inf")
            disp = 0
            # disp tracks the BEST rep — the steady state `warm_s`
            # reports — not whichever rep happened to run last
            for _ in range(max(reps, 1)):
                d0 = _dsp.count()
                ta = time.perf_counter()
                got = s.query(sql)
                dt = time.perf_counter() - ta
                if dt < best:
                    best, disp = dt, _dsp.count() - d0
            # attribution + device arm under force (see docstring)
            s.execute("SET tidb_device_engine_mode = 'force'")
            try:
                forced = s.query(sql)
                fused_n, fused_ops = _fused_op_counts(s, sql)
            finally:
                s.execute("SET tidb_device_engine_mode = 'auto'")
            arm_ok, arm_msg = rows_equal(got, forced, ordered=True)
            h = hashlib.sha256()
            for r in got:
                h.update(repr(normalize_row(r)).encode())
            q.update({
                "warm_s": round(best, 4),
                "warm_dispatches": disp,
                "rows": len(got),
                "fused_ops": fused_n,
                "device_arm_equal": bool(arm_ok),
                "result_hash": h.hexdigest()[:16],
            })
            if not arm_ok:
                q["device_arm_mismatch"] = str(arm_msg)[:300]
                out["all_exact"] = False
            if fused_ops:
                q["fused_op_names"] = fused_ops
            if fused_n:
                out["fused_queries"] += 1
            if conn is not None:
                ta = time.perf_counter()
                want = conn.execute(osql or sql).fetchall()
                sqlite_s = time.perf_counter() - ta
                ok, msg = rows_equal(got, want, ordered=True)
                q["sqlite_s"] = round(sqlite_s, 4)
                q["vs_sqlite"] = round(sqlite_s / max(best, 1e-9), 3)
                q["check"] = "ok" if ok else f"MISMATCH: {msg}"[:300]
                if ok:
                    vs_list.append(q["vs_sqlite"])
                else:
                    out["all_exact"] = False
        except Exception as e:  # noqa: BLE001
            q["error"] = f"{type(e).__name__}: {e}"[:300]
            out["all_exact"] = False
        out["queries"][name] = q
        log(f"#   {name}: {q.get('warm_s', '-')}s "
            f"disp={q.get('warm_dispatches', '-')} "
            f"fused_ops={q.get('fused_ops', '-')} "
            f"check={q.get('check', q.get('error', 'skipped'))}")
    if vs_list:
        gm = 1.0
        for v in vs_list:
            gm *= max(v, 1e-9)
        out["vs_sqlite_geomean"] = round(gm ** (1.0 / len(vs_list)), 3)
    if conn is not None:
        conn.close()
    log(f"# tpch grid: {sum(1 for q in out['queries'].values() if q.get('check') == 'ok')}/22 exact, "
        f"{out['fused_queries']} queries with fused operators, "
        f"vs_sqlite geomean {out.get('vs_sqlite_geomean', '-')}")
    if extra is not None:
        extra["tpch_grid"] = out
    return out


def bench_topn_fused(extra=None, sf=None, reps=None):
    """Fused device top-k microbench (ISSUE 18): an ORDER BY + LIMIT
    root over a lineitem scan, fused (FusedScanTopNExec: one
    scan→top-k device program per staged chunk carrying a bounded
    winner state, ONE fetch at finalize) vs the classic tree
    (pipeline_fuse=0: chunked scan dispatches + TopNExec materializing
    EVERY child row to host before np.lexsort keeps k). Arms
    INTERLEAVED through the SAME session with the plan cache on, like
    every two-arm bench here. Loud cross-checks: arms byte-identical
    to each other AND the sqlite oracle, the fused arm actually ran a
    FusedScanTopN operator (EXPLAIN ANALYZE attribution — a silent
    fallback must not masquerade as a fused win), and the warm
    dispatch budget. The ≥1.5x floor on the "topn" row is enforced by
    perf_check.py."""
    from tidb_tpu.executor.pipeline import DEVICE_CACHE
    from tidb_tpu.planner.feedback import STORE as FB
    from tidb_tpu.session import Session
    from tidb_tpu.storage.catalog import Catalog
    from tidb_tpu.storage.tpch import load_tpch
    from tidb_tpu.testutil import mirror_to_sqlite, rows_equal
    from tidb_tpu.utils import dispatch as _dsp

    sf = min(SF, 0.2) if sf is None else sf
    reps = REPS if reps is None else reps
    s = Session(catalog=Catalog(), chunk_capacity=CAP)
    s.execute("SET tidb_slow_log_threshold = 300000")
    s.execute("SET tidb_enable_non_prepared_plan_cache = 1")
    counts = load_tpch(s.catalog, sf=sf, native=False)
    rows = counts["lineitem"]
    conn = mirror_to_sqlite(s.catalog, tables=["lineitem"]) if ORACLE else None
    out = {"sf": sf, "lineitem_rows": rows, "queries": {}}
    # single sort key = the device fast path (single-array cut). Arms
    # compare FULL rows (both resolve key ties in drain order, so they
    # must agree row-for-row); the sqlite oracle compares the sort-key
    # column only — tie MEMBERSHIP at the limit boundary is
    # implementation-defined across engines, but the key multiset of
    # the top 100 is not.
    queries = {
        "topn": (
            "select l_extendedprice, l_orderkey, l_linenumber, "
            "l_quantity from lineitem "
            "order by l_extendedprice desc limit 100",
            "select l_extendedprice from lineitem "
            "order by l_extendedprice desc limit 100"),
        "topn_filtered": (
            "select l_extendedprice, l_orderkey, l_linenumber, "
            "l_shipdate from lineitem "
            "where l_shipdate < date '1997-01-01' "
            "order by l_extendedprice desc limit 100",
            "select l_extendedprice from lineitem "
            "where l_shipdate < '1997-01-01' "
            "order by l_extendedprice desc limit 100"),
    }

    def one(sql, fuse: bool):
        s.execute(f"SET tidb_tpu_pipeline_fuse = {int(fuse)}")
        d0 = _dsp.count()
        t0 = time.perf_counter()
        got = s.query(sql)
        return got, time.perf_counter() - t0, _dsp.count() - d0

    for name, (sql, lite) in queries.items():
        DEVICE_CACHE.clear()
        FB.clear()  # learned routing must not pre-steer either arm
        one(sql, True)
        one(sql, False)
        fused_best = classic_best = float("inf")
        fused_disp = classic_disp = 0
        fused_rows = classic_rows = None
        # dispatch counts follow the best rep (see bench_tpch_grid)
        for _ in range(max(reps, 2)):
            fused_rows, dt, disp = one(sql, True)
            if dt < fused_best:
                fused_best, fused_disp = dt, disp
            classic_rows, dt, disp = one(sql, False)
            if dt < classic_best:
                classic_best, classic_disp = dt, disp
        s.execute("SET tidb_tpu_pipeline_fuse = 1")
        fused_n, fused_ops = _fused_op_counts(s, sql)
        ok_arms, msg = rows_equal(fused_rows, classic_rows, ordered=True)
        ok_oracle, msg2 = True, "ok"
        if conn is not None:
            want = conn.execute(lite).fetchall()
            ok_oracle, msg2 = rows_equal(
                [(r[0],) for r in fused_rows], want, ordered=True)
        q = {
            "fused_warm_s": round(fused_best, 4),
            "classic_warm_s": round(classic_best, 4),
            "fused_over_classic": round(classic_best / fused_best, 3),
            "fused_warm_dispatches": fused_disp,
            "classic_warm_dispatches": classic_disp,
            "rows_per_sec_fused": round(rows / fused_best, 1),
            "fused_engaged": bool(
                fused_ops.get("FusedScanTopN", 0) > 0),
            "hash_equal": bool(ok_arms),
            "check": "ok" if ok_oracle else f"MISMATCH: {msg2}"[:300],
        }
        if not ok_arms:
            q["arm_mismatch"] = str(msg)[:300]
        out["queries"][name] = q
        log(f"#   {name}: fused={fused_best * 1e3:.1f}ms "
            f"({fused_disp} disp) classic={classic_best * 1e3:.1f}ms "
            f"({classic_disp} disp) speedup={q['fused_over_classic']}x "
            f"engaged={q['fused_engaged']} check={q['check']}")
    if conn is not None:
        conn.close()
    if extra is not None:
        extra["topn_fused"] = out
    return out


def bench_zone_pruning(extra=None, sf=None, reps=None):
    """Zone-map pruning microbench (ISSUE 8): TPC-H Q6 over a
    time-ordered (l_shipdate-clustered) lineitem — the production
    fact-table layout — pruned (columnar on) vs unpruned (columnar
    off), on the LOCAL engine where the segment store lives. Loud
    cross-checks: the engine-reported pruned fraction (the acceptance
    counter), result equality across both modes, and an exact
    sqlite-oracle comparison over an integer mirror of the four Q6
    columns (scaled-int arithmetic: no float fuzz in the check).

    ISSUE 18: the clustering comes from the CLUSTER BY (l_shipdate)
    DDL default in load_tpch — ordered compaction sorts lineitem at
    the first delta→segment fold — NOT from hand-ordered ingest
    (the deprecated cluster_lineitem kwarg). The ≥2x pruning floor now
    proves the maintained layout, not load-order luck."""
    import sqlite3
    from decimal import Decimal

    import numpy as np

    from tidb_tpu.session import Session
    from tidb_tpu.storage.catalog import Catalog
    from tidb_tpu.storage.tpch import load_tpch
    from tidb_tpu.storage.tpch_queries import Q
    from tidb_tpu.types import date_to_days
    from tidb_tpu.utils import metrics as _M

    sf = min(SF, 0.2) if sf is None else sf
    reps = REPS if reps is None else reps
    s = Session(catalog=Catalog(), chunk_capacity=1 << 20)
    load_tpch(s.catalog, sf=sf, native=False)
    t = s.catalog.table("test", "lineitem")
    n = t.n
    sql = Q["q6"][0]

    def segs():
        return (int(_M.SCAN_SEGMENTS_SCANNED_TOTAL.value()),
                int(_M.SCAN_SEGMENTS_PRUNED_TOTAL.value()))

    # warm both modes (store build + XLA compiles happen here)
    got_on = s.query(sql)
    s0 = segs()
    got_on = s.query(sql)
    s1 = segs()
    scanned, pruned = s1[0] - s0[0], s1[1] - s0[1]
    frac = pruned / max(scanned + pruned, 1)
    best_on = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        got_on = s.query(sql)
        best_on = min(best_on, time.perf_counter() - t0)
    s.execute("set tidb_tpu_columnar_enable = 0")
    got_off = s.query(sql)  # warm the raw path
    best_off = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        got_off = s.query(sql)
        best_off = min(best_off, time.perf_counter() - t0)
    s.execute("set tidb_tpu_columnar_enable = 1")

    # exact oracle: integer mirror of the four Q6 columns; revenue at
    # scale 4 (price scale 2 x discount scale 2) compares as an int
    conn = sqlite3.connect(":memory:")
    conn.execute("create table li (ship integer, disc integer, "
                 "qty integer, ext integer)")
    rows = np.stack([
        np.asarray(t.data["l_shipdate"][:n], dtype=np.int64),
        np.asarray(t.data["l_discount"][:n], dtype=np.int64),
        np.asarray(t.data["l_quantity"][:n], dtype=np.int64),
        np.asarray(t.data["l_extendedprice"][:n], dtype=np.int64),
    ], axis=1)
    conn.executemany("insert into li values (?,?,?,?)",
                     map(tuple, rows.tolist()))
    d1 = date_to_days(__import__("datetime").date(1994, 1, 1))
    d2 = date_to_days(__import__("datetime").date(1995, 1, 1))
    want = conn.execute(
        f"select sum(ext * disc) from li where ship >= {d1} and "
        f"ship < {d2} and disc between 5 and 7 and qty < 2400"
    ).fetchone()[0] or 0
    conn.close()
    got_scaled = int(Decimal(str(got_on[0][0] or 0)).scaleb(4))
    check = "ok"
    if got_scaled != int(want):
        check = f"MISMATCH: engine {got_scaled} != sqlite {int(want)}"
    if got_on != got_off:
        # append, don't overwrite: both diagnostics matter when both fail
        extra_msg = f"MISMATCH: pruned {got_on} != unpruned {got_off}"
        check = extra_msg if check == "ok" else f"{check}; {extra_msg}"
    out = {
        "sf": sf,
        "rows": int(n),
        "pruned_s": round(best_on, 4),
        "unpruned_s": round(best_off, 4),
        "pruned_over_unpruned": round(best_off / max(best_on, 1e-9), 3),
        "segs_scanned": scanned,
        "segs_pruned": pruned,
        "pruned_fraction": round(frac, 4),
        "check": check,
        # ISSUE 19 satellite: stamp the capture so perf_check (and a
        # reader of BENCH_r*) can tell machine drift from regression —
        # the SF1 ratio sits near its floor, provenance names the rev
        # and flag set that produced each number
        "provenance": bench_provenance(),
    }
    log(f"# zone pruning q6 sf={sf}: pruned={best_on * 1e3:.1f}ms "
        f"unpruned={best_off * 1e3:.1f}ms "
        f"({out['pruned_over_unpruned']}x), "
        f"segs {scanned}/{scanned + pruned} scanned "
        f"(frac pruned {frac:.2f}) check={check}")
    if extra is not None:
        extra["zone_pruning"] = out
    return out


def bench_budget_q18(catalog, extra=None):
    """Budget-capped q18 via segment spill (ISSUE 8): the same query,
    resident vs under a statement memory budget of half the segment
    store's resident bytes, on a LOCAL (no-mesh) session over an
    already-loaded TPC-H catalog. The budget run must complete by
    evicting/re-materializing segments (engine spill counters move)
    and produce byte-identical rows."""
    import hashlib

    from tidb_tpu.session import Session
    from tidb_tpu.storage.tpch_queries import Q
    from tidb_tpu.utils import metrics as _M

    s = Session(catalog=catalog, chunk_capacity=1 << 20)
    sql = Q["q18"][0]

    def result_hash(rows):
        h = hashlib.sha256()
        for r in rows:
            h.update(repr(r).encode())
        return h.hexdigest()[:16]

    s.query(sql)  # warm: builds stores, compiles
    t0 = time.perf_counter()
    resident = s.query(sql)
    resident_s = time.perf_counter() - t0
    li = s.catalog.table("test", "lineitem")
    store = getattr(li, "_segment_store", None)
    seg_bytes = store.resident_bytes() if store is not None else 0
    budget = max(64 << 20, seg_bytes // 2)
    out0 = _M.SPILL_SEGMENT_BYTES.value(dir="out")
    in0 = _M.SPILL_SEGMENT_BYTES.value(dir="in")
    s.execute(f"set tidb_mem_quota_query = {budget}")
    s.execute("set tidb_enable_tmp_storage_on_oom = 1")
    t0 = time.perf_counter()
    budgeted = s.query(sql)
    budget_s = time.perf_counter() - t0
    s.execute("set tidb_mem_quota_query = 2147483648")
    spill_out = int(_M.SPILL_SEGMENT_BYTES.value(dir="out") - out0)
    spill_in = int(_M.SPILL_SEGMENT_BYTES.value(dir="in") - in0)
    out = {
        "budget_bytes": int(budget),
        "segment_resident_bytes": int(seg_bytes),
        "resident_s": round(resident_s, 4),
        "budget_s": round(budget_s, 4),
        "overhead_vs_resident": round(budget_s / max(resident_s, 1e-9), 3),
        "spill_out_bytes": int(spill_out),
        "spill_in_bytes": int(spill_in),
        "hash_equal": result_hash(budgeted) == result_hash(resident),
        "result_hash": result_hash(resident),
    }
    log(f"# q18 budget: resident={resident_s:.2f}s "
        f"budget({budget >> 20}MiB)={budget_s:.2f}s "
        f"spill out={spill_out >> 20}MiB in={spill_in >> 20}MiB "
        f"hash_equal={out['hash_equal']}")
    if extra is not None:
        extra["q18_budget"] = out
    return out


def main():
    extra = {}
    platform = os.environ.get("BENCH_PLATFORM", "default")
    extra["platform"] = platform

    import tidb_tpu  # noqa: F401  (jax x64 config)
    import jax

    if platform != "default":
        jax.config.update("jax_platforms", platform)
    from tidb_tpu.utils.device import device_info

    extra["device"] = device_info()  # a backend that fails to start raises
    from tidb_tpu.parallel import make_mesh
    from tidb_tpu.session import Session
    from tidb_tpu.storage.tpch import load_tpch
    from tidb_tpu.storage.tpch_queries import Q

    extra["devices"] = [str(d) for d in jax.devices()][:8]

    t0 = time.perf_counter()
    # mesh session even on one chip: tables stay device-resident in the
    # shard cache and each query is one collective fragment dispatch
    mesh = make_mesh()
    s = Session(chunk_capacity=CAP, mesh=mesh)
    counts = load_tpch(s.catalog, sf=SF)
    rows = counts["lineitem"]
    extra["sf"] = SF
    extra["lineitem_rows"] = rows
    log(f"# sf={SF} lineitem={rows} gen={time.perf_counter() - t0:.1f}s")

    conn = None
    if ORACLE:
        from tidb_tpu.testutil import mirror_to_sqlite

        t0 = time.perf_counter()
        conn = mirror_to_sqlite(s.catalog, tables=["lineitem", "orders", "customer"])
        log(f"# sqlite mirror {time.perf_counter() - t0:.1f}s")

    # headline: Q1 (scan + filter + group-by agg) ---------------------------
    log("# q1")
    q1_rps, q1_vs, q1_best, q1_check = bench_query(
        s, Q["q1"][0], conn, Q["q1"][1] or Q["q1"][0], rows, extra=extra, tag="q1")
    if "MISMATCH" in q1_check:
        extra["q1_check"] = q1_check

    # Q6: range-predicate selection -> device filter kernel ------------------
    try:
        log("# q6")
        sql, lite = Q["q6"]
        rps, vs, best, check = bench_query(s, sql, conn, lite or sql, rows,
                                           extra=extra, tag="q6")
        extra["tpch_q6_rows_per_sec"] = round(rps, 1)
        extra["q6_vs_sqlite"] = round(vs, 3)
        # bytes actually consulted by Q6: 4 numeric lineitem columns
        extra["tpch_q6_gbps"] = round(rows * 4 * 8 / best / 1e9, 3)
        if "MISMATCH" in check:
            extra["q6_check"] = check
    except Exception as e:  # noqa: BLE001
        extra["q6_error"] = f"{type(e).__name__}: {e}"[:300]

    # join microbench: lineitem x orders build+probe throughput --------------
    try:
        log("# join microbench")
        jq = ("select count(*) as n, sum(l_quantity) as q from lineitem "
              "join orders on l_orderkey = o_orderkey where o_totalprice > 100000")
        rps, vs, best, check = bench_query(s, jq, conn, jq, rows,
                                           extra=extra, tag="join")
        # bytes through the join: probe keys+payload and build keys+filter col
        jbytes = rows * 2 * 8 + counts["orders"] * 2 * 8
        extra["join_build_probe_gbps"] = round(jbytes / best / 1e9, 3)
        extra["join_vs_sqlite"] = round(vs, 3)
        if "MISMATCH" in check:
            extra["join_check"] = check
    except Exception as e:  # noqa: BLE001
        extra["join_error"] = f"{type(e).__name__}: {e}"[:300]

    # plan-cache microbench: the OLTP statement path (host-only; no mesh
    # or sqlite involvement — the win being measured is Python planning)
    try:
        log("# plan cache microbench")
        extra["plan_cache"] = bench_plan_cache(extra)
    except Exception as e:  # noqa: BLE001
        extra["plan_cache_error"] = f"{type(e).__name__}: {e}"[:300]

    # release the SF1 working set before the join-heavy configs: keeping
    # gigabytes of prior sessions resident measurably slows the numpy/
    # XLA paths of later configs (page-cache pressure)
    import gc

    def drop(*objs):
        for o in objs:
            try:
                if hasattr(o, "close"):
                    o.close()
            except Exception:  # noqa: BLE001
                pass
        gc.collect()

    # Q18: 3-way join + large-key agg (BASELINE flagship config) -------------
    try:
        log(f"# q18 at sf={SF_Q18}")
        if abs(SF_Q18 - SF) > 1e-9:
            # separate data set: the SF1 working set is no longer needed
            drop(conn)
            s = counts = conn = None
            gc.collect()
            s18 = Session(chunk_capacity=CAP, mesh=mesh)
            c18 = load_tpch(s18.catalog, sf=SF_Q18)
            conn18 = None
            if ORACLE:
                from tidb_tpu.testutil import mirror_to_sqlite

                conn18 = mirror_to_sqlite(
                    s18.catalog, tables=["lineitem", "orders", "customer"])
        else:
            s18, c18, conn18 = s, counts, conn
        sql, lite = Q["q18"]
        rps, vs, best, check = bench_query(
            s18, sql, conn18, lite or sql, c18["lineitem"], extra=extra, tag="q18")
        extra["tpch_q18_rows_per_sec"] = round(rps, 1)
        extra["q18_vs_sqlite"] = round(vs, 3)
        extra["q18_sf"] = SF_Q18
        if "MISMATCH" in check:
            extra["q18_check"] = check
    except Exception as e:  # noqa: BLE001
        extra["q18_error"] = f"{type(e).__name__}: {e}"[:300]

    # Q18 streamed: the same query under a MEMORY BUDGET of lineitem/4
    # (VERDICT r4 task 4 / SURVEY.md:315 hard-part 6 at bench scale).
    # The budget binds whichever engine the router picks: the device
    # tier streams lineitem through fixed [P, R] fragment batches
    # (tidb_device_cache_bytes), the host tier spills runs and finishes
    # with the key-range external aggregation merge
    # (tidb_mem_quota_query). Either path counts as engaged; forcing a
    # mismatched engine would measure the budget against the wrong tier.
    try:
        if "q18_error" not in extra and s18 is not None:
            from tidb_tpu.parallel.partition import table_bytes
            from tidb_tpu.utils.metrics import EXTERNAL_AGG, FRAGMENT_DISPATCH

            def stream_engagements():
                return (FRAGMENT_DISPATCH.value(kind="general_segment_stream")
                        + FRAGMENT_DISPATCH.value(kind="general_generic_stream")
                        + EXTERNAL_AGG.value())

            li = s18.catalog.table("test", "lineitem")
            li_bytes = table_bytes(li)
            budget = max(1 << 20, li_bytes // 4)
            log(f"# q18 streamed (lineitem={li_bytes >> 20}MiB, "
                f"budget={budget >> 20}MiB)")
            best_res = best
            s18.execute(f"SET tidb_device_cache_bytes = {budget}")
            # the HOST quota floors at the engine's fixed per-query
            # working set (chunk buffers + scan staging ~ tens of MB):
            # at toy smoke SFs lineitem/4 dips below it and would OOM
            # on overhead, not on group state
            s18.execute(f"SET tidb_mem_quota_query = {max(budget, 32 << 20)}")
            s18.execute("SET tidb_enable_tmp_storage_on_oom = 1")
            d0 = stream_engagements()
            rps_s, vs_s, best_s, check_s = bench_query(
                s18, sql, conn18, lite or sql, c18["lineitem"],
                extra=extra, tag="q18_streamed")
            engaged = stream_engagements() > d0
            s18.execute("SET tidb_device_cache_bytes = 8589934592")
            s18.execute("SET tidb_mem_quota_query = 2147483648")  # default
            extra["q18_streamed"] = {
                "rows_per_sec": round(rps_s, 1),
                "vs_sqlite": round(vs_s, 3),
                "budget_bytes": budget,
                "lineitem_bytes": li_bytes,
                "engaged": bool(engaged),
                "overhead_vs_resident": round(best_s / best_res, 3),
                "check": check_s,
            }
    except Exception as e:  # noqa: BLE001
        extra["q18_streamed_error"] = f"{type(e).__name__}: {e}"[:300]

    # Q18 under a segment-spill budget (ISSUE 8): local engine over the
    # same catalog — completes by evicting/re-materializing segments,
    # byte-identical to the resident run
    try:
        if "q18_error" not in extra and s18 is not None:
            log("# q18 budget (segment spill)")
            bench_budget_q18(s18.catalog, extra)
    except Exception as e:  # noqa: BLE001
        extra["q18_budget_error"] = f"{type(e).__name__}: {e}"[:300]

    # SSB Q3.2: 4-way star join (BASELINE flagship config) -------------------
    try:
        log(f"# ssb q3.2 at sf={SF_SSB}")
        drop(locals().get("conn18"))
        s18 = conn18 = c18 = None
        gc.collect()
        from tidb_tpu.storage.ssb import SSB_QUERIES, load_ssb

        s_ssb = Session(chunk_capacity=CAP, mesh=mesh)
        c_ssb = load_ssb(s_ssb.catalog, sf=SF_SSB)
        conn_ssb = None
        if ORACLE:
            from tidb_tpu.testutil import mirror_to_sqlite

            conn_ssb = mirror_to_sqlite(s_ssb.catalog)
        sql = SSB_QUERIES["q3.2"]
        # unordered: q3.2's ORDER BY doesn't break revenue ties
        rps, vs, best, check = bench_query(
            s_ssb, sql, conn_ssb, sql, c_ssb["lineorder"], ordered=False,
            extra=extra, tag="ssb")
        extra["ssb_q32_rows_per_sec"] = round(rps, 1)
        extra["ssb_q32_vs_sqlite"] = round(vs, 3)
        extra["ssb_sf"] = SF_SSB
        if "MISMATCH" in check:
            extra["ssb_q32_check"] = check
    except Exception as e:  # noqa: BLE001
        extra["ssb_error"] = f"{type(e).__name__}: {e}"[:300]

    # TPC-DS Q95: semi-join / MPP exchange config ----------------------------
    try:
        log(f"# tpcds q95 at sf={SF_DS}")
        drop(locals().get("conn_ssb"))
        s_ssb = conn_ssb = c_ssb = None
        gc.collect()
        from tidb_tpu.storage.tpcds import Q95, Q95_SQLITE, load_tpcds_q95

        s_ds = Session(chunk_capacity=CAP, mesh=mesh)
        c_ds = load_tpcds_q95(s_ds.catalog, sf=SF_DS)
        conn_ds = None
        if ORACLE:
            from tidb_tpu.testutil import mirror_to_sqlite

            conn_ds = mirror_to_sqlite(s_ds.catalog)
        rps, vs, best, check = bench_query(
            s_ds, Q95, conn_ds, Q95_SQLITE, c_ds["web_sales"], extra=extra, tag="tpcds")
        extra["tpcds_q95_rows_per_sec"] = round(rps, 1)
        extra["tpcds_q95_vs_sqlite"] = round(vs, 3)
        extra["tpcds_sf"] = SF_DS
        if "MISMATCH" in check:
            extra["tpcds_q95_check"] = check
    except Exception as e:  # noqa: BLE001
        extra["tpcds_error"] = f"{type(e).__name__}: {e}"[:300]

    # fused-pipeline microbench (ISSUE 9): Q1/Q6 fused vs chunk-synced
    # on the single-chip spine, warm dispatch counts + oracle
    try:
        log("# pipeline microbench")
        bench_pipeline(extra)
    except Exception as e:  # noqa: BLE001
        extra["pipeline_error"] = f"{type(e).__name__}: {e}"[:300]

    # fused scan→probe microbench (ISSUE 10): the Q18 join fragment
    # fused vs classic + probe-mode equivalence, dispatch budget
    try:
        log("# join fused microbench")
        bench_join_fused(extra)
    except Exception as e:  # noqa: BLE001
        extra["join_fused_error"] = f"{type(e).__name__}: {e}"[:300]

    # fused TopN microbench (ISSUE 18): ORDER BY + LIMIT root fused
    # (device top-k state, one finalize fetch) vs classic materializing
    # sort, interleaved arms + oracle
    try:
        log("# topn fused microbench")
        bench_topn_fused(extra)
    except Exception as e:  # noqa: BLE001
        extra["topn_fused_error"] = f"{type(e).__name__}: {e}"[:300]

    # full TPC-H 22-query grid (ISSUE 18): per-query warm time,
    # dispatch counts, fused/classic attribution, indexed-sqlite oracle
    try:
        log("# tpch 22-query grid")
        bench_tpch_grid(extra)
    except Exception as e:  # noqa: BLE001
        extra["tpch_grid_error"] = f"{type(e).__name__}: {e}"[:300]

    # probe-kernel microbench (ISSUE 10): searchsorted vs hash table,
    # per backend — the TPU-vs-CPU join-kernel regression guard
    try:
        log("# probe kernel microbench")
        bench_probe(extra)
    except Exception as e:  # noqa: BLE001
        extra["probe_micro_error"] = f"{type(e).__name__}: {e}"[:300]

    # zone-map pruning microbench (ISSUE 8): Q6 over time-ordered
    # lineitem, pruned vs unpruned, engine counters + exact oracle
    try:
        drop(locals().get("conn_ds"))
        s_ds = conn_ds = c_ds = None
        gc.collect()
        log("# zone-map pruning microbench")
        bench_zone_pruning(extra)
    except Exception as e:  # noqa: BLE001
        extra["zone_pruning_error"] = f"{type(e).__name__}: {e}"[:300]

    # join microbench: the local-engine partitioned join (ISSUE 3) —
    # build x probe grid, cold vs warm, sqlite oracle + retrace guards.
    # LAST, after the big working sets are released: the >=5x acceptance
    # number must not absorb another config's page-cache pressure (the
    # baseline was measured on an idle machine)
    try:
        drop(locals().get("conn_ds"))
        s_ds = conn_ds = c_ds = None
        gc.collect()
        log("# join microbench")
        extra["join_micro"] = bench_join_micro(extra)
    except Exception as e:  # noqa: BLE001
        extra["join_micro_error"] = f"{type(e).__name__}: {e}"[:300]

    # multi-client OLTP through the serving tier (ISSUE 7): coalesced vs
    # unbatched stmts/s + p99 + admission counters, serial-oracle checked
    # (host-only: the win being measured is scheduling + batched dispatch)
    try:
        log("# oltp serving bench")
        extra["oltp"] = bench_oltp(extra)
    except Exception as e:  # noqa: BLE001
        extra["oltp_error"] = f"{type(e).__name__}: {e}"[:300]

    # mixed 90/10 with group-commit DML (ISSUE 17): window on vs off on
    # fresh catalogs, serial-oracle state-hash checked every run
    try:
        log("# mixed 90/10 group-commit bench")
        bench_mixed(extra)
    except Exception as e:  # noqa: BLE001
        extra["mixed_error"] = f"{type(e).__name__}: {e}"[:300]

    # HTAP: analytics during sustained ingest with background
    # compaction ON (ISSUE 17), staleness + p99 + flag-off equality
    try:
        log("# htap bench")
        bench_htap(extra)
    except Exception as e:  # noqa: BLE001
        extra["htap_error"] = f"{type(e).__name__}: {e}"[:300]

    # sharded scale-out capture (ISSUE 13): same scan-agg at 1/2/4
    # workers over SHARD BY placement -> MULTICHIP_r06.json
    try:
        log("# multichip scale-out bench")
        bench_multichip(extra)
    except Exception as e:  # noqa: BLE001
        extra["multichip_error"] = f"{type(e).__name__}: {e}"[:300]

    # elastic-topology SLO (ISSUE 19): p99 + throughput dip during a
    # live 12->24 online reshard under sustained mixed traffic; the
    # serving floor (every 1s window serves) is gated in perf_check
    try:
        log("# elastic reshard bench")
        bench_elastic(extra)
    except Exception as e:  # noqa: BLE001
        extra["elastic_error"] = f"{type(e).__name__}: {e}"[:300]

    extra["provenance"] = bench_provenance()
    print(json.dumps({
        "metric": "tpch_q1_rows_per_sec",
        "value": round(q1_rps, 1),
        "unit": "rows/sec",
        "vs_baseline": round(q1_vs, 3),
        "extra": extra,
    }))
    failed = sorted(k for k in extra if k.endswith("_error"))
    if failed:
        log(f"# FAILED phases: {failed}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
