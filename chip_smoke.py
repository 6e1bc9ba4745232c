#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served SQL path starts and
answers correctly on the TPU.

    python chip_smoke.py               one chip: the served path at TPC-H SF1
    python chip_smoke.py --chips 4     four chips: the mesh tier only (1x4 vs 1x1)
    python chip_smoke.py --rehearse    the same phases at SF0.01 on the CPU;
                                       never "ok": true, never exit code 0

Statements: TPC-H Q6 and Q1 and the lineitem-orders join statement on the
mesh tier (the two hand-written fragments of parallel/distsql.py), TPC-H
Q18's inner aggregate (GROUP BY l_orderkey, 1.5M groups) through the
general fragment compiler (parallel/fragment.py: sort-reduce; on four
chips the partial groups repartitioned over all_to_all and reduced
again, on one chip the partial table is the final one; one program and
one launch cold,
its group table sized from the key's distinct count as the bulk load
sketched it), TPC-H Q3 whole (customer, orders and lineitem joined, a
GROUP BY over the joined rows and a top 10) as ONE general fragment
launched once (its filters are estimated from what the bulk load
recorded of orders and lineitem, so no compaction buffer overflows), a
transaction (insert, aggregate,
ORDER BY LIMIT) on the fused segment-store tier with read-back on the
other connection, and a point get. The whole of TPC-H Q18 is NOT in
this list: since PR 35 it is ONE general fragment on one chip (the
IN-subquery's GROUP BY and HAVING compiled into the program) that holds
6 sorts, over the 4 a statement of this smoke may hold
(tests/test_chip_compile.py pins it); what it costs on the chip is the
benchmark's to say (cell tpch_sf1_power.q18; cold:
``scripts/q3_first_answer.py --statement q18``; ROADMAP S3).

One process. The server is booted through ``tidb_tpu.__main__.boot`` with
the default configuration (``--mesh auto``, status port on) and TPC-H SF1
preloaded; statements go through ``tidb_tpu.server.client.Client`` on two
connections; every answer is compared with a plain numpy reference over
the same generated arrays. Any mismatch, exception or non-TPU placement
exits non-zero without an ``"ok": true`` line. Earlier stdout lines (one
JSON object each) carry set-up seconds, per-statement cold/warm seconds,
compiles and compile-cache hits, dispatches by site, where each tier's
arrays live and HBM bytes; they are information from a single run, not a
benchmark. The last line is the verdict the driver reads.
"""

from __future__ import annotations

import argparse
import datetime
import json
import sys
import time
import traceback

import numpy as np

T0 = time.time()
STMT_TIMEOUT_S = 1150.0  # a client that gives up before the driver does


def emit(**kw) -> None:
    kw.setdefault("t", round(time.time() - T0, 1))
    print(json.dumps(kw, default=str), flush=True)


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# the plain reference: numpy over the generated arrays
# ---------------------------------------------------------------------------

class Ref:
    """Expected answers straight from the catalog's host arrays (decimals
    are scaled int64, dates are days since the epoch, strings are
    dictionary codes). Independent of parser, planner, executor and
    kernels: nothing here goes through tidb_tpu's SQL path."""

    def __init__(self, catalog, db="test"):
        self.t = {n: catalog.table(db, n)
                  for n in ("lineitem", "orders", "customer")}
        for name, tab in self.t.items():
            check(bool(tab.live_mask(0, tab.n).all()),
                  f"{name}: freshly loaded rows must all be live")

    def col(self, table, name):
        tab = self.t[table]
        return tab.data[name][:tab.n]

    def decode(self, table, name, codes):
        codes = np.asarray(codes)
        return self.t[table].dicts[name].decode(
            codes, np.ones(len(codes), dtype=np.bool_))

    @staticmethod
    def days(iso):
        return (datetime.date.fromisoformat(iso)
                - datetime.date(1970, 1, 1)).days

    # -- statements ---------------------------------------------------------

    def q6(self):
        sd, disc, qty, ext = (self.col("lineitem", c) for c in (
            "l_shipdate", "l_discount", "l_quantity", "l_extendedprice"))
        m = ((sd >= self.days("1994-01-01")) & (sd < self.days("1995-01-01"))
             & (disc >= 5) & (disc <= 7) & (qty < 2400))
        return [(int((ext[m] * disc[m]).sum()) / 1e4,)]

    def q1(self):
        c = {n: self.col("lineitem", n) for n in (
            "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
            "l_discount", "l_tax", "l_shipdate")}
        m = c["l_shipdate"] <= self.days("1998-09-02")
        rf, ls = c["l_returnflag"][m], c["l_linestatus"][m]
        q, e, d, t = (c[n][m] for n in ("l_quantity", "l_extendedprice",
                                        "l_discount", "l_tax"))
        dp = e * (100 - d)
        ch = dp * (100 + t)
        rows = []
        for f in np.unique(rf):
            for s in np.unique(ls):
                g = (rf == f) & (ls == s)
                n = int(g.sum())
                if not n:
                    continue
                sq, se, sd_ = int(q[g].sum()), int(e[g].sum()), int(d[g].sum())
                rows.append((
                    self.decode("lineitem", "l_returnflag", [f])[0],
                    self.decode("lineitem", "l_linestatus", [s])[0],
                    sq / 1e2, se / 1e2, int(dp[g].sum()) / 1e4,
                    int(ch[g].sum()) / 1e6, sq / n / 1e2, se / n / 1e2,
                    sd_ / n / 1e2, n))
        return sorted(rows)

    def _order_index(self):
        ok = self.col("orders", "o_orderkey")
        idx = np.full(int(ok.max()) + 1, -1, dtype=np.int64)
        idx[ok] = np.arange(len(ok))
        return idx

    def join(self):
        o_sel = self.col("orders", "o_totalprice") > 100000 * 100
        li = self._order_index()[self.col("lineitem", "l_orderkey")]
        m = (li >= 0) & o_sel[np.maximum(li, 0)]
        return [(int(m.sum()),
                 int(self.col("lineitem", "l_quantity")[m].sum()) / 1e2)]

    def q18_inner(self):
        lk = self.col("lineitem", "l_orderkey")
        sumq = np.zeros(int(lk.max()) + 1, dtype=np.int64)
        np.add.at(sumq, lk, self.col("lineitem", "l_quantity"))
        big = np.flatnonzero(sumq > 300 * 100)
        return [(int(k), int(sumq[k]) / 1e2) for k in big]

    def q3(self, segment="BUILDING", date="1995-03-15"):
        day = self.days(date)
        code = self.t["customer"].dicts["c_mktsegment"].values.index(segment)
        ck = self.col("customer", "c_custkey")
        in_segment = np.zeros(int(ck.max()) + 1, dtype=np.bool_)
        in_segment[ck[self.col("customer", "c_mktsegment") == code]] = True
        okey, odate, prio = (self.col("orders", c) for c in (
            "o_orderkey", "o_orderdate", "o_shippriority"))
        o_ok = (odate < day) & in_segment[self.col("orders", "o_custkey")]
        li = self._order_index()[self.col("lineitem", "l_orderkey")]
        m = ((li >= 0) & o_ok[np.maximum(li, 0)]
             & (self.col("lineitem", "l_shipdate") > day))
        revenue = np.zeros(len(o_ok), dtype=np.int64)
        np.add.at(revenue, li[m], self.col("lineitem", "l_extendedprice")[m]
                  * (100 - self.col("lineitem", "l_discount")[m]))
        rows = np.unique(li[m])  # the orders with a group
        top = rows[np.lexsort((okey[rows], odate[rows], -revenue[rows]))[:10]]
        epoch = datetime.date(1970, 1, 1)
        return [(int(okey[r]), int(revenue[r]) / 1e4,
                 str(epoch + datetime.timedelta(days=int(odate[r]))),
                 int(prio[r])) for r in top]

    def top_prices(self, k):
        ext = self.col("lineitem", "l_extendedprice")
        return sorted((int(v) for v in
                       np.partition(ext, len(ext) - k)[-k:]),
                      reverse=True)


JOIN_SQL = ("select count(*) as n, sum(l_quantity) as q from lineitem "
            "join orders on l_orderkey = o_orderkey "
            "where o_totalprice > 100000")
Q6_SHAPED = ("select sum(l_extendedprice * l_discount) as revenue "
             "from lineitem where l_shipdate >= date '1994-01-01' "
             "and l_shipdate < date '1995-01-01' "
             "and l_discount between 0.05 and 0.07 and l_quantity < 24")
# TPC-H Q18's inner aggregate: one group per order (1.5M groups at SF1)
# is no segment aggregation — the general fragment compiler's generic
# path takes it (per-shard sort-reduce; across chips the partial groups
# repartitioned by key over all_to_all and merged where they land)
Q18_INNER_SQL = ("select l_orderkey, sum(l_quantity) as q from lineitem "
                 "group by l_orderkey having sum(l_quantity) > 300 "
                 "order by l_orderkey")
# TPC-H Q3 whole (clause 2.4.3's validation parameters), l_orderkey
# appended to the ORDER BY so that a tie at the cut has one answer: two
# joins, a generic aggregate over the joined rows and the host's top 10,
# ONE general fragment
Q3_SQL = ("select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue, "
          "o_orderdate, o_shippriority from customer, orders, lineitem "
          "where c_mktsegment = 'BUILDING' and c_custkey = o_custkey "
          "and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15' "
          "and l_shipdate > date '1995-03-15' "
          "group by l_orderkey, o_orderdate, o_shippriority "
          "order by revenue desc, o_orderdate, l_orderkey limit 10")
TOPN_SQL = ("select l_extendedprice, l_orderkey from lineitem "
            "order by l_extendedprice desc limit 10")
GENERAL = ("q18_inner", "q3")  # the statements the general fragment compiler takes


def check_one_launch(name: str, cold: dict) -> None:
    """Every capacity of a general fragment is sized from the data: the
    group table from the key's distinct count, which the bulk load
    sketched (Table._seed_key_sketches), a filtered scan's compaction
    from the bounds the load recorded (statistics.record_load_stats).
    The cold statement compiles its fragment once and launches it once.
    A second launch is the overflow retry: an estimate fell short of the
    data, and the first launch's compile and device work were thrown away."""
    launches = {k: v for k, v in cold.items() if k.startswith("fragment:")}
    check(launches == {"fragment:general_generic": 1},
          f"{name} launched its fragment more than once cold "
          f"(a capacity knob overflowed): {launches}")


# ---------------------------------------------------------------------------
# observation: compiles, cache hits, dispatches, placement, HBM
# ---------------------------------------------------------------------------

class Observer:
    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        def on_duration(event, duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += float(duration)

        mon.register_event_listener(on_event)
        mon.register_event_duration_secs_listener(on_duration)

    def counters(self) -> dict:
        from tidb_tpu.utils.metrics import DISPATCH_TOTAL, FRAGMENT_DISPATCH

        d = {"compiles": self.compiles, "compile_s": self.compile_s,
             "cache_hits": self.cache_hits, "cache_misses": self.cache_misses}
        for labels, v in DISPATCH_TOTAL.samples():
            d["dispatch:" + labels.get("site", "?")] = v
        for labels, v in FRAGMENT_DISPATCH.samples():
            d["fragment:" + labels.get("kind", "?")] = v
        return d

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        out = {}
        for k, v in after.items():
            dv = v - before.get(k, 0)
            if dv:
                out[k] = round(dv, 2) if isinstance(dv, float) else dv
        return out


def hbm() -> list:
    """Per device: bytes in use and peak (None where the backend does
    not report, i.e. the CPU rehearsal)."""
    import jax

    out = []
    for d in jax.devices():
        ms = d.memory_stats() or {}
        out.append({"bytes_in_use": ms.get("bytes_in_use"),
                    "peak_bytes_in_use": ms.get("peak_bytes_in_use")})
    return out


def placement_delta(before: dict, after: dict) -> dict:
    out = {}
    for site, by in after.items():
        for plat, n in by.items():
            dn = n - before.get(site, {}).get(plat, 0)
            if dn:
                out.setdefault(site, {})[plat] = dn
    return out


def check_placement(want: str, what: str, by_site: dict, need=()) -> None:
    """Every array the tiers staged or produced lives on `want`, no
    Pallas kernel was traced for the interpreter, and the sites in
    `need` were exercised at all."""
    for site, by in by_site.items():
        bad = {p: n for p, n in by.items() if p != want}
        check(not bad, f"{what}: {site} arrays/kernels on {bad}, "
                       f"expected only {want!r}")
    for site in need:
        check(by_site.get(site), f"{what}: nothing recorded at {site!r} — "
                                 "the path under test did not run")


def resident_platforms(server) -> dict:
    """Where the long-lived device state lives right now: every
    connection's ShardedTable columns and every DeviceBufferCache entry."""
    import jax

    from tidb_tpu.executor.pipeline import DEVICE_CACHE

    def platforms(tree):
        out = {}
        for leaf in jax.tree_util.tree_leaves(tree):
            if isinstance(leaf, jax.Array):
                for d in leaf.devices():
                    out[d.platform] = out.get(d.platform, 0) + 1
        return out

    res = {"sharded_tables": {}, "device_cache": {}}
    for cid, sess in list(server.sessions.items()):
        cache = getattr(sess, "_shard_cache", None)
        if cache is None:
            continue
        for held, st in cache.resident():
            res["sharded_tables"][f"conn{cid}:{held.schema.name}"] = {
                "platforms": platforms((st.data, st.valid, st.sel)),
                "bytes": int(sum(a.nbytes for a in jax.tree_util.tree_leaves(
                    (st.data, st.valid, st.sel)))),
                "rows_per_part": st.rows_per_part, "n_parts": st.n_parts}
    for table, tag, chunks, nbytes in DEVICE_CACHE.resident():
        res["device_cache"][f"{table.schema.name}:{tag[0]}"] = {
            "platforms": platforms(chunks), "bytes": nbytes}
    return res


def check_resident(want: str, res: dict) -> None:
    for kind, entries in res.items():
        for name, e in entries.items():
            bad = {p: n for p, n in e["platforms"].items() if p != want}
            check(not bad, f"{kind} {name} lives on {bad}, expected {want!r}")


# ---------------------------------------------------------------------------
# the served path on one chip
# ---------------------------------------------------------------------------

def timed(client, sql):
    t0 = time.perf_counter()
    _names, rows = client.query(sql)
    return rows, time.perf_counter() - t0


def compare(name, got, want, ordered=True) -> None:
    from tidb_tpu.testutil import rows_equal

    ok, msg = rows_equal(got, want, ordered=ordered, rel_tol=1e-9)
    check(ok, f"{name}: answer differs from the numpy reference: {msg}")


def classic_operators(client, sql) -> list:
    """Operators EXPLAIN ANALYZE marks [classic] (ran the classic
    operator tree instead of a device fragment / fused program)."""
    _n, rows = client.query("explain analyze " + sql)
    return [" ".join(str(v) for v in r if v).strip()[:160]
            for r in rows if any("[classic]" in str(v) for v in r)]


def boot_argv(args) -> list:
    """The default configuration (--mesh auto, status port on) with
    TPC-H preloaded; the rehearsal only shrinks the data and names the
    CPU explicitly."""
    argv = ["--load-tpch", "0.01" if args.rehearse else "1", "--port", "0",
            "--status-port", "0"]
    if args.rehearse:
        argv += ["--device", "cpu"]
    return argv


def run(args, drive) -> dict:
    """Boot the server in this process, drive it, stop it."""
    from tidb_tpu.__main__ import boot  # nothing of jax is imported before
    from tidb_tpu.utils.device import track_placement

    track_placement()
    t0 = time.perf_counter()
    server = boot(boot_argv(args))  # raises without a device
    boot_s = time.perf_counter() - t0
    try:
        return drive(args, server, boot_s)
    finally:
        server.stop()


def _drive(args, server, boot_s) -> dict:
    import urllib.request

    import jax

    from tidb_tpu.server.client import Client
    from tidb_tpu.storage.tpch_queries import Q
    from tidb_tpu.utils.device import placement

    dev = dict(server.device)
    want = dev["platform"]
    if not args.rehearse:
        check(want == "tpu", f"jax found no TPU (platform {want!r})")
        check(dev["count"] == 1, f"the one-chip smoke got {dev['count']} "
                                 "devices; use --chips 4 for the mesh phase")
    with urllib.request.urlopen(
            f"http://{server.host}:{server.status_port}/status",
            timeout=10) as r:
        status = json.loads(r.read())
    check(status.get("platform") == want and status.get("count") == dev["count"],
          f"/status does not name the device: {status}")
    cache_dir = jax.config.jax_compilation_cache_dir
    emit(phase="boot", seconds=round(boot_s, 1), device=dev,
         mesh=str(dict(server.mesh.shape)), status=status,
         compile_cache_dir=cache_dir,
         rows={n: server.catalog.table("test", n).n
               for n in ("lineitem", "orders", "customer")},
         hbm=hbm())

    obs = Observer()
    t0 = time.perf_counter()
    ref = Ref(server.catalog)
    expected = {"q6": ref.q6(), "q1": ref.q1(), "join": ref.join(),
                "q18_inner": ref.q18_inner(), "q3": ref.q3()}
    base_top = ref.top_prices(10)
    emit(phase="reference", seconds=round(time.perf_counter() - t0, 1),
         q6=expected["q6"], join=expected["join"])

    a = Client(server.host, server.port, db="test", timeout=STMT_TIMEOUT_S)
    b = Client(server.host, server.port, db="test", timeout=STMT_TIMEOUT_S)
    if args.rehearse:
        # on an accelerator the device engine is the automatic choice;
        # the CPU rehearsal has to ask for it to walk the same path
        for c in (a, b):
            c.query("set tidb_device_engine_mode = 'force'")
    stmts = [("q6", Q["q6"][0]), ("q1", Q["q1"][0]), ("join", JOIN_SQL),
             ("q18_inner", Q18_INNER_SQL), ("q3", Q3_SQL)]
    classic = {}

    # 1-3: the analytic statements, mesh tier, connection A cold then warm
    for name, sql in stmts:
        p0, c0 = placement(), obs.counters()
        rows, cold = timed(a, sql)
        c1 = obs.counters()
        compare(name, rows, expected[name])
        rows, warm = timed(a, sql)
        c2 = obs.counters()
        compare(name + " (warm)", rows, expected[name])
        pd = placement_delta(p0, placement())
        emit(phase="statement", name=name, conn="A",
             cold_s=round(cold, 3), warm_s=round(warm, 3),
             cold=obs.delta(c0, c1), warm=obs.delta(c1, c2), placement=pd)
        check_placement(want, name, pd, need=("fragment",))
        if name in GENERAL:
            check("fragment:general_generic" in obs.delta(c1, c2),
                  f"{name} did not run as a general fragment "
                  f"(parallel/fragment.py): {obs.delta(c1, c2)}")
            check_one_launch(name, obs.delta(c0, c1))
        if name == "q6":
            emit(phase="hbm", after="connection A first analytic statement",
                 hbm=hbm())
        classic[name] = classic_operators(a, sql)

    # connection B: its own ShardCache, its own whole-table device copy
    for name, sql in stmts[:2]:
        c0 = obs.counters()
        rows, first = timed(b, sql)
        compare(name + " (conn B)", rows, expected[name])
        emit(phase="statement", name=name, conn="B",
             first_s=round(first, 3), first=obs.delta(c0, obs.counters()))
        if name == "q6":
            emit(phase="hbm", after="connection B first analytic statement",
                 hbm=hbm())
    res = resident_platforms(server)
    emit(phase="resident", **res)
    if not args.rehearse:
        check_resident(want, res)

    # 4: the transaction — the fused segment-store tier — and read-back
    key = int(ref.col("orders", "o_orderkey").max()) + 1
    prices = [20000100, 20000200, 20000300]  # scaled 1e2: above every SF1 row
    delta = sum(p * 6 for p in prices) / 1e4  # discount 0.06
    p0, c0 = placement(), obs.counters()
    t0 = time.perf_counter()
    a.query("begin")
    a.query(f"insert into orders values ({key}, 1, 'O', 600006.00, "
            "'1994-06-01', '1-URGENT', 'Clerk#000000001', 0, 'chip smoke')")
    a.query("insert into lineitem values " + ", ".join(
        f"({key}, 1, 1, {i + 1}, 10.00, {p / 100:.2f}, 0.06, 0.02, 'N', 'O', "
        "'1994-06-15', '1994-06-20', '1994-06-25', 'NONE', 'MAIL', "
        "'chip smoke')" for i, p in enumerate(prices)))
    in_txn_want = [(expected["q6"][0][0] + delta,)]
    rows, q6_cold = timed(a, Q6_SHAPED)
    compare("in-txn Q6-shaped aggregate", rows, in_txn_want)
    rows, q6_warm = timed(a, Q6_SHAPED)
    compare("in-txn Q6-shaped aggregate (warm)", rows, in_txn_want)
    top_want = sorted(prices + base_top, reverse=True)[:10]
    rows, topn_cold = timed(a, TOPN_SQL)
    compare("in-txn ORDER BY LIMIT (prices)", [(r[0],) for r in rows],
            [(p / 1e2,) for p in top_want])
    check([int(r[1]) for r in rows[:3]] == [key] * 3,
          f"in-txn ORDER BY LIMIT must lead with the txn's own rows: {rows[:3]}")
    rows, topn_warm = timed(a, TOPN_SQL)
    classic["txn_q6"] = classic_operators(a, Q6_SHAPED)
    classic["txn_topn"] = classic_operators(a, TOPN_SQL)
    a.query("commit")
    pd = placement_delta(p0, placement())
    emit(phase="transaction", seconds=round(time.perf_counter() - t0, 1),
         q6_cold_s=round(q6_cold, 3), q6_warm_s=round(q6_warm, 3),
         topn_cold_s=round(topn_cold, 3), topn_warm_s=round(topn_warm, 3),
         counters=obs.delta(c0, obs.counters()), placement=pd)
    check_placement(want, "transaction (fused segment-store tier)", pd,
                    need=("stage", "fused"))
    # the acknowledged write, read back on the OTHER connection
    _n, rows = b.query(
        f"select o_orderkey, o_totalprice from orders where o_orderkey = {key}")
    compare("read-back orders", rows, [(key, 600006.00)])
    for i, p in enumerate(prices):  # by primary key: (orderkey, linenumber)
        _n, rows = b.query(
            "select l_linenumber, l_extendedprice from lineitem "
            f"where l_orderkey = {key} and l_linenumber = {i + 1}")
        compare("read-back lineitem", rows, [(i + 1, p / 1e2)])
    c0 = obs.counters()
    rows, q6_after = timed(b, Q["q6"][0])
    compare("Q6 after commit (conn B)", rows, in_txn_want)
    emit(phase="read_back", key=key, q6_after_commit_s=round(q6_after, 3),
         counters=obs.delta(c0, obs.counters()), hbm=hbm())

    # 5: primary-key point get — host path, and it says so
    c0 = obs.counters()
    rows, pg = timed(b, "select o_orderkey, o_custkey from orders "
                        "where o_orderkey = 1")
    d = obs.delta(c0, obs.counters())
    check(len(rows) == 1 and int(rows[0][0]) == 1, f"point get: {rows}")
    emit(phase="point_get", seconds=round(pg, 4),
         device_dispatches={k: v for k, v in d.items()
                            if k.startswith("dispatch:")},
         note="host path: no device program or transfer"
         if not any(k.startswith("dispatch:") for k in d) else
         "dispatched to the device")

    res = resident_platforms(server)
    if not args.rehearse:
        check_resident(want, res)
    all_placement = placement()
    emit(phase="summary", classic_operators=classic,
         placement=all_placement, compiles=obs.compiles,
         compile_s=round(obs.compile_s, 1), cache_hits=obs.cache_hits,
         cache_misses=obs.cache_misses, hbm=hbm(),
         total_s=round(time.time() - T0, 1))
    if not args.rehearse:
        check(all_placement.get("pallas", {}).get("tpu"),
              "no Pallas kernel was compiled for the TPU on the smoke's path")
    a.close()
    b.close()
    return dev


# ---------------------------------------------------------------------------
# four chips: the mesh tier only, 1x4 against 1x1
# ---------------------------------------------------------------------------

def _drive_mesh4(args, server, boot_s) -> dict:
    import jax

    from tidb_tpu.parallel import make_mesh
    from tidb_tpu.server.client import Client
    from tidb_tpu.session import Session
    from tidb_tpu.storage.tpch_queries import Q

    dev = dict(server.device)
    if not args.rehearse:
        check(dev["platform"] == "tpu", f"jax found no TPU: {dev}")
    check(dev["count"] >= 4, f"--chips 4 needs four devices, jax has {dev}")
    n_shards = dict(server.mesh.shape)["shard"]
    check(n_shards == dev["count"],
          f"--mesh auto built {dict(server.mesh.shape)} over {dev['count']} devices")
    emit(phase="boot", seconds=round(boot_s, 1), device=dev,
         mesh=str(dict(server.mesh.shape)), hbm=hbm())
    obs = Observer()
    ref = Ref(server.catalog)
    stmts = [("q1", Q["q1"][0], ref.q1()), ("join", JOIN_SQL, ref.join()),
             ("q18_inner", Q18_INNER_SQL, ref.q18_inner())]
    c = Client(server.host, server.port, db="test", timeout=STMT_TIMEOUT_S)
    got4 = {}
    if args.rehearse:
        c.query("set tidb_device_engine_mode = 'force'")  # see _drive
    for name, sql, want in stmts:
        c0 = obs.counters()
        rows, cold = timed(c, sql)
        compare(f"{name} on 1x{n_shards}", rows, want)
        c1 = obs.counters()
        rows, warm = timed(c, sql)
        got4[name] = rows
        warm_d = obs.delta(c1, obs.counters())
        emit(phase="statement", name=name, mesh=f"1x{n_shards}",
             cold_s=round(cold, 3), warm_s=round(warm, 3),
             cold=obs.delta(c0, c1), warm=warm_d)
        if name == "q18_inner":
            check("fragment:general_generic" in warm_d,
                  f"Q18's inner aggregate ran no general fragment: {warm_d}")
            check_one_launch(name, obs.delta(c0, c1))

    # placement: a quarter of every column on each device, HBM balanced
    (sess,) = server.sessions.values()
    shares = {}
    for held, st in sess._shard_cache.resident():
        for col, arr in list(st.data.items()) + [("<sel>", st.sel)]:
            per = {}
            for sh in arr.addressable_shards:
                per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
            check(len(per) == n_shards and len(set(per.values())) == 1
                  and sum(per.values()) == arr.nbytes,
                  f"{held.schema.name}.{col}: shards {per} of {arr.nbytes} "
                  f"bytes are not an equal split over {n_shards} devices")
        shares[held.schema.name] = {
            "columns": len(st.data), "rows_per_part": st.rows_per_part,
            "bytes_per_device": int(sum(
                a.nbytes for a in jax.tree_util.tree_leaves(
                    (st.data, st.valid, st.sel))) // n_shards)}
    mem = hbm()
    used = [m["bytes_in_use"] for m in mem]
    emit(phase="placement", tables=shares, hbm=mem)
    if not args.rehearse:
        check(all(u for u in used) and max(used) <= 1.2 * min(used),
              f"per-device bytes in use differ by more than 20%: {used}")

    # the same statements on a 1x1 mesh over the first device, same process
    one = Session(catalog=server.catalog,
                  mesh=make_mesh(devices=jax.devices()[:1]))
    one.execute("use test")
    if args.rehearse:
        one.execute("set tidb_device_engine_mode = 'force'")  # see _drive
    for name, sql, want in stmts:
        t0 = time.perf_counter()
        rows = one.query(sql)
        secs = time.perf_counter() - t0
        compare(f"{name} on 1x1", rows, want)
        compare(f"{name}: 1x{n_shards} vs 1x1", got4[name], rows)
        emit(phase="statement", name=name, mesh="1x1", cold_s=round(secs, 3))
    emit(phase="summary", compiles=obs.compiles,
         compile_s=round(obs.compile_s, 1), cache_hits=obs.cache_hits,
         hbm=hbm(), total_s=round(time.time() - T0, 1))
    c.close()
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: run only the mesh phase on four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="SF0.01 on the CPU; ends with \"ok\": false")
    args = ap.parse_args(argv)
    try:
        dev = run(args, _drive_mesh4 if args.chips == 4 else _drive)
    except BaseException:  # noqa: BLE001 — every failure is a failed smoke
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    device = {"platform": dev["platform"], "kind": dev["device_kind"],
              "count": dev["count"]}
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "device": device}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
