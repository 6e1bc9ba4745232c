"""The writer beside the query streams, rehearsed on the CPU at SF0.01:
``tpch_sf1.refresh`` drives two query streams and one RF1-shaped writer
through the wire and every answer equals the numpy reference after one
of the commits it could have seen; the refresh generator keeps clause
4.2.3's rules; the admissible-k rule, case by case, on answers made by
hand; faults planted under the timed path each turn ``correct`` false by
the check that names them; the spans of the writer's statements stay out
of the query streams' means. Nothing here gives a speed."""

import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import control, program_spans, reference, run, spec, tpch_datagen, traffic  # noqa: E402

SF = 0.01
FORCE = ("set tidb_device_engine_mode = 'force'",)  # the CPU must ask for the device engine
CELL = "tpch_sf1.refresh"
WRITE_CHECKS = ["stale_answers", "failed_writes", "unread_acknowledged_rows",
                "unwritten_window", "unseen_writes"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The benchmark with the cell in it. ``BENCHMARK.json`` does not hold
    the cell yet (``benchmarks/not_admitted/tpch_sf1.refresh.json`` says
    why: its runs spread too widely on the program as it stands); that
    file's entries, added to a copy and nothing else touched, are all it
    takes: every file they name is in place."""
    root = str(tmp_path_factory.mktemp("with_refresh"))
    shutil.copytree(os.path.join(ROOT, "benchmarks"), os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "not_admitted", CELL + ".json")) as f:
        entries = json.load(f)
    for group in ("workloads", "end_to_end", "per_layer"):
        assert entries[group]
        bench[group] += entries[group]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def rehearse(root, seed=27, seconds=8.0, trace=False):
    cell = spec.Cell(CELL, root=root)
    return cell, run.run_cell(cell, seed, seconds, trace, require_chip=False,
                              sf=SF, pre_sql=FORCE)


@pytest.fixture(scope="module")
def traced(root):
    """One traced rehearsal, with what the span readers were given."""
    seen = {}
    real = program_spans._split

    def spy(ctx):
        seen["ctx"], seen["split"] = ctx, real(ctx)
        return seen["split"]

    program_spans._split = spy
    try:
        cell, res = rehearse(root, seed=2**31 + 27, trace=True)
    finally:
        program_spans._split = real
    return cell, res, seen


@pytest.fixture(scope="module")
def untraced(root):
    return rehearse(root)


# -- the cell ----------------------------------------------------------------

def test_the_cell_is_the_scan_cells_traffic_and_one_writer(root):
    mix, scan = spec.Cell(CELL, root=root).traffic, spec.Cell("tpch_sf1.scan").traffic
    assert mix["menu"] == scan["menu"] and mix["streams"] == scan["streams"] == 2
    assert mix["warm_passes"] == scan["warm_passes"] == 2 and mix["loop"] == "closed"
    assert traffic.writers(mix) == [{"statement": "rf1", "warm_transactions": 1,
                                     "params": {"orders_per_transaction": 100}}]
    assert traffic.writers(scan) == []
    for seed in (3, 2**31 + 11):  # the writer changes no query stream's order
        assert traffic.stream_orders(mix, seed) == traffic.stream_orders(scan, seed)
    with pytest.raises(ValueError):
        traffic.writers({"writers": mix["writers"] * 2})


def test_every_answer_equals_the_reference_after_a_commit_it_could_have_seen(untraced):
    cell, res = untraced
    assert res["correct"] is True and res["failed"] == 0
    checks = res["checks"]
    for name in ["exact_mismatches", "missing_answers", "wrong_statements"] + WRITE_CHECKS:
        assert checks[name] == {"value": 0, "limit": 0}, name
    assert checks["float_rel_gap"]["value"] <= reference.FLOAT_REL_LIMIT
    did = checks["compared"]
    assert did["transactions"] >= 3 and did["first_k"] == 1
    assert res["attempted"] == did["statements"] + did["transactions"]
    # answers matched after two or more different commits, none before set-up's
    assert len(did["answers_by_k"]) >= 2 and min(did["answers_by_k"]) >= 1
    assert sum(did["answers_by_k"].values()) == did["statements"]


def test_the_result_line_has_the_writers_metric_and_checks_last(untraced):
    cell, res = untraced
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert set(res["metrics"]) == {"rows_per_s", "stmt_p50_ms", "setup_s", "refresh_p50_ms"}
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end()}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert list(res["checks"]) == ["exact_mismatches", "float_rel_gap", "missing_answers",
                                   "wrong_statements"] + WRITE_CHECKS + ["compared"]
    json.dumps(res)


def test_a_traced_run_reports_the_writers_layers(traced):
    cell, res, _seen = traced
    assert res["correct"] is True
    assert set(res["metrics"]) == {m["name"] for m in cell.per_layer()} - {
        "device_idle_pct", "device_ms_per_stmt", "scan_agg_roofline"}  # no TPU plane here
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # every commit moves lineitem's version: the next statement of each
    # connection stages the whole table again and compiles for its row count
    assert m["stage_uploads_per_stmt"] > 1 and m["window_compiles"] >= 2
    assert m["refresh_lock_wait_ms"] > 0
    assert not set(res["metrics"]) & {m["name"] for m in cell.end_to_end()}


def test_the_writers_spans_stay_out_of_the_query_streams_means(traced):
    _cell, res, seen = traced
    queries, writer = seen["split"]
    did = res["checks"]["compared"]
    assert len(queries) == did["statements"]
    assert len(writer) == 4 * did["transactions"]  # BEGIN, two INSERTs, COMMIT
    kinds = [{n for n in t.self_us_by_name() if n.startswith("stmt.")} for t in writer]
    assert all(k <= {"stmt.begin", "stmt.insert", "stmt.commit"} for k in kinds)
    assert all("stmt.select" in t.self_us_by_name() for t in queries)
    # the five still partition what the query clients measured
    lat = [(r["t_done"] - r["t_send"]) / 1e6 for r in seen["ctx"].records]
    five = sum(res["metrics"][n]["value"] for n in (
        "queue_ms_per_stmt", "wire_ms_per_stmt", "plan_ms_per_stmt",
        "exec_host_ms_per_stmt", "device_wait_ms_per_stmt"))
    assert abs(five - sum(lat) / len(lat)) <= max(0.05 * sum(lat) / len(lat), 2.0)


# -- the refresh generator ---------------------------------------------------

def test_refresh_set_is_the_seeds_and_keeps_the_specs_rules():
    a, b, c = (tpch_datagen.refresh_set(SF, s, 2, 100) for s in (2**31 + 11, 2**31 + 11, 12))
    for table in ("orders", "lineitem"):
        for col in a[table][0]:
            assert np.array_equal(a[table][0][col], b[table][0][col])
    assert not np.array_equal(a["orders"][0]["o_totalprice"], c["orders"][0]["o_totalprice"])
    base = tpch_datagen.generate(SF, 12)
    assert a["lineitem"][1] == base["lineitem"][1] and a["orders"][1] == base["orders"][1]
    li, od = c["lineitem"][0], c["orders"][0]
    per_order = np.bincount(li["l_orderkey"] - od["o_orderkey"][0])
    assert len(per_order) == len(od["o_orderkey"]) == 100
    assert per_order.min() >= 1 and per_order.max() <= 7
    assert 100 <= li["l_quantity"].min() and li["l_quantity"].max() <= 5000
    assert li["l_discount"].max() <= 10 and li["l_tax"].max() <= 8
    assert (li["l_extendedprice"] == li["l_quantity"] // 100
            * tpch_datagen.retail_price(li["l_partkey"])).all()
    assert li["l_partkey"].max() <= tpch_datagen.sizes(SF)["part"]
    odate = np.repeat(od["o_orderdate"], per_order)
    assert ((li["l_shipdate"] > odate) & (li["l_shipdate"] <= odate + 121)).all()
    charge = li["l_extendedprice"] * (100 - li["l_discount"]) * (100 + li["l_tax"]) // 10000
    assert np.array_equal(
        np.bincount(li["l_orderkey"] - od["o_orderkey"][0], weights=charge).astype(np.int64),
        od["o_totalprice"])


def test_refresh_keys_never_meet_the_loaded_ones_and_row_counts_are_not_pinned():
    assert tpch_datagen.refresh_set(1.0, 5, 0, 100)["orders"][0]["o_orderkey"][0] == 1_500_001
    loaded = tpch_datagen.generate(SF, 5)["orders"][0]["o_orderkey"]
    keys, lines = [], set()
    for k in range(6):
        r = tpch_datagen.refresh_set(SF, 5, k, 100)
        keys.append(r["orders"][0]["o_orderkey"])
        lines.add(len(r["lineitem"][0]["l_orderkey"]))
        assert set(r["lineitem"][0]["l_orderkey"]) == set(keys[-1])
    keys = np.concatenate(keys)
    assert len(set(keys)) == 600 and keys.min() == loaded.max() + 1
    assert np.array_equal(keys, np.arange(keys.min(), keys.min() + 600))  # dense
    assert len(lines) >= 5  # a table that is written does not repeat its row counts
    assert lines != {len(tpch_datagen.refresh_set(SF, 6, k, 100)["lineitem"][0]["l_orderkey"])
                     for k in range(6)}


# -- the reference follows the commits --------------------------------------

@pytest.fixture(scope="module")
def versions(root):
    cell = spec.Cell(CELL, root=root)
    data = reference.Data(tpch_datagen.generate(SF, 9))
    ver, _w = run.versions(cell, data, SF, 9)
    return cell, data, ver


@pytest.mark.parametrize("item", [0, 1, 2, 3])
def test_added_states_equal_a_pass_over_the_appended_arrays(versions, item):
    cell, _data, ver = versions
    entry = cell.traffic["menu"][item]
    mod = cell.statements[entry["statement"]]
    seen = set()
    for k in range(4):
        want = control.to_wire(mod.reference(ver.data(k), entry["params"]))
        assert control.to_wire(ver.answer(item, k)) == want
        seen.add(json.dumps(want))
    assert len(seen) == 4  # every transaction moves every answer of this menu
    assert ver.rows("lineitem", 3) == ver.data(3).rows("lineitem")
    assert ver.total("orders", "o_totalprice", 3) == int(
        ver.data(3).col("orders", "o_totalprice").sum())


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float32_control_comes_out_not_correct(root, seed):
    out = control.control_run(spec.Cell(CELL, root=root), seed, sf=SF)
    assert out["correct"] is False
    assert out["checks"]["exact_mismatches"]["value"] >= 1
    assert out["checks"]["unread_acknowledged_rows"]["value"] >= 1  # float32 sums read back
    assert out["checks"]["compared"]["first_k"] == 1


def _answer(ver, item, k, t_send, t_done):
    return {"item": item, "rows": control.to_wire(ver.answer(item, k)), "error": None,
            "t_send": t_send, "t_done": t_done}


def _write(k, t_send, t_commit_send, t_ack):
    return {"k": k, "t_send": t_send, "t_commit_send": t_commit_send, "t_ack": t_ack,
            "error": None, "stmts": [(t_send, t_ack)]}


# one warm transaction (k=0); in the window k=1 is acknowledged at 20 and
# k=2 at 40, their COMMITs sent at 15 and 35
WRITES = [_write(1, 10, 15, 20), _write(2, 30, 35, 40)]


@pytest.mark.parametrize("k,t_send,t_done,verdict", [
    (1, 5, 9, "right"),     # nothing of the window yet
    (1, 12, 16, "right"),   # the COMMIT was sent before the answer: may not have landed
    (2, 12, 16, "right"),   # ... or may have
    (3, 22, 36, "right"),   # sent after k=1's ack, answered after k=2's COMMIT went out
    (2, 22, 36, "right"),
    (1, 22, 36, "stale"),   # k=1's transaction was acknowledged before the send
    (1, 45, 50, "stale"),
    (2, 45, 50, "stale"),
    (0, 5, 9, "stale"),     # set-up's transaction counts as acknowledged
    (3, 12, 16, "wrong"),   # no COMMIT of k=2 had been sent: a dirty read
    (2, 5, 9, "wrong"),
])
def test_the_admissible_k_rule(versions, k, t_send, t_done, verdict):
    cell, data, ver = versions
    records = [_answer(ver, 1, k, t_send, t_done)]
    written = {"first_k": 1, "writes": WRITES, "read_back": {}}
    checks = run.check_answers(cell, ver, records, written)
    assert records[0]["ok"] is (verdict == "right")
    assert checks["stale_answers"]["value"] == (verdict == "stale")
    assert (checks["exact_mismatches"]["value"] > 0) == (verdict == "wrong")
    assert checks["wrong_statements"]["value"] == (verdict != "right")
    if verdict == "right":
        assert records[0]["k"] == k


def test_a_transaction_seen_in_part_matches_no_k(versions):
    cell, data, ver = versions
    rows = ver.refresh(1)
    half = {"orders": rows["orders"],
            "lineitem": ({c: a[:len(a) // 2] for c, a in rows["lineitem"][0].items()},
                         rows["lineitem"][1])}
    torn = ver.data(1).plus(half)
    entry = cell.traffic["menu"][1]
    got = control.to_wire(cell.statements["q1"].reference(torn, entry["params"]))
    records = [{"item": 1, "rows": got, "error": None, "t_send": 12, "t_done": 45}]
    checks = run.check_answers(cell, ver, records, {
        "first_k": 1, "writes": WRITES, "read_back": {}})
    assert records[0]["ok"] is False and checks["stale_answers"]["value"] == 0
    assert checks["exact_mismatches"]["value"] > 0


@pytest.mark.parametrize("short,sum_off,want", [(0, 0, 0), (7, 0, 7), (0, 5, 1), (-3, 0, 3)])
def test_the_read_back_counts_rows_not_read(versions, short, sum_off, want):
    _cell, _data, ver = versions
    back = {}
    for table, (column, _scale) in ver.write.READ_BACK.items():
        n = ver.rows(table, 2) - (short if table == "lineitem" else 0)
        units = ver.total(table, column, 2) - (sum_off if table == "orders" else 0)
        back[table] = [(str(n), str(reference.Exact(units, 2).decimal()))]
    assert run.unread_rows(ver, back, 2) == want
    assert run.unread_rows(ver, {"orders": "TimeoutError: timed out"}, 2) == (
        ver.rows("orders", 2) + ver.rows("lineitem", 2))


# -- faults under the timed path: correct has to come out false --------------

def _nth(pred, n, then):
    """A Client.query that, on the n-th statement `pred` accepts, does `then`."""
    from tidb_tpu.server.client import Client

    real, seen = Client.query, {"n": 0}

    def query(self, sql):
        if pred(sql):
            seen["n"] += 1
            if seen["n"] == n:
                return then(real, self, sql)
        return real(self, sql)

    return query


def _drop(real, self, sql):
    real(self, "rollback")  # the client is told OK; nothing was committed
    return [], []


def _half(real, self, sql):
    head, _, values = sql.partition(" values ")
    rows = values.split("), (")
    return real(self, head + " values " + "), (".join(rows[:len(rows) // 2]) + ")")


def _refuse(real, self, sql):
    from tidb_tpu.server.client import ServerError

    raise ServerError(1105, "planted: the COMMIT errors")


def _stale_shards(monkeypatch):
    """Every connection keeps serving the lineitem copy it staged first
    (in set-up, after set-up's transaction): no later commit is read."""
    from tidb_tpu.parallel.executor import ShardCache

    real, first = ShardCache.get, {}

    def get(self, table, encode=False):
        if table.schema.name != "lineitem":
            return real(self, table, encode)
        if id(self) not in first:
            first[id(self)] = (self, real(self, table, encode))
        return first[id(self)][1]

    monkeypatch.setattr(ShardCache, "get", get)


@pytest.mark.parametrize("fault,named", [
    ("stale_shard", "stale_answers"),
    ("dropped_commit", "unread_acknowledged_rows"),
    ("half_a_transaction", "exact_mismatches"),
    ("commit_errors", "failed_writes"),
])
def test_a_planted_fault_fails_the_run_by_the_check_that_names_it(monkeypatch, root, fault, named):
    from tidb_tpu.server.client import Client

    if fault == "stale_shard":
        _stale_shards(monkeypatch)
    else:  # the 2nd of the run: the window's first (set-up sends one)
        pred, then = {
            "dropped_commit": (lambda s: s == "commit", _drop),
            "half_a_transaction": (lambda s: s.startswith("insert into lineitem"), _half),
            "commit_errors": (lambda s: s == "commit", _refuse)}[fault]
        monkeypatch.setattr(Client, "query", _nth(pred, 2, then))
    _cell, res = rehearse(root, seed=31, seconds=6.0)
    assert res["correct"] is False and res["failed"] > 0
    assert res["checks"][named]["value"] > 0
    if fault == "stale_shard":  # what was read is a committed state, only an old one
        assert res["checks"]["exact_mismatches"]["value"] == 0
        assert res["checks"]["unread_acknowledged_rows"]["value"] == 0
    if fault == "commit_errors":  # the error ends the writer's stream
        assert res["checks"]["compared"]["transactions"] == 0
        assert res["checks"]["unwritten_window"]["value"] == 1


def test_a_window_the_writer_never_got_into_is_not_correct(monkeypatch, root):
    monkeypatch.setattr(run, "_writer", lambda *a: None)
    _cell, res = rehearse(root, seed=32, seconds=1.0)
    assert res["correct"] is False and res["failed"] == 0
    assert res["checks"]["unwritten_window"]["value"] == 1
    assert res["checks"]["unseen_writes"]["value"] == 1
    assert "refresh_p50_ms" not in res["metrics"] and "rows_per_s" in res["metrics"]
