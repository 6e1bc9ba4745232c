"""The cell ``tpch_sf1_power.q18`` rehearsed on the CPU from exactly the
files and entries PR 35 added (a configuration, a traffic mix, a
statement, two readers): every answer of a window equals the numpy reference,
the result line keeps the contract's keys, a traced run reports the
per-layer metrics that need no device — among them
``subquery_host_ms_per_stmt`` 0.0 on the deployment's mesh of one part
and more than 0 where the subquery goes through the host —
``subqjoin_roofline`` reads the bytes worked out by hand at SF1 shapes,
faults planted under the timed path turn ``correct`` false, the control
in lower precision fails, and the reference agrees with a second, slower
formulation. Statement counts, never seconds: nothing here gives a speed.

The deployment is ONE chip (a 1x1 mesh); the tests' process has eight
CPU devices, so the rehearsal hands the harness a mesh of one of them —
here, in the test, not through an option of the program or the harness."""

import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import control, reference, run, spec, system, tpch_datagen, work  # noqa: E402

CELL = "tpch_sf1_power.q18"
CONFIG = "tpch_sf1_power_q18"
FORCE = ("set tidb_device_engine_mode = 'force'",)  # the CPU must ask for the device engine
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
PARAMS = {"quantity": 300}
SEED = 12  # four orders pass 300 at SF0.05, eight at SF0.1


def rehearse(seed=SEED, seconds=1.5, trace=False, sf=0.05, parts=1):
    """One run of the cell on a mesh of `parts` CPU devices."""
    import jax

    import tidb_tpu.parallel as par

    real = par.make_mesh
    cell = spec.Cell(CELL)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(par, "make_mesh",
                   lambda: real(devices=jax.devices()[:parts]))
        return cell, run.run_cell(cell, seed, seconds, trace, require_chip=False,
                                  sf=sf, pre_sql=FORCE)


@pytest.fixture(scope="module")
def plain():
    return rehearse(sf=0.1, seconds=2.5)


@pytest.fixture(scope="module")
def traced():
    return rehearse(trace=True)


def test_the_cell_is_made_of_new_files_and_appended_entries_only():
    """By name and by what stands BEFORE them, not by being last: the
    next cell is appended after these."""
    bench = spec.load_benchmark()
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    metrics = [m["name"] for m in bench["per_layer"]]
    # a configuration of its own (a model_config PR brings one): the
    # accepted tpch_sf1_power's node, layout and scale under Q18's source
    assert configs[:5] == ["tpch_sf1", "tpch_sf1_mesh4", "tpch_sf1_pk", "tpch_sf1_power",
                           CONFIG]
    assert bench["configs"][4] == {
        "name": CONFIG, "source": bench["configs"][4]["source"],
        "file": "benchmarks/configs/tpch_sf1_power_q18.json",
        "reduced": ["scale_factor"], "why": bench["configs"][4]["why"]}
    assert "2.4.18" in bench["configs"][4]["source"] and "5.3.3" in bench["configs"][4]["source"]
    assert all(len(bench["configs"][4][k]) <= 200 for k in ("source", "why"))
    assert bench["configs"][4]["source"] not in [c["source"] for c in bench["configs"][:4]]
    assert cells[:6] == ["tpch_sf1.scan", "tpch_sf1.join", "tpch_sf1_mesh4.join",
                         "tpch_sf1_pk.q18agg", "tpch_sf1_power.q3", CELL]
    assert metrics[:21][-3:] == ["joingroup_roofline", "subqjoin_roofline",
                                 "subquery_host_ms_per_stmt"]
    assert bench["workloads"][5] == {
        "name": CELL, "config": CONFIG, "traffic": "q18", "chips": 1,
        "why": bench["workloads"][5]["why"]}
    assert len(bench["workloads"][5]["why"]) <= 200
    assert bench["per_layer"][19] == {
        "name": "subqjoin_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels", "moves": "stmt_p50_ms",
        "workloads": [CELL]}
    assert bench["per_layer"][20] == {
        "name": "subquery_host_ms_per_stmt", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "plan and engine routing",
        "moves": "stmt_p50_ms", "workloads": [CELL]}
    # nothing that stood before them moved: Q3's entries as PR 32 left them
    assert bench["workloads"][4]["traffic"] == "q3"
    assert bench["per_layer"][18]["workloads"] == ["tpch_sf1_power.q3"]
    cell, q3 = spec.Cell(CELL), spec.Cell("tpch_sf1_power.q3")
    # what the harness reads of a configuration, and what the deployment
    # is, are the accepted tpch_sf1_power's; its own are the name, the
    # source, Q18's guarantees and what the file says Q18 holds and assumes
    own = {"name", "source", "deployment", "guarantees", "on_device", "assumed"}
    assert {k for k in cell.config if cell.config[k] != q3.config.get(k)} == own
    assert set(cell.config) == set(q3.config) and cell.config["name"] == CONFIG
    assert cell.config["source"] == bench["configs"][4]["source"]
    assert cell.config["chips"] == 1 and cell.config["cluster_by"] == {}
    assert cell.config["scale_factor"] == 1.0 and cell.config["reduced"] == ["scale_factor"]
    for g in ("arithmetic", "answers", "isolation"):
        assert cell.config["guarantees"][g] == q3.config["guarantees"][g]
    assert "first 100 rows of the total order" in cell.config["guarantees"]["order"]
    assert "NULL key matches nothing" in cell.config["guarantees"]["subquery"]
    assert "no guarantee of the deployment" in cell.config["assumed"]["client_patience"]
    assert cell.traffic["streams"] == 1 and cell.traffic["warm_passes"] == 1
    assert cell.traffic["loop"] == "closed" and cell.traffic["trace_seconds"] == 14
    assert [(m["statement"], m["params"]) for m in cell.traffic["menu"]] == [
        ("q18", PARAMS)]
    assert "2.4.18" in cell.traffic["source"] and "5.3.3" in cell.traffic["source"]
    assert "first 100 rows of the total order" in cell.traffic["guarantees"]["order"]
    assert "o_orderkey appended" in cell.traffic["assumed"]["statement"]
    assert [m["name"] for m in cell.end_to_end()] == ["rows_per_s", "stmt_p50_ms",
                                                      "setup_s"]
    reported = {m["name"] for m in cell.per_layer()}
    assert {"subqjoin_roofline", "subquery_host_ms_per_stmt"} <= reported
    # every accepted per-layer metric without a list of cells is this cell's too
    assert {m["name"] for m in bench["per_layer"] if "workloads" not in m} <= reported
    assert not reported & {"scan_agg_roofline", "join_roofline", "groupagg_roofline",
                           "joingroup_roofline", "exchange_ms_per_stmt",
                           "fragment_launches_per_stmt", "group_finalize_ms_per_stmt"}


def test_the_clients_patience_is_the_repos_rule_for_a_cold_statement():
    """600 s, no more (the parent's 14-18 minute cold statement must fail
    under it, by itself) and no less (the change's cold statement keeps
    4x of room), with the note that says whose number it is."""
    cell = spec.Cell(CELL)
    assert cell.traffic["statement_timeout_s"] == 600
    note = cell.traffic["timeout_note"]
    assert "client's patience" in note and "no guarantee of the deployment" in note
    assert "cannot run this cell" in note
    assert spec.Cell("tpch_sf1_power.q3").traffic["statement_timeout_s"] == 1100


def test_the_statement_names_its_tables_columns_and_roofline():
    mod = spec.Cell(CELL).statements["q18"]
    # lineitem once, though the statement names it twice: the rate reads beside q3's
    assert mod.TABLES == ("customer", "orders", "lineitem")
    assert mod.ROOFLINE == "subqjoin_roofline" and mod.LIMIT == 100
    assert mod.COLUMNS == {
        "customer": ("c_custkey", "c_name"),
        "orders": ("o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"),
        "lineitem": ("l_orderkey", "l_quantity")}
    text = mod.sql({"quantity": 313})
    assert "having sum(l_quantity) > 313)" in text and text.count("lineitem") == 2
    assert text.endswith("order by o_totalprice desc, o_orderdate, o_orderkey limit 100")
    with pytest.raises(ValueError):
        mod.sql({"quantity": "300) or (1=1"})


def test_every_answer_of_the_window_equals_the_reference(plain):
    cell, res = plain
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 2
    checks = res["checks"]
    assert checks["exact_mismatches"] == {"value": 0, "limit": 0}
    assert checks["missing_answers"]["value"] == 0
    assert checks["wrong_statements"]["value"] == 0
    assert checks["compared"]["statements"] == res["attempted"]
    assert checks["compared"]["cells"] == 8 * 6 * res["attempted"]  # eight rows of six


def test_result_line_has_the_contracts_keys(plain):
    cell, res = plain
    assert set(res) == RESULT_KEYS and list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"rows_per_s", "stmt_p50_ms", "setup_s"}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # the rows a statement addresses: its three tables whole, lineitem once
    n = tpch_datagen.sizes(1.0)
    assert n["customer"] + n["orders"] + n["lineitem"] == 7_651_215
    json.dumps(res)


@pytest.mark.parametrize("name,want", [
    # one fragment, the fetch of its group table, the projection and the
    # sort keys of the host's top 100: the subquery adds no launch
    ("dispatches_per_stmt", 4.0),
    ("window_compiles", 0.0),
    ("stage_uploads_per_stmt", 0.0),
    # the window has traces and none holds `fragment.broadcast`: 0.0, not nothing
    ("subquery_host_ms_per_stmt", 0.0),
])
def test_a_traced_run_reports_the_counts_that_need_no_device(traced, name, want):
    cell, res = traced
    assert res["correct"] is True
    assert res["metrics"][name]["value"] == want
    # no TPU plane in a CPU trace: no CPU number under a device metric's name
    assert not {"subqjoin_roofline", "device_ms_per_stmt",
                "device_idle_pct"} & set(res["metrics"])


def test_the_span_metrics_partition_the_latency(traced):
    _cell, res = traced
    five = [res["metrics"][n]["value"] for n in (
        "queue_ms_per_stmt", "wire_ms_per_stmt", "plan_ms_per_stmt",
        "exec_host_ms_per_stmt", "device_wait_ms_per_stmt")]
    assert all(v > 0 for v in five)
    assert res["metrics"]["setup_compile_s"]["value"] > 0


def test_a_subquery_through_the_host_reads_more_than_nothing():
    """On a mesh of several parts the program answers the subquery as a
    statement of its own (`fragment.broadcast`): the reader then reads its
    fetch, decode, filter and upload, a part of `exec_host` and
    `device_wait`, and the launches show in `dispatches_per_stmt`. (The
    parent's program did so on one part and had no such span: it reads
    0.0 here and 52 dispatches a statement.)"""
    _cell, res = rehearse(trace=True, parts=2, seconds=1.0)
    assert res["correct"] is True
    host = res["metrics"]["subquery_host_ms_per_stmt"]["value"]
    assert host > 0
    assert host < (res["metrics"]["exec_host_ms_per_stmt"]["value"]
                   + res["metrics"]["device_wait_ms_per_stmt"]["value"])
    assert res["metrics"]["dispatches_per_stmt"]["value"] > 4.0


def test_the_reader_sums_the_span_and_everything_beneath_it():
    """By hand, on a trace of its own: two broadcasts (one with a child
    that has a child), a sibling that is neither; self times add up to the
    broadcasts' durations. A program without the ring reads nothing."""
    from tidb_tpu.utils import tracing

    from benchmarks.layer_metrics import subquery_host_ms_per_stmt as reader

    tr = tracing.Trace("q18-test")
    t0 = tr.t0_perf
    root = tr.add_complete("wire.stmt", t0, 1.0)
    b1 = tr.add_complete("fragment.broadcast", t0 + 0.1, 0.3, root.span_id)
    f1 = tr.add_complete("dispatch.fetch", t0 + 0.15, 0.1, b1.span_id)
    tr.add_complete("device.wait", t0 + 0.16, 0.05, f1.span_id)
    tr.add_complete("fragment.broadcast", t0 + 0.5, 0.1, root.span_id)
    tr.add_complete("fragment.finalize", t0 + 0.7, 0.2, root.span_id)
    assert reader.subtree_self_us(tr) == 400_000
    plain_trace = tracing.Trace("q3-test")
    plain_trace.add_complete("wire.stmt", plain_trace.t0_perf, 1.0)
    assert reader.subtree_self_us(plain_trace) == 0

    class Ctx:
        records, writes = [], ()

    assert reader.read(Ctx()) is None  # no statement in the window: nothing to read


def sf1_shapes() -> dict:
    """The three tables as one chip holds them at SF1: int64 keys,
    decimals and plain integers, int32 dates and dictionary codes, a
    validity byte a value and a selection byte a row."""
    n = tpch_datagen.sizes(1.0)
    cols = {"customer": {"c_custkey": "int64", "c_name": "int32",
                         "c_acctbal": "int64"},
            "orders": {"o_orderkey": "int64", "o_custkey": "int64",
                       "o_orderdate": "int32", "o_totalprice": "int64",
                       "o_shippriority": "int64"},
            "lineitem": {"l_orderkey": "int64", "l_quantity": "int64",
                         "l_extendedprice": "int64", "l_shipdate": "int32"}}
    return {t: {"columns": {c: (d, (1, n[t])) for c, d in by.items()},
                "valid": {c: ("bool", (1, n[t])) for c in by},
                "sel": ("bool", (1, n[t]))} for t, by in cols.items()}


def test_subqjoin_roofline_reads_the_statements_bytes_at_sf1_shapes():
    """By hand: customer 150,000 x (8 + 4 + 2 validity + 1 selection) =
    2,250,000 B; orders 1,500,000 x (8 + 8 + 4 + 8 + 4 + 1) = 49,500,000;
    lineitem 6,001,215 x (8 + 8 + 2 + 1) = 114,023,085, ONCE though the
    statement names it twice: eight columns, their validity masks, three
    selection masks, 165,773,085 B, at 819 GB/s 0.2024 ms a statement,
    whatever implements the subquery and the joins."""
    from benchmarks import peaks

    cell = spec.Cell(CELL)
    shapes = sf1_shapes()
    mod = cell.statements["q18"]
    by_table = {t: work.min_bytes({t: mod.COLUMNS[t]}, shapes) for t in mod.TABLES}
    assert by_table == {"customer": 2_250_000, "orders": 49_500_000,
                        "lineitem": 114_023_085}
    assert work.min_bytes(mod.COLUMNS, shapes) == 165_773_085
    pk = peaks.peaks("TPU v5 lite")
    least, bound = work.least_seconds(mod.COLUMNS, shapes, pk, 1, exchanged=True)
    assert bound == "hbm" and least == pytest.approx(0.20241e-3, rel=1e-4)
    # two statements whole inside a traced span whose device ops took 5 s
    records = [{"item": 0, "t_send": 1e9 * i, "t_done": 1e9 * (i + 1), "ok": True}
               for i in range(2)]
    ctx = run.Context(cell, {"count": 1}, pk, shapes, records, 2.0, {}, {},
                      {"op_ns_mean": 5e9, "devices": ["TPU:0"]},
                      {"h0": 0.0, "h1": 2e9})
    got = cell.reader("subqjoin_roofline")(ctx)
    assert got == pytest.approx(100 * 2 * least / 5.0) and 0 < got < 100
    assert ctx.roofline_bounds == {"subqjoin_roofline": "hbm"}
    # a program with nothing traced, as a CPU run: nothing to read
    ctx = run.Context(cell, {"count": 1}, pk, shapes, records, 2.0, {}, {}, None, {})
    assert cell.reader("subqjoin_roofline")(ctx) is None


def test_the_device_holds_the_shapes_the_bytes_were_worked_out_from():
    """The dtypes above are the resident tables' own (a rehearsal's, at
    its scale): the hand count cannot drift from what the harness reads."""
    seen = {}
    real = system.table_shapes

    def spy(server):
        seen.update(real(server))
        return seen

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(system, "table_shapes", spy)
        rehearse(seconds=0.3, sf=0.01)
    want = sf1_shapes()
    assert set(seen) == {"customer", "orders", "lineitem"}  # three resident tables a connection
    for table, by_conn in seen.items():
        got = next(iter(by_conn.values()))
        assert got["n_parts"] == 1
        for col in spec.Cell(CELL).statements["q18"].COLUMNS[table]:
            assert got["columns"][col][0] == want[table]["columns"][col][0], (table, col)
            assert got["valid"][col][0] == "bool"
        assert got["sel"][0] == "bool"


# -- faults under the timed path: correct has to come out false -------------

def _alter_answers(monkeypatch, alter):
    from tidb_tpu.server.client import Client

    real = Client.query

    def altered(self, sql):
        names, rows = real(self, alter.get("sql", lambda s: s)(sql))
        if sql.startswith("select c_name, c_custkey, o_orderkey") and rows:
            rows = alter.get("rows", lambda r: r)(rows)
        return names, rows

    monkeypatch.setattr(Client, "query", altered)


def _cell(rows, i, j, value):
    return [tuple(value(v) if (a, b) == (i, j) else v for b, v in enumerate(r))
            for a, r in enumerate(rows)]


FAULTS = {
    "the_having_let_more_through": lambda mp: _alter_answers(mp, {
        "sql": lambda s: s.replace("having sum(l_quantity) > 300", "having sum(l_quantity) > 290")}),
    "the_having_let_fewer_through": lambda mp: _alter_answers(mp, {
        # (an order of 301.00 units passes under this seed)
        "sql": lambda s: s.replace("having sum(l_quantity) > 300", "having sum(l_quantity) > 305")}),
    "the_subquery_left_out": lambda mp: _alter_answers(mp, {
        "sql": lambda s: re.sub(r"o_orderkey in \(select .*? > \d+\) and ", "", s)}),
    "the_last_row_lost": lambda mp: _alter_answers(mp, {
        "rows": lambda r: list(r[:-1])}),
    "the_quantity_of_one_line": lambda mp: _alter_answers(mp, {
        "rows": lambda r: _cell(r, 0, 5, lambda v: "50.00")}),
    "a_cent_off_the_price": lambda mp: _alter_answers(mp, {
        "rows": lambda r: _cell(r, 1, 4, lambda v: str(v)[:-1] + str((int(str(v)[-1]) + 1) % 10))}),
    "another_customers_name": lambda mp: _alter_answers(mp, {
        "rows": lambda r: _cell(r, 0, 0, lambda v: "Customer#000000000")}),
    "the_order_of_the_rows_turned": lambda mp: _alter_answers(mp, {
        "rows": lambda r: list(r[::-1])}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_run(monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    _cell_, res = rehearse(seconds=1.0)
    assert res["correct"] is False and res["failed"] >= 1
    assert res["checks"]["exact_mismatches"]["value"] >= 1
    assert res["checks"]["wrong_statements"]["value"] == res["failed"]


# -- the reference: a control that fails, a second formulation that agrees ---

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float32_control_comes_out_not_correct(seed):
    """One running float32 total over the rows in key order: past 2**24
    units (some 6,600 lines in) a reading is off by whole units, so the
    sums of the orders that pass — and which orders pass — differ."""
    out = control.control_run(spec.Cell(CELL), seed, sf=0.05)
    assert out["correct"] is False
    assert out["checks"]["exact_mismatches"]["value"] >= 1


@pytest.mark.parametrize("seed", [5, 2**31 + 17, 12])
def test_the_reference_equals_a_slower_formulation(seed):
    """Python dicts, row by row, at QUANTITY values that give many rows
    (the LIMIT cuts), a few, and none."""
    data = reference.Data(tpch_datagen.generate(0.02, seed))
    mod = spec.Cell(CELL).statements["q18"]
    col = lambda t, c: data.col(t, c).tolist()  # noqa: E731
    qty = {}
    for k, q in zip(col("lineitem", "l_orderkey"), col("lineitem", "l_quantity")):
        qty[k] = qty.get(k, 0) + q
    name = dict(zip(col("customer", "c_custkey"), col("customer", "c_name")))
    for quantity in (220, 260, 350):
        rows = sorted(
            (-price, date, k, c)
            for k, c, date, price in zip(
                col("orders", "o_orderkey"), col("orders", "o_custkey"),
                col("orders", "o_orderdate"), col("orders", "o_totalprice"))
            if qty.get(k, 0) > quantity * 100 and c in name)[:100]
        got = mod.reference(data, {"quantity": quantity})
        assert len(got) == len(rows) and (len(got) == 100) == (quantity == 220)
        assert (len(got) == 0) == (quantity == 350)
        assert [(-g[4].units, data.days(g[3]), g[2], g[1]) for g in got] == rows
        assert all(g[0] == f"Customer#{g[1]:09d}" and g[4].scale == 2
                   and g[5].scale == 2 and g[5].units == qty[g[2]] for g in got)
        assert reference.compare_rows(control.to_wire(got), got)["exact_mismatches"] == 0
    keys, exact = mod.order_quantities(np.array([3, 1, 3, 2]), np.array([500, 700, 1100, 1300]))
    assert keys.tolist() == [1, 2, 3] and exact.tolist() == [700, 1300, 1600]
    _k, low = mod.order_quantities(np.array([3, 1, 3, 2]), np.array([500, 700, 1100, 1300]),
                                   np.float32)
    assert low.tolist() == exact.tolist()  # small totals: the control is the same arithmetic
