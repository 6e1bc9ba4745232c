"""The cell ``tpch_sf1_power.q22`` rehearsed on the CPU from exactly the
files and entries that make the cell (a configuration, a traffic mix, a
statement, two readers): every answer of a window equals the numpy
reference, a traced run reports ``plan_subqueries_per_stmt`` 0.0 where
the mesh tier compiles the AVG into the program and 1.0 where planning
runs it first, ``existsjoin_roofline`` reads the bytes worked out by hand
at SF1 shapes, faults planted under the timed path turn ``correct``
false, and the control in lower precision fails. Statement counts, never
seconds: nothing here gives a speed.

The deployment is ONE chip (a 1x1 mesh); the tests' process has eight
CPU devices, so the rehearsal hands the harness a mesh of one of them —
here, in the test, not through an option of the program or the harness."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import control, reference, run, spec, tpch_datagen, work  # noqa: E402

CELL = "tpch_sf1_power.q22"
CONFIG = "tpch_sf1_power_q22"
FORCE = ("set tidb_device_engine_mode = 'force'",)  # the CPU must ask for the device engine
PARAMS = {"codes": [13, 31, 23, 29, 30, 18, 17]}
SEED, SF = 2**31 + 29, 0.05


def rehearse(seed=SEED, seconds=1.5, trace=False, sf=SF, pre_sql=FORCE):
    """One run of the cell on a mesh of one CPU device."""
    import jax

    import tidb_tpu.parallel as par

    real = par.make_mesh
    cell = spec.Cell(CELL)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(par, "make_mesh", lambda: real(devices=jax.devices()[:1]))
        return cell, run.run_cell(cell, seed, seconds, trace, require_chip=False,
                                  sf=sf, pre_sql=pre_sql)


@pytest.fixture(scope="module")
def traced():
    return rehearse(trace=True)


def test_the_cell_is_made_of_new_files_and_appended_entries_only():
    """By name and by what stands BEFORE them, not by being last."""
    bench = spec.load_benchmark()
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    metrics = [m["name"] for m in bench["per_layer"]]
    assert configs[:6] == ["tpch_sf1", "tpch_sf1_mesh4", "tpch_sf1_pk", "tpch_sf1_power",
                           "tpch_sf1_power_q18", CONFIG]
    assert bench["configs"][5] == {
        "name": CONFIG, "source": bench["configs"][5]["source"],
        "file": "benchmarks/configs/tpch_sf1_power_q22.json",
        "reduced": ["scale_factor"], "why": bench["configs"][5]["why"]}
    assert "2.4.22" in bench["configs"][5]["source"]
    assert all(len(bench["configs"][5][k]) <= 200 for k in ("source", "why"))
    assert bench["configs"][5]["source"] not in [c["source"] for c in bench["configs"][:5]]
    assert cells[:7] == ["tpch_sf1.scan", "tpch_sf1.join", "tpch_sf1_mesh4.join",
                         "tpch_sf1_pk.q18agg", "tpch_sf1_power.q3", "tpch_sf1_power.q18",
                         CELL]
    assert bench["workloads"][6] == {
        "name": CELL, "config": CONFIG, "traffic": "q22", "chips": 1,
        "why": bench["workloads"][6]["why"]}
    assert len(bench["workloads"][6]["why"]) <= 200
    assert metrics[:27][-1] == "setup_upload_s"
    assert bench["per_layer"][27:29] == [
        {"name": "existsjoin_roofline", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "kernels", "moves": "stmt_p50_ms",
         "workloads": [CELL]},
        {"name": "plan_subqueries_per_stmt", "unit": "count", "better": "lower",
         "source": "program_span", "layer": "plan and engine routing",
         "moves": "stmt_p50_ms", "workloads": [CELL]}]
    # what the harness reads of a configuration, and what the deployment
    # is, are the accepted tpch_sf1_power's; its own are the name, the
    # source, Q22's guarantees and what the file says Q22 holds and assumes
    cell, q18 = spec.Cell(CELL), spec.Cell("tpch_sf1_power.q18")
    own = {"name", "source", "deployment", "guarantees", "on_device", "assumed"}
    assert {k for k in cell.config if cell.config[k] != q18.config.get(k)} == own
    assert set(cell.config) == set(q18.config) and cell.config["source"] == bench[
        "configs"][5]["source"]
    for g in ("answers", "isolation"):
        assert cell.config["guarantees"][g] == q18.config["guarantees"][g]
    assert "c_acctbal * count > sum" in cell.config["guarantees"]["arithmetic"]
    assert "no row of orders" in cell.config["guarantees"]["subquery"]
    assert [(m["statement"], m["params"]) for m in cell.traffic["menu"]] == [("q22", PARAMS)]
    assert cell.traffic["streams"] == 1 and cell.traffic["warm_passes"] == 4
    assert cell.traffic["trace_seconds"] == spec.Cell("tpch_sf1_power.q3").traffic[
        "trace_seconds"]
    assert [m["name"] for m in cell.end_to_end()] == ["rows_per_s", "stmt_p50_ms",
                                                      "setup_s"]
    reported = {m["name"] for m in cell.per_layer()}
    assert {"existsjoin_roofline", "plan_subqueries_per_stmt"} <= reported
    assert {m["name"] for m in bench["per_layer"] if "workloads" not in m} <= reported


def test_the_clients_patience_leaves_the_parent_its_answer_or_an_error_inside_a_run():
    """240 s (step 0 on the chip: the parent's cold first statement 103-104
    s, 27 s into its run; this PR's 53 s), with the note that says whose
    number it is."""
    cell = spec.Cell(CELL)
    assert cell.traffic["statement_timeout_s"] == 240
    note = cell.traffic["timeout_note"]
    assert "client's patience" in note and "no guarantee of the deployment" in note
    assert "103.98" in note and "53.32" in note


def test_the_statement_names_its_tables_columns_and_roofline():
    mod = spec.Cell(CELL).statements["q22"]
    assert mod.TABLES == ("customer", "orders")  # customer once: 1,650,000 rows
    n = tpch_datagen.sizes(1.0)
    assert sum(n[t] for t in mod.TABLES) == 1_650_000
    assert mod.ROOFLINE == "existsjoin_roofline"
    assert mod.COLUMNS == {"customer": ("c_custkey", "c_phone", "c_acctbal"),
                           "orders": ("o_custkey",)}
    text = mod.sql(PARAMS)
    assert text.count("('13', '31', '23', '29', '30', '18', '17')") == 2
    assert "not exists (select * from orders where o_custkey = c_custkey)" in text
    with pytest.raises(ValueError):
        mod.sql({"codes": ["13') or ('1"]})


def test_every_answer_of_the_window_equals_the_reference(traced):
    _cell, res = traced
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 2
    checks = res["checks"]
    assert checks["exact_mismatches"] == {"value": 0, "limit": 0}
    assert checks["compared"]["cells"] == 7 * 3 * res["attempted"]  # seven rows of three
    json.dumps(res)


def test_the_traced_run_reports_both_readers(traced):
    """0.0 where the mesh tier compiles the AVG into the program (the
    window's traces have counts and none is `subqueries_run`); no device
    plane in a CPU trace, so no roofline."""
    _cell, res = traced
    assert res["metrics"]["plan_subqueries_per_stmt"] == {"value": 0.0, "unit": "count"}
    assert res["metrics"]["window_compiles"]["value"] == 0.0
    assert "existsjoin_roofline" not in res["metrics"]


def test_planning_that_runs_the_subquery_first_reads_one_a_statement():
    """Where the mesh tier does not take the statement (the CPU's own
    engine routing, no `force`), the AVG folds to a literal as before:
    one statement within every statement's planning."""
    _cell, res = rehearse(trace=True, seconds=1.0, pre_sql=())
    assert res["correct"] is True
    assert res["metrics"]["plan_subqueries_per_stmt"]["value"] == 1.0


def sf1_shapes() -> dict:
    n = tpch_datagen.sizes(1.0)
    cols = {"customer": {"c_custkey": "int64", "c_phone": "int32", "c_acctbal": "int64"},
            "orders": {"o_custkey": "int64"}}
    return {t: {"columns": {c: (d, (1, n[t])) for c, d in by.items()},
                "valid": {c: ("bool", (1, n[t])) for c in by},
                "sel": ("bool", (1, n[t]))} for t, by in cols.items()}


def test_existsjoin_roofline_reads_the_statements_bytes_at_sf1_shapes():
    """By hand: customer 150,000 x (8 + 4 + 8 + 3 validity + 1 selection)
    = 3,600,000 B ONCE though the statement names it twice; orders
    1,500,000 x (8 + 1 + 1) = 15,000,000: 18,600,000 B, at 819 GB/s
    0.02271 ms a statement, whatever implements the AVG and the NOT
    EXISTS."""
    from benchmarks import peaks

    cell = spec.Cell(CELL)
    shapes = sf1_shapes()
    mod = cell.statements["q22"]
    assert work.min_bytes(mod.COLUMNS, shapes) == 18_600_000
    pk = peaks.peaks("TPU v5 lite")
    least, bound = work.least_seconds(mod.COLUMNS, shapes, pk, 1, exchanged=True)
    assert bound == "hbm" and least == pytest.approx(18.6e6 / pk["hbm_bytes_per_s"])
    records = [{"item": 0, "t_send": 1e9 * i, "t_done": 1e9 * (i + 1), "ok": True}
               for i in range(2)]
    ctx = run.Context(cell, {"count": 1}, pk, shapes, records, 2.0, {}, {},
                      {"op_ns_mean": 5e7, "devices": ["TPU:0"]}, {"h0": 0.0, "h1": 2e9})
    got = cell.reader("existsjoin_roofline")(ctx)
    assert got == pytest.approx(100 * 2 * least / 0.05) and 0 < got < 100
    ctx = run.Context(cell, {"count": 1}, pk, shapes, records, 2.0, {}, {}, None, {})
    assert cell.reader("existsjoin_roofline")(ctx) is None


def test_the_device_holds_the_shapes_the_bytes_were_worked_out_from():
    """The dtypes above are the resident tables' own, as the rehearsal's
    set-up line printed them into the harness's shapes."""
    from benchmarks import system

    seen = {}
    real = system.table_shapes

    def spy(server):
        seen.update(real(server))
        return seen

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(system, "table_shapes", spy)
        rehearse(seconds=0.3, sf=0.01)
    want = sf1_shapes()
    assert set(seen) == {"customer", "orders"}
    for table, by_conn in seen.items():
        got = next(iter(by_conn.values()))
        for col in spec.Cell(CELL).statements["q22"].COLUMNS[table]:
            assert got["columns"][col][0] == want[table]["columns"][col][0], (table, col)


# -- faults under the timed path: correct has to come out false -------------

def _answer_customers(seed, sf):
    """(a customer of the answer, a listed customer without orders whose
    balance is under the average): keys at the rehearsal's data."""
    data = reference.Data(tpch_datagen.generate(sf, seed))
    pool = data.tables["customer"][1]["c_phone"]
    prefix = np.array([s[:2] for s in pool])[data.col("customer", "c_phone")]
    bal = data.col("customer", "c_acctbal")
    key = data.col("customer", "c_custkey")
    listed = np.isin(prefix, [str(c) for c in PARAMS["codes"]])
    pos = listed & (bal > 0)
    over = bal * int(pos.sum()) > int(bal[pos].sum())
    none = ~np.isin(key, data.col("orders", "o_custkey"))
    return int(key[listed & over & none][0]), int(key[listed & ~over & none][0])


def _alter_sql(monkeypatch, alter):
    from tidb_tpu.server.client import Client

    real = Client.query

    def altered(self, sql):
        return real(self, alter(sql) if sql.startswith("select cntrycode") else sql)

    monkeypatch.setattr(Client, "query", altered)


@pytest.mark.parametrize("fault", ["one_customer_dropped_by_the_anti_join",
                                   "the_avg_comparison_flipped_at_one_row"])
def test_a_planted_fault_fails_the_run(monkeypatch, fault):
    kept, under = _answer_customers(SEED, 0.02)
    anti = "not exists (select * from orders where o_custkey = c_custkey)"
    if fault == "one_customer_dropped_by_the_anti_join":
        _alter_sql(monkeypatch, lambda s: s.replace(anti, f"{anti} and c_custkey <> {kept}"))
    else:
        _alter_sql(monkeypatch, lambda s: s.replace(
            "and c_acctbal > (", f"and (c_custkey = {under} or c_acctbal > (").replace(
            f") and {anti}", f")) and {anti}"))
    _cell, res = rehearse(seconds=1.0, sf=0.02)
    assert res["correct"] is False and res["failed"] >= 1
    assert res["checks"]["exact_mismatches"]["value"] >= 1


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float32_control_comes_out_not_correct(seed):
    """The AVG and each group's running total in float32: past 2**24
    units a reading is off by whole units."""
    out = control.control_run(spec.Cell(CELL), seed, sf=0.05)
    assert out["correct"] is False
    assert out["checks"]["exact_mismatches"]["value"] >= 1


@pytest.mark.parametrize("seed", [5, 2**31 + 17])
def test_the_reference_equals_a_slower_formulation(seed):
    """Python integers and dicts, customer by customer."""
    data = reference.Data(tpch_datagen.generate(0.02, seed))
    pool = data.tables["customer"][1]["c_phone"]
    col = lambda t, c: data.col(t, c).tolist()  # noqa: E731
    codes = {str(c) for c in PARAMS["codes"]}
    cust = list(zip(col("customer", "c_custkey"),
                    [pool[p][:2] for p in col("customer", "c_phone")],
                    col("customer", "c_acctbal")))
    positive = [b for _k, code, b in cust if code in codes and b > 0]
    has_orders = set(col("orders", "o_custkey"))
    groups = {}
    for k, code, b in cust:
        if code in codes and b * len(positive) > sum(positive) and k not in has_orders:
            n, s = groups.get(code, (0, 0))
            groups[code] = (n + 1, s + b)
    got = spec.Cell(CELL).statements["q22"].reference(data, PARAMS)
    assert [(c, n, s.units) for c, n, s in got] == [
        (c, n, s) for c, (n, s) in sorted(groups.items())]
    assert len(got) == 7
