"""The cell ``tpch_sf1_power.q9`` rehearsed on the CPU from exactly the
files and entries that make the cell (a configuration, a traffic mix, a
statement, two readers): every answer of a window equals the numpy
reference, the reference equals sqlite, a traced run reports
``compact_slots_per_stmt``, ``multijoin_roofline`` reads the bytes worked
out by hand at SF1 shapes, faults planted under the timed path turn
``correct`` false, and the control in lower precision fails. Statement
counts, never seconds: nothing here gives a speed.

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

CELL = "tpch_sf1_power.q9"
CONFIG = "tpch_sf1_power_q9"
FORCE = ("set tidb_device_engine_mode = 'force'",)  # the CPU must ask for the device engine
PARAMS = {"color": "blue"}
SEED, SF = 2**31 + 91, 0.02


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
    assert configs[:7] == ["tpch_sf1", "tpch_sf1_mesh4", "tpch_sf1_pk", "tpch_sf1_power",
                           "tpch_sf1_power_q18", "tpch_sf1_power_q22", CONFIG]
    assert bench["configs"][6] == {
        "name": CONFIG, "source": bench["configs"][6]["source"],
        "file": "benchmarks/configs/tpch_sf1_power_q9.json",
        "reduced": ["scale_factor"], "why": bench["configs"][6]["why"]}
    assert "2.4.9 " in bench["configs"][6]["source"]
    assert "2.4.9.3" in bench["configs"][6]["source"]
    assert bench["configs"][6]["source"] not in [c["source"] for c in bench["configs"][:6]]
    assert cells[:8] == ["tpch_sf1.scan", "tpch_sf1.join", "tpch_sf1_mesh4.join",
                         "tpch_sf1_pk.q18agg", "tpch_sf1_power.q3", "tpch_sf1_power.q18",
                         "tpch_sf1_power.q22", CELL]
    assert bench["workloads"][7] == {
        "name": CELL, "config": CONFIG, "traffic": "q9", "chips": 1,
        "why": bench["workloads"][7]["why"]}
    assert metrics[:29][-1] == "plan_subqueries_per_stmt"
    assert bench["per_layer"][29:31] == [
        {"name": "multijoin_roofline", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "kernels", "moves": "stmt_p50_ms",
         "workloads": [CELL]},
        {"name": "compact_slots_per_stmt", "unit": "count", "better": "lower",
         "source": "program_span", "layer": "plan and engine routing",
         "moves": "stmt_p50_ms", "workloads": [CELL]}]
    # what the harness reads of a configuration, and what the deployment
    # is, are the accepted tpch_sf1_power's; its own are the name, the
    # source, Q9's guarantees and what the file says Q9 holds and assumes
    cell, q22 = spec.Cell(CELL), spec.Cell("tpch_sf1_power.q22")
    own = {"name", "source", "deployment", "guarantees", "on_device", "assumed"}
    assert {k for k in cell.config if cell.config[k] != q22.config.get(k)} == own
    assert set(cell.config) == set(q22.config) and cell.config["source"] == bench[
        "configs"][6]["source"]
    for g in ("answers", "isolation"):
        assert cell.config["guarantees"][g] == q22.config["guarantees"][g]
    assert "scale 4" in cell.config["guarantees"]["arithmetic"]
    assert "14.8%" in cell.config["assumed"]["substitution_parameters"]
    assert "5.4%" in cell.config["assumed"]["substitution_parameters"]
    assert [(m["statement"], m["params"]) for m in cell.traffic["menu"]] == [("q9", PARAMS)]
    assert cell.traffic["streams"] == 1
    assert [m["name"] for m in cell.end_to_end()] == ["rows_per_s", "stmt_p50_ms",
                                                      "setup_s"]
    reported = {m["name"] for m in cell.per_layer()}
    assert {"multijoin_roofline", "compact_slots_per_stmt"} <= reported
    assert {m["name"] for m in bench["per_layer"] if "workloads" not in m} <= reported


def test_the_configurations_source_and_why_fit_their_limits():
    bench = spec.load_benchmark()
    entry = bench["configs"][6]
    assert all(0 < len(entry[k]) <= 200 and "\n" not in entry[k] and "\t" not in entry[k]
               for k in ("source", "why"))
    assert 0 < len(bench["workloads"][7]["why"]) <= 200
    assert spec.Cell(CELL).config["source"] == entry["source"]


def test_the_clients_patience_is_the_rule_for_a_cold_statement():
    """600 s (the repo's rule for a cold statement, as q18's), with the
    note that says whose number it is and what the chip read."""
    cell = spec.Cell(CELL)
    assert cell.traffic["statement_timeout_s"] == 600
    note = cell.traffic["timeout_note"]
    assert "client's patience" in note and "no guarantee of the deployment" in note
    assert "344.8" in note and "664 s" in note


def test_the_statement_names_its_tables_columns_and_roofline():
    mod = spec.Cell(CELL).statements["q9"]
    assert mod.TABLES == ("part", "supplier", "lineitem", "partsupp", "orders", "nation")
    n = tpch_datagen.sizes(1.0)
    assert sum(n[t] for t in mod.TABLES) == 8_511_240
    assert mod.ROOFLINE == "multijoin_roofline"
    assert set(mod.COLUMNS) == set(mod.TABLES)
    text = mod.sql(PARAMS)
    assert "p_name like '%blue%'" in text
    assert "ps_suppkey = l_suppkey and ps_partkey = l_partkey" in text
    assert text.endswith("order by nation, o_year desc")
    with pytest.raises(ValueError):
        mod.sql({"color": "blue%' or '1"})


def test_every_answer_of_the_window_equals_the_reference(traced):
    _cell, res = traced
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    checks = res["checks"]
    assert checks["exact_mismatches"] == {"value": 0, "limit": 0}
    assert checks["compared"]["cells"] % (3 * res["attempted"]) == 0
    assert checks["compared"]["cells"] > 0
    json.dumps(res)


def test_the_traced_run_reports_the_compactions_slots(traced):
    """The launch span's count: more than nothing (Q9's joins compact),
    the same for every statement (static capacities); no device plane in
    a CPU trace, so no roofline."""
    _cell, res = traced
    slots = res["metrics"]["compact_slots_per_stmt"]
    assert slots["unit"] == "count" and slots["value"] > 0
    assert float(slots["value"]).is_integer()
    assert res["metrics"]["window_compiles"]["value"] == 0.0
    assert "multijoin_roofline" not in res["metrics"]


def test_a_program_whose_launches_carry_no_count_reads_nothing():
    """As on the parent: the window's traces hold counts, none of them
    `compact_slots`."""
    cell = spec.Cell(CELL)

    class Ctx:
        _program_parts = ({}, {"session.plan/rows_counted": 5}, 2)

    assert cell.reader("compact_slots_per_stmt")(Ctx()) is None
    Ctx._program_parts = ({}, {"fragment.general_generic[parts=1]/compact_slots": 10,
                               "session.plan/rows_counted": 5}, 2)
    assert cell.reader("compact_slots_per_stmt")(Ctx()) == 5.0


def sf1_shapes() -> dict:
    n = tpch_datagen.sizes(1.0)
    cols = {"part": {"p_partkey": "int64", "p_name": "int32"},
            "supplier": {"s_suppkey": "int64", "s_nationkey": "int64"},
            "lineitem": {c: "int64" for c in ("l_orderkey", "l_partkey", "l_suppkey",
                                              "l_quantity", "l_extendedprice",
                                              "l_discount")},
            "partsupp": {"ps_partkey": "int64", "ps_suppkey": "int64",
                         "ps_supplycost": "int64"},
            "orders": {"o_orderkey": "int64", "o_orderdate": "int32"},
            "nation": {"n_nationkey": "int64", "n_name": "int32"}}
    return {t: {"columns": {c: (d, (1, n[t])) for c, d in by.items()},
                "valid": {c: ("bool", (1, n[t])) for c in by},
                "sel": ("bool", (1, n[t]))} for t, by in cols.items()}


def test_multijoin_roofline_reads_the_statements_bytes_at_sf1_shapes():
    """By hand, rows x (column bytes + a validity byte each + a selection
    byte): part 200,000 x 15 = 3,000,000; supplier 10,000 x 19 = 190,000;
    lineitem 6,001,215 x 55 = 330,066,825; partsupp 800,000 x 28 =
    22,400,000; orders 1,500,000 x 15 = 22,500,000; nation 25 x 15 = 375:
    378,157,200 B, at 819 GB/s 0.4617 ms a statement."""
    from benchmarks import peaks

    cell = spec.Cell(CELL)
    shapes = sf1_shapes()
    mod = cell.statements["q9"]
    assert work.min_bytes(mod.COLUMNS, shapes) == 378_157_200
    pk = peaks.peaks("TPU v5 lite")
    least, bound = work.least_seconds(mod.COLUMNS, shapes, pk, 1, exchanged=True)
    assert bound == "hbm" and least == pytest.approx(378_157_200 / pk["hbm_bytes_per_s"])
    records = [{"item": 0, "t_send": 1e9 * i, "t_done": 1e9 * (i + 1), "ok": True}
               for i in range(2)]
    ctx = run.Context(cell, {"count": 1}, pk, shapes, records, 2.0, {}, {},
                      {"op_ns_mean": 5e8, "devices": ["TPU:0"]}, {"h0": 0.0, "h1": 2e9})
    got = cell.reader("multijoin_roofline")(ctx)
    assert got == pytest.approx(100 * 2 * least / 0.5) and 0 < got < 100
    ctx = run.Context(cell, {"count": 1}, pk, shapes, records, 2.0, {}, {}, None, {})
    assert cell.reader("multijoin_roofline")(ctx) is None


def test_the_device_holds_the_shapes_the_bytes_were_worked_out_from():
    """The dtypes above are the resident tables' own, as the rehearsal's
    set-up read them into the harness's shapes."""
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
    assert set(seen) == set(want)
    for table, by_conn in seen.items():
        got = next(iter(by_conn.values()))
        for col in spec.Cell(CELL).statements["q9"].COLUMNS[table]:
            assert got["columns"][col][0] == want[table]["columns"][col][0], (table, col)


# -- faults under the timed path: correct has to come out false -------------

def _alter_sql(monkeypatch, alter):
    from tidb_tpu.server.client import Client

    real = Client.query

    def altered(self, sql):
        return real(self, alter(sql) if sql.startswith("select nation") else sql)

    monkeypatch.setattr(Client, "query", altered)


def _one_answer_line(seed, sf):
    """(l_orderkey, l_linenumber) of a line of a blue part: a line that
    counts in some group at the rehearsal's data."""
    data = reference.Data(tpch_datagen.generate(sf, seed))
    names = data.tables["part"][1]["p_name"]
    blue = [i for i, n in enumerate(names) if "blue" in n]
    parts = data.col("part", "p_partkey")[np.isin(data.col("part", "p_name"), blue)]
    i = int(np.flatnonzero(np.isin(data.col("lineitem", "l_partkey"), parts))[0])
    return (int(data.col("lineitem", "l_orderkey")[i]),
            int(data.col("lineitem", "l_linenumber")[i]))


@pytest.mark.parametrize("fault", ["one_line_dropped",
                                   "partsupp_joined_on_one_key_of_two"])
def test_a_planted_fault_fails_the_run(monkeypatch, fault):
    if fault == "one_line_dropped":
        okey, line = _one_answer_line(SEED, 0.01)
        _alter_sql(monkeypatch, lambda s: s.replace(
            "and s_nationkey = n_nationkey",
            f"and s_nationkey = n_nationkey and not (l_orderkey = {okey} "
            f"and l_linenumber = {line})"))
    else:
        # every line meets its part's four partsupp rows, not its own one
        _alter_sql(monkeypatch, lambda s: s.replace("and ps_suppkey = l_suppkey ", ""))
    _cell, res = rehearse(seconds=1.0, sf=0.01)
    assert res["correct"] is False and res["failed"] >= 1
    assert res["checks"]["exact_mismatches"]["value"] >= 1


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float32_control_comes_out_not_correct(seed):
    """Each amount and the running total over the lines in float32: past
    2**24 units (0.0017 at scale 4) a reading is off by whole units."""
    out = control.control_run(spec.Cell(CELL), seed, sf=0.02)
    assert out["correct"] is False
    assert out["checks"]["exact_mismatches"]["value"] >= 1


@pytest.mark.parametrize("seed", [5, 2**31 + 17])
def test_the_reference_equals_sqlite(seed):
    """The same arrays in sqlite, the statement's text over them with the
    decimals as their integer units (so the amount is an exact integer at
    scale 4) and sqlite's own date arithmetic for the year, at a small
    scale."""
    data = reference.Data(tpch_datagen.generate(0.01, seed))
    want = spec.Cell(CELL).statements["q9"].reference(data, PARAMS)
    got = _sqlite(data).execute(
        "select nation, o_year, sum(amount) from ("
        "select n_name as nation, cast(strftime('%Y', o_orderdate) as integer) as o_year, "
        "l_extendedprice * (100 - l_discount) - ps_supplycost * l_quantity as amount "
        "from part, supplier, lineitem, partsupp, orders, nation "
        "where s_suppkey = l_suppkey and ps_suppkey = l_suppkey "
        "and ps_partkey = l_partkey and p_partkey = l_partkey "
        "and o_orderkey = l_orderkey and s_nationkey = n_nationkey "
        "and p_name like '%blue%') group by nation, o_year "
        "order by nation, o_year desc").fetchall()
    # of 25 nations x 1992-1998, a few empty at this scale
    assert len(want) == len(got) and 150 < len(want) <= 175
    assert [(n, y, s.units) for n, y, s in want] == [tuple(r) for r in got]


def _sqlite(data):
    """The statement's six tables as sqlite tables: codes decoded,
    decimals as their integer units, dates as ISO days."""
    import datetime
    import sqlite3

    db = sqlite3.connect(":memory:")
    cols = spec.Cell(CELL).statements["q9"].COLUMNS
    epoch = datetime.date(1970, 1, 1)
    for table, names in cols.items():
        arrays, pools = data.tables[table]
        db.execute(f"create table {table} ({', '.join(names)})")
        values = []
        for c in names:
            col = arrays[c].tolist()
            if c in pools:
                col = [pools[c][v] for v in col]
            elif c == "o_orderdate":
                col = [(epoch + datetime.timedelta(days=v)).isoformat() for v in col]
            values.append(col)
        db.executemany(f"insert into {table} values ({', '.join('?' * len(names))})",
                       zip(*values))
    return db
