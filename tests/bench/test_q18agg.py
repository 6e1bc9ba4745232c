"""The cell ``tpch_sf1_pk.q18agg`` rehearsed on the CPU from exactly the
files and entries PR 28 added (a configuration, a traffic mix, a
statement, four readers): every answer equals the numpy reference, the
result line keeps the contract's keys, a traced run reports the new
per-layer metrics that need no device, ``groupagg_roofline`` reads the
bytes worked out by hand at SF1 shapes, faults planted under the timed
path turn ``correct`` false, the control in lower precision fails, and
the reference agrees with a second, slower formulation. Nothing here
gives a speed."""

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

CELL = "tpch_sf1_pk.q18agg"
FORCE = ("set tidb_device_engine_mode = 'force'",)  # the CPU must ask for the device engine
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
NEW_METRICS = ["groupagg_roofline", "group_finalize_ms_per_stmt",
               "fragment_launches_per_stmt", "setup_fragment_relaunches"]
# seeds whose answers are not empty at these scales: rows for QUANTITY
# 300 / 313 are 4 / 1 (SF0.05, seed 12) and 2 / 0 (SF0.01, seed 12)
SEED = 12


def rehearse(seed=SEED, seconds=1.0, trace=False, sf=0.01):
    cell = spec.Cell(CELL)
    return cell, run.run_cell(cell, seed, seconds, trace, require_chip=False,
                              sf=sf, pre_sql=FORCE)


@pytest.fixture(scope="module")
def plain():
    return rehearse(sf=0.05)


@pytest.fixture(scope="module")
def traced():
    return rehearse(trace=True)


def test_the_cell_is_made_of_new_files_and_appended_entries_only():
    bench = spec.load_benchmark()
    assert bench["configs"][-1]["name"] == "tpch_sf1_pk"
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "tpch_sf1_pk", "traffic": "q18agg", "chips": 1,
        "why": bench["workloads"][-1]["why"]}
    assert [m["name"] for m in bench["per_layer"][-4:]] == NEW_METRICS
    assert all(m["workloads"] == [CELL] for m in bench["per_layer"][-4:])
    cell = spec.Cell(CELL)
    assert cell.config["cluster_by"] == {} and cell.config["chips"] == 1
    assert cell.config["rows"] == spec.Cell("tpch_sf1.scan").config["rows"]
    assert cell.config["guarantees"]["arithmetic"] == \
        spec.Cell("tpch_sf1.scan").config["guarantees"]["arithmetic"]
    assert cell.traffic["streams"] == 1 and cell.traffic["warm_passes"] == 4
    assert [m["params"] for m in cell.traffic["menu"]] == [{"quantity": 300},
                                                          {"quantity": 313}]
    assert [m["name"] for m in cell.end_to_end()] == ["rows_per_s", "stmt_p50_ms",
                                                      "setup_s"]
    reported = {m["name"] for m in cell.per_layer()}
    assert set(NEW_METRICS) <= reported
    assert not reported & {"scan_agg_roofline", "join_roofline", "exchange_ms_per_stmt"}


def test_every_answer_of_the_window_equals_the_reference(plain):
    cell, res = plain
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= len(cell.traffic["menu"])
    checks = res["checks"]
    assert checks["exact_mismatches"] == {"value": 0, "limit": 0}
    assert checks["missing_answers"]["value"] == 0
    assert checks["compared"]["statements"] == res["attempted"]
    assert checks["compared"]["cells"] >= res["attempted"]  # no answer is empty twice over


def test_result_line_has_the_contracts_keys(plain):
    cell, res = plain
    assert set(res) == RESULT_KEYS and list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"rows_per_s", "stmt_p50_ms", "setup_s"}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    json.dumps(res)


@pytest.mark.parametrize("name", NEW_METRICS[1:])
def test_a_traced_run_reports_the_new_metrics_that_need_no_device(traced, name):
    cell, res = traced
    assert res["correct"] is True
    value = res["metrics"][name]["value"]
    want = {"group_finalize_ms_per_stmt": lambda v: v > 0,
            # one fragment a statement; the first launch of a new
            # connection is sized from the sketched key and is the only one
            "fragment_launches_per_stmt": lambda v: v == 1.0,
            "setup_fragment_relaunches": lambda v: v == 0.0}[name]
    assert want(value), value
    # no TPU plane in a CPU trace: no CPU number under a device metric's name
    assert "groupagg_roofline" not in res["metrics"]
    assert res["metrics"]["dispatches_per_stmt"]["value"] == 5.0
    assert res["metrics"]["window_compiles"]["value"] == 0.0


def test_the_span_metrics_still_partition_the_latency_with_the_finalize_in_it(traced):
    _cell, res = traced
    five = sum(res["metrics"][n]["value"] for n in (
        "queue_ms_per_stmt", "wire_ms_per_stmt", "plan_ms_per_stmt",
        "exec_host_ms_per_stmt", "device_wait_ms_per_stmt"))
    assert res["metrics"]["group_finalize_ms_per_stmt"]["value"] < \
        res["metrics"]["exec_host_ms_per_stmt"]["value"] < five


def test_a_program_without_the_span_or_the_counters_reads_as_nothing(monkeypatch):
    """The parent of PR 28 has no ``fragment.finalize``; a program with no
    fragment counter at all hands the two counter readers nothing."""
    cell = spec.Cell(CELL)

    class Ctx:
        records = [{"t_send": 0, "t_done": 1}]
        writes, warm = (), [(0, 1), (1, 2)]
        window_statements = 4
        window_counters, setup_counters = {"dispatches": 20}, {"dispatches": 9}

    class Trace:
        def root(self):
            return type("S", (), {"name": "wire.stmt"})()

        def interval_perf(self):
            return (0.0, 1e-9)

        def self_us_by_name(self):
            return {"wire.stmt": 5, "device.wait": 7}

    from tidb_tpu.utils import tracing

    monkeypatch.setattr(tracing.STORE, "finished", lambda: [Trace()])
    for name in NEW_METRICS[1:]:
        assert cell.reader(name)(Ctx()) is None
    Ctx.setup_counters = {"fragment:general_generic": 2, "fragment:compact": 1}
    Ctx.window_counters = {"fragment:general_generic": 4}
    assert cell.reader("setup_fragment_relaunches")(Ctx()) == 1  # the parent's reading
    assert cell.reader("fragment_launches_per_stmt")(Ctx()) == 1.0


def test_groupagg_roofline_reads_the_statements_bytes_at_sf1_shapes():
    """By hand: 6,001,215 rows x (8 B l_orderkey + 8 B l_quantity + two
    validity bytes + one selection byte) = 114,023,085 B; at 819 GB/s
    0.1392 ms a statement, whatever implements it."""
    from benchmarks import peaks

    cell = spec.Cell(CELL)
    n = tpch_datagen.sizes(1.0)["lineitem"]
    shapes = {"lineitem": {
        "columns": {c: ("int64", (1, n)) for c in ("l_orderkey", "l_quantity", "l_partkey")},
        "valid": {c: ("bool", (1, n)) for c in ("l_orderkey", "l_quantity", "l_partkey")},
        "sel": ("bool", (1, n))}}
    mod = cell.statements["q18agg"]
    assert mod.ROOFLINE == "groupagg_roofline" and mod.TABLES == ("lineitem",)
    assert work.min_bytes(mod.COLUMNS, shapes) == 114_023_085 == n * 19
    pk = peaks.peaks("TPU v5 lite")
    least, bound = work.least_seconds(mod.COLUMNS, shapes, pk, 1, exchanged=False)
    assert bound == "hbm" and least == pytest.approx(0.13922e-3, rel=1e-4)
    # two statements whole inside a traced span whose device ops took 9 s
    records = [{"item": i, "t_send": 1e9 * i, "t_done": 1e9 * (i + 1), "ok": True}
               for i in range(2)]
    ctx = run.Context(cell, {"count": 1}, pk, shapes, records, 2.0, {}, {},
                      {"op_ns_mean": 9e9, "devices": ["TPU:0"]},
                      {"h0": 0.0, "h1": 2e9})
    got = cell.reader("groupagg_roofline")(ctx)
    assert got == pytest.approx(100 * 2 * least / 9.0) and 0 < got < 100
    assert ctx.roofline_bounds == {"groupagg_roofline": "hbm"}


# -- faults under the timed path: correct has to come out false -------------

def _alter_answers(monkeypatch, alter):
    from tidb_tpu.server.client import Client

    real = Client.query

    def altered(self, sql):
        names, rows = real(self, alter.get("sql", lambda s: s)(sql))
        if sql.startswith("select l_orderkey") and rows:
            rows = alter.get("rows", lambda r: r)(rows)
        return names, rows

    monkeypatch.setattr(Client, "query", altered)


def _half_the_rows(monkeypatch):
    real = system.start_server

    def half(tables, pks, cluster_by):
        # every other row: lineitem lies in order-key order, so its first
        # half would keep the first half of the orders whole, and right
        arrays, pools = tables["lineitem"]
        cut = dict(tables)
        cut["lineitem"] = ({k: v[::2] for k, v in arrays.items()}, pools)
        return real(cut, pks, cluster_by)

    monkeypatch.setattr(system, "start_server", half)


FAULTS = {
    "having_dropped": lambda mp: _alter_answers(mp, {
        "sql": lambda s: re.sub(r"having .*? order by", "order by", s)}),
    "one_sum_off_by_a_unit_of_scale_2": lambda mp: _alter_answers(mp, {
        "rows": lambda r: [(r[0][0], str(r[0][1])[:-1]
                            + str((int(str(r[0][1])[-1]) + 1) % 10))] + list(r[1:])}),
    "half_the_rows_left_out": _half_the_rows,
    "the_answer_unsorted": lambda mp: _alter_answers(mp, {
        "rows": lambda r: list(reversed(r))}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_run(monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    _cell, res = rehearse()
    assert res["correct"] is False and res["failed"] >= 1
    assert res["checks"]["exact_mismatches"]["value"] >= 1
    assert res["checks"]["wrong_statements"]["value"] == res["failed"]


# -- the reference: a control that fails, a second formulation that agrees ---

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float32_control_comes_out_not_correct(seed):
    """One running total in float32 over the rows in key order, each
    group the difference of two readings (the statement module says why
    not an accumulator per group): the total passes 2**24 units after
    some 6,600 rows, so later groups' sums are off by whole units."""
    out = control.control_run(spec.Cell(CELL), seed, sf=0.05)
    assert out["correct"] is False
    assert out["checks"]["exact_mismatches"]["value"] >= 1


@pytest.mark.parametrize("seed", [5, 2**31 + 17, 12])
def test_the_reference_equals_a_slower_formulation(seed):
    data = reference.Data(tpch_datagen.generate(0.02, seed))
    sums = {}
    for k, q in zip(data.col("lineitem", "l_orderkey").tolist(),
                    data.col("lineitem", "l_quantity").tolist()):
        sums[k] = sums.get(k, 0) + q
    mod = spec.Cell(CELL).statements["q18agg"]
    for quantity in (250, 300, 313):
        want = sorted((k, q) for k, q in sums.items() if q > quantity * 100)
        got = mod.reference(data, {"quantity": quantity})
        assert [(k, e.units, e.scale) for k, e in got] == [(k, q, 2) for k, q in want]
        assert reference.compare_rows(control.to_wire(got), got)["exact_mismatches"] == 0
    assert "having sum(l_quantity) > 313 order by l_orderkey" in mod.sql({"quantity": 313})
    keys, exact = mod.group_sums(np.array([3, 1, 3, 2]), np.array([5, 7, 11, 13]))
    assert keys.tolist() == [1, 2, 3] and exact.tolist() == [7, 13, 16]
    _keys, low = mod.group_sums(np.array([3, 1, 3, 2]), np.array([5, 7, 11, 13]), np.float32)
    assert low.tolist() == [7, 13, 16]  # small totals: the control is the same arithmetic
