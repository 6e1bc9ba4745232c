"""The benchmark's harness, rehearsed on the CPU at SF0.01: both traffic
mixes drive the in-process server through the wire and every answer
equals the numpy reference; seeds; faults planted under the timed path
turn ``correct`` false; the float32 control fails; the result line has
the contract's keys; the measuring entry refuses to report without a
TPU; a cell, a mix, a statement and a per-layer metric are added as new
files only. Nothing here gives a speed: the CPU's numbers are never
written under a device metric's name."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import control, reference, run, spec, system, tpch_datagen, traffic  # noqa: E402

SF = 0.01
FORCE = ("set tidb_device_engine_mode = 'force'",)  # the CPU must ask for the device engine
CELLS = ["tpch_sf1.scan", "tpch_sf1.join", "tpch_sf1_mesh4.join"]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def rehearse(name, seed=11, seconds=1.0, trace=False, root=ROOT):
    cell = spec.Cell(name, root=root)
    return cell, run.run_cell(cell, seed, seconds, trace, require_chip=False,
                              sf=SF, pre_sql=FORCE)


@pytest.fixture(scope="module")
def results():
    return {name: rehearse(name) for name in CELLS}


@pytest.mark.parametrize("name", CELLS)
def test_every_answer_of_the_window_equals_the_reference(results, name):
    cell, res = results[name]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= len(cell.traffic["menu"])
    checks = res["checks"]
    assert checks["exact_mismatches"] == {"value": 0, "limit": 0}
    assert checks["missing_answers"]["value"] == 0
    assert checks["float_rel_gap"]["value"] <= reference.FLOAT_REL_LIMIT
    assert checks["compared"]["statements"] == res["attempted"]


@pytest.mark.parametrize("name", CELLS)
def test_result_line_has_the_contracts_keys(results, name):
    cell, res = results[name]
    assert set(res) == RESULT_KEYS
    assert list(res)[-1] == "checks"  # the numbers compared come last
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end()}
    assert "setup_s" in res["metrics"]
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(res)


@pytest.mark.parametrize("name", CELLS)
def test_a_cell_without_a_writer_prints_the_checks_it_always_did(results, name):
    """Letter for letter what the harness printed before it carried a
    writer (PR 26's tree): the same names in the same order, no other."""
    _cell, res = results[name]
    checks = dict(res["checks"], float_rel_gap={"value": 0.0, "limit": 1e-12})
    n, cells = (res["checks"]["compared"][k] for k in ("statements", "cells"))
    assert json.dumps(checks) == (
        '{"exact_mismatches": {"value": 0, "limit": 0}, '
        '"float_rel_gap": {"value": 0.0, "limit": 1e-12}, '
        '"missing_answers": {"value": 0, "limit": 0}, '
        '"wrong_statements": {"value": 0, "limit": 0}, '
        '"compared": {"statements": %d, "cells": %d}}' % (n, cells))
    assert res["attempted"] == n and "refresh_p50_ms" not in res["metrics"]


@pytest.mark.parametrize("mix,seed,orders", [
    ("scan", 2**31 + 11, [[1, 3, 0, 2], [2, 1, 0, 3]]),
    ("scan", 5, [[2, 1, 3, 0], [1, 0, 3, 2]]),
    ("join", 2**31 + 11, [[0]]),
])
def test_stream_orders_are_the_parents_for_a_fixed_seed(mix, seed, orders):
    with open(os.path.join(ROOT, "benchmarks", "traffic", mix + ".json")) as f:
        assert traffic.stream_orders(json.load(f), seed) == orders


def test_p95_is_reported_only_where_the_benchmark_lists_it(results):
    assert "stmt_p95_ms" in results["tpch_sf1.scan"][1]["metrics"]
    assert "stmt_p95_ms" not in results["tpch_sf1.join"][1]["metrics"]
    assert "stmt_p95_ms" not in results["tpch_sf1_mesh4.join"][1]["metrics"]


def test_traced_run_reports_per_layer_metrics_and_no_end_to_end():
    cell, res = rehearse("tpch_sf1.join", trace=True)
    assert res["correct"] is True
    assert set(res) == RESULT_KEYS | {"breakdown"}
    assert list(res)[-1] == "checks"
    assert res["metrics"]["dispatches_per_stmt"]["value"] == 3.0
    assert res["metrics"]["window_compiles"]["value"] == 0.0
    # no TPU plane in a CPU trace: the trace's readers find nothing to
    # read and return nothing; no CPU number under a device metric's name
    for name in ("device_idle_pct", "device_ms_per_stmt", "join_roofline"):
        assert name not in res["metrics"]
    assert not set(res["metrics"]) & {m["name"] for m in cell.end_to_end()}
    assert not os.path.exists(os.path.join(ROOT, ".bench_trace", cell.name))


def test_same_seed_same_data_and_traffic_another_seed_others():
    mix = spec.Cell("tpch_sf1.scan").traffic
    assert traffic.stream_orders(mix, 2**31 + 11) == traffic.stream_orders(mix, 2**31 + 11)
    orders = {json.dumps(traffic.stream_orders(mix, s)) for s in range(8)}
    assert len(orders) > 4
    for order in traffic.stream_orders(mix, 5):  # every seed: the whole menu
        assert sorted(order) == list(range(len(mix["menu"])))
    a, b, c = (tpch_datagen.generate(SF, s) for s in (2**31 + 11, 2**31 + 11, 12))
    for table in a:
        for col in a[table][0]:
            assert np.array_equal(a[table][0][col], b[table][0][col])
    assert not np.array_equal(a["lineitem"][0]["l_quantity"],
                              c["lineitem"][0]["l_quantity"])
    # every seed: the same row counts, so the same device shapes
    assert ({t: len(v[0][next(iter(v[0]))]) for t, v in a.items()}
            == {t: len(v[0][next(iter(v[0]))]) for t, v in c.items()}
            == tpch_datagen.sizes(SF))


def test_generated_data_keeps_the_specs_rules():
    assert tpch_datagen.sizes(1.0)["lineitem"] == 6_001_215
    t = tpch_datagen.generate(SF, 3)
    li, od = t["lineitem"][0], t["orders"][0]
    per_order = np.bincount(li["l_orderkey"])[1:]
    assert per_order.min() >= 1 and per_order.max() <= 7
    assert len(per_order) == len(od["o_orderkey"])
    assert li["l_quantity"].min() == 100 and li["l_quantity"].max() == 5000
    assert li["l_discount"].max() == 10 and li["l_tax"].max() == 8
    assert (li["l_extendedprice"] == li["l_quantity"] // 100
            * tpch_datagen.retail_price(li["l_partkey"])).all()
    charge = li["l_extendedprice"] * (100 - li["l_discount"]) * (100 + li["l_tax"]) // 10000
    assert np.array_equal(np.bincount(li["l_orderkey"], weights=charge)[1:].astype(np.int64),
                          od["o_totalprice"])
    for table, (arrays, pools) in t.items():
        for col, pool in pools.items():
            assert list(pool) == sorted(set(pool)), (table, col)
            assert arrays[col].min() >= 0 and arrays[col].max() < len(pool)


# -- faults under the timed path: correct has to come out false -------------

def test_an_answer_altered_where_it_is_produced_fails_the_run(monkeypatch):
    from tidb_tpu.server.client import Client

    real, seen = Client.query, {"n": 0}

    def altered(self, sql):
        names, rows = real(self, sql)
        if rows and sql.startswith("select count(*)"):
            seen["n"] += 1
            if seen["n"] == 3:  # one answer, inside the window
                rows = [(rows[0][0], str(rows[0][1])[:-1] + "7")]
        return names, rows

    monkeypatch.setattr(Client, "query", altered)
    _cell, res = rehearse("tpch_sf1.join")
    assert res["correct"] is False
    assert res["failed"] == 1 and res["attempted"] > 1
    assert res["checks"]["exact_mismatches"]["value"] == 1
    assert res["checks"]["wrong_statements"]["value"] == 1
    assert "rows_per_s" in res["metrics"]  # the others still count


def test_half_of_the_rows_left_out_fails_the_run(monkeypatch):
    real = system.start_server

    def half(tables, pks, cluster_by):
        arrays, pools = tables["lineitem"]
        n = len(arrays["l_orderkey"]) // 2
        cut = dict(tables)
        cut["lineitem"] = ({k: v[:n] for k, v in arrays.items()}, pools)
        return real(cut, pks, cluster_by)

    monkeypatch.setattr(system, "start_server", half)
    _cell, res = rehearse("tpch_sf1.scan")
    assert res["correct"] is False and res["failed"] == res["attempted"]
    assert res["checks"]["exact_mismatches"]["value"] > 0
    assert "rows_per_s" not in res["metrics"]  # no rate from wrong answers


def test_a_statement_that_never_answers_fails_the_run(monkeypatch):
    from tidb_tpu.server.client import Client

    real, seen = Client.query, {"n": 0}

    def lost(self, sql):
        seen["n"] += 1
        if seen["n"] == 4:
            raise TimeoutError("timed out")
        return real(self, sql)

    monkeypatch.setattr(Client, "query", lost)
    _cell, res = rehearse("tpch_sf1.join")
    assert res["correct"] is False
    assert res["checks"]["missing_answers"]["value"] == 1


def test_the_exchange_between_chips_left_out_fails_the_run(monkeypatch):
    import jax

    # every row stays on the chip that scanned it: matches whose two sides
    # hash to another chip are lost
    monkeypatch.setattr(jax.lax, "all_to_all", lambda x, *_a, **_k: x)
    cell, res = rehearse("tpch_sf1_mesh4.join")
    assert cell.chips == 4 and res["device"]["count"] >= 4  # a mesh, not one device
    assert res["correct"] is False and res["failed"] == res["attempted"]
    assert res["checks"]["exact_mismatches"]["value"] > 0


@pytest.mark.parametrize("name,sf", [("tpch_sf1.scan", SF), ("tpch_sf1.join", 0.05),
                                     ("tpch_sf1_mesh4.join", 0.05)])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float32_control_comes_out_not_correct(name, sf, seed):
    out = control.control_run(spec.Cell(name), seed, sf=sf)
    assert out["correct"] is False
    assert out["checks"]["exact_mismatches"]["value"] >= 1


def test_compare_rows_kinds():
    E = reference.Exact
    want = [("A", E(123456, 2), 7, 0.5)]
    assert reference.compare_rows([("A", "1234.56", "7", "0.5")], want)["exact_mismatches"] == 0
    assert reference.compare_rows([("A", "1234.560", "7", "0.5")], want)["exact_mismatches"] == 0
    assert reference.compare_rows([("A", "1234.57", "7", "0.5")], want)["exact_mismatches"] == 1
    assert reference.compare_rows([("B", "1234.56", "8", "0.5")], want)["exact_mismatches"] == 2
    gap = reference.compare_rows([("A", "1234.56", "7", "0.50000001")], want)["float_rel_gap"]
    assert 1e-8 < gap < 3e-8
    assert reference.compare_rows([], want)["exact_mismatches"] == 4
    assert reference.compare_rows(None, want)["exact_mismatches"] == 4
    assert not reference.answer_ok({"exact_mismatches": 0, "float_rel_gap": 1e-9})
    assert reference.answer_ok({"exact_mismatches": 0, "float_rel_gap": 3e-16})


# -- no TPU, no report -------------------------------------------------------

def test_measuring_entry_refuses_to_report_without_a_tpu():
    with pytest.raises(run.NoChip):
        run.run_cell(spec.Cell("tpch_sf1.scan"), 1, 1.0, False)
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="x")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload",
         "tpch_sf1.scan", "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout and "NoChip" in p.stderr


def test_unknown_device_kind_is_an_error_not_a_default():
    from benchmarks import peaks

    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


# -- driven by data: a cell from new files and one entry each ---------------

STATEMENT = '''
from benchmarks.reference import total
TABLES = ("orders",)
COLUMNS = {"orders": ("o_orderdate",)}
ROOFLINE = "scan_agg_roofline"
def sql(p):
    return f"select count(*) as n from orders where o_orderdate < date '{p['before']}'"
def reference(data, p, lowp=None):
    return [(int((data.col("orders", "o_orderdate") < data.days(p["before"])).sum()),)]
'''
READER = '''
def read(ctx):
    return float(ctx.window_statements) or None
'''


def test_a_cell_a_mix_a_statement_and_a_metric_are_added_as_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.join(d, f) for d, _s, fs in os.walk(root) for f in fs}
    here = os.path.join(root, "benchmarks")
    with open(os.path.join(here, "statements", "orders_before.py"), "w") as f:
        f.write(STATEMENT)
    with open(os.path.join(here, "layer_metrics", "stmts_in_window.py"), "w") as f:
        f.write(READER)
    with open(os.path.join(here, "traffic", "tiny.json"), "w") as f:
        json.dump({"loop": "closed", "streams": 1, "warm_passes": 1, "trace_seconds": 1,
                   "menu": [{"statement": "orders_before", "params": {"before": "1995-01-01"}},
                            {"statement": "q6", "params": {"date": "1993-01-01",
                                                           "discount": 5, "quantity": 24}}]}, f)
    with open(os.path.join(here, "traffic", "tiny_written.json"), "w") as f:  # a mix with a writer
        json.dump({"loop": "closed", "streams": 1, "warm_passes": 1, "trace_seconds": 1,
                   "menu": [{"statement": "orders_before", "params": {"before": "1995-01-01"}},
                            {"statement": "q1", "params": {"delta": 75}}],
                   "writers": [{"statement": "rf1", "params": {"orders_per_transaction": 20},
                                "warm_transactions": 0}]}, f)
    with open(os.path.join(here, "configs", "tpch_sf1.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "throwaway"
    with open(os.path.join(here, "configs", "throwaway.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "throwaway", "source": cfg["source"],
                             "file": "benchmarks/configs/throwaway.json",
                             "reduced": ["scale_factor"], "why": "test"})
    bench["workloads"].append({"name": "throwaway.tiny", "config": "throwaway",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    bench["workloads"].append({"name": "throwaway.tiny_written", "config": "throwaway",
                               "traffic": "tiny_written", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "stmts_in_window", "unit": "count", "better": "higher",
                               "source": "program_counter", "layer": "wire",
                               "moves": "rows_per_s", "workloads": ["throwaway.tiny"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = {os.path.join(d, f) for d, _s, fs in os.walk(root) for f in fs}
    assert len(after - before) == 6  # five new files and BENCHMARK.json; none edited

    cell, res = rehearse("throwaway.tiny", trace=True, root=root)
    assert res["correct"] is True and res["attempted"] >= 2
    assert res["metrics"]["stmts_in_window"]["value"] == res["attempted"]
    assert "scan_agg_roofline" not in res["metrics"]  # not this cell's, by its workloads key
    _cell, res = rehearse("throwaway.tiny", root=root)
    assert res["correct"] is True and res["metrics"]["rows_per_s"]["value"] > 0
    # the mix with a writer: files and one entry; a statement with no
    # `state` (orders_before) is answered from the appended arrays
    _cell, res = rehearse("throwaway.tiny_written", seconds=3.0, root=root)
    assert res["correct"] is True and res["checks"]["unread_acknowledged_rows"]["value"] == 0
    did = res["checks"]["compared"]
    assert did["first_k"] == 0 and did["transactions"] >= 1 and max(did["answers_by_k"]) >= 1
    with pytest.raises(spec.SpecError):
        spec.Cell("no.such.cell", root=root)


# -- BENCHMARK.json keeps to the limits the driver refuses a file over ------

NAME = r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}"
UNIT = r"[A-Za-z0-9_/%.\-]{1,16}"


def _one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_benchmark_json_keeps_to_the_contracts_limits():
    import re

    bench = spec.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(bench["command"]) <= 32 and all(_one_line(w) for w in bench["command"])
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    paths = bench["paths"]
    assert 1 <= len(paths) <= 16
    under = lambda f: any(f.startswith(p + "/") for p in paths)  # noqa: E731
    assert under(bench["command"][1])
    configs = {c["name"]: c for c in bench["configs"]}
    assert len(configs) == len(bench["configs"]) <= 24
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert re.fullmatch(NAME, c["name"]) and _one_line(c["source"]) and _one_line(c["why"])
        assert under(c["file"]) and len(c["reduced"]) <= 16
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"] and body["source"] == c["source"]
    assert len({c["file"] for c in bench["configs"]}) == len(configs)
    assert len({c["source"] for c in bench["configs"]}) == len(configs)
    cells = [w["name"] for w in bench["workloads"]]
    assert len(set(cells)) == len(cells) <= 24
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) == len(cells)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert re.fullmatch(NAME, w["name"]) and re.fullmatch(NAME, w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4) and _one_line(w["why"])
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(cells) // 2)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) == len(bench["end_to_end"]) <= 16
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    assert 1 <= len(bench["per_layer"]) <= 128
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and _one_line(m["layer"])
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert re.fullmatch(NAME, m["name"]) and re.fullmatch(UNIT, m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for name in cells:  # every cell: setup_s, another end-to-end metric, a per-layer one
        cell = spec.Cell(name)
        assert len(cell.end_to_end()) >= 2 and cell.per_layer()
    # a full check at this length, with all 24 cells, fits the driver's day
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
