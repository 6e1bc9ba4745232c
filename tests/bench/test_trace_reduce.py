"""The reduction from a profiler trace to numbers, on a hand-made trace
and on the small cut of a real one recorded on a TPU v5e
(``benchmarks/testdata/``), and the bytes functions against hand-worked
numbers at SF1 shapes. Nothing here describes a TPU topology or compiles
for one."""

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import peaks, spec, work  # noqa: E402
from benchmarks import trace_reduce as tr  # noqa: E402

US = 1000  # ns


def synthetic():
    """Two chips; ops in microseconds. Chip 0: a 'while' op 10-40 with two
    body ops inside it, an all-to-all 50-60, a fusion 80-90 in a second
    program. Chip 1: one op 0-25."""
    def ev(name, a, b):
        return [name, a * US, (b - a) * US]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [ev("jit_frag(123)", 5, 65),
                                               ev("jit_project(9)", 78, 92)]},
            {"name": "XLA Ops", "events": [ev("while.1", 10, 40), ev("sort.2", 12, 20),
                                           ev("fusion.3", 22, 38), ev("all-to-all.4", 50, 60),
                                           ev("fusion.5", 80, 90)]},
            {"name": "Steps", "events": [ev("0", 0, 100)]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [ev("fusion.9", 0, 25)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "bench-stream-0", "events": [ev("bench.query:0", 2, 70),
                                                  ev("bench.query:1", 75, 99)]},
            {"name": "main", "events": [ev("bench.sync_begin", 0, 1),
                                        ev("bench.sync_end", 100, 101)]}]},
        {"name": "/device:CUSTOM:x", "lines": [
            {"name": "XLA Ops", "events": [ev("not.a.chip", 0, 100)]}]},
    ]}


def test_busy_union_idle_share_and_nested_ops():
    r = tr.reduce_trace(synthetic(), 0, 100 * US)
    d0, d1 = r["devices"]
    assert [d["plane"] for d in r["devices"]] == ["/device:TPU:0", "/device:TPU:1"]
    # nested body ops are not counted twice: 30 + 10 + 10
    assert d0["busy_ns"] == 50 * US and d0["op_ns"] == 50 * US and d0["ops"] == 3
    assert d1["busy_ns"] == 25 * US
    assert d0["collective_ns"] == 10 * US and d1["collective_ns"] == 0
    assert r["busy_ns_max"] == 50 * US and r["busy_ns_mean"] == 37.5 * US
    assert r["span_ns"] == 100 * US
    assert d0["by_module_ns"] == {"jit_frag(123)": 40 * US, "jit_project(9)": 10 * US}


def test_span_clips_events_at_both_ends():
    r = tr.reduce_trace(synthetic(), 20 * US, 85 * US)
    assert r["devices"][0]["busy_ns"] == (20 + 10 + 5) * US
    assert r["devices"][1]["busy_ns"] == 5 * US


def test_device_ops_by_module_and_op_mean_over_chips():
    r = tr.reduce_trace(synthetic(), 0, 100 * US)
    ops = dict(r["device_ops"])
    assert ops["jit_frag(123)/while.1"] == pytest.approx(30e-6 / 2)
    assert ops["jit_frag(123)/all-to-all.4"] == pytest.approx(10e-6 / 2)
    assert ops["jit_project(9)/fusion.5"] == pytest.approx(10e-6 / 2)
    assert ops["(no module)/fusion.9"] == pytest.approx(25e-6 / 2)
    assert r["device_ops"][0][0] == "jit_frag(123)/while.1"  # longest first


def test_gaps_and_what_the_host_was_doing():
    t = synthetic()
    ops0 = tr.line_events(tr.device_planes(t)[0], tr.OPS_LINE)
    assert tr.gaps(ops0, 0, 100 * US) == [(0, 10 * US), (40 * US, 50 * US),
                                          (60 * US, 80 * US), (90 * US, 100 * US)]
    r = tr.reduce_trace(t, 0, 100 * US)
    # the longest gaps are the busiest chip's (chip 0), not chip 1's 75 us
    assert r["idle_gaps"][0] == ["host, inside bench.query:0", pytest.approx(20e-6)]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx([20e-6, 10e-6, 10e-6, 10e-6])
    by = r["idle_by_host_s"]
    # chip 0: 10 + 10 + 20(of which 60-70 in q0, 75-80 in q1: q0 covers more) + 10; chip 1: 75
    assert by["host, inside bench.query:0"] == pytest.approx((8 + 10 + 20 + 75) * 1e-6 / 2, rel=0.2)
    assert len(r["idle_gaps"]) <= 10 and len(r["device_ops"]) <= 10


def test_spans_given_by_the_caller_label_the_gaps_and_op_names_are_shortened():
    r = tr.reduce_trace(synthetic(), 0, 100 * US, spans=[["bench.query:q6#0", 0, 100 * US]])
    assert set(r["idle_by_host_s"]) == {"host, inside bench.query:q6#0"}
    r = tr.reduce_trace(synthetic(), 0, 100 * US, spans=[])
    assert set(r["idle_by_host_s"]) == {"no statement in flight"}
    hlo = ("%fusion.875 = u32[12002430]{0:T(1024)} fusion(u32[3000000]{0:T(1024)} %gte.994, "
           "s32[12003328]{0:T(1024)} %pad.9), kind=kCustom, calls=%fused_computation.clone")
    assert tr.short_op(hlo) == "%fusion.875 u32[12002430] fusion"
    assert tr.short_op("fusion.3") == "fusion.3"


def test_a_collective_is_told_by_its_opcode_not_by_its_name():
    # on the chip jax's all_to_all names the reshapes around the exchange too
    exchange = ("%all_to_all.61 = u32[4,1,750152]{2,1,0:T(1,128)S(1)} all-to-all(u32[4,1,750152]"
                "{2,1,0:T(1,128)S(1)} %all_to_all.60), channel_id=1, replica_groups={{0,1,2,3}}")
    reshape = "%all_to_all.60 = u32[4,1,750152]{2,1,0:T(1,128)S(1)} reshape(u32[3000608]{0:T(1024)S(1)} %slice.88)"
    psum = "%all-reduce.30 = (u32[1]{0:T(128)}, u32[1]{0:T(128)}) all-reduce(u32[1]{0:T(128)} %a, u32[1]{0:T(128)} %b)"
    fused = "%fusion.7 = u32[8]{0:T(128)} fusion(u32[8]{0} %all-reduce.30), kind=kLoop"
    assert [tr.opcode(n) for n in (exchange, reshape, psum, fused, "all-gather-start.2", "%while.1")] == [
        "all-to-all", "reshape", "all-reduce", "fusion", "all-gather-start", "while"]
    assert [bool(tr.COLLECTIVE.fullmatch(tr.opcode(n))) for n in (
        exchange, reshape, psum, fused, "all-gather-start.2")] == [True, False, True, False, True]


def test_no_device_plane_gives_nothing_to_read():
    t = {"planes": [p for p in synthetic()["planes"] if p["name"] == "/host:CPU"]}
    r = tr.reduce_trace(t, 0, 100 * US)
    assert r["devices"] == [] and r["busy_ns_max"] == 0 and r["device_ops"] == []


def test_cut_keeps_only_the_span():
    c = tr.cut(synthetic(), 45 * US, 85 * US)
    ops = tr.line_events(tr.device_planes(c)[0], tr.OPS_LINE)
    assert ops == [["all-to-all.4", 50 * US, 10 * US], ["fusion.5", 80 * US, 5 * US]]


# -- the recorded cut of a real trace ---------------------------------------

FIXTURE = os.path.join(ROOT, "benchmarks", "testdata", "scan_v5e_cut.trace.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def test_recorded_cut_is_small_and_has_one_tpu_plane(recorded):
    assert os.path.getsize(FIXTURE) < 1_000_000
    planes = tr.device_planes(recorded)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    assert tr.line_events(planes[0], tr.OPS_LINE)
    assert tr.line_events(planes[0], tr.MODULES_LINE)
    assert any(e[0].startswith("bench.query:") for e in tr.host_spans(recorded))


def test_recorded_cut_reduces_to_the_numbers_worked_by_hand(recorded):
    with open(os.path.join(ROOT, "benchmarks", "testdata", "scan_v5e_cut.expected.json")) as f:
        want = json.load(f)
    r = tr.reduce_trace(recorded, want["t0"], want["t1"])
    d = r["devices"][0]
    assert d["busy_ns"] == want["busy_ns"] and d["ops"] == want["ops"]
    assert d["op_ns"] == want["op_ns"]
    assert 0 < d["busy_ns"] <= d["op_ns"] <= r["span_ns"]  # one chip: ops never overlap... or nest
    assert 100.0 * (1 - r["busy_ns_max"] / r["span_ns"]) == pytest.approx(want["idle_pct"])
    assert d["by_module_ns"] == want["by_module_ns"]
    assert sum(d["by_module_ns"].values()) == d["op_ns"]
    assert r["device_ops"][0][0] == want["top_op"]
    gaps = sum(g[1] for g in r["idle_gaps"])
    assert gaps <= (r["span_ns"] - d["busy_ns"]) / 1e9 + 1e-12


def test_recorded_four_chip_cut_reduces_to_the_numbers_worked_apart():
    """A statement of the four-chip join: busy time and the exchange's
    time per chip as a separate sweep over the events gave them (the
    script that cut the fixture; nothing of trace_reduce)."""
    here = os.path.join(ROOT, "benchmarks", "testdata")
    assert os.path.getsize(os.path.join(here, "join_mesh4_v5e_cut.trace.json.gz")) < 1_000_000
    with gzip.open(os.path.join(here, "join_mesh4_v5e_cut.trace.json.gz"), "rt") as f:
        cut = json.load(f)
    with open(os.path.join(here, "join_mesh4_v5e_cut.expected.json")) as f:
        want = json.load(f)
    r = tr.reduce_trace(cut, want["t0"], want["t1"], spans=[])
    assert [d["plane"] for d in r["devices"]] == [f"/device:TPU:{i}" for i in range(4)]
    for d, w in zip(r["devices"], want["devices"]):
        assert d["busy_ns"] == w["busy_ns"] and d["collective_ns"] == w["collective_ns"]
        assert d["op_ns"] == d["busy_ns"]  # nested while bodies are not counted twice
    assert r["collective_ns_mean"] == sum(w["collective_ns"] for w in want["devices"]) / 4
    assert 100.0 * (1 - r["busy_ns_max"] / r["span_ns"]) == pytest.approx(want["idle_pct_busiest"])
    assert r["device_ops"][0][1] <= r["busy_ns_mean"] / 1e9  # seconds of one chip, not of four


# -- bytes: from the statement and the shapes, never the implementation ------

def sf1_shapes():
    def table(rows, cols):
        return {"columns": {n: (dt, (1, rows)) for n, dt in cols.items()},
                "valid": {n: ("bool", (1, rows)) for n in cols},
                "sel": ("bool", (1, rows))}
    i8, i4 = "int64", "int32"
    return {
        "lineitem": table(6_001_215, {
            "l_orderkey": i8, "l_quantity": i8, "l_extendedprice": i8, "l_discount": i8,
            "l_tax": i8, "l_returnflag": i4, "l_linestatus": i4, "l_shipdate": i4}),
        "orders": table(1_500_000, {"o_orderkey": i8, "o_totalprice": i8})}


@pytest.mark.parametrize("cell,statement,per_row,total", [
    # Q6: shipdate 4 + discount 8 + quantity 8 + extendedprice 8, 4 validity bytes, 1 selection
    ("tpch_sf1.scan", "q6", 33, 198_040_095),
    # Q1: two flags 4 + 4, four decimals 8 each, shipdate 4, 7 validity bytes, 1 selection
    ("tpch_sf1.scan", "q1", 52, 312_063_180),
    # join: (orderkey 8 + quantity 8 + 2 + 1) x 6,001,215 + (orderkey 8 + totalprice 8 + 2 + 1) x 1.5M
    ("tpch_sf1.join", "join_lo", None, 114_023_085 + 28_500_000),
])
def test_min_bytes_at_sf1_shapes(cell, statement, per_row, total):
    mod = spec.Cell(cell).statements[statement]
    got = work.min_bytes(mod.COLUMNS, sf1_shapes())
    assert got == total
    if per_row:
        assert got == per_row * 6_001_215


def test_least_seconds_names_its_bound():
    v5e = peaks.peaks("TPU v5 lite")
    cell = spec.Cell("tpch_sf1.join")
    q6 = spec.Cell("tpch_sf1.scan").statements["q6"]
    s, bound = work.least_seconds(q6.COLUMNS, sf1_shapes(), v5e, 1, exchanged=False)
    assert bound == "hbm" and s == pytest.approx(198_040_095 / 819e9)  # 0.2418 ms
    join = cell.statements["join_lo"]
    s1, b1 = work.least_seconds(join.COLUMNS, sf1_shapes(), v5e, 1, exchanged=True)
    assert b1 == "hbm" and s1 == pytest.approx(142_523_085 / 819e9)
    # four chips: a quarter of the bytes per chip from HBM (43.5 us), three
    # quarters of that quarter over a 200 GB/s interconnect (133.6 us)
    s4, b4 = work.least_seconds(join.COLUMNS, sf1_shapes(), v5e, 4, exchanged=True)
    assert b4 == "interconnect"
    assert s4 == pytest.approx(142_523_085 / 4 * 0.75 / 200e9)
