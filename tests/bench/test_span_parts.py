"""The six per-layer metrics that read the phases and counts the program
keeps ON its spans (PR 37: ``benchmarks/program_parts.py``), rehearsed on
the CPU at small scale in each of the six cells. Counts and sums only: a
CPU's milliseconds are never written under a device metric's name. A
traced run of every cell reports all six; ``plan_rows_counted_per_stmt``
is the calls of ``Table.live_rows`` a plan times the tables' rows, exactly;
``device.wait``'s two phases lie inside the span and its bytes are what
``XFER_BYTES{d2h}`` gained; uploads are set-up's, one a table a connection,
and none of the window's; the five accepted span metrics read as their own
test demands, for a phase is no span; a program without phases reads as
nothing.

A one-chip cell's deployment is a 1x1 mesh and the tests' process has
eight CPU devices, so the rehearsal hands the harness a mesh of the cell's
chips: here, in the test, not through an option of the program."""

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import program_parts, program_spans, run, spec  # noqa: E402

FORCE = ("set tidb_device_engine_mode = 'force'",)  # the CPU must ask for the device engine
SIX = {"plan_optimize_ms_per_stmt": ("ms", "plan and engine routing", "stmt_p50_ms"),
       "plan_build_ms_per_stmt": ("ms", "plan and engine routing", "stmt_p50_ms"),
       "plan_rows_counted_per_stmt": ("count", "plan and engine routing", "stmt_p50_ms"),
       "device_copy_ms_per_stmt": ("ms", "mesh tier programs", "stmt_p50_ms"),
       "fetched_mb_per_stmt": ("MB", "mesh tier programs", "stmt_p50_ms"),
       "setup_upload_s": ("s", "mesh tier residency", "setup_s")}
FIVE = ["queue_ms_per_stmt", "wire_ms_per_stmt", "plan_ms_per_stmt",
        "exec_host_ms_per_stmt", "device_wait_ms_per_stmt"]
# calls of Table.live_rows one plan makes, by statement and table: the
# same at every scale and on a mesh of one part and of four (ISSUE 37)
CALLS = {"q6": {"lineitem": 3}, "q1": {"lineitem": 4},
         "join_lo": {"lineitem": 4, "orders": 4},
         "q18agg": {"lineitem": 5},
         "q3": {"lineitem": 9, "orders": 7, "customer": 7},
         "q18": {"lineitem": 18, "orders": 8, "customer": 8}}
# cell -> (parts of its mesh, scale factor of the rehearsal)
CELLS = {"tpch_sf1.scan": (1, 0.01), "tpch_sf1.join": (1, 0.01),
         "tpch_sf1_mesh4.join": (4, 0.01), "tpch_sf1_pk.q18agg": (1, 0.01),
         "tpch_sf1_power.q3": (1, 0.01), "tpch_sf1_power.q18": (1, 0.05)}


def _d2h() -> float:
    from tidb_tpu.utils.metrics import XFER_BYTES

    return sum(v for lbl, v in XFER_BYTES.samples() if lbl.get("dir") == "d2h")


@pytest.fixture(scope="module", params=list(CELLS))
def traced(request):
    """One traced rehearsal of a cell, with the reader's selection, its
    context, every call of ``Table.live_rows`` by trace, and
    ``XFER_BYTES{d2h}`` read around the window."""
    import jax

    import tidb_tpu.parallel as par
    from tidb_tpu.storage.table import Table
    from tidb_tpu.utils import tracing

    parts, sf = CELLS[request.param]
    seen = {"calls": []}
    real_traces, real_window = program_spans.window_traces, run.drive_window
    real_mesh, real_live = par.make_mesh, Table.live_rows

    def spy_traces(ctx):
        seen["ctx"], seen["traces"] = ctx, real_traces(ctx)
        return seen["traces"]

    def spy_window(*a, **kw):
        b0 = _d2h()
        out = real_window(*a, **kw)
        seen["d2h"] = _d2h() - b0
        return out

    def spy_live(self):
        seen["calls"].append((tracing.current_trace_id(), self.schema.name, self.n))
        return real_live.fget(self)

    cell = spec.Cell(request.param)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(program_spans, "window_traces", spy_traces)
        mp.setattr(run, "drive_window", spy_window)
        mp.setattr(par, "make_mesh", lambda: real_mesh(devices=jax.devices()[:parts]))
        mp.setattr(Table, "live_rows", property(spy_live))
        res = run.run_cell(cell, 2**31 + 37, 1.5, True, require_chip=False,
                           sf=sf, pre_sql=FORCE)
    assert res["correct"] is True and res["failed"] == 0
    return SimpleNamespace(cell=cell, res=res, ctx=seen["ctx"], traces=seen["traces"],
                           calls=seen["calls"], d2h=seen["d2h"],
                           value=lambda name: res["metrics"][name]["value"])


def test_the_six_entries_are_appended_and_nothing_before_them_moved():
    bench = spec.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:21][-1] == "subquery_host_ms_per_stmt"
    assert names[21:27] == list(SIX)
    for m in bench["per_layer"][21:27]:
        unit, layer, moves = SIX[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": "program_span", "layer": layer, "moves": moves}
        assert layer in {e["layer"] for e in bench["per_layer"][:21]}
    assert len(bench["workloads"]) == 6 and len(bench["end_to_end"]) == 4


def test_a_traced_run_reports_the_six_and_every_entry_has_its_reader(traced):
    for name, (unit, _layer, _moves) in SIX.items():
        assert callable(traced.cell.reader(name))
        assert traced.res["metrics"][name]["unit"] == unit
        assert traced.value(name) >= 0
    # a cell's statements are planned, fetch and were uploaded once
    for name in ("plan_optimize_ms_per_stmt", "plan_build_ms_per_stmt",
                 "plan_rows_counted_per_stmt", "device_copy_ms_per_stmt",
                 "fetched_mb_per_stmt", "setup_upload_s"):
        assert traced.value(name) > 0, name
    assert len(traced.traces) == traced.res["attempted"] > 0


def test_live_rows_calls_a_plan_and_the_rows_they_read(traced):
    """Planning is all of a warm statement's calls; by statement they are
    3 (Q6), 4 (Q1), 8 (join_lo), 5 (q18agg), 23 (Q3), 34 (Q18)."""
    menu = [m["statement"] for m in traced.cell.traffic["menu"]]
    rows = {tab: n for _tid, tab, n in traced.calls}  # a table's n does not move
    by_trace = {}
    for tid, tab, _n in traced.calls:
        by_trace.setdefault(tid, {}).setdefault(tab, 0)
        by_trace[tid][tab] += 1
    window = [by_trace.get(tr.trace_id, {}) for tr in traced.traces]
    want = [CALLS[menu[r["item"]]] for r in traced.ctx.records]
    key = lambda d: sorted(d.items())  # noqa: E731
    assert sorted(window, key=key) == sorted(want, key=key)
    assert {sum(c.values()) for c in want} <= {3, 4, 8, 5, 23, 34}
    total = sum(calls * rows[tab] for c in want for tab, calls in c.items())
    assert traced.value("plan_rows_counted_per_stmt") == total / len(want)
    # the count sits on the span that planned, whoever asked
    for tr, c in zip(traced.traces, window):
        assert tr.counts().get("session.plan/rows_counted", 0) \
            == sum(calls * rows[tab] for tab, calls in c.items())


def test_the_parts_lie_inside_their_spans_and_their_metrics(traced):
    for tr in traced.traces:
        for s in list(tr.spans):
            if s.phases:
                assert sum(us for us, _calls in s.phases.values()) <= s.dur_us, s.name
            if s.name == "device.wait":
                assert list(s.phases) == ["ready", "copy"] and s.counts["bytes"] >= 0
            if s.name == "session.plan":
                assert list(s.phases) == ["cache", "bind", "rules", "lower", "privs", "build"]
                assert all(calls == 1 for _us, calls in s.phases.values())
    assert traced.value("plan_optimize_ms_per_stmt") + traced.value("plan_build_ms_per_stmt") \
        <= traced.value("plan_ms_per_stmt")
    assert traced.value("device_copy_ms_per_stmt") <= traced.value("device_wait_ms_per_stmt")


def test_fetched_bytes_are_what_the_programs_counter_gained(traced):
    n = len(traced.traces)
    assert traced.d2h > 0
    assert traced.value("fetched_mb_per_stmt") * n * 1e6 == pytest.approx(traced.d2h, rel=1e-12)
    assert sum(tr.counts().get("device.wait/bytes", 0) for tr in traced.traces) == traced.d2h


def test_uploads_are_set_ups_one_a_table_a_connection(traced):
    for tr in traced.traces:
        assert "stage.upload" not in [s.name for s in list(tr.spans)]
    assert traced.value("stage_uploads_per_stmt") == 0.0
    setup = program_parts.setup_traces(traced.ctx)
    warm = int(traced.cell.traffic.get("warm_passes", 1)) \
        * int(traced.cell.traffic["streams"]) * len(traced.cell.traffic["menu"])
    assert len(setup) == warm == len(traced.ctx.warm)
    assert not {id(t) for t in setup} & {id(t) for t in traced.traces}
    ups = [s for tr in setup for s in list(tr.spans) if s.name == "stage.upload"]
    tables = {t for mod in traced.cell.statements.values() for t in mod.TABLES}
    assert len(ups) == len(tables) * int(traced.cell.traffic["streams"])
    assert traced.value("setup_upload_s") == sum(s.dur_us for s in ups) / 1e6
    # what went to device_put is what lives on the device, a connection
    assert sum(s.counts["bytes"] for s in ups) == int(traced.cell.traffic["streams"]) \
        * sum(traced.ctx.shapes[t]["bytes"] for t in tables)
    # the site counts stay as they are: two a column, one for the live mask
    per_conn = sum(2 * len(traced.ctx.shapes[t]["columns"]) + 1 for t in tables)
    assert traced.ctx.setup_counters["dispatch:stage"] \
        == per_conn * int(traced.cell.traffic["streams"])


def test_the_five_accepted_span_metrics_read_as_before(traced):
    """A phase is no span: ``session.plan`` and ``device.wait`` have no
    children, their self time is their duration, and the five partition
    what the clients measured (``test_program_spans.py``'s own demand)."""
    for tr in traced.traces:
        self_us = tr.self_us()
        parents = {s.parent_id for s in list(tr.spans)}
        for s in list(tr.spans):
            if s.name in ("session.plan", "device.wait"):
                assert s.span_id not in parents and self_us[s.span_id] == s.dur_us
            assert program_spans.group_of(s.name) in program_spans.GROUPS
    lat = [(r["t_done"] - r["t_send"]) / 1e6 for r in traced.ctx.records]
    mean = sum(lat) / len(lat)
    five = sum(traced.value(n) for n in FIVE)
    assert abs(five - mean) <= max(0.05 * mean, 2.0), (five, mean)
    roots = sum(t.root().dur_us for t in traced.traces) / 1e3 / len(lat)
    assert five == pytest.approx(roots, abs=0.05)


def test_a_program_without_phases_reads_as_nothing(monkeypatch, traced):
    """The parent commit's traces have neither ``phases_us`` nor
    ``counts``: the six find nothing to read, return nothing and do not
    raise; the five accepted readers read what they read."""
    from tidb_tpu.utils import tracing

    def fresh():
        return run.Context(traced.cell, traced.ctx.device, traced.ctx.peaks, {},
                           traced.ctx.records, 1.0, {}, {}, None,
                           {"warm": traced.ctx.warm})

    ctx = fresh()
    for name in SIX:
        assert traced.cell.reader(name)(ctx) == traced.value(name)
    monkeypatch.delattr(tracing.Trace, "phases_us")
    monkeypatch.delattr(tracing.Trace, "counts")
    ctx = fresh()
    for name in SIX:
        assert traced.cell.reader(name)(ctx) is None
    for name in FIVE:
        assert traced.cell.reader(name)(ctx) == pytest.approx(traced.value(name))
