"""The five per-layer metrics that read the program's spans, rehearsed on
the CPU at SF0.01 (counts and sums only: a CPU's milliseconds are never
written under a device metric's name, and these are host spans): a traced
run of the scan cell reports all five; the reader selects exactly the
window's statements, none of the warm-up's; the five partition what the
clients measured; a program without the ring reads as nothing."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import program_spans, run, spec  # noqa: E402

SF = 0.01
FORCE = ("set tidb_device_engine_mode = 'force'",)  # the CPU must ask for the device engine
FIVE = ["queue_ms_per_stmt", "wire_ms_per_stmt", "plan_ms_per_stmt",
        "exec_host_ms_per_stmt", "device_wait_ms_per_stmt"]


@pytest.fixture(scope="module")
def traced_scan():
    """One traced rehearsal of the scan cell, with the reader's selection
    and its context kept for a look."""
    seen = {}
    real = program_spans.window_traces

    def spy(ctx):
        seen["ctx"], seen["traces"] = ctx, real(ctx)
        return seen["traces"]

    program_spans.window_traces = spy
    try:
        cell = spec.Cell("tpch_sf1.scan")
        res = run.run_cell(cell, 2**31 + 25, 1.5, True, require_chip=False,
                           sf=SF, pre_sql=FORCE)
    finally:
        program_spans.window_traces = real
    return cell, res, seen


def test_a_traced_run_reports_the_five_and_every_entry_has_its_reader(traced_scan):
    cell, res, _seen = traced_scan
    assert res["correct"] is True and res["failed"] == 0
    for name in FIVE:
        assert res["metrics"][name]["unit"] == "ms"
        assert res["metrics"][name]["value"] >= 0
    assert res["metrics"]["device_wait_ms_per_stmt"]["value"] > 0
    assert res["metrics"]["plan_ms_per_stmt"]["value"] > 0
    entries = {m["name"]: m for m in cell.bench["per_layer"]}
    for name in FIVE:
        assert entries[name]["source"] == "program_span"
        assert entries[name]["moves"] == "stmt_p50_ms"
        assert "workloads" not in entries[name]
        assert callable(cell.reader(name))


def test_the_reader_selects_the_windows_statements_and_no_other(traced_scan):
    from tidb_tpu.utils import tracing

    _cell, res, seen = traced_scan
    assert len(seen["traces"]) == res["attempted"] > 0
    first_send = min(r["t_send"] for r in seen["ctx"].records) / 1e9
    chosen = {id(t) for t in seen["traces"]}
    warm = [t for t in tracing.STORE.finished()
            if t.root().name == "wire.stmt" and id(t) not in chosen
            and t.interval_perf()[1] < first_send]
    # every statement of the mix ran on every connection before the
    # window: finished traces, ended before its first send, not counted
    assert len(warm) >= 2 * len(_cell.traffic["menu"])
    assert all(t.interval_perf()[0] >= first_send for t in seen["traces"])


def test_the_five_partition_what_the_clients_measured(traced_scan):
    _cell, res, seen = traced_scan
    lat = [(r["t_done"] - r["t_send"]) / 1e6 for r in seen["ctx"].records]
    mean = sum(lat) / len(lat)
    five = sum(res["metrics"][n]["value"] for n in FIVE)
    assert abs(five - mean) <= max(0.05 * mean, 2.0), (five, mean)
    # and, span by span, they are the traces' root durations
    roots = sum(t.root().dur_us for t in seen["traces"]) / 1e3 / len(lat)
    assert five == pytest.approx(roots, abs=0.05)


def test_every_span_name_has_one_group():
    for name, group in [("sched.queue", "queue"), ("sched.lock_wait", "queue"),
                        ("wire.stmt", "wire"), ("wire.write", "wire"),
                        ("session.parse", "plan"), ("session.plan", "plan"),
                        ("device.wait", "device_wait"),
                        ("stmt.select", "exec_host"),
                        ("session.execute", "exec_host"),
                        ("dispatch.fragment", "exec_host"),
                        ("dispatch.fetch", "exec_host"),
                        ("fragment.scan_agg[parts=1]", "exec_host")]:
        assert program_spans.group_of(name) == group
        assert group in program_spans.GROUPS


def test_a_program_without_the_ring_reads_as_nothing(monkeypatch, traced_scan):
    """The parent commit's tracer has no ring of finished traces: the
    readers find nothing to read, return nothing and do not raise."""
    from tidb_tpu.utils import tracing

    class Parent:  # what PR 24's TraceStore offers a reader
        def traces(self):
            return []

    cell, _res, seen = traced_scan
    monkeypatch.setattr(tracing, "STORE", Parent())
    ctx = run.Context(cell, seen["ctx"].device, seen["ctx"].peaks, {},
                      seen["ctx"].records, 1.0, {}, {}, None, {})
    for name in FIVE:
        assert cell.reader(name)(ctx) is None
