"""Partitioned device join (ISSUE 3): edge cases under the fused
kernels (ops/join_kernels.py) plus the retrace guard.

Every test runs the DEVICE tier explicitly (tidb_device_engine_mode =
force — the CPU-pinned test backend would otherwise route these joins
to the numpy host path) and most mirror the same statement through the
default auto route, so both tiers stay pinned to identical answers.
"""

import numpy as np
import pytest

from tidb_tpu.session import Session
from tidb_tpu.utils.metrics import JOIN_COMPILE_TOTAL


def _compiles() -> int:
    return int(sum(v for _, v in JOIN_COMPILE_TOTAL.samples()))


def _session(chunk_capacity=256, force_device=True):
    s = Session(chunk_capacity=chunk_capacity)
    s.execute("SET tidb_slow_log_threshold = 300000")
    if force_device:
        s.execute("SET tidb_device_engine_mode = 'force'")
    return s


def _both_tiers(chunk_capacity=256):
    return [_session(chunk_capacity, force_device=True),
            _session(chunk_capacity, force_device=False)]


class TestNullKeySemiAnti:
    """NULL join keys through semi/anti under the fused kernels: NOT IN
    goes empty when the build side holds a NULL; NOT EXISTS keeps
    NULL-key probe rows; IN/EXISTS never match NULL."""

    def _fill(self, s):
        s.execute("create table a (k bigint, v bigint)")
        s.execute("create table b (k bigint)")
        s.execute("insert into a values (1,10),(2,20),(null,30),(3,40)")
        s.execute("insert into b values (1),(null),(3)")

    def test_not_in_null_build(self):
        for s in _both_tiers():
            self._fill(s)
            assert s.query("select v from a where k not in"
                           " (select k from b)") == []

    def test_in_with_nulls(self):
        for s in _both_tiers():
            self._fill(s)
            assert sorted(s.query(
                "select v from a where k in (select k from b)")) == \
                [(10,), (40,)]

    def test_not_exists_keeps_null_probe(self):
        for s in _both_tiers():
            self._fill(s)
            assert sorted(s.query(
                "select v from a where not exists"
                " (select 1 from b where b.k = a.k)")) == [(20,), (30,)]

    def test_exists(self):
        for s in _both_tiers():
            self._fill(s)
            assert sorted(s.query(
                "select v from a where exists"
                " (select 1 from b where b.k = a.k)")) == [(10,), (40,)]


class TestDuplicateHeavyOverflow:
    """A duplicate-heavy build side whose expansion overflows one output
    tile: with chunk_capacity=64 a single probe chunk fans out to many
    [T, 64] tiles, crossing the per-dispatch tile budget."""

    @pytest.mark.parametrize("force", [True, False])
    def test_many_many_overflow(self, force):
        s = _session(chunk_capacity=64, force_device=force)
        s.execute("create table b (k bigint, v bigint)")
        s.execute("create table p (k bigint, w bigint)")
        tb = s.catalog.table("test", "b")
        tp = s.catalog.table("test", "p")
        # 3 keys x 40 duplicates on the build side; 30 probe rows per key
        bk = np.repeat(np.array([1, 2, 3]), 40)
        tb.insert_columns({"k": bk, "v": np.arange(len(bk))})
        pk = np.repeat(np.array([1, 2, 3, 99]), 30)
        tp.insert_columns({"k": pk, "w": np.arange(len(pk))})
        got = s.query("select count(*) as n, sum(b.v) as sv"
                      " from p join b on p.k = b.k")
        # 3 keys x 30 probe x 40 build = 3600 rows >> 64-slot tiles
        n = 3 * 30 * 40
        sv = 30 * sum(range(0, 40)) + 30 * sum(range(40, 80)) \
            + 30 * sum(range(80, 120))
        assert got == [(n, sv)]

    def test_left_join_overflow_with_unmatched(self):
        for s in _both_tiers(chunk_capacity=64):
            s.execute("create table b (k bigint, v bigint)")
            s.execute("create table p (k bigint, w bigint)")
            bk = np.repeat(np.array([7]), 100)
            s.catalog.table("test", "b").insert_columns(
                {"k": bk, "v": np.arange(100)})
            s.catalog.table("test", "p").insert_columns(
                {"k": np.array([7, 8, 9]), "w": np.array([1, 2, 3])})
            got = s.query("select count(*), count(b.v) from p"
                          " left join b on p.k = b.k")
            # 100 matches for k=7 plus one NULL-padded row for 8 and 9
            assert got == [(102, 100)]


class TestZeroRowSides:
    def test_zero_row_build(self):
        for s in _both_tiers():
            s.execute("create table b (k bigint, v bigint)")
            s.execute("create table p (k bigint, w bigint)")
            s.execute("insert into p values (1, 10), (2, 20)")
            assert s.query("select * from p join b on p.k = b.k") == []
            assert sorted(s.query(
                "select w from p left join b on p.k = b.k")) == \
                [(10,), (20,)]
            assert sorted(s.query(
                "select w from p where k not in (select k from b)")) == \
                [(10,), (20,)]

    def test_zero_row_probe(self):
        for s in _both_tiers():
            s.execute("create table b (k bigint, v bigint)")
            s.execute("create table p (k bigint, w bigint)")
            s.execute("insert into b values (1, 10)")
            assert s.query("select * from p join b on p.k = b.k") == []
            assert s.query("select w from p where k in"
                           " (select k from b)") == []


class TestShapeBucketBoundaries:
    """Probe tables at cap-1, cap, cap+1 rows: chunks land exactly on,
    under, and over the shape bucket / tile capacity."""

    @pytest.mark.parametrize("n_probe", [63, 64, 65])
    @pytest.mark.parametrize("force", [True, False])
    def test_boundary_chunks(self, n_probe, force):
        s = _session(chunk_capacity=64, force_device=force)
        s.execute("create table b (k bigint, v bigint)")
        s.execute("create table p (k bigint, w bigint)")
        nb = 16
        s.catalog.table("test", "b").insert_columns(
            {"k": np.arange(nb), "v": np.arange(nb) * 10})
        pk = np.arange(n_probe) % (nb + 4)  # some keys miss the build
        s.catalog.table("test", "p").insert_columns(
            {"k": pk, "w": np.arange(n_probe)})
        got = s.query("select count(*) as n, sum(b.v) as sv"
                      " from p join b on p.k = b.k")
        match = pk < nb
        n = int(match.sum())
        sv = int((pk[match] * 10).sum())
        assert got == [(n, sv if n else None)]


class TestFullInt64DomainKeys:
    @pytest.mark.parametrize("force", [True, False])
    def test_build_keys_span_whole_int64_range(self, force):
        """Build keys at INT64_MIN and INT64_MAX: the key range itself
        does not fit int64 — the pack params must not overflow (was an
        OverflowError regression on every non-host-eligible join)."""
        s = _session(force_device=force)
        s.execute("create table b (k bigint, v bigint)")
        s.execute("create table p (k bigint, w bigint)")
        lo, hi = -(1 << 63), (1 << 63) - 1
        s.execute(f"insert into b values ({lo}, 1), ({hi}, 2), (7, 3)")
        s.execute(f"insert into p values ({lo}, 10), (7, 30), (8, 40)")
        got = sorted(s.query(
            "select p.w, b.v from p left join b on p.k = b.k"),
            key=str)
        assert got == [(10, 1), (30, 3), (40, None)]
    def test_host_sorted_build_escape_hatch(self):
        """tidb_tpu_join_device_build = 0: host sort + staged sorted
        arrays must answer identically to the device build."""
        s = _session(chunk_capacity=128, force_device=True)
        s.execute("create table b (k bigint, v bigint)")
        s.execute("create table p (k bigint, w bigint)")
        rng = np.random.default_rng(5)
        s.catalog.table("test", "b").insert_columns(
            {"k": rng.integers(0, 300, 300), "v": np.arange(300)})
        s.catalog.table("test", "p").insert_columns(
            {"k": rng.integers(0, 300, 1000), "w": np.arange(1000)})
        queries = [
            "select count(*) as n, sum(p.w) as sw, sum(b.v) as sv"
            " from p join b on p.k = b.k",
            "select count(*), count(b.v) from p"
            " left join b on p.k = b.k and b.v < 10",
            "select count(*) from p where k not in (select k from b)",
        ]
        want = [s.query(q) for q in queries]
        s.execute("SET tidb_tpu_join_device_build = 0")
        got = [s.query(q) for q in queries]
        assert got == want


class TestProbeModeEquivalence:
    """ISSUE 10: tidb_tpu_join_probe_mode = xla routes the main
    join's range lookup through the open-addressing hash table (the
    TPU-shaped path, exercised here on CPU). Every mode must answer EXACTLY like the
    searchsorted default across the edge-case grid: NULL-key semi/anti,
    dup-heavy multi-tile expansion, zero-row sides, full-int64-domain
    keys, and shape-bucket boundaries."""

    # sparse 40-bit keys defeat the direct-address index, so the table
    # (or searchsorted) path genuinely runs; dense variants keep the
    # direct index and prove mode is a no-op there
    QUERIES = [
        "select count(*) as n, sum(b.v) as sv, sum(p.w) as sw"
        " from p join b on p.k = b.k",
        "select count(*) from p where k in (select k from b)",
        "select count(*) from p where k not in (select k from b)",
        "select count(*) from p where not exists"
        " (select 1 from b where b.k = p.k)",
        "select count(*), count(b.v) from p left join b on p.k = b.k",
    ]

    def _fill(self, s, nb, npr, sparse=True, with_null=False, stride=64):
        s.execute("create table b (k bigint, v bigint)")
        s.execute("create table p (k bigint, w bigint)")
        rng = np.random.default_rng(nb + npr)
        mul = (1 << 40) if sparse else 1
        if nb:
            s.catalog.table("test", "b").insert_columns(
                {"k": rng.integers(0, max(nb // 2, 1), nb) * mul,
                 "v": np.arange(nb)})
        if npr:
            s.catalog.table("test", "p").insert_columns(
                {"k": rng.integers(0, max(nb, 1) + stride, npr) * mul,
                 "w": np.arange(npr)})
        if with_null:
            s.execute("insert into b values (null, -1)")
            s.execute("insert into p values (null, -1)")

    def _grid(self, fill):
        results = {}
        for mode in ("off", "xla"):
            s = _session(chunk_capacity=256)
            s.execute(f"SET tidb_tpu_join_probe_mode = '{mode}'")
            fill(s)
            results[mode] = [sorted(s.query(q), key=str)
                             for q in self.QUERIES]
        assert results["xla"] == results["off"], "xla table != searchsorted"

    def test_sparse_keys_with_nulls(self):
        self._grid(lambda s: self._fill(s, 300, 1000, sparse=True,
                                        with_null=True))

    def test_dup_heavy_multi_tile(self):
        # 3 keys x 50 dups x many probes: expansion overflows the
        # per-dispatch tile budget under chunk_capacity=256
        def fill(s):
            s.execute("create table b (k bigint, v bigint)")
            s.execute("create table p (k bigint, w bigint)")
            bk = np.repeat(np.array([1, 2, 3]) * (1 << 40), 50)
            s.catalog.table("test", "b").insert_columns(
                {"k": bk, "v": np.arange(len(bk))})
            pk = np.repeat(np.array([1, 2, 3, 99]) * (1 << 40), 40)
            s.catalog.table("test", "p").insert_columns(
                {"k": pk, "w": np.arange(len(pk))})
        self._grid(fill)

    def test_zero_row_sides(self):
        self._grid(lambda s: self._fill(s, 0, 10))
        self._grid(lambda s: self._fill(s, 10, 0))

    def test_full_int64_domain(self):
        def fill(s):
            s.execute("create table b (k bigint, v bigint)")
            s.execute("create table p (k bigint, w bigint)")
            lo, hi = -(1 << 63), (1 << 63) - 1
            s.execute(f"insert into b values ({lo},1),({hi},2),(7,3),"
                      f"({hi},4)")
            s.execute(f"insert into p values ({lo},10),({hi},20),(7,30),"
                      f"(8,40)")
        self._grid(fill)

    def test_shape_bucket_boundaries(self):
        for npr in (255, 256, 257):
            self._grid(lambda s, npr=npr: self._fill(
                s, 64, npr, sparse=True))

    def test_mode_flip_mid_session_no_stale_plan(self):
        """SET on a live session must re-route the NEXT statement: the
        probe strategy is a jit static, so flipping the sysvar picks a
        different compiled program, never a stale one."""
        s = _session(chunk_capacity=128)
        self._fill(s, 200, 800, sparse=True)
        q = self.QUERIES[0]
        want = s.query(q)
        for mode in ("xla", "off", "auto"):
            s.execute(f"SET tidb_tpu_join_probe_mode = '{mode}'")
            assert s.query(q) == want, mode

    def test_mode_total_metric_moves(self):
        from tidb_tpu.utils.metrics import JOIN_PROBE_MODE_TOTAL

        def val(mode):
            # the fused scan→probe path labels itself fused_<mode>;
            # either surface proves the table path actually ran
            return sum(v for lbl, v in JOIN_PROBE_MODE_TOTAL.samples()
                       if lbl.get("mode") in (mode, f"fused_{mode}"))

        s = _session(chunk_capacity=128)
        self._fill(s, 200, 800, sparse=True)
        s.execute("SET tidb_tpu_join_probe_mode = 'xla'")
        c0 = val("xla")
        s.query(self.QUERIES[0])
        assert val("xla") > c0, "probe-mode counter did not move"


class TestRetraceGuard:
    """Executing the same join twice must not move JOIN_COMPILE_TOTAL on
    the second run: the fused kernels take every query-specific value as
    an argument, so a warm repeat is a pure jit-cache hit. A failure
    here means a shape key (or closure constant) leaked into traced
    code."""

    def test_same_join_twice_no_retrace(self):
        s = _session(chunk_capacity=128, force_device=True)
        s.execute("create table b (k bigint, v bigint)")
        s.execute("create table p (k bigint, w bigint)")
        rng = np.random.default_rng(3)
        s.catalog.table("test", "b").insert_columns(
            {"k": rng.integers(0, 200, 200), "v": np.arange(200)})
        s.catalog.table("test", "p").insert_columns(
            {"k": rng.integers(0, 200, 1000), "w": np.arange(1000)})
        q = ("select count(*) as n, sum(p.w) as sw"
             " from p join b on p.k = b.k")
        # warm twice: the very first re-plan may legitimately differ
        # (auto-analyze lands stats between runs); steady state may not
        first = s.query(q)
        assert s.query(q) == first
        c0 = _compiles()
        second = s.query(q)
        assert second == first
        assert _compiles() - c0 == 0, \
            "warm re-execution re-traced a join kernel"

    def test_left_and_semi_no_retrace(self):
        s = _session(chunk_capacity=128, force_device=True)
        s.execute("create table b (k bigint, v bigint)")
        s.execute("create table p (k bigint, w bigint)")
        s.execute("insert into b values (1,1),(2,2),(null,3)")
        s.execute("insert into p values (1,10),(3,30),(null,40)")
        queries = [
            "select w, v from p left join b on p.k = b.k",
            "select w from p where k in (select k from b)",
            "select w from p where not exists"
            " (select 1 from b where b.k = p.k)",
        ]
        for q in queries:
            first = s.query(q)
            assert s.query(q) == first  # steady the plan (auto-analyze)
            c0 = _compiles()
            assert s.query(q) == first
            assert _compiles() - c0 == 0, f"retrace on warm repeat: {q}"

    def test_explain_analyze_reports_recompiles_field(self):
        s = _session(chunk_capacity=128, force_device=True)
        s.execute("create table b (k bigint)")
        s.execute("create table p (k bigint)")
        s.execute("insert into b values (1)")
        s.execute("insert into p values (1),(2)")
        q = "select count(*) from p join b on p.k = b.k"
        s.query(q)  # compile out of band
        text = "\n".join(r[0] for r in s.query("explain analyze " + q))
        # warm run: the per-operator recompile column stays absent (0)
        assert "recompiles:" not in text
