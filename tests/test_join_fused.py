"""Fused scan→probe execution (ISSUE 10): the inner hash join whose
probe side is a plain scan pipeline runs decode+filter+project+probe+
expand as ONE jitted program per staged chunk, with the build side
device-resident across statements (DeviceBufferCache).

Pinned here: exact equality fused vs the chunk-synced classic tree vs
the sqlite oracle across the edge-case shapes, the warm dispatch budget
for the Q18 fragment shape, the build cache's invalidation rules (DML /
ANALYZE-adjacent ident moves, txn bypass, mode-change re-key), and the
fallback gates (fusion off, host engine) all answering identically.
"""

import numpy as np
import pytest

from tidb_tpu.executor.pipeline import DEVICE_CACHE
from tidb_tpu.session import Session
from tidb_tpu.utils import dispatch as dsp
from tidb_tpu.utils.metrics import JOIN_PROBE_MODE_TOTAL


def _fused_probes() -> float:
    return sum(v for lbl, v in JOIN_PROBE_MODE_TOTAL.samples()
               if str(lbl.get("mode", "")).startswith("fused_"))


def _session(cap=1 << 14, force=True):
    s = Session(chunk_capacity=cap)
    s.execute("SET tidb_slow_log_threshold = 300000")
    if force:
        s.execute("SET tidb_device_engine_mode = 'force'")
    # pin the Q18 join shape: eager aggregation would re-plan a partial
    # agg below the join and the probe side would no longer peel to a
    # plain scan (a legitimate plan — just not the one under test)
    s.execute("SET tidb_opt_agg_push_down = 0")
    return s


def _fill(s, n_dim=2000, n_fact=20000, dup=1, miss=500, seed=7):
    """Star shape: fact `l` probes dim `o` on a dense PK domain."""
    rng = np.random.default_rng(seed)
    s.execute("create table o (k bigint primary key, g bigint, p bigint)")
    s.execute("create table l (k bigint, q bigint)")
    if n_dim:
        s.catalog.table("test", "o").insert_columns(
            {"k": np.arange(n_dim), "g": np.arange(n_dim) % 7,
             "p": rng.integers(0, 1000, n_dim)})
    if n_fact:
        keys = np.repeat(rng.integers(0, max(n_dim, 1) + miss,
                                      n_fact // max(dup, 1) or 1), dup)
        s.catalog.table("test", "l").insert_columns(
            {"k": keys, "q": rng.integers(1, 50, len(keys))})


Q18_SHAPE = ("select g, count(*) as n, sum(l.q) as sq"
             " from l join o on l.k = o.k group by g order by g")

SHAPES = [
    Q18_SHAPE,
    # probe-side filter + projection fused below the probe
    "select count(*) as n, sum(l.q + 1) as sq from l join o"
    " on l.k = o.k where l.q < 25",
    # build-side filter (peeled into the cached build tag)
    "select count(*) as n from l join o on l.k = o.k where o.p < 500",
    # payload-free count
    "select count(*) from l join o on l.k = o.k",
]


class TestFusedVsClassicVsOracle:
    def _check(self, s, queries=SHAPES):
        from tidb_tpu.testutil import mirror_to_sqlite, rows_equal

        conn = mirror_to_sqlite(s.catalog, tables=["l", "o"])
        for q in queries:
            fused = s.query(q)
            s.execute("SET tidb_tpu_pipeline_fuse = 0")
            classic = s.query(q)
            s.execute("SET tidb_tpu_pipeline_fuse = 1")
            assert fused == classic, f"fused != classic: {q}"
            ok, msg = rows_equal(sorted(fused, key=str),
                                 sorted(conn.execute(q).fetchall(),
                                        key=str), ordered=True)
            assert ok, f"{q}: {msg}"
        conn.close()

    def test_q18_shape_engages_fused_path(self):
        s = _session()
        _fill(s)
        c0 = _fused_probes()
        self._check(s)
        assert _fused_probes() > c0, "fused scan→probe never engaged"

    def test_dup_heavy_overflow_windows(self):
        # expansion >> the in-program tile: 3600 output rows against a
        # 256-slot tile forces the overflow expand_tiles path
        s = _session(cap=256)
        s.execute("create table o (k bigint primary key, g bigint,"
                  " p bigint)")
        s.execute("create table l (k bigint, q bigint)")
        s.catalog.table("test", "o").insert_columns(
            {"k": np.arange(30), "g": np.arange(30) % 3,
             "p": np.arange(30)})
        lk = np.repeat(np.arange(0, 40), 120)  # keys 30..39 miss
        s.catalog.table("test", "l").insert_columns(
            {"k": lk, "q": np.ones(len(lk), dtype=np.int64)})
        self._check(s)

    def test_zero_row_and_no_match_sides(self):
        s = _session(cap=512)
        _fill(s, n_dim=100, n_fact=0)
        self._check(s, queries=[Q18_SHAPE])
        s2 = _session(cap=512)
        _fill(s2, n_dim=0, n_fact=500)
        self._check(s2, queries=[Q18_SHAPE])
        s3 = _session(cap=512)
        _fill(s3, n_dim=50, n_fact=500)
        # no key overlap at all: probe keys start past the dim domain
        s3.execute("update l set k = k + 1000000")
        self._check(s3, queries=[Q18_SHAPE])

    def test_null_keys_both_sides(self):
        s = _session(cap=512)
        rng = np.random.default_rng(11)
        s.execute("create table o (k bigint, g bigint, p bigint)")
        s.execute("create table l (k bigint, q bigint)")
        s.catalog.table("test", "o").insert_columns(
            {"k": np.arange(200), "g": np.arange(200) % 7,
             "p": rng.integers(0, 1000, 200)})
        s.catalog.table("test", "l").insert_columns(
            {"k": rng.integers(0, 260, 1000),
             "q": rng.integers(1, 50, 1000)})
        s.execute("insert into o values (null, 0, 0)")
        s.execute("insert into l values (null, 1), (null, 2)")
        self._check(s)

    def test_sparse_keys_table_probe_modes(self):
        """Sparse 40-bit keys defeat the direct index, so xla
        genuinely runs the hash table INSIDE the fused program."""
        s = _session(cap=1024)
        s.execute("create table o (k bigint, g bigint, p bigint)")
        s.execute("create table l (k bigint, q bigint)")
        rng = np.random.default_rng(5)
        s.catalog.table("test", "o").insert_columns(
            {"k": rng.integers(0, 400, 800) * (1 << 40),
             "g": np.arange(800) % 5, "p": np.arange(800)})
        s.catalog.table("test", "l").insert_columns(
            {"k": rng.integers(0, 500, 4000) * (1 << 40),
             "q": np.arange(4000)})
        want = s.query(Q18_SHAPE)
        for mode in ("xla", "off"):
            s.execute(f"SET tidb_tpu_join_probe_mode = '{mode}'")
            assert s.query(Q18_SHAPE) == want, mode
        s.execute("SET tidb_tpu_join_probe_mode = 'auto'")


class TestWarmDispatchBudget:
    def test_q18_shape_fragment_budget(self):
        """The ISSUE 10 acceptance proxy: a warm Q18-shape fragment
        (fused scan→probe feeding the group agg) issues <= 12 device
        dispatches — fused chunk programs + ONE window fetch + the agg
        update/finalize, with the build side AND the staged probe scan
        riding the device cache (zero staging)."""
        s = _session(cap=1 << 16)
        _fill(s, n_dim=3000, n_fact=50000)
        s.query(Q18_SHAPE)
        s.query(Q18_SHAPE)  # second fill: jits traced, caches filled
        c0 = dsp.count()
        s.query(Q18_SHAPE)
        warm = dsp.count() - c0
        assert warm <= 12, (warm, dsp.by_site())

    def test_warm_build_is_cached(self):
        """A warm repeated join must not re-drain/re-sort the build
        side: the DeviceBufferCache serves it (hit counter moves, no
        join.build dispatches)."""
        DEVICE_CACHE.clear()
        s = _session(cap=1 << 16)
        _fill(s, n_dim=2000, n_fact=30000)
        s.query(Q18_SHAPE)
        s.query(Q18_SHAPE)
        b0 = dict(dsp.by_site())
        s.query(Q18_SHAPE)
        b1 = dict(dsp.by_site())
        builds = b1.get("jit:join.build", 0) - b0.get("jit:join.build", 0)
        stages = b1.get("stage", 0) - b0.get("stage", 0)
        assert builds == 0, (builds, b1)
        assert stages == 0, (stages, b1)


class TestBuildCacheInvalidation:
    def test_dml_on_build_side_invalidates(self):
        s = _session(cap=1 << 14)
        _fill(s, n_dim=500, n_fact=5000)
        before = s.query(Q18_SHAPE)
        s.query(Q18_SHAPE)  # park the build in the device cache
        # move every dim row to group 0: a stale parked build would
        # still answer with 7 groups
        s.execute("update o set g = 0")
        after = s.query(Q18_SHAPE)
        assert len(after) == 1 and after != before
        from tidb_tpu.testutil import mirror_to_sqlite, rows_equal

        conn = mirror_to_sqlite(s.catalog, tables=["l", "o"])
        ok, msg = rows_equal(after, conn.execute(Q18_SHAPE).fetchall(),
                             ordered=True)
        assert ok, msg

    def test_dml_on_probe_side_invalidates(self):
        s = _session(cap=1 << 14)
        _fill(s, n_dim=500, n_fact=5000)
        s.query(Q18_SHAPE)
        s.query(Q18_SHAPE)
        s.execute("delete from l where q < 25")
        got = s.query(Q18_SHAPE)
        from tidb_tpu.testutil import mirror_to_sqlite, rows_equal

        conn = mirror_to_sqlite(s.catalog, tables=["l", "o"])
        ok, msg = rows_equal(got, conn.execute(Q18_SHAPE).fetchall(),
                             ordered=True)
        assert ok, msg

    def test_txn_reads_bypass_cache(self):
        s = _session(cap=1 << 14)
        _fill(s, n_dim=300, n_fact=3000)
        want = s.query(Q18_SHAPE)  # parks the committed build
        s.execute("begin")
        s.execute("update o set g = 0")
        in_txn = s.query(Q18_SHAPE)  # must see the provisional write
        assert len(in_txn) == 1
        s.execute("rollback")
        assert s.query(Q18_SHAPE) == want

    def test_mode_change_rekeys_parked_build(self):
        """tidb_tpu_join_probe_mode joins the build-cache tag: flipping
        it mints a fresh build (with/without the hash table) instead of
        serving state shaped for the other strategy."""
        s = _session(cap=1 << 14)
        s.execute("create table o (k bigint, g bigint, p bigint)")
        s.execute("create table l (k bigint, q bigint)")
        rng = np.random.default_rng(3)
        s.catalog.table("test", "o").insert_columns(
            {"k": rng.integers(0, 200, 400) * (1 << 40),
             "g": np.arange(400) % 4, "p": np.arange(400)})
        s.catalog.table("test", "l").insert_columns(
            {"k": rng.integers(0, 260, 2000) * (1 << 40),
             "q": np.arange(2000)})
        want = s.query(Q18_SHAPE)
        s.query(Q18_SHAPE)  # park under 'sorted'
        s.execute("SET tidb_tpu_join_probe_mode = 'xla'")
        assert s.query(Q18_SHAPE) == want  # fresh build w/ table
        s.execute("SET tidb_tpu_join_probe_mode = 'off'")
        assert s.query(Q18_SHAPE) == want  # the parked 'sorted' build


class TestFallbackGates:
    def test_fusion_off_keeps_classic_tree(self):
        s = _session(cap=1 << 14)
        _fill(s, n_dim=500, n_fact=5000)
        want = s.query(Q18_SHAPE)
        s.execute("SET tidb_tpu_pipeline_fuse = 0")
        c0 = _fused_probes()
        assert s.query(Q18_SHAPE) == want
        assert _fused_probes() == c0, "fuse=0 still ran the fused probe"

    def test_host_engine_keeps_numpy_probe(self):
        s = _session(cap=1 << 14, force=False)  # auto on CPU: host tier
        _fill(s, n_dim=500, n_fact=5000)
        c0 = _fused_probes()
        got = s.query(Q18_SHAPE)
        assert _fused_probes() == c0
        s2 = _session(cap=1 << 14, force=True)
        _fill(s2, n_dim=500, n_fact=5000)
        assert s2.query(Q18_SHAPE) == got

    def test_outer_joins_fuse_filtered_joins_keep_classic(self):
        """Plan-static gates after the ISSUE 18 widening: pure equi-key
        LEFT joins now ride the fused probe (NULL-pad via the unmatched
        mask), while other_cond joins still never route there (their
        residual re-verification lives in the classic tree)."""
        s = _session(cap=1 << 14)
        _fill(s, n_dim=300, n_fact=3000)
        c0 = _fused_probes()
        s.query("select count(*), count(o.g) from l left join o"
                " on l.k = o.k")
        assert _fused_probes() > c0, "equi-key left join no longer fuses"
        c1 = _fused_probes()
        s.query("select count(*) from l join o on l.k = o.k"
                " and o.p < l.q * 100")
        assert _fused_probes() == c1, "other_cond join ran the fused probe"

    def test_deadline_interrupts_fused_probe(self):
        """A typed statement deadline surfaces from inside the fused
        probe loop (raise_if_cancelled polls between device steps) and
        the session recovers cleanly."""
        from tidb_tpu.errors import QueryTimeoutError

        s = _session(cap=4096)
        _fill(s, n_dim=2000, n_fact=30000)
        s.query(Q18_SHAPE)  # compile out of band
        s.execute("SET max_execution_time = 1")
        with pytest.raises(QueryTimeoutError):
            s.query(Q18_SHAPE)
        s.execute("SET max_execution_time = 0")
        assert s.query(Q18_SHAPE)  # the deadline was statement-scoped
