"""Open-addressing hash probe (ops/hash_probe.py — the SURVEY.md:294-296
join-probe fast path). Pinned against searchsorted on every
consumption the fragment join makes: counts (hi - lo) everywhere, lo
wherever the count is non-zero."""

import numpy as np
import pytest

import jax.numpy as jnp

from tidb_tpu.ops import hash_probe as hp


def check(build_vals, probe_vals):
    sh = jnp.asarray(np.sort(np.asarray(build_vals, dtype=np.int64)))
    pr = jnp.asarray(np.asarray(probe_vals, dtype=np.int64))
    lo1, hi1 = hp.xla_probe_ranges(sh, pr)
    lo2, hi2 = hp.probe_ranges(sh, pr)
    c1 = np.asarray(hi1) - np.asarray(lo1)
    c2 = np.asarray(hi2) - np.asarray(lo2)
    assert (c1 == c2).all(), f"count mismatch: {int((c1 != c2).sum())}"
    nz = c1 > 0
    assert (np.asarray(lo1)[nz] == np.asarray(lo2)[nz]).all(), "lo mismatch"


class TestProbeRanges:
    def test_random_with_duplicates(self):
        rng = np.random.default_rng(1)
        build = rng.integers(-500, 500, 4000) * 7919
        probes = rng.integers(-800, 800, 9000) * 7919
        check(build, probes)

    def test_unique_dense(self):
        rng = np.random.default_rng(2)
        build = rng.permutation(50_000).astype(np.int64)
        probes = rng.integers(-10_000, 60_000, 80_000)
        check(build, probes)

    def test_all_absent_and_all_present(self):
        build = np.arange(0, 1000, 2)
        check(build, np.arange(1, 1001, 2))  # all miss
        check(build, build.copy())           # all hit

    def test_tiny_and_empty(self):
        check([42], [42, 43])
        check([], [1, 2, 3])

    def test_adversarial_same_home_cluster(self):
        # many values multiplied so their mixed homes cluster; the
        # in-jit lax.cond fallback must keep results exact regardless
        build = np.arange(64, dtype=np.int64) * (1 << 40)
        probes = np.arange(-8, 72, dtype=np.int64) * (1 << 40)
        check(build, probes)

    def test_over_capacity_falls_back(self):
        n = hp.MAX_CAPACITY  # 2n slots would exceed the capacity cap
        rng = np.random.default_rng(3)
        build = rng.integers(0, 1 << 40, n)
        probes = rng.integers(0, 1 << 40, 1000)
        check(build, probes)

    def test_full_int64_domain_keys(self):
        """Keys at INT64_MIN/INT64_MAX and around zero: the mixed-hash
        home/fingerprint arithmetic must be exact across the whole
        domain (uint64 wraparound territory)."""
        i64 = np.iinfo(np.int64)
        build = np.array([i64.min, i64.min + 1, -1, 0, 1,
                          i64.max - 1, i64.max, i64.max], dtype=np.int64)
        probes = np.array([i64.min, i64.min + 2, -1, 0, 2,
                           i64.max, i64.max - 1, 7], dtype=np.int64)
        check(build, probes)

    def test_sentinel_value_keys(self):
        """0x7FFFFFFF-adjacent keys: values whose mixed fingerprint
        could collide with the table's EMPTY sentinel are remapped
        consistently on both sides (silent match loss otherwise)."""
        build = np.array([0x7FFFFFFF, 0x7FFFFFFF, 0x7FFFFFFE, 0],
                         dtype=np.int64)
        check(build, build.copy())

    def test_capacity_boundary_builds(self):
        """Build sizes straddling a pow2 capacity step: the table's
        cap = next_pow2(2n) decision must stay exact at the edges."""
        rng = np.random.default_rng(9)
        for n in (7, 8, 9, 255, 256, 257):
            build = rng.integers(0, 1 << 30, n) * 2654435761
            probes = rng.integers(0, 1 << 30, 512) * 2654435761
            check(build, probes)


class TestModeResolution:
    def test_resolve_mode_on_cpu(self):
        # auto on a CPU target = searchsorted; explicit modes pass through
        assert hp.resolve_mode("off") == "sorted"
        assert hp.resolve_mode("auto") == "sorted"  # CPU-pinned tier-1
        assert hp.resolve_mode("xla") == "xla"

    def test_resolve_mode_tracks_forced_platform(self):
        from tidb_tpu.utils.device import force_platform

        with force_platform("tpu"):
            assert hp.resolve_mode("auto") == "xla"
        assert hp.resolve_mode("auto") == "sorted"

    def test_table_capacity_envelope(self):
        assert hp.table_capacity(0) is None
        assert hp.table_capacity(1) == 16
        assert hp.table_capacity(1000) == 2048
        assert hp.table_capacity(hp.MAX_CAPACITY // 2) == hp.MAX_CAPACITY
        assert hp.table_capacity(hp.MAX_CAPACITY // 2 + 1) is None


class TestSysvarEnum:
    def test_pallas_is_no_value_of_the_sysvar(self):
        """The Pallas probe went with PR 30 (the chip's compiler refused
        it): its name is refused like any unknown value, and the session
        keeps what it had."""
        from tidb_tpu.errors import ExecutionError
        from tidb_tpu.session import Session
        from tidb_tpu.session.sysvars import SYSVARS

        assert SYSVARS["tidb_tpu_join_probe_mode"].enum_values == (
            "off", "auto", "xla")
        s = Session()
        with pytest.raises(ExecutionError, match=r"invalid value 'pallas' "
                           r"for tidb_tpu_join_probe_mode \(allowed: "
                           r"off, auto, xla\)"):
            s.execute("SET tidb_tpu_join_probe_mode = 'pallas'")
        assert s.sysvars.get("tidb_tpu_join_probe_mode") == "auto"
        assert s.query("SHOW VARIABLES LIKE 'tidb_tpu_join_probe_mode'") \
            == [("tidb_tpu_join_probe_mode", "auto")]


class TestJoinIntegration:
    """End-to-end fragment joins with the table probe forced on."""

    @pytest.mark.parametrize("mode", ["xla"])
    def test_q18_shape_matches_oracle(self, mode):
        from tidb_tpu.parallel import make_mesh
        from tidb_tpu.session import Session
        from tidb_tpu.testutil import mirror_to_sqlite, rows_equal
        from tidb_tpu.utils import jitcache

        saved = hp._mode
        jitcache.clear()
        try:
            s = Session(chunk_capacity=1 << 14, mesh=make_mesh())
            # the sysvar is THE knob: it rides ExecContext into the
            # fragment builder as a trace-time static (ISSUE 12) — the
            # process global is no longer written per statement, so
            # concurrent sessions cannot clobber each other
            s.execute(f"set tidb_tpu_join_probe_mode = '{mode}'")
            s.execute("create table f (k bigint, v bigint)")
            s.execute("create table d (k bigint primary key, g bigint)")
            s.execute("insert into f values " + ",".join(
                f"({i % 53}, {i})" for i in range(3000)))
            s.execute("insert into d values " + ",".join(
                f"({i}, {i % 7})" for i in range(53)))
            s.execute("set tidb_device_engine_mode = 'force'")
            # per-STATEMENT threading: the query below carries the
            # session's mode through ExecContext into build_fn (and the
            # fragment cache key), never through the process global
            assert s.sysvars.get("tidb_tpu_join_probe_mode") == mode
            sql = ("select g, count(*), sum(v) from f join d on f.k = d.k "
                   "group by g order by g")
            got = s.query(sql)
            conn = mirror_to_sqlite(s.catalog)
            ok, msg = rows_equal(got, conn.execute(sql).fetchall(),
                                 ordered=True)
            assert ok, msg
        finally:
            hp.set_mode(saved)
            jitcache.clear()
