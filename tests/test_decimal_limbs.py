"""Two-limb exact DECIMAL SUM accumulation (VERDICT r3 task 6;
SURVEY.md:309 hard-part 3). Magnitudes that used to trip the
detect-and-fail f64 shadow guard (~2^62 of summed |value|) must now be
COMPUTED exactly whenever the final total fits the scaled-int64 result
column; only genuinely unrepresentable totals raise out-of-range.
Oracle: Python bignum arithmetic."""

from decimal import Decimal

import numpy as np
import pytest

from tidb_tpu.errors import ExecutionError
from tidb_tpu.session import Session

BIG = "9999999999999999.99"          # ~1e18 scaled units each
BIG_SCALED = 999999999999999999       # int(BIG * 100)


def _lit(v: int) -> str:
    """Exact decimal(…,2) literal from scaled-int units (float
    formatting loses precision past 2^53)."""
    sign, a = ("-" if v < 0 else ""), abs(v)
    return f"{sign}{a // 100}.{a % 100:02d}"


def _mk(rows_sql):
    s = Session()
    s.execute("create table d (g bigint, tag varchar(4), p decimal(18,2))")
    s.execute(f"insert into d values {rows_sql}")
    return s


def test_cancellation_beyond_old_guard_is_exact():
    """Alternating-sign big values: summed |v| ~ 2e19 blows the old 2^62
    guard, but the true total is tiny and must come back exact."""
    rows = ", ".join(
        f"(1, 'a', {'-' if i % 2 else ''}{BIG})" for i in range(20))
    s = _mk(rows + ", (1, 'a', 1.23)")
    assert Decimal(s.query("select sum(p) from d")[0][0]) == Decimal("1.23")


def test_total_near_int64_max_exact():
    """9 x ~1e18 scaled = 9e18 < 2^63: representable, must be exact."""
    rows = ", ".join(f"(1, 'a', {BIG})" for _ in range(9))
    s = _mk(rows)
    want = Decimal(BIG_SCALED * 9).scaleb(-2)
    assert Decimal(s.query("select sum(p) from d")[0][0]) == want


def test_unrepresentable_total_still_raises():
    rows = ", ".join(f"(1, 'a', {BIG})" for _ in range(20))
    s = _mk(rows)
    with pytest.raises(ExecutionError, match="out of range"):
        s.query("select sum(p) from d")


def test_grouped_generic_and_segment_paths_exact():
    """Group by a high-card int column (generic strategy) and by a
    small-domain string (segment strategy): both limb paths exact."""
    vals = []
    oracle = {}
    rng = np.random.default_rng(7)
    for i in range(600):
        g = i % 37
        v = int(rng.integers(-(10**17), 10**17))  # scaled units
        oracle[g] = oracle.get(g, 0) + v
        vals.append(f"({g}, 't{g % 3}', {_lit(v)})")
    s = _mk(", ".join(vals))
    got = dict(s.query("select g, sum(p) from d group by g"))
    assert set(got) == set(oracle)
    for g, tot in oracle.items():
        assert Decimal(got[g]) == Decimal(tot).scaleb(-2), g
    # segment strategy: group by the 3-value dict column
    got2 = dict(s.query("select tag, sum(p) from d group by tag"))
    by_tag = {}
    for g, tot in oracle.items():
        by_tag[f"t{g % 3}"] = by_tag.get(f"t{g % 3}", 0) + tot
    for t, tot in by_tag.items():
        assert Decimal(got2[t]) == Decimal(tot).scaleb(-2), t


def test_avg_uses_limbs():
    rows = ", ".join(f"(1, 'a', {BIG})" for _ in range(8))
    s = _mk(rows)
    got = float(s.query("select avg(p) from d")[0][0])
    want = float(BIG_SCALED * 8) / 8 / 100
    assert got == pytest.approx(want, rel=1e-12)


def test_ten_billion_row_equivalent_magnitude():
    """SUM(l_extendedprice)-shaped check at 1e10-row-equivalent
    magnitude: 5000 rows x ~1.8e15 scaled units ~ 9e18 total — the same
    scaled magnitude 1e10 rows of ~90k-priced line items would reach —
    exact vs Python ints."""
    rng = np.random.default_rng(3)
    vals = rng.integers(1_790_000_000_000_000, 1_810_000_000_000_000,
                        size=5000)
    total = int(vals.sum(dtype=object))
    rows = ", ".join(f"(1, 'a', {_lit(int(v))})" for v in vals)
    s = _mk(rows)
    assert Decimal(s.query("select sum(p) from d")[0][0]) == Decimal(total).scaleb(-2)


@pytest.mark.parametrize("n_dcn, n_shards", [(2, 4), (1, 1)],
                         ids=["2x4", "1x1"])
def test_mesh_fragment_limbs(devices8, monkeypatch, n_dcn, n_shards):
    """Distributed generic fragment path: limb states exchange + merge
    across shards exactly. On a mesh of one part nothing is exchanged
    and the partial table is the final one: its limbs are
    carry-normalised all the same (lo in [0, 2^32): the TopN's limb sort
    keys and the host finalize count on it)."""
    from tidb_tpu.parallel import executor as pe
    from tidb_tpu.parallel import make_mesh

    mesh = make_mesh(n_shards=n_shards, n_dcn=n_dcn, devices=devices8)
    s = Session(chunk_capacity=2048, mesh=mesh)
    # a one-device CPU mesh routes generic aggregation to the host engine
    # unless asked
    s.execute("set tidb_device_engine_mode = 'force'")
    emitted = []
    real = pe.DistFragmentExec._finalize_generic_tables
    monkeypatch.setattr(
        pe.DistFragmentExec, "_finalize_generic_tables",
        lambda self, out: (emitted.append(out), real(self, out))[1])
    s.execute("create table d (g bigint, p decimal(18,2))")
    rng = np.random.default_rng(13)
    oracle = {}
    vals = []
    for i in range(4000):
        g = int(rng.integers(0, 800))
        v = int(rng.integers(-(10**17), 10**17))
        oracle[g] = oracle.get(g, 0) + v
        vals.append(f"({g}, {_lit(v)})")
    for st in range(0, 4000, 500):
        s.execute("insert into d values " + ", ".join(vals[st:st + 500]))
    got = dict(s.query("select g, sum(p) from d group by g"))
    (table,) = emitted  # the mesh tier answered, with whole limbs
    lo, hi = np.asarray(table["a0.sum"]), np.asarray(table["a0.sumhi"])
    assert ((lo >= 0) & (lo < 1 << 32)).all() and hi.any()
    assert set(got) == set(oracle)
    for g, tot in oracle.items():
        assert Decimal(got[g]) == Decimal(tot).scaleb(-2), g
    # and through the TopN pushdown (limb sort keys on device)
    want = sorted(oracle.items(), key=lambda kv: (-kv[1], kv[0]))[:5]
    got_top = s.query("select g, sum(p) s from d group by g "
                      "order by s desc, g limit 5")
    assert [(g, Decimal(v)) for g, v in got_top] == \
        [(g, Decimal(t).scaleb(-2)) for g, t in want]
    # avg(decimal) sort key: limb->float division on device must
    # compile under jit and rank like the host finalize
    got_avg = s.query("select g, avg(p) a from d group by g "
                      "order by a desc, g limit 5")
    import collections
    cnts = collections.Counter()
    for r in vals:
        cnts[int(r.split(",")[0][1:])] += 1
    want_avg = sorted(
        ((g, (t / 100) / cnts[g]) for g, t in oracle.items()),
        key=lambda kv: (-kv[1], kv[0]))[:5]
    assert [g for g, _ in got_avg] == [g for g, _ in want_avg]
