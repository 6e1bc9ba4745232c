"""Native (C++) TPC-H generator: builds via g++ + ctypes, fills
orders/lineitem as device-repr columns + dictionary codes. The numpy
generator stays as the fallback and oracle shape."""

import numpy as np
import pytest

from tidb_tpu.session import Session
from tidb_tpu.storage.native_gen import load_native
from tidb_tpu.storage.tpch import load_tpch
from tidb_tpu.testutil import mirror_to_sqlite, rows_equal

pytestmark = pytest.mark.skipif(
    load_native() is None, reason="native toolchain unavailable")


@pytest.fixture(scope="module")
def sess():
    s = Session(chunk_capacity=8192)
    load_tpch(s.catalog, sf=0.01, native=True)
    return s


def test_schema_invariants(sess):
    t = sess.catalog.table("test", "lineitem")
    o = sess.catalog.table("test", "orders")
    nl, no = t.n, o.n
    assert no == 15000
    assert 1 * no <= nl <= 7 * no
    lq = t.data["l_quantity"][:nl]
    assert lq.min() >= 100 and lq.max() <= 5000  # scale-2 of 1..50
    ok = o.data["o_orderkey"][:no]
    assert ok.min() == 1 and ok.max() == no and len(np.unique(ok)) == no
    ship = t.data["l_shipdate"][:nl]
    rec = t.data["l_receiptdate"][:nl]
    assert (rec > ship).all()
    # FK domains
    assert t.data["l_orderkey"][:nl].max() <= no
    assert t.data["l_partkey"][:nl].min() >= 1


def test_totalprice_consistent(sess):
    # o_totalprice must equal the lineitem aggregation (Q18's semantics)
    # o_totalprice floors each line's scale-6 amount to cents (same as
    # the numpy generator), so the exact scale-6 sum can differ by up to
    # 1 cent per line (< 0.07 per order) — never more
    got = sess.query("""
        select count(*) from
        (select l_orderkey k,
                sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) s
         from lineitem group by l_orderkey) d
        join orders on k = o_orderkey
        where s - o_totalprice > 0.08 or o_totalprice - s > 0.08""")
    assert got[0][0] == 0


def test_strings_decode(sess):
    rows = sess.query(
        "select distinct l_returnflag from lineitem order by l_returnflag")
    assert rows == [("A",), ("N",), ("R",)]
    rows = sess.query(
        "select distinct o_orderstatus from orders order by o_orderstatus")
    assert [r[0] for r in rows] == ["F", "O", "P"] or len(rows) >= 2


def test_q1_against_oracle(sess):
    from tidb_tpu.storage.tpch_queries import Q

    conn = mirror_to_sqlite(sess.catalog, tables=["lineitem"])
    got = sess.query(Q["q1"][0])
    want = conn.execute(Q["q1"][1]).fetchall()
    ok, msg = rows_equal(got, want, ordered=True)
    assert ok, msg


def test_numpy_fallback_forced():
    s = Session()
    counts = load_tpch(s.catalog, sf=0.002, native=False)
    assert counts["lineitem"] > 0
    assert s.query("select count(*) from lineitem")[0][0] == counts["lineitem"]


class TestRebuildFromSource:
    """The library is never committed: a checkout builds it from
    native/tpch_gen.cpp, and staleness is decided by the recorded source
    hash, not by mtimes a copied tree does not keep."""

    @pytest.fixture
    def scratch_native(self, tmp_path, monkeypatch):
        import shutil

        from tidb_tpu.storage import native_gen as ng

        shutil.copy(ng._SRC, tmp_path / "tpch_gen.cpp")
        lib = str(tmp_path / "libtpchgen.so")
        monkeypatch.setattr(ng, "_SRC", str(tmp_path / "tpch_gen.cpp"))
        monkeypatch.setattr(ng, "_LIB", lib)
        monkeypatch.setattr(ng, "_LIB_HASH", lib + ".srchash")
        monkeypatch.setattr(ng, "_lib", None)
        monkeypatch.setattr(ng, "_load_error", None)
        return ng

    def test_missing_library_is_rebuilt(self, scratch_native):
        import os

        ng = scratch_native
        assert not os.path.exists(ng._LIB)
        assert ng.load_native() is not None, ng.load_error()
        assert os.path.exists(ng._LIB)
        assert ng._built_hash() == ng._source_hash()

    def test_changed_source_hash_forces_rebuild(self, scratch_native,
                                                monkeypatch):
        import os

        ng = scratch_native
        assert ng.load_native() is not None
        builds = []
        real = ng._build
        monkeypatch.setattr(
            ng, "_build", lambda h: (builds.append(h), real(h)))
        # same source, same hash: a fresh process loads without building
        # even though the library is OLDER than the source by mtime
        os.utime(ng._LIB, (1, 1))
        monkeypatch.setattr(ng, "_lib", None)
        assert ng.load_native() is not None and builds == []
        # the source changes: the recorded hash no longer matches
        with open(ng._SRC, "a") as f:
            f.write("\n// edited\n")
        monkeypatch.setattr(ng, "_lib", None)
        assert ng.load_native() is not None
        assert builds == [ng._source_hash()] == [ng._built_hash()]

    def test_gitignore_lists_the_library(self):
        import os

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, ".gitignore")) as f:
            assert "native/libtpchgen.so" in f.read().split()
