"""The mesh tier's local join (parallel/distsql.py `_local_join`): one
sort of both sides ranks every probe slot against a unique-key build
side. Pinned against a dictionary join in numpy on the slot layouts the
exchange really hands it, and through the served statement on a 1x1 (no
exchange: the local join takes the rows as the scan left them), a 1x4
and a 1x8 CPU mesh."""

import zlib

import jax
import numpy as np
import pytest

from tidb_tpu.errors import ExecutionError
from tidb_tpu.parallel import executor as pe
from tidb_tpu.parallel import make_mesh
from tidb_tpu.parallel.distsql import _local_join
from tidb_tpu.session import Session
from tidb_tpu.storage.catalog import Catalog
from tidb_tpu.storage.tpch import load_tpch
from tidb_tpu.testutil import mirror_to_sqlite, rows_equal

I64 = np.iinfo(np.int64)


def _reference(bk, bs, pk, ps):
    """{probe slot: build slot} of the live pairs with equal keys."""
    build = {int(k): i for i, k in enumerate(bk) if bs[i]}
    return {j: build[int(k)] for j, k in enumerate(pk)
            if ps[j] and int(k) in build}


def _slots(keys, live, n_slots, rng):
    """Lay `keys` out as the exchange does: live rows somewhere among
    `n_slots`, every other slot dead with key 0 and sel False."""
    k = np.zeros(n_slots, np.int64)
    s = np.zeros(n_slots, bool)
    at = rng.permutation(n_slots)[:len(keys)]
    k[at] = keys
    s[at] = live
    return k, s


def _case(name):
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    uniq = lambda n, lo, hi: rng.permutation(np.arange(lo, hi))[:n]  # noqa: E731
    if name == "dead_slots_beside_live_key_0":
        bkeys = np.array([0, 5, 7, -3, 11])
        bk, bs = _slots(bkeys, True, 12, rng)
        pk, ps = _slots(rng.choice([0, 5, 6, 7, -3, 12], 40), True, 90, rng)
    elif name == "int64_min_and_max":
        bk, bs = _slots(np.array([I64.min, I64.max, 0, -1, 1]), True, 9, rng)
        pk, ps = _slots(rng.choice([I64.min, I64.max, I64.min + 1,
                                    I64.max - 1, 0, -1, 1], 30), True, 50, rng)
    elif name == "filtered_build_row_never_matches":
        # build rows 3 and 8 were filtered out by a pushed predicate:
        # sel False, key kept — live probe rows carry both keys
        bkeys = np.arange(10)
        bk, bs = bkeys.copy(), ~np.isin(bkeys, [3, 8])
        pk, ps = _slots(rng.choice(bkeys, 60), True, 64, rng)
        assert {3, 8} <= set(pk[ps].tolist())
    elif name == "empty_build_side":
        bk, bs = np.zeros(0, np.int64), np.zeros(0, bool)
        pk, ps = _slots(rng.integers(-4, 4, 20), True, 32, rng)
    elif name == "all_dead_build_side":
        bk, bs = _slots(uniq(10, 0, 20), False, 16, rng)
        pk, ps = _slots(rng.integers(0, 20, 20), True, 32, rng)
    elif name == "all_dead_probe_side":
        bk, bs = _slots(uniq(10, 0, 20), True, 16, rng)
        pk, ps = _slots(rng.integers(0, 20, 20), False, 32, rng)
    elif name == "no_slots_at_all":
        bk, bs = np.zeros(0, np.int64), np.zeros(0, bool)
        pk, ps = np.zeros(0, np.int64), np.zeros(0, bool)
    elif name == "build_larger_than_probe":
        bk, bs = _slots(uniq(900, -500, 500), rng.random(900) < 0.8, 1500, rng)
        pk, ps = _slots(rng.integers(-600, 600, 100),
                        rng.random(100) < 0.9, 128, rng)
    elif name == "every_probe_row_one_build_row":
        bk, bs = _slots(np.array([42, 43]), True, 4, rng)
        pk, ps = _slots(np.full(300, 42), True, 512, rng)
    elif name.startswith("random_past_one_scan_block"):
        # nb + np > 4096: the blocked running maximum, not its flat tail
        nb, npr = (3000, 9000) if name.endswith("a") else (5000, 21000)
        bk, bs = _slots(uniq(nb // 2, -nb, nb) * 3, rng.random(nb // 2) < 0.7,
                        nb, rng)
        pk, ps = _slots(rng.integers(-nb, nb, npr // 2) * 3,
                        rng.random(npr // 2) < 0.9, npr, rng)
    else:
        raise AssertionError(name)
    return bk.astype(np.int64), bs, pk.astype(np.int64), ps


CASES = [
    "dead_slots_beside_live_key_0", "int64_min_and_max",
    "filtered_build_row_never_matches", "empty_build_side",
    "all_dead_build_side", "all_dead_probe_side", "no_slots_at_all",
    "build_larger_than_probe", "every_probe_row_one_build_row",
    "random_past_one_scan_block_a", "random_past_one_scan_block_b",
]


@pytest.mark.parametrize("name", CASES)
def test_local_join_equals_dictionary_join(name):
    bk, bs, pk, ps = _case(name)
    bidx, hit = map(np.asarray, jax.jit(_local_join)(bk, bs, pk, ps))
    assert bidx.shape == hit.shape == pk.shape
    # the index stays gatherable where nothing joined
    assert ((bidx >= 0) & (bidx < max(len(bk), 1))).all()
    got = {j: int(bidx[j]) for j in np.flatnonzero(hit)}
    assert got == _reference(bk, bs, pk, ps)


@pytest.mark.parametrize("seed", range(8))
def test_local_join_random_layouts(seed):
    rng = np.random.default_rng(seed)
    nb, npr = int(rng.integers(0, 60)), int(rng.integers(0, 200))
    pool = np.concatenate([rng.integers(-8, 40, 64), [0, I64.min, I64.max]])
    bkeys = rng.permutation(np.unique(pool))[:nb // 2 + 1]
    bk, bs = _slots(bkeys, rng.random(len(bkeys)) < 0.7,
                    nb + len(bkeys), rng)
    pk, ps = _slots(rng.choice(pool, npr), rng.random(npr) < 0.7,
                    2 * npr + 1, rng)
    bidx, hit = map(np.asarray, jax.jit(_local_join)(bk, bs, pk, ps))
    got = {j: int(bidx[j]) for j in np.flatnonzero(hit)}
    assert got == _reference(bk, bs, pk, ps)


@pytest.mark.parametrize("nb, npr, ok", [
    ((1 << 29) - 9, 8, True), ((1 << 29) - 8, 8, False), (8, 1 << 29, False),
], ids=["one_under", "at_the_limit", "probe_alone_over"])
def test_local_join_refuses_typed_past_its_slot_limit(nb, npr, ok):
    """The tag sits above 29 bits of slot index: a shard that would not
    fit them is refused typed while the fragment is traced, not joined
    wrongly (shapes only: nothing is allocated)."""
    sds = jax.ShapeDtypeStruct
    args = (sds((nb,), np.int64), sds((nb,), bool),
            sds((npr,), np.int64), sds((npr,), bool))
    if ok:
        bidx, hit = jax.eval_shape(_local_join, *args)
        assert bidx.shape == hit.shape == (npr,)
    else:
        with pytest.raises(ExecutionError, match="limit 536870911"):
            jax.eval_shape(_local_join, *args)


# -- the served statement ---------------------------------------------------

@pytest.fixture(scope="module")
def catalog():
    cat = Catalog()
    load_tpch(cat, sf=0.002)
    s = Session(catalog=cat)
    # a fact table whose key is NULL, 0, negative, absent from the
    # dimension, or the widest integers; a dimension keyed on all of them
    s.execute("create table dim (id bigint primary key, v bigint)")
    s.execute("create table fact (k bigint, x bigint)")
    ids = [0, 1, 2, -7, I64.max, I64.min] + list(range(100, 160))
    s.execute("insert into dim values " + ", ".join(
        f"({i}, {n * 10})" for n, i in enumerate(ids)))
    rng = np.random.default_rng(5)
    ks = rng.choice(ids + [3, -8, 9999], 500).tolist()
    rows = [("null" if rng.random() < 0.1 else str(k), int(rng.integers(100)))
            for k in ks]
    s.execute("insert into fact values " + ", ".join(
        f"({k}, {x})" for k, x in rows))
    return cat


@pytest.fixture(scope="module")
def oracle(catalog):
    return mirror_to_sqlite(catalog)


@pytest.fixture(scope="module", params=[1, 4, 8], ids=["1x1", "1x4", "1x8"])
def served(request, catalog):
    s = Session(catalog=catalog,
                mesh=make_mesh(devices=jax.devices()[:request.param]))
    # a one-device CPU mesh routes joins to the host engine unless asked
    s.execute("set tidb_device_engine_mode = 'force'")
    return s


STATEMENTS = {
    "benchmark_join": "select count(*) as n, sum(l_quantity) as q from "
                      "lineitem join orders on l_orderkey = o_orderkey "
                      "where o_totalprice > 100000",
    "grouped": "select l_returnflag, count(*) as n, sum(l_quantity) as q "
               "from lineitem join orders on l_orderkey = o_orderkey "
               "where o_totalprice > 100000 group by l_returnflag "
               "order by l_returnflag",
    "build_column_above_the_join":
        "select count(*), sum(l_quantity), max(o_totalprice), "
        "min(o_orderkey) from lineitem join orders on "
        "l_orderkey = o_orderkey where o_totalprice > 100000",
    "filters_on_both_sides_and_both_sides_summed":
        "select count(*), sum(l_extendedprice + o_totalprice) from lineitem "
        "join orders on l_orderkey = o_orderkey where l_quantity < 30 and "
        "o_totalprice > 50000",
    "null_zero_and_widest_keys":
        "select count(*), sum(x), sum(v), min(k), max(k) from fact "
        "join dim on k = id",
    "filtered_dimension": "select count(*), sum(x), max(v) from fact "
                          "join dim on k = id where v > 30",
    "nothing_joins": "select count(*), sum(x) from fact join dim on "
                     "k = id where v < 0",
}


@pytest.mark.parametrize("stmt", sorted(STATEMENTS))
def test_served_join_equals_oracle_on_one_and_four_shards(served, oracle,
                                                          stmt, monkeypatch):
    sql = STATEMENTS[stmt]
    made = []
    real = pe.make_join_agg_fragment
    monkeypatch.setattr(pe, "make_join_agg_fragment",
                        lambda *a, **k: (made.append(a), real(*a, **k))[1])
    served._shard_cache.fragments.clear()
    got = served.query(sql)
    assert made, "the statement did not take make_join_agg_fragment"
    ok, msg = rows_equal(got, oracle.execute(sql).fetchall(), ordered=True)
    assert ok, f"{sql}\n{msg}"
