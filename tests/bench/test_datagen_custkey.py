"""O_CUSTKEY by TPC-H v3 clause 4.2.3: no order names a customer key
divisible by 3, so a third of the customers have no orders (Q13, Q22's
NOT EXISTS). The loaded tables and the refresh stream's transactions
both draw it so. Every table but ``orders``, and every column of
``orders`` drawn before ``o_custkey``, is the data the generator drew
when ``o_custkey`` was uniform over every customer, bit for bit.
Numpy only."""

import hashlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import tpch_datagen  # noqa: E402

SF = 0.01
Q22_CODES = (13, 31, 23, 29, 30, 18, 17)  # clause 2.4.22.3's validation values

# sha256 at SF0.01 as the generator drew the tables with o_custkey uniform
# over every customer: of each table but orders (its arrays' name, dtype,
# shape and bytes, columns by name), and of the bytes of each column of
# orders drawn before o_custkey or computed from lineitem
BEFORE = {
    12: {
        "region": "ef47f9e7be80852bd709da64bcde500163d2544fb7f66cbc1bd3120ee4d500ca",
        "nation": "be56b3260b5838799492de04c8cdce44f410795c8df821480a9422e893d84915",
        "supplier": "9ddf1010055bc259320dacc081e27df81e3a8c29d3362d3e10824d696fded7c1",
        "customer": "bb855e9fe9e45070fa832d8fe7b4ad4be188ea9cfff6a037723904490db2b1ae",
        "part": "fd86e9c93617b3f1e7f37301952d1e4086ba25009540ca353ed3d15b50a004a7",
        "partsupp": "b79f6eb2321fe7d9eaeb87813f14e4992722edfefd20c9e2b49f6fb74ee9013b",
        "lineitem": "a9dc2d5ccdcaf8cff7588dde9d1fefffeb8dfa2dca88ff529b2453808293a0f5",
        "orders.o_orderkey": "211762750ba2a2ccd9a22b1e67bfbb7de4bb9426cd8202dd933044e73fd7b9e9",
        "orders.o_orderstatus": "8db65f7cdb42a0b44a5742d1a77964c91c75b63470c30bf5d96e096b4874083d",
        "orders.o_totalprice": "07fcf8d1ff29bfdbee890d63d2819663ae92983a99630fa50db9829615c69d76",
        "orders.o_orderdate": "86a3463ef15f1fe91e67dee01229ccc2897753b4975e8becf43f471b6f89610d",
        "orders.o_shippriority": "9a413b131ecf0ccfb02a837ddb33766d8604cc00da7937b79bd363b53c8d7d86",
    },
    2147498101: {
        "region": "6f604e00ad173dc0a90773df4af7b303b6f07d74a8001d46508c5aec7b84de85",
        "nation": "852d0bbe116b3116d1cf7afdd7c156df964c8fb24bdafb64a70a42f92db46cd1",
        "supplier": "f4b07d0e08615b6b497feb546b51225a78b47069ed1f3ddb76ca7976f34b985f",
        "customer": "4b49ff8e3ac768d12786be808dd22033edc4f11484d1f193d413e1e45fd929bb",
        "part": "f3402c267291b1a02909c3de522beb8669b9aa421bd71f5a2e8ef7f001a27a4a",
        "partsupp": "3657a4065d833c13ff51bb3376b7fad6ca705a4a5db10be1febe2d36458d0f8b",
        "lineitem": "2b7ce8497be148165ab05bde242b07c3ff92dff15dd40452cbe1fdf72f740aaa",
        "orders.o_orderkey": "211762750ba2a2ccd9a22b1e67bfbb7de4bb9426cd8202dd933044e73fd7b9e9",
        "orders.o_orderstatus": "d7c7a7bd81245e21d060cb9fe169855b2d85ecb2ca3eef7fbe9c46c6337817a4",
        "orders.o_totalprice": "0bca23b6c609d89175b4138230b042cba853eea27f391c55a0c0622fa9184d3b",
        "orders.o_orderdate": "7013f8a20c3aa786f7fad53b75d3c776e62dcfebb26eb93cf1e5610b302049fc",
        "orders.o_shippriority": "9a413b131ecf0ccfb02a837ddb33766d8604cc00da7937b79bd363b53c8d7d86",
    },
}


def digest(tables, part: str) -> str:
    if "." in part:
        table, col = part.split(".")
        return hashlib.sha256(tables[table][0][col].tobytes()).hexdigest()
    h = hashlib.sha256()
    arrays = tables[part][0]
    for col in sorted(arrays):
        a = arrays[col]
        for piece in (col.encode(), a.dtype.str.encode(), str(a.shape).encode(), a.tobytes()):
            h.update(piece)
    return h.hexdigest()


def customers_without_orders(tables) -> int:
    cust, orders = tables["customer"][0]["c_custkey"], tables["orders"][0]["o_custkey"]
    return int(len(cust) - np.isin(cust, orders).sum())


def q22_groups(tables) -> dict:
    """TPC-H Q22 in numpy at the validation codes: ``{cntrycode: customers}``
    of the customers with no orders whose balance is over the AVG of the
    positive balances in those codes (``bal * count > sum``: exact)."""
    c, o = tables["customer"][0], tables["orders"][0]
    code = np.array([int(p[:2]) for p in tpch_datagen.phone_pool()])[c["c_phone"]]
    bal = c["c_acctbal"]
    listed = np.isin(code, Q22_CODES)
    sub = listed & (bal > 0)
    keep = (listed & (bal * int(sub.sum()) > int(bal[sub].sum()))
            & ~np.isin(c["c_custkey"], o["o_custkey"]))
    codes, counts = np.unique(code[keep], return_counts=True)
    return dict(zip(codes.tolist(), counts.tolist()))


@pytest.fixture(scope="module")
def drawn():
    return {seed: tpch_datagen.generate(SF, seed) for seed in BEFORE}


@pytest.mark.parametrize("part", sorted(BEFORE[12]))
@pytest.mark.parametrize("seed", sorted(BEFORE))
def test_what_is_drawn_before_o_custkey_is_as_it_was(drawn, seed, part):
    assert digest(drawn[seed], part) == BEFORE[seed][part]


@pytest.mark.parametrize("seed", sorted(BEFORE))
def test_no_order_names_a_customer_key_divisible_by_3(drawn, seed):
    nc, no = tpch_datagen.sizes(SF)["customer"], tpch_datagen.sizes(SF)["orders"]
    keys = drawn[seed]["orders"][0]["o_custkey"]
    assert keys.dtype == np.int64 and len(keys) == no
    assert not (keys % 3 == 0).any()
    assert keys.min() >= 1 and keys.max() <= nc
    assert set(np.unique(keys).tolist()) == {k for k in range(1, nc + 1) if k % 3}


def test_o_custkey_is_the_same_for_the_same_seed_and_another_for_another():
    a, b, c = (tpch_datagen.generate(SF, s)["orders"][0]["o_custkey"]
               for s in (2**31 + 11, 2**31 + 11, 12))
    assert np.array_equal(a, b) and not np.array_equal(a, c)


@pytest.mark.parametrize("nc", [1, 2, 3, 4, 10, 11, 1500])
def test_the_kth_key_not_divisible_by_3_covers_each_such_key_once(nc):
    want = [k for k in range(1, nc + 1) if k % 3]
    assert len(want) == nc - nc // 3
    assert tpch_datagen._kth_not_divisible_by_3(np.arange(len(want))).tolist() == want


@pytest.mark.parametrize("k", [0, 1, 7])
def test_a_refresh_transaction_names_no_customer_key_divisible_by_3(k):
    keys = tpch_datagen.refresh_set(0.1, 2**31 + 23, k, 400)["orders"][0]["o_custkey"]
    assert len(keys) == 400 and not (keys % 3 == 0).any()
    assert keys.min() >= 1 and keys.max() <= tpch_datagen.sizes(0.1)["customer"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_third_of_the_customers_have_no_orders(seed):
    tables = tpch_datagen.generate(0.1, seed)
    nc = tpch_datagen.sizes(0.1)["customer"]
    assert abs(customers_without_orders(tables) - nc // 3) <= 1
    # Q22 counts about a seventh of them in each of its seven codes
    groups = q22_groups(tables)
    assert sorted(groups) == sorted(Q22_CODES)
    assert 500 <= sum(groups.values()) <= 760 and min(groups.values()) >= 50


def test_at_sf1_exactly_50000_customers_have_no_orders():
    tables = tpch_datagen.generate(1.0, 1)
    assert customers_without_orders(tables) == 50_000
    groups = q22_groups(tables)
    assert sum(groups.values()) == 6_271 and len(groups) == 7
