"""The benchmark's own TPC-H data, from ``--seed``: numpy only.

The data is the input that both sides get: the program ingests these
arrays through its bulk-load entry (``Table.ingest_encoded``) and the
reference (``benchmarks/reference.py``) reads the very same arrays, so
nothing the program's storage layer has touched reaches the reference.

Shapes follow TPC-H v3 clause 4.2.3 as far as the cells need them:
cardinalities SF x (10k supplier, 150k customer, 200k part, 800k
partsupp, 1.5M orders, 6,001,215 lineitem), 1-7 lines an order,
quantity 1-50, discount 0.00-0.10, tax 0.00-0.08, ship/commit/receipt
dates off the order date, retail price by the spec's formula,
o_totalprice summed from the order's lines. Decimals are integers at
the column's scale (2), dates are days since 1970-01-01, strings are
codes into sorted pools. Departures (listed in every configuration's
``assumed``): order keys are dense 1..N (dbgen leaves gaps), text
columns draw from small pools, and it is not dbgen's random stream.

Every seed gives the same row counts (lineitem is SF x 6,001,215
exactly, as the spec's table of cardinalities has it), so every seed
drives the same device shapes; what the seed changes is every value.

``refresh_set`` gives the rows of one transaction of the refresh stream
(clause 2.5.2's RF1: new orders with their lineitems), by the same
rules. There the lines an order are drawn and NOT nudged to a total: a
table that is being written never repeats its row counts.
"""

from __future__ import annotations

import numpy as np

START, END, CURRENT = 8035, 10440, 9298  # 1992-01-01, 1998-08-02, 1995-06-17

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
           ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
           ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
           ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
           ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
           ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
           ("UNITED KINGDOM", 3), ("UNITED STATES", 1)]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
INSTRUCT = ["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"]
COMMENTS = sorted(f"final deps c{i:02d} haggle" for i in range(64))
CONTAINERS = sorted(f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
                    for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK",
                              "CAN", "DRUM"))
TYPES = sorted(f"{a} {b} {c}" for a in ("STANDARD", "SMALL", "MEDIUM",
                                        "LARGE", "ECONOMY", "PROMO")
               for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED",
                         "BRUSHED")
               for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER"))
BRANDS = sorted(f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6))
MFGRS = [f"Manufacturer#{m}" for m in range(1, 6)]
WORDS = sorted(["almond", "antique", "aquamarine", "azure", "beige",
                "bisque", "black", "blanched", "blue", "blush", "brown",
                "burlywood", "burnished"])
PNAMES = sorted(f"{a} {b}" for a in WORDS for b in WORDS)

LINEITEM_SF1 = 6_001_215  # TPC-H v3 clause 4.2.5

PRIMARY_KEYS = {
    "region": ["r_regionkey"], "nation": ["n_nationkey"],
    "supplier": ["s_suppkey"], "customer": ["c_custkey"],
    "part": ["p_partkey"], "partsupp": ["ps_partkey", "ps_suppkey"],
    "orders": ["o_orderkey"], "lineitem": ["l_orderkey", "l_linenumber"],
}


def sizes(sf: float) -> dict:
    """Row counts at scale factor `sf`: a function of the scale alone."""
    def n(base):
        return max(1, int(round(base * sf)))
    return {"region": 5, "nation": 25, "supplier": n(10_000),
            "customer": n(150_000), "part": n(200_000),
            "partsupp": 4 * n(200_000), "orders": n(1_500_000),
            "lineitem": max(n(1_500_000), n(LINEITEM_SF1))}


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """Clause 4.2.3's P_RETAILPRICE, in cents."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def _lines_per_order(rng, n_orders: int, n_lines: int) -> np.ndarray:
    """1..7 lines an order, uniform, then nudged so the total is exactly
    `n_lines`: the surplus or deficit (a fraction of a percent) is spread
    one line at a time over randomly chosen orders that have room."""
    lines = rng.integers(1, 8, n_orders)
    diff = n_lines - int(lines.sum())
    while diff:
        step = 1 if diff > 0 else -1
        room = np.flatnonzero(lines < 7 if step > 0 else lines > 1)
        pick = rng.choice(room, size=min(abs(diff), len(room)), replace=False)
        lines[pick] += step
        diff -= step * len(pick)
    return lines


def phone_pool():
    pool = sorted(f"{10 + n}-{a:03d}-{(7 * a) % 1000:03d}-{(13 * a) % 10000:04d}"
                  for n in range(25) for a in range(40))
    return pool


def generate(sf: float, seed: int) -> dict:
    """All eight tables: ``{table: (arrays, pools)}``; `arrays` maps every
    column to an int64 array (codes for the columns named in `pools`)."""
    rng = np.random.default_rng([int(seed), 0x7C4])
    n = sizes(sf)
    out = {}
    ccode = lambda m: rng.integers(0, len(COMMENTS), m)  # noqa: E731
    phones = phone_pool()

    out["region"] = ({"r_regionkey": np.arange(5), "r_name": np.arange(5),
                      "r_comment": ccode(5)},
                     {"r_name": REGIONS, "r_comment": COMMENTS})
    names = sorted(nm for nm, _ in NATIONS)
    out["nation"] = ({"n_nationkey": np.arange(25),
                      "n_name": np.array([names.index(nm) for nm, _ in NATIONS]),
                      "n_regionkey": np.array([r for _, r in NATIONS]),
                      "n_comment": ccode(25)},
                     {"n_name": names, "n_comment": COMMENTS})

    ns = n["supplier"]
    out["supplier"] = ({
        "s_suppkey": np.arange(1, ns + 1), "s_name": np.arange(ns),
        "s_address": ccode(ns), "s_nationkey": rng.integers(0, 25, ns),
        "s_phone": rng.integers(0, len(phones), ns),
        "s_acctbal": rng.integers(-99999, 999999 + 1, ns),
        "s_comment": ccode(ns)}, {
        "s_name": [f"Supplier#{k:09d}" for k in range(1, ns + 1)],
        "s_address": COMMENTS, "s_phone": phones, "s_comment": COMMENTS})

    nc = n["customer"]
    out["customer"] = ({
        "c_custkey": np.arange(1, nc + 1), "c_name": np.arange(nc),
        "c_address": ccode(nc), "c_nationkey": rng.integers(0, 25, nc),
        "c_phone": rng.integers(0, len(phones), nc),
        "c_acctbal": rng.integers(-99999, 999999 + 1, nc),
        "c_mktsegment": rng.integers(0, 5, nc), "c_comment": ccode(nc)}, {
        "c_name": [f"Customer#{k:09d}" for k in range(1, nc + 1)],
        "c_address": COMMENTS, "c_phone": phones, "c_mktsegment": SEGMENTS,
        "c_comment": COMMENTS})

    npart = n["part"]
    pkeys = np.arange(1, npart + 1)
    out["part"] = ({
        "p_partkey": pkeys, "p_name": rng.integers(0, len(PNAMES), npart),
        "p_mfgr": rng.integers(0, 5, npart),
        "p_brand": rng.integers(0, len(BRANDS), npart),
        "p_type": rng.integers(0, len(TYPES), npart),
        "p_size": rng.integers(1, 51, npart),
        "p_container": rng.integers(0, len(CONTAINERS), npart),
        "p_retailprice": retail_price(pkeys), "p_comment": ccode(npart)}, {
        "p_name": PNAMES, "p_mfgr": MFGRS, "p_brand": BRANDS,
        "p_type": TYPES, "p_container": CONTAINERS, "p_comment": COMMENTS})

    ps_part = np.repeat(pkeys, 4)
    nps = len(ps_part)
    out["partsupp"] = ({
        "ps_partkey": ps_part,
        "ps_suppkey": (ps_part + np.tile(np.arange(4), npart)
                       * (ns // 4 + 1)) % ns + 1,
        "ps_availqty": rng.integers(1, 10_000, nps),
        "ps_supplycost": rng.integers(100, 100_000 + 1, nps),
        "ps_comment": ccode(nps)}, {"ps_comment": COMMENTS})

    no, nl = n["orders"], n["lineitem"]
    lines = _lines_per_order(rng, no, nl)
    out["lineitem"], out["orders"] = _orders_with_lines(
        rng, np.arange(1, no + 1), lines, sf, n)
    return out


def _orders_with_lines(rng, okeys: np.ndarray, lines: np.ndarray, sf: float,
                       n: dict) -> tuple:
    """(lineitem, orders) for the orders `okeys` with `lines` lineitems
    each, by clause 4.2.3's rules: the loaded tables and the refresh
    stream's transactions both come from here."""
    ccode = lambda m: rng.integers(0, len(COMMENTS), m)  # noqa: E731
    no, nl = len(okeys), int(lines.sum())
    ns, nc, npart = n["supplier"], n["customer"], n["part"]
    nclerk = max(1, int(1000 * sf))
    odate = rng.integers(START, END - 151 + 1, no)
    first = np.concatenate([[0], np.cumsum(lines)[:-1]])
    l_order = np.repeat(okeys, lines)
    l_odate = np.repeat(odate, lines)
    l_part = rng.integers(1, npart + 1, nl)
    qty = rng.integers(1, 51, nl)
    ext = qty * retail_price(l_part)
    disc = rng.integers(0, 11, nl)
    tax = rng.integers(0, 9, nl)
    ship = l_odate + rng.integers(1, 122, nl)
    receipt = ship + rng.integers(1, 31, nl)
    returned = receipt <= CURRENT
    shipped = ship <= CURRENT
    n_f = np.add.reduceat(shipped.astype(np.int64), first)
    lineitem = ({
        "l_orderkey": l_order, "l_partkey": l_part,
        "l_suppkey": (l_part + rng.integers(0, 4, nl) * (ns // 4 + 1)) % ns + 1,
        "l_linenumber": np.arange(nl) - np.repeat(first, lines) + 1,
        "l_quantity": qty * 100, "l_extendedprice": ext, "l_discount": disc,
        "l_tax": tax,
        # sorted pool A N R: returned -> A or R, else N
        "l_returnflag": np.where(returned, 2 * rng.integers(0, 2, nl), 1),
        "l_linestatus": np.where(shipped, 0, 1),  # F O
        "l_shipdate": ship, "l_commitdate": l_odate + rng.integers(30, 91, nl),
        "l_receiptdate": receipt,
        "l_shipinstruct": rng.integers(0, 4, nl),
        "l_shipmode": rng.integers(0, 7, nl), "l_comment": ccode(nl)}, {
        "l_returnflag": ["A", "N", "R"], "l_linestatus": ["F", "O"],
        "l_shipinstruct": INSTRUCT, "l_shipmode": SHIPMODES,
        "l_comment": COMMENTS})
    orders = ({
        "o_orderkey": okeys, "o_custkey": rng.integers(1, nc + 1, no),
        # sorted pool F O P: all lines shipped, none, some
        "o_orderstatus": np.where(n_f == lines, 0, np.where(n_f == 0, 1, 2)),
        "o_totalprice": np.add.reduceat(
            ext * (100 - disc) * (100 + tax) // 10000, first),
        "o_orderdate": odate, "o_orderpriority": rng.integers(0, 5, no),
        "o_clerk": rng.integers(0, nclerk, no),
        "o_shippriority": np.zeros(no, dtype=np.int64),
        "o_comment": ccode(no)}, {
        "o_orderstatus": ["F", "O", "P"], "o_orderpriority": PRIORITIES,
        "o_clerk": [f"Clerk#{k + 1:09d}" for k in range(nclerk)],
        "o_comment": COMMENTS})
    return lineitem, orders


def refresh_set(sf: float, seed: int, k: int, orders: int) -> dict:
    """Transaction `k` (0, 1, 2 ...) of the refresh stream: `orders` new
    orders and their lineitems, ``{table: (arrays, pools)}`` as `generate`
    gives them. Order keys go on densely from the loaded ones (at SF1 from
    1,500,001), so no key ever meets a loaded one or another
    transaction's; every order has 1 to 7 lineitems drawn from the seed,
    so a transaction's row count differs from the next one's."""
    rng = np.random.default_rng([int(seed), 0x7C5, int(k)])
    n = sizes(sf)
    okeys = n["orders"] + int(k) * int(orders) + np.arange(1, int(orders) + 1)
    lineitem, new_orders = _orders_with_lines(
        rng, okeys, rng.integers(1, 8, int(orders)), sf, n)
    return {"orders": new_orders, "lineitem": lineitem}
