"""TPC-H Q3 whole (clause 2.4.3, "Shipping Priority"): the ten unshipped
orders of one market segment with the highest revenue —

    select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
           o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = '[SEGMENT]' and c_custkey = o_custkey
      and l_orderkey = o_orderkey
      and o_orderdate < date '[DATE]' and l_shipdate > date '[DATE]'
    group by l_orderkey, o_orderdate, o_shippriority
    order by revenue desc, o_orderdate limit 10

with ``l_orderkey`` appended to the ORDER BY, so that a tie at the cut
has one answer. Three tables joined (customer 150,000, orders 1,500,000,
lineitem 6,001,215 rows at SF1), a filter on each, one group an order
that passes (some 11,000-12,000 at SF1), the first ten of their total
order. Parameters from the traffic file's menu: ``{"segment":
"BUILDING", "date": "1995-03-15"}`` (clause 2.4.3.3 draws SEGMENT from
the five segments and DATE from 1995-03-01..31; these are its
validation values)."""

import datetime

import numpy as np

from benchmarks.reference import Exact

TABLES = ("customer", "orders", "lineitem")
# what the statement has to read, whatever the implementation
COLUMNS = {"customer": ("c_custkey", "c_mktsegment"),
           "orders": ("o_orderkey", "o_custkey", "o_orderdate",
                      "o_shippriority"),
           "lineitem": ("l_orderkey", "l_extendedprice", "l_discount",
                        "l_shipdate")}
ROOFLINE = "joingroup_roofline"
LIMIT = 10
EPOCH = datetime.date(1970, 1, 1)


def sql(p: dict) -> str:
    segment, date = str(p["segment"]), str(p["date"])
    if not segment.replace(" ", "").isalpha():
        raise ValueError(f"segment {segment!r}")
    date = datetime.date.fromisoformat(date).isoformat()
    return ("select l_orderkey, "
            "sum(l_extendedprice * (1 - l_discount)) as revenue, "
            "o_orderdate, o_shippriority "
            "from customer, orders, lineitem "
            f"where c_mktsegment = '{segment}' and c_custkey = o_custkey "
            "and l_orderkey = o_orderkey "
            f"and o_orderdate < date '{date}' and l_shipdate > date '{date}' "
            "group by l_orderkey, o_orderdate, o_shippriority "
            f"order by revenue desc, o_orderdate, l_orderkey limit {LIMIT}")


def open_orders(data, p: dict) -> tuple:
    """(the lineitem rows that pass all three filters and both joins, the
    row of ``orders`` each belongs to). A semi-join by ``np.isin`` on each
    key; the order's row by binary search in its sorted keys."""
    pool = data.tables["customer"][1]["c_mktsegment"]
    day = data.days(p["date"])
    in_segment = data.col("customer", "c_custkey")[
        data.col("customer", "c_mktsegment") == pool.index(p["segment"])]
    o_key = data.col("orders", "o_orderkey")
    o_rows = np.flatnonzero(
        (data.col("orders", "o_orderdate") < day)
        & np.isin(data.col("orders", "o_custkey"), in_segment))
    o_rows = o_rows[np.argsort(o_key[o_rows], kind="stable")]
    l_key = data.col("lineitem", "l_orderkey")
    l_rows = np.flatnonzero(data.col("lineitem", "l_shipdate") > day)
    at = np.searchsorted(o_key[o_rows], l_key[l_rows])
    hit = at < len(o_rows)
    hit[hit] = o_key[o_rows[at[hit]]] == l_key[l_rows[hit]]
    return l_rows[hit], o_rows[at[hit]]


def revenues(l_order: np.ndarray, price: np.ndarray, discount: np.ndarray,
             lowp=None) -> tuple:
    """(the distinct order keys ascending, the first row of each, each
    one's SUM(l_extendedprice * (1 - l_discount)) in units of scale 4).
    Exact: the product of two scale-2 integers in int64 and one int64
    accumulator a key. The control (`lowp`, a numpy float dtype), as
    q18agg's: the products in `lowp` and ONE running total over the rows
    in key order, in `lowp`, each group the difference of two readings."""
    keys, first, inverse = np.unique(l_order, return_index=True,
                                     return_inverse=True)
    if lowp is None:
        sums = np.zeros(len(keys), dtype=np.int64)
        np.add.at(sums, inverse, price * (100 - discount))
        return keys, first, sums
    order = np.argsort(inverse, kind="stable")
    product = price[order].astype(lowp) * (100 - discount[order]).astype(lowp)
    running = np.cumsum(product, dtype=lowp)
    ends = np.cumsum(np.bincount(inverse, minlength=len(keys))) - 1
    sums = np.diff(running[ends], prepend=np.zeros(1, dtype=lowp))
    return keys, first, np.rint(sums).astype(np.int64)


def reference(data, p: dict, lowp=None) -> list:
    l_rows, o_rows = open_orders(data, p)
    keys, first, revenue = revenues(
        data.col("lineitem", "l_orderkey")[l_rows],
        data.col("lineitem", "l_extendedprice")[l_rows],
        data.col("lineitem", "l_discount")[l_rows], lowp)
    odate = data.col("orders", "o_orderdate")[o_rows[first]]
    prio = data.col("orders", "o_shippriority")[o_rows[first]]
    # ORDER BY revenue DESC, o_orderdate, l_orderkey: lexsort's last key first
    top = np.lexsort((keys, odate, -revenue))[:LIMIT]
    return [(int(keys[i]), Exact(int(revenue[i]), 4),
             (EPOCH + datetime.timedelta(days=int(odate[i]))).isoformat(),
             int(prio[i])) for i in top]
