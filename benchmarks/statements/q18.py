"""TPC-H Q18 whole (clause 2.4.18, "Large Volume Customer"): the hundred
dearest orders among those whose lineitems' quantities add up to more
than QUANTITY, each with its customer —

    select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
           sum(l_quantity)
    from customer, orders, lineitem
    where o_orderkey in (select l_orderkey from lineitem
                         group by l_orderkey
                         having sum(l_quantity) > [QUANTITY])
      and c_custkey = o_custkey and o_orderkey = l_orderkey
    group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    order by o_totalprice desc, o_orderdate limit 100

with ``o_orderkey`` appended to the ORDER BY, so that a tie at the cut
has one answer. An IN-subquery that is a GROUP BY over every row of
lineitem (6,001,215 rows into 1,500,000 groups at SF1) with a HAVING a
few tens of groups pass, a semi-join of orders with it, two joins
(customer 150,000, lineitem again), a five-key GROUP BY and the first
hundred of the total order. Parameter from the traffic file's menu:
``{"quantity": 300}`` (whole units; clause 2.4.18.4's validation value,
2.4.18.3 draws QUANTITY from 312..315). ``TABLES`` names lineitem once,
though the statement names it twice: the rows addressed read beside
Q3's."""

import datetime

import numpy as np

from benchmarks.reference import Exact

TABLES = ("customer", "orders", "lineitem")
# what the statement has to read, whatever the implementation
COLUMNS = {"customer": ("c_custkey", "c_name"),
           "orders": ("o_orderkey", "o_custkey", "o_orderdate",
                      "o_totalprice"),
           "lineitem": ("l_orderkey", "l_quantity")}
ROOFLINE = "subqjoin_roofline"
LIMIT = 100
EPOCH = datetime.date(1970, 1, 1)


def sql(p: dict) -> str:
    return ("select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice, "
            "sum(l_quantity) from customer, orders, lineitem "
            "where o_orderkey in (select l_orderkey from lineitem "
            "group by l_orderkey "
            f"having sum(l_quantity) > {int(p['quantity'])}) "
            "and c_custkey = o_custkey and o_orderkey = l_orderkey "
            "group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice "
            f"order by o_totalprice desc, o_orderdate, o_orderkey limit {LIMIT}")


def order_quantities(key: np.ndarray, qty: np.ndarray, lowp=None) -> tuple:
    """(the distinct order keys ascending, each one's SUM(l_quantity) in
    units of scale 2). Exact: one int64 accumulator a key
    (``np.add.at``). The control (`lowp`, a numpy float dtype), as
    q18agg's and q3's: ONE running total over the rows in key order, in
    `lowp`, each group the difference of two readings of it (an
    accumulator of its own per group would hide the precision: no order
    sums past 35,000 units, which float32 holds exactly)."""
    keys, inverse = np.unique(key, return_inverse=True)
    if lowp is None:
        sums = np.zeros(len(keys), dtype=np.int64)
        np.add.at(sums, inverse, qty)
        return keys, sums
    order = np.argsort(inverse, kind="stable")
    running = np.cumsum(qty[order].astype(lowp), dtype=lowp)
    ends = np.cumsum(np.bincount(inverse, minlength=len(keys))) - 1
    sums = np.diff(running[ends], prepend=np.zeros(1, dtype=lowp))
    return keys, np.rint(sums).astype(np.int64)


def _rows_of(keys_sorted: np.ndarray, wanted: np.ndarray) -> tuple:
    """(for each of `wanted` the place of its key in `keys_sorted`,
    whether the key is there): a sorted search."""
    at = np.searchsorted(keys_sorted, wanted)
    hit = at < len(keys_sorted)
    hit[hit] = keys_sorted[at[hit]] == wanted[hit]
    return at, hit


def reference(data, p: dict, lowp=None) -> list:
    keys, sums = order_quantities(data.col("lineitem", "l_orderkey"),
                                  data.col("lineitem", "l_quantity"), lowp)
    keep = sums > int(p["quantity"]) * 100  # HAVING, on the units
    large, large_q = keys[keep], sums[keep]
    # orders whose key is IN the subquery's answer (a semi-join), then
    # joined with lineitem on the same key: the outer SUM(l_quantity) of
    # an order is the subquery's own sum of it
    o_rows = np.flatnonzero(np.isin(data.col("orders", "o_orderkey"), large))
    at, hit = _rows_of(large, data.col("orders", "o_orderkey")[o_rows])
    o_rows, qty = o_rows[hit], large_q[at[hit]]
    # the customer of each (an inner join: an order without one is left out)
    c_key = data.col("customer", "c_custkey")
    by_key = np.argsort(c_key, kind="stable")
    at, hit = _rows_of(c_key[by_key], data.col("orders", "o_custkey")[o_rows])
    o_rows, qty, c_rows = o_rows[hit], qty[hit], by_key[at[hit]]
    okey = data.col("orders", "o_orderkey")[o_rows]
    odate = data.col("orders", "o_orderdate")[o_rows]
    price = data.col("orders", "o_totalprice")[o_rows]
    # ORDER BY o_totalprice DESC, o_orderdate, o_orderkey: lexsort's last key first
    top = np.lexsort((okey, odate, -price))[:LIMIT]
    return [(data.decode("customer", "c_name",
                         data.col("customer", "c_name")[c_rows[i]]),
             int(c_key[c_rows[i]]), int(okey[i]),
             (EPOCH + datetime.timedelta(days=int(odate[i]))).isoformat(),
             Exact(int(price[i]), 2), Exact(int(qty[i]), 2)) for i in top]
