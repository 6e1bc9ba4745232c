"""TPC-H Q18's inner block (clause 2.4.18, "Large Volume Customer"): the
orders whose lineitems' quantities add up to more than QUANTITY —

    select l_orderkey from lineitem group by l_orderkey
    having sum(l_quantity) > [QUANTITY]

with the sum in the select list and an ORDER BY, so that the answer has a
defined order and carries the number it was filtered on. One GROUP BY
over every row of lineitem into as many groups as there are orders
(1,500,000 at SF1), of which a few tens pass. Parameter from the traffic
file's menu: ``{"quantity": 300}`` (whole units; clause 2.4.18.3 draws
QUANTITY from 312..315)."""

import numpy as np

from benchmarks.reference import Exact

TABLES = ("lineitem",)
# what the statement has to read, whatever the implementation
COLUMNS = {"lineitem": ("l_orderkey", "l_quantity")}
ROOFLINE = "groupagg_roofline"


def sql(p: dict) -> str:
    return ("select l_orderkey, sum(l_quantity) as q from lineitem "
            "group by l_orderkey "
            f"having sum(l_quantity) > {int(p['quantity'])} "
            "order by l_orderkey")


def group_sums(key: np.ndarray, qty: np.ndarray, lowp=None) -> tuple:
    """(the distinct keys ascending, each one's sum of `qty` in units of
    scale 2). Exact: one int64 accumulator per key (``np.add.at``). The
    control (`lowp`, a numpy float dtype) is the shortcut a sort-based
    aggregate on a chip without 64-bit adders would be tempted by: ONE
    running total over the rows in key order, in `lowp`, and each group
    the difference of two readings of it. (An accumulator of its own per
    group would hide the precision: no order of TPC-H sums past 35,000
    units, which float32 holds exactly.)"""
    keys, inverse = np.unique(key, return_inverse=True)
    if lowp is None:
        sums = np.zeros(len(keys), dtype=np.int64)
        np.add.at(sums, inverse, qty)
        return keys, sums
    order = np.argsort(inverse, kind="stable")
    running = np.cumsum(qty[order].astype(lowp), dtype=lowp)
    ends = np.cumsum(np.bincount(inverse, minlength=len(keys))) - 1
    upto = running[ends]
    sums = np.diff(upto, prepend=np.zeros(1, dtype=lowp))
    return keys, np.rint(sums).astype(np.int64)


def reference(data, p: dict, lowp=None) -> list:
    keys, sums = group_sums(data.col("lineitem", "l_orderkey"),
                            data.col("lineitem", "l_quantity"), lowp)
    keep = sums > int(p["quantity"]) * 100  # HAVING, on the exact units
    return [(int(k), Exact(int(s), 2)) for k, s in zip(keys[keep], sums[keep])]
