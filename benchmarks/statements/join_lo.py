"""lineitem JOIN orders, filtered on the orders side, COUNT and SUM over
the matches: the largest join of TPC-H (the core of Q3, Q4, Q12, Q18 and
Q21) with the smallest output, and the join statement of ``chip_smoke.py``.
Parameter from the traffic file's menu: ``{"totalprice": 100000}``
(dollars)."""

import numpy as np

from benchmarks.reference import Exact, total

TABLES = ("lineitem", "orders")
COLUMNS = {"lineitem": ("l_orderkey", "l_quantity"),
           "orders": ("o_orderkey", "o_totalprice")}
ROOFLINE = "join_roofline"


def sql(p: dict) -> str:
    return ("select count(*) as n, sum(l_quantity) as q from lineitem "
            "join orders on l_orderkey = o_orderkey "
            f"where o_totalprice > {int(p['totalprice'])}")


def reference(data, p: dict, lowp=None) -> list:
    ok = data.col("orders", "o_orderkey")
    idx = np.full(int(ok.max()) + 1, -1, dtype=np.int64)
    idx[ok] = np.arange(len(ok))
    o_sel = data.col("orders", "o_totalprice") > int(p["totalprice"]) * 100
    li = idx[data.col("lineitem", "l_orderkey")]
    m = (li >= 0) & o_sel[np.maximum(li, 0)]
    n = (int(m.sum()) if lowp is None
         else total(m.astype(np.int64), lowp))
    return [(n, Exact(total(data.col("lineitem", "l_quantity")[m], lowp), 2))]
