"""TPC-H Q22 whole (clause 2.4.22, "Global Sales Opportunity"): by
country code, the customers of the listed codes who have placed no order
and whose balance is over the average positive balance of those codes —

    select cntrycode, count(*) as numcust, sum(c_acctbal) as totacctbal
    from (select substring(c_phone from 1 for 2) as cntrycode, c_acctbal
          from customer
          where substring(c_phone from 1 for 2) in ([I1], ..., [I7])
            and c_acctbal > (select avg(c_acctbal) from customer
                             where c_acctbal > 0.00
                               and substring(c_phone from 1 for 2)
                                   in ([I1], ..., [I7]))
            and not exists (select * from orders
                            where o_custkey = c_custkey)) as custsale
    group by cntrycode order by cntrycode

An uncorrelated scalar AVG subquery over customer, a NOT EXISTS over
orders (1,500,000 rows at SF1; a third of the customers have none, clause
4.2.3), a GROUP BY of the phone's first two characters. Parameter from
the traffic file's menu: ``{"codes": [13, 31, 23, 29, 30, 18, 17]}``
(clause 2.4.22.4's validation values). ``TABLES`` names customer once,
though the statement names it twice: 1,650,000 rows addressed a
statement."""

import numpy as np

from benchmarks.reference import Exact

TABLES = ("customer", "orders")
# what the statement has to read, whatever the implementation
COLUMNS = {"customer": ("c_custkey", "c_phone", "c_acctbal"),
           "orders": ("o_custkey",)}
ROOFLINE = "existsjoin_roofline"


def sql(p: dict) -> str:
    codes = ", ".join(f"'{int(c)}'" for c in p["codes"])
    listed = f"substring(c_phone, 1, 2) in ({codes})"
    return ("select cntrycode, count(*) as numcust, sum(c_acctbal) as totacctbal "
            "from (select substring(c_phone, 1, 2) as cntrycode, c_acctbal "
            f"from customer where {listed} "
            "and c_acctbal > (select avg(c_acctbal) from customer "
            f"where c_acctbal > 0.00 and {listed}) "
            "and not exists (select * from orders where o_custkey = c_custkey)"
            ") as custsale group by cntrycode order by cntrycode")


def reference(data, p: dict, lowp=None) -> list:
    """Exact: the balances are integers at scale 2, the AVG is compared
    as ``c_acctbal * count > sum`` (Python integers), the sums are
    int64. The control (`lowp`, a numpy float dtype): the AVG and each
    group's sum in `lowp`, the sum as ONE running total over the
    customers in code order, each group the difference of two readings
    (q18's and q3's control)."""
    pool = data.tables["customer"][1]["c_phone"]
    prefix = np.array([s[:2] for s in pool])[data.col("customer", "c_phone")]
    bal = data.col("customer", "c_acctbal")
    listed = np.isin(prefix, [str(int(c)) for c in p["codes"]])
    positive = listed & (bal > 0)
    n, s = int(positive.sum()), int(bal[positive].sum())
    if lowp is None:
        # AVG over no row is NULL, and a comparison with NULL keeps nothing
        over = (bal.astype(object) * n > s) if n else np.zeros(len(bal), bool)
    else:
        avg = (np.cumsum(bal[positive].astype(lowp), dtype=lowp)[-1] / lowp(n)
               if n else np.nan)
        over = bal.astype(lowp) > avg
    keep = (listed & over.astype(bool)
            & ~np.isin(data.col("customer", "c_custkey"),
                       data.col("orders", "o_custkey")))
    codes, inverse, counts = np.unique(prefix[keep], return_inverse=True,
                                       return_counts=True)
    if lowp is None:
        sums = np.zeros(len(codes), dtype=np.int64)
        np.add.at(sums, inverse, bal[keep])
    else:
        order = np.argsort(inverse, kind="stable")
        running = np.cumsum(bal[keep][order].astype(lowp), dtype=lowp)
        ends = np.cumsum(counts) - 1
        sums = np.rint(np.diff(running[ends], prepend=np.zeros(1, dtype=lowp))
                       ).astype(np.int64)
    return [(str(c), int(k), Exact(int(v), 2))
            for c, k, v in zip(codes, counts, sums)]
