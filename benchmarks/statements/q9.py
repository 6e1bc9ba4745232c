"""TPC-H Q9 whole (clause 2.4.9, "Product Type Profit Measure"): by
nation and year, the profit on every line of a part whose name holds
COLOR —

    select nation, o_year, sum(amount) as sum_profit
    from (select n_name as nation,
                 extract(year from o_orderdate) as o_year,
                 l_extendedprice * (1 - l_discount)
                   - ps_supplycost * l_quantity as amount
          from part, supplier, lineitem, partsupp, orders, nation
          where s_suppkey = l_suppkey
            and ps_suppkey = l_suppkey and ps_partkey = l_partkey
            and p_partkey = l_partkey and o_orderkey = l_orderkey
            and s_nationkey = n_nationkey
            and p_name like '%[COLOR]%') as profit
    group by nation, o_year
    order by nation, o_year desc

Six tables, five joins, one of them on partsupp's whole primary key
(ps_partkey, ps_suppkey), a LIKE over part's names, no date filter: the
lines of the chosen parts meet all of orders (part 200,000, supplier
10,000, lineitem 6,001,215, partsupp 800,000, orders 1,500,000, nation
25 rows at SF1: 8,511,240 addressed a statement). The ORDER BY is a
total order: (nation, o_year) is the group. Parameter from the traffic
file's menu: ``{"color": "blue"}`` (clause 2.4.9.3 draws COLOR from the
92 words of clause 4.2.3's P_NAME list, validation value green; the
generator's names come from 13 words of their own, which hold no green:
the configuration's ``assumed``)."""

import numpy as np

from benchmarks.reference import Exact

TABLES = ("part", "supplier", "lineitem", "partsupp", "orders", "nation")
# what the statement has to read, whatever the implementation
COLUMNS = {"part": ("p_partkey", "p_name"),
           "supplier": ("s_suppkey", "s_nationkey"),
           "lineitem": ("l_orderkey", "l_partkey", "l_suppkey", "l_quantity",
                        "l_extendedprice", "l_discount"),
           "partsupp": ("ps_partkey", "ps_suppkey", "ps_supplycost"),
           "orders": ("o_orderkey", "o_orderdate"),
           "nation": ("n_nationkey", "n_name")}
ROOFLINE = "multijoin_roofline"


def sql(p: dict) -> str:
    color = str(p["color"])
    if not color.isalpha():
        raise ValueError(f"color {color!r}")
    return ("select nation, o_year, sum(amount) as sum_profit from ("
            "select n_name as nation, extract(year from o_orderdate) as o_year, "
            "l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity "
            "as amount "
            "from part, supplier, lineitem, partsupp, orders, nation "
            "where s_suppkey = l_suppkey and ps_suppkey = l_suppkey "
            "and ps_partkey = l_partkey and p_partkey = l_partkey "
            "and o_orderkey = l_orderkey and s_nationkey = n_nationkey "
            f"and p_name like '%{color}%') as profit "
            "group by nation, o_year order by nation, o_year desc")


def _rows_of(keys: np.ndarray, wanted: np.ndarray) -> tuple:
    """(for each of `wanted` the row of `keys` that holds it, whether one
    does): a stable sort of `keys` and a sorted search."""
    order = np.argsort(keys, kind="stable")
    at = np.searchsorted(keys[order], wanted)
    hit = at < len(keys)
    hit[hit] = keys[order[at[hit]]] == wanted[hit]
    return order[np.minimum(at, len(keys) - 1)], hit


def years(days: np.ndarray) -> np.ndarray:
    """The calendar year of each day number (days since 1970-01-01)."""
    return (days.astype("datetime64[D]").astype("datetime64[Y]")
            .astype(np.int64) + 1970)


def reference(data, p: dict, lowp=None) -> list:
    """Exact: every amount an integer at scale 4 (a scale-2 price times
    a scale-2 factor, less a scale-2 cost times a scale-2 quantity) and
    one int64 accumulator a group. The control (`lowp`, a numpy float
    dtype): each amount in `lowp` and ONE running total over the lines
    in group order, each group the difference of two readings (q3's)."""
    color = str(p["color"]).lower()
    names = data.tables["part"][1]["p_name"]
    chosen = np.flatnonzero([color in n.lower() for n in names])
    parts = data.col("part", "p_partkey")[
        np.isin(data.col("part", "p_name"), chosen)]
    l_part = data.col("lineitem", "l_partkey")
    rows = np.flatnonzero(np.isin(l_part, parts))
    l_part, l_supp = l_part[rows], data.col("lineitem", "l_suppkey")[rows]
    # partsupp by its two-key primary key, one integer a pair
    width = int(max(data.col("partsupp", "ps_suppkey").max(initial=0),
                    l_supp.max(initial=0))) + 1
    ps_row, hit = _rows_of(
        data.col("partsupp", "ps_partkey") * width
        + data.col("partsupp", "ps_suppkey"), l_part * width + l_supp)
    s_row, s_hit = _rows_of(data.col("supplier", "s_suppkey"), l_supp)
    o_row, o_hit = _rows_of(data.col("orders", "o_orderkey"),
                            data.col("lineitem", "l_orderkey")[rows])
    keep = hit & s_hit & o_hit
    rows, ps_row, s_row, o_row = rows[keep], ps_row[keep], s_row[keep], o_row[keep]
    n_row, n_hit = _rows_of(data.col("nation", "n_nationkey"),
                            data.col("supplier", "s_nationkey")[s_row])
    rows, ps_row, o_row, n_row = rows[n_hit], ps_row[n_hit], o_row[n_hit], n_row[n_hit]
    price = data.col("lineitem", "l_extendedprice")[rows]
    discount = data.col("lineitem", "l_discount")[rows]
    qty = data.col("lineitem", "l_quantity")[rows]
    cost = data.col("partsupp", "ps_supplycost")[ps_row]
    nation = np.array([data.decode("nation", "n_name", c)
                       for c in data.col("nation", "n_name")])[n_row]
    year = years(data.col("orders", "o_orderdate")[o_row])
    groups, inverse = np.unique(np.stack([np.unique(nation, return_inverse=True)[1],
                                          year]), axis=1, return_inverse=True)
    inverse = inverse.reshape(-1)
    if lowp is None:
        sums = np.zeros(groups.shape[1], dtype=np.int64)
        np.add.at(sums, inverse, price * (100 - discount) - cost * qty)
    else:
        order = np.argsort(inverse, kind="stable")
        amount = (price[order].astype(lowp) * (100 - discount[order]).astype(lowp)
                  - cost[order].astype(lowp) * qty[order].astype(lowp))
        running = np.cumsum(amount, dtype=lowp)
        ends = np.cumsum(np.bincount(inverse, minlength=groups.shape[1])) - 1
        sums = np.rint(np.diff(running[ends], prepend=np.zeros(1, dtype=lowp))
                       ).astype(np.int64)
    nations = np.unique(nation)
    out = [(str(nations[g]), int(y), Exact(int(v), 4))
           for (g, y), v in zip(groups.T, sums)]
    # ORDER BY nation, o_year DESC
    return sorted(out, key=lambda r: (r[0], -r[1]))
