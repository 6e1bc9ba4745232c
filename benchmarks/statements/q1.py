"""TPC-H Q1 (pricing summary report), clause 2.4.1: one pass over
lineitem, one date filter, eight aggregates over the four or so groups
of (l_returnflag, l_linestatus). Substitution parameter DELTA (60-120
days) comes from the traffic file's menu: ``{"delta": 90}``."""

import numpy as np

from benchmarks.reference import Exact, total

TABLES = ("lineitem",)
COLUMNS = {"lineitem": ("l_returnflag", "l_linestatus", "l_quantity",
                        "l_extendedprice", "l_discount", "l_tax",
                        "l_shipdate")}
ROOFLINE = "scan_agg_roofline"


def sql(p: dict) -> str:
    return (
        "select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty, "
        "sum(l_extendedprice) as sum_base_price, "
        "sum(l_extendedprice * (1 - l_discount)) as sum_disc_price, "
        "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge, "
        "avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price, "
        "avg(l_discount) as avg_disc, count(*) as count_order "
        "from lineitem "
        f"where l_shipdate <= date '1998-12-01' - interval '{int(p['delta'])}' day "
        "group by l_returnflag, l_linestatus "
        "order by l_returnflag, l_linestatus")


def state(data, p: dict, lowp=None) -> dict:
    """Per group the five sums and the count: states of disjoint row sets
    add (``reference.add_states``), so the reference follows a writer
    without a pass over the table for every commit."""
    c = {n: data.col("lineitem", n) for n in COLUMNS["lineitem"]}
    m = c["l_shipdate"] <= data.days("1998-12-01") - int(p["delta"])
    rf, ls = c["l_returnflag"][m], c["l_linestatus"][m]
    q, e, d, t = (c[n][m] for n in ("l_quantity", "l_extendedprice",
                                    "l_discount", "l_tax"))
    cast = (lambda a: a) if lowp is None else (lambda a: a.astype(lowp))
    dp = cast(e) * cast(100 - d)
    ch = dp * cast(100 + t)
    out = {}
    for f in np.unique(rf):
        for s in np.unique(ls):
            g = (rf == f) & (ls == s)
            n = int(g.sum())
            if n:
                out[(int(f), int(s))] = tuple(
                    total(a[g], lowp) for a in (q, e, dp, ch, d)) + (n,)
    return out


def rows(state: dict, data) -> list:
    # codes into sorted pools: code order is text order
    return [(data.decode("lineitem", "l_returnflag", f),
             data.decode("lineitem", "l_linestatus", s),
             Exact(sq, 2), Exact(se, 2), Exact(sdp, 4), Exact(sch, 6),
             sq / n / 100, se / n / 100, sd / n / 100, n)
            for (f, s), (sq, se, sdp, sch, sd, n) in sorted(state.items())]


def reference(data, p: dict, lowp=None) -> list:
    return rows(state(data, p, lowp), data)
