"""TPC-H Q6 (forecasting revenue change), clause 2.4.6: one pass over
lineitem, three range filters, one SUM. Substitution parameters DATE
(first of January of a year), DISCOUNT and QUANTITY come from the
traffic file's menu: ``{"date": "1994-01-01", "discount": 6, "quantity": 24}``
(discount in hundredths)."""

import datetime

from benchmarks.reference import Exact, total

TABLES = ("lineitem",)
# what the statement has to read, whatever the implementation
COLUMNS = {"lineitem": ("l_shipdate", "l_discount", "l_quantity",
                        "l_extendedprice")}
ROOFLINE = "scan_agg_roofline"


def sql(p: dict) -> str:
    d = p["discount"] / 100
    return ("select sum(l_extendedprice * l_discount) as revenue "
            "from lineitem "
            f"where l_shipdate >= date '{p['date']}' "
            f"and l_shipdate < date '{p['date']}' + interval '1' year "
            f"and l_discount between {d:.2f} - 0.01 and {d:.2f} + 0.01 "
            f"and l_quantity < {int(p['quantity'])}")


def state(data, p: dict, lowp=None) -> dict:
    """The one sum, as ``{group: (terms ...)}``: states of disjoint row
    sets add (``reference.add_states``), which is how the reference
    follows a writer without a pass over the table for every commit."""
    sd, disc, qty, ext = (data.col("lineitem", c) for c in COLUMNS["lineitem"])
    lo = datetime.date.fromisoformat(p["date"])
    hi = lo.replace(year=lo.year + 1)
    m = ((sd >= data.days(lo.isoformat())) & (sd < data.days(hi.isoformat()))
         & (disc >= p["discount"] - 1) & (disc <= p["discount"] + 1)
         & (qty < int(p["quantity"]) * 100))
    if lowp is None:
        terms = ext[m] * disc[m]
    else:
        terms = ext[m].astype(lowp) * disc[m].astype(lowp)
    return {(): (total(terms, lowp),)}


def rows(state: dict, data) -> list:
    return [(Exact(state[()][0], 4),)]


def reference(data, p: dict, lowp=None) -> list:
    return rows(state(data, p, lowp), data)
