"""TPC-H refresh function RF1 (new sales), clause 2.5.2, as one
transaction: ``BEGIN``, one multi-row ``INSERT INTO orders``, one
multi-row ``INSERT INTO lineitem`` (every new order's 1 to 7 lines),
``COMMIT``. Parameters come from the traffic file's writer:
``{"orders_per_transaction": 100}``; 15 such transactions are one RF1 at
SF1 (0.1% of the orders). RF2, the deletes, is not sent.

A write statement has three functions. ``source`` makes, from the scale
and the seed, the one thing the other two read: ``refresh(k)``, the rows
of transaction `k` (``tpch_datagen.refresh_set``). ``transaction`` turns
them into the SQL the writer sends; ``apply`` appends the very same rows
to the reference's arrays.
"""

import datetime
import functools

from benchmarks import tpch_datagen

TABLES = ("orders", "lineitem")
# what the harness reads back, on a new connection, after the window:
# COUNT(*) and the SUM of one column (with its decimal scale) per table
READ_BACK = {"orders": ("o_totalprice", 2), "lineitem": ("l_extendedprice", 2)}

DECIMALS = {"o_totalprice", "l_quantity", "l_extendedprice", "l_discount", "l_tax"}
DATES = {"o_orderdate", "l_shipdate", "l_commitdate", "l_receiptdate"}
EPOCH = datetime.date(1970, 1, 1)


def source(scale: float, seed: int, params: dict):
    """``refresh(k)``: transaction `k`'s rows, ``{table: (arrays, pools)}``."""
    return functools.lru_cache(maxsize=None)(functools.partial(
        tpch_datagen.refresh_set, scale, seed,
        orders=int(params["orders_per_transaction"])))


def _insert(table: str, arrays: dict, pools: dict) -> str:
    texts = []
    for name, values in arrays.items():
        if name in pools:
            texts.append(["'" + pools[name][v] + "'" for v in values.tolist()])
        elif name in DECIMALS:  # an integer at scale 2, never negative here
            texts.append([f"{v // 100}.{v % 100:02d}" for v in values.tolist()])
        elif name in DATES:
            texts.append(["'" + (EPOCH + datetime.timedelta(days=v)).isoformat() + "'"
                          for v in values.tolist()])
        else:
            texts.append([str(v) for v in values.tolist()])
    return (f"insert into {table} ({', '.join(arrays)}) values "
            + ", ".join("(" + ", ".join(row) + ")" for row in zip(*texts)))


def transaction(refresh, k: int, params: dict) -> list:
    """The SQL texts of transaction `k`, in the order they are sent; the
    last one's OK packet is the acknowledgement."""
    rows = refresh(k)
    return (["begin"] + [_insert(t, *rows[t]) for t in TABLES] + ["commit"])


def apply(data, refresh, k: int):
    """`data` (a ``reference.Data``) with transaction `k`'s rows appended."""
    return data.plus(refresh(k))
