"""The plain reference's data access and the comparison that decides
``correct``. numpy and the standard library only: nothing here (or in a
statement's ``reference``) imports the program or reads anything the
program has made — the arrays are the benchmark's own generated data.

Cell values come in three kinds, by the type the statement's reference
gives them:

* ``Exact`` (an integer at a decimal scale), ``int`` and ``str``: the
  configuration guarantees exact decimal arithmetic, so the text the
  client received has to equal the reference exactly. Limit 0.
* ``float`` (AVG, which the engine divides in float64): compared by
  relative gap; the widest gap of a run is reported against
  ``FLOAT_REL_LIMIT``.
"""

from __future__ import annotations

import datetime
from decimal import Decimal, InvalidOperation

import numpy as np

# How this limit was set (readings in PERF.md section 2): sound runs read
# at most a few units of float64 rounding (2.2e-16 a step); the float32
# control reads 1e-8 and more. The limit sits between, nearer the
# upper end in orders of magnitude.
FLOAT_REL_LIMIT = 1e-12


class Exact:
    """An integer `units` at decimal `scale`: units / 10**scale, exactly."""

    __slots__ = ("units", "scale")

    def __init__(self, units, scale: int):
        self.units, self.scale = int(units), int(scale)

    def decimal(self) -> Decimal:
        return Decimal(self.units).scaleb(-self.scale)

    def __repr__(self):
        return f"Exact({self.decimal()})"


class Data:
    """The generated tables as the reference sees them."""

    def __init__(self, tables: dict):
        self.tables = tables

    def col(self, table: str, name: str) -> np.ndarray:
        return self.tables[table][0][name]

    def rows(self, table: str) -> int:
        return len(next(iter(self.tables[table][0].values())))

    def plus(self, rows: dict) -> "Data":
        """These tables with `rows` (``{table: (arrays, pools)}``, codes
        into the same pools) appended: what a committed insert leaves."""
        tables = dict(self.tables)
        for name, (arrays, _pools) in rows.items():
            mine, pools = self.tables[name]
            tables[name] = ({c: np.concatenate([a, arrays[c]])
                             for c, a in mine.items()}, pools)
        return Data(tables)

    def empty(self) -> "Data":
        """The same tables and pools with no row in them."""
        return Data({name: ({c: a[:0] for c, a in arrays.items()}, pools)
                     for name, (arrays, pools) in self.tables.items()})

    def decode(self, table: str, name: str, code: int) -> str:
        return self.tables[table][1][name][int(code)]

    @staticmethod
    def days(iso: str) -> int:
        return (datetime.date.fromisoformat(iso)
                - datetime.date(1970, 1, 1)).days


def total(values: np.ndarray, lowp):
    """SUM as the configuration states it (exact, int64) or, for the
    control, in the lower precision `lowp` (a numpy float dtype): the
    step a later change would be tempted by, since the chip emulates
    64-bit integers. The control's result is rounded to the integer."""
    if lowp is None:
        return int(values.sum())
    return int(np.rint(values.astype(lowp).sum(dtype=lowp)))


def _cell_gap(got, want):
    """(kind, gap): kind 'exact' with gap 0/1, or 'float' with the
    relative gap."""
    if isinstance(want, float):
        try:
            g = float(got)
        except (TypeError, ValueError):
            return "float", float("inf")
        return "float", abs(g - want) / max(abs(want), 1e-300)
    if isinstance(want, str):
        return "exact", 0.0 if got == want else 1.0
    w = want.decimal() if isinstance(want, Exact) else Decimal(int(want))
    try:
        return "exact", 0.0 if Decimal(str(got)) == w else 1.0
    except (InvalidOperation, TypeError, ValueError):
        return "exact", 1.0


def compare_rows(got, want) -> dict:
    """One answer against the reference, row by row in order (every
    statement of the benchmark has a defined order). Returns the count of
    exact cells that differ (a wrong row count counts every cell of the
    longer side) and the widest relative gap of the float cells."""
    bad, widest, cells = 0, 0.0, 0
    if got is None or len(got) != len(want):
        n = max(len(got or ()), len(want))
        width = len(want[0]) if want else 1
        return {"exact_mismatches": n * width, "float_rel_gap": 0.0,
                "cells": n * width}
    for rg, rw in zip(got, want):
        if len(rg) != len(rw):
            bad += len(rw)
            cells += len(rw)
            continue
        for g, w in zip(rg, rw):
            kind, gap = _cell_gap(g, w)
            cells += 1
            if kind == "exact":
                bad += int(gap)
            else:
                widest = max(widest, gap)
    return {"exact_mismatches": bad, "float_rel_gap": widest, "cells": cells}


def answer_ok(cmp: dict) -> bool:
    return cmp["exact_mismatches"] == 0 and cmp["float_rel_gap"] <= FLOAT_REL_LIMIT


class Versions:
    """The reference in a cell that writes: the answer to every menu
    entry after the first `k` transactions of the writer, k = 0, 1, 2 ...

    A statement whose module has ``state`` and ``rows`` (sums and counts
    by group: Q1, Q6) is answered from the loaded rows' state plus each
    transaction's own, added exactly; any other from the arrays with the
    transactions' rows appended (a pass over the whole table for each k).
    `write` is the writer's statement module, `refresh` what its
    ``source`` gave: the same rows the transaction's SQL carries."""

    def __init__(self, data: Data, menu: list, statements: dict, write=None,
                 refresh=None, lowp=None):
        self.menu, self.statements, self.lowp = menu, statements, lowp
        self.write, self.refresh = write, refresh
        self._data = [data]   # _data[k]: the arrays after k transactions
        self._none = data.empty()
        self._new = []        # _new[k]: transaction k's rows alone, as Data
        self._states = {}     # item -> [state after 0, 1, ... transactions]
        self._answers = {}

    def new_rows(self, k: int) -> Data:
        while len(self._new) <= k:
            self._new.append(self.write.apply(
                self._none, self.refresh, len(self._new)))
        return self._new[k]

    def data(self, k: int) -> Data:
        while len(self._data) <= k:
            self._data.append(self.write.apply(
                self._data[-1], self.refresh, len(self._data) - 1))
        return self._data[k]

    def rows(self, table: str, k: int = 0) -> int:
        return self._data[0].rows(table) + sum(
            self.new_rows(i).rows(table) for i in range(k))

    def total(self, table: str, column: str, k: int) -> int:
        """SUM of an integer column after `k` transactions."""
        return total(self._data[0].col(table, column), self.lowp) + sum(
            total(self.new_rows(i).col(table, column), self.lowp)
            for i in range(k))

    def answer(self, item: int, k: int = 0) -> list:
        if (item, k) not in self._answers:
            entry = self.menu[item]
            mod = self.statements[entry["statement"]]
            if k and hasattr(mod, "state"):
                states = self._states.setdefault(item, [mod.state(
                    self._data[0], entry["params"], lowp=self.lowp)])
                while len(states) <= k:
                    states.append(add_states(states[-1], mod.state(
                        self.new_rows(len(states) - 1), entry["params"],
                        lowp=self.lowp)))
                rows = mod.rows(states[k], self._data[0])
            else:
                rows = mod.reference(self.data(k), entry["params"],
                                     lowp=self.lowp)
            self._answers[(item, k)] = rows
        return self._answers[(item, k)]


def add_states(a: dict, b: dict) -> dict:
    """Two aggregation states ``{group: (sums and counts ...)}``, added."""
    out = dict(a)
    for group, terms in b.items():
        out[group] = (tuple(x + y for x, y in zip(out[group], terms))
                      if group in out else terms)
    return out
