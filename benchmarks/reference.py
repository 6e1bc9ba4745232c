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
