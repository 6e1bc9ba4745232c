"""The least work a statement asks of the memory system, from the
statement and the shapes of the tables on the device — never from the
implementation, so a change that replaces a kernel is read against the
same bytes.

A statement's module names the columns it has to read (``COLUMNS``). The
least a scan can do is read each of those columns once, at the width the
device holds it, with that column's validity mask and the table's
selection mask. A join reads the named columns of both sides once; on a
mesh of several chips each row's named columns also cross the
interconnect at most once ((chips-1)/chips of them, for rows hashed
evenly), which gives a second bound.
"""

from __future__ import annotations

import numpy as np


def _nbytes(entry) -> int:
    dtype, shape = entry
    return int(np.dtype(dtype).itemsize * int(np.prod(shape)))


def min_bytes(columns: dict, shapes: dict) -> int:
    """`columns`: table -> names the statement reads; `shapes`: table ->
    {"columns": {name: (dtype, shape)}, "valid": {...}, "sel": (dtype,
    shape)} as the device holds it."""
    total = 0
    for table, names in columns.items():
        t = shapes[table]
        total += _nbytes(t["sel"])
        for n in names:
            total += _nbytes(t["columns"][n]) + _nbytes(t["valid"][n])
    return total


def least_seconds(columns: dict, shapes: dict, peaks: dict, chips: int,
                  exchanged: bool) -> tuple:
    """(seconds, bound name): each chip reads its share of the bytes from
    its own HBM; where rows are exchanged between chips, the share that
    leaves a chip also crosses its interconnect."""
    b = min_bytes(columns, shapes)
    hbm = b / chips / peaks["hbm_bytes_per_s"]
    if exchanged and chips > 1:
        ici = b / chips * (chips - 1) / chips / peaks["ici_bytes_per_s"]
        if ici > hbm:
            return ici, "interconnect"
    return hbm, "hbm"
