#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the reference
put in the program's place, computed in the nearest precision below the
one the configuration states (float32 for exact 64-bit decimals: the
chip emulates 64-bit integers, so 32-bit accumulation is the step a
later change would be tempted by). It has to come out NOT correct.

    python3 benchmarks/control.py --workload tpch_sf1.scan --seeds 1,2,3

For every seed: the cell's data at the cell's own scale, every menu
entry answered in the lower precision, formatted as the wire would, and
judged by the same ``check_answers`` the benchmark's runs use. In a cell
with a writer the answers and the read-back are those after set-up's
transactions and one of the window's, acknowledged before any statement
was sent, so the control goes through the states the reference adds. Prints
one line per seed with the numbers compared beside their limits. Needs
no chip (numpy on the host); the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmarks import reference, spec, tpch_datagen  # noqa: E402
from benchmarks.run import check_answers, versions  # noqa: E402


def to_wire(rows: list) -> list:
    """Typed reference rows as the text protocol would carry them."""
    def text(v):
        if isinstance(v, reference.Exact):
            return str(v.decimal())
        return repr(v) if isinstance(v, float) else str(v)
    return [tuple(text(v) for v in row) for row in rows]


def control_run(cell, seed: int, sf=None, lowp=np.float32) -> dict:
    """One control 'run': every menu entry once, answered in `lowp`."""
    scale = float(cell.config["scale_factor"] if sf is None else sf)
    data = reference.Data(tpch_datagen.generate(scale, seed))
    low, w = versions(cell, data, scale, seed, lowp)
    exact, _w = versions(cell, data, scale, seed)
    k, written = 0, None
    if w:  # one transaction of the window, acknowledged before any send
        k = int(w.get("warm_transactions", 0)) + 1
        written = {"first_k": k - 1, "writes": [
            {"k": k - 1, "t_send": 0, "t_commit_send": 1, "t_ack": 2,
             "error": None, "stmts": [(0, 2)]}],
            "read_back": {table: to_wire([(low.rows(table, k), reference.Exact(
                low.total(table, column, k), digits))])
                for table, (column, digits) in low.write.READ_BACK.items()}}
    records = [{"item": i, "rows": to_wire(low.answer(i, k)), "error": None,
                "t_send": 3, "t_done": 4} for i in range(len(cell.traffic["menu"]))]
    checks = check_answers(cell, exact, records, written)
    correct = all(v["value"] <= v["limit"]
                  for v in checks.values() if "limit" in v)
    return {"seed": seed, "lowp": np.dtype(lowp).name, "correct": correct,
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sf", type=float, default=None)
    ap.add_argument("--lowp", default="float32")
    args = ap.parse_args(argv)
    cell = spec.Cell(args.workload)
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        out = control_run(cell, seed, args.sf, np.dtype(args.lowp).type)
        passed += out["correct"]
        print(json.dumps(out), flush=True)
    return 1 if passed else 0  # a control that comes out correct is the fault


if __name__ == "__main__":
    sys.exit(main())
