#!/usr/bin/env python3
"""Several runs of cells in one call, one process each, one after
another (this parent never touches jax, so each child gets the chip):

    python3 benchmarks/sweep.py --out chiprun_out/scan.jsonl \
        --workload tpch_sf1.scan --seeds 11,12,13,14,15,16 --sets 2 \
        [--seconds 35] [--trace 0] [--keep-trace DIR]

Writes every run's result line (with its seed, set, wall seconds and exit
code) to ``--out`` and prints, per metric, the median and the spread of
each set as the builder's contract defines it: (Q3 - Q1) / median with
``statistics.quantiles(values, n=4)``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def one_run(workload, seed, seconds, trace, keep_trace=None) -> dict:
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    if keep_trace:
        cmd += ["--keep-trace", keep_trace]
    t = time.time()
    p = subprocess.run(cmd, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    rec = {"workload": workload, "seed": seed, "trace": trace,
           "rc": p.returncode, "wall_s": round(time.time() - t, 1)}
    try:
        rec["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        rec["result"] = None
    if p.returncode or not rec["result"] or not rec["result"].get("correct"):
        rec["stdout_tail"] = lines[-12:]
        rec["stderr_tail"] = p.stderr.strip().splitlines()[-25:]
    # the run's own account of set-up, window and trace, for a later look
    rec["info"] = [ln for ln in lines[:-1] if any(
        f'"phase": "{ph}"' in ln for ph in ("warmed", "window", "trace"))]
    return rec


def spread(values: list) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--keep-trace", default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seconds = args.seconds
    if seconds is None:
        with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
            seconds = json.load(f)["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    sets = []
    with open(args.out, "a") as out:
        for k in range(args.sets):
            runs = []
            for seed in seeds:
                rec = one_run(args.workload, seed, seconds, args.trace,
                              args.keep_trace)
                rec["set"] = k
                out.write(json.dumps(rec) + "\n")
                out.flush()
                res = rec["result"] or {}
                print(json.dumps({"set": k, "seed": seed, "rc": rec["rc"],
                                  "wall_s": rec["wall_s"],
                                  "correct": res.get("correct"),
                                  "metrics": {n: m["value"] for n, m in
                                              res.get("metrics", {}).items()},
                                  "device": res.get("device")}), flush=True)
                if res.get("metrics"):
                    runs.append({n: m["value"] for n, m in res["metrics"].items()})
            sets.append(runs)
    for k, runs in enumerate(sets):
        names = sorted({n for r in runs for n in r})
        for n in names:
            vals = [r[n] for r in runs if n in r]
            if len(vals) >= 2:
                print(json.dumps({"set": k, "metric": n, "runs": len(vals),
                                  "median": statistics.median(vals),
                                  "spread": spread(vals),
                                  "min": min(vals), "max": max(vals)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
