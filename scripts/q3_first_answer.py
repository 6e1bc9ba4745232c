#!/usr/bin/env python3
"""What a cold TPC-H Q3 (or, ``--statement q18`` / ``q9``, Q18 / Q9) costs on the
device this process finds (ROADMAP S3's question to the chip): boots as
the benchmark's cells ``tpch_sf1_power.q3`` / ``.q18`` do, sends the
statement on a fresh connection and prints one JSON line a phase —

    python3 scripts/q3_first_answer.py [--statement q18|q9] [--root TREE] [--sf 1.0] [--seed N]

seconds to the first answer, backend compiles and their seconds,
launches (FRAGMENT_DISPATCH) and retries by knob (FRAGMENT_RETRY_TOTAL),
the capacity growths the connection ended on, peak device memory, then
the warm statement's latency over ``--repeats``; every answer is compared
with the statement's numpy reference (``benchmarks/statements/``). ``--root`` names
the checkout whose program and harness are imported (a ``git archive`` of
another commit); the statement's text and reference are this checkout's.
``--cpu`` asks the CPU for the device engine (a rehearsal: counts, no
speed). ``--dump-hlo DIR`` also writes each parameter set's compiled
program as text (gzip): its ops' ``op_name`` metadata carries the scopes
(``join0/join.probe`` ...) that a profiler trace's op names lack.
Information from single runs, not the benchmark.
"""

from __future__ import annotations

import argparse
import gzip
import importlib.util
import json
import os
import sys
import time

T0 = time.time()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# each statement's validation values (clauses 2.4.3.4, 2.4.18.4); Q9's
# COLOR is the one word of the generator's names that traffic/q9.json takes
PARAMS = {"q3": [{"segment": "BUILDING", "date": "1995-03-15"}],
          "q18": [{"quantity": 300}], "q9": [{"color": "blue"}]}


def emit(**kw) -> None:
    kw.setdefault("t", round(time.time() - T0, 1))
    print(json.dumps(kw, default=str), flush=True)


def program_counters() -> dict:
    """Every fragment counter the imported program has, by label."""
    from tidb_tpu.utils import metrics

    out = {}
    for name in ("FRAGMENT_DISPATCH", "FRAGMENT_RETRY_TOTAL",
                 "FRAGMENT_JOINS", "FRAGMENT_SUBQUERIES",
                 "FRAGMENT_COMPACTIONS", "FRAGMENT_COMPACT_SLOTS",
                 "FRAGMENT_EAGER_PARTIALS", "FRAGMENT_MULTIKEY_JOINS",
                 "FRAGMENT_EXCHANGE_STEPS",
                 "FRAGMENT_REDUCE_PAYLOADS", "FRAGMENT_COMPILE"):
        c = getattr(metrics, name, None)
        if c is None:
            continue
        for labels, v in c.samples():
            key = name + "{" + ",".join(
                f"{k}={labels[k]}" for k in sorted(labels)) + "}"
            out[key] = v
    return out


def delta(before: dict, after: dict) -> dict:
    return {k: round(v - before.get(k, 0), 3) for k, v in after.items()
            if v - before.get(k, 0)}


def spy_on_launches() -> list:
    """Every general fragment the program launches from here on, as
    (program, arguments, growths, probe mode)."""
    from tidb_tpu.parallel import executor as pe

    real, seen = pe.DistFragmentExec._dispatch_retry, []

    def spy(self, prog, args, shapes_sig, types_sig, growths, *span):
        out, grown = real(self, prog, args, shapes_sig, types_sig, growths, *span)
        seen.append((prog, args, grown, getattr(self.ctx, "join_probe_mode", None)))
        return out, grown

    pe.DistFragmentExec._dispatch_retry = spy
    return seen


def dump_hlo(out_dir: str, name: str, params: dict, prog, fn_args, growths,
             probe_mode) -> None:
    """The program the statement just ran, compiled again (the compile
    cache has it) and written as text."""
    import jax

    from tidb_tpu.utils.device import device_tier

    t = time.perf_counter()
    platform = next(iter(jax.tree_util.tree_leaves(fn_args)[0].devices())).platform
    with device_tier(platform):
        text = prog.build_fn(growths, probe_mode=probe_mode).lower(
            *fn_args).compile().as_text()
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "_".join(
        [name] + [str(v) for v in params.values()]) + ".hlo.txt.gz")
    with gzip.open(path, "wt") as f:
        f.write(text)
    emit(phase="hlo", path=path, chars=len(text), sorts=text.count(" sort("),
         seconds=round(time.perf_counter() - t, 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--timeout", type=float, default=1100.0)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--pre", default="[]",
                    help="JSON list of SET statements sent first on every connection")
    ap.add_argument("--dump-hlo", default=None, metavar="DIR")
    ap.add_argument("--statement", default="q3", choices=sorted(PARAMS))
    ap.add_argument("--params", default=None,
                    help="JSON list of parameter sets (default: the "
                         "statement's validation values)")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.root))

    from benchmarks import reference, system, tpch_datagen

    spec = importlib.util.spec_from_file_location(
        "statement", os.path.join(HERE, "benchmarks", "statements",
                                  args.statement + ".py"))
    stmt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(stmt)

    device, devs = system.device()
    emit(phase="device", root=os.path.abspath(args.root), **device)
    counters = system.Counters()
    tables = tpch_datagen.generate(args.sf, args.seed)
    data = reference.Data(tables)
    emit(phase="generate", rows={n: data.rows(n) for n in stmt.TABLES})
    server = system.start_server(tables, tpch_datagen.PRIMARY_KEYS, {})
    emit(phase="load", mesh=str(dict(server.mesh.shape)))
    launched = spy_on_launches() if args.dump_hlo else []
    pre = tuple(json.loads(args.pre))
    if args.cpu:
        pre += ("set tidb_device_engine_mode = 'force'",)
    ok = True
    try:
        for p in json.loads(args.params) if args.params else PARAMS[args.statement]:
            # a fresh connection: its own ShardCache, its own growths
            client = system.connect(server, args.timeout, pre)
            text, want = stmt.sql(p), stmt.reference(data, p)
            c0, f0 = counters.read(), program_counters()
            t = time.perf_counter()
            try:
                _names, rows = client.query(text)
            except Exception as e:  # noqa: BLE001 — not returning is the finding
                emit(phase="first_answer", params=p, error=f"{type(e).__name__}: {e}"[:300],
                     seconds=round(time.perf_counter() - t, 1),
                     counters=delta(c0, counters.read()),
                     program=delta(f0, program_counters()))
                ok = False
                break
            first = time.perf_counter() - t
            cmp = reference.compare_rows(rows, want)
            ok = ok and reference.answer_ok(cmp)
            sess = max(server.sessions.items())[1]
            growths = [list(g) for g in sess._shard_cache.growth.values()]
            emit(phase="first_answer", params=p, seconds=round(first, 3),
                 compared=cmp, rows=len(rows), first_row=str(rows[:1]),
                 counters=delta(c0, counters.read()),
                 program=delta(f0, program_counters()), growths=growths,
                 memory=system.memory(devs))
            if launched:
                dump_hlo(args.dump_hlo, args.statement, p, *launched[-1])
            c1, f1 = counters.read(), program_counters()
            warm = []
            for _ in range(args.repeats):
                t = time.perf_counter()
                _names, rows = client.query(text)
                warm.append(round((time.perf_counter() - t) * 1e3, 2))
                ok = ok and reference.answer_ok(
                    reference.compare_rows(rows, want))
            emit(phase="warm", params=p, ms=warm,
                 counters=delta(c1, counters.read()),
                 program=delta(f1, program_counters()),
                 memory=system.memory(devs),
                 resident={t: {"bytes": s["bytes"]} for t, by in
                           system.table_shapes(server).items()
                           for s in list(by.values())[-1:]})
            client.close()
    finally:
        server.stop()
    emit(phase="done", ok=ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
