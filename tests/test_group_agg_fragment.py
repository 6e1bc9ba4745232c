"""The general fragment's generic aggregate (parallel/fragment.py
compile_agg: compaction, per-shard partial sort-reduce, and on a mesh of
several parts the repartition of the partial groups and the exact final
sort-reduce; on one part the partial table is the final table) as TPC-H
Q18's inner block
drives it — GROUP BY a key of many values, HAVING on the sum, ORDER BY
the key — against a plain dict oracle on 1 and several parts of the CPU
mesh; the overflow retry and its counter; the rule that sizes the group
table (a bulk load sketches its primary-key columns, the aggregate's
estimate follows the sketch, and a new connection's first launch is its
only one); the stages' scopes in the compiled HLO; and the
``fragment.finalize`` span of a served statement."""

import re

import numpy as np
import pytest

from tidb_tpu.parallel import make_mesh
from tidb_tpu.session import Session
from tidb_tpu.statistics import NDVSketch, column_ndv
from tidb_tpu.storage.catalog import Catalog
from tidb_tpu.storage.table import ColumnInfo, TableSchema
from tidb_tpu.types import INT64 as BIGINT
from tidb_tpu.utils.metrics import FRAGMENT_DISPATCH, FRAGMENT_RETRY_TOTAL

I64 = np.iinfo(np.int64)
SQL = ("select k, sum(v) as q from t group by k "
       "having sum(v) > {having} order by k")


def keys_of(kind: str, n: int, rng) -> list:
    """`n` grouping keys (None = NULL) of one kind of case."""
    if kind == "all_distinct":
        return [int(k) for k in rng.permutation(n) + 1]
    if kind == "heavy_duplicates":
        return [int(k) for k in rng.integers(0, 7, n)]
    if kind == "null_and_negative":
        return [None if k == 0 else int(k) for k in rng.integers(-40, 40, n)]
    if kind == "int64_extremes":
        pool = [I64.min, I64.min + 1, -1, 0, 1, I64.max - 1, I64.max]
        return [pool[i] for i in rng.integers(0, len(pool), n)]
    raise ValueError(kind)


def catalog_with(keys: list, vals: list, bulk: bool) -> Catalog:
    """Table t(id PRIMARY KEY, k, v): through the bulk-load entry where
    no key is NULL (``ingest_encoded`` takes no validity), else by INSERT."""
    catalog = Catalog()
    cols = [ColumnInfo("id", BIGINT, not_null=True), ColumnInfo("k", BIGINT),
            ColumnInfo("v", BIGINT)]
    table = catalog.create_table("test", TableSchema("t", cols, primary_key=["id"]))
    if bulk:
        table.ingest_encoded({"id": np.arange(len(keys), dtype=np.int64),
                              "k": np.array(keys, dtype=np.int64),
                              "v": np.array(vals, dtype=np.int64)}, {})
    else:
        table.insert_rows([(i, k, v) for i, (k, v) in enumerate(zip(keys, vals))])
    return catalog


def session(catalog, devices, n_parts: int) -> Session:
    s = Session(catalog=catalog, chunk_capacity=1024,
                mesh=make_mesh(devices=devices[:n_parts]))
    # a CPU mesh routes generic aggregation to the host engine unless asked
    s.execute("set tidb_device_engine_mode = 'force'")
    return s


def oracle(keys: list, vals: list, having: int) -> list:
    sums = {}
    for k, v in zip(keys, vals):
        sums[k] = sums.get(k, 0) + v
    keep = [(k, q) for k, q in sums.items() if q > having]
    # ORDER BY k: NULL first, as MySQL sorts it
    return sorted(keep, key=lambda r: (r[0] is not None, r[0] or 0))


def launches(kind="general_generic") -> float:
    return sum(v for labels, v in FRAGMENT_DISPATCH.samples()
               if labels.get("kind") == kind)


def retries() -> dict:
    return {(labels.get("kind"), labels.get("knob")): v
            for labels, v in FRAGMENT_RETRY_TOTAL.samples()}


@pytest.mark.parametrize("n_parts", [1, 4, 8])
@pytest.mark.parametrize("kind", ["all_distinct", "heavy_duplicates",
                                  "null_and_negative", "int64_extremes"])
def test_group_by_having_order_by_equals_the_oracle(devices8, kind, n_parts):
    rng = np.random.default_rng([3, n_parts, len(kind)])
    n = 3000
    keys = keys_of(kind, n, rng)
    vals = [int(v) for v in rng.integers(-50, 5000, n)]
    having = {"all_distinct": 2500, "heavy_duplicates": 0}.get(kind, -10**9)
    s = session(catalog_with(keys, vals, bulk=None not in keys), devices8, n_parts)
    before = launches()
    got = s.query(SQL.format(having=having))
    assert launches() > before, "the statement took no general fragment"
    want = oracle(keys, vals, having)
    assert want and [tuple(r) for r in got] == want


@pytest.mark.parametrize("n_parts", [1, 4])
def test_an_estimate_far_too_small_is_retried_to_the_same_rows(devices8, n_parts):
    """The safety net under the rule: with no NDV to read, the estimate
    falls to n ** 0.75 (447 groups for 3,400 rows, 894 slots) and the
    group table overflows; the retry grows the knob that blew, counts it,
    and answers with the rows a right-sized table gives."""
    rng = np.random.default_rng(5)
    n = 3400
    keys = keys_of("all_distinct", n, rng)
    vals = [int(v) for v in rng.integers(1, 5000, n)]
    catalog = catalog_with(keys, vals, bulk=True)
    sized = session(catalog, devices8, n_parts).query(SQL.format(having=100))
    catalog.table("test", "t").ndv_sketch.clear()  # what a parent of PR 28 knew
    s = session(catalog, devices8, n_parts)
    l0, r0 = launches(), retries()
    got = s.query(SQL.format(having=100))
    grown = {k: v - r0.get(k, 0) for k, v in retries().items() if v - r0.get(k, 0)}
    assert grown == {("general_generic", "compact"): 1}
    assert launches() - l0 == 2  # the under-sized program, then the grown one
    assert got == sized and [tuple(r) for r in got] == oracle(keys, vals, 100)
    l0 = launches()
    assert s.query(SQL.format(having=100)) == sized  # the connection has learnt
    assert launches() - l0 == 1 and retries().get(("general_generic", "compact")) \
        == r0.get(("general_generic", "compact"), 0) + 1


@pytest.mark.parametrize("ndv,n,stride", [(40, 5000, 1), (900, 5000, 1), (5000, 5000, 1),
                                          (60000, 240000, 1), (5000, 20000, -977)])
def test_a_bulk_load_sketches_its_key_and_the_group_table_follows(devices8, ndv, n, stride):
    """The new rule, end to end: ``ingest_encoded`` seeds the NDV sketch
    of the primary key's columns; ``column_ndv`` is then within the
    sketch's stated error of the truth (exact under K values), for dense
    keys (cut to their distinct values by presence before they are
    hashed) and for sparse, negative ones (`stride`); the aggregate's
    estimate is that NDV and not n ** 0.75; and the first statement of a
    new connection launches its fragment once."""
    rng = np.random.default_rng(ndv)
    keys = stride * np.sort(np.concatenate(
        [np.arange(ndv), rng.integers(0, ndv, n - ndv)]))
    catalog = Catalog()
    cols = [ColumnInfo("k", BIGINT, not_null=True),
            ColumnInfo("line", BIGINT, not_null=True), ColumnInfo("v", BIGINT)]
    table = catalog.create_table(
        "test", TableSchema("t", cols, primary_key=["k", "line"]))
    assert column_ndv(table, "k") is None  # nothing loaded, nothing known
    table.ingest_encoded({"k": keys, "line": np.arange(n, dtype=np.int64),
                          "v": rng.integers(1, 50, n)}, {})
    est = column_ndv(table, "k")
    assert abs(est - ndv) <= (NDVSketch.REL_ERROR * ndv if ndv >= NDVSketch.K else 0)
    assert column_ndv(table, "v") is None  # key columns only
    s = session(catalog, devices8, 4)
    plan = s.query("explain " + SQL.format(having=0))
    agg_rows = [float(r[0].split()[1]) for r in plan if "HashAgg" in r[0]]
    assert agg_rows == [pytest.approx(min(est, n), rel=1e-3)]
    assert not agg_rows[0] == pytest.approx(n ** 0.75, rel=0.05)
    l0, r0 = launches(), retries()
    got = s.query(SQL.format(having=0))
    assert launches() - l0 == 1 and retries() == r0
    assert [r[0] for r in got] == sorted(stride * k for k in range(ndv))
    # later inserts keep feeding the sketch the load seeded
    table.insert_rows([(stride * (ndv + i), 0, 1) for i in range(ndv)])
    assert column_ndv(table, "k") == pytest.approx(2 * ndv, rel=max(
        NDVSketch.REL_ERROR, 1e-9) if 2 * ndv >= NDVSketch.K else 1e-9)


SCOPES = ["agg.partial", "exchange.agg/exchange.sort",
          "exchange.agg/exchange.scatter", "exchange.agg/exchange.all_to_all",
          "agg.final"]
# what each mesh compiles: one part owns every key, so its partial table
# is its final table and nothing is exchanged
SCOPES_OF = {4: SCOPES, 1: ["agg.partial"]}


@pytest.fixture(scope="module", params=sorted(SCOPES_OF), ids="1x{}".format)
def hlo_ops(request, devices8):
    """(parts, per variant the ops of the compiled general fragment of
    the statement on a mesh of so many parts, each as (the scope in its
    ``op_name``, opcode, result type)): one program per variant, without
    and with the compaction and top-n stages."""
    from tidb_tpu.parallel import executor as pe

    rng = np.random.default_rng(9)
    keys = [int(k) for k in rng.integers(0, 500, 4000)]
    catalog = catalog_with(keys, [1] * len(keys), bulk=True)
    out = {}
    real = pe.DistFragmentExec._dispatch_retry
    for variant, sql in (("plain", SQL.format(having=0)),
                         ("filtered_topn", "select k, sum(v) as q from t where v > 5 "
                                           "group by k order by q desc, k limit 3")):
        seen = []

        def spy(self, prog, args, shapes_sig, types_sig, growths, *span):
            seen.append((prog, args, growths))
            return real(self, prog, args, shapes_sig, types_sig, growths, *span)

        pe.DistFragmentExec._dispatch_retry = spy
        try:
            session(catalog, devices8, request.param).query(sql)
        finally:
            pe.DistFragmentExec._dispatch_retry = real
        (prog, args, growths), = seen
        text = prog.build_fn(growths).lower(*args).compile().as_text()
        ops = []
        for line in text.splitlines():
            name = re.search(r'op_name="jit\(frag_general\)/([^"]*)"', line)
            op = re.search(r'= (\(.*?\)|\S+) ([a-z][\w-]*)\(', line)
            if name and op:
                ops.append((name.group(1), op.group(2), op.group(1)))
        out[variant] = ops
    return request.param, out


@pytest.fixture(scope="module")
def hlo_by_scope(hlo_ops):
    """(parts, per variant the opcodes by scope)."""
    n_parts, ops_by_variant = hlo_ops
    out = {}
    for variant, ops in ops_by_variant.items():
        by_scope = out.setdefault(variant, {})
        for name, opcode, _ in ops:
            by_scope.setdefault(name, set()).add(opcode)
    return n_parts, out


@pytest.mark.parametrize("scope", SCOPES)
def test_each_stage_of_the_generic_aggregate_has_its_scope(hlo_by_scope, scope):
    n_parts, by_variant = hlo_by_scope
    ops = {op for name, found in by_variant["plain"].items()
           if f"/{scope}/" in f"/{name}/" for op in found}
    if scope not in SCOPES_OF[n_parts]:
        # one part: no exchange of the groups and no second sort-reduce
        assert not ops, (scope, sorted(ops))
        assert not any("exchange." in n or "agg.final" in n
                       for v in by_variant.values() for n in v)
        assert "all-to-all" not in set().union(
            *(o for v in by_variant.values() for o in v.values()))
        return
    assert ops, sorted(by_variant["plain"])
    if scope in ("agg.partial", "agg.final", "exchange.agg/exchange.sort"):
        assert "sort" in ops
    if scope == "exchange.agg/exchange.all_to_all":
        assert "all-to-all" in ops


@pytest.mark.parametrize("scope", ["scan", "agg.compact", "agg.topn"])
def test_the_optional_stages_have_their_scopes_too(hlo_by_scope, scope):
    """A filter is the scan's (a bare scan hands its columns on and
    leaves no op); a filtered input is compacted to its estimate before
    the partial sort; a pushed-down ORDER BY ... LIMIT keeps each shard's
    top groups (the planner pushes one down only onto a mesh: one part's
    groups are all the groups, and the root's TopN ranks them)."""
    n_parts, by_variant = hlo_by_scope
    assert not any(f"/{scope}/" in f"/{n}/" for n in by_variant["plain"])
    there = any(f"/{scope}/" in f"/{n}/" for n in by_variant["filtered_topn"])
    assert there == (scope != "agg.topn" or n_parts > 1), \
        sorted(by_variant["filtered_topn"])


def test_no_heavy_op_of_the_generic_aggregate_is_left_without_a_scope(hlo_by_scope):
    """Outside every scope lie the program's parameters and the assembly
    of the overflow vector, nothing that moves rows; on one part every
    sort and scatter is `agg.*`'s."""
    n_parts, by_variant = hlo_by_scope
    every = SCOPES_OF[n_parts] + ["scan", "agg.compact", "agg.topn"] + (
        ["exchange.agg"] if n_parts > 1 else [])
    for variant, by_scope in by_variant.items():
        bare = set().union(*(ops for n, ops in by_scope.items() if not any(
            f"/{s}/" in f"/{n}/" for s in every)))
        assert not bare & {"sort", "scatter", "gather", "all-to-all", "while",
                           "reduce", "dynamic-update-slice"}, (variant, sorted(bare))


@pytest.mark.parametrize("scope", ["agg.partial", "agg.final"])
def test_a_sort_reduce_sums_in_row_order_and_scatters_one_narrow_array(hlo_ops, scope):
    """PR 31: the statement's payloads (a count and an integer sum) are
    read off running totals at the run ends, so the one scatter left in
    a sort-reduce is the run ends' row numbers, 32 bits wide, where the
    parent scattered the key, its validity and every payload in 64 (five
    scatters a sort-reduce); and the parts of `_sort_reduce` are scopes
    of their own under whichever stage calls it."""
    n_parts, ops_by_variant = hlo_ops
    mine = [(name, opcode, type_) for name, opcode, type_ in ops_by_variant["plain"]
            if f"/{scope}/" in f"/{name}/"]
    if scope not in SCOPES_OF[n_parts]:
        assert not mine
        return
    scatters = [(name, type_) for name, opcode, type_ in mine if opcode == "scatter"]
    assert len(scatters) == 1, scatters
    (name, type_), = scatters
    assert f"{scope}/runs/" in name and type_.startswith("s32["), scatters
    subs = {part for name, _, _ in mine
            for part in name.split(f"{scope}/", 1)[1].split("/")[:1]}
    assert {"sort", "gather", "runs", "reduce", "keys"} <= subs, sorted(subs)
    # two gathers a sort-reduce, each of a stack of rows: the payloads
    # after the sort's permutation, and everything a group keeps (its
    # key, the running totals) from its run's last row
    gathers = [name.split(f"{scope}/", 1)[1].split("/")[0]
               for name, opcode, _ in mine if opcode == "gather"]
    assert sorted(gathers) == ["gather", "reduce"], gathers
    # one sort a sort-reduce, as before, and in its scope
    assert [name.split(f"{scope}/", 1)[1].split("/")[0]
            for name, opcode, _ in mine if opcode == "sort"] == ["sort"]


def lines_by_order(rng, n=3000):
    """(catalog, k, q, w) of t(k, line, q DECIMAL, w) in primary-key
    order, 700 keys: lineitem's shape, so the bulk load sketches `k` and
    a GROUP BY k is one launch cold."""
    from tidb_tpu.types import decimal_type

    catalog = Catalog()
    cols = [ColumnInfo("k", BIGINT, not_null=True), ColumnInfo("line", BIGINT, not_null=True),
            ColumnInfo("q", decimal_type(15, 2)), ColumnInfo("w", BIGINT)]
    table = catalog.create_table("test", TableSchema("t", cols, primary_key=["k", "line"]))
    k = np.sort(rng.integers(0, 700, n))
    q = rng.integers(1, 51, n) * 100  # quantity 1..50, scale 2
    w = rng.integers(-9, 9, n)
    table.ingest_encoded({"k": k, "line": np.arange(n, dtype=np.int64), "q": q, "w": w}, {})
    return catalog, k, q, w


def test_the_input_compaction_scatters_row_numbers_and_gathers_once(hlo_ops):
    """PR 36: `agg.compact` moves the filtered rows by ONE scatter of
    32-bit row numbers and ONE gather of the chunk's arrays as a stack,
    where the parent scattered each array by itself (the key and the
    value in 64 bits)."""
    _n_parts, ops_by_variant = hlo_ops
    mine = [(opcode, type_) for name, opcode, type_ in ops_by_variant["filtered_topn"]
            if "/agg.compact/" in f"/{name}/"]
    assert [t[:4] for o, t in mine if o == "scatter"] == ["s32["], mine
    assert [o for o, _ in mine].count("gather") == 1, mine


@pytest.mark.parametrize("n_parts", [1, 4])
def test_a_launch_counts_its_input_compaction(devices8, n_parts):
    """FRAGMENT_COMPACTIONS{kind}: Q18's inner aggregate reads an
    unfiltered scan, whose target is over its capacity: +0 a statement;
    under a filter the input is compacted to its estimate: +1 a launch,
    from the fragment cache too."""
    from tidb_tpu.utils.metrics import FRAGMENT_COMPACTIONS

    def compactions():
        return sum(v for labels, v in FRAGMENT_COMPACTIONS.samples()
                   if labels.get("kind") == "general_generic")

    catalog, k, q, w = lines_by_order(np.random.default_rng(36))
    s = session(catalog, devices8, n_parts)
    for sql, want in (
            ("select k, sum(q) from t group by k having sum(q) > 300 order by k", 0),
            ("select k, sum(q) from t where w > 2 group by k order by k", 1)):
        for _launch in ("traced", "from the fragment cache"):
            l0, c0 = launches(), compactions()
            rows = s.query(sql)
            assert launches() - l0 == 1 and compactions() - c0 == want
        if want:
            assert [(int(g), int(round(float(v) * 100))) for g, v in rows] == [
                (int(g), int(q[(k == g) & (w > 2)].sum()))
                for g in np.unique(k[w > 2])]


@pytest.mark.parametrize("n_parts", [1, 4])
def test_a_launch_counts_its_payloads_by_how_they_are_reduced(devices8, n_parts):
    """FRAGMENT_REDUCE_PAYLOADS{kind, path}: Q18's inner aggregate (a
    count and the two limbs of a decimal sum) adds 3 to `runs` and 0 to
    `scatter` a launch on one part; a MAX beside it adds its own count
    to `runs` and its extreme to `scatter`. Several parts reduce twice."""
    from tidb_tpu.utils.metrics import FRAGMENT_REDUCE_PAYLOADS

    def by_path():
        got = {"runs": 0.0, "scatter": 0.0}
        for labels, v in FRAGMENT_REDUCE_PAYLOADS.samples():
            if labels.get("kind") == "general_generic":
                got[labels["path"]] += v
        return got

    catalog, k, q, w = lines_by_order(np.random.default_rng(31))
    s = session(catalog, devices8, n_parts)
    twice = 1 if n_parts == 1 else 2
    for sql, want, rows in (
            ("select k, sum(q) from t group by k having sum(q) > 300 order by k",
             {"runs": 3, "scatter": 0},
             [(int(g), int(q[k == g].sum())) for g in np.unique(k)
              if q[k == g].sum() > 30000]),
            ("select k, sum(q), max(w) from t group by k order by k",
             {"runs": 4, "scatter": 1},
             [(int(g), int(q[k == g].sum()), int(w[k == g].max())) for g in np.unique(k)])):
        l0, p0 = launches(), by_path()
        got = s.query(sql)
        assert launches() - l0 == 1
        assert {p: v - p0[p] for p, v in by_path().items()} == {
            p: v * twice for p, v in want.items()}
        assert [(r[0],) + tuple(int(round(float(c) * 100)) if i == 0 else int(c)
                                for i, c in enumerate(r[1:])) for r in got] == rows


def test_a_served_statements_trace_names_the_finalize(devices8):
    """``fragment.finalize`` wraps ``_finalize_generic_tables``: the
    fetch (``dispatch.fetch`` and the ``device.wait`` in it) is its child,
    so its self time is the host's decode; by its prefix it counts under exec_host in program_spans."""
    from tidb_tpu.server.client import Client
    from tidb_tpu.server.server import Server
    from tidb_tpu.utils import tracing

    rng = np.random.default_rng(2)
    keys = [int(k) for k in rng.integers(0, 300, 2000)]
    vals = [int(v) for v in rng.integers(1, 50, 2000)]
    server = Server(catalog=catalog_with(keys, vals, bulk=True), host="127.0.0.1",
                    port=0, mesh=make_mesh(devices=devices8[:4]), status_port=0)
    server.start()
    try:
        c = Client(server.host, server.port, db="test", timeout=120)
        c.query("set tidb_device_engine_mode = 'force'")
        _names, rows = c.query(SQL.format(having=0))
        c.close()
    finally:
        server.stop()
    assert [(int(k), int(q)) for k, q in rows] == oracle(keys, vals, 0)
    trace = next(t for t in reversed(tracing.STORE.finished())
                 if "fragment.finalize" in t.self_us_by_name())
    spans = {}
    stack = list(trace.to_dict()["tree"])
    while stack:
        node = stack.pop()
        spans[node["name"]] = node
        stack.extend(node["children"])
    assert trace.root().name == "wire.stmt"
    fin = spans["fragment.finalize"]
    (fetch,) = fin["children"]  # the one device_get of the group tables
    assert fetch["name"] == "dispatch.fetch"
    assert [c["name"] for c in fetch["children"]] == ["device.wait"]
    assert 0 <= fin["self_us"] < fin["duration_us"]
    assert "fragment.general_generic[parts=4]" in spans
