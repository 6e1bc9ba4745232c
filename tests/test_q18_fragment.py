"""TPC-H Q18 whole through the general fragment compiler (PR 35): on a
mesh of ONE part the IN-subquery's GROUP BY and HAVING are a producer
inside the one program (`parallel/fragment.py _subquery_agg_producer`),
so a statement is one `general_generic` program and one launch, nothing
of the subquery's answer sizes anything (two seeds of data share a
fragment-cache key), the host's broadcast (`fragment.broadcast`) is
never opened; on several parts the subquery stays the broadcast it was.
The rows are the benchmark's own numpy reference's
(`benchmarks/statements/q18.py`) and the sqlite oracle's, on 1 and on 8
CPU devices; likewise a NOT IN of the same subquery with and without a
NULL key, a MIN in the HAVING (a segment op) and an AVG (a broadcast on
one part too)."""

import re

import numpy as np
import pytest

from benchmarks import reference, spec, tpch_datagen
from tidb_tpu.parallel import make_mesh
from tidb_tpu.planner import feedback
from tidb_tpu.session import Session
from tidb_tpu.storage.catalog import Catalog
from tidb_tpu.storage.table import ColumnInfo, TableSchema
from tidb_tpu.storage.tpch import TPCH_SCHEMAS
from tidb_tpu.testutil import mirror_to_sqlite, rows_equal
from tidb_tpu.utils import tracing
from tidb_tpu.utils.metrics import (
    FRAGMENT_COMPACTIONS,
    FRAGMENT_DISPATCH,
    FRAGMENT_RETRY_TOTAL,
    FRAGMENT_SUBQUERIES,
)

SF = 0.02  # 30,000 orders, 120,024 lineitems, 3,000 customers
SEEDS = [5, 2**31 + 17, 12]
# QUANTITY: an order has 1-7 lines of 1-50 units, so 350 is the most
MANY, FEW, NONE = 220, 260, 350
Q18 = spec.Cell("tpch_sf1_power.q18").statements["q18"]


def catalog_of(tables: dict) -> Catalog:
    """The benchmark's generated tables through the bulk-load entry, as
    `benchmarks/system.py start_server` ingests them."""
    catalog = Catalog()
    for name, (arrays, pools) in tables.items():
        cols = [ColumnInfo(n, t, not_null=nn) for n, t, nn in TPCH_SCHEMAS[name]]
        table = catalog.create_table("test", TableSchema(
            name, cols, primary_key=tpch_datagen.PRIMARY_KEYS[name]))
        table.ingest_encoded(dict(arrays), pools)
    return catalog


@pytest.fixture(scope="module")
def loads():
    """Per seed: (the catalog, the reference's view of the same arrays,
    the sqlite mirror)."""
    out = {}
    for seed in SEEDS:
        tables = tpch_datagen.generate(SF, seed)
        catalog = catalog_of(tables)
        oracle = mirror_to_sqlite(catalog, tables=["customer", "orders", "lineitem"])
        oracle.execute("create index li_ok on lineitem(l_orderkey)")
        out[seed] = catalog, reference.Data(tables), oracle
    return out


def session(catalog, devices, n_parts: int) -> Session:
    s = Session(catalog=catalog, mesh=make_mesh(devices=devices[:n_parts]))
    s.execute("use test")
    # a CPU mesh routes joins and generic aggregation to the host engine unless asked
    s.execute("set tidb_device_engine_mode = 'force'")
    feedback.STORE.clear()
    return s


def samples(counter) -> dict:
    """{(kind, path or knob or ''): n} of a fragment counter."""
    return {(labels.get("kind"), labels.get("path", labels.get("knob", ""))): n
            for labels, n in counter.samples()}


def delta(counter, before: dict) -> dict:
    return {k: n - before.get(k, 0) for k, n in samples(counter).items()
            if n - before.get(k, 0)}


def run_spied(s: Session, sql: str) -> tuple:
    """(rows, [(program, arguments, shapes, types, growths in, growths
    out)] of every `_dispatch_retry` the statement made, its trace)."""
    from tidb_tpu.parallel import executor as pe

    real, seen = pe.DistFragmentExec._dispatch_retry, []

    def spy(self, prog, args, shapes_sig, types_sig, growths, *span):
        out, grown = real(self, prog, args, shapes_sig, types_sig, growths, *span)
        seen.append((prog, args, shapes_sig, types_sig, growths, grown))
        return out, grown

    pe.DistFragmentExec._dispatch_retry = spy
    try:
        rows = s.query(sql)
    finally:
        pe.DistFragmentExec._dispatch_retry = real
    return rows, seen, tracing.STORE.finished()[-1]


def as_wire(rows) -> list:
    """Result rows as the text protocol would carry them: what the
    benchmark's comparison reads."""
    return [tuple(str(v) for v in row) for row in rows]


def check_rows(rows, data, oracle, quantity):
    """Against the benchmark's numpy reference, exactly (the comparison
    that decides `correct`), and against sqlite."""
    want = Q18.reference(data, {"quantity": quantity})
    cmp = reference.compare_rows(as_wire(rows), want)
    assert reference.answer_ok(cmp) and cmp["cells"] == 6 * len(want), cmp
    ok, msg = rows_equal(rows, oracle.execute(Q18.sql({"quantity": quantity})).fetchall(),
                         ordered=True)
    assert ok, msg
    return want


@pytest.mark.parametrize("quantity,n_rows", [(MANY, "the limit"), (FEW, "a few"), (NONE, 0)],
                         ids=["many", "few", "none"])
@pytest.mark.parametrize("seed", SEEDS)
def test_q18_on_one_part_is_one_program_and_one_launch(devices8, loads, seed, quantity,
                                                       n_rows):
    catalog, data, oracle = loads[seed]
    s = session(catalog, devices8, 1)
    l0, r0, q0 = (samples(c) for c in (FRAGMENT_DISPATCH, FRAGMENT_RETRY_TOTAL,
                                       FRAGMENT_SUBQUERIES))
    rows, seen, trace = run_spied(s, Q18.sql({"quantity": quantity}))
    want = check_rows(rows, data, oracle, quantity)
    assert len(want) == {"the limit": 100, 0: 0}.get(n_rows, len(want))
    assert n_rows != "a few" or 0 < len(want) < 100
    (prog, _args, _shapes, _types, growths, grown), = seen
    assert prog.n_subquery == 1 and not prog.broadcasts and prog.n_exchange == 0
    assert prog.n_join == 3 and prog.out_kind == "generic"
    assert prog.n_exists == 1  # the semi-join: its rank's count, no expansion
    # lineitem, which the statement names twice, is ONE argument of the
    # program (the compiler then shares the two sort-reduces' sort), and
    # such a source is never streamed in batches
    tables = [src.scan.table_name for src in prog.sources]
    assert sorted(tables) == ["customer", "lineitem", "orders"]
    assert tables.index("lineitem") in prog.stream_unsafe
    assert growths == grown == prog.growth_defaults  # every knob at its default
    assert delta(FRAGMENT_DISPATCH, l0) == {("general_generic", ""): 1}
    assert delta(FRAGMENT_RETRY_TOTAL, r0) == {}
    assert delta(FRAGMENT_SUBQUERIES, q0) == {("general_generic", "inline"): 1}
    names = set(trace.self_us_by_name())
    assert "fragment.general_generic[parts=1]" in names
    assert "fragment.broadcast" not in names


@pytest.mark.parametrize("quantity", [MANY, NONE], ids=["many", "none"])
def test_a_q18_launch_counts_its_compactions_the_subquerys_among_them(
        devices8, loads, quantity):
    """FRAGMENT_COMPACTIONS{kind}: of the program's 17 knobs
    (lineitem's eager partial's table 0, join0's expand and three
    targets 1-4, join1's 5-8, the subquery's input, table and survivors
    9-11, join2's three targets 12-14 — the semi-join decides existence
    from its rank's count, no expand knob — the root's 15-16) the trace
    compacts, at this scale, **the HAVING's survivors (11)** and join2's
    output (14) — whatever passes the HAVING, none included: the count
    is the program's, static — and a launch from the fragment cache adds
    the same two. The eager partial's table holds the key's estimated
    groups, under twice their estimate: the join over it compacts
    nothing of it."""
    catalog, _data, _oracle = loads[SEEDS[0]]
    s = session(catalog, devices8, 1)
    c0 = samples(FRAGMENT_COMPACTIONS)
    _rows, seen, _trace = run_spied(s, Q18.sql({"quantity": quantity}))
    (prog, _args, _shapes, _types, growths, grown), = seen
    assert growths == grown == prog.growth_defaults and prog.n_growth == 17
    (fn,) = [f for k, f in s._shard_cache.fragments.items() if k[0] == "frag"]
    assert fn.compactions == [11, 14]
    assert delta(FRAGMENT_COMPACTIONS, c0) == {("general_generic", ""): 2}
    c0 = samples(FRAGMENT_COMPACTIONS)
    s.query(Q18.sql({"quantity": quantity}))
    assert delta(FRAGMENT_COMPACTIONS, c0) == {("general_generic", ""): 2}


@pytest.mark.parametrize("n_parts", [1, 8])
def test_q18s_eager_table_takes_the_estimate_and_no_knob_grows(devices8, loads,
                                                              n_parts):
    """FRAGMENT_EAGER_PARTIALS{kind, sized}: the outer program's one eager
    partial (lineitem by order key, under join1) is sized from the key's
    sketched distinct count — a part's share of it, a quarter over —
    not one slot a lineitem row; the statement launches with every knob
    at its default cold and warm, on one part and on eight (where the
    subquery is a program of its own, with no eager partial), and the
    rows are the reference's."""
    from tidb_tpu.parallel.fragment import _Compiler
    from tidb_tpu.statistics import column_ndv
    from tidb_tpu.utils.metrics import FRAGMENT_EAGER_PARTIALS

    catalog, data, oracle = loads[SEEDS[1]]
    s = session(catalog, devices8, n_parts)
    ndv = column_ndv(catalog.table("test", "lineitem"), "l_orderkey")
    slots = int(np.ceil(_Compiler.NDV_HEADROOM * ndv / n_parts))
    for _ in range(2):  # cold, then from the fragment cache
        e0 = {tuple(sorted(k.items())): n for k, n in FRAGMENT_EAGER_PARTIALS.samples()}
        r0 = samples(FRAGMENT_RETRY_TOTAL)
        rows, seen, _trace = run_spied(s, Q18.sql({"quantity": FEW}))
        check_rows(rows, data, oracle, FEW)
        assert delta(FRAGMENT_RETRY_TOTAL, r0) == {}
        assert all(growths == grown for *_, growths, grown in seen)
        after = {tuple(sorted(k.items())): n for k, n in FRAGMENT_EAGER_PARTIALS.samples()}
        assert {k: n - e0.get(k, 0) for k, n in after.items() if n - e0.get(k, 0)} == {
            (("kind", "general_generic"), ("sized", "estimate")): 1}
    (outer,) = [prog for prog, *_ in seen if prog.n_join == 3]
    assert f"cap0:{slots}|eagg:" in outer.sig
    assert slots < catalog.table("test", "lineitem").n / n_parts / 3


def test_two_seeds_of_data_share_one_fragment_cache_key(devices8, loads):
    """The acceptance: nothing the subquery answers (69 groups pass here
    under one seed and 58 under the other) reaches a shape or the
    signature. The parent padded the fetched answer to a power of two and
    keyed the outer program on it."""
    keys, passed = [], []
    for seed in SEEDS[:2]:
        catalog, data, _oracle = loads[seed]
        s = session(catalog, devices8, 1)
        _rows, seen, _trace = run_spied(s, Q18.sql({"quantity": FEW}))
        (prog, _args, shapes, types, growths, _grown), = seen
        keys.append(("frag", prog.sig, growths, shapes, types))
        (cached,) = [k for k in s._shard_cache.fragments if k[0] == "frag"]
        assert cached[:5] == keys[-1]
        passed.append(len(Q18.reference(data, {"quantity": FEW})))
    assert keys[0] == keys[1]
    # (the seeds were chosen so that the pads would have differed)
    assert (passed[0] - 1).bit_length() != (passed[1] - 1).bit_length(), passed


@pytest.mark.parametrize("quantity", [MANY, FEW, NONE], ids=["many", "few", "none"])
def test_q18_on_eight_parts_broadcasts_the_subquery(devices8, loads, quantity):
    """Shard-local groups would fail a HAVING that their sum passes: on
    several parts the subquery is answered as a statement of its own and
    enters replicated, as before."""
    catalog, data, oracle = loads[SEEDS[0]]
    one = session(catalog, devices8, 1).query(Q18.sql({"quantity": quantity}))
    s = session(catalog, devices8, 8)
    q0 = samples(FRAGMENT_SUBQUERIES)
    rows, seen, trace = run_spied(s, Q18.sql({"quantity": quantity}))
    check_rows(rows, data, oracle, quantity)
    assert rows == one
    outer = [prog for prog, *_ in seen if prog.broadcasts]
    assert len(outer) == 1 and outer[0].n_subquery == 0
    assert delta(FRAGMENT_SUBQUERIES, q0) == {("general_generic", "broadcast"): 1}
    assert "fragment.broadcast" in trace.self_us_by_name()


# -- other subqueries of the kind, on hand-made tables ------------------------

N_ORDERS = 400


def small_catalog(null_key: bool) -> Catalog:
    """`o` (400 orders in 5 classes) and `l` (their lines: a nullable
    order key, a DECIMAL quantity, an integer); with `null_key`, a few
    lines whose key is NULL (a group of their own)."""
    rng = np.random.default_rng(18)
    catalog = Catalog()
    s = Session(catalog=catalog)
    s.execute("use test")
    s.execute("create table o (ok bigint primary key, cls bigint, price decimal(15,2))")
    s.execute("create table l (lk bigint, q decimal(15,2), n bigint)")
    s.execute("insert into o values " + ", ".join(
        f"({k}, {k % 5}, {rng.integers(100, 99999) / 100:.2f})"
        for k in range(1, N_ORDERS + 1)))
    lines = [(int(rng.integers(1, N_ORDERS + 40)), int(rng.integers(1, 51)),
              int(rng.integers(0, 9))) for _ in range(1500)]
    if null_key:
        lines += [(None, 50, 1)] * 3
    s.execute("insert into l values " + ", ".join(
        f"({'null' if k is None else k}, {q}.00, {n})" for k, q, n in lines))
    return catalog


SUBQUERIES = {
    # (the statement, does the subquery compile into the program on one part)
    "in_sum": ("select cls, count(*), sum(price) from o where ok in "
               "(select lk from l group by lk having sum(q) > 120) "
               "group by cls order by cls", True),
    "not_in_sum": ("select cls, count(*), sum(price) from o where ok not in "
                   "(select lk from l group by lk having sum(q) > 120) "
                   "group by cls order by cls", True),
    # MIN is a segment op of the sort-reduce, not a running total
    "in_min": ("select cls, count(*), sum(price) from o where ok in "
               "(select lk from l group by lk having min(q) > 10 and count(*) > 1) "
               "group by cls order by cls", True),
    # AVG stays a broadcast on one part too: its state is a sum and a
    # count, and the division (decimal scale, then the count, in float64)
    # is the host finalize's; `_group_rows` emits no such value
    "in_avg": ("select cls, count(*), sum(price) from o where ok in "
               "(select lk from l group by lk having avg(q) > 30) "
               "group by cls order by cls", False),
    # a subquery with a filter under the GROUP BY and a select list
    "in_filtered": ("select cls, count(*) from o where ok in "
                    "(select lk from l where n < 5 group by lk having sum(n) >= 6) "
                    "group by cls order by cls", True),
}


@pytest.fixture(scope="module")
def small():
    out = {}
    for null_key in (False, True):
        catalog = small_catalog(null_key)
        out[null_key] = catalog, mirror_to_sqlite(catalog)
    return out


@pytest.mark.parametrize("n_parts", [1, 8])
@pytest.mark.parametrize("null_key", [False, True], ids=["no_null", "null_key"])
@pytest.mark.parametrize("name", sorted(SUBQUERIES))
def test_a_subquery_of_the_kind_gives_the_oracles_rows(devices8, small, name, null_key,
                                                       n_parts):
    """The parent's broadcast path is the in-program reference: eight
    parts take it, one part compiles the subquery in, and both give what
    sqlite gives. With a NULL key in `l` the NOT IN is empty (the build
    side's NULL is counted on the part, `anti` with `exists_sem` false)
    and the IN never matches the NULL group."""
    sql, inline = SUBQUERIES[name]
    catalog, oracle = small[null_key]
    s = session(catalog, devices8, n_parts)
    q0 = samples(FRAGMENT_SUBQUERIES)
    rows, seen, trace = run_spied(s, sql)
    want = oracle.execute(sql).fetchall()
    ok, msg = rows_equal(rows, want, ordered=True)
    assert ok, msg
    if name == "not_in_sum":
        assert (want == []) == null_key
    else:
        assert want
    paths = {path for (_kind, path), n in delta(FRAGMENT_SUBQUERIES, q0).items()}
    assert paths == ({"inline"} if inline and n_parts == 1 else {"broadcast"})
    assert ("fragment.broadcast" in trace.self_us_by_name()) == (paths == {"broadcast"})
    outer = seen[-1][0]
    assert (outer.n_subquery, len(outer.broadcasts)) == (
        (1, 0) if paths == {"inline"} else (0, 1))


def test_a_having_nothing_passes_and_an_empty_table(devices8, small):
    catalog, oracle = small[False]
    s = session(catalog, devices8, 1)
    for sql in (SUBQUERIES["in_sum"][0].replace("> 120", "> 100000"),
                SUBQUERIES["not_in_sum"][0].replace("> 120", "> 100000"),
                SUBQUERIES["in_sum"][0].replace("from l group", "from l where n > 99 group")):
        rows, seen, _trace = run_spied(s, sql)
        assert seen[-1][0].n_subquery == 1
        ok, msg = rows_equal(rows, oracle.execute(sql).fetchall(), ordered=True)
        assert ok, msg


# -- a root aggregate of many keys: hash order, split groups counted -----------

MANY_KEYS = ("select cls, ok % 7 as a, ok % 5 as b, ok % 3 as c, ok % 2 as d, count(*), "
             "sum(price) from o where ok in (select lk from l group by lk having sum(q) > 60) "
             "group by cls, ok % 7, ok % 5, ok % 3, ok % 2 order by 1, 2, 3, 4, 5")


@pytest.mark.parametrize("weak_hash", [False, True], ids=["hash64", "hash_of_2_bits"])
def test_five_group_keys_come_in_hash_order_and_stay_exact(devices8, small, monkeypatch,
                                                           weak_hash):
    """Past `TIE_BREAK_KEYS` keys the root's table on one part is not
    sorted with every key as a tie-break (a program of 5 keys compiled
    past 1,000 s on the chip) but by the hash alone, and says how many
    runs a collision split: 0 under the 64-bit hash, so the host converts
    the table as it is; under a hash of two bits nearly every group is
    split and the host merges by exact key — the same rows either way."""
    from tidb_tpu.executor import agg_device
    from tidb_tpu.executor.aggregate import HashAggExec

    assert agg_device.TIE_BREAK_KEYS == 3
    if weak_hash:
        real = agg_device._group_hash
        monkeypatch.setattr(agg_device, "_group_hash",
                            lambda kb, kv: real(kb, kv) & 3)
    merged = []
    real_merge = HashAggExec._merge_partials
    monkeypatch.setattr(HashAggExec, "_merge_partials",
                        lambda self, parts: merged.append(len(parts)) or real_merge(self, parts))
    catalog, oracle = small[False]
    rows, seen, _trace = run_spied(session(catalog, devices8, 1), MANY_KEYS)
    ok, msg = rows_equal(rows, oracle.execute(MANY_KEYS).fetchall(), ordered=True)
    assert ok, msg
    assert len(rows) > 100 and seen[-1][0].n_subquery == 1
    assert merged == ([1] if weak_hash else [])
    # three keys keep the tie-break sort (TPC-H Q3's program): no count in the table
    three = ("select cls, ok % 7, ok % 5, count(*) from o where ok in "
             "(select lk from l group by lk having sum(q) > 60) "
             "group by cls, ok % 7, ok % 5 order by 1, 2, 3")
    rows3, _seen, _trace = run_spied(session(catalog, devices8, 1), three)
    ok, msg = rows_equal(rows3, oracle.execute(three).fetchall(), ordered=True)
    assert ok, msg
    assert merged == ([1] if weak_hash else [])


@pytest.mark.parametrize("case", ["collisions", "one_key", "none"])
def test_the_count_of_split_runs(case):
    """`_sort_reduce(exact="count")` on its own: keys (1, 2) and (2, 1)
    under a hash that cannot tell them apart interleave in row order, so
    their groups hold several slots and every extra run is counted; one
    key sorts by its bits and is never split."""
    import jax.numpy as jnp

    from tidb_tpu.executor import agg_device

    a = jnp.asarray([1, 2, 1, 2, 1, 3], dtype=jnp.int64)
    b = jnp.asarray([2, 1, 2, 1, 2, 3], dtype=jnp.int64)
    ones = jnp.ones(6, dtype=jnp.bool_)
    payload = [jnp.ones(6, dtype=jnp.int64)]
    keys, valids = ([a], [ones]) if case == "one_key" else ([a, b], [ones, ones])
    with pytest.MonkeyPatch.context() as mp:
        if case == "collisions":
            mp.setattr(agg_device, "_group_hash", lambda kb, kv: kb[0] + kb[1])
        n, _k, _v, red, splits = agg_device._sort_reduce(
            keys, valids, ones, payload, ["sum"], exact="count")
    if case == "collisions":
        # (1,2) (2,1) (1,2) (2,1) (1,2) share hash 3: five runs for two groups
        assert int(n) == 6 and int(splits) == 4
    else:
        assert int(n) == 3 and int(splits) == 0
    assert int(jnp.sum(red[0])) == 6
    assert agg_device._sort_reduce(keys, valids, ones, payload, ["sum"])[4] is None


# -- the scopes ------------------------------------------------------------

@pytest.fixture(scope="module")
def q18_scopes(devices8, loads):
    """The scope (``op_name`` less the program's name) of every op in the
    lowered text of Q18's program: a lowering, nothing compiled."""
    catalog, _data, _oracle = loads[SEEDS[0]]
    _rows, seen, _trace = run_spied(session(catalog, devices8, 1),
                                    Q18.sql({"quantity": FEW}))
    (prog, args, _shapes, _types, growths, _), = seen
    text = prog.build_fn(growths).lower(*args).as_text(debug_info=True)
    return set(re.findall(r'"jit\(frag_general\)/([^"]*)"', text))


@pytest.mark.parametrize("scope", [
    "subq0/agg.partial/sort", "subq0/agg.partial/gather", "subq0/agg.partial/runs",
    "subq0/agg.partial/reduce", "subq0/agg.partial/keys", "subq0/having",
    "subq0/compact"])
def test_the_subquerys_ops_carry_its_scope(q18_scopes, scope):
    assert any(f"/{scope}/" in f"/{n}/" for n in q18_scopes), sorted(
        n for n in q18_scopes if "subq" in n)[:20]


def test_the_subquery_nests_in_no_join_and_the_semi_join_ranks_it(q18_scopes):
    """The subquery's ops run before the semi-join that reads them
    (join2: customer with orders is join0, lineitem's eager partial
    join1); the join ranks its rows by the merged sort like any sharded
    build side."""
    subq = [n for n in q18_scopes if re.search(r"(^|/)subq0(/|$)", n)]
    assert subq and not any(re.search(r"(^|/)join\d+/", n) for n in subq)
    assert any(n.startswith("join2/join.build") for n in q18_scopes)
    assert not any("subq1" in n for n in q18_scopes)
    # the root aggregate and the eager partial keep their own scopes
    assert any(n.startswith("agg.partial/sort") for n in q18_scopes)
    assert any(n.startswith("agg.eager/sort") for n in q18_scopes)
