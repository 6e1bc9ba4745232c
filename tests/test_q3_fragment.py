"""TPC-H Q3 whole through the general fragment compiler (PR 32): one
program and one launch a parameter set at the capacities the planner
chose — because a bulk load records of every column what
`scan_selectivity` needs, so that nobody guesses 0.25 a filter — with the
sqlite oracle's rows on one part and on four; each join's ops under its
own scope; FRAGMENT_JOINS counting the joins a launch holds by probe
path; no second plan variant of a statement that is one program; and
(PR 33) each join ranking its probe slots by ONE merged sort of both
sides, to the bit what the searched and the table paths give."""

import re

import numpy as np
import pytest

from tidb_tpu.parallel import make_mesh
from tidb_tpu.planner import feedback
from tidb_tpu.session import Session
from tidb_tpu.statistics import load_stats, table_stats
from tidb_tpu.storage.catalog import Catalog
from tidb_tpu.storage.table import ColumnInfo, TableSchema
from tidb_tpu.testutil import index_tpch_oracle, mirror_to_sqlite, rows_equal
from tidb_tpu.types import DATE, INT64, STRING, decimal_type
from tidb_tpu.utils.metrics import (
    FRAGMENT_COMPACTIONS,
    FRAGMENT_DISPATCH,
    FRAGMENT_JOINS,
    FRAGMENT_RETRY_TOTAL,
)

Q3 = ("select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue, "
      "o_orderdate, o_shippriority from customer, orders, lineitem "
      "where c_mktsegment = '{segment}' and c_custkey = o_custkey "
      "and l_orderkey = o_orderkey and o_orderdate < date '{date}' "
      "and l_shipdate > date '{date}' "
      "group by l_orderkey, o_orderdate, o_shippriority "
      "order by revenue desc, o_orderdate, l_orderkey limit 10")
# the benchmark's menu (benchmarks/traffic/q3.json)
PARAMS = [{"segment": "BUILDING", "date": "1995-03-15"},
          {"segment": "MACHINERY", "date": "1995-03-22"}]
Q18_INNER = ("select l_orderkey, sum(l_quantity) as q from lineitem "
             "group by l_orderkey having sum(l_quantity) > 300 order by l_orderkey")
STAGES = ["join.compact", "join.build", "join.probe", "join.expand", "join.gather"]


@pytest.fixture(scope="module")
def tiny_tpch():
    from tidb_tpu.storage.tpch import load_tpch

    catalog = Catalog()
    load_tpch(catalog, sf=0.05)
    return catalog, index_tpch_oracle(mirror_to_sqlite(catalog))


def session(catalog, devices, n_parts: int) -> Session:
    s = Session(catalog=catalog, mesh=make_mesh(devices=devices[:n_parts]))
    s.execute("use test")
    # a CPU mesh routes joins and generic aggregation to the host engine unless asked
    s.execute("set tidb_device_engine_mode = 'force'")
    # every statement here is its digest's first: the plan a fresh server gives
    feedback.STORE.clear()
    return s


def by_labels(counter, kind="general_generic") -> dict:
    return {tuple(v for k, v in sorted(labels.items()) if k != "kind"): n
            for labels, n in counter.samples() if labels.get("kind") == kind}


def delta(counter, before: dict) -> dict:
    return {k: n - before.get(k, 0) for k, n in by_labels(counter).items()
            if n - before.get(k, 0)}


def joins_by_probe() -> dict:
    """FRAGMENT_JOINS summed over the fragment kinds, by probe path."""
    out = {}
    for labels, n in FRAGMENT_JOINS.samples():
        out[labels["probe"]] = out.get(labels["probe"], 0) + n
    return out


def run_spied(s: Session, sql: str) -> tuple:
    """(rows, [(program, arguments, growths in, growths out)]) of every
    `_dispatch_retry` the statement made."""
    from tidb_tpu.parallel import executor as pe

    real, seen = pe.DistFragmentExec._dispatch_retry, []

    def spy(self, prog, args, shapes_sig, types_sig, growths, *span):
        out, grown = real(self, prog, args, shapes_sig, types_sig, growths, *span)
        seen.append((prog, args, growths, grown))
        return out, grown

    pe.DistFragmentExec._dispatch_retry = spy
    try:
        return s.query(sql), seen
    finally:
        pe.DistFragmentExec._dispatch_retry = real


@pytest.mark.parametrize("params", PARAMS, ids=lambda p: p["segment"])
def test_q3_is_one_program_and_one_launch_at_its_default_growths(devices8, tiny_tpch,
                                                                 params):
    """The parent compiled and launched it twice: `o_orderdate < DATE` and
    `l_shipdate > DATE` keep 52% and 51% of their rows, twice the guessed
    0.25 fell short by a hair, and the growths ended (1, 2, 1, 1, 1, 2, 1,
    1, 1, 1)."""
    catalog, oracle = tiny_tpch
    s = session(catalog, devices8, 1)
    sql = Q3.format(**params)
    l0, r0 = by_labels(FRAGMENT_DISPATCH), by_labels(FRAGMENT_RETRY_TOTAL)
    rows, seen = run_spied(s, sql)
    (prog, _args, growths, grown), = seen
    assert prog.n_join == 2 and prog.n_exchange == 0
    assert growths == grown == prog.growth_defaults
    assert delta(FRAGMENT_DISPATCH, l0) == {(): 1}
    assert delta(FRAGMENT_RETRY_TOTAL, r0) == {}
    want = oracle.execute(sql.replace("date '", "'")).fetchall()
    assert len(want) == 10
    ok, msg = rows_equal(rows, want, ordered=True)
    assert ok, msg


def test_q3_on_four_parts_gives_the_same_rows(devices8, tiny_tpch):
    catalog, oracle = tiny_tpch
    sql = Q3.format(**PARAMS[0])
    one = session(catalog, devices8, 1).query(sql)
    rows, seen = run_spied(session(catalog, devices8, 4), sql)
    assert rows == one
    (prog, *_), = seen
    # both sides of both joins and the groups are repartitioned there
    assert prog.n_join == 2 and prog.n_exchange >= 4
    ok, msg = rows_equal(rows, oracle.execute(sql.replace("date '", "'")).fetchall(),
                         ordered=True)
    assert ok, msg


def test_a_launch_counts_its_joins_by_probe_path(devices8, tiny_tpch):
    """FRAGMENT_JOINS{kind, probe}: Q3's program holds two joins; under
    the default both rank by the merged sort, on every platform and at
    either build size; forced, `off` searches and `xla` probes the table
    (customer's build is under its half load, join0's result here too);
    a fragment without a join adds nothing."""
    catalog, _ = tiny_tpch
    for mode, path in (("off", "search"), ("xla", "table")):
        s = session(catalog, devices8, 1)
        s.execute(f"set tidb_tpu_join_probe_mode = '{mode}'")
        j0 = by_labels(FRAGMENT_JOINS)
        s.query(Q3.format(**PARAMS[0]))
        assert delta(FRAGMENT_JOINS, j0) == {(path,): 2}
    s = session(catalog, devices8, 1)
    j0 = by_labels(FRAGMENT_JOINS)
    _rows, seen = run_spied(s, Q3.format(**PARAMS[0]))
    assert delta(FRAGMENT_JOINS, j0) == {("merge",): 2}
    j0 = by_labels(FRAGMENT_JOINS)
    s.query(Q3.format(**PARAMS[0]))  # the program comes from the cache, its joins with it
    assert sum(delta(FRAGMENT_JOINS, j0).values()) == 2
    j0, l0 = by_labels(FRAGMENT_JOINS), by_labels(FRAGMENT_DISPATCH)
    _rows, seen = run_spied(s, Q18_INNER)
    assert seen[0][0].n_join == 0
    assert delta(FRAGMENT_DISPATCH, l0) == {(): 1} and delta(FRAGMENT_JOINS, j0) == {}


@pytest.mark.parametrize("n_parts,knobs", [(1, [3]), (4, [4, 5, 10])],
                         ids=["1x1", "1x4"])
def test_a_launch_counts_the_compactions_its_trace_took(devices8, tiny_tpch,
                                                        n_parts, knobs):
    """FRAGMENT_COMPACTIONS{kind} (PR 36): a launch adds the `_compact`s
    its program's trace took, which is static per built program (a
    target at or over its chunk's capacity compiles nothing). The
    program's first knob is lineitem's eager partial's table (join1's
    probe side, whose producer is compiled first); join0's follow. On one
    part, at this scale as at SF1, join0's build side (customer under its
    filter, knob 3) is compacted and join1's probe side is not: the eager
    table holds the key's estimated groups, under twice their estimate; a
    part of four holds a quarter of the rows against the same floors,
    and compacts join0's build side and output (4, 5) and join1's output
    (10). The program from the fragment cache brings its count with it;
    Q18's inner aggregate over an unfiltered scan compacts nothing."""
    catalog, _ = tiny_tpch
    s = session(catalog, devices8, n_parts)
    c0 = by_labels(FRAGMENT_COMPACTIONS)
    _rows, seen = run_spied(s, Q3.format(**PARAMS[0]))
    (prog, _args, growths, grown), = seen
    assert growths == grown == prog.growth_defaults
    (fn,) = [f for k, f in s._shard_cache.fragments.items() if k[0] == "frag"]
    assert fn.compactions == knobs
    assert all(prog.growth_kinds[k] == "compact" for k in knobs)
    assert delta(FRAGMENT_COMPACTIONS, c0) == {(): len(knobs)}
    c0 = by_labels(FRAGMENT_COMPACTIONS)
    s.query(Q3.format(**PARAMS[0]))  # from the fragment cache: not traced again
    assert delta(FRAGMENT_COMPACTIONS, c0) == {(): len(knobs)}
    c0, l0 = by_labels(FRAGMENT_COMPACTIONS), by_labels(FRAGMENT_DISPATCH)
    s.query(Q18_INNER)
    assert delta(FRAGMENT_DISPATCH, l0) == {(): 1}
    assert delta(FRAGMENT_COMPACTIONS, c0) == {}


def test_the_probe_says_which_path_it_traced_the_table_under_its_half_load_only():
    """`probe_for_join` hands back, with its ranges, the path it traced
    for them (static: the build's slots and the strategy); only shapes
    are looked at here, nothing is computed."""
    import jax

    from tidb_tpu.ops import hash_probe

    def path(n_build, mode):
        took = []

        def probe(sh, pr):
            lo, hi, p = hash_probe.probe_for_join(sh, pr, mode)
            took.append(p)
            return lo, hi

        i64 = jax.ShapeDtypeStruct
        lo, hi = jax.eval_shape(probe, i64((n_build,), np.int64), i64((8,), np.int64))
        assert lo.shape == hi.shape == (8,)
        return took[0]

    assert path(60_000, "xla") == "table"
    assert path(hash_probe.MAX_CAPACITY // 2, "xla") == "table"
    assert path(hash_probe.MAX_CAPACITY // 2 + 1, "xla") == "search"
    assert path(1_500_000, "xla") == "search"
    assert path(60_000, "off") == "search"


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_build_sort_orders_live_before_dead_and_rows_by_number(seed):
    """`sort_build_hashes` sorts ONE packed 32-bit operand beside the
    hash (the row number, the dead flag its top bit) and need not be
    stable: the permutation is the stable sort's by (hash, dead)."""
    import jax.numpy as jnp

    from tidb_tpu.ops.join_kernels import sort_build_hashes

    rng = np.random.default_rng(seed)
    n = 5000
    h = rng.integers(-40, 40, n) * (1 << 40)  # many duplicates, both signs
    live = rng.random(n) < 0.7
    sh, cvi, order = sort_build_hashes(jnp.asarray(h), jnp.asarray(live))
    want = np.lexsort((np.arange(n), ~live, h))
    assert order.dtype == jnp.int32 and np.array_equal(np.asarray(order), want)
    assert np.array_equal(np.asarray(sh), h[want])
    assert np.array_equal(np.asarray(cvi), np.concatenate([[0], np.cumsum(live[want])]))


I64 = np.iinfo(np.int64)


def _rank_case(seed):
    """(build hashes, live, probe hashes, ok): duplicates on both sides,
    dead rows on both sides, hash 0 and both ends of int64."""
    rng = np.random.default_rng(seed)
    nb, n_p = int(rng.integers(2, 6000)), int(rng.integers(2, 9000))
    pool = np.concatenate([rng.integers(-40, 40, 60) * (1 << 40),
                           [0, I64.min, I64.max]])
    return (rng.choice(pool, nb), rng.random(nb) < 0.7,
            rng.choice(pool, n_p), rng.random(n_p) < 0.8)


def _one_build_slot(seed):
    bh, bl, ph, pk = _rank_case(seed)
    return bh[:1], np.array([seed % 2 == 0]), np.where(pk, bh[0], ph), pk


def _dead_probe_side(seed):
    bh, bl, ph, pk = _rank_case(seed)
    return bh, bl, ph, np.zeros_like(pk)


@pytest.mark.parametrize("case,seed", [
    (_rank_case, 0), (_rank_case, 1), (_rank_case, 2), (_rank_case, 3),
    (_one_build_slot, 4), (_one_build_slot, 5), (_dead_probe_side, 6)],
    ids=lambda v: v.__name__.strip("_") if callable(v) else str(v))
def test_the_merged_rank_is_the_searched_one_to_the_bit(case, seed):
    """`merged_hash_ranges` against plain numpy AND against the pair it
    stands in for (`sort_build_hashes` + the binary search): the build
    rows of the merged order in their sequence ARE `order`; `cnt` is
    equal at every probe slot; wherever it is not 0 the rows named are
    the same in the same order (`start` is a place in the merged order
    where `lo` is one in the build's, and means nothing under cnt 0)."""
    import jax
    import jax.numpy as jnp

    from tidb_tpu.ops.join_kernels import (
        merged_hash_ranges,
        probe_hash_ranges,
        sort_build_hashes,
    )

    bh, bl, ph, pk = case(seed)
    dev = [jnp.asarray(a) for a in (bh, bl, ph, pk)]
    start, cnt, slot = (np.asarray(a) for a in jax.jit(merged_hash_ranges)(*dev))
    assert start.dtype == cnt.dtype == slot.dtype == np.int32
    sh, cvi, order = sort_build_hashes(dev[0], dev[1])
    lo, cnt_searched, path = probe_hash_ranges(sh, cvi, dev[2], dev[3], mode="off")
    assert path == "search"
    want_order = np.lexsort((np.arange(len(bh)), ~bl, bh))
    assert np.array_equal(slot[slot < len(bh)], want_order)
    assert np.array_equal(np.asarray(order), want_order)
    want_cnt = np.array([np.sum(bl & (bh == h)) if ok else 0 for h, ok in zip(ph, pk)])
    assert np.array_equal(cnt, want_cnt) and np.array_equal(np.asarray(cnt_searched), want_cnt)
    if case is _rank_case:
        assert (want_cnt > 1).any() and (want_cnt == 0).any()
    lo = np.asarray(lo)
    for j in np.nonzero(cnt)[0]:
        rows = slot[start[j]:start[j] + cnt[j]]
        assert np.array_equal(rows, np.nonzero(bl & (bh == ph[j]))[0])
        assert np.array_equal(rows, want_order[lo[j]:lo[j] + cnt[j]])


def test_the_merged_rank_refuses_a_shard_of_2_30_slots_when_traced():
    """The 2-bit tag sits above a 30-bit slot number in the sort's second
    key: one slot more and the fragment is refused before anything runs
    (shapes only here; one v5e holds no such shard)."""
    import jax

    from tidb_tpu.ops.join_kernels import merged_hash_ranges

    def shapes(n_build, n_probe):
        sds = jax.ShapeDtypeStruct
        return jax.eval_shape(
            merged_hash_ranges, sds((n_build,), np.int64), sds((n_build,), np.bool_),
            sds((n_probe,), np.int64), sds((n_probe,), np.bool_))

    start, cnt, slot = shapes(1 << 29, (1 << 29) - 1)
    assert start.shape == cnt.shape == ((1 << 29) - 1,) and slot.shape == ((1 << 30) - 1,)
    with pytest.raises(ValueError, match="1073741824 build\\+probe slots"):
        shapes(1 << 29, 1 << 29)


# one statement of each kind of join the general fragment compiles, the
# build side a scan under a filter
JOINS = {
    "inner_q3": Q3.format(**PARAMS[0]),
    "left": "select o_orderpriority, count(l_orderkey), count(*) from orders "
            "left join lineitem on o_orderkey = l_orderkey and l_quantity > 45 "
            "group by o_orderpriority order by o_orderpriority",
    "semi": "select o_orderpriority, count(*) from orders where exists "
            "(select 1 from lineitem where l_orderkey = o_orderkey and l_quantity > 49) "
            "group by o_orderpriority order by o_orderpriority",
    "anti": "select o_orderpriority, count(*) from orders where not exists "
            "(select 1 from lineitem where l_orderkey = o_orderkey and l_quantity > 10) "
            "group by o_orderpriority order by o_orderpriority",
    "not_in": "select o_orderpriority, count(*) from orders where o_custkey not in "
              "(select c_custkey from customer where c_acctbal > 0) "
              "group by o_orderpriority order by o_orderpriority",
}


@pytest.mark.parametrize("n_parts", [1, 8])
@pytest.mark.parametrize("kind", sorted(JOINS))
def test_every_kind_of_join_gives_the_same_rows_under_each_probe_mode(
        devices8, tiny_tpch, kind, n_parts):
    """The default ranks by the merged sort; `off` (the binary search) and
    `xla` (the forced table) are the in-program references. All three
    read the same `lo` and `cnt`, so an inner, a left, a semi, an anti and
    a NOT IN join give the oracle's rows under each, on one part and
    after the exchange on eight (the merged rank then takes the received
    slots, dead ones included)."""
    catalog, oracle = tiny_tpch
    sql = JOINS[kind]
    rows, probes = {}, {}
    for mode in ("auto", "off", "xla"):
        s = session(catalog, devices8, n_parts)
        s.execute(f"set tidb_tpu_join_probe_mode = '{mode}'")
        j0 = joins_by_probe()
        rows[mode], seen = run_spied(s, sql)
        assert seen and all(prog.n_join >= 1 for prog, *_ in seen), kind
        probes[mode] = {p for p, n in joins_by_probe().items() if n != j0.get(p, 0)}
    assert probes["auto"] == {"merge"} and probes["off"] == {"search"}
    assert "merge" not in probes["xla"]
    assert rows["auto"] == rows["off"] == rows["xla"]
    ok, msg = rows_equal(rows["auto"], oracle.execute(sql.replace("date '", "'")).fetchall(),
                         ordered=True)
    assert ok, msg


@pytest.mark.parametrize("a,b,base", [
    # customers of one segment at SF1 under two seeds; twice each
    (29761, 30385, 61440),
    # lineitem's partial groups: the key's sketch, the same for every seed
    (1492488.89, 1492488.89, 3145728),
    (5, 31, 64), (33, 36, 72),
])
def test_a_guessed_capacity_keeps_four_leading_bits(a, b, base):
    """A capacity is a shape and a shape is a compile: two loads of one
    deployment whose counts differ by a percent (the benchmark's seeds)
    share one program. A group table a quarter over its key's distinct
    count keeps its slots to the slot."""
    from tidb_tpu.parallel.fragment import _Compiler

    c = _Compiler(1)
    assert [c._compact_knob(a)[1], c._compact_knob(b)[1]] == [base, base]
    assert base >= 2 * max(a, b) and base <= 2 * max(a, b) * 1.125 + 64
    # q18agg's table
    assert c._compact_knob(1492488.89, c.NDV_HEADROOM, rounded=False)[1] == 1865612
    assert c.sig == [f"cap0:{base}", f"cap1:{base}", "cap2:1865612"]
    four = _Compiler(4)
    assert four._compact_knob(29761)[1] == 15360  # a part's share, then the bits


@pytest.mark.parametrize("n_parts,slots", [(1, 1865612), (8, 233202)])
def test_an_eager_table_at_sf1s_sketch_takes_its_estimate(monkeypatch, n_parts,
                                                          slots):
    """The eager partial's group table is sized by the rule of the root's
    (`_table_sizer`): lineitem's 1,492,489 sketched order keys a quarter
    over, to the slot, a part's share of them — not one slot for each of
    the part's 6,001,215 / n rows, which a join then compacted to twice
    the estimate (3,145,728 on one part). Under its chunk's capacity the
    table reports its groups' factor under its knob."""
    from types import SimpleNamespace

    from tidb_tpu.parallel import fragment
    from tidb_tpu.parallel.fragment import _Compiler

    # the overflow's max across the parts, outside a program
    monkeypatch.setattr(fragment, "pmax", lambda x, _axes: x)
    c = _Compiler(n_parts)
    to_table = c._table_sizer(SimpleNamespace(est_rows=1492488.89,
                                              est_from_ndv=True))
    assert c.sig == [f"cap0:{slots}"] and c.growth_kinds == ["compact"]
    asked = []

    def partial(chunk, slots):
        asked.append(slots)
        return {"n": np.int64(1_500_000 // n_parts)}

    ovfs = []
    rows = SimpleNamespace(capacity=-(-6_001_215 // n_parts))
    _table, sized = to_table(partial, rows, (1.0,), ovfs)
    assert asked == [slots] and sized and [k for k, _ in ovfs] == [0]
    # a table the estimate would not shrink keeps one slot a row
    _table, sized = to_table(partial, SimpleNamespace(capacity=slots), (1.0,), ovfs)
    assert not sized and len(ovfs) == 1


def eager_slots(monkeypatch) -> list:
    """The slots of every eager partial's group table traced from here
    on, as `_group_rows` receives them."""
    from tidb_tpu.parallel.fragment import _Compiler

    real, seen = _Compiler._group_rows, []

    def spy(agg, t):
        seen.append(t["k0.d"].shape[0])
        return real(agg, t)

    monkeypatch.setattr(_Compiler, "_group_rows", staticmethod(spy))
    return seen


@pytest.mark.parametrize("n_parts", [1, 8])
def test_the_eager_table_is_born_at_the_keys_estimated_groups(
        devices8, tiny_tpch, monkeypatch, n_parts):
    """FRAGMENT_EAGER_PARTIALS{kind, sized}: Q3's program holds one eager
    partial, and its table takes the estimate — a quarter over the
    sketched order keys, a part's share — not one slot a lineitem row. No knob grows on the cold statement or the warm one,
    on one part (one compaction a launch: customer's) or on eight, whose
    parts each hold a block of lineitem's rows; the rows are sqlite's."""
    from tidb_tpu.parallel.fragment import _Compiler
    from tidb_tpu.statistics import column_ndv
    from tidb_tpu.utils.metrics import FRAGMENT_EAGER_PARTIALS

    catalog, oracle = tiny_tpch
    sql = Q3.format(**PARAMS[1])
    want = oracle.execute(sql.replace("date '", "'")).fetchall()
    s = session(catalog, devices8, n_parts)
    slots = eager_slots(monkeypatch)
    ndv = column_ndv(catalog.table("test", "lineitem"), "l_orderkey")
    for _ in range(2):  # cold, then from the fragment cache
        e0, r0, c0 = (by_labels(c) for c in (
            FRAGMENT_EAGER_PARTIALS, FRAGMENT_RETRY_TOTAL, FRAGMENT_COMPACTIONS))
        rows, seen = run_spied(s, sql)
        (prog, _args, growths, grown), = seen
        assert growths == grown == prog.growth_defaults
        assert delta(FRAGMENT_EAGER_PARTIALS, e0) == {("estimate",): 1}
        assert delta(FRAGMENT_RETRY_TOTAL, r0) == {}
        if n_parts == 1:
            assert delta(FRAGMENT_COMPACTIONS, c0) == {(): 1}
        ok, msg = rows_equal(rows, want, ordered=True)
        assert ok, msg
    assert slots == [int(np.ceil(_Compiler.NDV_HEADROOM * ndv / n_parts))]
    lineitem = catalog.table("test", "lineitem").n
    assert slots[0] < lineitem / n_parts / 3


def test_an_eager_table_estimated_short_grows_once_and_answers_right(
        devices8, tiny_tpch, monkeypatch):
    """A sketch that reads a third of lineitem's order keys sizes the
    eager table a third too small: its groups overflow it, the launch
    reports the factor under the table's knob, and the statement is
    launched once more with that knob grown — no group dropped. The
    grown knob is kept: the next statement launches once."""
    import tidb_tpu.statistics as stats

    catalog, oracle = tiny_tpch
    real = stats.column_ndv

    def short(table, col_name):
        ndv = real(table, col_name)
        return ndv / 3 if col_name == "l_orderkey" and ndv else ndv

    monkeypatch.setattr(stats, "column_ndv", short)
    sql = Q3.format(**PARAMS[0])
    want = oracle.execute(sql.replace("date '", "'")).fetchall()
    s = session(catalog, devices8, 1)
    slots = eager_slots(monkeypatch)
    r0, l0 = by_labels(FRAGMENT_RETRY_TOTAL), by_labels(FRAGMENT_DISPATCH)
    rows, seen = run_spied(s, sql)
    (prog, _args, growths, grown), = seen
    ok, msg = rows_equal(rows, want, ordered=True)
    assert ok, msg
    assert delta(FRAGMENT_DISPATCH, l0) == {(): 2}
    assert delta(FRAGMENT_RETRY_TOTAL, r0) == {("compact",): 1}
    # the eager table's knob: its base is signed just before the partial
    k = int(re.search(r"(?:^|\|)cap(\d+):\d+\|eagg:", prog.sig).group(1))
    assert prog.growth_kinds[k] == "compact"
    assert growths[k] == 1.0 and grown[k] > 1.0
    assert len(slots) == 2 and slots[1] == int(np.ceil(grown[k] * slots[0]))
    (groups,), = oracle.execute(
        "select count(distinct l_orderkey) from lineitem where l_shipdate > "
        f"'{PARAMS[0]['date']}'").fetchall()
    assert slots[0] < groups <= slots[1]
    r0, l0 = by_labels(FRAGMENT_RETRY_TOTAL), by_labels(FRAGMENT_DISPATCH)
    rows, seen = run_spied(s, sql)
    assert seen[0][2] == grown and delta(FRAGMENT_DISPATCH, l0) == {(): 1}
    assert delta(FRAGMENT_RETRY_TOTAL, r0) == {}
    assert rows_equal(rows, want, ordered=True)[0]


@pytest.fixture(scope="module")
def q3_scopes(devices8, tiny_tpch):
    """The scope (``op_name`` less the program's name) of every op in the
    lowered text of Q3's program: a lowering, nothing compiled."""
    catalog, _ = tiny_tpch
    _rows, seen = run_spied(session(catalog, devices8, 1), Q3.format(**PARAMS[0]))
    (prog, args, growths, _), = seen
    text = prog.build_fn(growths).lower(*args).as_text(debug_info=True)
    return set(re.findall(r'"jit\(frag_general\)/([^"]*)"', text))


@pytest.mark.parametrize("join", ["join0", "join1"])
@pytest.mark.parametrize("stage", STAGES)
def test_each_stage_of_each_join_has_its_scope(q3_scopes, join, stage):
    """join0 is the join whose ops run first (orders with customer),
    join1 the one over its result (lineitem). join0 compacts customer;
    join1 compacts nothing on one part, at this scale as at SF1: its
    probe side is lineitem's eager table, which holds the key's estimated
    groups, under twice their estimate, and its build side and output
    reach their capacities."""
    mine = [n for n in q3_scopes if f"/{join}/{stage}/" in f"/{n}/"]
    if (join, stage) == ("join1", "join.compact"):
        assert not mine, mine
        return
    assert mine, sorted(n for n in q3_scopes if join in n)[:20]
    # a join's scope never nests in the other's
    other = "join1" if join == "join0" else "join0"
    assert not any(f"/{other}/" in f"/{n}/" for n in mine)


def test_the_eager_partial_under_the_joins_has_its_scope(q3_scopes):
    """Lineitem's rows are grouped by order key before they are joined
    (the planner's eager aggregation): that sort-reduce's ops are told
    from the root aggregate's (`agg.partial`) by `agg.eager`."""
    assert any(n.startswith("agg.eager/sort") for n in q3_scopes)
    assert any(n.startswith("agg.partial/sort") for n in q3_scopes)
    assert not any(re.match(r"(sort|gather|runs|reduce|keys)(/|$)", n) for n in q3_scopes)


def test_no_op_of_a_join_is_outside_the_five_stages_and_the_keys(q3_scopes):
    loose = {n for n in q3_scopes if re.search(r"(^|/)join\d+/", n)
             and not re.search(r"(^|/)join\d+/join\.(compact|build|probe|expand|gather)(/|$)", n)}
    # what is left directly under join<j>: the key bits and their validity
    assert loose and all(re.search(r"(^|/)join\d+/[^/]+$", n) for n in loose), sorted(loose)[:10]


# -- what a bulk load records, and what the planner reads of it -------------

N = 20_000
Q = np.random.default_rng(6).integers(1, 51, N) * 100  # 1..50 at scale 2
POOL = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]


@pytest.fixture()
def loaded():
    """A bulk-loaded, never-analysed table: a date column over six years
    and a five-value dictionary column, one value taking 60% of the rows."""
    rng = np.random.default_rng(5)
    catalog = Catalog()
    cols = [ColumnInfo("k", INT64, not_null=True), ColumnInfo("d", DATE, not_null=True),
            ColumnInfo("seg", STRING, not_null=True), ColumnInfo("v", INT64),
            ColumnInfo("q", decimal_type(15, 2))]
    table = catalog.create_table("test", TableSchema("t", cols, primary_key=["k"]))
    d = rng.integers(8035, 10290, N)
    seg = rng.choice(5, N, p=[0.1, 0.6, 0.1, 0.1, 0.1])
    table.ingest_encoded({"k": np.arange(N), "d": d, "seg": seg,
                          "v": rng.integers(0, 100, N),
                          "q": Q}, {"seg": POOL})
    s = Session(catalog=catalog)
    s.execute("use test")
    return s, table, d, seg


def est_rows(s: Session, where: str) -> float:
    for row in s.query(f"explain select count(*) from t where {where}"):
        if "TableFullScan" in row[0]:
            return float(row[0].split()[1])
    raise AssertionError("no scan in the plan")


@pytest.mark.parametrize("where,truth", [
    ("d < date '1995-03-15'", lambda d, seg: (d < 9204).mean()),
    ("d > date '1995-03-15'", lambda d, seg: (d > 9204).mean()),
    ("d >= date '1997-01-01'", lambda d, seg: (d >= 9862).mean()),
    ("seg = 'BUILDING'", lambda d, seg: (seg == 1).mean()),
    ("seg = 'MACHINERY'", lambda d, seg: (seg == 4).mean()),
    ("seg <> 'BUILDING'", lambda d, seg: (seg != 1).mean()),
    ("d < date '1995-03-15' and seg = 'BUILDING'",
     lambda d, seg: ((d < 9204) & (seg == 1)).mean()),
    # an INT literal against a DECIMAL column: read at the column's scale
    ("q < 10", lambda d, seg: (Q < 1000).mean()),
    ("q >= 24.5", lambda d, seg: (Q >= 2450).mean()),
    ("q < 1000", lambda d, seg: 1.0),
])
def test_a_bulk_loads_record_estimates_a_filter_from_the_data(loaded, where, truth):
    s, table, d, seg = loaded
    assert table_stats(table) is None  # never analysed
    assert abs(est_rows(s, where) / N - truth(d, seg)) < 0.05


def test_the_record_holds_bounds_null_counts_and_code_counts(loaded):
    _s, table, d, seg = loaded
    rec = load_stats(table)
    assert rec.n_rows == N and rec.version == table.version
    assert (rec.cols["d"].min, rec.cols["d"].max) == (float(d.min()), float(d.max()))
    assert rec.cols["d"].ndv is None and rec.cols["d"].null_count == 0
    assert abs(rec.cols["k"].ndv - N) / N < 0.1  # the key's sketch
    assert rec.cols["seg"].mcv == {POOL[c]: int(n) for c, n in
                                   enumerate(np.bincount(seg, minlength=5))}
    assert rec.cols["v"].null_count == 0


@pytest.mark.parametrize("write", [
    "insert into t values (20001, '1990-01-01', 'BUILDING', 1, 2.00)",
    "delete from t where k = 7",
    "update t set d = '2001-01-01' where k = 7",
])
def test_a_write_makes_the_record_stale_and_the_guess_returns(loaded, write):
    """A bound that no longer holds must not shrink an estimate: after
    any write the planner reads what it read before this record existed."""
    s, table, _d, _seg = loaded
    assert est_rows(s, "d < date '1993-01-01'") / N < 0.2
    # (a session's first write to a never-analysed table analyses it)
    s.execute("set tidb_enable_auto_analyze = 0")
    s.execute(write)
    assert load_stats(table) is None and table_stats(table) is None
    n = table.live_rows
    assert est_rows(s, "d < date '1993-01-01'") == pytest.approx(0.25 * n, rel=1e-3)
    assert est_rows(s, "seg = 'BUILDING'") == pytest.approx(0.25 * n, rel=1e-3)


def test_analyze_takes_over_and_the_plan_cache_key_is_untouched(loaded):
    s, table, d, _seg = loaded
    assert getattr(table, "stats", None) is None  # the load stores nothing where the plan cache looks
    s.execute("analyze table t")
    assert table_stats(table) is table.stats is not None
    assert abs(est_rows(s, "d < date '1995-03-15'") / N - (d < 9204).mean()) < 0.05


# -- a statement that is one compiled program keeps one plan variant ---------

def test_no_second_variant_of_a_fragment_program_is_explored(devices8, tiny_tpch):
    """Q3's default plan carries an eager partial (lineitem by order key
    under the joins). The push-vs-no-push measurement would explore the
    other variant from a digest's second execution on — a second program
    of the same statement, on the chip minutes of compiling in somebody's
    statement, once more per set of literals. `planner/feedback` refuses:
    the default plan ran as one general fragment, so the plan stands."""
    catalog, _ = tiny_tpch
    sql = Q3.format(**PARAMS[0])
    s = session(catalog, devices8, 1)
    apd, programs = [], set()
    for _ in range(3):
        _rows, seen = run_spied(s, sql)
        apd.append(s._fb_last_apd)
        programs.add(seen[0][0].sig)
    assert apd == [True, True, True] and len(programs) == 1
    (digest,) = [d for d in feedback.STORE.stats_dict()["digests"]]
    (variant,) = digest["variants"]
    assert variant["eager_partial"] and variant["execs"] == 3
    assert feedback.STORE.apd_decision(digest["digest"]) is None


def test_the_store_explores_what_is_not_a_fragment_program():
    """The same protocol, synthetically: an eager partial alone is
    explored (the host engine's tiers, as before); one that ran as a
    general fragment is not."""
    st = feedback.PlanFeedbackStore()
    for digest, fragment, want in (("host", False, False), ("frag", True, None)):
        obs = feedback.Observation()
        obs.eager_partial, obs.fragment_program, obs.latency_s = True, fragment, 0.1
        st.record(digest, "p-push", True, obs)
        assert st.apd_decision(digest) is want
