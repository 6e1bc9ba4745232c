"""The mesh tier's exchange (parallel/distsql.py `repartition_by_key`)
and the rule that a mesh of ONE part exchanges nothing: the rows come
back as they were given, the programs hold no exchange, the generic
aggregate's partial table is its final table (duplicate-free also where
several keys' hashes collide), and FRAGMENT_EXCHANGE_STEPS says at every
launch how many exchange steps the launched program holds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tidb_tpu.executor import agg_device
from tidb_tpu.parallel import executor as pe
from tidb_tpu.parallel import make_mesh
from tidb_tpu.parallel.distsql import _SPEC, exchange_steps, repartition_by_key
from tidb_tpu.session import Session
from tidb_tpu.utils.metrics import (
    FRAGMENT_DISPATCH,
    FRAGMENT_EXCHANGE_STEPS,
    FRAGMENT_RETRY_TOTAL,
)

I64 = np.iinfo(np.int64)
CASES = ["null_keys", "dead_rows", "key_0_beside_dead_slots",
         "int64_min_and_max", "nothing_live"]


def _rows(case: str, n: int = 64):
    """(key, key_valid, sel, payload) of `n` slots for one kind of case."""
    rng = np.random.default_rng(len(case))
    key = rng.integers(-50, 50, n)
    valid = np.ones(n, bool)
    sel = np.ones(n, bool)
    if case == "null_keys":
        valid = rng.random(n) < 0.6
    elif case == "dead_rows":
        sel = rng.random(n) < 0.5
    elif case == "key_0_beside_dead_slots":
        key = np.where(rng.random(n) < 0.5, 0, key)
        sel = rng.random(n) < 0.5
        assert (key[sel] == 0).any() and (key[~sel] == 0).any()
    elif case == "int64_min_and_max":
        key = rng.choice([I64.min, I64.max, I64.min + 1, I64.max - 1, 0], n)
        valid = rng.random(n) < 0.8
        sel = rng.random(n) < 0.8
    elif case == "nothing_live":
        sel = np.zeros(n, bool)
    return (key.astype(np.int64), valid, sel,
            {"a": np.arange(n, dtype=np.int64), "b": rng.random(n) < 0.5})


@pytest.mark.parametrize("case", CASES)
def test_one_part_hands_its_rows_on(case):
    """`n_parts == 1`: the arrays and the key as given, at [R] slots (not
    growth * R), sel & key_valid (a NULL key never joins), overflow 0 —
    and nothing of an exchange in the program, which needs no mesh."""
    key, valid, sel, arrays = _rows(case)
    fn = jax.jit(lambda a, s, k, v: repartition_by_key(a, s, k, v, 1, 2.0))
    out, out_sel, out_key, ovf = fn(arrays, sel, key, valid)
    assert set(out) == set(arrays)
    for name, a in arrays.items():
        assert out[name].dtype == a.dtype
        np.testing.assert_array_equal(np.asarray(out[name]), a)
    np.testing.assert_array_equal(np.asarray(out_sel), sel & valid)
    np.testing.assert_array_equal(np.asarray(out_key), key)
    assert int(ovf) == 0 and ovf.shape == ()
    text = fn.lower(arrays, sel, key, valid).as_text()
    for op in ("sort", "scatter", "all_to_all", "gather", "while"):
        assert op not in text, op
    # traced alone, what comes back IS what went in
    same, _, same_key, _ = repartition_by_key(arrays, sel, key, valid, 1)
    assert same is arrays and same_key is key


@pytest.mark.parametrize("n_parts", [1, 4, 8])
@pytest.mark.parametrize("case", CASES)
def test_every_live_row_arrives_once_on_the_part_that_owns_its_key(
        devices8, case, n_parts):
    """What one part and many parts share: the live rows with a key come
    out once each, payload beside key, and equal keys on one part."""
    mesh = make_mesh(devices=devices8[:n_parts])
    key, valid, sel, arrays = _rows(case, 64 * n_parts)
    growth = 2.0 * n_parts  # a bucket holds a whole part twice: no skew overflows

    def per_part(a, s, k, v):
        out, out_sel, out_key, ovf = repartition_by_key(
            {n: x[0] for n, x in a.items()}, s[0], k[0], v[0], n_parts, growth)
        return ({n: x[None] for n, x in out.items()}, out_sel[None],
                out_key[None], ovf[None])

    fn = jax.jit(jax.shard_map(
        per_part, mesh=mesh, in_specs=(_SPEC,) * 4,
        out_specs=(_SPEC, _SPEC, _SPEC, jax.sharding.PartitionSpec(
            mesh.axis_names)), check_vma=False))
    shape = (n_parts, -1)
    out, out_sel, out_key, ovf = fn(
        {n: x.reshape(shape) for n, x in arrays.items()}, sel.reshape(shape),
        key.reshape(shape), valid.reshape(shape))
    out_sel, out_key = np.asarray(out_sel), np.asarray(out_key)
    assert int(np.asarray(ovf).sum()) == 0
    live = sel & valid
    got = sorted(zip(out_key[out_sel].tolist(),
                     np.asarray(out["a"])[out_sel].tolist(),
                     np.asarray(out["b"])[out_sel].tolist()))
    assert got == sorted(zip(key[live].tolist(), arrays["a"][live].tolist(),
                             arrays["b"][live].tolist()))
    owners = {}
    for part in range(n_parts):
        for k in out_key[part][out_sel[part]].tolist():
            assert owners.setdefault(k, part) == part, k
    # [R] slots on one part, n_parts buckets of growth * R / n_parts beside it
    assert out_sel.shape[1] == (64 if n_parts == 1 else n_parts * 2 * 64)


def test_exchange_steps_counts_none_on_one_part():
    assert [exchange_steps(p, 2) for p in (1, 2, 4, 8)] == [0, 2, 2, 2]
    assert exchange_steps(1, 1) == 0 and exchange_steps(4, 1) == 1


# -- the counter at every launch ---------------------------------------------

JOIN = "select count(*), sum(x), sum(v) from fact join dim on k = id"
GROUP = "select k, sum(x) as q from fact group by k order by k"


@pytest.fixture(scope="module")
def catalog():
    s = Session()
    s.execute("create table dim (id bigint primary key, v bigint)")
    s.execute("create table fact (k bigint, g bigint, x bigint)")
    s.execute("insert into dim values " + ", ".join(
        f"({i}, {i * 10})" for i in range(-20, 60)))
    rng = np.random.default_rng(29)
    rows = [("null" if rng.random() < 0.1 else int(k), int(k) % 3, int(x))
            for k, x in zip(rng.integers(-30, 70, 900), rng.integers(0, 100, 900))]
    s.execute("insert into fact values " + ", ".join(
        f"({k}, {g}, {x})" for k, g, x in rows))
    return s.catalog, rows


def _served(catalog, devices, n_parts):
    s = Session(catalog=catalog, chunk_capacity=1024,
                mesh=make_mesh(devices=devices[:n_parts]))
    # a CPU mesh routes joins and generic aggregation to the host engine
    # unless asked
    s.execute("set tidb_device_engine_mode = 'force'")
    return s


def _by_kind(counter) -> dict:
    out = {}
    for labels, v in counter.samples():
        out[labels.get("kind")] = out.get(labels.get("kind"), 0) + v
    return out


@pytest.mark.parametrize("n_parts, per_launch", [
    (1, {"join_agg": 0, "general_generic": 0}),
    (4, {"join_agg": 2, "general_generic": 1}),
], ids=["1x1", "1x4"])
def test_a_launch_adds_its_programs_exchange_steps(devices8, catalog, n_parts,
                                                   per_launch):
    cat, rows = catalog
    s = _served(cat, devices8, n_parts)
    exch_retries = lambda: sum(  # noqa: E731
        v for labels, v in FRAGMENT_RETRY_TOTAL.samples()
        if labels.get("knob") == "exch")
    r0 = exch_retries()
    for sql, kind in ((JOIN, "join_agg"), (GROUP, "general_generic")):
        l0, e0 = _by_kind(FRAGMENT_DISPATCH), _by_kind(FRAGMENT_EXCHANGE_STEPS)
        for _ in range(2):
            got = s.query(sql)
        launched = _by_kind(FRAGMENT_DISPATCH)[kind] - l0.get(kind, 0)
        steps = _by_kind(FRAGMENT_EXCHANGE_STEPS)[kind] - e0.get(kind, 0)
        # (a group table sized from a guess may be launched anew, grown)
        assert launched >= 2, "the statement did not take the mesh tier"
        assert steps == launched * per_launch[kind]
        if kind == "join_agg":
            live = [(k, x) for k, _g, x in rows if k != "null" and -20 <= k < 60]
            assert [tuple(int(c) for c in got[0])] == [
                (len(live), sum(x for _, x in live), sum(k * 10 for k, _ in live))]
        else:
            sums = {}
            for k, _g, x in rows:
                k = None if k == "null" else k
                sums[k] = sums.get(k, 0) + x
            assert [tuple(r) for r in got] == sorted(
                sums.items(), key=lambda r: (r[0] is not None, r[0] or 0))
    if n_parts == 1:
        assert exch_retries() == r0


@pytest.mark.parametrize("n_parts", [1, 4], ids=["1x1", "1x4"])
def test_a_general_fragments_join_counts_two_steps_only_on_a_mesh(
        devices8, catalog, n_parts):
    """The general fragment's repartitioned join: `exchange` is false on
    one part and no "exch" knob is added; on four parts two steps for
    the join and one for the aggregate above it."""
    cat, _ = catalog
    s = _served(cat, devices8, n_parts)
    seen = []
    real = pe.DistFragmentExec._dispatch_retry

    def spy(self, prog, *rest):
        seen.append(prog)
        return real(self, prog, *rest)

    pe.DistFragmentExec._dispatch_retry = spy
    try:
        got = s.query("select g, id, sum(x) from fact join dim on k = id "
                      "group by g, id order by g, id")
    finally:
        pe.DistFragmentExec._dispatch_retry = real
    assert got and seen, "the statement took no general fragment"
    prog = seen[-1]
    if n_parts == 1:
        assert prog.n_exchange == 0 and "exch" not in prog.growth_kinds
        assert ":exchFalse" in prog.sig
    else:
        assert prog.n_exchange == 3
        assert prog.growth_kinds.count("exch") == 2  # one a join, one the agg's


# -- several keys whose hashes collide ---------------------------------------

def _colliding(kbits, kvalids):
    """Every group of four keys shares a hash: the order within a run of
    equal hashes is the rows', so equal keys are NOT contiguous."""
    return (kbits[0] + kbits[1]) & np.int64(3)


def test_a_multi_key_table_on_one_part_is_duplicate_free_under_collisions(
        devices8, monkeypatch):
    """`make_partial_kernel(exact=True)` is what the one-part fragment
    asks for: with the mixed hash made to collide, the inexact
    sort-reduce splits groups (the host executor merges them by key);
    the exact one emits every group once, and the served statement's
    rows are the oracle's."""
    monkeypatch.setattr(agg_device, "_group_hash", _colliding)
    rng = np.random.default_rng(41)
    n = 1500
    a = rng.integers(0, 9, n)
    b = np.where(rng.random(n) < 0.1, 0, rng.integers(-4, 5, n))
    b_null = rng.random(n) < 0.1
    v = rng.integers(1, 100, n)
    s = Session(chunk_capacity=4096,
                mesh=make_mesh(devices=devices8[:1]))
    s.execute("create table t (a bigint, b bigint, v bigint)")
    s.execute("insert into t values " + ", ".join(
        f"({x}, {'null' if isnull else y}, {z})"
        for x, y, isnull, z in zip(a, b, b_null, v)))
    s.execute("set tidb_device_engine_mode = 'force'")

    emitted = []
    real = pe.DistFragmentExec._finalize_generic_tables

    def spy(self, out):
        emitted.append(jax.device_get(out))
        return real(self, out)

    monkeypatch.setattr(pe.DistFragmentExec, "_finalize_generic_tables", spy)
    l0 = _by_kind(FRAGMENT_DISPATCH).get("general_generic", 0)
    got = s.query("select a, b, sum(v), count(*) from t group by a, b "
                  "order by a, b")
    assert _by_kind(FRAGMENT_DISPATCH)["general_generic"] == l0 + 1
    sums = {}
    for x, y, isnull, z in zip(a.tolist(), b.tolist(), b_null.tolist(), v.tolist()):
        key = (x, None if isnull else y)
        s0, c0 = sums.get(key, (0, 0))
        sums[key] = (s0 + z, c0 + 1)
    want = sorted(((x, y) + sc for (x, y), sc in sums.items()),
                  key=lambda r: (r[0], r[1] is not None, r[1] or 0))
    assert [tuple(None if c is None else int(c) for c in r) for r in got] == want
    (table,) = emitted
    n_groups = int(np.asarray(table["n"]).reshape(-1)[0])
    keys = list(zip(table["k0.d"][:n_groups].tolist(),
                    table["k1.d"][:n_groups].tolist(),
                    table["k1.v"][:n_groups].tolist()))
    assert n_groups == len(sums) == len(set(keys))

    # the fault the exact order guards against is real: the same kernel
    # without it splits groups under the same collisions
    from tidb_tpu.chunk.chunk import Chunk
    from tidb_tpu.chunk.column import Column
    from tidb_tpu.expression.expr import ColumnRef
    from tidb_tpu.planner.logical import AggSpec
    from tidb_tpu.types import INT64

    chunk = Chunk({"a": Column(jnp.asarray(a), jnp.ones(n, bool), INT64),
                   "b": Column(jnp.asarray(b), jnp.asarray(~b_null), INT64),
                   "v": Column(jnp.asarray(v), jnp.ones(n, bool), INT64)},
                  jnp.ones(n, bool))
    groups = [ColumnRef(INT64, "a"), ColumnRef(INT64, "b")]
    aggs = [AggSpec(func="sum", arg=ColumnRef(INT64, "v"), uid="s")]
    n_by = {exact: int(jax.jit(agg_device.make_partial_kernel(
        groups, aggs, exact=exact))(chunk)["n"]) for exact in (False, True)}
    assert n_by[True] == len(sums) < n_by[False]
