"""Device sort-based generic aggregation vs the numpy oracle path.

The generic strategy handles high-cardinality keys; the device path
(agg_device.py) must agree with the host groupby bit-for-bit on NULL
groups, float keys, multi-key grouping, spill-sized inputs, and every
agg function, with the numpy path kept as the oracle
(tidb_enable_tpu_exec=0)."""

import numpy as np
import pytest

from tidb_tpu.session import Session
from tidb_tpu.testutil import rows_equal


def _fill(s, n=5000, seed=3):
    rng = np.random.default_rng(seed)
    s.execute("CREATE TABLE g (k bigint, k2 varchar(10), f double, v bigint)")
    ks = rng.integers(0, 700, n)
    k2 = rng.integers(0, 5, n)
    fs = rng.standard_normal(n).round(3)
    vs = rng.integers(-50, 50, n)
    rows = []
    for i in range(n):
        k = "NULL" if ks[i] == 0 else str(ks[i])
        k2s = "NULL" if k2[i] == 4 else f"'s{k2[i]}'"
        f = "NULL" if i % 97 == 0 else repr(float(fs[i]))
        rows.append(f"({k}, {k2s}, {f}, {vs[i]})")
    for start in range(0, n, 500):
        s.execute("INSERT INTO g VALUES " + ", ".join(rows[start:start + 500]))


QUERIES = [
    "select k, count(*), sum(v), min(v), max(v), avg(v) from g group by k order by k",
    "select k, k2, count(*), sum(f) from g group by k, k2 order by k, k2",
    "select f, count(*) from g group by f order by f limit 50",
    "select k2, count(v), avg(f), min(f), max(f) from g group by k2 order by k2",
]


@pytest.fixture(scope="module")
def sessions():
    dev = Session(chunk_capacity=512)  # many chunks -> several merge levels
    # the auto engine heuristic routes generic agg to the host numpy
    # path on a bare CPU backend; these tests exist to exercise the
    # device kernels, so pin them on
    dev.execute("SET tidb_device_engine_mode = 'force'")
    _fill(dev)
    host = Session(chunk_capacity=512)
    host.execute("SET tidb_enable_tpu_exec = 0")
    _fill(host)
    return dev, host


@pytest.mark.parametrize("sql", QUERIES)
def test_device_matches_host(sessions, sql):
    dev, host = sessions
    got = dev.query(sql)
    want = host.query(sql)
    ok, msg = rows_equal(got, want, ordered=True)
    assert ok, msg


def test_uses_device_path(sessions):
    dev, _ = sessions
    from tidb_tpu.executor import aggregate as agg
    from tidb_tpu.executor import pipeline as pipe

    called = {}
    orig = agg.HashAggExec._run_generic_device
    orig_fused = pipe.FusedScanAggExec._run_generic_fused

    def spy(self):
        called["yes"] = True
        return orig(self)

    def spy_fused(self):
        # the fused scan→partial-agg pipeline (ISSUE 9) IS the device
        # path: group tables accumulate on device, one fetch at the end
        called["yes"] = True
        return orig_fused(self)

    agg.HashAggExec._run_generic_device = spy
    pipe.FusedScanAggExec._run_generic_fused = spy_fused
    try:
        dev.query("select k, count(*) from g group by k")
    finally:
        agg.HashAggExec._run_generic_device = orig
        pipe.FusedScanAggExec._run_generic_fused = orig_fused
    assert called.get("yes"), "generic agg did not take the device path"


def test_empty_input(sessions):
    dev, _ = sessions
    assert dev.query("select k, count(*) from g where k > 100000 group by k") == []


def test_distinct_falls_back(sessions):
    dev, host = sessions
    sql = "select k2, count(distinct v) from g group by k2 order by k2"
    ok, msg = rows_equal(dev.query(sql), host.query(sql), ordered=True)
    assert ok, msg


def test_distinct_global_count_empty_input():
    s = Session()
    s.execute("create table e (d bigint, a bigint)")
    r = s.query("select count(distinct d), count(*), count(a), sum(a) from e")
    assert r == [(0, 0, 0, None)], r
    s.execute("insert into e values (1, 10), (1, 20), (NULL, 30)")
    r = s.query("select count(distinct d), count(*), count(a), sum(a) from e")
    assert r == [(1, 3, 3, 60)], r


# -- the reduce of the sorted runs, slot for slot (PR 31) --------------------
#
# `_sort_reduce` reads an integer sum off one running total at the ends of
# the sorted runs; float sums, min and max keep their segment ops. The
# oracle below shares nothing with it but the order of the table's slots
# (the sort's keys): numpy's `add.at` / `minimum.at` / `maximum.at` per run
# of equal adjacent keys, into a table whose slots past `n` hold zero /
# False (min / max: the identity).

import jax
import jax.numpy as jnp

from tidb_tpu.chunk.chunk import Chunk
from tidb_tpu.chunk.column import Column
from tidb_tpu.executor import agg_device
from tidb_tpu.executor.aggregate import needs_sum_limbs, normalize_limbs, split_limbs
from tidb_tpu.expression.expr import ColumnRef
from tidb_tpu.planner.logical import AggSpec
from tidb_tpu.types import FLOAT64, INT64, TypeKind, decimal_type

DEC = decimal_type(18, 2)


def _colliding(kbits, kvalids):
    """Every four keys share a hash (tests/test_exchange.py's plant):
    within a run of equal hashes equal keys are NOT contiguous."""
    return (kbits[0] + kbits[1]) & np.int64(3)


def _case(name: str) -> dict:
    """{"cols": {name: (data, valid, type)}, "sel", "keys": [names],
    "aggs": [(func, column or None)], "collide": plant the hash}."""
    rng = np.random.default_rng(len(name) * 131 + ord(name[0]))
    n = 600
    ones = np.ones(n, dtype=bool)
    c = {"sel": ones, "keys": ["k"], "aggs": [("sum", "v"), ("count", None)],
         "collide": False}
    v = rng.integers(-1000, 1000, n)
    if name == "null_beside_zero":
        # a NULL key's data is garbage the bits must not see
        kv = rng.random(n) < 0.6
        k = np.where(kv, rng.integers(0, 3, n), rng.integers(5, 9, n))
        c["cols"] = {"k": (k, kv, INT64), "v": (v, rng.random(n) < 0.9, INT64)}
    elif name == "dead_rows_scattered":
        c["sel"] = rng.random(n) < 0.5
        c["cols"] = {"k": (rng.integers(-20, 20, n), ones, INT64), "v": (v, ones, INT64)}
    elif name == "every_row_dead":
        c["sel"] = ~ones
        c["cols"] = {"k": (rng.integers(0, 9, n), ones, INT64), "v": (v, ones, INT64)}
    elif name == "no_aggregate":  # SELECT DISTINCT's shape: keys only
        c["aggs"] = []
        c["cols"] = {"k": (rng.integers(0, 50, n), rng.random(n) < 0.9, INT64)}
    elif name == "one_group":
        c["cols"] = {"k": (np.full(n, 42), ones, INT64), "v": (v, ones, INT64)}
    elif name == "every_row_its_own_group":
        c["cols"] = {"k": (rng.permutation(n) - n // 2, ones, INT64), "v": (v, ones, INT64)}
    elif name == "colliding_hashes":
        c.update(keys=["k", "k2"], collide=True)
        k2v = rng.random(n) < 0.9
        c["cols"] = {"k": (rng.integers(0, 9, n), ones, INT64),
                     "k2": (np.where(rng.random(n) < 0.1, 0, rng.integers(-4, 5, n)),
                            k2v, INT64),
                     "v": (v, ones, INT64)}
    elif name == "sums_that_wrap":
        # each group's sum fits (|sum| < 2^63); the running total over
        # the rows in key order passes 2^63 dozens of times
        k = rng.integers(0, 150, n)
        big = np.int64(1) << np.int64(62)
        v = np.where(rng.random(n) < 0.5, big - rng.integers(0, 9, n),
                     rng.integers(-5, 5, n))
        first = np.zeros(n, dtype=bool)
        first[np.unique(k, return_index=True)[1]] = True
        v = np.where(first, v, rng.integers(-5, 5, n))  # one big row a group
        assert abs(int(v.astype(object).sum())) > 20 * (1 << 63)
        c["cols"] = {"k": (k, ones, INT64), "v": (v, ones, INT64)}
    elif name == "low_limbs_pass_2_32":
        # scaled decimals whose low 32 bits are near 2^32: eight rows of
        # a group carry out of the low limb
        k = rng.integers(0, 60, n)
        v = (rng.integers(-3, 4, n) << 32) + (1 << 32) - rng.integers(1, 1000, n)
        c["cols"] = {"k": (k, ones, INT64), "v": (v, rng.random(n) < 0.95, DEC)}
        c["aggs"] = [("sum", "v"), ("avg", "v")]
    elif name == "float_sum_min_max_beside_an_integer_sum":
        c["keys"] = ["k", "k2"]
        c["sel"] = rng.random(n) < 0.9
        f = rng.standard_normal(n) * 10.0 ** rng.integers(-8, 8, n)
        c["cols"] = {"k": (rng.integers(0, 12, n), ones, INT64),
                     "k2": (rng.integers(0, 3, n), rng.random(n) < 0.8, INT64),
                     "v": (v, rng.random(n) < 0.9, INT64),
                     "f": (f, rng.random(n) < 0.9, FLOAT64)}
        c["aggs"] = [("sum", "v"), ("sum", "f"), ("min", "v"), ("max", "v"),
                     ("min", "f"), ("max", "f"), ("avg", "f"), ("count", "v")]
    else:
        raise ValueError(name)
    return c


CASES = ["null_beside_zero", "dead_rows_scattered", "every_row_dead", "no_aggregate", "one_group",
         "every_row_its_own_group", "colliding_hashes", "sums_that_wrap",
         "low_limbs_pass_2_32", "float_sum_min_max_beside_an_integer_sum"]


def _specs(case):
    groups = [ColumnRef(case["cols"][k][2], k) for k in case["keys"]]
    aggs = [AggSpec(func=f, uid=f"a{j}",
                    arg=None if col is None else ColumnRef(case["cols"][col][2], col))
            for j, (f, col) in enumerate(case["aggs"])]
    return groups, aggs


def _payloads(case, aggs):
    """The statement's contributions a row, in `_state_layout`'s order:
    [(state name, op, numpy array)]."""
    sel = case["sel"]
    out = []
    for j, a in enumerate(aggs):
        if a.arg is None:
            d, ok = None, sel
        else:
            d, valid, type_ = case["cols"][a.arg.name]
            ok = sel & valid
        out.append((f"a{j}.cnt", "sum", ok.astype(np.int64)))
        if a.func in ("sum", "avg"):
            dt = np.float64 if type_.kind == TypeKind.FLOAT else np.int64
            contrib = np.where(ok, d, 0).astype(dt)
            if needs_sum_limbs(a):
                lo, hi = split_limbs(contrib)
                out += [(f"a{j}.sum", "sum", lo), (f"a{j}.sumhi", "sum", hi)]
            else:
                out.append((f"a{j}.sum", "sum", contrib))
        elif a.func in ("min", "max"):
            dt = type_.np_dtype
            ident = agg_device._ident_min(dt) if a.func == "min" else agg_device._ident_max(dt)
            out.append((f"a{j}.{a.func}", a.func, np.where(ok, d, ident).astype(dt)))
    return out


def _hash_np(kbits, kvalids, collide):
    if collide:
        return _colliding(kbits, kvalids)
    with np.errstate(over="ignore"):
        return np.asarray(agg_device._group_hash(
            [jnp.asarray(b) for b in kbits], [jnp.asarray(v) for v in kvalids]))


def _oracle_table(kdatas, kvalids, live, payloads, exact, collide, slots):
    """The group table numpy gives: rows in the order of the sort's keys
    (stable), one slot per run of equal adjacent keys."""
    n = len(live)
    kbits = [np.where(v, d, 0).astype(np.int64) for d, v in zip(kdatas, kvalids)]
    dead = (~live).astype(np.int64)
    if len(kbits) == 1:
        keys = [dead, kbits[0], kvalids[0].astype(np.int64)]
    elif exact:
        keys = ([dead, _hash_np(kbits, kvalids, collide)] + kbits
                + [v.astype(np.int64) for v in kvalids])
    else:
        keys = [dead, _hash_np(kbits, kvalids, collide)]
    order = np.lexsort(tuple(reversed(keys + [np.arange(n)])))
    order = order[live[order]]  # the live prefix
    mat = np.stack([b[order] for b in kbits]
                   + [v[order].astype(np.int64) for v in kvalids], axis=1)
    head = np.ones(len(order), dtype=bool)
    head[1:] = (mat[1:] != mat[:-1]).any(axis=1)
    run = np.cumsum(head) - 1
    n_runs = int(head.sum())
    table = {"n": n_runs}
    keep = run < slots
    for i, (d, v) in enumerate(zip(kdatas, kvalids)):
        kd = np.zeros(slots, dtype=d.dtype)
        kv = np.zeros(slots, dtype=bool)
        kd[run[keep]] = np.where(v, d, 0)[order][keep]
        kv[run[keep]] = v[order][keep]
        table[f"k{i}.d"], table[f"k{i}.v"] = kd, kv
    for name, op, arr in payloads:
        if op == "sum":
            acc = np.zeros(slots, dtype=arr.dtype)
            with np.errstate(over="ignore"):
                np.add.at(acc, run[keep], arr[order][keep])
        elif op == "min":
            acc = np.full(slots, agg_device._ident_min(arr.dtype), dtype=arr.dtype)
            np.minimum.at(acc, run[keep], arr[order][keep])
        else:
            acc = np.full(slots, agg_device._ident_max(arr.dtype), dtype=arr.dtype)
            np.maximum.at(acc, run[keep], arr[order][keep])
        table[name] = acc
    return table


def _assert_tables_equal(got, want):
    got = jax.device_get(got)
    assert int(got["n"]) == want["n"]
    assert sorted(got) == sorted(want)
    for name, w in want.items():
        g = np.asarray(got[name])
        if name.endswith(".d"):  # a NULL key's data is no one's to read
            g = np.where(np.asarray(got[name[:-1] + "v"]), g, 0)
        if name != "n":
            assert g.dtype == w.dtype and g.shape == w.shape, name
        # bit for bit: floats too (NaN-free by construction)
        assert np.array_equal(g, w), (name, np.flatnonzero(g != w)[:5])


def _chunk(case):
    cols = {name: Column(jnp.asarray(d), jnp.asarray(v), t)
            for name, (d, v, t) in case["cols"].items()}
    return Chunk(cols, jnp.asarray(case["sel"]))


@pytest.mark.parametrize("exact", [False, True], ids=["hash_order", "exact_order"])
@pytest.mark.parametrize("name", CASES)
def test_the_partial_table_equals_numpys_sums_slot_for_slot(name, exact, monkeypatch):
    case = _case(name)
    if case["collide"]:
        monkeypatch.setattr(agg_device, "_group_hash", _colliding)
    groups, aggs = _specs(case)
    n = len(case["sel"])
    kdatas = [case["cols"][k][0] for k in case["keys"]]
    kvalids = [case["cols"][k][1] for k in case["keys"]]
    payloads = _payloads(case, aggs)
    assert [(p[0], p[1]) for p in payloads] == agg_device._state_layout(aggs)
    kernel = agg_device.make_partial_kernel(groups, aggs, exact=exact)
    for slots in (n, 40):  # the whole table, and one cut short of its groups
        want = _oracle_table(kdatas, kvalids, case["sel"], payloads, exact,
                             case["collide"], slots)
        got = jax.jit(kernel, static_argnames="slots")(_chunk(case), slots=slots)
        _assert_tables_equal(got, want)
    # per key, whatever the runs: the exact order holds every key once;
    # under the hash order a planted collision splits keys into several
    # runs, which the consumer's merge by key puts together again
    bits = np.stack([np.where(v, d, 0) for d, v in zip(kdatas, kvalids)]
                    + [v.astype(np.int64) for v in kvalids], axis=1)[case["sel"]]
    n_keys = len(np.unique(bits, axis=0)) if len(bits) else 0
    full = _oracle_table(kdatas, kvalids, case["sel"], payloads, exact,
                         case["collide"], n)
    if exact or not case["collide"]:
        assert full["n"] == n_keys
    else:
        assert full["n"] > n_keys
    if name == "sums_that_wrap":
        got = jax.device_get(jax.jit(kernel)(_chunk(case)))
        k, v = case["cols"]["k"][0], case["cols"]["v"][0]
        for slot in range(int(got["n"])):
            assert int(got["a0.sum"][slot]) == int(
                v[k == got["k0.d"][slot]].astype(object).sum())
    if name == "float_sum_min_max_beside_an_integer_sum":
        # the float sum is the parent's expression to the bit: a
        # segment_sum over the sorted rows
        got = jax.device_get(jax.jit(kernel)(_chunk(case)))
        assert agg_device.reduce_paths(aggs).count("scatter") == 6  # 2 float sums, 4 extremes
        assert full["a1.sum"].dtype == np.float64
        assert np.array_equal(got["a1.sum"], full["a1.sum"])


@pytest.mark.parametrize("name", CASES)
def test_the_merged_table_equals_numpys_sums_slot_for_slot(name, monkeypatch):
    """`make_merge_kernel` over two tables (the case's rows, halved; each
    half's table by the oracle, so the partial kernel is not in the
    loop): its states are sums of sums and extremes of extremes, limbs
    carry-normalised after."""
    case = _case(name)
    if case["collide"]:
        monkeypatch.setattr(agg_device, "_group_hash", _colliding)
    groups, aggs = _specs(case)
    n = len(case["sel"])
    payloads = _payloads(case, aggs)
    halves = []
    for rows in (slice(0, n // 2), slice(n // 2, n)):
        kd = [case["cols"][k][0][rows] for k in case["keys"]]
        kv = [case["cols"][k][1][rows] for k in case["keys"]]
        halves.append(_oracle_table(
            kd, kv, case["sel"][rows], [(a, op, p[rows]) for a, op, p in payloads],
            False, case["collide"], n // 2))
    cat = {name: np.concatenate([h[name] for h in halves])
           for name in halves[0] if name != "n"}
    live = np.concatenate([np.arange(n // 2) < h["n"] for h in halves])
    nk = len(groups)
    want = _oracle_table(
        [cat[f"k{i}.d"] for i in range(nk)], [cat[f"k{i}.v"] for i in range(nk)],
        live, [(a, op, cat[a]) for a, op, _ in payloads], False, case["collide"], n)
    for j, a in enumerate(aggs):
        if f"a{j}.sumhi" in want:
            want[f"a{j}.sum"], want[f"a{j}.sumhi"] = normalize_limbs(
                want[f"a{j}.sum"], want[f"a{j}.sumhi"])
    tables = [{k: jnp.asarray(v) for k, v in h.items()} for h in halves]
    got = jax.jit(agg_device.make_merge_kernel(nk, aggs))(*tables)
    _assert_tables_equal(got, want)
    if name == "low_limbs_pass_2_32":
        lo = np.asarray(got["a0.sum"])
        assert ((0 <= lo) & (lo < 1 << 32)).all()
        # the two limbs together are the key's exact sum
        k, (v, ok, _) = case["cols"]["k"][0], case["cols"]["v"]
        for slot in range(int(got["n"])):
            rows = (k == int(got["k0.d"][slot])) & ok
            assert (int(got["a0.sumhi"][slot]) << 32) + int(lo[slot]) == int(
                v[rows].astype(object).sum())
