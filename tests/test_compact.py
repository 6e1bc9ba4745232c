"""`parallel/fragment.py _compact` (PR 36): a chunk's live rows moved to
the prefix of `cap` slots by ONE 32-bit scatter of row numbers and ONE
gather of all its arrays as an int64 stack — against the move it
replaced, a scatter per array, kept here as a few lines of numpy: the
same rows in the same slots to the bit, zero / False past the live
count, the same overflow factor."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import tidb_tpu  # noqa: F401  (x64)
from tidb_tpu.chunk.chunk import Chunk
from tidb_tpu.chunk.column import Column
from tidb_tpu.parallel import make_mesh
from tidb_tpu.parallel.fragment import _PACK, _compact, _compact_chunk
from tidb_tpu.types import BOOL, FLOAT64, INT64, STRING

R = 10_000  # past prefix.cumsum's 4,096: the blocked sum


def scatter_per_array(arrays, sel, cap):
    """The old body of `_compact`: per array, a buffer of zeros that the
    live rows under `cap` are written into at their rank."""
    live = np.flatnonzero(sel)
    kept = live[:cap]
    out = {}
    for name, a in arrays.items():
        out[name] = np.zeros(cap, dtype=a.dtype)
        out[name][:len(kept)] = a[kept]
    nsel = np.arange(cap) < min(len(live), cap)
    return out, nsel, max(-(-len(live) // cap) - 1, 0)


def bits(a: np.ndarray) -> np.ndarray:
    a = np.asarray(a)
    return a.view(np.int64) if a.dtype == np.float64 else a


def check(arrays, sel, cap):
    got, nsel, ovf = jax.jit(_compact, static_argnums=2)(
        {k: jnp.asarray(v) for k, v in arrays.items()}, jnp.asarray(sel), cap)
    want, want_sel, want_ovf = scatter_per_array(arrays, sel, cap)
    assert sorted(got) == sorted(want)  # (jit hands a dict back by sorted key)
    for name, w in want.items():
        g = np.asarray(got[name])
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(bits(g), bits(w), err_msg=name)
    np.testing.assert_array_equal(np.asarray(nsel), want_sel)
    assert ovf.dtype == jnp.int64 and int(ovf) == want_ovf
    return got, ovf


def _nan(payload: int) -> float:
    return np.array([0x7FF8000000000000 | payload], dtype=np.int64).view(
        np.float64)[0]


def column(kind: str, rng) -> dict:
    """One column's arrays ("c.d", and for DECIMAL limbs "c.hi" too)."""
    if kind == "int64":
        d = rng.integers(-2**62, 2**62, R)
        d[:2] = np.iinfo(np.int64).min, np.iinfo(np.int64).max
        return {"c.d": d}
    if kind == "int32":
        d = rng.integers(-2**31, 2**31, R).astype(np.int32)
        d[:2] = np.iinfo(np.int32).min, np.iinfo(np.int32).max
        return {"c.d": d}
    if kind == "float64":
        d = rng.normal(size=R)
        d[:6] = np.nan, _nan(0x1234), -0.0, 0.0, np.inf, -np.inf
        rng.shuffle(d)
        return {"c.d": d}
    if kind == "decimal_limbs":  # lo in [0, 2^32), hi signed
        return {"c.d": rng.integers(0, 2**32, R),
                "c.hi": rng.integers(-2**40, 2**40, R)}
    assert kind == "bool"
    return {"c.d": rng.random(R) < 0.5}


@pytest.mark.parametrize("nulls", [False, True], ids=["notnull", "nulls"])
@pytest.mark.parametrize(
    "kind", ["int64", "int32", "float64", "decimal_limbs", "bool"])
def test_moves_every_dtype_to_the_bit(kind, nulls):
    rng = np.random.default_rng(36)
    arrays = column(kind, rng)
    # data under a NULL is carried as it is: the old move did not look
    arrays["c.v"] = rng.random(R) < 0.8 if nulls else np.ones(R, np.bool_)
    check(arrays, rng.random(R) < 0.3, 4096)


@pytest.mark.parametrize("live,cap,factor", [
    ("none", 64, 0),        # all dead: nothing but zeros
    ("all", R, 0),          # all live, every slot taken
    ("all", 64, 156),       # all live, 64 slots: ceil(10000 / 64) - 1
    ("some", 64, 46),       # 3,000 live into 64: the first 64 survive
    ("some", 2999, 1),      # one row too many
    ("some", 3000, 0),      # exactly full
    ("some", 3001, 0),      # one slot to spare
], ids=lambda v: str(v))
def test_live_counts_and_overflow(live, cap, factor):
    rng = np.random.default_rng(7)
    sel = np.zeros(R, np.bool_)
    if live == "all":
        sel[:] = True
    elif live == "some":
        sel[rng.choice(R, 3000, replace=False)] = True
    arrays = {"k.d": rng.integers(-2**62, 2**62, R),
              "k.v": rng.random(R) < 0.9,
              "x.d": rng.normal(size=R), "x.v": rng.random(R) < 0.9}
    got, ovf = check(arrays, sel, cap)
    assert int(ovf) == factor
    if live == "some" and cap == 64:
        # the rows that survive an overflow are the first `cap` live ones
        np.testing.assert_array_equal(
            np.asarray(got["k.d"]), arrays["k.d"][np.flatnonzero(sel)[:64]])


@pytest.mark.parametrize("n_cols", [1, 31, _PACK, _PACK + 1, 70, 2 * _PACK + 1])
def test_wide_chunks_pack_their_validity_by_the_row(n_cols):
    """Past `_PACK` boolean arrays the bits take another stack row."""
    rng = np.random.default_rng(n_cols)
    arrays = {}
    for i in range(n_cols):
        arrays[f"c{i}.d"] = (rng.integers(-9, 9, R) if i % 3
                             else rng.random(R) < 0.5)
        arrays[f"c{i}.v"] = rng.random(R) < 0.7
    check(arrays, rng.random(R) < 0.4, 4096)


def test_a_chunk_without_columns_keeps_its_count():
    sel = np.arange(R) % 3 == 0
    got, ovf = check({}, sel, 128)
    assert got == {} and int(ovf) == -(-int(sel.sum()) // 128) - 1


def _eqn_counts(jaxpr, counts=None):
    counts = {} if counts is None else counts
    for e in jaxpr.eqns:
        counts.setdefault(e.primitive.name, []).append(e)
        for v in e.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                _eqn_counts(sub, counts)
    return counts


@pytest.mark.parametrize("floats", [False, True], ids=["ints", "floats_too"])
@pytest.mark.parametrize("n_cols", [1, 5, 70])
def test_one_32bit_scatter_and_one_gather_whatever_the_chunk(n_cols, floats):
    """The program `_compact` traces: however many arrays, ONE scatter,
    of int32 row numbers, and ONE gather, of an int64 stack; float
    columns, which the chip's compiler will not hand over as bits, ride
    in a float64 stack of their own: one gather more, however many."""
    types = [jnp.int64, jnp.float64 if floats else jnp.int32, jnp.int32]
    arrays = {}
    for i in range(n_cols):
        arrays[f"c{i}.d"] = jax.ShapeDtypeStruct((R,), types[i % 3])
        arrays[f"c{i}.v"] = jax.ShapeDtypeStruct((R,), jnp.bool_)
    eqns = _eqn_counts(jax.make_jaxpr(lambda a, s: _compact(a, s, 4096))(
        arrays, jax.ShapeDtypeStruct((R,), jnp.bool_)).jaxpr)
    scatters = [e for name, es in eqns.items() if name.startswith("scatter")
                for e in es]
    assert len(scatters) == 1
    assert {v.aval.dtype for v in scatters[0].invars} == {np.dtype(np.int32)}
    n_float = sum(a.dtype == jnp.float64 for a in arrays.values())
    stacks = {e.invars[0].aval.dtype.name: e.invars[0].aval.shape
              for e in eqns["gather"]}
    assert len(eqns["gather"]) == len(stacks) == 1 + bool(n_float)
    # each datum, the packed validity
    assert stacks["int64"] == (n_cols - n_float + -(-n_cols // _PACK), R)
    assert not n_float or stacks["float64"] == (n_float, R)


def test_refuses_a_shard_past_int32_when_traced():
    with pytest.raises(ValueError, match="2147483648 slots"):
        jax.eval_shape(lambda s: _compact({}, s, 64),
                       jax.ShapeDtypeStruct((1 << 31,), jnp.bool_))


def test_on_four_parts_each_compacts_its_own_rows(devices8):
    """Under `shard_map`, as the fragment runs it: every part moves its
    own rows and reports its own overflow."""
    rng = np.random.default_rng(4)
    mesh = make_mesh(devices=devices8[:4])
    axes = tuple(mesh.axis_names)
    arrays = {"k.d": rng.integers(-2**62, 2**62, (4, R)),
              "k.v": rng.random((4, R)) < 0.9,
              "f.d": rng.normal(size=(4, R)), "f.v": rng.random((4, R)) < 0.9}
    # part 0 empty, part 3 overflowing its 2,048 slots
    sel = rng.random((4, R)) < np.array([0.0, 0.1, 0.2, 0.5])[:, None]
    cap = 2048

    def per_part(a, s):
        out, nsel, ovf = _compact({k: v[0] for k, v in a.items()}, s[0], cap)
        return ({k: v[None] for k, v in out.items()}, nsel[None], ovf[None])

    spec = P(axes, None)
    got, nsel, ovf = jax.jit(jax.shard_map(
        per_part, mesh=mesh, in_specs=(spec, spec),
        out_specs=(spec, spec, P(axes))))(
            {k: jnp.asarray(v) for k, v in arrays.items()}, jnp.asarray(sel))
    for p in range(4):
        want, want_sel, want_ovf = scatter_per_array(
            {k: v[p] for k, v in arrays.items()}, sel[p], cap)
        for name, w in want.items():
            np.testing.assert_array_equal(
                bits(np.asarray(got[name][p])), bits(w), err_msg=(p, name))
        np.testing.assert_array_equal(np.asarray(nsel[p]), want_sel)
        assert int(ovf[p]) == want_ovf
    assert int(ovf[0]) == 0 and int(ovf[3]) >= 1


def _chunk(rng) -> Chunk:
    return Chunk({
        "a": Column(jnp.asarray(rng.integers(0, 99, R)),
                    jnp.asarray(rng.random(R) < 0.9), INT64),
        "s": Column(jnp.asarray(rng.integers(0, 9, R).astype(np.int32)),
                    jnp.asarray(rng.random(R) < 0.9), STRING),
        "f": Column(jnp.asarray(rng.normal(size=R)),
                    jnp.asarray(rng.random(R) < 0.9), FLOAT64),
        "b": Column(jnp.asarray(rng.random(R) < 0.5),
                    jnp.asarray(rng.random(R) < 0.9), BOOL),
    }, jnp.asarray(rng.random(R) < 0.25))


@pytest.mark.parametrize("cap", [64, 4096, R, R + 1])
def test_a_chunk_is_compacted_only_under_its_capacity(cap):
    """`_compact_chunk`: columns keep their types; at or over the chunk's
    capacity nothing is compiled, reported or counted."""
    chunk = _chunk(np.random.default_rng(cap))
    env, ovfs = {"compactions": []}, []
    mesh = make_mesh(devices=jax.devices()[:1])
    axes = tuple(mesh.axis_names)

    def run(ch):
        out = _compact_chunk(env, ch, cap, 5, ovfs)
        assert (out is ch) == (cap >= R)
        return out, [v for _, v in ovfs]

    out, reported = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False))(chunk)
    if cap >= R:
        assert env["compactions"] == [] and ovfs == [] and out.capacity == R
        return
    assert env["compactions"] == [5] and [k for k, _ in ovfs] == [5]
    arrays = {}
    for uid, col in chunk.columns.items():
        arrays[uid + ".d"] = np.asarray(col.data)
        arrays[uid + ".v"] = np.asarray(col.valid)
    want, want_sel, want_ovf = scatter_per_array(
        arrays, np.asarray(chunk.sel), cap)
    assert out.capacity == cap and int(reported[0]) == want_ovf
    np.testing.assert_array_equal(np.asarray(out.sel), want_sel)
    for uid, col in chunk.columns.items():
        assert out.columns[uid].type_ == col.type_
        np.testing.assert_array_equal(
            bits(np.asarray(out.columns[uid].data)), bits(want[uid + ".d"]))
        np.testing.assert_array_equal(
            np.asarray(out.columns[uid].valid), want[uid + ".v"])
