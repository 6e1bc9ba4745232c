"""The general fragment's joins as the compiler receives them (PR 38):
the lowered text of TPC-H Q3's, Q18's and Q22's one program at SF0.01,
each op under the scope its ``op_name`` gives. A join's expansion
(`join<j>/join.expand`) marks its row boundaries by ONE scatter-add of
32 bits — a 64-bit one this compiler lowers through a sort (230 ms a
statement over Q3's 3.1M slots, PERF.md section 5) — and reads each
slot's row by ONE gather of a [2, R] stack; its `join<j>/join.gather`
takes every array a side needs by ONE stack a side (one more where a
float rides), not a gather per column's data and validity. `_compact`,
whose stack-and-take the join now shares, lowers to one scatter and one
gather (the last test).

A semi or anti join on one key (Q18's IN, Q22's NOT EXISTS) expands
nothing: its `join<j>/join.exists` reads the rank's count, with no
scatter and no gather."""

import re

import jax
import jax.numpy as jnp
import pytest

from tidb_tpu.parallel import make_mesh
from tidb_tpu.parallel.fragment import _compact
from tidb_tpu.planner import feedback
from tidb_tpu.session import Session
from tidb_tpu.storage.tpch_queries import Q

N_JOINS = {"q3": 2, "q18": 3, "q22": 1}
# the joins that decide existence from the rank's count and expand nothing
EXISTS = {"q3": (), "q18": (2,), "q22": (0,)}


def lowered_ops(text: str) -> list:
    """[(opcode, [(shape, element type)] of its operands, scope)] of the
    ops of the lowered text's main function (the shard_map body is inlined
    there): a ``call @_take`` (jnp.take's own function) reads as a gather;
    the scope is the op's location name less the program's."""
    locs = dict(re.findall(r'^#(loc\d+) = loc\("([^"]*)"', text, re.M))
    out, regions, in_main = [], [], False
    for line in text.splitlines():
        if line.lstrip().startswith("func.func"):
            in_main = " @main(" in line
        if not in_main:
            continue
        indent = len(line) - len(line.lstrip())
        start = re.match(
            r'\s*%\S+ = (?:"stablehlo\.([\w.]+)"|stablehlo\.(\w+)|call @(\w+))',
            line)
        if start:
            op = start.group(1) or start.group(2) or (
                "gather" if start.group(3).startswith("_take") else "call")
            if line.rstrip().endswith("({"):  # its signature closes a region
                regions.append((indent, op))
                continue
        elif line.lstrip().startswith("})") and regions \
                and regions[-1][0] == indent:
            op = regions.pop()[1]
        else:
            continue
        sig = re.search(r': \((.*?)\) -> .* loc\(#(loc\d+)\)\s*$', line)
        if sig:
            operands = [(tuple(int(d) for d in t.split("x")[:-1]),
                         t.split("x")[-1])
                        for t in re.findall(r"tensor<([^>]*)>", sig.group(1))]
            scope = re.sub(r"^jit\([^)]*\)/", "", locs.get(sig.group(2), ""))
            out.append((op, operands, scope))
    return out


@pytest.fixture(scope="module")
def programs(devices8):
    """q -> the lowered ops of its general fragment at SF0.01, on one part."""
    from tidb_tpu.parallel import executor as pe
    from tidb_tpu.storage.catalog import Catalog
    from tidb_tpu.storage.tpch import load_tpch

    catalog = Catalog()
    load_tpch(catalog, sf=0.01)
    s = Session(catalog=catalog, mesh=make_mesh(devices=devices8[:1]))
    s.execute("use test")
    s.execute("set tidb_device_engine_mode = 'force'")
    feedback.STORE.clear()
    real, seen = pe.DistFragmentExec._dispatch_retry, []

    def spy(self, prog, args, shapes_sig, types_sig, growths, *span):
        out, grown = real(self, prog, args, shapes_sig, types_sig, growths,
                          *span)
        seen.append((prog, args, grown))
        return out, grown

    out = {}
    pe.DistFragmentExec._dispatch_retry = spy
    try:
        for q in N_JOINS:
            seen.clear()
            s.query(Q[q][0])
            (prog, args, growths), = seen
            assert prog.n_join == N_JOINS[q]
            out[q] = lowered_ops(prog.build_fn(growths).lower(*args).as_text(
                debug_info=True))
    finally:
        pe.DistFragmentExec._dispatch_retry = real
    return out


def in_scope(ops, scope: str) -> list:
    return [(o, operands) for o, operands, name in ops
            if f"/{scope}/" in f"/{name}/"]


@pytest.mark.parametrize("q", sorted(N_JOINS))
def test_a_joins_expansion_scatters_32_bits_and_gathers_one_stack(programs,
                                                                  q):
    for j in set(range(N_JOINS[q])) - set(EXISTS[q]):
        ops = in_scope(programs[q], f"join{j}/join.expand")
        scatters = [operands for o, operands in ops if o == "scatter"]
        # the boundary marks: int32 slots, int32 ones
        assert len(scatters) == 1, (j, ops)
        assert {t for _, t in scatters[0]} == {"i32"}, (j, scatters)
        for o, operands in ops:
            if o == "gather":
                # the [2, R] stack of first slots and build starts, or the
                # int32 merged order; never an int64 array by itself
                (shape, t), _idx = operands[:2]
                assert (len(shape) == 2 and shape[0] == 2 and t == "i64") \
                    or (len(shape) == 1 and t == "i32"), (j, operands)


@pytest.mark.parametrize("q", sorted(N_JOINS))
def test_a_joins_gather_takes_one_stack_a_side(programs, q):
    """Q3's and Q18's columns are integers (decimals as scaled int64,
    dates, dictionary codes): one int64 stack a side."""
    for j in set(range(N_JOINS[q])) - set(EXISTS[q]):
        ops = in_scope(programs[q], f"join{j}/join.gather")
        gathers = [operands[0] for o, operands in ops if o == "gather"]
        assert 1 <= len(gathers) <= 2, (j, gathers)
        assert all(len(shape) == 2 and t == "i64" for shape, t in gathers), (
            j, gathers)
        assert not [o for o, _ in ops if o == "scatter"], (j, ops)


@pytest.mark.parametrize("q", sorted(N_JOINS))
def test_an_existence_join_holds_no_scatter_and_no_gather(programs, q):
    """`join<j>/join.exists` is the comparison of the rank's count with 0
    and the semantics' masks: no scatter of match flags, no gather; and
    such a join has no `join.expand` and no `join.gather` at all. Every
    other join has both."""
    for j in range(N_JOINS[q]):
        exists = in_scope(programs[q], f"join{j}/join.exists")
        moved = (in_scope(programs[q], f"join{j}/join.expand")
                 + in_scope(programs[q], f"join{j}/join.gather"))
        if j in EXISTS[q]:
            assert exists and not moved, (j, moved)
            assert not [o for o, _ in exists if o in ("scatter", "gather")], (
                j, exists)
        else:
            assert moved and not exists, (j, exists)


def test_compact_alone_lowers_to_one_32bit_scatter_and_one_gather():
    """Two int64 columns and their masks from 6,144 slots into 3,072:
    ONE scatter, of int32 row numbers, and ONE gather, of the [3, R]
    stack (two words, one row of packed masks) — PR 36's program."""
    sds = jax.ShapeDtypeStruct
    arrays = {"a.d": sds((6144,), jnp.int64), "a.v": sds((6144,), jnp.bool_),
              "b.d": sds((6144,), jnp.int64), "b.v": sds((6144,), jnp.bool_)}
    text = jax.jit(lambda a, sel: _compact(a, sel, 3072)).lower(
        arrays, sds((6144,), jnp.bool_)).as_text(debug_info=True)
    ops = lowered_ops(text)
    scatters = [operands for o, operands, _ in ops if "scatter" in o]
    assert scatters == [[((3073,), "i32"), ((6144, 1), "i32"),
                         ((6144,), "i32")]], scatters
    gathers = [operands for o, operands, _ in ops if o == "gather"]
    assert gathers == [[((3, 6144), "i64"), ((3072,), "i32")]], gathers
