"""The chip's compiler, without the chip: every kernel of the served
path and one whole program of each device tier are compiled for a
DESCRIBED TPU v5e at TPC-H SF1 shapes (1<<20-row chunks; the
5,999,150-row whole-table [1, R] layout of the mesh tier). Nothing
runs — a compile that passes is not a chip run — but what Mosaic/XLA:TPU
refuses (VMEM budget, unaligned slices, ops that do not lower) fails
here, in tier-1, at no chip time.

This is the only file that describes the chip. The topology is
described inside a module-scoped fixture (never at import, never in
conftest, not autouse) and every compile happens in the test's own
process: one process at a time may load the TPU library.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from tidb_tpu.ops import hash_probe as hp
from tidb_tpu.ops import join_kernels as jk
from tidb_tpu.ops import segment_sum as ss
from tidb_tpu.ops import topk as tk
from tidb_tpu.utils.device import force_platform

R = 1 << 20                  # a packed scan batch (16 segments of 65536)
CHUNK = 1 << 16              # tidb_max_chunk_size: the served path's chunk
LINEITEM_SF1 = 5_999_150     # mesh tier: whole table as one [1, R] shard
ORDERS_BUCKET = 1 << 21      # shape_bucket(1,500,000): the join build side


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tpu_target(topo):
    """Trace for the TPU (kernel choice, interpret=False) and keep the
    persistent compile cache out of it: an executable compiled for a
    described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with force_platform("tpu"):
        yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(sharding):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)
    return make


def _like(tree, sharding):
    """Shapes of `tree` (arrays or ShapeDtypeStructs) on `sharding`."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args, **kwargs):
    return fn.lower(*args, **kwargs).compile()


# -- segment aggregation kernels --------------------------------------------

G_BUCKETS = [8, 128, 512, 1024, ss._MAX_PALLAS_G]


@pytest.mark.parametrize("G", G_BUCKETS)
@pytest.mark.parametrize("kernel,dtype", [
    (ss._pallas_segsum_f32, jnp.float32),
    (ss._pallas_segsum_i64, jnp.int64),
], ids=["f32", "i64"])
def test_pallas_segment_sum_compiles(one_chip, tpu_target, kernel, dtype, G):
    s = _sds(one_chip)
    c = _compile(kernel, s((R,), dtype), s((R,), jnp.int32),
                 G=G, Gp=ss._gp(G))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("fn,dtype", [
    (ss.segment_sum_f32, jnp.float32),
    (ss.segment_sum_i64, jnp.int64),
    (ss.segment_count, jnp.bool_),
], ids=["f32", "i64", "count"])
def test_above_cap_routes_to_xla(tpu_target, fn, dtype):
    """One bucket above the admitted cap never reaches the compiler as a
    Pallas kernel (it would be refused: vmem RESOURCE_EXHAUSTED)."""
    v = jax.ShapeDtypeStruct((R,), dtype)
    seg = jax.ShapeDtypeStruct((R,), jnp.int32)
    at_cap = str(jax.make_jaxpr(
        lambda a, b: fn(a, b, ss._MAX_PALLAS_G))(v, seg))
    above = str(jax.make_jaxpr(
        lambda a, b: fn(a, b, ss._MAX_PALLAS_G + 1))(v, seg))
    assert "pallas_call" in at_cap
    assert "pallas_call" not in above and "scatter" in above


# -- join kernels -----------------------------------------------------------

def test_probe_ranges_xla_compiles(one_chip, tpu_target):
    """The open-addressing table build + window-scan probe (what
    tidb_tpu_join_probe_mode=auto resolves to on a TPU)."""
    s = _sds(one_chip)
    _compile(hp.probe_ranges, s((1 << 18,), jnp.int64), s((R,), jnp.int64))
    assert hp.resolve_mode("auto") == "xla"


@pytest.mark.parametrize("kernel", [
    # any lax.sort with int64 keys costs the chip's compiler minutes
    # (~200 s for this one): not tier-1, run it with -m slow
    pytest.param("build_sort", marks=pytest.mark.slow),
    "direct_index", "probe_direct", "probe_sorted", "expand",
])
def test_join_kernel_compiles(one_chip, tpu_target, kernel):
    """lineitem ⋈ orders at SF1: build_sort + direct index over the
    orders bucket, probe_count and expand_tiles over a packed 1<<20-row
    probe batch. Seconds each since the prefix sums are blocked
    (ops/prefix.py): with a flat jnp.cumsum the direct index alone took
    the chip's compiler 48 s."""
    s = _sds(one_chip)
    B, N = ORDERS_BUCKET, R
    i64, b = jnp.int64, jnp.bool_
    scal = s((), i64)
    rng_bucket = 1 << 21  # SF1 o_orderkey domain (1,500,000) bucketed
    if kernel == "build_sort":
        _compile(jk._build_sort, (s((B,), i64),), (s((B,), b),), s((B,), b),
                 (s((B,), i64),), (s((B,), b),), (scal,), (scal,), (scal,),
                 modes=("int",), hash_mode=False)
    elif kernel == "direct_index":
        _compile(jk._build_direct_index, s((B,), i64), scal, scal,
                 rng_bucket=rng_bucket)
    elif kernel == "expand":
        _compile(jk._expand_tiles, s((N,), i64), s((N,), i64), s((N,), i64),
                 s((N,), i64), scal, (s((N,), i64),), (s((N,), b),),
                 (s((B,), i64),), (s((B,), b),),
                 n_tiles=1, tile_cap=N, build_cap=B, left=False,
                 with_probe_row=False, with_build_pos=False)
    else:
        direct = kernel == "probe_direct"
        firsts = s((rng_bucket + 1 if direct else 2,), i64)
        # called, not traced: eval_shape would memoize tracers in the
        # module's cache and fail every later join of this worker
        table = _like(jk.no_table(), one_chip)
        _compile(jk._probe_count, s((B,), i64), scal,
                 (s((N,), i64),), (s((N,), b),), s((N,), b),
                 (scal,), (scal,), (scal,), firsts, scal, scal, *table,
                 modes=("int",), hash_mode=False, left_pad=False,
                 direct=direct, probe="sorted")


# -- prefix sums ------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.int64, jnp.int32], ids=["i64", "i32"])
def test_prefix_cumsum_compiles_at_whole_table_length(one_chip, tpu_target,
                                                      dtype):
    """ops/prefix.cumsum over a whole SF1 lineitem column: seconds, where
    the flat jnp.cumsum costs the chip's compiler minutes."""
    import time

    from tidb_tpu.ops import prefix

    t0 = time.perf_counter()
    _compile(jax.jit(prefix.cumsum), _sds(one_chip)((LINEITEM_SF1,), dtype))
    assert time.perf_counter() - t0 < 60


# -- compaction -------------------------------------------------------------

def test_compaction_compiles_at_whole_table_length(one_chip, tpu_target):
    """parallel/fragment.py `_compact` over a whole SF1 lineitem shard
    into 3,145,728 slots (twice SF1's order keys, the target Q3's eager
    table was compacted to before it took its estimate), a column of
    every device type: ONE scatter, of the row numbers (`s32`: a 64-bit
    one would be a tuple of two u32 arrays), the int64 stack's two u32
    halves gathered, the float column in a stack of its own (this
    compiler keeps a float64 as two f32 and refuses its bitcast to
    int64), and no sort — it lowers a 64-bit scatter of this length
    through one."""
    import re

    from tidb_tpu.parallel.fragment import _compact

    sds, cap = _sds(one_chip), 3_145_728
    arrays = {"k.d": jnp.int64, "f.d": jnp.float64, "s.d": jnp.int32,
              "b.d": jnp.bool_}
    arrays.update({name[:2] + "v": jnp.bool_ for name in list(arrays)})
    text = _compile(
        jax.jit(lambda a, sel: _compact(a, sel, cap)),
        {n: sds((LINEITEM_SF1,), t) for n, t in arrays.items()},
        sds((LINEITEM_SF1,), jnp.bool_)).as_text()
    results = {opcode: [r.split("{")[0] for r, o in re.findall(
        r"= (\(.*?\)|\S+) ([a-z][\w-]*)\(", text) if o == opcode]
        for opcode in ("scatter", "gather", "sort")}
    assert results["scatter"] == [f"s32[{cap + 1}]"], results
    # two integer words and one row of five packed flags; the float
    assert sorted(results["gather"]) == (
        [f"f32[{cap}]"] * 2 + [f"u32[3,{cap}]"] * 2), results
    assert not results["sort"], results


# -- top-k ------------------------------------------------------------------

@pytest.mark.parametrize("n_keys", [
    1,
    # one lax.sort over seven operands with six int keys: the chip's
    # compiler needs many minutes for it (ROADMAP S3) — not tier-1
    pytest.param(2, marks=pytest.mark.slow),
], ids=["cut-single-key", "multi-key"])
def test_topk_merge_compiles(one_chip, tpu_target, n_keys):
    cap = 128
    state = _like(jax.eval_shape(
        lambda: tk.topk_init(cap, (False,) * n_keys,
                             (np.dtype("int64"), np.dtype("float64")))),
        one_chip)
    s = _sds(one_chip)
    pairs = tuple((s((CHUNK,), jnp.int32), s((CHUNK,), jnp.int64))
                  for _ in range(n_keys))
    payload = ((s((CHUNK,), jnp.int64), s((CHUNK,), jnp.bool_)),
               (s((CHUNK,), jnp.float64), s((CHUNK,), jnp.bool_)))
    _compile(tk._merge_topk, state, pairs, payload, s((CHUNK,), jnp.bool_),
             (True,) * n_keys)


# -- FoR decode -------------------------------------------------------------

def test_for_decode_compiles(one_chip, tpu_target):
    """Packed-batch frame-of-reference decode: 16 segments of 65536
    int16 payload rows against per-segment int64 bases."""
    from tidb_tpu.ops.segment_scan import make_segment_scan_fn
    from tidb_tpu.types import SQLType, TypeKind

    scan = make_segment_scan_fn([], [("c", SQLType(TypeKind.INT))],
                                seg_stride=1 << 16)
    s = _sds(one_chip)

    def run(data, valid, refs, sel):
        ch = scan(data, valid, refs, sel)
        return ch.columns["c"].data, ch.sel

    out = _compile(jax.jit(run), {"c": s((R,), jnp.int16)},
                   {"c": s((R,), jnp.bool_)}, {"c": s((16,), jnp.int64)},
                   s((R,), jnp.bool_))
    assert out.output_shardings is not None


# -- whole programs ---------------------------------------------------------

def _tpch(sf: float):
    from tidb_tpu.storage.catalog import Catalog
    from tidb_tpu.storage.tpch import load_tpch

    catalog = Catalog()
    load_tpch(catalog, sf=sf)
    return catalog


@pytest.fixture(scope="module")
def tiny_tpch():
    return _tpch(0.05)  # lineitem spans several 65536-row segments


@contextlib.contextmanager
def _capture(module, name):
    """Record the arguments of module.name calls (the engine's own
    planning decides stages/aggs/domains; the test only re-shapes)."""
    calls = []
    real = getattr(module, name)

    def spy(*a, **k):
        calls.append((a, k))
        return real(*a, **k)

    setattr(module, name, spy)
    try:
        yield calls
    finally:
        setattr(module, name, real)


def test_fused_scan_agg_program_compiles(one_chip, tpu_target, tiny_tpch):
    """Q6 through the fused segment-store tier (executor/pipeline.py):
    the program the engine planned at SF0.01, lowered at the packed
    1<<20-row batch the same plan stages at SF1."""
    from tidb_tpu.executor import pipeline as pl
    from tidb_tpu.session import Session
    from tidb_tpu.storage.tpch_queries import Q

    s = Session(catalog=tiny_tpch)
    staged = []
    real_chunks = pl.FusedScanAggExec._staged_chunks

    def spy_chunks(self, jobs):
        for ch in real_chunks(self, jobs):
            staged.append((self._seg_cap, ch))
            yield ch

    pl.FusedScanAggExec._staged_chunks = spy_chunks
    try:
        with force_platform("cpu"), \
                _capture(pl, "_make_fused_segment_fn") as made:
            s.query(Q["q6"][0])
    finally:
        pl.FusedScanAggExec._staged_chunks = real_chunks
    assert made and staged, "Q6 did not take the fused scan->agg path"
    (stages, col_types, group_exprs, aggs, domains, seg_cap), _ = made[-1]
    by_cap = {cap: ch for cap, ch in staged}
    assert seg_cap in by_cap, "no columnar segment batch was staged"
    data0, valid0, refs0, _sel = by_cap[seg_cap]
    assert refs0, "the segment batch carries no FoR-encoded column"
    from tidb_tpu.executor.aggregate import make_segment_kernel

    init_state, _u, _g = make_segment_kernel(group_exprs, aggs, domains)
    state = _like(jax.eval_shape(init_state), one_chip)
    fused = jax.jit(pl._make_fused_segment_fn(
        stages, col_types, group_exprs, aggs, domains, seg_cap),
        donate_argnums=0)
    sd = _sds(one_chip)
    # the served chunk (one segment per batch) and a 1<<20 packed
    # batch (16 segments through the program's internal scan)
    for n in (seg_cap, R):
        data = {u: sd((n,), a.dtype) for u, a in data0.items()}
        valid = {u: sd((n,), a.dtype) for u, a in valid0.items()}
        refs = {u: sd((n // seg_cap,), a.dtype) for u, a in refs0.items()}
        c = _compile(fused, state, data, valid, refs, sd((n,), jnp.bool_))
        assert "tpu_custom_call" in c.as_text()  # the Pallas segment sum


def _described_table(st, mesh, n_dev, rows):
    """(`st` as [n_dev, rows] parts on `mesh`, the shapes of its data,
    valid, sel and refs there): what a mesh fragment's maker and its
    jitted function take for one table."""
    import dataclasses

    from tidb_tpu.parallel.distsql import _SPEC

    sharded = _sds(NamedSharding(mesh, _SPEC))
    repl = NamedSharding(mesh, P())
    shapes = [{n: sharded((n_dev, rows), a.dtype) for n, a in st.data.items()},
              {n: sharded((n_dev, rows), a.dtype) for n, a in st.valid.items()},
              sharded((n_dev, rows), jnp.bool_),
              {n: jax.ShapeDtypeStruct((), np.int64, sharding=repl)
               for n in st.refs}]
    return dataclasses.replace(st, mesh=mesh, n_parts=n_dev,
                               rows_per_part=rows), shapes


@pytest.mark.parametrize("n_dev", [1, 4], ids=["1x1", "1x4"])
def test_mesh_q1_fragment_compiles(topo, tpu_target, tiny_tpch, n_dev):
    """Q1 as the mesh tier runs it: shard_map(scan -> filter -> segment
    agg -> psum merge) over the whole SF1 lineitem as [P, R/P]."""
    from tidb_tpu.parallel import make_mesh
    from tidb_tpu.parallel.distsql import make_agg_fragment

    (st, stages, group_exprs, aggs, domains), kw, _ = _planned_fragment(
        tiny_tpch, "make_agg_fragment")
    mesh = make_mesh(devices=topo.devices[:n_dev])
    described, shapes = _described_table(st, mesh, n_dev,
                                         -(-LINEITEM_SF1 // n_dev))
    fn = make_agg_fragment(described, stages, group_exprs, aggs, domains,
                           **kw)
    c = _compile(fn, *shapes)
    text = c.as_text()
    assert "tpu_custom_call" in text
    if n_dev > 1:
        assert "all-reduce" in text


EXCHANGE_SCOPES = [
    "exchange.probe/exchange.sort", "exchange.probe/exchange.scatter",
    "exchange.probe/exchange.all_to_all", "exchange.build/exchange.sort",
    "exchange.build/exchange.scatter", "exchange.build/exchange.all_to_all"]
# (maker, parts of the mesh) -> the program's name and its stages: a
# mesh of one part exchanges nothing
STAGE_SCOPES = {
    ("make_agg_fragment", 1): ("frag_scan_agg",
                               ["scan", "agg.update", "agg.merge"]),
    ("make_join_agg_fragment", 1): ("frag_join_agg", [
        "scan", "join.sort", "join.probe", "join.unsort", "join.gather",
        "agg.update", "agg.merge"]),
    ("make_join_agg_fragment", 4): ("frag_join_agg", [
        "scan", *EXCHANGE_SCOPES, "join.sort", "join.probe", "join.unsort",
        "join.gather", "agg.update", "agg.merge"]),
}
# a build-side column above the join, so that the gather has work
JOIN_SQL = ("select count(*), sum(l_quantity), max(o_totalprice) from lineitem"
            " join orders on l_orderkey = o_orderkey where o_totalprice > 100000")


def _planned_fragment(catalog, maker, n_dev=1):
    """(arguments, keywords, sharded tables) of the engine's own call of
    parallel/executor's `maker` for Q1 / JOIN_SQL on a CPU mesh of
    `n_dev` parts."""
    from tidb_tpu.parallel import executor as pe
    from tidb_tpu.parallel import make_mesh
    from tidb_tpu.session import Session
    from tidb_tpu.storage.tpch_queries import Q

    s = Session(catalog=catalog, mesh=make_mesh(devices=jax.devices()[:n_dev]))
    s.execute("set tidb_device_engine_mode = 'force'")
    with force_platform("cpu"), _capture(pe, maker) as made:
        s.query(Q["q1"][0] if maker == "make_agg_fragment" else JOIN_SQL)
    assert made, f"the statement did not take {maker}"
    args, kw = made[-1]
    return args, kw, [a for a in args if hasattr(a, "rows_per_part")]


@pytest.mark.parametrize("maker,n_dev", sorted(STAGE_SCOPES),
                         ids=lambda v: v if isinstance(v, str) else f"1x{v}")
def test_fragment_program_is_named_and_its_stages_are_scoped(tiny_tpch, maker,
                                                             n_dev):
    """What a device trace shows of a mesh fragment: the module is named
    for the fragment's kind (``jit_frag_join_agg``, not ``jit_per_shard``)
    and every op's metadata carries the stage that emitted it. What each
    mesh compiles: four parts exchange both join sides; one part holds
    no ``exchange.`` scope, no all-to-all, and no sort or scatter but the
    local join's and the aggregate's."""
    import re

    from tidb_tpu.parallel import executor as pe

    args, kw, tables = _planned_fragment(tiny_tpch, maker, n_dev)
    # lowered for the CPU the statement ran on, whatever the module's
    # other tests trace for: names and scopes are the platform's no more
    # than the plan's
    with force_platform("cpu"):
        fn = getattr(pe, maker)(*args, **kw)
        lowered = fn.lower(*[x for st in tables for x in
                             (st.data, st.valid, st.sel, st.refs)])
        text = lowered.as_text(debug_info=True)
        compiled = lowered.compile().as_text()
    name, scopes = STAGE_SCOPES[maker, n_dev]
    assert f"module @jit_{name} " in text
    # (a mesh of several parts lowers the per-part body as a function of
    # its own, whose locations start at the stage)
    prefix = f"jit({name})/" if n_dev == 1 else '"'
    for scope in scopes:
        assert f"{prefix}{scope}/" in text, scope
    if maker == "make_join_agg_fragment" and n_dev == 1:
        assert "exchange." not in text and "exchange." not in compiled
        assert "all_to_all" not in text and "all-to-all" not in compiled
        heavy = [line for line in compiled.splitlines()
                 if re.search(r'= (?:\(.*?\)|\S+) (sort|scatter)\(', line)]
        assert heavy  # the local join's two sorts at least
        for line in heavy:
            assert re.search(rf'op_name="jit\({name}\)/(join|agg)\.\w+/',
                             line), line
    elif n_dev > 1 and maker == "make_join_agg_fragment":
        assert "all-to-all" in compiled


def _ops_by_scope(text: str, scope_re: str) -> dict:
    """{groups of `scope_re` in an op's op_name: [(opcode, result type)]}
    over a compiled program's text, fused computations' bodies included."""
    import re

    ops = {}
    for line in text.splitlines():
        scope = re.search(r'op_name="[^"]*?' + scope_re + "/", line)
        op = re.search(r'= (\(.*?\)|\S+) ([a-z][\w-]*)\(', line)
        if scope and op:
            ops.setdefault(scope.groups(), []).append((op.group(2), op.group(1)))
    return ops


@pytest.mark.parametrize("n_dev", [1, 4], ids=["1x1", "1x4"])
def test_join_fragment_ranks_without_search(topo, tpu_target, tiny_tpch,
                                            n_dev):
    """What PR 26 bought, pinned in the chip's own compiled text: the
    local join (`join.sort`, `join.probe`, `join.unsort`) holds sorts and
    elementwise passes only — no `while` (a binary search kept as a loop,
    as the four-chip program had it), no gather (the same loop unrolled,
    as the one-chip program had it: 22-28 dependent rounds over every
    probe slot, 80% of a 10.3 s statement) and no scatter (which this
    compiler lowers through a sort of its own). The one gather a build
    column above the join needs stays under `join.gather`. Shapes: 2,048
    probe and 512 build rows a chip, where the compiler takes a sort in
    seconds."""
    from tidb_tpu.parallel import make_mesh
    from tidb_tpu.parallel.distsql import make_join_agg_fragment

    args, kw, (probe, build) = _planned_fragment(tiny_tpch,
                                                 "make_join_agg_fragment")
    mesh = make_mesh(devices=topo.devices[:n_dev])
    probe, p_shapes = _described_table(probe, mesh, n_dev, 2048)
    build, b_shapes = _described_table(build, mesh, n_dev, 512)
    fn = make_join_agg_fragment(probe, build, *args[2:], **kw)
    text = _compile(fn, *p_shapes, *b_shapes).as_text()
    by_scope = {stage: {o for o, _ in ops} for (stage,), ops in _ops_by_scope(
        text, r"jit\(frag_join_agg\)/[^\"]*?(join\.\w+)").items()}
    assert {"join.sort", "join.probe", "join.unsort",
            "join.gather"} <= set(by_scope), sorted(by_scope)
    assert "sort" in by_scope["join.sort"] and "sort" in by_scope["join.unsort"]
    for scope in ("join.sort", "join.probe", "join.unsort"):
        assert not by_scope[scope] & {"while", "gather", "scatter"}, (
            scope, by_scope[scope])
    assert "gather" in by_scope["join.gather"]
    if n_dev > 1:
        assert "all-to-all" in text


# -- general fragments (parallel/fragment.py compile_fragment) ---------------

def _general_fragments(catalog, sql, n_dev=1):
    """The compile_fragment programs the engine runs for `sql` on a CPU
    mesh of `n_dev` parts: [(FragmentProgram, argument shapes, growths,
    probe mode)], captured where DistFragmentExec dispatches them (after
    its capacity retries, so the growths are the ones that hold at this
    data size, knob for knob of that mesh's program: one part has no
    "exch" knob)."""
    from tidb_tpu.parallel import executor as pe
    from tidb_tpu.parallel import make_mesh
    from tidb_tpu.session import Session

    s = Session(catalog=catalog, mesh=make_mesh(devices=jax.devices()[:n_dev]))
    # a one-device CPU mesh routes joins to the host engine unless asked
    s.execute("set tidb_device_engine_mode = 'force'")
    got = []
    real = pe.DistFragmentExec._dispatch_retry

    def spy(self, prog, args, shapes_sig, types_sig, growths, *span):
        out, growths = real(self, prog, args, shapes_sig, types_sig, growths,
                            *span)
        got.append((prog, jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), args),
            growths, getattr(self.ctx, "join_probe_mode", None)))
        return out, growths

    pe.DistFragmentExec._dispatch_retry = spy
    try:
        with force_platform("cpu"):
            s.query(sql)
    finally:
        pe.DistFragmentExec._dispatch_retry = real
    assert got, "the statement did not take a compile_fragment program"
    return got


def _described_fragment(topo, prog, shapes, growths, probe_mode, n_dev=1):
    """(jitted fragment, arguments) of a program captured on a CPU mesh
    of n_dev parts, on a 1 x n_dev mesh of the described chips."""
    from tidb_tpu.parallel import make_mesh
    from tidb_tpu.parallel.fragment import _SPEC, compile_fragment

    mesh = make_mesh(devices=topo.devices[:n_dev])
    again = compile_fragment(prog.agg, mesh, n_dev, topn=prog.topn)
    specs = ([_SPEC, _SPEC, _SPEC, P()] * len(prog.sources)
             + [P()] * 3 * len(prog.broadcasts))

    def place(a, spec):
        shape = a.shape
        if spec is _SPEC:
            assert shape[0] == n_dev, (shape, n_dev)
        return jax.ShapeDtypeStruct(shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    args = [jax.tree_util.tree_map(lambda a, sp=sp: place(a, sp), sh)
            for sh, sp in zip(shapes, specs)]
    return again.build_fn(growths, probe_mode=probe_mode), args


def _sorts(jaxpr):
    """How many lax.sort equations a program holds, nested jaxprs
    included."""
    n = 0
    for e in jaxpr.eqns:
        n += e.primitive.name == "sort"
        for v in e.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                n += _sorts(sub)
    return n


# What a sort costs the chip's compiler at whole-table length (sandbox,
# libtpu 0.0.34): 30-65 s from 65536 rows up whatever its key width (and
# 104 s at 32768). A statement whose first execution must fit a 1200 s
# smoke can afford a handful; Q18's inner aggregate, which the smoke
# runs, holds 1 on one chip (3 on four: the exchange's argsort and the
# second sort-reduce).
SORT_BUDGET = 4


_S3 = pytest.mark.xfail(strict=True, reason="ROADMAP S3: on four chips "
                        "Q3's general fragment holds 10 sorts; its first "
                        "execution outlasts the smoke's 1200 s")
# Q18 whole is ONE program on one chip since PR 35 (the IN-subquery's
# GROUP BY and HAVING compiled in): 6 sorts traced — the eager partial,
# the subquery's sort-reduce (the compiler shares that sort with the
# eager partial's: lineitem is one argument), three joins' merged ranks,
# the root aggregate. Over the budget, yet the chip compiled it in
# 141.6 s (PR 35, PERF.md section 6; 249.9 s here, for a described v5e)
# where Q3's 4 sorts take 384 s: the count of sorts is the smoke's
# guard; the price is in the operands of each (with the root's five keys
# as tie-breaks, 14 operands, the same program took 1,685.9 s here and
# gave the chip's client no answer in 600 s: PERF.md section 7 (10)).
_S3_Q18 = pytest.mark.xfail(strict=True, reason="ROADMAP S3: Q18's one "
                            "general fragment holds 6 sorts on one chip, "
                            "over SORT_BUDGET (cold on the chip: one "
                            "compile of 141.6 s, PR 35)")


# Q9 whole is ONE program on one chip: 6 sorts traced, its
# five joins' merged ranks and the root's sort-reduce; over the budget,
# yet it answered cold on the chip in 344.8 s, 338.5 s of it one compile
# (PERF.md section 6)
_S3_Q9 = pytest.mark.xfail(strict=True, reason="ROADMAP S3: Q9's one "
                           "general fragment holds 6 sorts on one chip, "
                           "over SORT_BUDGET (cold on the chip: one "
                           "compile of 338.5 s)")


@pytest.mark.parametrize("q,n_dev", [
    ("q18_inner", 1), ("q3", 1), pytest.param("q3", 4, marks=_S3),
    pytest.param("q18", 1, marks=_S3_Q18), pytest.param("q9", 1, marks=_S3_Q9)])
def test_general_fragment_fits_a_cold_statement(topo, tpu_target, tiny_tpch, q,
                                                n_dev):
    """Strict: when S3 brings Q3's / Q18's programs under the budget,
    their cases turn green and they belong in chip_smoke.py's list.
    Q3 on ONE chip is under it since PR 29 (a mesh of one part exchanges
    nothing, so the argsort of every repartition is gone) and AT it since
    PR 32: with its filters estimated from the bulk load's record the
    planner pre-aggregates lineitem under the joins, a fourth sort. The
    chip was asked (PR 32, PERF.md section 6): a cold Q3 answers in
    395-418 s, its program compiling in 383-405 (the parent's three
    sorts: 702 s, twice); it is in the smoke's list. PR 33's merged rank
    keeps the count: the one sort of both sides takes the place of the
    build's own, join for join (cold on the chip: 384 s, 389 to the
    answer), and the two strict xfails stay (10 and 6: since PR 35 Q18's
    6 are ONE program's, counted here as `[6]`, no longer `[1, 5]`). Q9
    is a third: ONE program of 6 sorts, cold on the chip in
    344.8 s."""
    from chip_smoke import Q18_INNER_SQL
    from tidb_tpu.storage.tpch_queries import Q

    sql = Q18_INNER_SQL if q == "q18_inner" else Q[q][0]
    counts = []
    for prog, shapes, growths, mode in _general_fragments(tiny_tpch, sql,
                                                          n_dev):
        fn, args = _described_fragment(topo, prog, shapes, growths, mode,
                                       n_dev)
        counts.append(_sorts(jax.make_jaxpr(fn)(*args).jaxpr))
        if q == "q18_inner":
            _one_program_cold(tiny_tpch, prog, growths)
    assert sum(counts) <= SORT_BUDGET, counts


@pytest.fixture(scope="module")
def speck_tpch():
    return _tpch(0.001)  # 6,000 lineitem rows


@pytest.fixture(scope="module")
def speck_programs(topo, tpu_target, speck_tpch):
    """(q, n_dev) -> [(FragmentProgram, its built function, its compiled
    text)] of the statement's general fragments at SF0.001, where the
    compiler takes a program's sorts in seconds (Q3: 15 s the whole
    program; SF0.01: 1,160 s; SF0.05: 683 s); compiled once a module."""
    from tidb_tpu.storage.tpch_queries import Q

    done = {}

    def get(q, n_dev):
        if (q, n_dev) not in done:
            done[q, n_dev] = []
            for prog, shapes, growths, mode in _general_fragments(
                    speck_tpch, Q[q][0], n_dev):
                fn, args = _described_fragment(topo, prog, shapes, growths,
                                               mode, n_dev)
                done[q, n_dev].append(
                    (prog, fn, _compile(fn, *args).as_text()))
        return done[q, n_dev]

    return get


@pytest.mark.parametrize("n_dev", [1, 4], ids=["1x1", "1x4"])
def test_q3_fragment_joins_rank_without_search(speck_programs, n_dev):
    """What PR 33 bought, pinned in the chip's own compiled text as
    `test_join_fragment_ranks_without_search` pins PR 26's: under the
    default probe mode each of Q3's two joins ranks its probe slots by
    ONE sort of both sides (`join<j>/join.build`) and reads the ranges
    off the merged order (`join<j>/join.probe`): no `while` (a binary
    search, 2 x 21 rounds of a gather over every probe slot: 5.1 s of a
    9.4 s statement; or the table probe's 32 rounds), no `conditional`
    (the table with the whole search kept in its other arm), and what
    is scattered back to probe-slot order is 32 bits wide (a 64-bit
    scatter is a tuple of two u32 arrays in this text, and costs ten
    times the 32-bit one on the chip: PERF.md section 5)."""
    import re

    (prog, fn, text), = speck_programs("q3", n_dev)
    assert prog.n_join == 2
    assert fn.join_probes == ["merge", "merge"]
    ops = _ops_by_scope(text, r"(join\d+)/join\.(\w+)")  # by (join, stage)
    for j in ("join0", "join1"):
        build, probe = ops[j, "build"], ops[j, "probe"]
        assert [o for o, _ in build + probe].count("sort") == 1, (j, build)
        assert "sort" in [o for o, _ in build]
        for o, result in build + probe:
            assert o not in ("while", "conditional", "gather"), (j, o)
            if o == "scatter":
                assert re.match(r"(s32|u32|pred)\[", result), (j, result)
        assert "scatter" in [o for o, _ in probe]
    if n_dev > 1:
        assert "all-to-all" in text


@pytest.mark.parametrize("q,n_dev", [("q3", 1), ("q3", 4), ("q18", 1),
                                     ("q18", 4)])
def test_fragment_compactions_scatter_row_numbers_only(speck_programs, q,
                                                       n_dev):
    """What PR 36 bought, pinned in the chip's own compiled text: under
    every compaction scope of the general fragment (`join<j>/join.compact`,
    `subq<k>/compact`, `agg.compact`) a `scatter` writes 32-bit row
    numbers — ONE `s32|u32|pred[` result, never the tuple of two u32
    arrays that a 64-bit scatter is in this text and that this compiler
    lowers through a sort (508 ms a column at 6.0M rows against 36-45
    for the row numbers: PERF.md section 6) — and the columns follow by
    ONE gather a compaction, of all of them as an int64 stack: in this
    text its two u32 halves, `u32[rows, cap]` each (tests/test_compact.py
    holds the traced program to one). Q18 on one part holds its
    subquery's compaction (`subq0/compact`); on four the subquery is a
    program of its own."""
    import re

    scopes = r"((?:join\d+/join|subq\d+/|agg)\.?compact)"
    seen = set()
    for prog, fn, text in speck_programs(q, n_dev):
        ops = _ops_by_scope(text, scopes)
        # (none in Q18's subquery as a program of its own, on four parts)
        assert all(prog.growth_kinds[k] == "compact" for k in fn.compactions)
        in_scopes = [op for stage in ops.values() for op in stage]
        for o, result in in_scopes:
            if o == "scatter":
                assert re.match(r"(s32|u32|pred)\[", result), result
        n = len(fn.compactions)
        assert [o for o, _ in in_scopes].count("scatter") == n, sorted(ops)
        gathers = [result for o, result in in_scopes if o == "gather"]
        assert len(gathers) == 2 * n, (gathers, fn.compactions)
        assert all(re.match(r"u32\[\d+,\d+\]", g) for g in gathers), gathers
        seen |= {scope for (scope,) in ops}
    # lineitem's eager partial, join1's probe side, holds the key's
    # estimated groups, under twice their estimate: nothing compacts it
    # (on one part join1 compacts nothing; on four, its output)
    assert ("join1/join.compact" in seen) == (n_dev > 1), seen
    assert ("subq0/compact" in seen) == (q == "q18" and n_dev == 1), seen


@pytest.mark.parametrize("q,n_dev", [("q3", 1), ("q3", 4), ("q18", 1),
                                     ("q18", 4)])
def test_fragment_joins_expand_by_32bit_marks_and_stacked_gathers(
        speck_programs, q, n_dev):
    """What PR 38 bought, pinned in the chip's own compiled text: under
    every `join<j>/join.expand` a `scatter` writes 32-bit boundary marks
    (`tile_positions`) — never the tuple of two u32 arrays that a 64-bit
    scatter-add is in this text, lowered through a sort (230 ms a
    statement over Q3's 3.1M slots: PERF.md section 5) — and under every
    `join<j>/join.gather` the gathers are an int64 stack's two u32 halves a
    side (`_take_stacked`) and a float64 stack's two f32 halves where a
    float rides: no validity mask (`pred`) and no column by itself
    (tests/test_join_program_text.py counts the stacks a side). Q18's
    semi-join (join2) expands nothing: no op of it under
    either scope, and under its `join.exists` no scatter and no gather."""
    import re

    exists = {"q3": (), "q18": (2,)}[q]
    for prog, fn, text in speck_programs(q, n_dev):
        ops = _ops_by_scope(text, r"(join\d+)/join\.(expand|gather|exists)")
        # (on four parts Q18's subquery is a program of its own, no join)
        assert prog.n_exists == len([j for j in exists if j < prog.n_join])
        for j in range(prog.n_join):
            if j in exists:
                assert not ops.get((f"join{j}", "expand")), (j, sorted(ops))
                assert not ops.get((f"join{j}", "gather")), (j, sorted(ops))
                assert not [o for o, _ in ops.get((f"join{j}", "exists"), [])
                            if o in ("scatter", "gather")], (j, ops)
                continue
            expand = ops.get((f"join{j}", "expand"), [])
            scatters = [r for o, r in expand if o == "scatter"]
            assert len(scatters) == 1, (j, expand)
            assert re.match(r"(s32|u32)\[", scatters[0]), (j, scatters)
            gathers = [r for o, r in ops.get((f"join{j}", "gather"), [])
                       if o == "gather"]
            kinds = [g[:3] for g in gathers]
            assert 2 <= kinds.count("u32") <= 4, (j, gathers)
            assert kinds.count("u32") + kinds.count("f32") == len(kinds), (
                j, gathers)
            assert kinds.count("f32") <= 4, (j, gathers)


@pytest.mark.parametrize("n_dev", [1, 4], ids=["1x1", "1x4"])
def test_q22_compiles_its_scalar_subquery_and_existence_join(speck_programs,
                                                            n_dev):
    """In the chip's own compiled text, Q22 whole is ONE program —
    its AVG's masked sum and count under `scalar0` (an all-reduce across
    four chips), the bounds' 64-bit division, and the NOT EXISTS decided
    from the rank's count: under `join0/join.exists` no scatter and no
    gather, and no op of the join under `join.expand` or `join.gather`."""
    (prog, fn, text), = speck_programs("q22", n_dev)
    assert prog.n_scalar == 1 and prog.n_exists == 1 and prog.n_join == 1
    assert "expand" not in prog.growth_kinds
    ops = _ops_by_scope(text, r"(join0)/join\.(expand|gather|exists)")
    assert not ops.get(("join0", "expand")) and not ops.get(("join0", "gather"))
    assert not [o for o, _ in ops.get(("join0", "exists"), [])
                if o in ("scatter", "gather")], ops
    assert "/scalar0/" in text
    assert ("all-reduce" in text) == (n_dev > 1)


def _one_program_cold(catalog, prog, growths):
    """Q18's inner aggregate compiles one program a cold statement: no
    capacity knob grew (each growth is a compile and a launch thrown
    away), because the group table's slots come from the key's distinct
    count as the bulk load sketched it — a quarter over it, where the
    fallback n ** 0.75 sized it a twelfth of the groups at SF1 and an
    eighth here."""
    import re

    from tidb_tpu.parallel.fragment import _Compiler
    from tidb_tpu.statistics import column_ndv

    assert growths == prog.growth_defaults, (growths, prog.growth_defaults)
    lineitem = catalog.table("test", "lineitem")
    ndv = column_ndv(lineitem, "l_orderkey")
    orders = catalog.table("test", "orders").n
    assert abs(ndv - orders) / orders < 0.1
    caps = [int(c) for c in re.findall(r"cap\d+:(\d+)", prog.sig)]
    assert caps[-1] == int(np.ceil(_Compiler.NDV_HEADROOM * ndv))
    assert orders <= caps[-1] < 2 * lineitem.n ** 0.75 * 8


@pytest.fixture(scope="module")
def sf1_tpch():
    return _tpch(1.0)


@pytest.mark.slow
@pytest.mark.parametrize("stmt,n_dev", [
    ("q18_inner", 1), ("q18_inner", 4), ("q3", 1), ("q18", 1)])
def test_general_fragment_compiles_at_sf1(topo, tpu_target, sf1_tpch, stmt,
                                          n_dev):
    """The mesh tier's compile_fragment programs as planned at SF1,
    compiled for the described chip(s) at the shapes and capacities the
    SF1 run settled on: chip_smoke.py's general-fragment statement (Q18's
    inner aggregate, one chip and four: accepted, 515 s and 276 s in the
    sandbox with PR 22's capacities; since PR 28 the cold statement's
    only program, its group table 1.25 x the sketched 1.49M order keys),
    and the whole of Q3 and Q18: the two together were still compiling
    after 90 minutes in the sandbox (PR 22). Q3's program as PR 32
    leaves it (benchmark data, SF1 shapes) compiles here in 626 s, on
    the chip in 383-405 (PERF.md section 6); Q18's one program (PR 35)
    compiles on the chip in 141.6 s and here, from the benchmark's data
    without running the statement, in 249.9 s. Not tier-1: run it with
    -m slow."""
    from chip_smoke import Q18_INNER_SQL
    from tidb_tpu.storage.tpch_queries import Q

    sql = Q18_INNER_SQL if stmt == "q18_inner" else Q[stmt][0]
    for prog, shapes, growths, mode in _general_fragments(sf1_tpch, sql,
                                                          n_dev):
        fn, args = _described_fragment(topo, prog, shapes, growths, mode,
                                       n_dev)
        text = _compile(fn, *args).as_text()
        if n_dev > 1:
            assert "all-to-all" in text


def _collective(topo, fn, dtype):
    from tidb_tpu.parallel import make_mesh
    from tidb_tpu.parallel.distsql import _AXES, _SPEC

    mesh = make_mesh(devices=topo.devices[:4])
    prog = jax.jit(jax.shard_map(
        lambda v: fn(v[0], _AXES), mesh=mesh,
        in_specs=(_SPEC,), out_specs=P(), check_vma=False))
    x = jax.ShapeDtypeStruct((4, 2048), dtype,
                             sharding=NamedSharding(mesh, _SPEC))
    return _compile(prog, x).as_text()


@pytest.mark.parametrize("dtype", [jnp.int64, jnp.float64, jnp.int32],
                         ids=["i64", "f64", "i32"])
@pytest.mark.parametrize("op", ["pmax", "pmin", "psum"])
def test_merge_collectives_compile_on_four_chips(topo, tpu_target, op, dtype):
    """merge_state's reductions over both mesh axes, as distsql issues
    them (64-bit min/max gather instead of all-reducing)."""
    from tidb_tpu.parallel import distsql

    fn = jax.lax.psum if op == "psum" else getattr(distsql, op)
    text = _collective(topo, fn, dtype)
    assert "all-reduce" in text or "all-gather" in text


@pytest.mark.xfail(strict=True, raises=jax.errors.JaxRuntimeError,
                   reason="Supported lowering only of Sum all reduce")
@pytest.mark.parametrize("op", ["pmax", "pmin"])
def test_lax_minmax_of_64bit_is_refused(topo, tpu_target, op):
    """Why distsql.pmax/pmin exist: the chip's compiler refuses a 64-bit
    max/min all-reduce. Strict: when this compiles, delete them."""
    _collective(topo, getattr(jax.lax, op), jnp.int64)
