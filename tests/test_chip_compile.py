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
    with ss.force_platform("tpu"):
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
    _compile(hp.probe_ranges, s((1 << 18,), jnp.int64), s((R,), jnp.int64),
             use_pallas=False)


@pytest.mark.xfail(strict=True, raises=ValueError,
                   reason=hp.PALLAS_PROBE_REFUSAL)
def test_probe_pallas_is_refused(one_chip, tpu_target):
    """hash_probe._probe_pallas does not lower for the chip
    (`k = keys_ref[pos]` is a vector gather from a VMEM ref). Strict:
    when it starts compiling, ROADMAP D3 decides to keep it."""
    s = _sds(one_chip)
    _compile(hp.probe_ranges, s((1 << 12,), jnp.int64),
             s((1 << 14,), jnp.int64), use_pallas=True)


def test_probe_mode_pallas_raises_typed_on_tpu(tpu_target):
    from tidb_tpu.errors import UnsupportedError

    with pytest.raises(UnsupportedError, match=hp.PALLAS_PROBE_REFUSAL):
        hp.resolve_mode("pallas")
    assert hp.resolve_mode("auto") == "xla"  # never picked automatically


@pytest.mark.parametrize("kernel", [
    # any lax.sort with int64 keys costs the chip's compiler minutes
    # (~200 s for this one): not tier-1, run it with -m slow
    pytest.param("build_sort", marks=pytest.mark.slow),
    "direct_index", "probe_direct", "probe_sorted", "expand",
])
def test_join_kernel_compiles(one_chip, tpu_target, kernel):
    """lineitem ⋈ orders at SF1: build_sort + direct index over the
    orders bucket, probe_count and expand_tiles over one served chunk.
    The probe side is CHUNK rows, not 1<<20: XLA:TPU takes ~70 s to
    compile a flat 1<<20-row int64 cumsum (6 s at 65536) — findings
    recorded in ROADMAP S3, too slow to repeat per shape in tier-1."""
    s = _sds(one_chip)
    B, N = ORDERS_BUCKET, CHUNK
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
        table = _like(jax.eval_shape(jk.no_table), one_chip)
        _compile(jk._probe_count, s((B,), i64), scal,
                 (s((N,), i64),), (s((N,), b),), s((N,), b),
                 (scal,), (scal,), (scal,), firsts, scal, scal, *table,
                 modes=("int",), hash_mode=False, left_pad=False,
                 direct=direct, probe="sorted")


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

@pytest.fixture(scope="module")
def tiny_tpch():
    from tidb_tpu.storage.catalog import Catalog
    from tidb_tpu.storage.tpch import load_tpch

    catalog = Catalog()
    load_tpch(catalog, sf=0.05)  # lineitem spans several 65536-row segments
    return catalog


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
        with ss.force_platform("cpu"), \
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
    # the served chunk (one segment per batch) and bench.py's 1<<20
    # packed batch (16 segments through the program's internal scan)
    for n in (seg_cap, R):
        data = {u: sd((n,), a.dtype) for u, a in data0.items()}
        valid = {u: sd((n,), a.dtype) for u, a in valid0.items()}
        refs = {u: sd((n // seg_cap,), a.dtype) for u, a in refs0.items()}
        c = _compile(fused, state, data, valid, refs, sd((n,), jnp.bool_))
        assert "tpu_custom_call" in c.as_text()  # the Pallas segment sum


def _q1_fragment_args(catalog, mesh1):
    """make_agg_fragment's arguments as the engine plans Q1 on a mesh."""
    from tidb_tpu.parallel import executor as pe
    from tidb_tpu.session import Session
    from tidb_tpu.storage.tpch_queries import Q

    s = Session(catalog=catalog, mesh=mesh1)
    with _capture(pe, "make_agg_fragment") as made:
        s.query(Q["q1"][0])
    assert made, "Q1 did not take the mesh scan->agg fragment"
    return made[-1]


@pytest.mark.parametrize("n_dev", [1, 4], ids=["1x1", "1x4"])
def test_mesh_q1_fragment_compiles(topo, tpu_target, tiny_tpch, n_dev):
    """Q1 as the mesh tier runs it: shard_map(scan -> filter -> segment
    agg -> psum merge) over the whole SF1 lineitem as [P, R/P]."""
    import dataclasses

    from tidb_tpu.parallel import make_mesh
    from tidb_tpu.parallel.distsql import _SPEC, make_agg_fragment

    (st, stages, group_exprs, aggs, domains), kw = _q1_fragment_args(
        tiny_tpch, make_mesh(devices=jax.devices()[:1]))
    mesh = make_mesh(devices=topo.devices[:n_dev])
    rows = -(-LINEITEM_SF1 // n_dev)
    sharded = _sds(NamedSharding(mesh, _SPEC))
    repl = NamedSharding(mesh, P())
    described = dataclasses.replace(st, mesh=mesh, n_parts=n_dev,
                                    rows_per_part=rows)
    fn = make_agg_fragment(described, stages, group_exprs, aggs, domains,
                           **kw)
    data = {n: sharded((n_dev, rows), a.dtype) for n, a in st.data.items()}
    valid = {n: sharded((n_dev, rows), a.dtype) for n, a in st.valid.items()}
    refs = {n: jax.ShapeDtypeStruct((), np.int64, sharding=repl)
            for n in st.refs}
    c = _compile(fn, data, valid, sharded((n_dev, rows), jnp.bool_), refs)
    text = c.as_text()
    assert "tpu_custom_call" in text
    if n_dev > 1:
        assert "all-reduce" in text


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
