"""Segment aggregation as a tiled one-hot multiply-and-sum (Pallas).

XLA lowers `acc.at[seg].add(vals)` to a serialized scatter on TPU; the
kernels here reduce each tile of 1,024 rows against a one-hot of its
segment ids instead. It is NOT a matmul: the one-hot is built and
contracted on the VPU (a broadcast multiply and a sum over the tile),
the MXU is not used:

    onehot[s, l, g] = (seg[s, l] == g)            # [8, 128, Gp] from iota
    partial[g]      = sum_{s,l} vals[s, l] * onehot[s, l, g]
    out[g]         += partial                     # accumulated across the grid

On the chip the int64 kernel is 96% of the scan cell's device time at
0.15% of the HBM roofline (`tpch_sf1.scan`, `scan_agg_roofline`
0.14618, ledger, PR 29; 29.4 ms a call over 6.0M rows): ROADMAP B3.

Exactness: f32 accumulation is integer-exact below 2^24, so
  * segment_count is EXACT for any chunk up to 2^24 rows (per-tile
    partial <= TILE, total <= R) — counts dispatch to Pallas on TPU;
  * segment_sum_f32 matches XLA f32 summation to reordering — used for
    FLOAT aggregates where SQL float semantics already permit it;
  * int64/decimal sums run the byte-limb kernel (_pallas_segsum_i64),
    exact by construction.

Group count G is padded to the 128-lane boundary; segment ids >= G are
the caller's NULL/overflow slots and pad lanes simply accumulate zeros
that are sliced off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from tidb_tpu.utils.device import note_placement, target_platform

__all__ = ["segment_count", "segment_sum_f32", "segment_sum_i64",
           "pallas_enabled", "set_pallas_enabled", "xla_segment_sum",
           "pallas_interpret"]

_TILE = 1024
# Largest group count the Pallas kernels take; above it the XLA scatter
# runs. The [8, 128, Gp] one-hot must fit Mosaic's 16 MiB scoped VMEM:
# the v5e compiler accepts the f32/count kernel up to Gp = 3584 and the
# int64 limb kernel up to Gp = 4096, and refuses the next 128-lane
# buckets (RESOURCE_EXHAUSTED ... vmem). 2048 leaves both a margin;
# tests/test_chip_compile.py compiles both kernels at this cap.
_MAX_PALLAS_G = 2048

_enabled: bool | None = None  # None = auto (TPU backend only)


def set_pallas_enabled(v: bool | None) -> None:
    global _enabled
    _enabled = v


def pallas_enabled() -> bool:
    if _enabled is not None:
        return _enabled
    return target_platform() == "tpu"


def pallas_interpret() -> bool:
    """The ``interpret=`` of every pallas_call in ops/. A TPU always
    compiles the kernel: one the chip's compiler refuses raises, it is
    never run interpreted and never handed to the XLA reference. On the
    CPU a Pallas kernel is reached only when the caller asked for it
    explicitly (set_pallas_enabled(True) — the tier-1 tests), and
    there it interprets. The choice goes to the
    placement log when chip_smoke.py has switched that on."""
    p = target_platform()
    if p == "tpu":
        note_placement("pallas", None, platform="tpu")
        return False
    if p == "cpu":
        note_placement("pallas", None, platform="interpret")
        return True
    raise NotImplementedError(
        f"the Pallas kernels target TPU; no lowering for platform {p!r}")


def xla_segment_sum(vals: jax.Array, seg: jax.Array, G: int) -> jax.Array:
    """Reference path: XLA scatter-add. A single-segment (global) sum is
    a masked reduction instead — every row collides on one slot and
    XLA:CPU serializes colliding scatter updates (~35 ms per 2^17-row
    chunk, measured driving count/sum over a join output). The mask
    keeps the scatter contract: seg ids >= G (callers' NULL/overflow
    drop slots) still contribute nothing."""
    if G == 1:
        return jnp.sum(jnp.where(seg == 0, vals, 0))[None]
    return jnp.zeros(G, dtype=vals.dtype).at[seg].add(vals)


_SUB = 8  # sublanes per tile row; tile is [_SUB, 128] = _TILE elements
_LANES = 128


def _pad_tile(x: jax.Array, fill) -> jax.Array:
    """[R] -> [n_tiles, 8, 128] (Mosaic's (8, 128) f32 tiling)."""
    R = x.shape[0]
    Rp = ((R + _TILE - 1) // _TILE) * _TILE
    if Rp != R:
        x = jnp.concatenate([x, jnp.full(Rp - R, fill, dtype=x.dtype)])
    return x.reshape(Rp // _TILE, _SUB, _LANES)


@functools.partial(jax.jit, static_argnames=("G", "Gp"))
def _pallas_segsum_f32(vals: jax.Array, seg: jax.Array, G: int, Gp: int) -> jax.Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    vals2 = _pad_tile(vals.astype(jnp.float32), 0.0)
    seg2 = _pad_tile(seg.astype(jnp.int32), Gp)  # pad rows land off-range
    n_tiles = vals2.shape[0]

    def kernel(vals_ref, seg_ref, out_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        s = seg_ref[0]  # [8, 128] int32
        v = vals_ref[0]  # [8, 128] f32
        # one-hot over a new trailing group axis, contracted on the VPU;
        # [8, 128, Gp] stays well inside VMEM for the segment-agg G range
        gid = jax.lax.broadcasted_iota(jnp.int32, (_SUB, _LANES, Gp), 2)
        onehot = (s[:, :, None] == gid).astype(jnp.float32)
        part = jnp.sum(v[:, :, None] * onehot, axis=(0, 1))  # [Gp]
        out_ref[:] = out_ref[:] + part[None, :]

    # trace the kernel with x64 OFF: the engine enables x64 globally
    # (decimals are scaled int64), but Mosaic can't legalize the i64
    # constants that leak into index maps / grid bookkeeping
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((1, Gp), jnp.float32),
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((1, _SUB, _LANES), lambda i: (i, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, _SUB, _LANES), lambda i: (i, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, Gp), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM),
            interpret=pallas_interpret(),
        )(vals2, seg2)
    return out[0, :G]


def _gp(G: int) -> int:
    return max(((G + 127) // 128) * 128, 128)


def segment_sum_f32(vals: jax.Array, seg: jax.Array, G: int) -> jax.Array:
    """Float32 segment sum; Pallas on TPU, XLA elsewhere."""
    if not pallas_enabled() or G > _MAX_PALLAS_G:
        return xla_segment_sum(vals.astype(jnp.float32), seg, G)
    return _pallas_segsum_f32(vals, seg, G, _gp(G))


_N_LIMBS = 8
_LIMB_BITS = 8


@functools.partial(jax.jit, static_argnames=("G", "Gp"))
def _pallas_segsum_i64(vals: jax.Array, seg: jax.Array, G: int, Gp: int) -> jax.Array:
    """EXACT int64 (decimal) segment sum on the Pallas path.

    The value splits into 8 unsigned byte limbs OUTSIDE the kernel (the
    kernel traces with x64 off — Mosaic cannot legalize i64); each limb
    accumulates in int32 on the VPU against the shared one-hot, and the
    limb sums recombine in uint64 with natural wraparound — exact for
    any int64 inputs because two's-complement addition is mod 2^64.
    Exactness bound: per-limb sums must fit int32, i.e. 255 * R < 2^31
    (R < 2^23 rows), enforced by the dispatcher."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    u = jax.lax.bitcast_convert_type(vals.astype(jnp.int64), jnp.uint64)
    limbs = [
        ((u >> jnp.uint64(_LIMB_BITS * j)) & jnp.uint64(0xFF)).astype(jnp.int32)
        for j in range(_N_LIMBS)
    ]
    limbs2 = jnp.stack([_pad_tile(l, 0) for l in limbs], axis=1)
    # [n_tiles, 8 limbs, 8 sub, 128 lanes]
    seg2 = _pad_tile(seg.astype(jnp.int32), Gp)
    n_tiles = seg2.shape[0]

    def kernel(limbs_ref, seg_ref, out_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        s = seg_ref[0]  # [8, 128] int32
        gid = jax.lax.broadcasted_iota(jnp.int32, (_SUB, _LANES, Gp), 2)
        onehot = (s[:, :, None] == gid).astype(jnp.int32)
        for j in range(_N_LIMBS):
            v = limbs_ref[0, j]  # [8, 128] int32
            part = jnp.sum(v[:, :, None] * onehot, axis=(0, 1))  # [Gp]
            out_ref[j, :] = out_ref[j, :] + part

    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((_N_LIMBS, Gp), jnp.int32),
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((1, _N_LIMBS, _SUB, _LANES),
                             lambda i: (i, 0, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, _SUB, _LANES), lambda i: (i, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((_N_LIMBS, Gp), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM),
            interpret=pallas_interpret(),
        )(limbs2, seg2)
    # recombine: limb sums (int32, exact) widen to uint64, shift, add —
    # wraparound is exactly int64 addition's
    acc = jnp.zeros(Gp, dtype=jnp.uint64)
    for j in range(_N_LIMBS):
        acc = acc + (out[j].astype(jnp.uint64) << jnp.uint64(_LIMB_BITS * j))
    return jax.lax.bitcast_convert_type(acc, jnp.int64)[:G]


def segment_sum_i64(vals: jax.Array, seg: jax.Array, G: int) -> jax.Array:
    """Exact int64/decimal segment sum; Pallas limb kernel on TPU, XLA
    scatter elsewhere. Covers Q1's decimal sum_qty/sum_base_price/
    sum_disc_price/sum_charge accumulators."""
    if (not pallas_enabled() or G > _MAX_PALLAS_G
            or vals.shape[0] >= (1 << 23)):  # 255 * R < 2^31 limb bound
        return xla_segment_sum(vals.astype(jnp.int64), seg, G)
    return _pallas_segsum_i64(vals, seg, G, _gp(G))


def segment_count(mask: jax.Array, seg: jax.Array, G: int) -> jax.Array:
    """Count mask-true rows per segment, EXACT (counts < 2^24), int64.

    The hottest accumulator shape in segment aggregation: occ + one cnt
    per aggregate function all reduce a boolean through this. Against
    the XLA int64 scatter on the chip: not measured (the f32 kernel
    takes 6.9 ms a call over 6.0M rows in the one-chip join's
    `agg.update`, PERF.md section 5, PR 29)."""
    if (not pallas_enabled() or G > _MAX_PALLAS_G
            or mask.shape[0] >= (1 << 24)):  # f32 exactness bound
        return xla_segment_sum(mask.astype(jnp.int64), seg, G)
    f = _pallas_segsum_f32(mask.astype(jnp.float32), seg, G, _gp(G))
    return f.astype(jnp.int64)
