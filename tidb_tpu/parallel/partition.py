"""Sharded tables: the partition catalog (region-cache analogue).

A host Table is split row-wise into P equal fixed-capacity partitions,
one per mesh shard, padded to a static per-shard row capacity R. Layout
is [P, R] per column with the leading axis sharded over ("dcn","shard"),
so every fragment sees exactly one partition as a capacity-R Chunk and
XLA never moves base data — only exchange traffic crosses ICI.

Ref counterpart: distsql region splitting + tablecodec row layout; here
rows are born columnar and the "region boundary" is a static row range.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tidb_tpu.parallel.mesh import dcn_axis, shard_axis
from tidb_tpu.types import SQLType
from tidb_tpu.utils import tracing

__all__ = ["ShardedTable", "shard_table", "stream_batches", "table_bytes"]


@dataclass
class ShardedTable:
    """Columns as [P, R] device arrays sharded on axis 0 of `mesh`.

    With ``encode=True`` staging, integer-backed columns travel
    frame-of-reference encoded: ``data[name]`` holds ``value - ref`` in
    the narrowest of int8/int16/int32 that covers the column's valid
    range, and ``refs[name]`` carries the int64 base. Fragment programs
    decode (``stored + ref``, widened to the column's device repr)
    INSIDE the compiled program, so the narrow bytes are all that cross
    host→device — the columnar store's byte shrink applied to the
    distributed staging path (ISSUE 9 satellite / ROADMAP 5a)."""

    mesh: Mesh
    n_parts: int
    rows_per_part: int
    total_rows: int
    data: Dict[str, jax.Array]      # name -> [P, R]
    valid: Dict[str, jax.Array]     # name -> [P, R] bool
    sel: jax.Array                  # [P, R] bool: live rows
    types: Dict[str, SQLType]
    dicts: Dict[str, object]        # string dictionaries (host-side)
    # FoR bases for encoded columns (absent name = raw staging); np
    # scalars passed to fragments as ARGS so per-batch bases never bake
    # into a trace
    refs: Dict[str, np.int64] = field(default_factory=dict)
    # process-unique, never-recycled id: cache keys built from it can never
    # alias a different sharding the way id()-based keys can after GC
    serial: int = field(default_factory=itertools.count().__next__)



def table_bytes(table, columns: Optional[List[str]] = None) -> int:
    """Device bytes a full sharding of `table` would occupy (data +
    validity for the chosen columns)."""
    names = columns or [c.name for c in table.schema.columns]
    n = table.n
    total = 0
    for name in names:
        total += n * (table.data[name].dtype.itemsize + 1)  # + valid byte
    return total + n  # + sel mask


def _encode_staged(d: np.ndarray, v: np.ndarray, type_: SQLType):
    """(stored, ref) when FoR staging pays for this column slice, else
    (None, 0). Delegates the selection rule AND the NULL-pinning shift
    to columnar.encoding.encode_column — the ONE encoder whose payloads
    ops/segment_scan.decode_for decodes — keeping only the
    did-it-actually-shrink guard local (the segment store accepts
    same-width encodings; the staging path has nothing to gain)."""
    from tidb_tpu.columnar.encoding import INT_BACKED_KINDS, encode_column

    if type_.kind not in INT_BACKED_KINDS \
            or not np.issubdtype(d.dtype, np.integer) \
            or d.dtype.itemsize <= 1 or not v.any():
        return None, 0
    enc, stored = encode_column(d, v, type_)
    if enc.kind != "for" or stored.dtype.itemsize >= d.dtype.itemsize:
        return None, 0
    return stored, enc.ref


def stream_batches(table, mesh: Mesh, columns: Optional[List[str]],
                   rows_per_part: int, encode: bool = False):
    """Yield fixed-shape ShardedTable batches covering the whole table.

    The >HBM path (ref: SURVEY.md hard part 6 + the IndexLookUp double
    pipeline): batch b stages rows [b*P*R, (b+1)*P*R) as one [P, R]
    sharding. Every batch has identical shapes/types, so the compiled
    fragment is reused across batches, and jax's async dispatch overlaps
    batch k's compute with batch k+1's host->device staging."""
    n_parts = mesh.shape[dcn_axis] * mesh.shape[shard_axis]
    rows_per_batch = n_parts * rows_per_part
    n = table.n
    for start in range(0, max(n, 1), rows_per_batch):
        yield shard_table(table, mesh, columns=columns,
                          rows_per_part=rows_per_part,
                          row_range=(start, min(start + rows_per_batch, n)),
                          encode=encode)


def shard_table(table, mesh: Mesh, columns: Optional[List[str]] = None,
                rows_per_part: Optional[int] = None,
                row_range: Optional[tuple] = None,
                encode: bool = False) -> ShardedTable:
    """Partition a host Table (or a row range of it) across the mesh's
    (dcn x shard) grid. ``encode=True`` stages integer-backed columns
    FoR-encoded in narrow dtypes (see ShardedTable.refs). The whole of
    it (encode, pad, ``device_put``) is the span ``stage.upload`` with
    the count ``bytes`` of what was handed to ``device_put``."""
    with tracing.span("stage.upload"):
        n_parts = mesh.shape[dcn_axis] * mesh.shape[shard_axis]
        lo, hi = row_range if row_range is not None else (0, table.n)
        n = hi - lo
        R = rows_per_part or max((n + n_parts - 1) // n_parts, 1)
        if R * n_parts < n:
            raise ValueError(f"rows_per_part {R} too small for {n} rows / {n_parts} parts")
        names = columns or [c.name for c in table.schema.columns]
        spec = NamedSharding(mesh, P((dcn_axis, shard_axis), None))

        live = np.zeros((n_parts, R), dtype=np.bool_)
        data: Dict[str, jax.Array] = {}
        valid: Dict[str, jax.Array] = {}
        types: Dict[str, SQLType] = {}
        dicts: Dict[str, object] = {}
        refs: Dict[str, np.int64] = {}

        host_cols = {}
        for name in names:
            info = table.schema.col(name)
            d, v = table.column_slice(name, lo, hi)
            if encode:
                stored, ref = _encode_staged(d, v, info.type_)
                if stored is not None:
                    d = stored
                    refs[name] = np.int64(ref)
            buf = np.zeros((n_parts, R), dtype=d.dtype)
            vbuf = np.zeros((n_parts, R), dtype=np.bool_)
            host_cols[name] = (buf, vbuf, d, v)
            types[name] = info.type_
            dc = table.dicts.get(name)
            if dc is not None:
                dicts[name] = dc

        row_live = table.live_mask(lo, hi)
        for p in range(n_parts):
            s, e = p * R, min((p + 1) * R, n)
            if s >= n:
                break
            m = e - s
            live[p, :m] = row_live[s:e]
            for name in names:
                buf, vbuf, d, v = host_cols[name]
                buf[p, :m] = d[s:e]
                vbuf[p, :m] = v[s:e]

        from tidb_tpu.utils import dispatch as dsp
        from tidb_tpu.utils.device import note_placement

        nbytes = live.nbytes
        for name in names:
            buf, vbuf, _, _ = host_cols[name]
            data[name] = jax.device_put(buf, spec)
            valid[name] = jax.device_put(vbuf, spec)
            dsp.record(2, site="stage")
            nbytes += buf.nbytes + vbuf.nbytes
        sel = jax.device_put(live, spec)
        dsp.record(site="stage")
        # booked when device_put has returned, not when the bytes are on
        # the device: no sync here, the statement's first fetch waits for
        # what the transfers still owe
        dsp.record_xfer(nbytes, "h2d")
        tracing.add("bytes", nbytes)
        note_placement("shard", (data, valid, sel))

        return ShardedTable(
            mesh=mesh, n_parts=n_parts, rows_per_part=R, total_rows=n,
            data=data, valid=valid, sel=sel, types=types, dicts=dicts,
            refs=refs,
        )
