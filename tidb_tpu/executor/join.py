"""HashJoinExec (ref: executor/join.go — build + concurrent probe workers).

TPU redesign: hash tables are scatter-hostile, so the build side becomes a
*sorted* key array (+ row payload) on device, and each probe chunk runs
through the fused kernels in ops/join_kernels.py:

    probe_count:  key pack -> searchsorted -> match count -> prefix sum
    expand_tiles: [T, C] fixed-capacity output tiles per dispatch

The build phase is device-resident on the jitted tier: packed keys +
payload are staged once (padded to a power-of-two shape bucket) and the
pack + sort + payload gather run as ONE device program — no host
``np.argsort`` round trip. The host tier (``tidb_enable_tpu_exec`` off)
keeps its numpy probe and pays exactly one sort and one gather per
payload column.

The kernels live at module level in ops/join_kernels.py and take every
query-specific value as an argument, so a repeated join re-traces
NOTHING at steady state (``JOIN_COMPILE_TOTAL`` guards this; EXPLAIN
ANALYZE shows per-operator ``recompiles:``). The only host syncs per
probe chunk are the match total (to size the expansion) — everything
else stays on device.

Multi-key equi joins pack keys into one int64 using host-known ranges
(offset+stride per key); if ranges overflow int64, packing switches to
a 64-bit mixing hash of the composite key with exact on-device
verification — expanded candidate rows are filtered by real key
equality, so hash collisions only cost extra candidates, never wrong
results (the reference similarly falls back from its perfect-hash fast
path to a generic one).

Join kinds: inner, left (outer), semi, anti (with NOT IN null semantics:
any NULL build key -> empty result).
"""

from __future__ import annotations

import time
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from tidb_tpu.chunk.chunk import Chunk
from tidb_tpu.chunk.column import Column
from tidb_tpu.executor.base import ExecContext, Executor, raise_if_cancelled
from tidb_tpu.ops import join_kernels as jk
from tidb_tpu.utils.jitcache import cached_jit
from tidb_tpu.expression.compiler import compile_predicate, eval_expr
from tidb_tpu.types import INT64, TypeKind

__all__ = ["HashJoinExec", "IndexJoinExec"]


def _pad_np(a: np.ndarray, cap: int, fill=0) -> np.ndarray:
    """Pad a host array to a shape-bucket capacity."""
    n = len(a)
    if n == cap:
        return a
    out = np.full(cap, fill, dtype=a.dtype)
    out[:n] = a
    return out


def _pad_dev(a, cap: int, fill=0):
    """Pad a (possibly device) array to a shape-bucket capacity."""
    n = a.shape[0]
    if n == cap:
        return a
    if isinstance(a, np.ndarray):
        return _pad_np(a, cap, fill)
    return jnp.concatenate([a, jnp.full(cap - n, fill, dtype=a.dtype)])


class HashJoinExec(Executor):
    def __init__(self, schema, probe_child, build_child, kind: str,
                 probe_keys: List, build_keys: List, other_cond=None,
                 probe_schema=None, build_schema=None, exists_sem: bool = False):
        super().__init__(schema, [probe_child, build_child])
        self.kind = kind
        self.probe_keys = probe_keys
        self.build_keys = build_keys
        self.other_cond = other_cond
        self.probe_schema = probe_schema
        self.build_schema = build_schema
        self.exists_sem = exists_sem

    # ------------------------------------------------------------------

    def open(self, ctx: ExecContext) -> None:
        super().open(ctx)
        self.ctx = ctx
        self._pending: List[Chunk] = []
        self._drained = False
        self._build()

    def _build(self):
        """Drain the build child; compact key + payload columns; then
        EITHER one host sort (host numpy tier — no device staging at
        all) OR one padded staging transfer + the fused device
        pack/sort/gather kernel (jitted tier)."""
        t0 = time.perf_counter()
        build_child = self.children[1]
        keys_ir = self.build_keys

        def eval_keys(chunk):
            # keyless (cross) join: a constant key matches everything
            if not keys_ir:
                z = jnp.zeros(chunk.capacity, dtype=jnp.int64)
                return [(z, jnp.ones(chunk.capacity, dtype=jnp.bool_))], chunk.sel
            outs = [eval_expr(k, chunk) for k in keys_ir]
            return outs, chunk.sel

        eval_keys = cached_jit("joinkeys", repr(keys_ir), lambda: eval_keys)

        def eval_keys_any(chunk):
            # numpy first: key exprs are almost always column refs /
            # dict lookups, and the jitted evaluator recompiles per
            # query (per-query uids in its closure)
            if not keys_ir:
                z = np.zeros(chunk.capacity, dtype=np.int64)
                return ([(z, np.ones(chunk.capacity, dtype=np.bool_))],
                        chunk.sel)
            outs = [self._np_eval_key(k, chunk) for k in keys_ir]
            if all(o is not None for o in outs):
                return outs, chunk.sel
            return eval_keys(chunk)

        key_cols = [[] for _ in (keys_ir or [None])]
        key_ok = []
        payload: dict = {c.uid: ([], []) for c in (self.build_schema or [])}
        for chunk in build_child.chunks():
            # KILL/deadline interrupts the build drain chunk-by-chunk
            raise_if_cancelled(self.ctx)
            outs, sel = eval_keys_any(chunk)
            sel = np.asarray(sel)
            live = np.nonzero(sel)[0]
            ok = np.ones(len(live), dtype=np.bool_)
            for i, (d, v) in enumerate(outs):
                key_cols[i].append(np.asarray(d)[live])
                ok &= np.asarray(v)[live]
            key_ok.append(ok)
            for uid in payload:
                col = chunk.columns[uid]
                payload[uid][0].append(np.asarray(col.data)[live])
                payload[uid][1].append(np.asarray(col.valid)[live])

        key_arrays = [np.concatenate(p) if p else np.zeros(0, dtype=np.int64) for p in key_cols]
        ok = np.concatenate(key_ok) if key_ok else np.zeros(0, dtype=np.bool_)
        self._build_had_null = bool((~ok).any())
        self._n_build = int(ok.sum())

        # pack parameters (and the hash-mode decision) come from the
        # VALID keys only — a NULL slot's garbage value must not blow
        # the range into hash mode
        valid_keys = [k[ok] for k in key_arrays]
        self._pack_info = self._key_pack_info(valid_keys)
        self._has_filter = self.other_cond is not None or self._hash_mode
        self._payload_uids = list(payload)
        self._build_schema_by_uid = {c.uid: c for c in (self.build_schema or [])}

        keep_np = self._host_probe_eligible()
        nbytes = 0
        tier = "host" if keep_np else "device"
        if keep_np:
            # host tier: ONE argsort and ONE gather per column — the
            # sorted arrays are derived once and never staged to device
            # (the numpy probe path is the only consumer; the
            # tidb_tpu_join_device_build=0 escape hatch shares
            # _host_firsts but pads to a jit shape bucket)
            packed = self._pack_host(valid_keys)
            order = np.argsort(packed, kind="stable")
            self._sorted_keys_np = packed[order]
            live_idx = np.flatnonzero(ok)[order]
            self._sorted_keys = None
            self._build_payload = {}
            self._build_payload_np = {}
            nbytes = self._sorted_keys_np.nbytes
            # direct-address probe index (radix histogram) for dense
            # packed domains: O(1) gathers beat per-element binary search
            dom = self._direct_domain(len(self._sorted_keys_np))
            self._firsts_np = None
            if dom is not None:
                lo, rng = dom
                self._firsts_np = self._host_firsts(
                    self._sorted_keys_np, lo, rng)
                self._direct_lo_np, self._direct_rng_np = lo, rng
                nbytes += self._firsts_np.nbytes
            for uid, (dlist, vlist) in payload.items():
                c = self._build_schema_by_uid[uid]
                d = (np.concatenate(dlist) if dlist
                     else np.zeros(0, dtype=c.type_.np_dtype))
                v = (np.concatenate(vlist) if vlist
                     else np.zeros(0, dtype=np.bool_))
                d, v = d[live_idx], v[live_idx]
                nbytes += d.nbytes + v.nbytes
                self._build_payload_np[uid] = (d, v)
        elif (getattr(self.ctx, "join_device_build", True)
                or self._hash_mode):
            # hash mode always builds on device: its packed keys only
            # exist there (the host combiner was retired with the old
            # double-sort build)
            nbytes = self._stage_device_build(key_arrays, ok, payload)
        else:
            # tidb_tpu_join_device_build = 0 escape hatch: sort on host,
            # stage the already-sorted arrays. The probe kernels are
            # identical — only the sort placement changes.
            nbytes = self._stage_host_sorted_build(key_arrays, ok, payload)
            tier = "host_sorted"
        # account the materialized build side against the query budget
        # (ref: HashJoinExec's build RowContainer under the memory tracker)
        self._mem_tracker = self.ctx.mem_tracker.child("hashjoin.build")
        self._build_bytes = int(nbytes)
        self._mem_tracker.consume(self._build_bytes)
        from tidb_tpu.utils.metrics import JOIN_BUILD_SECONDS

        JOIN_BUILD_SECONDS.observe(time.perf_counter() - t0, tier=tier)

    def close(self) -> None:
        if getattr(self, "_build_bytes", 0):
            self._mem_tracker.release(self._build_bytes)
            self._build_bytes = 0
        super().close()

    def _key_pack_info(self, key_arrays: List[np.ndarray]):
        """Pack parameters per key WITHOUT materializing packed keys
        (the jitted tier packs on device). Sets self._hash_mode; returns
        [(mode, lo, stride, rng), ...] or [("hash", modes)] when the
        range product overflows int64."""
        self._hash_mode = False
        modes = ["bits" if np.issubdtype(k.dtype, np.floating) else "int"
                 for k in key_arrays]
        if len(key_arrays) == 1:
            k = key_arrays[0]
            if modes[0] == "int" and len(k):
                # lo/rng of the packed domain feed the direct-address
                # index decision (the probe packer ignores them for
                # single keys, so recording real values is free)
                lo, hi = int(k.min()), int(k.max())
                rng = hi - lo + 1
                if rng >= (1 << 63):
                    # keys span (almost) the whole int64 domain: the rng
                    # itself doesn't fit int64 (the probe-param arrays
                    # would overflow). Direct indexing is ineligible
                    # anyway — record 0, the "unknown range" marker.
                    rng = 0
                return [(modes[0], lo, 1, rng)]
            return [(modes[0], 0, 1, 0)]
        conv = [k.astype(np.float64).view(np.int64) if m == "bits"
                else k.astype(np.int64) for k, m in zip(key_arrays, modes)]
        info = []
        stride = 1
        for k, mode in zip(conv, modes):
            lo = int(k.min()) if len(k) else 0
            hi = int(k.max()) if len(k) else 0
            rng = hi - lo + 1
            if rng <= 0 or rng * stride > (1 << 62):
                self._hash_mode = True
                return [("hash", tuple(modes))]
            info.append((mode, lo, stride, rng))
            stride *= rng
        return info

    # direct-address index ceilings: absolute (host/device memory for the
    # [rng + 1] prefix array) and relative to the build bucket (don't
    # mint a giant histogram for a tiny build over a sparse domain)
    DIRECT_ABS_LIMIT = 1 << 23
    DIRECT_REL_LIMIT = 32

    def _direct_domain(self, n_bucket: int):
        """(lo, rng) of the packed-key domain when the direct-address
        (radix histogram) probe index pays off, else None. Dense build
        keys — the PK-FK common case — resolve probes in O(1) gathers."""
        if self._hash_mode or self._n_build == 0:
            return None
        info = self._pack_info
        if len(info) == 1:
            mode, lo, _stride, rng = info[0]
            if mode != "int" or rng <= 0:
                return None
        else:
            lo = 0
            rng = info[-1][2] * info[-1][3]  # prod of per-key ranges
        if rng > min(self.DIRECT_ABS_LIMIT,
                     max(1 << 18, self.DIRECT_REL_LIMIT * n_bucket)):
            return None
        return lo, rng

    @staticmethod
    def _host_firsts(sorted_packed: np.ndarray, lo: int, rng: int,
                     pad_to: int = 0) -> np.ndarray:
        """The direct-address index, built on host: bincount + cumsum
        prefix array over the dense packed domain [lo, lo+rng). One
        definition for BOTH host consumers — the numpy probe tier
        (exact length) and the host_sorted escape hatch, whose jit
        consumer needs `pad_to` shape-bucket padding (fill = n so
        out-of-domain gathers read an empty range). The device twin is
        ops/join_kernels.build_direct_index."""
        counts = np.bincount(sorted_packed - lo, minlength=rng)
        firsts = np.concatenate([np.zeros(1, dtype=np.int64),
                                 np.cumsum(counts, dtype=np.int64)])
        if pad_to > rng:
            firsts = _pad_np(firsts, pad_to + 1, len(sorted_packed))
        return firsts

    def _pack_host(self, key_arrays: List[np.ndarray]) -> np.ndarray:
        """Range-pack valid build keys on host (host tier only; hash
        mode never reaches here — it forces the jitted path)."""
        info = self._pack_info
        if len(key_arrays) == 1:
            return self._np_as_int64(key_arrays[0], info[0][0])
        packed = np.zeros(len(key_arrays[0]), dtype=np.int64)
        for k, (mode, lo, stride, rng) in zip(key_arrays, info):
            packed = packed + (self._np_as_int64(k, mode) - lo) * stride
        return packed

    def _resolve_probe_table(self) -> int:
        """Resolve the probe strategy (tidb_tpu_join_probe_mode via
        hash_probe.resolve_mode — trace-time platform aware) and build
        the open-addressing table ONCE over the staged sorted keys when
        the table path is selected. Dense packed domains keep the O(1)
        direct-address index instead (it beats any hash walk), and
        over-capacity builds fall back to searchsorted. Returns the
        table's resident bytes for the memory tracker."""
        from tidb_tpu.ops import hash_probe as hp

        self._probe_mode = hp.resolve_mode(
            getattr(self.ctx, "join_probe_mode", "off"))
        self._probe_table = None
        if self._probe_mode == "sorted" or self._direct:
            self._probe_mode = "sorted"
            return 0
        t = jk.build_hash_table(self._sorted_keys)
        if t is None:  # build side exceeds the VMEM capacity envelope
            self._probe_mode = "sorted"
            return 0
        self._probe_table = t
        return int(sum(a.nbytes for a in t[:3]))

    def _set_probe_pack_params(self, nk: int) -> None:
        """Device copies of the pack parameters the probe kernel takes
        as traced args (modes stay static)."""
        info = self._pack_info
        if self._hash_mode:
            self._modes = tuple(info[0][1])
            los = strides = rngs = np.zeros(nk, dtype=np.int64)
        else:
            self._modes = tuple(e[0] for e in info)
            los = np.asarray([e[1] for e in info], dtype=np.int64)
            strides = np.asarray([e[2] for e in info], dtype=np.int64)
            rngs = np.asarray([e[3] for e in info], dtype=np.int64)
        self._los = jnp.asarray(los)
        self._strides = jnp.asarray(strides)
        self._rngs = jnp.asarray(rngs)

    def _stage_host_sorted_build(self, key_arrays, ok, payload) -> int:
        """tidb_tpu_join_device_build = 0 escape hatch: the build sorts
        on host (one argsort + one gather per column, like the numpy
        tier) and the SORTED arrays stage to device for the same fused
        probe kernels. Correctness-identical to the device build."""
        from tidb_tpu.utils import dispatch as dsp

        self._set_probe_pack_params(len(key_arrays))
        valid_keys = [k[ok] for k in key_arrays]
        packed = self._pack_host(valid_keys)
        order = np.argsort(packed, kind="stable")
        sorted_np = packed[order]
        live_idx = np.flatnonzero(ok)[order]
        n = len(sorted_np)
        B = jk.shape_bucket(n)
        # padding must keep the array sorted: dead slots -> INT64_MAX
        self._sorted_keys = jnp.asarray(
            _pad_np(sorted_np, B, np.iinfo(np.int64).max))
        self._n_build_dev = jnp.asarray(n, dtype=jnp.int64)
        self._sorted_keys_np = None
        self._build_payload_np = {}
        self._build_keyvals_dev = ()  # hash mode never takes this path
        self._build_payload = {}
        nbytes = self._sorted_keys.nbytes
        n_staged = 1
        for uid in self._payload_uids:
            dlist, vlist = payload[uid]
            c = self._build_schema_by_uid[uid]
            d = (np.concatenate(dlist) if dlist
                 else np.zeros(0, dtype=c.type_.np_dtype))
            v = (np.concatenate(vlist) if vlist
                 else np.zeros(0, dtype=np.bool_))
            dd = jnp.asarray(_pad_np(d[live_idx], B))
            vv = jnp.asarray(_pad_np(v[live_idx], B, False))
            self._build_payload[uid] = (dd, vv)
            nbytes += dd.nbytes + vv.nbytes
            n_staged += 2
        dom = self._direct_domain(B)
        self._direct = dom is not None
        if self._direct:
            lo, rng = dom
            # bucket the histogram length like the device build does, or
            # the probe kernel would re-trace per build data size
            self._firsts = jnp.asarray(self._host_firsts(
                sorted_np, lo, rng,
                pad_to=jk.shape_bucket(rng, floor=64)))
            self._direct_lo, self._direct_rng = lo, rng
            n_staged += 1
        else:
            self._firsts = jnp.zeros(2, dtype=jnp.int64)
            self._direct_lo = self._direct_rng = 0
        nbytes += self._firsts.nbytes
        nbytes += self._resolve_probe_table()
        dsp.record(n_staged, site="stage")
        return nbytes

    def _stage_device_build(self, key_arrays, ok, payload) -> int:
        """Pad to a power-of-two shape bucket, stage ONCE, and run the
        fused pack+sort+gather kernel — the build side becomes
        device-resident sorted arrays with NULL/dead keys at the tail.
        Returns resident bytes for the memory tracker."""
        from tidb_tpu.utils import dispatch as dsp

        nk = len(key_arrays)
        self._set_probe_pack_params(nk)
        B = jk.shape_bucket(len(ok))
        ok_p = jnp.asarray(_pad_np(ok, B, False))
        kd = tuple(jnp.asarray(_pad_np(np.asarray(k), B)) for k in key_arrays)
        kv = (ok_p,) * nk  # key validity is already folded into ok
        pd, pv = [], []
        for uid in self._payload_uids:
            dlist, vlist = payload[uid]
            c = self._build_schema_by_uid[uid]
            d = (np.concatenate(dlist) if dlist
                 else np.zeros(0, dtype=c.type_.np_dtype))
            v = (np.concatenate(vlist) if vlist
                 else np.zeros(0, dtype=np.bool_))
            pd.append(jnp.asarray(_pad_np(d, B)))
            pv.append(jnp.asarray(_pad_np(v, B, False)))
        dsp.record(1 + nk + 2 * len(pd), site="stage")

        sorted_keys, n_build_dev, out_d, out_v, out_k = jk.build_sort(
            kd, kv, ok_p, tuple(pd), tuple(pv),
            self._los, self._strides, self._rngs,
            modes=self._modes, hash_mode=self._hash_mode)
        self._sorted_keys = sorted_keys
        self._n_build_dev = n_build_dev
        # direct-address probe index over a dense packed domain, built on
        # device from the sorted keys (shape-bucketed so repeats reuse
        # the compiled histogram kernel)
        dom = self._direct_domain(B)
        self._direct = dom is not None
        if self._direct:
            lo, rng = dom
            rng_bucket = jk.shape_bucket(rng, floor=64)
            self._firsts = jk.build_direct_index(
                sorted_keys, n_build_dev, lo, rng_bucket)
            self._direct_lo, self._direct_rng = lo, rng
        else:
            self._firsts = jnp.zeros(2, dtype=jnp.int64)
            self._direct_lo = self._direct_rng = 0
        self._sorted_keys_np = None
        self._build_payload_np = {}
        self._build_payload = {
            uid: (d, v)
            for uid, d, v in zip(self._payload_uids, out_d, out_v)
        }
        # raw key values build-sorted: exact verification of
        # hash-expanded candidate rows reads them (passed as kernel
        # ARGS, never closure state — see _match_filter)
        self._build_keyvals_dev = out_k if self._hash_mode else ()
        nbytes = sorted_keys.nbytes + self._firsts.nbytes
        nbytes += self._resolve_probe_table()
        for d, v in zip(out_d, out_v):
            nbytes += d.nbytes + v.nbytes
        for k in self._build_keyvals_dev:
            nbytes += k.nbytes
        return nbytes

    # deferred-sync window for the device probe: per-chunk match totals
    # accumulate as device scalars and resolve in ONE batched fetch per
    # window instead of one int() sync per chunk (ISSUE 9). The byte cap
    # bounds how many probe chunks (plus their count arrays) stay
    # referenced on device while their totals are in flight.
    PROBE_SYNC_CHUNKS = 8
    PROBE_DEFER_BYTES = 128 << 20

    def next(self) -> Optional[Chunk]:
        while True:
            if self._pending:
                return self._pending.pop(0)
            if self._drained:
                return None
            self._fill_pending()

    def _fill_pending(self) -> None:
        """Pull probe chunks until output lands in _pending or the child
        drains. Device-tier chunks needing a match total (inner/left,
        filtered semi/anti) DEFER it: probe_count results queue with
        their device totals, and one batched device_get per window
        resolves every queued chunk — the probe phase of a fragment now
        syncs O(chunks / window), not O(chunks)."""
        deferred: List[dict] = []
        dbytes = 0
        while not self._pending and not self._drained:
            chunk = self.children[0].next()
            if chunk is None:
                self._drained = True
                break
            # a KILL/deadline must interrupt the probe drain between
            # device steps, not wait for the root chunk loop
            raise_if_cancelled(self.ctx)
            if self._host_probe_eligible():
                self._process_probe_chunk_np(chunk)
                continue
            tok = self._probe_start_device(chunk)
            if tok is None:
                continue  # fully handled (unfiltered semi/anti)
            deferred.append(tok)
            # the window pins the chunk's columns AND the probe_count
            # results: 4 int64 + 2 bool [Rp] arrays per token
            dbytes += sum(c.data.nbytes + c.valid.nbytes
                          for c in chunk.columns.values())
            dbytes += tok["Rp"] * 34
            if (len(deferred) >= self.PROBE_SYNC_CHUNKS
                    or dbytes >= self.PROBE_DEFER_BYTES):
                self._probe_finish_batch(deferred)
                deferred = []
                dbytes = 0
        if deferred:
            self._probe_finish_batch(deferred)

    def _host_probe_eligible(self) -> bool:
        """The numpy probe path covers the workhorse shapes on the host
        engine (ctx.device_agg off): direct-address gathers (or binary
        search) + exact np.repeat expansion with no staging at all.
        Left joins and filtered/hash-verified probes take the fused
        device kernels (NULL padding + re-verification logic)."""
        return (not getattr(self.ctx, "device_agg", True)
                and self.kind in ("inner", "semi", "anti")
                and self.other_cond is None
                and not self._hash_mode)

    @staticmethod
    def _keep_unmatched(sel, ok, matched, build_had_null, exists_sem):
        """Anti-join keep mask, shared (semantically) with the jitted
        path: NOT EXISTS keeps NULL-key probe rows; NOT IN goes empty
        when the build side held a NULL key (caller handles that)."""
        if exists_sem:
            return sel & ~(ok & matched)
        return sel & ok & ~matched

    def _np_eval_key(self, e, chunk: Chunk):
        """Numpy (data, valid) for the key shapes the host path meets —
        column refs, literals, dictionary Lookups. Returns None for
        anything else (caller falls back to the jitted evaluator).
        Evaluating keys without jax matters: a per-join jax.jit keyed on
        per-query uids recompiled EVERY query (~20ms per join — the
        fixed cost that made every small host join cost ~30ms)."""
        from tidb_tpu.expression.expr import ColumnRef, Literal, Lookup

        if isinstance(e, ColumnRef):
            col = chunk.columns[e.name]
            return np.asarray(col.data), np.asarray(col.valid)
        if isinstance(e, Literal):
            cap = chunk.capacity
            dt = e.type_.np_dtype  # match the jitted evaluator's dtype:
            # pack-mode selection ('bits' for floats) depends on it
            if e.value is None:
                return (np.zeros(cap, dtype=dt),
                        np.zeros(cap, dtype=np.bool_))
            return (np.full(cap, e.value, dtype=dt),
                    np.ones(cap, dtype=np.bool_))
        if isinstance(e, Lookup):
            base = self._np_eval_key(e.arg, chunk)
            if base is None:
                return None
            data, valid = base
            table = np.asarray(e.table, dtype=e.type_.np_dtype)
            if len(table) == 0:  # empty dictionary: every code is absent
                return (np.zeros(len(data), dtype=e.type_.np_dtype),
                        np.zeros(len(data), dtype=np.bool_))
            idx = np.clip(data.astype(np.int64), 0, len(e.table) - 1)
            out = table[idx]
            if e.table_valid is not None:
                tv = np.asarray(e.table_valid, dtype=np.bool_)
                valid = valid & tv[idx]
            valid = valid & (data >= 0) & (data < len(e.table))
            return out, valid
        return None

    @staticmethod
    def _np_as_int64(d: np.ndarray, mode: str) -> np.ndarray:
        if mode == "bits":
            return d.astype(np.float64).view(np.int64)
        return d.astype(np.int64)

    def _np_pack_probe(self, outs):
        """Numpy mirror of the device packer (range packing; hash mode never
        reaches the numpy path — _host_probe_eligible excludes it)."""
        info = self._pack_info
        if len(outs) == 1:
            d, v = outs[0]
            return (self._np_as_int64(d, info[0][0]), v,
                    np.ones_like(v, dtype=np.bool_))
        packed = np.zeros(len(outs[0][0]), dtype=np.int64)
        valid = np.ones(len(outs[0][0]), dtype=np.bool_)
        in_range = np.ones_like(valid)
        for (d, v), (mode, lo, stride, rng) in zip(outs, info):
            d = self._np_as_int64(d, mode)
            valid = valid & v
            in_range = in_range & (d >= lo) & (d < lo + rng)
            packed = packed + np.clip(d - lo, 0, max(rng - 1, 0)) * stride
        return packed, valid, in_range

    def _probe_key_arrays(self, chunk: Chunk, host: bool = True):
        """(key datas, key valids) for one probe chunk.

        ``host=True`` (the numpy tier): pure numpy when the key exprs
        allow it (almost always — column refs / dictionary lookups),
        else the cached jitted evaluator.

        ``host=False`` (the device tier): plain ColumnRef keys pass
        their arrays through UNTOUCHED — a device-resident column must
        not detour through np.asarray (a synchronous device->host
        round trip per probe chunk on real hardware); anything else
        evaluates in one cached jitted kernel per key-expr repr
        (reused across executions; binder uids are deterministic)."""
        if not self.probe_keys:
            return (), ()
        if not host:
            from tidb_tpu.expression.expr import ColumnRef

            if all(isinstance(k, ColumnRef) for k in self.probe_keys):
                cols = [chunk.columns[k.name] for k in self.probe_keys]
                return (tuple(c.data for c in cols),
                        tuple(c.valid for c in cols))
        elif getattr(self, "_probe_key_mode", None) != "jit":
            outs = [self._np_eval_key(k, chunk) for k in self.probe_keys]
            if all(o is not None for o in outs):
                self._probe_key_mode = "np"
                return (tuple(o[0] for o in outs),
                        tuple(o[1] for o in outs))
            self._probe_key_mode = "jit"
        if getattr(self, "_probe_key_fn", None) is None:
            keys_ir = self.probe_keys

            def keyfn(ch):
                return tuple(tuple(eval_expr(k, ch)) for k in keys_ir)

            self._probe_key_fn = cached_jit(
                "joinprobekeys", repr(keys_ir), lambda: keyfn)
        outs = self._probe_key_fn(chunk)
        return tuple(o[0] for o in outs), tuple(o[1] for o in outs)

    def _np_probe_keys(self, chunk: Chunk):
        """Key eval + pack for the numpy probe path."""
        if not self.probe_keys:
            cap = chunk.capacity
            return (np.zeros(cap, dtype=np.int64), np.asarray(chunk.sel),
                    np.ones(cap, dtype=np.bool_))
        kd, kv = self._probe_key_arrays(chunk)
        outs = [(np.asarray(d), np.asarray(v)) for d, v in zip(kd, kv)]
        packed, valid, in_r = self._np_pack_probe(outs)
        return packed, valid & np.asarray(chunk.sel), in_r

    def _process_probe_chunk_np(self, chunk: Chunk):
        from tidb_tpu.utils.metrics import JOIN_PROBE_MODE_TOTAL

        JOIN_PROBE_MODE_TOTAL.inc(mode="host")
        packed, ok, in_r = self._np_probe_keys(chunk)
        if self._firsts_np is not None:
            # dense packed domain: O(1) gathers into the radix histogram
            idx = packed - self._direct_lo_np
            in_r = in_r & (idx >= 0) & (idx < self._direct_rng_np)
            idx = np.clip(idx, 0, self._direct_rng_np - 1)
            start = self._firsts_np[idx]
            count = np.where(ok & in_r, self._firsts_np[idx + 1] - start, 0)
        else:
            sk = self._sorted_keys_np
            start = np.searchsorted(sk, packed, side="left")
            end = np.searchsorted(sk, packed, side="right")
            count = np.where(ok & in_r, end - start, 0)

        if self.kind in ("semi", "anti"):
            matched = count > 0
            if self.kind == "semi":
                self._pending.append(chunk.with_sel(jnp.asarray(ok & matched)))
                return
            if self._build_had_null and not self.exists_sem:
                return  # NOT IN with NULL in subquery: no row is ever TRUE
            keep = self._keep_unmatched(np.asarray(chunk.sel), ok, matched,
                                        self._build_had_null, self.exists_sem)
            self._pending.append(chunk.with_sel(jnp.asarray(keep)))
            return

        total = int(count.sum())
        if self.kind == "inner":  # host path is unfiltered by
            # eligibility; the exact output count is already host-side
            self.stats.add_out_rows(total)
        if total == 0:
            return
        cum = np.cumsum(count)
        cum_excl = cum - count
        cap = self.ctx.chunk_capacity
        build_schema = {c.uid: c for c in (self.build_schema or [])}
        probe_np = {uid: (np.asarray(col.data), np.asarray(col.valid))
                    for uid, col in chunk.columns.items()}
        # columns with no NULLs skip the validity gather entirely (scan
        # output is usually all-valid; from_numpy mints the ones mask)
        all_valid = {uid: bool(v.all()) for uid, (d, v) in probe_np.items()}
        ball_valid = {uid: bool(v.all())
                      for uid, (d, v) in self._build_payload_np.items()}
        types = {uid: chunk.columns[uid].type_ for uid in probe_np}
        types.update({uid: build_schema[uid].type_
                      for uid in self._build_payload_np})
        # window the EXPANSION itself (not just the emission): a
        # many-to-many join's full expansion can dwarf host memory
        rows_of_window = np.searchsorted(cum, np.arange(0, total, cap),
                                         side="right")
        for wi, w in enumerate(range(0, total, cap)):
            hi = min(w + cap, total)
            m = hi - w
            lo_row = rows_of_window[wi]
            hi_row = int(np.searchsorted(cum, hi - 1, side="right"))
            rows = np.arange(lo_row, hi_row + 1)
            reps = np.minimum(cum[rows], hi) - np.maximum(cum_excl[rows], w)
            probe_row = np.repeat(rows, reps)
            # one repeat of the per-row offset replaces two per-output
            # gathers: build_pos = j + (start[row] - cum_excl[row])
            build_pos = (np.arange(w, hi, dtype=np.int64)
                         + np.repeat(start[rows] - cum_excl[rows], reps))
            arrays, valids = {}, {}
            for uid, (d, v) in probe_np.items():
                arrays[uid] = d[probe_row]
                if not all_valid[uid]:
                    valids[uid] = v[probe_row]
            for uid, (d, v) in self._build_payload_np.items():
                arrays[uid] = d[build_pos]
                if not ball_valid[uid]:
                    valids[uid] = v[build_pos]
            ccap = 8
            while ccap < m:
                ccap *= 2
            self._pending.append(
                Chunk.from_numpy(arrays, types, valids=valids, capacity=ccap))

    def _probe_finish_batch(self, tokens: List[dict]) -> None:
        """Resolve a deferred window: ONE device_get moves every queued
        chunk's match total, then each chunk finishes (expansion /
        qualification) with its now-host-known size."""
        # THE intentional probe sync, batched: one fetch of the
        # accumulated per-chunk match totals per deferred window
        # (PROBE_SYNC_CHUNKS chunks), replacing the per-chunk int()
        # round trip this loop used to pay; the totals size the tile
        # expansions (sanctioned device_get outside any loop — the
        # chunk-loop sync-budget pass watches the loop form)
        from tidb_tpu.utils import dispatch as dsp

        totals = dsp.device_get([t["total_dev"] for t in tokens])
        if self.kind == "inner" and not self._has_filter:
            # plan feedback: for the unfiltered inner join the summed
            # match totals ARE the output cardinality, host-known from
            # the fetch this loop already pays — record it for free
            self.stats.add_out_rows(int(sum(int(t) for t in totals)))
        for tok, total in zip(tokens, totals):
            try:
                self._probe_finish(tok, int(total))
            finally:
                from tidb_tpu.utils.metrics import JOIN_PROBE_SECONDS

                # spans launch -> expansion incl. any deferral wait;
                # overlapped chunks legitimately overlap their windows
                JOIN_PROBE_SECONDS.observe(time.perf_counter() - tok["t0"],
                                           kind=self.kind)

    def _probe_start_device(self, chunk: Chunk) -> Optional[dict]:
        """Launch the fused probe_count for one chunk. Unfiltered
        semi/anti joins finish here (their keep mask needs no total);
        everything else returns a deferral token carrying the device
        results, resolved later by _probe_finish_batch."""
        t0 = time.perf_counter()
        # hash-packed keys need exact re-verification of every candidate
        # row, so they take the same filtered paths as other_cond
        has_filter = self._has_filter
        key_datas, key_valids = self._probe_key_arrays(chunk, host=False)
        cap = chunk.capacity
        Rp = jk.shape_bucket(cap)
        sel = chunk.sel
        if Rp != cap:  # shape-bucket the probe: pad keys + sel to pow2
            key_datas = tuple(_pad_dev(d, Rp) for d in key_datas)
            key_valids = tuple(_pad_dev(v, Rp, False) for v in key_valids)
            sel = _pad_dev(sel, Rp, False)
        left_pad = self.kind == "left" and not has_filter
        start, count, real_count, cum, total_dev, ok, matched = jk.probe_count(
            self._sorted_keys, self._n_build_dev, key_datas, key_valids,
            sel, self._los, self._strides, self._rngs,
            self._firsts, self._direct_lo, self._direct_rng,
            modes=self._modes, hash_mode=self._hash_mode,
            left_pad=left_pad, direct=self._direct,
            table=self._probe_table, probe=self._probe_mode)

        if self.kind in ("semi", "anti") and not has_filter:
            if Rp != cap:
                matched = matched[:cap]
            okc = ok[:cap] if Rp != cap else ok
            if self.kind == "semi":
                self._pending.append(chunk.with_sel(okc & matched))
            elif self._build_had_null and not self.exists_sem:
                pass  # NOT IN with NULL in subquery: no row is ever TRUE
            elif self.exists_sem:
                # NOT EXISTS: a NULL probe key never matches -> row kept
                self._pending.append(
                    chunk.with_sel(chunk.sel & ~(okc & matched)))
            else:
                self._pending.append(
                    chunk.with_sel(chunk.sel & okc & ~matched))
            from tidb_tpu.utils.metrics import JOIN_PROBE_SECONDS

            JOIN_PROBE_SECONDS.observe(time.perf_counter() - t0,
                                       kind=self.kind)
            return None
        return {"chunk": chunk, "start": start, "count": count,
                "real_count": real_count, "cum": cum,
                "total_dev": total_dev, "ok": ok, "matched": matched,
                "cap": cap, "Rp": Rp, "t0": t0}

    def _probe_finish(self, tok: dict, total: int) -> None:
        """Complete one deferred probe chunk with its host-known match
        total: qualification for filtered semi/anti, tile expansion for
        inner/left."""
        chunk = tok["chunk"]
        start, count, real_count = tok["start"], tok["count"], \
            tok["real_count"]
        cum, ok = tok["cum"], tok["ok"]
        cap, Rp = tok["cap"], tok["Rp"]
        has_filter = self._has_filter

        if self.kind in ("semi", "anti"):  # has_filter: qualified path
            matched = self._qualified_matches(
                chunk, start, real_count, cum, total)
            okc = ok[:cap] if Rp != cap else ok
            if self.kind == "semi":
                self._pending.append(chunk.with_sel(okc & matched))
                return
            if self._build_had_null and not self.exists_sem:
                return  # NOT IN with NULL in subquery: no row is ever TRUE
            if self.exists_sem:
                # NOT EXISTS: a NULL probe key never matches -> row kept
                keep = chunk.sel & ~(okc & matched)
            else:
                keep = chunk.sel & okc & ~matched
            self._pending.append(chunk.with_sel(keep))
            return

        left_other = self.kind == "left" and has_filter
        if total == 0 and not left_other:
            return
        matched_np = (np.zeros(cap, dtype=np.bool_) if left_other else None)
        for out in self._expand_windows(chunk, start, count, real_count,
                                        cum, total, bookkeeping=has_filter):
            if has_filter:
                out = self._match_filter(out)
                if left_other:
                    osel = np.asarray(out.sel)
                    rows = np.asarray(
                        out.columns["__probe_row__"].data)[osel]
                    matched_np[rows] = True
                # bookkeeping columns stay internal to the match tracking
                out = Chunk(
                    {u: c for u, c in out.columns.items()
                     if u not in ("__probe_row__", "__build_pos__")},
                    out.sel,
                )
            self._pending.append(out)
        if left_other:
            # probe rows whose every match failed other_cond (or that had
            # none) emit one NULL-payload row each, per LEFT JOIN semantics
            unmatched = chunk.sel & jnp.asarray(~matched_np)
            # host-sync: intentional sync on the left-join + other_cond
            # tail — one bool per chunk decides whether a NULL-pad
            # chunk is emitted at all
            if bool(np.asarray(unmatched).any()):
                self._pending.append(self._null_build_chunk(chunk, unmatched))

    def _expand_windows(self, chunk: Chunk, start, count, real_count, cum,
                        total: int, bookkeeping: bool):
        """Yield output Chunks of capacity ctx.chunk_capacity via fused
        [T, C] tile dispatches — up to ctx.join_tiles output tiles per
        device round trip instead of one dispatch per window."""
        C = self.ctx.chunk_capacity
        max_tiles = max(1, getattr(self.ctx, "join_tiles", 8))
        p_uids = list(chunk.columns.keys())
        p_datas = tuple(chunk.columns[u].data for u in p_uids)
        p_valids = tuple(chunk.columns[u].valid for u in p_uids)
        b_uids = self._payload_uids
        b_datas = tuple(self._build_payload[u][0] for u in b_uids)
        b_valids = tuple(self._build_payload[u][1] for u in b_uids)
        w0 = 0
        while w0 < total:
            rem = -(-(total - w0) // C)  # ceil-div: tiles still needed
            T = min(jk.shape_bucket(rem, floor=1), max_tiles)
            out_p, out_b, sel_t, prow, bpos = jk.expand_tiles(
                start, count, real_count, cum, w0, p_datas, p_valids,
                b_datas, b_valids, n_tiles=T, tile_cap=C,
                build_cap=self._sorted_keys.shape[0],
                left=self.kind == "left",
                with_probe_row=bookkeeping,
                with_build_pos=bookkeeping and self._hash_mode)
            for i in range(min(T, rem)):
                cols = {}
                for u, (d2, v2) in zip(p_uids, out_p):
                    cols[u] = Column(d2[i], v2[i], chunk.columns[u].type_)
                for u, (d2, v2) in zip(b_uids, out_b):
                    cols[u] = Column(d2[i], v2[i],
                                     self._build_schema_by_uid[u].type_)
                if prow is not None:
                    cols["__probe_row__"] = Column(prow[i], sel_t[i], INT64)
                if bpos is not None:
                    cols["__build_pos__"] = Column(bpos[i], sel_t[i], INT64)
                yield Chunk(cols, sel_t[i])
            w0 += T * C

    def _qualified_matches(self, chunk: Chunk, start, count, cum,
                           total: int):
        """[capacity] bool: probe rows with at least one build match passing
        other_cond — via windowed expansion (semi/anti joins carrying extra
        conditions, e.g. decorrelated EXISTS with non-equi predicates)."""
        matched = np.zeros(chunk.capacity, dtype=np.bool_)
        for out in self._expand_windows(chunk, start, count, count, cum,
                                        total, bookkeeping=True):
            out = self._match_filter(out)
            osel = np.asarray(out.sel)
            rows = np.asarray(out.columns["__probe_row__"].data)[osel]
            matched[rows] = True
        return jnp.asarray(matched)

    def _match_filter(self, out: Chunk) -> Chunk:
        """Filter expanded candidate rows: exact key equality when the
        keys were hash-packed, then other_cond if present. The compiled
        fn is cached across queries by expr repr; the build key values
        are ARGS (not closure state), so a cache hit can never read a
        stale build side."""
        if getattr(self, "_filter_fn", None) is None:
            other = (compile_predicate(self.other_cond)
                     if self.other_cond is not None else None)
            hash_mode = self._hash_mode
            probe_keys = self.probe_keys
            modes = self._pack_info[0][1] if hash_mode else ()

            def fn(ch, keyvals):
                keep = ch.sel
                if hash_mode:
                    pos = ch.columns["__build_pos__"].data
                    for k_ir, mode, bv in zip(probe_keys, modes, keyvals):
                        pv = jk.as_int64_key(eval_expr(k_ir, ch)[0], mode)
                        keep = keep & (jnp.take(bv, pos, mode="clip") == pv)
                if other is not None:
                    keep = keep & other(ch)
                return ch.with_sel(keep)

            self._filter_fn = cached_jit(
                "joinfilter",
                f"{hash_mode}:{modes}:{self.probe_keys!r}"
                f":{self.other_cond!r}",
                lambda: fn)
        return self._filter_fn(out, tuple(
            getattr(self, "_build_keyvals_dev", ())))

    def _null_build_chunk(self, chunk: Chunk, sel) -> Chunk:
        """Probe columns pass through; build payload is all-NULL."""
        build_schema = {c.uid: c for c in (self.build_schema or [])}
        cols = dict(chunk.columns)
        for uid in self._build_payload:
            c = build_schema[uid]
            cols[uid] = Column(
                np.zeros(chunk.capacity, dtype=c.type_.np_dtype),
                np.zeros(chunk.capacity, dtype=np.bool_),
                c.type_,
            )
        return Chunk(cols, sel)

class IndexJoinExec(Executor):
    """Index-lookup join (ref: executor's IndexLookUpJoin; SURVEY.md:91):
    the inner side is never scanned — each outer chunk's join keys are
    batch-binary-searched against the inner table's sorted index cache
    (the same substrate PointGet/IndexRangeScan probe), candidate rows
    pass MVCC visibility, and matches gather straight from table
    storage. O((outer + matches) log n) host work, independent of the
    inner table's size — the access-path alternative the cascades memo
    costs against the hash join's exchange + build."""

    def __init__(self, schema, outer: Executor, eq_outer, inner_table,
                 index_name, inner_schema, inner_cond, other_cond):
        super().__init__(schema, [outer])
        self.eq_outer = eq_outer
        self.inner_table = inner_table
        self.index_name = index_name
        self.inner_schema = inner_schema
        self.inner_cond = inner_cond
        self.other_cond = other_cond

    def open(self, ctx: ExecContext) -> None:
        super().open(ctx)
        self.ctx = ctx
        from tidb_tpu.expression.compiler import compile_expr

        self._key_fns = [compile_expr(e) for e in self.eq_outer]
        self._pending: List[Chunk] = []
        self._skeys, self._srows = self.inner_table._sorted_index(
            self.index_name)
        self._resid = None
        if self.inner_cond is not None or self.other_cond is not None:
            conds = [c for c in (self.inner_cond, self.other_cond)
                     if c is not None]
            self._resid = [compile_predicate(c) for c in conds]

    def next(self) -> Optional[Chunk]:
        while True:
            if self._pending:
                return self._pending.pop(0)
            ch = self.children[0].next()
            if ch is None:
                return None
            self._join_chunk(ch)

    def _join_chunk(self, ch: Chunk) -> None:
        sel = np.asarray(ch.sel)
        live = np.nonzero(sel)[0]
        if len(live) == 0:
            return
        skeys, srows = self._skeys, self._srows
        nkeys = len(self._key_fns)
        i64 = np.iinfo(np.int64)
        # the index may be wider than the join key set (a composite pk
        # probed on its prefix): floor/ceil the suffix fields so the
        # whole equal-prefix run matches, not just suffix == 0
        probe_lo = np.zeros(len(live), dtype=skeys.dtype)
        probe_hi = np.zeros(len(live), dtype=skeys.dtype)
        for name in skeys.dtype.names[nkeys:]:
            probe_lo[name] = i64.min
            probe_hi[name] = i64.max
        kvalid = np.ones(len(live), dtype=np.bool_)
        for i, fn in enumerate(self._key_fns):
            col = fn(ch)
            kvalid &= np.asarray(col.valid)[live]
            keys = np.asarray(col.data)[live].astype(np.int64)
            probe_lo[f"k{i}"] = keys
            probe_hi[f"k{i}"] = keys
        # NULL keys match nothing; searchsorted over the composite tuple
        # gives the exact equality run — no hashing, no collisions
        lo = np.searchsorted(skeys, probe_lo, side="left")
        hi = np.searchsorted(skeys, probe_hi, side="right")
        counts = np.where(kvalid, hi - lo, 0)
        total = int(counts.sum())
        if total == 0:
            return
        outer_pos = np.repeat(np.arange(len(live)), counts)
        starts = np.repeat(lo, counts)
        offs = np.arange(total) - np.repeat(
            np.cumsum(counts) - counts, counts)
        cand = srows[starts + offs]
        vis = self.inner_table._mvcc_mask(
            cand, read_ts=self.ctx.read_ts, marker=self.ctx.txn_marker)
        cand = cand[vis]
        outer_rows = live[outer_pos[vis]]
        # windowed emission: expansion is bounded to chunk_capacity per
        # output chunk (the HashJoinExec contract), so a many-match key
        # set cannot spike host memory or mint giant downstream shapes
        win = max(self.ctx.chunk_capacity, 8)
        for s0 in range(0, len(cand), win):
            self._emit(ch, outer_rows[s0:s0 + win], cand[s0:s0 + win])

    def _emit(self, ch: Chunk, outer_rows, cand) -> None:
        if len(cand) == 0:
            return
        cap = 8
        while cap < len(cand):
            cap *= 2
        cols = {}
        for c in self.inner_schema:
            d = self.inner_table.data[c.name][cand]
            v = self.inner_table.valid[c.name][cand]
            cols[c.uid] = Column.from_numpy(d, c.type_, valid=v,
                                            capacity=cap)
        for uid, col in ch.columns.items():
            d = np.asarray(col.data)[outer_rows]
            v = np.asarray(col.valid)[outer_rows]
            cols[uid] = Column.from_numpy(d, col.type_, valid=v,
                                          capacity=cap)
        osel = np.zeros(cap, dtype=np.bool_)
        osel[: len(cand)] = True
        out = Chunk(cols, osel)
        if self._resid is not None:
            for pred in self._resid:
                out = out.filter(pred(out))
        self.stats.chunks += 1
        self._pending.append(out)
