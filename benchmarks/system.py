"""The system under test: beside ``program_spans.py`` (the program's
spans) the one module of the benchmark that imports the program. It
assembles the server as ``tidb_tpu.__main__.boot`` does (device, mesh,
catalog, data, ``Server.start()``) — by hand only because ``boot()``
cannot be given data — opens wire clients, and reads the program's
counters. Everything else under ``benchmarks/`` (traffic,
reference, comparison, reduction, peaks, bytes) stays clear of it.
"""

from __future__ import annotations


def device():
    """Platform, kind, count as jax reports them, and the jax devices.
    Importing the program first sets x64 and the compile cache directory
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``)."""
    import tidb_tpu  # noqa: F401
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}, devs


def start_server(tables: dict, primary_keys: dict, cluster_by: dict):
    """Catalog with the eight tables ingested through the bulk-load entry,
    ``--mesh auto``'s mesh over every device, server defaults."""
    from tidb_tpu.parallel import make_mesh
    from tidb_tpu.server.server import Server
    from tidb_tpu.storage.catalog import Catalog
    from tidb_tpu.storage.table import ColumnInfo, TableSchema
    from tidb_tpu.storage.tpch import TPCH_SCHEMAS
    from tidb_tpu.utils.device import device_info

    device_info()  # initialises the backend, finds the host glue's device
    mesh = make_mesh()
    catalog = Catalog()
    for name, (arrays, pools) in tables.items():
        cols = [ColumnInfo(n, t, not_null=nn) for n, t, nn in TPCH_SCHEMAS[name]]
        table = catalog.create_table("test", TableSchema(
            name, cols, primary_key=primary_keys[name],
            cluster_by=cluster_by.get(name)))
        # ingest_encoded remaps codes in place under a _ci collation:
        # hand it its own dict, never the reference's
        table.ingest_encoded(dict(arrays), pools)
    server = Server(catalog=catalog, host="127.0.0.1", port=0, mesh=mesh,
                    status_port=0)
    server.start()
    return server


def connect(server, timeout_s: float, pre_sql=()):
    from tidb_tpu.server.client import Client

    c = Client(server.host, server.port, db="test", timeout=timeout_s)
    for sql in pre_sql:
        c.query(sql)
    return c


def table_shapes(server) -> dict:
    """What lives on the device, per connection and table: the dtypes and
    shapes of every resident column (the bytes functions' input)."""
    import jax

    out = {}
    for cid, sess in list(server.sessions.items()):
        cache = getattr(sess, "_shard_cache", None)
        if cache is None:
            continue
        for held, st in cache.resident():
            cols = {n: (str(a.dtype), tuple(a.shape)) for n, a in st.data.items()}
            out.setdefault(held.schema.name, {})[cid] = {
                "columns": cols,
                "valid": {n: (str(a.dtype), tuple(a.shape))
                          for n, a in st.valid.items()},
                "sel": (str(st.sel.dtype), tuple(st.sel.shape)),
                "bytes": int(sum(a.nbytes for a in jax.tree_util.tree_leaves(
                    (st.data, st.valid, st.sel)))),
                "rows_per_part": st.rows_per_part, "n_parts": st.n_parts}
    return out


class Counters:
    """jax's monitoring events (backend compiles and their seconds,
    persistent-cache hits and misses) and the program's process-wide
    dispatch counters by site. A copy of ``chip_smoke.py``'s Observer."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += float(duration)

    def read(self) -> dict:
        from tidb_tpu.utils.metrics import DISPATCH_TOTAL, FRAGMENT_DISPATCH

        d = {"compiles": self.compiles, "compile_s": self.compile_s,
             "cache_hits": self.cache_hits, "cache_misses": self.cache_misses,
             "dispatches": 0}
        for labels, v in DISPATCH_TOTAL.samples():
            d["dispatch:" + labels.get("site", "?")] = v
            d["dispatches"] += v
        for labels, v in FRAGMENT_DISPATCH.samples():
            d["fragment:" + labels.get("kind", "?")] = v
        return d


def memory(devs) -> list:
    """Per device: bytes in use and peak (None on a backend that does
    not report, i.e. a CPU rehearsal)."""
    out = []
    for d in devs:
        ms = d.memory_stats() or {}
        out.append({"bytes_in_use": ms.get("bytes_in_use"),
                    "peak_bytes_in_use": ms.get("peak_bytes_in_use")})
    return out
