"""tidb-server equivalent: boot the MySQL-protocol server from the CLI.

    python -m tidb_tpu [--host H] [--port P] [--config file.toml]
                       [--mesh {auto,none}] [--load-tpch SF]
                       [--root-password PW]

Ref: tidb-server/main.go (flag parsing -> config merge -> bootstrap ->
Server.Run). Config file keys mirror the flags; explicit flags win.
"""

from __future__ import annotations

import argparse
import sys


def parse_args(argv):
    ap = argparse.ArgumentParser(prog="tidb_tpu", description=__doc__)
    ap.add_argument("--host", default=None, help="listen address (default 127.0.0.1)")
    ap.add_argument("--port", type=int, default=None, help="listen port (default 4000)")
    ap.add_argument("--status-port", type=int, default=None,
                    help="HTTP status/metrics port (default 10080; -1 disables)")
    ap.add_argument("--config", default=None, help="TOML config file")
    ap.add_argument("--mesh", choices=["auto", "none"], default=None,
                    help="auto: shard tables over all visible devices")
    ap.add_argument("--load-tpch", type=float, default=None, metavar="SF",
                    help="preload TPC-H tables at scale factor SF")
    ap.add_argument("--root-password", default=None,
                    help="set the root account password at boot")
    ap.add_argument("--plugin-modules", default=None,
                    help="comma-separated module path prefixes INSTALL "
                         "PLUGIN may import (default: none — SQL plugin "
                         "loading disabled on the server)")
    ap.add_argument("--device", choices=["default", "cpu"], default=None,
                    help="cpu: run on the CPU backend explicitly (tests, "
                         "machines without an accelerator); default: "
                         "whatever jax finds, and fail if that fails")
    return ap.parse_args(argv)


def load_config(path):
    import tomllib

    with open(path, "rb") as f:
        return tomllib.load(f)


def boot(argv=None):
    """Parse flags, initialise the backend, load data and start the
    server; returns the started ``Server`` (``main`` then blocks on it;
    ``chip_smoke.py`` and tests drive it in-process). Anything that
    cannot be brought up — the backend, the mesh ``--mesh auto`` asks
    for, the preload — raises: there is no headless or CPU carry-on."""
    args = parse_args(argv if argv is not None else sys.argv[1:])
    cfg = load_config(args.config) if args.config else {}
    host = args.host or cfg.get("host", "127.0.0.1")
    port = args.port if args.port is not None else int(cfg.get("port", 4000))
    status_port = (args.status_port if args.status_port is not None
                   else int(cfg.get("status_port", 10080)))
    if status_port < 0:
        status_port = None
    mesh_mode = args.mesh or cfg.get("mesh", "auto")
    sf = args.load_tpch if args.load_tpch is not None else cfg.get("load_tpch")
    root_pw = (args.root_password if args.root_password is not None
               else cfg.get("root_password"))
    plugin_mods = (args.plugin_modules if args.plugin_modules is not None
                   else cfg.get("plugin_modules", ""))

    import tidb_tpu  # noqa: F401  (x64 config before jax backend init)

    device = args.device or cfg.get("device", "default")
    if device != "default":
        import jax

        jax.config.update("jax_platforms", device)

    from tidb_tpu.server.server import Server
    from tidb_tpu.storage.catalog import Catalog
    from tidb_tpu.utils.device import device_info

    info = device_info()  # initialises the backend; a failure raises
    mesh = None
    if mesh_mode == "auto":
        from tidb_tpu.parallel import make_mesh

        mesh = make_mesh()

    catalog = Catalog()
    # SQL-reachable plugin imports are allowlisted on the wire server
    catalog.plugins.allowed_prefixes = tuple(
        p.strip() for p in str(plugin_mods).split(",") if p.strip())
    if root_pw:
        catalog.set_password("root", root_pw)
    if sf:
        from tidb_tpu.storage.tpch import load_tpch

        counts = load_tpch(catalog, sf=float(sf))
        print(f"# loaded TPC-H sf={sf}: {counts}", file=sys.stderr)

    server = Server(catalog=catalog, host=host, port=port, mesh=mesh,
                    status_port=status_port)
    server.start()
    if server.status_port is not None:
        print(f"# status port http://{server.host}:{server.status_port}"
              "/metrics /status /schema", file=sys.stderr)
    print(f"# device platform={info['platform']} "
          f"device_kind={info['device_kind']} devices={info['count']} "
          f"mesh={mesh_mode}", file=sys.stderr)
    print(f"# tidb_tpu server listening on {server.host}:{server.port}",
          file=sys.stderr)
    return server


def main(argv=None) -> int:
    try:
        server = boot(argv)
    except Exception as e:  # noqa: BLE001 — any bring-up failure is fatal
        print(f"# tidb_tpu failed to start: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    try:
        server._accept_thread.join()
    except KeyboardInterrupt:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
