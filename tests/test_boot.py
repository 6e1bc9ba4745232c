"""Start-up: the boot function of ``python -m tidb_tpu`` names its
device and fails loudly, and the compile cache is placeable from
outside (ISSUE 22)."""

import json
import os
import subprocess
import sys
import urllib.request

import pytest

from tidb_tpu import __main__ as entry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_boot_reports_its_device_on_status(capsys):
    server = entry.boot(["--device", "cpu", "--port", "0",
                         "--status-port", "0"])
    try:
        assert server.mesh is not None  # --mesh auto is the default
        with urllib.request.urlopen(
                f"http://{server.host}:{server.status_port}/status",
                timeout=10) as r:
            status = json.loads(r.read())
        assert status["platform"] == "cpu"
        assert status["device_kind"]
        assert status["count"] >= 1
        assert server.device == {k: status[k] for k in
                                 ("platform", "device_kind", "count")}
        (line,) = [ln for ln in capsys.readouterr().err.splitlines()
                   if ln.startswith("# device ")]
        assert "platform=cpu" in line and "device_kind=" in line \
            and f"devices={status['count']}" in line
    finally:
        server.stop()


def test_mesh_auto_that_cannot_build_a_mesh_exits_nonzero(monkeypatch, capsys):
    """No headless boot: --mesh auto without a mesh is an error."""
    import tidb_tpu.parallel as par

    def boom(*_a, **_k):
        raise ValueError("mesh 1x9 needs 9 devices, have 8")

    monkeypatch.setattr(par, "make_mesh", boom)
    argv = ["--device", "cpu", "--port", "0", "--status-port", "-1"]
    with pytest.raises(ValueError):
        entry.boot(argv)
    assert entry.main(argv) != 0
    assert "failed to start" in capsys.readouterr().err
    # the explicit single-device choice still boots
    server = entry.boot(argv + ["--mesh", "none"])
    try:
        assert server.mesh is None
    finally:
        server.stop()


def test_accelerator_without_host_backend_stops_the_boot(monkeypatch, capsys):
    """JAX_PLATFORMS=tpu initialises no cpu backend, and the host glue
    needs one: start-up says so once instead of every statement failing
    inside host_eager()."""
    import jax

    from tidb_tpu.utils import device

    class Chip:
        platform, device_kind = "tpu", "TPU v5 lite"

    def no_cpu(backend=None, **_k):
        raise RuntimeError(f"Unknown backend {backend}")

    for name in ("_probed", "_cpu_device", "_accel_device"):
        monkeypatch.setattr(device, name, getattr(device, name))  # restored
    device._probed = False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [Chip()])
    monkeypatch.setattr(jax, "local_devices", no_cpu)
    argv = ["--port", "0", "--status-port", "-1", "--mesh", "none"]
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS must include cpu"):
        entry.boot(argv)
    assert entry.main(argv) != 0
    assert "JAX_PLATFORMS must include cpu" in capsys.readouterr().err


def _cache_config(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, jax, tidb_tpu; print(json.dumps(["
         "jax.config.jax_compilation_cache_dir, "
         "jax.config.jax_persistent_cache_min_compile_time_secs]))"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=120,
        check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("env_dir", ["/some/dir", None],
                         ids=["env-set", "env-unset"])
def test_compile_cache_directory(env_dir):
    """JAX_COMPILATION_CACHE_DIR wins when set (tidb_tpu names no
    directory in code); otherwise <checkout>/.jax_cache. One threshold
    of at most a second for every process."""
    cache_dir, min_secs = _cache_config(env_dir)
    assert cache_dir == (env_dir or os.path.join(ROOT, ".jax_cache"))
    assert min_secs <= 1.0


def _run_smoke(args, cwd, extra_env=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(extra_env or {})}
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def test_chip_smoke_alone_fails_and_prints_no_result(tmp_path):
    """In a directory that holds the script and nothing else of the repo
    it must fail without a result line."""
    import shutil

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = _run_smoke([], str(tmp_path), {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_chip_smoke_rehearsal_walks_every_phase_and_never_says_ok():
    """The guide's first rehearsal as a test: SF0.01 on the CPU through
    boot(), both connections, the transaction on the fused tier and the
    read-back — and, without a TPU, never `"ok": true`, never exit 0."""
    out = _run_smoke(["--rehearse"], ROOT,
                     {"XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    assert out.returncode != 0, out.stderr[-2000:]
    assert lines and lines[-1]["ok"] is False and lines[-1]["rehearsal"], \
        out.stderr[-2000:]
    assert '"ok": true' not in out.stdout
    phases = [ln.get("phase") for ln in lines]
    for want in ("boot", "reference", "statement", "resident",
                 "transaction", "read_back", "point_get", "summary"):
        assert want in phases, (want, phases)
    txn = next(ln for ln in lines if ln.get("phase") == "transaction")
    assert set(txn["placement"]) >= {"stage", "fused"}
    assert next(ln for ln in lines if ln.get("phase") == "point_get")[
        "device_dispatches"] == {}
