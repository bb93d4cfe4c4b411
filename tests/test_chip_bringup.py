"""Bring-up invariants (ISSUE 21): nothing on the main path lets a CPU run
pass for a chip run, one process owns the chip, and chip_smoke.py's control
flow holds on the CPU at a tiny size."""

import http.server
import json
import os
import socket
import subprocess
import sys
import textwrap
import threading
import time
import types

import pytest

from predictionio_tpu.parallel import mesh as M
from predictionio_tpu.tools import cli

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def _fake_jax(requested, devices):
    def _devices():
        if isinstance(devices, Exception):
            raise devices
        return devices
    return types.SimpleNamespace(
        config=types.SimpleNamespace(jax_platforms=requested),
        devices=_devices)


class TestPlatformResolver:
    """device_platform(): a TPU, or an error that names what JAX reported
    — the CPU only under an explicit JAX_PLATFORMS=cpu."""

    @pytest.fixture(autouse=True)
    def fresh(self, monkeypatch):
        monkeypatch.setattr(M, "_platform", None)

    def test_cpu_without_being_asked_is_an_error(self, monkeypatch):
        # JAX_PLATFORMS unset and the TPU backend failed quietly: jax
        # hands back CPU devices and says nothing
        monkeypatch.setattr(
            M, "_jax", lambda: _fake_jax("", [_Dev("cpu", "cpu")]))
        with pytest.raises(M.DeviceUnavailable, match="'cpu'") as e:
            M.device_platform()
        assert "JAX_PLATFORMS=<unset>" in str(e.value)
        assert M._platform is None          # a failure is not memoised

    def test_gpu_is_not_a_tpu_either(self, monkeypatch):
        monkeypatch.setattr(
            M, "_jax", lambda: _fake_jax("", [_Dev("gpu", "A100")]))
        with pytest.raises(M.DeviceUnavailable, match="A100"):
            M.device_platform()

    def test_loud_backend_failure_becomes_device_unavailable(
            self, monkeypatch):
        # JAX_PLATFORMS=tpu,cpu (the chip machine's setting) and the
        # chip is held by another process: jax raises at first use
        boom = RuntimeError("Unable to initialize backend 'tpu': ABORTED: "
                            "libtpu multi-process lockfile")
        monkeypatch.setattr(M, "_jax", lambda: _fake_jax("tpu,cpu", boom))
        with pytest.raises(M.DeviceUnavailable, match="lockfile"):
            M.device_platform()

    def test_explicit_cpu_and_tpu_resolve_once(self, monkeypatch):
        monkeypatch.setattr(
            M, "_jax", lambda: _fake_jax("cpu", [_Dev("cpu", "cpu")] * 8))
        assert M.device_platform() == {
            "platform": "cpu", "device_kind": "cpu", "n": 8}
        # resolved once: a later call never re-probes
        monkeypatch.setattr(M, "_jax", lambda: pytest.fail("re-resolved"))
        assert M.device_platform()["n"] == 8
        monkeypatch.setattr(M, "_platform", None)
        monkeypatch.setattr(
            M, "_jax", lambda: _fake_jax("", [_Dev("tpu", "TPU v5 lite")]))
        assert M.device_platform() == {
            "platform": "tpu", "device_kind": "TPU v5 lite", "n": 1}


def test_host_only_servers_create_no_backend(tmp_path):
    """The event server and the dashboard own no device: constructing
    them and rendering /metrics over HTTP must not initialize ANY jax
    backend (the device-memory gauge used to call jax.local_devices()
    at collect time, taking the chip from the engine server)."""
    prog = textwrap.dedent("""
        import sys, urllib.request
        sys.path.insert(0, %(repo)r)
        from predictionio_tpu.data.api.event_server import (
            EventServer, EventServerConfig)
        from predictionio_tpu.tools.dashboard import (Dashboard,
                                                      DashboardConfig)
        es = EventServer(EventServerConfig(ip="127.0.0.1", port=0,
                                           stats=True)).start()
        db = Dashboard(DashboardConfig(ip="127.0.0.1", port=0)).start()
        for port in (es.config.port, db.config.port):
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30).read()
            assert b"pio_compile_requests_total" in body, body[:200]
            assert b"pio_jax_device_memory_bytes" not in body
        es.stop(); db.stop()
        from jax._src import xla_bridge
        assert xla_bridge._backends == {}, xla_bridge._backends
        assert not xla_bridge.backends_are_initialized()
        print("NO-BACKEND")
        """) % {"repo": REPO}
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PIO_FS_BASEDIR"] = str(tmp_path / "store")
    p = subprocess.run([sys.executable, "-c", prog], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0 and "NO-BACKEND" in p.stdout, p.stderr[-3000:]


def test_host_only_verbs_are_pinned_before_they_run(monkeypatch):
    """cli.main() pins every verb that owns no device to the CPU backend
    before dispatching it; the device verbs are left to the resolver."""
    pinned = []
    monkeypatch.setattr(M, "host_only", lambda: pinned.append(True))
    for verb, fn in (("version", "cmd_version"), ("train", "cmd_train")):
        monkeypatch.setattr(cli, fn, lambda args: 0)
    assert cli.main(["version"]) == 0 and pinned == [True]
    assert cli.main(["train"]) == 0 and pinned == [True]
    assert {"train", "deploy", "update", "eval", "run"} <= cli.DEVICE_VERBS
    assert not {"eventserver", "dashboard", "adminserver", "status", "app",
                "accesskey", "import", "export", "template"} \
        & cli.DEVICE_VERBS


class TestDeployFreesTheChipFirst:
    def test_stale_server_is_stopped_before_the_first_jax_call(
            self, monkeypatch):
        """cmd_deploy POSTs /stop to a stale server on its port and waits
        for it to go BEFORE anything that can initialize a backend (the
        stale server is the process holding the chip)."""
        order = []

        class _Reached(Exception):
            pass

        def first_jax_call():
            order.append("jax")
            raise _Reached()

        monkeypatch.setattr(
            cli, "_stop_stale_server",
            lambda ip, port: order.append(("stop", ip, port)))
        monkeypatch.setattr(M, "init_distributed", first_jax_call)
        args = cli.build_parser().parse_args(["deploy", "--port", "18123"])
        with pytest.raises(_Reached):
            cli.cmd_deploy(args)
        assert order == [("stop", "127.0.0.1", 18123), "jax"]
        # mesh workers own no port: only the primary probes
        order.clear()
        monkeypatch.setenv("PIO_PROCESS_ID", "1")
        with pytest.raises(_Reached):
            cli.cmd_deploy(args)
        assert order == ["jax"]

    def test_stop_waits_until_the_listener_is_gone(self):
        """_stop_stale_server returns only once the port refuses
        connections — /stop is acknowledged well before the old process
        has let go."""
        closed_at = []

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"{}")

                def later():
                    time.sleep(0.8)
                    closed_at.append(time.monotonic())
                    srv.shutdown()
                    srv.server_close()
                threading.Thread(target=later, daemon=True).start()

            def log_message(self, *a):
                pass

        srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        port = srv.server_address[1]
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        cli._stop_stale_server("127.0.0.1", port)
        returned = time.monotonic()
        t.join(timeout=10)
        assert not t.is_alive()
        assert closed_at and returned >= closed_at[0]
        with pytest.raises(OSError):
            socket.create_connection(("127.0.0.1", port), timeout=1)
        # nothing listening: returns at once, no error
        cli._stop_stale_server("127.0.0.1", port)


def test_chip_smoke_tiny_passes_on_cpu(tmp_path):
    """chip_smoke.py --tiny: the chip smoke's whole control flow — store
    populate, event server REST, pio train / deploy / queries / status /
    update / undeploy / redeploy as separate processes, the float64 row
    re-solve and the served top-k check — at toy size on the CPU, with
    the compile cache placed from outside. Only the platform assertion
    is relaxed, and the result line says platform=cpu."""
    cache = tmp_path / "placed_cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--tiny"],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, (p.stdout[-3000:], p.stderr[-3000:])
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu",
                                           "kind": "cpu", "count": 1}}
    assert "platform=cpu" in p.stdout
    assert "compiled nothing" in p.stdout      # second deploy: cache hits
    assert any(f.is_file() for f in cache.iterdir())


def test_chip_smoke_refuses_to_run_without_a_chip_or_a_checkout(tmp_path):
    """The chip run (no --tiny) on a machine whose JAX finds no TPU, and
    the script alone in an empty directory: both exit non-zero and print
    no result line."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and '"ok"' not in p.stdout
    assert "no accelerator" in p.stderr
    alone = tmp_path / "alone"
    alone.mkdir()
    with open(os.path.join(REPO, "chip_smoke.py")) as src, \
            open(alone / "chip_smoke.py", "w") as dst:
        dst.write(src.read())
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=alone,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and '"ok"' not in p.stdout
    assert "bin/pio not found" in p.stderr
