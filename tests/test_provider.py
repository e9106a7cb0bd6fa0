"""FD provider seam (vfd/FDProvider.java analog): the pure-Python
backend serves the same surface as the native one. The whole suite runs
against it in CI spirit via VPROXY_TPU_FD_PROVIDER=py; these tests pin
the selection mechanics and the Python pump engine directly."""
import os
import pathlib
import socket
import subprocess
import sys
import time

import pytest

from vproxy_tpu.net import vtl_py

REPO = str(pathlib.Path(__file__).resolve().parents[1])


def test_env_selects_python_provider():
    r = subprocess.run(
        [sys.executable, "-c",
         "from vproxy_tpu.net import vtl\n"
         "assert vtl.PROVIDER == 'py', vtl.PROVIDER\n"
         "assert type(vtl.LIB).__name__ == 'PyLib'\n"
         "lfd = vtl.tcp_listen('127.0.0.1', 0)\n"
         "ip, port = vtl.sock_name(lfd)\n"
         "assert port > 0\n"
         "vtl.close(lfd)\n"
         "print('py provider ok')"],
        capture_output=True, text=True, timeout=60,
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
             "VPROXY_TPU_FD_PROVIDER": "py"})
    assert r.returncode == 0, r.stderr
    assert "py provider ok" in r.stdout


@pytest.mark.parametrize("test_file", ["test_docker_plugin.py",
                                       "test_tls.py"])
def test_suite_file_runs_on_the_python_provider(test_file):
    """The provider the loader falls back to serves these files with no
    failure (native-only tests may skip): the docker plugin's sockets
    and the TLS capability probe both go through LIB."""
    r = subprocess.run(
        [sys.executable, "-m", "pytest", f"tests/{test_file}", "-q",
         "-p", "no:cacheprovider", "-p", "no:randomly"],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**{k: v for k, v in os.environ.items()
                if not k.startswith("PYTEST_")},
             "JAX_PLATFORMS": "cpu", "VPROXY_TPU_FD_PROVIDER": "py"})
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-1000:]
    assert " passed" in r.stdout and "failed" not in r.stdout, r.stdout[-500:]


def test_python_pump_splices_and_reports_done():
    """The Python pump mirrors the native engine: bidirectional bytes,
    FIN propagation, byte counters, EV_PUMP_DONE via poll."""
    lib = vtl_py.PyLib()
    lp = lib.vtl_new()
    a0, a1 = socket.socketpair()
    b0, b1 = socket.socketpair()
    for s in (a0, a1, b0, b1):
        s.setblocking(False)
    # register the pump ends with LIVE wrappers (detach invalidates the
    # original objects) — FIN propagation shuts down via the registry
    fd_a = a1.detach()
    fd_b = b0.detach()
    vtl_py._socks[fd_a] = socket.socket(fileno=fd_a)
    vtl_py._socks[fd_b] = socket.socket(fileno=fd_b)
    pid = lib.vtl_pump_new(lp, fd_a, fd_b, 8192)
    assert pid > 0

    a0.sendall(b"x" * 10000)   # a -> b
    b1.sendall(b"y" * 5000)    # b -> a
    tags = [0] * 64
    evs = [0] * 64
    got_a2b = b""
    got_b2a = b""
    deadline = time.time() + 5
    done = False
    a0.shutdown(socket.SHUT_WR)
    b1.shutdown(socket.SHUT_WR)
    while time.time() < deadline and not done:
        n = lib.vtl_poll(lp, tags, evs, 64, 100)
        for i in range(n):
            if evs[i] == vtl_py.EV_PUMP_DONE:
                assert tags[i] == pid
                done = True
        for s, _ in ((b1, "a2b"), (a0, "b2a")):
            try:
                d = s.recv(65536)
            except BlockingIOError:
                continue
            if s is b1:
                got_a2b += d
            else:
                got_b2a += d
    # drain whatever is left after done; both peers must then see EOF
    # (the pump propagated the FINs)
    eofs = 0
    for s in (b1, a0):
        deadline2 = time.time() + 3
        while time.time() < deadline2:
            try:
                d = s.recv(65536)
            except BlockingIOError:
                time.sleep(0.01)
                continue
            except OSError:
                break
            if not d:
                eofs += 1
                break
            if s is b1:
                got_a2b += d
            else:
                got_b2a += d
    assert done
    assert eofs == 2, "peers must see the propagated FINs"
    assert got_a2b == b"x" * 10000
    assert got_b2a == b"y" * 5000
    out = [0, 0, 0]
    assert lib.vtl_pump_stat(lp, pid, out) == 0
    assert out[0] == 10000 and out[1] == 5000 and out[2] == 0
    assert lib.vtl_pump_free(lp, pid) == 0
    lib.vtl_free(lp)
    a0.close()
    b1.close()
