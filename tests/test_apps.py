"""Apps: Simple mode, HelloWorld, KcpTun, ServerAddressUpdater.

Reference analogs: vproxyx/Simple.java, HelloWorld.java, KcpTun.java,
app/ServerAddressUpdater.java — exercised on loopback like the
reference's CI does.
"""
import socket
import threading
import time

import pytest

from vproxy_tpu.net.eventloop import SelectorEventLoop


def wait_for(cond, timeout=8.0):
    t0 = time.time()
    while not cond():
        if time.time() - t0 > timeout:
            raise TimeoutError()
        time.sleep(0.01)


def _echo_id_backend(tag: bytes):
    """fake backend that answers any data with its id (SURVEY §4 pattern)."""
    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(16)
    port = srv.getsockname()[1]

    def run():
        while True:
            try:
                c, _ = srv.accept()
            except OSError:
                return
            try:
                c.recv(4096)
                c.sendall(tag)
                c.close()
            except OSError:
                pass
    threading.Thread(target=run, daemon=True).start()
    return srv, port


def test_simple_mode_gen_script():
    from vproxy_tpu.apps.simple import build_script, parse_args
    bind, backends, protocol, ssl, gen = parse_args(
        ["bind", "8080", "backend", "127.0.0.1:81,127.0.0.1:82",
         "protocol", "http", "gen"])
    assert gen and bind == 8080 and len(backends) == 2
    script = build_script(bind, backends, protocol, ssl)
    assert script[0] == "add upstream ups0"
    assert any("tcp-lb" in l and "protocol http" in l for l in script)
    assert sum("add server " in l for l in script) == 2


def test_simple_mode_lb_end_to_end():
    """the build_script commands produce a working LB."""
    from vproxy_tpu.apps.simple import build_script
    from vproxy_tpu.control.app import Application
    from vproxy_tpu.control.command import Command

    s1, p1 = _echo_id_backend(b"b1")
    s2, p2 = _echo_id_backend(b"b2")
    app = Application.create(workers=1)
    try:
        for line in build_script(0, [f"127.0.0.1:{p1}", f"127.0.0.1:{p2}"],
                                 "tcp", None):
            Command.execute(app, line)
        lb = app.tcp_lbs["lb0"]
        port = lb.server_socks[0].port
        # wait for health checks to mark backends up
        g = app.server_groups["sg0"]
        wait_for(lambda: all(s.healthy for s in g.servers), timeout=15)
        seen = set()
        for _ in range(8):
            c = socket.create_connection(("127.0.0.1", port), timeout=3)
            c.sendall(b"x")
            seen.add(c.recv(16))
            c.close()
        assert seen == {b"b1", b"b2"}  # balanced over both
    finally:
        app.close()
        s1.close()
        s2.close()


def test_helloworld_tcp_udp_echo():
    from vproxy_tpu.apps.helloworld import GREETING, start
    loop = SelectorEventLoop("hwtest")
    loop.loop_thread()
    try:
        tcp, udp, port = start(loop, 0)
        c = socket.create_connection(("127.0.0.1", port), timeout=3)
        c.sendall(b"ping")
        buf = c.recv(256)
        assert buf.startswith(GREETING)
        c.close()
        u = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        u.settimeout(3)
        u.sendto(b"uping", ("127.0.0.1", port))
        data, _ = u.recvfrom(256)
        assert data == GREETING + b"uping"
        u.close()
    finally:
        loop.close()


def test_kcptun_end_to_end():
    """client TCP -> kcp tunnel -> server -> target echo backend."""
    from vproxy_tpu.apps.kcptun import TunClient, run_server

    tgt, tport = _echo_id_backend(b"target-hit")
    loop = SelectorEventLoop("kcptun-test")
    loop.loop_thread()
    try:
        usrv = run_server(loop, 0, "127.0.0.1", tport)
        uport = usrv.local[1]
        cli = TunClient(loop, 0, "127.0.0.1", uport, bind_ip="127.0.0.1")
        wait_for(lambda: cli.sess is not None and cli.sess.up, timeout=8)
        c = socket.create_connection(("127.0.0.1", cli.port), timeout=5)
        c.sendall(b"hello-tunnel")
        c.settimeout(5)
        assert c.recv(64) == b"target-hit"
        c.close()
        cli.close()
        usrv.close()
    finally:
        loop.close()
        tgt.close()


def test_server_address_updater_swaps_ip():
    from vproxy_tpu.components.elgroup import EventLoopGroup
    from vproxy_tpu.components.servergroup import (HealthCheckConfig,
                                                   ServerGroup)
    from vproxy_tpu.components.updater import ServerAddressUpdater

    elg = EventLoopGroup("upd", 1)
    g = ServerGroup("g", elg, HealthCheckConfig(protocol="none",
                                                period_ms=100))
    try:
        s = g.add("s0", "10.255.0.1", 80)  # stale ip
        s.host_name = "localhost"
        upd = ServerAddressUpdater(lambda: [g])
        changed = upd.check_once()
        assert changed == {"g/s0": "127.0.0.1"}
        assert g.servers[0].ip == "127.0.0.1"
        # second pass: no change
        assert upd.check_once() == {}
        upd.close()
    finally:
        g.close()
        elg.close()


def test_daemon_restart_and_reload_logic(tmp_path, monkeypatch):
    """drive Daemon._do_reload/crash-restart with a stub child process."""
    import vproxy_tpu.apps.daemon as D

    class FakeProc:
        n = 0
        platform = "tpu"  # what the child's boot line reported

        def __init__(self):
            FakeProc.n += 1
            self.pid = 1000 + FakeProc.n
            self._rc = None
            self.signals = []

        def poll(self):
            return self._rc

        def send_signal(self, sig):
            self.signals.append(sig)
            self._rc = 0

        def wait(self, timeout=None):
            return self._rc

        def kill(self):
            self._rc = -9

    d = D.Daemon([])
    monkeypatch.setattr(d, "_spawn", lambda: FakeProc())
    monkeypatch.setattr(D, "RELOAD_GRACE_S", 0.1)
    d.child = d._spawn()
    first = d.child
    d._do_reload()
    assert d.child is not first          # new child took over
    assert first.signals                 # old child got SIGTERM
    assert first.poll() is not None

    # a reload whose new child lands on the cpu after an accelerator
    # served is a FAILED reload: new child killed, old one kept
    d.accelerator = "tpu"
    kept = d.child

    def cpu_child():
        p = FakeProc()
        p.platform = "cpu"
        return p

    monkeypatch.setattr(d, "_spawn", cpu_child)
    d._do_reload()
    assert d.child is kept and kept.poll() is None
