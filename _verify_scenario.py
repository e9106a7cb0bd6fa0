"""verify scenario: hash classify path through the engine + tcp-lb e2e."""
import random, socket, threading, time
import numpy as np

# ---- 1. engine-level classify: hash backend vs oracle, with live update
from vproxy_tpu.rules.engine import CidrMatcher, HintMatcher
from vproxy_tpu.rules import oracle
from vproxy_tpu.rules.ir import AclRule, Hint, HintRule, Proto, RouteRule, RouteTable
from vproxy_tpu.utils.ip import Network, mask_bytes, parse_ip

rnd = random.Random(7)
rules = []
for i in range(5000):
    k = i % 10
    if k < 5: rules.append(HintRule(host=f"s{i}.ns{i%31}.corp.example"))
    elif k < 7: rules.append(HintRule(host=f"s{i}.ns{i%31}.corp.example", uri=f"/v{i%5}"))
    elif k < 8: rules.append(HintRule(host=f"s{i}.corp.example", port=443))
    elif k < 9: rules.append(HintRule(host="*", uri=f"/w{i%3}"))
    else: rules.append(HintRule(uri="*"))
hm = HintMatcher(rules, backend="jax")
hints = []
for i in range(512):
    j = rnd.randrange(5000)
    r = rules[j]
    h = r.host if r.host and r.host != "*" else f"s{j}.ns{j%31}.corp.example"
    if i % 4 == 0: hints.append(Hint(host=h, port=r.port or 0, uri=r.uri if r.uri != "*" else None))
    elif i % 4 == 1: hints.append(Hint(host="sub." + h, uri="/v3/extra"))
    elif i % 4 == 2: hints.append(Hint(host="nomatch.invalid", uri=f"/w{i%3}/x"))
    else: hints.append(Hint(uri=f"/v{i%5}"))
got = hm.match(hints)
want = [oracle.search(rules, h) for h in hints]
assert list(got) == want, [i for i,(g,w) in enumerate(zip(got,want)) if g!=w][:5]
print(f"[1] hint hash classify: 512 queries vs oracle on 5000 rules OK")

# live update (no retrace when shapes hold)
rules2 = rules[:2500] + [HintRule(host="brand.new.example")]
hm.set_rules(rules2)
assert hm.match([Hint(host="brand.new.example")])[0] == 2500
print(f"[2] live rule update OK (capacity reuse: {hm._caps['r_cap']})")

# routes + acl
rt = RouteTable()
for i in range(800):
    ml = rnd.choice([8, 12, 16, 24, 32])
    ip = bytes([10 + i % 4, rnd.randrange(256), rnd.randrange(256), 0])
    m = mask_bytes(ml)
    net = Network(bytes(np.frombuffer(ip, np.uint8) & np.frombuffer(m, np.uint8)), m)
    try: rt.add(RouteRule(f"r{i}", net))
    except ValueError: pass
nets = [r.rule for r in rt.rules]
cm = CidrMatcher(nets, backend="jax")
addrs = [bytes([10 + rnd.randrange(5), rnd.randrange(256), rnd.randrange(256), rnd.randrange(256)]) for _ in range(400)]
got = cm.match(addrs)
for i, a in enumerate(addrs):
    w = next((j for j, n in enumerate(nets) if n.contains_ip(a)), -1)
    assert got[i] == w, (i, got[i], w)
print(f"[3] LPM route hash classify: 400 addrs vs ordered scan on {len(nets)} routes OK")

acl = [AclRule("deny80", Network(parse_ip("10.2.0.0"), mask_bytes(16)), Proto.TCP, 80, 80, False),
       AclRule("allowall", Network(parse_ip("10.0.0.0"), mask_bytes(8)), Proto.TCP, 0, 65535, True)]
am = CidrMatcher([r.network for r in acl], backend="jax", acl=acl)
assert am.match([parse_ip("10.2.3.4")], [80])[0] == 0
assert am.match([parse_ip("10.2.3.4")], [443])[0] == 1
assert am.match([parse_ip("11.1.1.1")], [80])[0] == -1
print("[4] ACL port-range first-match OK")

# ---- 2. tcp-lb end-to-end on loopback (component stack incl. health checks)
from vproxy_tpu.components.elgroup import EventLoopGroup
from vproxy_tpu.components.secgroup import SecurityGroup
from vproxy_tpu.components.servergroup import HealthCheckConfig, ServerGroup
from vproxy_tpu.components.tcplb import TcpLB
from vproxy_tpu.components.upstream import Upstream

class IdServer:
    def __init__(self, sid):
        self.sid = sid.encode(); self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0)); self.sock.listen(64)
        self.port = self.sock.getsockname()[1]
        threading.Thread(target=self._serve, daemon=True).start()
    def _serve(self):
        while True:
            try: c, _ = self.sock.accept()
            except OSError: return
            c.sendall(self.sid); c.close()

a, b = IdServer("A"), IdServer("B")
elg = EventLoopGroup("worker", 2)
sg = ServerGroup("sg0", elg, HealthCheckConfig(timeout_ms=500, period_ms=200, up=1, down=2), method="wrr")
sg.add("a", "127.0.0.1", a.port, 1)
sg.add("b", "127.0.0.1", b.port, 1)
ups = Upstream("ups0"); ups.add(sg)
deadline = time.time() + 5
while time.time() < deadline and not all(s.healthy for s in sg.servers):
    time.sleep(0.05)
assert all(s.healthy for s in sg.servers), "health checks did not come up"
lb = TcpLB("lb0", elg, elg, "127.0.0.1", 0, ups, security_group=SecurityGroup.allow_all())
lb.start()
seen = set()
for _ in range(8):
    c = socket.create_connection(("127.0.0.1", lb.bind_port), timeout=3)
    seen.add(c.recv(16).decode()); c.close()
assert seen == {"A", "B"}, seen
print(f"[5] tcp-lb e2e on loopback: round-robin across both backends OK {seen}")
lb.stop(); sg.close(); elg.close()
print("VERIFY SCENARIO PASSED")

# ---- 6. micro-batch classify queue: concurrent http-splice through device
import threading as _th
from vproxy_tpu.rules.service import ClassifyService
ClassifyService.reset()
_svc = ClassifyService.get()
_svc.mode = "device"
from tests.test_tcplb import IdServer as _Id, fast_hc as _hc, http_get_id as _get, wait_healthy as _wh
from vproxy_tpu.components.elgroup import EventLoopGroup as _ELG
from vproxy_tpu.components.servergroup import ServerGroup as _SG
from vproxy_tpu.components.tcplb import TcpLB as _LB
from vproxy_tpu.components.upstream import Upstream as _UP
from vproxy_tpu.rules.ir import Hint as _Hint, HintRule as _HR

_elg = _ELG("w", 2); _a, _b = _Id("A", http=True), _Id("B", http=True)
_g1 = _SG("g1", _elg, _hc(), "wrr"); _g1.add("a", "127.0.0.1", _a.port)
_g2 = _SG("g2", _elg, _hc(), "wrr"); _g2.add("b", "127.0.0.1", _b.port)
_wh(_g1, 1); _wh(_g2, 1)
_u = _UP("u"); _u.add(_g1, annotations=_HR(host="a.corp")); _u.add(_g2, annotations=_HR(host="b.corp"))
_lb = _LB("lb", _elg, _elg, "127.0.0.1", 0, _u, protocol="http-splice"); _lb.start()
for _n in (16, 32):  # compile the batch-size buckets up front
    _u.search_batch([_Hint.of_host("warm.x")] * _n)

_res = [None] * 30
_ths = [_th.Thread(target=lambda i=i: _res.__setitem__(i, _get(_lb.bind_port, "a.corp" if i % 2 else "b.corp"))) for i in range(30)]
[t.start() for t in _ths]; [t.join(25) for t in _ths]
_bad = [(i, r) for i, r in enumerate(_res) if r is None or r[1] != ("A" if i % 2 else "B")]
assert not _bad, (_bad[:3], len(_bad), _svc.stats.snapshot())
assert _svc.stats.device_queries >= 30, _svc.stats.snapshot()
assert _svc.stats.dispatches < _svc.stats.queries, _svc.stats.snapshot()
print(f"[6] micro-batch queue: 30 concurrent http-splice reqs -> "
      f"{_svc.stats.dispatches} device dispatches, max batch {_svc.stats.max_batch} OK")
_lb.stop(); _g1.close(); _g2.close(); _elg.close()
print("VERIFY SCENARIO PASSED (incl. classify queue)")

# ---- 7. accept-path latency contract: in auto mode every query is
# answered inline from the host index in microseconds, however slow the
# device is (no query reaches it)
ClassifyService.reset()
_svc7 = ClassifyService.get()
assert _svc7.mode == "auto"
from vproxy_tpu.rules.engine import HintMatcher as _HM7
_rules7 = [_HR(host=f"svc{i}.accept.example") for i in range(20000)]
_m7 = _HM7(_rules7)
_m7.match([_Hint.of_host("warm.example")] * 16)
_real7 = _m7.dispatch_snap
def _slow7(snap, hints):
    time.sleep(0.05)  # a slow (50ms) device round trip
    return _real7(snap, hints)
_m7.dispatch_snap = _slow7
# calibrate the pass bound against THIS host's measured per-lookup cost
# (the raw index_snap the inline path rides): an absolute 1000us bound
# flakes on slow/contended hosts while hiding regressions on fast ones.
# 50x raw-lookup p50 covers the service layer (locks, stats, histogram);
# the 500us floor covers timer granularity on very fast hosts.
_snap7 = _m7.snapshot()
_cal7 = []
for _i in range(200):
    _t0 = time.perf_counter()
    _m7.index_snap(_snap7, _Hint.of_host(f"svc{_i}.accept.example"))
    _cal7.append(time.perf_counter() - _t0)
_cal7.sort()
_base7_us = _cal7[100] * 1e6
_bound7_us = max(500.0, 50.0 * _base7_us)
_lat7 = []
for _i in range(200):
    _fired = []
    _t0 = time.perf_counter()
    _svc7.submit_hint(_m7, _Hint.of_host(f"svc{_i}.accept.example"),
                      lambda idx, _pl: _fired.append(idx))
    _dt = time.perf_counter() - _t0
    assert _fired == [_i], (_i, _fired)   # inline: answered synchronously
    _lat7.append(_dt * 1e6)
_lat7.sort()
_p50, _p99 = _lat7[100], _lat7[198]
assert _p99 < _bound7_us, (_p50, _p99, _base7_us, _bound7_us)
print(f"[7] accept-path inline classify @20k rules: p50 {_p50:.1f}us "
      f"p99 {_p99:.1f}us over 200 lone queries, "
      f"{_svc7.stats.oracle_queries} host-indexed, "
      f"{_svc7.stats.device_queries} device OK")
print("VERIFY SCENARIO PASSED (incl. accept-path latency)")

# ---- 8. switch data plane (fast path) + DNS .vproxy.local introspection,
# driven end-to-end through the public surface (real UDP datagrams in,
# real datagrams out; command grammar for the dns resources)
from vproxy_tpu.components.secgroup import SecurityGroup as _SG8
from vproxy_tpu.net.eventloop import SelectorEventLoop as _L8
from vproxy_tpu.rules.ir import RouteRule as _RR8
from vproxy_tpu.utils.ip import Network as _N8, parse_ip as _pip8
from vproxy_tpu.vswitch.switch import Switch as _SW8, synthetic_mac as _smac8
from vproxy_tpu.vswitch import packets as _P8

_l8 = _L8("v8"); _l8.loop_thread()
_sw8 = _SW8("v8", _l8, "127.0.0.1", 0)
_sw8.start()
_n81 = _sw8.add_network(11, _N8.parse("10.8.0.0/16"))
_n82 = _sw8.add_network(12, _N8.parse("10.9.0.0/16"))
_gw8 = _pip8("10.8.0.1"); _n81.ips.add(_gw8, _smac8(11, _gw8))
_s28 = _pip8("10.9.255.1"); _n82.ips.add(_s28, _smac8(12, _s28))
_n81.add_route(_RR8("r", _N8.parse("10.9.0.0/16"), to_vni=12))
import socket as _sk8
_h8 = _sk8.socket(_sk8.AF_INET, _sk8.SOCK_DGRAM); _h8.bind(("127.0.0.1", 0)); _h8.settimeout(5)
_hmac8 = b"\x02\x77\x00\x00\x00\x01"
_dmac8 = b"\x02\x77\x00\x00\x00\x02"
_n82.macs.record(_dmac8, type("RawSink", (), {
    "name": "sink", "local_side_vni": 0,
    "send_vxlan": lambda self, sw, p: None,
    "send_vxlan_raw": lambda self, sw, d: _h8.sendto(d, _h8.getsockname()),
})())
for _i in range(64):
    _n82.arps.record(bytes([10, 9, 0, 1 + _i]), _dmac8)
_out8 = 0
_burst8 = []
for _i in range(64):
    _ip8 = _P8.Ipv4(src=bytes([10, 8, 0, 2]), dst=bytes([10, 9, 0, 1 + _i]),
                    proto=17, payload=b"z" * 8, ttl=33)
    _e8 = _P8.Ethernet(_smac8(11, _gw8), _hmac8, 0x0800, b"", packet=_ip8)
    _burst8.append((_P8.Vxlan(11, _e8).to_bytes(), "127.0.0.1", 33333))
_l8.call_sync(lambda: _sw8._input_batch(_burst8), timeout=60)
for _i in range(64):
    _d8, _ = _h8.recvfrom(4096)
    _vx8 = _P8.Vxlan.parse(_d8)
    assert _vx8.vni == 12 and _vx8.ether.packet.ttl == 32
    _out8 += 1
assert _sw8.fastpath is not None
print(f"[8a] switch fast path: 64/{_out8} routed v4 datagrams re-encapped "
      f"(vni 11->12, ttl 33->32, checksum verified by parser) OK")
_sw8.stop(); _l8.close(); _h8.close()

from vproxy_tpu.control.app import Application as _App8
from vproxy_tpu.control.command import Command as _C8
import os as _os8, sys as _sys8
_sys8.path.insert(0, _os8.path.join(
    _os8.path.dirname(_os8.path.abspath(__file__)), "tests"))
from tests.test_dns import dns_query as _dq8
from vproxy_tpu.dns import packet as _DP8
_app8 = _App8.create(workers=1)
try:
    _C8.execute(_app8, "add upstream u8")
    _C8.execute(_app8, "add tcp-lb web8 address 127.0.0.1:0 upstream u8")
    _C8.execute(_app8, "add dns-server d8 address 127.0.0.1:0 upstream u8")
    _r8 = _dq8(_app8.dns_servers["d8"].bind_port, "web8.tcp-lb.vproxy.local.")
    assert _r8.answers and _r8.answers[0].rdata == _pip8("127.0.0.1")
    _r8b = _dq8(_app8.dns_servers["d8"].bind_port, "who.am.i.vproxy.local.")
    assert _r8b.answers[0].rdata == _pip8("127.0.0.1")
    print("[8b] dns .vproxy.local introspection: live tcp-lb resolved via "
          "UDP query OK")
finally:
    _app8.close()
print("VERIFY SCENARIO PASSED (incl. switch fast path + dns introspection)")

# ---- 9. multi-host mesh surface: the 2-host simulated layout through the
# public dryrun entry (tables replicated per host, rules sharded in-host).
# Fresh subprocess: the virtual device count must be set before jax init.
import os as _os9, subprocess as _sp9, sys as _sys9
_env9 = {k: v for k, v in _os9.environ.items()
         if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS")}
_env9["PYTHONPATH"] = _os9.path.dirname(_os9.path.abspath(__file__))
_r9 = _sp9.run([_sys9.executable, "-c",
                "import __graft_entry__ as G; G.dryrun_multichip(8)"],
               env=_env9, capture_output=True, timeout=300,
               cwd=_env9["PYTHONPATH"])
assert _r9.returncode == 0, _r9.stdout[-2000:] + _r9.stderr[-2000:]
assert b"2-host (host,batch,rules) replicated-table layout verified" in     _r9.stdout, _r9.stdout[-500:]
print("[9] multi-host dryrun (8 devices, 2-host simulated layout) OK")
print("VERIFY SCENARIO PASSED (incl. multi-host mesh dryrun)")

# ---- 10. native TLS splice: a real TLS client through a TLS-terminating
# tcp-lb whose record layer runs in the C pump (OpenSSL via dlopen)
import ssl as _ssl10, subprocess as _sp10, tempfile as _tf10
from vproxy_tpu.net import vtl as _vtl10
if _vtl10.tls_available() and _vtl10.PROVIDER == "native":
    _d10 = _tf10.mkdtemp()
    _crt10, _key10 = f"{_d10}/c.crt", f"{_d10}/c.key"
    _sp10.run(["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
               "-keyout", _key10, "-out", _crt10, "-days", "2",
               "-subj", "/CN=v10.example.com"], check=True,
              capture_output=True)
    from vproxy_tpu.components.certkey import CertKey as _CK10
    from vproxy_tpu.components.elgroup import EventLoopGroup as _ELG10
    from vproxy_tpu.components.servergroup import ServerGroup as _SG10
    from vproxy_tpu.components.tcplb import TcpLB as _LB10
    from vproxy_tpu.components.upstream import Upstream as _UP10
    from tests.test_tcplb import IdServer as _Id10, fast_hc as _hc10, \
        wait_healthy as _wh10
    _elg10 = _ELG10("w10", 1)
    _s10 = _Id10("T")
    _g10 = _SG10("g10", _elg10, _hc10(), "wrr")
    _g10.add("t", "127.0.0.1", _s10.port)
    _wh10(_g10, 1)
    _u10 = _UP10("u10"); _u10.add(_g10)
    _lb10 = _LB10("lb10", _elg10, _elg10, "127.0.0.1", 0, _u10,
                  protocol="tcp", cert_keys=[_CK10("c", _crt10, _key10)])
    _lb10.start()
    _cx10 = _ssl10.SSLContext(_ssl10.PROTOCOL_TLS_CLIENT)
    _cx10.check_hostname = False
    _cx10.verify_mode = _ssl10.CERT_NONE
    import socket as _sk10
    with _sk10.create_connection(("127.0.0.1", _lb10.bind_port),
                                 timeout=5) as _raw10:
        with _cx10.wrap_socket(_raw10,
                               server_hostname="v10.example.com") as _c10:
            _c10.settimeout(5)
            _c10.sendall(b"ping")
            _r10 = _c10.recv(16)
    assert _r10.startswith(b"T"), _r10
    _lb10.stop(); _g10.close(); _s10.close(); _elg10.close()
    print("[10] native TLS splice: handshake+echo through the C-side "
          "OpenSSL pump OK")
else:
    print("[10] native TLS unavailable in this env (skipped)")
print("VERIFY SCENARIO PASSED (incl. native TLS splice)")
