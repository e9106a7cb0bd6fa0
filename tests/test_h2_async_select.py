"""The h2 processor's asynchronous select: a new stream is parked until
`engine.select_async` answers — at once, later, or after the session has
gone — and what arrives for it meanwhile (DATA, END_STREAM, trailers,
RST_STREAM) waits behind its request; then 48 streams on one connection
through a served TcpLB(protocol="h2") over an Upstream of multi-rule
`source` groups, ClassifyService mode `device`, each reaching the
backend the plain reference names, with the l7 spans and counters."""
import os
import socket
import struct
import sys
import threading
import time
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "drivers"))

import h2_backends  # noqa: E402  (plain data and sockets, no program)
import h2_wire as W  # noqa: E402
import reference_h2  # noqa: E402

from vproxy_tpu.components.elgroup import EventLoopGroup  # noqa: E402
from vproxy_tpu.components.servergroup import (  # noqa: E402
    HealthCheckConfig, ServerGroup)
from vproxy_tpu.components.tcplb import TcpLB  # noqa: E402
from vproxy_tpu.components.upstream import Upstream  # noqa: E402
from vproxy_tpu.processors import hpack  # noqa: E402
from vproxy_tpu.processors.base import ProcessorEngine  # noqa: E402
from vproxy_tpu.processors.h2 import (  # noqa: E402
    DATA, ERR_REFUSED, F_END_HEADERS, F_END_STREAM, HEADERS, PREFACE,
    RST_STREAM, SETTINGS, WINDOW_UPDATE, H2Session, _Side, frame)
from vproxy_tpu.rules import engine as E  # noqa: E402
from vproxy_tpu.rules import maglev as MG  # noqa: E402
from vproxy_tpu.rules.ir import HintRule  # noqa: E402
from vproxy_tpu.rules.service import ClassifyService  # noqa: E402
from vproxy_tpu.utils import trace  # noqa: E402
from vproxy_tpu.utils.metrics import GlobalInspection  # noqa: E402

SEL = SimpleNamespace(key=("10.9.0.1", 80))
REQ = [(b":method", b"POST"), (b":scheme", b"http"), (b":path", b"/up"),
       (b":authority", b"a.example.com")]


def parked_total() -> int:
    return GlobalInspection.get().get_counter(
        "vproxy_l7_parked_frames_total").value()


class Engine(ProcessorEngine):
    """What a session may call, recorded; selections answered inline
    (`inline`) or held until the test answers them."""

    def __init__(self, inline=None):
        self.front = bytearray()
        self.backs: dict = {}
        self.pending: list = []
        self.inline = inline
        self.closed = False

    def send_front(self, data):
        self.front += data

    def send_back(self, conn_id, data):
        self.backs.setdefault(conn_id, bytearray()).extend(data)

    def select_async(self, hint, cb):
        if self.inline is not None:
            cb(self.inline)
        else:
            self.pending.append((hint, cb))

    def open(self, sel):
        return len(self.backs) + 1

    def close_back(self, conn_id):
        pass

    def close(self):
        self.closed = True


def client_side(eng: Engine) -> tuple:
    """A session past its preface, and an encoder for the client."""
    s = H2Session(eng, ("172.20.0.7", 5555))
    s.on_front_data(PREFACE + frame(SETTINGS, 0, 0))
    return s, hpack.Encoder()


def back_frames(eng: Engine, conn_id: int = 1) -> list:
    """What the session sent toward a backend: [(type, flags, sid,
    payload or headers)]."""
    side = _Side(server=True, send=lambda b: None)
    dec = hpack.Decoder()
    out = []
    for ftype, flags, sid, payload in side.feed(bytes(eng.backs.get(
            conn_id, b""))):
        if ftype == SETTINGS:
            continue
        if ftype == HEADERS:
            payload = dec.decode(payload)
        out.append((ftype, flags, sid, payload))
    return out


def front_frames(eng: Engine) -> list:
    side = _Side(server=False, send=lambda b: None)
    return [f for f in side.feed(bytes(eng.front)) if f[0] != SETTINGS]


def test_inline_answer_sends_at_once_with_forwarded_for():
    eng = Engine(inline=SEL)
    s, enc = client_side(eng)
    s.on_front_data(frame(HEADERS, F_END_HEADERS | F_END_STREAM, 1,
                          enc.encode(REQ)))
    (ftype, flags, sid, headers), = back_frames(eng)
    assert ftype == HEADERS and flags & F_END_STREAM and sid == 1
    assert headers == REQ + [(b"x-forwarded-for", b"172.20.0.7")]
    assert s.streams_opened == 1 and s.streams[1].parked is None


def test_a_later_answer_and_one_after_close():
    eng = Engine()
    s, enc = client_side(eng)
    for sid in (1, 3):
        s.on_front_data(frame(HEADERS, F_END_HEADERS | F_END_STREAM, sid,
                              enc.encode(REQ)))
    assert not eng.backs and len(eng.pending) == 2     # both parked
    eng.pending[0][1](SEL)
    assert [f[2] for f in back_frames(eng)] == [1]
    eng.closed = True               # the session is gone
    eng.pending[1][1](SEL)          # a late answer: dropped, no raise
    assert [f[2] for f in back_frames(eng)] == [1]


def test_an_existing_forwarded_for_is_appended_to():
    eng = Engine(inline=SEL)
    s, enc = client_side(eng)
    s.on_front_data(frame(HEADERS, F_END_HEADERS | F_END_STREAM, 1,
                          enc.encode(REQ + [(b"x-forwarded-for",
                                             b"198.51.100.1")])))
    headers = back_frames(eng)[0][3]
    assert headers[-1] == (b"x-forwarded-for", b"198.51.100.1, 172.20.0.7")


@pytest.mark.parametrize("end", ["data", "empty_data", "trailers"])
def test_frames_wait_behind_a_parked_stream(end):
    eng = Engine()
    s, enc = client_side(eng)
    n0 = parked_total()
    s.on_front_data(frame(HEADERS, F_END_HEADERS, 1, enc.encode(REQ)))
    s.on_front_data(frame(DATA, 0, 1, b"abc"))
    if end == "data":
        s.on_front_data(frame(DATA, F_END_STREAM, 1, b"def"))
    elif end == "empty_data":
        s.on_front_data(frame(DATA, F_END_STREAM, 1, b""))
    else:
        s.on_front_data(frame(HEADERS, F_END_HEADERS | F_END_STREAM, 1,
                              enc.encode([(b"x-trailer", b"t")])))
    assert not eng.backs and parked_total() == n0 + 2
    # the client's DATA is granted its window while parked, as always
    ups = [f for f in front_frames(eng) if f[0] == WINDOW_UPDATE]
    assert {f[2] for f in ups} == {0, 1}
    eng.pending[0][1](SEL)
    got = back_frames(eng)
    assert got[0][0] == HEADERS and not got[0][1] & F_END_STREAM
    body = b"".join(p for t, _f, _s, p in got if t == DATA)
    assert body == (b"abcdef" if end == "data" else b"abc")
    last = got[-1]
    if end == "trailers":
        assert last[0] == HEADERS and last[1] & F_END_STREAM \
            and last[3] == [(b"x-trailer", b"t")]
    else:
        assert last[0] == DATA and last[1] & F_END_STREAM


def test_a_reset_drops_the_park_and_the_late_answer():
    eng = Engine()
    s, enc = client_side(eng)
    n0 = parked_total()
    s.on_front_data(frame(HEADERS, F_END_HEADERS, 1, enc.encode(REQ)))
    s.on_front_data(frame(RST_STREAM, 0, 1, struct.pack(">I", 8)))
    assert 1 not in s.streams and parked_total() == n0 + 1
    eng.pending[0][1](SEL)
    assert not eng.backs
    s.on_front_data(frame(HEADERS, F_END_HEADERS | F_END_STREAM, 3,
                          enc.encode(REQ)))
    eng.pending[1][1](SEL)          # the session serves on
    assert [f[2] for f in back_frames(eng)] == [1]     # backend sid 1


def test_no_backend_resets_the_stream():
    eng = Engine()
    s, enc = client_side(eng)
    s.on_front_data(frame(HEADERS, F_END_HEADERS | F_END_STREAM, 1,
                          enc.encode(REQ)))
    eng.pending[0][1](None)
    rst = [f for f in front_frames(eng) if f[0] == RST_STREAM]
    assert rst == [(RST_STREAM, 0, 1, struct.pack(">I", ERR_REFUSED))]
    assert 1 not in s.streams and not eng.backs


# ------------------------------------------------- served, mode device

GROUPS, RULES_PER = 6, 4


@pytest.fixture
def served(monkeypatch):
    """TcpLB(h2) over 6 `source` groups of 4 Host rules each (one group
    with no healthy member) and a rule-less default group; h2c backends
    in a thread of their own, one listener a member."""
    monkeypatch.setenv("VPROXY_TPU_CLASSIFY", "device")
    ClassifyService.reset()
    elg = EventLoopGroup("h2sel", 1)
    hc = HealthCheckConfig(protocol="none")
    listeners, made = [], []
    for g in range(GROUPS + 1):
        alias = f"g{g}" if g < GROUPS else "default"
        sg = ServerGroup(alias, elg, hc, "source")
        for b in range(2 + g % 3):
            ls = socket.socket()
            ls.bind(("127.0.0.1", 0))
            ls.listen(64)
            port = ls.getsockname()[1]
            sg.add(f"m{b}", "127.0.0.1", port)
            listeners.append((ls, f"{alias}|127.0.0.1:{port}"))
        made.append(sg)
    elg.next().call_sync(lambda: None)
    dead = made[2]                  # a matched group with no healthy member

    def down():
        for name in list(dead._checkers):
            for _ in range(hc.down):
                dead._checkers[name]._result(False)
    elg.next().call_sync(down)
    rules = [(f"svc{i}.example.com", 0, None)
             for i in range(GROUPS * RULES_PER)]
    rule_group = [i % GROUPS for i in range(len(rules))]
    pub_rules, pub_group = reference_h2.published(rules, rule_group, GROUPS)
    ups = Upstream("h2sel")
    ups.add_many([(made[g], 0, [HintRule(host=h) for (h, _p, _u), gg
                                 in zip(pub_rules, pub_group) if gg == g])
                  for g in range(GROUPS)] + [(made[-1], 10, [])])
    E.flush_installs(10)
    srv = h2_backends.Server(listeners)
    t = threading.Thread(target=srv.run, daemon=True)
    t.start()
    lb = TcpLB("h2sel", elg, elg, "127.0.0.1", 0, ups, protocol="h2")
    lb.start()
    healthy = [[sg.maglev_identity(s) for s in sg.servers if s.healthy]
               for sg in made]
    yield SimpleNamespace(lb=lb, pub=(pub_rules, pub_group), healthy=healthy)
    lb.stop()
    srv.stop = True
    t.join(5)
    srv.close()
    ups.close()
    for sg in made:
        sg.close()
    elg.close()
    ClassifyService.reset()     # its dispatcher thread ends with it


def test_48_streams_reach_the_backends_the_reference_names(served):
    names = [f"www.svc{i}.example.com" for i in range(0, 40)] + \
        [f"svc{i}.example.com" for i in range(4)] + \
        [f"nohost{i}.invalid" for i in range(4)]
    assert len(names) == 48
    trace.reset()
    prev = trace.sample_every()
    trace.configure(1)
    before = dict(trace.span_totals())
    gi = GlobalInspection.get()
    wheres = ("device_pick", "group_next", "fallback", "refused")
    sel0 = {w: gi.get_counter("vproxy_upstream_selects_total", where=w).value()
            for w in wheres}
    st = ClassifyService.get().stats
    dev0 = st.device_queries
    try:
        c = socket.create_connection(("127.0.0.1", served.lb.bind_port),
                                     timeout=10)
        src = c.getsockname()[0]
        blocks = b"".join(
            W.frame(W.HEADERS, W.F_END_HEADERS | W.F_END_STREAM, 2 * k + 1,
                    W.encode([(b":method", b"GET"), (b":scheme", b"http"),
                              (b":path", b"/"), (b":authority", n.encode())]))
            for k, n in enumerate(names))
        c.sendall(W.PREFACE + W.settings([]) + blocks)
        buf, got, done = bytearray(), {}, set()
        while len(done) < 48:
            data = c.recv(65536)
            assert data, "the LB closed the connection"
            buf += data
            for ftype, fl, sid, payload in W.frames(buf):
                if ftype == W.HEADERS:
                    got[sid] = dict(W.decode(W.header_block(fl, payload)))
                if ftype in (W.DATA, W.RST_STREAM) and fl & W.F_END_STREAM \
                        or ftype == W.RST_STREAM:
                    done.add(sid)
                if ftype == W.SETTINGS and not fl & W.F_ACK:
                    c.sendall(W.frame(W.SETTINGS, W.F_ACK, 0))
        # a read's span closes on the loop after its last write, and a
        # span that outlives tracing is lost: the client can hold every
        # response before the span that sent it is booked
        deadline = time.monotonic() + 10
        while True:
            tot = trace.span_totals()
            n = tot.get("l7/back_read", {}).get("sum_items", 0) \
                - before.get("l7/back_read", {}).get("sum_items", 0)
            if n >= 48 or time.monotonic() > deadline:
                break
            time.sleep(0.01)
        c.close()
    finally:
        trace.configure(prev)
    rules, groups = served.pub
    ip = bytes(int(x) for x in src.split("."))
    want = reference_h2.answers(rules, groups, served.healthy[:GROUPS],
                                served.healthy[GROUPS], MG.GROUP_M,
                                [(n, 0, "/", ip, None) for n in names])
    for k, n in enumerate(names):
        h = got[2 * k + 1]
        g, i = want[k]
        assert h[b":status"] == b"200"
        assert h[b"x-backend"].decode() == served.healthy[g][i], n
        assert h[b"x-seen-forwarded-for"] == src.encode()
    sel = {w: gi.get_counter("vproxy_upstream_selects_total", where=w).value()
           - sel0[w] for w in wheres}
    misses = int((want[:, 0] == GROUPS).sum())
    assert misses >= 4 + 1          # the four misses and the dead group
    assert sel == {"device_pick": 48 - misses, "group_next": 0,
                   "fallback": misses, "refused": 0}
    assert st.device_queries - dev0 == 48 and st.oracle_queries == 0

    def delta(key, field):
        return tot[key][field] - before.get(key, {}).get(field, 0)
    assert delta("l7/front_read", "sum_items") == 48
    assert delta("l7/select", "n") == 48
    assert delta("l7/selected", "sum_items") == 48
    assert delta("l7/back_read", "sum_items") == 48
