"""End-to-end request tracing (utils/trace + native/vtl.cpp span rings
+ the plane instrumentation): sampling determinism, span-ring overflow
accounting, whole-lifetime lane traces, the cross-plane stitch through
a sampled punt, install traces bracketing unstalled dispatches, and the
operator surfaces (`trace <id>`, `list trace`, /metrics zeros,
/events?trace=)."""
import socket
import struct
import subprocess
import sys
import threading
import time

import pytest

from vproxy_tpu.net import vtl
from vproxy_tpu.utils import trace

from tests.test_tcplb import stack  # noqa: F401 — the lb fixture

needs_lanes = pytest.mark.skipif(
    not (vtl.lanes_supported() and vtl.trace_supported()),
    reason="native provider without lane/trace symbols")


@pytest.fixture(autouse=True)
def _trace_off():
    """Every test starts and ends with the knob off and an empty
    buffer (the knob is process-global, C side included)."""
    trace.configure(0)
    trace.reset()
    yield
    trace.configure(0)
    trace.reset()


def _wait(pred, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return pred()


# ------------------------------------------------------------- sampling

def test_sampling_off_is_off():
    assert not trace.enabled()
    assert trace.maybe_sample() == 0
    assert not trace.sampled_key(b"anything")


def test_counter_sampling_every_nth():
    trace.configure(4)
    hits = sum(1 for _ in range(400) if trace.maybe_sample())
    assert hits == 100  # deterministic 1-in-N, not probabilistic


def test_key_sampling_value_stable_across_processes():
    """The VPROXY_TPU_FAILPOINT_SEED idiom: the same (seed, key)
    decides identically in every process — spawn two interpreters and
    compare their decision vectors."""
    prog = (
        "import os; os.environ['VPROXY_TPU_TRACE_SAMPLE']='4';"
        "os.environ['VPROXY_TPU_TRACE_SEED']='s1';"
        "from vproxy_tpu.utils import trace;"
        "print(''.join('1' if trace.sampled_key(b'key%d' % i) else '0'"
        "              for i in range(200)))")
    outs = [subprocess.run([sys.executable, "-c", prog],
                           capture_output=True, text=True, timeout=60,
                           ).stdout.strip() for _ in range(2)]
    assert outs[0] and outs[0] == outs[1]
    assert "1" in outs[0] and "0" in outs[0]  # neither all nor none
    # a different seed samples a different subset (2^-200-ish to match)
    prog2 = prog.replace("'s1'", "'s2'")
    out2 = subprocess.run([sys.executable, "-c", prog2],
                          capture_output=True, text=True,
                          timeout=60).stdout.strip()
    assert out2 != outs[0]


def test_trace_id_namespaces_disjoint():
    # python allocates odd ids; the C lane plane even ones
    assert trace.new_trace_id() % 2 == 1
    assert trace.new_trace_id() != trace.new_trace_id()


# -------------------------------------------------------------- buffer

def test_buffer_bounded_and_drops_counted():
    trace.configure(1)
    before = trace.py_dropped_total()
    for i in range(trace.MAX_TRACES + 50):
        trace.record_span(trace.new_trace_id(), "accept", "acl", i, 1)
    assert len(trace.trace_ids()) == trace.MAX_TRACES
    assert trace.py_dropped_total() >= before + 50


def test_bind_context_and_span_record():
    trace.configure(1)
    tid = trace.new_trace_id()
    assert trace.current_id() == 0
    with trace.bind(tid):
        assert trace.current_id() == tid
        trace.record_span(trace.current_id(), "engine", "launch",
                          1000, 5, fused=True)
    assert trace.current_id() == 0
    spans = trace.get_trace(tid)
    assert len(spans) == 1 and spans[0]["fused"] is True


def test_waterfall_and_summaries():
    trace.configure(1)
    tid = trace.new_trace_id()
    trace.record_span(tid, "accept", "acl", 1000, 500)
    trace.record_span(tid, "accept", "connect", 1500, 2000)
    s = trace.summaries()
    assert any(t["trace"] == tid and t["spans"] == 2 for t in s)
    lines = trace.waterfall(tid)
    assert "acl" in "\n".join(lines) and "connect" in "\n".join(lines)
    assert trace.waterfall(999999)[0].startswith("trace 999999: not")


# ----------------------------------------------------- operator surfaces

def test_metrics_preregistered_zeros():
    """The PR-9 silent-drops rule: the trace series exist on /metrics
    BEFORE the first sampled request."""
    from vproxy_tpu.utils.metrics import GlobalInspection
    text = GlobalInspection.get().prometheus_string()
    assert 'vproxy_trace_drop_total{ring="lane"}' in text
    assert 'vproxy_trace_drop_total{ring="py"}' in text
    for plane in ("lane", "accept", "engine", "install", "cluster"):
        assert f'vproxy_trace_spans_total{{plane="{plane}"}}' in text


def test_command_surface_trace():
    from vproxy_tpu.control.command import CmdError, Command
    trace.configure(1)
    tid = trace.new_trace_id()
    trace.record_span(tid, "accept", "acl", 1000, 500)
    out = Command.execute(None, "list trace")
    assert any(f"[{tid}]" in line for line in out)
    detail = Command.execute(None, "list-detail trace")
    assert any(t["trace"] == tid for t in detail)
    wf = Command.execute(None, f"trace {tid}")
    assert "acl" in "\n".join(wf)
    with pytest.raises(CmdError):
        Command.execute(None, "trace nope")


def test_flight_recorder_trace_crossref():
    from vproxy_tpu.utils.events import FlightRecorder
    FlightRecorder.reset()
    rec = FlightRecorder.get()
    rec.record("conn", "plain event")
    rec.record("conn", "traced event", trace_id=42)
    rec.record("conn", "unsampled", trace_id=0)  # 0 = no crossref
    evs = rec.snapshot(trace=42)
    assert len(evs) == 1 and evs[0]["msg"] == "traced event"
    assert "trace_id" not in rec.snapshot(trace=None)[0]
    assert "trace_id" not in rec.snapshot()[2]


# ------------------------------------------------------------ C planes

class _Backend:
    """Accept-and-serve-one-line backend for raw lane tests."""

    def __init__(self):
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(128)
        self.port = self.sock.getsockname()[1]
        self.alive = True
        threading.Thread(target=self._run, daemon=True).start()

    def _run(self):
        while self.alive:
            try:
                c, _ = self.sock.accept()
            except OSError:
                return
            try:
                c.sendall(b"ok\n")
                c.close()
            except OSError:
                pass

    def close(self):
        self.alive = False
        try:
            self.sock.close()
        except OSError:
            pass


def _raw_lanes(backend_port, nlanes=1):
    h = vtl.lanes_new("127.0.0.1", 0, 64, nlanes, 65536, False, 60000,
                      3000)
    rec = vtl.LANE_REC.pack(b"127.0.0.1", backend_port, 0, 1)
    gen = vtl.lane_gen(h)
    assert vtl.lane_install(h, rec, 1, [0], gen) == 1
    return h, vtl.lanes_port(h)


@needs_lanes
def test_native_trace_rec_abi():
    assert int(vtl.LIB.vtl_trace_rec_size()) == vtl.TRACE_REC.size
    assert vtl.TRACE_REC.size == 40
    assert struct.calcsize("<QQQQIBBH") == 40


class _LanePoller:
    """Background lane_poll pump (the lane thread's role): serving and
    span writes happen INSIDE lane_poll, so a test that blocks on
    recv() needs someone polling. Optionally drains the span ring
    (SPSC: this thread is then the one consumer)."""

    def __init__(self, h, drain=True):
        self.h = h
        self.drain = drain
        self.recs: list = []
        self.stop = threading.Event()
        self.t = threading.Thread(target=self._run, daemon=True)
        self.t.start()

    def _run(self):
        while not self.stop.is_set():
            punts = vtl.lane_poll(self.h, 0, 50)
            if punts:
                for p in punts:
                    vtl.close(p[0])
            if self.drain:
                self.recs += vtl.trace_drain(self.h, 0)
            if punts is None:
                return

    def close(self):
        self.stop.set()
        self.t.join(5)


@needs_lanes
def test_lane_whole_lifetime_trace_monotonic():
    """One sampled lane-served connection yields accept -> route_pick
    -> connect -> splice -> close with monotonic, non-overlapping
    stages — the whole-lifetime C-plane trace."""
    be = _Backend()
    trace.configure(1)
    h, port = _raw_lanes(be.port)
    poller = _LanePoller(h)
    try:
        c = socket.create_connection(("127.0.0.1", port), timeout=5)
        c.settimeout(5)
        assert c.recv(16) == b"ok\n"
        c.close()
        assert _wait(lambda: len(poller.recs) >= 5)
        recs = poller.recs
        spans = {r[5]: r for r in recs}
        names = [vtl.TRACE_SPANS[i] for i in sorted(spans)]
        assert names == ["accept", "route_pick", "connect", "splice",
                         "close"], names
        tids = {r[0] for r in recs}
        assert len(tids) == 1 and list(tids)[0] % 2 == 0  # one EVEN id
        ordered = sorted(recs, key=lambda r: r[1])
        for a, b in zip(ordered, ordered[1:]):
            assert a[1] + a[2] <= b[1] + 1000, \
                f"stage overlap: {a} vs {b}"  # 1us clock-read slack
        splice = spans[vtl.TRACE_SPANS.index("splice")]
        assert splice[3] >= 3  # aux = bytes moved ("ok\n")
    finally:
        vtl.lanes_shutdown(h, 100)
        poller.close()
        vtl.lanes_free(h)
        be.close()


@needs_lanes
def test_span_ring_overflow_counted_never_silent():
    """A ring smaller than the span volume must DROP and COUNT, not
    block the lane or grow unbounded."""
    be = _Backend()
    trace.configure(1)
    vtl.trace_set_ring_cap(64)
    poller = None
    try:
        h, port = _raw_lanes(be.port)
        poller = _LanePoller(h, drain=False)  # serve but NEVER drain
        try:
            drops0 = vtl.trace_counters()[1]
            # ~40 conns x 5 spans >> 64 slots, never drained meanwhile
            for _ in range(40):
                c = socket.create_connection(("127.0.0.1", port),
                                             timeout=5)
                c.settimeout(5)
                c.recv(16)
                c.close()
            assert _wait(lambda: vtl.trace_counters()[1] > drops0)
            poller.close()
            poller = None
            # the drain returns at most the ring's capacity
            recs = vtl.trace_drain(h, 0, 256)
            total = len(recs)
            while recs:
                recs = vtl.trace_drain(h, 0, 256)
                total += len(recs)
            assert total <= 64
        finally:
            vtl.lanes_shutdown(h, 100)
            if poller is not None:
                poller.close()
            else:
                while vtl.lane_poll(h, 0, 100) is not None:
                    pass
            vtl.lanes_free(h)
    finally:
        vtl.trace_set_ring_cap(8192)
        be.close()


@needs_lanes
def test_punt_carries_trace_id():
    """A sampled punt's LanePunt record carries the C-side trace id so
    the python path CONTINUES the trace (the cross-plane stitch)."""
    be = _Backend()
    trace.configure(1)
    h = vtl.lanes_new("127.0.0.1", 0, 64, 1, 65536, False, 60000, 3000)
    port = vtl.lanes_port(h)  # NO entry installed: every accept punts
    try:
        c = socket.create_connection(("127.0.0.1", port), timeout=5)
        punts = []
        deadline = time.time() + 5
        while time.time() < deadline and not punts:
            punts = vtl.lane_poll(h, 0, 100) or []
        assert punts, "no punt arrived"
        fd, kind, err, cip, cport, bip, bport, tid = punts[0]
        assert kind == vtl.LANE_PUNT_CLASSIC
        assert tid != 0 and tid % 2 == 0  # sampled: EVEN C-plane id
        vtl.close(fd)
        c.close()
        # the C-side spans for the same trace id are in the ring
        recs = vtl.trace_drain(h, 0)
        names = {vtl.TRACE_SPANS[r[5]] for r in recs if r[0] == tid}
        assert {"accept", "punt"} <= names
    finally:
        vtl.lanes_shutdown(h, 100)
        while vtl.lane_poll(h, 0, 100) is not None:
            pass
        vtl.lanes_free(h)
        be.close()


# -------------------------------------------------- cross-plane stitch

@needs_lanes
def test_stitched_trace_lane_to_python(stack):
    """A sampled connection arriving at the C lanes whose entry punts
    (non-trivial ACL -> empty lane entry) yields ONE trace spanning the
    C plane (accept + punt) and the python planes (acl, backend_pick,
    connect, splice, close) with consistent monotonic timestamps — the
    acceptance stitch."""
    from vproxy_tpu.components.secgroup import SecurityGroup
    from vproxy_tpu.components.servergroup import ServerGroup
    from vproxy_tpu.components.tcplb import TcpLB
    from vproxy_tpu.components.upstream import Upstream
    from vproxy_tpu.rules.ir import AclRule, Proto
    from vproxy_tpu.utils.ip import Network
    from tests.test_tcplb import IdServer, fast_hc, tcp_get_id, \
        wait_healthy

    elg = stack["make_elg"](2)
    srv = IdServer("A")
    stack["servers"].append(srv)
    g = ServerGroup("st-g", elg, fast_hc())
    stack["groups"].append(g)
    g.add("a", "127.0.0.1", srv.port)
    wait_healthy(g, 1)
    ups = Upstream("st-u")
    ups.add(g)
    sg = SecurityGroup("st-acl", default_allow=False)
    sg.add_rule(AclRule("lo", Network.parse("127.0.0.0/8"), Proto.TCP,
                        1, 65535, True))
    trace.configure(1)
    lb = TcpLB("st-lb", elg, elg, "127.0.0.1", 0, ups, protocol="tcp",
               lanes=2, security_group=sg)
    stack["lbs"].append(lb)
    lb.start()
    assert lb.lanes is not None
    assert tcp_get_id(lb.bind_port) == "A"

    def stitched():
        # complete only: the session's connect/splice/close spans land
        # at pump DONE, after the client already saw its bytes
        for t in trace.summaries(last=0):
            if "lane" in t["planes"] and "accept" in t["planes"] \
                    and any(s["span"] == "close"
                            for s in trace.get_trace(t["trace"])):
                return t
        return None

    assert _wait(lambda: stitched() is not None, timeout=8), \
        "no complete cross-plane trace appeared"
    t = stitched()
    spans = trace.get_trace(t["trace"])
    by_plane = {p: [s for s in spans if s["plane"] == p]
                for p in t["planes"]}
    lane_names = {s["span"] for s in by_plane["lane"]}
    py_names = {s["span"] for s in by_plane["accept"]}
    assert {"accept", "punt"} <= lane_names
    assert {"acl", "backend_pick", "connect", "close"} <= py_names
    # consistent monotonic timestamps across planes: the C accept span
    # precedes every python span (same CLOCK_MONOTONIC on both sides)
    c_start = min(s["t_ns"] for s in by_plane["lane"])
    py_start = min(s["t_ns"] for s in by_plane["accept"])
    assert c_start <= py_start
    t0 = min(s["t_ns"] for s in spans)
    t1 = max(s["t_ns"] + s["dur_ns"] for s in spans)
    assert 0 < t1 - t0 < 60 * 10**9  # one sane end-to-end window


# ------------------------- the operator's drive (the grammar-built app)

@pytest.fixture
def grammar_app():
    """An app built the operator's way: Command grammar, lanes on, an
    HTTP controller beside it, every request sampled."""
    from vproxy_tpu.control.app import Application
    from vproxy_tpu.control.command import Command
    from vproxy_tpu.control.http_controller import HttpController
    from vproxy_tpu.utils import lifecycle
    from tests.test_tcplb import IdServer
    lifecycle.reset()
    trace.configure(1)
    app = Application.create(workers=2)
    ctl = HttpController(app, "127.0.0.1", 0)
    ctl.start()
    srv = IdServer("A")
    try:
        for cmd in (
                "add upstream u0",
                "add server-group g0 timeout 500 period 100 up 1 down 1",
                "add server-group g0 to upstream u0 weight 10",
                f"add server sA to server-group g0 address "
                f"127.0.0.1:{srv.port} weight 10"):
            assert Command.execute(app, cmd) == "OK", cmd
        g = app.server_groups["g0"]
        assert _wait(lambda: any(s.healthy for s in g.servers), 10)
        assert Command.execute(
            app, "add tcp-lb lb0 address 127.0.0.1:0 upstream u0 "
            "protocol tcp lanes 2") == "OK"
        yield app, ctl, app.tcp_lbs["lb0"]
    finally:
        ctl.stop()
        app.close()
        srv.close()
        lifecycle.reset()


def _http_json(port, path):
    import json
    import urllib.request
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=5) as r:
        return json.loads(r.read())


@needs_lanes
def test_operator_surfaces_serve_a_lane_trace(grammar_app):
    """Lane-served connections of a grammar-built tcp-lb: a
    whole-lifetime C-plane trace, and `list trace`, `trace <id>`,
    `GET /trace?id=` on the HTTP controller, /metrics and the C
    counters all show it (the round-14 scenario drive)."""
    from vproxy_tpu.control.command import Command
    from vproxy_tpu.utils.metrics import GlobalInspection
    from tests.test_tcplb import tcp_get_id
    app, ctl, lb = grammar_app
    assert lb.lanes is not None
    spans0, drops0 = vtl.trace_counters()
    for _ in range(5):
        assert tcp_get_id(lb.bind_port) == "A"
    assert lb.accepted == 0, "python accept path fired"

    def whole():
        for t in trace.summaries(last=0):
            names = {s["span"] for s in trace.get_trace(t["trace"])
                     if s["plane"] == "lane"}
            if {"accept", "route_pick", "connect", "splice",
                    "close"} <= names:
                return t["trace"]
        return None

    assert _wait(lambda: whole() is not None, 10), \
        "no whole-lifetime lane trace drained"
    tid = whole()
    spans = trace.get_trace(tid)
    for a, b in zip(spans, spans[1:]):
        assert a["t_ns"] + a["dur_ns"] <= b["t_ns"] + 1000, (a, b)
    assert any(f"[{tid}]" in line
               for line in Command.execute(app, "list trace"))
    assert any("splice" in line
               for line in Command.execute(app, f"trace {tid}"))
    doc = _http_json(ctl.bind_port, f"/trace?id={tid}")
    assert doc["trace"] == tid and len(doc["spans"]) == len(spans) >= 5
    text = GlobalInspection.get().prometheus_string()
    line = next(l for l in text.splitlines() if l.startswith(
        'vproxy_trace_spans_total{plane="lane"}'))
    assert float(line.split()[-1]) >= 5
    spans_c, drops_c = vtl.trace_counters()
    assert spans_c - spans0 >= 25 and drops_c == drops0, (spans_c, drops_c)


@needs_lanes
def test_grammar_built_acl_stitches_lane_accept_and_engine(grammar_app):
    """A security group added through the grammar empties the lane
    entry: the sampled punt's trace runs on through the python accept
    path AND the engine plane (the ACL classify), the controller serves
    the same spans, and the flight recorder's events join it."""
    from vproxy_tpu.control.command import Command
    from vproxy_tpu.utils.events import FlightRecorder
    from tests.test_tcplb import tcp_get_id
    app, ctl, lb = grammar_app
    for cmd in ("add security-group acl0 default deny",
                "add security-group-rule lo to security-group acl0 "
                "network 127.0.0.0/8 protocol tcp port-range 1,65535 "
                "default allow",
                "update tcp-lb lb0 security-group acl0"):
        assert Command.execute(app, cmd) == "OK", cmd
    assert _wait(lambda: lb.lanes.stat().get("pick") == "empty", 10)
    assert tcp_get_id(lb.bind_port) == "A"     # punted, served by python

    def stitched():
        for t in trace.summaries(last=0):
            if {"lane", "accept"} <= set(t["planes"]) and any(
                    s["span"] == "close"
                    for s in trace.get_trace(t["trace"])):
                return t
        return None

    assert _wait(lambda: stitched() is not None, 10), "no stitched trace"
    st = stitched()
    spans = trace.get_trace(st["trace"])
    assert {"lane", "accept", "engine"} <= {s["plane"] for s in spans}
    doc = _http_json(ctl.bind_port, f"/trace?id={st['trace']}")
    assert len(doc["spans"]) == len(spans)
    assert FlightRecorder.get().snapshot(trace=st["trace"]), \
        "no recorder event carries the trace id"


# ------------------------------------------------------ install traces

def test_install_trace_brackets_unstalled_dispatch():
    """A traced standby install shows compile / upload / swap spans,
    and dispatches submitted DURING the install keep answering (the
    TableInstaller stall-free contract, now span-visible)."""
    from vproxy_tpu.rules.engine import HintMatcher, flush_installs
    from vproxy_tpu.rules.ir import Hint, HintRule
    trace.configure(1)
    m = HintMatcher([HintRule(host="seed.example.com")], backend="jax")
    m.match([Hint(host="seed.example.com")])  # warm the jit OUTSIDE
    done = threading.Event()

    def install():
        m.set_rules([HintRule(host=f"h{i}.example.com")
                     for i in range(3000)])
        done.set()

    th = threading.Thread(target=install, daemon=True)
    th.start()
    # dispatch while the standby build runs — a FRESH trace context per
    # query (the per-trace span cap must not swallow late launches)
    qtids = []
    while not done.is_set():
        qt = trace.new_trace_id()
        qtids.append(qt)
        with trace.bind(qt):
            out = m.match([Hint(host="seed.example.com")])
        if int(out[0]) != 0:
            # the swap publishes INSIDE set_rules, before done.set():
            # a query landing in that window correctly answers -1
            # against the NEW table (which has no seed rule). Legal
            # only at the very end of the install — done must follow
            # promptly; anything else is a real torn dispatch.
            assert int(out[0]) == -1 and done.wait(5), out
            break
    th.join(30)
    flush_installs(30)
    itids = [t["trace"] for t in trace.summaries(last=0)
             if any(s["plane"] == "install"
                    for s in trace.get_trace(t["trace"]))]
    assert itids, "no install trace recorded"
    ispans = trace.get_trace(itids[-1])
    names = {s["span"] for s in ispans if s["plane"] == "install"}
    assert {"compile", "upload", "swap", "install"} <= names
    # the query traces carry launch markers from DURING the install
    # window — dispatch never waited for the swap
    inst = next(s for s in ispans if s["span"] == "install")
    launches = [s for qt in qtids for s in trace.get_trace(qt)
                if s["span"] == "launch"]
    assert launches, "no launch markers on the query traces"
    w0, w1 = inst["t_ns"], inst["t_ns"] + inst["dur_ns"]
    assert any(w0 <= s["t_ns"] <= w1 for s in launches), \
        "no dispatch launched inside the install window"


# --------------------------------------------------- stage histograms

@needs_lanes
def test_lane_stage_histograms_fold(stack):
    """Lane-served connections land in the SAME vproxy_accept_stage_us
    series python-path connections populate (the stat-ABI widening)."""
    from vproxy_tpu.components.servergroup import ServerGroup
    from vproxy_tpu.components.tcplb import TcpLB
    from vproxy_tpu.components.upstream import Upstream
    from vproxy_tpu.utils.metrics import GlobalInspection
    from tests.test_tcplb import IdServer, fast_hc, tcp_get_id, \
        wait_healthy

    def stage_count(stage):
        snap = GlobalInspection.get().bench_snapshot()
        v = snap.get(f"vproxy_accept_stage_us.{stage}")
        return v.get("n", 0) if isinstance(v, dict) else 0

    before = {s: stage_count(s) for s in ("backend_pick", "handover",
                                          "total")}
    elg = stack["make_elg"](2)
    srv = IdServer("A")
    stack["servers"].append(srv)
    g = ServerGroup("sh-g", elg, fast_hc())
    stack["groups"].append(g)
    g.add("a", "127.0.0.1", srv.port)
    wait_healthy(g, 1)
    ups = Upstream("sh-u")
    ups.add(g)
    lb = TcpLB("sh-lb", elg, elg, "127.0.0.1", 0, ups, protocol="tcp",
               lanes=2)
    stack["lbs"].append(lb)
    lb.start()
    assert lb.lanes is not None
    for _ in range(10):
        assert tcp_get_id(lb.bind_port) == "A"
    assert lb.accepted == 0  # all served in C — YET the histograms move
    raw = vtl.lanes_stage_stat(lb.lanes.handle, 2)
    assert raw[0] >= 10  # C-side cumulative total-stage count
    assert _wait(lambda: all(
        stage_count(s) >= before[s] + 10
        for s in ("backend_pick", "handover", "total")), timeout=8)


def test_histogram_merge_parity():
    """The C bucket rule must equal Histogram._bucket_of so merged
    counts land where observe() would put them."""
    from vproxy_tpu.utils.metrics import Histogram
    h = Histogram("t_us")
    # C: us<=1 -> 0 else min(bit_length(us-1), 27)
    for us in (0, 1, 2, 3, 4, 5, 1000, 1 << 26, 1 << 40):
        c_bucket = 0 if us <= 1 else min(max(us - 1, 1).bit_length(), 27)
        assert h._bucket_of(float(us)) == c_bucket, us
    h.observe(100.0)
    deltas = [0] * 28
    deltas[h._bucket_of(100.0)] = 3
    h.merge(deltas, 300.0, 3)
    assert h.value() == 4
    assert h.percentiles()["n"] == 4


def test_step_loop_queue_shape():
    """StepLoop queue items carry the trace context (6-tuples) and the
    degraded host-index path records spans for sampled queries."""
    from vproxy_tpu.rules.engine import HintMatcher
    from vproxy_tpu.rules.ir import Hint, HintRule
    from vproxy_tpu.cluster.submit import StepLoop
    trace.configure(1)
    m = HintMatcher([HintRule(host="x.example.com")], backend="host")
    loop = StepLoop(m, membership=None, step_ms=5, batch_cap=4,
                    timeout_ms=200)
    loop.degraded = True  # force the host-index path, no clock needed
    got = []
    tid = trace.new_trace_id()
    with trace.bind(tid):
        loop.submit(Hint(host="x.example.com"),
                    lambda idx, pl: got.append(idx))
    with loop._qlock:
        batch = list(loop._q)
        loop._q.clear()
    assert len(batch[0]) == 6 and batch[0][5] == tid
    loop._serve_host(batch)
    assert got == [0]
    spans = trace.get_trace(tid)
    assert any(s["span"] == "host_index" and s["plane"] == "cluster"
               for s in spans)


# ------------------------------------------- the dispatcher's batch cycle

CYCLE = ("dispatch", "encode", "launch", "d2h_sync", "deliver")


def _totals_delta(before: dict) -> dict:
    """span_totals() now minus `before`, numeric fields only (totals are
    process-lifetime: other tests of the session have added to them)."""
    out = {}
    for key, tot in trace.span_totals().items():
        was = before.get(key, {})
        out[key] = {k: tot[k] - was.get(k, 0)
                    for k in ("n", "sum_ns", "sum_cpu_ns", "sum_items")}
    return out


def _serve_hints(n, sampled_at=None):
    """n hint queries through a fresh device-mode service on a small
    table -> (stats, trace id bound to query `sampled_at` or 0, what
    serving them added to the span totals)."""
    from vproxy_tpu.rules.engine import HintMatcher
    from vproxy_tpu.rules.ir import Hint, HintRule
    from vproxy_tpu.rules.service import ClassifyService
    m = HintMatcher([HintRule(host=f"s{i}.example.com") for i in range(64)],
                    backend="jax")
    m.match([Hint.of_host("warm.example.com")] * 16)    # compile outside
    svc = ClassifyService(mode="device")
    before = trace.span_totals()
    got, done = [], threading.Event()

    def cb(idx, _pl):
        got.append(idx)
        if len(got) == n:
            done.set()

    tid = 0
    try:
        for i in range(n):
            h = Hint.of_host(f"s{i % 64}.example.com")
            if i == sampled_at:
                tid = trace.new_trace_id()
                with trace.bind(tid):
                    svc.submit_hint(m, h, cb)
            else:
                svc.submit_hint(m, h, cb)
        assert done.wait(30)
        assert sorted(got) == sorted(i % 64 for i in range(n))
    finally:
        svc.close()
        if svc._thread is not None:
            svc._thread.join(10)    # leaving the last wait records it
            assert not svc._thread.is_alive()
    return svc.stats, tid, _totals_delta(before)


def test_batch_cycle_totals_every_batch():
    """Tracing on: every batch adds one dispatch / launch / d2h_sync /
    deliver and its encode to the totals, with items and CPU time."""
    trace.configure(1)
    stats, _tid, d = _serve_hints(40)
    batches = stats.dispatches
    assert batches >= 1
    for span in CYCLE:
        assert d[f"engine/{span}"]["n"] == batches, (span, d)
        assert d[f"engine/{span}"]["sum_ns"] > 0
    assert d["engine/wait"]["n"] >= 1 and d["engine/wait"]["sum_ns"] > 0
    for span in ("encode", "deliver"):
        tot = d[f"engine/{span}"]
        assert tot["sum_items"] == 40
        assert 0 < tot["sum_cpu_ns"] <= tot["sum_ns"]
    tot = trace.span_totals()["engine/launch"]
    assert sum(tot["buckets"]) == tot["n"] and len(tot["buckets"]) == 28
    assert 0 < tot["first_ns"] <= tot["last_ns"]


def test_sampled_request_trace_holds_the_cycle_nested():
    """The batch's spans are buffered on its first sampled request:
    encode + launch lie inside dispatch and name it as parent; the
    request's own submit_lock_wait and queue_wait are there too."""
    trace.configure(1)
    _stats, tid, _d = _serve_hints(12, sampled_at=0)
    by = {}
    for s in trace.get_trace(tid):
        assert s["plane"] == "engine"
        by.setdefault(s["span"], []).append(s)
    assert set(CYCLE) | {"submit_lock_wait", "queue_wait"} <= set(by), by
    disp = by["dispatch"][0]
    for name in ("encode", "launch"):
        s = by[name][0]
        assert s["parent"] == "dispatch"
        assert disp["t_ns"] <= s["t_ns"] and \
            s["t_ns"] + s["dur_ns"] <= disp["t_ns"] + disp["dur_ns"]
    assert by["launch"][0]["fused"] is False
    assert by["launch"][0]["bucket"] >= disp["batch"] >= 1
    assert by["queue_wait"][0]["batch"] == disp["batch"]
    assert by["encode"][0]["items"] == disp["batch"]
    assert by["encode"][0]["cpu_ns"] <= by["encode"][0]["dur_ns"]
    assert by["deliver"][0]["items"] == by["d2h_sync"][0]["batch"]
    ends = [by[n][0]["t_ns"] for n in
            ("submit_lock_wait", "queue_wait", "dispatch", "d2h_sync",
             "deliver")]
    assert ends == sorted(ends)     # one clock, the order of the cycle


def test_span_totals_survive_reset():
    trace.configure(1)
    _serve_hints(4)
    before = trace.span_totals()
    assert before["engine/launch"]["n"] >= 1
    trace.reset()
    assert trace.span_totals() == before and not trace.trace_ids()


def test_unsampled_batch_adds_totals_and_no_trace():
    """No batch-only traces: they would evict every request trace."""
    trace.configure(64)
    stats, _tid, d = _serve_hints(10)
    assert d["engine/launch"]["n"] == stats.dispatches >= 1
    assert d["engine/deliver"]["sum_items"] == 10
    assert trace.trace_ids() == []
    assert d.get("engine/queue_wait", {"n": 0})["n"] == 0


def test_tracing_off_leaves_no_totals_no_spans_no_gc_hook():
    import gc
    assert not any(getattr(cb, "__module__", "") == trace.__name__
                   for cb in gc.callbacks)
    _stats, tid, d = _serve_hints(10, sampled_at=3)  # a bound id, knob off
    assert all(v["n"] == 0 for v in d.values())
    assert trace.get_trace(tid) == [] and trace.trace_ids() == []


def test_gc_hook_lives_while_tracing_is_on():
    import gc
    trace.configure(8)
    assert gc.callbacks.count(trace._gc_hook) == 1
    trace.configure(16)     # no second copy
    assert gc.callbacks.count(trace._gc_hook) == 1
    before = trace.span_totals().get("runtime/gc_pause", {"n": 0})
    gc.collect()
    after = trace.span_totals()["runtime/gc_pause"]
    assert after["n"] == before["n"] + 1 and after["sum_ns"] > 0
    trace.configure(0)
    assert trace._gc_hook not in gc.callbacks
    gc.collect()
    assert trace.span_totals()["runtime/gc_pause"]["n"] == after["n"]


def test_span_metrics_family_preregistered_at_zero_and_env_knob():
    """A fresh process: the whole vproxy_trace_span_us{plane,span}
    vocabulary is on /metrics at zero with the knob unset and nothing
    is hooked into the collector; VPROXY_TPU_TRACE_SAMPLE=N at import
    installs the gc hook."""
    prog = (
        "import gc;"
        "from vproxy_tpu.utils import trace;"
        "import jax;"   # collections run inside this import, hook or not
        "from vproxy_tpu.utils.metrics import GlobalInspection;"
        "t = GlobalInspection.get().prometheus_string();"
        "print(int(trace._gc_hook in gc.callbacks), trace.span_totals());"
        "print('\\n'.join(l for l in t.splitlines()"
        "      if l.startswith('vproxy_trace_span_us_count')))")
    import os
    env = {k: v for k, v in os.environ.items()
           if k != "VPROXY_TPU_TRACE_SAMPLE"}
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=60)
    lines = out.stdout.strip().splitlines()
    assert lines[0] == "0 {}", (out.stdout, out.stderr)
    assert sorted(lines[1:]) == sorted(
        f'vproxy_trace_span_us_count{{plane="{p}",span="{s}"}} 0'
        for p, s in trace.SPANS)
    out = subprocess.run([sys.executable, "-c", prog],
                         env=dict(env, VPROXY_TPU_TRACE_SAMPLE="8"),
                         capture_output=True, text=True, timeout=60)
    assert out.stdout.startswith("1 ") and not out.stderr, \
        (out.stdout, out.stderr)


def test_span_metrics_family_follows_the_totals():
    from vproxy_tpu.utils.metrics import GlobalInspection
    trace.configure(1)
    _serve_hints(6)
    tot = trace.span_totals()["engine/launch"]
    text = GlobalInspection.get().prometheus_string()
    lbl = '{plane="engine",span="launch"}'
    assert f"vproxy_trace_span_us_count{lbl} {tot['n']}" in text
    assert f'vproxy_trace_span_us_bucket{{le="+Inf",plane="engine",' \
           f'span="launch"}} {tot["n"]}' in text
    got = float(next(l for l in text.splitlines() if l.startswith(
        f"vproxy_trace_span_us_sum{lbl}")).split()[-1])
    assert got == pytest.approx(tot["sum_ns"] / 1000.0)


def test_spans_enter_the_profiler_trace(tmp_path):
    """While tracing is on the batch cycle's spans are TraceAnnotations
    named vproxy/<plane>/<span>: a jax.profiler trace of a served batch
    holds them on its own clock, encode + launch inside dispatch."""
    import glob
    import jax
    from jax.profiler import ProfileData
    trace.configure(1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _serve_hints(8)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))[0]
    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for plane in ProfileData.from_file(path).planes
           if not plane.name.startswith("/device:")
           for line in plane.lines for e in line.events
           if e.name.startswith("vproxy/")]
    names = {n for n, _s, _e in evs}
    assert {f"vproxy/engine/{s}" for s in CYCLE + ("wait",)} <= names
    disp = [(s, e) for n, s, e in evs if n == "vproxy/engine/dispatch"]
    assert disp
    for ds, de in disp:     # (the warm-up's encode + launch have none)
        for inner in ("vproxy/engine/encode", "vproxy/engine/launch"):
            assert any(n == inner and ds <= s and e <= de
                       for n, s, e in evs), (inner, ds, de)
