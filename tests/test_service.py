"""ClassifyService — the cross-connection micro-batch queue (north star).

Covers: batching ratio (N concurrent queries -> far fewer device
dispatches), correctness vs the host oracle, auto-mode policy, device
failover to the oracle, and the live TcpLB http-splice data plane
flowing through device batches end-to-end.
"""
import socket
import threading
import time

import pytest

from vproxy_tpu.rules.engine import CidrMatcher, HintMatcher
from vproxy_tpu.rules import oracle
from vproxy_tpu.rules.ir import AclRule, Hint, HintRule, Proto
from vproxy_tpu.rules.service import ClassifyService
from vproxy_tpu.utils.ip import Network, mask_bytes


@pytest.fixture(autouse=True)
def fresh_service():
    ClassifyService.reset()
    yield
    ClassifyService.reset()


def mk_rules(n=50):
    return [HintRule(host=f"svc{i}.example.com") for i in range(n)]


def collect(n):
    """-> (cb, results, done_event): cb collects n results."""
    results = {}
    done = threading.Event()
    lock = threading.Lock()

    def cb(i, idx):
        with lock:
            results[i] = idx
            if len(results) == n:
                done.set()

    return cb, results, done


def test_concurrent_queries_batch_into_few_dispatches():
    svc = ClassifyService.get()
    svc.mode = "device"
    m = HintMatcher(mk_rules(64))
    n = 200
    cb, results, done = collect(n)
    hints = [Hint.of_host(f"svc{i % 64}.example.com") for i in range(n)]
    # warm the jit so compile time doesn't serialize the first batch
    m.match([Hint.of_host("warm.example.com")] * 16)

    for i, h in enumerate(hints):
        svc.submit_hint(m, h, lambda idx, _pl, i=i: cb(i, idx))
    assert done.wait(30)
    # correctness vs oracle
    for i, h in enumerate(hints):
        assert results[i] == oracle.search(m.rules, h)
    # the whole point: far fewer dispatches than queries
    assert svc.stats.device_queries == n
    assert svc.stats.dispatches < n / 4, (
        f"{svc.stats.dispatches} dispatches for {n} queries — not batching")
    assert svc.stats.max_batch >= 2


def test_auto_mode_lone_small_query_uses_oracle():
    svc = ClassifyService.get()
    assert svc.mode == "auto"
    m = HintMatcher(mk_rules(8))
    cb, results, done = collect(1)
    svc.submit_hint(m, Hint.of_host("svc3.example.com"),
                    lambda idx, _pl: cb(0, idx))
    assert done.wait(10)
    assert results[0] == 3
    assert svc.stats.oracle_queries == 1
    assert svc.stats.dispatches == 0


def test_cidr_batching_with_ports():
    svc = ClassifyService.get()
    svc.mode = "device"
    acls = [AclRule(f"r{i}",
                    Network(bytes([10, i, 0, 0]), mask_bytes(16)),
                    Proto.TCP, 1000, 2000, i % 2 == 0)
            for i in range(32)]
    m = CidrMatcher([a.network for a in acls], acl=acls)
    n = 100
    cb, results, done = collect(n)
    queries = [(bytes([10, i % 40, 1, 2]), 1500 if i % 3 else 99)
               for i in range(n)]
    m.match([b"\x0a\x00\x00\x01"], [1500])  # warm jit
    for i, (a, p) in enumerate(queries):
        svc.submit_cidr(m, a, p, lambda idx, _pl, i=i: cb(i, idx))
    assert done.wait(30)
    for i, (a, p) in enumerate(queries):
        assert results[i] == m.oracle_one(a, p), (i, a, p)
    assert svc.stats.dispatches < n / 4


def test_device_failure_degrades_to_oracle_and_recovers():
    svc = ClassifyService.get()
    svc.mode = "device"
    svc.retry_s = 0.3
    m = HintMatcher(mk_rules(16))

    boom = {"on": True}
    real_dispatch = m.dispatch_snap

    def flaky(snap, hints, **kw):
        if boom["on"]:
            raise RuntimeError("device dropped")
        return real_dispatch(snap, hints, **kw)

    m.dispatch_snap = flaky
    # a batch while the device is broken: served by the oracle, no crash
    cb, results, done = collect(10)
    for i in range(10):
        svc.submit_hint(m, Hint.of_host(f"svc{i}.example.com"),
                        lambda idx, _pl, i=i: cb(i, idx))
    assert done.wait(10)
    assert all(results[i] == i for i in range(10))
    assert svc.stats.failovers >= 1
    assert svc.stats.oracle_queries >= 10
    assert not svc.device_ok()

    # after retry_s the device is probed again and serves
    boom["on"] = False
    time.sleep(0.4)
    cb2, results2, done2 = collect(4)
    for i in range(4):
        svc.submit_hint(m, Hint.of_host(f"svc{i}.example.com"),
                        lambda idx, _pl, i=i: cb2(i, idx))
    assert done2.wait(10)
    assert all(results2[i] == i for i in range(4))
    assert svc.stats.device_queries >= 4


def test_rule_update_between_batches_stays_consistent():
    """An update must swap table+rules atomically: results always match
    ONE version's oracle, never a torn mix."""
    svc = ClassifyService.get()
    svc.mode = "device"
    rules_v1 = mk_rules(32)
    rules_v2 = [HintRule(host=f"svc{i}.example.org") for i in range(32)]
    m = HintMatcher(rules_v1)
    m.match([Hint.of_host("warm.example.com")] * 16)

    stop = threading.Event()

    def updater():
        while not stop.is_set():
            m.set_rules(rules_v2)
            m.set_rules(rules_v1)

    th = threading.Thread(target=updater, daemon=True)
    th.start()
    try:
        hint = Hint.of_host("svc7.example.com")  # matches v1 only
        hint2 = Hint.of_host("svc7.example.org")  # matches v2 only
        for _ in range(50):
            n = 8
            cb, results, done = collect(n)
            for i in range(n):
                svc.submit_hint(m, hint if i % 2 else hint2,
                                lambda idx, _pl, i=i: cb(i, idx))
            assert done.wait(10)
            for i, idx in results.items():
                # whichever version served the batch, 7 or -1 are the only
                # legal answers; any other index means torn state
                assert idx in (7, -1), results
    finally:
        stop.set()
        th.join(timeout=2)


def test_e2e_http_splice_flows_through_device_batches():
    from tests.test_tcplb import IdServer, fast_hc, http_get_id, wait_healthy
    from vproxy_tpu.components.elgroup import EventLoopGroup
    from vproxy_tpu.components.servergroup import ServerGroup
    from vproxy_tpu.components.tcplb import TcpLB
    from vproxy_tpu.components.upstream import Upstream

    svc = ClassifyService.get()
    svc.mode = "device"

    elg = EventLoopGroup("w", 2)
    s1, s2 = IdServer("A", http=True), IdServer("B", http=True)
    g1 = ServerGroup("g1", elg, fast_hc(), "wrr")
    g2 = ServerGroup("g2", elg, fast_hc(), "wrr")
    lb = None
    try:
        g1.add("a", "127.0.0.1", s1.port, weight=1)
        g2.add("b", "127.0.0.1", s2.port, weight=1)
        wait_healthy(g1, 1)
        wait_healthy(g2, 1)
        ups = Upstream("u")
        ups.add(g1, annotations=HintRule(host="a.example.com"))
        ups.add(g2, annotations=HintRule(host="b.example.com"))
        lb = TcpLB("lb", elg, elg, "127.0.0.1", 0, ups,
                   protocol="http-splice")
        lb.start()

        n = 40
        out = [None] * n
        ths = []

        def one(i):
            host = "a.example.com" if i % 2 else "b.example.com"
            _, body = http_get_id(lb.bind_port, host)
            out[i] = (host, body)

        for i in range(n):
            th = threading.Thread(target=one, args=(i,))
            th.start()
            ths.append(th)
        for th in ths:
            th.join(timeout=20)
        for i, r in enumerate(out):
            assert r is not None, f"request {i} did not finish"
            host, body = r
            assert body == ("A" if host.startswith("a.") else "B"), out[i]
        # hint lookups rode the device in micro-batches
        assert svc.stats.device_queries >= n
        assert svc.stats.dispatches < svc.stats.queries
    finally:
        if lb is not None:
            lb.stop()
        for x in (g1, g2):
            x.close()
        for s in (s1, s2):
            s.close()
        elg.close()


def test_dns_query_rides_the_queue():
    from vproxy_tpu.components.elgroup import EventLoopGroup
    from vproxy_tpu.components.servergroup import ServerGroup
    from vproxy_tpu.components.upstream import Upstream
    from vproxy_tpu.dns import packet as P
    from vproxy_tpu.dns.server import DNSServer
    from tests.test_tcplb import fast_hc

    svc = ClassifyService.get()
    svc.mode = "device"

    elg = EventLoopGroup("w", 1)
    g = ServerGroup("g", elg, fast_hc(), "wrr")
    srv = None
    try:
        g.add("a", "10.1.2.3", 80, weight=1)
        g.servers[0].healthy = True  # no live hc target; force healthy
        ups = Upstream("rr")
        ups.add(g, annotations=HintRule(host="web.example.com"))
        srv = DNSServer("dns", elg.next(), "127.0.0.1", 0, ups)
        srv.start()

        q = P.Packet(id=7, is_resp=False, rd=True, questions=[
            P.Question(qname="web.example.com.", qtype=P.A)])
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.settimeout(5)
        s.sendto(q.encode(), ("127.0.0.1", srv.bind_port))
        data, _ = s.recvfrom(4096)
        s.close()
        resp = P.parse(data)
        assert resp.id == 7 and resp.answers
        assert resp.answers[0].rdata == bytes([10, 1, 2, 3])
        assert svc.stats.queries >= 1
    finally:
        if srv is not None:
            srv.stop()
        g.close()
        elg.close()


def test_mixed_port_and_portless_cidr_queries_keep_semantics():
    """port=None means 'ignore port ranges' — it must not be coerced to
    port 0 when sharing a flush with port-carrying queries."""
    svc = ClassifyService.get()
    svc.mode = "device"
    acls = [AclRule(f"r{i}",
                    Network(bytes([10, i, 0, 0]), mask_bytes(16)),
                    Proto.TCP, 1000, 2000, True)
            for i in range(20)]
    m = CidrMatcher([a.network for a in acls], acl=acls)
    m.match([b"\x0a\x00\x00\x01"], [1500])  # warm jit
    n = 40
    cb, results, done = collect(n)
    # even i: port-carrying (in range); odd i: port=None (range ignored)
    queries = [(bytes([10, i % 20, 1, 2]), 1500 if i % 2 == 0 else None)
               for i in range(n)]
    for i, (a, p) in enumerate(queries):
        svc.submit_cidr(m, a, p, lambda idx, _pl, i=i: cb(i, idx))
    assert done.wait(30)
    for i, (a, p) in enumerate(queries):
        assert results[i] == m.oracle_one(a, p) == i % 20, (i, results[i])


def test_latency_budget_reroutes_lone_big_table_queries():
    """Weak #5: a lone accept against a big table must not eat an
    over-budget device round trip forever — once the device EWMA blows
    the budget and the oracle is faster, lone queries reroute (with
    periodic re-probes of the device)."""
    svc = ClassifyService.get()
    assert svc.mode == "auto"
    svc.inline_lone = False  # exercise the budget policy, not the lane
    svc.budget_us = 1000.0  # 1ms budget
    m = HintMatcher(mk_rules(300))  # > SMALL_TABLE
    # make the device path artificially slow (50ms)
    real = m.dispatch_snap

    def slow(snap, hints, **kw):
        time.sleep(0.05)
        return real(snap, hints, **kw)

    m.dispatch_snap = slow
    m.match([Hint.of_host("warm.example.com")] * 16)  # warm jit

    def lone(i):
        cb, results, done = collect(1)
        svc.submit_hint(m, Hint.of_host(f"svc{i}.example.com"),
                        lambda idx, _pl: cb(0, idx))
        assert done.wait(10)
        return results[0]

    # 1st lone query probes the device (EWMA unknown), then oracle probe,
    # then steady-state reroutes to the oracle
    for i in range(8):
        assert lone(i) == i
    assert svc.stats.budget_reroutes >= 4
    assert svc.stats.oracle_queries >= 4
    # correctness is unchanged either way
    assert lone(123) == 123
    # stats surface the latency contract
    lat = svc.stats.latency_percentiles()
    assert lat is not None and lat["n"] >= 9
    assert lat["p50_us"] > 0
    snap = svc.stats.snapshot()
    assert "latency_p50_us" in snap and "budget_reroutes" in snap


def test_latency_budget_off_keeps_device_for_lone_big_queries():
    svc = ClassifyService.get()
    assert svc.mode == "auto"
    svc.inline_lone = False  # fast lane off: budget knob governs
    svc.budget_us = 0.0  # knob off -> previous behavior
    m = HintMatcher(mk_rules(300))
    m.match([Hint.of_host("warm.example.com")] * 16)
    cb, results, done = collect(1)
    svc.submit_hint(m, Hint.of_host("svc7.example.com"),
                    lambda idx, _pl: cb(0, idx))
    assert done.wait(10)
    assert results[0] == 7
    assert svc.stats.device_queries == 1
    assert svc.stats.oracle_queries == 0


def test_inline_host_path_is_synchronous_and_probes_off_path():
    """Budget-rerouted lone queries are answered INLINE on the
    submitting thread (no dispatcher hop — the accept-path latency
    contract), and the device EWMA is refreshed by an off-path probe
    thread, never by making a real query eat the device round trip."""
    import threading as _t

    svc = ClassifyService.get()
    assert svc.mode == "auto"
    svc.budget_us = 1000.0
    svc._ewma["device"] = 50_000.0  # over budget -> host path
    m = HintMatcher(mk_rules(300))
    m.match([Hint.of_host("warm.example.com")] * 16)

    probe_seen = _t.Event()
    real = m.dispatch_snap

    def slow(snap, hints, **kw):
        probe_seen.set()          # only the probe thread gets here
        time.sleep(0.02)
        return real(snap, hints, **kw)

    m.dispatch_snap = slow
    caller = _t.get_ident()
    hits = []
    from vproxy_tpu.rules.service import PROBE_EVERY
    for i in range(PROBE_EVERY + 2):
        fired = []
        svc.submit_hint(m, Hint.of_host(f"svc{i % 300}.example.com"),
                        lambda idx, _pl: fired.append(
                            (idx, _t.get_ident())))
        # inline contract: the callback already ran, on THIS thread
        assert fired and fired[0][1] == caller, i
        hits.append(fired[0][0])
    assert hits[:4] == [0, 1, 2, 3]
    assert probe_seen.wait(5)     # the off-path probe fired...
    for _ in range(100):          # ...and refreshed the device EWMA
        if svc._ewma["device"] != 50_000.0:
            break
        time.sleep(0.05)
    assert svc._ewma["device"] != 50_000.0
    # every query was served by the host index, none by the device
    assert svc.stats.oracle_queries >= PROBE_EVERY + 2


def test_micro_batches_always_ride_device_despite_budget():
    """n >= 2 is never rerouted by the budget policy: the policy only
    gates LONE queries (which the inline fast path serves from the host
    index); any batch that forms rides the device regardless of how bad
    the device EWMA looks."""
    svc = ClassifyService.get()
    assert svc.mode == "auto"
    svc.inline_lone = False  # decision-point asserts use the budget path
    svc.budget_us = 1.0  # absurdly tight budget
    svc._ewma["device"] = 1e6  # pretend the device is terrible
    svc._ewma["oracle"] = 10.0
    m = HintMatcher(mk_rules(300))
    m.match([Hint.of_host("warm.example.com")] * 16)
    # the routing contract, at the decision point the dispatcher uses
    assert svc._use_device(m, 2)      # micro-batch: always the device
    assert svc._use_device(m, 100)
    assert not svc._lone_path_is_device()  # lone over budget: host
    # and a burst stays correct end-to-end whichever path served it
    n = 50
    cb, results, done = collect(n)
    for i in range(n):
        svc.submit_hint(m, Hint.of_host(f"svc{i}.example.com"),
                        lambda idx, _pl, i=i: cb(i, idx))
    assert done.wait(30)
    for i in range(n):
        assert results[i] == i
    # with the device over budget every lone submission was answered
    # inline from the host index — no device round trip on the path
    assert svc.stats.oracle_queries >= n - 10
    assert svc.stats.budget_reroutes >= n - 10


def test_inline_fast_lane_default_and_parity_vs_oracle():
    """Round-6 fast lane: in auto mode EVERY lone query against a big
    table is answered inline from the host index by default (no budget
    gate, no device EWMA warm-up), and the winner is bit-for-bit the
    oracle's across exact hosts, dot-suffix matches, uri prefixes,
    port rules, wildcards and misses. Zero device dispatches."""
    import threading as _t

    svc = ClassifyService.get()
    assert svc.mode == "auto" and svc.inline_lone

    rules = []
    for i in range(200):
        rules.append(HintRule(host=f"svc{i}.lane.example.com"))
    for i in range(60):
        rules.append(HintRule(host=f"svc{i}.lane.example.com",
                              uri=f"/api/v{i % 7}"))
    for i in range(40):
        rules.append(HintRule(host=f"svc{i}.lane.example.com", port=443))
    rules.append(HintRule(host="*", uri="/fallback"))
    m = HintMatcher(rules)  # > SMALL_TABLE: the lane is live
    m.match([Hint.of_host("warm.example.com")] * 16)

    queries = []
    for i in range(0, 200, 7):
        queries.append(Hint.of_host(f"svc{i}.lane.example.com"))
        queries.append(Hint.of_host(f"x.svc{i}.lane.example.com"))
        queries.append(Hint.of_host_uri(f"svc{i}.lane.example.com",
                                        f"/api/v{i % 7}/deep"))
        queries.append(Hint.of_host_port(f"svc{i}.lane.example.com", 443))
    queries.append(Hint.of_host_uri("unknown.example.org", "/fallback/x"))
    queries.append(Hint.of_host("no.match.example.org"))

    caller = _t.get_ident()
    for h in queries:
        fired = []
        svc.submit_hint(m, h,
                        lambda idx, _pl: fired.append((idx, _t.get_ident())))
        # the fast-lane contract: answered before submit returns, on the
        # submitting thread
        assert fired and fired[0][1] == caller, h
        assert fired[0][0] == oracle.search(rules, h), h
    assert svc.stats.dispatches == 0
    assert svc.stats.device_queries == 0
    assert svc.stats.inline_fast >= len(queries)


# ------------------------------------------------ batch-cycle span totals

@pytest.mark.parametrize("kind", ["hint", "cidr", "cpick"])
def test_batch_cycle_spans_for_each_kind(kind):
    """Tracing on: whichever matcher kind a batch rides, its cycle lands
    in utils/trace's totals — one launch (named by kind, fused for the
    classify+pick program) per device batch, every real query counted
    once at encode and once at deliver."""
    from vproxy_tpu.rules.maglev import FusedPair, MaglevMatcher
    from vproxy_tpu.utils import trace
    n = 24
    hm = HintMatcher(mk_rules(64))
    if kind == "cidr":
        m = CidrMatcher([Network(bytes([10, i, 0, 0]), mask_bytes(16))
                         for i in range(32)])
        m.match([b"\x0a\x00\x00\x01"] * 16)     # warm the jit
    else:
        m = hm if kind == "hint" else FusedPair(
            hm, MaglevMatcher([(f"b{i}", 1) for i in range(5)], m=251))
    got, done = [], threading.Event()

    def cb(*verdict):
        got.append(verdict[0])
        if len(got) == n:
            done.set()

    svc = ClassifyService(mode="device")
    trace.configure(1)
    try:
        tid = trace.new_trace_id()
        with trace.bind(tid):   # every query sampled: one trace
            # the first submit may ride alone (and compile): totals are
            # read as a whole, whatever the batches came out as
            before = trace.span_totals()
            for i in range(n):
                h = Hint.of_host(f"svc{i}.example.com")
                if kind == "hint":
                    svc.submit_hint(m, h, cb)
                elif kind == "cidr":
                    svc.submit_cidr(m, bytes([10, i, 1, 2]), None, cb)
                else:
                    svc.submit_classify_pick(m, h, bytes([172, 16, 0, i]),
                                             80 + i, cb)
        assert done.wait(60)
        assert sorted(got) == list(range(n))
        after = trace.span_totals()
        spans = trace.get_trace(tid)
    finally:
        trace.configure(0)
        trace.reset()
        svc.close()

    def moved(span, field):
        return after[f"engine/{span}"][field] \
            - before.get(f"engine/{span}", {}).get(field, 0)

    batches = svc.stats.dispatches
    assert batches >= 1 and svc.stats.device_queries == n
    for span in ("dispatch", "launch", "d2h_sync", "deliver"):
        assert moved(span, "n") == batches, span
    assert moved("encode", "sum_items") == moved("deliver", "sum_items") == n
    assert moved("queue_wait", "n") == moved("submit_lock_wait", "n") == n
    launches = [s for s in spans if s["span"] == "launch"]
    assert len(launches) == batches
    assert all(s["kind"] == kind and s["fused"] is (kind == "cpick")
               and s["parent"] == "dispatch" for s in launches)
    assert sum(s["batch"] for s in spans if s["span"] == "dispatch") == n


# ---- deliver: a batch's latency bookkeeping once a batch (PR 30) ----

def _held_batch(svc, m, submit_all, timeout=30):
    """Deliver what submit_all() submits as ONE dispatcher batch: a gate
    query's callback (loop=None: it runs on the dispatcher thread) holds
    the dispatcher while the others pile up in the pending queue."""
    entered, release = threading.Event(), threading.Event()

    def gate(*_verdict):
        entered.set()
        assert release.wait(timeout)

    svc.submit_hint(m, Hint.of_host("gate.example.com"), gate)
    assert entered.wait(timeout)
    try:
        submit_all()
    finally:
        release.set()


@pytest.mark.parametrize("kind", ["hint", "cidr", "cpick"])
@pytest.mark.parametrize("size", ["below", "at", "above"])
def test_batch_latencies_are_recorded_once_a_batch(kind, size):
    """A batch of n through the dispatcher is n samples in the
    instance's reservoir and in the global histogram whatever its
    length; from LAT_BATCH_MIN on they go through observe_many and
    latency_batched counts them, below it nothing does. Every callback
    gets Python ints."""
    from vproxy_tpu.rules import service
    from vproxy_tpu.rules.maglev import FusedPair, MaglevMatcher
    lo = service.LAT_BATCH_MIN
    n = {"below": lo - 1, "at": lo, "above": 3 * lo + 4}[size]
    hm = HintMatcher(mk_rules(64))
    cm = CidrMatcher([Network(bytes([10, i, 0, 0]), mask_bytes(16))
                      for i in range(64)])
    pair = FusedPair(hm, MaglevMatcher([(f"b{i}", 1) for i in range(5)],
                                       m=251))
    svc = ClassifyService(mode="host")
    global_before = svc.stats.lat_hist.state()[0]
    got, done = [], threading.Event()

    def cb(*verdict):
        got.append(verdict)
        if len(got) == n:
            done.set()

    def submit_all():
        for i in range(n):
            h = Hint.of_host(f"svc{i}.example.com")
            if kind == "hint":
                svc.submit_hint(hm, h, cb)
            elif kind == "cidr":
                svc.submit_cidr(cm, bytes([10, i, 1, 2]), None, cb)
            else:
                svc.submit_classify_pick(pair, h, bytes([172, 16, 0, i]),
                                         80 + i, cb)

    try:
        _held_batch(svc, hm, submit_all)
        assert done.wait(30)
    finally:
        svc.close()
    assert svc.stats.max_batch == n   # one batch, the whole of it
    # + 1: the gate query, a lone one, took the scalar observe
    assert svc.stats.latency_percentiles()["n"] == n + 1
    assert svc.stats.lat_hist.state()[0] - global_before == n + 1
    assert svc.stats.latency_batched == (n if n >= lo else 0)
    assert [v[0] for v in got] == list(range(n))   # the order submitted
    for verdict in got:
        assert len(verdict) == (3 if kind == "cpick" else 2)
        assert all(type(x) is int for x in verdict[:-1]), verdict


def test_global_latency_count_rises_with_the_instance():
    """The process-global vproxy_classify_latency_us series and the
    instance's reservoir take the same samples from one batch: equal
    count deltas, equal sums, and /metrics shows the batched counter
    beside the histogram's own _count."""
    from vproxy_tpu.utils.metrics import GlobalInspection
    ClassifyService.reset()
    svc = ClassifyService.get()
    svc.mode = "host"
    m = HintMatcher(mk_rules(64))
    n = 40
    cb, results, done = collect(n)
    c0, s0, _b = svc.stats.lat_hist.state()

    def submit_all():
        for i in range(n):
            svc.submit_hint(m, Hint.of_host(f"svc{i}.example.com"),
                            lambda idx, _pl, i=i: cb(i, idx))

    _held_batch(svc, m, submit_all)
    assert done.wait(30)
    assert results == {i: i for i in range(n)}
    c1, s1, _b = svc.stats.lat_hist.state()
    cl, sl, _b = svc.stats._lat_local.state()
    assert c1 - c0 == cl == n + 1
    assert s1 - s0 == pytest.approx(sl, rel=1e-6)
    text = GlobalInspection.get().registry.prometheus_text()
    assert f"vproxy_classify_latency_batched_total {n}\n" in text
    assert f"vproxy_classify_latency_us_count {c1}\n" in text


def test_inline_answers_record_through_the_scalar_path():
    svc = ClassifyService.get()
    assert svc.mode == "auto"
    m = HintMatcher(mk_rules(8))
    got = []
    for i in range(20):
        svc.submit_hint(m, Hint.of_host(f"svc{i % 8}.example.com"),
                        lambda idx, _pl: got.append(idx))
    assert got == [i % 8 for i in range(20)]    # inline: synchronous
    assert all(type(i) is int for i in got)
    assert svc.stats.latency_percentiles()["n"] == 20
    assert svc.stats.latency_batched == 0


def test_failing_callback_is_logged_and_the_batch_goes_on(monkeypatch):
    """loop=None: the callback runs on the dispatcher thread with no
    closure around it; one that raises is logged, the callbacks after
    it in the batch still run and the dispatcher thread survives."""
    from vproxy_tpu.rules import service
    logged = []

    class Log:
        @staticmethod
        def error(msg, exc=False):
            logged.append((msg, exc))

    monkeypatch.setattr(service, "_log", Log)
    svc = ClassifyService(mode="host")
    m = HintMatcher(mk_rules(64))
    n = 20
    got, done = [], threading.Event()

    def cb(idx, _pl):
        if idx in (0, 7):
            raise ValueError(f"callback {idx} fails")
        got.append(idx)
        if len(got) == n - 2:
            done.set()

    def submit_all():
        for i in range(n):
            svc.submit_hint(m, Hint.of_host(f"svc{i}.example.com"), cb)

    try:
        _held_batch(svc, m, submit_all)
        assert done.wait(30)
        assert got == [i for i in range(n) if i not in (0, 7)]
        assert logged == [("classify callback failed", True)] * 2
        assert svc.stats.max_batch == n
        assert svc.stats.latency_percentiles()["n"] == n + 1
        # the thread outlived it: the next query is served by it
        assert svc._thread.is_alive()
        cb2, results, done2 = collect(1)
        svc.submit_hint(m, Hint.of_host("svc9.example.com"),
                        lambda idx, _pl: cb2(0, idx))
        assert done2.wait(30) and results[0] == 9
    finally:
        svc.close()


@pytest.mark.parametrize("kind", ["hint", "cpick"])
def test_error_fill_delivers_no_match_to_the_batch(kind):
    """A batch whose dispatch raises gets the scalar fill [-1] * n: a
    plain idx -1 — and for a classify+pick batch, whose rows are
    (verdict, pick) pairs, both -1 — with payload None."""
    class Broken:
        backend = "host"

        def snapshot(self):
            raise RuntimeError("no generation")

    svc = ClassifyService(mode="host")
    gate_m = HintMatcher(mk_rules(4))
    n = 15
    got, done = [], threading.Event()

    def cb(*verdict):
        got.append(verdict)
        if len(got) == n:
            done.set()

    def submit_all():
        for i in range(n):
            h = Hint.of_host(f"svc{i}.example.com")
            if kind == "hint":
                svc.submit_hint(broken, h, cb)
            else:
                svc.submit_classify_pick(broken, h, b"\x0a\x00\x00\x01",
                                         80, cb)

    broken = Broken()
    try:
        _held_batch(svc, gate_m, submit_all)
        assert done.wait(30)
    finally:
        svc.close()
    want = (-1, -1, None) if kind == "cpick" else (-1, None)
    assert got == [want] * n
    assert all(type(x) is int for v in got for x in v[:-1])
    assert svc.stats.latency_percentiles()["n"] == n + 1
