"""A DNS zone as the program holds one, at toy size: the benchmark's
`dns-100k` zone (benchmark/builders/dns_zone.py) served by a real
DNSServer over loopback with the classify service in mode device,
every answer held to the plain reference (benchmark/reference_dns.py),
the answer cache on and off; `Upstream.add_many` against n `add`s;
`protocol: none` health checks that arm no timer; the `dns` plane's
spans and counters."""
import os
import socket
import sys
import time

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference_dns as R  # noqa: E402
from builders import dns_zone  # noqa: E402
from drivers import dns_udp_client as wire  # noqa: E402

from vproxy_tpu.components import servergroup as SG  # noqa: E402
from vproxy_tpu.components.elgroup import EventLoopGroup  # noqa: E402
from vproxy_tpu.components.servergroup import (  # noqa: E402
    HealthCheckConfig, ServerGroup)
from vproxy_tpu.components.upstream import Upstream  # noqa: E402
from vproxy_tpu.dns.server import DNSServer  # noqa: E402
from vproxy_tpu.rules.ir import HintRule  # noqa: E402
from vproxy_tpu.rules.service import ClassifyService  # noqa: E402
from vproxy_tpu.utils import trace  # noqa: E402
from vproxy_tpu.utils.metrics import GlobalInspection  # noqa: E402

from tests.test_tcplb import wait_healthy  # noqa: E402

CONFIG = {"sizes": {"hint_rules": 500, "wildcard_zones": 10,
                    "exact_names": 90}}
TRAFFIC = {"forms": ["exact"] * 8 + ["wildcard", "nxdomain"],
           "qtypes": ["A", "A", "A", "AAAA"]}
SEED = 2**31 + 400


@pytest.fixture
def zone(monkeypatch):
    monkeypatch.setenv("VPROXY_TPU_CLASSIFY", "device")
    ClassifyService.reset()
    dep = dns_zone.build(CONFIG, SEED)
    dep.install()
    yield dep
    dep.close()
    ClassifyService.reset()


def serve(dep, **kw):
    elg = EventLoopGroup("dnsz", 1)
    srv = DNSServer("dnsz", elg.next(), "127.0.0.1", 0, dep.upstream, **kw)
    srv.start()
    return elg, srv


def read_reply(data: bytes, name: str, t: int, owner: dict = None):
    """A reply to (name, t) as the reference reads it: NXDOMAIN, EMPTY,
    the group `owner` names for the answered (family, address) — -8 for
    one it does not — or that pair itself without `owner`; MALFORMED for
    a reply of another question, rcode or family."""
    rc, fam, addr = wire.read_answer(data, wire.question(name, t))
    if rc == 3 and fam == 0:
        return R.NXDOMAIN
    if rc == 0 and fam == 0:
        return R.EMPTY
    if rc == 0 and fam == (4 if t == R.A else 6):
        return (fam, addr) if owner is None else owner.get((fam, addr), -8)
    return wire.MALFORMED


def ask(port: int, queries: list, owner: dict = None,
        burst: int = 64) -> list:
    """-> each reply as `read_reply` reads it."""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.settimeout(5)
    out = []
    try:
        for at in range(0, len(queries), burst):
            part = queries[at:at + burst]
            for i, (name, t) in enumerate(part):
                s.sendto(i.to_bytes(2, "big") + wire.query_body(name, t),
                         ("127.0.0.1", port))
            got = {}
            while len(got) < len(part):
                data = s.recv(4096)
                got[int.from_bytes(data[:2], "big")] = data
            out += [read_reply(got[i], name, t, owner)
                    for i, (name, t) in enumerate(part)]
    finally:
        s.close()
    return out


@pytest.mark.parametrize("cache_ms", [1000, 0], ids=["cache", "no-cache"])
def test_zone_answers_equal_the_reference(zone, monkeypatch, cache_ms):
    """Every answer of the served zone — exact, wildcard, A-only, A+AAAA,
    the 4-A rotation, NXDOMAIN — is the reference's, asked twice (the
    second time from the cache where it is on)."""
    monkeypatch.setenv("VPROXY_TPU_DNS_CACHE_MS", str(cache_ms))
    pool = zone.pool_kind("dns", 400, TRAFFIC, SEED)
    want = zone.answers_kind("dns", pool, False, SEED)
    owner = R.address_owner(zone.plain["zone"])
    elg, srv = serve(zone)
    try:
        svc = ClassifyService.get()
        q0, d0 = svc.stats.queries, svc.stats.device_queries
        for _ in range(2):
            assert ask(srv.bind_port, pool, owner) == want.tolist()
        assert svc.stats.oracle_queries == 0
        assert svc.stats.queries - q0 == svc.stats.device_queries - d0 > 0
        assert (srv.cache_hits > 0) == (cache_ms > 0)
    finally:
        srv.stop()
        elg.close()
    kinds = {(g >= 0) + 2 * (g == R.EMPTY) for g in want.tolist()}
    assert kinds == {0, 1, 2}    # NXDOMAIN, a group, no record
    four = [g for g in want.tolist() if g >= 0 and
            len(zone.plain["zone"][g][1]) == 4]
    assert four and any(zone.plain["wildcard"][g] for g in want.tolist()
                        if g >= 0)


def test_the_four_address_records_rotate(zone, monkeypatch):
    """DNS-as-LB: a 4-A record answers its four addresses in turn."""
    monkeypatch.setenv("VPROXY_TPU_DNS_CACHE_MS", "0")
    elg, srv = serve(zone)
    try:
        name, a4, _a6 = next(r for r in zone.plain["zone"] if len(r[1]) == 4)
        seen = ask(srv.bind_port, [(name, R.A)] * 8, burst=1)
        assert {a[:4] for _f, a in seen} == set(a4)
    finally:
        srv.stop()
        elg.close()


# ------------------------------------------------ Upstream.add_many

def _groups(elg, n: int) -> list:
    out = []
    for i in range(n):
        method = ("wrr", "source", "wlc")[i % 3]
        g = ServerGroup(f"am{i}", elg, HealthCheckConfig(protocol="none"),
                        method)
        for k in range(1 + i % 3):
            g.add(f"s{k}", f"10.7.{i}.{k + 1}", 80)
        out.append(g)
    return out


def test_add_many_is_n_adds_with_one_publish(monkeypatch):
    monkeypatch.setattr(SG.random, "randrange", lambda n: 0)
    elg = EventLoopGroup("am", 1)
    groups = _groups(elg, 12)
    try:
        wait_healthy(groups[-1], 3)
        entries = [(g, 5 + i % 4, HintRule(host=f"h{i}.example.com"))
                   for i, g in enumerate(groups)]
        one, many = Upstream("am-one"), Upstream("am-many")
        for g, w, a in entries:
            one.add(g, w, a)
        publishes = []
        orig = many._pair.set_rules
        monkeypatch.setattr(many._pair, "set_rules", lambda *a, **kw: (
            publishes.append(1), orig(*a, **kw))[1])
        handles = many.add_many(entries)
        assert len(publishes) == 1
        assert [h.group for h in handles] == [h.group for h in one.handles] \
            == groups
        assert [h.weight for h in handles] == [w for _g, w, _a in entries]
        assert many._matcher.rules == one._matcher.rules
        payload = many._matcher.snap_payload(many._matcher.snapshot())
        assert [h.group for h in payload] == groups
        assert many._wrr_seq == one._wrr_seq
        # a row of the pick-table set for the `source` groups alone
        assert [h.ref >= 0 for h in handles] == \
            [g.method == "source" for g in groups]
        with pytest.raises(ValueError):
            many.add_many([(groups[0], 10, None)])
        one.close()
        many.close()
    finally:
        for g in groups:
            g.close()
        elg.close()


def test_wrr_equal_weights_are_the_reference_sequence(monkeypatch):
    """The equal-weight shortcut of `_wrr_compute` gives what the
    subtract-sum loop gives (copied here), unequal weights take it."""
    monkeypatch.setattr(SG.random, "randrange", lambda n: 0)

    def loop(weights):
        w, orig, seq = list(weights), list(weights), []
        total = sum(w)
        while True:
            i = max(range(len(w)), key=lambda k: (w[k], -k))
            seq.append(i)
            w[i] -= total
            if all(x == 0 for x in w):
                return seq
            w = [x + o for x, o in zip(w, orig)]
            total = sum(w)

    class S:
        def __init__(self, weight):
            self.weight = weight
    for weights in ([10] * 7, [3] * 50, [10, 20, 30], [1, 1, 2], [0, 0]):
        assert SG.ServerGroup._wrr_compute([S(x) for x in weights]) \
            == loop(weights), weights


# ----------------------------------------------------- protocol: none

def test_none_check_marks_up_once_and_arms_no_timer(monkeypatch):
    monkeypatch.setattr(SG, "EJECT_FAILURES", 2)
    elg = EventLoopGroup("none", 1)
    # up = 2: the one verdict still makes the server healthy at once
    g = ServerGroup("gn", elg, HealthCheckConfig(protocol="none",
                                                 period_ms=50, up=2))
    try:
        x = g.add("x", "10.6.0.1", 1)
        g.add("y", "10.6.0.2", 2)
        wait_healthy(g, 2, timeout=2.0)
        elg.next().call_sync(lambda: None)
        assert all(c._periodic is None for c in g._checkers.values())
        # a passive ejection arms the re-admission pass, which ends
        # with the server back in rotation
        g.report_failure(x)
        g.report_failure(x)
        assert x.ejected and not x.healthy
        elg.next().call_sync(lambda: None)
        assert g._checkers["x"]._periodic is not None
        monkeypatch.setattr(SG, "EJECT_BASE_S", 0.1)
        x._eject_until = time.monotonic() + 0.1
        wait_healthy(g, 2, timeout=5.0)
        deadline = time.time() + 2
        while g._checkers["x"]._periodic is not None:
            assert time.time() < deadline
            time.sleep(0.02)
        assert not x.ejected
    finally:
        g.close()
        elg.close()


# ------------------------------------------------- the `dns` plane

def _counter(name: str, **labels) -> float:
    return GlobalInspection.get().get_counter(name, **labels).value()


def test_drain_spans_and_counters_follow_the_mix(zone):
    """`dns/drain` items = datagrams read, `dns/answer` items =
    responses sent by the classified path, and the four counter
    families move as the query mix says."""
    records = zone.plain["zone"]
    exact = next(r for r, w in enumerate(zone.plain["wildcard"]) if not w)
    name = records[exact][0]
    queries = [(name, R.A), (name, R.A), ("nope.invalid", R.A)]
    before = {k: _counter(f"vproxy_dns_{k}_total")
              for k in ("queries", "cache_hits", "cache_clears")}
    noerr = _counter("vproxy_dns_answers_total", rcode="noerror")
    nx = _counter("vproxy_dns_answers_total", rcode="nxdomain")
    prev = trace.sample_every()
    trace.configure(1)
    t0 = trace.span_totals()
    elg, srv = serve(zone)
    try:
        # the whole cache cleared when full: 4,097 entries, one more
        srv._ans_cache.update({("fill", i, True): (0.0, None, b"")
                               for i in range(4097)})
        got = [ask(srv.bind_port, [q], burst=1)[0] for q in queries]
        assert got[2] == R.NXDOMAIN
    finally:
        srv.stop()
        elg.close()
        t1 = trace.span_totals()
        trace.configure(prev)

    def delta(span, key):
        return t1.get(span, {}).get(key, 0) - t0.get(span, {}).get(key, 0)
    assert delta("dns/drain", "sum_items") == 3
    assert delta("dns/drain", "n") >= 1
    assert delta("dns/answer", "sum_items") == 2   # the miss and NXDOMAIN
    assert _counter("vproxy_dns_queries_total") - before["queries"] == 3
    assert _counter("vproxy_dns_cache_hits_total") \
        - before["cache_hits"] == 1
    assert _counter("vproxy_dns_cache_clears_total") \
        - before["cache_clears"] == 1
    assert _counter("vproxy_dns_answers_total", rcode="noerror") \
        - noerr == 2
    assert _counter("vproxy_dns_answers_total", rcode="nxdomain") - nx == 1
    assert srv.responses == 3 and srv.cache_hits == 1
    np.testing.assert_equal(len(srv._ans_cache), 1)
