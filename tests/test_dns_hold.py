"""The dns-server's drain holds its rrsets lookups (dns/server.py
`_drain` / `_flush`): the lookups of a drain's cache misses reach the
classify service together once the socket is empty, or at `HOLD_CAP`;
the answers are the ones each query gets alone; cache hits are answered
before the hand-over; a raising datagram strands nothing held before
it; a lookup that starts outside a drain is submitted at once."""
import json
import os
import socket
import sys
import threading

import pytest

from vproxy_tpu.dns import packet as P
from vproxy_tpu.dns import server as dns_server
from vproxy_tpu.rules.service import ClassifyService
from vproxy_tpu.utils import trace

from tests.test_dns_zone import (  # noqa: F401  (zone: a fixture)
    BENCH, SEED, TRAFFIC, R, _counter, read_reply, serve, wire, zone)

sys.path.insert(0, os.path.join(BENCH, "readers"))
import span_items_mean  # noqa: E402


def _flushes(why: str) -> float:
    return _counter("vproxy_dns_hold_flushes_total", why=why)


def _held() -> float:
    return _counter("vproxy_dns_held_lookups_total")


def exchange(srv, packets: list, one_drain: bool,
             expect: int = -1) -> list:
    """Send each packet to the server -> its replies in the order they
    came (`expect` of them, all by default). one_drain: all of them are
    sent while the server's loop is held in a task, so they lie in its
    socket when it next reads: one drain. Else each is sent when the
    one before it is answered: a drain each."""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.settimeout(5)
    to = ("127.0.0.1", srv.bind_port)
    out = []
    try:
        if not one_drain:
            for p in packets:
                s.sendto(p, to)
                out.append(s.recv(4096))
            return out
        held, go = threading.Event(), threading.Event()
        srv.loop.run_on_loop(lambda: (held.set(), go.wait(5)))
        assert held.wait(5)
        try:
            for p in packets:
                s.sendto(p, to)
        finally:
            go.set()
        while len(out) < (len(packets) if expect < 0 else expect):
            out.append(s.recv(4096))
        return out
    finally:
        s.close()


def queries_to_packets(queries: list) -> list:
    return [i.to_bytes(2, "big") + wire.query_body(name, t)
            for i, (name, t) in enumerate(queries)]


def readings(replies: list, queries: list, owner: dict) -> dict:
    """The replies by id (a query's index), as the reference reads
    them."""
    out = {}
    for d in replies:
        i = int.from_bytes(d[:2], "big")
        out[i] = read_reply(d, *queries[i], owner)
    return out


def answered(replies: list, queries: list, owner: dict) -> list:
    """Every query's reply, in query order, as the reference reads it."""
    got = readings(replies, queries, owner)
    assert len(got) == len(replies) == len(queries)
    return [got[i] for i in range(len(queries))]


def warm(srv, zone) -> None:
    """One lookup answered before the counting starts: the dispatcher
    thread running, the smallest batch compiled."""
    q = zone.pool_kind("dns", 1, TRAFFIC, SEED + 1)
    exchange(srv, queries_to_packets(q), one_drain=False)


@pytest.fixture
def uncached(monkeypatch):
    monkeypatch.setenv("VPROXY_TPU_DNS_CACHE_MS", "0")


@pytest.mark.parametrize("one_drain", [True, False],
                         ids=["one-drain", "a-drain-each"])
def test_a_drain_hands_its_misses_over_as_one_flush(zone, uncached,
                                                    one_drain):
    """N misses read in one drain: one `empty` flush of N, one device
    batch, one `dns/flush` span of N; read a drain each: N of each.
    Either way every answer is the reference's."""
    n = 32
    queries = zone.pool_kind("dns", n, TRAFFIC, SEED)
    want = zone.answers_kind("dns", queries, False, SEED).tolist()
    owner = R.address_owner(zone.plain["zone"])
    elg, srv = serve(zone)
    prev_trace, prev_slice = trace.sample_every(), sys.getswitchinterval()
    try:
        warm(srv, zone)
        svc = ClassifyService.get()
        trace.configure(1)
        t0 = trace.span_totals().get("dns/flush", {})
        held0, empty0, cap0 = _held(), _flushes("empty"), _flushes("cap")
        d0 = svc.stats.dispatches
        # a long switch interval: the flush's submits are not cut by a
        # forced hand-over of the interpreter to the woken dispatcher
        sys.setswitchinterval(0.5)
        replies = exchange(srv, queries_to_packets(queries), one_drain)
        sys.setswitchinterval(prev_slice)
        t1 = trace.span_totals()["dns/flush"]
        assert answered(replies, queries, owner) == want
        flushes = 1 if one_drain else n
        assert _held() - held0 == n
        assert _flushes("empty") - empty0 == flushes
        assert _flushes("cap") - cap0 == 0
        assert svc.stats.dispatches - d0 == flushes
        assert svc.stats.oracle_queries == 0
        delta = {"dns/flush": {k: t1[k] - t0.get(k, 0)
                               for k in ("n", "sum_items")}}
        assert delta["dns/flush"] == {"n": flushes, "sum_items": n}
        # the benchmark's metric, read as the benchmark reads it
        with open(os.path.join(BENCH, "metrics",
                               "dns_lookups_per_flush.json")) as f:
            metric = json.load(f)
        assert metric["reader"] == "span_items_mean"
        assert span_items_mean.compute(delta, metric["params"]) == n / flushes
    finally:
        sys.setswitchinterval(prev_slice)
        trace.configure(prev_trace)
        srv.stop()
        elg.close()


def test_cache_hits_are_answered_before_the_flush(zone, monkeypatch):
    """A drain of miss, hit, miss, hit: both hits are sent from the
    cache before the held lookups are submitted, and are the first
    replies to arrive."""
    pool = zone.pool_kind("dns", 8, TRAFFIC, SEED + 2)
    owner = R.address_owner(zone.plain["zone"])
    want = zone.answers_kind("dns", pool, False, SEED).tolist()
    hits = [q for q, g in zip(pool, want) if g >= 0][:2]
    misses = zone.pool_kind("dns", 8, TRAFFIC, SEED + 3)
    misses = [q for q in misses if q not in hits][:2]
    assert len(hits) == 2 and len(misses) == 2
    queries = [misses[0], hits[0], misses[1], hits[1]]
    elg, srv = serve(zone)
    try:
        # the hits cached, one drain each
        exchange(srv, queries_to_packets(hits), one_drain=False)
        seen, start = [], []
        search, drain = zone.upstream.search_for_group_async, srv._drain

        def drain_spy(fd):
            start.append((srv.cache_hits, srv.responses))
            return drain(fd)

        def search_spy(hint, cb, loop=None):
            seen.append((srv.cache_hits, srv.responses))
            search(hint, cb, loop)
        monkeypatch.setattr(srv, "_drain", drain_spy)
        monkeypatch.setattr(zone.upstream, "search_for_group_async",
                            search_spy)
        replies = exchange(srv, queries_to_packets(queries), one_drain=True)
        (h0, r0), = start
        assert seen == [(h0 + 2, r0 + 2)] * 2
        assert {int.from_bytes(d[:2], "big") for d in replies[:2]} == {1, 3}
        want = zone.answers_kind("dns", queries, False, SEED).tolist()
        assert answered(replies, queries, owner) == want
    finally:
        srv.stop()
        elg.close()


@pytest.mark.parametrize("cap,caps,empties", [(4, 2, 1), (5, 2, 0),
                                              (16, 0, 1)])
def test_a_full_hold_is_handed_over_at_the_cap(zone, uncached, monkeypatch,
                                               cap, caps, empties):
    """A drain of 10 misses under a cap of `cap`: a `cap` flush each
    time the hold fills, an `empty` one for what is left at the end."""
    monkeypatch.setattr(dns_server, "HOLD_CAP", cap)
    queries = zone.pool_kind("dns", 10, TRAFFIC, SEED + 4)
    owner = R.address_owner(zone.plain["zone"])
    elg, srv = serve(zone)
    try:
        warm(srv, zone)
        held0, empty0, cap0 = _held(), _flushes("empty"), _flushes("cap")
        replies = exchange(srv, queries_to_packets(queries), one_drain=True)
        want = zone.answers_kind("dns", queries, False, SEED).tolist()
        assert answered(replies, queries, owner) == want
        assert _held() - held0 == 10
        assert _flushes("cap") - cap0 == caps
        assert _flushes("empty") - empty0 == empties
    finally:
        srv.stop()
        elg.close()


@pytest.mark.parametrize("bad", [0, 2, 4])
def test_a_raising_datagram_strands_no_held_lookup(zone, uncached,
                                                   monkeypatch, capfd, bad):
    """Of 5 datagrams of one drain, the one at `bad` raises in its
    handling: the lookups held before it are handed over as the drain
    unwinds, the ones after it are read by the next drain; all 4 are
    answered as the reference answers them."""
    queries = zone.pool_kind("dns", 5, TRAFFIC, SEED + 5)
    owner = R.address_owner(zone.plain["zone"])
    elg, srv = serve(zone)
    try:
        warm(srv, zone)
        handle, flush, flushed = srv._handle, srv._flush, []

        def planted(req, ip, port):
            if req.id == bad:
                raise RuntimeError("planted")
            handle(req, ip, port)

        def spy(why):
            if srv._hold:
                flushed.append(len(srv._hold))
            flush(why)
        monkeypatch.setattr(srv, "_handle", planted)
        monkeypatch.setattr(srv, "_flush", spy)
        held0 = _held()
        replies = exchange(srv, queries_to_packets(queries), one_drain=True,
                           expect=4)
        assert "planted" in capfd.readouterr().err
        assert flushed == [k for k in (bad, 4 - bad) if k]
        assert _held() - held0 == 4
        want = zone.answers_kind("dns", queries, False, SEED).tolist()
        assert readings(replies, queries, owner) == \
            {i: want[i] for i in range(5) if i != bad}
    finally:
        srv.stop()
        elg.close()


def test_a_second_question_is_submitted_at_once(zone, uncached,
                                                monkeypatch):
    """A two-question query read in a drain with two one-question ones:
    its first question is held with theirs; its second starts when the
    first is answered, on the loop and outside any drain, and goes to
    the service at once — three lookups held, four submitted."""
    exact = [r[0] for r, w in zip(zone.plain["zone"], zone.plain["wildcard"])
             if not w]
    n1, n2 = exact[0], exact[1]
    singles = [q for q in zone.pool_kind("dns", 4, TRAFFIC, SEED + 6)
               if q[0] not in (n1, n2)][:2]
    owner = R.address_owner(zone.plain["zone"])
    elg, srv = serve(zone)
    try:
        warm(srv, zone)
        seen, in_drain = [], [False]
        drain, search = srv._drain, zone.upstream.search_for_group_async

        def drain_spy(fd):
            in_drain[0] = True
            try:
                return drain(fd)
            finally:
                in_drain[0] = False

        def search_spy(hint, cb, loop=None):
            seen.append((hint.host, in_drain[0]))
            search(hint, cb, loop)
        monkeypatch.setattr(srv, "_drain", drain_spy)
        monkeypatch.setattr(zone.upstream, "search_for_group_async",
                            search_spy)
        held0 = _held()
        two = P.Packet(id=1, rd=True, questions=[P.Question(n1, P.A),
                                                 P.Question(n2, P.A)])
        packets = [i.to_bytes(2, "big") + wire.query_body(*q)
                   for i, q in zip((0, 2), singles)]
        packets.insert(1, two.encode())
        replies = exchange(srv, packets, one_drain=True)
        assert _held() - held0 == 3
        assert seen == [(singles[0][0], True), (n1, True),
                        (singles[1][0], True), (n2, False)]
        both = P.parse(next(d for d in replies if d[:2] == b"\0\1"))
        want = zone.answers_kind("dns", [(n1, R.A), (n2, R.A)], False,
                                 SEED).tolist()
        assert both.rcode == 0 and [
            owner[(4, bytes(a.rdata).ljust(16, b"\0"))]
            for a in both.answers] == want
        rest = [d for d in replies if d[:2] != b"\0\1"]
        got = {int.from_bytes(d[:2], "big"): d for d in rest}
        want = zone.answers_kind("dns", singles, False, SEED).tolist()
        assert [read_reply(got[i], *q, owner)
                for i, q in zip((0, 2), singles)] == want
    finally:
        srv.stop()
        elg.close()
