"""A node that runs more than one resource: four matchers behind ONE
ClassifyService (a DNS qname hint table; an LB Host table of another
cap inside a FusedPair; a route and an ACL CidrMatcher), one submitting
thread, all four query kinds interleaved. Every callback has to get its
own kind's answer from the device, and the dispatcher's multi-matcher
cycle has to show in the tracing: `engine/cycle` a wake, one
`engine/turn_wait` a uniform part, per-kind batch counters."""
import random
import threading
import time

import pytest

from vproxy_tpu.rules import maglev, oracle
from vproxy_tpu.rules import service as service_mod
from vproxy_tpu.rules.engine import CidrMatcher, HintMatcher
from vproxy_tpu.rules.ir import AclRule, Hint, HintRule, Proto
from vproxy_tpu.rules.service import ClassifyService
from vproxy_tpu.utils import trace
from vproxy_tpu.utils.ip import Network, mask_bytes

KINDS = ("hint", "route", "acl", "cpick")
SERVICE_KIND = {"hint": "hint", "route": "cidr", "acl": "cidr",
                "cpick": "cpick"}
N = 600
M = 251


def dns_rules(n=300, zone="com"):
    """The northstar forms: host, host + uri prefix, host + port."""
    out = []
    for i in range(n):
        host = f"svc{i}.ns{i % 7}.example.{zone}"
        out.append(HintRule(host=host, uri=f"/api/v{i % 5}") if i % 5 == 3
                   else HintRule(host=host, port=443) if i % 5 == 4
                   else HintRule(host=host))
    return out


def net(a: int, b: int, masklen: int) -> Network:
    mask = mask_bytes(masklen)
    return Network(bytes(x & m for x, m in zip(bytes([a, b, 0, 0]), mask)),
                   mask)


class Node:
    """The tables of one mesh gateway node, at a few hundred rules."""

    def __init__(self):
        self.dns = dns_rules()
        self.lb = [HintRule(host=f"svc{i}.ns{i % 7}.example.com")
                   for i in range(40)]
        self.backends = [(f"10.0.{i}.1:80", 1) for i in range(24)]
        self.routes = [net(10 + i % 3, i % 200, 16 + i % 9)
                       for i in range(200)]
        nets = [net(10 + i % 3, (i * 7) % 200, 12 + i % 12)
                for i in range(150)]
        self.acls = [AclRule(f"r{i}", n, Proto.TCP, (i * 37) % 5000,
                             (i * 37) % 5000 + 2000, i % 2 == 0)
                     for i, n in enumerate(nets)]
        self.hm = HintMatcher(self.dns, payload=("dns", 1))
        self.pair = maglev.FusedPair(
            HintMatcher(self.lb, payload=("lb", 1)),
            maglev.MaglevMatcher(self.backends, m=M, payload=("mm", 1)))
        self.route = CidrMatcher(self.routes, payload=("route", 1))
        self.acl = CidrMatcher(nets, acl=self.acls, payload=("acl", 1))
        self.table = maglev.build_table(self.backends, M)

    def stream(self, seed: int, n: int = N) -> list:
        """-> [(kind, query)], seeded, the kinds interleaved at random;
        one query in ten asks for what no table holds."""
        rs = random.Random(seed)
        out = []
        for _ in range(n):
            kind = rs.choice(KINDS)
            miss = rs.random() < 0.1
            i = rs.randrange(300)
            if kind == "hint":
                host = f"svc{i}.ns{i % 7}.example.{'net' if miss else 'com'}"
                q = Hint(host="x." + host, port=0, uri=f"/api/v{i % 5}/u") \
                    if i % 3 == 1 else Hint(host=host, port=443) \
                    if i % 3 == 2 else Hint.of_host(host)
            elif kind == "cpick":
                i %= 40
                host = f"svc{i}.ns{i % 7}.example.{'net' if miss else 'com'}"
                q = (Hint.of_host("www." + host),
                     bytes([172, 16, rs.randrange(256), rs.randrange(256)]),
                     rs.randrange(1024, 65536))
            else:
                addr = bytes([(100 if miss else 10) + rs.randrange(3),
                              rs.randrange(200), rs.randrange(256), 1])
                q = (addr, None) if kind == "route" \
                    else (addr, rs.randrange(8000))
            out.append((kind, q))
        return out

    def want(self, kind: str, q) -> tuple:
        """The host's answer: rules/oracle.py, the matchers' oracle_snap
        and the Maglev table's own slot."""
        if kind == "hint":
            return (oracle.search(self.dns, q),)
        if kind == "cpick":
            return (oracle.search(self.lb, q[0]),
                    maglev.pick(self.table, q[1], q[2]))
        m = self.route if kind == "route" else self.acl
        return (m.oracle_snap(m.snapshot(), q[0], q[1]),)

    def submit(self, svc, kind: str, q, cb) -> None:
        if kind == "hint":
            svc.submit_hint(self.hm, q, cb)
        elif kind == "cpick":
            svc.submit_classify_pick(self.pair, q[0], q[1], q[2], cb)
        else:
            svc.submit_cidr(self.route if kind == "route" else self.acl,
                            q[0], q[1], cb)


class Served:
    """One stream through a fresh device-mode service; what every
    callback got, the service's counters and, with `spans`, every span
    the run handed to trace.note_span."""

    def __init__(self, node: Node, seed: int, traced: bool, monkeypatch,
                 sampled_at: int = -1, during=None):
        self.stream = node.stream(seed)
        self.got: dict = {}          # i -> (answer tuple, payload)
        self.spans: list = []
        self.tid = 0
        done = threading.Event()

        def cb_for(i):
            def cb(*a):
                self.got[i] = (tuple(a[:-1]), a[-1])
                if len(self.got) == len(self.stream):
                    done.set()
            return cb

        note = trace.note_span

        def noting(tid, plane, span, t0, dur, cpu_ns=0, items=0, **fields):
            self.spans.append(dict(fields, span=f"{plane}/{span}", t0=t0,
                                   t1=t0 + dur, items=items))
            return note(tid, plane, span, t0, dur, cpu_ns, items, **fields)

        monkeypatch.setattr(trace, "note_span", noting)
        svc = ClassifyService(mode="device")
        trace.configure(1 if traced else 0)
        try:
            for i, (kind, q) in enumerate(self.stream):
                if i == sampled_at:
                    self.tid = trace.new_trace_id()
                    with trace.bind(self.tid):
                        node.submit(svc, kind, q, cb_for(i))
                else:
                    node.submit(svc, kind, q, cb_for(i))
                if during is not None:
                    during(i, self)
            assert done.wait(120), f"{len(self.got)} of {N} delivered"
        finally:
            svc.close()
            if svc._thread is not None:
                svc._thread.join(10)
            self.buffered = trace.get_trace(self.tid) if self.tid else []
            trace.configure(0)
            trace.reset()
            monkeypatch.setattr(trace, "note_span", note)
        self.stats = svc.stats

    def of(self, name: str) -> list:
        return [s for s in self.spans if s["span"] == name]


@pytest.fixture(scope="module")
def node():
    return Node()


@pytest.fixture(scope="module")
def traced(node):
    mp = pytest.MonkeyPatch()
    try:
        return Served(node, 2029, True, mp, sampled_at=100)
    finally:
        mp.undo()


@pytest.fixture(autouse=True)
def _trace_off():
    trace.configure(0)
    yield
    trace.configure(0)
    trace.reset()


def test_the_two_hint_tables_have_different_caps(node):
    """Two `hint_hash_match` program shapes live in one process."""
    a, b = node.hm.snapshot()[0], node.pair.hm.snapshot()[0]
    assert node.hm.size() == 300 and node.pair.size() == 40
    assert a.caps != b.caps


@pytest.mark.parametrize("kind", KINDS)
def test_every_callback_gets_its_own_kinds_answer(node, traced, kind):
    """Each query's verdict (and pick) equals the host oracle's for ITS
    table, whatever shared the wake with it, and carries that table's
    payload."""
    mine = [i for i, (k, _q) in enumerate(traced.stream) if k == kind]
    assert len(mine) > N // 8
    payload = {"hint": ("dns", 1), "route": ("route", 1), "acl": ("acl", 1),
               "cpick": (("lb", 1), ("mm", 1))}[kind]
    hits = 0
    for i in mine:
        answer, pl = traced.got[i]
        assert answer == node.want(kind, traced.stream[i][1]), \
            (i, traced.stream[i])
        assert pl == payload
        hits += answer[0] >= 0
    assert 0 < hits < len(mine)     # matches and misses, both served


def test_the_device_served_every_query(traced):
    st = traced.stats
    assert st.queries == st.device_queries == N
    assert st.oracle_queries == 0 and st.failovers == 0
    assert st.inline_fast == 0 and st.last_failover == ""


def test_batch_counters_by_kind_add_up(traced):
    st = traced.stats
    assert set(st.batches) == set(st.batch_queries) \
        == {"hint", "cidr", "cpick"}
    assert sum(st.batches.values()) == st.dispatches
    assert sum(st.batch_queries.values()) == st.device_queries == N
    for sk in st.batches:
        sent = sum(1 for k, _q in traced.stream if SERVICE_KIND[k] == sk)
        assert st.batch_queries[sk] == sent
        assert 1 <= st.batches[sk] <= sent
    # one dispatch span a device batch, named by the same kind
    for sk, n in st.batches.items():
        assert sum(1 for s in traced.of("engine/dispatch")
                   if s["kind"] == sk) == n


def test_cycle_counts_every_query_and_every_part(traced):
    cycles = traced.of("engine/cycle")
    assert cycles and all(c["batches"] >= 1 for c in cycles)
    assert sum(c["items"] for c in cycles) == N
    n_dispatch = len(traced.of("engine/dispatch"))
    assert n_dispatch == traced.stats.dispatches
    assert sum(c["batches"] for c in cycles) == n_dispatch
    assert len(traced.of("engine/turn_wait")) == n_dispatch
    # the stream keeps four matchers pending: some wake takes several
    assert max(c["batches"] for c in cycles) >= 2
    # route and ACL lookups never share a part
    assert {s["kind"] for s in traced.of("engine/turn_wait")} \
        == {"hint", "cidr", "cpick"}


def test_every_turn_wait_lies_inside_its_cycle(traced):
    """A part waits from the wake's swap to its own dispatch: the turn
    waits of a cycle start with it, end in the order the parts were
    begun, each where that part's dispatch span starts, and hold the
    cycle's queries between them."""
    cycles = traced.of("engine/cycle")
    waits = traced.of("engine/turn_wait")
    dispatches = traced.of("engine/dispatch")
    k = 0
    for c in cycles:
        mine = waits[k:k + c["batches"]]
        for w, d in zip(mine, dispatches[k:k + c["batches"]]):
            assert c["t0"] <= w["t0"] and w["t1"] <= c["t1"]
            assert w["t1"] <= d["t0"] and d["t1"] <= c["t1"]
            assert (w["kind"], w["batch"]) == (d["kind"], d["batch"])
            assert w["items"] == w["batch"]
        assert [w["t1"] for w in mine] == sorted(w["t1"] for w in mine)
        assert sum(w["items"] for w in mine) == c["items"]
        k += c["batches"]
    assert k == len(waits)
    # cycles do not overlap: one dispatcher
    assert all(a["t1"] <= b["t0"] for a, b in zip(cycles, cycles[1:]))


def test_turn_wait_is_part_of_the_sampled_requests_queue_wait(traced):
    """queue_wait = the wait for the swap + the wait for a turn: the
    sampled request's trace holds its part's turn_wait, no longer than
    its own queue_wait and ending where that ends."""
    by = {s["span"]: s for s in traced.buffered}
    assert {"queue_wait", "turn_wait", "dispatch", "deliver"} <= set(by)
    qw, tw = by["queue_wait"], by["turn_wait"]
    assert tw["kind"] == qw["kind"] and tw["batch"] == qw["batch"]
    assert tw["items"] == tw["batch"]
    assert qw["t_ns"] <= tw["t_ns"] and tw["dur_ns"] <= qw["dur_ns"]
    assert tw["t_ns"] + tw["dur_ns"] <= by["dispatch"]["t_ns"]
    assert not any(s["span"] == "cycle" for s in traced.buffered)


def test_span_totals_take_the_cycle(node, monkeypatch):
    before = trace.span_totals()
    run = Served(node, 7, True, monkeypatch)
    after = trace.span_totals()

    def moved(span, field):
        return after[span][field] - before.get(span, {}).get(field, 0)

    assert moved("engine/cycle", "sum_items") == N
    assert moved("engine/turn_wait", "sum_items") == N
    assert moved("engine/turn_wait", "n") == moved("engine/dispatch", "n") \
        == run.stats.dispatches
    assert 1 <= moved("engine/cycle", "n") <= run.stats.dispatches
    assert moved("engine/cycle", "sum_ns") > 0


def test_tracing_off_reaches_no_new_span_call(node, monkeypatch):
    """Knob off: the wake builds no _Cycle, calls no span() and no
    note_span(); the answers and the counters are the same."""
    calls = []
    monkeypatch.setattr(service_mod, "_Cycle",
                        lambda parts: calls.append("cycle"))
    span = trace.span
    monkeypatch.setattr(
        trace, "span",
        lambda *a, **kw: calls.append(a[:2]) or span(*a, **kw))
    before = trace.span_totals()
    run = Served(node, 11, False, monkeypatch)
    assert run.spans == [] and trace.span_totals() == before
    # the spans of PR 27 go through trace.span() and get the shared
    # no-op; neither of the new ones is among the calls
    assert "cycle" not in calls
    assert not {("engine", "cycle"), ("engine", "turn_wait")} & set(calls)
    assert run.stats.device_queries == N and run.stats.oracle_queries == 0
    assert sum(run.stats.batches.values()) == run.stats.dispatches
    for i, (kind, q) in enumerate(run.stream):
        assert run.got[i][0] == node.want(kind, q)


@pytest.mark.parametrize("kind", ["hint", "route"])
def test_payload_is_of_the_generation_that_answered(kind, monkeypatch):
    """A generation installed in the middle of the mixed stream: every
    verdict is one generation's exact answer and carries THAT
    generation's payload, also when other matchers share the wake."""
    node = Node()
    if kind == "hint":
        m, old = node.hm, node.dns
        v2 = dns_rules(300, zone="org") + old[:100]

        def install():
            m.set_rules(v2, payload=("dns", 2))
    else:
        m = node.route

        def install():
            m.set_networks(node.routes[50:], payload=("route", 2))
    snaps = {}

    def during(i, run):
        if i == 0:
            snaps[1] = m.snapshot()
        if i == N // 2:
            # generation 1 has answered some query of this kind
            mine = [j for j in range(i) if run.stream[j][0] == kind]
            deadline = time.monotonic() + 60
            while not any(j in run.got for j in mine):
                assert time.monotonic() < deadline
                time.sleep(0.001)
            install()
            snaps[2] = m.snapshot()

    run = Served(node, 31, False, monkeypatch, during=during)
    gens = set()
    for i, (k, q) in enumerate(run.stream):
        answer, pl = run.got[i]
        if k != kind:
            assert answer == node.want(k, q)
            continue
        gen = pl[1]
        gens.add(gen)
        assert pl[0] == ("dns" if kind == "hint" else "route")
        if kind == "hint":
            assert answer == (oracle.search(old if gen == 1 else v2, q),)
        else:
            assert answer == (m.oracle_snap(snaps[gen], q[0], None),)
        if i > N // 2:
            assert gen == 2     # set_* returned: read-your-writes
    assert gens == {1, 2}
    assert run.stats.device_queries == N and run.stats.failovers == 0


def test_new_spans_are_in_the_vocabulary_and_on_metrics():
    from vproxy_tpu.utils.metrics import CLASSIFY_KINDS, GlobalInspection
    assert ("engine", "cycle") in trace.SPANS
    assert ("engine", "turn_wait") in trace.SPANS
    text = GlobalInspection.get().registry.prometheus_text()
    for span in ("cycle", "turn_wait"):
        assert f'vproxy_trace_span_us_count{{plane="engine",span="{span}"}}' \
            in text
    assert CLASSIFY_KINDS == ("hint", "cidr", "cpick")
    for fam in ("vproxy_classify_batches_total",
                "vproxy_classify_batch_queries_total"):
        for k in CLASSIFY_KINDS:
            assert f'{fam}{{kind="{k}"}} ' in text


def test_batch_counters_on_metrics_follow_the_service(node, monkeypatch):
    from vproxy_tpu.utils.metrics import GlobalInspection
    ClassifyService.reset()
    svc = ClassifyService.get()
    svc.mode = "device"
    try:
        got, done = [], threading.Event()

        def cb(*a):
            got.append(a)
            if len(got) == 30:
                done.set()

        for i in range(30):
            node.submit(svc, *node.stream(5, 30)[i], cb)
        assert done.wait(60)
        text = GlobalInspection.get().registry.prometheus_text()
        for k, n in svc.stats.batches.items():
            assert f'vproxy_classify_batches_total{{kind="{k}"}} {n}\n' \
                in text
            assert (f'vproxy_classify_batch_queries_total{{kind="{k}"}} '
                    f'{svc.stats.batch_queries[k]}\n') in text
        assert sum(svc.stats.batch_queries.values()) == 30
    finally:
        ClassifyService.reset()
