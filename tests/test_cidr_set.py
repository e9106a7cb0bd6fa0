"""CidrTableSet — many ordered CIDR tables behind one program.

The set against the benchmark's plain multi-VPC reference
(benchmark/reference_vpc.py: RouteTable.lookup a VNI), against N
separate CidrMatchers verdict for verdict, and through ClassifyService:
a burst that names every VPC is ONE device batch, every verdict the
named VPC's own, and a one-VPC change rebuilds one VPC's tables.
"""
import os
import sys
import threading

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

import reference as ref  # noqa: E402  (plain data, nothing of the program)
import reference_vpc  # noqa: E402

from vproxy_tpu.ops import hashmatch as H  # noqa: E402
from vproxy_tpu.rules import engine  # noqa: E402
from vproxy_tpu.rules.engine import (CidrMatcher, CidrTableSet,  # noqa: E402
                                     TableInstaller)
from vproxy_tpu.rules.ir import AclRule, Proto  # noqa: E402
from vproxy_tpu.rules.service import ClassifyService  # noqa: E402
from vproxy_tpu.utils.ip import Network, mask_bytes  # noqa: E402

VPCS = 8


def plain_tables(seed: int, sizes=None) -> list:
    """VPC v's routes (value_u32, masklen), overlapping across VPCs:
    /8../28 inside 10/8..13/8, first-containing order left to chance
    (the set and the reference both answer by list order)."""
    rs = np.random.default_rng(seed)
    sizes = sizes if sizes is not None \
        else [0] + [int(n) for n in rs.integers(1, 301, VPCS - 1)]
    out = []
    for n in sizes:
        nets = set()
        while len(nets) < n:
            m = int(rs.integers(8, 29))
            v = ((10 + int(rs.integers(0, 4))) << 24) \
                | int(rs.integers(0, 1 << 24))
            nets.add(((v >> (32 - m)) << (32 - m), m))
        out.append(sorted(nets, key=lambda e: (-e[1], e[0])))
    return out


def network(e) -> Network:
    return Network(int(e[0]).to_bytes(4, "big"), mask_bytes(e[1]))


def v6_network(e, vpc: int) -> Network:
    """The same shapes under fd00:<vpc % 2>::/32, prefix 32 + m."""
    ip = bytes([0xfd, 0, 0, vpc % 2]) + int(e[0]).to_bytes(4, "big") \
        + b"\x00" * 8
    return Network(ip, mask_bytes(32 + e[1]))


def lookups(tables: list, n: int, seed: int) -> list:
    """[(vpc, addr4)]: inside a route of the asked VPC, of another VPC,
    or nowhere."""
    rs = np.random.default_rng(seed + 1000)
    flat = [(v, e) for v, t in enumerate(tables) for e in t]
    out = []
    for j in range(n):
        v, e = flat[int(rs.integers(0, len(flat)))]
        a = e[0] | (int(rs.integers(0, 1 << 32)) & ((1 << (32 - e[1])) - 1))
        if j % 3 == 1:
            v = int(rs.integers(0, len(tables)))    # another tenant asks
        elif j % 10 == 9:
            a = (100 << 24) | (a & 0xFFFFFF)        # nobody routes it
        out.append((v, a.to_bytes(4, "big")))
    return out


def make_set(tables: list, backend: str = "jax"):
    ts = CidrTableSet("v4", backend=backend)
    views = [ts.view() for _ in tables]
    for view, t in zip(views, tables):
        view.set_networks([network(e) for e in t])
    return ts, views


@pytest.mark.parametrize("backend", ["jax", "host"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_set_against_the_plain_vpc_reference(seed, backend):
    tables = plain_tables(seed)
    ts, views = make_set(tables, backend)
    qs = lookups(tables, 400, seed)
    want = reference_vpc.vpc_first_match(tables, qs)
    got = ts.match([views[v] for v, _a in qs], [a for _v, a in qs])
    assert got.tolist() == want.tolist()
    assert (want >= 0).sum() > 100 and (want < 0).sum() > 40
    assert ts.size() == sum(map(len, tables))
    assert [v.size() for v in views] == [len(t) for t in tables]
    # the empty VPC answers -1 whatever is asked of it
    assert views[0].size() == 0
    assert ts.match([views[0]] * 5, [a for _v, a in qs[:5]]).tolist() \
        == [-1] * 5


def test_same_prefix_in_two_vpcs_answers_by_the_named_vpc():
    ts = CidrTableSet("v4", backend="jax")
    a, b = ts.view(), ts.view()
    a.set_networks([Network.parse("10.1.0.0/16"), Network.parse("10.0.0.0/8")])
    b.set_networks([Network.parse("10.0.0.0/8"), Network.parse("10.1.0.0/16"),
                    Network.parse("11.0.0.0/8")])
    q = [bytes([10, 1, 2, 3]), bytes([10, 9, 9, 9]), bytes([11, 0, 0, 1])]
    assert ts.match([a] * 3, q).tolist() == [0, 1, -1]
    assert ts.match([b] * 3, q).tolist() == [0, 0, 2]
    assert ts.match([a, b, a], q).tolist() == [0, 0, -1]


@pytest.mark.parametrize("seed", [4, 5])
def test_removed_and_unknown_tables_answer_minus_one(seed):
    tables = plain_tables(seed)
    ts, views = make_set(tables)
    other = CidrTableSet("v4", backend="jax").view()    # another set's view
    qs = lookups(tables, 200, seed)
    want = reference_vpc.vpc_first_match(tables, qs)
    views[3].release()
    gone = [None if v == 3 else t for v, t in enumerate(tables)]
    got = ts.match([views[v] for v, _a in qs], [a for _v, a in qs])
    for (v, _a), g, w in zip(qs, got.tolist(), want.tolist()):
        assert g == (-1 if v == 3 else w)
    assert ts.match([other] * 4, [a for _v, a in qs[:4]]).tolist() == [-1] * 4
    assert ts.size() == sum(len(t) for t in gone if t)
    # its table id goes to the next VPC; the released view stays empty
    fresh = ts.view()
    fresh.set_networks([Network.parse("10.0.0.0/8")])
    a = bytes([10, 5, 5, 5])
    assert ts.match([fresh, views[3]], [a, a]).tolist() == [0, -1]


@pytest.mark.parametrize("seed", [6, 7])
def test_v6_and_mixed_families_against_separate_matchers(seed):
    """v4 and v6 tables in one set, v4 / v6 / v4-mapped lookups: verdict
    for verdict what N separate CidrMatchers give."""
    tables = plain_tables(seed, sizes=[40, 0, 150, 90, 7, 300])
    nets = [[network(e) for e in t[::2]] + [v6_network(e, v) for e in t[1::2]]
            for v, t in enumerate(tables)]
    ts = CidrTableSet("any", backend="jax")
    views = [ts.view() for _ in nets]
    for view, n in zip(views, nets):
        view.set_networks(n)
    sep = [CidrMatcher(n, backend="jax") for n in nets]
    rs = np.random.default_rng(seed)
    qs = []
    for v, a in lookups(tables, 300, seed):
        form = int(rs.integers(0, 3))
        if form == 1:       # inside (or beside) the VPC's v6 shapes
            a = bytes([0xfd, 0, 0, v % 2]) + a + bytes(rs.integers(0, 256, 8)
                                                       .astype(np.uint8))
        elif form == 2:     # v4-mapped
            a = b"\x00" * 10 + b"\xff\xff" + a
        qs.append((v, a))
    got = ts.match([views[v] for v, _a in qs], [a for _v, a in qs])
    want = [int(sep[v].match([a])[0]) if nets[v] else -1 for v, a in qs]
    assert got.tolist() == want
    assert sum(w >= 0 for w in want) > 60
    host = [ts.index_snap(ts.snapshot(), a, None, views[v].key) for v, a in qs]
    assert host == want


@pytest.mark.parametrize("seed", [8, 9])
def test_acl_views_with_port_ranges(seed):
    """ACL tables in a set: the port gate, several ranges a network
    (wider buckets, and past 16 a second hop), port=None ungated; a
    route table beside them ignores the port."""
    tables = plain_tables(seed, sizes=[60, 120, 5, 30])
    rs = np.random.default_rng(seed)
    acl_tabs = []
    for v, t in enumerate(tables[:3]):
        # VPC 1 repeats one network 20 times: a bucket of two rows
        t = t + ([t[0]] * 20 if v == 1 else [])
        acl_tabs.append([(e[0], e[1], lo, lo + int(rs.integers(0, 3000)))
                         for e in t
                         for lo in [int(rs.integers(0, 60000))]])
    ts = CidrTableSet("v4", backend="jax")
    views = [ts.view() for _ in tables]
    for view, t in zip(views, acl_tabs):
        nets = [network(e) for e in t]
        view.set_networks(nets, acl=[
            AclRule(f"r{i}", nets[i], Proto.TCP, e[2], e[3], True)
            for i, e in enumerate(t)])
    views[3].set_networks([network(e) for e in tables[3]])
    assert ts.bucket_stat()["hops"] == 2 and ts.snapshot().gated
    qs = []
    for v, a in lookups(tables, 300, seed):
        t = acl_tabs[v] if v < 3 else None
        e = t[int(rs.integers(0, len(t)))] if t else (0, 0, 0, 65535)
        qs.append((v, a, int(rs.integers(e[2], e[3] + 1))))
    got = ts.match([views[v] for v, _a, _p in qs], [a for _v, a, _p in qs],
                   [p for _v, _a, p in qs])
    nop = ts.match([views[v] for v, _a, _p in qs], [a for _v, a, _p in qs])
    for (v, a, p), g, g0 in zip(qs, got.tolist(), nop.tolist()):
        plain = acl_tabs[v] if v < 3 else tables[3]
        assert g == int(ref.cidr_first_match(plain, [(a, p)], v < 3)[0])
        assert g0 == int(ref.cidr_first_match(plain, [(a,)], False)[0])
    assert sum(g != g0 for g, g0 in zip(got, nop)) > 10


def test_one_vpc_change_builds_one_vpcs_tables_and_traces_nothing():
    tables = plain_tables(10)
    ts, views = make_set(tables)
    qs = lookups(tables, 300, 10)
    asked = [views[v] for v, _a in qs]
    addrs = [a for _v, a in qs]
    before = ts.match(asked, addrs).tolist()
    snaps = [v.snapshot() for v in views]
    sums = [v.checksum() for v in views]
    builds, traced = engine.cidr_set_table_builds_total(), \
        H.cidr_set_jit._cache_size()
    gen = ts.generation
    # VPC 5 swaps half of its routes for others, same size
    changed = list(tables)
    changed[5] = sorted(set(tables[5][::2]) | set(plain_tables(
        11, sizes=[len(tables[5])])[0][: len(tables[5]) // 2]),
        key=lambda e: (-e[1], e[0]))
    views[5].set_networks([network(e) for e in changed[5]])
    assert engine.cidr_set_table_builds_total() == builds + 1
    assert ts.generation == gen + 1
    after = ts.match(asked, addrs).tolist()
    assert after == reference_vpc.vpc_first_match(changed, qs).tolist()
    assert all(a == b for (v, _a), a, b in zip(qs, after, before) if v != 5)
    assert any(a != b for (v, _a), a, b in zip(qs, after, before) if v == 5)
    assert H.cidr_set_jit._cache_size() == traced   # same shapes
    # the other VPCs' tables are the objects they were; VPC 5's is new
    for v, (view, snap, cs) in enumerate(zip(views, snaps, sums)):
        assert (view.snapshot() is snap) == (v != 5)
        assert (view.checksum() == cs) == (v != 5)


def test_a_swap_serves_no_wrong_verdict_on_either_side():
    """Lookups race a VPC's installs: every answer is one generation's —
    the changing VPC's old or new table, the others' own, always."""
    tables = plain_tables(12, sizes=[150, 200, 80])
    ts, views = make_set(tables)
    alt = list(tables)
    alt[1] = plain_tables(13, sizes=[200])[0]
    qs = lookups(tables, 120, 12) + lookups(alt, 120, 13)
    asked = [views[v] for v, _a in qs]
    addrs = [a for _v, a in qs]
    old = reference_vpc.vpc_first_match(tables, qs).tolist()
    new = reference_vpc.vpc_first_match(alt, qs).tolist()
    assert old != new
    bad, rounds, stop = [], [0], threading.Event()

    def reader():
        while not stop.is_set():
            got = ts.match(asked, addrs).tolist()
            if got != old and got != new:
                bad.append(got)
            rounds[0] += 1

    t = threading.Thread(target=reader)
    t.start()
    try:
        for k in range(6):
            views[1].set_networks([network(e)
                                   for e in (alt, tables)[k % 2][1]])
    finally:
        stop.set()
        t.join(30)
    assert not t.is_alive() and rounds[0] > 0 and not bad
    assert ts.match(asked, addrs).tolist() == old


def service_burst(svc, ts, views, qs, ports=None):
    """Submit every lookup while the dispatcher is held inside a
    callback, so the burst is one wake; -> (verdicts, dispatches)."""
    hold, entered, done = threading.Event(), threading.Event(), \
        threading.Event()
    got = [None] * len(qs)
    left = [len(qs)]

    def blocker(_idx, _payload):
        entered.set()
        hold.wait(10)

    def cb(i):
        def on(idx, _payload):
            got[i] = idx
            left[0] -= 1
            if not left[0]:
                done.set()
        return on

    svc.submit_cidr(views[qs[0][0]], qs[0][1], None, blocker)
    assert entered.wait(30)
    d0 = svc.stats.dispatches
    for i, (v, a) in enumerate(qs):
        svc.submit_cidr(views[v], a, None if ports is None else ports[i],
                        cb(i))
    hold.set()
    assert done.wait(30)
    return got, svc.stats.dispatches - d0


def test_a_burst_naming_every_vpc_is_one_dispatch_through_the_service():
    tables = plain_tables(14, sizes=[30, 200, 90, 5, 300, 60, 120, 250])
    ts, views = make_set(tables)
    qs = lookups(tables, 256, 14)
    assert {v for v, _a in qs} == set(range(VPCS))
    svc = ClassifyService(mode="device")
    try:
        got, dispatches = service_burst(svc, ts, views, qs)
        assert got == reference_vpc.vpc_first_match(tables, qs).tolist()
        assert dispatches == 1
        assert svc.stats.oracle_queries == 0 and svc.stats.failovers == 0
        # the same burst on N separate matchers: a batch a matcher
        sep = [CidrMatcher([network(e) for e in t], backend="jax")
               for t in tables]
        got, dispatches = service_burst(svc, None, sep, qs)
        assert got == reference_vpc.vpc_first_match(tables, qs).tolist()
        assert dispatches == VPCS
    finally:
        svc.close()


def test_service_failover_answers_from_the_named_vpcs_host_table():
    from vproxy_tpu.utils import failpoint
    tables = plain_tables(15, sizes=[150, 200, 0, 40])
    ts, views = make_set(tables)
    qs = lookups(tables, 64, 15)
    svc = ClassifyService(mode="device")
    try:
        failpoint.arm("device.dispatch.error", count=1)
        got, _d = service_burst(svc, ts, views, qs)
        assert got == reference_vpc.vpc_first_match(tables, qs).tolist()
        assert svc.stats.failovers == 1 and svc.stats.oracle_queries > 0
    finally:
        failpoint.clear()
        svc.close()
    # auto mode: a lone lookup is answered inline, from the view's table
    auto = ClassifyService(mode="auto")
    try:
        out = []
        v, a = next((v, a) for v, a in qs if len(tables[v]) > 128)
        auto.submit_cidr(views[v], a, None, lambda i, _p: out.append(i))
        assert out == reference_vpc.vpc_first_match(tables, [(v, a)]).tolist()
    finally:
        auto.close()


def test_table_set_span_counts_the_tables_a_batch_names():
    from vproxy_tpu.utils import trace
    tables = plain_tables(16, sizes=[50, 60, 70, 80])
    ts, views = make_set(tables)
    qs = [(v, a) for v, a in lookups(tables, 90, 16) if v != 2]
    prev = trace.sample_every()
    trace.configure(1)
    try:
        n0 = trace.span_totals().get("engine/table_set",
                                     {"n": 0, "sum_items": 0})
        ts.match([views[v] for v, _a in qs], [a for _v, a in qs])
        tot = trace.span_totals()["engine/table_set"]
    finally:
        trace.configure(prev)
    assert tot["n"] == n0["n"] + 1
    assert tot["sum_items"] == n0["sum_items"] + 3
    assert ("engine", "table_set") in trace.SPANS


def test_plain_matcher_program_has_no_table_id():
    """One table: the jitted call takes (tables, the batch's arena) and
    an arena with no table-id column, and its lowered text gathers no
    group row by table id; the set's program is that text plus those
    gathers."""
    nets = [network(e) for e in plain_tables(17, sizes=[120])[0]]
    tab = H.compile_cidr_hash(nets)
    q = H.cidr_queries(32)
    assert set(q) == {"a16", "fam"}
    plain = H.cidr_hash_jit.lower(tab.arrays, q.arena, q.layout)
    assert len(plain.args_info[0]) == 2     # the layout is static
    arrays, _caps, _b = H.stack_cidr_tables([tab, None, tab])
    qs = H.cidr_queries(32, tid=True)
    assert set(qs) == {"a16", "fam", "tid"}
    stacked = H.cidr_set_jit.lower(arrays, qs.arena, qs.layout)
    n_plain = plain.as_text().count("stablehlo.gather")
    n_set = stacked.as_text().count("stablehlo.gather")
    assert 0 < n_plain < n_set
    # the matcher's own dispatch goes through that call: tables, arena,
    # layout
    cm = CidrMatcher(nets, backend="jax")
    seen = []
    orig = H.cidr_hash_jit
    try:
        H.cidr_hash_jit = lambda *a, **kw: seen.append((len(a), kw)) \
            or orig(*a, **kw)
        cm.match([bytes([10, 0, 0, 1])] * 200)
    finally:
        H.cidr_hash_jit = orig
    assert seen == [(3, {})]


def test_view_quacks_like_a_matcher_for_a_vpc_network():
    ts = CidrTableSet("v4", backend="jax")
    view = ts.view()
    assert view.backend == "jax" and view.table_set is ts
    assert view.size() == 0 and view.snapshot() is None
    assert view.match_one(bytes([10, 0, 0, 1])) == -1
    empty = view.checksum()
    nets = [Network.parse("10.1.0.0/16"), Network.parse("10.0.0.0/8")]
    view.set_networks(nets)
    assert view.size() == 2 and view.checksum() != empty
    assert view.checksum() == CidrMatcher(nets, backend="host").checksum()
    assert view.match_one(bytes([10, 1, 0, 1])) == 0      # host scan: small
    assert view.oracle_one(bytes([10, 2, 0, 1])) == 1
    assert view.match([bytes([10, 1, 0, 1]), bytes([11, 0, 0, 1])]).tolist() \
        == [0, -1]
    # past SMALL_TABLE entries IN THE SET a lone lookup rides the device
    big = ts.view()
    big.set_networks([network(e) for e in plain_tables(18, sizes=[200])[0]])
    launches = engine.dispatch_launches_total()
    assert view.match_one(bytes([10, 1, 0, 1])) == 0
    assert engine.dispatch_launches_total() == launches + 1
    view.set_networks([])
    assert view.size() == 0 and view.checksum() == empty
    with pytest.raises(ValueError):
        CidrTableSet("v4", backend="jax-fp")


def test_set_metrics_surface():
    from vproxy_tpu.utils.metrics import GlobalInspection
    ts = CidrTableSet("v6", backend="jax")
    views = [ts.view() for _ in range(3)]
    for v in views[:2]:
        v.set_networks([Network.parse("fd00::/16")])
    assert engine.cidr_set_tables()["v6"] >= 2
    text = GlobalInspection.get().prometheus_string()
    line = next(ln for ln in text.splitlines() if ln.startswith(
        'vproxy_engine_cidr_set_tables{family="v6"}'))
    assert float(line.split()[-1]) >= 2
    assert "vproxy_engine_cidr_set_table_builds_total" in text
    assert ts.published_table_bytes() > 0
    assert engine.table_bytes_total("cidr") >= ts.published_table_bytes()
    assert TableInstaller.get().flush(10)
