"""MaglevTableSet / GroupedPair — one pick table a server-group behind
one program: classify, then the pick from the table of the group the
matched rule names.

The pair against the benchmark's plain reference (benchmark/
reference_groups.py: Upstream.searchForGroup, then that ServerGroup's
`next`, method `source`) through ClassifyService and through the host
lane; against ServerGroup._source_next member for member; one launch a
batch; a one-group health edge rebuilds one row; a row answers only
under the token it was handed out with; and Upstream.next_async /
seek_async reach the pair for `source` groups and return what
group.next returns.
"""
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

import reference_groups  # noqa: E402  (plain data, nothing of the program)

from vproxy_tpu.components.elgroup import EventLoopGroup  # noqa: E402
from vproxy_tpu.components.servergroup import (  # noqa: E402
    HealthCheckConfig, ServerGroup)
from vproxy_tpu.components.upstream import Upstream  # noqa: E402
from vproxy_tpu.ops import fused as F  # noqa: E402
from vproxy_tpu.rules import engine as E  # noqa: E402
from vproxy_tpu.rules import maglev as MG  # noqa: E402
from vproxy_tpu.rules.ir import Hint, HintRule  # noqa: E402
from vproxy_tpu.rules.service import ClassifyService  # noqa: E402

M = 251
M_GROUP = MG.GROUP_M    # a ServerGroup's own table size
GROUPS = 12
SCENARIOS = ("all_source", "some_without_table", "quarter_down")


@pytest.fixture(autouse=True)
def _fresh_service():
    ClassifyService.reset()
    yield
    ClassifyService.reset()


# ------------------------------------------------ seeded plain deployment

def plain(seed: int, scenario: str, rules_n: int = 300):
    """-> (rules, rule_group, healthy): hint rules as (host, port, uri),
    the group each names (-1: none) and each group's healthy members'
    identities in the group's own order."""
    rs = np.random.default_rng(seed)
    rules = [(f"svc{i}.ns{i % 7}.s{seed}.example.com", 0, None)
             for i in range(rules_n)]
    rule_group = [i % GROUPS for i in range(rules_n)]
    healthy = []
    for g in range(GROUPS):
        names = [f"g{g}|10.{seed}.{g}.{b + 1}:80"
                 for b in range(int(rs.integers(1, 9)))]
        if scenario == "quarter_down":
            names = [s for k, s in enumerate(names) if (k + g) % 4]
        healthy.append(names)
    if scenario == "some_without_table":
        for g in range(1, GROUPS, 4):
            healthy[g] = []                 # no healthy member: no table
        rule_group = [-1 if i % 10 == 3 else g     # a rule with no group
                      for i, g in enumerate(rule_group)]
    return rules, rule_group, healthy


def queries(seed: int, rules: list, n: int = 400) -> list:
    """(host, 0, None, client address): 3 in 4 a name under the rule's
    domain, 1 in 10 a host no rule holds."""
    rs = np.random.default_rng(seed + 1000)
    out = []
    for j in range(n):
        host = rules[int(rs.integers(0, len(rules)))][0]
        if j % 10 == 9:
            host = host.replace("example.com", "nomatch.invalid")
        if j % 4:
            host = "www." + host
        out.append((host, 0, None, bytes(rs.integers(0, 256, 4).tolist())))
    return out


def set_entries(ts, ref, entries, payload=None, wait=True):
    """One row from (identity, weight) entries; none = no table."""
    entries = list(entries)
    ts.install(ref, lambda: (MG.build_table(entries, ts.m),
                             [n for n, _w in entries], payload)
               if entries else None, wait=wait)


def install(rules, rule_group, healthy, backend="jax", skip=()):
    """-> (pair, refs): the deployment through the pair; groups in
    `skip` own a row that is never installed."""
    ts = MG.MaglevTableSet(m=M, backend=backend)
    pair = MG.GroupedPair(E.HintMatcher(backend=backend), ts)
    refs = [ts.alloc() for _ in healthy]
    for g, (ref, names) in enumerate(zip(refs, healthy)):
        if g not in skip:
            set_entries(ts, ref, [(s, 10) for s in names], payload=g)
    pair.set_rules([HintRule(host=h, port=p, uri=u) for h, p, u in rules],
                   payload="handles",
                   groups=[refs[g] if g >= 0 else -1 for g in rule_group])
    return pair, refs


def payloads(qs: list) -> list:
    return [(Hint(host=q[0], port=q[1], uri=q[2]), q[3], None) for q in qs]


def through_service(svc, pair, qs: list) -> np.ndarray:
    got = np.full((len(qs), 2), -7, np.int32)
    left = [len(qs)]
    done = threading.Event()

    def cb(k):
        def f(verdict, pick, payload):
            assert payload is None or payload[0] == "handles"
            got[k] = (verdict, pick)
            left[0] -= 1
            if not left[0]:
                done.set()
        return f
    for k, (hint, ip, port) in enumerate(payloads(qs)):
        svc.submit_classify_pick(pair, hint, ip, port, cb(k))
    assert done.wait(30)
    return got


# -------------------------------------------- the pair and the reference

@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_pair_equals_reference_through_the_service(seed, scenario):
    rules, rule_group, healthy = plain(seed, scenario)
    pair, _refs = install(rules, rule_group, healthy)
    qs = queries(seed, rules)
    want = reference_groups.classify_pick(rules, rule_group, healthy, M, qs)
    svc = ClassifyService(mode="device")
    try:
        l0, f0 = E.dispatch_launches_total(), E.fused_dispatches_total()
        got = through_service(svc, pair, qs)
        st = svc.stats
        assert (got == want).all()
        assert st.device_queries == len(qs) and st.oracle_queries == 0
        # one launch a batch, and every one the grouped program
        assert E.dispatch_launches_total() - l0 == st.dispatches
        assert E.fused_dispatches_total() - f0 == st.dispatches
        assert st.batches["cpick"] == st.dispatches
        resolved = int(((want[:, 0] >= 0) & (want[:, 1] >= 0)).sum())
        assert st.group_picks == {"device": resolved, "host": 0}
    finally:
        svc.close()
    hit = want[:, 0] >= 0
    assert 0.8 < hit.mean() < 0.95 and (want[~hit, 1] == -1).all()
    if scenario == "some_without_table":
        assert (want[hit, 1] == -1).any()     # groups without a table


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_pair_equals_reference_on_the_host_lane(seed, scenario):
    rules, rule_group, healthy = plain(seed, scenario)
    pair, _refs = install(rules, rule_group, healthy)
    qs = queries(seed, rules)
    want = reference_groups.classify_pick(rules, rule_group, healthy, M, qs)
    snap = pair.snapshot()
    got = np.array([pair.index_snap(snap, p) for p in payloads(qs)])
    assert (got == want).all()
    # the same from the service with the device out of the way
    svc = ClassifyService(mode="host")
    try:
        assert (through_service(svc, pair, qs) == want).all()
        assert svc.stats.oracle_queries == len(qs)
        assert svc.stats.group_picks["device"] == 0
        assert svc.stats.group_picks["host"] == int(
            ((want[:, 0] >= 0) & (want[:, 1] >= 0)).sum())
    finally:
        svc.close()


@pytest.mark.parametrize("backend", ["jax-fp", "host"])
def test_other_backends_classify_there_and_pick_on_the_host(backend):
    rules, rule_group, healthy = plain(5, "quarter_down")
    pair, _refs = install(rules, rule_group, healthy, backend=backend)
    assert pair.mm.snapshot().dev is None       # no device table there
    qs = queries(5, rules, 200)
    want = reference_groups.classify_pick(rules, rule_group, healthy, M, qs)
    snap = pair.snapshot()
    l0 = E.fused_dispatches_total()
    out = pair.dispatch_snap(snap, payloads(qs), pad_to=256)
    assert (np.asarray(out)[:len(qs)] == want).all()
    assert E.fused_dispatches_total() == l0
    svc = ClassifyService(mode="device")
    try:
        assert (through_service(svc, pair, qs) == want).all()
        assert svc.stats.group_picks["device"] == 0
        assert svc.stats.group_picks["host"] > 0
    finally:
        svc.close()


def test_error_fill_answers_both_minus_one():
    rules, rule_group, healthy = plain(4, "all_source", 40)
    pair, _refs = install(rules, rule_group, healthy)
    svc = ClassifyService(mode="device")
    try:
        def boom(*a, **kw):
            raise ValueError("planted")
        svc._begin_uniform = boom
        got = through_service(svc, pair, queries(4, rules, 8))
        assert (got == -1).all()
    finally:
        svc.close()


def test_next_async_survives_an_error_fill(lb, monkeypatch):
    ups, _groups = lb
    monkeypatch.setenv("VPROXY_TPU_CLASSIFY", "device")
    ClassifyService.reset()

    def boom(*a, **kw):
        raise ValueError("planted")
    ClassifyService.get()._begin_uniform = boom
    ip = clients(1)[0]
    assert connect(ups, Hint(host="app1.example.com"), ip, seek=True) is None
    assert connect(ups, Hint(host="app1.example.com"), ip) is not None  # WRR


# ------------------------------------------------ rows, tokens, installs

def test_hint_generation_ahead_of_the_set_answers_minus_one():
    """Rules that name a row the set has not installed yet: pick -1 on
    the device and on the host, then the group's own table once it is
    there."""
    rules, rule_group, healthy = plain(6, "all_source", 120)
    late = {2, 7}
    pair, refs = install(rules, rule_group, healthy, skip=late)
    qs = queries(6, rules)
    hollow = [names if g not in late else []
              for g, names in enumerate(healthy)]
    want = reference_groups.classify_pick(rules, rule_group, hollow, M, qs)
    snap = pair.snapshot()
    assert (np.asarray(pair.dispatch_snap(snap, payloads(qs)))[:len(qs)]
            == want).all()
    assert (np.array([pair.index_snap(snap, p) for p in payloads(qs)])
            == want).all()
    named_late = np.isin(want[:, 0] % GROUPS, list(late)) & (want[:, 0] >= 0)
    assert named_late.any() and (want[named_late, 1] == -1).all()
    for g in late:
        set_entries(pair.mm, refs[g], [(s, 10) for s in healthy[g]])
    full = reference_groups.classify_pick(rules, rule_group, healthy, M, qs)
    snap = pair.snapshot()
    assert (np.asarray(pair.dispatch_snap(snap, payloads(qs)))[:len(qs)]
            == full).all()
    assert (full[named_late, 1] >= 0).all()


def test_a_row_never_answers_for_its_earlier_owner():
    """An old hint generation paired with a set generation in which a
    row has a new owner: the old owner's rules answer -1, never the new
    group's backend — on the device and on the host."""
    rules, rule_group, healthy = plain(7, "all_source", 120)
    pair, refs = install(rules, rule_group, healthy)
    ts = pair.mm
    old_hsnap = pair.hm.snapshot()
    ts.release(refs[4], wait=True)
    newcomer = ts.alloc()
    assert MG.ref_row(newcomer) == MG.ref_row(refs[4]) \
        and newcomer != refs[4]
    set_entries(ts, newcomer, [(f"gX|10.9.9.{b}:80", 10) for b in range(5)])
    assert ts.snapshot().owner[MG.ref_row(newcomer)] \
        == MG.ref_token(newcomer)
    qs = queries(7, rules)
    gone = [names if g != 4 else [] for g, names in enumerate(healthy)]
    want = reference_groups.classify_pick(rules, rule_group, gone, M, qs)
    torn = (old_hsnap, ts.snapshot())
    assert (np.asarray(pair.dispatch_snap(torn, payloads(qs)))[:len(qs)]
            == want).all()
    assert (np.array([pair.index_snap(torn, p) for p in payloads(qs)])
            == want).all()
    of_4 = (want[:, 0] >= 0) & (want[:, 0] % GROUPS == 4)
    assert of_4.any() and (want[of_4, 1] == -1).all()


def test_row_type_is_the_narrowest_and_the_shape_is_the_cap():
    ts = MG.MaglevTableSet(m=M, backend="jax")
    refs = [ts.alloc() for _ in range(3)]
    set_entries(ts, refs[0], [(f"a{b}", 10) for b in range(8)])
    snap = ts.snapshot()
    assert snap.tabs.shape == (16, M) and snap.tabs.dtype == np.int8
    assert ts.published_table_bytes() == 16 * M + 16 * 4
    set_entries(ts, refs[1], [(f"b{b}", 10) for b in range(200)])
    assert ts.snapshot().tabs.dtype == np.int16     # 200 members
    assert ts.snapshot().tabs[0].tolist() == snap.tabs[0].tolist()
    more = [ts.alloc() for _ in range(14)]          # rows 3 .. 16
    set_entries(ts, more[0], [("c0", 10)])
    # sized by the rows handed out, not by the rows installed so far:
    # a deployment that allocs all its groups first reshapes once
    assert ts.snapshot().tabs.shape == (32, M)      # the cap doubled
    assert ts.size() == 3 and MG.set_groups_total() >= 3
    with pytest.raises(ValueError):
        MG.MaglevTableSet(m=250)


def test_a_raising_source_does_not_take_the_other_rows_with_it():
    """Three rows coalesced into one installer call, one source raises:
    the other two publish as built, the failed row leaves the set (its
    edge said the old table is out of date: -1, the host is asked), and
    a waiter of the call sees the exception."""
    ts = MG.MaglevTableSet(m=M, backend="jax")
    refs = [ts.alloc() for _ in range(4)]
    for k, ref in enumerate(refs):
        set_entries(ts, ref, [(f"r{k}b{b}", 10) for b in range(3)])
    before = ts.snapshot()
    entered, gate = threading.Event(), threading.Event()

    def slow():         # holds the installer inside one _install call
        entered.set()
        gate.wait(10)
        return None

    def bad():
        raise RuntimeError("no table today")
    ts.install(refs[3], slow, wait=False)
    assert entered.wait(10)
    set_entries(ts, refs[0], [("r0b0", 10), ("r0new", 10)], wait=False)
    ts.install(refs[1], bad, wait=False)
    set_entries(ts, refs[2], [("r2b1", 10)], wait=False)
    builds = MG.set_table_builds_total()
    gate.set()
    assert E.flush_installs(10)
    snap = ts.snapshot()
    assert set(snap.rows) == {refs[0], refs[2]}
    assert MG.set_table_builds_total() == builds + 2
    assert snap.rows[refs[0]].names == ["r0b0", "r0new"]
    assert snap.rows[refs[2]].names == ["r2b1"]
    assert snap.owner[MG.ref_row(refs[1])] == -1 \
        and (snap.tabs[MG.ref_row(refs[1])] == -1).all()
    assert (before.tabs[MG.ref_row(refs[1])] >= 0).all()
    with pytest.raises(RuntimeError):       # alone, to its waiter
        ts.install(refs[2], bad)
    assert set(ts.snapshot().rows) == {refs[0]}
    with pytest.raises(ValueError):         # a table of another size
        ts.install(refs[0], lambda: (np.zeros(M + 2, np.int32), ["x"], 0))
    set_entries(ts, refs[1], [("r1back", 10)])      # and comes back
    assert set(ts.snapshot().rows) == {refs[1]}


# ------------------------------------------- real groups behind an Upstream

def group(alias: str, n: int, elg, method: str = "source") -> ServerGroup:
    g = ServerGroup(alias, elg,
                    HealthCheckConfig(protocol="none", period_ms=60000),
                    method=method)
    for i in range(n):
        g.add(f"s{i}", f"10.2.{len(alias)}.{i + 1}", 1000 + i)
    for s in g.servers:
        s.healthy = True
    g._notify(g.servers[0], True)       # an UP edge: listeners hear of it
    return g


@pytest.fixture
def lb():
    """An Upstream of six `source` groups of 2..7 members, a `wrr` and
    a `wlc` group, each annotated with its own Host."""
    elg = EventLoopGroup("grp-elg", 1)
    ups = Upstream("u-grp", backend="jax")
    groups = [group(f"g{'x' * i}", 2 + i, elg) for i in range(6)]
    groups.append(group("gwrr-aaaa", 3, elg, method="wrr"))
    groups.append(group("gwlc-aaaaa", 3, elg, method="wlc"))
    for i, g in enumerate(groups):
        ups.add(g, annotations=HintRule(host=f"app{i}.example.com"))
    E.flush_installs(10)
    yield ups, groups
    for g in groups:
        g.close()
    elg.close()


def clients(n: int, seed: int = 3) -> list:
    rs = np.random.default_rng(seed)
    return [bytes(rs.integers(0, 256, 4).tolist()) for _ in range(n)]


def batch(ups, qs: list) -> tuple:
    """One device batch of (group index, client) -> rows, payload."""
    pair = ups._pair
    snap = pair.snapshot()
    rows = np.asarray(pair.dispatch_snap(
        snap, [(Hint(host=f"www.app{i}.example.com"), ip, None)
               for i, ip in qs]))[:len(qs)]
    return rows, pair.snap_payload(snap)


def test_device_pick_is_source_next_slot_load(lb):
    ups, groups = lb
    assert ups._picks.size() == 6       # the `source` groups alone
    qs = [(i % 8, ip) for i, ip in enumerate(clients(480))]
    rows, (handles, members) = batch(ups, qs)
    for (i, ip), (verdict, pick) in zip(qs, rows.tolist()):
        assert handles[verdict].group is groups[i]
        if groups[i].method != "source":
            assert pick == -1
            continue
        servers, table = groups[i].maglev_table()
        assert pick == table[MG.flow_hash(ip) % len(table)]
        assert members[handles[verdict].ref][1][pick] \
            is groups[i].next(ip).svr


def test_one_group_health_edge_rebuilds_one_row(lb):
    ups, groups = lb
    ts = ups._picks
    qs = [(i % 6, ip) for i, ip in enumerate(clients(600))]
    before, _pl = batch(ups, qs)
    snap0 = ts.snapshot()
    builds, programs = MG.set_table_builds_total(), F.group_jit._cache_size()
    victim = groups[2].servers[1]           # one of four members
    victim.healthy = False
    groups[2]._notify(victim, False)        # the hc DOWN edge
    assert E.flush_installs(10)
    assert MG.set_table_builds_total() == builds + 1
    snap1 = ts.snapshot()
    row2 = MG.ref_row(ups.handles[2].ref)
    same = [r for r in range(snap0.tabs.shape[0]) if r != row2]
    assert (snap1.tabs[same] == snap0.tabs[same]).all()
    assert (snap1.tabs[row2] != snap0.tabs[row2]).any()
    assert 0.15 < ts.last_remap < 0.40      # ~ the dead member's 1/4
    after, (handles, members) = batch(ups, qs)
    assert F.group_jit._cache_size() == programs        # no retrace
    other = np.array([i != 2 for i, _ip in qs])
    assert (after[other] == before[other]).all()
    for (i, ip), (verdict, pick) in zip(qs, after.tolist()):
        if i == 2:
            s = members[handles[verdict].ref][1][pick]
            assert s is not victim and s is groups[2].next(ip).svr


def connect(ups, hint, ip, fam=None, seek=False):
    out, done = [], threading.Event()

    def cb(c):
        out.append(c)
        done.set()
    if seek:
        ups.seek_async(ip, hint, cb, fam=fam)
    else:
        ups.next_async(ip, hint, cb, fam=fam)
    assert done.wait(10)
    return out[0]


@pytest.mark.parametrize("mode", ["device", "auto"])
def test_next_async_returns_what_group_next_returns(lb, mode, monkeypatch):
    ups, groups = lb
    monkeypatch.setenv("VPROXY_TPU_CLASSIFY", mode)
    ClassifyService.reset()
    st = ClassifyService.get().stats
    for k, ip in enumerate(clients(60, seed=9)):
        i = k % 6
        c = connect(ups, Hint(host=f"app{i}.example.com"), ip, seek=k % 2)
        assert c.group is groups[i] and c.svr is groups[i].next(ip).svr
    if mode == "device":    # every one a grouped batch, picked there
        assert st.batches["cpick"] == 60 and st.batches["hint"] == 0
        assert st.group_picks == {"device": 60, "host": 0}
    else:                   # lone queries: the inline host lane
        assert st.oracle_queries == 60 and st.dispatches == 0
        assert st.group_picks == {"device": 0, "host": 60}


def test_next_async_falls_back_to_group_next(lb, monkeypatch):
    ups, groups = lb
    monkeypatch.setenv("VPROXY_TPU_CLASSIFY", "device")
    ClassifyService.reset()
    st = ClassifyService.get().stats
    ip = clients(1, seed=21)[0]
    # a family: the member set the table was built over does not apply
    c = connect(ups, Hint(host="app3.example.com"), ip, fam="v4")
    assert c.svr is groups[3].next(ip, "v4").svr
    assert st.batches["hint"] == 1 and st.batches["cpick"] == 0
    # wrr / wlc groups hold no table: the group picks, as before
    for i in (6, 7):
        for seek in (False, True):
            c = connect(ups, Hint(host=f"app{i}.example.com"), ip, seek=seek)
            assert c.group is groups[i] and c.svr in groups[i].servers
    assert st.group_picks == {"device": 0, "host": 0}
    # the picked member went down and the row has not heard of it yet
    picked = groups[3].next(ip).svr
    picked.healthy = False
    c = connect(ups, Hint(host="app3.example.com"), ip)
    assert c.svr is not picked and c.svr.healthy and c.group is groups[3]
    picked.healthy = True
    # the group left method `source` while its row still holds a table
    groups[3].method = "wrr"
    c = connect(ups, Hint(host="app3.example.com"), ip)
    assert c.group is groups[3] and c.svr in groups[3].servers
    groups[3]._fire_change()
    assert E.flush_installs(10) and ups._picks.size() == 5
    # no rule matches: next falls to the upstream's WRR, seek to nothing
    assert connect(ups, Hint(host="nomatch.invalid"), ip) is not None
    assert connect(ups, Hint(host="nomatch.invalid"), ip, seek=True) is None


@pytest.mark.parametrize("edit", ["remove", "weight", "add"])
def test_next_async_never_uses_a_row_behind_its_edge(lb, edit, monkeypatch):
    """A member removed, re-weighted or added, and the installer not
    yet heard from (its submit held back here): the row the device
    reads is the old table's, its members all `healthy`, and
    next_async still answers what group.next answers — never the
    removed member."""
    ups, groups = lb
    monkeypatch.setenv("VPROXY_TPU_CLASSIFY", "device")
    ClassifyService.reset()
    st = ClassifyService.get().stats
    g, held = groups[4], []                 # six members
    monkeypatch.setattr(ups._picks, "_submit",
                        lambda ref, source, wait: held.append(ref))
    gone = g.servers[2]
    if edit == "remove":
        g.remove(gone.name)
    elif edit == "weight":
        g.set_weight(gone.name, 90)         # most slots move to it
    else:
        g.add("late", "10.2.9.9", 999).healthy = True
    assert held and set(held) == {ups.handles[4].ref}
    assert ups._picks.snapshot().payloads[ups.handles[4].ref][0] \
        != g.health_version                 # the row is behind
    moved = 0
    for k, ip in enumerate(clients(120, seed=31)):
        c = connect(ups, Hint(host="app4.example.com"), ip, seek=k % 2)
        assert c.group is g and c.svr is g.next(ip).svr
        assert edit != "remove" or c.svr is not gone
        stale = ups._picks.snapshot().rows[ups.handles[4].ref]
        moved += stale.payload[1][stale.tlist[
            MG.flow_hash(ip) % M_GROUP]] is not c.svr
    assert moved > 5        # the old table would have answered otherwise
    assert st.batches["cpick"] == 120       # every one asked the device
    # the installer catches up: the row is of the group's generation
    monkeypatch.undo()
    monkeypatch.setenv("VPROXY_TPU_CLASSIFY", "device")
    g._fire_change()
    assert E.flush_installs(10)
    row = ups._picks.snapshot().rows[ups.handles[4].ref]
    assert row.payload[0] == g.health_version
    for ip in clients(40, seed=32):
        c = connect(ups, Hint(host="app4.example.com"), ip)
        assert c.svr is g.next(ip).svr \
            and c.svr is row.payload[1][row.tlist[MG.flow_hash(ip) % M_GROUP]]


def test_remove_gives_the_row_back(lb):
    ups, groups = lb
    ref = ups.handles[1].ref
    ups.remove(groups[1])
    assert E.flush_installs(10) and ups._picks.size() == 5
    assert ref not in ups._picks.snapshot().rows
    g = group("gnew-bb", 3, groups[0].elg)
    try:
        h = ups.add(g, annotations=HintRule(host="new.example.com"))
        assert E.flush_installs(10)
        assert MG.ref_row(h.ref) == MG.ref_row(ref) and h.ref != ref
        ip = clients(1, seed=5)[0]
        rows, (handles, members) = batch(ups, [(0, ip)])
        snap = ups._pair.snapshot()
        v, p = ups._pair.index_snap(
            snap, (Hint(host="new.example.com"), ip, None))
        assert handles[v].group is g and members[h.ref][1][p] is g.next(ip).svr
    finally:
        g.close()


# ------------------------------------------------- programs and surfaces

def test_plain_fused_program_is_untouched():
    """A plain FusedPair lowers to the same program text whether or not
    its hint matcher carries a group column, with no trace of the
    grouped chain in it; the grouped program is its own function."""
    rules = [HintRule(host=f"svc{i}.example.com") for i in range(50)]
    plain_hm = E.HintMatcher(rules, backend="jax")
    grouped_hm = E.HintMatcher(backend="jax")
    grouped_hm.set_rules(rules, groups=[-1] * len(rules))
    assert set(grouped_hm.snapshot()[5]) == set(plain_hm.snapshot()[5])
    mm = MG.MaglevMatcher([(f"b{i}", 1) for i in range(8)], m=M)
    hints = [Hint(host="www.svc3.example.com")] * 4
    lowered = []
    for hm in (plain_hm, grouped_hm):
        hsnap = hm.snapshot()
        q = E._fused_hint_q(hsnap[0], hints, 4, slots=True)
        lowered.append(F.fused_jit.lower(hsnap[5], mm.snapshot()[1],
                                         q.arena, q.layout))
    assert lowered[0].as_text() == lowered[1].as_text()
    scopes = lowered[1].as_text(debug_info=True)
    assert "/maglev_pick/" in scopes and "/group_pick/" not in scopes
    assert F.group_jit is not F.fused_jit
    ts = MG.MaglevTableSet(m=M, backend="jax")
    set_entries(ts, ts.alloc(), [("x", 10)])
    dev = ts.snapshot().dev
    hsnap = grouped_hm.snapshot()
    q = E._fused_hint_q(hsnap[0], hints, 4, slots=True)
    grouped = F.group_jit.lower(
        hsnap[5], hsnap[6][1], dev[1], dev[0], q.arena,
        q.layout).as_text(debug_info=True)
    assert "jit(fused_group_pick)/group_pick/" in grouped


def test_span_and_counters_are_on_the_surfaces():
    from vproxy_tpu.utils import trace
    from vproxy_tpu.utils.metrics import GlobalInspection
    assert ("engine", "group_pick") in trace.SPANS
    rules, rule_group, healthy = plain(8, "all_source", 60)
    builds = MG.set_table_builds_total()
    pair, _refs = install(rules, rule_group, healthy)
    assert MG.set_table_builds_total() == builds + GROUPS
    prev = trace.sample_every()
    trace.configure(1)
    svc = ClassifyService(mode="device")
    try:
        n0 = trace.span_totals().get("engine/group_pick", {"n": 0,
                                                           "sum_items": 0})
        got = through_service(svc, pair, queries(8, rules, 100))
        tot = trace.span_totals()["engine/group_pick"]
        assert tot["n"] - n0["n"] == svc.stats.dispatches
        assert tot["sum_items"] - n0["sum_items"] \
            == int(((got[:, 0] >= 0) & (got[:, 1] >= 0)).sum())
    finally:
        svc.close()
        trace.configure(prev)
    text = GlobalInspection.get().prometheus_string()
    for line in ('vproxy_classify_group_picks_total{where="device"}',
                 'vproxy_classify_group_picks_total{where="host"}',
                 "vproxy_maglev_set_groups ",
                 "vproxy_maglev_set_table_builds_total ",
                 'vproxy_trace_span_us_count{plane="engine",'
                 'span="group_pick"}'):
        assert line in text, line


def test_installs_under_readers_keep_device_and_host_alike():
    """Writers cycle alloc -> install -> release on rows of their own
    while a reader dispatches: on every snapshot pair the device and
    the host lane agree row for row (a lost update or a torn publish
    would part them)."""
    import sys as _sys
    rules, rule_group, healthy = plain(9, "all_source", 120)
    pair, _refs = install(rules, rule_group, healthy)
    ts = pair.mm
    qs = payloads(queries(9, rules, 128))
    stop, errors = threading.Event(), []

    def writer(w: int) -> None:
        k = 0
        try:
            while not stop.is_set():
                ref = ts.alloc()
                set_entries(ts, ref, [(f"w{w}k{k}b{b}", 10)
                                      for b in range(1 + k % 5)])
                ts.release(ref, wait=k % 2 == 0)
                k += 1
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)
    prev = _sys.getswitchinterval()
    _sys.setswitchinterval(1e-4)
    threads = [threading.Thread(target=writer, args=(w,), daemon=True)
               for w in range(4)]
    try:
        for t in threads:
            t.start()
        t_end, rounds = time.monotonic() + 2.0, 0
        while time.monotonic() < t_end or rounds < 3:
            snap = pair.snapshot()
            dev = np.asarray(pair.dispatch_snap(snap, qs))[:len(qs)]
            host = np.array([pair.index_snap(snap, p) for p in qs])
            assert (dev == host).all()
            rounds += 1
    finally:
        stop.set()
        for t in threads:
            t.join(10)
        _sys.setswitchinterval(prev)
    assert not errors and rounds >= 3
    assert not any(t.is_alive() for t in threads)
    assert E.flush_installs(10) and ts.size() == GROUPS
