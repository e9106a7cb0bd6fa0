"""Several rules to one group (Upstream.add_many with a list of HintRules
an entry): the same answers, synchronous and through
`Upstream.next_async`, as one handle a rule with the rules in the order
the upstream publishes them — group by group, each group's rules in
entry order, the earliest rule winning at equal level — and a
single-rule upstream publishes exactly the rules and payload it did."""
import threading

import pytest

from vproxy_tpu.components.elgroup import EventLoopGroup
from vproxy_tpu.components.servergroup import HealthCheckConfig, ServerGroup
from vproxy_tpu.components.upstream import Upstream
from vproxy_tpu.rules import engine as E
from vproxy_tpu.rules.ir import Hint, HintRule
from vproxy_tpu.rules.service import ClassifyService
from vproxy_tpu.utils.metrics import UPSTREAM_SELECT_WHERES, GlobalInspection

GROUPS = 5
RULES = 23


@pytest.fixture(autouse=True)
def _fresh_service():
    ClassifyService.reset()
    yield
    ClassifyService.reset()


@pytest.fixture
def elg():
    e = EventLoopGroup("rules-elg", 1)
    made: list = []
    yield e, made
    for g in made:
        g.close()
    e.close()


def group(alias: str, members: int, elg) -> ServerGroup:
    e, made = elg
    g = ServerGroup(alias, e, HealthCheckConfig(protocol="none"), "source")
    for i in range(members):
        g.add(f"s{i}", f"10.3.{len(made)}.{i + 1}", 2000 + i)
    e.next().call_sync(lambda: None)       # the `none` checks' UP verdict
    made.append(g)
    return g


def rule_hosts(order: str) -> list:
    """(host, group) for every rule; rules 3 and 4 share a host with
    rule 1 (another group's), so first match decides between them."""
    out = [(f"svc{i}.example.com", i % GROUPS) for i in range(RULES)]
    out[3] = ("svc1.example.com", 3 % GROUPS)
    out[4] = ("example.com", 4 % GROUPS)      # a suffix of every host
    if order == "reversed":
        out.reverse()
    elif order == "blocks":
        out.sort(key=lambda hg: (hg[1], hg[0]))
    return out


def queries() -> list:
    qs = [f"svc{i}.example.com" for i in range(RULES)]
    qs += [f"www.svc{i}.example.com" for i in range(0, RULES, 3)]
    qs += ["nohost.invalid", "other.example.com", "example.com"]
    return [Hint.of_host(h) for h in qs]


def build(elg, order: str, entry_order: list):
    """-> (multi, single): the same rules as one entry a group (groups
    in `entry_order`) and as one handle a rule in the published order;
    each handle's group maps to the multi upstream's group index."""
    hosts = rule_hosts(order)
    groups = {g: group(f"g{g}", 2 + g, elg) for g in entry_order}
    multi = Upstream("multi")
    by_group = {g: [HintRule(host=h) for h, gg in hosts if gg == g]
                for g in entry_order}
    multi.add_many([(groups[g], 0, by_group[g]) for g in entry_order]
                   + [(group("fallback", 3, elg), 10, [])])
    single = Upstream("single")
    single.add_many([(group(f"one{g}-{k}", 2 + g, elg), 0, r)
                     for g in entry_order
                     for k, r in enumerate(by_group[g])])
    E.flush_installs(10)
    return multi, single, groups


def alias_of(h) -> str:
    """The multi upstream's group a handle stands for."""
    return None if h is None else h.group.alias.split("-")[0] \
        .replace("one", "g")


@pytest.mark.parametrize("order", ["interleaved", "reversed", "blocks"])
@pytest.mark.parametrize("entry_order", [[0, 1, 2, 3, 4], [4, 2, 0, 3, 1]])
def test_several_rules_answer_as_one_handle_a_rule(elg, order, entry_order):
    multi, single, _groups = build(elg, order, entry_order)
    assert multi._matcher.size() == single._matcher.size() == RULES
    # published handle by handle, each handle's rules in entry order
    assert multi._matcher.rules == single._matcher.rules == \
        [r for h in multi.handles for r in h.rules]
    qs = queries()
    got = [alias_of(h) for h in multi.search_batch(qs)]
    want = [alias_of(h) for h in single.search_batch(qs)]
    assert got == want
    assert got.count(None) == 1       # nohost.invalid: no rule at all
    # the earliest published rule wins at equal level: svc1 is held by
    # group 1 (rule 1) and group 3 (rule 3); the one entered first wins
    i1 = multi.search_for_group(Hint.of_host("svc1.example.com"))
    assert i1.group.alias == ("g1" if entry_order.index(1)
                              < entry_order.index(3) else "g3")


@pytest.mark.parametrize("mode", ["device", "auto"])
@pytest.mark.parametrize("order", ["interleaved", "reversed"])
def test_next_async_answers_as_one_handle_a_rule(elg, mode, order,
                                                 monkeypatch):
    monkeypatch.setenv("VPROXY_TPU_CLASSIFY", mode)
    ClassifyService.reset()
    multi, single, groups = build(elg, order, [0, 1, 2, 3, 4])
    fallback = multi.handles[-1].group
    gi = GlobalInspection.get()

    def selects() -> dict:
        return {w: gi.get_counter("vproxy_upstream_selects_total",
                                  where=w).value()
                for w in UPSTREAM_SELECT_WHERES}
    qs = queries()
    for k, hint in enumerate(qs):
        ip = bytes([172, 20, k, 1])
        out, done = [], threading.Event()

        def cb(c):
            out.append(c)
            done.set()
        before = selects()
        multi.next_async(ip, hint, cb)
        assert done.wait(10)
        c = out[0]
        counted = {w: n - before[w] for w, n in selects().items()
                   if n > before[w]}
        h = single.search_for_group(hint)
        if h is None:       # no match: the WRR fallback, its own pick
            assert c.group is fallback and counted == {"fallback": 1}
            assert c.svr is fallback.next(ip).svr
            continue
        g = groups[int(alias_of(h)[1:])]
        assert c.group is g and c.svr is g.next(ip).svr
        assert counted == {"device_pick": 1}
    st = ClassifyService.get().stats
    if mode == "device":
        assert st.oracle_queries == 0 and st.device_queries == len(qs)
    else:
        assert st.oracle_queries == len(qs)


def test_a_single_rule_upstream_publishes_what_it_did(elg):
    gs = [group(f"s{i}", 2, elg) for i in range(4)]
    ups = Upstream("plain")
    ups.add_many([(g, 10, HintRule(host=f"h{i}.example.com"))
                  for i, g in enumerate(gs[:3])] + [(gs[3], 10, None)])
    m = ups._matcher
    snap = m.snapshot()
    assert m.snap_payload(snap) == ups.handles
    assert [h.rules for h in ups.handles] == \
        [[HintRule(host=f"h{i}.example.com")] for i in range(3)] \
        + [[HintRule()]]
    assert [h.merged_rule() for h in ups.handles] == \
        [HintRule(host=f"h{i}.example.com") for i in range(3)] + [HintRule()]
    assert m.size() == 4
    # the operator surface's edit replaces the rules, as before
    ups.set_annotations(gs[1], HintRule(host="new.example.com"))
    ups.set_annotations(gs[2], [HintRule(host="a.example.com"),
                                HintRule(host="b.example.com")])
    assert ups.handles[1].rules == [HintRule(host="new.example.com")]
    assert ups.handles[2].annotations == HintRule(host="a.example.com")
    assert m.size() == 5
    for host, want in (("new.example.com", gs[1]), ("b.example.com", gs[2]),
                       ("h0.example.com", gs[0])):
        assert ups.search_for_group(Hint.of_host(host)).group is want


@pytest.fixture
def app():
    from vproxy_tpu.control.app import Application
    a = Application.create(workers=1)
    yield a
    a.close()


@pytest.mark.parametrize("anno,want", [
    ('[{"vproxy/hint-host":"a.example.com"}, '
     '{"vproxy/hint-host":"b.example.com","vproxy/hint-uri":"/x"}]',
     [HintRule(host="a.example.com"), HintRule(host="b.example.com", uri="/x")]),
    ("[]", []),
    ('{"vproxy/hint-host":"c.example.com"}', [HintRule(host="c.example.com")]),
    (None, [HintRule()]),
], ids=["several", "none", "one", "unannotated"])
def test_a_saved_config_reloads_every_rule_of_a_group(app, tmp_path, anno,
                                                      want):
    """The command grammar takes a group's rules as one json object or a
    json list (`[]`: none), and persist writes them back the same way."""
    from vproxy_tpu.control import persist
    from vproxy_tpu.control.command import Command

    def run(line):
        return Command.execute(app, line)
    run("add upstream ups0")
    run("add server-group sg0 timeout 500 period 100 up 1 down 1 "
        "protocol none method source")
    run("add server-group sg0 to upstream ups0 weight 10"
        + (f" annotations {anno}" if anno else ""))
    assert app.upstreams["ups0"].handles[0].rules == want
    published = list(app.upstreams["ups0"]._matcher.rules)
    assert len(published) == len(want)
    path = tmp_path / "cfg"
    persist.save(app, str(path))
    run("remove server-group sg0 from upstream ups0")
    run("force-remove upstream ups0")
    run("force-remove server-group sg0")
    persist.load(app, str(path))
    ups = app.upstreams["ups0"]
    assert ups.handles[0].rules == want
    assert list(ups._matcher.rules) == published
