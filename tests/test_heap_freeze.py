"""The rule heap out of the collector's reach (utils/heap.py).

Counts, never times: a publish and the dispatcher's start freeze what
is alive; repeated installs do not grow the frozen population; a cycle
frozen alive and dropped later is reclaimed once the growth rule has
the heap examined again — by the event itself when idle, by the
collector's own next full collection under load, where no event may
run one; a lookup in flight across a publish keeps its generation.
"""
import gc
import threading
import weakref

import pytest

from vproxy_tpu.rules import engine
from vproxy_tpu.rules.engine import CidrMatcher, CidrTableSet, HintMatcher
from vproxy_tpu.rules.ir import Hint, HintRule
from vproxy_tpu.rules.service import ClassifyService
from vproxy_tpu.utils import heap
from vproxy_tpu.utils.ip import Network
from vproxy_tpu.utils.metrics import GlobalInspection


def hint_rules(n, dom="example.com"):
    return [HintRule(host=f"svc{i}.{dom}") for i in range(n)]


def networks(n, base=10):
    return [Network.parse(f"{base}.{i >> 8}.{i & 255}.0/24")
            for i in range(n)]


def idle():
    """The next publish finds no lookup served lately."""
    engine._LAST_SERVE[0] = float("-inf")


def publish():
    HintMatcher().set_rules(hint_rules(4))


@pytest.fixture(autouse=True)
def settled():
    """Whatever earlier tests of this worker left — a thaw waiting for
    its collection, a count past the factor — is settled first: frozen,
    and under FACTOR x base."""
    for _ in range(4):
        idle()
        publish()
        if heap._last < heap.FACTOR * heap._base:
            break
    assert not heap._thawed and heap._last < heap.FACTOR * heap._base
    idle()


def install_hint():
    rules = hint_rules(300)
    HintMatcher().set_rules(rules)
    return rules


def install_cidr():
    nets = networks(300)
    CidrMatcher().set_networks(nets)
    return nets


def install_view():
    nets = networks(300, base=11)
    CidrTableSet("v4").view().set_networks(nets)
    return nets


@pytest.mark.parametrize("install", [install_hint, install_cidr,
                                     install_view])
def test_publish_freezes_the_rule_heap(install):
    n0 = heap.freezes_total("publish")
    rules = install()
    assert heap.freezes_total("publish") == n0 + 1
    assert heap.frozen_objects() >= len(rules)
    # frozen objects are in no generation the collector examines
    examined = {id(o) for o in gc.get_objects()}
    assert gc.is_tracked(rules[0])
    assert not any(id(r) in examined for r in rules)


def test_replace_installs_do_not_grow_the_frozen_heap():
    """The rule heap has no cycles: a replaced generation dies by
    reference count, frozen or not."""
    m = HintMatcher()
    m.set_rules(hint_rules(1000))
    first = heap.frozen_objects()
    for k in range(20):
        idle()
        m.set_rules(hint_rules(1000, dom=f"d{k}.example.org"))
    assert m.generation >= 21
    assert heap.frozen_objects() <= 1.5 * first


class _Node:
    pass


class _GcSpy:
    """utils/heap's `gc`, recording what its collect() is asked for."""

    def __init__(self):
        self.collected = []

    def collect(self, generation=2):
        self.collected.append(generation)
        return gc.collect(generation)

    def __getattr__(self, name):
        return getattr(gc, name)


@pytest.mark.parametrize("load", [False, True], ids=["idle", "under_load"])
def test_frozen_cycle_is_reclaimed_after_the_growth_rule(load, monkeypatch):
    spy = _GcSpy()
    monkeypatch.setattr(heap, "gc", spy)
    # a cycle alive at a publish, with enough beside it that the freeze
    # leaves the count FACTOR-fold over the settled heap's
    a, b = _Node(), _Node()
    a.other, b.other = b, a
    alive = weakref.ref(a)
    ballast = [[] for _ in range(heap.FACTOR * heap._base - heap._last
                                 + 10_000)]
    n0 = heap.reexaminations_total()
    publish()
    assert heap._last >= heap.FACTOR * heap._base
    assert heap.reexaminations_total() == n0    # asked of the NEXT publish
    del a, b
    gc.collect()
    assert alive() is not None      # frozen: no collection examines it
    spy.collected.clear()
    if load:
        engine.note_serving()
    publish()
    assert heap.reexaminations_total() == n0 + 1
    if load:
        # no full collection from the event, and nothing frozen until
        # the collector has run its own (the test's stands in for it)
        assert 2 not in spy.collected
        gc.collect()
    assert alive() is None
    assert not heap._thawed
    assert heap._base == heap._last <= heap.frozen_objects() + len(ballast)
    assert heap.frozen_objects() > 0
    del ballast


def lookup(svc, m, host):
    got, done = [], threading.Event()

    def cb(idx, payload):
        got.append((idx, payload))
        done.set()
    svc.submit_hint(m, Hint.of_host(host), cb)
    assert done.wait(30)
    return got[0]


def test_dispatcher_start_freezes_once():
    m = HintMatcher()
    m.set_rules(hint_rules(300))
    svc = ClassifyService(mode="device")
    try:
        n0 = heap.freezes_total("serve_start")
        assert lookup(svc, m, "svc7.example.com")[0] == 7
        assert heap.freezes_total("serve_start") == n0 + 1
        assert heap._base == heap._last     # bring-up ends here
        for i in range(1000):
            assert lookup(svc, m, f"svc{i % 300}.example.com")[0] == i % 300
        assert svc.stats.dispatches >= 1001
        assert heap.freezes_total("serve_start") == n0 + 1
    finally:
        svc.close()


def test_lookup_in_flight_across_publish_keeps_its_generation():
    """The request, its callback and the old generation are frozen by
    the publish while the batch is on its way: delivered all the same,
    index and payload of the generation it was dispatched against."""
    m = HintMatcher()
    m.set_rules(hint_rules(300), payload="old")
    svc = ClassifyService(mode="device")
    taken, go = threading.Event(), threading.Event()
    submit = svc._device_submit

    def held(kind, matcher, snap, reqs):
        arr = submit(kind, matcher, snap, reqs)
        taken.set()
        assert go.wait(30)
        return arr
    svc._device_submit = held
    try:
        got, done = [], threading.Event()
        svc.submit_hint(m, Hint.of_host("svc7.example.com"),
                        lambda i, p: (got.append((i, p)), done.set()))
        assert taken.wait(30)
        n0 = heap.freezes_total("publish")
        m.set_rules(hint_rules(300, dom="example.org")[::-1], payload="new")
        assert heap.freezes_total("publish") == n0 + 1
        go.set()
        assert done.wait(30)
        assert got == [(7, "old")]
        svc._device_submit = submit
        assert lookup(svc, m, "svc7.example.org") == (292, "new")
        assert lookup(svc, m, "svc7.example.com") == (-1, "new")
    finally:
        go.set()
        svc.close()


def test_heap_series_on_metrics():
    publish()
    text = GlobalInspection.get().registry.prometheus_text()
    snap = {ln.split(" ")[0]: float(ln.split(" ")[1])
            for ln in text.splitlines() if ln.startswith("vproxy_runtime_")}
    assert snap["vproxy_runtime_heap_frozen_objects"] > 0
    assert snap['vproxy_runtime_heap_freezes_total{event="publish"}'] \
        == heap.freezes_total("publish") >= 1
    assert 'vproxy_runtime_heap_freezes_total{event="serve_start"}' in snap
    assert snap["vproxy_runtime_heap_reexaminations_total"] \
        == heap.reexaminations_total()
