"""The dispatcher's loop accounted for (utils/trace SPANS, rules/service
`_run`): with tracing on, `engine/wait`, `engine/swap`, `engine/cycle`
and `engine/drain` tile the dispatcher thread, every phase of a batch
lies inside its cycle or drain, the double buffer's pattern
(`engine/inflight`, `engine/kernel_wait`) is noted beside the always-on
counter it shares a decision with, and `engine/launch` says what the
jitted call was handed. Structure only: no wall-time threshold but the
share of the stretch that lies in no span."""
import threading

import numpy as np
import pytest

from vproxy_tpu.rules import engine as E
from vproxy_tpu.rules.engine import CidrMatcher, HintMatcher
from vproxy_tpu.rules.ir import Hint, HintRule
from vproxy_tpu.rules.service import ClassifyService
from vproxy_tpu.utils import trace
from vproxy_tpu.utils.ip import Network

TOP = ("wait", "swap", "cycle", "drain")
CHILDREN = ("begin", "dispatch", "readback_start", "d2h_sync", "group_pick",
            "deliver", "release")
NEW = ("swap", "drain", "readback_start", "inflight", "kernel_wait",
       "swap_lock", "begin", "release")
ROUNDS = 60


@pytest.fixture(autouse=True)
def _trace_off():
    trace.configure(0)
    trace.reset()
    yield
    trace.configure(0)
    trace.reset()


class _NotReady:
    """A device result whose kernel is never done when the dispatcher
    comes for it, and whose copy cannot be started early."""

    def __init__(self, arr):
        self._arr = arr

    def is_ready(self) -> bool:
        return False

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._arr)


class _SlowHint(HintMatcher):
    def dispatch_snap(self, *a, **kw):
        return _NotReady(super().dispatch_snap(*a, **kw))


def _matchers():
    hm = _SlowHint([HintRule(host=f"s{i}.example.com") for i in range(64)],
                   backend="jax")
    cm = CidrMatcher([Network.parse(f"10.{i}.0.0/16") for i in range(32)],
                     backend="jax")
    hm.match([Hint.of_host("warm.example.com")] * 8)    # compile outside
    cm.match([bytes([10, 1, 2, 3])] * 8)
    return hm, cm


def _drive(hm, cm, rounds=ROUNDS, per=6):
    """`rounds` bursts of `per` hint + `per` route lookups through a
    fresh device-mode service, each burst awaited before the next: every
    burst is at least one cycle, and its last batch is drained with
    nothing pending. -> (stats, the dispatcher's thread id)."""
    svc = ClassifyService(mode="device")
    got = []
    done = threading.Semaphore(0)

    def cb(idx, _pl):
        got.append(idx)
        done.release()

    try:
        for r in range(rounds):
            for i in range(per):
                svc.submit_hint(hm, Hint.of_host(f"s{(r + i) % 64}"
                                                 ".example.com"), cb)
                svc.submit_cidr(cm, bytes([10, (r + i) % 32, 0, 1]), None,
                                cb)
            for _ in range(2 * per):
                assert done.acquire(timeout=30)
        ident = svc._thread.ident
    finally:
        svc.close()
        if svc._thread is not None:
            svc._thread.join(10)    # leaving the last wait records it
            assert not svc._thread.is_alive()
    assert len(got) == rounds * per * 2 and min(got) >= 0
    return svc.stats, ident


@pytest.fixture
def noted(monkeypatch):
    """Every span noted while tracing is on, whatever its trace id:
    (thread id, "plane/span", start ns, duration ns, items, fields)."""
    log = []
    real = trace.note_span

    def note(trace_id, plane, span, t0, dur, cpu_ns=0, items=0, **fields):
        log.append((threading.get_ident(), plane + "/" + span, t0, dur,
                    items, fields))
        real(trace_id, plane, span, t0, dur, cpu_ns, items, **fields)

    monkeypatch.setattr(trace, "note_span", note)
    return log


def _of(log, ident, names):
    return sorted((t0, t0 + dur, name.split("/")[1], items, fields)
                  for th, name, t0, dur, items, fields in log
                  if th == ident and name in {"engine/" + n for n in names})


def test_top_level_spans_tile_the_dispatcher_thread(noted):
    trace.configure(1)
    hm, cm = _matchers()
    stats, ident = _drive(hm, cm)
    tops = _of(noted, ident, TOP)
    n = {name: sum(1 for t in tops if t[2] == name) for name in TOP}
    assert n["cycle"] >= ROUNDS and n["drain"] >= 1 and n["wait"] >= 1
    # a swap is noted less the park inside it: cut it there, into what
    # lies before and after the wait that follows it
    pieces, waits = [], [t for t in tops if t[2] == "wait"]
    for t0, t1, name, _items, fields in tops:
        if name != "swap":
            pieces.append((t0, t1, name))
            continue
        park = fields["park_ns"]
        if not park:
            pieces.append((t0, t1, name))
            continue
        w = next(w for w in waits if w[0] >= t0)
        assert w[1] <= t1 + park        # the wait lies inside the swap
        pieces += [(t0, w[0], name), (w[1], t1 + park, name)]
    pieces.sort()
    gaps = 0
    for a, b in zip(pieces, pieces[1:]):
        assert a[1] <= b[0], f"top-level spans overlap: {a} {b}"
        gaps += b[0] - a[1]
    stretch = pieces[-1][1] - pieces[0][0]
    assert gaps < 0.05 * stretch, (gaps, stretch)
    # every query taken by a swap is a query of the cycle that follows
    swaps = [t for t in tops if t[2] == "swap"]
    cycles = [t for t in tops if t[2] == "cycle"]
    # the acquire of `_cv` alone, noted beside its swap: it begins where
    # the swap does and is the first thing in it
    locks = _of(noted, ident, ("swap_lock",))
    assert len(locks) == len(swaps)
    for lk, sw in zip(locks, swaps):
        assert lk[0] == sw[0] and lk[1] - lk[0] <= \
            sw[1] - sw[0] + sw[4]["park_ns"]
    assert sum(t[3] for t in swaps) == sum(t[3] for t in cycles) \
        == stats.device_queries == ROUNDS * 12
    assert sum(1 for t in swaps if t[3]) == len(cycles)


def test_children_lie_inside_their_cycle_or_drain(noted):
    trace.configure(1)
    hm, cm = _matchers()
    stats, ident = _drive(hm, cm)
    parents = _of(noted, ident, ("cycle", "drain"))
    kids = _of(noted, ident, CHILDREN)
    assert sum(1 for k in kids if k[2] == "dispatch") == stats.dispatches
    by_parent = {}
    for k in kids:
        inside = [i for i, p in enumerate(parents)
                  if p[0] <= k[0] and k[1] <= p[1]]
        assert len(inside) == 1, f"{k} lies in {len(inside)} parents"
        by_parent.setdefault(inside[0], []).append(k)
    for i, ks in by_parent.items():
        for a, b in zip(ks, ks[1:]):
            assert a[1] <= b[0], f"children overlap: {a} {b}"
        if parents[i][2] == "drain":    # a read, its delivery, let go
            assert [k[2] for k in ks] == ["d2h_sync", "deliver", "release"]
    # encode and launch inside their dispatch, as before
    for t0, t1, name, _i, fields in _of(noted, ident, ("encode", "launch")):
        assert fields["parent"] == "dispatch"
        assert any(k[2] == "dispatch" and k[0] <= t0 and t1 <= k[1]
                   for k in kids), (name, t0)


def test_double_buffer_pattern_shares_the_counters_decision(noted):
    trace.configure(1)
    hm, cm = _matchers()
    stats, ident = _drive(hm, cm)
    n = {name: sum(1 for th, nm, *_r in noted if nm == "engine/" + name)
         for name in NEW + ("d2h_sync", "dispatch")}
    assert n["inflight"] == n["d2h_sync"] == n["readback_start"] \
        == n["dispatch"] == n["begin"] == n["release"] \
        == stats.dispatches >= 2 * ROUNDS
    # every hint batch was not ready (_NotReady): one decision, the
    # counter and the span
    assert n["kernel_wait"] == stats.readback_kernel_waits \
        >= stats.batches["hint"] >= ROUNDS
    syncs = {(t0, dur) for _th, nm, t0, dur, _i, _f in noted
             if nm == "engine/d2h_sync"}
    for _th, nm, t0, dur, _i, _f in noted:
        if nm == "engine/kernel_wait":
            assert (t0, dur) in syncs       # the sync's own interval
    # a batch is in flight from its launch's return to the dispatcher's
    # coming for it: its inflight ends, then its sync begins, batch by
    # batch in the order of the one thread that notes both
    sync_t0 = sorted(t0 for t0, _d in syncs)
    ends = sorted(t0 + dur for _th, nm, t0, dur, _i, _f in noted
                  if nm == "engine/inflight")
    for i, (end, t0) in enumerate(zip(ends, sync_t0)):
        assert end <= t0 and (i + 1 == len(ends) or t0 <= ends[i + 1])
    assert 1 <= n["drain"] <= stats.dispatches
    tot = trace.span_totals()
    for name in NEW:
        assert tot["engine/" + name]["n"] >= n[name] > 0


def test_launch_counts_the_numpy_arguments_it_was_handed():
    hm, cm = _matchers()
    hints = [Hint.of_host("s1.example.com")] * 5
    q = E._fused_hint_q(hm.snapshot()[0], hints, 8)
    # the encoded query: 13 numpy columns, every one a view of the one
    # arena the launch is handed
    assert len(q) == 13 and all(
        isinstance(v, np.ndarray) and np.shares_memory(v, q.arena)
        for v in q.values())
    by_hand = 1
    trace.configure(1)
    before = trace.span_totals().get("engine/launch",
                                     {"n": 0, "sum_items": 0})
    np.asarray(hm.dispatch_snap(hm.snapshot(), hints, pad_to=8))
    # a route table ignores ports: address bytes and family, one arena
    np.asarray(cm.dispatch_snap(cm.snapshot(), [bytes([10, 1, 2, 3])] * 3,
                                None, pad_to=4))
    after = trace.span_totals()["engine/launch"]
    assert after["n"] - before["n"] == 2
    assert after["sum_items"] - before["sum_items"] == by_hand + 1
    # the helper: dicts and sequences walked, device arrays count nothing
    import jax.numpy as jnp
    a = np.zeros((4, 16), np.uint8)
    assert E._host_arrays((a, {"x": a, "y": [a, np.int32(3)]}, None,
                           jnp.zeros(4), "s")) == (4, 3 * 64 + 4)


def _served(kind):
    """-> (a served launch of `kind` as the service makes it, the bytes
    of the arena it hands over): lookups at a pad bucket, against the
    published generation."""
    from vproxy_tpu.ops import hashmatch as H
    from vproxy_tpu.rules import maglev as MG
    from vproxy_tpu.rules.ir import AclRule, Proto
    rules = [HintRule(host=f"s{i}.example.com",
                      uri=f"/a{i}" if i % 3 == 0 else None)
             for i in range(64)]
    hints = [Hint(host=f"www.s{i}.example.com", uri="/a3/x")
             for i in range(5)]
    ips = [bytes([10, i, 2, 3]) for i in range(5)]
    nets = [Network.parse(f"10.{i}.0.0/16") for i in range(32)]
    if kind == "hint":
        hm = HintMatcher(rules, backend="jax")
        tab = hm.snapshot()[0]
        lay = H.hint_layout(8, tab.hw, tab.uw, 5, tab.caps["lset"])
        return lambda: hm.dispatch_snap(hm.snapshot(), hints, pad_to=8), lay
    if kind == "route":
        cm = CidrMatcher(nets, backend="jax")
        return lambda: cm.dispatch_snap(cm.snapshot(), ips, [80] * 5,
                                        pad_to=8), H.cidr_layout(8)
    if kind == "acl":
        acl = [AclRule(f"a{i}", n, Proto.TCP, 0, 1000 * i, True)
               for i, n in enumerate(nets)]
        cm = CidrMatcher(nets, backend="jax", acl=acl)
        return lambda: cm.dispatch_snap(cm.snapshot(), ips, [0] * 5,
                                        pad_to=8), \
            H.cidr_layout(8, gated=True)
    if kind == "table_set":
        ts = E.CidrTableSet("v4", backend="jax")
        views = [ts.view(), ts.view()]
        views[0].set_networks(nets[:20])
        views[1].set_networks(nets[::-1])
        keys = [views[i % 2].key for i in range(5)]
        return lambda: ts.dispatch_snap(ts.snapshot(), ips, None, keys,
                                        pad_to=8), \
            H.cidr_layout(8, tid=True)
    payloads = [(h, ip, None) for h, ip in zip(hints, ips)]
    if kind == "fused_pair":
        hm = HintMatcher(rules, backend="jax")
        pair = MG.FusedPair(hm, MG.MaglevMatcher(
            [(f"b{i}", 1) for i in range(8)], m=251))
    else:
        ts = MG.MaglevTableSet(m=251, backend="jax")
        pair = MG.GroupedPair(HintMatcher(backend="jax"), ts)
        ref = ts.alloc()
        ts.install(ref, lambda: (MG.build_table([("x", 10), ("y", 10)], 251),
                                 ["x", "y"], None))
        pair.set_rules(rules, groups=[ref] * len(rules))
        hm = pair.hm
    tab = hm.snapshot()[0]
    lay = H.hint_layout(8, tab.hw, tab.uw, 5, tab.caps["lset"], slots=True)
    return lambda: pair.dispatch_snap(pair.snapshot(), payloads,
                                      pad_to=8), lay


@pytest.mark.parametrize("kind", ["hint", "route", "acl", "table_set",
                                  "fused_pair", "grouped_pair"])
def test_served_launch_is_handed_one_arena(kind, noted):
    """Every served program of the "jax" backend takes its batch as one
    numpy array: `engine/launch` counts 1 item a launch, its h2d_bytes
    are the arena's, and the always-on counter beside the launch counter
    moves by one a launch."""
    launch, layout = _served(kind)
    np.asarray(launch())        # compile outside
    trace.configure(1)
    l0, a0 = E.dispatch_launches_total(), E.launch_host_arrays_total()
    before = trace.span_totals().get("engine/launch",
                                     {"n": 0, "sum_items": 0})
    for _ in range(3):
        out = np.asarray(launch())
    assert out.shape[0] == 8 and (out[:5] >= 0).all()
    after = trace.span_totals()["engine/launch"]
    assert after["n"] - before["n"] == 3
    assert after["sum_items"] - before["sum_items"] == 3
    assert E.dispatch_launches_total() - l0 == 3
    assert E.launch_host_arrays_total() - a0 == 3
    launches = [(items, f) for _th, nm, _t0, _d, items, f in noted
                if nm == "engine/launch"]
    assert [(i, f["h2d_bytes"]) for i, f in launches] \
        == [(1, 4 * layout.words)] * 3


@pytest.mark.parametrize("backend", ["jax", "jax-fp", "jax-dense",
                                     "jax-sharded"])
def test_host_array_counter_is_the_spans_count_on_every_backend(backend):
    """The always-on counter adds what the call site knows; the span
    walks the call's arguments. One number, whatever the backend hands
    its program."""
    nets = [Network.parse(f"10.{i}.0.0/16") for i in range(32)]
    hm = HintMatcher([HintRule(host=f"s{i}.example.com", uri="/a")
                      for i in range(64)], backend=backend)
    cm = CidrMatcher(nets, backend=backend)
    hints = [Hint(host="w.s3.example.com", uri="/a/b")] * 5
    ips = [bytes([10, 3, 2, 1])] * 5
    trace.configure(1)
    a0 = E.launch_host_arrays_total()
    before = trace.span_totals().get("engine/launch",
                                     {"n": 0, "sum_items": 0})
    assert np.asarray(hm.dispatch_snap(hm.snapshot(), hints,
                                       pad_to=8))[:5].tolist() == [3] * 5
    assert np.asarray(cm.dispatch_snap(cm.snapshot(), ips, None,
                                       pad_to=8))[:5].tolist() == [3] * 5
    after = trace.span_totals()["engine/launch"]
    assert after["n"] - before["n"] == 2
    # one arena a launch on "jax"; the sharded backends place their
    # queries themselves and hand over one numpy scalar; the others
    # their columns
    counted = E.launch_host_arrays_total() - a0
    assert counted == after["sum_items"] - before["sum_items"]
    assert counted == 2 if backend in ("jax", "jax-sharded") else counted > 4


def test_tracing_off_the_new_sites_read_no_clock_and_total_nothing(
        noted, monkeypatch):
    import time
    from types import SimpleNamespace
    from vproxy_tpu.rules import service as S
    before = trace.span_totals()
    reads = []
    # service.py's `time`, its monotonic_ns counted (every new site's
    # clock; monotonic() is the submit path's and the latency's)
    clock = SimpleNamespace(
        monotonic=time.monotonic, sleep=time.sleep,
        monotonic_ns=lambda: reads.append(1) or time.monotonic_ns())
    hm, cm = _matchers()
    monkeypatch.setattr(S, "time", clock)
    stats, _ident = _drive(hm, cm, rounds=5)
    assert stats.dispatches >= 5 and not reads and not noted
    assert trace.span_totals() == before
    assert stats.readback_kernel_waits >= stats.batches["hint"]


def test_dispatcher_steps_aside_when_it_queued_for_its_own_lock(monkeypatch):
    """The dispatcher that had to queue for `_cv` for more than half an
    interpreter slice (a holder lost the interpreter: several submitters
    convoying) sleeps as long again before it takes the queue, four
    slices at most; one that got its lock at once never sleeps."""
    import sys
    import time
    from types import SimpleNamespace
    from vproxy_tpu.rules import service as S
    slept = []
    monkeypatch.setattr(S, "time", SimpleNamespace(
        monotonic=time.monotonic, monotonic_ns=time.monotonic_ns,
        sleep=lambda s: slept.append(s) or time.sleep(s)))
    hm, _cm = _matchers()
    real = hm.dispatch_snap.__func__
    busy = threading.Event()

    def slow(self, *a, **kw):       # a cycle long enough to take the lock in
        busy.set()
        time.sleep(0.1)
        return real(self, *a, **kw)

    monkeypatch.setattr(_SlowHint, "dispatch_snap", slow)
    svc = ClassifyService(mode="device")
    got = []
    done = threading.Semaphore(0)
    try:
        def ask():
            svc.submit_hint(hm, Hint.of_host("s1.example.com"),
                            lambda idx, _pl: got.append(idx)
                            or done.release())
        ask()
        # uncontended: no sleep (a loaded machine may take the
        # interpreter from a submitter under the lock for a slice or
        # two; never for the 100 ms this test holds it)
        cap = 4 * sys.getswitchinterval()
        assert done.acquire(timeout=30) and cap not in slept
        busy.clear()
        ask()
        assert busy.wait(30)
        with svc._cv:       # held past the end of the dispatcher's cycle
            time.sleep(0.2)
        assert done.acquire(timeout=30)
        ask()               # the next iterations: it steps aside once
        assert done.acquire(timeout=30)
    finally:
        svc.close()
    assert got == [1, 1, 1]
    # it queued ~100 ms: one step aside, of the four slices' cap
    assert slept.count(cap) == 1 and max(slept) == cap, slept
    assert min(slept) > sys.getswitchinterval() / 2
