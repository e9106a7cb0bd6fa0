"""Asynchronous readback — a device batch's device->host copy starts
when its launch returns (service._start_readback) and is awaited where
it always was, in _finish_inflight.

A fake device result records what the dispatcher asks of it and when:
the copy starts once a batch, after the launch, before that batch's
__array__ and before the next batch's encode; verdicts and their order
do not change; a result without the method is read as before; a copy
that cannot start loses nothing; the two counters move by one a device
batch. Then the five served programs and the host-pick wrapper, as they
are.
"""
import threading

import numpy as np
import pytest

from vproxy_tpu.rules import maglev as MG
from vproxy_tpu.rules import service
from vproxy_tpu.rules.engine import CidrMatcher, CidrTableSet, HintMatcher
from vproxy_tpu.rules.ir import Hint, HintRule
from vproxy_tpu.rules.service import ClassifyService
from vproxy_tpu.utils.ip import Network, mask_bytes

TIMEOUT = 30


@pytest.fixture(autouse=True)
def fresh_service():
    ClassifyService.reset()
    yield
    ClassifyService.reset()


def hint_matcher(n=64):
    m = HintMatcher([HintRule(host=f"svc{i}.example.com") for i in range(n)])
    m.match([Hint.of_host("warm.example.com")] * 16)    # compile here
    return m


def host_of(i):
    return Hint.of_host(f"svc{i}.example.com")


class Result:
    """What a device batch returns, recording on `log` what is asked of
    it. copy: "ok" | "raises" | "absent" (the result has no such
    method); array: "ok" | "raises"."""

    def __init__(self, log, k, value, ready=True, copy="ok", array="ok"):
        self._log, self._k, self._value = log, k, value
        self._ready, self._array = ready, array
        if copy != "absent":
            self.copy_to_host_async = self._copy
            self.is_ready = self._is_ready
        self._copy_raises = copy == "raises"

    def _copy(self):
        self._log.append(("copy", self._k))
        if self._copy_raises:
            raise RuntimeError("no transfer manager")

    def _is_ready(self):
        self._log.append(("ready?", self._k))
        return self._ready

    def __array__(self, dtype=None, copy=None):
        self._log.append(("array", self._k))
        if self._array == "raises":
            raise RuntimeError("device dropped")
        return np.asarray(self._value)


class Device:
    """A HintMatcher whose dispatch_snap hands back `Result`s: batch k
    is made with results[k]'s keywords; a batch whose spec says
    `hold=True` stays inside its launch until release() — the next
    batch's queries are submitted meanwhile, so the dispatcher finds
    them pending when the launch returns."""

    def __init__(self, specs):
        self.m = hint_matcher()
        self.log, self.sizes = [], []
        self._specs = specs
        self._real = self.m.dispatch_snap
        self.in_launch, self._go = threading.Event(), threading.Event()
        self.m.dispatch_snap = self._dispatch

    def release(self):
        self._go.set()

    def _dispatch(self, snap, hints, **kw):
        k = len(self.sizes)
        spec = dict(self._specs[k])
        self.log.append(("encode", k))
        self.sizes.append(len(hints))
        value = np.asarray(self._real(snap, hints, **kw))
        if spec.pop("hold", False):
            self.in_launch.set()
            assert self._go.wait(TIMEOUT)
        self.log.append(("launched", k))
        if spec.pop("numpy", False):
            return value
        return Result(self.log, k, value, **spec)


def run_two_batches(svc, dev, na=20, nb=12):
    """Batch 0 (na queries) is held in its launch while batch 1's nb
    are submitted: -> the verdicts in the order the callbacks ran. A
    gate query on a matcher of its own holds the dispatcher while batch
    0's queries pile up, so each is ONE batch."""
    gate_m = hint_matcher(4)
    got, done = [], threading.Event()
    entered, release = threading.Event(), threading.Event()

    def gate(*_verdict):
        entered.set()
        assert release.wait(TIMEOUT)

    def cb(idx, _payload):
        got.append(idx)
        if len(got) == na + nb:
            done.set()

    svc.submit_hint(gate_m, Hint.of_host("gate.example.com"), gate)
    assert entered.wait(TIMEOUT)
    before = counters(svc)
    for i in range(na):
        svc.submit_hint(dev.m, host_of(i), cb)
    release.set()
    assert dev.in_launch.wait(TIMEOUT)
    for i in range(nb):
        svc.submit_hint(dev.m, host_of(40 + i), cb)
    dev.release()
    assert done.wait(TIMEOUT)
    assert dev.sizes == [na, nb]
    return got, before


def counters(svc) -> dict:
    st = svc.stats
    with st.lock:
        return {"batches": st.dispatches, "device_queries": st.device_queries,
                "oracle_queries": st.oracle_queries,
                "failovers": st.failovers,
                "prefetch": st.readback_prefetch,
                "kernel_waits": st.readback_kernel_waits}


def moved(svc, before) -> dict:
    now = counters(svc)
    return {k: now[k] - before[k] for k in now}


# ------------------------------------------------- when the copy starts

@pytest.mark.parametrize("ready", [(True, True), (False, True),
                                   (True, False), (False, False)])
def test_copy_starts_at_launch_before_the_next_batch_encodes(ready):
    """Two batches through the double buffer: each one's copy starts as
    its launch returns — batch 0's before batch 1's encode begins —
    and each is awaited (is it ready? then __array__) only when the
    dispatcher comes to deliver it, in order. The counters: one
    prefetch a device batch, one kernel wait a batch that was not
    ready."""
    dev = Device([{"hold": True, "ready": ready[0]}, {"ready": ready[1]}])
    svc = ClassifyService(mode="device")
    try:
        got, before = run_two_batches(svc, dev)
        delta = moved(svc, before)
    finally:
        svc.close()
    assert dev.log == [
        ("encode", 0), ("launched", 0), ("copy", 0),
        ("encode", 1), ("launched", 1), ("copy", 1),
        ("ready?", 0), ("array", 0), ("ready?", 1), ("array", 1)]
    assert got == list(range(20)) + list(range(40, 52))
    assert delta == {"batches": 2, "device_queries": 32,
                     "oracle_queries": 0, "failovers": 0, "prefetch": 2,
                     "kernel_waits": ready.count(False)}


# ----------------------------- results the early copy does not apply to

@pytest.mark.parametrize("first", [
    {"copy": "absent"},              # a wrapper with __array__ alone
    {"numpy": True},                 # dispatch_snap's host returns
    {"copy": "raises"},              # the copy cannot be started
    {"copy": "raises", "ready": False},
], ids=["no_method", "numpy", "copy_raises", "copy_raises_not_ready"])
def test_batch_without_a_started_copy_is_read_blocking(first, monkeypatch):
    """Batch 0's copy is not started (no such method, or it raises):
    the batch stays in flight and is delivered from the blocking read,
    every verdict, in order, the device not marked down; batch 1 beside
    it is prefetched as ever."""
    logged = []

    class Log:
        @staticmethod
        def error(msg, exc=False):
            logged.append((msg, exc))
        alert = error

    monkeypatch.setattr(service, "_log", Log)
    dev = Device([dict(first, hold=True), {}])
    svc = ClassifyService(mode="device")
    try:
        got, before = run_two_batches(svc, dev)
        delta = moved(svc, before)
        assert svc.device_ok()
    finally:
        svc.close()
    assert got == list(range(20)) + list(range(40, 52))
    raises = first.get("copy") == "raises"
    assert delta == {"batches": 2, "device_queries": 32,
                     "oracle_queries": 0, "failovers": 0, "prefetch": 1,
                     "kernel_waits": int(first.get("ready") is False)}
    assert len(logged) == int(raises) and all(exc for _m, exc in logged)
    assert [e for e in dev.log if e[1] == 1] == [
        ("encode", 1), ("launched", 1), ("copy", 1), ("ready?", 1),
        ("array", 1)]
    want0 = [("encode", 0), ("launched", 0)]
    if raises:
        want0 += [("copy", 0), ("ready?", 0)]
    if not first.get("numpy"):
        want0.append(("array", 0))
    assert [e for e in dev.log if e[1] == 0] == want0


@pytest.mark.parametrize("copy", ["ok", "raises"])
def test_failing_blocking_read_still_degrades_to_the_oracle(copy):
    """The blocking read is where a dead device shows, prefetched or
    not: that batch is answered by the host index, the device marked
    down, nothing lost; it is no device batch, so neither counter
    moves for it."""
    dev = Device([{"hold": True, "copy": copy, "array": "raises"}, {}])
    svc = ClassifyService(mode="device")
    try:
        got, before = run_two_batches(svc, dev)
        delta = moved(svc, before)
        assert not svc.device_ok()
    finally:
        svc.close()
    assert got == list(range(20)) + list(range(40, 52))
    assert delta == {"batches": 1, "device_queries": 12,
                     "oracle_queries": 20, "failovers": 1, "prefetch": 1,
                     "kernel_waits": 0}


# ------------------------------------------ the served programs, as is

def _cidr_nets(n=32):
    return [Network(bytes([10, i, 0, 0]), mask_bytes(16)) for i in range(n)]


def _grouped_pair(installed: bool):
    """A GroupedPair over 4 groups; not installed: the set holds no
    table, so the pair classifies on the device and picks on the host
    (maglev._HostPickRows)."""
    ts = MG.MaglevTableSet(m=251, backend="jax")
    pair = MG.GroupedPair(HintMatcher(backend="jax"), ts)
    refs = [ts.alloc() for _ in range(4)]
    if installed:
        for g, ref in enumerate(refs):
            entries = [(f"g{g}|10.0.{g}.{b}:80", 10) for b in range(3)]
            ts.install(ref, lambda e=entries: (
                MG.build_table(e, ts.m), [n for n, _w in e], g), wait=True)
    pair.set_rules([HintRule(host=f"svc{i}.example.com") for i in range(64)],
                   groups=[refs[i % 4] for i in range(64)])
    return pair


PROGRAMS = ["hint_hash_match", "cidr_hash_match", "cidr_set_match",
            "fused_classify_pick", "fused_group_pick", "host_pick_rows"]


@pytest.mark.parametrize("program", PROGRAMS)
def test_every_served_program_is_prefetched_by_its_result_type(program):
    """The real results: a jax array (each of the five programs) has
    copy_to_host_async, so every device batch is prefetched; the
    GroupedPair's host-pick wrapper has none and is read as before.
    The verdicts are the host index's either way."""
    n = 24
    hosts = [host_of(i) for i in range(n)]
    ips = [bytes([10, i, 1, 2]) for i in range(n)]
    svc = ClassifyService(mode="device")
    got, done = {}, threading.Event()

    def cb(i):
        def f(*verdict):
            got[i] = verdict[:-1]
            if len(got) == n:
                done.set()
        return f

    if program == "hint_hash_match":
        m = hint_matcher()
        want = [(m.index_snap(m.snapshot(), h),) for h in hosts]
        submit = lambda i: svc.submit_hint(m, hosts[i], cb(i))  # noqa: E731
    elif program == "cidr_hash_match":
        m = CidrMatcher(_cidr_nets())
        want = [(m.index_snap(m.snapshot(), a, None),) for a in ips]
        submit = lambda i: svc.submit_cidr(m, ips[i], None, cb(i))  # noqa: E731
    elif program == "cidr_set_match":
        ts = CidrTableSet("v4", backend="jax")
        views = [ts.view(), ts.view()]
        views[0].set_networks(_cidr_nets())
        views[1].set_networks(_cidr_nets()[::-1])
        want = [(i if i % 2 == 0 else 31 - i,) for i in range(n)]
        submit = lambda i: svc.submit_cidr(  # noqa: E731
            views[i % 2], ips[i], None, cb(i))
    else:
        if program == "fused_classify_pick":
            m = MG.FusedPair(hint_matcher(), MG.MaglevMatcher(
                [(f"b{i}", 1) for i in range(5)], m=251))
        else:
            m = _grouped_pair(installed=program == "fused_group_pick")
        snap = m.snapshot()
        want = [tuple(int(x) for x in m.index_snap(snap, (h, a, None)))
                for h, a in zip(hosts, ips)]
        submit = lambda i: svc.submit_classify_pick(  # noqa: E731
            m, hosts[i], ips[i], None, cb(i))
    try:
        for i in range(n):
            submit(i)
        assert done.wait(60)
        st = counters(svc)
    finally:
        svc.close()
    assert [got[i] for i in range(n)] == want
    if program in ("fused_group_pick", "host_pick_rows"):
        picked = [p for _v, p in want if p >= 0]
        assert len(picked) == (n if program == "fused_group_pick" else 0)
    assert st["batches"] >= 1 and st["device_queries"] == n
    assert st["oracle_queries"] == st["failovers"] == 0
    assert st["prefetch"] == (0 if program == "host_pick_rows"
                              else st["batches"])
    assert 0 <= st["kernel_waits"] <= st["prefetch"]


def test_counters_are_on_metrics():
    from vproxy_tpu.utils.metrics import GlobalInspection
    svc = ClassifyService.get()
    svc.mode = "device"
    dev = Device([{"hold": True, "ready": False}, {}])
    _got, before = run_two_batches(svc, dev)
    assert moved(svc, before)["prefetch"] == 2
    now = counters(svc)
    text = GlobalInspection.get().prometheus_string()
    assert f"vproxy_engine_readback_prefetch_total {now['prefetch']}\n" \
        in text
    assert ("vproxy_engine_readback_kernel_waits_total "
            f"{now['kernel_waits']}\n") in text
