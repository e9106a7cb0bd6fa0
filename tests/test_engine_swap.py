"""Double-buffered generation installs (rules/engine.py TableInstaller).

The stall-free contract: set_rules() compiles + uploads a STANDBY table
on the background installer thread and publishes with ONE atomic tuple
swap. Dispatchers keep answering the old generation for the entire
compile — provable with the `engine.swap.stall` failpoint — and flip
atomically after: zero torn or failed queries, ever.
"""
import threading
import time

import numpy as np
import pytest

from vproxy_tpu.rules import engine
from vproxy_tpu.rules.engine import HintMatcher, CidrMatcher, TableInstaller
from vproxy_tpu.rules.ir import Hint, HintRule
from vproxy_tpu.utils import failpoint


@pytest.fixture(autouse=True)
def clean_faults():
    failpoint.clear()
    yield
    failpoint.clear()


def mk_rules(n, dom="example.com"):
    return [HintRule(host=f"svc{i}.{dom}") for i in range(n)]


def test_set_rules_publishes_via_installer_and_waits():
    m = HintMatcher(mk_rules(8))
    g0 = m.generation
    m.set_rules(mk_rules(12))
    assert m.generation == g0 + 1
    assert m.size() == 12
    assert int(m.match([Hint.of_host("svc11.example.com")])[0]) == 11
    # the module-wide publish counter moved too (feeds the gauge)
    assert engine.generation_total() >= m.generation


def test_dispatch_serves_old_generation_through_stalled_install():
    """Arm engine.swap.stall: the install sleeps inside the worker.
    Every query during the stall answers from the OLD generation; after
    the swap, the NEW one — no torn reads, no failures, no waiting."""
    import os
    os.environ["VPROXY_TPU_SWAP_STALL_S"] = "0.6"
    old = mk_rules(300)                       # > SMALL_TABLE: device path
    new = mk_rules(300, dom="example.org")    # disjoint winner set
    m = HintMatcher(old)
    m.match([Hint.of_host("warm.example.com")] * 4)  # warm jit
    h_old = Hint.of_host("svc7.example.com")   # 7 in old, -1 in new
    h_new = Hint.of_host("svc7.example.org")   # -1 in old, 7 in new

    failpoint.arm("engine.swap.stall", count=1)
    t_install = threading.Thread(target=lambda: m.set_rules(new),
                                 daemon=True)
    gen0 = m.generation
    t0 = time.monotonic()
    t_install.start()
    flips = []
    answered = 0
    while time.monotonic() - t0 < 5.0:
        snap = m._pub
        a = int(m.match([h_old])[0])
        b = int(m.match([h_new])[0])
        # legal states: old generation (7, -1) or new generation (-1, 7)
        # — since match() snapshots per call, a flip mid-pair may pair
        # old/new answers, but each answer must belong to SOME
        # generation: never (a, b) == (7, 7)-from-one-snapshot or a
        # failure. Assert per-answer legality:
        assert a in (7, -1), a
        assert b in (7, -1), b
        answered += 2
        flips.append(m.generation)
        if m.generation > gen0:
            break
    t_install.join(timeout=10)
    assert not t_install.is_alive()
    assert m.generation == gen0 + 1
    # during the armed stall (>= 0.6s) the old generation kept serving
    assert answered >= 2
    assert flips[0] == gen0, "first answers must ride the old generation"
    # post-swap the new rules serve
    assert int(m.match([h_new])[0]) == 7
    assert int(m.match([h_old])[0]) == -1


def test_stalled_install_does_not_block_dispatch_latency():
    """While an install is stalled 0.6s, lone host-index answers keep
    their microsecond latency (the old p99-killer was the GIL-holding
    synchronous compile in the mutation path)."""
    import os
    os.environ["VPROXY_TPU_SWAP_STALL_S"] = "0.6"
    m = HintMatcher(mk_rules(1000))
    failpoint.arm("engine.swap.stall", count=1)
    th = threading.Thread(target=lambda: m.set_rules(mk_rules(1000)),
                          daemon=True)
    th.start()
    time.sleep(0.05)  # the worker is inside the stall now
    lats = []
    for i in range(200):
        t0 = time.perf_counter()
        snap = m.snapshot()
        idx = m.index_snap(snap, Hint.of_host(f"svc{i}.example.com"))
        lats.append(time.perf_counter() - t0)
        assert idx == i
    th.join(timeout=10)
    # p99 of host-index answers under a stalled install stays < 5ms
    # (generous: CI-grade GIL noise, not a perf claim)
    assert sorted(lats)[int(len(lats) * 0.99)] < 5e-3


def test_coalesced_installs_last_writer_wins():
    m = HintMatcher(mk_rules(4))
    tickets = [TableInstaller.get().submit(
        m, (mk_rules(4 + k), None)) for k in range(6)]
    for t in tickets:
        t.ev.wait(10)
    assert engine.flush_installs(timeout=10)
    assert m.size() in (9,)  # the newest pending list won
    assert int(m.match_one(Hint.of_host("svc8.example.com"))) == 8


def test_install_error_propagates_to_waiter_and_keeps_serving():
    from vproxy_tpu.ops.tables import MAX_HOST
    m = HintMatcher(mk_rules(4))
    with pytest.raises(ValueError):
        m.set_rules([HintRule(host="x" * (MAX_HOST + 10))])
    # the published generation still serves
    assert m.match_one(Hint.of_host("svc1.example.com")) == 1


def test_cidr_set_networks_rides_installer():
    from vproxy_tpu.utils.ip import Network, mask_bytes
    nets = [Network(bytes([10, 0, i, 0]), mask_bytes(24)) for i in range(8)]
    cm = CidrMatcher(nets)
    g0 = cm.generation
    cm.set_networks(nets + [Network(bytes([10, 1, 0, 0]), mask_bytes(16))])
    assert cm.generation == g0 + 1
    assert cm.match_one(bytes([10, 1, 2, 3])) == 8


def test_swap_metrics_and_table_bytes_surface():
    from vproxy_tpu.utils.metrics import GlobalInspection
    gi = GlobalInspection.get()
    m = HintMatcher(mk_rules(200))
    before = gi.get_histogram("vproxy_engine_swap_ms", reservoir=512)
    n0 = before.value()
    m.set_rules(mk_rules(210))
    hist = gi.get_histogram("vproxy_engine_swap_ms", reservoir=512)
    assert hist.value() > n0
    text = gi.prometheus_string()
    assert "vproxy_engine_generation" in text
    assert 'vproxy_engine_table_bytes{matcher="hint"}' in text
    assert m.published_table_bytes() > 0
    assert engine.table_bytes_total("hint") >= m.published_table_bytes()
    snap = gi.bench_snapshot()
    assert "vproxy_engine_generation" in snap
    assert snap["vproxy_engine_generation"] >= m.generation


@pytest.mark.parametrize("ranges,width,hops,share", [(3, 4, 1, 0.0),
                                                     (40, 16, 3, 0.5)])
def test_cidr_bucket_gauges_surface(ranges, width, hops, share):
    """The bucket layout of an installed cidr table reads where
    table-bytes does: per table in `list-detail security-group`, over
    all live tables on /metrics. A one-hop table, and one network under
    40 port ranges (three rows a bucket: two hops past the first)."""
    from vproxy_tpu.control.app import Application
    from vproxy_tpu.control.command import Command
    from vproxy_tpu.utils.metrics import GlobalInspection
    app = Application.create(workers=1)
    try:
        Command.execute(app, "add security-group g default deny")
        for i in range(ranges):     # one network, `ranges` port ranges
            Command.execute(
                app, f"add security-group-rule f{i} to security-group g "
                f"network 10.1.0.0/16 protocol tcp "
                f"port-range {100 * i},{100 * i + 50} default allow")
        Command.execute(
            app, "add security-group-rule o to security-group g "
            "network 10.2.0.0/24 protocol tcp port-range 1,9 default allow")
        g = app.security_groups["g"]
        m = g._tables[next(iter(g._tables))][0]
        st = m.bucket_stat()
        # each network sits in 3 groups' tables (v4, ::v4, ::ffff:v4)
        assert st == {"width": width, "hops": hops, "used_slots": 6,
                      "overflow_slots": 3 if hops > 1 else 0}
        line, = Command.execute(app, "list-detail security-group")
        assert (f"tcp backend {m.backend} rules {ranges + 1} "
                f"table-bytes {m.published_table_bytes()} "
                f"bucket-width {width} hops {hops} "
                f"overflow-share {share:.4f}") in line
        total = engine.cidr_bucket_stat()   # every live table's
        assert total["width"] >= width and total["hops"] >= hops
        if hops > 1:
            assert total["overflow_share"] > 0
        text = GlobalInspection.get().prometheus_string()
        for name in ("vproxy_engine_cidr_bucket_width",
                     "vproxy_engine_cidr_lookup_hops",
                     "vproxy_engine_cidr_overflow_share"):
            assert f"\n{name} " in text
        assert 'vproxy_engine_table_bytes{matcher="cidr"}' in text
    finally:
        app.close()


def test_default_mesh_cache_keyed_on_devices_and_batch(monkeypatch):
    """The old module-global _MESH was never invalidated — a batch-knob
    (or device-set) change after first use served a stale mesh."""
    m1 = engine.default_mesh()
    assert engine.default_mesh() is m1  # cached on identical key
    monkeypatch.setenv("VPROXY_TPU_MESH_BATCH", "2")
    m2 = engine.default_mesh()
    assert m2 is not m1
    assert m2.shape["batch"] == 2
    monkeypatch.delenv("VPROXY_TPU_MESH_BATCH", raising=False)
    m3 = engine.default_mesh()
    assert m3.shape["batch"] == 1


def test_mesh_backend_auto_selection(monkeypatch):
    """default_backend(): explicit env wins; forced-CPU meshes shard
    only when VPROXY_TPU_MESH_SERVE=1 (virtual devices share a socket);
    off switch honored."""
    monkeypatch.delenv("VPROXY_TPU_MATCHER", raising=False)
    monkeypatch.setenv("VPROXY_TPU_MESH_SERVE", "1")
    assert engine.default_backend() == "jax-sharded"
    monkeypatch.setenv("VPROXY_TPU_MESH_BACKEND", "jax-fp-sharded")
    assert engine.default_backend() == "jax-fp-sharded"
    monkeypatch.setenv("VPROXY_TPU_MESH_SERVE", "0")
    assert engine.default_backend() == "jax"
    # auto on the virtual CPU mesh: single-device serving (opt-in only)
    monkeypatch.setenv("VPROXY_TPU_MESH_SERVE", "auto")
    assert engine.default_backend() == "jax"
    monkeypatch.setenv("VPROXY_TPU_MATCHER", "jax-fp")
    assert engine.default_backend() == "jax-fp"


def test_mesh_serve_matcher_end_to_end(monkeypatch):
    """A matcher built under VPROXY_TPU_MESH_SERVE=1 lands on the
    sharded backend and serves parity with the oracle."""
    monkeypatch.delenv("VPROXY_TPU_MATCHER", raising=False)
    monkeypatch.setenv("VPROXY_TPU_MESH_SERVE", "1")
    rules = mk_rules(300)
    m = HintMatcher(rules)
    assert m.backend == "jax-sharded"
    got = m.match([Hint.of_host(f"svc{i}.example.com") for i in range(32)])
    assert list(got) == list(range(32))
    # a generation install on the sharded backend swaps atomically too
    m.set_rules(mk_rules(300, dom="example.org"))
    assert int(m.match([Hint.of_host("svc3.example.org")])[0]) == 3
