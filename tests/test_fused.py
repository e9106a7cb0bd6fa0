"""Fused classify+pick dispatch (ops/fused.py + rules/engine.py).

The one-launch contract: a batch's verdict (hint match) AND pick
(Maglev) come from ONE compiled
program over int8/int32-packed tables, bit-identical to the unfused
op chain, published through the same double-buffered TableInstaller
swap, with the launch counter proving "one launch per batch" instead
of asserting it.
"""
import random
import threading
import time

import numpy as np
import pytest

from vproxy_tpu.rules import engine
from vproxy_tpu.rules.engine import HintMatcher, fused_dispatch, pad_batch
from vproxy_tpu.rules.ir import Hint, HintRule
from vproxy_tpu.rules.maglev import FusedPair, MaglevMatcher, \
    classify_and_pick
from vproxy_tpu.utils import failpoint


@pytest.fixture(autouse=True)
def clean_faults():
    failpoint.clear()
    yield
    failpoint.clear()


def mk_rules(n, seed=11):
    rnd = random.Random(seed)
    out = []
    for i in range(n):
        r = rnd.randrange(20)
        if r < 12:
            out.append(HintRule(host=f"svc{i}.ns{i % 997}.example.com"))
        elif r < 15:
            out.append(HintRule(host=f"svc{i}.ns{i % 997}.example.com",
                                uri=f"/api/v{i % 17}"))
        elif r < 17:
            out.append(HintRule(host=f"svc{i}.ns{i % 997}.example.com",
                                port=443))
        elif r < 19:
            out.append(HintRule(uri=f"/static/{i}"))
        else:
            out.append(HintRule(host="*", uri=f"/w{i % 5}"))
    return out


def mk_queries(rules, b, seed=7):
    rnd = random.Random(seed)
    hints = []
    for i in range(b):
        j = rnd.randrange(len(rules))
        host = rules[j].host
        if host is None or host == "*":
            host = f"nohost{j}.ns.example.com"
        k = i % 4
        if k == 0:
            hints.append(Hint.of_host(host))
        elif k == 1:
            hints.append(Hint.of_host_uri("x." + host, f"/api/v{j % 17}/s"))
        elif k == 2:
            hints.append(Hint.of_host_port(host, 443 if i % 2 else 8443))
        else:
            hints.append(Hint(uri=f"/static/{j}"))
    return hints


def mk_ips(n, seed=5):
    rnd = random.Random(seed)
    return [bytes([10 + rnd.randrange(14), rnd.randrange(256),
                   rnd.randrange(256), rnd.randrange(256)])
            for _ in range(n)]


def _unfused_chain(hm, mm, hints, ips, ports=None):
    """The pre-r12 op chain: hint dispatch + maglev pick dispatch."""
    hsnap, msnap = hm.snapshot(), mm.snapshot()
    v = np.asarray(hm.dispatch_snap(hsnap, hints))
    p = np.asarray(mm.dispatch_snap(msnap, ips, ports))
    return v, p


# ------------------------------------------------------------- parity


def _parity_case(n_rules, b):
    rules = mk_rules(n_rules)
    hm = HintMatcher(rules, backend="jax")
    mm = MaglevMatcher([(f"10.9.{i // 250}.{i % 250}:80", 1 + i % 4)
                        for i in range(11)], m=4099)
    hints = mk_queries(rules, b)
    ips = mk_ips(b)
    ports = [None if i % 3 == 0 else (1024 + i) for i in range(b)]
    rv, rp = _unfused_chain(hm, mm, hints, ips, ports)
    # b is no bucket size, so the encoder writes into its pad bucket:
    # the real rows answer as unpadded, the pad rows match nothing
    cap = pad_batch(b)
    assert cap > b
    out = np.asarray(fused_dispatch(hm, hm.snapshot(), mm, mm.snapshot(),
                                    hints, ips, ports, pad_to=cap))
    assert out.shape[0] == cap and (out[b:, 0] == -1).all()
    out = out[:b]
    assert np.array_equal(rv, out[:, 0]), "verdicts diverged"
    assert np.array_equal(rp, out[:, 1]), "picks diverged"
    # and through the public entry (padding path included)
    v2, p2, _hp, _mp = classify_and_pick(hm, mm, hints, ips, ports)
    assert np.array_equal(rv, v2) and np.array_equal(rp, p2)


def test_fused_parity_randomized_100k():
    """The acceptance bar: randomized 100k-rule table, fused ==
    unfused, verdict AND pick bit-identical."""
    _parity_case(100_000, 500)


def test_fused_parity_uri_free_specialized_table():
    """A generation with zero uri rules packs WITHOUT the uri sweep
    (ops/fused.py static specialization — the bench/production pure-
    host shape); parity must hold including uri-carrying queries."""
    rules = [HintRule(host=f"svc{i}.ns{i % 97}.example.com")
             for i in range(5_000)]
    rules += [HintRule(host="*"), HintRule(host="w.example.com",
                                           port=443)]
    hm = HintMatcher(rules, backend="jax")
    assert "pk_uslot" not in hm.snapshot()[5]  # specialized layout
    mm = MaglevMatcher([(f"b{i}", 1) for i in range(4)], m=251)
    b = 96
    hints = [Hint.of_host(f"svc{i}.ns{i % 97}.example.com")
             for i in range(b - 3)]
    hints += [Hint(host="w.example.com", uri="/ignored", port=443),
              Hint(uri="/only-uri"), Hint()]
    ips = mk_ips(b)
    rv, rp = _unfused_chain(hm, mm, hints, ips)
    out = np.asarray(fused_dispatch(hm, hm.snapshot(), mm,
                                    mm.snapshot(), hints, ips))[:b]
    assert np.array_equal(rv, out[:, 0])
    assert np.array_equal(rp, out[:, 1])


@pytest.mark.slow
@pytest.mark.timeout(1800)
def test_fused_parity_randomized_1m_slow():
    _parity_case(1_000_000, 1000)


def test_fused_pad_rows_never_match():
    rules = mk_rules(300)
    hm = HintMatcher(rules, backend="jax")
    mm = MaglevMatcher([("b0", 1)], m=251)
    hints = mk_queries(rules, 3)
    ips = mk_ips(3)
    out = np.asarray(fused_dispatch(hm, hm.snapshot(), mm, mm.snapshot(),
                                    hints, ips, pad_to=16))
    assert out.shape[0] == 16
    assert (out[3:, 0] == -1).all()  # pad rows: invalid probes only


def test_fused_unavailable_fallbacks():
    """A non-"jax" backend publishes no packed tables; classify_and_pick
    falls back to the overlapped chain with identical results."""
    rules = mk_rules(300)
    hm_host = HintMatcher(rules, backend="host")
    mm = MaglevMatcher([(f"b{i}", 1) for i in range(3)], m=251)
    assert fused_dispatch(hm_host, hm_host.snapshot(), mm, mm.snapshot(),
                          mk_queries(rules, 4), mk_ips(4)) is None
    v, p, _hp, _mp = classify_and_pick(hm_host, mm, mk_queries(rules, 4),
                                       mk_ips(4))
    assert len(v) == 4 and len(p) == 4


# ------------------------------------------------- one-launch counter


def test_fused_one_launch_per_batch_counter():
    """The scrape-verifiable claim: a fused batch moves the dispatch
    launch counter by EXACTLY one; the unfused chain by two."""
    rules = mk_rules(400)
    hm = HintMatcher(rules, backend="jax")
    mm = MaglevMatcher([(f"b{i}", 1) for i in range(4)], m=251)
    hints = mk_queries(rules, 32)
    ips = mk_ips(32)
    classify_and_pick(hm, mm, hints, ips)  # warm both jits
    _unfused_chain(hm, mm, hints, ips)
    l0, f0 = engine.dispatch_launches_total(), \
        engine.fused_dispatches_total()
    v, p, _hp, _mp = classify_and_pick(hm, mm, hints, ips)
    assert engine.dispatch_launches_total() - l0 == 1
    assert engine.fused_dispatches_total() - f0 == 1
    _unfused_chain(hm, mm, hints, ips)
    assert engine.dispatch_launches_total() - l0 == 3  # +2 for the chain
    assert engine.fused_dispatches_total() - f0 == 1
    from vproxy_tpu.utils.metrics import GlobalInspection
    text = GlobalInspection.get().prometheus_string()
    assert "vproxy_engine_dispatch_launches_total" in text
    assert "vproxy_engine_fused_dispatches_total" in text


# --------------------------------------- install-under-fused-load swap


def test_install_under_fused_load_atomic_swap():
    """engine.swap.stall: while a standby install (including the packed
    tables) is deliberately stalled, fused dispatches keep answering
    the OLD generation; after the atomic pub swap, the NEW one — and
    the (verdict, pick) pair always comes from ONE snapshot pair.
    Zero errors, zero torn reads."""
    import os
    os.environ["VPROXY_TPU_SWAP_STALL_S"] = "0.6"
    old = [HintRule(host=f"svc{i}.example.com") for i in range(300)]
    new = [HintRule(host=f"svc{i}.example.org") for i in range(300)]
    hm = HintMatcher(old, backend="jax")
    mm = MaglevMatcher([(f"b{i}", 1) for i in range(4)], m=251)
    h_old = Hint.of_host("svc7.example.com")   # 7 in old, -1 in new
    h_new = Hint.of_host("svc7.example.org")   # -1 in old, 7 in new
    ip = bytes([10, 0, 0, 7])
    classify_and_pick(hm, mm, [h_old, h_new], [ip, ip])  # warm
    want_pick = mm.pick_one(ip)

    failpoint.arm("engine.swap.stall", count=1)
    th = threading.Thread(target=lambda: hm.set_rules(new), daemon=True)
    gen0 = hm.generation
    th.start()
    t0 = time.monotonic()
    answered = 0
    first_gen = None
    while time.monotonic() - t0 < 5.0:
        v, p, _hp, _mp = classify_and_pick(hm, mm, [h_old, h_new],
                                           [ip, ip])
        assert int(v[0]) in (7, -1) and int(v[1]) in (7, -1), v
        assert int(p[0]) == want_pick and int(p[1]) == want_pick
        if first_gen is None:
            first_gen = hm.generation
        answered += 1
        if hm.generation > gen0:
            break
    th.join(timeout=10)
    assert not th.is_alive()
    assert hm.generation == gen0 + 1
    assert answered >= 1 and first_gen == gen0
    # post-swap: the NEW generation's packed tables serve
    v, p, _hp, _mp = classify_and_pick(hm, mm, [h_old, h_new], [ip, ip])
    assert int(v[0]) == -1 and int(v[1]) == 7
    assert hm.fused_stat()["available"]


def test_maglev_install_swaps_pick_atomically():
    hm = HintMatcher(mk_rules(64), backend="jax")
    mm = MaglevMatcher([("only:1", 1)], m=251)
    ips = mk_ips(16)
    hints = mk_queries(hm.rules, 16)
    v, p, _hp, _mp = classify_and_pick(hm, mm, hints, ips)
    assert (np.asarray(p) == 0).all()
    mm.set_backends([("only:1", 1), ("second:2", 1)])
    v, p, _hp, _mp = classify_and_pick(hm, mm, hints, ips)
    msnap = mm.snapshot()
    for i, ip in enumerate(ips):
        assert int(p[i]) == mm.pick_snap(msnap, ip)
    assert set(np.asarray(p).tolist()) <= {0, 1}


# ------------------------------------------------- service + step loop


def test_service_cpick_batch_and_inline():
    from vproxy_tpu.rules.service import ClassifyService
    rules = mk_rules(300)
    hm = HintMatcher(rules, backend="jax")
    mm = MaglevMatcher([(f"b{i}", 1) for i in range(5)], m=251)
    pair = FusedPair(hm, mm)
    hints = mk_queries(rules, 24)
    ips = mk_ips(24)
    msnap = mm.snapshot()
    hsnap = hm.snapshot()

    svc = ClassifyService(mode="device")
    try:
        got = {}
        evs = []
        for i in range(24):
            ev = threading.Event()
            evs.append(ev)
            svc.submit_classify_pick(
                pair, hints[i], ips[i], None,
                lambda v, p, pl, i=i, ev=ev: (got.__setitem__(i, (v, p)),
                                              ev.set()))
        for ev in evs:
            assert ev.wait(30)
        for i in range(24):
            assert got[i][0] == hm.index_snap(hsnap, hints[i])
            assert got[i][1] == mm.pick_snap(msnap, ips[i])
        assert svc.stats.dispatches >= 1
    finally:
        svc.close()

    # lone query in auto mode: the inline host lane answers (v, p)
    svc2 = ClassifyService(mode="auto")
    try:
        res = []
        ev = threading.Event()
        svc2.submit_classify_pick(pair, hints[3], ips[3], None,
                                  lambda v, p, pl: (res.append((v, p)),
                                                    ev.set()))
        assert ev.wait(10)
        assert res[0] == (hm.index_snap(hsnap, hints[3]),
                          mm.pick_snap(msnap, ips[3]))
    finally:
        svc2.close()


def test_service_cpick_device_fault_fails_over_to_host():
    from vproxy_tpu.rules.service import ClassifyService
    rules = mk_rules(300)
    hm = HintMatcher(rules, backend="jax")
    mm = MaglevMatcher([(f"b{i}", 1) for i in range(3)], m=251)
    pair = FusedPair(hm, mm)
    hints = mk_queries(rules, 8)
    ips = mk_ips(8)
    hsnap, msnap = hm.snapshot(), mm.snapshot()
    failpoint.arm("device.dispatch.error", count=1)
    svc = ClassifyService(mode="device")
    try:
        got = {}
        evs = []
        for i in range(8):
            ev = threading.Event()
            evs.append(ev)
            svc.submit_classify_pick(
                pair, hints[i], ips[i], None,
                lambda v, p, pl, i=i, ev=ev: (got.__setitem__(i, (v, p)),
                                              ev.set()))
        for ev in evs:
            assert ev.wait(30)
        # the batch that hit the fault served from the host planes —
        # same winners, zero failed queries
        for i in range(8):
            assert got[i] == (hm.index_snap(hsnap, hints[i]),
                              mm.pick_snap(msnap, ips[i]))
        assert svc.stats.failovers >= 1
    finally:
        svc.close()


def test_steploop_fused_pick_and_degraded_host_path():
    from vproxy_tpu.cluster.submit import StepLoop
    rules = mk_rules(300)
    hm = HintMatcher(rules, backend="jax")
    mm = MaglevMatcher([(f"b{i}", 1) for i in range(4)], m=251)
    hints = mk_queries(rules, 4)
    ips = mk_ips(4)
    hsnap, msnap = hm.snapshot(), mm.snapshot()
    sl = StepLoop(hm, None, step_ms=1, batch_cap=8, timeout_ms=2000,
                  maglev=mm)
    assert sl.status()["fused"]
    sl.start()
    try:
        out, out2 = [], []
        ev, ev2 = threading.Event(), threading.Event()
        sl.submit_pick(hints[0], ips[0], None,
                       lambda v, p, pl: (out.append((v, p)), ev.set()))
        sl.submit(hints[1], lambda v, pl: (out2.append(v), ev2.set()))
        assert ev.wait(15) and ev2.wait(15)
        assert out[0] == (hm.index_snap(hsnap, hints[0]),
                          mm.pick_snap(msnap, ips[0]))
        assert out2[0] == hm.index_snap(hsnap, hints[1])
        # degraded serving keeps picks flowing from the host planes
        sl.degraded = True
        ev3 = threading.Event()
        out3 = []
        sl.submit_pick(hints[2], ips[2], None,
                       lambda v, p, pl: (out3.append((v, p)), ev3.set()))
        assert ev3.wait(15)
        assert out3[0] == (hm.index_snap(hsnap, hints[2]),
                          mm.pick_snap(msnap, ips[2]))
    finally:
        sl.stop()


def test_steploop_submit_pick_requires_maglev():
    from vproxy_tpu.cluster.submit import StepLoop
    sl = StepLoop(HintMatcher(mk_rules(8), backend="jax"), None,
                  step_ms=1, batch_cap=4, timeout_ms=500)
    with pytest.raises(ValueError):
        sl.submit_pick(Hint.of_host("x.example.com"), b"\x00" * 4, None,
                       lambda v, p, pl: None)
