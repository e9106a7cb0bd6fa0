"""Metrics registry + GlobalInspection HTTP surface.

Reference analogs: prometheus/Metrics.java text exposition,
GlobalInspection.java dumps, TestPrometheus.
"""
import json
import socket
import threading
import time

import numpy as np
import pytest

from vproxy_tpu.net.eventloop import SelectorEventLoop
from vproxy_tpu.utils.events import FlightRecorder
from vproxy_tpu.utils.metrics import (Counter, Gauge, GaugeF, GlobalInspection,
                                      Histogram, MetricsRegistry,
                                      launch_inspection_http)


def http_get(port, path):
    s = socket.create_connection(("127.0.0.1", port), timeout=3)
    s.sendall(b"GET %s HTTP/1.1\r\nHost: x\r\nConnection: close\r\n\r\n"
              % path.encode())
    buf = b""
    while True:
        d = s.recv(65536)
        if not d:
            break
        buf += d
    s.close()
    head, _, body = buf.partition(b"\r\n\r\n")
    return int(head.split()[1]), body


def test_registry_text_format():
    r = MetricsRegistry()
    c = r.counter("vproxy_requests_total", loop="w0")
    c.incr(3)
    g = r.gauge("vproxy_conns")
    g.set(7)
    r.gauge_f("vproxy_dyn", lambda: 1.5)
    text = r.prometheus_text()
    assert '# TYPE vproxy_requests_total counter' in text
    assert 'vproxy_requests_total{loop="w0"} 3' in text
    assert "vproxy_conns 7" in text
    assert "vproxy_dyn 1.5" in text


def test_global_inspection_http():
    loop = SelectorEventLoop("gi")
    loop.loop_thread()
    time.sleep(0.05)  # loop registers itself on first spin
    srv = launch_inspection_http(loop, "127.0.0.1", 0)
    port = srv.port
    try:
        st, body = http_get(port, "/metrics")
        assert st == 200
        assert b"vproxy_event_loop_count" in body
        assert b"vproxy_open_fd_count" in body
        st, body = http_get(port, "/jstack")
        assert st == 200 and b"Thread" in body
        st, body = http_get(port, "/lsof")
        assert st == 200 and body.strip()
        st, body = http_get(port, "/healthz")
        assert st == 200 and body == b"OK"
    finally:
        srv.close()
        loop.close()


def test_histogram_buckets():
    """log2 bucket placement: each observation lands in the smallest
    bucket whose upper bound covers it; _bucket lines are cumulative."""
    h = Histogram("lat_us", buckets=8)
    for v, want in ((0.5, 1), (1.0, 1), (1.5, 2), (2.0, 2), (3.0, 4),
                    (4.0, 4), (100.0, 128), (128.0, 128)):
        before = dict(zip([1 << k for k in range(8)] + ["+Inf"],
                          h._counts))
        h.observe(v)
        after = dict(zip([1 << k for k in range(8)] + ["+Inf"], h._counts))
        assert after[want] == before[want] + 1, (v, want)
    # past the last bound -> +Inf
    h.observe(1e9)
    assert h._counts[-1] == 1
    assert h._count == 9


def test_histogram_exposition():
    r = MetricsRegistry()
    h = r.histogram("vproxy_lat_us", buckets=4, stage="acl")
    for v in (1, 2, 3, 100):
        h.observe(v)
    text = r.prometheus_text()
    assert "# TYPE vproxy_lat_us histogram" in text
    # cumulative: le=1 -> 1, le=2 -> 2, le=4 -> 3, le=8 -> 3, +Inf -> 4
    assert 'vproxy_lat_us_bucket{le="1",stage="acl"} 1' in text
    assert 'vproxy_lat_us_bucket{le="2",stage="acl"} 2' in text
    assert 'vproxy_lat_us_bucket{le="4",stage="acl"} 3' in text
    assert 'vproxy_lat_us_bucket{le="8",stage="acl"} 3' in text
    assert 'vproxy_lat_us_bucket{le="+Inf",stage="acl"} 4' in text
    assert 'vproxy_lat_us_sum{stage="acl"} 106' in text
    assert 'vproxy_lat_us_count{stage="acl"} 4' in text


def test_histogram_percentiles_reservoir_and_estimate():
    # with a reservoir: exact over the window
    h = Histogram("x_us", reservoir=1000)
    for v in range(1, 1001):  # 1..1000
        h.observe(float(v))
    p = h.percentiles()
    assert p["n"] == 1000
    assert abs(p["p50"] - 500) <= 2
    assert abs(p["p99"] - 990) <= 2
    assert abs(p["p999"] - 999) <= 2
    # without: log-linear estimate from the buckets, right magnitude
    h2 = Histogram("y_us")
    for v in range(1, 1001):
        h2.observe(float(v))
    p2 = h2.percentiles()
    assert 256 <= p2["p50"] <= 1024
    assert 512 <= p2["p99"] <= 1024


def test_histogram_thread_safety_totals():
    h = Histogram("t_us", reservoir=64)

    def w():
        for _ in range(1000):
            h.observe(7.0)
    ts = [threading.Thread(target=w) for _ in range(4)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert h._count == 4000
    assert h._sum == 7.0 * 4000


def test_histogram_thread_safety_batches_beside_samples():
    """observe_many from some threads while others observe: no update
    is lost (the dispatcher's batches and the submitters' inline
    answers share the process-global histogram)."""
    import sys
    h = Histogram("t_us", reservoir=64)
    batch = np.full(50, 3.0)

    def many():
        for _ in range(200):
            h.observe_many(batch)

    def one():
        for _ in range(2000):
            h.observe(7.0)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=f) for f in (many, one) * 4]
        [t.start() for t in ts]
        [t.join(60) for t in ts]
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts)
    n, total, counts = h.state()
    assert n == 4 * (200 * 50 + 2000) == sum(counts) == h._res_n
    assert total == 4 * (200 * 50 * 3.0 + 2000 * 7.0)
    assert counts[2] == 4 * 200 * 50 and counts[3] == 4 * 2000
    assert set(h._res) <= {3.0, 7.0} and len(h._res) == 64


def _lognormal(n, seed=30):
    # medians of ~20 ms in us, the shape of a served batch's latencies
    return np.random.default_rng(seed).lognormal(10.0, 1.5, n)


_EDGES = ([0.0, 0.5, 1.0, 1.0 + 1e-7, 2.0, 2.0 ** 26, 2.0 ** 26 + 1, 1e12]
          + [2.0 ** k for k in (1, 2, 3, 10, 20, 25)]
          + [2.0 ** k + 1e-3 for k in (1, 2, 3, 10, 20, 25)])

# id -> (buckets, reservoir, samples observed one by one first, batches)
_OBSERVE_MANY_CASES = {
    "empty": (27, 64, [], [[]]),
    "empty_after_samples": (27, 64, [3.0, 70.0], [[]]),
    "one_value": (27, 64, [], [[37.5]]),
    "bucket_edges": (27, 64, [], [_EDGES]),
    "lognormal_1834": (27, 4096, [], [_lognormal(1834)]),
    "wraps_the_ring": (27, 64, list(range(50)), [_lognormal(30)]),
    "ends_at_the_ring_end": (27, 64, list(range(50)), [_lognormal(14)]),
    "longer_than_the_ring": (27, 64, list(range(5)), [_lognormal(200)]),
    "exactly_the_ring": (27, 64, list(range(5)), [_lognormal(64)]),
    "two_batches": (27, 64, [], [_lognormal(40, 1), _lognormal(41, 2)]),
    "two_batches_past_the_ring": (27, 64, [1.5], [_lognormal(100, 1),
                                                  _lognormal(70, 2)]),
    "no_reservoir": (27, 0, [9.0], [_lognormal(300)]),
    "few_buckets": (8, 0, [], [_EDGES]),
}


@pytest.mark.parametrize("case", sorted(_OBSERVE_MANY_CASES))
def test_observe_many_leaves_what_observe_would(case):
    """observe_many(vs) == for v in vs: observe(v): state(), the
    exposition text, the percentiles and the reservoir ring."""
    buckets, reservoir, first, batches = _OBSERVE_MANY_CASES[case]
    ref = Histogram("h_us", buckets=buckets, reservoir=reservoir)
    got = Histogram("h_us", buckets=buckets, reservoir=reservoir)
    for v in first:
        ref.observe(float(v))
        got.observe(float(v))
    for vs in batches:
        for v in vs:
            ref.observe(float(v))
        got.observe_many(np.asarray(vs, dtype=np.float64))
    (n_ref, sum_ref, counts_ref), (n, total, counts) = ref.state(), \
        got.state()
    assert (n, counts) == (n_ref, counts_ref)
    assert total == pytest.approx(sum_ref, rel=1e-9, abs=0.0)
    # the text: every line but _sum to the letter, _sum as a number
    lines_ref, lines = ref.sample_lines(), got.sample_lines()
    assert [ln for ln in lines if "_sum" not in ln] \
        == [ln for ln in lines_ref if "_sum" not in ln]
    (s_ref,), (s_got,) = ([ln for ln in ls if "_sum" in ln]
                          for ls in (lines_ref, lines))
    assert float(s_got.split()[-1]) == pytest.approx(
        float(s_ref.split()[-1]), rel=1e-9, abs=0.0)
    assert got._res_n == ref._res_n and got._res == ref._res
    assert len(got._res) == reservoir
    assert all(type(v) is float for v in got._res)
    assert got.percentiles() == ref.percentiles()


def test_flight_recorder_ring_and_events_endpoint():
    FlightRecorder.reset()
    try:
        fr = FlightRecorder.get()
        for i in range(5):
            fr.record("conn", f"c{i} closed", bytes_in=i)
        snap = fr.snapshot()
        assert [e["msg"] for e in snap] == [f"c{i} closed" for i in range(5)]
        assert [e["seq"] for e in snap] == [1, 2, 3, 4, 5]
        assert snap[0]["bytes_in"] == 0
        assert fr.lines(2) == fr.lines()[-2:]

        loop = SelectorEventLoop("ev")
        loop.loop_thread()
        srv = launch_inspection_http(loop, "127.0.0.1", 0)
        try:
            st, body = http_get(srv.port, "/events")
            assert st == 200
            evs = json.loads(body)
            assert len(evs) == 5 and evs[-1]["msg"] == "c4 closed"
            st, body = http_get(srv.port, "/events?n=2")
            assert [e["msg"] for e in json.loads(body)] == \
                ["c3 closed", "c4 closed"]
        finally:
            srv.close()
            loop.close()
    finally:
        FlightRecorder.reset()


def test_flight_recorder_capacity_eviction():
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.record("k", str(i))
    snap = fr.snapshot()
    assert len(snap) == 4
    assert [e["msg"] for e in snap] == ["6", "7", "8", "9"]
    assert fr.dropped == 6


def test_event_log_command():
    FlightRecorder.reset()
    try:
        from vproxy_tpu.control.command import Command
        FlightRecorder.get().record("hc_down", "g/s 1.2.3.4:80 DOWN",
                                    group="g")
        lines = Command.execute(None, "list event-log")
        assert len(lines) == 1 and "hc_down" in lines[0]
        detail = Command.execute(None, "list-detail event-log")
        assert detail[0]["kind"] == "hc_down"
        assert detail[0]["group"] == "g"
    finally:
        FlightRecorder.reset()


def test_pump_counters_roundtrip():
    """Bytes moved by the splice pump show up in vtl.pump_counters()
    and on /metrics as vproxy_pump_bytes_total (native C atomics or the
    py provider's tallies — whichever provider is loaded)."""
    from vproxy_tpu.net import vtl
    from vproxy_tpu.net.connection import Connection, Handler, ServerSock

    before = vtl.pump_counters()
    assert len(before) == 4

    backend = socket.socket()
    backend.bind(("127.0.0.1", 0))
    backend.listen(8)
    bport = backend.getsockname()[1]

    def serve():
        c, _ = backend.accept()
        while True:
            d = c.recv(65536)
            if not d:
                break
            c.sendall(d)
        c.close()
    threading.Thread(target=serve, daemon=True).start()

    loop = SelectorEventLoop("pumpc")
    loop.loop_thread()
    done = {}

    def on_accept(cfd, ip, port):
        back = Connection.connect(loop, "127.0.0.1", bport)

        class Back(Handler):
            def on_connected(self, conn):
                bfd = conn.detach()
                loop.pump(cfd, bfd, 65536, lambda a2b, b2a, err:
                          done.setdefault("stat", (a2b, b2a, err)))

            def on_closed(self, conn, err):
                done.setdefault("stat", (0, 0, err or 1))
        back.set_handler(Back())

    holder = {}
    loop.run_on_loop(lambda: holder.setdefault(
        "srv", ServerSock(loop, "127.0.0.1", 0, on_accept)))
    t0 = time.time()
    while "srv" not in holder and time.time() - t0 < 5:
        time.sleep(0.005)
    try:
        cli = socket.create_connection(
            ("127.0.0.1", holder["srv"].port), timeout=5)
        payload = b"z" * 200_000
        threading.Thread(target=lambda: (cli.sendall(payload),
                                         cli.shutdown(socket.SHUT_WR)),
                         daemon=True).start()
        rx = b""
        while True:
            d = cli.recv(65536)
            if not d:
                break
            rx += d
        cli.close()
        assert rx == payload
        t0 = time.time()
        while "stat" not in done and time.time() - t0 < 5:
            time.sleep(0.005)
    finally:
        loop.close()
        backend.close()

    after = vtl.pump_counters()
    moved = after[0] - before[0]
    assert moved >= 2 * len(payload), (before, after)  # both directions
    assert after[1] > before[1]  # write calls
    # and the /metrics surface exposes the same counter
    text = GlobalInspection.get().registry.prometheus_text()
    assert "vproxy_pump_bytes_total" in text
    assert "vproxy_pump_splice_calls_total" in text


def test_accept_stage_histograms_on_metrics():
    from vproxy_tpu.utils.metrics import accept_stage_observe
    accept_stage_observe("acl", 0.000050)
    accept_stage_observe("total", 0.000200)
    text = GlobalInspection.get().registry.prometheus_text()
    assert 'vproxy_accept_stage_us_bucket{le="64",stage="acl"}' in text
    assert 'vproxy_accept_stage_us_count{stage="total"} ' in text


def test_bench_snapshot_shape():
    gi = GlobalInspection.get()
    h = gi.get_histogram("vproxy_snaptest_us", stage="x")
    h.observe(10.0)
    c = gi.get_counter("vproxy_snaptest_total", reason="r")
    c.incr(3)
    snap = gi.bench_snapshot()
    assert snap["vproxy_snaptest_total.r"] == 3
    assert snap["vproxy_snaptest_us.x"]["n"] == 1
    assert "p99" in snap["vproxy_snaptest_us.x"]


def test_loop_registration_lifecycle():
    gi = GlobalInspection.get()
    before = len(gi._loops)
    lp = SelectorEventLoop("gi2")
    lp.loop_thread()
    time.sleep(0.05)
    assert len(gi._loops) == before + 1
    lp.close()
    assert len(gi._loops) == before


def test_families_pre_registered_before_any_traffic():
    """The PR-9 pre-registration rule, enforced repo-wide by vlint's
    registry audit (docs/static-analysis.md): the closed-vocabulary
    families must exist — at zero — on a scrape before any event, and
    the histogram config owned by the eager site must survive the
    component-side get_histogram dedup."""
    import vproxy_tpu.vswitch.swmetrics  # noqa: F401 — registry module
    gi = GlobalInspection.get()
    text = gi.registry.prometheus_text()
    for stage in ("acl", "classify", "backend_pick", "handover",
                  "total"):
        assert f'vproxy_accept_stage_us_count{{stage="{stage}"}}' in text
    for reason in ("acl_deny", "arp_unresolved", "egress_short_write",
                   "route_miss", "same_iface", "unknown_vni"):
        assert f'vproxy_switch_drops_total{{reason="{reason}"}}' in text
    assert 'vproxy_switch_slowpath_total{reason="bad_csum"}' in text
    assert 'vproxy_switch_forwards_total{path="fast"}' in text
    assert "vproxy_switch_rx_total" in text
    assert "vproxy_engine_swap_ms_count" in text
    assert "vproxy_maglev_build_ms_count" in text
    # reservoir config lives at the eager site; the creators in
    # rules/engine.py and rules/maglev.py must resolve to the SAME
    # instances (first-creation-wins through _get_named)
    from vproxy_tpu.rules import maglev
    from vproxy_tpu.rules.engine import _swap_hist
    assert _swap_hist()._res_cap == 512
    assert maglev._build_ms()._res_cap == 256
