"""`h2lb-groups256.h2-c16m48`: the tcp-lb's h2 plane served over h2c,
each stream's backend held to the plain reference (reference_h2.py)
through run.py's own path.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_h2_cell.py -q

At toy size on the CPU (`selftest.TOY` reaches the builder through
`sizes.hint_rules`), the look for a chip skipped: the cell is correct
and reports what its traced line declares; its control (a matched
group's pick from the next group's table, through the same h2 entry)
is not; and the path broken underneath for each fault the cell can
have — a wrong group, a select that never answers, a reset stream, a
request without X-Forwarded-For, a body cut short or another request's,
an answer from the host — turns
`correct` false. tests/test_bench_h2_cell.py runs these same tests in
tier-1.
"""
from __future__ import annotations

import os
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import pytest  # noqa: E402

import run  # noqa: E402
from selftest import TOY  # noqa: E402

CELL = "h2lb-groups256.h2-c16m48"
OWN = ["selects_not_on_device", "answered_on_host", "streams_reset",
       "xff_wrong", "bodies_wrong"]


def toy(seed: int, trace: bool = False, **kw) -> dict:
    return run.run_cell(CELL, seed, 1.0, trace, require_tpu=False,
                        overrides=TOY, **kw)


def values(r: dict) -> dict:
    return {k: c["value"] for k, c in r["compared"].items()}


def no_children_left() -> bool:
    """No client or backends process of this one's is still running."""
    out = subprocess.run(["pgrep", "-P", str(os.getpid()), "-f",
                          "drivers/h2_(client|backends).py"],
                         capture_output=True, text=True).stdout.split()
    return not out


def test_toy_cell_is_correct_through_its_own_checks():
    """What selftest.py runs: the cell at toy size, untraced and
    traced, correct, with the driver's own checks after run.py's."""
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    r = toy(2**31 + 50)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 500
    assert list(r["compared"]) == ["wrong_verdicts", "undelivered"] + OWN
    assert all(c == {"value": 0, "limit": 0} for c in r["compared"].values())
    assert set(r["metrics"]) == {"matches_per_s", "classify_p99_ms",
                                 "setup_s"}
    t = toy(51, trace=True)
    m = t["metrics"]
    declared = {x["name"] for x in bench["per_layer"]
                if run.applies(x, CELL)}
    assert t["correct"] and set(m) <= declared
    # what a CPU run can read: every program span and counter metric
    for name in ("batch_mean", "classify_p50_ms", "encode_us_per_query",
                 "encode_cpu_us_per_query", "dispatch_launch_us",
                 "launch_host_arrays", "compiles_in_window", "gc_pause_pct",
                 "h2_streams_per_read", "h2_front_us_per_stream",
                 "h2_select_wait_us", "h2_relay_us_per_stream",
                 "h2_device_pick_pct", "h2_queue_wait_ms",
                 "h2_deliver_us_per_query"):
        assert name in m, name
    assert 50 < m["h2_device_pick_pct"]["value"] < 100   # misses fall back
    assert m["h2_streams_per_read"]["value"] >= 1
    assert m["compiles_in_window"]["value"] == 0
    assert no_children_left()


@pytest.mark.parametrize("seed", [52, 2**31 + 53])
def test_control_is_not_correct(seed):
    r = toy(seed, control=True)
    v = values(r)
    assert not r["correct"] and v["wrong_verdicts"] > 0
    # through the h2 entry, claiming the device: only the answers fail
    assert [v[k] for k in ["undelivered"] + OWN] == [0] * 6


def _wrap_select(change):
    """before_window hook: the upstream's next_async, each answer
    passed through change(k, connector) -> connector | None | "drop"."""
    def hook(svc):
        ups = svc.dep.upstream
        orig = ups.next_async
        n = [0]

        def next_async(ip, hint, cb, fam=None, loop=None):
            def done(c):
                n[0] += 1
                out = change(n[0], ups, ip, c)
                if out != "drop":
                    cb(out)
            orig(ip, hint, done, fam=fam, loop=loop)
        ups.next_async = next_async
    return hook


def test_a_wrong_group_is_a_wrong_answer():
    def other(k, ups, ip, c):
        if k % 40:
            return c
        hs = [h for h in ups.handles if h.group is not c.group]
        return hs[k % len(hs)].group.next(ip) or c
    r = toy(54, before_window=_wrap_select(other))
    v = values(r)
    assert not r["correct"] and v["wrong_verdicts"] > 0
    assert v["undelivered"] == v["streams_reset"] == 0


def test_a_dropped_select_reads_as_undelivered():
    r = toy(55, before_window=_wrap_select(
        lambda k, ups, ip, c: "drop" if k % 97 == 0 else c))
    v = values(r)
    assert not r["correct"] and v["undelivered"] > 0
    assert v["wrong_verdicts"] == 0
    assert no_children_left()


def test_a_reset_stream_trips_its_check():
    r = toy(56, before_window=_wrap_select(
        lambda k, ups, ip, c: None if k % 97 == 0 else c))
    v = values(r)
    assert not r["correct"] and v["streams_reset"] > 0


def test_a_missing_forwarded_for_trips_its_check(monkeypatch):
    from vproxy_tpu.processors import h2
    monkeypatch.setattr(h2, "with_forwarded_for", lambda hs, ip: hs)
    r = toy(57)
    v = values(r)
    assert not r["correct"] and v["xff_wrong"] > 0
    assert v["wrong_verdicts"] == 0


def test_a_select_the_host_answers_trips_the_device_checks():
    """Right answers from the wrong place: the service's host mode."""
    def host(svc):
        svc.classify.mode = "host"
    r = toy(58, before_window=host)
    v = values(r)
    assert not r["correct"] and v["wrong_verdicts"] == 0
    assert v["answered_on_host"] > 0 and v["selects_not_on_device"] > 0


@pytest.mark.parametrize("fault", ["cut_short", "another_streams"])
def test_a_body_relayed_wrong_trips_its_check(monkeypatch, fault):
    """The proxy's DATA toward the client, every 53rd frame: one byte
    short, or the body of the frame sent before it."""
    from vproxy_tpu.processors import h2
    orig = h2._Side.send_data
    n, last = [0], [b""]

    def send_data(self, sid, chunk, end_stream):
        if self.server:
            n[0] += 1
            if n[0] % 53 == 0:
                chunk = chunk[:-1] if fault == "cut_short" else last[0]
            last[0] = chunk
        orig(self, sid, chunk, end_stream)
    monkeypatch.setattr(h2._Side, "send_data", send_data)
    r = toy(59)
    v = values(r)
    assert not r["correct"] and v["bodies_wrong"] > 0
    assert v["wrong_verdicts"] == v["streams_reset"] == v["undelivered"] == 0
