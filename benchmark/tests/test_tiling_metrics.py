"""The readers of the dispatcher's tiling spans (PR 37), each fed a
hand-made `span_totals()` dict, the metric files that name them, and one
toy run of the harness that has to report every one of them.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_tiling_metrics.py -q
"""
from __future__ import annotations

import importlib
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import pytest  # noqa: E402

import program_trace  # noqa: E402
import run  # noqa: E402
from readers import span_diff_mean, span_self_time, span_tiling_rest  # noqa: E402

MS = 1_000_000


def total(n, sum_ns, cpu=0, items=0, first=0, last=0):
    return {"n": n, "sum_ns": sum_ns, "sum_cpu_ns": cpu, "sum_items": items,
            "buckets": [0] * 28, "first_ns": first, "last_ns": last}


# a second of a dispatcher: 100 wakes, 120 device batches of 30, 20 of
# them drained with nothing pending, 30 read before their kernel was done
HAND = {
    "engine/wait": total(25, 100 * MS, first=1000 * MS, last=1900 * MS),
    "engine/swap": total(145, 20 * MS, items=3600, first=1000 * MS,
                         last=1990 * MS),
    "engine/cycle": total(100, 800 * MS, items=3600, first=1001 * MS,
                          last=1980 * MS),
    "engine/drain": total(20, 50 * MS, first=1010 * MS, last=2000 * MS),
    "engine/dispatch": total(120, 480 * MS, items=3600, first=1001 * MS,
                             last=1979 * MS),
    "engine/encode": total(120, 200 * MS, cpu=150 * MS, items=3600),
    "engine/launch": total(120, 240 * MS, items=480),
    "engine/readback_start": total(120, 12 * MS),
    "engine/d2h_sync": total(120, 69 * MS),
    "engine/kernel_wait": total(30, 60 * MS),
    "engine/deliver": total(120, 204 * MS, cpu=150 * MS, items=3600),
    "engine/inflight": total(120, 360 * MS),
    "engine/turn_wait": total(120, 90 * MS, items=3600),
    "engine/swap_lock": total(145, 5 * MS),
    "engine/begin": total(120, 17 * MS, items=3600),
    "engine/release": total(120, 36 * MS, items=3600),
}
WANT = {
    "dispatcher_unaccounted_pct": 3.0,      # 100 - 970 ms / 1000 ms
    "cycle_self_pct": 100 * 32 / 850,       # (850 - 818) / 850 ms
    "dispatch_self_us_per_query": 40 * 1000 / 3600,     # 480 - 440 ms
    "dispatcher_swap_us": 20 * 1000 / 145,
    "readback_start_us": 100.0,
    "parks_per_cycle": 0.25,
    "drains_per_batch": 20 / 120,
    "inflight_age_us": 3000.0,
    "kernel_waits_per_batch": 0.25,
    "ready_sync_us": 100.0,                 # (69 - 60) ms / (120 - 30)
    "launch_host_arrays": 4.0,
    "dispatcher_lock_wait_us": 5 * 1000 / 145,
    "release_us_per_query": 10.0,           # 36 ms / 3,600 requests
}
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
NEW = [m["name"] for m in BENCH["per_layer"] if m["name"] in WANT]
SERVED = [w["name"] for w in BENCH["workloads"]
          if run.load_json(HERE, "traffic", w["traffic"] + ".json")
          ["driver"] == "classify_closed_loop"]


def spec_of(name: str) -> dict:
    return run.load_json(HERE, "metrics", name + ".json")


def value(name: str, totals: dict, monkeypatch):
    spec = spec_of(name)
    reader = importlib.import_module("readers." + spec["reader"])
    monkeypatch.setattr(program_trace, "span_totals", lambda: totals)
    return reader.read(None, spec.get("params", {}))


def test_the_new_metrics_are_declared_for_every_cell():
    # (no launch_offcpu_pct: thread CPU time cannot be trusted on the
    # chip's host, PERF.md §7)
    assert set(WANT) == set(NEW)
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for name in NEW:
        m = by_name[name]
        assert m["source"] == "program_span" and m["moves"] in e2e
        # every cell, or (ISSUE 39) every cell that drives a
        # ClassifyService: a burst through the switch has no dispatcher
        assert m.get("workloads") in (None, SERVED)
        spec = spec_of(name)
        assert os.path.exists(os.path.join(
            HERE, "readers", spec["reader"] + ".py"))
        assert spec["reads"] and spec["covers"]
    # what the benchmark had stands before them, in its order
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_on_hand_made_totals(name, monkeypatch):
    assert value(name, HAND, monkeypatch) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_program_without_spans_gives_nothing(name, monkeypatch):
    assert value(name, {}, monkeypatch) is None


def test_the_parent_commits_spans_alone_raise_nothing(monkeypatch):
    """The driver lays these files over the parent's checkout: its
    totals hold none of the spans PR 37 adds."""
    old = {k: v for k, v in HAND.items() if k.split("/")[1] not in (
        "swap", "drain", "readback_start", "inflight", "kernel_wait",
        "swap_lock", "begin", "release")}
    for name in NEW:
        value(name, old, monkeypatch)       # a number or None, no raise
    for name in ("dispatcher_swap_us", "readback_start_us",
                 "inflight_age_us", "dispatcher_lock_wait_us",
                 "release_us_per_query"):
        assert value(name, old, monkeypatch) is None


def test_a_cell_with_no_kernel_wait_still_reports(monkeypatch):
    none = {k: v for k, v in HAND.items() if k != "engine/kernel_wait"}
    assert value("kernel_waits_per_batch", none, monkeypatch) == 0.0
    assert value("ready_sync_us", none, monkeypatch) == \
        pytest.approx(69 * 1000 / 120)
    assert value("drains_per_batch",
                 {k: v for k, v in HAND.items() if k != "engine/drain"},
                 monkeypatch) == 0.0
    assert value("parks_per_cycle",
                 {k: v for k, v in HAND.items() if k != "engine/wait"},
                 monkeypatch) == 0.0


def test_span_diff_mean():
    p = {"a": "engine/d2h_sync", "b": "engine/kernel_wait"}
    assert span_diff_mean.compute({}, p) is None
    assert span_diff_mean.compute({"engine/kernel_wait": total(3, MS)},
                                  p) is None
    every = {"engine/d2h_sync": total(30, 70 * MS),
             "engine/kernel_wait": total(30, 70 * MS)}
    assert span_diff_mean.compute(every, p) is None     # b.n == a.n
    assert span_diff_mean.compute(HAND, p) == pytest.approx(100.0)


def test_span_self_time():
    p = {"spans": ["engine/dispatch"], "per": "sum_items",
         "children": ["engine/encode", "engine/table_set", "engine/launch"]}
    assert span_self_time.compute({}, p) is None
    assert span_self_time.compute(
        {"engine/encode": total(1, MS)}, p) is None     # no parent
    only = {"engine/dispatch": HAND["engine/dispatch"]}
    assert span_self_time.compute(only, p) == \
        pytest.approx(480 * 1000 / 3600)                # no child seen
    # a child larger than its parent (it ran outside it too): 0, reported
    big = dict(only, **{"engine/launch": total(130, 600 * MS)})
    assert span_self_time.compute(big, p) == 0.0
    assert span_self_time.compute(big, dict(p, per="share")) == 0.0
    assert span_self_time.compute(HAND, dict(p, per="n")) == \
        pytest.approx(40 * 1000 / 120)
    no_items = {"engine/dispatch": total(5, 10 * MS)}
    assert span_self_time.compute(no_items, p) is None


def test_span_tiling_rest():
    p = {"top": ["engine/wait", "engine/swap", "engine/cycle",
                 "engine/drain"]}
    assert span_tiling_rest.compute({}, p) is None
    assert span_tiling_rest.compute(
        {"engine/deliver": HAND["engine/deliver"]}, p) is None
    # the parent commit: wait and cycle alone, the rest is no span's
    old = {k: HAND[k] for k in ("engine/wait", "engine/cycle")}
    assert span_tiling_rest.compute(old, p) == \
        pytest.approx(100.0 - 100.0 * 900 / 980)
    # spans that overlap after all cannot read below nothing
    over = dict(HAND, **{"engine/cycle": total(100, 990 * MS, first=1001 * MS,
                                               last=1980 * MS)})
    assert span_tiling_rest.compute(over, p) == 0.0
    # the stretch is the top-level spans' own: a sampled submitter's
    # span that began before the dispatcher did changes nothing
    early = dict(HAND, **{"engine/queue_wait": total(9, MS, first=1,
                                                     last=5000 * MS)})
    assert span_tiling_rest.compute(early, p) == pytest.approx(3.0)


def test_a_toy_run_reports_every_new_metric():
    """`selftest.py`'s traced toy run of one cell, through run.py's own
    path: the program's spans reach the result line. (The totals are the
    process's: what the cell added is read from their rise.)"""
    import selftest
    before = program_trace.span_totals()
    r = run.run_cell("lb-host10k.cpick-w64", selftest.SEED, 2.0, True,
                     require_tpu=False, overrides=selftest.TOY)
    assert r["correct"]
    missing = [n for n in NEW if n not in r["metrics"]]
    assert not missing, missing
    m = {n: r["metrics"][n]["value"] for n in NEW}
    assert 0.0 <= m["dispatcher_unaccounted_pct"] < 100.0
    assert 0.0 <= m["cycle_self_pct"] < 100.0
    assert 0.0 <= m["kernel_waits_per_batch"] <= 1.0
    assert 0.0 <= m["drains_per_batch"] <= 1.0
    after = program_trace.span_totals()

    def rise(span, field):
        return after[span][field] - before.get(span, {field: 0})[field]

    # one packed arena every launch (14 arrays until PR 38)
    assert rise("engine/launch", "sum_items") == \
        rise("engine/launch", "n") > 0
    assert rise("engine/inflight", "n") == rise("engine/d2h_sync", "n") \
        == rise("engine/readback_start", "n") == rise("engine/dispatch", "n")
