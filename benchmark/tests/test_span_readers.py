"""The readers of the program's span totals, each fed a hand-made
`span_totals()` dict.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_span_readers.py -q

Every `per_layer` metric of BENCHMARK.json that rests on these readers
is computed here from its own `metrics/<name>.json`, so a metric file
that names a span, a field or a reader wrongly fails here, on the CPU.
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

MS = 1_000_000


def total(n, sum_ns, cpu=0, items=0, first=0, last=0):
    return {"n": n, "sum_ns": sum_ns, "sum_cpu_ns": cpu, "sum_items": items,
            "buckets": [0] * 28, "first_ns": first, "last_ns": last}


# a second of a dispatcher: 100 batches of 32, parked for a fifth of it
HAND = {
    "engine/wait": total(10, 200 * MS, first=1000 * MS, last=1900 * MS),
    "engine/dispatch": total(100, 300 * MS, first=1001 * MS, last=1990 * MS),
    "engine/encode": total(200, 160 * MS, cpu=40 * MS, items=3200,
                           first=1001 * MS, last=1989 * MS),
    "engine/launch": total(100, 110 * MS, first=1002 * MS, last=1990 * MS),
    "engine/d2h_sync": total(100, 35 * MS, first=1003 * MS, last=1995 * MS),
    "engine/deliver": total(100, 40 * MS, cpu=10 * MS, items=3200,
                            first=1004 * MS, last=2000 * MS),
    "engine/submit_lock_wait": total(50, 2 * MS, first=1000 * MS,
                                     last=1999 * MS),
    "runtime/gc_pause": total(4, 90 * MS, first=900 * MS, last=1800 * MS),
}
WANT = {
    "dispatch_launch_us": 1100.0,       # 110 ms / 100 launches
    "dispatch_d2h_sync_us": 350.0,
    "deliver_us_per_query": 12.5,       # 40 ms / 3,200 queries
    "encode_cpu_us_per_query": 12.5,    # 40 ms of CPU / 3,200
    "submit_lock_wait_us": 40.0,
    "dispatcher_offcpu_pct": 75.0,      # (200 - 50) / 200 ms
    "dispatcher_busy_pct": 80.0,        # 1 - 200 ms / (2000 - 1000 ms)
    "gc_pause_pct": 9.0,                # 90 ms / the engine's 1000 ms
}


def value(name: str, totals: dict, monkeypatch):
    spec = run.load_json(HERE, "metrics", name + ".json")
    reader = importlib.import_module("readers." + spec["reader"])
    monkeypatch.setattr(program_trace, "span_totals", lambda: totals)
    return reader.read(None, spec.get("params", {}))


def served(bench: dict) -> list:
    return [w["name"] for w in bench["workloads"]
            if run.load_json(HERE, "traffic", w["traffic"] + ".json")
            ["driver"] == "classify_closed_loop"]


def test_every_span_metric_is_declared():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in WANT:
        assert by_name[name]["source"] == "program_span"
        # every cell, or (ISSUE 39) every cell that drives a
        # ClassifyService, where the span is the dispatcher's
        assert by_name[name].get("workloads") in (None, served(bench))
        assert not any(s in name for s in
                       ("roofline", "us_per_batch", "device_idle_pct"))


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_hand_made_totals(name, monkeypatch):
    assert value(name, HAND, monkeypatch) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_on_a_program_without_spans(name, monkeypatch):
    """The parent commit: no totals, no metric, no exception."""
    assert value(name, {}, monkeypatch) is None


def test_a_span_never_seen_is_no_share_of_a_stretch_that_exists(monkeypatch):
    some = {k: v for k, v in HAND.items()
            if k not in ("engine/wait", "runtime/gc_pause")}
    assert value("dispatcher_busy_pct", some, monkeypatch) == 100.0
    assert value("gc_pause_pct", some, monkeypatch) == 0.0
    assert value("submit_lock_wait_us",
                 {"engine/submit_lock_wait": total(0, 0)},
                 monkeypatch) is None


def test_seam_reads_the_program_or_nothing(monkeypatch):
    from vproxy_tpu.utils import trace
    assert program_trace.span_totals() == trace.span_totals()
    monkeypatch.delattr(trace, "span_totals")       # the parent's module
    assert program_trace.span_totals() == {}
