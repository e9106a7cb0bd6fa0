"""The per-layer metrics of `mesh-200k.mixed-w4096`, each computed from
its own `metrics/<name>.json` on hand-made span totals and a hand-made
window.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_mesh_readers.py -q

A metric file that names a span, a field, a kind or a reader wrongly
fails here, on the CPU; a program without the spans (the parent commit)
gives nothing and does not raise.
"""
from __future__ import annotations

import importlib
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import program_trace  # noqa: E402
import run  # noqa: E402

CELL = "mesh-200k.mixed-w4096"
MS = 1_000_000


def total(n, sum_ns, items=0):
    return {"n": n, "sum_ns": sum_ns, "sum_cpu_ns": 0, "sum_items": items,
            "buckets": [0] * 28, "first_ns": 0, "last_ns": 0}


# a second of a dispatcher that takes four matchers a wake
HAND = {
    "engine/cycle": total(25, 900 * MS, items=50_000),
    "engine/turn_wait": total(100, 1400 * MS, items=50_000),
    "engine/dispatch": total(100, 400 * MS),
}
WANT = {
    "dispatch_cycle_us": 36_000.0,      # 900 ms / 25 wakes
    "turn_wait_us": 14_000.0,           # 1,400 ms / 100 parts
    "batches_per_cycle": 4.0,           # 100 device batches / 25 wakes
}


def read(name: str, ctx=None, totals=None, monkeypatch=None):
    spec = run.load_json(HERE, "metrics", name + ".json")
    reader = importlib.import_module("readers." + spec["reader"])
    if totals is not None:
        monkeypatch.setattr(program_trace, "span_totals", lambda: totals)
    return reader.read(ctx, spec.get("params", {}))


def window(kinds: list, lat_ms: list, in_window: list | None = None):
    """One query a pool rank: rank r asks kinds[r], is submitted at
    1 s + r ms and answered lat_ms[r] later; the window is 1 s .. 2 s."""
    n = len(kinds)
    t_sub = np.array([1000 * MS + r * MS for r in range(n)], np.int64)
    if in_window is not None:
        t_sub = np.where(in_window, t_sub, 10 * MS)   # before the window
    win = SimpleNamespace(
        rank=np.arange(n, dtype=np.int32), t_sub=t_sub,
        t_done=t_sub + (np.array(lat_ms) * MS).astype(np.int64),
        t_open=1000 * MS, t_close=2000 * MS)
    plan = SimpleNamespace(pool=[(k, ()) for k in kinds],
                           traffic={"driver": "classify_closed_loop"})
    return SimpleNamespace(win=win, plan=plan)


@pytest.mark.parametrize("name", sorted(WANT))
def test_span_metric_on_hand_made_totals(name, monkeypatch):
    assert read(name, totals=HAND, monkeypatch=monkeypatch) \
        == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_span_metric_finds_nothing_on_the_parent(name, monkeypatch):
    """No cycle spans: no totals at all, or PR 27's alone."""
    assert read(name, totals={}, monkeypatch=monkeypatch) is None
    pr27 = {"engine/dispatch": HAND["engine/dispatch"]}
    assert read(name, totals=pr27, monkeypatch=monkeypatch) is None


def test_batches_per_cycle_edges(monkeypatch):
    none_counted = dict(HAND, **{"engine/cycle": total(0, 0)})
    assert read("batches_per_cycle", totals=none_counted,
                monkeypatch=monkeypatch) is None
    host_only = {"engine/cycle": total(5, 10 * MS)}   # wakes, no device batch
    assert read("batches_per_cycle", totals=host_only,
                monkeypatch=monkeypatch) == 0.0


def test_cpick_p99_reads_the_cpick_queries_alone():
    kinds = ["hint", "cpick"] * 100
    lat = [500.0 if k == "hint" else 1.0 + r // 2
           for r, k in enumerate(kinds)]     # cpick: 1 .. 100 ms
    got = read("cpick_p99_ms", window(kinds, lat))
    assert got == pytest.approx(float(np.percentile(np.arange(1, 101), 99)))
    # a query submitted before the window opened is no sample of it
    inside = [r >= 100 for r in range(200)]
    got = read("cpick_p99_ms", window(kinds, lat, inside))
    assert got == pytest.approx(float(np.percentile(np.arange(51, 101), 99)))


def test_cpick_p99_counts_an_undelivered_query_as_the_drain_limit():
    import drivers.classify_closed_loop as driver
    ctx = window(["cpick"] * 10, [2.0] * 10)
    ctx.win.t_done[3] = 0
    got = read("cpick_p99_ms", ctx)
    assert got > driver.DRAIN_S * 1000 * 0.9


def test_cpick_p99_finds_nothing_without_a_cpick_query():
    assert read("cpick_p99_ms", window(["hint", "route", "acl"] * 5,
                                       [3.0] * 15)) is None
    none_inside = window(["cpick"] * 4, [3.0] * 4, [False] * 4)
    assert read("cpick_p99_ms", none_inside) is None


def test_the_ten_metrics_are_declared_for_the_mesh_cell_alone():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    names = {m["name"] for m in mine}
    assert names == set(WANT) | {"cpick_p99_ms"} | {
        f"mesh_{k}_{what}" for k in ("hint_hash_match", "fused_classify_pick",
                                     "cidr_hash_match")
        for what in ("us_per_batch", "roofline")}
    by = {m["name"]: m for m in mine}
    assert by["cpick_p99_ms"]["moves"] == "classify_p99_ms"
    assert all(by[n]["moves"] == "matches_per_s" for n in names
               if n != "cpick_p99_ms")
    for n in WANT:
        assert by[n]["source"] == "program_span"


@pytest.mark.parametrize("kernel", ["hint_hash_match", "fused_classify_pick",
                                    "cidr_hash_match"])
@pytest.mark.parametrize("what", ["us_per_batch", "roofline"])
def test_mesh_kernel_metrics_read_as_the_old_cells_do(kernel, what):
    """The same reader on the same program under a new name."""
    old = run.load_json(HERE, "metrics", f"{kernel}_{what}.json")
    new = run.load_json(HERE, "metrics", f"mesh_{kernel}_{what}.json")
    assert new == old
