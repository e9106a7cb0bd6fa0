"""`switch-vpc64.route-w1024`: its per-layer metrics, each computed from
its own `metrics/<name>.json` on hand-made span totals; its plain
reference; and a planted cross-tenant fault, which `correct` has to
catch at toy size on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_vpc_cell.py -q

The `route` control (answers taken from the next VPC's table) runs with
every other cell's control in test_correct.py.
"""
from __future__ import annotations

import importlib
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import program_trace  # noqa: E402
import reference_vpc  # noqa: E402
import run  # noqa: E402
from selftest import TOY  # noqa: E402

CELL = "switch-vpc64.route-w1024"
MS = 1_000_000


def total(n, sum_ns, items=0):
    return {"n": n, "sum_ns": sum_ns, "sum_cpu_ns": 0, "sum_items": items,
            "buckets": [0] * 28, "first_ns": 0, "last_ns": 0}


# a second of a dispatcher whose wakes are one batch of ~48 VPCs each
HAND = {
    "engine/cycle": total(250, 900 * MS, items=70_000),
    "engine/dispatch": total(250, 400 * MS),
    "engine/table_set": total(250, 10 * MS, items=12_000),
}
WANT = {
    "vpc_tables_per_batch": 48.0,       # 12,000 tables named / 250 batches
    "vpc_batches_per_cycle": 1.0,
    "vpc_dispatch_cycle_us": 3_600.0,   # 900 ms / 250 wakes
}


def read(name: str, totals: dict, monkeypatch):
    spec = run.load_json(HERE, "metrics", name + ".json")
    reader = importlib.import_module("readers." + spec["reader"])
    monkeypatch.setattr(program_trace, "span_totals", lambda: totals)
    return reader.read(None, spec.get("params", {}))


@pytest.mark.parametrize("name", sorted(WANT))
def test_span_metric_on_hand_made_totals(name, monkeypatch):
    assert read(name, HAND, monkeypatch) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_span_metric_finds_nothing_on_the_parent(name, monkeypatch):
    """No span totals at all, or (tables a batch) a program whose
    batches name no set: nothing, and no exception."""
    assert read(name, {}, monkeypatch) is None
    if name == "vpc_tables_per_batch":
        parent = {k: v for k, v in HAND.items() if k != "engine/table_set"}
        assert read(name, parent, monkeypatch) is None
        assert read(name, {"engine/table_set": total(0, 0)},
                    monkeypatch) is None


def test_a_split_by_vpc_shows_in_batches_per_cycle(monkeypatch):
    split = dict(HAND, **{"engine/dispatch": total(250 * 48, 400 * MS)})
    assert read("vpc_batches_per_cycle", split, monkeypatch) == 48.0


BURST_CELL = "switch-vpc64.route-burst1024"     # the set's other cell
SHARED = {"vpc_tables_per_batch", "vpc_cidr_set_match_us_per_batch",
          "vpc_cidr_set_match_roofline"}


def test_the_five_metrics_are_declared_for_the_vpc_cell_alone():
    """... or, for the three that read the set's program and its
    table-id column, for the two cells that run it (ISSUE 39); the two
    that read the dispatcher's wake stay this cell's alone."""
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") in ([CELL], [CELL, BURST_CELL])}
    assert set(mine) == set(WANT) | {"vpc_cidr_set_match_us_per_batch",
                                     "vpc_cidr_set_match_roofline"}
    assert {n for n, m in mine.items()
            if m["workloads"] == [CELL, BURST_CELL]} == SHARED
    assert all(m["moves"] == "matches_per_s" for m in mine.values())
    for n in WANT:
        assert mine[n]["source"] == "program_span"
    for what in ("us_per_batch", "roofline"):
        spec = run.load_json(HERE, "metrics",
                             f"vpc_cidr_set_match_{what}.json")
        assert spec["params"]["program"] == "jit_cidr_set_match"
        assert mine[f"vpc_cidr_set_match_{what}"]["source"] == "device_trace"
    cell = run.find_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["config"] == "switch-vpc64"


def test_reference_answers_by_the_named_vpc_alone():
    a = [(10 << 24 | 1 << 16, 16), (10 << 24, 8)]
    b = [(10 << 24, 8), (11 << 24, 8)]
    q = [(0, bytes([10, 1, 2, 3])), (1, bytes([10, 1, 2, 3])),
         (0, bytes([11, 0, 0, 1])), (1, bytes([11, 0, 0, 1])),
         (2, bytes([10, 1, 2, 3])), (-1, bytes([10, 1, 2, 3]))]
    assert reference_vpc.vpc_first_match([a, b, []], q).tolist() \
        == [0, 0, -1, 1, -1, -1]
    assert "vproxy_tpu" not in open(reference_vpc.__file__).read() \
        .split('"""', 2)[2]


def test_deployment_shape():
    from builders import switch_vpc as B
    sizes = B.vpc_sizes(50000, 64)
    assert sum(sizes) == 50000 and len(sizes) == 64
    assert sizes[0] == max(sizes) and 10500 < sizes[0] < 10600
    assert 160 <= min(sizes) <= 170
    t3, t4 = B.vpc_routes(sizes[40], 3), B.vpc_routes(sizes[40], 4)
    assert len(set(t3)) == len(t3) == sizes[40]
    assert set(t3) != set(t4) and set(t3) & set(t4)      # differ, overlap
    # held as a RouteTable holds them: every route before each that holds it
    for i, r in enumerate(t3):
        assert not any(B.gen.net_contains(e, r) for e in t3[:i])
    dep = B.build({"sizes": {"vpcs": 8, "routes": 400, "acls": 20}}, 5)
    pool = dep.pool_kind("route", 300, {"miss_every": 10}, 5)
    assert len({q[0] for q in pool}) == 300         # distinct addresses
    assert {q[2] for q in pool} == set(range(8)) and all(
        q[1] is None for q in pool)
    want = dep.answers_kind("route", pool, False, 5)
    other = dep.answers_kind("route", pool, True, 5)
    assert 0.85 < (want >= 0).mean() < 0.95 and (want != other).mean() > 0.5
    one = dep.work("route", pool[0])
    assert one == B.TABLE_ID + B.work.cidr_bytes(
        dep.plain["route"][pool[0][2]], False)


def test_a_cross_tenant_answer_is_not_correct():
    """The set serves one VPC's lookups from another VPC's table (right
    program, wrong table-id column): `correct` is false."""
    def plant(_svc):
        from vproxy_tpu.rules import engine
        orig = engine.CidrTableSet.dispatch_snap
        seen = [0]

        def crossed(self, snap, addrs, ports, keys, **kw):
            seen[0] += 1
            if seen[0] == 20:       # one batch, mid-window
                keys = keys[1:] + keys[:1]
            return orig(self, snap, addrs, ports, keys, **kw)
        engine.CidrTableSet.dispatch_snap = crossed
        plant.undo = lambda: setattr(engine.CidrTableSet, "dispatch_snap",
                                     orig)
    try:
        r = run.run_cell(CELL, 61, 1.0, False, require_tpu=False,
                         overrides=TOY, before_window=plant)
    finally:
        plant.undo()
    assert not r["correct"]
    assert r["compared"]["wrong_verdicts"]["value"] >= 1
    assert r["compared"]["failovers"]["value"] == 0
