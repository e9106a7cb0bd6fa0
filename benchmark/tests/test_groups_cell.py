"""`lb-groups256.cpick-w768`: its per-layer metrics, each computed from
its own `metrics/<name>.json` on hand-made span totals; its plain
reference; the deployment's shape; and a planted cross-group fault,
which `correct` has to catch at toy size on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_groups_cell.py -q

The `cpick` control (the pick taken from the next group's table) runs
with every other cell's control in test_correct.py.
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
import reference as ref  # noqa: E402
import reference_groups  # noqa: E402
import run  # noqa: E402
from selftest import TOY  # noqa: E402

CELL = "lb-groups256.cpick-w768"
MS = 1_000_000


def total(n, sum_ns, items=0):
    return {"n": n, "sum_ns": sum_ns, "sum_cpu_ns": 0, "sum_items": items,
            "buckets": [0] * 28, "first_ns": 0, "last_ns": 0}


# a second of a dispatcher whose wakes are one grouped batch of ~380
HAND = {
    "engine/cycle": total(160, 960 * MS, items=60_800),
    "engine/dispatch": total(160, 500 * MS),
    "engine/launch": total(160, 300 * MS),
    "engine/group_pick": total(160, 4 * MS, items=51_200),
}
WANT = {
    "grp_device_picks_per_batch": 320.0,    # 51,200 picks / 160 batches
    "grp_launches_per_batch": 1.0,
    "grp_dispatch_cycle_us": 6_000.0,       # 960 ms / 160 wakes
}
KERNEL = ("grp_fused_group_pick_us_per_batch",
          "grp_fused_group_pick_roofline")


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
    """No span totals at all, or (picks a batch) a program that has no
    `engine/group_pick` span: nothing, and no exception."""
    assert read(name, {}, monkeypatch) is None
    if name == "grp_device_picks_per_batch":
        parent = {k: v for k, v in HAND.items()
                  if k != "engine/group_pick"}
        assert read(name, parent, monkeypatch) is None
        assert read(name, {"engine/group_pick": total(0, 0)},
                    monkeypatch) is None


def test_two_launches_a_batch_show_in_launches_per_batch(monkeypatch):
    split = dict(HAND, **{"engine/launch": total(320, 300 * MS)})
    assert read("grp_launches_per_batch", split, monkeypatch) == 2.0


def test_the_five_metrics_are_declared_for_the_groups_cell_alone():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == set(WANT) | set(KERNEL)
    assert all(m["moves"] == "matches_per_s" for m in mine.values())
    layers = {m["layer"] for m in bench["per_layer"]
              if m.get("workloads") != [CELL]}
    assert {m["layer"] for m in mine.values()} <= layers
    for n in WANT:
        assert mine[n]["source"] == "program_span"
    for n in KERNEL:
        spec = run.load_json(HERE, "metrics", n + ".json")
        assert spec["params"]["program"] == "jit_fused_group_pick"
        assert mine[n]["source"] == "device_trace"
    from vproxy_tpu.ops import fused
    assert fused.group_jit.__name__ == "fused_group_pick"
    cell = run.find_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["config"] == "lb-groups256"
    config = next(c for c in bench["configs"] if c["name"] == "lb-groups256")
    assert config["source"] == run.load_json(
        HERE, "configs", "lb-groups256.json")["source"]


def test_reference_picks_inside_the_matched_group_alone():
    rules = [("a.example.com", 0, None), ("b.example.com", 0, None),
             ("c.example.com", 0, None), ("d.example.com", 0, None)]
    healthy = [["g0|10.0.0.1:80", "g0|10.0.0.2:80", "g0|10.0.0.3:80"],
               ["g1|10.0.1.1:80"], []]
    ip = bytes([172, 16, 3, 9])
    qs = [("www.a.example.com", 0, None, ip), ("b.example.com", 0, None, ip),
          ("c.example.com", 0, None, ip), ("d.example.com", 0, None, ip),
          ("x.invalid", 0, None, ip)]
    got = reference_groups.classify_pick(rules, [0, 1, 2, -1], healthy,
                                         251, qs)
    tab0 = ref.maglev_table(healthy[0], 251)
    assert got.tolist() == [[0, tab0[ref.fnv64(ip) % 251]], [1, 0],
                            [2, -1], [3, -1], [-1, -1]]
    # address affinity: no port in the flow key
    assert reference_groups.address_pick(tab0, ip) \
        != ref.maglev_pick(tab0, ip, 4242) or len(set(tab0)) == 1
    other = reference_groups.classify_pick(rules, [0, 1, 2, -1], healthy,
                                           251, qs, shift=1)
    assert other[:, 0].tolist() == got[:, 0].tolist()
    assert other[:, 1].tolist() == [0, -1, tab0[ref.fnv64(ip) % 251], -1, -1]
    assert "vproxy_tpu" not in open(reference_groups.__file__).read() \
        .split('"""', 2)[2]


def test_reference_table_is_the_programs_group_table():
    """The identities and the table a real `source` ServerGroup holds
    are the reference's, member for member."""
    from vproxy_tpu.components.elgroup import EventLoopGroup
    from vproxy_tpu.components.servergroup import (HealthCheckConfig,
                                                   ServerGroup)
    from vproxy_tpu.rules import maglev
    elg = EventLoopGroup("ref-elg", 1)
    g = ServerGroup("g7", elg, HealthCheckConfig(protocol="none",
                                                 period_ms=60000),
                    method="source")
    try:
        for b in range(5):
            g.add(f"s{b}", f"10.0.7.{b + 1}", 80)
        for s in g.servers:
            s.healthy = s.name != "s2"
        g._recalc()
        servers, table = g.maglev_table()
        names = [f"g7|10.0.7.{b + 1}:80" for b in range(5) if b != 2]
        assert [g.maglev_identity(s) for s in servers] == names
        assert table.tolist() == ref.maglev_table(names, maglev.GROUP_M)
        ip = bytes([172, 16, 200, 1])
        assert g.next(ip).svr is servers[
            reference_groups.address_pick(table.tolist(), ip)]
    finally:
        g.close()
        elg.close()


def test_deployment_shape():
    from builders import lb_groups as B
    sizes = B.group_sizes(256, 1024, 2**31 + 5)
    assert sum(sizes) == 1024 and len(sizes) == 256
    assert min(sizes) == 1 and max(sizes) == 8
    assert sizes != B.group_sizes(256, 1024, 6)
    with pytest.raises(ValueError):
        B.group_sizes(4, 64, 1)
    config = run.load_json(HERE, "configs", "lb-groups256.json")
    assert config["assumed"]["down_every"] == 16 and config["reduced"] == []
    config["sizes"].update(TOY["sizes"])
    dep = B.build(config, 5)
    members = [m for ms in dep.members for m in ms]
    assert len(members) == 64 and len(dep.members) == 16
    assert sum(1 for _n, up in members if not up) == 4      # 1 in 16
    assert members[0][0].startswith("g0|10.0.0.1:80")
    traffic = run.load_json(HERE, "traffic", "cpick-w768.json")
    assert traffic["outstanding"] == 768 and traffic["kinds"] == ["cpick"]
    pool = dep.pool_kind("cpick", 600, traffic, 5)
    assert len({(q[0], q[3]) for q in pool}) == 600     # distinct pairs
    assert all(q[1] == 0 and q[2] is None and q[4] is None for q in pool)
    want = dep.answers_kind("cpick", pool, False, 5)
    other = dep.answers_kind("cpick", pool, True, 5)
    hit = want[:, 0] >= 0
    assert 0.85 < hit.mean() < 0.95 and (want[~hit] == -1).all()
    assert (want[:, 0] == other[:, 0]).all()
    assert (want[hit, 1] != other[hit, 1]).mean() > 0.3
    assert dep.work("cpick", pool[0]) == B.LbHost.work(
        dep, "cpick", pool[0]) + B.RULE_GROUP


def test_another_groups_backend_is_not_correct():
    """The set serves one batch's picks from the rows of other groups
    (right program, wrong rule -> group column): `correct` is false."""
    def plant(_svc):
        from vproxy_tpu.rules import engine
        orig = engine.grouped_dispatch
        seen = [0]

        def crossed(hsnap, ssnap, *a, **kw):
            seen[0] += 1
            if seen[0] == 20:       # one batch, mid-window
                import jax.numpy as jnp
                col = hsnap[6]
                hsnap = hsnap[:6] + ((col[0], jnp.roll(col[1], 1, axis=0)),)
            return orig(hsnap, ssnap, *a, **kw)
        engine.grouped_dispatch = crossed
        plant.undo = lambda: setattr(engine, "grouped_dispatch", orig)
    try:
        r = run.run_cell(CELL, 61, 1.0, False, require_tpu=False,
                         overrides=TOY, before_window=plant)
    finally:
        plant.undo()
    assert not r["correct"]
    assert r["compared"]["wrong_verdicts"]["value"] >= 1
    assert r["compared"]["failovers"]["value"] == 0
