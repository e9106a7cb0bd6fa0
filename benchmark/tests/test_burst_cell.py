"""`switch-vpc64.route-burst1024` and the seam it came through: a
driver that brings its own service, control, counters and device-side
checks (README.md, "A driver").

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_burst_cell.py -q

At toy size on the CPU, through run.py's own path with only the look
for a chip skipped: the cell is correct; its control (every lookup
answered from the next VPC's table, through the burst entry) is not;
the timed path broken underneath for each fault the cell can have — an
answer altered where it is produced, a rule of another VPC's table
handed back, a burst answered by the host's scan (right answers, wrong
server), a burst that took two launches (right answers, wrong count) —
turns `correct` false; a driver whose checks carry no proof that the
device served is refused before anything is driven; and a driver that
brings no hook is held to the five checks it always was.
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

import run  # noqa: E402
from selftest import TOY  # noqa: E402

CELL = "switch-vpc64.route-burst1024"
OWN = ["bursts_not_one_device_launch", "answered_on_host",
       "route_set_backend_not_jax"]
BURST = TOY["traffic"]["burst"]


def toy(seed: int, cell: str = CELL, trace: bool = False, **kw) -> dict:
    return run.run_cell(cell, seed, 1.0, trace, require_tpu=False,
                        overrides=TOY, **kw)


def values(r: dict) -> dict:
    return {k: c["value"] for k, c in r["compared"].items()}


def test_sound_run_is_correct_and_prints_its_own_checks():
    r = toy(2**31 + 5)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 10 * BURST
    assert list(r["compared"]) == ["wrong_verdicts", "undelivered"] + OWN
    assert all(c == {"value": 0, "limit": 0} for c in r["compared"].values())
    assert set(r["metrics"]) == {"matches_per_s", "classify_p99_ms",
                                 "setup_s"}


@pytest.mark.parametrize("seed", [21, 22, 2**31 + 23])
def test_control_is_not_correct(seed):
    r = toy(seed, control=True)
    v = values(r)
    assert not r["correct"] and r["failed"] > 0
    assert v["wrong_verdicts"] > 0
    # through the burst entry, claiming the device: only the answers fail
    assert [v[k] for k in ["undelivered"] + OWN] == [0, 0, 0, 0]


def test_traced_run_reports_what_it_can_read_and_nothing_else():
    """No ClassifyService: the dispatcher's metrics are not declared for
    this cell, and no reader invents one from the spans it lacks."""
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    r = toy(31, trace=True)
    m = r["metrics"]
    assert r["correct"]
    declared = {x["name"] for x in bench["per_layer"]
                if run.applies(x, CELL)}
    assert set(m) <= declared
    assert m["batch_mean"]["value"] == BURST
    assert m["launch_host_arrays"]["value"] == 1.0
    assert m["burst_call_us"]["value"] > 0
    assert m["vpc_tables_per_batch"]["value"] > 1
    assert m["dispatch_launch_us"]["value"] > 0
    for name in ("queue_wait_ms", "dispatcher_busy_pct", "parks_per_cycle",
                 "deliver_us_per_query", "dispatch_d2h_sync_us"):
        assert name not in m and name not in declared
    # every metric declared without a `workloads` list is one a cell
    # that goes round the service can read (the device ones on a chip)
    everywhere = {x["name"] for x in bench["per_layer"]
                  if "workloads" not in x}
    assert everywhere - set(m) == {"device_idle_pct"}


def test_altered_answer_is_not_correct():
    """One verdict of one burst altered where the set produces it."""
    def plant(svc):
        ts, seen = svc.route_set, [0]
        orig = ts.match

        def altered(views, addrs, ports=None):
            out = np.array(orig(views, addrs, ports))
            seen[0] += 1
            if seen[0] == 20:
                out[0] = -1 if out[0] >= 0 else 0
            return out
        ts.match = altered

    r = toy(41, before_window=plant)
    v = values(r)
    assert not r["correct"] and v["wrong_verdicts"] == 1 == r["failed"]
    assert [v[k] for k in OWN] == [0, 0, 0]


def test_a_rule_of_another_vpc_is_not_correct():
    """The entry hands back a RouteRule of another VPC's table at the
    same index: the mapping after the window must not take it."""
    def plant(svc):
        orig, seen = svc.route_lookup_burst, [0]

        def swapped(lookups):
            res = orig(lookups)
            seen[0] += 1
            if seen[0] == 10:
                other = svc.networks[0].routes.rules_v4
                res = [other[0] if r is not None else None for r in res]
            return res
        svc.route_lookup_burst = swapped

    r = toy(42, before_window=plant)
    assert not r["correct"] and values(r)["wrong_verdicts"] >= 1


def test_a_burst_answered_on_the_host_is_not_correct():
    """One burst takes route_lookup_burst's small-table branch (the
    host's scan a lookup): every answer right, none from the device."""
    def plant(svc):
        from vproxy_tpu.rules import engine
        orig, seen = svc.route_lookup_burst, [0]

        def hosted(lookups):
            seen[0] += 1
            if seen[0] != 15:
                return orig(lookups)
            keep, engine.SMALL_TABLE = engine.SMALL_TABLE, 1 << 30
            try:
                return orig(lookups)
            finally:
                engine.SMALL_TABLE = keep
        svc.route_lookup_burst = hosted

    r = toy(43, before_window=plant)
    v = values(r)
    assert not r["correct"]
    assert v["wrong_verdicts"] == 0 and v["undelivered"] == 0
    assert v["answered_on_host"] == BURST
    assert v["bursts_not_one_device_launch"] == 1
    assert r["failed"] == BURST


def test_a_burst_of_two_launches_is_not_correct():
    """One burst split in two dispatches (as a burst split by VPC would
    be): every answer right and from the device, the count wrong."""
    def plant(svc):
        ts, seen = svc.route_set, [0]
        orig = ts.match

        def split(views, addrs, ports=None):
            seen[0] += 1
            if seen[0] != 25:
                return orig(views, addrs, ports)
            h = len(addrs) // 2
            return np.concatenate([orig(views[:h], addrs[:h]),
                                   orig(views[h:], addrs[h:])])
        ts.match = split

    r = toy(44, before_window=plant)
    v = values(r)
    assert not r["correct"]
    assert v["wrong_verdicts"] == 0 and v["answered_on_host"] == 0
    assert v["bursts_not_one_device_launch"] == 1
    assert r["failed"] == 0     # nothing wrong, nothing from the host


def test_a_loop_that_dies_leaves_its_burst_undelivered():
    def plant(svc):
        orig, seen = svc.route_lookup_burst, [0]

        def dying(lookups):
            seen[0] += 1
            if seen[0] == 12:
                raise ValueError("planted fault in the burst entry")
            return orig(lookups)
        svc.route_lookup_burst = dying

    r = toy(45, before_window=plant)
    assert not r["correct"]
    assert values(r)["undelivered"] == BURST


# ---- the seam

def driver_module():
    return importlib.import_module("drivers.switch_burst_loop")


def test_checks_without_a_device_side_proof_are_refused(monkeypatch):
    d = driver_module()
    monkeypatch.setattr(d, "CHECKS", {k: "host" if v == "device" else v
                                      for k, v in d.CHECKS.items()})
    built = []
    builder = importlib.import_module("builders.switch_vpc_networks")
    monkeypatch.setattr(builder, "build",
                        lambda *a: built.append(a) or 1 / 0)
    with pytest.raises(SystemExit, match="proof that the device served"):
        toy(51)
    assert not built        # refused before anything was built or driven


@pytest.mark.parametrize("roles, said", [
    ({"wrong_verdicts": "device"}, "run.py's own"),
    ({"x": "device", "y": "sometimes"}, "unknown roles"),
])
def test_checks_that_name_run_pys_own_or_no_role_are_refused(
        monkeypatch, roles, said):
    monkeypatch.setattr(driver_module(), "CHECKS", roles)
    with pytest.raises(SystemExit, match=said):
        toy(52)


def test_checks_other_than_declared_are_refused(monkeypatch):
    d = driver_module()
    monkeypatch.setattr(d, "checks", lambda *a: {
        "bursts_not_one_device_launch": [0, 0]})
    with pytest.raises(SystemExit, match="declared the checks"):
        toy(53)


def test_a_loosened_limit_is_refused(monkeypatch):
    d = driver_module()
    orig = d.checks

    def loose(*a):
        got = orig(*a)
        got["answered_on_host"][1] = 5
        return got
    monkeypatch.setattr(d, "checks", loose)
    with pytest.raises(SystemExit, match="their limit is 0"):
        toy(54)


def test_a_driver_without_hooks_is_held_to_the_five_checks_it_was():
    """`northstar-100k.cidr-w1024`, the closed loop that brings no hook:
    the same `compared` keys, in the same order, each with limit 0, and
    the same end-to-end metric names as before the seam."""
    r = toy(61, cell="northstar-100k.cidr-w1024")
    assert r["correct"]
    assert list(r["compared"]) == [
        "wrong_verdicts", "undelivered", "answered_by_host_oracle",
        "failovers", "not_answered_by_device"]
    assert all(c == {"value": 0, "limit": 0} for c in r["compared"].values())
    assert list(r["metrics"]) == ["matches_per_s", "classify_p99_ms",
                                  "setup_s"]
    d = importlib.import_module("drivers.classify_closed_loop")
    assert not any(hasattr(d, h) for h in (
        "service", "control_service", "checks", "CHECKS", "counters"))
    assert run.declared_checks(d) is run.SERVICE_CHECKS


def test_instrument_wraps_what_exists():
    """A service that is no ClassifyService: the traced run's instrument
    wraps the calls it names and none it lacks, and puts them back."""
    import program

    class Svc:
        def __init__(self):
            self.calls = 0

        def entry(self):
            self.calls += 1

        def bench_spans(self):
            return [(self, "entry", "bench/entry")]

    svc = Svc()
    inst = program.Instrument(svc, 64)
    try:
        svc.entry()
        svc.entry()
    finally:
        inst.close()
    assert svc.calls == 2 and inst.totals["bench/entry"][0] == 2
    assert "entry" not in vars(svc)
    assert "bench/submit" not in inst.totals


# ---- the deployment: VpcNetworks over one table set

def test_the_networks_hold_the_tables_route_w1024_installs():
    """Same routes, same order, one table of one set a VPC; and the
    direct RouteTable is the list `set_routes` would have built."""
    from builders import switch_vpc, switch_vpc_networks
    from vproxy_tpu.vswitch.network import VpcNetwork
    config = {"sizes": {"vpcs": 8, "routes": 400, "acls": 20}}
    a = switch_vpc.build(config, 7)
    b = switch_vpc_networks.build(config, 7)
    assert a.plain == b.plain
    b.install()
    sw = b.switch
    assert len(sw.networks) == 8
    assert {id(v.table_set) for v in sw.views()} == {id(sw.route_set)}
    assert [v.size() for v in sw.views()] == [len(t) for t in
                                              b.plain["route"]]
    net = sw.networks[3]
    slow = VpcNetwork(99, net.v4net)
    slow.set_routes(list(net.routes.rules_v4))
    assert [r.alias for r in slow.routes.rules_v4] \
        == [r.alias for r in net.routes.rules_v4]
    pool = b.pool({"kinds": ["route"], "pool": 300, "miss_every": 10}, 7)
    want = b.answers(pool)[:, 0]
    got = sw.verdicts(sw.route_lookup_burst(sw.lookups(pool)),
                      np.array([q[2] for _k, q in pool], np.int32))
    assert got.tolist() == want.tolist() and (want >= 0).mean() > 0.8
    assert sw.counters()["host_lookups"] == 0
    # a small set is scanned on the host, and that is counted
    tiny = switch_vpc_networks.build(
        {"sizes": {"vpcs": 2, "routes": 40, "acls": 5}}, 7)
    tiny.install()
    tpool = tiny.pool({"kinds": ["route"], "pool": 50, "miss_every": 10}, 7)
    tiny.switch.route_lookup_burst(tiny.switch.lookups(tpool))
    assert tiny.switch.counters()["host_lookups"] == 50


def test_the_cell_as_declared():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = run.find_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["config"] == "switch-vpc64"
    traffic = run.load_json(HERE, "traffic", cell["traffic"] + ".json")
    twin = run.load_json(HERE, "traffic", "route-w1024.json")
    # the two switch cells differ in the entry point and in nothing else
    for k in ("pool", "zipf_s", "miss_every", "ramp_seconds", "kinds",
              "outstanding"):
        assert traffic[k] == twin[k]
    assert traffic["burst"] == 1024 and traffic["bursts_in_flight"] == 1 \
        and traffic["loops"] == 1
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == {"burst_call_us"}
    seven = [w["name"] for w in bench["workloads"] if w["name"] != CELL]
    service_only = [m["name"] for m in bench["per_layer"]
                    if m.get("workloads") == seven]
    assert len(service_only) == 18 and "queue_wait_ms" in service_only
