#!/usr/bin/env python3
"""CPU rehearsal of the harness at toy size — no chip, no device metric.

    python3 benchmark/selftest.py

Checks, each printed as one line: every cell of BENCHMARK.json runs end
to end through run.py's own path (builder, driver, readers) with
`correct` true; a cell whose driver brings its own service and checks
prints those checks, its control comes out not correct through the
driver's own entry, and without a device-side proof it is refused; one
seed draws the same pool and query sequence twice;
the plain reference agrees with its own linear scan and with the
program's oracle (`rules/oracle.py`, the matchers' `oracle_snap`) on a
sample; and the trace reduction gives the expected busy time, program
times and idle gaps on a hand-made trace and on the excerpt of a chip
trace kept under testdata/. Exits non-zero on the first failure.
"""
from __future__ import annotations

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"     # a rehearsal, by definition

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402  (sets sys.path for the repo root too)

TOY = {"sizes": {"hint_rules": 1000, "routes": 500, "acls": 50,
                 "groups": 16, "backends": 64, "maglev_m": 251},
       "traffic": {"outstanding": 64, "pool": 512, "burst": 64}}
SEED = 2**31 + 12345


def check(ok: bool, what: str) -> None:
    print(("ok   " if ok else "FAIL ") + what, flush=True)
    if not ok:
        sys.exit(1)


def cells_end_to_end(bench: dict) -> None:
    for w in bench["workloads"]:
        for trace in (False, True):
            r = run.run_cell(w["name"], SEED, 2.0, trace, require_tpu=False,
                             overrides=TOY)
            names = {m["name"] for m in
                     bench["per_layer" if trace else "end_to_end"]}
            check(r["correct"] and r["failed"] == 0 and r["attempted"] > 0
                  and set(r["metrics"]) <= names,
                  f"{w['name']} trace={int(trace)}: correct, "
                  f"{r['attempted']} queries, metrics "
                  f"{sorted(r['metrics'])}")
            if trace:
                check(not any("roofline" in k or "us_per_batch" in k
                              or k == "device_idle_pct"
                              for k in r["metrics"]),
                    "  no device metric from a CPU run")


def driver_hooks(bench: dict) -> None:
    """The cells whose driver brings its own service, control and
    checks (README.md, "A driver")."""
    import importlib
    for w in bench["workloads"]:
        traffic = run.load_json(HERE, "traffic", w["traffic"] + ".json")
        driver = importlib.import_module("drivers." + traffic["driver"])
        if not hasattr(driver, "checks"):
            continue
        r = run.run_cell(w["name"], SEED, 1.0, False, require_tpu=False,
                         overrides=TOY, control=True)
        own = list(r["compared"])[len(run.KEPT):]
        check(list(r["compared"])[:2] == list(run.KEPT)
              and own == list(driver.CHECKS)
              and "device" in driver.CHECKS.values(),
              f"{w['name']}: compared by run.py's {list(run.KEPT)} and the "
              f"driver's own {own}")
        check(not r["correct"]
              and r["compared"]["wrong_verdicts"]["value"] > 0
              and all(r["compared"][k]["value"] == 0 for k in own),
              f"{w['name']}: its control, through the driver's own entry, "
              f"is not correct ({r['compared']['wrong_verdicts']['value']} "
              f"wrong verdicts, the device-side checks 0)")
        keep = driver.CHECKS
        driver.CHECKS = {k: "other" for k in keep}
        try:
            run.run_cell(w["name"], SEED, 1.0, False, require_tpu=False,
                         overrides=TOY)
            refused = False
        except SystemExit as e:
            refused = "proof that the device served" in str(e)
        finally:
            driver.CHECKS = keep
        check(refused, f"{w['name']}: without a device-side proof among "
                       f"its checks the driver is refused")


def same_seed_same_inputs() -> None:
    import importlib
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = w["name"]
        config = run.load_json(HERE, "configs", w["config"] + ".json")
        traffic = run.load_json(HERE, "traffic", w["traffic"] + ".json")
        config["sizes"].update(TOY["sizes"])
        traffic.update(TOY["traffic"])
        builder = importlib.import_module(
            "builders." + traffic.get("builder", config["builder"]))
        driver = importlib.import_module("drivers." + traffic["driver"])
        plans = []
        for seed in (SEED, SEED, SEED + 1):
            dep = builder.build(config, seed)
            plans.append(driver.Plan(dep, traffic, seed, 2.0))
        a, b, c = plans
        check(a.pool == b.pool and (a.seq == b.seq).all(),
              f"{cell}: one seed, the same pool and sequence twice")
        check(a.pool != c.pool and (a.seq != c.seq).any(),
              f"{cell}: another seed, other queries in another order")
        kinds = lambda p: [k for k, _q in p.pool]  # noqa: E731
        check(kinds(a) == kinds(c),
              f"{cell}: every seed has the same kind at each pool rank")


def reference_agrees() -> None:
    """gen + reference against the program's own oracle, rule for rule."""
    import numpy as np
    import gen
    import reference as ref
    from vproxy_tpu.rules import oracle
    from vproxy_tpu.rules.engine import CidrMatcher
    from vproxy_tpu.rules.ir import AclRule, Hint, HintRule, Proto
    from vproxy_tpu.rules import maglev
    from vproxy_tpu.utils.ip import Network, mask_bytes

    tag = gen.seed_tag(SEED)
    rules = gen.north_star_hint_rules(2000, tag)
    rules[7] = ("*", 0, None)            # forms the tables do not have:
    rules[11] = (None, 0, "/api")        # still the reference's to get right
    rules[13] = (None, 8443, None)
    pool = gen.hint_pool(600, rules, tag, SEED, 10)
    pool += [("nohost.invalid", 0, "/api/v3/u"), (None, 0, "/api/x"),
             ("a.b.invalid", 8443, None), (rules[40][0], 80, None)]
    fast = ref.HintReference(rules)
    prog_rules = [HintRule(host=h, port=p, uri=u) for h, p, u in rules]
    bad = 0
    for q in pool:
        want = oracle.search(prog_rules, Hint(host=q[0], port=q[1], uri=q[2]))
        bad += (fast.search(q) != want) + (ref.hint_search(rules, q) != want)
    check(bad == 0, f"hint reference == its linear scan == rules/oracle.py "
                    f"on {len(pool)} queries")

    # the route list is one a RouteTable holds: the fast post-order
    # equals the plain copy of addRule and the program's own RouteTable
    from vproxy_tpu.rules.ir import RouteRule, RouteTable
    added = gen.distinct_routes(900)
    slow: list = []
    for r in added:
        gen.route_table_insert(r, slow)
    rt = RouteTable()
    for i, (v, m) in enumerate(added):
        rt.add(RouteRule(f"r{i}", Network(v.to_bytes(4, "big"),
                                          mask_bytes(m))))
    prog = [(int.from_bytes(r.rule.ip, "big"),
             sum(bin(b).count("1") for b in r.rule.mask))
            for r in rt.rules_v4]
    check(len(set(added)) == 900 and gen.route_table_order(added) == slow
          == prog, "900 distinct routes: post-order == plain addRule copy "
                   "== the program's RouteTable order")

    routes, acls = gen.north_star_routes(3000), gen.north_star_acls(400)
    for kind, nets in (("route", routes), ("acl", acls)):
        networks = [Network(int(n[0]).to_bytes(4, "big"), mask_bytes(n[1]))
                    for n in nets]
        acl = [AclRule(f"r{i}", networks[i], Proto.TCP, n[2], n[3], True)
               for i, n in enumerate(nets)] if kind == "acl" else None
        cm = CidrMatcher(networks, backend="host", acl=acl)
        qs = gen.cidr_pool(300, nets, SEED, 10, kind == "acl")
        got = ref.cidr_first_match(nets, qs, kind == "acl")
        want = [cm.oracle_snap(cm.snapshot(), q[0],
                               q[1] if kind == "acl" else None) for q in qs]
        hit = np.array(want) >= 0
        check(got.tolist() == want and 0.85 < hit.mean() < 0.95,
              f"{kind} reference == CidrMatcher.oracle_snap on {len(qs)} "
              f"lookups ({hit.mean():.0%} match, "
              f"{len(set(want))} different answers)")
        if kind == "route":
            ml = np.array([n[1] for n in nets])
            longest = [max((n[1] for n in nets
                            if (int.from_bytes(q[0], "big") >> (32 - n[1]))
                            == (n[0] >> (32 - n[1]))), default=-1)
                       for q in qs]
            check([ml[g] if g >= 0 else -1 for g in got] == longest
                  and len(set(want)) > len(qs) // 2,
                  "  first containing route == longest prefix; answers "
                  "spread over the table")
            stale = ref.cidr_first_match(gen.mutate_nets(nets, SEED, 0.05),
                                         qs, False)
            check((stale != got).any(), "  the stale control differs")
        else:
            check((ref.cidr_first_match(nets, qs, False) != got).any(),
                  "  the noport control differs")

    names = [f"10.0.{i}.1:80" for i in range(40)]
    tab = ref.maglev_table(names, 251)
    check(tab == maglev.build_table([(s, 1) for s in names], 251).tolist()
          and all(ref.maglev_pick(tab, bytes([172, 16, 0, i]), 1000 + i)
                  == maglev.pick(np.array(tab), bytes([172, 16, 0, i]),
                                 1000 + i) for i in range(50)),
          "maglev reference == rules/maglev.py table and picks")
    stale = gen.mutate_hint_rules(rules, SEED)
    check(sum(a != b for a, b in zip(rules, stale)) == 20,
          "the stale control changes 1 % of the rules")


def trace_reduction() -> None:
    import tracered as T
    us = 1000
    hand = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": T.MODULES, "events": [
                ["jit_a(1)", 10 * us, 30 * us], ["jit_b(2)", 60 * us, 20 * us],
                ["jit_a(1)", 100 * us, 30 * us]]},
            {"name": T.OPS, "events": [
                ["fusion.1", 10 * us, 10 * us], ["fusion.2", 15 * us, 25 * us],
                ["copy.3", 60 * us, 20 * us], ["fusion.1", 100 * us, 30 * us]]}]},
        {"name": "/host:CPU", "lines": [{"name": "t1", "events": [
            ["bench/window", 0, 200 * us], ["bench/submit", 40 * us, 15 * us],
            ["bench/encode", 42 * us, 8 * us],
            ["bench/deliver", 85 * us, 10 * us]]}]}]}
    check(abs(T.busy_seconds(hand) - 80e-6) < 1e-12,
          "hand trace: busy = union of op intervals = 80 us")
    p = T.programs(hand)
    check(p["jit_a"][0] == 2 and abs(p["jit_a"][1] - 60e-6) < 1e-12
          and abs(p["jit_b"][1] - 20e-6) < 1e-12,
          "hand trace: jit_a 2 launches 60 us, jit_b 1 launch 20 us")
    gaps = dict(T.idle_gaps(hand, 0, 200 * us))
    check(abs(gaps["bench/encode"] - 8e-6) < 1e-12
          and abs(gaps["bench/submit"] - 7e-6) < 1e-12
          and abs(gaps["bench/deliver"] - 10e-6) < 1e-12
          and abs(sum(gaps.values()) - 120e-6) < 1e-12,
          "hand trace: 120 us idle, split over encode 8 / submit 7 / "
          "deliver 10 / none 95")
    check(T.top_device_ops(hand, 2) == [["fusion.1", 40e-6],
                                        ["fusion.2", 25e-6]],
          "hand trace: top operations")
    for name in sorted(os.listdir(os.path.join(HERE, "testdata"))):
        if not name.startswith("clip_") or not name.endswith(".json"):
            continue
        tr = T.load(os.path.join(HERE, "testdata", name))
        want = run.load_json(HERE, "testdata",
                             name.replace("clip_", "expected_"))
        progs = {k: [v[0], round(v[1], 9)] for k, v in T.programs(tr).items()}
        check(T.device_planes(tr) and progs == want["programs"]
              and abs(T.busy_seconds(tr) - want["busy_s"]) < 1e-9,
              f"{name}: programs {progs}, busy {T.busy_seconds(tr):.6f}s "
              f"as recorded")


def main() -> int:
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    trace_reduction()
    reference_agrees()
    same_seed_same_inputs()
    cells_end_to_end(bench)
    driver_hooks(bench)
    print("selftest passed (CPU rehearsal: no device metric printed)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
