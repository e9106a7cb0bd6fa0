"""Round-13 verify drive: fused classify+pick dispatch — one launch,
one memory sweep per batch — end-to-end through the operator surface.

Run: env PYTHONPATH=/root/repo JAX_PLATFORMS=cpu python _verify_fused.py

Phases:
  [1] operator plane — an upstream built via the COMMAND GRAMMAR on the
      single-device "jax" backend publishes packed fused tables:
      `list-detail upstream` shows `fused on(jit,...)`, the HTTP detail
      carries the `engine.fused` object, and the
      vproxy_engine_{dispatch_launches,fused_dispatches}_total families
      scrape.
  [2] one launch, bit-identical — classify_and_pick over a batch: the
      launch counter moves by EXACTLY 1 (the unfused chain moves it by
      2), verdicts == the host index, picks == the host maglev oracle.
  [3] generation install under fused load — `add fault
      engine.swap.stall` through the grammar while classify_and_pick
      hammers: every (verdict, pick) pair comes from ONE snapshot pair
      (old generation through the stall, new after the atomic flip),
      zero failures, packed tables republished.
  [4] consumer surfaces — ClassifyService.submit_classify_pick batches
      through a FusedPair (fused micro-batch parity) and a StepLoop
      with the maglev plane (submit_pick at zero extra launches,
      status fused:true).
  [5] fallback — a "host"-backend matcher publishes no packed tables
      and classify_and_pick serves the two-dispatch chain with the
      same answers.
"""
import json
import os
import sys
import threading
import time
import urllib.request

os.environ.setdefault("VPROXY_TPU_SWAP_STALL_S", "0.6")

from vproxy_tpu.utils.jaxenv import force_cpu  # noqa: E402

force_cpu(1)

import numpy as np  # noqa: E402


def say(msg):
    print(msg, flush=True)


def synth_clients(n):
    return [bytes((10, 1 + i // 65536, (i // 256) % 256, i % 256))
            for i in range(n)]


def main():
    from vproxy_tpu.control.app import Application
    from vproxy_tpu.control.command import Command
    from vproxy_tpu.control.http_controller import HttpController
    from vproxy_tpu.rules import engine as E
    from vproxy_tpu.rules.engine import HintMatcher
    from vproxy_tpu.rules.ir import Hint, HintRule
    from vproxy_tpu.rules.maglev import (FusedPair, MaglevMatcher,
                                         classify_and_pick)
    from vproxy_tpu.utils.metrics import GlobalInspection

    app = Application(workers=2)
    ctl = HttpController(app, "127.0.0.1", 0)
    ctl.start()
    try:
        # ---- [1] operator plane: grammar-built upstream -> fused on
        Command.execute(app, "add upstream u0")
        Command.execute(app, "add server-group g0 timeout 200 period 200 "
                             "up 1 down 2")
        Command.execute(
            app, 'add server-group g0 to upstream u0 weight 10 '
                 'annotations {"vproxy/hint-host":"app.fused.example"}')
        ups = app.upstreams["u0"]
        assert ups._matcher.backend == "jax", ups._matcher.backend
        fs = ups._matcher.fused_stat()
        assert fs["available"] and fs["kernel"] == "jit", fs
        line = Command.execute(app, "list-detail upstream")[0]
        assert "fused on(jit," in line, line
        with urllib.request.urlopen(
                f"http://127.0.0.1:{ctl.bind_port}/api/v1/module/upstream",
                timeout=5) as r:
            doc = json.loads(r.read())
        obj = doc[0]["engine"]["fused"]
        assert obj["available"] and obj["kernel"] == "jit" \
            and obj["packed_bytes"] > 0, obj
        text = GlobalInspection.get().prometheus_string()
        for fam in ("vproxy_engine_dispatch_launches_total",
                    "vproxy_engine_fused_dispatches_total"):
            assert fam in text, fam
        say(f"[1] grammar upstream on backend=jax publishes packed "
            f"tables: list-detail '{line.split('checksum')[1].strip()}', "
            f"HTTP fused={obj}, launch-counter families scrape")

        # ---- [2] one launch, bit-identical (verdict, pick[, route])
        rules = [HintRule(host=f"svc{i}.ns{i % 97}.fused.example")
                 for i in range(20_000)]
        rules += [HintRule(host="*", uri="/w"),
                  HintRule(uri="/static/7"),
                  HintRule(host="p.fused.example", port=443)]
        hm = HintMatcher(rules, backend="jax")
        mm = MaglevMatcher([(f"b{i}:10.0.0.{i}:80", 1 + i % 3)
                            for i in range(9)])
        b = 384
        hints = [Hint.of_host(f"svc{(i * 7) % 20_000}"
                              f".ns{(i * 7) % 97}.fused.example")
                 for i in range(b - 2)]
        hints += [Hint(uri="/static/7"), Hint()]
        ips = synth_clients(b)
        ports = [None if i % 3 == 0 else 1024 + i for i in range(b)]
        classify_and_pick(hm, mm, hints, ips, ports)  # warm the jit
        l0, f0 = E.dispatch_launches_total(), E.fused_dispatches_total()
        v, p, _hp, _mp = classify_and_pick(hm, mm, hints, ips, ports)
        dl = E.dispatch_launches_total() - l0
        assert dl == 1, f"fused batch cost {dl} launches"
        assert E.fused_dispatches_total() - f0 == 1
        hsnap, msnap = hm.snapshot(), mm.snapshot()
        for i in range(b):
            assert int(v[i]) == hm.index_snap(hsnap, hints[i]), i
            assert int(p[i]) == mm.pick_snap(msnap, ips[i], ports[i]), i
        l0 = E.dispatch_launches_total()
        np.asarray(hm.dispatch_snap(hsnap, hints))
        np.asarray(mm.dispatch_snap(msnap, ips, ports))
        chain = E.dispatch_launches_total() - l0
        assert chain == 2, chain
        say(f"[2] {b}-query batch: fused=1 launch (chain=2), "
            f"verdicts==host index, picks==maglev oracle")

        # ---- [3] stalled generation install under fused load
        rules2 = [HintRule(host=f"svc{i}.ns{i % 97}.fused.example")
                  for i in range(1000)]
        gen0 = hm.generation
        Command.execute(app, "add fault engine.swap.stall count 1")
        done = threading.Event()
        err = []

        def swap():
            try:
                hm.set_rules(rules2)
            except Exception as e:  # noqa: BLE001
                err.append(e)
            finally:
                done.set()

        th = threading.Thread(target=swap, daemon=True)
        t0 = time.monotonic()
        th.start()
        served = old_served = 0
        probe = [Hint.of_host("svc7.ns7.fused.example"), Hint()]
        pips = synth_clients(2)
        want_picks = [mm.pick_snap(msnap, ip) for ip in pips]
        while not done.is_set():
            vv, pp, _h, _m = classify_and_pick(hm, mm, probe, pips)
            assert int(vv[0]) >= 0 and int(vv[1]) == -1, vv
            assert [int(x) for x in pp] == want_picks, pp
            if hm.generation == gen0:
                old_served += 1
            served += 1
        th.join(10)
        assert not err and hm.generation == gen0 + 1
        assert old_served >= 1, "no batch observed the old generation"
        assert hm.fused_stat()["available"], "packed tables lost on swap"
        say(f"[3] stalled install ({time.monotonic() - t0:.2f}s incl. "
            f"0.6s failpoint): {served} fused batches served, "
            f"{old_served} on the old generation, 0 failures, packed "
            f"tables republished (gen {gen0}->{hm.generation})")

        # ---- [4] consumer surfaces: service cpick + step loop
        from vproxy_tpu.rules.service import ClassifyService
        pair = FusedPair(hm, mm)
        hsnap2, msnap2 = hm.snapshot(), mm.snapshot()
        q_hints = [Hint.of_host(f"svc{i}.ns{i % 97}.fused.example")
                   for i in range(16)]
        q_ips = synth_clients(16)
        svc = ClassifyService(mode="device")
        try:
            got, evs = {}, []
            for i in range(16):
                ev = threading.Event()
                evs.append(ev)
                svc.submit_classify_pick(
                    pair, q_hints[i], q_ips[i], None,
                    lambda vv, pp, pl, i=i, ev=ev: (
                        got.__setitem__(i, (vv, pp)), ev.set()))
            assert all(ev.wait(30) for ev in evs)
            for i in range(16):
                assert got[i] == (hm.index_snap(hsnap2, q_hints[i]),
                                  mm.pick_snap(msnap2, q_ips[i])), i
        finally:
            svc.close()
        from vproxy_tpu.cluster.submit import StepLoop
        sl = StepLoop(hm, None, step_ms=1, batch_cap=8, timeout_ms=2000,
                      maglev=mm)
        assert sl.status()["fused"]
        sl.start()
        try:
            res, ev = [], threading.Event()
            sl.submit_pick(q_hints[3], q_ips[3], None,
                           lambda vv, pp, pl: (res.append((vv, pp)),
                                               ev.set()))
            assert ev.wait(15)
            assert res[0] == (hm.index_snap(hsnap2, q_hints[3]),
                              mm.pick_snap(msnap2, q_ips[3]))
        finally:
            sl.stop()
        say(f"[4] service cpick 16/16 parity through the FusedPair; "
            f"StepLoop(maglev=) status fused=true, submit_pick answers "
            f"(verdict, pick) through the step clock")

        # ---- [5] fallback: no packed tables -> the two-dispatch chain
        v5c, _p, _h, _m = classify_and_pick(hm, mm, probe, pips)
        hm_host = HintMatcher(list(rules2), backend="host")
        assert hm_host.fused_stat() == {"available": False}
        assert E.fused_dispatch(hm_host, hm_host.snapshot(), mm,
                                mm.snapshot(), probe, pips) is None
        v5, p5, _h, _m = classify_and_pick(hm_host, mm, probe, pips)
        assert [int(x) for x in v5] == [int(x) for x in v5c] and \
            [int(x) for x in p5] == want_picks
        say("[5] a host-backend matcher publishes no packed tables; "
            "classify_and_pick falls back to the chain, same answers")

        say("FUSED VERIFY OK")
    finally:
        try:
            Command.execute(app, "remove fault engine.swap.stall")
        except Exception:  # noqa: BLE001
            pass
        try:
            ctl.stop()
        except Exception:  # noqa: BLE001
            pass
        app.close()


if __name__ == "__main__":
    sys.exit(main() or 0)
