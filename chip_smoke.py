#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served classify path runs
on the attached accelerator.

    python3 chip_smoke.py        # no arguments, no environment

One process (one process per chip). Exits 0 only if every gate below
held on a TPU; prints one JSON object as its last stdout line. It never
sets JAX_PLATFORMS, imports nothing that pins the CPU, and has no switch
that lets a CPU run pass: off the chip it names the platform it found
and exits 1 before running anything.

Legs, all through the entry points a user reaches:

* native — libvtl.so is removed and rebuilt from native/vtl.cpp on this
  machine; the pure-Python provider cannot stand in.
* served — an Application as main.py builds it, configured through
  Command.execute: one upstream, 256 server-groups annotated with Host
  hints (BASELINE.json config 2's group count), id-backends on loopback,
  a tcp-lb in http-splice mode. Bursts of 64 concurrent HTTP/1.1
  requests with distinct Host headers under VPROXY_TPU_CLASSIFY=device:
  every response comes from the group rules/oracle.py names, and the
  ClassifyService counters show the DEVICE answered every query
  (device_queries == requests, oracle_queries == 0, failovers == 0).
  The same bursts under the default `auto` policy print the
  inline/device split (information, not a gate).
* grouped — the same served path over `source` groups: a second
  upstream of 8 server-groups of method `source`, 3 id-backends each, so
  its accept path submits classify AND the matched group's pick in one
  call (maglev.GroupedPair, one launch a batch). A burst, then one
  backend is stopped and its health check takes it down (one group's
  row is rebuilt, no other), then a second burst: on both sides of the
  edge every response comes from the backend the host planes name
  (rules/oracle.py for the group, ServerGroup.next for the member), the
  device made every pick, and after the edge nobody reaches the dead
  backend.
* width — the north-star table (100k hint rules, 50k routes, 5k ACLs;
  benchmark/gen.py, the tables of the northstar-100k cells) installed
  through set_rules/set_networks (the
  TableInstaller) on whatever default_backend() returns here; 16,384
  hint, route and ACL queries (+ fused classify+pick) from several
  threads through ClassifyService(mode="device"): every verdict equals
  the host index, a sample equals the linear oracle, no failover, a
  fused batch costs exactly one launch, and a generation install under
  queries serves the new rule.

In the served, grouped and width legs, on the "jax" backend, every
launch was handed ONE numpy array — its batch's packed arena
(vproxy_engine_launch_host_arrays_total over
vproxy_engine_dispatch_launches_total is 1.0): the programs slice byte
columns out of int32 words, so the answers above are also the proof of
the chip's byte order.

Printed but not gated (bring-up evidence, not benchmark numbers): table
build / upload seconds, every compile with its seconds, persistent
compile-cache hits and misses, first and steady dispatch at batch
1 / 256 / 16,384 around block_until_ready, peak device bytes, per-device
table bytes, the fused program's packed bytes.
"""
from __future__ import annotations

import json
import os
import random
import selectors
import socket
import sys
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 20260926  # query/host sampling and the rule names' tag label

FAILURES: list[str] = []


def say(msg: str) -> None:
    print(msg, flush=True)


def gate(ok: bool, what: str) -> None:
    """Record one pass/fail condition; the run fails if any gate did."""
    if not ok:
        FAILURES.append(what)
        say(f"FAIL: {what}")


class LaunchArrays:
    """numpy arrays handed to the jitted calls of a stretch, over its
    launches (vproxy_engine_launch_host_arrays_total over
    vproxy_engine_dispatch_launches_total): 1.0 where every launch took
    its batch as one packed arena, which the "jax" backend always does."""

    def __init__(self):
        from vproxy_tpu.rules import engine as E
        self._e = E
        self._l0 = E.dispatch_launches_total()
        self._a0 = E.launch_host_arrays_total()

    def check(self, tag: str, backend: str) -> None:
        dl = self._e.dispatch_launches_total() - self._l0
        da = self._e.launch_host_arrays_total() - self._a0
        say(f"{tag}: vproxy_engine_launch_host_arrays_total / "
            f"vproxy_engine_dispatch_launches_total = {da} / {dl}"
            + (f" = {da / dl:.3f}" if dl else ""))
        if backend == "jax":
            gate(dl > 0 and da == dl,
                 f"{tag}: {da} numpy arrays over {dl} launches on backend "
                 f"jax, want one packed arena a launch")


# ------------------------------------------------------------ jax evidence

class JaxLog:
    """Compiles and persistent-cache traffic, as jax.monitoring reports
    them: one (name, seconds) per backend compile request (a cache hit
    shows as a request with a small duration), plus hit/miss events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as mon
        self.compiles: list[tuple[str, float]] = []
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == self.COMPILE:
            self.compiles.append((str(kw.get("fun_name", "?")), secs))

    def _event(self, event: str, **kw) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def mark(self) -> int:
        return len(self.compiles)

    def since(self, mark: int) -> str:
        new = self.compiles[mark:]
        return (f"{len(new)} compiles, {sum(s for _, s in new):.1f}s")

    def report(self) -> None:
        by_name: dict[str, list[float]] = {}
        for name, secs in self.compiles:
            by_name.setdefault(name, []).append(secs)
        say(f"compiles: {len(self.compiles)} programs, "
            f"{sum(s for _, s in self.compiles):.1f}s total; persistent "
            f"cache hits={self.hits} misses(written)={self.misses}")
        for name, secs in sorted(by_name.items(),
                                 key=lambda kv: -sum(kv[1])):
            say(f"  {name}: {len(secs)} programs, {sum(secs):.2f}s "
                f"(max {max(secs):.2f}s)")


def device_bytes(*dev_dicts) -> dict:
    """Bytes per device id over the arrays' addressable shards — makes
    "everything on device 0" visible on a mesh."""
    out: dict[int, int] = {}
    for d in dev_dicts:
        for arr in (d or {}).values():
            for sh in getattr(arr, "addressable_shards", ()):
                out[sh.device.id] = out.get(sh.device.id, 0) \
                    + sh.data.nbytes
    return out


# ------------------------------------------------------------- native leg

def native_leg() -> None:
    """Remove libvtl.so, let net/vtl.py build it from source here, and
    refuse the pure-Python provider."""
    if "vproxy_tpu.net.vtl" in sys.modules:
        raise RuntimeError("net/vtl.py already imported: cannot prove a "
                           "from-source native build")
    so = os.path.join(HERE, "vproxy_tpu", "native", "libvtl.so")
    if os.path.exists(so):
        os.unlink(so)
    # explicit provider: a failed build/load raises (net/vtl.py) instead
    # of falling back to the Python pump
    os.environ["VPROXY_TPU_FD_PROVIDER"] = "native"
    t0 = time.time()
    from vproxy_tpu.net import vtl
    dt = time.time() - t0
    gate(vtl.PROVIDER == "native" and vtl.LIB is not None
         and os.path.exists(so),
         f"native provider not serving (PROVIDER={vtl.PROVIDER!r})")
    say(f"native: libvtl.so built from native/vtl.cpp in {dt:.1f}s, "
        f"provider={vtl.PROVIDER}")


# ------------------------------------------------------------- served leg

class IdBackends:
    """n loopback HTTP backends on one selector thread; backend i
    answers every request with the body b"<i>"."""

    def __init__(self, n: int):
        self.sel = selectors.DefaultSelector()
        self.ports: list[int] = []
        self._stop = False
        self._dead: set = set()     # listeners to close, by backend
        for i in range(n):
            s = socket.socket()
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", 0))
            s.listen(128)
            s.setblocking(False)
            self.sel.register(s, selectors.EVENT_READ, ("listen", i, None))
            self.ports.append(s.getsockname()[1])
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="id-backends")
        self.thread.start()

    def stop(self, i: int) -> None:
        """Backend i goes away: its listener is closed (on the selector
        thread), so the next health check finds the port refused."""
        self._dead.add(i)

    def _run(self) -> None:
        while not self._stop:
            for key in [k for k in self.sel.get_map().values()
                        if k.data[0] == "listen" and k.data[1] in self._dead]:
                self.sel.unregister(key.fileobj)
                key.fileobj.close()
            for key, _ in self.sel.select(0.2):
                kind, i, buf = key.data
                sock = key.fileobj
                try:
                    if kind == "listen":
                        c, _addr = sock.accept()
                        c.setblocking(False)
                        self.sel.register(c, selectors.EVENT_READ,
                                          ("conn", i, bytearray()))
                        continue
                    data = sock.recv(65536)
                    if data:
                        buf += data
                        if b"\r\n\r\n" not in buf:
                            continue
                        body = b"%d" % i
                        sock.setblocking(True)
                        sock.sendall(b"HTTP/1.1 200 OK\r\ncontent-length: "
                                     b"%d\r\nconnection: close\r\n\r\n%s"
                                     % (len(body), body))
                    self.sel.unregister(sock)
                    sock.close()
                except OSError:
                    try:
                        self.sel.unregister(sock)
                    except (KeyError, ValueError):
                        pass
                    sock.close()

    def close(self) -> None:
        self._stop = True
        self.thread.join(5)
        for key in list(self.sel.get_map().values()):
            key.fileobj.close()
        self.sel.close()


def http_get(port: int, host: str) -> tuple[str, str]:
    """One HTTP/1.1 request through the LB -> (status line, body)."""
    c = socket.create_connection(("127.0.0.1", port), timeout=30)
    try:
        c.settimeout(30)
        c.sendall(b"GET / HTTP/1.1\r\nhost: %s\r\nconnection: close\r\n\r\n"
                  % host.encode())
        data = b""
        while True:
            d = c.recv(65536)
            if not d:
                break
            data += d
    finally:
        c.close()
    head, _, body = data.partition(b"\r\n\r\n")
    return head.split(b"\r\n")[0].decode(), body.decode()


def _burst(port: int, hosts: list[str]) -> list:
    """len(hosts) concurrent requests; -> [(status, body) | exception]."""
    out: list = [None] * len(hosts)
    go = threading.Barrier(len(hosts))

    def one(i: int) -> None:
        try:
            go.wait(30)
            out[i] = http_get(port, hosts[i])
        except Exception as e:  # noqa: BLE001 — reported per request
            out[i] = e

    ths = [threading.Thread(target=one, args=(i,), daemon=True)
           for i in range(len(hosts))]
    for t in ths:
        t.start()
    for t in ths:
        t.join(60)
    return out


def served_leg(n_groups: int = 256, bursts: int = 3,
               burst: int = 64) -> dict:
    from vproxy_tpu.control.app import Application
    from vproxy_tpu.control.command import Command
    from vproxy_tpu.rules import engine as E
    from vproxy_tpu.rules import oracle
    from vproxy_tpu.rules.ir import Hint
    from vproxy_tpu.rules.service import ClassifyService

    def host_of(i: int) -> str:
        return f"svc{i}.ns{i % 7}.smoke.example.com"

    t0 = time.time()
    backends = IdBackends(n_groups)
    app = Application.create()
    ev: dict = {}
    try:
        Command.execute(app, "add upstream u0")
        for i in range(n_groups):
            Command.execute(
                app, f"add server-group g{i} timeout 500 period 2000 "
                     f"up 1 down 3")
            Command.execute(
                app, f"add server s{i} to server-group g{i} address "
                     f"127.0.0.1:{backends.ports[i]} weight 10")
            Command.execute(
                app, f"add server-group g{i} to upstream u0 weight 10 "
                     f'annotations {{"vproxy/hint-host":"{host_of(i)}"}}')
        ups = app.upstreams["u0"]
        m = ups._matcher
        deadline = time.time() + 60
        while time.time() < deadline and not all(
                s.healthy for g in app.server_groups.values()
                for s in g.servers):
            time.sleep(0.05)
        gate(all(s.healthy for g in app.server_groups.values()
                 for s in g.servers), "served: backends never went healthy")
        Command.execute(app, "add tcp-lb lb0 address 127.0.0.1:0 "
                             "upstream u0 protocol http-splice")
        port = app.tcp_lbs["lb0"].bind_port
        ev["setup_s"] = round(time.time() - t0, 2)
        say(f"served: {n_groups} groups via the command grammar on "
            f"backend={m.backend}, generation={m.generation}, "
            f"setup {ev['setup_s']}s")

        # distinct Host headers, exact and suffix forms, every burst
        rnd = random.Random(SEED)
        picks = rnd.sample(range(n_groups), min(n_groups, burst))
        waves = []
        for b in range(bursts):
            waves.append([(f"w{b}x{k}." if (k + b) % 2 else "")
                          + host_of(picks[k % len(picks)])
                          for k in range(burst)])
        rules, handles = m.rules, m.snapshot()[3]

        def expected(host: str) -> str:
            idx = oracle.search(rules, Hint.of_host_uri(host, "/"))
            return handles[idx].group.alias[1:] if idx >= 0 else "none"

        def run_policy(policy: str) -> dict:
            os.environ["VPROXY_TPU_CLASSIFY"] = policy
            ClassifyService.reset()  # the next get() reads the policy
            svc = ClassifyService.get()
            bad = 0
            t0 = time.time()
            for hosts in waves:
                for host, res in zip(hosts, _burst(port, hosts)):
                    want = expected(host)
                    if isinstance(res, Exception) or res[1] != want:
                        bad += 1
                        if bad <= 5:
                            say(f"  {policy}: {host} -> {res!r}, "
                                f"want backend {want}")
            st = svc.stats.snapshot()
            st["wrong"] = bad
            st["last_failover"] = svc.stats.last_failover
            st["wall_s"] = round(time.time() - t0, 2)
            return st

        n_req = bursts * burst
        arrays = LaunchArrays()
        dev = run_policy("device")
        arrays.check("served[device]", m.backend)
        say(f"served[device]: {n_req} requests in {bursts} bursts of "
            f"{burst}: {dev}")
        gate(dev["wrong"] == 0,
             f"served: {dev['wrong']} responses from the wrong group")
        gate(dev["device_queries"] == n_req and dev["oracle_queries"] == 0
             and dev["failovers"] == 0,
             f"served: the device did not answer every query "
             f"(device_queries={dev['device_queries']}/{n_req}, "
             f"oracle_queries={dev['oracle_queries']}, failovers="
             f"{dev['failovers']}, last={dev['last_failover']!r})")
        detail = Command.execute(app, "list-detail upstream")
        say(f"served: list-detail upstream -> {detail}")
        gate(f"backend {E.default_backend()} " in str(detail),
             "served: upstream not on default_backend()")
        auto = run_policy("auto")
        say(f"served[auto]: inline={auto['inline_fast']} device="
            f"{auto['device_queries']} host-batch="
            f"{auto['oracle_queries'] - auto['inline_fast']} wrong="
            f"{auto['wrong']} failovers={auto['failovers']} "
            f"(split is information, not a gate)")
        gate(auto["wrong"] == 0 and auto["failovers"] == 0,
             f"served[auto]: wrong={auto['wrong']} failovers="
             f"{auto['failovers']} last={auto['last_failover']!r}")
        ev.update(device=dev, auto=auto, backend=m.backend,
                  generation=m.generation)
    finally:
        os.environ.pop("VPROXY_TPU_CLASSIFY", None)
        app.close()
        ClassifyService.reset()
        backends.close()
    return ev


# ------------------------------------------------------------ grouped leg

def grouped_leg(n_groups: int = 8, per_group: int = 3,
                burst: int = 64) -> dict:
    from vproxy_tpu.control.app import Application
    from vproxy_tpu.control.command import Command
    from vproxy_tpu.rules import engine as E
    from vproxy_tpu.rules import maglev as MG
    from vproxy_tpu.rules import oracle
    from vproxy_tpu.rules.ir import Hint
    from vproxy_tpu.rules.service import ClassifyService
    from vproxy_tpu.utils.ip import parse_ip

    def host_of(i: int) -> str:
        return f"grp{i}.ns{i % 3}.smoke.example.com"

    backends = IdBackends(n_groups * per_group)
    app = Application.create()
    ev: dict = {}
    os.environ["VPROXY_TPU_CLASSIFY"] = "device"
    ClassifyService.reset()
    try:
        Command.execute(app, "add upstream u1")
        for i in range(n_groups):
            Command.execute(
                app, f"add server-group s{i} timeout 300 period 300 "
                     f"up 1 down 2 method source")
            for b in range(per_group):
                Command.execute(
                    app, f"add server b{b} to server-group s{i} address "
                         f"127.0.0.1:{backends.ports[i * per_group + b]} "
                         f"weight 10")
            Command.execute(
                app, f"add server-group s{i} to upstream u1 weight 10 "
                     f'annotations {{"vproxy/hint-host":"{host_of(i)}"}}')
        ups = app.upstreams["u1"]
        groups = [app.server_groups[f"s{i}"] for i in range(n_groups)]
        deadline = time.time() + 60
        while time.time() < deadline and not all(
                s.healthy for g in groups for s in g.servers):
            time.sleep(0.05)
        gate(all(s.healthy for g in groups for s in g.servers),
             "grouped: backends never went healthy")
        E.flush_installs(30)
        gate(ups._picks.size() == n_groups,
             f"grouped: the set holds {ups._picks.size()} tables, want "
             f"{n_groups}")
        Command.execute(app, "add tcp-lb lb1 address 127.0.0.1:0 "
                             "upstream u1 protocol http-splice")
        port = app.tcp_lbs["lb1"].bind_port
        client = parse_ip("127.0.0.1")  # every request's source address
        id_of = {p: i for i, p in enumerate(backends.ports)}
        m = ups._matcher
        hosts = [("w%d." % k if k % 2 else "") + host_of(k % n_groups)
                 for k in range(burst)]

        def one_burst(tag: str) -> dict:
            svc = ClassifyService.get()
            before = (svc.stats.snapshot(), dict(svc.stats.group_picks),
                      E.dispatch_launches_total())
            rules, handles = m.rules, m.snapshot()[3]
            want = []
            for h in hosts:     # the host planes: the oracle, the group
                idx = oracle.search(rules, Hint.of_host_uri(h, "/"))
                want.append(str(id_of[
                    handles[idx].group.next(client).svr.port]))
            got = _burst(port, hosts)
            wrong = 0
            for h, res, w in zip(hosts, got, want):
                if isinstance(res, Exception) or res[1] != w:
                    wrong += 1
                    if wrong <= 5:
                        say(f"  grouped[{tag}]: {h} -> {res!r}, want "
                            f"backend {w}")
            st = svc.stats.snapshot()
            d = {k: st[k] - before[0][k] for k in (
                "queries", "dispatches", "device_queries",
                "oracle_queries", "failovers")}
            d["device_picks"] = svc.stats.group_picks["device"] \
                - before[1]["device"]
            d["launches"] = E.dispatch_launches_total() - before[2]
            d["wrong"] = wrong
            d["bodies"] = sorted({r[1] for r in got
                                  if not isinstance(r, Exception)})
            say(f"grouped[{tag}]: {burst} requests: {d}")
            gate(wrong == 0, f"grouped[{tag}]: {wrong} responses from a "
                             f"backend the host planes do not name")
            gate(d["device_queries"] == burst and d["oracle_queries"] == 0
                 and d["failovers"] == 0 and d["device_picks"] == burst,
                 f"grouped[{tag}]: the device did not make every pick "
                 f"({d}, last={svc.stats.last_failover!r})")
            gate(d["launches"] == d["dispatches"],
                 f"grouped[{tag}]: {d['launches']} launches for "
                 f"{d['dispatches']} batches")
            return d

        arrays = LaunchArrays()
        ev["before"] = one_burst("before the edge")
        # the backend this client reaches in group 3 goes away: its
        # clients must move, nobody else's
        victim = groups[3].next(client).svr
        builds = MG.set_table_builds_total()
        t0 = time.time()
        backends.stop(id_of[victim.port])
        while time.time() - t0 < 30 and victim.healthy:
            time.sleep(0.02)
        gate(not victim.healthy, "grouped: the health check never took "
                                 "the stopped backend down")
        E.flush_installs(30)
        ev["edge_s"] = round(time.time() - t0, 2)
        ev["row_builds"] = MG.set_table_builds_total() - builds
        gate(ev["row_builds"] == 1,
             f"grouped: one group's edge rebuilt {ev['row_builds']} rows")
        ev["after"] = one_burst("after the edge")
        arrays.check("grouped", m.backend)
        gate(str(id_of[victim.port]) not in ev["after"]["bodies"],
             "grouped: a request reached the dead backend")
        say(f"grouped: edge {ev['edge_s']}s from stop to published row, "
            f"{ev['row_builds']} row rebuilt, remap "
            f"{ups._picks.last_remap:.3f}")
    finally:
        os.environ.pop("VPROXY_TPU_CLASSIFY", None)
        app.close()
        ClassifyService.reset()
        backends.close()
    return ev


# -------------------------------------------------------------- width leg

def _drive(svc, kind: str, submit, n: int, threads: int) -> tuple:
    """Submit n queries of one kind from *threads* threads through the
    service; -> (results list, wall seconds). submit(i, cb) enqueues
    query i."""
    out: list = [None] * n
    left = [n]
    lock = threading.Lock()
    done = threading.Event()

    def deliver(i: int, val) -> None:
        out[i] = val
        with lock:
            left[0] -= 1
            if left[0] == 0:
                done.set()

    def worker(t: int) -> None:
        for i in range(t, n, threads):
            submit(i, deliver)

    t0 = time.time()
    ths = [threading.Thread(target=worker, args=(t,), daemon=True)
           for t in range(threads)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    gate(done.wait(900), f"width: {kind} queries never all delivered "
                         f"({left[0]}/{n} missing)")
    return out, time.time() - t0


def _traced_install(install) -> tuple:
    """Run install() (set_rules/set_networks calls, i.e. the
    TableInstaller) with tracing on, so the installer's own spans can be
    read back. -> (wall seconds, {"<matcher>.<compile|upload|swap>": s});
    `compile` is the host-side table build."""
    from vproxy_tpu.utils import trace
    trace.configure(1)
    try:
        t0 = time.time()
        install()
        wall = time.time() - t0
    finally:
        trace.configure(0)
    phases: dict[str, float] = {}
    for tid in trace.trace_ids():
        for s in trace.get_trace(tid):
            if s["plane"] == "install" and s["span"] != "install":
                key = f"{s.get('matcher', '?')}.{s['span']}"
                phases[key] = round(phases.get(key, 0.0)
                                    + s["dur_ns"] / 1e9, 3)
    trace.reset()
    return round(wall, 2), phases


def _hint_dispatch_timing(hm, hsnap, hints, sizes) -> dict:
    """First and steady (median of 7) hint dispatch per batch size: host
    encode + h2d + kernel, timed around block_until_ready. Evidence."""
    import jax
    from vproxy_tpu.rules.engine import pad_batch
    from vproxy_tpu.rules.service import PAD_LO
    out = {}
    for b in sizes:
        cap = pad_batch(b, lo=PAD_LO)
        reps = []
        for _ in range(8):
            t0 = time.perf_counter()
            jax.block_until_ready(hm.dispatch_snap(
                hsnap, hints[:b], pad_to=cap, sync=False))
            reps.append(time.perf_counter() - t0)
        out[b] = {"first_ms": round(reps[0] * 1e3, 2),
                  "steady_median_ms": round(
                      sorted(reps[1:])[len(reps) // 2 - 1] * 1e3, 3)}
    return out


def _install_under_load(svc, hm, hints, want_h, rules2, changed: int,
                        probe) -> dict:
    """hm.set_rules(rules2) while a closed loop (window 64) keeps hint
    queries in flight over rules the change cannot touch; then *probe*
    must answer *changed*. Gates: generation +1, new rule serves, every
    query under the install right, no failover, and the publish froze
    the new generation out of the collector's reach (utils/heap)."""
    from vproxy_tpu.rules import engine as E
    from vproxy_tpu.utils import heap
    gen0, total0 = hm.generation, E.generation_total()
    frozen0 = heap.freezes_total("publish")
    stop = threading.Event()
    bg = {"n": 0, "wrong": 0}
    idxs = [i for i in range(len(hints)) if want_h[i] != changed]

    def background() -> None:
        k = 0
        while not stop.is_set():
            chunk = [idxs[(k + j) % len(idxs)] for j in range(64)]
            k += 64
            left = [len(chunk)]
            fin = threading.Event()

            def cb(idx, _pl, i):
                bg["n"] += 1
                bg["wrong"] += idx != want_h[i]
                left[0] -= 1
                if left[0] == 0:
                    fin.set()

            for i in chunk:
                svc.submit_hint(hm, hints[i],
                                lambda idx, pl, i=i: cb(idx, pl, i))
            if not fin.wait(120):
                bg["wrong"] += 1
                return

    th = threading.Thread(target=background, daemon=True)
    th.start()
    time.sleep(0.2)
    t0 = time.time()
    hm.set_rules(rules2)
    install_s = time.time() - t0
    got: list = []
    fin = threading.Event()
    svc.submit_hint(hm, probe, lambda idx, _pl: (got.append(idx), fin.set()))
    fin.wait(120)
    stop.set()
    th.join(130)
    gate(hm.generation == gen0 + 1 and E.generation_total() == total0 + 1,
         f"width: generation counter did not move by one "
         f"({gen0}->{hm.generation})")
    gate(got == [changed],
         f"width: new rule answered {got}, want [{changed}]")
    gate(bg["n"] > 0 and bg["wrong"] == 0 and svc.stats.failovers == 0,
         f"width: queries under the install: n={bg['n']} wrong="
         f"{bg['wrong']} failovers={svc.stats.failovers} "
         f"last={svc.stats.last_failover!r}")
    gate(heap.freezes_total("publish") == frozen0 + 1
         and heap.frozen_objects() >= len(rules2),
         f"width: the publish under load froze "
         f"{heap.freezes_total('publish') - frozen0} times, "
         f"{heap.frozen_objects()} objects frozen")
    say(f"width: generation {gen0}->{hm.generation} installed under load "
        f"in {install_s:.1f}s (paced standby build), {bg['n']} queries "
        f"answered meanwhile, wrong={bg['wrong']}; the changed rule "
        f"answers {got}; {heap.frozen_objects()} objects frozen, "
        f"{heap.reexaminations_total()} re-examinations")
    return {"seconds": round(install_s, 2), "queries_during": bg["n"]}


def width_leg(jlog: JaxLog, n_rules: int = 100_000,
              n_routes: int = 50_000, n_acls: int = 5_000,
              n_queries: int = 16_384, threads: int = 8,
              sample: int = 64) -> dict:
    import numpy as np

    from benchmark import gen
    from vproxy_tpu.rules import engine as E
    from vproxy_tpu.rules import oracle
    from vproxy_tpu.rules.engine import CidrMatcher, HintMatcher
    from vproxy_tpu.rules.ir import AclRule, Hint, HintRule, Proto
    from vproxy_tpu.rules.maglev import FusedPair, MaglevMatcher
    from vproxy_tpu.rules.service import PAD_LO, ClassifyService
    from vproxy_tpu.utils.ip import Network, mask_bytes

    ev: dict = {"backend": E.default_backend()}
    mark = jlog.mark()
    t0 = time.time()

    def net(n):
        return Network(int(n[0]).to_bytes(4, "big"), mask_bytes(n[1]))

    tag = gen.seed_tag(SEED)
    plain_rules = gen.north_star_hint_rules(n_rules, tag)
    plain_acls = gen.north_star_acls(n_acls)
    hint_rules = [HintRule(host=h, port=p, uri=u) for h, p, u in plain_rules]
    routes = [net(n) for n in gen.north_star_routes(n_routes)]
    acls = [AclRule(f"r{i}", net(n), Proto.TCP, n[2], n[3], i % 2 == 0)
            for i, n in enumerate(plain_acls)]
    # one query in 16 misses; addresses and ports aim at ACL entries
    hints = [Hint(host=h, port=p, uri=u) for h, p, u in
             gen.hint_pool(n_queries, plain_rules, tag, SEED, 16)]
    addrs, ports = zip(*gen.cidr_pool(n_queries, plain_acls, SEED, 16, True))
    say(f"width: generated {n_rules}+{n_routes}+{n_acls} rules, "
        f"{n_queries} queries in {time.time() - t0:.1f}s; "
        f"backend={ev['backend']}")

    # ---- install through the TableInstaller
    hm, rm, am = HintMatcher(), CidrMatcher(), CidrMatcher()
    ev["install_s"], ev["install_phases_s"] = _traced_install(lambda: (
        hm.set_rules(hint_rules), rm.set_networks(routes),
        am.set_networks([a.network for a in acls], acl=acls)))
    gate(hm.backend == rm.backend == am.backend == ev["backend"]
         and hm.size() == n_rules and rm.size() == n_routes
         and am.size() == n_acls, "width: tables not installed at size")
    hsnap, rsnap, asnap = hm.snapshot(), rm.snapshot(), am.snapshot()
    mesh = getattr(hm, "_mesh", None)
    ev["mesh"] = dict(mesh.shape) if mesh is not None else None
    ev["table_bytes_per_device"] = device_bytes(
        hsnap[1], hsnap[5], rsnap[0], asnap[0])
    say(f"width: installed in {ev['install_s']}s (host build / upload / "
        f"swap seconds by matcher: {ev['install_phases_s']}); "
        f"mesh={ev['mesh']}; table bytes per device="
        f"{ev['table_bytes_per_device']}")

    mm = MaglevMatcher([(f"b{i}:80", 1) for i in range(16)])
    pair = FusedPair(hm, mm)
    psnap = pair.snapshot()
    n_pick = max(4, n_queries // 4)

    # ---- expected answers: the host index (rules/index.py)
    t0 = time.time()
    want_h = [hm.index_snap(hsnap, h) for h in hints]
    want_r = [rm.index_snap(rsnap, a, None) for a in addrs]
    want_a = [am.index_snap(asnap, a, p) for a, p in zip(addrs, ports)]
    want_p = [pair.index_snap(psnap, (hints[i], addrs[i], ports[i]))
              for i in range(n_pick)]
    say(f"width: host-index expectations in {time.time() - t0:.1f}s "
        f"(hint hits {sum(1 for v in want_h if v >= 0)}, route hits "
        f"{sum(1 for v in want_r if v >= 0)}, acl hits "
        f"{sum(1 for v in want_a if v >= 0)} of {n_queries})")

    # ---- the served path: several threads -> ClassifyService(device)
    svc = ClassifyService(mode="device")
    try:
        runs = (
            ("hint", n_queries, want_h, lambda i, cb: svc.submit_hint(
                hm, hints[i], lambda idx, _pl: cb(i, idx))),
            ("route", n_queries, want_r, lambda i, cb: svc.submit_cidr(
                rm, addrs[i], None, lambda idx, _pl: cb(i, idx))),
            ("acl", n_queries, want_a, lambda i, cb: svc.submit_cidr(
                am, addrs[i], ports[i], lambda idx, _pl: cb(i, idx))),
            ("classify_pick", n_pick, want_p,
             lambda i, cb: svc.submit_classify_pick(
                 pair, hints[i], addrs[i], ports[i],
                 lambda v, p, _pl: cb(i, (v, p)))),
        )
        total = 0
        arrays = LaunchArrays()
        for kind, n, want, submit in runs:
            mark_k = jlog.mark()
            d0 = svc.stats.dispatches
            got, wall = _drive(svc, kind, submit, n, threads)
            wrong = sum(1 for g, w in zip(got, want) if g != w)
            total += n
            say(f"width[{kind}]: {n} queries, {threads} threads, "
                f"{svc.stats.dispatches - d0} device batches, {wall:.1f}s "
                f"wall incl. {jlog.since(mark_k)}; wrong={wrong}")
            gate(wrong == 0, f"width: {wrong}/{n} {kind} verdicts differ "
                             f"from the host index")
        arrays.check("width", hm.backend)
        st = svc.stats.snapshot()
        ev["service"] = st
        gate(st["device_queries"] == total and st["oracle_queries"] == 0
             and st["failovers"] == 0,
             f"width: the device did not answer every query "
             f"(device_queries={st['device_queries']}/{total}, "
             f"oracle_queries={st['oracle_queries']}, failovers="
             f"{st['failovers']}, last={svc.stats.last_failover!r})")
        say(f"width: {st['readback_prefetch']} of {st['dispatches']} "
            f"device batches had their readback started at launch, "
            f"{st['readback_kernel_waits']} read before their kernel "
            f"was done")
        say(f"width: service counters {st}")

        # ---- a sample against the linear oracle (rules/oracle.py)
        picks = random.Random(SEED).sample(range(n_queries),
                                           min(sample, n_queries))
        bad = sum(1 for i in picks
                  if oracle.search(hint_rules, hints[i]) != want_h[i]
                  or rm.oracle_snap(rsnap, addrs[i], None) != want_r[i]
                  or am.oracle_snap(asnap, addrs[i], ports[i]) != want_a[i])
        gate(bad == 0, f"width: {bad}/{len(picks)} sampled verdicts differ "
                       f"from the linear oracle")
        say(f"width: {len(picks)} sampled hint+route+acl verdicts equal "
            f"the linear oracle")

        # ---- fused classify+pick: one launch per batch
        fs = ev["fused"] = hm.fused_stat()
        b = min(256, n_pick)
        payloads = [(hints[i], addrs[i], ports[i]) for i in range(b)]
        l0, f0 = E.dispatch_launches_total(), E.fused_dispatches_total()
        out = np.asarray(pair.dispatch_snap(
            psnap, payloads, pad_to=E.pad_batch(b, lo=PAD_LO)))
        dl = E.dispatch_launches_total() - l0
        df = E.fused_dispatches_total() - f0
        gate([tuple(int(x) for x in row) for row in out[:b]]
             == want_p[:b], "width: fused batch verdicts/picks wrong")
        if fs["available"]:
            gate(dl == 1 and df == 1 and fs["packed_bytes"] > 0,
                 f"width: a fused batch cost {dl} launches "
                 f"({df} fused) over {fs['packed_bytes']} packed bytes, "
                 f"want exactly 1 over a packed table")
            say(f"width: fused packed bytes={fs['packed_bytes']}, "
                f"a {b}-query classify+pick batch = {dl} launch")
        else:
            say(f"width: no fused tables on backend {hm.backend} "
                f"(fused serves the single-device 'jax' backend); a "
                f"{b}-query classify+pick batch = {dl} launches")

        ev["hint_dispatch_ms"] = _hint_dispatch_timing(
            hm, hsnap, hints, (1, 256, n_queries))
        say(f"width: hint dispatch (host encode + h2d + kernel, to "
            f"block_until_ready) by batch: {ev['hint_dispatch_ms']}")

        new_host = "changed.smoke.example.com"
        rules2 = list(hint_rules)
        rules2[17] = HintRule(host=new_host)
        ev["generation_install"] = _install_under_load(
            svc, hm, hints, want_h, rules2, 17, Hint.of_host(new_host))
    finally:
        svc.close()
    say(f"width: {jlog.since(mark)} in this leg")
    return ev


# ------------------------------------------------------------------- main

def main() -> int:
    t_all = time.time()
    from vproxy_tpu.utils.jaxenv import compile_cache_dir
    cache = compile_cache_dir()
    import jax
    devs = jax.devices()
    d0 = devs[0]
    cached = sum(len(fs) for _, _, fs in os.walk(cache))
    say(f"jax {jax.__version__} platform={d0.platform} "
        f"device_kind={d0.device_kind!r} count={len(devs)} "
        f"compile-cache={cache} ({cached} files)")
    if d0.platform != "tpu":
        print(f"chip_smoke: platform is {d0.platform!r}, not 'tpu' — no "
              f"accelerator, nothing run", file=sys.stderr)
        return 1
    try:  # 64 concurrent requests x 4 sockets + 256 listeners
        import resource
        _soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    except (ImportError, ValueError, OSError):
        pass
    jlog = JaxLog()
    # width before served: the TableInstaller paces a standby build
    # ~7x whenever a query was served in the last 5 s, and the bulk
    # install should not pay that (the install under load, inside the
    # width leg, does — by design)
    for name, leg in (("native", native_leg),
                      ("width", lambda: width_leg(jlog)),
                      ("served", served_leg),
                      ("grouped", grouped_leg)):
        t0 = time.time()
        try:
            leg()
        except Exception as e:  # noqa: BLE001 — a dead leg fails the run
            traceback.print_exc()
            gate(False, f"{name} leg raised {e!r}")
        say(f"--- {name} leg: {time.time() - t0:.1f}s")
    jlog.report()
    stats = d0.memory_stats() or {}
    say(f"device memory: peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use')} bytes_limit="
        f"{stats.get('bytes_limit')}")
    say(f"total wall {time.time() - t_all:.1f}s; failures: "
        f"{FAILURES or 'none'}")
    if FAILURES:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
