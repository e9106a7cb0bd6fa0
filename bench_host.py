"""Host-path req/s benchmark: the native splice pump under HTTP load.

BASELINE.md's haproxy-parity rows (reference wrk runs,
/root/reference/benchmark/report/2019/06/05/bench.md:17-19: tcp-lb
173k req/s TCP splice, 112k with L7 parsing) need a host-side answer:
this harness drives THIS framework's TcpLB over loopback with a native
epoll load tool (vproxy_tpu/native/hostbench.cpp — Python clients would
measure the GIL, not the pump).

Topology per mode:
  hostbench client -> TcpLB (this framework) -> hostbench servers
plus a direct client->server run for the machine's ceiling.

Modes:
  * direct      — no LB; the harness/loopback ceiling.
  * tcp         — TcpLB protocol=tcp: backend picked per connection,
                  then the C++ splice pump owns the bytes (vtl.cpp:342).
  * http-splice — TcpLB parses the first request's Host header, picks
                  the group via the classify queue, then splices.

Prints ONE JSON line: {"host_direct_rps", "host_tcp_rps",
"host_http_rps", ...}. bench.py merges these fields into BENCH output.

Round-6 additions (docs/perf.md):

* host_canary_MBps — a FIXED canary: 1GB pumped through a loopback
  native splice before any measured row, so the historical 151-258k
  http-splice spread can be attributed to machine load vs code (the
  host-path analog of bench.py's canary_step_ms).
* short-connection A/B — the accept-path row runs twice: warm backend
  pool OFF (host_tcp_short_nopool_rps — rides the C connect+pump fast
  lane, vtl_pump_connect) and ON (host_tcp_short_pool_rps).
  host_tcp_short_rps = the better of the two (target: haproxy's 10,052
  from BASELINE.md), host_tcp_short_best says which won here, and
  host_short_vs_ceiling normalizes by host_direct_short_rps (the
  kernel's own no-LB connect/accept cycle). TCP_DEFER_ACCEPT is
  enabled on the LB listeners for all rows (client-speaks-first).

Round-9 additions (docs/perf.md, ISSUE 8):

* C accept-lane A/B — the short row runs lanes-off (the r6 C
  connect+pump fast lane) and lanes-on (vtl.cpp accept lanes: the WHOLE
  short-connection lifetime in C). The io_uring probe result rides the
  artifact (`host_uring_probe`, `host_lane_engine`) so it is honest
  about which completion engine ran — this container's 4.4 kernel
  denies io_uring and the lanes run the epoll engine.
* GIL-contention A/B — the same rows with one python thread doing
  CPU-bound work (standing in for on-host classify/compile load, the
  production state of a vproxy-tpu node): the python accept path
  collapses (every accept waits on the GIL), the lanes hold. This is
  the displacement the lanes buy; `host_lanes_gil_speedup` is the
  headline ratio.
* kernel-serialization evidence — two direct short benches run in
  PARALLEL against separate servers sum to the same rate as one
  (`host_direct_short_2x_sum` ~ `host_direct_short_rps`): this
  container class serializes ALL connection setup in the sandbox
  kernel, which pins the uncontended LB short row near 0.5x of direct
  (2 connects + 2 accepts per request vs 1 + 1) regardless of
  accept-plane parallelism.
* `--lanes` runs ONLY the lane stage (BENCH_r09_builder_lanes.json).

Env knobs: HOSTBENCH_CONNS (64), HOSTBENCH_SECS (8), HOSTBENCH_PIPELINE
(4), HOSTBENCH_BACKENDS (2), HOSTBENCH_WORKERS (4), HOSTBENCH_POOL
(32), HOSTBENCH_CANARY_MB (1024), HOSTBENCH_DEFER_ACCEPT (1),
HOSTBENCH_LANES (4).
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
NATIVE = os.path.join(HERE, "vproxy_tpu", "native")
BIN = os.path.join(NATIVE, "hostbench")


def _env_int(k, d):
    return int(os.environ.get(k, str(d)))


def build_tool():
    src = os.path.join(NATIVE, "hostbench.cpp")
    if (os.path.exists(BIN)
            and os.path.getmtime(BIN) >= os.path.getmtime(src)):
        return
    subprocess.check_call(["g++", "-O2", "-o", BIN, src, "-ldl"])


def start_server():
    p = subprocess.Popen([BIN, "server", "0"], stdout=subprocess.PIPE,
                         text=True)
    line = p.stdout.readline()
    port = json.loads(line)["listening"]
    return p, port


def run_client(port, conns, secs, pipeline, tls_sni=None, short=False):
    if short:
        cmd = [BIN, "shortclient", "127.0.0.1", str(port), str(conns),
               str(secs)]
    elif tls_sni is None:
        cmd = [BIN, "client", "127.0.0.1", str(port), str(conns),
               str(secs), str(pipeline)]
    else:
        cmd = [BIN, "tlsclient", "127.0.0.1", str(port), tls_sni,
               str(conns), str(secs), str(pipeline)]
    out = subprocess.run(cmd, stdout=subprocess.PIPE, text=True,
                         timeout=secs + 60)
    return json.loads(out.stdout.strip().splitlines()[-1])


def splice_canary(elg, mb: int):
    """Pump a known `mb` MB through a loopback native splice and report
    MB/s — a fixed workload whose rate classes the machine this run
    (VERDICT r5 item 9). Returns None when the native pump is absent
    (py provider) or the byte count doesn't check out."""
    import socket as S

    from vproxy_tpu.net import vtl as _vtl
    if _vtl.PROVIDER != "native":
        return None
    lp = elg.next()
    a, b = S.socketpair()          # writer -> pump front
    sink_l = S.socket()
    sink_l.bind(("127.0.0.1", 0))
    sink_l.listen(1)
    c = S.create_connection(sink_l.getsockname())  # pump back -> sink
    srv, _ = sink_l.accept()
    total = mb << 20
    got = [0]

    def sink():
        while got[0] < total:
            d = srv.recv(1 << 20)
            if not d:
                break
            got[0] += len(d)

    st = threading.Thread(target=sink, daemon=True)
    st.start()
    b.setblocking(False)  # the pump's kick-read must never block the loop
    c.setblocking(False)
    bfd, cfd = b.detach(), c.detach()  # the pump owns these from here
    done = threading.Event()
    chunk = b"\x00" * (1 << 20)
    t0 = time.time()
    lp.call_sync(lambda: lp.pump(bfd, cfd, 1 << 20,
                                 lambda *_: done.set()))
    try:
        for _ in range(mb):
            a.sendall(chunk)
    finally:
        a.close()  # EOF propagates through the pump to the sink
    st.join(120)
    secs = time.time() - t0
    done.wait(5)
    srv.close()
    sink_l.close()
    return round(mb / secs, 1) if got[0] >= total else None


def run_storm():
    """`--storm`: drive the adversarial scenario suite (tools/storm.py)
    and snapshot its SLO gates as the BENCH artifact — the orchestrator
    commits the result (BENCH_r10_builder_storm.json) like every other
    bench round. STORM_SEED / STORM_SCALE parameterize; the seed rides
    the artifact so a failed gate replays exactly."""
    sys.path.insert(0, os.path.join(HERE, "tools"))
    import storm
    seed = _env_int("STORM_SEED", 0)
    scale = float(os.environ.get("STORM_SCALE", "1.0"))
    report = storm.run_all(
        seed=seed, scale=scale,
        log=lambda m: print(f"[storm] {m}", file=sys.stderr))
    out_path = os.environ.get("HOSTBENCH_RESULT_FILE")
    if out_path:
        with open(out_path + ".tmp", "w") as f:
            json.dump(report, f, indent=2, default=str)
        os.replace(out_path + ".tmp", out_path)
    print(json.dumps(report))
    return 0 if report["pass"] else 1


def run_maglev():
    """`--maglev`: the consistent-hash rows (ISSUE 10, docs/perf.md).

    1. backend-pick A/B — the accept path's per-connection pick timed
       for method=wrr (lock + sequence walk) vs method=source (maglev:
       one FNV + one slot load): `host_pick_{wrr,maglev}_{p50,p99}_us`.
       Gate: maglev no slower than wrr at p99 (x1.1 tolerance).
    2. end-to-end lane short-connection A/B — the SAME short bench with
       the C lane pick in wrr vs maglev mode, median of 3 interleaved
       reps (the r09 discipline).
    3. churn-on-resize — a LIVE 4-node membership fleet (real UDP
       heartbeats): steer a client population, kill one peer
       mid-traffic, wait for the DOWN edge, re-steer. The fraction of
       clients whose peer changed is the row; ideal is the dead peer's
       share (25%), the gate allows permutation churn + sampling noise
       (<=28%), and the mod-hash baseline shows the ~75% reshuffle this
       replaces.
    """
    import random as _random
    import socket as _socket

    result = {"stage": "maglev"}
    out_path = os.environ.get("HOSTBENCH_RESULT_FILE")

    def flush():
        if out_path:
            with open(out_path + ".tmp", "w") as f:
                json.dump(result, f)
            os.replace(out_path + ".tmp", out_path)

    from vproxy_tpu.components.elgroup import EventLoopGroup
    from vproxy_tpu.components.servergroup import (HealthCheckConfig,
                                                   ServerGroup)
    from vproxy_tpu.net import vtl as _v

    # ---- 1. backend-pick micro A/B (the accept path's pick op) ----
    elg = EventLoopGroup("mg-bench", 1)
    try:
        hc = HealthCheckConfig(protocol="none", period_ms=60000)
        picks = _env_int("HOSTBENCH_PICKS", 20000)
        rng = _random.Random(42)
        ips = [bytes([10, 0, rng.randrange(256), rng.randrange(256)])
               for _ in range(4096)]
        groups = {}
        for method in ("wrr", "source"):
            g = ServerGroup(f"mg-{method}", elg, hc, method=method)
            for i in range(8):
                g.add(f"s{i}", f"10.2.0.{i}", 2000 + i)
            for s in g.servers:
                s.healthy = True
            for ip in ips:  # warm: table/sequence build + hash memo —
                g.next(ip)  # steady state is what the accept path runs
            groups[method] = g
        # 3 interleaved reps, median per percentile (the r09 A/B
        # discipline): one noisy window on this shared container must
        # not decide either side
        t_ns = time.perf_counter_ns
        reps: dict = {"wrr": [], "source": []}
        for _rep in range(3):
            for method in ("wrr", "source"):
                g = groups[method]
                lat = []
                for i in range(picks):
                    ip = ips[i & 4095]
                    t0 = t_ns()
                    g.next(ip)
                    lat.append(t_ns() - t0)
                lat.sort()
                reps[method].append(lat)
        for g in groups.values():
            g.close()
        for method, key in (("wrr", "wrr"), ("source", "maglev")):
            for pct, frac in (("p50", 0.5), ("p99", 0.99)):
                vals = sorted(lat[int(len(lat) * frac)]
                              for lat in reps[method])
                result[f"host_pick_{key}_{pct}_us"] = round(
                    vals[1] / 1000, 3)
        result["host_pick_maglev_vs_wrr_p99"] = round(
            result["host_pick_maglev_p99_us"]
            / max(result["host_pick_wrr_p99_us"], 1e-9), 3)
        result["host_pick_maglev_no_slower_pass"] = bool(
            result["host_pick_maglev_vs_wrr_p99"] <= 1.10)
        flush()

        # ---- 2. end-to-end lane short A/B: C pick wrr vs maglev ----
        if _v.lanes_supported() and _v.maglev_supported():
            build_tool()
            from vproxy_tpu.components import lanes as lanes_mod
            from vproxy_tpu.components.tcplb import TcpLB
            from vproxy_tpu.components.upstream import Upstream
            procs = []
            welg = EventLoopGroup("mg-w", _env_int("HOSTBENCH_WORKERS", 4))
            saved_pick = lanes_mod.LANE_PICK
            try:
                backends = []
                for _ in range(2):
                    p, port = start_server()
                    procs.append(p)
                    backends.append(port)
                hcr = HealthCheckConfig(timeout_ms=300, period_ms=200,
                                        up=1, down=2)
                g = ServerGroup("mg-lan-g", welg, hcr, "wrr")
                for i, port in enumerate(backends):
                    g.add(f"b{i}", "127.0.0.1", port, weight=1)
                deadline = time.time() + 10
                while time.time() < deadline and not all(
                        s.healthy for s in g.servers):
                    time.sleep(0.05)
                ups = Upstream("mg-lan-u")
                ups.add(g)
                conns = _env_int("HOSTBENCH_CONNS", 64)
                secs = max(3.0,
                           float(os.environ.get("HOSTBENCH_SECS", "8")) / 2)
                lanes_n = _env_int("HOSTBENCH_LANES", 4)
                ab = {"wrr": [], "maglev": []}
                for _rep in range(3):
                    for side in ("wrr", "maglev"):
                        lanes_mod.LANE_PICK = side
                        lb = TcpLB(f"mg-ab-{side}-{_rep}", welg, welg,
                                   "127.0.0.1", 0, ups, protocol="tcp",
                                   lanes=lanes_n)
                        lb.start()
                        try:
                            if lb.lanes is None:
                                raise RuntimeError("lanes fell back")
                            run_client(lb.bind_port, min(conns, 8), 1.0,
                                       1, short=True)
                            r = run_client(lb.bind_port, conns, secs, 1,
                                           short=True)
                            ab[side].append((r["rps"], r["errors"]))
                            if side == "maglev":
                                st = lb.lanes.stat()
                                result["host_lanes_maglev_stat"] = {
                                    "pick": st.get("pick"),
                                    "m": (st.get("maglev") or {}).get("m"),
                                    "served": st.get("served"),
                                    "hit_rate": st.get("hit_rate"),
                                    "accept_ewma_ms":
                                        st.get("accept_ewma_ms")}
                        finally:
                            lb.stop()
                med = {s: sorted(x[0] for x in ab[s])[1] for s in ab}
                result["host_lanes_short_wrr_rps"] = med["wrr"]
                result["host_lanes_short_maglev_rps"] = med["maglev"]
                result["host_lanes_short_reps"] = ab
                result["host_lanes_maglev_vs_wrr"] = round(
                    med["maglev"] / max(1.0, med["wrr"]), 3)
                flush()
            finally:
                lanes_mod.LANE_PICK = saved_pick
                for p in procs:
                    p.terminate()
                welg.close()
    finally:
        elg.close()

    # ---- 3. churn-on-resize: live 4-peer fleet, 1 death ----
    sys.path.insert(0, os.path.join(HERE, "tools"))
    from _fleetlib import free_port, wait_for

    from vproxy_tpu.cluster.membership import Membership, parse_peers
    ports = [free_port(_socket.SOCK_DGRAM) for _ in range(4)]
    spec = ",".join(f"127.0.0.1:{p}" for p in ports)
    nodes = [Membership(i, parse_peers(spec)) for i in range(4)]
    try:
        for n in nodes:
            n.start()
        if not wait_for(lambda: all(n.peers_up() == 4 for n in nodes),
                        20):
            result["cluster_maglev_error"] = "fleet never converged"
        else:
            rng = _random.Random(_env_int("HOSTBENCH_SEED", 9))
            ips = [bytes([198, 18, rng.randrange(256),
                          rng.randrange(256)]) for _ in range(4000)]
            m0 = nodes[0]
            before = {ip: m0.steer_peer(ip).node_id for ip in ips}
            nodes[3].close()  # mid-traffic death
            if not wait_for(lambda: m0.peers_up() == 3, 20):
                result["cluster_maglev_error"] = "DOWN edge never fired"
            else:
                after = {ip: m0.steer_peer(ip).node_id for ip in ips}
                moved = sum(1 for ip in ips if before[ip] != after[ip])
                churn = moved / len(ips)
                dead_share = sum(
                    1 for ip in ips if before[ip] == 3) / len(ips)
                result["cluster_maglev_churn_1of4"] = round(churn, 4)
                result["cluster_maglev_dead_peer_share"] = round(
                    dead_share, 4)
                result["cluster_maglev_slot_remap"] = \
                    m0.steer_status()["last_remap"]
                result["cluster_maglev_table_m"] = m0.steer_status()["m"]
                # ideal = the dead peer's share (~25%); the gate allows
                # permutation churn + sampling noise on top
                result["cluster_maglev_churn_pass"] = bool(churn <= 0.28)
                # the before-world: a mod-N rehash moves ~3/4 of clients
                from vproxy_tpu.rules.maglev import fnv64
                base_moved = sum(1 for ip in ips
                                 if fnv64(ip) % 4 != fnv64(ip) % 3)
                result["cluster_modhash_churn_1of4"] = round(
                    base_moved / len(ips), 4)
    finally:
        for n in nodes:
            n.close()
    flush()
    print(json.dumps(result))
    ok = (result.get("cluster_maglev_churn_pass", False)
          and result.get("host_pick_maglev_no_slower_pass", False))
    return 0 if ok else 1


def run_trace():
    """`--trace`: the request-tracing rows (ISSUE 12,
    docs/observability.md).

    1. **zero-overhead gate** — interleaved median-of-3 short-conn A/B
       on the lanes path: sampling knob ABSENT (module default) vs
       explicitly OFF (configure(0)) must land within noise — the
       knob-off branch is the only cost tracing adds to an unsampled
       build. A sampled (1-in-8) row rides along for honesty.
    2. **attribution capture** — sample=1 over BOTH accept planes (C
       lanes and the python path) plus a standby table install under
       that load: per-stage p50/p99 table, the slowest traces with
       full spans, and the reconciliation of per-stage sums against
       each trace's end-to-end time (the "stages account for the
       latency" gate).

    The artifact is the committed BENCH_r13 trace round."""
    conns = _env_int("HOSTBENCH_CONNS", 32)
    secs = float(os.environ.get("HOSTBENCH_SECS", "4"))
    lanes_n = _env_int("HOSTBENCH_LANES", 4)
    build_tool()
    from vproxy_tpu.components.elgroup import EventLoopGroup
    from vproxy_tpu.components.servergroup import (HealthCheckConfig,
                                                   ServerGroup)
    from vproxy_tpu.components.tcplb import TcpLB
    from vproxy_tpu.components.upstream import Upstream
    from vproxy_tpu.net import vtl as _v
    from vproxy_tpu.utils import trace as TR

    result = {"trace_conns": conns, "trace_secs": secs,
              "trace_lanes": lanes_n,
              "trace_native": _v.trace_supported()}
    out_path = os.environ.get("HOSTBENCH_RESULT_FILE")

    def flush():
        if out_path:
            with open(out_path + ".tmp", "w") as f:
                json.dump(result, f, indent=2)
            os.replace(out_path + ".tmp", out_path)

    procs = []
    lb = None
    elg = None
    groups = []
    try:
        p, bport = start_server()
        procs.append(p)
        elg = EventLoopGroup("w", 4)
        hc = HealthCheckConfig(timeout_ms=300, period_ms=200, up=1, down=2)
        g = ServerGroup("g", elg, hc, "wrr")
        groups.append(g)
        g.add("b0", "127.0.0.1", bport, weight=1)
        deadline = time.time() + 10
        while time.time() < deadline and \
                not any(s.healthy for s in g.servers):
            time.sleep(0.05)
        if not any(s.healthy for s in g.servers):
            result["trace_error"] = "backend never became healthy"
            flush()
            raise RuntimeError(result["trace_error"])
        ups = Upstream("u")
        ups.add(g)

        # ---- 1. zero-overhead gate (absent vs off vs sampled) -------
        # "absent" and "off" are the SAME branch by construction (the
        # env unset and configure(0) both leave SAMPLE=0) — the A/B is
        # the proof plus a noise-floor calibration. Short-conn rps on
        # this sandboxed kernel bursts ±4x with ambient load, so the
        # discipline is PAIRED ratios with alternating order (position
        # bias cancels) and the median over 5 pairs.
        lb = TcpLB("lb-trace", elg, elg, "127.0.0.1", 0, ups,
                   protocol="tcp", lanes=lanes_n)
        lb.start()
        result["trace_lane_engine"] = (lb.lanes.engine()
                                       if lb.lanes is not None else "off")
        run_client(lb.bind_port, min(conns, 8), 1.0, 1, short=True)
        rep_secs = max(2.0, secs / 2)

        def _paired_ratios(knob_a, knob_b, reps=5):
            # ratio = side_b / side_a per rep, order alternating
            ratios, raw = [], []
            for rep in range(reps):
                sides = [("a", knob_a), ("b", knob_b)]
                if rep % 2:
                    sides.reverse()
                rr = {}
                for name, knob in sides:
                    TR.configure(knob)
                    time.sleep(0.5)  # settle: drain the accept burst
                    rr[name] = run_client(lb.bind_port, conns, rep_secs,
                                          1, short=True)["rps"]
                raw.append(rr)
                ratios.append(rr["b"] / max(1.0, rr["a"]))
            ratios.sort()
            return ratios[len(ratios) // 2], raw

        off_vs_absent, raw1 = _paired_ratios(0, 0)
        sampled_vs_off, raw2 = _paired_ratios(0, 8)
        TR.configure(0)
        result["trace_overhead_off_vs_absent"] = round(off_vs_absent, 3)
        result["trace_overhead_sampled_vs_off"] = round(
            sampled_vs_off, 3)
        result["trace_overhead_pairs"] = {"off_vs_absent": raw1,
                                          "sampled_vs_off": raw2}
        # within the sandboxed kernel's same-config noise band (the
        # r09/r11 interleaved runs measured ±15% single-sample bounce;
        # the median-of-5 paired ratio tightens that, but the honest
        # gate stays generous)
        result["trace_overhead_pass"] = bool(
            0.8 <= off_vs_absent <= 1.25)
        flush()

        # ---- 2. attribution capture (sample=1, both planes) ---------
        # per-phase snapshots: the process buffer is bounded (512
        # traces), so each load phase is captured and reset before the
        # next would evict it; the attribution table merges all phases
        captured: list = []  # (phase, [trace dicts with spans])

        def snap_phase(name):
            entries = [dict(t, spans=TR.get_trace(t["trace"]))
                       for t in TR.summaries(last=0)]
            captured.append((name, entries))
            TR.reset()
            return entries

        TR.reset()
        TR.configure(1)
        # widen the trace buffer for the capture: sample=1 at full
        # short-conn load generates traces faster than the production
        # bound (512) holds, and the rare install trace must not lose
        # its slot to the thousandth connection
        prev_max = TR.MAX_TRACES
        TR.MAX_TRACES = 8192
        run_client(lb.bind_port, conns, rep_secs, 1, short=True)
        # a standby install UNDER that load: compile/upload/swap spans
        # bracketing unstalled dispatches (the TableInstaller contract)
        from vproxy_tpu.rules.engine import HintMatcher
        from vproxy_tpu.rules.ir import HintRule
        m = HintMatcher([HintRule(host="seed.example.com")],
                        backend="jax")
        inst = threading.Thread(target=lambda: m.set_rules(
            [HintRule(host=f"h{i}.trace.example.com")
             for i in range(2000)]), daemon=True)
        inst.start()
        run_client(lb.bind_port, conns, rep_secs, 1, short=True)
        inst.join(60)
        lb.stop()  # lane threads drain their span rings on shutdown
        lb = None
        lane_entries = snap_phase("lane")
        install_spans = [s for t in lane_entries for s in t["spans"]
                         if s["plane"] == "install"]
        result["trace_install_phases"] = sorted(
            {s["span"] for s in install_spans})
        result["trace_install_trace"] = install_spans

        # the python accept plane: same load, lanes off
        lb = TcpLB("lb-trace-py", elg, elg, "127.0.0.1", 0, ups,
                   protocol="tcp", lanes=0)
        lb.start()
        run_client(lb.bind_port, min(conns, 8), 1.0, 1, short=True)
        run_client(lb.bind_port, conns, rep_secs, 1, short=True)
        lb.stop()
        lb = None
        time.sleep(0.5)
        snap_phase("py")

        # the stitched cross-plane trace: a lanes LB whose non-trivial
        # ACL compiles an EMPTY lane entry — every accept begins its
        # trace in C (accept + punt spans) and the python path
        # CONTINUES it through acl/classify/pick/connect/splice
        from vproxy_tpu.components.secgroup import SecurityGroup
        from vproxy_tpu.rules.ir import AclRule, Proto
        from vproxy_tpu.utils.ip import Network
        sg = SecurityGroup("trace-acl", default_allow=False)
        sg.add_rule(AclRule("lo", Network.parse("127.0.0.0/8"),
                            Proto.TCP, 1, 65535, True))
        lb = TcpLB("lb-trace-stitch", elg, elg, "127.0.0.1", 0, ups,
                   protocol="tcp", lanes=lanes_n, security_group=sg)
        lb.start()
        run_client(lb.bind_port, min(conns, 8), 2.0, 1, short=True)
        lb.stop()
        lb = None
        time.sleep(1.0)
        TR.configure(0)
        stitch_entries = snap_phase("stitched")
        TR.MAX_TRACES = prev_max

        def _reconcile(entries):
            """Per complete trace: sum of stage durations vs its own
            end-to-end window — the stages must ACCOUNT for the
            latency, not decorate it. Classified by path: pure lane /
            pure python / stitched (a sampled punt that began in C and
            finished in python — its gap IS the punt handoff)."""
            recon = {"lane": [], "py": [], "stitched": []}
            for t in entries:
                spans = t["spans"]
                if "close" not in {s["span"] for s in spans}:
                    continue  # still in flight at capture end
                has_lane = any(s["plane"] == "lane" for s in spans)
                has_py = any(s["plane"] == "accept" for s in spans)
                path = ("stitched" if has_lane and has_py
                        else "lane" if has_lane else "py")
                t0 = min(s["t_ns"] for s in spans)
                t1 = max(s["t_ns"] + s["dur_ns"] for s in spans)
                stage_sum = sum(
                    s["dur_ns"] for s in spans
                    if s["span"] in ("accept", "route_pick", "connect",
                                     "splice", "acl", "backend_pick"))
                if t1 > t0:
                    recon[path].append(stage_sum / (t1 - t0))
            out = {}
            for path, ratios in recon.items():
                if ratios:
                    ratios.sort()
                    out[path] = {
                        "n": len(ratios),
                        "median": round(ratios[len(ratios) // 2], 3),
                        "min": round(ratios[0], 3),
                        "max": round(ratios[-1], 3)}
            return out

        all_entries = [t for _, entries in captured for t in entries]
        for path, rec in _reconcile(all_entries).items():
            result[f"trace_reconcile_{path}"] = rec
        # the per-stage attribution table over every captured phase
        by: dict = {}
        for t in all_entries:
            for s in t["spans"]:
                by.setdefault(f"{s['plane']}/{s['span']}", []).append(
                    s["dur_ns"] / 1000.0)
        result["trace_stage_table"] = {
            k: {"n": len(v),
                "p50_us": round(sorted(v)[len(v) // 2], 1),
                "p99_us": round(sorted(v)[min(len(v) - 1,
                                              (len(v) * 99) // 100)], 1)}
            for k, v in sorted(by.items())}
        worst = sorted(all_entries, key=lambda t: t["total_us"],
                       reverse=True)[:5]
        result["slowest_traces"] = worst
        result["trace_stitched"] = sum(
            1 for t in stitch_entries if len(t["planes"]) > 1)
        stitched = [t for t in stitch_entries
                    if "lane" in t["planes"] and "accept" in t["planes"]]
        if stitched:
            result["trace_stitched_example"] = max(
                stitched, key=lambda t: len(t["planes"]))

        spans_c, drops_c = _v.trace_counters()
        result["trace_c_spans"] = spans_c
        result["trace_c_ring_drops"] = drops_c
        result["trace_py_drops"] = TR.py_dropped_total()
        # gate: lane and python stages each cover >=90% of end-to-end
        # at the median (the residue is real scheduling gap time; far
        # under would mean a stage went missing). The stitched path is
        # reported, not gated: its gap IS the punt-handoff queue time.
        result["trace_reconcile_pass"] = bool(
            result.get("trace_reconcile_lane", {}).get("median", 0) >= 0.9
            and result.get("trace_reconcile_py", {}).get("median", 0)
            >= 0.9)
        flush()
    finally:
        if lb is not None:
            try:
                lb.stop()
            except Exception:
                pass
        for g_ in groups:
            try:
                g_.close()
            except Exception:
                pass
        if elg is not None:
            try:
                elg.close()
            except Exception:
                pass
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(5)
            except subprocess.TimeoutExpired:
                p.kill()
    print(json.dumps(result))
    flush()
    ok = result.get("trace_overhead_pass", False) and \
        result.get("trace_reconcile_pass", False)
    return 0 if ok else 1


def run_analytics():
    """`--analytics`: the traffic-analytics rows (ISSUE 15,
    docs/observability.md "traffic analytics").

    1. **overhead gate** — interleaved PAIRED short-conn A/B on the
       lanes path: analytics OFF vs ON (the per-accept cost is two
       shard updates + the per-tick drain), median ratio over 7
       alternating-order pairs, gate rps_off/rps_on <= 1.05. An
       off-vs-absent pair rides along as the noise-floor calibration
       (identical branch by construction, PR-13 discipline) with the
       honest [0.8, 1.25] band.
    2. **plane capture** — traffic through BOTH accept planes (C lanes
       and lanes=0 python path) with analytics on: the top tables must
       attribute the loopback client, the backend and both LBs, and
       the per-dim snapshot lands in the artifact.
    3. **seeded-Zipf accuracy** — the sketch contract measured
       in-process: Space-Saving top-K superset of every key above
       N/K, Count-Min never undercounting with >=95% of keys inside
       e*N/width (the per-key probabilistic bound's quantile form).

    The artifact is the committed BENCH_r14 analytics round."""
    import random as _random

    conns = _env_int("HOSTBENCH_CONNS", 32)
    secs = float(os.environ.get("HOSTBENCH_SECS", "4"))
    lanes_n = _env_int("HOSTBENCH_LANES", 4)
    build_tool()
    from vproxy_tpu.components.elgroup import EventLoopGroup
    from vproxy_tpu.components.servergroup import (HealthCheckConfig,
                                                   ServerGroup)
    from vproxy_tpu.components.tcplb import TcpLB
    from vproxy_tpu.components.upstream import Upstream
    from vproxy_tpu.net import vtl as _v
    from vproxy_tpu.utils import sketch as SK

    result = {"analytics_conns": conns, "analytics_secs": secs,
              "analytics_lanes": lanes_n,
              "analytics_native": _v.hh_supported()}
    out_path = os.environ.get("HOSTBENCH_RESULT_FILE")

    def flush():
        if out_path:
            with open(out_path + ".tmp", "w") as f:
                json.dump(result, f, indent=2)
            os.replace(out_path + ".tmp", out_path)

    procs = []
    lb = None
    elg = None
    groups = []
    try:
        p, bport = start_server()
        procs.append(p)
        elg = EventLoopGroup("w", 4)
        hc = HealthCheckConfig(timeout_ms=300, period_ms=200, up=1, down=2)
        g = ServerGroup("g", elg, hc, "wrr")
        groups.append(g)
        g.add("b0", "127.0.0.1", bport, weight=1)
        deadline = time.time() + 10
        while time.time() < deadline and \
                not any(s.healthy for s in g.servers):
            time.sleep(0.05)
        if not any(s.healthy for s in g.servers):
            result["analytics_error"] = "backend never became healthy"
            flush()
            raise RuntimeError(result["analytics_error"])
        ups = Upstream("u")
        ups.add(g)

        # ---- 1. overhead gate (off vs on, paired + interleaved) -----
        lb = TcpLB("lb-hh", elg, elg, "127.0.0.1", 0, ups,
                   protocol="tcp", lanes=lanes_n)
        lb.start()
        result["analytics_lane_engine"] = (lb.lanes.engine()
                                           if lb.lanes is not None
                                           else "off")
        run_client(lb.bind_port, min(conns, 8), 1.0, 1, short=True)
        rep_secs = max(2.0, secs / 2)

        def _paired_ratios(knob_a, knob_b, reps=7):
            # ratio = side_a rps / side_b rps per rep (a=off, b=on:
            # >1 means the knob costs throughput), order alternating
            ratios, raw = [], []
            for rep in range(reps):
                sides = [("a", knob_a), ("b", knob_b)]
                if rep % 2:
                    sides.reverse()
                rr = {}
                for name, knob in sides:
                    SK.configure(on=knob)
                    time.sleep(0.5)  # settle: drain the accept burst
                    rr[name] = run_client(lb.bind_port, conns, rep_secs,
                                          1, short=True)["rps"]
                raw.append(rr)
                ratios.append(rr["a"] / max(1.0, rr["b"]))
            ratios.sort()
            return ratios[len(ratios) // 2], raw

        off_vs_absent, raw0 = _paired_ratios(False, False, reps=5)
        off_vs_on, raw1 = _paired_ratios(False, True)
        SK.configure(on=True)
        result["analytics_overhead_off_vs_absent"] = round(
            off_vs_absent, 3)
        result["analytics_overhead_off_vs_on"] = round(off_vs_on, 3)
        result["analytics_overhead_pairs"] = {"off_vs_absent": raw0,
                                              "off_vs_on": raw1}
        # the ISSUE gate: analytics ON costs <= 5% of lane short-conn
        # throughput (median paired ratio; the true per-accept cost is
        # two shard updates against a ~350us connection lifetime)
        result["analytics_overhead_pass"] = bool(off_vs_on <= 1.05)
        # knob-off zero-cost: off and absent are the same branch by
        # construction — the pair is the noise-floor calibration
        result["analytics_offcost_pass"] = bool(
            0.8 <= off_vs_absent <= 1.25)
        flush()

        # ---- 2. plane capture (both accept planes) ------------------
        SK.reset()
        # DELTA, not the cumulative atomic: phase 1's overhead runs
        # already drove the process-global counter into the thousands,
        # so a broken phase-2 drain would still read > 0 from it
        c_shard0 = _v.hh_counters()[0]
        run_client(lb.bind_port, conns, rep_secs, 1, short=True)
        time.sleep(0.5)  # lane 0's next tick folds the routes credit
        lane_updates = _v.hh_counters()[0] - c_shard0
        # drain evidence: the clients dim filled while the ONLY running
        # LB was lane-served (python accepts == punts == 0), so every
        # key arrived through vtl_hh_drain, not a python site
        lane_drained = (sum(e["count"]
                            for e in SK.top_table("clients", 0))
                        if lb.accepted == 0 else 0)
        lb.stop()
        lb = None
        lb = TcpLB("lb-hh-py", elg, elg, "127.0.0.1", 0, ups,
                   protocol="tcp", lanes=0)
        lb.start()
        run_client(lb.bind_port, conns, rep_secs, 1, short=True)
        lb.stop()
        lb = None
        snap = SK.snapshot()
        result["analytics_snapshot"] = snap
        tops = snap["top"]
        lane_ok = any(e["key"] == "lb-hh" for e in tops["routes"])
        py_ok = any(e["key"] == "lb-hh-py" for e in tops["routes"])
        client_ok = bool(tops["clients"]) and \
            tops["clients"][0]["key"] == "127.0.0.1"
        backend_ok = any(e["key"] == f"127.0.0.1:{bport}"
                         for e in tops["backends"])
        result["analytics_capture"] = {
            "top_client_is_loopback": client_ok,
            "backend_attributed": backend_ok,
            "lane_lb_in_routes": lane_ok,
            "py_lb_in_routes": py_ok,
            "lane_shard_update_delta": lane_updates,
            "lane_drained_client_count": lane_drained,
            "shard_overflows": _v.hh_counters()[1],
        }
        result["analytics_capture_pass"] = bool(
            client_ok and backend_ok and lane_ok and py_ok
            and lane_updates > 0 and lane_drained > 0)
        flush()

        # ---- 3. seeded-Zipf accuracy (the sketch contract) ----------
        rng = _random.Random(1414)
        n_keys, n_events, k = 500, 30000, 32
        keys = [f"198.51.{i // 250}.{i % 250}" for i in range(n_keys)]
        weights = [1.0 / (i + 1) ** 1.2 for i in range(n_keys)]
        stream = rng.choices(keys, weights=weights, k=n_events)
        true = {}
        for key in stream:
            true[key] = true.get(key, 0) + 1
        ws = SK.WindowedSketch("bench", window_s=1e9, k=k)
        t0 = ws._rotate_at - ws.window_s
        for key in stream:
            ws.update(key, now=t0)
        top_keys = {e["key"] for e in ws.top(now=t0)}
        threshold = n_events / k
        heavy = {key for key, c in true.items() if c > threshold}
        missing = heavy - top_keys
        cm = ws._cur[0]
        bound = 2.72 * n_events / cm.width
        over = under = 0
        for key, t in true.items():
            est = cm.estimate(key.encode())
            if est < t:
                under += 1
            if est > t + bound:
                over += 1
        result["analytics_zipf"] = {
            "events": n_events, "distinct": n_keys, "k": k,
            "true_heavy_hitters": len(heavy),
            "heavy_missing_from_topk": len(missing),
            "cm_undercounts": under,
            "cm_over_epsilon_keys": over,
            "cm_epsilon_bound": round(bound, 1),
            "top5": [{"key": e["key"], "count": e["count"],
                      "err": e["err"],
                      "true": true.get(e["key"], 0)}
                     for e in ws.top(5, now=t0)],
        }
        result["analytics_zipf_pass"] = bool(
            not missing and under == 0
            and over <= 0.05 * len(true))
        flush()
    finally:
        if lb is not None:
            try:
                lb.stop()
            except Exception:
                pass
        for g_ in groups:
            try:
                g_.close()
            except Exception:
                pass
        if elg is not None:
            try:
                elg.close()
            except Exception:
                pass
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(5)
            except subprocess.TimeoutExpired:
                p.kill()
    print(json.dumps(result))
    flush()
    ok = (result.get("analytics_overhead_pass", False)
          and result.get("analytics_capture_pass", False)
          and result.get("analytics_zipf_pass", False))
    return 0 if ok else 1


def run_replay():
    """`--replay`: the workload capture -> replay -> fidelity loop
    (ISSUE 16, docs/replay.md).

    1. **source capture** — a seeded-Zipf client mix (distinct
       loopback source addresses, ground-truth heavy hitters known in
       advance) through a real TcpLB inside a capture window; export
       the WorkloadModel.
    2. **determinism** — the same (model, seed) must produce the same
       schedule hash in THIS process and in a fresh interpreter
       (tools/replay.py --hash-only).
    3. **fidelity at 1x** — replay the model against a fresh world
       with re-capture: >= 4/5 top-K client identity and offered-rate
       ratio within [0.9, 1.1], zero hard failures.
    4. **capture-off overhead** — paired order-alternating A/B on the
       lane short-conn path, VPROXY_TPU_WORKLOAD off vs on, median
       ratio of 7 gate <= 1.05 (the analytics-stage discipline), with
       the off-vs-absent noise-floor pair riding along.
    5. **capacity row** — the model's per-client rate scaled to a 10M
       user diurnal peak over the measured per-node capacity.

    The artifact is the committed BENCH replay round."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "tools"))
    import replay as RP
    from vproxy_tpu.components.elgroup import EventLoopGroup
    from vproxy_tpu.components.servergroup import (HealthCheckConfig,
                                                   ServerGroup)
    from vproxy_tpu.components.tcplb import TcpLB
    from vproxy_tpu.components.upstream import Upstream
    from vproxy_tpu.utils import sketch as SK
    from vproxy_tpu.utils import workload as WL
    from vproxy_tpu.utils.workload import WorkloadModel

    seed = _env_int("HOSTBENCH_SEED", 16)
    conns = _env_int("HOSTBENCH_CONNS", 32)
    secs = float(os.environ.get("HOSTBENCH_SECS", "4"))
    lanes_n = _env_int("HOSTBENCH_LANES", 4)
    build_tool()
    result = {"replay_seed": seed, "replay_conns": conns,
              "replay_secs": secs}
    out_path = os.environ.get("HOSTBENCH_RESULT_FILE")

    def flush():
        if out_path:
            with open(out_path + ".tmp", "w") as f:
                json.dump(result, f, indent=2)
            os.replace(out_path + ".tmp", out_path)

    procs = []
    lb = None
    elg = None
    groups = []
    try:
        # ---- 1. source capture: seeded-Zipf mix, real LB ------------
        SK.reset()
        WL.reset()
        world = RP.ReplayWorld(alias="bench-replay-src")
        try:
            WL.capture_start()
            mix = RP.drive_zipf_mix(world.lb.bind_port, seed=seed,
                                    n=240, clients=6, pace_s=0.01)
            WL.capture_stop()
            model = WorkloadModel.fit(seed=seed)
        finally:
            world.close()
        result["replay_mix"] = {k: mix[k] for k in ("ok", "fail",
                                                    "shed")}
        result["replay_true_top5"] = mix["true_top"][:5]
        result["replay_source_rate_hz"] = model.plane_rate("accept")
        flush()

        # ---- 2. same-seed schedule identity across processes --------
        h_local = RP.schedule_hash(
            RP.build_schedule(model, seed, max_arrivals=200))
        h_again = RP.schedule_hash(
            RP.build_schedule(model, seed, max_arrivals=200))
        fd, mpath = tempfile.mkstemp(suffix=".json")
        with os.fdopen(fd, "w") as f:
            f.write(model.to_json())
        try:
            from vproxy_tpu.utils.jaxenv import cpu_subprocess_env
            sub = subprocess.run(
                [sys.executable, os.path.join(here, "tools",
                                              "replay.py"),
                 "--model", mpath, "--seed", str(seed),
                 "--max-arrivals", "200", "--hash-only"],
                capture_output=True, text=True, timeout=180,
                env=cpu_subprocess_env())
            h_sub = sub.stdout.strip()
        finally:
            os.unlink(mpath)
        result["replay_schedule_hash"] = h_local
        result["replay_schedule_hash_subprocess"] = h_sub
        result["replay_determinism_pass"] = bool(
            sub.returncode == 0 and h_local == h_again
            and h_sub == h_local)
        flush()

        # ---- 3. replay at 1x with the fidelity gate -----------------
        rep = RP.run_replay(model, seed=seed, speed=1.0,
                            max_arrivals=200, fidelity_gate=True,
                            rate_band=(0.9, 1.1))
        result["replay_1x"] = {
            "arrivals": rep["arrivals"], "span_s": rep["span_s"],
            "late_s": rep["late_s"], "results": rep["results"],
            "p50_ms": rep["p50_ms"], "p99_ms": rep["p99_ms"],
            "slo": rep["slo"],
            "schedule_hash": rep["schedule_hash"],
        }
        result["replay_fidelity"] = rep["fidelity"]
        result["replay_fidelity_pass"] = bool(
            rep["fidelity"]["pass"] and rep["results"]["fail"] == 0)
        flush()

        # ---- 4. capture-off overhead (paired A/B, lanes path) -------
        p, bport = start_server()
        procs.append(p)
        elg = EventLoopGroup("w", 4)
        hc = HealthCheckConfig(timeout_ms=300, period_ms=200, up=1,
                               down=2)
        g = ServerGroup("g", elg, hc, "wrr")
        groups.append(g)
        g.add("b0", "127.0.0.1", bport, weight=1)
        deadline = time.time() + 10
        while time.time() < deadline and \
                not any(s.healthy for s in g.servers):
            time.sleep(0.05)
        if not any(s.healthy for s in g.servers):
            result["replay_error"] = "backend never became healthy"
            flush()
            raise RuntimeError(result["replay_error"])
        ups = Upstream("u")
        ups.add(g)
        lb = TcpLB("lb-wl", elg, elg, "127.0.0.1", 0, ups,
                   protocol="tcp", lanes=lanes_n)
        lb.start()
        run_client(lb.bind_port, min(conns, 8), 1.0, 1, short=True)
        rep_secs = max(2.0, secs / 2)

        def _paired_ratios(knob_a, knob_b, reps=7):
            # ratio = side_a rps / side_b rps per rep (a=off, b=on:
            # >1 means the knob costs throughput), order alternating
            ratios, raw = [], []
            for r in range(reps):
                sides = [("a", knob_a), ("b", knob_b)]
                if r % 2:
                    sides.reverse()
                rr = {}
                for name, knob in sides:
                    WL.configure(on=knob)
                    time.sleep(0.5)  # settle: drain the accept burst
                    rr[name] = run_client(lb.bind_port, conns,
                                          rep_secs, 1,
                                          short=True)["rps"]
                raw.append(rr)
                ratios.append(rr["a"] / max(1.0, rr["b"]))
            ratios.sort()
            return ratios[len(ratios) // 2], raw

        off_vs_absent, raw0 = _paired_ratios(False, False, reps=5)
        off_vs_on, raw1 = _paired_ratios(False, True)
        WL.configure(on=True)
        result["replay_overhead_off_vs_absent"] = round(
            off_vs_absent, 3)
        result["replay_overhead_off_vs_on"] = round(off_vs_on, 3)
        result["replay_overhead_pairs"] = {"off_vs_absent": raw0,
                                           "off_vs_on": raw1}
        # the ISSUE gate: capture ON costs <= 5% of lane short-conn
        # throughput (per accept: one atomic exchange + three
        # per-connection bucket adds at reap)
        result["replay_overhead_pass"] = bool(off_vs_on <= 1.05)
        result["replay_offcost_pass"] = bool(
            0.8 <= off_vs_absent <= 1.25)
        flush()

        # ---- 5. capacity-planning row -------------------------------
        node_rps = max(rr["b"] for rr in raw1)
        result["replay_capacity"] = RP.capacity_row(
            model, node_capacity_rps=node_rps)
        flush()
    finally:
        if lb is not None:
            try:
                lb.stop()
            except Exception:
                pass
        for g_ in groups:
            try:
                g_.close()
            except Exception:
                pass
        if elg is not None:
            try:
                elg.close()
            except Exception:
                pass
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(5)
            except subprocess.TimeoutExpired:
                p.kill()
    print(json.dumps(result))
    flush()
    ok = (result.get("replay_determinism_pass", False)
          and result.get("replay_fidelity_pass", False)
          and result.get("replay_overhead_pass", False)
          and result.get("replay_offcost_pass", False))
    return 0 if ok else 1


def run_policing():
    """`--policing`: the admission-policing rows (ISSUE 19,
    docs/robustness.md "admission policing").

    1. **overhead gate** — interleaved PAIRED short-conn A/B on the
       lanes path: policing OFF vs ON with a live decision table
       that CONTAINS the bench client (huge quota, so every accept
       pays the full probe + bucket debit and none sheds — the
       honest worst case for the hot path), median ratio over 7
       alternating-order pairs, gate rps_off/rps_on <= 1.05; the
       off-vs-absent pair rides along as the noise floor. The probe
       delta is recorded so a silently-empty table can't fake a pass.
    2. **adversarial_crowd** — the storm scenario verdict embedded
       whole: replayed legit mix + attacking herd, legit SLO with
       policing on, herd shed >=90% attributed, OFF differential.

    The artifact is the committed BENCH_r19 policing round."""
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "tools"))
    conns = _env_int("HOSTBENCH_CONNS", 32)
    secs = float(os.environ.get("HOSTBENCH_SECS", "4"))
    lanes_n = _env_int("HOSTBENCH_LANES", 4)
    seed = _env_int("HOSTBENCH_SEED", 7)
    scale = float(os.environ.get("HOSTBENCH_STORM_SCALE", "1.0"))
    build_tool()
    from vproxy_tpu.components.elgroup import EventLoopGroup
    from vproxy_tpu.components.servergroup import (HealthCheckConfig,
                                                   ServerGroup)
    from vproxy_tpu.components.tcplb import TcpLB
    from vproxy_tpu.components.upstream import Upstream
    from vproxy_tpu.net import vtl as _v
    from vproxy_tpu.policing import engine as PE
    from vproxy_tpu.policing.engine import Policy
    from vproxy_tpu.utils import sketch as SK

    result = {"policing_conns": conns, "policing_secs": secs,
              "policing_lanes": lanes_n, "policing_seed": seed,
              "policing_native": _v.police_supported()}
    out_path = os.environ.get("HOSTBENCH_RESULT_FILE")

    def flush():
        if out_path:
            with open(out_path + ".tmp", "w") as f:
                json.dump(result, f, indent=2)
            os.replace(out_path + ".tmp", out_path)

    procs = []
    lb = None
    elg = None
    groups = []
    eng = PE.default()
    try:
        p, bport = start_server()
        procs.append(p)
        elg = EventLoopGroup("w", 4)
        hc = HealthCheckConfig(timeout_ms=300, period_ms=200, up=1,
                               down=2)
        g = ServerGroup("g", elg, hc, "wrr")
        groups.append(g)
        g.add("b0", "127.0.0.1", bport, weight=1)
        deadline = time.time() + 10
        while time.time() < deadline and \
                not any(s.healthy for s in g.servers):
            time.sleep(0.05)
        if not any(s.healthy for s in g.servers):
            result["policing_error"] = "backend never became healthy"
            flush()
            raise RuntimeError(result["policing_error"])
        ups = Upstream("u")
        ups.add(g)

        # ---- 1. overhead gate (off vs on, paired + interleaved) -----
        SK.reset()
        eng.set_policies([])
        eng.reset()
        PE.configure(True)
        lb = TcpLB("lb-pol", elg, elg, "127.0.0.1", 0, ups,
                   protocol="tcp", lanes=lanes_n)
        lb.start()
        result["policing_lane_engine"] = (lb.lanes.engine()
                                          if lb.lanes is not None
                                          else "off")
        # a quota the bench can never trip: every accept runs the full
        # probe + debit (the measured cost) and zero accepts shed (a
        # shed would make ON *faster* and rot the gate's meaning)
        eng.set_policy(Policy("bench", "clients", 1e5, 2e5, "shed"))
        run_client(lb.bind_port, min(conns, 8), 1.0, 1, short=True)
        # the bench client must be IN the installed table before the
        # measured pairs: wait for the lane drain to surface it, then
        # tick (detection precedes enforcement, the storm discipline)
        deadline = time.time() + 6
        while time.time() < deadline and not any(
                r["key"] == "127.0.0.1"
                for r in SK.top_table("clients", 0)):
            time.sleep(0.05)
        PE.tick()
        result["policing_table_armed"] = any(
            e["key"] == "127.0.0.1" for e in eng.table_snapshot())
        checked0 = (_v.police_counters(lb.lanes.handle)[0]
                    if _v.police_supported() and lb.lanes is not None
                    else 0)
        rep_secs = max(2.0, secs / 2)

        def _paired_ratios(knob_a, knob_b, reps=7):
            # ratio = side_a rps / side_b rps per rep (a=off, b=on:
            # >1 means the knob costs throughput), order alternating
            ratios, raw = [], []
            for rep in range(reps):
                sides = [("a", knob_a), ("b", knob_b)]
                if rep % 2:
                    sides.reverse()
                rr = {}
                for name, knob in sides:
                    PE.configure(knob)
                    time.sleep(0.5)  # settle: drain the accept burst
                    rr[name] = run_client(lb.bind_port, conns,
                                          rep_secs, 1,
                                          short=True)["rps"]
                raw.append(rr)
                ratios.append(rr["a"] / max(1.0, rr["b"]))
            ratios.sort()
            return ratios[len(ratios) // 2], raw

        off_vs_absent, raw0 = _paired_ratios(False, False, reps=5)
        off_vs_on, raw1 = _paired_ratios(False, True)
        PE.configure(True)
        ctr = (_v.police_counters(lb.lanes.handle)
               if _v.police_supported() and lb.lanes is not None
               else (0, 0, 0, 0, 0))
        result["policing_overhead_off_vs_absent"] = round(
            off_vs_absent, 3)
        result["policing_overhead_off_vs_on"] = round(off_vs_on, 3)
        result["policing_overhead_pairs"] = {"off_vs_absent": raw0,
                                             "off_vs_on": raw1}
        result["policing_probe_checked"] = ctr[0] - checked0
        result["policing_probe_shed"] = ctr[1]
        # the ISSUE gate: policing ON costs <= 5% of lane short-conn
        # throughput (the true per-accept cost is one open-addressed
        # probe + one integer bucket debit)
        result["policing_overhead_pass"] = bool(off_vs_on <= 1.05)
        result["policing_offcost_pass"] = bool(
            0.8 <= off_vs_absent <= 1.25)
        # evidence the ON sides measured a LIVE table, not a miss: the
        # probe found-and-debited, and found-path sheds stayed zero
        result["policing_probe_active"] = bool(
            not _v.police_supported()
            or (ctr[0] - checked0 > 0 and ctr[1] == 0))
        flush()
        lb.stop()
        lb = None
        eng.set_policies([])
        eng.reset()

        # ---- 2. the adversarial_crowd verdict, embedded whole -------
        import storm as ST
        res = ST.scenario_adversarial_crowd(scale=scale, seed=seed)
        result["policing_storm"] = res
        result["policing_storm_pass"] = bool(res.get("pass"))
        flush()
    finally:
        PE.configure(True)
        try:
            eng.set_policies([])
            eng.reset()
        except Exception:
            pass
        if lb is not None:
            try:
                lb.stop()
            except Exception:
                pass
        for g_ in groups:
            try:
                g_.close()
            except Exception:
                pass
        if elg is not None:
            try:
                elg.close()
            except Exception:
                pass
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(5)
            except subprocess.TimeoutExpired:
                p.kill()
    print(json.dumps(result))
    flush()
    ok = (result.get("policing_overhead_pass", False)
          and result.get("policing_offcost_pass", False)
          and result.get("policing_probe_active", False)
          and result.get("policing_storm_pass", False))
    return 0 if ok else 1


def main():
    # SIGTERM (bench.py's stage timeout) must run the finally block —
    # otherwise the native server processes are orphaned forever
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    from vproxy_tpu.utils.jaxenv import force_cpu

    if "--storm" in sys.argv[1:]:
        force_cpu(8)  # a host bench: the tools it imports expect the CPU
        return run_storm()

    if "--maglev" in sys.argv[1:]:
        return run_maglev()

    if "--trace" in sys.argv[1:]:
        return run_trace()
    if "--analytics" in sys.argv[1:]:
        return run_analytics()
    if "--replay" in sys.argv[1:]:
        force_cpu(8)  # a host bench: the tools it imports expect the CPU
        return run_replay()
    if "--policing" in sys.argv[1:]:
        force_cpu(8)  # a host bench: the tools it imports expect the CPU
        return run_policing()

    # --lanes: run ONLY the accept-lane stage (direct ceiling +
    # serialization evidence + lanes on/off + GIL-contention A/B) —
    # the BENCH_r09_builder_lanes.json artifact
    lanes_only = "--lanes" in sys.argv[1:]

    conns = _env_int("HOSTBENCH_CONNS", 64)
    secs = float(os.environ.get("HOSTBENCH_SECS", "8"))
    pipeline = _env_int("HOSTBENCH_PIPELINE", 4)
    n_backends = _env_int("HOSTBENCH_BACKENDS", 2)
    workers = _env_int("HOSTBENCH_WORKERS", 4)
    pool_n = _env_int("HOSTBENCH_POOL", 32)
    # hostbench clients speak first (HTTP), so the LB listeners can defer
    # accepts until data arrives; per-listen env read makes this apply to
    # every LB below without touching the backend servers' C listeners
    defer = _env_int("HOSTBENCH_DEFER_ACCEPT", 1)
    if defer > 0:
        os.environ["VPROXY_TPU_DEFER_ACCEPT"] = str(defer)

    build_tool()
    procs = []
    result = {"host_conns": conns, "host_secs": secs,
              "host_pipeline": pipeline, "host_workers": workers,
              "host_defer_accept_s": defer}
    out_path = os.environ.get("HOSTBENCH_RESULT_FILE")

    def flush():
        # incremental: a timeout mid-stage keeps the finished sections
        if out_path:
            with open(out_path + ".tmp", "w") as f:
                json.dump(result, f)
            os.replace(out_path + ".tmp", out_path)

    lb = None
    elg = acceptor = None
    groups = []
    try:
        backends = []
        for _ in range(n_backends):
            p, port = start_server()
            procs.append(p)
            backends.append(port)

        # ceiling: client -> server direct
        r = run_client(backends[0], conns, secs, pipeline)
        result["host_direct_rps"] = r["rps"]
        result["host_direct_errors"] = r["errors"]
        # short-connection ceiling WITHOUT the LB: what connect/accept
        # cost on this kernel alone — the denominator that makes the LB
        # short row comparable across machines (sandboxed kernels have
        # been measured 5-6x slower per accept cycle than bare metal)
        # median-of-3: the denominator of host_short_vs_ceiling must
        # not ride one sample's ambient-load luck
        dsr = sorted(run_client(backends[0], conns, max(2.0, secs / 2),
                                1, short=True)["rps"] for _ in range(3))
        result["host_direct_short_rps"] = dsr[1]
        result["host_direct_short_reps"] = dsr
        flush()

        # kernel-serialization evidence: two direct short benches run
        # in PARALLEL against separate servers. On this container class
        # the sum lands at ~one bench's rate — the sandbox kernel
        # serializes all connection setup machine-wide, which is what
        # pins any LB short row (2 connects + 2 accepts per request)
        # near 0.5x of direct no matter how parallel the accept plane.
        if len(backends) >= 2:
            par_out = [None, None]

            def _par_short(i, port):
                par_out[i] = run_client(port, conns, 3.0, 1, short=True)

            ts = [threading.Thread(target=_par_short, args=(i, backends[i]))
                  for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if par_out[0] and par_out[1]:
                two_x = round(par_out[0]["rps"] + par_out[1]["rps"], 1)
                result["host_direct_short_2x_sum"] = two_x
                scaling = round(
                    two_x / max(1.0, result["host_direct_short_rps"]), 3)
                # a parallel-capable kernel doubles (~2.0x); this
                # container class measures ~1.1-1.4x — connection setup
                # is substantially serialized machine-wide
                result["host_direct_short_2x_scaling"] = scaling
                result["host_kernel_serialized"] = bool(scaling < 1.6)
        flush()

        from vproxy_tpu.components.elgroup import EventLoopGroup
        from vproxy_tpu.components.servergroup import (HealthCheckConfig,
                                                       ServerGroup)
        from vproxy_tpu.components.tcplb import TcpLB
        from vproxy_tpu.components.upstream import Upstream
        from vproxy_tpu.rules.ir import HintRule

        acceptor = EventLoopGroup("acc", 1)
        elg = EventLoopGroup("w", workers)

        # fixed canary FIRST: what the machine's splice path is worth
        # this run, before any LB row can be mis-attributed to code
        if not lanes_only:
            canary = splice_canary(elg,
                                   _env_int("HOSTBENCH_CANARY_MB", 1024))
            if canary is not None:
                result["host_canary_MBps"] = canary
            flush()

        hc = HealthCheckConfig(timeout_ms=300, period_ms=200, up=1, down=2)
        g = ServerGroup("g", elg, hc, "wrr")
        groups.append(g)
        for i, port in enumerate(backends):
            g.add(f"b{i}", "127.0.0.1", port, weight=1)
        deadline = time.time() + 10
        while time.time() < deadline and \
                sum(1 for s in g.servers if s.healthy) < n_backends:
            time.sleep(0.05)
        healthy = sum(1 for s in g.servers if s.healthy)
        if healthy == 0:
            # a 0-rps "measurement" of a backend-less LB is a lie —
            # mark the failure and skip the LB modes entirely
            result["host_error"] = "backends never became healthy"
            flush()
            raise RuntimeError(result["host_error"])
        ups = Upstream("u")
        ups.add(g, annotations=HintRule(host="bench.example.com"))

        for mode, key in (() if lanes_only else
                          (("tcp", "host_tcp_rps"),
                           ("http-splice", "host_http_rps"))):
            lb = TcpLB(f"lb-{mode}", acceptor, elg, "127.0.0.1", 0, ups,
                       protocol=mode)
            lb.start()
            try:
                # warmup: first http-splice connections pay the classify
                # path's one-time jit compile; keep it out of the window
                run_client(lb.bind_port, min(conns, 4), 1.0, 1)
                r = run_client(lb.bind_port, conns, secs, pipeline)
                result[key] = r["rps"]
                result[key.replace("_rps", "_errors")] = r["errors"]
                flush()
            finally:
                lb.stop()
                lb = None

        # short connections (connection-per-request): the accept path —
        # ACL + classify + backend pick + pump setup/teardown per req.
        # A/B: warm backend pool OFF (the r5 configuration) then ON (the
        # headline; the delta is the pool's worth). Reference row: 6,511
        # req/s (bench.md:19, its hardware); haproxy row: 10,052.
        from vproxy_tpu.utils.metrics import GlobalInspection

        def _pool_ctr(alias, res):
            return GlobalInspection.get().get_counter(
                "vproxy_lb_pool_total", lb=alias, result=res).value()

        lanes_n = _env_int("HOSTBENCH_LANES", 4)
        from vproxy_tpu.net import vtl as _v
        result["host_uring_probe"] = _v.uring_probe_fields()
        result["host_lanes"] = lanes_n
        variants = [("nopool", 0, 0, "host_tcp_short_nopool_rps")]
        if not lanes_only:
            variants.append(("pool", pool_n, 0, "host_tcp_short_pool_rps"))
        for variant, pool_sz, n_lanes, key in variants:
            # acceptor group == worker group for the short rows: accepts
            # spread over every loop's REUSEPORT listener and sessions
            # are served where they were accepted — one cross-loop hop
            # fewer per connection (measured +12% on the short row)
            lb = TcpLB(f"lb-short-{variant}", elg, elg,
                       "127.0.0.1", 0, ups, protocol="tcp",
                       pool_size=pool_sz, lanes=n_lanes)
            lb.start()
            try:
                # warmup primes the classify jit AND the per-loop pools
                run_client(lb.bind_port, min(conns, 8), 1.0, 1, short=True)
                r = run_client(lb.bind_port, conns, secs, 1, short=True)
                result[key] = r["rps"]
                result[key.replace("_rps", "_errors")] = r["errors"]
                if pool_sz:
                    result["host_pool_size"] = pool_sz
                    for res_ in ("hit", "miss", "stale"):
                        result[f"host_pool_{res_}"] = _pool_ctr(
                            lb.alias, res_)
                flush()
            finally:
                lb.stop()
                lb = None

        # lanes-off vs lanes-on, MEDIAN OF 3 INTERLEAVED reps (the
        # BENCH_r08 generation-swap discipline): on this sandboxed
        # kernel both rows sit inside the serialized-connection-setup
        # ceiling band, and single samples bounce ±15% with machine
        # load — interleaving cancels the drift, the median kills the
        # outlier rep
        if _v.lanes_supported():
            ab: dict = {"off": [], "on": []}
            rep_secs = max(3.0, secs / 2)
            for _rep in range(3):
                for side, n_lanes in (("off", 0), ("on", lanes_n)):
                    lb = TcpLB(f"lb-short-ab-{side}-{_rep}", elg, elg,
                               "127.0.0.1", 0, ups, protocol="tcp",
                               lanes=n_lanes)
                    lb.start()
                    if side == "on" and lb.lanes is None:
                        # engine honesty: a fallen-back LB must never
                        # publish python-accept numbers as a lanes row
                        lb.stop()
                        raise RuntimeError(
                            "lanes failed to come up mid-bench")
                    try:
                        run_client(lb.bind_port, min(conns, 8), 1.0, 1,
                                   short=True)
                        r = run_client(lb.bind_port, conns, rep_secs, 1,
                                       short=True)
                        ab[side].append((r["rps"], r["errors"]))
                        if side == "on" and lb.lanes is not None:
                            # engine honesty: which engine REALLY ran
                            result["host_lane_engine"] = lb.lanes.engine()
                            st = lb.lanes.stat()
                            result["host_lane_stat"] = {
                                k: st.get(k) for k in
                                ("served", "punts", "punt_stale",
                                 "punt_connect_fail", "hit_rate")}
                    finally:
                        lb.stop()
                        lb = None
            med = {s: sorted(x[0] for x in ab[s])[1] for s in ab}
            result["host_tcp_short_lanes_rps"] = med["on"]
            result["host_tcp_short_lanes_off_rps"] = med["off"]
            result["host_tcp_short_lanes_errors"] = sum(
                x[1] for x in ab["on"])
            result["host_tcp_short_lanes_off_errors"] = sum(
                x[1] for x in ab["off"])
            result["host_tcp_short_lanes_reps"] = {
                s: [x[0] for x in ab[s]] for s in ab}
            flush()

        # GIL-contention A/B: one CPU-bound python thread stands in for
        # on-host classify/compile work (a vproxy-tpu node's production
        # state). The python accept path pays the GIL per connection;
        # the C lanes never touch it — this is the displacement win the
        # lanes buy on any kernel, and the headline ratio on sandboxed
        # kernels whose serialized connection setup caps the
        # uncontended row (host_kernel_serialized above).
        if _v.lanes_supported():
            gil_stop = threading.Event()

            def _gil_spin():
                x = 0
                while not gil_stop.is_set():
                    for _ in range(10000):
                        x = (x * 1103515245 + 12345) & 0xFFFFFFFF

            spin = threading.Thread(target=_gil_spin, daemon=True)
            spin.start()
            try:
                for variant, n_lanes, key in (
                        ("gil-nolanes", 0,
                         "host_tcp_short_gil_nolanes_rps"),
                        ("gil-lanes", lanes_n,
                         "host_tcp_short_gil_lanes_rps")):
                    lb = TcpLB(f"lb-short-{variant}", elg, elg,
                               "127.0.0.1", 0, ups, protocol="tcp",
                               lanes=n_lanes)
                    lb.start()
                    if n_lanes and lb.lanes is None:
                        lb.stop()
                        raise RuntimeError(
                            "lanes failed to come up mid-bench (gil row)")
                    try:
                        run_client(lb.bind_port, min(conns, 8), 1.0, 1,
                                   short=True)
                        r = run_client(lb.bind_port, conns,
                                       max(3.0, secs / 2), 1, short=True)
                        result[key] = r["rps"]
                        result[key.replace("_rps", "_errors")] = \
                            r["errors"]
                        flush()
                    finally:
                        lb.stop()
                        lb = None
            finally:
                gil_stop.set()
                spin.join(2)
            if result.get("host_tcp_short_gil_nolanes_rps"):
                result["host_lanes_gil_speedup"] = round(
                    result.get("host_tcp_short_gil_lanes_rps", 0)
                    / result["host_tcp_short_gil_nolanes_rps"], 3)

        # headline = the best configuration measured THIS run; every
        # contender is its own first-class row so the artifact shows
        # which won and by how much on THIS machine
        pool_rps = result.get("host_tcp_short_pool_rps", 0)
        nopool_rps = result.get("host_tcp_short_nopool_rps", 0)
        lanes_rps = result.get("host_tcp_short_lanes_rps", 0)
        best_short = max(pool_rps, nopool_rps, lanes_rps)
        result["host_tcp_short_rps"] = best_short
        result["host_tcp_short_best"] = (
            "lanes" if best_short == lanes_rps and lanes_rps else
            "pool" if best_short == pool_rps and pool_rps else "nopool")
        result["host_short_vs_ref_6511"] = round(best_short / 6511.3, 3)
        result["host_short_vs_haproxy_10052"] = round(
            best_short / 10052.0, 3)
        if nopool_rps and pool_rps:
            result["host_short_pool_speedup"] = round(
                pool_rps / nopool_rps, 3)
        lanes_off = result.get("host_tcp_short_lanes_off_rps", nopool_rps)
        if lanes_rps and lanes_off:
            # the same-run interleaved lanes-on / lanes-off ratio
            # (uncontended; the GIL ratio above is the contended one)
            result["host_lanes_speedup"] = round(lanes_rps / lanes_off, 3)
        if result.get("host_direct_short_rps"):
            # the machine-normalized short row: LB cycle vs the kernel's
            # own no-LB connect/accept cycle on the same run
            result["host_short_vs_ceiling"] = round(
                best_short / result["host_direct_short_rps"], 3)
        flush()

        # TLS-terminating protocol=tcp: the C-side OpenSSL splice pump
        # (SSLWrapRingBuffer-at-engine-speed analog). Contract: within
        # 2x of the plaintext splice rate.
        from vproxy_tpu.net import vtl as _vtl
        if not lanes_only and _vtl.tls_available():
            import tempfile
            d = tempfile.mkdtemp(prefix="hostbench-tls-")
            cert, keyf = os.path.join(d, "c.crt"), os.path.join(d, "c.key")
            subprocess.run(
                ["openssl", "req", "-x509", "-newkey", "rsa:2048",
                 "-nodes", "-keyout", keyf, "-out", cert, "-days", "2",
                 "-subj", "/CN=bench.example.com"],
                check=True, capture_output=True)
            from vproxy_tpu.components.certkey import CertKey
            ck = CertKey("bench", cert, keyf)
            lb = TcpLB("lb-tls", acceptor, elg, "127.0.0.1", 0, ups,
                       protocol="tcp", cert_keys=[ck])
            lb.start()
            try:
                run_client(lb.bind_port, min(conns, 4), 1.0, 1,
                           tls_sni="bench.example.com")
                r = run_client(lb.bind_port, conns, secs, pipeline,
                               tls_sni="bench.example.com")
                result["host_tls_rps"] = r["rps"]
                result["host_tls_errors"] = r["errors"]
                if result.get("host_tcp_rps"):
                    result["host_tls_vs_plain"] = round(
                        r["rps"] / result["host_tcp_rps"], 3)
                flush()
            finally:
                lb.stop()
                lb = None
        # vs the reference's published wrk numbers on ITS hardware —
        # context, not a same-machine comparison
        if result.get("host_tcp_rps"):
            result["host_tcp_vs_ref_173k"] = round(
                result["host_tcp_rps"] / 173000.0, 3)
        if result.get("host_http_rps"):
            result["host_http_vs_ref_112k"] = round(
                result["host_http_rps"] / 112000.0, 3)

        # /metrics snapshot: the accept-path span histograms
        # (vproxy_accept_stage_us{stage=...}), the classify latency
        # histogram, and the native pump counters accumulated over the
        # load above — the latency contract IN the artifact, sourced
        # from the same surface production scrapes
        from vproxy_tpu.utils.metrics import GlobalInspection
        snap = GlobalInspection.get().bench_snapshot()
        result["host_metrics"] = {
            k: v for k, v in snap.items()
            if k.startswith(("vproxy_accept_stage_us",
                             "vproxy_classify_latency_us",
                             "vproxy_pump_", "vproxy_loop_"))}
        acc = snap.get("vproxy_accept_stage_us.total")
        if isinstance(acc, dict):
            for q in ("p50", "p99", "p999"):
                result[f"host_accept_{q}_us"] = acc.get(q)
        flush()
    finally:
        if lb is not None:
            try:
                lb.stop()
            except Exception:
                pass
        for g in groups:
            try:
                g.close()
            except Exception:
                pass
        for h in (elg, acceptor):
            if h is not None:
                try:
                    h.close()
                except Exception:
                    pass
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(5)
            except subprocess.TimeoutExpired:
                p.kill()

    print(json.dumps(result))
    flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
