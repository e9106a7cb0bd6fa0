"""Closed-loop driver at the tcp-lb's own entry: HTTP/2 streams.

The served process holds what an HTTP/2 LB node holds: the
ClassifyService singleton (`ClassifyService.get()`) in the
configuration's mode, and a `TcpLB(protocol="h2")` on an event loop of
its own bound to 127.0.0.1, its backend the builder's `Upstream`. Its
h2 processor parks each new stream until `Upstream.next_async` names
the backend: one classify + pick submit a stream, one device launch a
micro-batch. Two processes of their own, plain Python: the backends
(`drivers/h2_backends.py`, one h2c listener a member, inherited from
this process) and the clients (`drivers/h2_client.py`: `connections`
h2c connections, each from its own source address, `streams` open on
each, a stream that ends starting the next at once). A stream's latency
runs from its HEADERS sent to its END_STREAM received, both on
CLOCK_MONOTONIC, which the processes share with run.py's window. Each
response names the backend that served it (`x-backend`) and echoes the
X-Forwarded-For the backend saw, and its body has to be the page for
the stream's own request (the backend writes the request's :authority
into it: BODY_WRONG where it is cut short or another request's); the
backend is mapped here to what the
reference answers (reference_h2.py): (group, index in that group's
healthy members), or a value the reference never gives (DOWN_MEMBER: a
member that is down; UNKNOWN: no such backend; RESET: the stream was
reset). A stream that never ended is undelivered.

This driver brings all four of run.py's optional hooks (README.md):
`service`, `control_service`, `CHECKS` + `checks`, `counters`; what a
traced run wraps is named by the service (`bench_spans`).

Traffic parameters (a `traffic/<name>.json`):
    kinds          ["h2"]
    connections    C, client connections, one source address each
    outstanding    streams in flight, C x streams a connection
    pool           distinct (authority, source) entries; entry j is
                   connection j % C's
    zipf_s         a connection draws its own entries, rank r with
                   P ~ 1/(r+1)**zipf_s
    miss_every     1 entry in miss_every aims at a host no rule holds
    ramp_seconds   driven but not measured, before the window opens;
                   each connection's row starts with one entry a group
                   its entries aim at, so the backend connections it
                   uses are opened in the ramp
"""
from __future__ import annotations

import atexit
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

import gen
from drivers import h2_client as wire
from drivers.classify_closed_loop import (  # noqa: F401  (the readers' API)
    Window, latencies_ms, rate_by_second, warm)
# the closed loop's numbers (`matches_per_s`: responses received in the
# window a second; `classify_p99_ms`: HEADERS sent to END_STREAM
# received), a window that finished nothing reading as the drain limit
from drivers.dns_udp_closed_loop import end_to_end  # noqa: F401

CHECKS = {
    "selects_not_on_device": "device",
    "answered_on_host": "host",
    "streams_reset": "other",
    "xff_wrong": "other",
    "bodies_wrong": "other",
}
WHERES = ("device_pick", "group_next", "fallback", "refused")
RESET, DOWN_MEMBER, UNKNOWN, MALFORMED = -5, -6, -8, -7
HERE = os.path.dirname(os.path.abspath(__file__))
CLIENT = os.path.join(HERE, "h2_client.py")
BACKENDS = os.path.join(HERE, "h2_backends.py")
CLIENT_DRAIN_S = 10.0
SEQUENCE = 1 << 16      # draws a connection; it wraps

_children: list = []    # every process started here, killed at exit


def _kill_children() -> None:
    for p in _children:
        if p.poll() is None:
            p.kill()
            p.wait()
    _children.clear()


atexit.register(_kill_children)


def _spawn(args: list, **kw) -> subprocess.Popen:
    p = subprocess.Popen([sys.executable] + args, stdin=subprocess.PIPE,
                         stdout=subprocess.PIPE, text=True, **kw)
    _children.append(p)
    return p


def _stop(proc, timeout: float) -> str:
    """Wait for a child; SIGTERM it (it still writes what it has), then
    kill it. -> what went wrong, "" for nothing."""
    try:
        proc.wait(timeout)
        return "" if proc.returncode == 0 else \
            f"{os.path.basename(proc.args[1])} exited {proc.returncode}"
    except subprocess.TimeoutExpired:
        proc.terminate()
    try:
        proc.wait(30)
        return f"{os.path.basename(proc.args[1])} did not end within " \
               f"{timeout:.0f} s: terminated"
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        return f"{os.path.basename(proc.args[1])} did not end: killed"


class Plan:
    """The pool, and each connection's row of pool ranks: one entry a
    group its entries aim at (the ramp opens those backends), then a
    Zipf draw over its own entries."""

    def __init__(self, dep, traffic: dict, seed: int, seconds: float):
        self.traffic = traffic
        self.pool = dep.pool(traffic, seed)
        C = traffic["connections"]
        own = [np.arange(c, len(self.pool), C) for c in range(C)]
        draws = gen.zipf_sequence(len(own[0]), SEQUENCE * C,
                                  traffic["zipf_s"], seed).reshape(C, -1)
        rows = []
        for c in range(C):
            ranks = own[c][draws[c] % len(own[c])]
            first = {}
            for r in own[c].tolist():
                first.setdefault(dep.pool_group[r], r)
            head = np.array(sorted(first.values()), np.int32)
            rows.append(np.concatenate([head, ranks])[:SEQUENCE])
        self.seq = np.stack(rows).astype(np.int32)     # [C, SEQUENCE]

    def streams(self) -> int:
        return self.traffic["outstanding"] // self.traffic["connections"]


class H2Plane:
    """An HTTP/2 LB node: the service singleton, the tcp-lb on its own
    loop, the backends process."""

    def __init__(self, dep, traffic: dict):
        from vproxy_tpu.components.elgroup import EventLoopGroup
        from vproxy_tpu.components.tcplb import TcpLB
        from vproxy_tpu.rules.service import ClassifyService
        ClassifyService.reset()     # a fresh one, in the configured mode
        self.classify = ClassifyService.get()
        self.dep = dep
        self.tmp = tempfile.mkdtemp(prefix="h2_backends_")
        self.accepts_path = os.path.join(self.tmp, "accepts")
        fds = os.path.join(self.tmp, "fds")
        with open(fds, "w") as f:
            f.write("".join(f"{s.fileno()} {ident}\n" for s, ident
                            in zip(dep.listeners, dep.idents)))
        self.backends = _spawn([BACKENDS, "serve", "--fds", fds,
                                "--accepts", self.accepts_path],
                               pass_fds=[s.fileno() for s in dep.listeners])
        ready = self.backends.stdout.readline()
        if not ready.startswith("ready"):
            raise RuntimeError(f"the backends did not start: {ready!r}")
        for s in dep.listeners:     # the backends process holds them now
            s.close()
        self.elg = EventLoopGroup("h2lb", 1)
        self.lb = TcpLB("h2lb", self.elg, self.elg, "127.0.0.1", 0,
                        dep.upstream, protocol="h2")
        self.lb.start()
        self.port = self.lb.bind_port
        self.backend_error = ""

    def counters(self) -> dict:
        from vproxy_tpu.rules import engine as E
        from vproxy_tpu.utils.metrics import GlobalInspection
        gi = GlobalInspection.get()
        st = self.classify.stats
        d = {"queries": st.queries, "dispatches": st.dispatches,
             "device_queries": st.device_queries,
             "oracle_queries": st.oracle_queries,
             "failovers": st.failovers, "last_failover": st.last_failover,
             "launches": E.dispatch_launches_total(),
             "parked_frames": gi.get_counter(
                 "vproxy_l7_parked_frames_total").value()}
        for w in WHERES:
            d["l7_" + w] = gi.get_counter("vproxy_upstream_selects_total",
                                          where=w).value()
        d["l7_selects"] = sum(d["l7_" + w] for w in WHERES)
        return d

    def bench_spans(self) -> list:
        cs = self.classify
        return [(cs, "_device_submit", "bench/submit"),
                (cs, "_finish_inflight", "bench/readback_deliver"),
                (cs, "_deliver", "bench/deliver")]

    def accepts(self) -> np.ndarray:
        """The backends' accept times, once they have ended."""
        try:
            return np.fromfile(self.accepts_path, "<i8")
        except (OSError, ValueError):
            return np.zeros(0, np.int64)

    def stop_backends(self) -> None:
        if self.backends.poll() is None:
            self.backends.stdin.close()     # ends it; it writes its accepts
            self.backend_error = _stop(self.backends, 30)

    def close(self) -> None:
        from vproxy_tpu.rules.service import ClassifyService
        self.lb.stop()
        self.elg.close()
        self.stop_backends()
        self.dep.close()
        ClassifyService.reset()
        shutil.rmtree(self.tmp, ignore_errors=True)


def service(dep, plan: Plan) -> H2Plane:
    if getattr(dep, "upstream", None) is None:
        raise ValueError(f"{type(dep).__name__} installs no Upstream: the "
                         f"configuration has to name a builder that does")
    return H2Plane(dep, plan.traffic)


class Control:
    """The control through the same entry: an h2c responder on its own
    port, in this process, that answers every stream from the plain
    reference with the configuration's guarantee broken
    (`dep.controls["h2"]`: a matched group's pick from the next group's
    table, answered as that group's backend) and echoes the client's
    address as X-Forwarded-For. It claims that the device answered every
    stream, so only the answers fail."""

    def __init__(self, dep, plan: Plan, seed: int):
        import socket

        from drivers import h2_backends
        self.what = f"h2: {dep.controls['h2']}"
        broken = dep.answers(plan.pool, control=True, seed=seed)
        groups = dep.sizes["groups"]
        names = {(g, i): n.encode() for g, ms in enumerate(dep.healthy)
                 for i, n in enumerate(ms)}
        names.update({(groups, i): n.encode()
                      for i, n in enumerate(dep.default)})
        C = plan.traffic["connections"]
        self._answer = {}
        for r, (_k, q) in enumerate(plan.pool):
            src = ".".join(map(str, q[3])).encode()
            self._answer[(q[0].encode(), src)] = names[tuple(broken[r])]
        self.answered = 0
        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        sock.listen(C)
        self.port = sock.getsockname()[1]
        self.srv = h2_backends.Server([(sock, "control")], self._respond)
        self._thread = threading.Thread(target=self.srv.run, daemon=True,
                                        name="h2-control")
        self._thread.start()
        self.dep = dep

    def _respond(self, conn, headers: list) -> tuple:
        auth = next((v for k, v in headers if k == b":authority"), b"")
        src = conn.peer.encode()
        self.answered += 1
        return self._answer.get((auth, src), b"none"), src

    def counters(self) -> dict:
        n = self.answered
        d = {"queries": n, "dispatches": n, "device_queries": n,
             "oracle_queries": 0, "failovers": 0, "last_failover": "",
             "launches": n, "parked_frames": 0, "l7_selects": n}
        d.update({"l7_" + w: 0 for w in WHERES})
        d["l7_device_pick"] = n
        return d

    def accepts(self) -> np.ndarray:
        return np.zeros(0, np.int64)

    def stop_backends(self) -> None:
        pass

    def close(self) -> None:
        self.srv.stop = True
        self._thread.join(5.0)
        self.srv.close()
        self.dep.close()


def control_service(dep, plan: Plan, seed: int) -> Control:
    return Control(dep, plan, seed)


def counters(svc) -> dict:
    return svc.counters()


def drive(dep, svc, plan: Plan, seconds: float, read_counters,
          on_open=None, on_tick=None, on_close=None,
          instrument=None) -> Window:
    """Run ramp + window + drain; -> Window (`client_flags`: each
    stream's RESET / XFF_WRONG / NOT_200 / BODY_WRONG bits; `backend_connects`:
    connections the backends accepted inside the window)."""
    tr = plan.traffic
    C = tr["connections"]
    clock = time.perf_counter_ns
    tmp = tempfile.mkdtemp(prefix="h2_client_")
    pool, seqf, ids, out = (os.path.join(tmp, x)
                            for x in ("pool", "seq", "ids", "out"))
    with open(pool, "w") as f:
        f.write("".join(f"{q[0]}\n" for _k, q in plan.pool))
    with open(ids, "w") as f:
        f.write("".join(f"{n}\n" for n in dep.idents))
    plan.seq.astype("<i4").tofile(seqf)
    win = Window()
    win.error = ""
    win.counters_start = read_counters()
    proc = _spawn([CLIENT, "client", "--server", f"127.0.0.1:{svc.port}",
                   "--sources", ",".join(
                       ".".join(map(str, plan.pool[c][1][3]))
                       for c in range(C)),
                   "--streams", str(plan.streams()), "--pool", pool,
                   "--seq", seqf, "--idents", ids, "--out", out,
                   "--drain", str(CLIENT_DRAIN_S)])
    try:
        ready = proc.stdout.readline()
        if not ready.startswith("ready"):
            raise RuntimeError(f"the client did not start: {ready!r}")
        time.sleep(tr["ramp_seconds"])
        if on_open is not None:
            on_open()
        win.counters_open = read_counters()
        win.t_open = clock()
        deadline = win.t_open + int(seconds * 1e9)
        while clock() < deadline:
            time.sleep(min(0.25, max(0.0, (deadline - clock()) / 1e9)))
            if on_tick is not None:
                on_tick()
        win.t_close = clock()
        win.counters_close = read_counters()
        proc.stdin.write(f"until {win.t_close}\n")
        proc.stdin.flush()
        if on_close is not None:
            on_close()
        win.error = _stop(proc, CLIENT_DRAIN_S + 60)
        res = wire.read_results(out)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        shutil.rmtree(tmp, ignore_errors=True)
    win.counters_end = read_counters()
    svc.stop_backends()
    acc = svc.accepts()
    win.backend_connects = int(((acc >= win.t_open)
                                & (acc < win.t_close)).sum())
    print(f"backend connections accepted: {len(acc)}, "
          f"{win.backend_connects} inside the window", flush=True)
    return window(dep, win, res)


def window(dep, win: Window, res: dict) -> Window:
    """The client's records -> the run's Window, each response's backend
    mapped to what the reference answers (module doc)."""
    n = win.n = res["n"]
    win.rank = np.frombuffer(res["rank"], np.int32).copy()
    win.t_sub = np.frombuffer(res["t_sub"], np.int64).copy()
    win.t_done = np.frombuffer(res["t_done"], np.int64).copy()
    win.undelivered = int((win.t_done == 0).sum())
    win.client_flags = np.frombuffer(res["flags"], np.int8).copy()
    ident = np.frombuffer(res["ident"], np.int32)
    table = np.array([a if a is not None else (DOWN_MEMBER, DOWN_MEMBER)
                      for a in dep.answer_of] + [(UNKNOWN, UNKNOWN),
                                                 (MALFORMED, MALFORMED)],
                     np.int32).reshape(-1, 2)
    # ident -1 (no x-backend) -> MALFORMED row, -2 (unknown) -> UNKNOWN
    at = np.where(ident >= 0, ident,
                  np.where(ident == wire.UNKNOWN_IDENT, len(dep.answer_of),
                           len(dep.answer_of) + 1))
    win.got = table[at] if n else np.zeros((0, 2), np.int32)
    win.got[(win.client_flags & wire.NOT_200) != 0] = MALFORMED
    win.got[(win.client_flags & wire.RESET) != 0] = RESET
    return win


def checks(dep, svc, plan: Plan, win: Window) -> dict:
    """The driver's own proof that the device served, over ramp + window
    + drain: every stream's select rode a device lookup (the selects the
    tcp-lb counted equal the service's device lookups, and the window
    had some), none was answered on the host (the oracle, a failover, a
    matched group's own `next`); no stream was reset (a refused select
    included); every X-Forwarded-For the backends saw was the client's
    address; every response's body was its own request's, whole."""
    a, b = win.counters_start, win.counters_end
    o, c = win.counters_open, win.counters_close

    def d(key, x=a, y=b):
        return y[key] - x[key]
    none_in_window = int(d("device_queries", o, c) <= 0)
    flags = win.client_flags
    to_default = int((dep.answers(plan.pool)[win.rank, 0]
                      == dep.sizes["groups"]).sum())
    print(f"selects by what answered (ramp+window+drain): "
          f"{ {w: d('l7_' + w) for w in WHERES} }; the reference sends "
          f"{to_default} of the {win.n} streams to the default group",
          flush=True)
    return {
        "selects_not_on_device":
            [abs(d("l7_selects") - d("device_queries")) + none_in_window, 0],
        "answered_on_host": [d("oracle_queries") + d("failovers")
                             + d("l7_group_next"), 0],
        "streams_reset": [int(((flags & wire.RESET) != 0).sum())
                          + d("l7_refused"), 0],
        "xff_wrong": [int(((flags & wire.XFF_WRONG) != 0).sum()), 0],
        "bodies_wrong": [int(((flags & wire.BODY_WRONG) != 0).sum()), 0],
    }
