"""Burst driver at the switch's own entry: `route_lookup_burst`.

A Switch is one event loop. A wake drains up to `RECV_BURST` datagrams
(`vswitch/switch.py`), and `vswitch/stack.py _route_flush` hands the
burst's deferred route lookups, whatever VPCs they arrived in, to
`vswitch.network.route_lookup_burst([(VpcNetwork, dst), ...])`: ONE
synchronous `CidrTableSet.match` on the loop's own thread — no
ClassifyService, no `_Req`, no dispatcher, no second batch in flight.
This driver is that loop with the sockets left out: it takes the next
`burst` ranks of the seeded sequence, calls the program's function,
keeps what it returned, goes on. A lookup's latency is its burst's
call, start to return. The RouteRules are mapped to table indices after
the window: nothing happens per lookup inside the timed loop that the
switch's own loop would not do.

This driver brings all four of run.py's optional hooks (README.md):
`service`, `control_service`, `CHECKS` + `checks`, `counters`; what a
traced run wraps is named by the service (`bench_spans`).

Traffic parameters (a `traffic/<name>.json`):
    builder           the builder that installs the deployment as
                      VpcNetworks (its `switch`: program.SwitchRoutes)
    kinds             ["route"]
    burst             lookups a call (Switch.RECV_BURST)
    bursts_in_flight  1, loops 1: the call is synchronous, the loop one
    outstanding       = burst: the pad bucket the set-up warms
    pool, zipf_s, miss_every, ramp_seconds   as classify_closed_loop
"""
from __future__ import annotations

import itertools
import threading
import time
import traceback
from array import array

import numpy as np

import gen
import drivers.classify_closed_loop as closed_loop
from drivers.classify_closed_loop import (  # noqa: F401  (the readers' API)
    DRAIN_S, Plan, Window, latencies_ms, rate_by_second)

# what `checks` returns, and what each is for run.py: one has to be the
# proof that the device served ("device"); "host" counts lookups
# answered on the host, which `failed` in the result line adds up
CHECKS = {
    "bursts_not_one_device_launch": "device",
    "answered_on_host": "host",
    "route_set_backend_not_jax": "other",
}


def service(dep, plan: Plan):
    """The switch's routing state the builder installed."""
    if getattr(dep, "switch", None) is None:
        raise ValueError(f"{type(dep).__name__} installs no VpcNetworks: "
                         f"the traffic file has to name a builder that does")
    return dep.switch


class Control:
    """The control through the burst entry: the plain reference with the
    route guarantee broken (`dep.controls["route"]`: every lookup
    answered from the next VPC's table), keyed by the destination
    alone. It claims one launch a burst, so only the verdicts fail, and
    it takes a burst about as long as the device path does (a sleep):
    the control runs at the cell's own load, not at a dict's."""

    BURST_S = 0.004     # the program's call at 1,024 rows (PERF.md §5)

    def __init__(self, dep, plan: Plan, seed: int):
        self.what = ", ".join(f"{k}: {dep.controls[k]}"
                              for k in dict.fromkeys(plan.traffic["kinds"]))
        broken = dep.answers(plan.pool, control=True, seed=seed)[:, 0]
        self._answer = {q[0]: int(a)
                        for a, (_k, q) in zip(broken.tolist(), plan.pool)}
        self._bursts = 0

    def lookups(self, pool: list) -> list:
        return [(q[2], q[0]) for _k, q in pool]

    def route_lookup_burst(self, lookups: list) -> list:
        self._bursts += 1
        answer = self._answer
        time.sleep(self.BURST_S)
        return [answer[a] for _vpc, a in lookups]

    def verdicts(self, results: list, vpcs: np.ndarray) -> np.ndarray:
        return np.array(results, np.int32)

    def counters(self) -> dict:
        return {"launches": self._bursts, "host_arrays": self._bursts,
                "host_lookups": 0, "backend": "jax", "generation": 0}

    def close(self) -> None:
        pass


def control_service(dep, plan: Plan, seed: int) -> Control:
    return Control(dep, plan, seed)


def counters(svc) -> dict:
    return svc.counters()


def warm(dep, plan: Plan, buckets: list) -> int:
    """The one shape the window forms: a burst of `burst` rows through
    the entry the window drives (twice: compile or load, then run)."""
    svc = service(dep, plan)
    per_rank = svc.lookups(plan.pool)
    n = plan.traffic["burst"]
    part = (per_rank * (n // len(per_rank) + 1))[:n]
    for _ in range(2):
        svc.route_lookup_burst(part)
    return 2


def drive(dep, svc, plan: Plan, seconds: float, read_counters,
          on_open=None, on_tick=None, on_close=None,
          instrument=None) -> Window:
    """Run ramp + window + drain; -> Window, one record a lookup (each
    carries its burst's start and end) plus the bursts themselves
    (`burst_t0`, `burst_t1`, `bursts`, `counters_start/end`)."""
    tr = plan.traffic
    size = tr["burst"]
    if tr["bursts_in_flight"] != 1 or tr["loops"] != 1:
        raise ValueError("route_lookup_burst is synchronous and a Switch "
                         "is one loop: bursts_in_flight and loops are 1")
    per_rank = svc.lookups(plan.pool)
    seq = plan.seq.tolist()
    nseq = len(seq)
    seq += seq[:size]               # a burst may straddle the wrap
    burst = svc.route_lookup_burst  # the program's function itself
    t0s, t1s = array("q"), array("q")
    results: list = []              # one list a burst, as returned
    clock = time.perf_counter_ns
    state = {"stop": False, "error": "", "mark": ""}
    marks: dict = {}    # counters the loop read between two bursts

    def progress() -> dict:
        c = read_counters()
        done = len(t1s)
        c.update(bursts=done, dispatches=c["launches"],
                 device_queries=done * size - c["host_lookups"])
        return c

    def loop() -> None:
        try:
            for b in itertools.count():
                if state["mark"]:   # no burst in flight: launches and
                    marks[state["mark"]] = progress()   # bursts agree
                    state["mark"] = ""
                if state["stop"]:
                    return
                off = (b * size) % nseq
                lookups = [per_rank[r] for r in seq[off:off + size]]
                t0s.append(clock())
                res = burst(lookups)
                t1s.append(clock())
                results.append(res)
        except Exception:   # the burst in flight stays undelivered
            state["error"] = traceback.format_exc()

    win = Window()
    win.counters_start = progress()
    thread = threading.Thread(target=loop, name="switch-loop", daemon=True)
    thread.start()
    time.sleep(tr["ramp_seconds"])
    if on_open is not None:
        on_open()
    win.counters_open = progress()   # stands if the loop has died
    state["mark"] = "open"
    win.t_open = clock()
    deadline = win.t_open + int(seconds * 1e9)
    while clock() < deadline:
        time.sleep(min(0.25, max(0.0, (deadline - clock()) / 1e9)))
        if on_tick is not None:
            on_tick()
    win.t_close = clock()
    win.counters_close = progress()
    state["mark"] = "close"
    state["stop"] = True
    if on_close is not None:
        on_close()
    thread.join(DRAIN_S)    # the burst in flight is late, not wrong
    started, done = len(t0s), min(len(t1s), len(results))
    win.counters_open = marks.get("open", win.counters_open)
    win.counters_close = marks.get("close", win.counters_close)
    win.counters_end = progress()
    win.error = state["error"]
    win.bursts = started
    win.burst_t0 = np.frombuffer(t0s[:done], np.int64)
    win.burst_t1 = np.frombuffer(t1s[:done], np.int64)
    n = win.n = started * size
    win.undelivered = (started - done) * size
    win.rank = plan.seq[np.arange(n) % nseq]
    win.t_sub = np.repeat(np.frombuffer(t0s[:started], np.int64), size)
    win.t_done = np.zeros(n, np.int64)
    win.t_done[:done * size] = np.repeat(win.burst_t1, size)
    win.got = np.full((n, 2), gen.NOPICK, np.int32)
    short = [len(r) for r in results[:done] if len(r) != size]
    if short:
        raise RuntimeError(f"a burst of {size} lookups came back with "
                           f"{short[:5]} answers")
    vpcs = np.array([q[2] for _k, q in plan.pool], np.int32)
    win.got[:done * size, 0] = svc.verdicts(
        list(itertools.chain.from_iterable(results[:done])),
        vpcs[win.rank[:done * size]])
    return win


def end_to_end(win: Window) -> dict:
    """The closed loop's numbers, lookup by lookup (a lookup's latency
    is its burst's call). A loop that died before the window opened
    submitted nothing inside it: its latency reads as the drain limit,
    as an answer that never came does."""
    if not len(latencies_ms(win)):
        secs = (win.t_close - win.t_open) / 1e9
        return {"matches_per_s": 0.0, "classify_p99_ms": DRAIN_S * 1e3,
                "_window_s": secs, "_delivered_in_window": 0,
                "_latency_samples": 0}
    return closed_loop.end_to_end(win)


def checks(dep, svc, plan: Plan, win: Window) -> dict:
    """The driver's own proof that the device served, over ramp + window
    + drain: every burst issued was ONE device launch (whatever VPCs it
    named: a burst split by VPC or answered by the host's scan moves
    the count), the set answered nothing on the host, and its backend
    is the device's."""
    a, b = win.counters_start, win.counters_end
    return {
        "bursts_not_one_device_launch":
            [abs((b["launches"] - a["launches"]) - win.bursts), 0],
        "answered_on_host": [b["host_lookups"] - a["host_lookups"], 0],
        "route_set_backend_not_jax": [int(b["backend"] != "jax"), 0],
    }
