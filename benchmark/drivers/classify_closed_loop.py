"""Closed-loop driver at the ClassifyService boundary.

Callers of the service are event loops that each wait for a verdict
before a connection, query or frame proceeds, so `outstanding` queries
are in flight at all times: each delivered verdict frees one slot, and a
submitter thread fills it with the next query of the seeded sequence at
once. Callbacks only record the verdict and hand the slot back; they do
not resubmit on the dispatcher thread. Latency runs from just before
`submit_*` to the callback. Nothing is sized by a rate: every record is
appended as it happens, so a faster program needs no edit here.

Traffic parameters (a `traffic/<name>.json`):
    kinds          query kinds by pool rank, rank r asks kinds[r % len]
    outstanding    W, queries in flight
    submitters     submitter threads (event loops that share the service)
    pool           distinct queries; rank r is drawn with P ~ 1/(r+1)**zipf_s
    zipf_s
    ramp_seconds   driven but not measured, before the window opens
"""
from __future__ import annotations

import itertools
import queue
from array import array
import threading
import time

import numpy as np

import gen

DRAIN_S = 60.0      # wait this long past the close for late verdicts
SEQUENCE = 1 << 20  # draws in the seeded sequence of pool ranks; it wraps


class Plan:
    def __init__(self, dep, traffic: dict, seed: int, seconds: float):
        self.traffic = traffic
        self.pool = dep.pool(traffic, seed)            # [(kind, query)]
        self.seq = gen.zipf_sequence(len(self.pool), SEQUENCE,
                                     traffic["zipf_s"], seed)


def warm(dep, plan: Plan, buckets: list) -> int:
    calls = 0
    for kind in dict.fromkeys(plan.traffic["kinds"]):
        qs = [q for k, q in plan.pool if k == kind]
        calls += dep.warm(kind, qs, buckets)
    return calls


class Window:
    """What the loop recorded, by global sequence number k."""

    def __init__(self):
        self.n = 0                  # queries submitted
        self.rank = None            # int32 [n] pool rank of query k
        self.t_sub = None           # int64 [n] ns
        self.t_done = None          # int64 [n] ns, 0 = never delivered
        self.got = None             # int32 [n, 2] verdict, pick (gen.NOPICK)
        self.t_open = self.t_close = 0   # ns, the measured window
        self.undelivered = 0
        self.counters_open: dict = {}
        self.counters_close: dict = {}


def drive(dep, svc, plan: Plan, seconds: float, read_counters,
          on_open=None, on_tick=None, on_close=None,
          instrument=None) -> Window:
    """Run ramp + window + drain; -> Window. on_open/on_close are called
    at the edges of the measured window and on_tick about four times a
    second inside it (the traced run starts and stops the profiler
    there). instrument: the traced run's trace-id sampler."""
    tr = plan.traffic
    W = tr["outstanding"]
    seq = array("i", plan.seq.tobytes())
    nseq = len(seq)
    # per pool rank: the submit call, and whether it answers (verdict, pick)
    calls = [(dep.submit_call(k, svc, q), dep.has_pick(k))
             for k, q in plan.pool]
    # verdicts as delivered, in arrays of machine integers: nothing is
    # allocated per record that the garbage collector tracks, and a full
    # collection does not walk the millions of records of a window (as
    # Python lists they added ~10 ns an entry to every full collection,
    # which grew the program's pauses as the run went on)
    def ints():
        return array("q")
    dk, dt, dv = ints(), ints(), ints()             # k, t_done, verdict
    pk, pt, pv, pp = ints(), ints(), ints(), ints()  # the same with a pick
    free: queue.SimpleQueue = queue.SimpleQueue()
    counter = itertools.count()
    clock = time.perf_counter_ns
    state = {"stop": False}

    class Slot:
        __slots__ = ("k",)

        def done(self, idx, _payload):
            dk.append(self.k)
            dt.append(clock())
            dv.append(idx)
            free.put(self)

        def done2(self, verdict, pick, _payload):
            pk.append(self.k)
            pt.append(clock())
            pv.append(verdict)
            pp.append(pick)
            free.put(self)

    for _ in range(W):
        free.put(Slot())
    submitted: list = []     # one (ks, ts) pair of lists per submitter

    def submitter() -> None:
        ks, ts = ints(), ints()
        submitted.append((ks, ts))
        while not state["stop"]:
            try:
                slot = free.get(timeout=0.05)
            except queue.Empty:
                continue
            if state["stop"]:
                free.put(slot)
                return
            k = next(counter)
            slot.k = k
            call, pick = calls[seq[k % nseq]]
            cb = slot.done2 if pick else slot.done
            ks.append(k)
            if instrument is not None:
                tid = instrument.sample()
                if tid:
                    ts.append(clock())
                    with instrument.bind(tid):
                        call(cb)
                    continue
            ts.append(clock())
            call(cb)

    threads = [threading.Thread(target=submitter, name=f"submit-{i}",
                                daemon=True)
               for i in range(tr["submitters"])]
    win = Window()
    for t in threads:
        t.start()
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
    state["stop"] = True
    if on_close is not None:
        on_close()
    for t in threads:
        t.join(5.0)
    # late verdicts are late, not wrong: wait for every slot to come home
    t_end = time.monotonic() + DRAIN_S
    while free.qsize() < W and time.monotonic() < t_end:
        time.sleep(0.01)
    win.undelivered = W - free.qsize()
    n = win.n = sum(len(ks) for ks, _ts in submitted)
    win.rank = plan.seq[np.arange(n) % nseq]
    win.t_sub = np.zeros(n, np.int64)
    def arr(a, m):      # a copy: a straggler may still append to `a`
        return np.frombuffer(a[:m], np.int64)
    for ks, ts in submitted:
        win.t_sub[arr(ks, len(ts))] = arr(ts, len(ts))
    win.t_done = np.zeros(n, np.int64)
    win.got = np.full((n, 2), gen.NOPICK, np.int32)
    m = min(len(dk), len(dt), len(dv))      # a straggler may still append
    win.t_done[arr(dk, m)] = arr(dt, m)
    win.got[arr(dk, m), 0] = arr(dv, m)
    m = min(len(pk), len(pt), len(pv), len(pp))
    win.t_done[arr(pk, m)] = arr(pt, m)
    win.got[arr(pk, m), 0] = arr(pv, m)
    win.got[arr(pk, m), 1] = arr(pp, m)
    return win


def end_to_end(win: Window) -> dict:
    """The window's user-visible numbers: a rate over all the verdicts
    delivered inside the window and all of its seconds, and the latency
    of every query submitted inside it (a late verdict counts with its
    wait; one that never came counts as the drain limit)."""
    secs = (win.t_close - win.t_open) / 1e9
    inside = (win.t_done >= win.t_open) & (win.t_done < win.t_close)
    lat_ms = latencies_ms(win)
    return {
        "matches_per_s": float(inside.sum() / secs),
        "classify_p99_ms": float(np.percentile(lat_ms, 99)),
        "_window_s": secs,
        "_delivered_in_window": int(inside.sum()),
        "_latency_samples": int(len(lat_ms)),
    }


def latencies_ms(win: Window) -> np.ndarray:
    """Submit -> callback of every query submitted inside the window."""
    sub_in = (win.t_sub >= win.t_open) & (win.t_sub < win.t_close)
    done = np.where(win.t_done > 0, win.t_done,
                    win.t_close + int(DRAIN_S * 1e9))
    return (done[sub_in] - win.t_sub[sub_in]) / 1e6


def rate_by_second(win: Window) -> list:
    """Verdicts delivered in each whole second of the window."""
    secs = int((win.t_close - win.t_open) // 1_000_000_000)
    t = win.t_done[(win.t_done >= win.t_open) & (win.t_done < win.t_close)]
    if secs < 1:
        return []
    return np.bincount(((t - win.t_open) // 1_000_000_000).astype(np.int64),
                       minlength=secs)[:secs].tolist()
