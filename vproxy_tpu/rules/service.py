"""ClassifyService — the cross-connection micro-batching dispatch queue.

THE north-star mechanism (BASELINE.json): data-plane code (TcpLB hint
classify, SecurityGroup ACL gates, DNS qname lookup, switch routing)
never dispatches the device per connection; it enqueues a query with a
callback and, in device mode, the service coalesces everything that
arrives while the previous device batch is in flight into ONE dispatch
("natural batching": the dispatch latency itself is the batch window,
so the queue adapts from batch=1 at idle to hundreds under load with no
timer).

This replaces the reference's per-connection linear scans
(Upstream.searchForGroup Upstream.java:187-198, SecurityGroup.allow
SecurityGroup.java:30-45, RouteTable.lookup RouteTable.java:44) with a
shared per-process batching front to the compiled device tables.

Dispatch path (mode = VPROXY_TPU_CLASSIFY, default "auto"), one rule a
mode:

* "auto"   — every query is answered INLINE on the submitting thread
             from the snapshot's O(probes) host index (rules/index.py —
             exact, ~2-10us, winner bit-for-bit vs the oracle): no
             dispatcher-thread hop, no device round trip. This is THE
             accept path — what makes the BASELINE p99 < 50us
             accept-path contract meetable. No query reaches the device,
             and the dispatcher thread never starts.
* "device" — every query goes through the dispatcher to the device,
             micro-batched, with failover to the oracle while the device
             is marked down (below). Tests and every benchmark cell run
             this mode.
* "host"   — the dispatcher answers from the host oracle (latency floor;
             also the correctness baseline).

The service shrinks the interpreter's GIL slice (VPROXY_TPU_GIL_SLICE_MS,
default 1ms vs CPython's 5ms): a thread that holds the GIL — the
dispatcher's encode and launch, a busy event loop — delays a submitter
or a loop by ~one slice at most, and every benchmark cell is measured
under it.

Every delivered query also records submit->delivery latency into a
fixed reservoir; stats.latency_percentiles() surfaces p50/p99 (the
BASELINE "p99 classify latency" contract, measured at the service
boundary). A delivered batch records its samples once, before its
first callback, through Histogram.observe_many (one vectorised pass and
one lock acquisition a histogram; stats.latency_batched counts those
samples); a batch under LAT_BATCH_MIN and every inline answer take the
scalar Histogram.observe, which is cheaper for a lone verdict.

Failure containment: if a device dispatch raises, the service logs one
alarm, serves that batch and everything after it from the host oracle,
and re-probes the device every RETRY_S seconds. Accepts never die with
a classify backtrace. Every such event counts in `stats.failovers` and
leaves its exception text in `stats.last_failover`, so a caller that
must KNOW the device served (chip_smoke.py) reads the counters instead
of trusting correct answers.

Batch shapes are padded to power-of-two buckets (min VPROXY_TPU_PAD_LO,
default 4) so the jitted matchers compile a handful of programs, not
one per batch size. Padding is ARRAY-level (engine dispatch_snap
pad_to): only the real queries pay the host-side encode; pad rows are
invalid-probe fills that can never match.

The dispatcher is DOUBLE-BUFFERED (round 8) for cheap-dispatch
backends: a device batch is submitted asynchronously, and the
dispatcher goes straight back to draining the queue — the next
batch's encode overlaps the previous batch's device compute, and the
previous result is pulled (one host round trip per batch) just before
delivery. A straggler that missed batch k no longer waits out k's
full round trip before k+1 even starts; that wait was THE
service_device_p99 driver (round 6). Mesh-SHARDED backends instead
submit synchronously (see _device_submit): their per-dispatch cost is
fixed and high, so parking the dispatcher through the round trip —
the "natural batching" window above — beats the overlap (A/B'd).

Callbacks are delivered on the submitting event loop (loop-confinement
discipline, SURVEY §5 race-detection row): a delivered batch's callbacks
for one loop are handed to it in ONE run_many_on_loop() — one lock hold
and one wakeup for each batch and loop, not one a callback (a wakeup is
a ctypes call that hands the interpreter to a busy loop thread);
submissions without a loop get the callback on the dispatcher thread,
inline, in batch order. Always on, on /metrics:
vproxy_classify_loop_handoffs_total (one a (batch, loop) hand-over) and
vproxy_classify_loop_callbacks_total (callbacks handed over); tracing
on, the `engine/handoff` span, nested in `engine/deliver`, one a
hand-over, items = callbacks handed.
"""
from __future__ import annotations

import os
import sys
import threading
import time
from functools import partial
from operator import attrgetter
from typing import Callable, Optional, Sequence

import numpy as np

from ..utils import heap, sketch, trace
from ..utils.log import Logger
from ..utils.metrics import CLASSIFY_KINDS
from .engine import SMALL_TABLE, pad_batch, serving_recent
from .ir import Hint

_log = Logger("classify")

RETRY_S = float(os.environ.get("VPROXY_TPU_DEVICE_RETRY_S", "5"))
PAD_LO = int(os.environ.get("VPROXY_TPU_PAD_LO", "4"))
GIL_SLICE_MS = float(os.environ.get("VPROXY_TPU_GIL_SLICE_MS", "1"))
LAT_RESERVOIR = 4096  # submit->delivery latency samples kept
# a batch this long records its latencies vectorised: the numpy pass
# over both histograms costs a fixed ~27 us + 0.12 us a sample on the
# chip's host, a scalar observe() pair 2.05 us a sample — even at 14
LAT_BATCH_MIN = 16

_gil_slice_applied = False


def _apply_gil_slice() -> None:
    """Shrink the interpreter's thread-switch interval (once, process-
    wide, never loosening an even smaller configured value): a thread
    holding the GIL (the dispatcher's encode + launch, a busy loop) can
    then delay another by ~one slice instead of CPython's default 5ms.
    Every benchmark cell is measured under it."""
    global _gil_slice_applied
    if _gil_slice_applied or GIL_SLICE_MS <= 0:
        return
    _gil_slice_applied = True
    import sys
    want = GIL_SLICE_MS / 1000.0
    if want < sys.getswitchinterval():
        sys.setswitchinterval(want)


def _guarded_cb(cb, *args) -> None:
    """Run one classify callback; a failing one is logged, not raised,
    so the rest of its batch is still delivered."""
    try:
        cb(*args)
    except MemoryError:
        raise
    except Exception:
        _log.error("classify callback failed", exc=True)


_handoff_counters: Optional[tuple] = None


def _handoff_metrics() -> tuple:
    """(hand-overs, callbacks handed over) — the process's counters,
    looked up once (utils/metrics registers them eagerly, so a scrape
    shows the zeros)."""
    global _handoff_counters
    if _handoff_counters is None:
        from ..utils.metrics import GlobalInspection
        gi = GlobalInspection.get()
        _handoff_counters = (
            gi.get_counter("vproxy_classify_loop_handoffs_total"),
            gi.get_counter("vproxy_classify_loop_callbacks_total"))
    return _handoff_counters


def _hand_over(loop, fns: list, tid: int) -> None:
    """One loop's callbacks of a delivered batch in one hand-over: one
    lock hold and one wakeup (run_many_on_loop). Where the loop is gone
    nothing was queued, and they run here, in order, so cleanup
    (closing an accepted fd) still happens."""
    with trace.span("engine", "handoff", tid=tid, items=len(fns)):
        handed = loop.run_many_on_loop(fns)
    if handed:
        handoffs, callbacks = _handoff_metrics()
        handoffs.incr()
        callbacks.incr(len(fns))
        return
    for fn in fns:
        fn()


class _Req:
    __slots__ = ("payload", "cb", "loop", "t0", "tid")

    def __init__(self, payload, cb, loop, tid=0):
        self.payload = payload
        self.cb = cb
        self.loop = loop
        self.t0 = time.monotonic()
        # the submitter's trace context rides the request so the
        # dispatcher thread can attach its spans (queue wait, dispatch,
        # d2h sync, deliver) to the sampled request that triggered them
        self.tid = tid


_T0 = attrgetter("t0")    # a request's submit time


class _Inflight:
    """One async-submitted device batch awaiting its sync + delivery
    (the dispatcher's double buffer slot)."""

    __slots__ = ("kind", "matcher", "reqs", "snap", "arr", "tid",
                 "prefetched", "t_ret")

    def __init__(self, kind, matcher, reqs, snap, arr, tid, prefetched,
                 t_ret):
        self.kind = kind
        self.matcher = matcher
        self.reqs = reqs
        self.snap = snap
        self.arr = arr
        self.tid = tid    # the batch's first sampled request, 0 = none
        self.prefetched = prefetched  # its readback started at launch
        # tracing on: monotonic ns at which its launch had returned (the
        # start of its `engine/inflight`), else 0
        self.t_ret = t_ret


def _start_readback(arr) -> bool:
    """Start the device->host copy of a batch's result now that its
    launch has returned, so the copy runs in the runtime's threads
    behind the next batch's encode + launch and _finish_inflight's
    np.asarray finds the host value there. Decided from the result's
    type: a numpy return or a host-pick wrapper has no such method and
    is read as before. A copy that cannot be started loses nothing —
    the batch stays in flight and the blocking read fetches it (its
    failure is the one that degrades the batch). -> started."""
    start = getattr(arr, "copy_to_host_async", None)
    if start is None:
        return False
    try:
        start()
    except MemoryError:
        raise
    except Exception:
        _log.error("readback prefetch failed; the batch is read "
                   "blocking", exc=True)
        return False
    return True


class _Swap:
    """Tracing on: one iteration of the dispatcher's loop up to its
    cycle, as the top-level span `engine/swap` — from the iteration's
    top to the start of its `engine/cycle` (to its own end where it took
    nothing), less the park inside it, which is `engine/wait`'s: the
    acquire of `_cv` (noted again as `engine/swap_lock`, not a leaf),
    the swap of the pending queue — which lets go of the last wake's
    lists, and with them of every request finished inside that wake —
    and the split into uniform parts. Its profiler annotation holds the
    park's, its total does not: wait, swap, cycle and drain tile the
    thread."""

    __slots__ = ("ann", "t0", "t_lock", "park_ns")

    def __init__(self):
        self.ann = trace.annotation("vproxy/engine/swap", {})
        self.t0 = self.t_lock = time.monotonic_ns()
        self.park_ns = 0

    def locked(self) -> None:
        """`_cv` is held."""
        self.t_lock = time.monotonic_ns()

    def parked(self) -> None:
        """Back from a park that began right after the acquire."""
        self.park_ns = time.monotonic_ns() - self.t_lock

    def close(self, items: int) -> None:
        """The iteration's cycle begins, or it took nothing: `items`
        queries taken."""
        dur_ns = time.monotonic_ns() - self.t0 - self.park_ns
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        trace.note_span(0, "engine", "swap", self.t0, dur_ns, items=items,
                        park_ns=self.park_ns)
        trace.note_span(0, "engine", "swap_lock", self.t0,
                        self.t_lock - self.t0)


class _Cycle:
    """Tracing on: one dispatcher wake that found work, as spans.
    `engine/cycle` runs from the end of the wake's `engine/swap` (the
    pending queue taken and split into uniform parts) to the end of
    the wake's last turn (its dispatch and the delivery of the batch
    before it). One `engine/turn_wait` a uniform part runs from the
    cycle's start to that part's own dispatch: the share of its queries'
    queue_wait spent behind the other matchers of the wake. All are
    entered at the start and left innermost first, so they nest in a
    profiler trace; a turn_wait is buffered on its part's first sampled
    request."""

    __slots__ = ("span", "waits")

    def __init__(self, parts: list):
        self.span = trace.span("engine", "cycle", tid=0,
                               items=sum(len(p) for _k, _m, p in parts),
                               batches=len(parts))
        self.span.__enter__()
        self.waits = [
            trace.span("engine", "turn_wait",
                       tid=next((r.tid for r in p if r.tid), 0),
                       items=len(p), kind=k, batch=len(p))
            for k, _m, p in reversed(parts)]
        for w in self.waits:
            w.__enter__()

    def turn(self) -> None:
        """The next part's dispatch begins."""
        self.waits.pop().__exit__(None, None, None)

    def close(self) -> None:
        self.span.__exit__(None, None, None)


class ClassifyStats:
    """Counters surfaced via utils/metrics GlobalInspection."""

    def __init__(self):
        self.queries = 0          # total submitted
        self.dispatches = 0       # device batches dispatched
        self.device_queries = 0   # queries answered by the device
        # the same two by service kind, one increment a batch (on
        # /metrics as vproxy_classify_batches_total{kind} and
        # vproxy_classify_batch_queries_total{kind})
        self.batches = dict.fromkeys(CLASSIFY_KINDS, 0)
        self.batch_queries = dict.fromkeys(CLASSIFY_KINDS, 0)
        self.oracle_queries = 0   # queries answered by the host oracle
        self.failovers = 0        # device errors that degraded a batch
        self.last_failover = ""   # repr of the newest such error
        self.max_batch = 0
        self.budget_reroutes = 0  # pinned at 0: benchmark/program.py reads it
        self.inline_fast = 0      # inline answers a device could have given
        # latency samples recorded a batch at a time (observe_many); on
        # /metrics as vproxy_classify_latency_batched_total, beside the
        # histogram's own _count
        self.latency_batched = 0
        # lookups of a maglev.GroupedPair whose pick came out of the
        # matched group's own table (verdict >= 0 and pick >= 0), by
        # where the pick was made; one increment a batch (on /metrics
        # as vproxy_classify_group_picks_total{where})
        self.group_picks = {"device": 0, "host": 0}
        # device batches whose device->host copy was started when their
        # launch returned, and device batches whose result was not yet
        # ready when _finish_inflight came for it (the sync then waits
        # for the kernel, which no early copy hides); one increment a
        # batch (on /metrics as vproxy_engine_readback_prefetch_total
        # and vproxy_engine_readback_kernel_waits_total)
        self.readback_prefetch = 0
        self.readback_kernel_waits = 0
        # counter read-modify-writes go through `lock` (writers are the
        # dispatcher thread AND every inline-answering submit thread)
        self.lock = threading.Lock()
        # submit->delivery latency rides the process-global histogram
        # (utils/metrics): log2 buckets on /metrics as
        # vproxy_classify_latency_us_{bucket,sum,count}. That series
        # survives ClassifyService.reset() — it is per-process, like the
        # /metrics surface it feeds. A second, UNregistered histogram
        # keeps this instance's own exact reservoir window, so the
        # p99-contract percentiles of a fresh service (bench runs one
        # per contract) are not polluted by a previous instance's
        # samples still sitting in a shared ring.
        from ..utils.metrics import GlobalInspection, Histogram
        self.lat_hist = GlobalInspection.get().get_histogram(
            "vproxy_classify_latency_us", reservoir=LAT_RESERVOIR)
        self._lat_local = Histogram("classify_latency_local_us",
                                    reservoir=LAT_RESERVOIR)

    def bump(self, name: str, n: int = 1) -> None:
        with self.lock:
            setattr(self, name, getattr(self, name) + n)

    def record_latency(self, seconds: float) -> None:
        us = seconds * 1e6
        self.lat_hist.observe(us)
        self._lat_local.observe(us)

    def record_latencies(self, now: float, reqs: list) -> None:
        """One sample a request of a delivered batch: now - its t0."""
        n = len(reqs)
        if n < LAT_BATCH_MIN:
            for r in reqs:
                self.record_latency(now - r.t0)
            return
        us = (now - np.fromiter(map(_T0, reqs), np.float64, n)) * 1e6
        self.lat_hist.observe_many(us)
        self._lat_local.observe_many(us)
        with self.lock:
            self.latency_batched += n

    def latency_percentiles(self) -> Optional[dict]:
        """p50/p99/p999 submit->delivery latency in us (exact over this
        instance's reservoir window)."""
        pct = self._lat_local.percentiles((50.0, 99.0, 99.9))
        if pct is None:
            return None
        return {"n": pct["n"], "p50_us": pct["p50"],
                "p99_us": pct["p99"], "p999_us": pct["p999"]}

    def snapshot(self) -> dict:
        d = {k: getattr(self, k) for k in (
            "queries", "dispatches", "device_queries", "oracle_queries",
            "failovers", "max_batch", "inline_fast",
            "readback_prefetch", "readback_kernel_waits")}
        lat = self.latency_percentiles()
        if lat is not None:
            d["latency_p50_us"] = round(lat["p50_us"], 1)
            d["latency_p99_us"] = round(lat["p99_us"], 1)
        return d


class ClassifyService:
    _instance: Optional["ClassifyService"] = None
    _instance_lock = threading.Lock()

    @classmethod
    def get(cls) -> "ClassifyService":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        """Test hook: drop the singleton (a new one lazily respawns)."""
        with cls._instance_lock:
            inst, cls._instance = cls._instance, None
        if inst is not None:
            inst.close()

    def __init__(self, mode: Optional[str] = None):
        self.mode = mode or os.environ.get("VPROXY_TPU_CLASSIFY", "auto")
        self.retry_s = RETRY_S
        _apply_gil_slice()
        self.stats = ClassifyStats()
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        # key -> (kind, matcher, list[_Req]); key identifies the matcher
        # (a table set, for lookups of its views)
        self._pending: dict[int, tuple[str, object, list[_Req]]] = {}
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._device_down_until = 0.0

    # ------------------------------------------------------------- submit

    def submit_hint(self, matcher, hint: Hint,
                    cb: Callable[[int, object], None], loop=None) -> None:
        """Queue one hint classify; cb(rule_idx, payload) with idx=-1 for
        no match and payload = the matcher generation's attached object
        (Upstream registers its GroupHandle list there so idx is always
        interpreted against the generation that produced it)."""
        self._submit("hint", matcher, hint, cb, loop)

    def submit_cidr(self, matcher, addr: bytes, port: Optional[int],
                    cb: Callable[[int, object], None], loop=None) -> None:
        """Queue one route/ACL lookup; cb(first-match idx, payload), -1
        for none. port=None skips ACL port-range gating entirely. A
        view of a table set (engine.CidrTableView) is filed under its
        set, the view's key riding in the payload (an int: the tuple
        holds nothing the collector tracks): lookups of every table of
        the set coalesce into one device batch, each answered from the
        table it names."""
        ts = getattr(matcher, "table_set", None)
        if ts is not None:
            self._submit("cidr", ts, (addr, port, matcher.key), cb, loop)
        else:
            self._submit("cidr", matcher, (addr, port), cb, loop)

    def submit_classify_pick(self, pair, hint: Hint, ip: bytes,
                             port: Optional[int],
                             cb: Callable[[int, int, object], None],
                             loop=None) -> None:
        """Queue one fused classify+pick against a maglev.FusedPair or
        GroupedPair: cb(verdict_idx, pick_idx, (hint_payload,
        maglev_payload)). In device mode micro-batches ride the pair's
        ONE-launch program (rules/engine.fused_dispatch /
        grouped_dispatch); in auto mode every query takes the inline
        host lane (hint index + O(1) maglev read), as plain hint
        submits do. A grouped
        pair's pick is a slot of the table of the group the matched
        rule names (an index into that group's published member list),
        -1 where the verdict is -1 or its group holds no table.
        port=None = source affinity (the shared Maglev hash
        contract)."""
        self._submit("cpick", pair, (hint, ip, port), cb, loop)

    def _submit(self, kind: str, matcher, payload, cb, loop) -> None:
        inline = False
        # tracing on: how long a sampled submit waits for the lock. The
        # trace id is read INSIDE the lock, where _Req used to read it:
        # a Python call between a submitter's waking and its taking the
        # lock cost the 8-submitter cell 4 % of its rate (PERF.md §6)
        t_lock = time.monotonic_ns() if trace.SAMPLE else 0
        with self._cv:
            tid = trace.current_id()
            if t_lock:
                t_in = time.monotonic_ns()
            if self._closed:
                raise OSError("ClassifyService is closed")
            self.stats.queries += 1
            key = id(matcher)
            ent = self._pending.get(key)
            if ent is None and self._inline_host(matcher):
                inline = True  # answered below, outside the lock
            elif ent is None:
                self._pending[key] = (kind, matcher,
                                      [_Req(payload, cb, loop, tid)])
            else:
                ent[2].append(_Req(payload, cb, loop, tid))
            if not inline:
                if self._thread is None:
                    # what the process built since its last install
                    # (listeners, groups, its callers' closures) is as
                    # long-lived as the tables: frozen before the
                    # dispatcher exists, once (utils/heap). Here and
                    # not in _run: there the submitters would queue a
                    # whole window behind the dispatcher's first wake
                    heap.settle("serve_start", idle=not serving_recent())
                    self._thread = threading.Thread(
                        target=self._run, name="classify-dispatch",
                        daemon=True)
                    self._thread.start()
                self._cv.notify()
        if t_lock and tid:
            trace.note_span(tid, "engine", "submit_lock_wait", t_lock,
                            t_in - t_lock, kind=kind)
        if inline:
            self._answer_inline(kind, matcher, payload, cb, loop)

    def _inline_host(self, matcher) -> bool:
        """auto mode: the query is answered synchronously on the
        submitting thread from the host index. `inline_fast` counts the
        answers on a device backend, device up, table over SMALL_TABLE
        (chip_smoke.py's auto leg and benchmark/program.py counters()
        read it). Called under the lock; must stay O(1)."""
        if self.mode != "auto":
            return False
        if getattr(matcher, "backend", "host") != "host" \
                and time.monotonic() >= self._device_down_until \
                and matcher.size() > SMALL_TABLE:
            self.stats.inline_fast += 1
        return True

    def _answer_inline(self, kind: str, matcher, payload, cb, loop) -> None:
        """Serve one query from the snapshot's host index, inline.
        Delivery keeps the loop-confinement contract: run_on_loop runs
        the callback immediately when the submitter IS the loop thread
        (the accept path — fully synchronous), else queues it there."""
        t0 = time.monotonic()
        tid = trace.current_id()
        snap = matcher.snapshot()
        try:
            if kind in ("hint", "cpick"):
                # cpick: the FusedPair host lane -> (verdict, pick)
                i = matcher.index_snap(snap, payload)
            else:   # (addr, port) and, for a table set, the view's key
                i = matcher.index_snap(snap, *payload)
        except MemoryError:
            raise
        except Exception:
            _log.error("inline classify failed; delivering no-match",
                       exc=True)
            i = (-1, -1) if kind == "cpick" else -1
        dt = time.monotonic() - t0
        if tid:
            trace.record_span(tid, "engine", "classify_inline",
                              int(t0 * 1e9), int(dt * 1e9), kind=kind)
        st = self.stats
        with st.lock:
            st.oracle_queries += 1
            st.max_batch = max(st.max_batch, 1)
            if kind == "cpick" and i[0] >= 0 and i[1] >= 0 \
                    and getattr(matcher, "grouped", False):
                st.group_picks["host"] += 1     # a batch of one
        st.record_latency(dt)
        pl = matcher.snap_payload(snap)
        args = (int(i[0]), int(i[1]), pl) if kind == "cpick" \
            else (int(i), pl)
        if loop is None or not loop.run_on_loop(
                partial(_guarded_cb, cb, *args)):
            _guarded_cb(cb, *args)

    # ---------------------------------------------------------- dispatcher

    def _run(self) -> None:
        # double-buffered: at most ONE device batch in flight while the
        # next one encodes/submits; the in-flight result syncs just
        # before its delivery (one host round trip per batch)
        inflight: Optional[_Inflight] = None
        queued = 0.0    # seconds the last acquire of `_cv` took
        convoy = sys.getswitchinterval() / 2
        while True:
            if queued > convoy:
                # the dispatcher queued for its own lock for more than
                # half an interpreter slice: a holder lost the
                # interpreter inside `_submit`, which one submitter's
                # few microseconds under the lock almost never do and
                # several submitters do all the time. They convoy on
                # the lock and on the interpreter, and a dispatcher
                # that fights them for both comes round to ever smaller
                # batches (each one a launch, an encode at the small
                # sizes' cost). It steps aside for as long again as it
                # queued before it takes their queue (PERF.md §6, PR 38:
                # the jitted call used to hand the interpreter back once
                # an argument, which were the submitters' turns) — for
                # four slices at most: a longer wait was a stall (a
                # machine stop, a collection under the lock), and
                # sleeping it again would double a pause
                with trace.span("engine", "wait", tid=0):
                    time.sleep(min(queued, 8 * convoy))
            # tracing on: wait, swap, cycle and drain tile this thread
            swap = _Swap() if trace.SAMPLE else None
            t_lock = time.monotonic()
            with self._cv:
                queued = time.monotonic() - t_lock
                if swap is not None:
                    swap.locked()
                if not self._pending and not self._closed \
                        and inflight is None:
                    with trace.span("engine", "wait", tid=0):
                        while not self._pending and not self._closed:
                            self._cv.wait()
                    if swap is not None:
                        swap.parked()
                batches = list(self._pending.values())
                self._pending.clear()
                closed = self._closed
            if not batches:
                if swap is not None:
                    swap.close(0)
                if inflight is not None:
                    # nothing came while the batch was in flight: it is
                    # read right behind its own launch, outside any cycle
                    with trace.span("engine", "drain", tid=inflight.tid,
                                    kind=inflight.kind,
                                    batch=len(inflight.reqs)):
                        self._finish_guarded(inflight)
                        with trace.span("engine", "release", tid=0,
                                        items=len(inflight.reqs)):
                            inflight = None
                    continue
                if closed:
                    return
                continue
            parts = [(kind, matcher, part)
                     for kind, matcher, reqs in batches
                     for part in self._split_uniform(kind, reqs)]
            if swap is not None:
                swap.close(sum(len(p) for _k, _m, p in parts))
            # tracing on: the wake and each part's wait for its turn
            cycle = _Cycle(parts) if trace.SAMPLE else None
            for kind, matcher, part in parts:
                if cycle is not None:
                    cycle.turn()
                nxt = None
                try:
                    nxt = self._begin_uniform(kind, matcher, part)
                except MemoryError:
                    raise  # OOM contract: log-then-die (utils/oom)
                except Exception:
                    # the dispatcher thread must survive ANY per-batch
                    # error (incl. oracle/delivery bugs) — a dead thread
                    # would strand every future classify silently.
                    # Callbacks get -1 ("no match") so callers proceed.
                    _log.error("classify dispatch failed; delivering "
                               "no-match to batch", exc=True)
                    try:
                        self._deliver(part, [-1] * len(part), kind=kind)
                    except MemoryError:
                        raise
                    except Exception:
                        _log.error("classify delivery failed", exc=True)
                if inflight is not None:
                    # deliver the PREVIOUS batch now that the next one
                    # is already on the device
                    self._finish_guarded(inflight)
                    # the last reference to a batch of an earlier wake:
                    # its result and every request of it are freed here
                    with trace.span("engine", "release", tid=0,
                                    items=len(inflight.reqs)):
                        inflight = None
                inflight = nxt
            if cycle is not None:
                cycle.close()

    def _use_device(self, matcher) -> bool:
        """device mode, a device backend, the device not marked down
        (auto never reaches the dispatcher)."""
        return (self.mode != "host"
                and getattr(matcher, "backend", "host") != "host"
                and time.monotonic() >= self._device_down_until)

    @staticmethod
    def _split_uniform(kind: str, reqs: list[_Req]) -> list[list[_Req]]:
        if kind == "cidr":
            # port=None means "ignore port ranges" and must NOT share a
            # device batch with port-carrying queries (it would be coerced
            # to port 0 and gated against the ACL ranges)
            with_p = [r for r in reqs if r.payload[1] is not None]
            without = [r for r in reqs if r.payload[1] is None]
            if with_p and without:
                return [with_p, without]
        return [reqs]

    def _begin_uniform(self, kind: str, matcher,
                       reqs: list[_Req]) -> Optional["_Inflight"]:
        """Submit one uniform batch: device batches go out ASYNC and
        return an _Inflight for _finish_inflight to sync+deliver; host
        batches deliver here and return None."""
        n = len(reqs)
        # tracing on: what comes before the dispatch is a phase of its own
        with trace.span("engine", "begin", tid=0, items=n, kind=kind):
            with self.stats.lock:  # inline submit threads write stats too
                self.stats.max_batch = max(self.stats.max_batch, n)
            # ONE generation for device/oracle/payload
            snap = matcher.snapshot()
            if sketch.ON:
                # device-plane attribution: which upstream's classify load
                # is filling the batches (routes dim, `upstream:<alias>`
                # keys, weight = batch occupancy)
                own = getattr(matcher, "owner_alias", None)
                if own:
                    sketch.update("routes", f"upstream:{own}", n,
                                  plane="engine")
            # sampled requests in the batch: the batch's phases (dispatch
            # and the engine's encode + launch under it, d2h sync, deliver,
            # host_index) are buffered on the FIRST one's trace — one span,
            # not one per request — and totalled for every batch while
            # tracing is on; queue wait is recorded for every sampled
            # request on BOTH serving branches
            tid = 0
            if trace.SAMPLE:
                t_q = time.monotonic_ns()
                for r in reqs:
                    if r.tid:
                        tid = tid or r.tid
                        t_sub = int(r.t0 * 1e9)
                        trace.note_span(r.tid, "engine", "queue_wait", t_sub,
                                        t_q - t_sub, kind=kind, batch=n)
        if self._use_device(matcher):
            try:
                # the bind hands the engine's encode and launch spans
                # the sampled request's trace
                with trace.bind(tid), trace.span("engine", "dispatch",
                                                 tid=tid, items=n,
                                                 kind=kind, batch=n):
                    arr = self._device_submit(kind, matcher, snap, reqs)
                with trace.span("engine", "readback_start", tid=tid,
                                kind=kind):
                    prefetched = _start_readback(arr)
                return _Inflight(kind, matcher, reqs, snap, arr, tid,
                                 prefetched,
                                 time.monotonic_ns() if trace.SAMPLE else 0)
            except MemoryError:
                raise
            except Exception as e:
                self._device_failed(e, n)
        t0 = time.monotonic()
        idxs = self._oracle_batch(kind, matcher, snap, reqs)
        if tid:
            trace.record_span(tid, "engine", "host_index",
                              int(t0 * 1e9),
                              int((time.monotonic() - t0) * 1e9),
                              kind=kind, batch=n)
        self.stats.bump("oracle_queries", n)
        self._note_group_picks(matcher, idxs, "host")
        self._deliver(reqs, idxs, matcher.snap_payload(snap), kind=kind,
                      tid=tid)
        return None

    def _note_group_picks(self, matcher, rows, where: str,
                          tid: int = 0) -> None:
        """A batch of a grouped pair, answered: count the lookups whose
        pick came out of the matched group's table, in one vectorised
        pass over the batch's (verdict, pick) rows. A device batch also
        leaves the `engine/group_pick` span (items = that count)."""
        if not getattr(matcher, "grouped", False):
            return
        t0 = time.monotonic_ns() if trace.SAMPLE and where == "device" \
            else 0
        rows = np.asarray(rows).reshape(-1, 2)
        n = int(np.count_nonzero((rows[:, 0] >= 0) & (rows[:, 1] >= 0)))
        with self.stats.lock:
            self.stats.group_picks[where] += n
        if t0:
            trace.note_span(tid, "engine", "group_pick", t0,
                            time.monotonic_ns() - t0, items=n,
                            batch=len(rows))

    def _finish_guarded(self, inf: "_Inflight") -> None:
        """_finish_inflight behind the dispatcher's survival guard: the
        thread must outlive ANY per-batch error (incl. oracle/delivery
        bugs) — a dead dispatcher would strand every future classify
        silently. Callbacks get -1 ("no match") so callers proceed."""
        try:
            self._finish_inflight(inf)
        except MemoryError:
            raise  # OOM contract: log-then-die, not limp (utils/oom)
        except Exception:
            _log.error("classify finish failed; delivering no-match "
                       "to batch", exc=True)
            try:
                self._deliver(inf.reqs, [-1] * len(inf.reqs),
                              kind=inf.kind)
            except MemoryError:
                raise
            except Exception:
                _log.error("classify delivery failed", exc=True)

    def _finish_inflight(self, inf: "_Inflight") -> None:
        """Pull one in-flight device batch (the single host round trip)
        and deliver; a device error here degrades THIS batch to the
        oracle and marks the device down, same as a submit failure."""
        n = len(inf.reqs)
        if inf.t_ret:
            # how long the result had to become ready: not a leaf, it
            # lies over whatever the dispatcher did meanwhile
            trace.note_span(inf.tid, "engine", "inflight", inf.t_ret,
                            time.monotonic_ns() - inf.t_ret,
                            kind=inf.kind, batch=n)
        idxs = None
        try:
            ready = getattr(inf.arr, "is_ready", None)
            kernel_wait = ready is not None and not ready()
            # one decision, two sinks: the counter below and, tracing
            # on, the sync's interval noted again as `engine/kernel_wait`
            with trace.span("engine", "d2h_sync", tid=inf.tid,
                            also="kernel_wait" if kernel_wait else None,
                            kind=inf.kind, batch=n):
                idxs = np.asarray(inf.arr)[:n]
            st = self.stats
            with st.lock:
                st.dispatches += 1
                st.device_queries += n
                st.batches[inf.kind] += 1
                st.batch_queries[inf.kind] += n
                st.readback_prefetch += inf.prefetched
                st.readback_kernel_waits += kernel_wait
        except MemoryError:
            raise
        except Exception as e:
            self._device_failed(e, n)
        where = "host" if getattr(inf.arr, "picks_on_host", False) \
            else "device"
        if idxs is None:
            idxs = self._oracle_batch(inf.kind, inf.matcher, inf.snap,
                                      inf.reqs)
            self.stats.bump("oracle_queries", n)
            where = "host"
        self._note_group_picks(inf.matcher, idxs, where, inf.tid)
        try:
            self._deliver(inf.reqs, idxs,
                          inf.matcher.snap_payload(inf.snap),
                          kind=inf.kind, tid=inf.tid)
        except MemoryError:
            raise
        except Exception:
            _log.error("classify delivery failed", exc=True)

    def _device_failed(self, e: Exception, n: int) -> None:
        self.stats.bump("failovers")
        self.stats.last_failover = repr(e)
        self._device_down_until = time.monotonic() + self.retry_s
        _log.alert(f"device classify failed ({e!r}); serving from "
                   f"host oracle, retry in {self.retry_s:.0f}s")
        from ..utils import events
        events.record("classify_failover",
                      f"device classify failed: {e!r}",
                      batch=n, retry_s=self.retry_s)

    def _device_submit(self, kind: str, matcher, snap, reqs: list[_Req]):
        """Encode + submit (NO sync): returns the async device result.
        Only the real queries are encoded — the engine pads the arrays
        to the batch bucket with can-never-match fill rows."""
        from ..utils import failpoint
        if failpoint.hit("device.dispatch.error", kind):
            # injected device fault: exercises the host-oracle failover
            # (and the down-until/re-probe machinery) deterministically
            raise RuntimeError("failpoint device.dispatch.error")
        n = len(reqs)
        cap = pad_batch(n, lo=PAD_LO)
        # dispatch-cost policy (A/B'd in round 8, sandbox): cheap single-device
        # dispatches PIPELINE (async submit — straggler overlap is the
        # r06->r08 service p99 win, 2.3ms -> 1.5ms), while mesh-sharded
        # dispatches PARK the dispatcher (sync): their fixed
        # per-dispatch cost is high enough that the natural-batching
        # window matters more than overlap (sharded closed-loop p50
        # 3.4ms sync vs 5.9ms async — async halves the batch size)
        sync = getattr(matcher, "backend", "host") in (
            "jax-sharded", "jax-fp-sharded")
        if kind in ("hint", "cpick"):
            # cpick is the FusedPair's matcher interface: the same
            # dispatch_snap call, ONE launch answering verdicts AND picks
            return matcher.dispatch_snap(snap, [r.payload for r in reqs],
                                         pad_to=cap, sync=sync)
        addrs = [r.payload[0] for r in reqs]
        ports = [r.payload[1] for r in reqs]
        if ports[0] is None:  # uniform batches only (see _split_uniform)
            ports = None
        if len(reqs[0].payload) > 2:    # a table set: the view keys' column
            return matcher.dispatch_snap(
                snap, addrs, ports, [r.payload[2] for r in reqs],
                pad_to=cap, sync=sync)
        return matcher.dispatch_snap(snap, addrs, ports, pad_to=cap,
                                     sync=sync)

    def _oracle_batch(self, kind: str, matcher, snap,
                      reqs: list[_Req]) -> list[int]:
        """Host-served batch (device down / host path): rides the
        snapshot's O(probes) index — same winner as the linear oracle
        (rules/index.py parity tests), O(table) cheaper per query."""
        if kind in ("hint", "cpick"):
            return [matcher.index_snap(snap, r.payload) for r in reqs]
        return [matcher.index_snap(snap, *r.payload) for r in reqs]

    def _deliver(self, reqs: list[_Req], idxs, payload=None,
                 kind: str = "hint", tid: int = 0) -> None:
        """cb(idx, payload) — or cb(verdict, pick, payload) for cpick
        batches, where a row is the fused program's (verdict, pick)
        pair (a scalar row is an error fill: both -1). payload is the
        matcher-owner's object versioned with the table generation that
        served the batch (None when the owner didn't register one).
        A request without a loop has its callback run here, in batch
        order; the others' callbacks are collected per loop and each
        loop gets its batch's in ONE hand-over (_hand_over), in batch
        order. tid: the batch's first sampled request."""
        with trace.span("engine", "deliver", tid=tid, cpu=True,
                        items=len(reqs), kind=kind):
            self.stats.record_latencies(time.monotonic(), reqs)
            # the verdicts as Python ints, converted once a batch
            rows = np.asarray(idxs)
            cpick = kind == "cpick"
            if cpick and rows.ndim == 1:  # an error fill: both -1
                rows = np.stack([rows, rows], axis=1)
            by_loop: dict = {}      # loop -> its callbacks, batch order
            for r, row in zip(reqs, rows.tolist()):
                args = (row[0], row[1], payload) if cpick \
                    else (row, payload)
                if r.loop is None:
                    _guarded_cb(r.cb, *args)
                    continue
                # a closure is built only to be handed to a loop
                fns = by_loop.get(r.loop)
                if fns is None:
                    fns = by_loop[r.loop] = []
                fns.append(partial(_guarded_cb, r.cb, *args))
            for loop, fns in by_loop.items():
                _hand_over(loop, fns, tid)

    # ------------------------------------------------------------- control

    def device_ok(self) -> bool:
        return time.monotonic() >= self._device_down_until

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify()
