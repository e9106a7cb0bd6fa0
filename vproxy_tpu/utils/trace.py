"""Request tracing — span-level latency attribution across every plane.

The PR-1 observability layer (utils/metrics histograms + the
utils/events flight recorder) answers "how much / how fast" and "what
happened around second X"; this module answers "where did THIS request
spend its time". A sampled request carries a **trace context** — a
nonzero u64 trace id — through every plane it touches, and each plane
records `(trace_id, plane, span, t_start_ns, dur_ns, fields)` into one
process-wide bounded buffer:

* **lane**    — the C accept plane (native/vtl.cpp): each lane thread
  writes fixed binary TraceRec records into a lock-free SPSC span ring
  (accept → route_pick → connect → splice → close for lane-served
  connections; accept → punt for punted ones, with the trace id riding
  the widened LanePunt so the python path CONTINUES the same trace).
  components/lanes.py drains the rings through `vtl_trace_drain` into
  this buffer. Ring overflow is counted, never silent
  (`vproxy_trace_drop_total{ring="lane"}`).
* **accept**  — the python accept path (components/tcplb.py): acl,
  backend_pick, connect, splice, close, total.
* **engine**  — classify dispatch (rules/service.py + rules/engine.py).
  The dispatcher thread is TILED by four top-level spans — no two
  overlap, and from the first one's start to the last one's end only
  the spans' own entry and exit lie in none of them: wait (parked in
  the condition variable with nothing pending and nothing in flight),
  swap (one per iteration of the loop: from its top to the start of
  its cycle — to its own end where it took nothing — LESS the wait
  inside it: the acquire of the service's lock, the swap of the
  pending queue, which frees the requests finished inside the last
  wake, the split into uniform parts; `items` queries taken), cycle
  (one per
  wake that found work: from the end of its swap — the pending queue
  already taken and split — to the end of the wake's last turn;
  `items` queries, `batches` uniform parts begun) and drain (an
  iteration that found nothing pending with a batch in flight: that
  batch's d2h_sync + deliver, read right behind its own launch with no
  next batch to overlap). Inside a cycle, one span each per batch:
  begin (`_begin_uniform` up to its dispatch: the snapshot, the stats
  lock and, tracing on, the scan for sampled requests; `items`),
  dispatch (the `_device_submit` call, `items` queries; parent of
  encode, table_set and launch), encode (host encode + padding of one
  batch: `items` real queries, `cpu_ns`), launch (the jitted call:
  enqueue AND the implicit upload of its numpy arguments; `items` =
  how many numpy arrays it was handed, `h2d_bytes` their bytes;
  `kind`, `fused`, `bucket`), table_set (a batch of a CidrTableSet: forming
  its table-id column; `items` distinct tables the batch names),
  readback_start (the `copy_to_host_async()` of the result, right
  after the launch), d2h_sync (the blocking `np.asarray` of the
  result; in a cycle or a drain), group_pick (a grouped pair's batch:
  counting its device picks), deliver (the callback loop: `items`,
  `cpu_ns`; inside it handoff, one per (batch, event loop): the
  loop's callbacks queued in one lock hold with one wakeup, `items`
  callbacks handed), release (letting go of the finished batch, in a cycle or
  a drain: a batch of an earlier wake is freed there — its result,
  its `items` requests, their payloads and callbacks). NOT leaves —
  they lie over the others and are left out of the tiling: swap_lock
  (the acquire of the service's lock alone, the first thing inside
  every swap), turn_wait (one per uniform part: from the cycle's
  start to the part's own dispatch — the share of queue_wait spent
  behind the other matchers of the wake; `items`, `kind`, `batch`),
  inflight (one per device batch: from its launch's return to the
  dispatcher's coming for its result — how long the result had to
  become ready), kernel_wait (the interval of a batch's d2h_sync,
  noted a second time ONLY when the result was not ready when the
  dispatcher came for it — the decision that feeds
  vproxy_engine_readback_kernel_waits_total; d2h_sync less
  kernel_wait is the sync of results that were ready). Per sampled
  request: queue_wait (`batch`), submit_lock_wait (a submitter waiting
  for the service's lock), classify_inline / host_index fallbacks.
* **install** — the TableInstaller (rules/engine.py): every standby
  generation install traced as compile / upload / swap spans.
* **cluster** — the step-synchronized submit loop (cluster/submit.py):
  barrier, collective, barrier_stall, host_index — a degraded query's
  trace shows WHICH phase ate the time on the node that served it.
* **dns** — the DNS server's loop (dns/server.py): drain (one per
  readable wakeup; `items` datagrams read — their parse, the answers
  the cache gives inline and the flushes lie inside it), flush (one per
  hand-over of the rrsets lookups a drain held; `items` lookups
  submitted) and answer (one per classified query answered on the
  loop: the group's `next`, the encode and the sendto; `items`
  responses sent).
* **l7** — an L7 session's loop (components/l7.py, processors/h2.py):
  front_read (one per frontend read a session parses; `items` streams
  it opened), select (one per stream whose backend is chosen through
  `Upstream.next_async`: from its submit to its answer) and back_read
  (one per backend read relayed; `items` streams it completed).
* **runtime** — the interpreter: gc_pause (`gen`), from a `gc.callbacks`
  hook that lives exactly as long as tracing is on.

Two sinks. The bounded trace BUFFER holds spans of sampled requests (a
batch's spans attach to its first sampled request; a batch without one
buffers nothing). The span TOTALS (`span_totals()`, exported as
`vproxy_trace_span_us{plane,span}`) take every span of the `SPANS`
vocabulary, every batch, while tracing is on: n, sum_ns, sum_cpu_ns,
sum_items, log2 buckets, first and last timestamp — process-lifetime,
`reset()` leaves them. `cpu_ns` is `time.thread_time_ns()` at the span's
two ends: encode and deliver make no blocking call, so wall - CPU there
is time the thread was runnable and not running (GIL or scheduler).
While tracing is on `span()` also enters a `jax.profiler.TraceAnnotation`
named `vproxy/<plane>/<span>` (only in a process that has imported JAX),
so the spans sit in a profiler trace on its own clock, beside the device.

Sampling: `VPROXY_TPU_TRACE_SAMPLE` = N samples 1-in-N (0 = off, the
default). Knob-off cost is one branch per site — per batch on the
dispatcher, never per query. On, a batch costs its nine `span()`s
(ten where a second encoder runs, one more for each event loop its
callbacks are handed to), a wake two more and a drain one
(two clock reads, a profiler annotation and one locked add each;
encode and deliver two `thread_time_ns()` more — a syscall of ~6 us
on some hosts), a `note_span()` each for inflight, swap_lock and,
where they apply, kernel_wait, table_set and group_pick (a locked
add) and the walk over the launch's arguments; a submit two clock
reads; the DNS server a readable wakeup and a classified answer one
span each (a query: two spans where the cache missed); an L7 session
a read one span and a stream's select a `note_span()`. Two deciders:

* `maybe_sample()` — deterministic counter-based 1-in-N (the accept
  paths; every Nth request).
* `sampled_key(key)` — seeded hash decision, value-stable across
  processes (FNV-1a 64 over `VPROXY_TPU_TRACE_SEED` + key — the
  VPROXY_TPU_FAILPOINT_SEED idiom: the same key samples identically on
  every host, so a fleet traces the same request end to end).

Trace ids: python allocates ODD ids, the C lane plane allocates EVEN
ids (one atomic each) — no coordination, no collisions. Timestamps are
CLOCK_MONOTONIC nanoseconds on both sides (time.monotonic_ns() and
clock_gettime share the clock on linux), so cross-plane spans in one
trace order consistently.

Surfaces: `GET /trace` (inspection server + HTTP controller),
`list[-detail] trace` and the bare `trace <id>` line on every command
surface, and `tools/traceview.py` for a saved `GET /trace` body.
"""
from __future__ import annotations

import gc
import itertools
import os
import sys
import threading
import time
from collections import OrderedDict
from typing import Optional

SAMPLE = int(os.environ.get("VPROXY_TPU_TRACE_SAMPLE", "0") or 0)
SEED = os.environ.get("VPROXY_TPU_TRACE_SEED", "")
# bounded: at most this many live traces; evicting a trace counts its
# spans as dropped (ring="py") — bounded memory, never silent loss
MAX_TRACES = int(os.environ.get("VPROXY_TPU_TRACE_BUF", "512"))
MAX_SPANS_PER_TRACE = 256

PLANES = ("lane", "accept", "engine", "install", "cluster", "dns", "l7")

_lock = threading.Lock()
_traces: "OrderedDict[int, list]" = OrderedDict()
_plane_spans = {p: 0 for p in PLANES}
_py_dropped = 0
_id_seq = itertools.count(0)
_sample_seq = itertools.count(0)
_tls = threading.local()


def sample_every() -> int:
    return SAMPLE


def enabled() -> bool:
    return SAMPLE > 0


def configure(n: int) -> None:
    """Set the sampling knob at runtime (bench/test hook; production
    uses the env). Pushes the knob into the C lane plane too, so C
    sampling and python sampling flip together."""
    global SAMPLE
    SAMPLE = int(n)
    _gc_hook_sync()
    try:
        from ..net import vtl
        if hasattr(vtl, "trace_set_sample"):
            vtl.trace_set_sample(SAMPLE)
    except Exception:
        pass  # py provider / pre-trace .so: python-plane tracing only


def fnv64(data: bytes) -> int:
    """FNV-1a 64 (the maglev/flow-cache hash idiom) — value-stable
    across processes, unlike PYTHONHASHSEED-randomized hash()."""
    h = 14695981039346656037
    for b in data:
        h ^= b
        h = (h * 1099511628211) & 0xFFFFFFFFFFFFFFFF
    return h


def sampled_key(key) -> bool:
    """Seeded, value-stable 1-in-N decision for `key` (bytes or str):
    the same (seed, key) decides identically in every process — the
    VPROXY_TPU_FAILPOINT_SEED reproducibility contract, trace form."""
    if SAMPLE <= 0:
        return False
    if SAMPLE == 1:
        return True
    kb = key if isinstance(key, (bytes, bytearray)) else str(key).encode()
    return fnv64(SEED.encode() + b"\x00" + bytes(kb)) % SAMPLE == 0


def new_trace_id() -> int:
    """Fresh python-plane trace id (odd; the C lane plane allocates
    even ids from its own atomic — disjoint by construction)."""
    return (next(_id_seq) << 1) | 1


def maybe_sample() -> int:
    """Deterministic counter-based 1-in-N: a fresh trace id for every
    Nth call, 0 otherwise. The accept paths' decider."""
    if SAMPLE <= 0:
        return 0
    if next(_sample_seq) % SAMPLE:
        return 0
    return new_trace_id()


# ------------------------------------------------------------- context

class bind:
    """Context manager pushing `tid` as the current trace context for
    this thread (no-op for tid=0): spans recorded by downstream code
    (engine encode + launch spans, installer phases) attach to the request
    that triggered them."""

    __slots__ = ("tid",)

    def __init__(self, tid: int):
        self.tid = tid

    def __enter__(self):
        if self.tid:
            stack = getattr(_tls, "stack", None)
            if stack is None:
                stack = _tls.stack = []
            stack.append(self.tid)
        return self.tid

    def __exit__(self, *exc):
        if self.tid:
            _tls.stack.pop()
        return False


def current_id() -> int:
    """The calling thread's active trace id, 0 when none (one getattr
    + a truthiness check when tracing never bound on this thread)."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else 0


# -------------------------------------------------------------- buffer

def record_span(trace_id: int, plane: str, span: str, t_start_ns: int,
                dur_ns: int, **fields) -> None:
    """Append one span (any thread). Bounded: trace eviction and
    per-trace span caps count into the py drop tally, never block."""
    global _py_dropped
    if not trace_id:
        return
    ev = {"trace": trace_id, "plane": plane, "span": span,
          "t_ns": int(t_start_ns), "dur_ns": int(dur_ns)}
    if fields:
        ev.update(fields)
    with _lock:
        spans = _traces.get(trace_id)
        if spans is None:
            if len(_traces) >= MAX_TRACES:
                _, evicted = _traces.popitem(last=False)
                _py_dropped += len(evicted)
            spans = _traces[trace_id] = []
        if len(spans) >= MAX_SPANS_PER_TRACE:
            _py_dropped += 1
            return
        spans.append(ev)
        _plane_spans[plane] = _plane_spans.get(plane, 0) + 1


def ingest_lane_recs(recs) -> None:
    """Fold drained C TraceRecs ((trace_id, t_start_ns, dur_ns, aux,
    lane, span, flags, err) tuples, net/vtl.py trace_drain shape) into
    the buffer. Called from the lane threads (components/lanes.py)."""
    from ..net.vtl import TRACE_SPANS
    for tid, t_ns, dur_ns, aux, lane, span, flags, err in recs:
        name = TRACE_SPANS[span] if span < len(TRACE_SPANS) \
            else f"span{span}"
        fields = {"lane": lane}
        if name == "splice":
            fields["bytes"] = aux
        elif name == "punt":
            fields["kind"] = "connect_fail" if aux else "classic"
        if err:
            fields["err"] = err
        record_span(tid, "lane", name, t_ns, dur_ns, **fields)


def plane_spans_total(plane: str) -> int:
    return _plane_spans.get(plane, 0)


def py_dropped_total() -> int:
    return _py_dropped


def reset() -> None:
    """Test hook: drop every buffered trace (counters and span totals
    stay — they are process-lifetime totals, like every other /metrics
    series)."""
    with _lock:
        _traces.clear()


# ------------------------------------------------- span totals + span()

# the closed vocabulary of totalled spans (utils/metrics pre-registers
# vproxy_trace_span_us{plane,span} for each pair, at zero)
SPANS = (("engine", "wait"), ("engine", "cycle"), ("engine", "turn_wait"),
         ("engine", "dispatch"), ("engine", "encode"), ("engine", "launch"),
         ("engine", "d2h_sync"), ("engine", "deliver"),
         ("engine", "queue_wait"), ("engine", "submit_lock_wait"),
         ("engine", "table_set"), ("engine", "group_pick"),
         ("engine", "swap"), ("engine", "drain"),
         ("engine", "readback_start"), ("engine", "inflight"),
         ("engine", "kernel_wait"), ("engine", "swap_lock"),
         ("engine", "begin"), ("engine", "release"), ("engine", "handoff"),
         ("dns", "drain"), ("dns", "flush"), ("dns", "answer"),
         ("l7", "front_read"), ("l7", "select"), ("l7", "selected"),
         ("l7", "back_read"), ("runtime", "gc_pause"))
# bucket upper bounds 1, 2, 4 ... 2**26 us, then +Inf: utils/metrics.Histogram's
TOTAL_BUCKETS = 27


class _Total:
    __slots__ = ("n", "sum_ns", "sum_cpu_ns", "sum_items", "buckets",
                 "first_ns", "last_ns")

    def __init__(self):
        self.n = self.sum_ns = self.sum_cpu_ns = self.sum_items = 0
        self.buckets = [0] * (TOTAL_BUCKETS + 1)
        self.first_ns = self.last_ns = 0

    def add(self, t_start_ns: int, dur_ns: int, cpu_ns: int,
            items: int) -> None:
        us = -(-dur_ns // 1000)     # Histogram._bucket_of, from integer ns
        self.buckets[0 if us <= 1 else
                     min((us - 1).bit_length(), TOTAL_BUCKETS)] += 1
        self.n += 1
        self.sum_ns += dur_ns
        self.sum_cpu_ns += cpu_ns
        self.sum_items += items
        if not self.first_ns:
            self.first_ns = t_start_ns
        self.last_ns = t_start_ns + dur_ns

    def as_dict(self) -> dict:
        return {k: (list(self.buckets) if k == "buckets"
                    else getattr(self, k)) for k in self.__slots__}


_tot_lock = threading.Lock()
_totals: "dict[str, _Total]" = {}


def note_span(trace_id: int, plane: str, span: str, t_start_ns: int,
              dur_ns: int, cpu_ns: int = 0, items: int = 0,
              **fields) -> None:
    """One finished span of the SPANS vocabulary into both sinks: the
    totals always, the trace buffer when `trace_id` is nonzero. Nothing
    once tracing is off (a span that outlives `configure(0)` is lost)."""
    if SAMPLE <= 0:
        return
    key = plane + "/" + span
    with _tot_lock:
        tot = _totals.get(key)
        if tot is None:
            tot = _totals[key] = _Total()
        tot.add(t_start_ns, dur_ns, cpu_ns, items)
    if trace_id:
        if cpu_ns:
            fields["cpu_ns"] = cpu_ns
        if items:
            fields["items"] = items
        record_span(trace_id, plane, span, t_start_ns, dur_ns, **fields)


def span_totals() -> dict:
    """{"plane/span": {n, sum_ns, sum_cpu_ns, sum_items, buckets,
    first_ns, last_ns}} of every span totalled since the process began
    (empty while tracing was never on)."""
    with _tot_lock:
        out = {k: t.as_dict() for k, t in _totals.items()}
    if _gc_total.n:     # its one writer takes no lock, see _gc_hook
        out["runtime/gc_pause"] = _gc_total.as_dict()
    return out


_ann_cls = None     # jax.profiler.TraceAnnotation, once this process has JAX


def annotation(name: str, fields: dict):
    """An entered profiler annotation carrying `fields`, or None in a
    process that has not imported JAX (the accept planes: no profiler
    can be running there). Looked up, never imported: the gc hook can
    fire in the middle of `import jax`."""
    global _ann_cls
    if _ann_cls is None:
        _ann_cls = getattr(sys.modules.get("jax.profiler"),
                           "TraceAnnotation", None)
        if _ann_cls is None:
            return None
    ann = _ann_cls(name, **fields)
    ann.__enter__()
    return ann


class _Span:
    __slots__ = ("plane", "name", "tid", "cpu", "items", "also", "fields",
                 "t0", "cpu0", "ann")

    def __init__(self, plane, name, tid, cpu, items, also, fields):
        self.plane, self.name, self.tid = plane, name, tid
        self.cpu, self.items, self.also = cpu, items, also
        self.fields = fields

    def __enter__(self):
        self.ann = annotation("vproxy/" + self.plane + "/" + self.name,
                              self.fields)
        self.t0 = time.monotonic_ns()
        if self.cpu:
            self.cpu0 = time.thread_time_ns()
        return self

    def __exit__(self, *exc):
        cpu_ns = time.thread_time_ns() - self.cpu0 if self.cpu else 0
        dur_ns = time.monotonic_ns() - self.t0
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        note_span(self.tid, self.plane, self.name, self.t0, dur_ns,
                  cpu_ns, self.items, **self.fields)
        if self.also:
            note_span(self.tid, self.plane, self.also, self.t0, dur_ns,
                      **self.fields)
        return False


class _NoSpan:
    # `items` may be set inside the block where it is known only at the
    # end (dns/drain): the knob can turn off between the caller's check
    # and `span()`, and on the no-op the count is dropped
    __slots__ = ("items",)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_SPAN = _NoSpan()


def span(plane: str, name: str, tid: Optional[int] = None,
         cpu: bool = False, items: int = 0, also: Optional[str] = None,
         **fields):
    """Context manager around one phase of a batch (SPANS vocabulary):
    on exit the span goes to the totals, and to the trace buffer under
    `tid` (default: the thread's bound trace context) when that is
    nonzero. cpu: also take the thread's CPU time. also: a second span
    name the same interval is noted under (one decision of the caller's,
    two sinks; no annotation of its own). With tracing off this is one
    branch and a shared no-op."""
    if SAMPLE <= 0:
        return _NO_SPAN
    return _Span(plane, name, current_id() if tid is None else tid, cpu,
                 items, also, fields)


# gc_pause: collections stop every thread, so the hook's start/stop pair
# has one writer at a time and adds to its own total WITHOUT _tot_lock —
# a collection can begin on a thread that holds it.
_gc_total = _Total()
_gc_open = [0, None]    # start ns, profiler annotation


def _gc_hook(phase: str, info: dict) -> None:
    if phase == "start":
        _gc_open[1] = annotation("vproxy/runtime/gc_pause",
                                 {"gen": info["generation"]})
        _gc_open[0] = time.monotonic_ns()
    elif _gc_open[0]:
        t0, ann = _gc_open
        _gc_open[0], _gc_open[1] = 0, None
        if ann is not None:
            ann.__exit__(None, None, None)
        _gc_total.add(t0, time.monotonic_ns() - t0, 0, 0)


def _gc_hook_sync() -> None:
    """The hook is installed exactly while tracing is on."""
    if SAMPLE > 0 and _gc_hook not in gc.callbacks:
        gc.callbacks.append(_gc_hook)
    elif SAMPLE <= 0 and _gc_hook in gc.callbacks:
        gc.callbacks.remove(_gc_hook)
        _gc_open[0] = 0


_gc_hook_sync()     # a nonzero VPROXY_TPU_TRACE_SAMPLE at import


# ------------------------------------------------------------- queries

def get_trace(trace_id: int) -> list:
    """All spans of one trace, start-time ordered ([] when unknown)."""
    with _lock:
        spans = list(_traces.get(trace_id, ()))
    return sorted(spans, key=lambda s: (s["t_ns"], s["dur_ns"]))


def trace_ids(last: int = 0) -> list:
    with _lock:
        ids = list(_traces.keys())
    return ids[-last:] if last > 0 else ids


def summaries(last: int = 64) -> list:
    """Newest-last trace summaries: id, span count, planes touched,
    end-to-end ns (max span end - min span start)."""
    out = []
    with _lock:
        items = list(_traces.items())[-last:] if last > 0 \
            else list(_traces.items())
    for tid, spans in items:
        if not spans:
            continue
        t0 = min(s["t_ns"] for s in spans)
        t1 = max(s["t_ns"] + s["dur_ns"] for s in spans)
        out.append({"trace": tid, "spans": len(spans),
                    "planes": sorted({s["plane"] for s in spans}),
                    "total_us": round((t1 - t0) / 1000.0, 1)})
    return out


def waterfall(trace_id: int, width: int = 48) -> list:
    """Text waterfall for one trace (the `trace <id>` command): one bar
    per span, offset/scaled to the trace's own [t0, t1] window."""
    spans = get_trace(trace_id)
    if not spans:
        return [f"trace {trace_id}: not found (evicted or never sampled)"]
    return render_spans(trace_id, spans, width)


def render_spans(trace_id, spans: list, width: int = 48) -> list:
    """Waterfall renderer over raw span dicts — shared by the live
    `trace <id>` command and tools/traceview.py (offline artifacts)."""
    spans = sorted(spans, key=lambda s: (s["t_ns"], s["dur_ns"]))
    t0 = min(s["t_ns"] for s in spans)
    t1 = max(s["t_ns"] + s["dur_ns"] for s in spans)
    total = max(1, t1 - t0)
    out = [f"trace {trace_id}  total {total / 1000.0:.1f}us  "
           f"spans {len(spans)}"]
    for s in spans:
        off = int((s["t_ns"] - t0) * width / total)
        w = max(1, int(s["dur_ns"] * width / total))
        w = min(w, width - off) if off < width else 1
        bar = " " * min(off, width - 1) + "#" * w
        extras = " ".join(
            f"{k}={s[k]}" for k in sorted(s)
            if k not in ("trace", "plane", "span", "t_ns", "dur_ns"))
        out.append(f"  [{bar:<{width}}] {s['plane']:>7}/{s['span']:<14} "
                   f"+{(s['t_ns'] - t0) / 1000.0:9.1f}us "
                   f"{s['dur_ns'] / 1000.0:9.1f}us"
                   + (f"  {extras}" if extras else ""))
    return out


def slowest(n: int = 8) -> list:
    """The n slowest buffered traces, spans attached — the worst-trace
    dump shape shared by the bench --trace stage, storm and chaos
    reports (docs/observability.md)."""
    worst = sorted(summaries(last=0), key=lambda t: t["total_us"],
                   reverse=True)[:n]
    return [dict(t, spans=get_trace(t["trace"])) for t in worst]


def stage_table() -> dict:
    """Per-(plane, span) duration percentiles over every buffered
    trace — the bench attribution table's source. -> {"plane/span":
    {"n", "p50_us", "p99_us"}}."""
    by: dict[str, list] = {}
    with _lock:
        all_spans = [s for spans in _traces.values() for s in spans]
    for s in all_spans:
        by.setdefault(f"{s['plane']}/{s['span']}",
                      []).append(s["dur_ns"] / 1000.0)
    out = {}
    for key, durs in sorted(by.items()):
        durs.sort()
        n = len(durs)
        out[key] = {"n": n,
                    "p50_us": round(durs[n // 2], 1),
                    "p99_us": round(durs[min(n - 1, (n * 99) // 100)], 1)}
    return out
