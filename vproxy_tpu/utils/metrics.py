"""Prometheus-style metrics registry + global inspection surface.

Parity: reference `vproxybase/prometheus/Metrics.java` (Counter / Gauge
/ GaugeF with a label set, text exposition) and `GlobalInspection.java:
24-205`: one process-global surface collecting direct-memory bytes,
per-loop thread registry, stack-trace dump and open-FD dump, exposed
over HTTP (`getPrometheusString():177`,
`GlobalInspectionHttpServerLauncher.java:9` — /metrics, /lsof, /jstack).
"""
from __future__ import annotations

import os
import sys
import threading
import traceback
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np


# the kinds a ClassifyService batch can be (rules/service.py `_submit`):
# the closed label vocabulary of vproxy_classify_batches_total{kind}
CLASSIFY_KINDS = ("hint", "cidr", "cpick")
# a DNS response's rcode (RFC 1035 4.1.1; 6..15 are "other"): the closed
# label vocabulary of vproxy_dns_answers_total{rcode} (dns/server.py)
DNS_RCODES = ("noerror", "formerr", "servfail", "nxdomain", "notimp",
              "refused", "other")
# why a DNS drain handed its held rrsets lookups over (dns/server.py
# `_flush`): the drain's end, or the hold full — the closed label
# vocabulary of vproxy_dns_hold_flushes_total{why}
DNS_FLUSH_WHYS = ("empty", "cap")
# what answered an `Upstream.next_async` (components/upstream.py): the
# closed label vocabulary of vproxy_upstream_selects_total{where}
UPSTREAM_SELECT_WHERES = ("device_pick", "group_next", "fallback",
                          "refused")


def _fmt_labels(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return "{" + inner + "}"


class Metric:
    mtype = "untyped"

    def __init__(self, name: str, labels: Optional[Dict[str, str]] = None):
        self.name = name
        self.labels = dict(labels or {})

    def value(self) -> float:
        raise NotImplementedError

    def sample_line(self) -> str:
        v = self.value()
        v_str = "%d" % v if float(v).is_integer() else repr(float(v))
        return f"{self.name}{_fmt_labels(self.labels)} {v_str}"

    def sample_lines(self) -> List[str]:
        return [self.sample_line()]


class Counter(Metric):
    mtype = "counter"

    def __init__(self, name: str, labels: Optional[Dict[str, str]] = None):
        super().__init__(name, labels)
        self._v = 0
        self._lock = threading.Lock()

    def incr(self, n: int = 1) -> None:
        with self._lock:
            self._v += n

    def value(self) -> float:
        return self._v


class Gauge(Metric):
    mtype = "gauge"

    def __init__(self, name: str, labels: Optional[Dict[str, str]] = None):
        super().__init__(name, labels)
        self._v = 0.0

    def set(self, v: float) -> None:
        self._v = v

    def add(self, d: float) -> None:
        self._v += d

    def value(self) -> float:
        return self._v


class GaugeF(Metric):
    """Gauge computed by a function at scrape time."""
    mtype = "gauge"

    def __init__(self, name: str, fn: Callable[[], float],
                 labels: Optional[Dict[str, str]] = None):
        super().__init__(name, labels)
        self.fn = fn

    def value(self) -> float:
        return float(self.fn())


class Histogram(Metric):
    """Fixed log2-bucket histogram with Prometheus exposition.

    Bucket upper bounds are 1, 2, 4, ... 2**(buckets-1) in the metric's
    own unit (latencies here use microseconds, hence the `_us` naming
    convention), plus the implicit +Inf bucket. The hot path is one
    uncontended lock acquisition, a bit_length() bucket pick and three
    integer adds — no allocation, no percentile math. A caller holding
    a whole batch of samples (ClassifyService's deliver) records it
    through observe_many(): the same state as one observe() a sample,
    for one vectorised bucket pick and one lock acquisition a batch;
    lone samples take observe().

    An optional reservoir (ring of the last N raw samples) makes
    percentiles() EXACT over the recent window instead of log2-bucket
    estimates; the classify latency contract (BASELINE p99 < 50us) is
    measured through it, while /metrics scrapes see the cumulative
    buckets either way.
    """
    mtype = "histogram"

    def __init__(self, name: str, labels: Optional[Dict[str, str]] = None,
                 buckets: int = 27, reservoir: int = 0):
        super().__init__(name, labels)
        self._bounds = [1 << k for k in range(buckets)]
        self._counts = [0] * (buckets + 1)  # + the +Inf bucket
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()
        self._res_cap = reservoir
        self._res: List[float] = [0.0] * reservoir
        self._res_n = 0

    def _bucket_of(self, v: float) -> int:
        if v <= 1.0:
            return 0
        iv = int(v)
        if iv < v:
            iv += 1
        return min((iv - 1).bit_length(), len(self._bounds))

    def observe(self, v: float) -> None:
        i = self._bucket_of(v)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1
            if self._res_cap:
                self._res[self._res_n % self._res_cap] = v
                self._res_n += 1

    def observe_many(self, values) -> None:
        """Record a float array as one observe() a value, in order,
        would: same buckets, count and reservoir ring, the sum to float
        rounding (the batch is summed first, then added)."""
        vs = np.asarray(values, dtype=np.float64)
        n = vs.size
        if n == 0:
            return
        # _bucket_of, vectorised: for an integer m = ceil(v) - 1 below
        # 2**53, frexp's exponent is m.bit_length() (and 0 for m = 0)
        exp = np.frexp(np.maximum(np.ceil(vs) - 1.0, 0.0))[1]
        per_bucket = np.bincount(
            np.minimum(exp, len(self._bounds)),
            minlength=len(self._counts)).tolist()
        total = float(vs.sum())
        cap = self._res_cap
        # a batch longer than the ring leaves only its last `cap` values
        tail = vs[-cap:].tolist() if cap else []
        with self._lock:
            for i, d in enumerate(per_bucket):
                if d:
                    self._counts[i] += d
            self._sum += total
            self._count += n
            if cap:
                m = len(tail)
                start = (self._res_n + n - m) % cap
                first = min(m, cap - start)  # up to the ring's end
                self._res[start:start + first] = tail[:first]
                self._res[:m - first] = tail[first:]
                self._res_n += n

    def merge(self, bucket_deltas, sum_delta: float,
              count_delta: int) -> None:
        """Fold pre-bucketed counts in (the C accept-lane stage
        histograms: native/vtl.cpp buckets with the same log2 rule and
        python merges the per-tick deltas, so lane-served connections
        land in the SAME series python-path connections populate). The
        reservoir stays sample-level-only by design — percentiles fall
        back to the bucket estimate when merged counts dominate."""
        if count_delta <= 0:
            return
        with self._lock:
            for i, d in enumerate(bucket_deltas):
                if d:
                    self._counts[i] += d
            self._sum += sum_delta
            self._count += count_delta

    def value(self) -> float:
        return self._count

    def state(self) -> Tuple[int, float, List[int]]:
        """(count, sum, [bucket counts]) snapshot — the workload-capture
        delta-window primitive (utils/workload.py)."""
        with self._lock:
            return self._count, self._sum, list(self._counts)

    def sample_lines(self) -> List[str]:
        with self._lock:
            counts = list(self._counts)
            total, s = self._count, self._sum
        return _histogram_lines(self.name, self.labels, self._bounds,
                                counts, total, s)

    def percentiles(self, qs=(50.0, 99.0, 99.9)) -> Optional[Dict[str, float]]:
        """-> {"n", "p50", "p99", "p999", ...} or None when empty.
        Exact over the reservoir window when one is configured, else a
        log-linear estimate from the cumulative buckets."""
        with self._lock:
            if self._count == 0:
                return None
            if self._res_cap and self._res_n:
                n = min(self._res_n, self._res_cap)
                window = sorted(self._res[:n])
                out = {"n": self._res_n}
                for q in qs:
                    i = min(n - 1, max(0, int(round(q / 100.0 * (n - 1)))))
                    out[_q_key(q)] = float(window[i])
                return out
            counts = list(self._counts)
            total = self._count
        out = {"n": total}
        for q in qs:
            out[_q_key(q)] = _bucket_quantile(self._bounds, counts, total,
                                              q / 100.0)
        return out


def _histogram_lines(name: str, labels: Dict[str, str], bounds, counts,
                     total: int, s: float) -> List[str]:
    """Prometheus exposition of one histogram series: cumulative
    `_bucket` lines, `_sum`, `_count`."""
    out = []
    cum = 0
    for bound, n in zip(bounds, counts):
        cum += n
        lbl = _fmt_labels({**labels, "le": str(bound)})
        out.append(f"{name}_bucket{lbl} {cum}")
    lbl = _fmt_labels({**labels, "le": "+Inf"})
    out.append(f"{name}_bucket{lbl} {total}")
    base = _fmt_labels(labels)
    s_str = "%d" % s if float(s).is_integer() else repr(float(s))
    out.append(f"{name}_sum{base} {s_str}")
    out.append(f"{name}_count{base} {total}")
    return out


class TraceSpanHistogram(Metric):
    """/metrics view of one utils/trace span total (`span_totals()`):
    the series `vproxy_trace_span_us{plane,span}`, in microseconds, at
    zero until tracing has been on."""
    mtype = "histogram"

    def __init__(self, plane: str, span: str):
        super().__init__("vproxy_trace_span_us",
                         {"plane": plane, "span": span})
        self._key = f"{plane}/{span}"

    def _total(self) -> Optional[dict]:
        from . import trace
        return trace.span_totals().get(self._key)

    def value(self) -> float:
        tot = self._total()
        return tot["n"] if tot else 0

    def sample_lines(self) -> List[str]:
        from . import trace
        tot = self._total()
        n = trace.TOTAL_BUCKETS
        return _histogram_lines(
            self.name, self.labels, [1 << k for k in range(n)],
            tot["buckets"] if tot else [0] * (n + 1),
            tot["n"] if tot else 0, tot["sum_ns"] / 1000.0 if tot else 0)


def _q_key(q: float) -> str:
    return "p" + ("%g" % q).replace(".", "")


def _bucket_quantile(bounds, counts, total, q: float) -> float:
    """Log-linear interpolation inside the winning log2 bucket."""
    rank = q * total
    cum = 0
    lo = 0.0
    for bound, n in zip(bounds, counts):
        if cum + n >= rank and n > 0:
            frac = (rank - cum) / n
            return lo + frac * (bound - lo)
        cum += n
        lo = float(bound)
    return float(bounds[-1] * 2)  # landed in +Inf


class MetricsRegistry:
    def __init__(self):
        self._metrics: List[Metric] = []
        self._lock = threading.Lock()

    def add(self, m: Metric) -> Metric:
        with self._lock:
            self._metrics.append(m)
        return m

    def remove(self, m: Metric) -> None:
        with self._lock:
            if m in self._metrics:
                self._metrics.remove(m)

    def counter(self, name: str, **labels) -> Counter:
        return self.add(Counter(name, labels))  # type: ignore[return-value]

    def gauge(self, name: str, **labels) -> Gauge:
        return self.add(Gauge(name, labels))  # type: ignore[return-value]

    def gauge_f(self, name: str, fn, **labels) -> GaugeF:
        return self.add(GaugeF(name, fn, labels))  # type: ignore[return-value]

    def histogram(self, name: str, buckets: int = 27, reservoir: int = 0,
                  **labels) -> Histogram:
        return self.add(Histogram(name, labels, buckets=buckets,
                                  reservoir=reservoir))  # type: ignore[return-value]

    def prometheus_text(self) -> str:
        with self._lock:
            metrics = list(self._metrics)
        by_name: Dict[str, Tuple[str, List[Metric]]] = {}
        for m in metrics:
            by_name.setdefault(m.name, (m.mtype, []))[1].append(m)
        out = []
        for name in sorted(by_name):
            mtype, ms = by_name[name]
            out.append(f"# TYPE {name} {mtype}")
            for m in ms:
                out.extend(m.sample_lines())
        return "\n".join(out) + ("\n" if out else "")


class GlobalInspection:
    """Process-global metric + introspection surface (singleton)."""

    _instance: Optional["GlobalInspection"] = None
    _ilock = threading.Lock()

    def __init__(self):
        self.registry = MetricsRegistry()
        self._loops: Dict[int, object] = {}  # id(loop) -> SelectorEventLoop
        self._lock = threading.Lock()
        # (name, sorted-label-items) -> Metric for get-or-create users
        self._named: Dict[tuple, Metric] = {}
        self.registry.gauge_f("vproxy_event_loop_count",
                              lambda: len(self._loops))
        self.registry.gauge_f("vproxy_open_fd_count",
                              lambda: len(self._open_fds()))
        self.registry.gauge_f("vproxy_thread_count",
                              lambda: threading.active_count())
        # micro-batch classify queue (rules/service.py — the north-star
        # data plane): batching ratio = queries / dispatches
        for k in ("queries", "dispatches", "device_queries",
                  "oracle_queries", "failovers", "max_batch"):
            self.registry.gauge_f(
                f"vproxy_classify_{k}", lambda k=k: self._classify_stat(k))
        # latency samples recorded a batch at a time (observe_many):
        # over vproxy_classify_latency_us_count, the share of verdicts
        # delivered in batches of LAT_BATCH_MIN or more
        self.registry.gauge_f(
            "vproxy_classify_latency_batched_total",
            lambda: self._classify_stat("latency_batched"))
        # device batches and their queries by service kind (one matcher
        # kind a batch): which plane's lookups fill the dispatcher
        for k in CLASSIFY_KINDS:
            self.registry.gauge_f(
                "vproxy_classify_batches_total",
                lambda k=k: self._classify_stat("batches", k), kind=k)
            self.registry.gauge_f(
                "vproxy_classify_batch_queries_total",
                lambda k=k: self._classify_stat("batch_queries", k), kind=k)
        # asynchronous readback (rules/service.py _start_readback): device
        # batches whose device->host copy was started at launch, and
        # device batches whose kernel had not finished when the
        # dispatcher came for the result; over
        # vproxy_classify_batches_total the share the early copy covers
        # and the share it cannot help
        for k in ("prefetch", "kernel_waits"):
            self.registry.gauge_f(
                f"vproxy_engine_readback_{k}_total",
                lambda k=k: self._classify_stat(f"readback_{k}"))
        # native splice-pump counters (net/native/vtl.cpp, the hot-byte
        # black box): bytes spliced, write syscalls, short writes, TLS
        # handshakes — read through the C-ABI getter in net/vtl.py
        for i, k in enumerate(("bytes", "splice_calls", "short_writes",
                               "tls_handshakes")):
            self.registry.gauge_f(f"vproxy_pump_{k}_total",
                                  lambda i=i: self._pump_counter(i))
        # switch flow-cache counters (native/vtl.cpp flow table + the
        # zero-Python forwarding loop): probe outcomes plus native-side
        # forward/drop totals with drop REASONS preserved — no silent C
        # drops. Zeros when the provider/.so lacks the cache.
        for i, k in enumerate(("hit", "miss", "evict", "stale")):
            self.registry.gauge_f(f"vproxy_switch_flowcache_{k}_total",
                                  lambda i=i: self._flowcache_counter(i))
        self.registry.gauge_f("vproxy_switch_native_fwd_total",
                              lambda: self._flowcache_counter(4))
        try:  # the reason-index contract lives in net/vtl.py
            from ..net.vtl import FLOW_DROP_REASONS as _fc_reasons
        except Exception:  # provider import failure: labels still exist
            _fc_reasons = ("acl_deny", "same_iface", "route_miss",
                           "unknown_vni", "egress_short_write", "other")
        for j, r in enumerate(_fc_reasons):
            self.registry.gauge_f("vproxy_switch_native_drop_total",
                                  lambda j=j: self._flowcache_counter(5 + j),
                                  reason=r)
        # accept-lane counters (native/vtl.cpp accept lanes, the C
        # accept plane): accepts taken by lanes, sessions served wholly
        # in C, and punts by reason — classic (no entry / armed
        # failpoints / overload), stale (generation gate), connect_fail
        # (fed to the retry/ejection machinery). Zeros without the .so.
        self.registry.gauge_f("vproxy_lane_accepted_total",
                              lambda: self._lane_counter(0))
        self.registry.gauge_f("vproxy_lane_served_total",
                              lambda: self._lane_counter(1))
        for j, r in enumerate(("classic", "stale", "connect_fail")):
            self.registry.gauge_f("vproxy_lane_punt_total",
                                  lambda j=j: self._lane_counter(2 + j),
                                  reason=r)
        # classify-engine generation installs (rules/engine.py): total
        # published generations and the published device-table bytes
        # per matcher kind; vproxy_engine_swap_ms (install latency) is
        # get_histogram'd by the TableInstaller on first publish
        self.registry.gauge_f("vproxy_engine_generation",
                              self._engine_generation)
        for kind in ("hint", "cidr"):
            self.registry.gauge_f(
                "vproxy_engine_table_bytes",
                lambda kind=kind: self._engine_table_bytes(kind),
                matcher=kind)
        # the cidr hash tables' bucket layout (engine.cidr_bucket_stat):
        # width 16 / hops 1 / share 0 is the one-hop case the kernel is
        # sized for; hops > 1 says some network carries more port
        # ranges than a slot row holds
        for name, key in (("vproxy_engine_cidr_bucket_width", "width"),
                          ("vproxy_engine_cidr_lookup_hops", "hops"),
                          ("vproxy_engine_cidr_overflow_share",
                           "overflow_share")):
            self.registry.gauge_f(
                name, lambda key=key: self._engine_cidr_bucket(key))
        # cidr table sets (engine.CidrTableSet: a switch's RouteTables,
        # one program): tables held by family, and per-table host builds
        # — a one-VPC route change moves the counter by that VPC alone
        for fam in ("v4", "v6", "any"):
            self.registry.gauge_f(
                "vproxy_engine_cidr_set_tables",
                lambda fam=fam: self._engine_cidr_set_tables(fam),
                family=fam)
        self.registry.gauge_f(
            "vproxy_engine_cidr_set_table_builds_total",
            lambda: self._engine_stat("cidr_set_table_builds_total"))
        # per-group pick tables (maglev.MaglevTableSet: an Upstream's
        # `source` groups, one program): tables held, per-group row
        # installs — a one-group health edge moves the counter by one —
        # and the lookups of a GroupedPair whose pick came out of the
        # matched group's table, by where it was made
        self.registry.gauge_f("vproxy_maglev_set_groups",
                              lambda: self._maglev_stat("set_groups_total"))
        self.registry.gauge_f(
            "vproxy_maglev_set_table_builds_total",
            lambda: self._maglev_stat("set_table_builds_total"))
        for where in ("device", "host"):
            self.registry.gauge_f(
                "vproxy_classify_group_picks_total",
                lambda where=where: self._classify_stat("group_picks",
                                                        where),
                where=where)
        # fused-dispatch accounting (rules/engine.py note_launch): total
        # device launches on the dispatch path and how many batches rode
        # the fused one-launch program — the scrape-verifiable form of
        # the "one launch per batch" claim (docs/perf.md fused section):
        # on a fused-only load the two counters move in lockstep
        self.registry.gauge_f("vproxy_engine_dispatch_launches_total",
                              lambda: self._engine_stat(
                                  "dispatch_launches_total"))
        self.registry.gauge_f("vproxy_engine_fused_dispatches_total",
                              lambda: self._engine_stat(
                                  "fused_dispatches_total"))
        # numpy arguments those launches were handed (each one an
        # implicit upload on the calling thread): over the launches,
        # 1.0 where every launch takes one packed query arena
        self.registry.gauge_f("vproxy_engine_launch_host_arrays_total",
                              lambda: self._engine_stat(
                                  "launch_host_arrays_total"))
        # the collector and the rule heap (utils/heap): objects frozen
        # out of the collector's reach, the freezes by the event that
        # made them, and the full re-examinations the growth rule asked
        from . import heap as _heap
        self.registry.gauge_f("vproxy_runtime_heap_frozen_objects",
                              lambda: float(_heap.frozen_objects()))
        for ev in _heap.EVENTS:
            self.registry.gauge_f(
                "vproxy_runtime_heap_freezes_total",
                lambda ev=ev: float(_heap.freezes_total(ev)), event=ev)
        self.registry.gauge_f(
            "vproxy_runtime_heap_reexaminations_total",
            lambda: float(_heap.reexaminations_total()))
        # cluster plane (vproxy_tpu/cluster): fleet membership, rule
        # generation convergence, and the step-synchronized dispatch
        # clock — all 0 until a ClusterNode boots
        for k in ("peers_up", "generation", "generation_lag",
                  "steps_total", "barrier_stalls_total"):
            self.registry.gauge_f(
                f"vproxy_cluster_{k}", lambda k=k: self._cluster_stat(k))
        # event-loop health: worst timer slip and longest single callback
        # across all live loops since the previous scrape (the known
        # GIL-contention p999 culprits); reading resets the window
        self.registry.gauge_f("vproxy_loop_timer_slip_us_max",
                              lambda: self._loop_health("slip"))
        self.registry.gauge_f("vproxy_loop_callback_us_max",
                              lambda: self._loop_health("cb"))
        # span tracing (utils/trace.py + native/vtl.cpp span rings):
        # pre-registered so a scrape shows the ZEROS before the first
        # sampled request — the PR-9 "silent drops counted" rule: a
        # span ring overflowing under storm load must show on /metrics
        # as a nonzero drop count, not as mysteriously missing spans
        self.registry.gauge_f("vproxy_trace_spans_total",
                              self._trace_c_spans, plane="lane")
        for pl in ("accept", "engine", "install", "cluster", "dns", "l7"):
            self.registry.gauge_f("vproxy_trace_spans_total",
                                  lambda pl=pl: self._trace_py_spans(pl),
                                  plane=pl)
        self.registry.gauge_f("vproxy_trace_drop_total",
                              self._trace_c_drops, ring="lane")
        self.registry.gauge_f("vproxy_trace_drop_total",
                              self._trace_py_drops, ring="py")
        # the batch cycle's span totals (trace.SPANS, a closed
        # vocabulary): every series at zero before tracing is ever on
        from . import trace as _trace
        for plane, span in _trace.SPANS:
            self.registry.add(TraceSpanHistogram(plane, span))
        # traffic-analytics plane (utils/sketch + native HH shards):
        # pre-registered with CLOSED label vocabularies (the PR-13
        # registry rule) — vproxy_hh_count{dim,slot} exposes the top-K
        # table slots per dimension, the counters account every update
        # plane and every lossy path (shard overflow, fleet-merge
        # truncation) so a scrape distinguishes "no traffic" from
        # "analytics off" from "dropped"
        from . import sketch as _sketch
        for dim in _sketch.DIMS:
            for slot in range(_sketch.TOP_SLOTS):
                self.registry.gauge_f(
                    "vproxy_hh_count",
                    lambda dim=dim, slot=slot: _sketch.top_slot(dim,
                                                                slot),
                    dim=dim, slot=str(slot))
        for pl in _sketch.PLANES:
            self.registry.gauge_f(
                "vproxy_analytics_updates_total",
                lambda pl=pl: float(_sketch.plane_updates_total(pl)),
                plane=pl)
        self.registry.gauge_f("vproxy_analytics_drop_total",
                              self._hh_overflow, reason="shard_overflow")
        # merge_truncated is the LATEST fleet merge's beyond-top-table
        # row count (a level, not a lifetime total — fleet merges run
        # per render, so a cumulative tally would track dashboard poll
        # rate instead of data loss)
        self.registry.gauge_f(
            "vproxy_analytics_drop_total",
            lambda: float(_sketch.merge_truncated_last()),
            reason="merge_truncated")
        self.registry.gauge_f(
            "vproxy_analytics_rotations_total",
            lambda: float(_sketch.rotations_total()))
        self.registry.gauge_f(
            "vproxy_analytics_enabled",
            lambda: 1.0 if _sketch.enabled() else 0.0)
        # policing plane (vproxy_tpu/policing — sketch-driven admission):
        # enforcement-table size, install/gossip counters, and policed-
        # action totals over the CLOSED action × dim grid, eagerly
        # registered so a scrape shows the zeros before the first
        # policy. The per-LB axis stays off this family (an open lb
        # vocabulary here would defeat the closed-grid registration);
        # per-LB attribution rides vproxy_lb_shed_total{reason="policed"}
        # and GET /policing.
        for k in ("keys", "tables_installed_total", "gossip_merges_total"):
            self.registry.gauge_f(f"vproxy_policy_{k}",
                                  lambda k=k: self._policing_stat(k))
        self.registry.gauge_f("vproxy_policing_enabled",
                              lambda: self._policing_stat("enabled"))
        for act in ("monitor", "throttle", "shed"):
            for dim in _sketch.DIMS:
                self.registry.gauge_f(
                    "vproxy_lb_policed_total",
                    lambda act=act, dim=dim: self._policed_total(act,
                                                                 dim),
                    action=act, dim=dim)
        # silent-drop accounting (udp_drop_incr below): created eagerly
        # so a scrape shows the zero before the first drop
        self.get_counter("vproxy_udp_drop_total")
        # the DNS server's plane (dns/server.py), answers by rcode over
        # its closed vocabulary
        for name in ("queries", "cache_hits", "cache_clears",
                     "held_lookups"):
            self.get_counter(f"vproxy_dns_{name}_total")
        for rc in DNS_RCODES:
            self.get_counter("vproxy_dns_answers_total", rcode=rc)
        for why in DNS_FLUSH_WHYS:
            self.get_counter("vproxy_dns_hold_flushes_total", why=why)
        # the classify service's deliver (rules/service.py _hand_over):
        # one a (batch, loop) hand-over and the callbacks handed over —
        # over the hand-overs, the burst one loop is woken for
        self.get_counter("vproxy_classify_loop_handoffs_total")
        self.get_counter("vproxy_classify_loop_callbacks_total")
        # asynchronous backend selects by what answered
        # (components/upstream.py next_async), and the L7 plane's frames
        # held for a stream that waits for its backend (processors/h2.py)
        for where in UPSTREAM_SELECT_WHERES:
            self.get_counter("vproxy_upstream_selects_total", where=where)
        self.get_counter("vproxy_l7_parked_frames_total")
        # the h2 hop encoder's field memo (processors/hpack.py
        # StaticEncoder), one increment a header block
        for res in ("hit", "miss"):
            self.get_counter("vproxy_h2_hpack_field_memo_total",
                             result=res)
        self.get_counter("vproxy_h2_hpack_field_memo_clears_total")
        # maglev table-compiler accounting (rules/maglev.py): eager for
        # the same reason — a scrape shows the zeros before any build
        self.get_counter("vproxy_maglev_table_builds_total")
        self.get_gauge("vproxy_maglev_remap_fraction")
        # accept-path stage histograms (the PR-1 span family): the
        # stage vocabulary is closed, so the five series exist — at
        # zero — before the first connection. accept_stage_observe /
        # accept_stage_merge dedup onto these instances via _get_named.
        for st in ("acl", "classify", "backend_pick", "handover",
                   "total"):
            self.get_histogram("vproxy_accept_stage_us", stage=st)
        # workload-capture plane (utils/workload.py): per-plane arrival
        # inter-arrival histograms + the process-wide per-connection
        # bytes/duration series — CLOSED vocabularies, eagerly created
        # so the vlint registry pass stays green with zero new baseline
        # entries (the per-LB labeled conn series created at TcpLB
        # construction reuse these family names; the registry check is
        # name-level)
        from . import workload as _workload
        for pl in _workload.PLANES:
            self.get_histogram("vproxy_workload_interarrival_us",
                               plane=pl)
        self.get_histogram("vproxy_lb_conn_bytes")
        self.get_histogram("vproxy_lb_conn_duration_ms")
        self.registry.gauge_f(
            "vproxy_workload_capture_enabled",
            lambda: 1.0 if _workload.enabled() else 0.0)
        # install/build latency histograms: eagerly created HERE (the
        # reservoir config lives at this single site — _get_named's
        # first-creation-wins rule means the component-side
        # get_histogram calls in rules/engine.py and rules/maglev.py
        # resolve to these instances)
        self.get_histogram("vproxy_engine_swap_ms", reservoir=512)
        self.get_histogram("vproxy_maglev_build_ms", reservoir=256)

    @staticmethod
    def _classify_stat(key: str, kind: Optional[str] = None) -> float:
        from ..rules.service import ClassifyService
        svc = ClassifyService._instance
        if svc is None:
            return 0.0
        val = getattr(svc.stats, key)
        return float(val if kind is None else val[kind])

    @staticmethod
    def _engine_generation() -> float:
        import sys
        eng = sys.modules.get("vproxy_tpu.rules.engine")
        return 0.0 if eng is None else float(eng.generation_total())

    @staticmethod
    def _engine_table_bytes(kind: str) -> float:
        import sys  # scrape must not force a jax import
        eng = sys.modules.get("vproxy_tpu.rules.engine")
        return 0.0 if eng is None else float(eng.table_bytes_total(kind))

    @staticmethod
    def _engine_cidr_bucket(key: str) -> float:
        import sys  # scrape must not force a jax import
        eng = sys.modules.get("vproxy_tpu.rules.engine")
        return 0.0 if eng is None else float(eng.cidr_bucket_stat()[key])

    @staticmethod
    def _engine_cidr_set_tables(family: str) -> float:
        import sys  # scrape must not force a jax import
        eng = sys.modules.get("vproxy_tpu.rules.engine")
        return 0.0 if eng is None \
            else float(eng.cidr_set_tables().get(family, 0))

    @staticmethod
    def _maglev_stat(name: str) -> float:
        import sys  # scrape must not force a jax import
        mg = sys.modules.get("vproxy_tpu.rules.maglev")
        return 0.0 if mg is None else float(getattr(mg, name)())

    @staticmethod
    def _engine_stat(name: str) -> float:
        import sys  # scrape must not force a jax import
        eng = sys.modules.get("vproxy_tpu.rules.engine")
        return 0.0 if eng is None else float(getattr(eng, name)())

    @staticmethod
    def _cluster_stat(key: str) -> float:
        from ..cluster import ClusterNode
        node = ClusterNode._instance
        return 0.0 if node is None else node.stat(key)

    @staticmethod
    def _pump_counter(i: int) -> float:
        from ..net import vtl
        return float(vtl.pump_counters()[i])

    @staticmethod
    def _flowcache_counter(i: int) -> float:
        from ..net import vtl
        return float(vtl.flowcache_counters()[i])

    @staticmethod
    def _lane_counter(i: int) -> float:
        from ..net import vtl
        return float(vtl.lane_counters()[i])

    @staticmethod
    def _trace_c_spans() -> float:
        from ..net import vtl
        return float(vtl.trace_counters()[0])

    @staticmethod
    def _trace_c_drops() -> float:
        from ..net import vtl
        return float(vtl.trace_counters()[1])

    @staticmethod
    def _trace_py_spans(plane: str) -> float:
        from . import trace
        return float(trace.plane_spans_total(plane))

    @staticmethod
    def _trace_py_drops() -> float:
        from . import trace
        return float(trace.py_dropped_total())

    @staticmethod
    def _policing_stat(key: str) -> float:
        import sys  # scrape must not force the policing import
        eng = sys.modules.get("vproxy_tpu.policing.engine")
        if eng is None:
            return 0.0
        return float(eng.default().status().get(key, 0))

    @staticmethod
    def _policed_total(action: str, dim: str) -> float:
        import sys  # scrape must not force the policing import
        eng = sys.modules.get("vproxy_tpu.policing.engine")
        return 0.0 if eng is None else float(
            eng.default().policed_total(action=action, dim=dim))

    @staticmethod
    def _hh_overflow() -> float:
        from ..net import vtl
        return float(vtl.hh_counters()[1])

    def _loop_health(self, key: str) -> float:
        with self._lock:
            loops = list(self._loops.values())
        worst = 0.0
        for lp in loops:
            take = getattr(lp, "take_health", None)
            if take is not None:
                worst = max(worst, take(key))
        return worst * 1e6

    def bench_snapshot(self) -> dict:
        """The flat-dict view of /metrics: per-series percentiles
        for every histogram plus raw values for counters/gauges, keyed
        by exposition name with label values folded in
        (vproxy_accept_stage_us{stage="acl"} ->
        "vproxy_accept_stage_us.acl"). tools/storm.py writes it into its
        report so the latency contract and drop rates land beside the
        scenario verdicts; tests read single series from it."""
        with self.registry._lock:
            metrics = list(self.registry._metrics)
        out: Dict[str, object] = {}
        for m in metrics:
            key = m.name
            if m.labels:
                key += "." + ".".join(
                    str(v) for _, v in sorted(m.labels.items()))
            try:
                if isinstance(m, Histogram):
                    pct = m.percentiles()
                    if pct is not None:
                        out[key] = {k: (round(v, 1)
                                        if isinstance(v, float) else v)
                                    for k, v in pct.items()}
                else:
                    out[key] = m.value()
            except Exception:
                pass  # a dead GaugeF fn must not sink the artifact
        return out

    # ------------------------------------------- named get-or-create

    def get_counter(self, name: str, **labels) -> Counter:
        return self._get_named(name, labels,
                               lambda: Counter(name, labels))  # type: ignore[return-value]

    def get_gauge(self, name: str, **labels) -> Gauge:
        return self._get_named(name, labels,
                               lambda: Gauge(name, labels))  # type: ignore[return-value]

    def get_histogram(self, name: str, buckets: int = 27, reservoir: int = 0,
                      **labels) -> Histogram:
        return self._get_named(
            name, labels, lambda: Histogram(name, labels, buckets=buckets,
                                            reservoir=reservoir))  # type: ignore[return-value]

    def _get_named(self, name: str, labels: dict, mk) -> Metric:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            m = self._named.get(key)
            if m is None:
                m = self._named[key] = mk()
                self.registry.add(m)
        return m

    @classmethod
    def get(cls) -> "GlobalInspection":
        with cls._ilock:
            if cls._instance is None:
                cls._instance = GlobalInspection()
            return cls._instance

    # ----------------------------------------------------------- loops

    def register_loop(self, loop) -> None:
        with self._lock:
            self._loops[id(loop)] = loop

    def deregister_loop(self, loop) -> None:
        with self._lock:
            self._loops.pop(id(loop), None)

    # ------------------------------------------------------------ dumps

    @staticmethod
    def _open_fds() -> List[str]:
        try:
            return sorted(os.listdir("/proc/self/fd"), key=int)
        except OSError:
            return []

    def open_fd_dump(self) -> str:
        """lsof analog: fd -> target (GlobalInspection.java:196-205)."""
        lines = []
        for fd in self._open_fds():
            try:
                target = os.readlink(f"/proc/self/fd/{fd}")
            except OSError:
                target = "?"
            lines.append(f"{fd}\t{target}")
        return "\n".join(lines) + "\n"

    @staticmethod
    def stack_trace_dump() -> str:
        """jstack analog (GlobalInspection.java:181-194)."""
        names = {t.ident: t.name for t in threading.enumerate()}
        out = []
        for tid, frame in sys._current_frames().items():
            out.append(f'Thread "{names.get(tid, "?")}" id={tid}')
            out.extend(l.rstrip() for l in traceback.format_stack(frame))
            out.append("")
        return "\n".join(out)

    def prometheus_string(self) -> str:
        return self.registry.prometheus_text()


# accept-path span timers (components/tcplb.py + components/upstream.py):
# one histogram family, labeled by stage — acl (accept->ACL verdict),
# classify (hint submit->index), backend_pick (group/WRR selection),
# handover (backend connect->pump running), total (accept->pump running).
# Local memo keeps the hot path at one dict hit; a racy double-create
# resolves to the same metric through get_histogram's dedup.
_ACCEPT_STAGE_HISTS: Dict[str, Histogram] = {}

# UDP drops that used to be silent (docs/robustness.md): the BlockingUdp
# facade's queue-full drop (net/wrapfd.py) and a DNS response the kernel
# refused with EAGAIN under storm load (dns/server.py). One process
# counter; memoized so the drop path costs a dict hit, and pre-created
# at first GlobalInspection access so /metrics shows the zero.
_UDP_DROP_CTR: Optional[Counter] = None

# what answered each Upstream.next_async, by UPSTREAM_SELECT_WHERES;
# memoized as the stage histograms are
_UPSTREAM_SELECT_CTRS: Dict[str, Counter] = {}


def udp_drop_incr(n: int = 1) -> None:
    global _UDP_DROP_CTR
    if _UDP_DROP_CTR is None:
        _UDP_DROP_CTR = GlobalInspection.get().get_counter(
            "vproxy_udp_drop_total")
    _UDP_DROP_CTR.incr(n)


def upstream_select_incr(where: str) -> None:
    c = _UPSTREAM_SELECT_CTRS.get(where)
    if c is None:
        c = _UPSTREAM_SELECT_CTRS[where] = GlobalInspection.get().get_counter(
            "vproxy_upstream_selects_total", where=where)
    c.incr()


def accept_stage_observe(stage: str, seconds: float) -> None:
    h = _ACCEPT_STAGE_HISTS.get(stage)
    if h is None:
        h = _ACCEPT_STAGE_HISTS[stage] = GlobalInspection.get().get_histogram(
            "vproxy_accept_stage_us", stage=stage)
    h.observe(seconds * 1e6)


def accept_stage_merge(stage: str, bucket_deltas, sum_us: float,
                       count: int) -> None:
    """Fold C-side pre-bucketed stage counts (accept lanes,
    vtl_lanes_stage_stat deltas) into the SAME
    vproxy_accept_stage_us{stage=} series the python accept path
    populates — lane-served connections stop being invisible to the
    stage histograms."""
    h = _ACCEPT_STAGE_HISTS.get(stage)
    if h is None:
        h = _ACCEPT_STAGE_HISTS[stage] = GlobalInspection.get().get_histogram(
            "vproxy_accept_stage_us", stage=stage)
    h.merge(bucket_deltas, sum_us, count)


# per-connection size/duration histograms (the workload-capture
# satellite): one process-wide aggregate pair (lb=None — what the
# workload model reads) plus a labeled pair per LB. Memoized like the
# stage histograms; a racy double-create dedups through _get_named.
_CONN_HISTS: Dict[Optional[str], Tuple[Histogram, Histogram]] = {}


def conn_hists(lb: Optional[str] = None) -> Tuple[Histogram, Histogram]:
    """(bytes, duration_ms) histogram pair for one LB (or the process
    aggregate when lb is None)."""
    pair = _CONN_HISTS.get(lb)
    if pair is None:
        gi = GlobalInspection.get()
        labels = {"lb": lb} if lb else {}
        pair = _CONN_HISTS[lb] = (
            gi.get_histogram("vproxy_lb_conn_bytes", **labels),
            gi.get_histogram("vproxy_lb_conn_duration_ms", **labels))
    return pair


def conn_observe(lb: Optional[str], nbytes: float, dur_ms: float) -> None:
    """One closed python-path session's size/duration, folded into the
    per-LB series AND the process aggregate the workload model reads."""
    for target in ((None, lb) if lb else (None,)):
        hb, hd = conn_hists(target)
        hb.observe(nbytes)
        hd.observe(dur_ms)


def conn_merge(lb: Optional[str], which: str, bucket_deltas,
               sum_delta: float, count: int) -> None:
    """Fold C-side pre-bucketed per-connection counts (accept lanes,
    vtl_lanes_capture_stat deltas) into the SAME series the python
    splice path populates — lane-served connections stop being
    invisible to the conn histograms. which: "bytes" | "duration_ms"."""
    idx = 0 if which == "bytes" else 1
    for target in ((None, lb) if lb else (None,)):
        conn_hists(target)[idx].merge(bucket_deltas, sum_delta, count)


def launch_inspection_http(loop, ip: str, port: int):
    """Serve /metrics, /lsof, /jstack, /events, /healthz — the
    reference's `-Dglobal_inspection=host:port` server (Main.java:
    85-104) plus the flight-recorder dump. Returns the HttpServer
    (close() to stop)."""
    from ..lib.vserver import HttpServer
    from . import failpoint, lifecycle
    from .events import FlightRecorder

    gi = GlobalInspection.get()
    srv = HttpServer(loop)
    srv.get("/metrics", lambda ctx: ctx.resp
            .header("Content-Type", "text/plain; version=0.0.4")
            .end(gi.prometheus_string()))
    srv.get("/lsof", lambda ctx: ctx.resp
            .header("Content-Type", "text/plain").end(gi.open_fd_dump()))
    srv.get("/jstack", lambda ctx: ctx.resp
            .header("Content-Type", "text/plain").end(gi.stack_trace_dump()))

    def events(ctx) -> None:
        try:
            last = int(ctx.req.query.get("n", "0"))
        except ValueError:
            last = 0
        try:  # ?trace=<id>: only events cross-referencing that trace
            tid = int(ctx.req.query.get("trace", "0"))
        except ValueError:
            tid = 0
        # ?plane=<p>: only events of that plane (utils/events.plane_of
        # — the analytics drill-down filter)
        plane = ctx.req.query.get("plane") or None

        # ?since=&until=: monotonic-ns bounds, the SAME clock trace
        # spans stamp t_ns with — a capture window joins against
        # recorder events without clock arithmetic
        def _ns(key):
            try:
                v = int(ctx.req.query.get(key, "0"))
            except ValueError:
                v = 0
            return v or None

        ctx.resp.end(FlightRecorder.get().snapshot(
            last, trace=tid or None, plane=plane,
            since=_ns("since"), until=_ns("until")))

    srv.get("/events", events)

    def analytics(ctx) -> None:
        # the heavy-hitter plane (utils/sketch): local top tables +
        # the fleet-merged view when a cluster is booted (one shared
        # assembly across all three serving surfaces)
        from . import sketch as SK
        out = SK.snapshot_with_fleet()
        # per-node policed attribution (the enforcement half of the
        # analytics loop — what the detected heavy hitters COST them)
        from ..cluster import ClusterNode
        from ..policing import engine as PE
        node = ClusterNode._instance
        out["policing"] = (node.fleet_policing() if node is not None
                           else {"self": PE.default().policed_by_node(),
                                 "peers": {}})
        ctx.resp.end(out)

    srv.get("/analytics", analytics)

    def policing_ep(ctx) -> None:
        # the Guardian enforcement surface (vproxy_tpu/policing):
        # engine status + declared policies + the live enforcement
        # table (per-key buckets with origin/ttl — local vs gossiped)
        from ..policing import engine as PE
        eng = PE.default()
        st = eng.status()
        st["policy_list"] = eng.list_policies()
        st["table"] = eng.table_snapshot()
        st["policed_by_node"] = eng.policed_by_node()
        st["shed_receipt"] = eng.shed_receipt()
        ctx.resp.end(st)

    srv.get("/policing", policing_ep)

    def workload_ep(ctx) -> None:
        # the capture artifact (utils/workload): the current window's
        # fitted model — tools/replay.py consumes this live
        from . import workload as WL
        ctx.resp.end(WL.export_model())

    srv.get("/workload", workload_ep)

    def trace_ep(ctx) -> None:
        # GET /trace -> recent trace summaries; ?id=<trace> -> that
        # trace's spans (start-time ordered); ?n= bounds the list
        from . import trace as TR
        try:
            tid = int(ctx.req.query.get("id", "0"))
        except ValueError:
            tid = 0
        if tid:
            ctx.resp.end({"trace": tid, "spans": TR.get_trace(tid)})
            return
        try:
            last = int(ctx.req.query.get("n", "64"))
        except ValueError:
            last = 64
        ctx.resp.end({"sample_every": TR.sample_every(),
                      "traces": TR.summaries(last)})

    srv.get("/trace", trace_ep)
    srv.get("/faults", lambda ctx: ctx.resp.end(failpoint.active()))

    def cluster(ctx) -> None:
        from ..cluster import ClusterNode
        node = ClusterNode._instance
        ctx.resp.end({"enabled": False} if node is None else node.status())

    srv.get("/cluster", cluster)

    def healthz(ctx) -> None:
        # draining flips to 503 so upstream LB health probes steer away
        # while in-flight sessions finish (utils/lifecycle)
        if lifecycle.is_draining():
            ctx.resp.status(503).end(b"draining")
        else:
            ctx.resp.end(b"OK")

    srv.get("/healthz", healthz)
    srv.listen(port, ip)
    return srv
