"""ClassifyEngine — the runtime seam between resources and the matchers.

This is the TPU analog of the reference's per-connection match loops:
components (Upstream, SecurityGroup, switch Table, DNSServer) register
their rules here; data-plane code calls the batched query API. Mirrors
the reference's provider SPI (-Dvfd, FDProvider.java:12-45) as
`backend="jax" | "jax-dense" | "host"`:

* "host"      — the pure-Python oracle (correctness fallback + latency
                floor for tiny tables).
* "jax"       — DEFAULT: cuckoo-hash classify kernels (ops/hashmatch):
                O(1) probes per query, byte-verified (exact regardless
                of hash behavior), gather-bound.
* "jax-fp"    — packed fingerprint kernels (ops/fphash): ~25x fewer
                gathered rows per query than "jax" (the measured cost
                driver in an earlier cost model). Exact for every key
                in the table; a query key NOT in the table can
                false-positive with probability 2^-64 per probe. No
                benchmark cell has run it on the chip yet.
* "jax-dense" — the dense matmul kernels (ops/matchers): O(rules) MXU
                work per query; kept as the brute-force cross-check and
                for rule-axis mesh sharding experiments.
* "jax-sharded" — the cuckoo-hash kernels SPMD over a (batch, rules)
                device mesh (parallel/mesh): each device holds a
                contiguous rule slice compiled into its own table and
                the winner rides pmax/pmin ICI collectives. Rule
                updates reuse caps (same shapes, no retrace); an update
                that outgrows the caps (ops.hashmatch.CapsExceeded)
                transparently rebuilds tables — the jitted fn simply
                retraces on the new shapes.
* "jax-fp-sharded" — the packed fingerprint kernels over the same mesh
                machinery: per-shard fp tables under one unified caps
                dict, same pmax/pmin winner reduction. The multi-chip
                form of the throughput path.

Rule updates never retrace: tables are fixed-capacity (padded), and an
update recompiles numpy arrays and re-uploads same-shape buffers.
Capacity (or a cuckoo bucket tier) grows when exceeded, which
recompiles the jitted matcher once for the new shapes.

Generation installs are DOUBLE-BUFFERED (the Pope MLSys'23 weight-swap
idiom applied to rule tables): set_rules()/set_networks() hand the new
rule list to a process-wide background installer (TableInstaller) that
compiles and device_puts a STANDBY table while dispatchers keep
serving the published generation, then publishes by one atomic tuple
swap. Dispatchers never wait on compilation — a 1M-rule compile, a
slow device upload, or an armed `engine.swap.stall` failpoint delays
only the install, never a query. Every publish bumps the matcher's
`generation`, records `vproxy_engine_swap_ms`, and refreshes the
`vproxy_engine_table_bytes{matcher}` accounting.
"""
from __future__ import annotations

import os
import threading
import time
import weakref
from itertools import repeat
from typing import NamedTuple, Optional, Sequence

import numpy as np

from ..ops import hashmatch as H
from ..ops import tables as T
from ..ops.bitmatch import unpack_bits
from ..ops.matchers import cidr_match_jit, hint_match_jit, table_arrays
from ..utils import heap, trace
from ..utils.log import Logger
from . import oracle
from .ir import AclRule, Hint, HintRule, Proto

_log = Logger("engine")


def mesh_serving() -> bool:
    """True when matchers without an explicit backend should serve SPMD
    over the device mesh. VPROXY_TPU_MESH_SERVE: "1"/"on" forces it,
    "0"/"off" disables, "auto" (default) shards whenever the mesh spans
    more than one REAL accelerator device. Virtual host-platform CPU
    devices (XLA_FLAGS=--xla_force_host_platform_device_count=N) are
    opt-in ("1"): they share one socket, so SPMD there buys rule-table
    capacity per device but ~3x dispatch latency (measured r08) — the
    right default for tests/bench scale runs, the wrong one for every
    small-table matcher in the process."""
    mode = os.environ.get("VPROXY_TPU_MESH_SERVE", "auto")
    if mode in ("0", "off", "no"):
        return False
    import jax
    try:
        devs = jax.devices()
    except Exception:
        return False
    if len(devs) <= 1:
        return False
    if mode in ("1", "on", "yes"):
        return True
    return devs[0].platform != "cpu"


def default_backend() -> str:
    """VPROXY_TPU_MATCHER when set; otherwise the mesh-sharded backend
    (VPROXY_TPU_MESH_BACKEND, default the byte-verified "jax-sharded")
    when mesh_serving() says the device mesh should carry the tables,
    else the single-device "jax" path."""
    env = os.environ.get("VPROXY_TPU_MATCHER")
    if env:
        return env
    if mesh_serving():
        return os.environ.get("VPROXY_TPU_MESH_BACKEND", "jax-sharded")
    return "jax"


_MESH: Optional[tuple] = None  # ((devices...), batch) -> Mesh


def default_mesh():
    """Process-wide (batch, rules) mesh for jax-sharded matchers; batch
    axis size from VPROXY_TPU_MESH_BATCH (default 1 = rules-only).

    Keyed on the CURRENT device set + batch knob, not cached forever: a
    device-count change after first use (a test-forced mesh, a late
    jax.distributed bring-up) must produce a fresh mesh, not silently
    serve the stale one."""
    global _MESH
    import jax
    from ..parallel import mesh as M
    batch = int(os.environ.get("VPROXY_TPU_MESH_BATCH", "1"))
    key = (tuple(jax.devices()), batch)
    if _MESH is None or _MESH[0] != key:
        _MESH = (key, M.make_mesh(batch=batch))
    return _MESH[1]


def pad_batch(n: int, mult: int = 1, lo: int = 16) -> int:
    """Batch-shape bucket: pow2 growth from `lo`, rounded up to a
    multiple of `mult` (the mesh batch-axis size, so the axis always
    divides the padded batch). ClassifyService uses the same buckets
    (mult=1) so the jitted matchers see few trace shapes."""
    c = lo
    while c < n:
        c <<= 1
    return -(-c // mult) * mult


# Below this rule count, single (unbatched) queries run on the host oracle:
# a python scan over a handful of rules is ~1us while a device dispatch is
# ~1ms — the device path wins only for big tables or batched queries. The
# device table is still compiled and kept in sync (used by match() batches).
SMALL_TABLE = int(os.environ.get("VPROXY_TPU_SMALL_TABLE", "128"))


def _to_device(arrs: dict) -> dict:
    import jax
    import jax.numpy as jnp
    out = {}
    for k, v in arrs.items():
        if v.dtype == np.float32 and v.ndim == 2:  # matmul weights -> bf16
            out[k] = jax.device_put(jnp.asarray(v, dtype=jnp.bfloat16))
        else:
            out[k] = jax.device_put(v)
    return out


def _sync_standby(dev) -> None:
    """Materialize a standby table's device buffers BEFORE the publish
    swap: device_put is async, and an unsynced publish makes the first
    post-swap dispatch eat the whole table transfer (measured ~30ms
    spikes at 20k rules — the install thread must pay that wait, never
    a serving thread). A failed transfer fails the install (the
    TableInstaller tickets the exception; the serving generation is
    unchanged)."""
    if dev:
        import jax
        jax.block_until_ready(list(dev.values()))


def _install_phase(tid: int, span: str, t0_ns: int, **fields) -> None:
    """One standby-install phase span (compile / upload / swap) on the
    installer's trace (utils/trace) — tid 0 (constructor compiles, or
    tracing off) records nothing."""
    if tid:
        trace.record_span(tid, "install", span, t0_ns,
                          time.monotonic_ns() - t0_ns, **fields)


# batch padding at the ARRAY level: a pad row must read as "no probes,
# no match" to the kernel. The fp query arrays (fingerprints, byte
# windows, flags) zero-fill — exactly what encoding an empty Hint()
# produces, without paying the encode for it. (The cuckoo encoder
# writes into its pad bucket itself: encode_hint_queries' pad_to.)
def _pad_hint_q(q: dict, cap: int) -> dict:
    out = {}
    for k, v in q.items():
        n = v.shape[0]
        if n >= cap:
            out[k] = v
            continue
        pad = np.zeros((cap - n,) + v.shape[1:], v.dtype)
        out[k] = np.concatenate([v, pad])
    return out


# --------------------------------------------- generation-install plumbing
#
# Process-wide accounting of published table generations, surfaced on
# /metrics (utils/metrics) and in `list-detail upstream`:
#   vproxy_engine_generation      — total generation publishes
#   vproxy_engine_swap_ms         — install latency histogram (compile +
#                                   upload + publish, background thread)
#   vproxy_engine_table_bytes{matcher="hint"|"cidr"} — device bytes of
#                                   every live matcher's published table
#   vproxy_engine_cidr_bucket_width / _lookup_hops / _overflow_share —
#                                   the cidr hash tables' bucket layout
#                                   (cidr_bucket_stat)

_gen_lock = threading.Lock()
_GENERATION = [0]
_MATCHERS: "weakref.WeakSet" = weakref.WeakSet()
_LAST_SERVE = [0.0]  # monotonic ts of the last serving-path read
_LAUNCHES = [0]      # device launches on the dispatch path (per batch)
_HOST_ARRAYS = [0]   # numpy arguments those launches were handed
_FUSED_DISP = [0]    # of which: fused one-launch dispatches


def note_serving() -> None:
    """Serving-path breadcrumb (one float store): dispatch_snap /
    index_snap and the classify submit path mark activity so the
    installer only PACES standby compiles when there is serving
    latency to protect — a batch config apply on an idle process
    builds at full speed."""
    _LAST_SERVE[0] = time.monotonic()


def serving_recent(window_s: float = 5.0) -> bool:
    return time.monotonic() - _LAST_SERVE[0] < window_s


def generation_total() -> int:
    return _GENERATION[0]


def note_launch(n: int = 1) -> None:
    """Count one device launch on the dispatch path (a lock-free int
    store race can only lose a count, never corrupt — same contract as
    the C-side counters). This is what makes the fused path's
    one-launch-per-batch claim SCRAPE-verifiable
    (vproxy_engine_dispatch_launches_total) instead of bench-asserted:
    every jitted submit site increments it, so fused batches move the
    counter by exactly 1 and the unfused chain by one per chained op."""
    _LAUNCHES[0] += n


def _host_arrays(args) -> tuple:
    """-> (how many, bytes of) the numpy values among a jitted call's
    arguments, dicts and sequences walked: what the call uploads
    implicitly. Device arrays (the resident tables, queries a sharded
    backend has placed itself) count nothing."""
    n = nbytes = 0
    todo = [args]
    while todo:
        a = todo.pop()
        if isinstance(a, (np.ndarray, np.generic)):
            n += 1
            nbytes += a.nbytes
        elif isinstance(a, dict):
            todo.extend(a.values())
        elif isinstance(a, (tuple, list)):
            todo.extend(a)
    return n, nbytes


def launch_span(kind: str, bucket: int, fused: bool = False, args=(),
                host_arrays: int = 1):
    """Count one device launch (note_launch) and time it: the `launch`
    span (utils/trace) around the jitted call itself. That call
    enqueues the program AND uploads its numpy arguments — the served
    path makes no explicit device_put, so the two are one number here;
    what tells them apart is what the call was handed: args, the
    call's arguments, of which the span's `items` are the numpy ones
    and `h2d_bytes` their bytes. host_arrays: how many of them are
    numpy, as the call site knows it — always counted
    (vproxy_engine_launch_host_arrays_total: over the launches, 1.0
    where every launch is handed one packed arena), the walk over args
    only while tracing. (No `cpu_ns`: the thread CPU clock of
    the chip's host moves in 10 ms ticks and read 0.21 of the wall over
    busy stretches of 1 ms — PERF.md §7.)
    fused vs unfused is distinguishable per launch, so a sampled
    request's trace shows how many programs its batch really cost.
    bucket: the padded batch the program was compiled for."""
    note_launch()
    _HOST_ARRAYS[0] += host_arrays
    n = nbytes = 0
    if trace.SAMPLE:
        n, nbytes = _host_arrays(args)
    return trace.span("engine", "launch", items=n, kind=kind, fused=fused,
                      bucket=bucket, h2d_bytes=nbytes, parent="dispatch")


def encode_span(items: int):
    """The `encode` span around the host encode + padding of one batch.
    items: real queries encoded (0 where a second encoder handles the
    same queries, so a batch's queries count once)."""
    return trace.span("engine", "encode", cpu=True, items=items,
                      parent="dispatch")


def dispatch_launches_total() -> int:
    return _LAUNCHES[0]


def launch_host_arrays_total() -> int:
    return _HOST_ARRAYS[0]


def fused_dispatches_total() -> int:
    return _FUSED_DISP[0]


def table_bytes_total(kind: str) -> int:
    """Sum of published device-table bytes across live matchers of one
    kind ("hint" | "cidr"). The WeakSet snapshot rides _gen_lock —
    matcher constructors add concurrently, and CPython raises on a set
    mutated mid-iteration (a scrape must never lose to a config
    apply)."""
    with _gen_lock:
        matchers = list(_MATCHERS)
    total = 0
    for m in matchers:
        if m._kind == kind:
            total += m.published_table_bytes()
    return total


def cidr_bucket_stat() -> dict:
    """Bucket layout of the live "jax" / "jax-sharded" cidr tables, for
    /metrics: the widest bucket row, the most hops a lookup makes, and
    the share of used cuckoo slots that own an overflow row — whether
    the deployed tables are in the one-hop case (hops 1, share 0)."""
    with _gen_lock:
        matchers = list(_MATCHERS)
    stats = [b for b in (m.bucket_stat() for m in matchers
                         if m._kind == "cidr") if b]
    used = sum(b["used_slots"] for b in stats)
    return {"width": max((b["width"] for b in stats), default=0),
            "hops": max((b["hops"] for b in stats), default=0),
            "overflow_share": sum(b["overflow_slots"] for b in stats)
            / used if used else 0.0}


def _swap_hist():
    # pre-registered (reservoir config included) in
    # GlobalInspection.__init__ — this resolves to that instance
    from ..utils.metrics import GlobalInspection
    return GlobalInspection.get().get_histogram("vproxy_engine_swap_ms")


class _InstallTicket:
    """One caller's claim on a pending install; `exc` carries the
    compile failure back to a waiting set_rules()."""

    __slots__ = ("ev", "exc")

    def __init__(self):
        self.ev = threading.Event()
        self.exc: Optional[BaseException] = None


class TableInstaller:
    """The double-buffer worker: compiles + uploads STANDBY tables off
    the mutation path, one install at a time, then lets the matcher
    publish with an atomic tuple swap.

    * set_rules()/set_networks() enqueue (args, payload) and by default
      WAIT for the publish (read-your-writes for config handlers and
      the cluster replication checksum gate); wait=False callers get a
      ticket they can ignore.
    * dispatchers never wait: they read the published snapshot, which
      only ever changes by one atomic assignment AFTER the standby
      table is fully built and uploaded.
    * back-to-back installs for one matcher COALESCE: only the newest
      pending rule list compiles; earlier waiters are released by the
      newer publish (their write was superseded — same last-writer-wins
      outcome as racing synchronous compiles, at one compile's cost).
    * the compile yields the GIL between phases (sleep(0)) so a
      million-rule build starves inline accept-path answers by at most
      one interpreter slice, not whole seconds.
    * failpoint `engine.swap.stall` sleeps VPROXY_TPU_SWAP_STALL_S
      inside the worker — the provable "slow install stalls nothing"
      edge.
    """

    _instance: Optional["TableInstaller"] = None
    _ilock = threading.Lock()

    @classmethod
    def get(cls) -> "TableInstaller":
        with cls._ilock:
            if cls._instance is None:
                cls._instance = TableInstaller()
            return cls._instance

    def __init__(self):
        self._cv = threading.Condition()
        # id(matcher) -> (matcher, args, [tickets]); order preserved
        self._jobs: dict[int, tuple] = {}
        self._order: list[int] = []
        self._inflight = 0
        self._thread: Optional[threading.Thread] = None

    def submit(self, matcher, args: tuple) -> _InstallTicket:
        t = _InstallTicket()
        with self._cv:
            key = id(matcher)
            job = self._jobs.get(key)
            if job is None:
                self._jobs[key] = (matcher, args, [t])
                self._order.append(key)
            else:  # coalesce: newest rules win, all waiters ride along
                self._jobs[key] = (matcher, args, job[2] + [t])
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name="engine-install", daemon=True)
                self._thread.start()
            self._cv.notify()
        return t

    def flush(self, timeout: Optional[float] = None) -> bool:
        """Block until every pending install published (True) or the
        timeout passed (False). The cluster replication gate calls this
        before checksumming so a wait=False mutation can never pair an
        old table checksum with a new generation."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while self._jobs or self._inflight:
                left = None if deadline is None \
                    else deadline - time.monotonic()
                if left is not None and left <= 0:
                    return False
                self._cv.wait(0.05 if left is None else min(left, 0.05))
        return True

    def _run(self) -> None:
        from ..ops.cuckoo import set_build_pacing
        from ..utils import failpoint
        try:
            # background-priority: the standby compile must lose every
            # scheduling fight with a serving thread. GIL handoff is
            # interval-driven either way (service shrinks it to ~1ms),
            # but the compile's GIL-released phases (numpy, XLA
            # compile, device transfers) otherwise steal the serving
            # path's cores — measured 5x p99 inflation on a shared
            # socket without this.
            os.setpriority(os.PRIO_PROCESS, threading.get_native_id(), 15)
        except (AttributeError, OSError, PermissionError):
            pass  # non-linux / restricted: yields below still apply
        while True:
            with self._cv:
                while not self._order:
                    self._cv.wait(1.0)
                key = self._order.pop(0)
                matcher, args, tickets = self._jobs.pop(key)
                self._inflight += 1
            exc: Optional[BaseException] = None
            try:
                if failpoint.hit("engine.swap.stall"):
                    # a deliberately slow compile: dispatch must keep
                    # answering the old generation for this whole sleep
                    time.sleep(float(os.environ.get(
                        "VPROXY_TPU_SWAP_STALL_S", "0.5")))
                # standby-compile pacing: each cooperative yield in the
                # build hot loops sleeps ~r x the work since the last
                # one, capping this thread's CPU/GIL duty at 1/(1+r). A
                # full-speed compile costs serving threads ~half the
                # GIL (measured ~2.5x dispatch p99); pacing trades
                # install latency (background, invisible by design)
                # for flat serving latency. Re-read per job:
                # VPROXY_TPU_INSTALL_PACE=0 disables (tests, batch
                # loads with no concurrent serving). Applied ONLY
                # when the serving path was active in the last few
                # seconds (note_serving) — an idle batch apply
                # builds at full speed.
                set_build_pacing(float(os.environ.get(
                    "VPROXY_TPU_INSTALL_PACE", "6"))
                    if serving_recent() else 0.0)
                t0 = time.monotonic()
                time.sleep(0)  # explicit preemption point pre-compile
                # installs are rare: when tracing is on, EVERY install
                # gets its own trace — _recompile's phase spans
                # (compile / upload / swap) attach through the bound
                # context, so an install-under-load trace shows the
                # standby build bracketing unstalled dispatches
                itid = trace.new_trace_id() if trace.enabled() else 0
                with trace.bind(itid):
                    matcher._install(args)
                if itid:
                    trace.record_span(
                        itid, "install", "install", int(t0 * 1e9),
                        int((time.monotonic() - t0) * 1e9),
                        matcher=getattr(matcher, "_kind", "?"))
                _swap_hist().observe((time.monotonic() - t0) * 1e3)
                # the generation just published is long-lived: out of
                # the collector's reach before the waiters go on, so
                # no later full collection walks it (utils/heap)
                heap.settle("publish", idle=not serving_recent())
            except MemoryError as e:
                # OOM keeps the log-then-die contract (utils/oom), but
                # the waiters must still see a FAILED install — a
                # survivor embedding without the oom handler would
                # otherwise ack a mutation that never landed
                exc = e
                raise
            except BaseException as e:  # noqa: BLE001 — ticketed
                exc = e
                _log.error("standby table install failed; serving "
                           "generation unchanged", exc=True)
            finally:
                with self._cv:
                    self._inflight -= 1
                    self._cv.notify_all()
                for t in tickets:
                    t.exc = exc
                    t.ev.set()


def flush_installs(timeout: Optional[float] = None) -> bool:
    """Convenience: wait for all pending generation installs (no-op
    when the installer never started)."""
    inst = TableInstaller._instance
    return True if inst is None else inst.flush(timeout)


# --------------------------------------------- fused classify+pick entry
#
# ops/fused.py packs the compiled hash tables into int8/int32 layouts
# (one meta row + one byte row per rule, one slot row per cuckoo slot)
# and compiles the whole dispatch chain — probe, gather, verdict
# resolve, Maglev pick — into ONE jitted program. A "jax"-backend hint
# matcher always publishes them; every other backend serves the
# two-dispatch chain (fused_dispatch -> None). The packed arrays are built INSIDE the matcher's standby
# compile below, so they publish through the same TableInstaller
# atomic-swap as every other table: a fused reader can never pair one
# generation's probe salts with another's packed records.

def _fused_stat(fd: Optional[dict]) -> dict:
    """Fused-dispatch state for the operator surfaces (list-detail
    upstream / HTTP engine object) — ONE shape for both matcher kinds:
    packed-table availability, device bytes, and the serving kernel
    (the constant "jit": ops/fused.fused_jit is the one tier)."""
    if fd is None:
        return {"available": False}
    return {"available": True, "kernel": "jit",
            "packed_bytes": int(sum(getattr(v, "nbytes", 0)
                                    for v in fd.values()))}


def fused_dispatch(hm, hsnap: tuple, mm, msnap: tuple, hints,
                   ips: Sequence[bytes],
                   ports: Optional[Sequence[int]] = None,
                   pad_to: Optional[int] = None):
    """ONE launch answering (verdict, pick) for a batch: encoded hint
    queries + host-side Maglev slots into the fused program against
    one (hint, maglev) snapshot pair. Returns the async int32 [B, 2]
    device array, or None when the fused path is unavailable for
    these snapshots (non-"jax" backend or a pre-fused publish) —
    callers fall back to the two-dispatch chain."""
    if not hints or len(hints) != len(ips):
        return None
    fd = hsnap[5] if len(hsnap) > 5 else None
    if fd is None or not hsnap[2]:
        return None
    mtab, mdev = msnap[0], msnap[1]
    if mtab is None or mdev is None:
        return None
    q = _fused_encode(hsnap, len(mtab), hints, ips, ports, pad_to)
    from ..ops import fused as F
    with launch_span("cpick", len(q.slots), fused=True,
                     args=(fd, mdev, q.arena)):
        return F.fused_jit(fd, mdev, q.arena, q.layout)


def grouped_dispatch(hsnap: tuple, ssnap, m: int, hints,
                     ips: Sequence[bytes],
                     ports: Optional[Sequence[int]] = None,
                     pad_to: Optional[int] = None):
    """fused_dispatch for a maglev.GroupedPair: ONE launch answering
    (verdict, pick from the table of the group the matched rule names)
    against one (hint, pick-table set) snapshot pair — the rule -> group
    column from the hint generation, the tables and their owner tokens
    from the set's. None when either side has no device form (a backend
    other than "jax", a hint generation installed without a group
    column, a set that holds no table): the pair then picks on the
    host."""
    if not hints or len(hints) != len(ips):
        return None
    fd = hsnap[5] if len(hsnap) > 5 else None
    col = hsnap[6] if len(hsnap) > 6 else None
    if fd is None or not hsnap[2] or col is None or col[1] is None \
            or ssnap.dev is None:
        return None
    q = _fused_encode(hsnap, m, hints, ips, ports, pad_to)
    from ..ops import fused as F
    with launch_span("cpick", len(q.slots), fused=True,
                     args=(fd, col[1], ssnap.dev, q.arena)):
        return F.group_jit(fd, col[1], ssnap.dev[1], ssnap.dev[0], q.arena,
                           q.layout)


def _fused_encode(hsnap: tuple, m: int, hints, ips, ports,
                  pad_to: Optional[int]) -> H.QueryArena:
    """The host half of one fused batch, either program's: the encoded
    hint queries and, in the same arena, the Maglev slots of a table of
    m slots, at the batch's bucket; counts the fused dispatch."""
    note_serving()
    q = _fused_hint_q(hsnap[0], hints, pad_to, slots=True)
    _FUSED_DISP[0] += 1
    _fused_slots(m, ips, ports, q.slots)
    return q


def _fused_hint_q(tab, hints, pad_to: Optional[int],
                  slots: bool = False) -> H.QueryArena:
    with encode_span(len(hints)):
        return H.encode_hint_queries(hints, tab, pad_to=pad_to or 0,
                                     slots=slots)


def _encode_addrs(addrs, ports, pad_to: Optional[int], items: int,
                  tid: bool = False) -> H.QueryArena:
    """One cidr batch at its bucket, the columns a16, fam and (ports
    given) port of one arena, with room for a table-id column where
    `tid`: family -1 marks pad rows — they match no group and walk no
    trie."""
    with encode_span(items):
        q = H.cidr_queries(max(len(addrs), pad_to or 0),
                           gated=ports is not None, tid=tid)
        T.encode_ips(addrs, out=(q["a16"], q["fam"]))
        if ports is not None:
            q["port"][:len(addrs)] = ports
    return q


def _fused_slots(m: int, ips, ports, col: np.ndarray) -> None:
    """Host-side Maglev slots of a table of m slots (maglev.flow_slots
    — THE one copy of the slot-hash contract, so fused picks are
    bit-identical to every other pick plane) into the arena's slot
    column; pad rows ride slot 0 and are sliced off by the caller."""
    from .maglev import flow_slots
    with encode_span(0):    # the batch's queries count at the hint encode
        col[:len(ips)] = flow_slots(m, ips, ports)


class HintMatcher:
    """Device-backed (or host-fallback) Upstream/DNS hint matcher."""

    _kind = "hint"

    def __init__(self, rules: Sequence[HintRule] = (), backend: Optional[str] = None,
                 payload=None, mesh=None):
        self.backend = backend or default_backend()
        self._rules: list[HintRule] = list(rules)
        self._dev: Optional[dict] = None
        self._tab = None  # hash-path table meta
        self._caps: Optional[dict] = None
        self._mesh = mesh  # jax-sharded only (lazily defaulted)
        self._fn = None    # jax-sharded jitted matcher (shape-agnostic)
        self.generation = 0  # bumps on every publish (atomic swap)
        # (tab, dev, rules, payload, index) published as ONE tuple so
        # concurrent readers (the ClassifyService dispatcher) never see a
        # torn table/rule/payload version across a set_rules() swap;
        # `payload` is an opaque owner-supplied object versioned WITH the
        # rules (e.g. Upstream's GroupHandle list) so a matched index is
        # always interpreted against the same generation it was matched
        # in; `index` is the O(probes) host-side HintIndex the service's
        # auto mode answers every query from (rules/index.py);
        # then the packed fused tables, and the rule -> group column of
        # a maglev.GroupedPair's matcher (refs, device [r_cap, 2]) —
        # None unless set_rules was given `groups`
        self._pub: tuple = (None, None, [], payload, None)
        self._payload = payload
        self._groups: Optional[list] = None
        self._cksum = None  # (pub-tuple, crc32) cache — see checksum()
        self._recompile()
        with _gen_lock:
            _MATCHERS.add(self)

    @property
    def rules(self) -> list[HintRule]:
        return list(self._pub[2])  # the PUBLISHED generation

    def set_rules(self, rules: Sequence[HintRule], payload=None,
                  wait: bool = True,
                  groups: Optional[Sequence[int]] = None) -> None:
        """Install a new rule generation via the background
        TableInstaller (standby compile + atomic publish). wait=True
        (default) blocks THIS caller until the publish — dispatchers
        never block either way; wait=False returns immediately (the
        caller reads the old generation until the swap lands).
        groups: per rule, the ref of the maglev.MaglevTableSet row its
        server-group owns (-1: none) — published in the same tuple as
        the tables, so a verdict is never read against another
        generation's column."""
        if groups is not None and len(groups) != len(rules):
            raise ValueError(f"{len(groups)} group refs for "
                             f"{len(rules)} rules")
        t = TableInstaller.get().submit(
            self, (list(rules), payload,
                   None if groups is None else list(groups)))
        if wait:
            t.ev.wait()
            if t.exc is not None:
                raise t.exc

    def _install(self, args: tuple) -> None:
        """TableInstaller worker entry: compile + publish one standby
        generation (never called concurrently — one installer thread).
        Transactional: a failed compile restores the serving rule list
        so every read surface still describes the published table."""
        rules, payload = args[:2]
        groups = args[2] if len(args) > 2 else None
        old = (self._rules, self._payload, self._tab, self._dev,
               self._caps, self._groups)
        self._rules = list(rules)
        self._payload = payload
        self._groups = groups
        try:
            self._recompile()
        except BaseException:
            # restore EVERYTHING a reader or the next recompile touches
            # — a half-updated (_tab, _dev) pair would hash queries
            # with one generation's salts against the other's table
            (self._rules, self._payload, self._tab, self._dev,
             self._caps, self._groups) = old
            raise

    def published_table_bytes(self) -> int:
        """Device bytes of the published generation's table arrays."""
        dev = self._pub[1]
        if not dev:
            return 0
        return int(sum(getattr(v, "nbytes", 0) for v in dev.values()))

    def _recompile(self) -> None:
        itid = trace.current_id()  # nonzero only under a traced install
        t_ph = time.monotonic_ns() if itid else 0
        if self.backend == "jax":
            self._tab = H.compile_hint_hash(self._rules, caps=self._caps)
            self._caps = self._tab.caps
            self._dev = _to_device(self._tab.arrays)
        elif self.backend == "jax-fp":
            from ..ops import fphash as F
            try:
                self._tab = F.compile_hint_fp(self._rules, caps=self._caps)
            except H.CapsExceeded:
                # update outgrew the reused shapes: fresh build (the
                # jitted matcher retraces on the new shapes)
                self._tab = F.compile_hint_fp(self._rules)
            self._caps = self._tab.caps
            self._dev = _to_device(self._tab.arrays)
        elif self.backend in ("jax-sharded", "jax-fp-sharded"):
            from ..parallel import mesh as M
            if self._mesh is None:
                self._mesh = default_mesh()
            shards = self._mesh.shape["rules"]
            if self.backend == "jax-fp-sharded":
                from ..ops import fphash as F
                compile_sharded = F.compile_hint_fp_sharded
            else:
                compile_sharded = H.compile_hint_hash_sharded
            try:
                self._tab = compile_sharded(self._rules, shards,
                                            caps=self._caps)
            except H.CapsExceeded:
                # update outgrew the reused shapes: transparent rebuild
                # (the jitted fn retraces on the new shapes)
                self._tab = compile_sharded(self._rules, shards)
            self._caps = self._tab.shards[0].caps
            self._dev = M.shard_hash_table(self._tab, self._mesh)
            # memory-lean: the stacked host copy is dead weight once the
            # device holds the shards (a 1M-rule standby would otherwise
            # hold table bytes THREE times mid-install); ndims survive
            # for the jitted-fn spec build
            M.release_host(self._tab)
            # _fn is NOT reset: it closes over key ndims + kernel only,
            # and jit re-specializes on shape changes by itself — the
            # caps-reuse no-retrace contract depends on keeping it
        elif self.backend == "jax-dense":
            cap = self._dev["active"].shape[0] if self._dev is not None else None
            if cap is not None and len(self._rules) > cap:
                cap = None  # outgrew capacity: let the compiler pick a bucket
            tab = T.compile_hint_rules(self._rules, cap=cap)
            self._dev = _to_device(table_arrays(tab))
        idx = None
        # small tables answer lone queries with the linear oracle (the
        # same crossover match_one uses), so the index build — a second
        # O(rules) bucket construction on the update path — only pays
        # for itself past SMALL_TABLE. Built for EVERY backend: the
        # inline accept path serves host-backend matchers too, and a
        # big table must never put an O(rules) scan on an event loop
        if len(self._rules) > SMALL_TABLE:
            from .index import HintIndex
            idx = HintIndex(self._rules)
        # packed fused-dispatch tables (ops/fused.py): built in THIS
        # standby compile and published in the SAME atomic tuple swap —
        # the fused reader's generation consistency is the pub tuple's
        fused_dev = None
        if self.backend == "jax":
            from ..ops import fused as F
            fused_dev = _to_device(F.pack_hint_table(self._tab.arrays))
        group_col = None    # (refs, their device column or None)
        if self._groups is not None:
            gdev = None
            if fused_dev is not None:
                import jax
                from .maglev import group_column
                gdev = jax.device_put(group_column(
                    self._groups, fused_dev["pk_meta"].shape[0]))
            group_col = (self._groups, gdev)
        _install_phase(itid, "compile", t_ph, matcher="hint",
                       rules=len(self._rules))
        t_ph = time.monotonic_ns() if itid else 0
        _sync_standby(self._dev)
        _sync_standby(fused_dev)
        if group_col is not None:
            _sync_standby({"rule_group": group_col[1]})
        _install_phase(itid, "upload", t_ph, matcher="hint")
        time.sleep(0)  # preemption point between compile and publish
        t_ph = time.monotonic_ns() if itid else 0
        self._pub = (self._tab, self._dev, list(self._rules), self._payload,
                     idx, fused_dev, group_col)
        self.generation += 1
        with _gen_lock:
            _GENERATION[0] += 1
        _install_phase(itid, "swap", t_ph, matcher="hint",
                       generation=self.generation)

    def encode(self, hints: Sequence[Hint]) -> H.QueryArena:
        """Pre-encode a query batch for submit() (hash backend only).
        Bound to the current table version — re-encode after set_rules."""
        assert self.backend == "jax"
        return H.encode_hint_queries(hints, self._tab)

    def submit(self, q: H.QueryArena):
        """Dispatch an encoded batch; returns the device array (async)."""
        with launch_span("hint", q["hostb"].shape[0],
                         args=(self._dev, q.arena)):
            idx, _ = H.hint_hash_jit(self._dev, q.arena, q.layout)
        return idx

    def fused_stat(self) -> dict:
        """See engine._fused_stat — packed hint-table state."""
        pub = self._pub
        return _fused_stat(pub[5] if len(pub) > 5 else None)

    def match(self, hints: Sequence[Hint]) -> np.ndarray:
        """-> int32 [B] matched rule index, -1 for none."""
        snap = self._pub
        if self.backend == "host" and snap[2] and hints:
            return np.array([oracle.search(snap[2], h) for h in hints],
                            np.int32)
        return np.asarray(self.dispatch_snap(snap, hints))

    def match_one(self, hint: Hint) -> int:
        # PUBLISHED rules, never self._rules: a standby install mutates
        # the latter seconds before the atomic publish, and a serving
        # read must not route by a generation no surface reports yet
        pub = self._pub
        if self.backend != "host" and len(pub[2]) <= SMALL_TABLE:
            return oracle.search(pub[2], hint)
        return int(self.match([hint])[0])

    # ---- ClassifyService API (rules/service.py) ----

    def size(self) -> int:
        return len(self._pub[2])

    def checksum(self) -> int:
        """u32 checksum of the PUBLISHED rule generation (crc32 over the
        canonical rule reprs): two hosts whose tables compiled from the
        same rule list hash identically regardless of caps-growth
        history. The cluster replication gate (cluster/replicate.py)
        compares this across hosts before installing a generation.
        Computed once per generation (cached at publish): replication
        polls read it every few hundred ms and must not pay an O(rules)
        string build each time."""
        pub = self._pub
        cached = self._cksum
        if cached is not None and cached[0] is pub:
            return cached[1]
        import zlib
        v = zlib.crc32("\n".join(map(repr, pub[2])).encode())
        self._cksum = (pub, v)
        return v

    def snapshot(self) -> tuple:
        """One consistent (table, device, rules, payload) generation."""
        return self._pub

    @staticmethod
    def snap_payload(snap: tuple):
        return snap[3]

    def oracle_snap(self, snap: tuple, hint: Hint) -> int:
        return oracle.search(snap[2], hint)

    def index_snap(self, snap: tuple, hint: Hint) -> int:
        """O(probes) host lookup against the snapshot's HintIndex (same
        winner as oracle_snap); falls back to the linear oracle when the
        snapshot has no index (host backend)."""
        note_serving()
        idx = snap[4] if len(snap) > 4 else None
        if idx is None:
            return oracle.search(snap[2], hint)
        return idx.lookup(hint)

    def oracle_one(self, hint: Hint) -> int:
        return self.oracle_snap(self._pub, hint)

    def dispatch_snap(self, snap: tuple, hints: Sequence[Hint],
                      pad_to: Optional[int] = None, sync: bool = True):
        """Encode + submit one batch against the snapshotted table
        generation (async device result; np.asarray() it to block).

        pad_to: target batch shape (a pad_batch bucket). The hash
        backends encode ONLY the real hints and zero/invalid-fill the
        probe arrays to the bucket — the dispatch path never pays the
        rolling-hash passes for padding rows (they cost the same numpy
        work as real queries).

        sync=False (the service's double-buffered dispatcher): the
        sharded backends return the RAW padded device output instead of
        to_local()[:n] — to_local materializes (np.asarray) on a
        single process, which would silently turn the "async" submit
        into a full round-trip wait. The caller np.asarray()s and
        slices at finish time. Multi-process meshes still to_local here
        (shard dedup needs it)."""
        note_serving()
        tab, dev, rules = snap[0], snap[1], snap[2]
        if not rules or not hints:
            return np.full(len(hints), -1, np.int32)
        # every branch below is one dispatch: one encode span (host
        # encode + padding) and one launch span (the jitted call)
        n = len(hints)
        if self.backend == "jax":
            # ONE copy of the encode idiom, shared with the fused
            # entry: the encoder hashes the real rows only and writes
            # them into the padded bucket, pad rows invalid probes
            q = _fused_hint_q(tab, hints, pad_to)
            with launch_span("hint", q["hostb"].shape[0],
                             args=(dev, q.arena)):
                idx, _ = H.hint_hash_jit(dev, q.arena, q.layout)
            return idx
        if self.backend == "jax-fp":
            from ..ops import fphash as F
            with encode_span(n):
                q = F.encode_hint_queries_fp(hints, tab)
                if pad_to and pad_to > n:
                    q = _pad_hint_q(q, pad_to)
            # resolve the member-mode env knob HERE, per dispatch: jit
            # keys on the static mode arg, so passing None would bake
            # the first dispatch's VPROXY_TPU_FP_MEMBER into the cache
            # and silently ignore later changes (stale lowering)
            with launch_span("hint", max(n, pad_to or 0), args=(dev, q),
                             host_arrays=len(q)):
                idx, _ = F.hint_fp_jit(dev, q,
                                       mode=F.default_member_mode())
            return idx
        if self.backend in ("jax-sharded", "jax-fp-sharded"):
            from ..parallel import mesh as M
            from ..parallel.mesh import query_shards
            cap = pad_batch(max(n, pad_to or 0), query_shards(self._mesh))
            with encode_span(n):
                if self.backend == "jax-fp-sharded":
                    from ..ops import fphash as F
                    padded = list(hints) + [Hint()] * (cap - n)
                    q = F.encode_hint_queries_fp_sharded(padded, tab)
                    kernel = F.hint_fp_match
                else:
                    # single-pass multi-salt encode: one rolling-hash
                    # pass serves every shard (the old path re-encoded
                    # per shard — 8x the host cost of the whole
                    # dispatch)
                    q = H.encode_hint_queries_sharded(hints, tab,
                                                      pad_to=cap)
                    kernel = None
            qd = M.shard_hint_queries_sharded(q, self._mesh)
            if self._fn is None:
                self._fn = M.make_sharded_hint_fn(
                    self._mesh, {k: v.ndim for k, v in tab.arrays.items()},
                    {k: v.ndim for k, v in q.items()}, kernel=kernel)
            size = np.int32(tab.shard_size)
            with launch_span("hint", cap, args=(dev, qd, size)):
                out = self._fn(dev, qd, size)
            if not sync:
                import jax
                if jax.process_count() <= 1:
                    return out  # async: caller syncs + slices
            # to_local: this process's slice on a multi-process mesh,
            # plain np.asarray single-process
            return M.to_local(out)[:n]
        with encode_span(n):
            if pad_to and pad_to > n:
                hints = list(hints) + [Hint()] * (pad_to - n)
            q = T.encode_hints(hints)
        with launch_span("hint", len(hints), args=(dev, q), host_arrays=5):
            idx, _ = hint_match_jit(
                dev, q["host"], q["has_host"], unpack_bits(q["uri"]),
                q["has_uri"], q["port"])
        return idx


def _cidr_scan(nets, acl, addr: bytes, port: Optional[int]) -> int:
    """The ordered scan (RouteTable.lookup / SecurityGroup.allow): index
    of the first network that holds addr and, for an ACL asked with a
    port, whose range holds the port; -1 for none."""
    for j, net in enumerate(nets):
        if net.contains_ip(addr) and (
                port is None or acl is None or
                (acl[j].min_port <= port <= acl[j].max_port)):
            return j
    return -1


def _cidr_checksum(nets, acl) -> int:
    import zlib
    text = "\n".join(map(repr, nets))
    if acl is not None:
        text += "\n" + "\n".join(map(repr, acl))
    return zlib.crc32(text.encode())


class CidrMatcher:
    """Device-backed ordered first-match CIDR matcher (routes / ACL)."""

    _kind = "cidr"

    def __init__(self, networks: Sequence = (), backend: Optional[str] = None,
                 acl: Optional[Sequence[AclRule]] = None, payload=None,
                 mesh=None):
        self.backend = backend or default_backend()
        self._nets = list(networks)
        self._acl = list(acl) if acl is not None else None
        self._dev: Optional[dict] = None
        self._caps: Optional[dict] = None
        self._tab = None   # jax-sharded stacked table meta
        self._buckets: Optional[dict] = None  # see bucket_stat()
        self._mesh = mesh  # jax-sharded only (lazily defaulted)
        self._fns: dict = {}  # jax-sharded jitted fns keyed by with_port
        self.generation = 0  # bumps on every publish (atomic swap)
        # (dev, nets, acl, payload, tab, index) — one atomic generation
        # (see HintMatcher._pub for the why)
        self._pub: tuple = (None, [], None, payload, None, None)
        self._payload = payload
        self._cksum = None  # (pub-tuple, crc32) cache — see checksum()
        self._recompile()
        with _gen_lock:
            _MATCHERS.add(self)

    def set_networks(self, networks: Sequence, acl: Optional[Sequence[AclRule]] = None,
                     payload=None, wait: bool = True) -> None:
        """Install a new generation via the background TableInstaller
        (see HintMatcher.set_rules — same standby-swap contract)."""
        t = TableInstaller.get().submit(
            self, (list(networks),
                   list(acl) if acl is not None else None, payload))
        if wait:
            t.ev.wait()
            if t.exc is not None:
                raise t.exc

    def _install(self, args: tuple) -> None:
        """See HintMatcher._install — transactional standby compile."""
        networks, acl, payload = args
        old = (self._nets, self._acl, self._payload, self._tab,
               self._dev, self._caps, self._buckets)
        self._nets = list(networks)
        self._acl = list(acl) if acl is not None else None
        self._payload = payload
        try:
            self._recompile()
        except BaseException:
            (self._nets, self._acl, self._payload, self._tab,
             self._dev, self._caps, self._buckets) = old
            raise

    def published_table_bytes(self) -> int:
        dev = self._pub[0]
        if not dev:
            return 0
        return int(sum(getattr(v, "nbytes", 0) for v in dev.values()))

    def bucket_stat(self) -> Optional[dict]:
        """The installed hash table's bucket layout (hashmatch
        HashCidrTable.buckets; summed over the shards of a sharded
        table): row width, hops a lookup makes, used slots and how many
        of them own an overflow row. None on the backends with another
        layout. Surfaced in `list-detail security-group` and, over all
        live tables, on /metrics (cidr_bucket_stat)."""
        return self._buckets

    def _recompile(self) -> None:
        itid = trace.current_id()  # nonzero only under a traced install
        t_ph = time.monotonic_ns() if itid else 0
        if self.backend == "jax":
            tab = H.compile_cidr_hash(self._nets, acl=self._acl, caps=self._caps)
            self._caps = tab.caps
            self._buckets = tab.buckets
            self._dev = _to_device(tab.arrays)
        elif self.backend == "jax-fp":
            from ..ops import fphash as F
            try:
                tab = F.compile_cidr_fp(self._nets, acl=self._acl,
                                        caps=self._caps)
            except H.CapsExceeded:
                tab = F.compile_cidr_fp(self._nets, acl=self._acl)
            self._caps = tab.caps
            self._dev = _to_device(tab.arrays)
        elif self.backend in ("jax-sharded", "jax-fp-sharded"):
            from ..parallel import mesh as M
            if self._mesh is None:
                self._mesh = default_mesh()
            shards = self._mesh.shape["rules"]
            if self.backend == "jax-fp-sharded":
                from ..ops import fphash as F
                compile_sharded = F.compile_cidr_fp_sharded
            else:
                compile_sharded = H.compile_cidr_hash_sharded
            try:
                self._tab = compile_sharded(
                    self._nets, shards, acl=self._acl, caps=self._caps)
            except H.CapsExceeded:
                # update outgrew the reused shapes: transparent rebuild
                self._tab = compile_sharded(self._nets, shards,
                                            acl=self._acl)
            self._caps = self._tab.shards[0].caps
            if self.backend == "jax-sharded":
                per = [t.buckets for t in self._tab.shards]
                self._buckets = {
                    "width": per[0]["width"], "hops": per[0]["hops"],
                    "used_slots": sum(b["used_slots"] for b in per),
                    "overflow_slots": sum(b["overflow_slots"] for b in per)}
            self._dev = M.shard_hash_table(self._tab, self._mesh)
            M.release_host(self._tab)  # memory-lean: see HintMatcher
            # _fns kept: see HintMatcher._recompile
        elif self.backend == "jax-dense":
            cap = self._dev["allow"].shape[0] if self._dev is not None else None
            if cap is not None and len(self._nets) > cap:
                cap = None
            tab = T.compile_cidr_rules(self._nets, cap=cap, acl=self._acl)
            self._dev = _to_device(table_arrays(tab))
        idx = None
        if len(self._nets) > SMALL_TABLE:  # every backend: see HintMatcher
            from .index import CidrIndex
            idx = CidrIndex(self._nets, acl=self._acl)
        _install_phase(itid, "compile", t_ph, matcher="cidr",
                       rules=len(self._nets))
        t_ph = time.monotonic_ns() if itid else 0
        _sync_standby(self._dev)
        _install_phase(itid, "upload", t_ph, matcher="cidr")
        time.sleep(0)  # preemption point between compile and publish
        t_ph = time.monotonic_ns() if itid else 0
        self._pub = (self._dev, list(self._nets),
                     None if self._acl is None else list(self._acl),
                     self._payload, self._tab, idx)
        self.generation += 1
        with _gen_lock:
            _GENERATION[0] += 1
        _install_phase(itid, "swap", t_ph, matcher="cidr",
                       generation=self.generation)

    def match(self, addrs: Sequence[bytes],
              ports: Optional[Sequence[int]] = None) -> np.ndarray:
        """-> int32 [B] first matching rule index (order = insert order), -1
        for none."""
        snap = self._pub
        if self.backend == "host" and snap[1] and addrs:
            return np.array(
                [self.oracle_snap(snap, a, None if ports is None else ports[i])
                 for i, a in enumerate(addrs)], np.int32)
        return np.asarray(self.dispatch_snap(snap, addrs, ports))

    def _scan_one(self, addr: bytes, port: Optional[int]) -> int:
        return self.oracle_snap(self._pub, addr, port)

    def oracle_one(self, addr: bytes, port: Optional[int] = None) -> int:
        return self.oracle_snap(self._pub, addr, port)

    def match_one(self, addr: bytes, port: Optional[int] = None) -> int:
        # published-generation gate: see HintMatcher.match_one
        if self.backend != "host" and len(self._pub[1]) <= SMALL_TABLE:
            return self._scan_one(addr, port)
        return int(self.match([addr], None if port is None else [port])[0])

    # ---- ClassifyService API (rules/service.py) ----

    def size(self) -> int:
        return len(self._pub[1])

    def checksum(self) -> int:
        """u32 checksum of the published networks+ACL generation (see
        HintMatcher.checksum — the cluster replication gate; cached per
        published generation)."""
        snap = self._pub
        cached = self._cksum
        if cached is not None and cached[0] is snap:
            return cached[1]
        v = _cidr_checksum(snap[1], snap[2])
        self._cksum = (snap, v)
        return v

    def snapshot(self) -> tuple:
        """One consistent (device, nets, acl, payload) generation."""
        return self._pub

    @staticmethod
    def snap_payload(snap: tuple):
        return snap[3]

    def oracle_snap(self, snap: tuple, addr: bytes,
                    port: Optional[int] = None) -> int:
        return _cidr_scan(snap[1], snap[2], addr, port)

    def index_snap(self, snap: tuple, addr: bytes,
                   port: Optional[int] = None) -> int:
        """O(groups) host lookup against the snapshot's CidrIndex (same
        winner as oracle_snap); linear fallback without one."""
        note_serving()
        idx = snap[5] if len(snap) > 5 else None
        if idx is None:
            return self.oracle_snap(snap, addr, port)
        # route tables ignore ports entirely (oracle_snap's acl gate)
        return idx.lookup(addr, None if snap[2] is None else port)

    def dispatch_snap(self, snap: tuple, addrs: Sequence[bytes],
                      ports: Optional[Sequence[int]],
                      pad_to: Optional[int] = None, sync: bool = True):
        """Encode + submit one batch against the snapshotted table
        generation (async device result; np.asarray() it to block).
        pad_to: pad the encoded arrays to this batch bucket (family -1
        marks pad rows — matches no group, walks no trie). sync: see
        HintMatcher.dispatch_snap."""
        note_serving()
        dev, nets, acl = snap[0], snap[1], snap[2]
        if not nets or not addrs:
            return np.full(len(addrs), -1, np.int32)
        # route tables (acl=None) have zeroed port-range columns: the port
        # gate must be skipped entirely or every port>0 query misses
        q = _encode_addrs(addrs, None if acl is None else ports, pad_to,
                          items=len(addrs))
        a16, fam, p = q["a16"], q["fam"], q.get("port")
        if self.backend in ("jax-sharded", "jax-fp-sharded"):
            return self._dispatch_sharded(snap, a16, fam, p, sync=sync)
        # every branch is one dispatch
        if self.backend == "jax":
            with launch_span("cidr", a16.shape[0], args=(dev, q.arena)):
                return H.cidr_hash_jit(dev, q.arena, q.layout)
        with launch_span("cidr", a16.shape[0], args=(dev, a16, fam, p),
                         host_arrays=len(q)):
            if self.backend == "jax-fp":
                from ..ops import fphash as F
                return F.cidr_fp_jit(dev, a16, fam, p)
            return cidr_match_jit(dev, a16, fam, p)

    def _dispatch_sharded(self, snap: tuple, a16: np.ndarray,
                          fam: np.ndarray, p: Optional[np.ndarray],
                          sync: bool = True):
        from ..parallel import mesh as M
        dev, tab = snap[0], snap[4]
        from ..parallel.mesh import query_shards
        n = a16.shape[0]
        cap = pad_batch(n, query_shards(self._mesh))
        if cap != n:
            a16 = np.concatenate(
                [a16, np.zeros((cap - n,) + a16.shape[1:], a16.dtype)])
            fam = np.concatenate([fam, np.zeros(cap - n, fam.dtype)])
            if p is not None:
                p = np.concatenate([p, np.zeros(cap - n, p.dtype)])
        a16d, famd, pd = M.shard_addr_queries(a16, fam, self._mesh, p)
        with_port = p is not None
        fn = self._fns.get(with_port)
        if fn is None:
            kernel = None
            if self.backend == "jax-fp-sharded":
                from ..ops import fphash as F
                kernel = F.cidr_fp_match
            fn = self._fns[with_port] = M.make_sharded_cidr_fn(
                self._mesh, {k: v.ndim for k, v in tab.arrays.items()},
                with_port, kernel=kernel)
        size = np.int32(tab.shard_size)
        with launch_span("cidr", cap, args=(dev, a16d, famd, pd, size)):
            out = fn(dev, a16d, famd, pd, size) if with_port \
                else fn(dev, a16d, famd, size)
        if not sync:
            import jax
            if jax.process_count() <= 1:
                return out  # async: caller syncs + slices
        return M.to_local(out)[:n]


# ------------------------------------------------- a set of ordered tables
#
# A switch holds one RouteTable a VNI (vswitch/Table.java:13). Served as
# one CidrMatcher each, a burst that names N VPCs is N device batches —
# N launch floors for a few lookups apiece. A CidrTableSet keeps every
# table of one owner on the device behind ONE program
# (ops/hashmatch.stack_cidr_tables, cidr_set_match): a lookup names its
# table, a batch is one launch however many tables it names. Each table
# is compiled on its own and kept on the host, so a change to one VPC
# rebuilds that VPC's cuckoo tables and restacks the rest as they are.

_SET_TABLE_BUILDS = [0]  # per-table host builds, all sets (see note_launch)
_NO_TABLE = repeat(-1)


def cidr_set_table_builds_total() -> int:
    return _SET_TABLE_BUILDS[0]


def cidr_set_tables() -> dict:
    """{family: tables held} over the live sets, for /metrics."""
    with _gen_lock:
        sets = [m for m in _MATCHERS if isinstance(m, CidrTableSet)]
    out: dict = {}
    for ts in sets:
        out[ts.family] = out.get(ts.family, 0) + len(ts.snapshot().tables)
    return out


class _SetTable(NamedTuple):
    """One table of a set generation, as the host holds it."""
    tid: int                  # its row of the stacked device arrays
    nets: list
    acl: Optional[list]
    index: object             # CidrIndex past SMALL_TABLE entries
    hashed: object            # its own compiled HashCidrTable ("jax")


class _SetSnap(NamedTuple):
    """One published generation of a set: what a batch reads, whole."""
    dev: Optional[dict]
    tables: dict              # view key -> _SetTable
    tid_of: dict              # view key -> tid
    total: int                # entries over all tables
    gated: bool               # some table is an ACL: ports are compared


class CidrTableView:
    """One table of a CidrTableSet, with the face of a CidrMatcher where
    a VpcNetwork and ClassifyService.submit_cidr use one. It holds no
    table itself: every read goes to its set's published generation,
    under its own key (never reused, so a released view's late lookups
    find nothing rather than a later tenant's table)."""

    _kind = "cidr"

    def __init__(self, table_set: "CidrTableSet", key: int):
        self.table_set = table_set
        self.key = key
        self._cksum = None  # (table, crc32): see CidrMatcher.checksum

    @property
    def backend(self) -> str:
        return self.table_set.backend

    def set_networks(self, networks: Sequence,
                     acl: Optional[Sequence[AclRule]] = None,
                     wait: bool = True) -> None:
        """Install this table's next generation through the background
        TableInstaller: its own cuckoo tables are rebuilt, the set's
        other tables restacked as they are, the whole set republished
        by one swap."""
        self._submit((list(networks),
                      list(acl) if acl is not None else None), wait)

    def release(self, wait: bool = True) -> None:
        """Give the table back (Switch.del_network): the next generation
        holds nothing under this view, and its lookups answer -1."""
        self._submit(None, wait)

    def _submit(self, args, wait: bool) -> None:
        t = TableInstaller.get().submit(self, args)
        if wait:
            t.ev.wait()
            if t.exc is not None:
                raise t.exc

    def _install(self, args) -> None:
        self.table_set._install_table(self, args)

    def snapshot(self) -> Optional[_SetTable]:
        """This table in the published generation (None: it holds
        nothing). The same object until this table is installed again,
        whatever happens to the set's other tables."""
        return self.table_set.snapshot().tables.get(self.key)

    def size(self) -> int:
        tab = self.snapshot()
        return len(tab.nets) if tab is not None else 0

    def checksum(self) -> int:
        tab = self.snapshot()
        cached = self._cksum
        if cached is not None and cached[0] is tab:
            return cached[1]
        v = _cidr_checksum((), None) if tab is None \
            else _cidr_checksum(tab.nets, tab.acl)
        self._cksum = (tab, v)
        return v

    def match(self, addrs: Sequence[bytes],
              ports: Optional[Sequence[int]] = None) -> np.ndarray:
        return self.table_set.match([self] * len(addrs), addrs, ports)

    def oracle_one(self, addr: bytes, port: Optional[int] = None) -> int:
        ts = self.table_set
        return ts.oracle_snap(ts.snapshot(), addr, port, self.key)

    def match_one(self, addr: bytes, port: Optional[int] = None) -> int:
        # the crossover is the set's: one device batch serves them all
        ts = self.table_set
        if ts.backend == "host" or ts.size() <= SMALL_TABLE:
            return ts.index_snap(ts.snapshot(), addr, port, self.key)
        return int(self.match([addr], None if port is None else [port])[0])


class CidrTableSet:
    """Many ordered CIDR tables on one device behind one program; hands
    out a CidrTableView a table. The ClassifyService interface is a
    CidrMatcher's with one more column: the view each lookup names."""

    _kind = "cidr"
    BACKENDS = ("jax", "host")

    def __init__(self, family: str = "any", backend: Optional[str] = None):
        self.backend = backend or default_backend()
        if self.backend not in self.BACKENDS:
            raise ValueError(f"no table set on backend {self.backend!r}: "
                             f"keep a CidrMatcher a table there")
        self.family = family  # /metrics label: "v4" | "v6" | "any"
        self.generation = 0
        self._lock = threading.Lock()   # _next_key, _tids
        self._next_key = 0
        self._tids: dict[int, int] = {}        # live view key -> tid
        self._caps: Optional[dict] = None
        self._buckets: Optional[dict] = None
        self._pub = _SetSnap(None, {}, {}, 0, False)
        with _gen_lock:
            _MATCHERS.add(self)

    def view(self) -> CidrTableView:
        """A new, empty table (Switch.add_network)."""
        with self._lock:
            key, self._next_key = self._next_key, self._next_key + 1
            used = set(self._tids.values())
            self._tids[key] = next(t for t in range(len(used) + 1)
                                   if t not in used)
        return CidrTableView(self, key)

    def _install_table(self, view: CidrTableView, args) -> None:
        """The installer's call (its one thread: installs of a set never
        overlap): build `view`'s table alone (args None: drop it and
        forget the view), publish the set. Transactional: a failed build
        leaves the published generation as it was."""
        with self._lock:
            tid = self._tids.get(view.key)
        if tid is None:
            return      # released before its install ran
        tables = dict(self._pub.tables)
        if args is None or not args[0]:
            changed = tables.pop(view.key, None) is not None
        else:
            nets, acl = args
            hashed = index = None
            if self.backend == "jax":
                hashed = H.compile_cidr_hash(nets, acl=acl)
                _SET_TABLE_BUILDS[0] += 1
            if len(nets) > SMALL_TABLE:
                from .index import CidrIndex
                index = CidrIndex(nets, acl=acl)
            tables[view.key] = _SetTable(tid, nets, acl, index, hashed)
            changed = True
        if changed:
            self._publish(tables)
        if args is None:
            with self._lock:
                del self._tids[view.key]

    def _publish(self, tables: dict) -> None:
        itid = trace.current_id()  # nonzero only under a traced install
        t_ph = time.monotonic_ns() if itid else 0
        dev = None
        if self.backend == "jax" and tables:
            by_tid: list = [None] * (max(t.tid for t in tables.values()) + 1)
            for t in tables.values():
                by_tid[t.tid] = t.hashed
            arrays, caps, buckets = H.stack_cidr_tables(by_tid, self._caps)
            dev = _to_device(arrays)
        total = sum(len(t.nets) for t in tables.values())
        _install_phase(itid, "compile", t_ph, matcher="cidr", rules=total)
        t_ph = time.monotonic_ns() if itid else 0
        _sync_standby(dev)
        _install_phase(itid, "upload", t_ph, matcher="cidr")
        time.sleep(0)  # preemption point between compile and publish
        t_ph = time.monotonic_ns() if itid else 0
        if dev is not None:
            self._caps, self._buckets = caps, buckets
        self._pub = _SetSnap(
            dev, tables, {k: t.tid for k, t in tables.items()}, total,
            any(t.acl is not None for t in tables.values()))
        self.generation += 1
        with _gen_lock:
            _GENERATION[0] += 1
        _install_phase(itid, "swap", t_ph, matcher="cidr",
                       generation=self.generation)

    def published_table_bytes(self) -> int:
        dev = self._pub.dev
        if not dev:
            return 0
        return int(sum(getattr(v, "nbytes", 0) for v in dev.values()))

    def bucket_stat(self) -> Optional[dict]:
        """The set's unified bucket layout (see CidrMatcher.bucket_stat)."""
        return self._buckets

    def match(self, views: Sequence[CidrTableView], addrs: Sequence[bytes],
              ports: Optional[Sequence[int]] = None) -> np.ndarray:
        """-> int32 [B]: lookup b's first match in views[b]'s table, by
        that table's own indices; -1 for none. One dispatch."""
        snap = self._pub
        keys = [v.key for v in views]
        if self.backend == "host":
            return np.array(
                [self.index_snap(snap, a, None if ports is None else ports[i],
                                 keys[i]) for i, a in enumerate(addrs)],
                np.int32)
        return np.asarray(self.dispatch_snap(snap, addrs, ports, keys))

    # ---- ClassifyService API (rules/service.py) ----

    def size(self) -> int:
        return self._pub.total

    def snapshot(self) -> _SetSnap:
        return self._pub

    @staticmethod
    def snap_payload(snap: _SetSnap):
        return None     # a view registers no payload

    @staticmethod
    def oracle_snap(snap: _SetSnap, addr: bytes, port: Optional[int],
                    key: int) -> int:
        tab = snap.tables.get(key)
        if tab is None:
            return -1
        return _cidr_scan(tab.nets, tab.acl, addr, port)

    def index_snap(self, snap: _SetSnap, addr: bytes, port: Optional[int],
                   key: int) -> int:
        """The host's answer for one lookup of the view `key` (failover,
        lone queries): that table's CidrIndex, or its ordered scan."""
        note_serving()
        tab = snap.tables.get(key)
        if tab is None:
            return -1
        if tab.index is None:
            return _cidr_scan(tab.nets, tab.acl, addr, port)
        return tab.index.lookup(addr, None if tab.acl is None else port)

    def dispatch_snap(self, snap: _SetSnap, addrs: Sequence[bytes],
                      ports: Optional[Sequence[int]],
                      keys: Sequence[int],
                      pad_to: Optional[int] = None, sync: bool = True):
        """One batch over the snapshotted generation, whatever tables it
        names by their views' keys (see CidrMatcher.dispatch_snap). A
        lookup whose view holds no table in this generation is encoded
        as a pad row."""
        note_serving()
        n = len(addrs)
        if snap.dev is None or not n:
            return np.full(n, -1, np.int32)
        q = _encode_addrs(addrs, ports if snap.gated else None, pad_to,
                          items=n, tid=True)
        t0 = time.monotonic_ns() if trace.SAMPLE else 0
        col = np.fromiter(map(snap.tid_of.get, keys, _NO_TABLE),
                          np.int32, n)
        np.maximum(col, 0, out=q["tid"][:n])
        q["fam"][:n][col < 0] = -1
        if t0:
            # counted inside the span: the sort hands the GIL over, and
            # a submitter's turn then belongs to what caused it
            named = len(np.unique(col[col >= 0]))
            trace.note_span(trace.current_id(), "engine", "table_set", t0,
                            time.monotonic_ns() - t0, items=named,
                            parent="dispatch")
        with launch_span("cidr", q["fam"].shape[0],
                         args=(snap.dev, q.arena)):
            return H.cidr_set_jit(snap.dev, q.arena, q.layout)
