"""Maglev consistent-hash backend selection (Eisenbud et al., NSDI'16).

The table compiler behind every plane that picks a destination:

* **build_table()** — the permutation-fill algorithm: each backend gets
  a (offset, skip) permutation of the M (prime) slots from two FNV-1a
  hashes of its identity, and backends claim slots in a weighted turn
  order (the WRR subtract-sum sequence over the weights, so slot
  ownership tracks weight share to within ~1/M·N). The result is an
  int32 slot→backend lookup table with the Maglev disruption bound:
  adding/removing one backend moves ≈ its weight share of slots (plus a
  small permutation-churn tail), never an arbitrary reshuffle.
* **flow_hash()/pick()** — the ONE hash contract shared by all three
  planes (this module, the C lanes/flow cache in native/vtl.cpp, and
  the cluster steerer): FNV-1a 64 over the raw address bytes, plus the
  port as two big-endian bytes when per-connection spread is wanted
  (`port=None` = source affinity: one backend per client address).
  tests/test_maglev.py proves python == C == device picks bit-exactly.
* **MaglevMatcher** — the JAX-engine plane: the table rides the same
  double-buffered generation machinery as the hint/cidr matchers
  (rules/engine.py TableInstaller — standby build + one atomic publish,
  installs never stall serving) and `dispatch_snap` answers a batch of
  addresses with a jitted device gather, so a classify dispatch can
  return backend picks alongside match verdicts from one snapshot pair.

* **MaglevTableSet / GroupedPair** — the LB as upstream lays it out:
  one table a server-group (M = GROUP_M), all of them on the device as
  one `[groups_cap, M]` array behind one program, installed a group at
  a time; the pair answers (verdict, pick from the table of the group
  the matched rule names) in one launch (ops/fused.fused_group_pick).

Metrics (utils/metrics): vproxy_maglev_table_builds_total,
vproxy_maglev_build_ms (histogram), vproxy_maglev_remap_fraction (the
last build's fraction of slots that changed owner — the churn a resize
actually caused), vproxy_maglev_set_groups (tables the live sets hold),
vproxy_maglev_set_table_builds_total (per-group row installs).

Knobs: VPROXY_TPU_MAGLEV_M (65537 — engine/cluster tables),
VPROXY_TPU_MAGLEV_GROUP_M (4099 — per-ServerGroup tables, rebuilt on
membership edges and so sized for build cost over precision; both must
be prime or the permutations do not cover the table).
"""
from __future__ import annotations

import math
import os
import threading
import time
from typing import Callable, NamedTuple, Optional, Sequence

import numpy as np

FNV64_OFFSET = 0xCBF29CE484222325
FNV64_PRIME = 0x100000001B3
_MASK64 = (1 << 64) - 1

DEFAULT_M = int(os.environ.get("VPROXY_TPU_MAGLEV_M", "65537"))
GROUP_M = int(os.environ.get("VPROXY_TPU_MAGLEV_GROUP_M", "4099"))

_TURN_CAP = 4096  # weighted turn-order bound (weights renormalized past it)


def fnv64(data: bytes) -> int:
    """FNV-1a 64 — the shared hash of every maglev plane (the C side in
    native/vtl.cpp implements the same loop; parity is tested)."""
    h = FNV64_OFFSET
    for b in data:
        h = ((h ^ b) * FNV64_PRIME) & _MASK64
    return h


def flow_hash(ip: bytes, port: Optional[int] = None) -> int:
    """The flow key hash: raw address bytes (4 for v4, 16 for v6, as
    utils/ip.parse_ip produces and as they sit in a sockaddr), plus the
    port as two big-endian bytes when per-connection spread is wanted.
    port=None is SOURCE AFFINITY: every connection from one client
    address lands on one backend."""
    if port is None:
        return fnv64(ip)
    return fnv64(ip + bytes((port >> 8 & 0xFF, port & 0xFF)))


def flow_slots(m: int, ips: Sequence[bytes],
               ports: Optional[Sequence[int]] = None) -> np.ndarray:
    """Host-side Maglev table slots for a batch — THE one copy of the
    slot-hash contract every pick plane (device gather, fused program,
    host pick) derives from; a per-element None port is source
    affinity. -> int64 [len(ips)]."""
    return np.fromiter(
        (flow_hash(ip, None if ports is None else ports[i]) % m
         for i, ip in enumerate(ips)), np.int64, len(ips))


def _turns(weights: Sequence[int]) -> list[int]:
    """Weighted turn order for the fill loop: the reference's
    subtract-sum WRR sequence (components/lanes._wrr_seq semantics),
    gcd-reduced and capped — each backend takes turns claiming slots in
    proportion to its weight, which is what makes slot ownership track
    weight share."""
    if not weights:
        return []
    if len(set(weights)) == 1:
        return list(range(len(weights)))
    g = 0
    for w in weights:
        g = math.gcd(g, w)
    if g > 1:
        weights = [w // g for w in weights]
    total = sum(weights)
    if total > _TURN_CAP:
        weights = [max(1, (w * _TURN_CAP) // total) for w in weights]
        total = sum(weights)
    if total > _TURN_CAP:
        return list(range(len(weights)))
    cur = list(weights)
    seq: list[int] = []
    while True:
        idx = max(range(len(cur)), key=lambda i: (cur[i], -i))
        seq.append(idx)
        cur[idx] -= total
        if all(w == 0 for w in cur):
            return seq
        for i in range(len(cur)):
            cur[i] += weights[i]


def _is_prime(n: int) -> bool:
    if n < 2:
        return False
    for p in range(2, int(n ** 0.5) + 1):
        if n % p == 0:
            return False
    return True


def build_table(entries: Sequence[tuple[str, int]],
                m: Optional[int] = None) -> np.ndarray:
    """Compile the slot→backend lookup table.

    entries: (identity, weight) per backend, weight > 0; identity is
    whatever names the backend stably across rebuilds (ip:port for
    servers, node ids for cluster peers) — a backend keeps its
    permutation, and therefore most of its slots, across resizes.
    Returns int32[m]; every slot owned (m prime, skip ∈ [1, m-1], so
    each permutation covers the whole table). An empty entry list
    returns an all -1 table.
    """
    if m is None:
        m = DEFAULT_M
    if m < 3 or not _is_prime(m):
        raise ValueError(f"maglev table size {m} must be a prime >= 3")
    t0 = time.monotonic()
    n = len(entries)
    # plain-list fill: numpy scalar loads/stores are ~30x a list's in
    # this loop, and group-size builds run under the group lock on a
    # health edge — the list fill keeps that window ~100µs, not ~5ms
    tab = [-1] * m
    if n:
        cur, skips = [], []
        for name, _w in entries:
            b = name.encode() if isinstance(name, str) else bytes(name)
            cur.append(fnv64(b"o:" + b) % m)
            skips.append(fnv64(b"s:" + b) % (m - 1) + 1)
        turns = _turns([max(1, int(w)) for _, w in entries])
        filled = 0
        while filled < m:
            for i in turns:
                # next unclaimed slot in backend i's permutation —
                # walked incrementally (slot += skip mod m): slots
                # behind cur[i] were claimed when this permutation
                # passed them, so the next free one is always ahead
                sl = cur[i]
                sk = skips[i]
                while tab[sl] >= 0:
                    sl += sk
                    if sl >= m:
                        sl -= m
                tab[sl] = i
                sl += sk
                cur[i] = sl - m if sl >= m else sl
                filled += 1
                if filled >= m:
                    break
    table = np.asarray(tab, np.int32)
    _builds_total().incr()
    _build_ms().observe((time.monotonic() - t0) * 1e3)
    return table


def remap_fraction(old: Optional[np.ndarray], new: np.ndarray,
                   old_names: Optional[Sequence[str]] = None,
                   new_names: Optional[Sequence[str]] = None) -> float:
    """Fraction of slots whose OWNER changed between two builds — the
    churn a resize actually caused. With name lists the comparison is
    by identity (indexes shift when a backend leaves); without, by raw
    index (valid only for same-membership rebuilds). Records the
    vproxy_maglev_remap_fraction gauge."""
    if old is None or len(old) != len(new):
        f = 1.0
    else:
        if old_names is not None and new_names is not None:
            o = np.array([old_names[i] if 0 <= i < len(old_names) else ""
                          for i in old], dtype=object)
            nw = np.array([new_names[i] if 0 <= i < len(new_names) else ""
                           for i in new], dtype=object)
            f = float(np.mean(o != nw))
        else:
            f = float(np.mean(old != new))
    _remap_gauge().set(f)
    return f


def pick(table: np.ndarray, ip: bytes, port: Optional[int] = None) -> int:
    """O(1) host-side pick: slot = flow_hash % M. -1 = empty table."""
    return int(table[flow_hash(ip, port) % len(table)])


# ------------------------------------------------------------ metrics

def _builds_total():
    from ..utils.metrics import GlobalInspection
    return GlobalInspection.get().get_counter(
        "vproxy_maglev_table_builds_total")


def _build_ms():
    # pre-registered (reservoir config included) in
    # GlobalInspection.__init__ — this resolves to that instance
    from ..utils.metrics import GlobalInspection
    return GlobalInspection.get().get_histogram("vproxy_maglev_build_ms")


def _remap_gauge():
    from ..utils.metrics import GlobalInspection
    return GlobalInspection.get().get_gauge("vproxy_maglev_remap_fraction")


# ------------------------------------------------- JAX engine plane

_take_jit = None


def _device_take(dev_table, slots: np.ndarray):
    """Jitted device gather: the maglev pick column a batched dispatch
    returns alongside its match verdicts."""
    global _take_jit
    import jax
    import jax.numpy as jnp
    if _take_jit is None:
        _take_jit = jax.jit(lambda t, s: jnp.take(t, s, mode="clip"))
    return _take_jit(dev_table, slots)


class MaglevMatcher:
    """Device-backed per-generation Maglev table, published through the
    SAME double-buffer machinery as the hint/cidr matchers: set_backends
    enqueues on the process-wide TableInstaller (standby build + device
    upload off the mutation path, then ONE atomic pub-tuple swap), so a
    table rebuild never stalls a serving dispatch."""

    _kind = "maglev"

    def __init__(self, entries: Sequence[tuple[str, int]] = (),
                 m: Optional[int] = None, payload=None):
        self.m = m or DEFAULT_M
        self._entries: list = list(entries)
        self._payload = payload
        self.generation = 0
        self.last_remap = 0.0  # fraction of slots the last install moved
        # (np table, device table, entries, payload) — one atomic tuple
        # so a reader never pairs one generation's table with another's
        # entry list
        self._pub: tuple = (None, None, [], payload)
        self._recompile()
        from . import engine as E
        with E._gen_lock:
            E._MATCHERS.add(self)

    # ---------------------------------------------------------- install

    def set_backends(self, entries: Sequence[tuple[str, int]],
                     payload=None, wait: bool = True) -> None:
        """Install a new backend generation via the background
        TableInstaller (see HintMatcher.set_rules — same standby-swap
        contract: dispatchers never wait, wait=True gives the caller
        read-your-writes)."""
        from .engine import TableInstaller
        t = TableInstaller.get().submit(self, (list(entries), payload))
        if wait:
            t.ev.wait()
            if t.exc is not None:
                raise t.exc

    def _install(self, args: tuple) -> None:
        entries, payload = args
        old = (self._entries, self._payload)
        self._entries = list(entries)
        self._payload = payload
        try:
            self._recompile()
        except BaseException:
            self._entries, self._payload = old
            raise

    def _recompile(self) -> None:
        from . import engine as E
        tab = build_table(self._entries, self.m)
        prev = self._pub[0]
        if prev is None or not self._pub[2]:
            # first build, or empty->populated: an all -1 table owned
            # no flows, so "100% of slots changed owner" would misread
            # a bring-up as total churn
            self.last_remap = 0.0
        else:
            prev_names = [name for name, _ in self._pub[2]] or None
            names = [name for name, _ in self._entries] or None
            self.last_remap = remap_fraction(prev, tab, prev_names, names)
        import jax
        dev = jax.device_put(tab)
        E._sync_standby({"table": dev})
        time.sleep(0)  # preemption point between compile and publish
        self._pub = (tab, dev, list(self._entries), self._payload)
        self.generation += 1
        with E._gen_lock:
            E._GENERATION[0] += 1

    def published_table_bytes(self) -> int:
        dev = self._pub[1]
        return int(getattr(dev, "nbytes", 0)) if dev is not None else 0

    # ------------------------------------------------------------ reads

    def snapshot(self) -> tuple:
        return self._pub

    @staticmethod
    def snap_payload(snap: tuple):
        return snap[3]

    def size(self) -> int:
        return len(self._pub[2])

    def checksum(self) -> int:
        import zlib
        return zlib.crc32(
            "\n".join(f"{n}:{w}" for n, w in self._pub[2]).encode())

    def pick_one(self, ip: bytes, port: Optional[int] = None) -> int:
        return self.pick_snap(self._pub, ip, port)

    def pick_snap(self, snap: tuple, ip: bytes,
                  port: Optional[int] = None) -> int:
        tab = snap[0]
        if tab is None or not snap[2]:
            return -1
        return pick(tab, ip, port)

    def dispatch_snap(self, snap: tuple, ips: Sequence[bytes],
                      ports: Optional[Sequence[int]] = None):
        """Batched device picks against one snapshotted generation
        (async device array; np.asarray() to block). Slots are hashed
        host-side — the same python-int FNV path the encoders use — and
        the gather runs jitted on the device holding the table."""
        tab, dev = snap[0], snap[1]
        if tab is None or not snap[2] or not len(ips):
            return np.full(len(ips), -1, np.int32)
        slots = flow_slots(len(tab), ips, ports)
        from . import engine as E
        with E.launch_span("pick", len(slots), args=(dev, slots)):
            return _device_take(dev, slots)

    def match(self, ips: Sequence[bytes],
              ports: Optional[Sequence[int]] = None) -> np.ndarray:
        return np.asarray(self.dispatch_snap(self._pub, ips, ports))


def classify_and_pick(hint_matcher, maglev: MaglevMatcher, hints,
                      ips: Sequence[bytes],
                      ports: Optional[Sequence[int]] = None):
    """ONE batched dispatch answering BOTH questions: match verdicts
    from the hint matcher and backend picks from the maglev table
    against one atomic snapshot pair. On a "jax" matcher with packed
    tables published (the default) this is the FUSED one-launch
    program (rules/engine.fused_dispatch); other
    backends keep the pre-r12 overlapped two-dispatch submit. ->
    (verdicts int32[B], picks int32[B], hint_payload, maglev_payload)."""
    from . import engine as E
    hsnap = hint_matcher.snapshot()
    msnap = maglev.snapshot()
    out = E.fused_dispatch(hint_matcher, hsnap, maglev, msnap, hints,
                           ips, ports)
    if out is not None:
        arr = np.asarray(out)[: len(hints)]
        return (np.ascontiguousarray(arr[:, 0]),
                np.ascontiguousarray(arr[:, 1]),
                hint_matcher.snap_payload(hsnap),
                maglev.snap_payload(msnap))
    if getattr(hint_matcher, "backend", None) == "host":
        v = np.array([hint_matcher.oracle_snap(hsnap, h) for h in hints],
                     np.int32)
    else:
        v = hint_matcher.dispatch_snap(hsnap, hints)  # async device call
    p = maglev.dispatch_snap(msnap, ips, ports)       # overlaps the first
    return (np.asarray(v), np.asarray(p),
            hint_matcher.snap_payload(hsnap), maglev.snap_payload(msnap))


def _split_payloads(payloads) -> tuple:
    """(hint, ip, port) payloads of one batch -> (hints, ips, ports);
    ports None where every lookup is source affinity."""
    ports = [p[2] for p in payloads]
    return ([p[0] for p in payloads], [p[1] for p in payloads],
            None if all(p is None for p in ports) else ports)


class FusedPair:
    """A (HintMatcher, MaglevMatcher) pair presented through the
    matcher interface the dispatch consumers speak (ClassifyService,
    cluster StepLoop): snapshot() is the atomic snapshot PAIR,
    dispatch_snap() is the fused one-launch (verdict, pick) batch, and
    index_snap() is the host fast lane (O(probes) hint index + O(1)
    maglev table read) for inline lone queries and degraded serving.
    Payloads ride as (hint_payload, maglev_payload)."""

    def __init__(self, hint_matcher, maglev: MaglevMatcher):
        self.hm = hint_matcher
        self.mm = maglev

    @property
    def backend(self) -> str:
        return self.hm.backend

    def size(self) -> int:
        return self.hm.size()

    @property
    def generation(self) -> int:
        return self.hm.generation + self.mm.generation

    def snapshot(self) -> tuple:
        return (self.hm.snapshot(), self.mm.snapshot())

    @staticmethod
    def snap_payload(snap: tuple):
        hsnap, msnap = snap
        return (hsnap[3], msnap[3])

    def index_snap(self, snap: tuple, payload: tuple) -> tuple:
        """(verdict, pick) from the host planes — the same winners as
        the fused program (index parity is tested at the matcher
        level; pick parity is the shared FNV contract)."""
        hsnap, msnap = snap
        hint, ip, port = payload
        return (self.hm.index_snap(hsnap, hint),
                self.mm.pick_snap(msnap, ip, port))

    def dispatch_snap(self, snap: tuple, payloads, pad_to=None,
                      sync: bool = True):
        """One fused launch for a batch of (hint, ip, port) payloads;
        async [cap, 2] device array. Falls back to the overlapped
        two-dispatch chain (host-side stack) when the fused path is
        unavailable for this snapshot."""
        from . import engine as E
        hsnap, msnap = snap
        hints, ips, ports = _split_payloads(payloads)
        out = E.fused_dispatch(self.hm, hsnap, self.mm, msnap, hints,
                               ips, ports, pad_to=pad_to)
        if out is not None:
            return out
        v = self.hm.dispatch_snap(hsnap, hints, pad_to=pad_to,
                                  sync=sync)
        p = self.mm.dispatch_snap(msnap, ips, ports)
        return _LazyPairRows(v, p, len(hints))


class _LazyPairRows:
    """FusedPair's unfused-fallback result: both dispatches are already
    submitted (overlapped, async); the d2h sync happens when the
    CONSUMER np.asarray()s — preserving the service dispatcher's
    double-buffering (submit batch k+1 before pulling k) exactly like
    the fused path's async device array does."""

    def __init__(self, v, p, n: int):
        self._v, self._p, self._n = v, p, n

    def __array__(self, dtype=None, copy=None):
        n = self._n
        out = np.stack([np.asarray(self._v)[:n].astype(np.int32),
                        np.asarray(self._p)[:n].astype(np.int32)],
                       axis=1)
        return out if dtype is None else out.astype(dtype)


# ------------------------------------------------- per-group table set
#
# Upstream picks INSIDE the group the hint matched (Upstream.
# searchForGroup -> that ServerGroup's next, method `source`): one
# Maglev table a server-group, and the table a lookup reads depends on
# its verdict. The set keeps every group's table on the device as one
# array of one M, so classify and the dependent pick are one program.

ROW_BITS = 12               # a ref is (token << ROW_BITS) | row
MAX_ROWS = 1 << ROW_BITS
_TOKENS = 1 << (31 - ROW_BITS)
_SET_BUILDS = [0]   # row installs that wrote a table, all sets


def ref_row(ref: int) -> int:
    return ref & (MAX_ROWS - 1)


def ref_token(ref: int) -> int:
    return ref >> ROW_BITS


def group_column(refs: Sequence[int], cap: int) -> np.ndarray:
    """The device form of a hint generation's rule -> group column:
    int32 [cap, 2] of (row, token), (-1, -1) for a rule that names no
    group and for the pad rows."""
    col = np.full((cap, 2), -1, np.int32)
    r = np.asarray(refs, np.int64)
    named = r >= 0
    col[:len(r), 0] = np.where(named, r & (MAX_ROWS - 1), -1)
    col[:len(r), 1] = np.where(named, r >> ROW_BITS, -1)
    return col


def set_table_builds_total() -> int:
    return _SET_BUILDS[0]


def set_groups_total() -> int:
    """Tables the live sets hold (vproxy_maglev_set_groups)."""
    import sys      # a scrape must not force the engine's jax import:
    E = sys.modules.get(__package__ + ".engine")    # no engine, no set
    if E is None:
        return 0
    with E._gen_lock:
        matchers = list(E._MATCHERS)
    return sum(m.size() for m in matchers
               if isinstance(m, MaglevTableSet))


class _SetRow(NamedTuple):
    table: np.ndarray       # int32 [M] slot -> member index
    tlist: list             # the same as plain ints: the host lane's load
    names: list             # member identities, for the remap fraction
    payload: object         # the owner's member list of this build


class _SetSnap(NamedTuple):
    """One published generation of a set: the host rows by ref, the
    stacked host copy the next install starts from, its device copy and
    the tokens the device checks a row against."""
    rows: dict              # ref -> _SetRow
    tabs: Optional[np.ndarray]      # [cap, M], narrowest dtype
    owner: Optional[np.ndarray]     # int32 [cap] token, -1 = no table
    dev: object             # (device tabs, device owner) or None
    payloads: dict          # ref -> payload, what snap_payload hands out


_MIN_CAP = 16   # rows a set's device array starts with


def _row_dtype(members: int):
    """The narrowest signed type that holds a member index and -1."""
    return np.int8 if members <= 127 else \
        np.int16 if members <= 32767 else np.int32


class MaglevTableSet:
    """Many per-group Maglev tables of one M, on the device as ONE
    `[groups_cap, M]` array behind one program (ops/fused.group_jit).

    A group owns a row for its life in the set: `alloc()` hands out a
    ref (row + a token no earlier owner of the row had), `release()`
    gives it back. `install(ref, source)` enqueues that ONE row on the
    TableInstaller: `source()` runs on the installer thread and returns
    (table int32 [M], member identities, payload) or None for "no
    table" (method not `source`, no healthy member); the other rows'
    host tables are copied, not rebuilt, the device array is uploaded
    whole into a fresh buffer and published by one atomic swap. The
    device shape changes only when the rows handed out outgrow `cap`
    (16 to start with; it doubles) or a group outgrows the row type —
    then, and only then, the program retraces."""

    _kind = "maglev"

    def __init__(self, m: Optional[int] = None,
                 backend: Optional[str] = None):
        from . import engine as E
        self.m = m or GROUP_M
        if self.m < 3 or not _is_prime(self.m):
            raise ValueError(f"maglev table size {self.m} must be a "
                             f"prime >= 3")
        self.backend = backend or E.default_backend()
        self.cap = _MIN_CAP     # rows of the device array; only grows
        self.generation = 0
        self.last_remap = 0.0   # slots the last row install moved
        self._lock = threading.Lock()   # _live, _next_token, _pending
        self._live: dict[int, int] = {}     # row -> ref
        self._next_token = 0
        self._pending: dict[int, Optional[Callable]] = {}
        self._pub = _SetSnap({}, None, None, None, {})
        with E._gen_lock:
            E._MATCHERS.add(self)

    # ----------------------------------------------------------- rows

    def alloc(self) -> int:
        """A row for one group -> its ref. The row may have belonged to
        a released group; the token has not."""
        with self._lock:
            row = next(r for r in range(len(self._live) + 1)
                       if r not in self._live)
            if row >= MAX_ROWS:
                raise ValueError(f"a pick-table set holds at most "
                                 f"{MAX_ROWS} groups")
            ref = (self._next_token << ROW_BITS) | row
            self._next_token = (self._next_token + 1) % _TOKENS
            self._live[row] = ref
        return ref

    def release(self, ref: int, wait: bool = False) -> None:
        """The group left: its row is free for the next alloc, its table
        leaves the set with the next publish."""
        with self._lock:
            if self._live.get(ref_row(ref)) == ref:
                del self._live[ref_row(ref)]
        self._submit(ref, None, wait)

    def install(self, ref: int, source: Callable,
                wait: bool = True) -> None:
        """Enqueue ONE row's (re)build; `source` is called on the
        installer thread (see the class doc). wait=False is the form a
        change listener uses: bump and defer."""
        self._submit(ref, source, wait)

    def _submit(self, ref: int, source, wait: bool) -> None:
        from .engine import TableInstaller
        with self._lock:
            # a dict keeps one entry a ref: the newest source wins, as
            # the installer's own coalescing has it for whole tables
            self._pending.pop(ref, None)
            self._pending[ref] = source
        t = TableInstaller.get().submit(self, ())
        if wait:
            t.ev.wait()
            if t.exc is not None:
                raise t.exc

    def _install(self, _args) -> None:
        """The installer's call (its one thread): rebuild the rows that
        are pending, reuse every other, publish. A row whose source
        raises (or hands back a table of another size) LEAVES the set —
        its edge said the published table is out of date, so its group
        answers -1 and is asked on the host until its next install —
        and the other rows coalesced into this call publish as built;
        the first such exception is raised after the publish, to this
        call's waiters."""
        with self._lock:
            pending, self._pending = self._pending, {}
            live = dict(self._live)
        rows = dict(self._pub.rows)
        changed, failed = [], None
        for ref, source in pending.items():
            spec = None
            try:
                if source is not None and live.get(ref_row(ref)) == ref:
                    spec = source()
                if spec is not None:
                    table, names, payload = spec
                    table = np.asarray(table, np.int32)
                    if table.shape != (self.m,):
                        raise ValueError(f"a row of this set is {self.m} "
                                         f"slots, not {table.shape}")
            except MemoryError:
                raise
            except Exception as e:      # noqa: BLE001 — raised below
                failed, spec = failed or e, None
            if spec is None:
                if rows.pop(ref, None) is not None:
                    changed.append(ref)
                continue
            prev = rows.get(ref)
            self.last_remap = 0.0 if prev is None else remap_fraction(
                prev.table, table, prev.names, names)
            rows[ref] = _SetRow(table, table.tolist(), list(names), payload)
            _SET_BUILDS[0] += 1
            changed.append(ref)
        if changed:
            self._publish(rows, changed, max(live, default=0))
        if failed is not None:
            raise failed

    def _publish(self, rows: dict, changed: list, top_row: int) -> None:
        from . import engine as E
        old = self._pub
        cap = self.cap      # sized by the rows handed out, never shrunk
        while cap <= max(top_row, max(map(ref_row, rows), default=0)):
            cap *= 2
        dtype = _row_dtype(max((len(r.names) for r in rows.values()),
                               default=0))
        tabs = owner = dev = None
        if self.backend == "jax" and rows:
            if old.tabs is not None and old.tabs.shape[0] == cap \
                    and old.tabs.dtype == dtype:
                tabs, owner = old.tabs.copy(), old.owner.copy()
                todo = changed      # the other rows are copied as built
            else:
                tabs = np.full((cap, self.m), -1, dtype)
                owner = np.full(cap, -1, np.int32)
                todo = list(rows)
            for ref in todo:
                row = ref_row(ref)
                r = rows.get(ref)
                if r is not None:
                    tabs[row], owner[row] = r.table, ref_token(ref)
                elif owner[row] == ref_token(ref):  # not re-owned since
                    tabs[row], owner[row] = -1, -1
            import jax
            dev = (jax.device_put(tabs), jax.device_put(owner))
            E._sync_standby({"tabs": dev[0], "owner": dev[1]})
        time.sleep(0)  # preemption point between build and publish
        self.cap = cap
        self._pub = _SetSnap(rows, tabs, owner, dev,
                             {ref: r.payload for ref, r in rows.items()})
        self.generation += 1
        with E._gen_lock:
            E._GENERATION[0] += 1

    # ---------------------------------------------------------- reads

    def published_table_bytes(self) -> int:
        dev = self._pub.dev
        return 0 if dev is None else int(dev[0].nbytes + dev[1].nbytes)

    def size(self) -> int:
        """Groups that hold a table in the published generation."""
        return len(self._pub.rows)

    def snapshot(self) -> _SetSnap:
        return self._pub

    @staticmethod
    def snap_payload(snap: _SetSnap) -> dict:
        return snap.payloads

    @staticmethod
    def pick_snap(snap: _SetSnap, ref: int, slot: int) -> int:
        """The host's pick: one list load of the table `ref` owns in
        this generation; -1 where it owns none."""
        row = snap.rows.get(ref)
        return -1 if row is None else row.tlist[slot]


class GroupedPair:
    """(HintMatcher, MaglevTableSet) through FusedPair's interface:
    classify, then the pick from the table of the group the matched
    rule names. The rule -> group column rides the hint generation
    (`set_rules(..., groups=refs)`), the tables the set's; a batch
    reads one snapshot pair. Backend "jax": one launch a batch
    (engine.grouped_dispatch). Any other backend: classify as that
    backend serves it and the pick on the host, when the verdicts are
    pulled."""

    grouped = True      # the service counts the picks a batch resolved

    def __init__(self, hint_matcher, table_set: MaglevTableSet):
        self.hm = hint_matcher
        self.mm = table_set
        self.backend = hint_matcher.backend     # fixed when it was made
        # (ip, port) -> flow_hash for the host lane, as a ServerGroup
        # memoises its clients' (`_maglev_hash`): pure in the key, so it
        # outlives every generation; the slot is re-derived a pick
        self._hash: dict = {}

    def size(self) -> int:
        return self.hm.size()

    @property
    def generation(self) -> int:
        return self.hm.generation + self.mm.generation

    def set_rules(self, rules, payload=None, groups=None,
                  wait: bool = True) -> None:
        """One hint generation with its rule -> group column: groups[i]
        is the ref of the set row rule i's group owns, -1 for none."""
        self.hm.set_rules(rules, payload=payload, wait=wait,
                          groups=[-1] * len(rules) if groups is None
                          else groups)

    def snapshot(self) -> tuple:
        return (self.hm.snapshot(), self.mm.snapshot())

    @staticmethod
    def snap_payload(snap: tuple):
        hsnap, ssnap = snap
        return (hsnap[3], ssnap.payloads)

    def _host_pick(self, snap: tuple, verdict: int, slot: int) -> int:
        hsnap, ssnap = snap
        col = hsnap[6] if len(hsnap) > 6 else None
        if verdict < 0 or col is None:
            return -1
        return self.mm.pick_snap(ssnap, col[0][verdict], slot)

    def index_snap(self, snap: tuple, payload: tuple) -> tuple:
        """(verdict, pick) from the host planes: the hint index, then
        one list load of the matched group's table. The lone accept's
        path under `auto`: `_host_pick` spelled out, no call it can
        spare."""
        hint, ip, port = payload
        hsnap, ssnap = snap
        v = self.hm.index_snap(hsnap, hint)
        col = hsnap[6] if len(hsnap) > 6 else None
        row = None if v < 0 or col is None else ssnap.rows.get(col[0][v])
        if row is None:
            return v, -1
        hc, key = self._hash, ip if port is None else (ip, port)
        h = hc.get(key)
        if h is None:
            if len(hc) >= 16384:    # bounded: clear beats LRU churn
                hc.clear()
            h = hc[key] = flow_hash(ip, port)
        return v, row.tlist[h % self.mm.m]

    def dispatch_snap(self, snap: tuple, payloads, pad_to=None,
                      sync: bool = True):
        """One batch of (hint, ip, port) payloads -> async [cap, 2]
        rows of (verdict, pick)."""
        from . import engine as E
        hsnap, ssnap = snap
        hints, ips, ports = _split_payloads(payloads)
        out = E.grouped_dispatch(hsnap, ssnap, self.mm.m, hints, ips,
                                 ports, pad_to=pad_to)
        if out is not None:
            return out
        if self.hm.backend == "host":   # no device dispatch there
            v = np.array([self.hm.index_snap(hsnap, h) for h in hints],
                         np.int32)
        else:
            v = self.hm.dispatch_snap(hsnap, hints, pad_to=pad_to,
                                      sync=sync)
        return _HostPickRows(self, snap, v, flow_slots(self.mm.m, ips, ports))


class _HostPickRows:
    """GroupedPair's result where the grouped program cannot run (a
    backend other than "jax", a set with no device table yet): the
    classify is submitted, the picks are read on the host once the
    consumer np.asarray()s the verdicts."""

    picks_on_host = True

    def __init__(self, pair: GroupedPair, snap: tuple, v, slots):
        self._pair, self._snap, self._v, self._slots = pair, snap, v, slots

    def __array__(self, dtype=None, copy=None):
        n = len(self._slots)
        v = np.asarray(self._v)[:n].astype(np.int32)
        pick = self._pair._host_pick
        out = np.stack([v, np.fromiter(
            (pick(self._snap, vi, si)
             for vi, si in zip(v.tolist(), self._slots.tolist())),
            np.int32, n)], axis=1)
        return out if dtype is None else out.astype(dtype)
